"""Spans and counters of the streaming engine (repro.explore.spans).

  * self time: a span's duration less its child spans', on one thread
    and on two at once, with an injected clock;
  * a streamed sweep's ``meta`` carries every span's self time, and the
    self times inside the ``stream`` span add up to the sweep's seconds;
    the spans change no front;
  * compiles are credited to the span that makes them: survivors are cut
    on the host, so a survivor count new to the process compiles nothing;
  * ``rows_var_on_device`` counts the rows whose variation columns an x64
    program derived: every point of such a sweep, none on float32 or
    numpy.
"""
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.cnn import SEARCH_SPACE, ArchChoice
from repro.core.dataflow import LayerStack
from repro.core.supernet import arch_to_layers
from repro.core.workloads import get_network
from repro.explore import DesignSpace, VectorOracleBackend, spans
from repro.explore.device import DevicePlan, ParetoSpec, build_plan
from repro.explore.streaming import (ParetoAccumulator, TopKAccumulator,
                                     stream_co_explore, stream_explore)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

METRICS = ("latency_s", "power_mw", "area_mm2")


class Ticks:
  """A clock that moves only when told to."""

  def __init__(self):
    self.now = 0.0
    self.lock = threading.Lock()

  def __call__(self):
    with self.lock:
      return self.now

  def step(self, dt):
    with self.lock:
      self.now += dt


@pytest.fixture
def ticks(monkeypatch):
  clock = Ticks()
  monkeypatch.setattr(spans, "clock", clock)
  return clock


def test_self_time_of_a_parent_with_two_children(ticks):
  with spans.recording() as rec:
    with spans.span("resolve"):
      ticks.step(1.0)
      with spans.span("wait"):
        ticks.step(2.0)
      ticks.step(0.5)
      with spans.span("fetch"):
        ticks.step(4.0)
        with spans.span("slice"):  # a grandchild counts for its parent
          ticks.step(8.0)
  assert rec.self_s["resolve"] == 1.5
  assert rec.self_s["wait"] == 2.0
  assert rec.self_s["fetch"] == 4.0
  assert rec.self_s["slice"] == 8.0
  assert sum(rec.self_s.values()) == 15.5


def test_self_time_on_two_threads_at_once(ticks):
  with spans.recording() as rec:
    inside, leave = threading.Barrier(3), threading.Event()

    def worker(name):
      with spans.span("dispatch"):
        with spans.span(name):
          inside.wait(timeout=10)
          assert leave.wait(timeout=10)

    threads = [threading.Thread(target=spans.bind(rec, worker), args=(n,))
               for n in ("batch_inputs", "launch")]
    for t in threads:
      t.start()
    inside.wait(timeout=10)
    ticks.step(3.0)  # both threads sit in their inner span meanwhile
    leave.set()
    for t in threads:
      t.join(timeout=10)
  # each thread's stack is its own: the outer spans saw no self time
  assert rec.self_s["batch_inputs"] == 3.0
  assert rec.self_s["launch"] == 3.0
  assert rec.self_s["dispatch"] == 0.0


def test_spans_outside_a_recording_count_nothing(ticks):
  with spans.span("sample"):
    ticks.step(1.0)
  with spans.recording() as rec:
    pass
  assert sum(rec.self_s.values()) == 0.0


@pytest.fixture(scope="module")
def layers():
  return get_network("resnet20")[:4]


@pytest.fixture(scope="module")
def arch_accs():
  rng = np.random.RandomState(5)
  archs = [ArchChoice(tuple((int(rng.choice(r)), int(rng.choice(c)))
                            for r, c in SEARCH_SPACE)) for _ in range(6)]
  return list(zip(archs, rng.uniform(0.5, 0.95, size=len(archs))))


def reducers(cols):
  return {"pareto": ParetoAccumulator(cols),
          "top": TopKAccumulator(7, by="energy_mj")}


def plain(backend, layers, seed):
  return stream_explore(backend, DesignSpace(), layers, n_per_type=150,
                        seed=seed, chunk_size=64, workers=1,
                        reducers=reducers(("perf_per_area", "energy_mj")))


def joint(backend, arch_accs, seed):
  return stream_co_explore(backend, DesignSpace(), arch_accs,
                           n_hw_per_type=20, seed=seed, image_size=16,
                           chunk_size=50, workers=1,
                           reducers=reducers(("top1_err", "energy_mj",
                                              "area_mm2")))


@pytest.mark.parametrize("kind", ["plain", "joint"])
def test_sweep_meta_carries_every_span(kind, layers, arch_accs):
  run = (lambda b, s: plain(b, layers, s)) if kind == "plain" \
      else (lambda b, s: joint(b, arch_accs, s))
  device = VectorOracleBackend(jit=True)
  run(device, 3)  # compile first, so the sweep below is the steady one
  got, want = run(device, 4), run(VectorOracleBackend(), 4)
  for name in ("pareto", "top"):
    for col in METRICS:
      assert np.array_equal(getattr(got[name], col),
                            getattr(want[name], col)), (name, col)
  meta = got.meta
  for name in spans.SPANS:
    for key in ("self_s_", "n_compiles_", "compile_s_"):
      assert isinstance(meta[key + name], float), key + name
  assert "rows_per_sec" not in meta
  inside = sum(meta["self_s_" + n] for n in spans.SPANS if n != "plan")
  assert inside == pytest.approx(meta["seconds"], rel=0.02)
  for name in ("sample", "dispatch", "batch_inputs", "place", "launch",
               "resolve", "wait", "slice", "fetch", "fold_chunk"):
    assert meta["self_s_" + name] > 0.0, name
  assert meta["self_s_speculate"] == meta["self_s_sdc_check"] == 0.0
  assert meta["bytes_to_device"] > 0.0
  # the numpy path has the same keys, with no device stage in them
  host = want.meta
  assert host["self_s_place"] == host["bytes_to_device"] == 0.0
  assert host["self_s_stream"] > 0.0


@pytest.mark.parametrize("backend_kw", [{"jit": True},
                                        {"jit": True, "precision": "float32"},
                                        {}], ids=["x64", "float32", "numpy"])
@pytest.mark.parametrize("kind", ["plain", "joint"])
def test_rows_var_on_device_counts_the_sweep_on_x64(kind, backend_kw, layers,
                                                    arch_accs, monkeypatch):
  """An x64 program derives every row's variation columns itself, so its
  sweep's counter equals the sweep's points; float32 (no 64-bit
  integers) and numpy sweeps take the host's, and count none.  The
  host's share of the bundle keeps one ``batch_inputs`` span a chunk."""
  opened = []

  class Counted(spans.span):
    __slots__ = ()

    def __enter__(self):
      opened.append(self.name)
      return super().__enter__()

  monkeypatch.setattr(spans, "span", Counted)
  backend = VectorOracleBackend(**backend_kw)
  res = plain(backend, layers, 6) if kind == "plain" \
      else joint(backend, arch_accs, 6)
  x64 = backend_kw == {"jit": True}
  assert res.n_rows > 0
  assert res.meta["rows_var_on_device"] == (res.n_rows if x64 else 0)
  if backend.jit:
    assert opened.count("batch_inputs") == res.meta["n_chunks"] > 1


@pytest.mark.parametrize("kind", ["plain", "joint"])
def test_new_survivor_counts_compile_slices_once(kind, layers, arch_accs):
  """Survivors come back at the plan's fixed capacity and are cut on the
  host: once the fused program is compiled, a survivor count new to the
  process compiles nothing under ``slice``, nor anywhere else."""
  backend = VectorOracleBackend(jit=True)
  cols = ("perf_per_area", "energy_mj") if kind == "plain" \
      else ("top1_err", "energy_mj", "area_mm2")
  # survivor capacities no other test uses: these shapes are new
  plan = build_plan({"pareto": ParetoAccumulator(cols)},
                    joint=kind == "joint",
                    cap=1237 if kind == "plain" else 1239)
  archs = tuple(a for a, _ in arch_accs)
  accs = np.asarray([acc for _, acc in arch_accs], np.float64)
  stack = LayerStack.from_layer_lists(
      [arch_to_layers(a, image_size=16) for a in archs])

  def pending(seed):
    table = DesignSpace().sample_table(10, seed=seed)
    if kind == "plain":
      return backend.fused_eval_pending(
          table, layers, "net", plan, np.arange(len(table), dtype=np.int64))
    return backend.fused_co_eval_pending(
        table, stack, "net", plan,
        np.arange(len(archs) * len(table), dtype=np.int64), 0, accs, archs)

  def resolved(seed):
    with spans.recording() as rec:
      with spans.span("stream"):
        chunk = pending(seed).resolve()
    return chunk, rec

  first, rec = resolved(29)  # compiles the fused program, and no slice
  assert rec.n_compiles["slice"] == 0
  again, rec = resolved(30)  # another survivor count: nothing compiles
  assert len(again.payloads["pareto"][1]) != len(first.payloads["pareto"][1])
  assert rec.n_compiles["slice"] == 0
  assert sum(rec.n_compiles.values()) == 0
  assert first.n_transferred == again.n_transferred == plan.cap


def test_compile_totals_count_the_process(layers):
  s0, n0 = spans.compile_totals()
  jax.jit(lambda x: x * 3.0 + 0.123)(np.arange(7.0))
  s1, n1 = spans.compile_totals()
  assert n1 == n0 + 1 and s1 > s0


def test_programs_are_named_after_what_they_run(layers, arch_accs):
  from repro.explore import device
  specs = (("pareto", ParetoSpec(("perf_per_area", "energy_mj"),
                                 ("perf_per_area",))),)
  assert device.make_eval_fn(tuple(layers), None).__name__ == "eval"
  assert device.make_eval_fn(tuple(layers), DevicePlan(specs)).__name__ \
      == "fused_eval"
  assert device.make_joint_fn(None).__name__ == "joint"
  assert device.make_joint_fn(DevicePlan(specs)).__name__ == "fused_joint"
  # the name a device trace shows is the lowered module's
  from repro.core import oracle
  inputs = oracle.batch_inputs(DesignSpace().sample_table(8, seed=1))
  with jax.enable_x64(True):
    program = VectorOracleBackend(jit=True)._eval_fn(tuple(layers),
                                                     DevicePlan(specs))
    assert "@jit_fused_eval" in program.lower(inputs).as_text()
