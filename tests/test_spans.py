"""Spans and counters of the streaming engine (repro.explore.spans).

  * self time: a span's duration less its child spans', on one thread
    and on two at once, with an injected clock;
  * a streamed sweep's ``meta`` carries every span's self time, and the
    self times inside the ``stream`` span add up to the sweep's seconds;
    the spans change no front;
  * compiles are credited to the span that makes them: a survivor count
    new to the process compiles slices, a repeated one does not.
"""
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.cnn import SEARCH_SPACE, ArchChoice
from repro.core.workloads import get_network
from repro.explore import DesignSpace, VectorOracleBackend, spans
from repro.explore.device import DevicePlan, ParetoSpec
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


def test_new_survivor_counts_compile_slices_once(layers):
  backend = VectorOracleBackend(jit=True)
  # a survivor capacity no other test uses: these slice shapes are new
  plan = DevicePlan(specs=(("pareto", ParetoSpec(
      ("perf_per_area", "energy_mj"), ("perf_per_area",))),), cap=1237)
  table = DesignSpace().sample_table(40, seed=29)
  idx = np.arange(len(table), dtype=np.int64)

  def resolved():
    with spans.recording() as rec:
      with spans.span("stream"):
        chunk = backend.fused_eval_pending(table, layers, "net", plan,
                                           idx).resolve()
    return chunk, rec

  first, rec = resolved()
  assert first.n_transferred > 0
  assert rec.n_compiles["slice"] >= 1
  assert rec.compile_s["slice"] > 0.0
  again, rec = resolved()  # the same survivor count: nothing compiles
  assert again.n_transferred == first.n_transferred
  assert rec.n_compiles["slice"] == 0
  assert sum(rec.n_compiles.values()) == 0


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
