"""The program's spans and counters as the benchmark reads them, on the
CPU: a profiler trace of a tiny sweep read by ``bench.trace.load``, the
compile count of a sweep against the benchmark's ``CompileClock``, and
the per-layer metrics that read the counters."""
import glob
import os

import numpy as np
import pytest

from bench_cells import tiny_cell

from bench import chip, harness, trace

COUNTER_METRICS = {
    "sample_ms_per_mpoint": "self_s_sample",
    "batch_inputs_ms_per_mpoint": "self_s_batch_inputs",
    "place_ms_per_mpoint": "self_s_place",
    "wait_ms_per_mpoint": "self_s_wait",
    "fetch_ms_per_mpoint": "self_s_fetch",
    "fold_ms_per_mpoint": "self_s_fold_chunk",
    "slice_compiles_per_sweep": "n_compiles_slice",
}
CELLS = {"resnet20_cifar10.dse1m": "fused_eval",
         "supernet_coexplore.stream10m": "fused_joint"}

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def no_cache_dir(monkeypatch):
  # keep this test process's compile-cache settings as they were
  monkeypatch.setattr("repro.compile_cache.enable_compile_cache",
                      lambda: "")


def driver(name):
  cell = tiny_cell(name)
  return cell.driver.Driver(cell.config, cell.workload, cell.traffic)


def inside(events, inner, outer):
  """Every ``inner`` event lies inside some ``outer`` event."""
  outers = [(s, e) for n, s, e in events if n == outer]
  inners = [(s, e) for n, s, e in events if n == inner]
  return inners and all(any(so <= s and e <= eo for so, eo in outers)
                        for s, e in inners)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_profiler_trace_shows_the_program_spans(name, tmp_path):
  import jax
  from jax.profiler import ProfileData
  d = driver(name)
  d.sweep(5)  # compile outside the trace
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  opts.host_tracer_level = 2
  jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
  with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
    d.sweep(6)
  jax.profiler.stop_trace()
  (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
  host = trace.load(path)["host"]  # the main thread's events
  names = {n for n, _, _ in host}
  assert {"stream", "plan", "sample", "dispatch", "batch_inputs", "place",
          "launch", "resolve", "wait", "slice", "fetch",
          "fold_chunk"} <= names
  for parent, children in (("dispatch", ("batch_inputs", "place",
                                         "launch")),
                           ("resolve", ("wait", "slice", "fetch")),
                           ("stream", ("dispatch", "resolve",
                                       "fold_chunk"))):
    for child in children:
      assert inside(host, child, parent), (child, parent)
  # the program is named after what it runs: on the CPU the call shows
  # on the host plane, and its module (a device plane's name) is jit_<name>
  program = CELLS[name]
  assert f"PjitFunction({program})" in names
  assert "PjitFunction(run)" not in names
  stats = {}
  for plane in ProfileData.from_file(path).planes:
    for line in plane.lines:
      for ev in line.events:
        if ev.name == "resolve":
          stats = dict(ev.stats)
  assert {"sweep", "chunk", "survivors"} <= set(stats)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sweep_compiles_match_the_compile_clock(name):
  clock = chip.CompileClock()
  d = driver(name)
  outcomes = []
  for seed in (7, 8):  # the first compiles its programs, both slices
    c0 = clock.mark()
    outcomes.append((d.sweep(seed), clock.mark()[1] - c0[1]))
  for out, counted in outcomes:
    spanned = sum(v for k, v in out.meta.items()
                  if k.startswith("n_compiles_"))
    assert spanned == counted
  assert outcomes[0][1] > 0


def ctx_with(meta, traced=True, n_rows=2_000_000):
  outcome = type("Outcome", (), {"meta": meta})()
  sweeps = [{"ok": True, "n_rows": n_rows, "outcome": outcome},
            {"ok": False, "n_rows": 0}]
  return {"trace": {"busy_s": [0.1], "window_s": 1.0} if traced else None,
          "sweeps": sweeps}


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_metric_reads_the_sweeps(metric):
  read = harness.load_module(os.path.join(harness.BENCH_DIR, "metrics",
                                          metric + ".py")).read
  key = COUNTER_METRICS[metric]
  got = read(ctx_with({key: 3.0, "seconds": 9.0}))
  # 3 s over 2M points is 1500 ms/Mpoint; a count is per sweep
  assert got == (3.0 if key.startswith("n_compiles_") else 1500.0)
  assert read(ctx_with({key: 3.0}, traced=False)) is None  # the CPU
  assert read(ctx_with({"seconds": 9.0})) is None  # a program without it


def test_new_metrics_are_in_every_cell():
  bench = harness.load_benchmark()
  for name in [w["name"] for w in bench["workloads"]]:
    cell = harness.resolve(bench, name)
    assert set(COUNTER_METRICS) <= {m["name"] for m in cell.per_layer}


def test_traced_run_on_the_cpu_leaves_the_counters_out():
  out = harness.run_cell(tiny_cell("supernet_coexplore.stream10m"),
                         2**31 + 11, 1.0, True, 0.0, require_chip=False)
  assert out["correct"]
  assert not set(COUNTER_METRICS) & set(out["metrics"])
  assert np.isfinite(out["metrics"]["transfer_pct"]["value"])
