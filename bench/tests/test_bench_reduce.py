"""The reduction from a profiler trace to busy time, idle time by host
event and device programs, on a trace recorded on a TPU v5e: a jitted
float64 reduction and three new-length slices (each compiled on the spot)
inside a ``sweep`` annotation, then a sleep."""
import os

import pytest

import bench_cells  # noqa: F401  (puts the benchmark on sys.path)

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_v5e_probe.xplane.pb")


@pytest.fixture(scope="module")
def events():
  return trace.load(DATA, window="sweep")


def test_union_and_gaps():
  busy = trace.union([(5, 7), (1, 3), (2, 4), (9, 20)], 0, 10)
  assert busy == [(1, 4), (5, 7), (9, 10)]
  assert trace.gaps(busy, 0, 10) == [(0, 1), (4, 5), (7, 9)]
  assert trace.gaps([], 0, 10) == [(0, 10)]


def test_attribute_goes_to_innermost_cover():
  host = [("outer", 0, 100), ("inner", 20, 40), ("other", 60, 70)]
  got = trace.attribute([(10, 50), (80, 120)], host, window="none")
  assert got["inner"] == pytest.approx(20e-9)
  assert got["outer"] == pytest.approx(40e-9)
  assert got["no host event"] == pytest.approx(20e-9)


def test_recorded_trace_planes(events):
  assert list(events["devices"]) == ["/device:TPU:0"]
  assert len(events["devices"]["/device:TPU:0"]) == 6
  assert any(name == "sweep" for name, _, _ in events["host"])


def test_recorded_trace_reduction(events):
  r = trace.reduce(events, window="sweep")
  assert r["window_s"] == pytest.approx(0.222079413)
  # six programs: three 6.5 us reductions and three 3 us slices
  assert r["busy_s"][0] == pytest.approx(22.241e-6)
  assert dict(r["device_ops"]) == pytest.approx(
      {"jit__lambda": 13.144e-6, "jit_dynamic_slice": 9.097e-6})
  idle = dict(r["idle_gaps"])
  # the slices compile while the device waits
  assert max(idle, key=idle.get) == "backend_compile_and_load"
  assert idle["no host event"] == pytest.approx(0.0527, abs=1e-3)
  lo, hi = 0.0, r["window_s"]
  assert sum(idle.values()) <= hi - lo - r["busy_s"][0] + 1e-12


def test_reduction_needs_its_window(events):
  assert trace.reduce(events) is None
  assert trace.load(DATA)["host"] == []


def test_device_metrics_read_the_reduction():
  from bench import harness
  read = lambda name, ctx: harness.load_module(os.path.join(  # noqa: E731
      harness.BENCH_DIR, "metrics", name + ".py")).read(ctx)
  ctx = {"trace": {"busy_s": [1.0, 3.0], "window_s": 4.0},
         "traced_points": 2_000_000}
  assert read("device_idle_pct", ctx) == pytest.approx(50.0)
  assert read("device_ms_per_mpoint", ctx) == pytest.approx(2000.0)
  ctx = {"trace": None, "traced_points": 0}
  assert read("device_idle_pct", ctx) is None
  assert read("device_ms_per_mpoint", ctx) is None
