"""The comparison that decides ``correct`` fails where it should, at a
size a test run holds, on the CPU: the float32 control (the program's own
path one precision below the float64 its configurations state), and whole
runs of the harness, past its look for a chip, with the timed path broken
underneath: an answer altered where it is produced, and half of the
chunks left out of the fold."""
import numpy as np
import pytest

from bench_cells import tiny_cell

from bench import compare, harness

CELLS = ["resnet20_cifar10.dse1m", "supernet_coexplore.stream10m"]


@pytest.fixture(autouse=True)
def no_cache_dir(monkeypatch):
  # keep this test process's compile-cache settings as they were
  monkeypatch.setattr("repro.compile_cache.enable_compile_cache",
                      lambda: "")


def numbers(cell, precision, seed=11):
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic,
                              precision=precision)
  got = driver.sweep(seed)
  ref = cell.driver.reference(cell.config, cell.workload, cell.traffic, seed)
  return compare.verdict(compare.readings(got.answers, ref,
                                          cell.traffic["reducers"]),
                         cell.limits)


@pytest.mark.parametrize("name", CELLS)
def test_device_path_passes_and_float32_control_fails(name):
  cell = tiny_cell(name)
  assert compare.within(numbers(cell, "x64"))
  control = numbers(cell, "float32")
  assert not compare.within(control)
  assert control["value_rel_gap"]["value"] > 100 * \
      control["value_rel_gap"]["limit"]


def alter_answer(monkeypatch):
  """Every row a fused chunk returns reports a latency 1% too long."""
  from repro.explore.device import PendingFused
  resolve = PendingFused.resolve

  def altered(self):
    chunk = resolve(self)
    for payload in chunk.payloads.values():
      payload[1].latency_s = payload[1].latency_s * 1.01
    return chunk
  monkeypatch.setattr(PendingFused, "resolve", altered)


def drop_half(monkeypatch):
  """Every other chunk is never folded."""
  from repro.explore import streaming
  fold = streaming.fold_chunk
  seen = []

  def half(reducers, counters, result):
    seen.append(1)
    if len(seen) % 2:
      fold(reducers, counters, result)
  monkeypatch.setattr(streaming, "fold_chunk", half)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [None, alter_answer, drop_half],
                         ids=["sound", "altered", "half"])
def test_run_with_timed_path_broken(name, fault, monkeypatch):
  cell = tiny_cell(name)
  if fault is not None:
    fault(monkeypatch)
  out = harness.run_cell(cell, 2**31 + 9, 0.3, False, 0.0,
                         require_chip=False)
  assert out["attempted"] >= 1 and out["failed"] == 0
  assert out["correct"] is (fault is None), out["checks"]
  assert list(out)[-1] == "checks"
  assert np.isfinite(out["metrics"]["points_per_s"]["value"])


def test_window_that_spends_its_questions_fails():
  # a window that would ask a question twice ends with a failed sweep
  cell = tiny_cell("supernet_coexplore.stream10m")
  cell.traffic.update(questions=2)
  out = harness.run_cell(cell, 2**31 + 9, 60.0, False, 0.0,
                         require_chip=False)
  assert out["attempted"] == 3 and out["failed"] == 1
  assert out["correct"] is False


def test_traced_run_on_the_cpu_reports_no_device_metric():
  # the CPU has no device plane to read: the per-layer metrics that come
  # from the host are there, the device's are left out, never zero
  out = harness.run_cell(tiny_cell(CELLS[0]), 2**31 + 9, 1.5, True, 0.0,
                         require_chip=False)
  assert out["correct"]
  assert set(out["metrics"]) == {"window_compile_s", "transfer_pct",
                                 "sweep_p95_s"}
  assert "breakdown" not in out and "busy_s" not in out["device"]
