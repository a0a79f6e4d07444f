"""The four-chip cell ``supernet_coexplore.pool4`` at a size a test run
holds, on four virtual CPU devices (a child process: the device count is
fixed when the process starts): the pool's compiles per device add up to
the sweep's, a sound run is correct, and a run that folds only one
device's chunks is not."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench_cells import ROOT

SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=4").strip()
    from repro.explore.device import ensure_exact_cpu_codegen
    ensure_exact_cpu_codegen()
    import repro.compile_cache
    repro.compile_cache.enable_compile_cache = lambda: ""
    from bench import chip, harness
    from bench_cells import TINY

    cell = harness.resolve(harness.load_benchmark(),
                           "supernet_coexplore.pool4")
    tiny = TINY["supernet_coexplore.stream10m"]
    cell.traffic.update(tiny["traffic"])
    cell.config.update(tiny["config"])
    cell.workload = harness.load_module(os.path.join(
        harness.BENCH_DIR, "configs", "supernet_coexplore.py")).workload(
            cell.config)
    out = {"traffic_pool": cell.traffic["pool"], "chips": cell.chips}

    clock = chip.CompileClock()
    driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic)
    out["sweeps"] = []
    for seed in (3, 4):
      n0 = clock.mark()[1]
      meta = driver.sweep(seed).meta
      out["sweeps"].append({
          "clock": clock.mark()[1] - n0,
          "spanned": sum(v for k, v in meta.items()
                         if k.startswith("n_compiles_")),
          "per_device": meta["fleet_device_compiles"],
          "chunks": meta["fleet_device_chunks"]})
    del driver

    sound = harness.run_cell(cell, 2**31 + 21, 0.3, False, 0.0,
                             require_chip=False)
    out["sound"] = {"correct": sound["correct"],
                    "devices": sound["device"]["count"]}

    # the fault: every chunk runs, only those of one device are folded
    from repro.explore import backend, device, fleet, streaming
    dispatch = backend.VectorOracleBackend.fused_co_eval_pending
    resolve = device.PendingFused.resolve
    fold = streaming.fold_chunk
    kept = []

    def tagged(self, *args, **kwargs):
      pending = dispatch(self, *args, **kwargs)
      pending.pinned = fleet.pinned_device()
      return pending

    def carried(self):
      chunk = resolve(self)
      chunk.pinned = self.pinned
      return chunk

    def one_device(reducers, counters, result):
      kept.append(result.pinned) if not kept else None
      if result.pinned == kept[0]:
        fold(reducers, counters, result)

    backend.VectorOracleBackend.fused_co_eval_pending = tagged
    device.PendingFused.resolve = carried
    streaming.fold_chunk = one_device
    broken = harness.run_cell(cell, 2**31 + 21, 0.3, False, 0.0,
                              require_chip=False)
    out["broken"] = {"correct": broken["correct"],
                     "ids_differ": broken["checks"]["ids_differ"]["value"]}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def pool4():
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env["JAX_PLATFORMS"] = "cpu"
  env["PYTHONPATH"] = os.pathsep.join(
      [ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__)])
  proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                        capture_output=True, text=True, timeout=600)
  assert proc.returncode == 0, proc.stderr[-4000:]
  return json.loads(proc.stdout.strip().splitlines()[-1])


def test_pool_cell_runs_on_four_devices(pool4):
  assert pool4["chips"] == 4 and pool4["traffic_pool"] is True
  assert pool4["sound"]["devices"] == 4


def test_pool_compiles_per_device_add_up_to_the_sweep(pool4):
  first, second = pool4["sweeps"]
  for sweep in (first, second):
    assert len(sweep["per_device"]) == 4
    assert sum(sweep["per_device"]) == sweep["spanned"] == sweep["clock"]
  # the window's first sweep compiles the fused program on each device
  assert first["clock"] > 0
  assert sum(1 for n in first["per_device"] if n > 0) >= 2


def test_sound_pool_run_is_correct(pool4):
  assert pool4["sound"]["correct"] is True


def test_pool_run_folding_one_device_is_not_correct(pool4):
  assert pool4["broken"]["correct"] is False
  assert pool4["broken"]["ids_differ"] > 0
