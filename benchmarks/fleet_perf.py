"""Elastic fleet perf record: multi-device scaling + chaos bit-identity.

Measures sweep throughput (design pairs/s) on 1 vs 8 forced XLA host
devices — each point in its own child process, since the device count is
fixed at jax start — and runs the acceptance chaos scenario (1 straggler
+ 1 device lost mid-sweep + 1 silently-corrupting chunk with the SDC
sentinel on) asserting its Pareto front is bit-identical to the solo
numpy baseline.  Results land in ``results/BENCH_fleet.json``;
``FLEET_BENCH_SCALE=smoke`` (CI) shrinks the sweep while still
exercising every phase.

The >= 4x scaling gate is enforced only at full scale on hosts with
>= 8 cores: forced host devices share physical cores, so on a smaller
box the 8-device point measures dispatch overhead, not parallel
capacity.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_worker(n_devices: int, mode: str, n_per_type: int,
                chunk_size: int) -> dict:
  env = dict(os.environ)
  env.pop("XLA_FLAGS", None)  # the child builds its own device topology
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(_REPO, "src"),
       env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
  proc = subprocess.run(
      [sys.executable, "-m", "benchmarks.fleet_worker", str(n_devices),
       mode, str(n_per_type), str(chunk_size)],
      capture_output=True, text=True, env=env, cwd=_REPO, timeout=3600)
  if proc.returncode != 0:
    raise RuntimeError(
        f"fleet worker ({n_devices} dev, {mode}) failed:\n"
        + proc.stderr[-4000:])
  return json.loads(proc.stdout.splitlines()[-1])


def refuse_on_accelerator() -> None:
  """The fleet points run in child processes, each with its own forced
  XLA host-device count.  On an accelerator host that measures nothing
  real, and a child cannot reach a chip that a parent process already
  holds — so the fleet benchmark refuses there instead of hanging."""
  from repro.explore.device import compiles_for_cpu
  if not compiles_for_cpu():
    raise RuntimeError(
        "benchmarks.fleet_perf forces XLA host-device counts in child "
        "processes and runs on CPU hosts only; on a TPU host run "
        "`python chip_smoke.py --chips 4`, which drives the device fleet "
        "in one process")


def fleet_perf() -> None:
  from benchmarks.common import emit, write_bench_json

  refuse_on_accelerator()
  smoke = os.environ.get("FLEET_BENCH_SCALE") == "smoke"
  if smoke:
    n_per_type, chunk_size = 200, 100        # 800 rows, 8 chunks
  else:
    n_per_type, chunk_size = 25000, 6250     # 100k rows, 16 chunks

  solo = _run_worker(1, "solo", n_per_type, chunk_size)
  one = _run_worker(1, "fleet", n_per_type, chunk_size)
  eight = _run_worker(8, "fleet", n_per_type, chunk_size)
  chaos = _run_worker(8, "chaos", n_per_type, chunk_size)

  # bit-identity: the healthy 8-device front and the chaos front must
  # both reproduce the solo numpy front exactly (JSON doubles round-trip)
  for name, run in (("fleet8", eight), ("chaos", chaos)):
    for part in ("front", "top"):
      assert run[part] == solo[part], f"{name} {part} != solo"
  meta = chaos["meta"]
  assert meta["n_device_losses"] == 1.0, meta
  assert meta["n_corruptions_detected"] == 1.0, meta
  assert meta["n_corruption_checks"] >= 1.0, meta
  assert meta["n_resharded"] >= 1.0, meta
  assert meta["n_leaked_watchdogs"] == 0.0, meta

  scaling = eight["pairs_per_sec"] / one["pairs_per_sec"]
  if not smoke and (os.cpu_count() or 1) >= 8:
    assert scaling >= 4.0, (
        f"8-device scaling {scaling:.2f}x < 4x at full scale")

  emit("fleet_pairs_per_sec_1dev", 1e6 / one["pairs_per_sec"],
       f"pairs/s={one['pairs_per_sec']:.0f}")
  emit("fleet_pairs_per_sec_8dev", 1e6 / eight["pairs_per_sec"],
       f"pairs/s={eight['pairs_per_sec']:.0f} scaling={scaling:.2f}x")
  emit("fleet_chaos_sweep", chaos["wall_s"] * 1e6,
       f"bit-identical lost={int(meta['n_device_losses'])} "
       f"sdc={int(meta['n_corruptions_detected'])} "
       f"resharded={int(meta['n_resharded'])}")

  write_bench_json("fleet", {
      "scale": "smoke" if smoke else "full",
      "n_rows": solo["n_rows"],
      "pairs_per_sec_solo_numpy": solo["pairs_per_sec"],
      "pairs_per_sec_1dev": one["pairs_per_sec"],
      "pairs_per_sec_8dev": eight["pairs_per_sec"],
      "scaling_1_to_8": scaling,
      "scaling_gate_enforced": bool(not smoke
                                    and (os.cpu_count() or 1) >= 8),
      "chaos": {
          "bit_identical_to_solo": True,
          "wall_s": chaos["wall_s"],
          "n_device_losses": meta["n_device_losses"],
          "n_corruption_checks": meta["n_corruption_checks"],
          "n_corruptions_detected": meta["n_corruptions_detected"],
          "n_resharded": meta["n_resharded"],
          "n_speculative": meta["n_speculative"],
          "n_leaked_watchdogs": meta["n_leaked_watchdogs"],
      },
      "device_topology_8dev": eight["topology"],
  })


ALL = [fleet_perf]
