"""Child process for the fleet scaling/chaos benchmark.

The XLA host-device count is fixed at process start, so every
measurement point (1 device, 8 devices, chaos) runs in its own child
with ``--xla_force_host_platform_device_count=N`` set *before* jax
loads.  Importing ``repro.explore.device`` then appends the exact-codegen
flags, so the parity contract (device results bit-identical to numpy)
holds inside every child exactly as it does in the tests.

Usage: python -m benchmarks.fleet_worker N_DEVICES MODE N_PER_TYPE CHUNK
  MODE: solo  — numpy baseline (no pool), the bit-identity reference
        fleet — healthy fleet sweep over all N visible devices
        chaos — fleet sweep with 1 straggler + 1 device lost mid-sweep
                + 1 silently-corrupting chunk, SDC sentinel on

Prints one JSON record on stdout: pairs/s over a timed post-warmup run,
the Pareto front columns (JSON floats round-trip doubles exactly, so
the parent compares them bit-for-bit), and the fleet meta counters.
"""
from __future__ import annotations

import json
import os
import sys
import time


def main() -> None:
  n_devices, mode = int(sys.argv[1]), sys.argv[2]
  n_per_type, chunk_size = int(sys.argv[3]), int(sys.argv[4])
  from benchmarks.fleet_perf import refuse_on_accelerator
  refuse_on_accelerator()
  os.environ["XLA_FLAGS"] = (
      os.environ.get("XLA_FLAGS", "")
      + f" --xla_force_host_platform_device_count={n_devices}").strip()
  # XLA latches its flags when the client initializes, and
  # visible_devices() below initializes it before the first backend is
  # built — so the exact-codegen flags must be in place *now*, not left
  # to VectorOracleBackend(jit=True).__init__
  from repro.explore.device import ensure_exact_cpu_codegen
  ensure_exact_cpu_codegen()
  from repro.core.workloads import get_network
  from repro.explore import (DesignSpace, DevicePool, Fault, FaultPlan,
                             ParetoAccumulator, ResiliencePolicy,
                             RetryPolicy, TopKAccumulator,
                             VectorOracleBackend, stream_explore,
                             visible_devices)
  from repro.explore.fleet import device_topology

  layers = get_network("resnet20")[:4]
  space = DesignSpace()
  n_chunks = -(-4 * n_per_type // chunk_size)  # 4 PE types

  def reducers():
    return {"pareto": ParetoAccumulator(),
            "top": TopKAccumulator(20, by="power_mw")}

  def sweep():
    kw = dict(network="resnet20", n_per_type=n_per_type, seed=17,
              chunk_size=chunk_size, reducers=reducers())
    if mode == "solo":
      return stream_explore(VectorOracleBackend(), space, layers,
                            workers=1, **kw)
    chaos = mode == "chaos"
    pool = DevicePool(sdc_check_every=4 if chaos else 0)
    policy = None
    if chaos:
      assert n_chunks >= 5, f"chaos needs >= 5 chunks, got {n_chunks}"
      policy = ResiliencePolicy(
          retry=RetryPolicy(sleep=lambda s: None),
          fault_plan=FaultPlan([
              Fault("device-lost", 1, "fleet"),
              Fault("corrupt", 2, "fleet"),
              Fault("slow", n_chunks - 1, "fleet"),
          ]))
    return stream_explore(VectorOracleBackend(jit=True), space, layers,
                          pool=pool, policy=policy, **kw)

  assert len(visible_devices()) == n_devices
  sweep()                                     # warmup: compile + caches
  t0 = time.perf_counter()
  res = sweep()
  dt = time.perf_counter() - t0

  front = res.results["pareto"]
  meta = {k: v for k, v in res.meta.items()
          if isinstance(v, (int, float, str))}
  print(json.dumps({
      "mode": mode,
      "n_devices": n_devices,
      "n_rows": int(res.n_rows),
      "pairs_per_sec": res.n_rows / dt,
      "wall_s": dt,
      "front": {col: getattr(front, col).tolist()
                for col in ("latency_s", "power_mw", "area_mm2")},
      "top": {col: getattr(res.results["top"], col).tolist()
              for col in ("latency_s", "power_mw", "area_mm2")},
      "meta": meta,
      "topology": device_topology(),
  }))


if __name__ == "__main__":
  main()
