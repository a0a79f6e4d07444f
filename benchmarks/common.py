"""Benchmark harness utilities: timing, the name,us_per_call,derived CSV,
and JSON perf records under ``results/`` (BENCH_*.json) so the performance
trajectory is tracked across PRs."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional

# where write_bench_json lands records; benchmarks.run --json-dir overrides
JSON_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "results")


def time_call(fn: Callable, n: int = 3, warmup: int = 1) -> float:
  """Mean wall-time per call in microseconds."""
  for _ in range(warmup):
    fn()
  t0 = time.perf_counter()
  for _ in range(n):
    fn()
  return (time.perf_counter() - t0) / n * 1e6


def emit(name: str, us_per_call: float, derived: str) -> None:
  print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def bench_provenance() -> Dict[str, object]:
  """Reproducibility stamp shared by every BENCH_*.json record: commit,
  UTC timestamp, library versions, core count, and the jax device kind
  the numbers were measured on."""
  import datetime
  import subprocess

  import numpy as np
  try:
    commit = subprocess.run(
        ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
        text=True, timeout=10,
        cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
  except Exception:  # noqa: BLE001 - provenance must never fail a bench
    commit = ""
  prov: Dict[str, object] = {
      "git_commit": commit or "unknown",
      "timestamp_utc": datetime.datetime.now(datetime.timezone.utc)
      .strftime("%Y-%m-%dT%H:%M:%SZ"),
      "numpy_version": np.__version__,
      "cpu_count": int(os.cpu_count() or 1),
  }
  import jax

  from repro.explore.fleet import device_topology
  prov["jax_version"] = jax.__version__
  topo = device_topology()
  prov["jax_device_kind"] = topo["device_kinds"][0]
  prov["device_topology"] = topo
  return prov


def write_bench_json(name: str, record: Dict) -> str:
  """Write ``results/BENCH_<name>.json`` (pretty, stable key order),
  stamped with :func:`bench_provenance`."""
  os.makedirs(JSON_DIR, exist_ok=True)
  path = os.path.join(JSON_DIR, f"BENCH_{name}.json")
  record = dict(record)
  record.setdefault("provenance", bench_provenance())
  with open(path, "w") as f:
    json.dump(record, f, indent=2, sort_keys=True)
    f.write("\n")
  return path
