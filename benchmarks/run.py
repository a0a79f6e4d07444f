"""Benchmark runner: one function per paper table/figure + framework perf.

Prints ``name,us_per_call,derived`` CSV rows; benchmarks that track the
perf trajectory additionally write ``BENCH_*.json`` records (default
under ``results/``, see --json-dir), each stamped with provenance
(git commit, UTC timestamp, numpy/jax versions, cpu count, jax device
kind — see ``benchmarks.common.bench_provenance``) — e.g.
``BENCH_explore.json`` with scalar-vs-vector sweep points/sec and the
Pareto-front time.
Usage: PYTHONPATH=src python -m benchmarks.run [--suite name]
       [--only substr] [--json-dir DIR]
"""
from __future__ import annotations

import argparse
import sys
import traceback

# suites that run streaming_perf's device-resident phase and therefore
# need the XLA exactness flags set before this process's first jax
# compilation (see repro.explore.device.ensure_exact_cpu_codegen); the
# flags pessimize unrelated jax codegen slightly, so suites without a
# device phase are left untouched to keep their perf records comparable
_DEVICE_SUITES = ("streaming", "framework", "all")


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--suite", default="all",
                  choices=("paper", "accuracy", "framework", "coexplore",
                           "streaming", "search", "resilience", "service",
                           "fleet", "all"),
                  help="benchmark module to run (default: all); "
                       "'coexplore' runs just the joint-sweep perf record, "
                       "'streaming' the constant-memory sweep-engine record "
                       "(STREAMING_BENCH_SCALE=smoke shrinks it for CI), "
                       "'search' the guided-search front-quality record "
                       "(SEARCH_BENCH_SCALE=smoke shrinks it for CI), "
                       "'resilience' the kill-and-resume / fault-healing "
                       "record (RESILIENCE_BENCH_SCALE=smoke for CI), "
                       "'service' the store-hit / delta-sweep amortization "
                       "record (SERVICE_BENCH_SCALE=smoke for CI), "
                       "'fleet' the multi-device scaling + chaos "
                       "bit-identity record (FLEET_BENCH_SCALE=smoke for "
                       "CI; each point is a child process with its own "
                       "forced XLA host-device count)")
  ap.add_argument("--only", default=None,
                  help="run only benchmarks whose name contains this")
  ap.add_argument("--json-dir", default=None,
                  help="directory for BENCH_*.json perf records "
                       "(default: results/)")
  args = ap.parse_args()
  if args.suite in _DEVICE_SUITES:
    from repro.explore.device import ensure_exact_cpu_codegen
    ensure_exact_cpu_codegen()
  from repro.compile_cache import enable_compile_cache
  enable_compile_cache()
  if args.json_dir:
    from benchmarks import common
    common.JSON_DIR = args.json_dir

  from benchmarks import (accuracy_experiments, fleet_perf, framework_perf,
                          paper_figures, search_perf, service_perf)
  suites = {
      "paper": paper_figures.ALL,
      "accuracy": accuracy_experiments.ALL,
      "framework": framework_perf.ALL,
      "coexplore": [framework_perf.coexplore_vector_perf],
      "streaming": [framework_perf.streaming_perf],
      "search": search_perf.ALL,
      "resilience": [framework_perf.resilience_perf],
      "service": service_perf.ALL,
      "fleet": fleet_perf.ALL,
  }
  benches = suites.get(args.suite) or (paper_figures.ALL
                                       + accuracy_experiments.ALL
                                       + framework_perf.ALL
                                       + search_perf.ALL
                                       + service_perf.ALL
                                       + fleet_perf.ALL)
  print("name,us_per_call,derived")
  failures = 0
  for fn in benches:
    if args.only and args.only not in fn.__name__:
      continue
    try:
      fn()
    except Exception as e:  # noqa: BLE001
      failures += 1
      print(f"{fn.__name__},nan,FAILED:{type(e).__name__}:{e}",
            flush=True)
      traceback.print_exc(file=sys.stderr)
  if failures:
    sys.exit(1)


if __name__ == "__main__":
  main()
