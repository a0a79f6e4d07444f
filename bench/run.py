"""Run one cell of the benchmark and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration, a
traffic mix and a chip count; every file they need is found by name (see
``bench/harness.py``).  The run needs that many TPU chips and has no CPU
fallback: without them it exits 3 and prints no result.  The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last the ``checks``: each number compared for
``correct`` beside its limit, which also close standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import chip, harness  # noqa: E402


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = ap.parse_args()
  cell = harness.resolve(harness.load_benchmark(), args.workload)
  try:
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
  except chip.NoChip as e:
    print(f"no result: {e}", file=sys.stderr, flush=True)
    sys.exit(3)
  print(json.dumps(result), flush=True)


if __name__ == "__main__":
  main()
