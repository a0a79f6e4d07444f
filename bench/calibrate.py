"""Readings for a cell's comparison limits, in one process on the chip:
the numbers that sound sweeps of the timed path give over many seeds
(the lower readings), and those of its float32 control over a few (the
upper readings).

  python3 bench/calibrate.py --workload <cell> --seeds 101-112 \\
      --control-seeds 201-203 [--fault-seeds 301-303] [--out <file.jsonl>]

Each sweep goes through the cell's driver, as in the window; the control
is the same driver on ``VectorOracleBackend(jit=True,
precision="float32")``, the program's own path one precision below the
float64 the configuration states.  A fault, read on the timed path with
every other chunk left out of the fold, shows what ``ids_differ`` reads
when answers go missing.  One JSON line per sweep: which side,
the run seed, the sweep's seconds, the reference's seconds, and the
numbers.  The benchmark's own runs never call this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import compare, harness, sweep  # noqa: E402


def seed_range(text: str):
  lo, _, hi = text.partition("-")
  return list(range(int(lo), int(hi or lo) + 1))


def drop_half_of_the_chunks() -> None:
  """Plant the fault: every other chunk is never folded."""
  from repro.explore import streaming
  fold, seen = streaming.fold_chunk, []

  def half(reducers, counters, result):
    seen.append(1)
    if len(seen) % 2:
      fold(reducers, counters, result)
  streaming.fold_chunk = half


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seeds", type=seed_range, required=True)
  ap.add_argument("--control-seeds", type=seed_range, required=True)
  ap.add_argument("--fault-seeds", type=seed_range, default=[])
  ap.add_argument("--out", default="")
  args = ap.parse_args()
  cell = harness.resolve(harness.load_benchmark(), args.workload)
  _, _, program, _ = harness.prepare(cell, require_chip=True)
  control = cell.driver.Driver(cell.config, cell.workload, cell.traffic,
                               precision="float32")
  t0 = time.perf_counter()
  warm = harness.sweep_seed(0, -1)
  sweep.compile_ahead(program.warm_jobs(warm) + control.warm_jobs(warm))
  harness.log(f"compiled both paths in {time.perf_counter() - t0:.1f} s")
  lines = []
  for side, driver, seeds in (("program", program, args.seeds),
                              ("control", control, args.control_seeds),
                              ("fault", program, args.fault_seeds)):
    if side == "fault":
      drop_half_of_the_chunks()
    for seed in seeds:
      s = harness.sweep_seed(seed, 0)
      t1 = time.perf_counter()
      try:
        got = driver.sweep(s)
      except Exception as e:  # a control that crashes has failed
        if side != "control":
          raise
        lines.append({"side": side, "seed": seed, "error": repr(e)})
        print(json.dumps(lines[-1]), flush=True)
        continue
      t2 = time.perf_counter()
      ref = cell.driver.reference(cell.config, cell.workload, cell.traffic,
                                  s)
      t3 = time.perf_counter()
      lines.append({
          "side": side, "seed": seed, "sweep_seed": s, "sweep_s": t2 - t1,
          "reference_s": t3 - t2,
          **compare.readings(got.answers, ref, cell.traffic["reducers"])})
      print(json.dumps(lines[-1]), flush=True)
  if args.out:
    with open(args.out, "w") as f:
      f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
  main()
