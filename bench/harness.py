"""One run of one cell: resolve the cell's files by name, build its inputs
from the seed, compile its program shapes ahead, drive the timed window,
check what the window produced against the plain reference, and assemble
the result line.

Everything that belongs to one configuration, traffic mix, sweep kind or
metric is a file of its own, found by name:

  bench/configs/<config>.json   the configuration as run
  bench/configs/<config>.py     its plain reference: ``workload(config)``
  bench/traffic/<mix>.json      sweep kind, sizes, chunk, reducers, loop
  bench/drivers/<kind>.py       ``Driver`` and ``reference`` of a sweep kind
  bench/metrics/<metric>.py     ``read(ctx)``: the metric, or None
  bench/limits/<cell>.json      the limits of the comparison
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import sys
import time
import traceback
from typing import Callable, Dict, List

import numpy as np

from bench import chip, compare, sweep, trace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(msg: str) -> None:
  print(msg, file=sys.stderr, flush=True)


def load_benchmark(root: str = ROOT) -> dict:
  with open(os.path.join(root, "BENCHMARK.json")) as f:
    return json.load(f)


def load_json(*parts) -> dict:
  with open(os.path.join(*parts)) as f:
    return json.load(f)


def load_module(path: str):
  """Import one file of the benchmark by its path."""
  name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
      os.sep, "_").replace(".", "_").replace("-", "_")
  spec = importlib.util.spec_from_file_location(name, path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


@dataclasses.dataclass
class Cell:
  name: str
  chips: int
  config: dict
  traffic: dict
  workload: dict
  limits: dict
  end_to_end: List[dict]
  per_layer: List[dict]
  driver: object  # the sweep kind's module: Driver, reference


def resolve(bench: dict, name: str, root: str = ROOT,
            bench_dir: str = BENCH_DIR) -> Cell:
  """The cell called ``name``, with every file it names loaded."""
  cells = {w["name"]: w for w in bench["workloads"]}
  if name not in cells:
    raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
  w = cells[name]
  entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
  config = load_json(root, entry["file"])
  traffic = load_json(bench_dir, "traffic", w["traffic"] + ".json")
  e2e = [m for m in bench["end_to_end"]
         if name in m.get("workloads", [name])]
  moved = {m["name"] for m in e2e}
  per_layer = [m for m in bench["per_layer"]
               if (name in m["workloads"] if "workloads" in m
                   else m["moves"] in moved)]
  return Cell(
      name=name, chips=int(w["chips"]), config=config, traffic=traffic,
      workload=load_module(os.path.join(
          bench_dir, "configs", w["config"] + ".py")).workload(config),
      limits=load_json(bench_dir, "limits", name + ".json")["limits"],
      end_to_end=e2e, per_layer=per_layer,
      driver=load_module(os.path.join(bench_dir, "drivers",
                                      traffic["kind"] + ".py")))


def sweep_seed(seed, i: int) -> int:
  """The sampling seed of sweep ``i`` of a run seeded ``seed`` (negative
  ``i`` are the warm-up's and the sample's own): 31 bits of a hash."""
  digest = hashlib.sha256(f"{seed}:{i}".encode()).digest()
  return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


class QuestionsSpent(RuntimeError):
  """The window asked for more sweeps than its traffic mix holds."""


def schedule(seed: int, traffic: dict) -> Callable[[int], int]:
  """The sampling seed of each sweep of the window.  Fresh ones from the
  run's seed; or, where the traffic mix names ``questions``, that many
  fixed sweeps (none of them the warm-up's) in one fixed order, the same
  in every run: the program keeps compiled survivor slices for the whole
  process, so the order of the questions changes the work a window does.
  No question is asked twice, since a repeat would find its slices
  compiled already: the sweep after the last raises ``QuestionsSpent``."""
  n = int(traffic.get("questions", 0))
  if not n:
    return lambda i: sweep_seed(seed, i)

  def question(i: int) -> int:
    if i >= n:
      raise QuestionsSpent(f"sweep {i} asks for question {i + 1} of {n}")
    return sweep_seed("questions", i)
  return question


def prepare(cell: Cell, require_chip: bool, precision: str = "x64"):
  """The devices, a compile clock, and the cell's driver, with JAX's
  persistent compile cache turned on first, as the program's entry
  points do."""
  from repro.compile_cache import enable_compile_cache
  cache_dir = enable_compile_cache()
  import jax
  devs = chip.devices(cell.chips) if require_chip \
      else jax.devices()[:cell.chips]
  clock = chip.CompileClock()
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic,
                              precision=precision)
  return devs, clock, driver, cache_dir


def warm(driver, clock, seed: int) -> None:
  s0, n0 = clock.mark()
  t0 = time.perf_counter()
  jobs = driver.warm_jobs(sweep_seed(seed, -1))
  sweep.compile_ahead(jobs)
  s1, n1 = clock.mark()
  log(f"warm-up: {len(jobs)} program shapes in "
      f"{time.perf_counter() - t0:.3f} s, {n1 - n0} compiles summing "
      f"{s1 - s0:.3f} s")


def run_window(driver, clock, seconds: float, seeds: Callable[[int], int],
               capture=None, trace_seconds: float = 0.0) -> dict:
  """Sweeps back to back, one client, from the window's start until
  ``seconds`` have passed; a sweep that starts inside the window runs to
  its end and counts.  With a ``capture``, whole sweeps from a third of
  the window on are traced for about ``trace_seconds``.  Each sweep's
  time, compiles and garbage-collection pauses go to standard error."""
  from jax.profiler import TraceAnnotation
  sweeps: List[dict] = []
  traced_points = 0
  pause = [0.0, 0.0]  # seconds paused collecting garbage; current start

  def on_gc(phase, info):
    if phase == "start":
      pause[1] = time.perf_counter()
    else:
      pause[0] += time.perf_counter() - pause[1]
  gc.callbacks.append(on_gc)
  t0 = time.perf_counter()
  trace_from = t0 + seconds / 3.0
  trace_until = None
  i = 0
  while time.perf_counter() - t0 < seconds:
    if capture is not None and trace_until is None \
        and time.perf_counter() >= trace_from:
      capture.start()
      trace_until = time.perf_counter() + trace_seconds
    try:
      s = seeds(i)
    except QuestionsSpent as e:  # the mix is too short: a failed sweep
      log(f"sweep {i} failed: {e}")
      now = time.perf_counter() - t0
      sweeps.append({"seed": None, "start": now, "end": now, "ok": False,
                     "n_rows": 0, "rows_transferred": 0.0})
      break
    c0 = clock.mark()
    e0 = clock.event_seconds()
    clock.take_longest()
    g0 = pause[0]
    rec = {"seed": s, "start": time.perf_counter() - t0,
           "ok": False, "n_rows": 0, "rows_transferred": 0.0}
    try:
      with TraceAnnotation("sweep"):
        out = driver.sweep(s)
      rec.update(
          ok=not (out.meta.get("n_retries", 0) or
                  out.meta.get("n_demotions", 0)),
          n_rows=int(out.n_rows),
          rows_transferred=float(out.meta.get("rows_transferred", 0.0)),
          outcome=out)
    except Exception:  # a sweep that raised counts as failed; go on
      log(f"sweep {i} (seed {s}) failed:\n{traceback.format_exc()}")
    rec["end"] = time.perf_counter() - t0
    c1 = clock.mark()
    events = {k: round(v - e0.get(k, 0.0), 3)
              for k, v in clock.event_seconds().items()
              if v - e0.get(k, 0.0) > 0.25}
    log(f"sweep {i}: {rec['end'] - rec['start']:.3f} s, {c1[1] - c0[1]} "
        f"compiles summing {c1[0] - c0[0]:.3f} s (longest "
        f"{clock.take_longest():.3f} s), garbage collection "
        f"{pause[0] - g0:.3f} s; JAX events over 0.25 s: {events}")
    sweeps.append(rec)
    i += 1
    if capture is not None and capture.active:
      traced_points += rec["n_rows"] if rec["ok"] else 0
      if time.perf_counter() >= trace_until:
        capture.stop()
  gc.callbacks.remove(on_gc)
  if capture is not None and capture.active:
    capture.stop()
  return {"t0": t0, "sweeps": sweeps, "traced_points": traced_points}


def check(cell: Cell, sweeps: List[dict], seed: int) -> Dict[str, object]:
  """Compare a sample of the window's completed sweeps, drawn from the
  seed, with the plain reference."""
  done = [s for s in sweeps if s["ok"]]
  n = min(int(cell.traffic["reference_sweeps"]), len(done))
  rng = np.random.RandomState(sweep_seed(seed, -2))
  picked = sorted(rng.choice(len(done), size=n, replace=False)) if n else []
  per_sweep = []
  t0 = time.perf_counter()
  for k in picked:
    s = done[k]
    ref = cell.driver.reference(cell.config, cell.workload, cell.traffic,
                                s["seed"])
    per_sweep.append(compare.readings(s["outcome"].answers, ref,
                                      cell.traffic["reducers"]))
  log(f"reference: {n} sweeps compared in {time.perf_counter() - t0:.3f} s")
  return {"n": n, "numbers": compare.combine(per_sweep)}


def read_metrics(specs: List[dict], ctx: dict,
                 bench_dir: str = BENCH_DIR) -> Dict[str, dict]:
  out = {}
  for m in specs:
    value = load_module(os.path.join(bench_dir, "metrics",
                                     m["name"] + ".py")).read(ctx)
    if value is not None:
      out[m["name"]] = {"value": float(value), "unit": m["unit"]}
  return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, require_chip: bool = True) -> dict:
  """One run of ``cell``: the result line's object."""
  devs, clock, driver, cache_dir = prepare(cell, require_chip)
  log(f"cell {cell.name} seed {seed} on {chip.describe(devs)}, "
      f"compile cache {cache_dir}")
  warm(driver, clock, seed)
  capture = None
  if traced:
    trace.instrument()
    capture = trace.Capture()
  c0 = clock.mark()
  window = run_window(driver, clock, seconds, schedule(seed, cell.traffic),
                      capture,
                      float(cell.traffic.get("trace_seconds", 0.0)))
  c1 = clock.mark()
  device = {**chip.describe(devs),
            "memory_peak_bytes": chip.memory_peak_bytes(devs)}
  trace_result = capture.read() if capture is not None else None
  del driver
  gc.collect()
  sweeps = window["sweeps"]
  failed = sum(1 for s in sweeps if not s["ok"])
  log(f"window: {len(sweeps)} sweeps, {failed} failed, last ended "
      f"{max(s['end'] for s in sweeps):.3f} s after its start; "
      f"{c1[1] - c0[1]} compiles summing {c1[0] - c0[0]:.3f} s")
  ctx = {"setup_s": window["t0"] - t_start, "sweeps": sweeps,
         "compile_s": c1[0] - c0[0], "trace": trace_result,
         "traced_points": window["traced_points"]}
  result = check(cell, sweeps, seed)
  checks = compare.verdict(result["numbers"], cell.limits)
  correct = failed == 0 and result["n"] > 0 and compare.within(checks)
  metrics = read_metrics(cell.per_layer if traced else cell.end_to_end, ctx)
  out = {"correct": correct, "attempted": len(sweeps), "failed": failed,
         "metrics": metrics, "device": device}
  if trace_result is not None:
    device["busy_s"] = float(np.mean(trace_result["busy_s"]))
    device["window_s"] = trace_result["window_s"]
    out["breakdown"] = {"device_ops": trace_result["device_ops"],
                        "idle_gaps": trace_result["idle_gaps"]}
  out["checks"] = {"sweeps_compared": {"value": result["n"],
                                       "limit": "at least 1"},
                   **checks}
  for name, c in out["checks"].items():
    log(f"check {name}: {c['value']} (limit {c['limit']})")
  return out
