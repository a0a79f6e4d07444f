"""What every sweep driver shares: the reducers a traffic mix names, the
answers a sweep folded, and compiling a cell's program shapes ahead.

A driver (``bench/drivers/<kind>.py``) defines ``Driver(config, workload,
traffic, precision)`` with ``warm_jobs(seed)`` and
``sweep(seed) -> Outcome``, and
``reference(config, workload, traffic, seed)``: the plain reference's
answers for one sweep.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List

import numpy as np

ANSWER_COLUMNS = ("latency_s", "power_mw", "area_mm2")


@dataclasses.dataclass
class Outcome:
  """One sweep as the client received it: rows folded, the engine's
  counters, and each reducer's answer (global row ids and values)."""
  n_rows: int
  meta: Dict[str, object]
  answers: Dict[str, Dict[str, np.ndarray]]


def make_reducers(specs: Dict[str, dict]) -> Dict[str, object]:
  from repro.explore import ParetoAccumulator, TopKAccumulator
  out = {}
  for name, spec in specs.items():
    if spec["kind"] == "pareto":
      out[name] = ParetoAccumulator(tuple(spec["cols"]))
    elif spec["kind"] == "topk":
      out[name] = TopKAccumulator(int(spec["k"]), by=spec["by"])
    else:
      raise ValueError(f"unknown reducer kind {spec['kind']!r}")
  return out


def answers(reducers: Dict[str, object]) -> Dict[str, Dict[str, np.ndarray]]:
  """Each reducer's answer: a front's rows in ascending row id, a top-k
  list best first, with the ids the accumulator holds for them."""
  out = {}
  for name, r in reducers.items():
    frame = r.result()
    out[name] = {"ids": np.asarray(r.indices, np.int64),
                 **{c: np.asarray(getattr(frame, c), np.float64)
                    for c in ANSWER_COLUMNS}}
  return out


def first_of_each_shape(shapes: List[tuple]) -> List[int]:
  seen, picks = set(), []
  for i, s in enumerate(shapes):
    if s not in seen:
      seen.add(s)
      picks.append(i)
  return picks


def pinned_jobs(tasks, pool) -> List[Callable[[], object]]:
  """One job per task (per task and device in a pool) that dispatches
  the chunk and waits for its result."""
  if pool is None:
    return [lambda t=t: t().resolve() for t in tasks]
  from repro.explore.fleet import pin

  def on_device(device, task):
    with pin(device):
      return task().resolve()
  return [lambda d=d, t=t: on_device(d, t)
          for d in pool.devices() for t in tasks]


def compile_ahead(jobs: List[Callable[[], object]]) -> None:
  """Run every job at once, so XLA compiles the shapes concurrently, then
  each job again alone: concurrent first calls of one jitted function
  with two shapes can leave one of them out of its in-process cache, and
  the window would then fetch it again (from the persistent cache, about
  a second for the joint cell's second block shape)."""
  with ThreadPoolExecutor(max(len(jobs), 1)) as ex:
    for fut in [ex.submit(job) for job in jobs]:
      fut.result()
  for job in jobs:
    job()
