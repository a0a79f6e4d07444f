"""Streaming sweep engine: constant-memory, parallel exploration with
online Pareto / top-k / stats reduction.

QUIDAM's pre-characterized models make evaluating a design point cheap
(Sec. 4.1), so the binding constraint on sweep size becomes *memory*: the
one-shot paths materialize the full ConfigTable/JointTable plus a full
ResultFrame of every evaluated point, even though the paper only ever
consumes fronts, top-k lists, and distribution stats.  This module fuses
sampling -> evaluation -> reduction into a bounded-memory pipeline:

  chunks      lazy sampling (``DesignSpace.iter_tables``) or lazy
              JointTable block slices (``JointTable.block_slices``) —
              the full sweep never exists as one array
  evaluation  each chunk goes through the backend's ``evaluate_table`` /
              ``co_evaluate_table`` exactly as the one-shot path would,
              optionally on a thread pool (the numpy formulas release
              the GIL; the jax ``jit=True`` path keeps one submitting
              thread with a dispatch-ahead window on one device, and a
              :class:`~repro.explore.fleet.DevicePool` spreads chunks
              over several)
  reduction   online accumulators fold ``(chunk frame, global row ids)``
              blocks and keep only the survivors
  progress    :class:`SweepProgress` owns a sweep's resume, folds,
              checkpoints and run stats for every engine that drains
              chunks (:func:`run_stream`, :func:`repro.explore.fleet
              .run_fleet`, the exploration service)

Every accumulator is **chunk-order invariant** and emits survivors in
global row order, so streaming results are bit-identical (numpy path) to
the one-shot frame's ``pareto``/``top_k`` on the same sweep — for any
chunk size, any partition, any fold order (enforced by
``tests/test_streaming.py`` property tests).

  ParetoAccumulator     block-decomposed front merge: per-chunk
                        ``pareto_mask``, then front-vs-front elimination
                        (every dominated point is dominated by a front
                        point, so merging fronts is exact)
  TopKAccumulator       argpartition-based k-best under one column, ties
                        broken by global row id (== the one-shot stable
                        sort)
  StatsAccumulator      streaming count/mean/std/min/max (Chan's
                        parallel-Welford merge)
  HistogramAccumulator  fixed-range bin counts + approximate quantiles
  CollectAccumulator    keeps everything (the ``vectorized="auto"``
                        above-threshold path: parallel chunk evaluation,
                        full frame out)

Entry points: ``ExplorationSession.explore(..., stream=True,
reducers=...)`` / ``co_explore(..., stream=True)``, or the
``stream_explore`` / ``stream_co_explore`` drivers below.
"""
from __future__ import annotations

import copy
import dataclasses
import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor, wait
from typing import (Callable, Dict, Iterable, Iterator, NoReturn, Optional,
                    Sequence, Tuple)

import numpy as np

from repro.explore import spans
from repro.explore.frame import (_MAXIMIZE_COLUMNS, ResultFrame, pareto_mask,
                                 stable_topk_indices)
from repro.explore.resilience import (ChunkError, ChunkTask, ResiliencePolicy,
                                      Rung, SweepJournal, SweepKilled,
                                      arch_accs_fingerprint,
                                      reducers_fingerprint, space_fingerprint,
                                      sweep_key)
from repro.explore.space import DesignSpace

# explore/co_explore(vectorized="auto") switch to the parallel streaming
# engine (CollectAccumulator: identical full frame out) at this many rows
STREAM_AUTO_MIN_ROWS = 1_000_000

# a chunk producer — the engine's unit of work.  Tasks return either the
# evaluated (frame, global row ids) pair directly, or an asynchronous
# handle with .resolve() (the device path's PendingFrame / PendingFused)
Task = Callable[[], object]


# how many device chunks a single submitting thread keeps in flight: the
# engine materializes + dispatches chunk n+ahead while the device still
# runs chunk n (jax async dispatch), so host sampling/hashing overlaps
# device execution — the double-buffering that replaced the old
# "jit backends get one fully-serial worker" special case
DISPATCH_AHEAD = 2


def default_workers(backend=None) -> int:
  """Thread-pool width: one per core up to 8 for the numpy formulas
  (they release the GIL); 1 for a ``jit=True`` backend — its chunks are
  dispatched asynchronously with a ``DISPATCH_AHEAD`` in-flight window,
  so the single submitting thread still overlaps host and device work
  (several devices are used through a ``DevicePool``)."""
  if backend is not None and getattr(backend, "jit", False):
    return 1
  return max(1, min(8, os.cpu_count() or 1))


def _empty_frame() -> ResultFrame:
  z = np.zeros(0)
  return ResultFrame(z, z, z, np.zeros(0, dtype="<U1"))


# ---------------------------------------------------------------------------
# reducers
# ---------------------------------------------------------------------------

class Reducer:
  """Online reduction over evaluated chunks.

  ``fold(frame, indices)`` consumes one chunk (``indices`` are the
  chunk's global row ids in the equivalent one-shot frame);
  ``result()`` emits the reduction.  Implementations must be
  chunk-order invariant: folding any partition of the sweep in any
  order yields the same result.

  Device-fusable reducers additionally implement ``device_spec()``
  (what the fused device program must compute per chunk, see
  :mod:`repro.explore.device`) and ``fold_payload(payload)`` (consume
  that program's per-chunk output).  The host accumulator state stays
  the cross-chunk merge either way — a fused chunk folds exactly like a
  host chunk whose rows were pre-thinned to an exact superset of the
  survivors, which is why the bit-identity guarantees carry over.
  """

  def fold(self, frame: ResultFrame, indices: np.ndarray) -> None:
    raise NotImplementedError

  def result(self):
    raise NotImplementedError

  def device_spec(self):
    """The fused-device request, or None when this reducer needs full
    chunks (the engine then falls back to plain per-chunk evaluation)."""
    return None

  def fold_payload(self, payload) -> None:
    """Consume one fused-chunk payload.  The default handles the
    ``("rows", frame, indices)`` form every row-keeping reducer uses."""
    kind, frame, indices = payload
    if kind != "rows":
      raise ValueError(f"{type(self).__name__} cannot fold {kind!r}")
    self.fold(frame, indices)

  def snapshot(self) -> Dict[str, object]:
    """Journal-serializable copy of the accumulator state (see
    :class:`repro.explore.resilience.SweepJournal`).  The default deep
    copies ``__dict__`` wholesale — accumulator state is numpy arrays,
    scalars, frames and lists, all picklable and all isolated from
    later in-place folds by the copy.  Override for reducers holding
    live handles."""
    return {"cls": type(self).__name__,
            "state": copy.deepcopy(self.__dict__)}

  def restore(self, snap: Dict[str, object]) -> None:
    """Adopt a :meth:`snapshot`; folding the not-yet-journaled chunks on
    top is bit-identical to an uninterrupted run (chunk-order
    invariance quantifies over *every* partition, including the
    before/after-restore one)."""
    if snap.get("cls") != type(self).__name__:
      raise ValueError(f"snapshot of {snap.get('cls')!r} cannot restore "
                       f"a {type(self).__name__}")
    self.__dict__.update(copy.deepcopy(snap["state"]))

  def fingerprint(self) -> str:
    """Content key for the journal's reducer-plan component: two
    reducers with equal fingerprints accept each other's snapshots."""
    return type(self).__name__

  def remap_indices(self, ranker: Callable[[ResultFrame], np.ndarray]) -> None:
    """Rewrite the retained survivors' global row ids via ``ranker``
    (a frame -> int64 ids function).  Delta-sweeps (see
    :mod:`repro.explore.store`) restore a cached accumulator whose ids
    were assigned under the *base* space's enumeration and re-address
    them in the edited space before folding the new subgrid; as long as
    the remap is strictly monotone over the old points, every selection
    and tie-break is unchanged.  Default: no retained ids, nothing to
    do (stats/histogram state is id-free)."""


class ParetoAccumulator(Reducer):
  """Online non-dominated front over the given columns.

  Per chunk: local ``pareto_mask``, then a front-vs-front merge with the
  running front (exact — any point dominated by a non-front point is
  also dominated by a front point, so eliminating within the union of
  fronts loses nothing).  ``result()`` is a survivors-only ResultFrame
  in global row order: bit-identical rows to
  ``frame.select(frame.pareto(cols))`` on the one-shot path.
  """

  def __init__(self, cols: Sequence[str] = ("perf_per_area", "energy_mj"),
               maximize: Optional[Sequence[str]] = None):
    self.cols = tuple(cols)
    self._mx = _MAXIMIZE_COLUMNS if maximize is None else frozenset(maximize)
    self._obj: Optional[np.ndarray] = None
    self._idx = np.zeros(0, np.int64)
    self._frame: Optional[ResultFrame] = None

  def _objectives(self, frame: ResultFrame) -> np.ndarray:
    return np.stack([-frame.column(c) if c in self._mx else frame.column(c)
                     for c in self.cols], axis=1).astype(np.float64)

  def fold(self, frame: ResultFrame, indices: np.ndarray) -> None:
    if not len(frame):
      return
    obj = self._objectives(frame)
    keep = np.flatnonzero(pareto_mask(obj))
    cand_obj = obj[keep]
    cand_idx = np.asarray(indices, np.int64)[keep]
    cand_frame = frame.select(keep)
    if self._frame is not None:
      cand_obj = np.concatenate([self._obj, cand_obj])
      cand_idx = np.concatenate([self._idx, cand_idx])
      cand_frame = ResultFrame.concat([self._frame, cand_frame])
    sel = np.flatnonzero(pareto_mask(cand_obj))
    self._obj = cand_obj[sel]
    self._idx = cand_idx[sel]
    self._frame = cand_frame.select(sel)

  @property
  def indices(self) -> np.ndarray:
    """Global row ids of the current front, ascending."""
    return np.sort(self._idx)

  def device_spec(self):
    from repro.explore.device import ParetoSpec
    return ParetoSpec(self.cols,
                      tuple(c for c in self.cols if c in self._mx))

  def remap_indices(self, ranker) -> None:
    if self._frame is not None and len(self._frame):
      self._idx = np.asarray(ranker(self._frame), np.int64)

  def fingerprint(self) -> str:
    mx = ",".join(sorted(c for c in self.cols if c in self._mx))
    return f"Pareto(cols={','.join(self.cols)};mx={mx})"

  def result(self) -> ResultFrame:
    if self._frame is None:
      return _empty_frame()
    return self._frame.select(np.argsort(self._idx, kind="stable"))


class TopKAccumulator(Reducer):
  """Online k-best rows under one column (argpartition-based, ties broken
  by global row id).  ``result()`` is a best-first ResultFrame,
  bit-identical to the one-shot ``frame.top_k(k, by)``."""

  def __init__(self, k: int, by: str = "perf_per_area",
               maximize: Optional[bool] = None):
    if k <= 0:
      raise ValueError(f"k must be positive, got {k}")
    self.k = int(k)
    self.by = by
    self.maximize = by in _MAXIMIZE_COLUMNS if maximize is None else maximize
    self._key = np.zeros(0, np.float64)
    self._idx = np.zeros(0, np.int64)
    self._frame: Optional[ResultFrame] = None

  def fold(self, frame: ResultFrame, indices: np.ndarray) -> None:
    if not len(frame):
      return
    vals = np.asarray(frame.column(self.by), np.float64)
    key = -vals if self.maximize else vals
    idx = np.asarray(indices, np.int64)
    loc = stable_topk_indices(key, self.k, tie=idx)
    cand_key = np.concatenate([self._key, key[loc]])
    cand_idx = np.concatenate([self._idx, idx[loc]])
    sub = frame.select(loc)
    cand_frame = sub if self._frame is None \
        else ResultFrame.concat([self._frame, sub])
    sel = stable_topk_indices(cand_key, self.k, tie=cand_idx)
    self._key = cand_key[sel]
    self._idx = cand_idx[sel]
    self._frame = cand_frame.select(sel)

  @property
  def indices(self) -> np.ndarray:
    """Global row ids of the current k-best, best-first."""
    return self._idx.copy()

  def device_spec(self):
    from repro.explore.device import TopKSpec
    return TopKSpec(self.by, self.k, self.maximize)

  def remap_indices(self, ranker) -> None:
    if self._frame is not None and len(self._frame):
      self._idx = np.asarray(ranker(self._frame), np.int64)

  def fingerprint(self) -> str:
    return f"TopK(k={self.k};by={self.by};mx={self.maximize})"

  def result(self) -> ResultFrame:
    # state is already (key, global id)-ordered best-first
    return self._frame if self._frame is not None else _empty_frame()


class StatsAccumulator(Reducer):
  """Streaming count/mean/std/min/max of one column (Chan's parallel
  Welford merge — exact min/max/count, float-associativity-level mean and
  std).  Quantiles need the data: see HistogramAccumulator."""

  def __init__(self, col: str):
    self.col = col
    self.n = 0
    self._mean = 0.0
    self._m2 = 0.0
    self._min = np.inf
    self._max = -np.inf

  def fold(self, frame: ResultFrame, indices: np.ndarray) -> None:
    v = np.asarray(frame.column(self.col), np.float64)
    if not v.size:
      return
    mean_b = float(v.mean())
    # a single row has zero spread by definition; computing (v - mean)**2
    # would turn a non-finite value into a NaN M2 partial (inf - inf)
    m2_b = 0.0 if v.size == 1 else float(((v - mean_b) ** 2).sum())
    self._merge(v.size, mean_b, m2_b, float(v.min()), float(v.max()))

  def _merge(self, n_b: int, mean_b: float, m2_b: float, min_b: float,
             max_b: float) -> None:
    """Chan's parallel merge of one (count, mean, M2, min, max) partial —
    shared by host chunks and fused device partials."""
    if not self.n:
      # adopt the first partial directly: bit-identical to the merge
      # formula for finite means (delta*n_b/total collapses to mean_b
      # exactly), and NaN-free when mean_b is +-inf (the general formula
      # multiplies delta**2 by n == 0 -> inf * 0 -> NaN)
      self.n = n_b
      self._mean = mean_b
      self._m2 += m2_b
      self._min = min(self._min, min_b)
      self._max = max(self._max, max_b)
      return
    delta = mean_b - self._mean
    total = self.n + n_b
    self._m2 += m2_b + delta * delta * self.n * n_b / total
    self._mean += delta * n_b / total
    self.n = total
    self._min = min(self._min, min_b)
    self._max = max(self._max, max_b)

  def device_spec(self):
    from repro.explore.device import StatsSpec
    return StatsSpec(self.col)

  def fingerprint(self) -> str:
    return f"Stats(col={self.col})"

  def fold_payload(self, payload) -> None:
    kind, data = payload[0], payload[1]
    if kind != "stats":
      return super().fold_payload(payload)
    if data["n"]:
      self._merge(data["n"], data["mean"], data["m2"], data["min"],
                  data["max"])

  def result(self) -> Dict[str, float]:
    if not self.n:
      return {k: float("nan")
              for k in ("count", "mean", "std", "min", "max")}
    return {"count": float(self.n), "mean": self._mean,
            "std": float(np.sqrt(self._m2 / self.n)),
            "min": self._min, "max": self._max}


class HistogramAccumulator(Reducer):
  """Streaming fixed-range histogram of one column.

  The bin range must be declared up front (streaming cannot rescale);
  values outside ``(lo, hi)`` are clipped into the edge bins.
  ``result()`` returns ``{"counts", "edges"}``; :meth:`quantile` linearly
  interpolates within bins (approximate — error bounded by bin width).
  """

  def __init__(self, col: str, lo: float, hi: float, bins: int = 64):
    if not hi > lo:
      raise ValueError(f"need hi > lo, got ({lo}, {hi})")
    if bins <= 0:
      raise ValueError(f"bins must be positive, got {bins}")
    self.col = col
    self.edges = np.linspace(float(lo), float(hi), int(bins) + 1)
    self.counts = np.zeros(int(bins), np.int64)

  def fold(self, frame: ResultFrame, indices: np.ndarray) -> None:
    v = np.asarray(frame.column(self.col), np.float64)
    if not v.size:
      return
    v = np.clip(v, self.edges[0], self.edges[-1])
    self.counts += np.histogram(v, bins=self.edges)[0]

  def device_spec(self):
    from repro.explore.device import HistSpec
    return HistSpec(self.col, float(self.edges[0]), float(self.edges[-1]),
                    len(self.counts))

  def fingerprint(self) -> str:
    return (f"Hist(col={self.col};lo={self.edges[0]!r};"
            f"hi={self.edges[-1]!r};bins={len(self.counts)})")

  def fold_payload(self, payload) -> None:
    kind, data = payload[0], payload[1]
    if kind != "hist":
      return super().fold_payload(payload)
    self.counts += np.asarray(data, np.int64)

  def quantile(self, q: float) -> float:
    """Approximate q-quantile from the bin counts (linear within bins)."""
    total = int(self.counts.sum())
    if not total:
      return float("nan")
    target = np.clip(q, 0.0, 1.0) * total
    cum = np.cumsum(self.counts)
    b = int(np.searchsorted(cum, target, side="left"))
    b = min(b, len(self.counts) - 1)
    below = cum[b] - self.counts[b]
    frac = (target - below) / max(self.counts[b], 1)
    return float(self.edges[b]
                 + np.clip(frac, 0.0, 1.0) * (self.edges[b + 1]
                                              - self.edges[b]))

  def result(self) -> Dict[str, np.ndarray]:
    return {"counts": self.counts.copy(), "edges": self.edges.copy()}


class CollectAccumulator(Reducer):
  """Keeps every chunk and reassembles the full frame in global row
  order — NOT constant-memory.  This is how ``vectorized="auto"`` runs
  big sweeps through the parallel engine while preserving the one-shot
  return type bit-exactly."""

  def __init__(self):
    self._frames = []
    self._idx = []

  def fold(self, frame: ResultFrame, indices: np.ndarray) -> None:
    if not len(frame):
      return
    self._frames.append(frame)
    self._idx.append(np.asarray(indices, np.int64))

  def remap_indices(self, ranker) -> None:
    self._idx = [np.asarray(ranker(f), np.int64) for f in self._frames]

  def result(self) -> ResultFrame:
    if not self._frames:
      return _empty_frame()
    big = self._frames[0] if len(self._frames) == 1 \
        else ResultFrame.concat(self._frames)
    idx = np.concatenate(self._idx)
    return big.select(np.argsort(idx, kind="stable"))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamResult:
  """Outcome of a streaming sweep: one entry per reducer (by name) plus
  run stats.  ``res["pareto"]`` etc. index into ``results``."""
  results: Dict[str, object]
  n_rows: int
  seconds: float
  meta: Dict[str, float]

  def __getitem__(self, name: str):
    return self.results[name]


def new_counters() -> Dict[str, int]:
  """A fresh run-stats dict in the shape the journal checkpoints."""
  return {"n_rows": 0, "n_chunks": 0, "n_transferred": 0,
          "n_prefetched": 0, "n_overflows": 0, "n_retries": 0,
          "n_demotions": 0}


def fold_chunk(reducers: Dict[str, Reducer], counters: Dict[str, int],
               result) -> None:
  """Resolve (if pending) and fold one completed chunk into every
  reducer, updating ``counters``.  Shared by :func:`run_stream` and the
  exploration service's session scheduler so both fold identically."""
  with spans.span("fold_chunk"):
    if hasattr(result, "resolve"):
      result = result.resolve()
    counters["n_chunks"] += 1
    payloads = getattr(result, "payloads", None)
    if payloads is not None:  # a device FusedChunk (duck-typed: keeps
      counters["n_rows"] += result.n_rows  # numpy path device-import-free
      counters["n_transferred"] += result.n_transferred
      counters["n_prefetched"] += result.n_prefetched
      counters["n_overflows"] += getattr(result, "n_overflows", 0)
      for name, payload in payloads.items():
        reducers[name].fold_payload(payload)
      return
    frame, indices = result
    counters["n_rows"] += len(frame)
    counters["n_transferred"] += len(frame)
    for r in reducers.values():
      r.fold(frame, indices)


class SweepProgress:
  """One sweep's progress: its reducers and counters, the chunks already
  folded, and the journal they checkpoint to.  The one owner of resume,
  fold, checkpoint and run stats for every engine that drains chunks:
  :func:`run_stream`, :func:`repro.explore.fleet.run_fleet` and the
  exploration service.

  ``journal`` is a :class:`SweepJournal` (or its directory) or None.  On
  construction, its best record under ``key`` (``load_state``) restores
  the reducers and counters, and the chunks it holds count as resumed.
  A checkpoint goes through ``write`` — ``journal.record``, the atomic
  snapshot, unless the caller passes another (the service passes
  ``journal.append``).  ``policy`` supplies the retry and demotion
  counts this run adds to the journaled ones, and the watchdog and
  breaker meta.
  """

  def __init__(self, reducers: Dict[str, Reducer],
               policy: Optional[ResiliencePolicy] = None, journal=None,
               key: str = "", checkpoint_every: int = 1,
               write: Optional[Callable[[str, Dict[str, object]], None]]
               = None):
    self.reducers = reducers
    self.policy = policy
    self.counters = new_counters()
    self.done: set = set()
    self.key = key
    self.checkpoint_every = max(int(checkpoint_every), 1)
    self._since_ckpt = 0
    self._write = None
    if journal is not None:
      if not isinstance(journal, SweepJournal):
        journal = SweepJournal(journal)
      self._write = write if write is not None else journal.record
      state = journal.load_state(key)
      if state is not None:
        self.done = set(state["done"])
        for name, r in reducers.items():
          r.restore(state["reducers"][name])
        self.counters.update(state["counters"])
    self.n_resumed = len(self.done)
    self._base = (self.counters["n_retries"], self.counters["n_demotions"])

  def todo(self, tasks: Iterable[Task]) -> Iterator[Tuple[int, Task]]:
    """(global chunk index, task) pairs, skipping already-folded chunks
    before they are materialized or dispatched."""
    for i, t in enumerate(tasks):
      index = getattr(t, "index", i)
      if index not in self.done:
        yield index, t

  def fold(self, index: int, result) -> None:
    """Fold one completed chunk (see :func:`fold_chunk`), mark it done
    and checkpoint at the cadence.  A kill flushes the journal and
    propagates as it is; any other failure goes through :meth:`fail`."""
    try:
      with spans.at(index):
        fold_chunk(self.reducers, self.counters, result)
    except SweepKilled:
      self.checkpoint(force=True)
      raise
    except Exception as e:
      self.fail(index, e)
    self.done.add(index)
    self.checkpoint()

  def fail(self, index: int, exc: Exception) -> NoReturn:
    """Flush the journal, then surface the failing chunk's global index
    (a bare re-raise would lose it): a :class:`ChunkError` as it is,
    anything else wrapped in one."""
    self.checkpoint(force=True)
    if isinstance(exc, ChunkError):
      raise exc
    raise ChunkError(index, f"{type(exc).__name__}: {exc}") from exc

  def checkpoint(self, force: bool = False) -> None:
    """Journal the reducers, counters and done set every
    ``checkpoint_every`` calls, or now with ``force``."""
    if self._write is None:
      return
    self._since_ckpt += 1
    if not force and self._since_ckpt < self.checkpoint_every:
      return
    self.counters["n_retries"], self.counters["n_demotions"] = self.totals()
    self._write(self.key, {
        "done": set(self.done),
        "reducers": {n: r.snapshot() for n, r in self.reducers.items()},
        "counters": dict(self.counters)})
    self._since_ckpt = 0

  def totals(self) -> Tuple[int, int]:
    """Retries and demotions: the journaled ones plus this run's."""
    r, d = self._base
    if self.policy is not None:
      r, d = r + self.policy.n_retries, d + self.policy.n_demotions
    return r, d

  def result(self, seconds: float, workers: float, **extra) -> StreamResult:
    """The sweep's :class:`StreamResult`: every reducer's result and the
    run stats, then ``extra`` (the engine's own meta keys)."""
    c = self.counters
    n_retries, n_demotions = self.totals()
    meta = {"seconds": seconds, "workers": float(workers),
            "n_chunks": float(c["n_chunks"]),
            "rows_transferred": float(c["n_transferred"]),
            "rows_prefetched": float(c["n_prefetched"]),
            "n_retries": float(n_retries),
            "n_demotions": float(n_demotions),
            "n_resumed_chunks": float(self.n_resumed),
            "n_overflows": float(c["n_overflows"])}
    if self.policy is not None:
      meta["n_leaked_watchdogs"] = float(self.policy.watchdogs.n_live())
      if self.policy.breaker is not None:
        meta.update(self.policy.breaker.meta())
    meta.update(extra)
    return StreamResult(
        results={name: r.result() for name, r in self.reducers.items()},
        n_rows=c["n_rows"], seconds=seconds, meta=meta)


# ROB002: every wait in explore/ must carry a bounded timeout (the
# watchdog idiom) — the pool waits below re-arm in a loop, so a slow
# chunk never wedges the submitting thread invisibly
POOL_WAIT_SECONDS = 60.0


def run_stream(tasks: Iterable[Task], reducers: Dict[str, Reducer],
               workers: int = 1, dispatch_ahead: int = DISPATCH_AHEAD,
               policy: Optional[ResiliencePolicy] = None,
               resume_from=None, journal_key: str = "",
               checkpoint_every: int = 1, pool=None) -> StreamResult:
  """Drain ``tasks`` (each producing one evaluated chunk), folding every
  reducer as chunks complete.

  A task may return the plain ``(frame, indices)`` tuple, or an
  asynchronous handle — anything with a ``resolve()`` method, i.e. the
  device path's :class:`~repro.explore.device.PendingFrame` /
  :class:`~repro.explore.device.PendingFused`.  Handles are kept in a
  bounded ``dispatch_ahead`` window before resolution, so a single
  submitting thread materializes + dispatches upcoming chunks while the
  device still executes earlier ones (jax async dispatch).

  ``workers > 1`` evaluates chunks on a thread pool with a bounded
  in-flight window (2x workers), so peak memory stays O(window x chunk);
  folds happen on the submitting thread only, in submission order
  whatever order the workers finish in, so a sweep folds the same way on
  any core count (Pareto/top-k do not care; StatsAccumulator's merge
  does, in the last bits).

  Failure semantics (see :mod:`repro.explore.resilience` and
  docs/explore.md "Failure semantics & resume"):

  * ``policy`` — a :class:`ResiliencePolicy` executing each
    :class:`ChunkTask` through retry + the degradation ladder; its
    retry/demotion totals land in ``meta``.
  * a fatally failing chunk cancels all not-yet-started work and raises
    :class:`ChunkError` carrying the chunk's *global index* (the
    previous behavior lost both the index and the in-flight window).
  * ``resume_from`` — a :class:`SweepJournal` (or its directory path).
    Reducer snapshots plus the set of folded chunk indices are recorded
    under ``journal_key`` every ``checkpoint_every`` folds *and* on the
    way out of a fatal error; on entry, a matching record restores the
    reducers and already-folded chunks are skipped before dispatch.
    Chunk-order invariance makes the resumed final reductions
    bit-identical to an uninterrupted run.
  * ``pool`` — a :class:`repro.explore.fleet.DevicePool`; the sweep is
    handed to :func:`repro.explore.fleet.run_fleet`, which shards chunks
    across the pool's devices with health tracking, straggler
    speculation, elastic resharding and the silent-corruption sentinel.
    Chunk-partition bit-identity keeps the fronts identical to this
    single-device path.

  ``meta`` also carries the sweep's span counters (see
  :mod:`repro.explore.spans`).
  """
  if pool is not None:
    from repro.explore.fleet import run_fleet
    return run_fleet(tasks, reducers, pool, policy=policy,
                     dispatch_ahead=dispatch_ahead, resume_from=resume_from,
                     journal_key=journal_key,
                     checkpoint_every=checkpoint_every)
  with spans.recording() as recorder:
    with spans.span("stream"):
      out = _drain(tasks, reducers, recorder, max(1, int(workers)),
                   dispatch_ahead, policy, resume_from, journal_key,
                   checkpoint_every)
    out.meta.update(recorder.meta())
  return out


def _drain(tasks, reducers, recorder, workers, dispatch_ahead, policy,
           resume_from, journal_key, checkpoint_every) -> StreamResult:
  """The body of :func:`run_stream` on one device, inside its span."""
  t0 = time.perf_counter()
  progress = SweepProgress(reducers, policy, resume_from, journal_key,
                           checkpoint_every)

  def execute_at(index, task):
    with spans.at(index):
      return policy.execute(task) if policy is not None else task()

  if workers == 1:
    window: "deque" = deque()
    for index, task in progress.todo(tasks):
      try:
        res = execute_at(index, task)
      except Exception as e:
        progress.fail(index, e)
      if hasattr(res, "resolve"):
        window.append((index, res))
        if len(window) > max(int(dispatch_ahead), 0):
          progress.fold(*window.popleft())
      else:
        progress.fold(index, res)
    while window:
      progress.fold(*window.popleft())
  else:
    with ThreadPoolExecutor(max_workers=workers) as pool:
      pending: "deque" = deque()  # (future, global chunk index), FIFO
      on_worker = spans.bind(recorder, execute_at)

      def drain_oldest() -> None:
        fut, index = pending[0]
        wait((fut,), timeout=POOL_WAIT_SECONDS)
        if not fut.done():
          return
        pending.popleft()
        try:
          res = fut.result()
        except Exception as e:
          progress.fail(index, e)
        progress.fold(index, res)

      try:
        for index, task in progress.todo(tasks):
          pending.append((pool.submit(on_worker, index, task), index))
          while len(pending) >= 2 * workers:
            drain_oldest()
        while pending:
          drain_oldest()
      except Exception:
        # fatal: drop queued chunks so the pool shuts down promptly
        # instead of grinding through the whole in-flight window
        for fut, _ in pending:
          fut.cancel()
        raise
  progress.checkpoint(force=True)
  return progress.result(time.perf_counter() - t0, workers)


# ---------------------------------------------------------------------------
# drivers: plain DSE + joint co-exploration
# ---------------------------------------------------------------------------

def default_explore_reducers() -> Dict[str, Reducer]:
  """The paper's default plain-sweep reduction plan."""
  return {"pareto": ParetoAccumulator()}


def default_co_reducers() -> Dict[str, Reducer]:
  """The paper's default 3-objective joint-front reduction plan."""
  return {"pareto": ParetoAccumulator(("top1_err", "energy_mj",
                                       "area_mm2"))}


def explore_sweep_key(space: DesignSpace, reducers: Dict[str, Reducer], *,
                      n_per_type: int, seed: int, method: str,
                      chunk_size: int, network: str) -> str:
  """The content-addressed journal key of a plain streamed sweep."""
  return sweep_key("explore", space_fingerprint(space),
                   reducers_fingerprint(reducers),
                   {"n_per_type": n_per_type, "seed": seed,
                    "method": method, "chunk_size": chunk_size,
                    "network": network})


def co_explore_sweep_key(space: DesignSpace, reducers: Dict[str, Reducer],
                         arch_accs, *, n_hw_per_type: int, seed: int,
                         image_size: int, method: str,
                         chunk_size: int) -> str:
  """The content-addressed journal key of a streamed co-exploration."""
  archs = tuple(arch for arch, _ in arch_accs)
  accs = np.asarray([float(acc) for _, acc in arch_accs], np.float64)
  return sweep_key("co-explore", space_fingerprint(space),
                   reducers_fingerprint(reducers),
                   {"n_hw_per_type": n_hw_per_type, "seed": seed,
                    "image_size": image_size, "method": method,
                    "chunk_size": chunk_size,
                    "archs": arch_accs_fingerprint(archs, accs)})


def explore_tasks(backend, space: DesignSpace, layers, network: str,
                  n_per_type: int, seed: int, method: str, chunk_size: int,
                  reducers: Dict[str, Reducer],
                  row_ids: Optional[Callable[[object, int], np.ndarray]]
                  = None) -> Iterator[ChunkTask]:
  """The ladder-carrying chunk tasks of a plain streamed sweep.

  Extracted from :func:`stream_explore` so the exploration service (and
  the delta-sweep driver in :mod:`repro.explore.store`) consume the
  exact same task generators and ladders as the standalone driver.
  ``row_ids`` overrides the global row-id assignment — default is the
  one-shot sample order ``arange(offset, offset+len)``; delta-sweeps
  pass the parent space's canonical grid ranks instead.
  """
  if not hasattr(backend, "evaluate_table"):
    raise ValueError(f"backend {backend.name!r} has no evaluate_table; "
                     "streaming requires the columnar path")
  plan = None
  device_mode = getattr(backend, "jit", False) \
      and hasattr(backend, "fused_eval_pending")
  if device_mode:
    from repro.explore.device import build_plan
    with spans.span("plan"):
      plan = build_plan(reducers, joint=False)
  # the terminal numpy rung: bypasses jit even on a device backend
  host_eval = getattr(backend, "host_evaluate_table", None)
  if host_eval is None:
    host_eval = backend.evaluate_table

  def make_task(chunk, idx, ci) -> ChunkTask:
    rungs = []
    if plan is not None:
      rungs.append(Rung(
          "fused-device",
          lambda: backend.fused_eval_pending(chunk, layers, network, plan,
                                             idx),
          layer="device"))
    if device_mode:
      rungs.append(Rung(
          "device",
          lambda: backend.eval_pending(chunk, layers, network, idx),
          layer="device"))
    rungs.append(Rung("numpy",
                      lambda: (host_eval(chunk, layers, network), idx),
                      layer="backend"))
    return ChunkTask(index=ci, rungs=tuple(rungs))

  def gen() -> Iterator[ChunkTask]:
    offset = 0
    for ci, chunk in enumerate(
        space.iter_tables(n_per_type, seed=seed, method=method,
                          chunk_size=chunk_size)):
      if row_ids is None:
        idx = np.arange(offset, offset + len(chunk), dtype=np.int64)
      else:
        idx = np.asarray(row_ids(chunk, offset), np.int64)
      offset += len(chunk)
      yield make_task(chunk, idx, ci)

  return gen()


def co_explore_tasks(backend, space: DesignSpace, arch_accs,
                     n_hw_per_type: int, seed: int, image_size: int,
                     method: str, chunk_size: int,
                     reducers: Dict[str, Reducer]) -> Iterator[ChunkTask]:
  """The ladder-carrying chunk tasks of a streamed co-exploration —
  extracted from :func:`stream_co_explore` for the same service/driver
  sharing as :func:`explore_tasks`."""
  from repro.core.dataflow import LayerStack  # deferred: keep header lean
  from repro.core.supernet import arch_to_layers  # deferred: pulls jax
  if not hasattr(backend, "co_evaluate_table"):
    raise ValueError(f"backend {backend.name!r} has no co_evaluate_table; "
                     "streaming requires the joint columnar path")
  archs = tuple(arch for arch, _ in arch_accs)
  accs = np.asarray([float(acc) for _, acc in arch_accs], np.float64)
  plan = None
  device_mode = getattr(backend, "jit", False) \
      and hasattr(backend, "fused_co_eval_pending")
  dedup = None
  with spans.span("plan"):
    stack = LayerStack.from_layer_lists(
        [arch_to_layers(a, image_size=image_size) for a in archs])
    if device_mode:
      from repro.explore.device import build_plan
      plan = build_plan(reducers, joint=True)
      # one global distinct-layer factorization: every block slices the
      # same unique rows, so one compiled program serves the whole sweep
      unique_cols, slot_ids = stack.dedup_slots()
      dedup = lambda a_sl: (unique_cols, slot_ids[a_sl])  # noqa: E731
  # the terminal numpy rung: bypasses jit even on a device backend
  host_co = getattr(backend, "host_co_evaluate_table", None)
  if host_co is None:
    host_co = backend.co_evaluate_table

  def make_task(hw_sub, sub_stack, a_sl, idx, ci) -> ChunkTask:
    a_lo = a_sl.start
    rungs = []
    if plan is not None:
      rungs.append(Rung(
          "fused-device",
          lambda: backend.fused_co_eval_pending(
              hw_sub, sub_stack, "coexplore", plan, idx, a_lo, accs[a_sl],
              archs, dedup=dedup(a_sl)),
          layer="device"))
    if device_mode:
      rungs.append(Rung(
          "device",
          lambda: backend.co_eval_pending(
              hw_sub, sub_stack, "coexplore", idx, a_lo, accs[a_sl], archs,
              dedup=dedup(a_sl)),
          layer="device"))

    def run():
      f = host_co(hw_sub, sub_stack, network="coexplore")
      f.extra["arch_id"] = f.extra["arch_id"] + a_lo
      f.extra["top1"] = accs[f.extra["arch_id"]]
      f.arch_lookup = archs
      return f, idx
    rungs.append(Rung("numpy", run, layer="backend"))
    return ChunkTask(index=ci, rungs=tuple(rungs))

  def gen() -> Iterator[ChunkTask]:
    offset = 0
    ci = 0
    for ti, pe_type in enumerate(space.pe_types):
      with spans.span("sample"):
        hw = space.sample_type_table(pe_type, n_hw_per_type,
                                     seed=seed + 17 * ti, method=method)
        joint = hw.cross(stack.n_archs)
      for a_sl, h_sl in joint.block_slices(chunk_size):
        with spans.span("sample"):
          idx = offset + joint.block_indices(a_sl, h_sl)
          task = make_task(hw.select(h_sl),
                           stack.slice_archs(a_sl.start, a_sl.stop),
                           a_sl, idx, ci)
        yield task
        ci += 1
      offset += len(joint)

  return gen()


def stream_explore(backend, space: DesignSpace, layers, network: str = "net",
                   n_per_type: int = 200, seed: int = 17,
                   method: str = "random",
                   reducers: Optional[Dict[str, Reducer]] = None,
                   chunk_size: int = 65536,
                   workers: Optional[int] = None,
                   policy: Optional[ResiliencePolicy] = None,
                   resume_from=None,
                   checkpoint_every: int = 1, pool=None) -> StreamResult:
  """Sample -> evaluate -> reduce a plain HW sweep in bounded memory.

  Chunks come from ``space.iter_tables`` (bit-identical concatenation to
  ``sample_table``), evaluate through ``backend.evaluate_table``, and
  fold into ``reducers`` (default: one ParetoAccumulator on the paper's
  (perf_per_area, energy) axes).  Global row ids follow the one-shot
  sample order, so survivors match the one-shot frame row for row.

  On a ``jit=True`` backend chunks dispatch asynchronously; when every
  reducer is device-fusable the evaluate+reduce pipeline additionally
  fuses into one jitted program per chunk (see
  :mod:`repro.explore.device`), so only O(cap) floats come back per
  chunk, cut to the survivors on the host, instead of full metric
  arrays.

  Each chunk carries the full fallback ladder ``fused-device ->
  unfused-device -> numpy`` (whichever rungs the backend supports); a
  ``policy`` walks it on failures, and ``resume_from`` journals /
  restores the sweep under a content-addressed key derived from the
  space, oracle version, reducer plan, and the sampling parameters —
  the backend itself is *not* part of the key (parity makes checkpoints
  portable across the numpy and device paths).
  """
  if reducers is None:
    reducers = default_explore_reducers()
  with spans.recording():
    tasks = explore_tasks(backend, space, layers, network, n_per_type, seed,
                          method, chunk_size, reducers)
    key = ""
    if resume_from is not None:
      key = explore_sweep_key(space, reducers, n_per_type=n_per_type,
                              seed=seed, method=method,
                              chunk_size=chunk_size, network=network)
    out = run_stream(tasks, reducers,
                     workers=default_workers(backend) if workers is None
                     else workers,
                     policy=policy, resume_from=resume_from,
                     journal_key=key, checkpoint_every=checkpoint_every,
                     pool=pool)
  out.meta.update(layer_meta(layers))
  return out


def layer_meta(layers) -> Dict[str, float]:
  """What a plain sweep evaluated per point: ``n_layer_shapes``, the
  layer entries (each a distinct shape once identical layers merge);
  ``n_layer_slots``, the layers they stand for (the sum of counts);
  ``n_cache_shapes``, the entries whose right operand is a cache; and
  ``n_narrow_shapes``, those whose right operand has a stored width of
  its own."""
  from repro.core.dataflow import layer_count
  return {"n_layer_shapes": float(len(layers)),
          "n_layer_slots": float(sum(layer_count(l) for l in layers)),
          "n_cache_shapes": float(sum(
              getattr(l, "operand", "weight") == "cache" for l in layers)),
          "n_narrow_shapes": float(sum(
              getattr(l, "bits", None) is not None for l in layers))}


def stream_co_explore(backend, space: DesignSpace, arch_accs,
                      n_hw_per_type: int = 20, seed: int = 3,
                      image_size: int = 32, method: str = "random",
                      reducers: Optional[Dict[str, Reducer]] = None,
                      chunk_size: int = 65536,
                      workers: Optional[int] = None,
                      policy: Optional[ResiliencePolicy] = None,
                      resume_from=None,
                      checkpoint_every: int = 1, pool=None) -> StreamResult:
  """Joint HW x NN co-exploration in bounded memory: the arch x HW cross
  product is visited as ``JointTable.block_slices`` blocks (HW sampled
  once per PE type — the small input side; the 100M-pair product never
  materializes), each block evaluated via ``backend.co_evaluate_table``
  on an arch-sliced LayerStack.  Chunk frames carry the same ``top1`` /
  ``arch_id`` / ``arch_lookup`` columns as the one-shot joint frame, and
  global row ids replicate its (pe_type, arch, hw) order exactly.
  Default reducers: a ParetoAccumulator on the paper's 3-objective
  (top1_err, energy_mj, area_mm2) joint front.
  """
  if reducers is None:
    reducers = default_co_reducers()
  with spans.recording():
    tasks = co_explore_tasks(backend, space, arch_accs, n_hw_per_type, seed,
                             image_size, method, chunk_size, reducers)
    key = ""
    if resume_from is not None:
      key = co_explore_sweep_key(space, reducers, arch_accs,
                                 n_hw_per_type=n_hw_per_type, seed=seed,
                                 image_size=image_size, method=method,
                                 chunk_size=chunk_size)
    return run_stream(tasks, reducers,
                      workers=default_workers(backend) if workers is None
                      else workers,
                      policy=policy, resume_from=resume_from,
                      journal_key=key, checkpoint_every=checkpoint_every,
                      pool=pool)
