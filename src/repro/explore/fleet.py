"""Elastic device-fleet execution for streaming sweeps.

The streaming engine (repro.explore.streaming) keeps one submitting
thread and a small in-flight window; on a multi-device host that window
all lands on the default device.  This module shards streaming chunks
across *all* visible devices — each chunk is pinned to one device and
runs the same fused evaluate+reduce program there; the host merge is
unchanged.  Chunk-partition bit-identity (every reducer is chunk-order
invariant, every chunk a pure function of ``(space, chunk_index,
seed)``) makes any sharding, resharding, re-execution, or re-ordering
sound: the final fronts are bit-identical to a solo single-device run.

A fleet fails in ways one device never does, so the execution layer is
built around a health registry and three mitigations:

  DevicePool   per-device health: EWMA chunk latencies (via
               :class:`repro.train.fault_tolerance.StragglerMonitor` —
               the trainer's monitor generalized to exploration),
               consecutive-failure counts, and a per-device
               :class:`~repro.explore.resilience.CircuitBreaker` so one
               sick device is quarantined instead of tripping the whole
               rung.  Quarantined (or lost) devices rejoin through the
               breaker's half-open probe.
  stragglers   the slowest in-flight shard is speculatively re-dispatched
               to an idle healthy device; the first bit-identical result
               wins and the loser is discarded (``n_speculative``).
  elasticity   on device loss or quarantine the pool shrinks, orphaned
               chunks re-enter the queue and are resharded onto the
               surviving devices (``n_resharded``).
  SDC sentinel silent data corruption produces no exception — the only
               detector is recomputation.  With ``sdc_check_every > 0``
               device results are buffered per device (deferred fold);
               every check window a seeded sample chunk is re-evaluated
               on the terminal numpy rung and compared value-for-value.
               The parity contract makes device x64 results bit-identical
               to numpy, so ANY mismatch is corruption, not roundoff:
               the device is quarantined and its buffered chunks replay
               on healthy devices (``n_corruption_checks`` /
               ``n_corruptions_detected``).

Device *placement* rides on a thread-local pin: :func:`pin` marks the
submitting thread's target device and the backend's pending entry points
(`repro.explore.backend`) commit each chunk's inputs there with
``jax.device_put`` — jax then executes the jitted program on the
committed device, and its output buffers expose ``is_ready()`` for the
non-blocking readiness polling the straggler logic needs.

:func:`visible_devices` is the ONE sanctioned device enumeration in the
tree — analysis rule ROB003 bans direct ``jax.devices()`` /
``jax.local_devices()`` calls everywhere else so all device access goes
through the health-tracked pool.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.core.seeding import derive_seed
from repro.explore import spans
from repro.explore.resilience import (ChunkTask, CircuitBreaker,
                                      ResiliencePolicy, SweepKilled)
from repro.train.fault_tolerance import StragglerMonitor


# ---------------------------------------------------------------------------
# sanctioned device enumeration (ROB003)
# ---------------------------------------------------------------------------

def visible_devices() -> Tuple[object, ...]:
  """All addressable jax devices.  This is the single sanctioned call
  site of ``jax.devices()`` in the tree (analysis rule ROB003): every
  other module reaches devices through here or a :class:`DevicePool`,
  so health tracking and quarantine cannot be bypassed."""
  import jax
  return tuple(jax.devices())


def device_topology() -> Dict[str, object]:
  """Provenance-stamp description of the fleet (platform, count, kinds).
  An enumeration error propagates: a stamp never names a device that
  was not found."""
  devs = visible_devices()
  kinds = sorted({str(d.device_kind) for d in devs})
  return {"platform": str(devs[0].platform), "n_devices": len(devs),
          "device_kinds": kinds}


# ---------------------------------------------------------------------------
# thread-local device pinning
# ---------------------------------------------------------------------------

_TLS = threading.local()


def pinned_device():
  """The device the current thread's dispatches are pinned to (or None:
  default placement)."""
  return getattr(_TLS, "device", None)


@contextlib.contextmanager
def pin(device):
  """Pin this thread's backend dispatches to ``device``: the pending
  entry points commit chunk inputs there (``jax.device_put``), so the
  jitted program executes on that device.  Pins nest; the previous pin
  is restored on exit."""
  prev = getattr(_TLS, "device", None)
  _TLS.device = device
  try:
    yield device
  finally:
    _TLS.device = prev


# ---------------------------------------------------------------------------
# the health registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceHealth:
  """Mutable per-device record inside a :class:`DevicePool`."""
  device: object
  breaker: CircuitBreaker
  n_chunks: int = 0            # completed chunks
  n_failures: int = 0          # consecutive failures (resets on success)
  n_dispatched: int = 0
  outstanding: int = 0         # checked-out, not yet checked-in
  n_losses: int = 0            # injected/observed device-lost events

  @property
  def ewma_key(self) -> str:
    return str(id(self))


class DevicePool:
  """Health registry + admission control for a device fleet.

  One pool is shared by every consumer multiplexed over the fleet
  (:func:`run_fleet` sweeps, exploration-service sessions), so the
  quarantine decision reflects the *device*, not any single session's
  luck — the per-device generalization of PR 9's shared
  :class:`~repro.explore.resilience.CircuitBreaker`.

  ``checkout()`` admits a dispatch on the healthiest available device
  (fewest outstanding shards, breaker willing); ``checkin()`` releases
  it; ``record_latency`` / ``record_success`` / ``record_failure`` feed
  the health state.  ``quarantine()`` force-opens a device's breaker
  (device loss, SDC divergence) — the device rejoins later through the
  breaker's ordinary half-open probe, so recovery needs no extra
  machinery.  Thread-safe.

  ``sdc_check_every`` arms the silent-corruption sentinel in
  :func:`run_fleet`: N > 0 defers folds and re-checks one seeded chunk
  per N buffered results per device; 0 disables buffering entirely (the
  zero-overhead healthy path).
  """

  def __init__(self, devices: Optional[Iterable[object]] = None, *,
               ewma_alpha: float = 0.25, speculation_factor: float = 4.0,
               sdc_check_every: int = 0, seed: int = 0,
               breaker_threshold: int = 3, breaker_cooldown: int = 8,
               breaker_jitter: int = 2):
    devs = tuple(visible_devices() if devices is None else devices)
    if not devs:
      raise ValueError("DevicePool needs at least one device")
    if speculation_factor <= 1.0:
      raise ValueError(
          f"speculation_factor must exceed 1.0, got {speculation_factor}")
    if sdc_check_every < 0:
      raise ValueError(
          f"sdc_check_every must be >= 0, got {sdc_check_every}")
    self.seed = int(seed)
    self.speculation_factor = float(speculation_factor)
    self.sdc_check_every = int(sdc_check_every)
    self._monitor = StragglerMonitor(alpha=float(ewma_alpha))
    self._health: List[DeviceHealth] = [
        DeviceHealth(d, CircuitBreaker(
            threshold=breaker_threshold, cooldown=breaker_cooldown,
            jitter=breaker_jitter,
            seed=derive_seed("fleet-device", seed, i)))
        for i, d in enumerate(devs)]
    self._lock = threading.Lock()
    # fleet-wide mitigation counters (shared by every consumer)
    self.n_speculative = 0
    self.n_resharded = 0
    self.n_corruption_checks = 0
    self.n_corruptions_detected = 0

  # -- topology -------------------------------------------------------------

  @property
  def n_devices(self) -> int:
    return len(self._health)

  def device(self, i: int):
    return self._health[i].device

  def devices(self) -> Tuple[object, ...]:
    return tuple(h.device for h in self._health)

  # -- admission ------------------------------------------------------------

  def checkout(self, require_idle: bool = False,
               exclude: Tuple[int, ...] = ()) -> Optional[int]:
    """Admit one dispatch: returns the index of the healthiest available
    device (fewest outstanding shards; its breaker consulted exactly
    once), or None when every device refuses — callers then fall back to
    the terminal host rung.  ``require_idle`` restricts to devices with
    nothing in flight (speculation targets)."""
    with self._lock:
      order = sorted(range(len(self._health)),
                     key=lambda i: (self._health[i].outstanding, i))
      for i in order:
        h = self._health[i]
        if i in exclude or (require_idle and h.outstanding):
          continue
        if h.breaker.allow_device():
          h.outstanding += 1
          h.n_dispatched += 1
          return i
    return None

  def checkin(self, i: int) -> None:
    with self._lock:
      self._health[i].outstanding = max(0, self._health[i].outstanding - 1)

  # -- health feed ----------------------------------------------------------

  def record_latency(self, i: int, seconds: float) -> None:
    with self._lock:
      h = self._health[i]
      h.n_chunks += 1
      self._monitor.record(h.ewma_key, float(seconds))

  def record_success(self, i: int) -> None:
    h = self._health[i]
    with self._lock:
      h.n_failures = 0
    h.breaker.record_success()

  def record_failure(self, i: int) -> None:
    h = self._health[i]
    with self._lock:
      h.n_failures += 1
    h.breaker.record_failure()

  def quarantine(self, i: int) -> None:
    """Force-open a device's breaker (loss / corruption); it rejoins via
    the ordinary half-open probe after the seeded cooldown."""
    self._health[i].breaker.trip()

  def lose_device(self, i: int) -> None:
    """A device vanished mid-sweep: quarantine it and count the loss.
    (If it comes back, the half-open probe readmits it.)"""
    with self._lock:
      self._health[i].n_losses += 1
    self.quarantine(i)

  # -- fleet statistics -----------------------------------------------------

  def ewma(self, i: int) -> Optional[float]:
    st = self._monitor.hosts.get(self._health[i].ewma_key)
    return float(st.ewma) if st is not None and st.count else None

  def fleet_latency(self) -> Optional[float]:
    """Fleet-median EWMA chunk latency — the straggler reference point
    (a shard is speculated past ``speculation_factor`` x this)."""
    with self._lock:
      med = self._monitor.fleet_median()
    return float(med) if med > 0.0 else None

  def note_speculation(self, n: int = 1) -> None:
    with self._lock:
      self.n_speculative += int(n)

  def note_reshard(self, n: int = 1) -> None:
    with self._lock:
      self.n_resharded += int(n)

  def note_corruption_check(self, n: int = 1) -> None:
    with self._lock:
      self.n_corruption_checks += int(n)

  def note_corruption(self, n: int = 1) -> None:
    with self._lock:
      self.n_corruptions_detected += int(n)

  def counters(self) -> Dict[str, int]:
    """Snapshot of the fleet mitigation counters (cumulative over the
    pool's lifetime; runs diff two snapshots for per-run meta)."""
    with self._lock:
      return {"n_speculative": self.n_speculative,
              "n_resharded": self.n_resharded,
              "n_corruption_checks": self.n_corruption_checks,
              "n_corruptions_detected": self.n_corruptions_detected,
              "n_device_losses": sum(h.n_losses for h in self._health)}

  def meta(self) -> Dict[str, object]:
    """Snapshot for ``StreamResult.meta`` merging: counters plus the
    per-device breaker states and health stats."""
    out: Dict[str, object] = {k: float(v) for k, v in self.counters().items()}
    states = [h.breaker.state for h in self._health]
    out["fleet_devices"] = float(self.n_devices)
    out["fleet_device_states"] = states
    out["n_quarantined_devices"] = float(
        sum(1 for s in states if s != "closed"))
    out["fleet_device_chunks"] = [float(h.n_chunks) for h in self._health]
    out["fleet_device_ewma_s"] = [
        e if e is not None else -1.0
        for e in (self.ewma(i) for i in range(self.n_devices))]
    return out


# ---------------------------------------------------------------------------
# fleet execution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Shard:
  """One in-flight dispatch: a chunk pinned to one pool device."""
  index: int
  task: ChunkTask
  dev: Optional[int]           # pool device index; None = host fallback
  handle: object               # pending handle or immediate result
  t0: float
  immediate: bool              # result needs no resolve()
  slow: bool = False           # injected-slow fault fired at dispatch
  corrupt: bool = False        # injected-corrupt fault fired at dispatch
  speculated: bool = False     # a twin has been launched
  twin: Optional["_Shard"] = None


def _handle_ready(shard: _Shard) -> bool:
  if shard.immediate:
    return True
  fn = getattr(shard.handle, "is_ready", None)
  if fn is None:
    return False
  try:
    return bool(fn())
  except Exception:
    return False


def _corrupt_result(result):
  """Deterministic stand-in for silent device corruption: bump every
  transferred survivor's latency by one ulp (and histogram counts /
  stats means by one quantum).  No exception, values still plausible —
  exactly the failure mode only recomputation can catch."""
  payloads = getattr(result, "payloads", None)
  if payloads is None:
    frame, _ = result
    frame.latency_s = np.nextafter(frame.latency_s, np.inf)
    return result
  for payload in payloads.values():
    kind = payload[0]
    if kind == "rows":
      payload[1].latency_s = np.nextafter(payload[1].latency_s, np.inf)
    elif kind == "hist":
      counts = np.asarray(payload[1])
      if counts.size:
        counts[0] += 1
    elif kind == "stats":
      payload[1]["mean"] = np.nextafter(payload[1].get("mean", 0.0), np.inf)
  return result


def _frame_rows_match(ref_frame, ref_idx: np.ndarray, frame,
                      ids: np.ndarray) -> bool:
  """Do the transferred survivor rows (values at global ids) match the
  reference numpy evaluation bit for bit?"""
  ref_idx = np.asarray(ref_idx, np.int64)
  ids = np.asarray(ids, np.int64)
  if not ids.size:
    return True
  order = np.argsort(ref_idx, kind="stable")
  pos = np.clip(np.searchsorted(ref_idx[order], ids), 0, ref_idx.size - 1)
  pos = order[pos]
  if not np.array_equal(ref_idx[pos], ids):
    return False
  return all(np.array_equal(np.asarray(frame.column(c), np.float64),
                            np.asarray(ref_frame.column(c), np.float64)[pos])
             for c in ("latency_s", "power_mw", "area_mm2"))


def _results_match(result, reference) -> bool:
  """Compare a device chunk result against the terminal numpy rung's
  evaluation of the same chunk.  Row-carrying payloads (pareto / top-k
  survivors, full frames) are compared value-for-value — exact by the
  parity contract, so any mismatch is corruption.  Stats partials are
  merge-order-dependent (ulp-level, see EXA003) and histogram payloads
  carry no row ids; both are skipped — every default reduction plan
  ships row payloads, which carry all transferred values."""
  ref_frame, ref_idx = reference
  payloads = getattr(result, "payloads", None)
  if payloads is None:
    frame, ids = result
    return _frame_rows_match(ref_frame, ref_idx, frame, ids)
  for payload in payloads.values():
    if payload[0] == "rows":
      if not _frame_rows_match(ref_frame, ref_idx, payload[1], payload[2]):
        return False
  return True


def run_fleet(tasks: Iterable[ChunkTask], reducers: Dict[str, object],
              pool: DevicePool, *,
              policy: Optional[ResiliencePolicy] = None,
              dispatch_ahead: Optional[int] = None,
              resume_from=None, journal_key: str = "",
              checkpoint_every: int = 1):
  """Drain ``tasks`` across the pool's devices, folding every reducer as
  chunks complete — the fleet analogue of
  :func:`repro.explore.streaming.run_stream` (same journaling, same
  failure semantics, same ``StreamResult`` shape) with health-aware
  sharding, straggler speculation, elastic resharding, and the SDC
  sentinel layered on top.  Bit-identity: reducers are chunk-order
  invariant and every re-execution is a pure recomputation, so the final
  fronts match a solo single-device run exactly.  ``meta`` carries the
  sweep's span counters (:mod:`repro.explore.spans`) and
  ``fleet_device_compiles``, the sweep's compiles on each pool device.
  """
  with spans.recording() as recorder:
    with spans.span("stream"):
      out = _drain_fleet(tasks, reducers, pool, policy, dispatch_ahead,
                         resume_from, journal_key, checkpoint_every)
    out.meta.update(recorder.meta())
    out.meta["fleet_device_compiles"] = recorder.compiles_per_device(
        pool.n_devices)
  return out


def _drain_fleet(tasks, reducers, pool, policy, dispatch_ahead, resume_from,
                 journal_key, checkpoint_every):
  """The body of :func:`run_fleet`, inside its span."""
  # deferred: streaming imports fleet lazily too (pool= routing)
  from repro.explore.streaming import DISPATCH_AHEAD, SweepProgress
  if dispatch_ahead is None:
    dispatch_ahead = DISPATCH_AHEAD
  t0 = time.perf_counter()
  plan = policy.fault_plan if policy is not None else None
  progress = SweepProgress(reducers, policy, resume_from, journal_key,
                           checkpoint_every)
  base_fleet = pool.counters()

  def execute(task):
    if policy is not None:
      return policy.execute(task)
    return task()

  def run_terminal(task: ChunkTask):
    """The chunk on its terminal (numpy) rung — the all-devices-refused
    fallback and the SDC sentinel's reference evaluation."""
    if policy is not None:
      out = policy.execute_from(task, len(task.rungs) - 1)
    else:
      out = task.rungs[-1].fn()
    if hasattr(out, "resolve"):
      out = out.resolve()
    return out

  source = progress.todo(tasks)
  queue: "deque" = deque()        # requeued (orphaned / replayed) chunks
  inflight: List[_Shard] = []
  # dev index -> [(chunk index, task, resolved result)] awaiting the
  # sentinel's validation before folding (sdc_check_every > 0 only)
  buffers: Dict[int, List[Tuple[int, ChunkTask, object]]] = {}
  sdc_rng = np.random.RandomState(derive_seed("fleet-sdc", pool.seed))
  window_cap = max(1, pool.n_devices) * max(int(dispatch_ahead), 1)

  def next_item() -> Optional[Tuple[int, ChunkTask]]:
    if queue:
      return queue.popleft()
    return next(source, None)

  def dispatch(index: int, task: ChunkTask) -> None:
    has_device_rung = any(r.layer == "device"
                          for r in getattr(task, "rungs", ()))
    dev = pool.checkout() if has_device_rung else None
    slow = corrupt = False
    if dev is not None and plan is not None:
      kind = plan.check_fleet(dev, index)
      if kind == "device-lost":
        # the device vanished at this chunk boundary: quarantine it,
        # orphan its in-flight shards, reshard everything onto the rest
        pool.checkin(dev)
        pool.lose_device(dev)
        requeued = 1  # the chunk we were about to dispatch
        for s in [s for s in inflight if s.dev == dev]:
          inflight.remove(s)
          pool.checkin(dev)
          if s.twin is not None:
            # a twin on another device carries the chunk — don't
            # requeue, or the chunk would fold twice
            s.twin.twin = None
            continue
          queue.appendleft((s.index, s.task))
          requeued += 1
        buf = buffers.pop(dev, [])
        for i, t, _ in reversed(buf):
          queue.appendleft((i, t))
        pool.note_reshard(requeued + len(buf))
        queue.appendleft((index, task))
        return
      slow = kind == "slow"
      corrupt = kind == "corrupt"
    start = time.perf_counter()
    try:
      if dev is not None:
        with pin(pool.device(dev)), spans.at(index, dev):
          out = execute(task)
      elif has_device_rung:
        # every device quarantined: the terminal numpy rung is the safe
        # harbor (bit-identical by the parity contract)
        out = run_terminal(task)
      else:
        out = execute(task)
    except SweepKilled:
      progress.checkpoint(force=True)
      raise
    except Exception as e:
      if dev is not None:
        pool.checkin(dev)
        pool.record_failure(dev)
      progress.fail(index, e)
    inflight.append(_Shard(index, task, dev, out, start,
                           immediate=not hasattr(out, "resolve"),
                           slow=slow, corrupt=corrupt))

  def try_speculate() -> None:
    """Twin the slowest straggler onto an idle healthy device.  A shard
    counts as a straggler when its injected-slow fault fired, or when it
    is unready past ``speculation_factor`` x the fleet-median EWMA
    latency.  First bit-identical result wins; the loser is discarded."""
    fleet_lat = pool.fleet_latency()
    now = time.perf_counter()
    for shard in inflight:
      if shard.speculated or shard.twin is not None or shard.dev is None:
        continue
      straggling = shard.slow
      if not straggling:
        if fleet_lat is None or _handle_ready(shard):
          continue
        straggling = (now - shard.t0) > pool.speculation_factor * fleet_lat
      if not straggling:
        continue
      alt = pool.checkout(require_idle=True, exclude=(shard.dev,))
      if alt is None:
        continue
      shard.speculated = True
      try:
        with pin(pool.device(alt)), spans.at(shard.index, alt), \
            spans.span("speculate"):
          out = execute(shard.task)
      except SweepKilled:
        progress.checkpoint(force=True)
        raise
      except Exception:
        # the speculation failed, the original is still in flight —
        # mitigation must never make things worse
        pool.checkin(alt)
        pool.record_failure(alt)
        continue
      twin = _Shard(shard.index, shard.task, alt, out, now,
                    immediate=not hasattr(out, "resolve"),
                    corrupt=shard.corrupt)
      twin.twin = shard
      shard.twin = twin
      inflight.append(twin)
      pool.note_speculation()
      return

  def validate(dev: int, force: bool = False) -> None:
    """The SDC sentinel: once a device has ``sdc_check_every`` buffered
    results (or at final flush), re-evaluate one seeded sample chunk on
    the numpy rung and compare.  Match folds the whole buffer;
    divergence quarantines the device and replays its chunks."""
    buf = buffers.get(dev)
    if not buf:
      return
    if not force and len(buf) < pool.sdc_check_every:
      return
    pick = int(sdc_rng.randint(len(buf)))
    index, task, result = buf[pick]
    pool.note_corruption_check()
    with spans.at(index, dev), spans.span("sdc_check"):
      reference = run_terminal(task)
      matched = _results_match(result, reference)
    if matched:
      for i, _, r in buf:
        progress.fold(i, r)
      buf.clear()
      return
    pool.note_corruption()
    pool.quarantine(dev)
    pool.note_reshard(len(buf))
    for i, t, _ in reversed(buf):
      queue.appendleft((i, t))
    buf.clear()

  def finish(shard: _Shard) -> None:
    inflight.remove(shard)
    twin = shard.twin
    if twin is not None:
      # keep-first: the twin's (bit-identical) result is abandoned;
      # jax drains the orphaned dispatch harmlessly
      if twin in inflight:
        inflight.remove(twin)
      if twin.dev is not None:
        pool.checkin(twin.dev)
      shard.twin = twin.twin = None
    try:
      with spans.at(shard.index, shard.dev):
        result = shard.handle if shard.immediate \
            else shard.handle.resolve()
    except SweepKilled:
      if shard.dev is not None:
        pool.checkin(shard.dev)
      progress.checkpoint(force=True)
      raise
    except Exception as e:
      if shard.dev is not None:
        pool.checkin(shard.dev)
        pool.record_failure(shard.dev)
      progress.fail(shard.index, e)
    if shard.dev is None:
      progress.fold(shard.index, result)
      return
    pool.checkin(shard.dev)
    pool.record_latency(shard.dev, time.perf_counter() - shard.t0)
    pool.record_success(shard.dev)
    if shard.corrupt:
      result = _corrupt_result(result)
    if pool.sdc_check_every > 0:
      buffers.setdefault(shard.dev, []).append(
          (shard.index, shard.task, result))
      validate(shard.dev)
    else:
      progress.fold(shard.index, result)

  while True:
    while len(inflight) < window_cap:
      item = next_item()       # requeued chunks first, then the source
      if item is None:
        break
      dispatch(*item)
    if inflight:
      try_speculate()
      shard = next((s for s in inflight if _handle_ready(s)), None)
      finish(shard if shard is not None else inflight[0])
      continue
    if queue:
      continue                 # device-lost replays still pending
    if any(buffers.values()):
      for dev in list(buffers):
        validate(dev, force=True)
      continue  # a failed validation requeues chunks
    break

  progress.checkpoint(force=True)
  seconds = time.perf_counter() - t0
  fleet_now, pool_meta = pool.counters(), pool.meta()
  # per-run deltas of the (pool-lifetime) mitigation counters
  extra = {k: float(fleet_now[k] - base_fleet[k]) for k in fleet_now}
  extra.update((k, pool_meta[k]) for k in (
      "fleet_devices", "fleet_device_states", "n_quarantined_devices",
      "fleet_device_chunks", "fleet_device_ewma_s"))
  return progress.result(seconds, 1.0, **extra)
