"""Profiler trace of a slice of the window, and its reduction to what the
per-layer metrics and the breakdown read.

A slice is bracketed on the main Python thread by a ``traced_window``
annotation.  Inside it:

* device busy time is the union of the intervals in which a program ran
  on the device (the ``XLA Modules`` line of each ``/device:`` plane;
  its ``XLA Ops`` when a plane has no module line), averaged over the
  devices that ran anything;
* the device ops that took most time are those programs, summed by name
  with the trailing fingerprint ``(digits)`` dropped;
* every stretch of each device's idle time is named by the innermost
  event that covers it on the host thread that drives the window (the
  one holding the ``traced_window`` span), and summed by that name.
"""
from __future__ import annotations

import functools
import glob
import importlib
import inspect
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "traced_window"

# host stages of the program that a traced run wraps in spans of their
# own, so that idle gaps can be named by stage: (module, attribute, span)
STAGES = (
    ("repro.explore.space", "DesignSpace.iter_tables", "sample"),
    ("repro.explore.space", "DesignSpace.sample_type_table", "sample"),
    ("repro.core.oracle", "batch_inputs", "batch_inputs"),
    ("repro.explore.backend", "VectorOracleBackend.fused_eval_pending",
     "dispatch"),
    ("repro.explore.backend", "VectorOracleBackend.fused_co_eval_pending",
     "dispatch"),
    ("repro.explore.device", "PendingFused.resolve", "resolve"),
    ("repro.explore.streaming", "fold_chunk", "fold_chunk"),
)
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
  """The disjoint, sorted union of ``intervals`` clipped to [lo, hi]."""
  out: List[Interval] = []
  for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
    if e <= s:
      continue
    if out and s <= out[-1][1]:
      out[-1] = (out[-1][0], max(out[-1][1], e))
    else:
      out.append((s, e))
  return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
  """The complement of a disjoint sorted union within [lo, hi]."""
  out, t = [], lo
  for s, e in busy:
    if s > t:
      out.append((t, s))
    t = max(t, e)
  if hi > t:
    out.append((t, hi))
  return out


def _events(line) -> List[Tuple[str, float, float]]:
  return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
          for e in line.events]


def load(path: str, window: str = WINDOW_SPAN) -> Dict[str, object]:
  """Device program intervals per device plane, and the events of the
  host thread that holds the ``window`` span (the main Python thread,
  whose line the profiler names after the thread), from an
  ``.xplane.pb`` file (times in ns)."""
  from jax.profiler import ProfileData
  data = ProfileData.from_file(path)
  devices: Dict[str, list] = {}
  host: list = []
  for plane in data.planes:
    lines = {ln.name: ln for ln in plane.lines}
    if plane.name.startswith("/device:") and ("XLA Modules" in lines
                                               or "XLA Ops" in lines):
      line = lines.get("XLA Modules") or lines["XLA Ops"]
      devices[plane.name] = _events(line)
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        events = _events(line)
        if any(name == window for name, _, _ in events):
          host.extend(events)
  return {"devices": devices, "host": host}


def _spanned(fn, name: str):
  """``fn`` with every call, or every step of the generator it returns,
  inside a host span called ``name``."""
  import jax
  if inspect.isgeneratorfunction(fn):
    @functools.wraps(fn)
    def steps(*args, **kwargs):
      it = fn(*args, **kwargs)
      while True:
        with jax.profiler.TraceAnnotation(name):
          try:
            item = next(it)
          except StopIteration:
            return
        yield item
    return steps

  @functools.wraps(fn)
  def call(*args, **kwargs):
    with jax.profiler.TraceAnnotation(name):
      return fn(*args, **kwargs)
  return call


def instrument(stages=STAGES) -> List[str]:
  """Wrap each program stage that exists in a span; returns those found."""
  found = []
  for module, attr, name in stages:
    try:
      owner = importlib.import_module(module)
    except ImportError:
      continue
    *path, leaf = attr.split(".")
    for part in path:
      owner = getattr(owner, part, None)
    fn = getattr(owner, leaf, None) if owner is not None else None
    if callable(fn):
      setattr(owner, leaf, _spanned(fn, name))
      found.append(attr)
  return found


def attribute(idle: List[Interval], host,
              window: str = WINDOW_SPAN) -> Dict[str, float]:
  """Seconds of the idle intervals (sorted, disjoint) by what the host was
  doing: each stretch of a gap goes to the shortest host event that covers
  it, or to "no host event"."""
  out: Dict[str, float] = {}
  events = sorted((s, e, name) for name, s, e in host
                  if name != window and e > s)
  active: list = []
  k = 0
  for lo, hi in idle:
    while k < len(events) and events[k][0] < hi:
      active.append(events[k])
      k += 1
    active = [ev for ev in active if ev[1] > lo]
    cuts = sorted({lo, hi} | {t for s, e, _ in active for t in (s, e)
                              if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
      cover = [ev for ev in active if ev[0] <= a and ev[1] >= b]
      name = min(cover, key=lambda ev: ev[1] - ev[0])[2] if cover \
          else "no host event"
      out[name] = out.get(name, 0.0) + (b - a) * 1e-9
  return out


def reduce(events: Dict[str, object], top: int = 10,
           window: str = WINDOW_SPAN) -> Optional[dict]:
  """Busy seconds per device, the slice length, the programs that took
  most device time and the idle gaps by host event, over the slice; None
  when the trace holds no slice or no device activity in it."""
  host = events["host"]
  spans = [(s, e) for name, s, e in host if name == window]
  if not spans:
    return None
  lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
  host = [h for h in host if h[2] > lo and h[1] < hi]
  busy, ops, idle = [], {}, {}
  for plane, evs in events["devices"].items():
    merged = union([(s, e) for _, s, e in evs], lo, hi)
    if not merged:
      continue
    busy.append(sum(e - s for s, e in merged) * 1e-9)
    for name, s, e in evs:
      d = min(e, hi) - max(s, lo)
      if d > 0:
        key = _FINGERPRINT.sub("", name)
        ops[key] = ops.get(key, 0.0) + d * 1e-9
    for key, secs in attribute(gaps(merged, lo, hi), host,
                                window).items():
      idle[key] = idle.get(key, 0.0) + secs
  if not busy:
    return None
  rank = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                          key=lambda kv: -kv[1])[:top]
  return {"busy_s": busy, "window_s": (hi - lo) * 1e-9,
          "device_ops": rank(ops), "idle_gaps": rank(idle)}


class Capture:
  """Start and stop the JAX profiler around a slice of the window, with
  the slice marked by a ``traced_window`` annotation, and reduce what it
  wrote.  The trace goes to a temporary directory that is removed after
  reading."""

  def __init__(self):
    self._dir: Optional[str] = None
    self._span = None

  @property
  def active(self) -> bool:
    return self._span is not None

  def start(self) -> None:
    import jax
    self._dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(self._dir, profiler_options=opts)
    self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    self._span.__enter__()

  def stop(self) -> None:
    import jax
    self._span.__exit__(None, None, None)
    self._span = None
    jax.profiler.stop_trace()

  def read(self) -> Optional[dict]:
    if self._dir is None:  # the window closed before the slice began
      return None
    try:
      files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                        recursive=True)
      return reduce(load(files[0])) if files else None
    finally:
      shutil.rmtree(self._dir, ignore_errors=True)
