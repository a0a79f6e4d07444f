"""Spans and counters of a streamed sweep.

``span(name)`` marks one stage a chunk passes through.  Where jax is
loaded it enters a ``jax.profiler.TraceAnnotation`` of that name, so a
profiler trace shows the stage on its host plane, on the device planes'
clock; the annotation carries ``sweep`` (a process-wide sweep number),
``chunk`` (the global chunk index) and, in a pool, ``device``.  Spans
nest on a per-thread stack, and each adds its *self time* (its duration
less the time its child spans cover) to the recorder bound to the
thread, if one is.

``recording()`` binds a recorder for one sweep: ``run_stream`` and
``run_fleet`` bind one for the whole call, ``stream_explore`` and
``stream_co_explore`` before they build their tasks, and pool workers
bind the submitting thread's (``bind``).  ``Recorder.meta()`` is what
lands in ``StreamResult.meta``: ``self_s_<span>``,
``n_compiles_<span>`` and ``compile_s_<span>`` for every span below,
``bytes_to_device``, and ``rows_var_on_device`` (result rows whose
variation columns a device program derived, not the host).

One ``jax.monitoring`` listener credits each XLA backend compile to the
innermost span open on the thread that compiles (to ``stream`` when
none is), to the pool device the thread works for, and to a
process-wide total (``compile_totals``).  The module imports jax only
when it is already loaded, so the numpy path stays free of device
imports.  Nothing here is switched: a span costs about a microsecond
with no profiler running, and no span sits in a per-row loop.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

# every stage a chunk can pass through, outermost first, and the lowering
# of a model config into layers (repro.core.workloads.from_config)
SPANS = ("stream", "plan", "sample", "dispatch", "batch_inputs", "place",
         "launch", "resolve", "wait", "slice", "fetch", "fold_chunk",
         "speculate", "sdc_check", "lower")

# the clock spans are timed on (tests inject their own)
clock = time.perf_counter

_SWEEPS = itertools.count(1)
_LOCK = threading.Lock()
_TOTAL = [0, 0.0]  # compiles in this process, and their seconds
_compile_event = ""
_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


class Recorder:
  """One sweep's counters, shared by every thread that works for it."""

  def __init__(self):
    self.sweep = next(_SWEEPS)
    self.lock = threading.Lock()
    self.self_s = dict.fromkeys(SPANS, 0.0)
    self.n_compiles = dict.fromkeys(SPANS, 0)
    self.compile_s = dict.fromkeys(SPANS, 0.0)
    self.bytes_to_device = 0
    self.rows_var_on_device = 0
    self.device_compiles: Dict[int, int] = {}

  def meta(self) -> Dict[str, float]:
    out: Dict[str, float] = {}
    with self.lock:
      for name in SPANS:
        out["self_s_" + name] = self.self_s[name]
        out["n_compiles_" + name] = float(self.n_compiles[name])
        out["compile_s_" + name] = self.compile_s[name]
      out["bytes_to_device"] = float(self.bytes_to_device)
      out["rows_var_on_device"] = float(self.rows_var_on_device)
    return out

  def compiles_per_device(self, n_devices: int) -> List[float]:
    with self.lock:
      return [float(self.device_compiles.get(i, 0))
              for i in range(n_devices)]


class _Thread(threading.local):
  def __init__(self):
    self.recorder: Optional[Recorder] = None
    self.stack: List["span"] = []
    self.chunk: Optional[int] = None
    self.device: Optional[int] = None


_TLS = _Thread()


def _on_event(event: str, seconds: float, **_) -> None:
  if event != _compile_event:
    return
  with _LOCK:
    _TOTAL[0] += 1
    _TOTAL[1] += seconds
  t = _TLS
  rec = t.recorder
  if rec is None:
    return
  name = t.stack[-1].name if t.stack else "stream"
  with rec.lock:
    rec.n_compiles[name] += 1
    rec.compile_s[name] += seconds
    if t.device is not None:
      rec.device_compiles[t.device] = \
          rec.device_compiles.get(t.device, 0) + 1


def _annotations():
  """jax's TraceAnnotation once this process has imported jax (the
  compile listener is registered then), else None."""
  global _compile_event, _annotation
  if _annotation is None and "jax" in sys.modules:
    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT
    with _LOCK:
      if _annotation is None:
        _compile_event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _annotation = jax.profiler.TraceAnnotation
  return _annotation


def compile_totals() -> Tuple[float, int]:
  """(seconds, count) of the XLA backend compiles this process has made
  since jax and this module first met (a persistent-cache hit compiles
  nothing)."""
  _annotations()
  with _LOCK:
    return _TOTAL[1], _TOTAL[0]


class span:
  """``with span("resolve"):`` — one stage, as described above.
  ``note(**args)`` adds arguments known only inside the span."""
  __slots__ = ("name", "_args", "_annotation", "_t0", "_children")

  def __init__(self, name: str, **args):
    self.name = name
    self._args = args

  def __enter__(self) -> "span":
    t = _TLS
    annotation = _annotation or _annotations()
    self._annotation = None
    if annotation is not None:
      args = self._args
      if t.recorder is not None:
        args["sweep"] = t.recorder.sweep
      if t.chunk is not None:
        args["chunk"] = t.chunk
      if t.device is not None:
        args["device"] = t.device
      self._annotation = annotation(self.name, **args)
      self._annotation.__enter__()
    t.stack.append(self)
    self._children = 0.0
    self._t0 = clock()
    return self

  def note(self, **args) -> None:
    if self._annotation is not None:
      self._annotation.set_metadata(**args)

  def __exit__(self, *exc) -> bool:
    took = clock() - self._t0
    t = _TLS
    t.stack.pop()
    if t.stack:
      t.stack[-1]._children += took
    rec = t.recorder
    if rec is not None:
      with rec.lock:
        rec.self_s[self.name] += took - self._children
    if self._annotation is not None:
      self._annotation.__exit__(*exc)
    return False


class recording:
  """Bind a recorder to this thread for the block, and yield it: the one
  already bound (``stream_explore`` binds one before it builds its
  tasks), or a fresh one."""

  def __enter__(self) -> Recorder:
    self._outer = _TLS.recorder
    if self._outer is None:
      _TLS.recorder = Recorder()
    return _TLS.recorder

  def __exit__(self, *exc) -> bool:
    _TLS.recorder = self._outer
    return False


class at:
  """Tag the spans this thread opens in the block with a chunk index
  and, in a pool, the device index the chunk runs on."""

  def __init__(self, chunk: Optional[int], device: Optional[int] = None):
    self._tags = (chunk, device)

  def __enter__(self) -> None:
    t = _TLS
    self._outer = (t.chunk, t.device)
    t.chunk, t.device = self._tags

  def __exit__(self, *exc) -> bool:
    _TLS.chunk, _TLS.device = self._outer
    return False


def bind(recorder: Optional[Recorder], fn):
  """``fn`` run with ``recorder`` bound to whichever thread calls it."""
  def call(*args, **kwargs):
    t = _TLS
    outer = t.recorder
    t.recorder = recorder
    try:
      return fn(*args, **kwargs)
    finally:
      t.recorder = outer
  return call


def count_bytes(n: int) -> None:
  """Add ``n`` bytes copied host->device to the bound recorder."""
  rec = _TLS.recorder
  if rec is not None:
    with rec.lock:
      rec.bytes_to_device += int(n)


def count_var_rows(n: int) -> None:
  """Add ``n`` result rows whose variation columns the device derives
  to the bound recorder."""
  rec = _TLS.recorder
  if rec is not None:
    with rec.lock:
      rec.rows_var_on_device += int(n)
