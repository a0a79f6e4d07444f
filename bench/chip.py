"""The device a run is on: what JAX reports, the check that it is the
accelerator the cell asks for, its published peaks, its memory peak, and
the compile clock."""
from __future__ import annotations

import collections
import json
import os
import threading

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
  """The machine lacks the accelerator or the chip count a cell asks for."""


def devices(chips: int):
  """The ``chips`` TPU devices of this machine; NoChip when JAX finds no
  TPU, another number of chips, or a device kind with no peaks."""
  import jax
  try:
    devs = jax.devices()
  except RuntimeError as e:  # no backend could start
    raise NoChip(f"JAX found no device: {e}") from e
  if devs[0].platform != "tpu":
    raise NoChip(f"no TPU: JAX runs on {devs[0].platform}; the benchmark "
                 "has no CPU fallback")
  if len(devs) != chips:
    raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
  peaks(devs[0].device_kind)
  return devs


def peaks(kind: str) -> dict:
  """The published peaks of one chip of ``kind``; an unknown kind is an
  error, never a default."""
  with open(PEAKS_FILE) as f:
    table = json.load(f)
  if kind not in table:
    raise NoChip(f"device kind {kind!r} has no entry in {PEAKS_FILE}")
  return table[kind]


def describe(devs) -> dict:
  return {"platform": devs[0].platform, "kind": devs[0].device_kind,
          "count": len(devs)}


def memory_peak_bytes(devs) -> int:
  """Peak bytes in use on the fullest device, as its allocator reports."""
  peak = 0
  for d in devs:
    stats = d.memory_stats() or {}
    peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
  return peak


class CompileClock:
  """Sums XLA backend compile time and counts compiles, from JAX's
  monitoring events (a persistent-cache hit compiles nothing).  It also
  keeps the longest single compile and the seconds of every timed JAX
  event by name, so that a stall can be put down to one of them."""

  def __init__(self):
    from jax import monitoring
    from jax._src import dispatch
    self.seconds = 0.0
    self.count = 0
    self.longest = 0.0
    self.events = collections.Counter()
    self.lock = threading.Lock()  # compiles may finish on several threads
    event = dispatch.BACKEND_COMPILE_EVENT

    def listener(name, secs, **_):
      with self.lock:
        self.events[name] += secs
        if name == event:
          self.seconds += secs
          self.count += 1
          self.longest = max(self.longest, secs)

    monitoring.register_event_duration_secs_listener(listener)

  def mark(self):
    return self.seconds, self.count

  def event_seconds(self) -> dict:
    """Seconds of every timed JAX event so far, by name."""
    with self.lock:
      return dict(self.events)

  def take_longest(self) -> float:
    """The longest single compile since the last call."""
    longest, self.longest = self.longest, 0.0
    return longest
