"""What the per-layer metrics read from the program's own span counters:
the ``self_s_<span>`` and ``n_compiles_<span>`` entries that each sweep's
``StreamResult.meta`` carries (``repro.explore.spans``).

They are read only in a traced run, whose device plane shows that the
run was on the chip (a CPU run is never reported as a speed), and only
from completed sweeps whose meta holds the counter: a program without
spans has none, and the metric is then left out of the result line."""
from typing import List, Optional


def sweeps_with(ctx: dict, key: str) -> List[dict]:
  if ctx["trace"] is None:
    return []
  return [s for s in ctx["sweeps"]
          if s["ok"] and key in getattr(s.get("outcome"), "meta", {})]


def ms_per_mpoint(ctx: dict, span: str) -> Optional[float]:
  """Host self milliseconds of ``span`` per million points, over the
  window's completed sweeps."""
  key = "self_s_" + span
  done = sweeps_with(ctx, key)
  points = sum(s["n_rows"] for s in done)
  if not points:
    return None
  return sum(s["outcome"].meta[key] for s in done) * 1e3 / (points / 1e6)


def mean_per_sweep(ctx: dict, key: str) -> Optional[float]:
  done = sweeps_with(ctx, key)
  if not done:
    return None
  return sum(s["outcome"].meta[key] for s in done) / len(done)
