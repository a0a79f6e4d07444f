"""What the layer-table program (``jit_fused_eval_table``) does, as the
two per-layer metrics of its cells read it from a traced run: its device
seconds in the traced slice, and the bytes it cannot avoid moving.

The bound is the memory roofline: a TPU v5e publishes no float64 peak
(the chip emulates float64 on its bf16 and int units), so the share of
``hbm_bytes_per_s`` (``bench/peaks.json``) is the one roofline a float64
program has.  The bytes counted are those any program computing the same
outputs must move at least once: the chunk's input columns and the layer
table, read once (the ``bytes_to_device`` the program's ``place`` spans
count), the three float64 outputs (latency, power, area) of every point
written once, and the reduction outputs written once (a row id and three
float64 values per row the reducers return, ``rows_transferred``).
Intermediates are left out, so the share cannot pass 100%.
"""
from typing import Optional

PROGRAM = "jit_fused_eval_table"
FLOAT_BYTES = 8


def device_seconds(ctx: dict) -> Optional[float]:
  """Device seconds of the program in the traced slice, or None when the
  run was untraced or the program never ran there."""
  t = ctx["trace"]
  if t is None:
    return None
  secs = sum(s for name, s in t["device_ops"] if name == PROGRAM)
  return secs or None


def sweeps_with_table(ctx: dict) -> list:
  return [s for s in ctx["sweeps"] if s["ok"] and "n_layer_shapes" in
          getattr(s.get("outcome"), "meta", {})]


def sweep_bytes(meta: dict, n_rows: int) -> float:
  """The bytes one sweep's table programs cannot avoid moving.  A sweep
  in which a chunk overflowed the survivor cap fetched whole chunks, not
  reduction outputs: its reduction outputs are then left out."""
  out = meta["bytes_to_device"] + 3 * FLOAT_BYTES * n_rows
  if not meta.get("n_overflows", 0):
    out += (1 + 3) * FLOAT_BYTES * meta["rows_transferred"]
  return float(out)


def bytes_per_point(ctx: dict) -> Optional[float]:
  done = sweeps_with_table(ctx)
  points = sum(s["n_rows"] for s in done)
  if not points:
    return None
  return sum(sweep_bytes(s["outcome"].meta, s["n_rows"])
             for s in done) / points
