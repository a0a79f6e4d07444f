"""Rows that crossed from the device to the host, as a share of the
points evaluated, over the window's completed sweeps (the engine's
``rows_transferred`` counter)."""


def read(ctx):
  done = [s for s in ctx["sweeps"] if s["ok"]]
  points = sum(s["n_rows"] for s in done)
  if not points:
    return None
  return 100.0 * sum(s["rows_transferred"] for s in done) / points
