"""The 95th percentile of the time from the client's call to its folded
fronts, over every sweep completed in the window: where a stall inside
one sweep, such as a slow compile, shows undiluted by the rate."""
import numpy as np


def read(ctx):
  took = [s["end"] - s["start"] for s in ctx["sweeps"] if s["ok"]]
  return float(np.percentile(took, 95)) if took else None
