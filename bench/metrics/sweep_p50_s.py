"""The median time from the client's call to its folded fronts, over
every sweep completed in the window.  Reported by the DSE cell, whose
window holds about 100 sweeps; its 95th percentile sat on the edge
between the few sweeps that compile and the rest and swung by 11% from
run to run.  The joint cell's 13-16 sweeps a window leave even its median
to the order of its questions; its stalls show in ``points_per_s`` and,
in the traced run, in ``sweep_p95_s``."""
import numpy as np


def read(ctx):
  took = [s["end"] - s["start"] for s in ctx["sweeps"] if s["ok"]]
  return float(np.median(took)) if took else None
