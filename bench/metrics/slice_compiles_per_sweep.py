"""XLA compiles per sweep inside the program's ``slice`` spans: one
survivor slice program per survivor count the process has not sliced
before (``n_compiles_slice``), averaged over the window's completed
sweeps."""
from bench import counters


def read(ctx):
  return counters.mean_per_sweep(ctx, "n_compiles_slice")
