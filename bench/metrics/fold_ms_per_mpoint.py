"""Host milliseconds per million points spent folding survivors into the
fronts: the self time of the program's ``fold_chunk`` spans."""
from bench import counters


def read(ctx):
  return counters.ms_per_mpoint(ctx, "fold_chunk")
