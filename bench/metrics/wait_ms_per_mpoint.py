"""Host milliseconds per million points blocked on the device programs: the
self time of the program's ``wait`` spans, the first blocking read of each
chunk's results."""
from bench import counters


def read(ctx):
  return counters.ms_per_mpoint(ctx, "wait")
