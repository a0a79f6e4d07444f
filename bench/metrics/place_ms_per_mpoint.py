"""Host milliseconds per million points spent copying chunk inputs to the
device: the self time of the program's ``place`` spans."""
from bench import counters


def read(ctx):
  return counters.ms_per_mpoint(ctx, "place")
