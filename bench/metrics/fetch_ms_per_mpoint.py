"""Host milliseconds per million points spent copying survivors from the
device: the self time of the program's ``fetch`` spans."""
from bench import counters


def read(ctx):
  return counters.ms_per_mpoint(ctx, "fetch")
