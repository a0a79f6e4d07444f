"""Host milliseconds per million points spent building the program's input
columns: the self time of its ``batch_inputs`` spans
(``oracle.batch_inputs`` for each chunk)."""
from bench import counters


def read(ctx):
  return counters.ms_per_mpoint(ctx, "batch_inputs")
