"""Host milliseconds per million points spent sampling design points: the
self time of the program's ``sample`` spans (``DesignSpace.iter_tables``,
and the joint generator's sampling and block slicing)."""
from bench import counters


def read(ctx):
  return counters.ms_per_mpoint(ctx, "sample")
