"""Device busy milliseconds, summed over the devices used, per million
design points completed in the traced slice of the window."""


def read(ctx):
  t = ctx["trace"]
  if t is None or not ctx["traced_points"]:
    return None
  return sum(t["busy_s"]) * 1e3 / (ctx["traced_points"] / 1e6)
