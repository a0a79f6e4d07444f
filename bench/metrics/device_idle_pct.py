"""The share of the traced slice of the window in which no program ran
on the device, averaged over the devices used."""


def read(ctx):
  t = ctx["trace"]
  if t is None or t["window_s"] <= 0:
    return None
  busy = sum(t["busy_s"]) / len(t["busy_s"])
  return 100.0 * (1.0 - busy / t["window_s"])
