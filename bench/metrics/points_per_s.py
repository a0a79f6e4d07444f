"""Design points evaluated and folded into fronts per second: the points
of every sweep completed in the window, over the time from the window's
start to the last completion."""


def read(ctx):
  done = [s for s in ctx["sweeps"] if s["ok"]]
  if not done:
    return None
  return sum(s["n_rows"] for s in done) / max(s["end"] for s in ctx["sweeps"])
