"""Device nanoseconds of the layer-table program (``jit_fused_eval_table``)
per design point and per distinct layer shape it evaluates, over the
traced slice of the window: the cost of one (shape, point) cell of its
grid.  The shape count is the sweeps' ``n_layer_shapes``."""
from bench import table_roofline


def read(ctx):
  secs = table_roofline.device_seconds(ctx)
  done = table_roofline.sweeps_with_table(ctx)
  if secs is None or not done or not ctx["traced_points"]:
    return None
  shapes = done[0]["outcome"].meta["n_layer_shapes"]
  return secs * 1e9 / (ctx["traced_points"] * shapes)
