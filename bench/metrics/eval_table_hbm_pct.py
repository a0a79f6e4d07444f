"""The layer-table program's share of its memory roofline over the traced
slice: the bytes it cannot avoid moving for the traced points
(``bench/table_roofline.py``) over its device seconds times the chip's
``hbm_bytes_per_s``."""
from bench import chip, table_roofline


def read(ctx):
  secs = table_roofline.device_seconds(ctx)
  per_point = table_roofline.bytes_per_point(ctx)
  if secs is None or per_point is None or not ctx["traced_points"]:
    return None
  import jax
  peak = chip.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
  return 100.0 * per_point * ctx["traced_points"] / (secs * peak)
