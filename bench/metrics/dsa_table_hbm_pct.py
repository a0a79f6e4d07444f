"""The layer-table program's share of its memory roofline in the traced
slice of the DeepSeek-V3.2 cell: the bytes it cannot avoid moving
(``bench/table_roofline.py``) over its device seconds times the chip's
``hbm_bytes_per_s``, ``eval_table_hbm_pct``'s reading, read there, under
a name of its own so that this table is compared only with itself."""
import os

from bench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "eval_table_hbm_pct.py")).read
