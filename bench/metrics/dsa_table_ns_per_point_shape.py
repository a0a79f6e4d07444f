"""Device nanoseconds of the layer-table program (``jit_fused_eval_table``)
per design point and per entry in the traced slice of the DeepSeek-V3.2
cell, whose table holds the sparse-attention entries and the 8-bit key
cache: ``eval_table_ns_per_point_shape``'s reading, read there, under a
name of its own so that this table is compared only with itself."""
import os

from bench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "eval_table_ns_per_point_shape.py")).read
