"""Plain reference of the GEMM layer kind: row-stationary cycles of
``groups`` products (M x K) @ (K x N) on every design point, and a plain
sweep over a network of such products, in float64 numpy.

Written from the mapping's equations, independent of the program under
test (it imports nothing of it): the M rows of the left operand over the
PE columns, every PE row a tile of K by N, N tiled by the psum
scratchpad and K by the filter and ifmap scratchpads; DRAM reads the left
operand once if it fits half the global buffer, else once per N tile,
the right operand once if it fits a quarter, else once per column fold,
and writes the output once; a cache operand moves at the activation
width.  Groups run one after another; an entry counts ``count`` times.
"""
from __future__ import annotations

import numpy as np

from bench.reference import oracle, sweeps


def gemm_cycles(c: dict, entry, clock) -> np.ndarray:
  """Cycles of one instance of ``entry`` (M, K, N, groups, operand, ...)
  on every design point of the columns ``c``."""
  m, k, n, groups, operand = entry[:5]
  m, k, n = float(m), float(k), float(n)
  right_bits = c["act"] if operand == "cache" else c["wgt"]
  col_folds = np.ceil(m / c["pe_cols"])
  cols_used = np.minimum(m, c["pe_cols"])
  sets = c["pe_rows"]
  f_tile = np.maximum(1.0, np.minimum(n, c["sp_ps"]))
  k_tile = np.maximum(1.0, np.minimum(k, c["sp_fw"] // f_tile))
  k_tile = np.maximum(1.0, np.minimum(k_tile,
                                      np.maximum(c["sp_if"], 1.0) * sets))
  n_tiles = np.ceil(n / f_tile)
  passes = np.ceil(np.ceil(k / k_tile) / sets) * n_tiles * col_folds
  compute = np.maximum(passes * (k_tile * f_tile + (1.0 + cols_used)),
                       m * k * n / c["n_pe"])
  gbuf_bits = c["gbuf_kb"] * 1024 * 8
  dram_left = m * k * np.where(m * k * c["act"] <= 0.5 * gbuf_bits, 1.0,
                               n_tiles)
  dram_right = k * n * np.where(k * n * right_bits <= 0.25 * gbuf_bits, 1.0,
                                col_folds)
  dram_bits = dram_left * c["act"] + dram_right * right_bits \
      + m * n * c["psum"]
  dram_cycles = dram_bits / 8.0 / (c["bandwidth_gbps"] * 1e9) \
      / (1e-6 / clock)
  return (compute + np.maximum(0.0, dram_cycles - 0.85 * compute)) * groups


def network_latency(c: dict, entries, clock) -> np.ndarray:
  """Seconds per design point: each entry's cycles times its count,
  added entry by entry in order, over the clock."""
  total = 0.0
  for entry in entries:
    total = total + entry[5] * gemm_cycles(c, entry, clock)
  return total / (clock * 1e6)


def explore(config: dict, entries, n_per_type: int, seed: int,
            reducers: dict) -> dict:
  """Plain sweep of a GEMM network: ``n_per_type`` random points of each
  PE type (type i seeded ``seed + 100 * i``), as
  :func:`bench.reference.sweeps.explore` samples them."""
  ids, parts = [], []
  for ti, pe_type in enumerate(config["pe_types"]):
    c = oracle.columns(pe_type, sweeps.sample_hw(config["hw_ranges"],
                                                 n_per_type, seed + 100 * ti))
    clock, pwr, area = oracle.hw_targets(c)
    parts.append((network_latency(c, entries, clock), pwr, area))
    ids.append(ti * n_per_type + np.arange(n_per_type, dtype=np.int64))
  lat, pwr, area = (np.concatenate(x) for x in zip(*parts))
  return sweeps.reduce_points(np.concatenate(ids),
                              sweeps.columns_of(lat, pwr, area), reducers)
