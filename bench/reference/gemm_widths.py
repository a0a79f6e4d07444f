"""Plain reference of GEMM entries whose right operand may be stored at
a width of its own, such as DeepSeek Sparse Attention's 8-bit indexer
key cache: an entry (M, K, N, groups, operand, count, bits).

With ``bits`` None an entry moves as ``bench/reference/gemm.py`` says: a
weight at the PE type's weight width, a cache at its activation width.
With ``bits`` set its right operand moves at that many bits on every PE
type, wherever gemm.py uses the right operand's width: the fit test in a
quarter of the global buffer and the DRAM bits.  The mapping is
gemm.py's; such an entry is computed there as a weight operand with the
weight width set to ``bits`` for that entry alone.  Imports nothing of
the program under test.
"""
from __future__ import annotations

import numpy as np

from bench.reference import gemm, oracle, sweeps


def entry_cycles(c: dict, entry, clock) -> np.ndarray:
  """Cycles of one instance of ``entry`` on every design point of ``c``."""
  bits = entry[6]
  if bits is None:
    return gemm.gemm_cycles(c, entry, clock)
  stored = dict(c, wgt=np.full_like(c["wgt"], float(bits)))
  return gemm.gemm_cycles(stored, tuple(entry[:4]) + ("weight",), clock)


def network_latency(c: dict, entries, clock) -> np.ndarray:
  """Seconds per design point: each entry's cycles times its count,
  added entry by entry in order, over the clock."""
  total = 0.0
  for entry in entries:
    total = total + entry[5] * entry_cycles(c, entry, clock)
  return total / (clock * 1e6)


def explore(config: dict, entries, n_per_type: int, seed: int,
            reducers: dict) -> dict:
  """Plain sweep of such a network, sampled as
  :func:`bench.reference.gemm.explore` samples it."""
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
