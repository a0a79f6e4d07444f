"""Plain reference of the synthesis oracle: clock, power, area and
row-stationary latency of design points, in float64 numpy.

Written from the model's equations (the QUIDAM template: Eyeriss-style
row-stationary array, per-PE scratchpads, a global buffer and a DRAM link)
and independent of the program under test: it imports nothing of it and
takes nothing it makes.  Every function works on whole columns of design
points; the operation order follows the equations as written, so on the
CPU the program's numpy path agrees with it to the last bit.
"""
from __future__ import annotations

import hashlib

import numpy as np

# 45 nm unit constants
GATE_AREA_UM2 = 0.798
SRAM_BIT_UM2 = 0.57
GATE_LEAKAGE_UW = 0.0025
SPAD_PJ_PER_BIT = 0.006
GBUF_PJ_PER_BIT = 0.025
FIFO_PJ_PER_BIT = 0.004
FIFO_DEPTH = 4
FLOP_BIT_UM2 = 2.0
NOC_GATES_PER_PE = 300
PSUM_AMORTIZE = 3.0
ARRAY_CTRL_GATES = 12_000
ACTIVITY = 0.62


def _mult(n):
  return 10 * n * n


def _add(n):
  return 7 * n


def _shift(width, stages):
  return 3 * width * stages


# per PE type: act, weight, psum bits, arithmetic gates, MAC energy (pJ),
# critical path (ns)
PE_TYPES = {
    "FP32": (32, 32, 32,
             _mult(24) + _add(10) + 900 + _shift(27, 5) * 2 + _add(27) + 700,
             3.7 + 0.9, 3.364),
    "INT16": (16, 16, 32, _mult(16) + _add(32), 0.8 + 0.1, 3.237),
    "LightPE-1": (8, 4, 24, _shift(16, 3) + _add(24), 0.024 + 0.08, 1.926),
    "LightPE-2": (8, 8, 24, 2 * _shift(16, 3) + 2 * _add(24),
                  2 * 0.024 + 0.08 + 0.05, 2.027),
}

KNOBS = ("pe_rows", "pe_cols", "sp_if", "sp_fw", "sp_ps", "gbuf_kb",
         "bandwidth_gbps")

def _name_const(name: str) -> np.uint64:
  return np.uint64(int.from_bytes(hashlib.sha256(name.encode()).digest()[:8],
                                  "little"))


def _mix(z: np.ndarray) -> np.ndarray:
  """splitmix64 finalizer, wrapping mod 2**64."""
  z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
  z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
  return z ^ (z >> np.uint64(31))


def variation(pe_type: str, hw: dict, salt: str, pct: float) -> np.ndarray:
  """The deterministic layout-variation multiplier in [1-pct, 1+pct]: a
  splitmix64 chain over the salt, the PE type and every knob."""
  n = len(hw["pe_rows"])
  h = np.full(n, _name_const(salt), np.uint64)
  keys = [np.full(n, _name_const(pe_type), np.uint64)]
  keys += [np.asarray(hw[k]).astype(np.int64).astype(np.uint64)
           for k in KNOBS[:-1]]
  keys.append(np.asarray(hw["bandwidth_gbps"], np.float64).view(np.uint64))
  with np.errstate(over="ignore"):
    for v in keys:
      h = _mix(h ^ v)
  u = h / 2.0**64
  return (u * 2.0 - 1.0) * pct + 1.0


def _levels(words):
  """Address-decoder depth, ceil(log2(words)), at least 1."""
  return np.maximum(np.ceil(np.log2(np.maximum(words, 2.0))), 1.0)


def _access_scale(words):
  return (0.47 + 0.45 * np.sqrt(np.maximum(words, 1.0) / 64.0)
          + 0.022 * _levels(words))


def _sram_area_um2(bits, words):
  decoder = 6.0 * _levels(words) * np.sqrt(np.maximum(bits, 1.0)) / 8.0
  area = bits * SRAM_BIT_UM2 + 3.0 * np.sqrt(np.maximum(bits, 0.0)) \
      + decoder + 15.0
  return np.where(bits <= 0, 0.0, area)


def columns(pe_type: str, hw: dict) -> dict:
  """float64 columns of one PE type's design points: knobs, #PE, the PE
  constants and the three variation multipliers."""
  c = {k: np.asarray(hw[k], np.float64) for k in KNOBS}
  c["n_pe"] = c["pe_rows"] * c["pe_cols"]
  act, wgt, psum, gates, mac_pj, crit = PE_TYPES[pe_type]
  for name, v in (("act", act), ("wgt", wgt), ("psum", psum),
                  ("gates", gates), ("mac_pj", mac_pj), ("crit", crit)):
    c[name] = np.full(c["n_pe"].shape, float(v))
  c["var_clk"] = variation(pe_type, hw, "clk", 0.004)
  c["var_area"] = variation(pe_type, hw, "area", 0.005)
  c["var_pwr"] = variation(pe_type, hw, "pwr", 0.005)
  return c


def hw_targets(c: dict):
  """(clock MHz, power mW, area mm2) per design point."""
  n_pe = c["n_pe"]
  act, wgt, psum = c["act"], c["wgt"], c["psum"]
  # clock: arithmetic critical path plus a control term
  ctrl_ns = 0.028 * np.log2(np.maximum(n_pe, 2.0)) \
      + 0.006 * np.log2(np.maximum(c["sp_fw"] + c["sp_if"] + c["sp_ps"], 2.0))
  clock = 1000.0 / ((c["crit"] + ctrl_ns) * c["var_clk"])
  # area: PEs (arithmetic, scratchpads, FIFOs, control), NoC, top control
  arith = c["gates"] * GATE_AREA_UM2
  spad = (_sram_area_um2(c["sp_if"] * act, c["sp_if"])
          + _sram_area_um2(c["sp_fw"] * wgt, c["sp_fw"])
          + _sram_area_um2(c["sp_ps"] * psum, c["sp_ps"]))
  fifo = FIFO_DEPTH * (2 * act + wgt + psum) * FLOP_BIT_UM2
  pe_um2 = arith + spad + fifo + (0.04 * (arith + spad)
                                  + 220 * GATE_AREA_UM2)
  word = (act + wgt + psum) / 3.0
  noc = NOC_GATES_PER_PE * (word / 21.0) * n_pe * GATE_AREA_UM2
  congestion = 0.30 * np.power(n_pe / 1024.0, 0.7)
  route = 1.0 / (1.0 - np.minimum(congestion, 0.45))
  array_area = (pe_um2 * n_pe + noc + ARRAY_CTRL_GATES * GATE_AREA_UM2) \
      * route * c["var_area"] * 1e-6
  gbuf_area = _sram_area_um2(c["gbuf_kb"] * 1024 * 8, c["gbuf_kb"] * 512) \
      * 1.15 * 1e-6
  # power: PE activity, NoC, leakage with self-heating, global buffer
  f_hz = clock * 1e6
  spad_pj = SPAD_PJ_PER_BIT * (
      act * _access_scale(c["sp_if"]) + wgt * _access_scale(c["sp_fw"])
      + (2.0 / PSUM_AMORTIZE) * psum * _access_scale(c["sp_ps"]))
  per_pe_pj = c["mac_pj"] + spad_pj + FIFO_DEPTH * 0.25 * FIFO_PJ_PER_BIT
  dyn = n_pe * per_pe_pj * ACTIVITY * f_hz * 1e-9 \
      + n_pe * 0.004 * (f_hz * 1e-9) * word
  logic_um2 = (c["gates"] + NOC_GATES_PER_PE * word / 21.0) \
      * GATE_AREA_UM2 * n_pe + ARRAY_CTRL_GATES * GATE_AREA_UM2
  sram_bits = n_pe * (c["sp_if"] * act + c["sp_fw"] * wgt
                      + c["sp_ps"] * psum)
  leakage = ((logic_um2 / GATE_AREA_UM2) * GATE_LEAKAGE_UW
             + sram_bits * 0.00035) * 1e-3
  density = dyn / np.maximum(array_area, 1e-6)
  array_power = dyn * c["var_pwr"] \
      + leakage * (1.0 + 0.9 * density / (density + 40.0))
  gbuf_power = np.sqrt(n_pe) * word * (
      GBUF_PJ_PER_BIT * _access_scale(c["gbuf_kb"] * 16.0)) * ACTIVITY \
      * f_hz * 1e-9 + c["gbuf_kb"] * 8192 * 0.00035 * 1e-3
  return clock, array_power + gbuf_power, array_area + gbuf_area


def layer_cycles(c: dict, layer, clock):
  """Row-stationary cycles of one conv layer (A, C, F, K, S, P, ...) on every
  design point: folded spatial mapping, scratchpad-bounded tiling,
  compute cycles, then the DRAM-bandwidth stall beyond 85% overlap."""
  a, ch, f, k, s, p = (float(v) for v in layer[:6])
  out = np.floor((a + 2.0 * p - k) / max(s, 1.0)) + 1.0
  e = max(out, 1.0)
  macs = out * out * k * k * ch * f
  rows, cols = c["pe_rows"], c["pe_cols"]
  col_folds = np.ceil(e / cols)
  cols_used = np.minimum(e, cols)
  k_rows = np.minimum(k, rows)
  row_folds = np.ceil(k / rows)
  sets = np.where(row_folds == 1, np.maximum(rows // k_rows, 1.0), 1.0)
  f_tile = np.maximum(1.0, np.minimum(f, c["sp_ps"]))
  c_tile = np.maximum(1.0, np.minimum(
      ch, c["sp_fw"] // np.maximum(k * f_tile, 1.0)))
  c_tile = np.maximum(1.0, np.minimum(
      c_tile, np.maximum(c["sp_if"] // max(k, 1.0), 1.0) * sets))
  n_f = np.ceil(f / f_tile)
  passes = np.ceil(np.ceil(ch / c_tile) / sets) * n_f * col_folds * row_folds
  compute = np.maximum(passes * (e * k * c_tile * f_tile + (k + cols_used)),
                       macs / c["n_pe"])
  gbuf_bits = c["gbuf_kb"] * 1024 * 8
  ifmap, weights, ofmap = a * a * ch, k * k * ch * f, out * out * f
  dram_if = ifmap * np.where(ifmap * c["act"] <= 0.5 * gbuf_bits, 1.0, n_f)
  dram_w = weights * np.where(weights * c["wgt"] <= 0.25 * gbuf_bits, 1.0,
                              col_folds)
  dram_bits = dram_if * c["act"] + dram_w * c["wgt"] + ofmap * c["psum"]
  dram_cycles = dram_bits / 8.0 / (c["bandwidth_gbps"] * 1e9) \
      / (1e-6 / clock)
  return compute + np.maximum(0.0, dram_cycles - 0.85 * compute)
