"""Synthesis oracle: the stand-in for Synopsys DC + VCS @ FreePDK45.

The paper characterizes every design point with commercial synthesis
(power/area/clock) plus RTL simulation (latency).  Neither tool can run in
this environment, so this module provides an *analytical gate/SRAM-level
model* with documented 45 nm constants (see :mod:`repro.core.pe`), plus a
deterministic, config-hashed "layout variation" term so the downstream
polynomial regression faces realistically noisy targets.

Calibration anchors (paper, Table 3 + Figs 6/8 orderings):
  clock:  FP32 275 MHz | INT16 285 MHz | LightPE-2 435 MHz | LightPE-1 455 MHz
  area/power: FP32 > INT16 >> LightPE-2 > LightPE-1 per PE.

Everything is per *design point* (AcceleratorConfig); latency additionally
takes workload layers and delegates to the RS dataflow model.  Every
target also has a vectorized ``*_batch`` sibling that evaluates a whole
:class:`repro.core.table.ConfigTable` at once (bit-identical to the
scalar path on numpy; optional jax device path) — the engine behind
:class:`repro.explore.VectorOracleBackend`'s million-point sweeps.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import struct
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import pe as pe_lib
from repro.core.dataflow import (AcceleratorConfig, ConvLayer, LayerStats,
                                 layer_count, simulate_network)
from repro.core.table import scratch_buf as _scratch_buf

# Characterization-model version: bump whenever oracle outputs change for
# the same config (invalidates on-disk polynomial-model caches fitted
# against older outputs).  v2: column-hashed _variation (splitmix64 chain
# over key columns) replaced the per-point string SHA-256.
ORACLE_VERSION = 2

# FIFO depth per the Eyeriss-style template (4 FIFOs per PE, Fig. 3).
FIFO_DEPTH = 4
FLOP_BIT_UM2 = 2.0          # latch-based FIFO storage cell
NOC_GATES_PER_PE = 300      # X-bus router slice + links at 21-bit mean width
PSUM_AMORTIZE = 3.0         # psum spad is touched once per K MACs (a local
                            # accumulator register holds the running sum;
                            # K=3 kernels dominate the workloads)
ARRAY_CTRL_GATES = 12_000   # top-level controller, address generators


# Layout variation hashes the design point's KEY COLUMNS (not a formatted
# key string): salt and PE-type names are folded in as one-time SHA-256
# constants, then each knob column is chained through a splitmix64-style
# finalizer.  The same mixer runs per-row on Python ints (scalar path) and
# on uint64 numpy columns (:func:`_variation_batch`), so the vectorized
# million-point path is bit-identical to the scalar oracle by construction.
_MASK64 = (1 << 64) - 1


@functools.lru_cache(maxsize=None)
def _name_const(name: str) -> int:
  """Stable 64-bit constant for a salt / PE-type name (one-time hash)."""
  return int.from_bytes(hashlib.sha256(name.encode()).digest()[:8], "little")


def _mix64(z: int) -> int:
  """splitmix64 finalizer on a Python int (mod 2^64)."""
  z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
  z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK64
  return z ^ (z >> 31)


def _variation_key_ints(cfg: AcceleratorConfig) -> Tuple[int, ...]:
  return (_name_const(cfg.pe_type), cfg.pe_rows, cfg.pe_cols, cfg.sp_if,
          cfg.sp_fw, cfg.sp_ps, cfg.gbuf_kb,
          int.from_bytes(struct.pack("<d", float(cfg.bandwidth_gbps)),
                         "little"))


def _variation(cfg: AcceleratorConfig, salt: str, pct: float) -> float:
  """Deterministic pseudo-random multiplier in [1-pct, 1+pct]."""
  h = _name_const(salt)
  for v in _variation_key_ints(cfg):
    h = _mix64(h ^ v)
  u = (h / 2**64) * 2.0 - 1.0
  return 1.0 + pct * u


def _sram_area_um2(bits: float, words: float = 64.0) -> float:
  """CACTI-flavoured small-SRAM area: cells + sqrt-periphery + decoder
  steps (ceil(log2 words) levels) + fixed."""
  if bits <= 0:
    return 0.0
  decoder = 6.0 * pe_lib.decoder_levels(words) * math.sqrt(max(bits, 1.0)) \
      / 8.0
  return bits * pe_lib.SRAM_BIT_UM2 + 3.0 * math.sqrt(bits) + decoder + 15.0


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------

def clock_mhz(cfg: AcceleratorConfig) -> float:
  """Post-synthesis clock estimate.

  period = arithmetic critical path + control/wire term that grows with the
  array size and scratchpad address depth.  Calibrated so the nominal
  16x16 / (12,224,24) / 128 KiB design reproduces the paper's Table 3.
  """
  pe = cfg.pe
  ctrl_ns = 0.028 * math.log2(max(cfg.n_pe, 2)) \
      + 0.006 * math.log2(max(cfg.sp_fw + cfg.sp_if + cfg.sp_ps, 2))
  period_ns = pe.critical_path_ns + ctrl_ns
  period_ns *= _variation(cfg, "clk", 0.004)
  return 1000.0 / period_ns


# ---------------------------------------------------------------------------
# area
# ---------------------------------------------------------------------------

def pe_area_um2(cfg: AcceleratorConfig) -> float:
  """One PE: arithmetic + 3 scratchpads + 4 FIFOs + local control."""
  pe = cfg.pe
  arith = pe.arith_gates * pe_lib.GATE_AREA_UM2
  spad = (_sram_area_um2(cfg.sp_if * pe.act_bits, cfg.sp_if)
          + _sram_area_um2(cfg.sp_fw * pe.weight_bits, cfg.sp_fw)
          + _sram_area_um2(cfg.sp_ps * pe.psum_bits, cfg.sp_ps))
  fifo_bits = FIFO_DEPTH * (2 * pe.act_bits + pe.weight_bits + pe.psum_bits)
  fifo = fifo_bits * FLOP_BIT_UM2
  ctrl = 0.04 * (arith + spad) + 220 * pe_lib.GATE_AREA_UM2
  return arith + spad + fifo + ctrl


def array_area_mm2(cfg: AcceleratorConfig) -> float:
  """PE-array subsystem (array + NoC + control, EXCLUDING global buffer).

  This is the polynomial area model's target: the paper's 4-feature vector
  (SP_if, SP_ps, SP_fw, #PE) cannot see GBS, so the global buffer is
  composed separately as a pre-characterized SRAM macro (datasheet-style),
  see :func:`gbuf_area_mm2`.
  """
  pe = cfg.pe
  pe_area = pe_area_um2(cfg) * cfg.n_pe
  word = (pe.act_bits + pe.weight_bits + pe.psum_bits) / 3.0
  noc = NOC_GATES_PER_PE * (word / 21.0) * cfg.n_pe * pe_lib.GATE_AREA_UM2
  top = ARRAY_CTRL_GATES * pe_lib.GATE_AREA_UM2
  # routing congestion: utilization degrades as the array grows, the placer
  # needs slack area ~ 1/(1 - congestion) — a rational factor polynomials
  # only approximate gradually (this is what pushes the CV-optimal degree up)
  congestion = 0.30 * (cfg.n_pe / 1024.0) ** 0.7
  route = 1.0 / (1.0 - min(congestion, 0.45))
  um2 = (pe_area + noc + top) * route * _variation(cfg, "area", 0.005)
  return um2 * 1e-6


def gbuf_area_mm2(cfg: AcceleratorConfig) -> float:
  """Global-buffer SRAM macro area (closed form, banking overhead incl.)."""
  return _sram_area_um2(cfg.gbuf_kb * 1024 * 8, cfg.gbuf_kb * 512) \
      * 1.15 * 1e-6


def area_mm2(cfg: AcceleratorConfig) -> float:
  """Full accelerator: PE array subsystem + global buffer macro."""
  return array_area_mm2(cfg) + gbuf_area_mm2(cfg)


# ---------------------------------------------------------------------------
# power
# ---------------------------------------------------------------------------

def leakage_mw(cfg: AcceleratorConfig) -> float:
  """Array static power ~ gate-area equivalent (gbuf leakage lives in
  :func:`gbuf_power_mw`)."""
  pe = cfg.pe
  word = (pe.act_bits + pe.weight_bits + pe.psum_bits) / 3.0
  logic_um2 = (pe.arith_gates + NOC_GATES_PER_PE * word / 21.0) \
      * pe_lib.GATE_AREA_UM2 * cfg.n_pe \
      + ARRAY_CTRL_GATES * pe_lib.GATE_AREA_UM2
  sram_bits = cfg.n_pe * (cfg.sp_if * pe.act_bits + cfg.sp_fw * pe.weight_bits
                          + cfg.sp_ps * pe.psum_bits)
  leak = (logic_um2 / pe_lib.GATE_AREA_UM2) * pe_lib.GATE_LEAKAGE_UW \
      + sram_bits * 0.00035
  return leak * 1e-3  # uW -> mW


def array_power_mw(cfg: AcceleratorConfig) -> float:
  """PE-array characterization power (DC default activity), EXCL. gbuf.

  Activity model: every cycle each PE performs one MAC, reads act+weight
  from its scratchpads and read-modify-writes one psum.  Per-bit scratchpad
  access energy grows with scratchpad depth (bitline capacitance ~ sqrt of
  cell count) — genuinely nonlinear in the DSE axes.
  """
  pe = cfg.pe
  f_hz = clock_mhz(cfg) * 1e6
  e = pe_lib.ENERGY_PJ
  spad_pj = e["spad_access_per_bit"] * (
      pe.act_bits * pe_lib.sram_access_scale(cfg.sp_if)
      + pe.weight_bits * pe_lib.sram_access_scale(cfg.sp_fw)
      + (2.0 / PSUM_AMORTIZE) * pe.psum_bits
      * pe_lib.sram_access_scale(cfg.sp_ps))
  per_pe_pj = (pe.mac_energy_pj + spad_pj
               + FIFO_DEPTH * 0.25 * e["fifo_access_per_bit"])
  activity = 0.62  # DC default toggling assumption
  dyn_pe_mw = cfg.n_pe * per_pe_pj * activity * f_hz * 1e-9
  gbuf_word_bits = (pe.act_bits + pe.weight_bits + pe.psum_bits) / 3.0
  noc_mw = cfg.n_pe * 0.004 * (f_hz * 1e-9) * gbuf_word_bits
  dyn = dyn_pe_mw + noc_mw
  # self-heating feedback: leakage rises with power density (saturating
  # rational in the features -> hard for low-degree polynomials)
  density = dyn / max(array_area_mm2(cfg), 1e-6)  # mW / mm^2
  leak = leakage_mw(cfg) * (1.0 + 0.9 * density / (density + 40.0))
  return dyn * _variation(cfg, "pwr", 0.005) + leak


def gbuf_power_mw(cfg: AcceleratorConfig) -> float:
  """Global-buffer macro power: ports scale with the array edge
  (~sqrt(#PE)); per-bit energy scales with capacity; plus SRAM leakage."""
  pe = cfg.pe
  f_hz = clock_mhz(cfg) * 1e6
  e = pe_lib.ENERGY_PJ
  gbuf_word_bits = (pe.act_bits + pe.weight_bits + pe.psum_bits) / 3.0
  gbuf_pj_bit = e["gbuf_access_per_bit"] * pe_lib.sram_access_scale(
      cfg.gbuf_kb * 16.0)
  dyn = math.sqrt(cfg.n_pe) * gbuf_word_bits * gbuf_pj_bit * 0.62 \
      * f_hz * 1e-9
  leak = cfg.gbuf_kb * 8192 * 0.00035 * 1e-3
  return dyn + leak


def power_mw(cfg: AcceleratorConfig) -> float:
  """Full accelerator characterization power."""
  return array_power_mw(cfg) + gbuf_power_mw(cfg)


# ---------------------------------------------------------------------------
# full characterization (the expensive call QUIDAM's models replace)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Characterization:
  """Everything the paper extracts from DC + VCS for one design point."""
  clock_mhz: float
  area_mm2: float
  power_mw: float
  latency_s: float
  energy_mj: float
  per_layer_cycles: List[float]
  per_layer_energy_mj: List[float]
  utilization: float


def characterize(cfg: AcceleratorConfig,
                 layers: Sequence[ConvLayer]) -> Characterization:
  """Synthesize + simulate one (hardware, network) pair.  Per-layer
  cycles and energy are one instance's; totals count each entry
  ``count`` times (:class:`repro.core.dataflow.GemmLayer`).

  This is the slow path (a Python-level per-layer dataflow walk standing in
  for hours of synthesis + RTL simulation); QUIDAM's polynomial models are
  trained on its outputs and replace it during DSE.
  """
  clk = clock_mhz(cfg)
  leak = leakage_mw(cfg)
  latency_s, energy_mj, stats = simulate_network(cfg, layers, clk, leak)
  per_cyc = [s.cycles for s in stats]
  from repro.core.dataflow import layer_energy_pj  # local to avoid cycle
  per_e = [layer_energy_pj(cfg, l, s, clk, leak) * 1e-9
           for l, s in zip(layers, stats)]
  # left to right, as the vectorized oracle adds: builtin sum() of floats
  # compensates its rounding (Python >= 3.12) and lands an ulp away
  util_weighted = total_cycles = 0.0
  for l, s in zip(layers, stats):
    n = layer_count(l)
    util_weighted += n * (s.utilization * s.cycles)
    total_cycles += n * s.cycles
  util = util_weighted / max(total_cycles, 1e-12)
  return Characterization(
      clock_mhz=clk, area_mm2=area_mm2(cfg), power_mw=power_mw(cfg),
      latency_s=latency_s, energy_mj=energy_mj,
      per_layer_cycles=per_cyc, per_layer_energy_mj=per_e,
      utilization=util)


def characterize_layer_latency(cfg: AcceleratorConfig, layer: ConvLayer
                               ) -> float:
  """Ground-truth single-layer latency in seconds (latency-model target)."""
  from repro.core.dataflow import simulate_layer
  clk = clock_mhz(cfg)
  st = simulate_layer(cfg, layer, clk)
  return st.cycles / (clk * 1e6)


# ---------------------------------------------------------------------------
# vectorized siblings: whole ConfigTables at once
# ---------------------------------------------------------------------------
# Every scalar formula above has a ``*_batch`` twin that evaluates a
# :class:`repro.core.table.ConfigTable` column-at-a-time.  The formulas are
# written against an array module ``xp`` (numpy by default; jax.numpy for
# the optional device path) and mirror the scalar expressions op for op, so
# the numpy path is bit-identical to looping the scalar oracle.  The
# variation term is precomputed with numpy uint64 arithmetic either way
# (jax traces treat it as an input), because the mixer needs uint64.


def _mix64_batch(z: np.ndarray, out: Optional[np.ndarray] = None
                 ) -> np.ndarray:
  """splitmix64 finalizer across a uint64 column (wraps mod 2^64)."""
  z = np.multiply(z ^ (z >> np.uint64(30)), np.uint64(0xBF58476D1CE4E5B9),
                  out=out)
  z = np.multiply(z ^ (z >> np.uint64(27)), np.uint64(0x94D049BB133111EB),
                  out=out)
  return np.bitwise_xor(z, z >> np.uint64(31), out=out)


def _variation_batch(table, salt: str, pct: float,
                     scratch: Optional[Dict] = None) -> np.ndarray:
  """Vectorized :func:`_variation`: one multiplier per table row."""
  n = len(table)
  type64 = np.asarray([_name_const(t) for t in table.pe_type_names],
                      np.uint64)[table.pe_code]
  h = _scratch_buf(scratch, f"var64_{salt}", n, np.uint64)
  if h is None:
    h = np.empty(n, np.uint64)
  h[...] = _name_const(salt)
  cols = (type64,
          table.pe_rows.astype(np.uint64), table.pe_cols.astype(np.uint64),
          table.sp_if.astype(np.uint64), table.sp_fw.astype(np.uint64),
          table.sp_ps.astype(np.uint64), table.gbuf_kb.astype(np.uint64),
          table.bandwidth_gbps.astype(np.float64).view(np.uint64))
  for v in cols:
    np.bitwise_xor(h, v, out=h)
    _mix64_batch(h, out=h)
  u = _scratch_buf(scratch, f"var_{salt}", n, np.float64)
  if u is None:
    u = np.empty(n, np.float64)
  # same IEEE op sequence as the expression form: /2^64, *2, -1, *pct, +1
  np.true_divide(h, 2.0**64, out=u)
  np.multiply(u, 2.0, out=u)
  np.subtract(u, 1.0, out=u)
  np.multiply(u, pct, out=u)
  np.add(u, 1.0, out=u)
  return u


def batch_inputs(table, scratch: Optional[Dict] = None
                 ) -> Dict[str, np.ndarray]:
  """The array bundle all batch formulas consume: numeric columns +
  per-row PE constants + the three precomputed variation columns + the
  transcendental terms (log2 / pow) of the area/clock formulas.

  The transcendentals are precomputed with host numpy for the same reason
  the variation columns are: they are pure functions of the config
  columns, and libm (numpy) and XLA disagree by 1 ulp on ``log2``/``pow``
  — precomputing them makes the ``jax.jit`` x64 device path bit-identical
  to the numpy path by construction (basic arithmetic, ``sqrt``, ``ceil``
  and floor-division are IEEE-exact in both).

  ``scratch`` (a plain dict owned by the caller, one per worker thread)
  lets repeated chunked calls reuse the feature temporaries instead of
  allocating ~20 fresh arrays per chunk; the returned dict then aliases
  the scratch buffers, so the caller must consume it before the next
  call with the same scratch.
  """
  cols = table.numeric_columns(scratch=scratch)
  cols["var_clk"] = _variation_batch(table, "clk", 0.004, scratch)
  cols["var_area"] = _variation_batch(table, "area", 0.005, scratch)
  cols["var_pwr"] = _variation_batch(table, "pwr", 0.005, scratch)
  n = len(table)
  l2pe = _scratch_buf(scratch, "log2_n_pe", n, np.float64)
  cols["log2_n_pe"] = np.log2(np.maximum(cols["n_pe"], 2.0), out=l2pe)
  sp = cols["sp_fw"] + cols["sp_if"] + cols["sp_ps"]
  l2sp = _scratch_buf(scratch, "log2_sp_words", n, np.float64)
  cols["log2_sp_words"] = np.log2(np.maximum(sp, 2.0, out=sp), out=l2sp)
  cg = _scratch_buf(scratch, "congestion", n, np.float64)
  cols["congestion"] = np.multiply(
      0.30, np.power(cols["n_pe"] / 1024.0, 0.7, out=cg), out=cg)
  return cols


def _decoder_levels_arr(words, xp):
  if xp is np:
    return np.maximum(np.ceil(np.log2(np.maximum(words, 2.0))), 1.0)
  # On device, ceil(log2(w)) is the bit length of ceil(w) - 1: integer
  # arithmetic, exact everywhere.  A device log2 is not: the TPU's
  # emulated float64 log2(2**19) lands above 19 (and fused XLA:CPU log2
  # above 29 at 2**29), and ceil turns that ulp into a whole level.
  import jax
  int_t = xp.int64 if words.dtype == xp.float64 else xp.int32
  w = xp.ceil(xp.maximum(words, 2.0)).astype(int_t) - 1
  bits = xp.iinfo(int_t).bits - jax.lax.clz(w)
  return xp.maximum(bits.astype(words.dtype), 1.0)


def _sram_access_scale_arr(words, xp):
  return (0.47 + 0.45 * xp.sqrt(xp.maximum(words, 1.0) / 64.0)
          + 0.022 * _decoder_levels_arr(words, xp))


def _sram_area_um2_arr(bits, words, xp):
  decoder = 6.0 * _decoder_levels_arr(words, xp) \
      * xp.sqrt(xp.maximum(bits, 1.0)) / 8.0
  area = bits * pe_lib.SRAM_BIT_UM2 + 3.0 * xp.sqrt(xp.maximum(bits, 0.0)) \
      + decoder + 15.0
  return xp.where(bits <= 0, 0.0, area)


def _clock_cols(c, xp):
  # log2 terms come precomputed from batch_inputs when available (host
  # numpy: keeps the jitted x64 path bit-identical — XLA's log2 is 1 ulp
  # off libm); bare numeric_columns() dicts compute them inline
  # fallbacks below only run for bare numeric_columns() dicts, which are
  # host numpy by construction — batch_inputs precomputes for the device
  l2_pe = c["log2_n_pe"] if "log2_n_pe" in c \
      else xp.log2(xp.maximum(c["n_pe"], 2.0))  # repro: ignore[EXA002]
  l2_sp = c["log2_sp_words"] if "log2_sp_words" in c \
      else xp.log2(xp.maximum(c["sp_fw"] + c["sp_if"] + c["sp_ps"], 2.0))  # repro: ignore[EXA002]
  ctrl_ns = 0.028 * l2_pe + 0.006 * l2_sp
  period_ns = (c["critical_path_ns"] + ctrl_ns) * c["var_clk"]
  return 1000.0 / period_ns


def _pe_area_cols(c, xp):
  arith = c["arith_gates"] * pe_lib.GATE_AREA_UM2
  spad = (_sram_area_um2_arr(c["sp_if"] * c["act_bits"], c["sp_if"], xp)
          + _sram_area_um2_arr(c["sp_fw"] * c["weight_bits"], c["sp_fw"], xp)
          + _sram_area_um2_arr(c["sp_ps"] * c["psum_bits"], c["sp_ps"], xp))
  fifo_bits = FIFO_DEPTH * (2 * c["act_bits"] + c["weight_bits"]
                            + c["psum_bits"])
  fifo = fifo_bits * FLOP_BIT_UM2
  ctrl = 0.04 * (arith + spad) + 220 * pe_lib.GATE_AREA_UM2
  return arith + spad + fifo + ctrl


def _array_area_cols(c, xp):
  pe_area = _pe_area_cols(c, xp) * c["n_pe"]
  word = (c["act_bits"] + c["weight_bits"] + c["psum_bits"]) / 3.0
  noc = NOC_GATES_PER_PE * (word / 21.0) * c["n_pe"] * pe_lib.GATE_AREA_UM2
  top = ARRAY_CTRL_GATES * pe_lib.GATE_AREA_UM2
  # pow is precomputed on host like the log2 terms (see _clock_cols);
  # the fallback only runs for host-numpy numeric_columns() dicts
  congestion = c["congestion"] if "congestion" in c \
      else 0.30 * (c["n_pe"] / 1024.0) ** 0.7  # repro: ignore[EXA002]
  route = 1.0 / (1.0 - xp.minimum(congestion, 0.45))
  um2 = (pe_area + noc + top) * route * c["var_area"]
  return um2 * 1e-6


def _gbuf_area_cols(c, xp):
  return _sram_area_um2_arr(c["gbuf_kb"] * 1024 * 8, c["gbuf_kb"] * 512, xp) \
      * 1.15 * 1e-6


def _leakage_cols(c, xp):
  word = (c["act_bits"] + c["weight_bits"] + c["psum_bits"]) / 3.0
  logic_um2 = (c["arith_gates"] + NOC_GATES_PER_PE * word / 21.0) \
      * pe_lib.GATE_AREA_UM2 * c["n_pe"] \
      + ARRAY_CTRL_GATES * pe_lib.GATE_AREA_UM2
  sram_bits = c["n_pe"] * (c["sp_if"] * c["act_bits"]
                           + c["sp_fw"] * c["weight_bits"]
                           + c["sp_ps"] * c["psum_bits"])
  leak = (logic_um2 / pe_lib.GATE_AREA_UM2) * pe_lib.GATE_LEAKAGE_UW \
      + sram_bits * 0.00035
  return leak * 1e-3


def _array_power_cols(c, xp, clock=None, array_area=None):
  if clock is None:
    clock = _clock_cols(c, xp)
  if array_area is None:
    array_area = _array_area_cols(c, xp)
  f_hz = clock * 1e6
  e = pe_lib.ENERGY_PJ
  spad_pj = e["spad_access_per_bit"] * (
      c["act_bits"] * _sram_access_scale_arr(c["sp_if"], xp)
      + c["weight_bits"] * _sram_access_scale_arr(c["sp_fw"], xp)
      + (2.0 / PSUM_AMORTIZE) * c["psum_bits"]
      * _sram_access_scale_arr(c["sp_ps"], xp))
  per_pe_pj = (c["mac_energy_pj"] + spad_pj
               + FIFO_DEPTH * 0.25 * e["fifo_access_per_bit"])
  activity = 0.62
  dyn_pe_mw = c["n_pe"] * per_pe_pj * activity * f_hz * 1e-9
  gbuf_word_bits = (c["act_bits"] + c["weight_bits"] + c["psum_bits"]) / 3.0
  noc_mw = c["n_pe"] * 0.004 * (f_hz * 1e-9) * gbuf_word_bits
  dyn = dyn_pe_mw + noc_mw
  density = dyn / xp.maximum(array_area, 1e-6)
  leak = _leakage_cols(c, xp) * (1.0 + 0.9 * density / (density + 40.0))
  return dyn * c["var_pwr"] + leak


def _gbuf_power_cols(c, xp, clock=None):
  if clock is None:
    clock = _clock_cols(c, xp)
  f_hz = clock * 1e6
  e = pe_lib.ENERGY_PJ
  gbuf_word_bits = (c["act_bits"] + c["weight_bits"] + c["psum_bits"]) / 3.0
  gbuf_pj_bit = e["gbuf_access_per_bit"] * _sram_access_scale_arr(
      c["gbuf_kb"] * 16.0, xp)
  dyn = xp.sqrt(c["n_pe"]) * gbuf_word_bits * gbuf_pj_bit * 0.62 \
      * f_hz * 1e-9
  leak = c["gbuf_kb"] * 8192 * 0.00035 * 1e-3
  return dyn + leak


# -- public batch API (each takes a ConfigTable, like the scalar siblings
# take an AcceleratorConfig) -------------------------------------------------

def clock_mhz_batch(table, xp=np, inputs: Optional[Dict] = None) -> np.ndarray:
  """Vectorized :func:`clock_mhz` over a ConfigTable."""
  return _clock_cols(inputs if inputs is not None else batch_inputs(table), xp)


def pe_area_um2_batch(table, xp=np, inputs: Optional[Dict] = None
                      ) -> np.ndarray:
  """Vectorized :func:`pe_area_um2`."""
  return _pe_area_cols(
      inputs if inputs is not None else batch_inputs(table), xp)


def array_area_mm2_batch(table, xp=np, inputs: Optional[Dict] = None
                         ) -> np.ndarray:
  """Vectorized :func:`array_area_mm2`."""
  return _array_area_cols(
      inputs if inputs is not None else batch_inputs(table), xp)


def gbuf_area_mm2_batch(table, xp=np, inputs: Optional[Dict] = None
                        ) -> np.ndarray:
  """Vectorized :func:`gbuf_area_mm2`."""
  return _gbuf_area_cols(
      inputs if inputs is not None else batch_inputs(table), xp)


def area_mm2_batch(table, xp=np, inputs: Optional[Dict] = None) -> np.ndarray:
  """Vectorized :func:`area_mm2`."""
  c = inputs if inputs is not None else batch_inputs(table)
  return _array_area_cols(c, xp) + _gbuf_area_cols(c, xp)


def leakage_mw_batch(table, xp=np, inputs: Optional[Dict] = None
                     ) -> np.ndarray:
  """Vectorized :func:`leakage_mw`."""
  return _leakage_cols(
      inputs if inputs is not None else batch_inputs(table), xp)


def array_power_mw_batch(table, xp=np, inputs: Optional[Dict] = None
                         ) -> np.ndarray:
  """Vectorized :func:`array_power_mw`."""
  return _array_power_cols(
      inputs if inputs is not None else batch_inputs(table), xp)


def gbuf_power_mw_batch(table, xp=np, inputs: Optional[Dict] = None
                        ) -> np.ndarray:
  """Vectorized :func:`gbuf_power_mw`."""
  return _gbuf_power_cols(
      inputs if inputs is not None else batch_inputs(table), xp)


def power_mw_batch(table, xp=np, inputs: Optional[Dict] = None) -> np.ndarray:
  """Vectorized :func:`power_mw`."""
  c = inputs if inputs is not None else batch_inputs(table)
  clock = _clock_cols(c, xp)
  return _array_power_cols(c, xp, clock=clock) \
      + _gbuf_power_cols(c, xp, clock=clock)


def power_area_batch(table, xp=np, inputs: Optional[Dict] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
  """(power_mw, area_mm2) per row, sharing the clock / array-area
  intermediates both targets need — the hot pair of every DSE sweep."""
  c = inputs if inputs is not None else batch_inputs(table)
  clock = _clock_cols(c, xp)
  array_area = _array_area_cols(c, xp)
  gbuf_area = _gbuf_area_cols(c, xp)
  power = _array_power_cols(c, xp, clock=clock, array_area=array_area) \
      + _gbuf_power_cols(c, xp, clock=clock)
  return power, array_area + gbuf_area


@dataclasses.dataclass
class BatchCharacterization:
  """Column form of :class:`Characterization` for N design points."""
  clock_mhz: np.ndarray
  area_mm2: np.ndarray
  power_mw: np.ndarray
  latency_s: np.ndarray
  energy_mj: np.ndarray
  utilization: np.ndarray

  def __len__(self) -> int:
    return int(self.clock_mhz.shape[0])


def hw_batch_targets(c, xp=np) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                        np.ndarray]:
  """(clock_mhz, power_mw, area_mm2, leakage_mw) from an inputs bundle —
  the shared workload-independent half of :func:`characterize_batch` /
  :func:`characterize_joint` (and of the fused device programs)."""
  clock = _clock_cols(c, xp)
  array_area = _array_area_cols(c, xp)
  area = array_area + _gbuf_area_cols(c, xp)
  power = _array_power_cols(c, xp, clock=clock, array_area=array_area) \
      + _gbuf_power_cols(c, xp, clock=clock)
  leak = _leakage_cols(c, xp)
  return clock, power, area, leak


def characterize_batch(table, layers: Sequence[ConvLayer], xp=np,
                       inputs: Optional[Dict] = None
                       ) -> BatchCharacterization:
  """Vectorized :func:`characterize`: one synthesis-oracle characterization
  per table row, sharing clock/area/variation intermediates across targets.
  """
  from repro.core.dataflow import simulate_network_batch
  c = inputs if inputs is not None else batch_inputs(table)
  clock, power, area, leak = hw_batch_targets(c, xp)
  latency_s, energy_mj, utilization = simulate_network_batch(
      c, layers, clock, leak, xp=xp)
  return BatchCharacterization(
      clock_mhz=clock, area_mm2=area, power_mw=power,
      latency_s=latency_s, energy_mj=energy_mj, utilization=utilization)


def characterize_table(table, cols, counts, xp=np,
                       inputs: Optional[Dict] = None
                       ) -> BatchCharacterization:
  """:func:`characterize_batch` over a layer table
  (:func:`repro.core.dataflow.layer_table`): bit-identical on the numpy
  path, with the layer features as arrays, so that the ``jax.jit``
  device path compiles one program per number of entries (see
  :func:`repro.core.dataflow.simulate_network_table`)."""
  from repro.core.dataflow import simulate_network_table
  c = inputs if inputs is not None else batch_inputs(table)
  clock, power, area, leak = hw_batch_targets(c, xp)
  latency_s, energy_mj, utilization = simulate_network_table(
      c, cols, counts, clock, leak, xp=xp)
  return BatchCharacterization(
      clock_mhz=clock, area_mm2=area, power_mw=power,
      latency_s=latency_s, energy_mj=energy_mj, utilization=utilization)


def characterize_layer_latency_batch(table, layer: ConvLayer, xp=np,
                                     inputs: Optional[Dict] = None
                                     ) -> np.ndarray:
  """Vectorized :func:`characterize_layer_latency` (seconds per row)."""
  from repro.core.dataflow import simulate_layer_batch
  c = inputs if inputs is not None else batch_inputs(table)
  clk = _clock_cols(c, xp)
  st = simulate_layer_batch(c, layer, clk, xp=xp)
  return st.cycles / (clk * 1e6)


# ---------------------------------------------------------------------------
# joint HW x NN characterization: every architecture x every design point
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class JointCharacterization:
  """Characterization of ``n_archs x n_hw`` (architecture, HW) pairs.

  Clock / power / area depend only on the hardware and are ``(n_hw,)``;
  the workload-dependent targets are ``(n_archs, n_hw)`` (arch-major,
  matching :class:`repro.core.table.JointTable` row order when
  flattened)."""
  clock_mhz: np.ndarray
  area_mm2: np.ndarray
  power_mw: np.ndarray
  latency_s: np.ndarray
  energy_mj: np.ndarray
  utilization: np.ndarray

  @property
  def n_archs(self) -> int:
    return int(self.latency_s.shape[0])

  @property
  def n_hw(self) -> int:
    return int(self.latency_s.shape[1])


def characterize_joint(table, stack, xp=np, inputs: Optional[Dict] = None
                       ) -> JointCharacterization:
  """Joint :func:`characterize_batch`: one characterization per
  (architecture, design point) pair, computing the HW-only targets
  (clock/area/power) once per design point instead of once per pair.

  ``stack`` is a :class:`repro.core.dataflow.LayerStack`; on the numpy
  path row ``a`` of the workload targets is bit-identical to
  ``characterize_batch(table, stack.layers_of(a))``.
  """
  from repro.core.dataflow import simulate_network_stack
  c = inputs if inputs is not None else batch_inputs(table)
  clock, power, area, leak = hw_batch_targets(c, xp)
  latency_s, energy_mj, utilization = simulate_network_stack(
      c, stack, clock, leak, xp=xp)
  return JointCharacterization(
      clock_mhz=clock, area_mm2=area, power_mw=power,
      latency_s=latency_s, energy_mj=energy_mj, utilization=utilization)


def characterize_joint_dedup(table, unique_cols, slot_ids, valid, xp=np,
                             inputs: Optional[Dict] = None
                             ) -> JointCharacterization:
  """Distinct-layer twin of :func:`characterize_joint` — same outputs,
  bit-identical on the numpy path, with the dataflow formulas evaluated
  once per distinct layer shape instead of once per (arch, slot) (see
  :func:`repro.core.dataflow.simulate_network_stack_dedup`).  This is the
  form the exact ``jax.jit`` device path compiles: stack data enters as
  arrays, so one executable serves every arch block of a streaming sweep.
  """
  from repro.core.dataflow import simulate_network_stack_dedup
  c = inputs if inputs is not None else batch_inputs(table)
  clock, power, area, leak = hw_batch_targets(c, xp)
  latency_s, energy_mj, utilization = simulate_network_stack_dedup(
      c, unique_cols, slot_ids, valid, clock, leak, xp=xp)
  return JointCharacterization(
      clock_mhz=clock, area_mm2=area, power_mw=power,
      latency_s=latency_s, energy_mj=energy_mj, utilization=utilization)
