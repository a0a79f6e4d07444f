"""Row-stationary (RS) dataflow model for the QUIDAM accelerator template.

This is the cycle-approximate analytical model of the Eyeriss-style spatial
array the paper synthesizes (Sec. 3.1): a ``rows x cols`` PE grid running
row-stationary dataflow, per-PE scratchpads (ifmap/filter/psum), a global
buffer, and DRAM behind a finite-bandwidth link.

It provides the *ground-truth* latency / utilization / memory-access counts
that the paper obtains from Synopsys VCS testbenches; the polynomial PPA
models of :mod:`repro.core.ppa` are trained against it (together with the
area/power numbers from :mod:`repro.core.oracle`).

Mapping summary (Chen et al., ISCA'16):
  * a logical PE set of ``K`` rows x ``E`` cols computes one 2-D conv plane;
    PE(i, j) convolves filter row ``i`` against ifmap row ``i + j`` and
    produces psums of output row ``j``.
  * the logical set is folded onto the physical array: ``E`` folds over the
    columns, ``K`` folds over the rows; leftover rows replicate additional
    channel/filter tiles.
  * scratchpads bound the per-pass tile sizes:
      - psum spad       -> F_tile accumulators held per PE
      - filter spad     -> K * C_tile * F_tile weights held per PE
      - ifmap spad      -> sliding window of C_tile * K activations
  * passes iterate over ceil(C / C_tile) * ceil(F / F_tile) tiles; psums
    spill to the global buffer between channel tiles.

Matrix products (:class:`GemmLayer`) have a row-stationary mapping of
their own, written out in that class's docstring: the rows of the left
operand take the place of output rows, with a kernel one wide.

Each simulation entry point has a vectorized ``*_batch`` sibling
(:func:`simulate_layer_batch`, :func:`simulate_network_batch`) that
evaluates a whole :class:`repro.core.table.ConfigTable` column-at-a-time,
bit-identically to the scalar model on the numpy path.
:func:`simulate_network_table` evaluates a merged layer table (every
entry once, weighted by its count) with the layer features as arrays.

For joint HW x NN co-exploration the per-network layer loop additionally
batches over *architectures*: :class:`LayerStack` pre-packs every
architecture's layer features into padded ``(n_archs, max_layers)``
tensors once, and :func:`simulate_network_stack` evaluates all
``n_archs x n_hw`` pairs with one ``(n_archs, n_hw)``-shaped pass per
layer slot — the same formulas as :func:`simulate_layer_batch`, with the
layer-side constants promoted from Python floats to broadcast arrays, so
the numpy path stays bit-identical to the scalar nested loop.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import pe as pe_lib


@dataclasses.dataclass(frozen=True)
class ConvLayer:
  """One conv (or 1x1-conv-as-matmul) workload layer.

  A: input feature-map spatial dim (assumed square A x A)
  C: input channels;  F: output channels (filter count)
  K: kernel size;     S: stride;     P: padding
  rs/ds: ResNet regular / dotted (projection) skip-connection indicators,
  the two binary extra features of the paper's latency model.
  """
  name: str
  A: int
  C: int
  F: int
  K: int = 1
  S: int = 1
  P: int = 0
  rs: int = 0
  ds: int = 0

  @property
  def out_dim(self) -> int:
    return (self.A + 2 * self.P - self.K) // self.S + 1

  @property
  def macs(self) -> int:
    e = self.out_dim
    return e * e * self.K * self.K * self.C * self.F

  @property
  def weight_count(self) -> int:
    return self.K * self.K * self.C * self.F

  @property
  def ifmap_count(self) -> int:
    return self.A * self.A * self.C

  @property
  def ofmap_count(self) -> int:
    e = self.out_dim
    return e * e * self.F

  def features(self) -> Tuple[float, ...]:
    """The layer-side features of the paper's 12-dim latency vector."""
    return (float(self.A), float(self.C), float(self.F), float(self.K),
            float(self.S), float(self.P), float(self.rs), float(self.ds))


OPERANDS = ("weight", "cache")


@dataclasses.dataclass(frozen=True)
class GemmLayer:
  """``groups`` independent products (M, K) @ (K, N), run one after
  another, standing for ``count`` identical layers.

  The left operand (M x K) is an activation.  The right operand (K x N)
  is either a weight (``operand="weight"``) or an activation read back
  from memory, such as a sequence's KV cache (``operand="cache"``).
  Each group has a right operand of its own: one head's absorbed
  projection, or one sequence's cache.

  Row-stationary mapping of one group (the conv mapping with the M rows
  of the left operand as output rows, each one wide, and a kernel one
  wide; R x Cl PEs, scratchpads sp_if / sp_fw / sp_ps words):

    col_folds = ceil(M / Cl)            M rows over the PE columns
    cols_used = min(M, Cl)
    sets      = R                       every PE row a (K, N) tile
    f_tile    = max(1, min(N, sp_ps))   N tiled by the psum spad
    k_tile    = max(1, min(K, sp_fw // f_tile, sp_if * sets))
                                        K tiled by the filter and
                                        ifmap spads
    passes    = ceil(ceil(K / k_tile) / sets) * ceil(N / f_tile)
                * col_folds
    compute   = max(passes * (k_tile * f_tile + 1 + cols_used),
                    M K N / (R Cl))

  DRAM words: the left operand M K once if it fits half the global
  buffer at ``act_bits``, else once per N tile; the right operand K N
  once if it fits a quarter of the buffer at its own bits, else once per
  column fold; the output M N once at ``psum_bits``.  The right operand
  moves at its own width through the scratchpads, the buffer and DRAM:
  ``bits`` where the entry stores one (say, an 8-bit key cache), else
  ``act_bits`` for a ``cache`` and ``weight_bits`` for a weight.  That
  width decides the quarter-buffer fit test, the right operand's DRAM
  bits and its scratchpad, buffer and DRAM energy.  A cache's fit test
  is per group, so a batch's caches never share the buffer.  The stall
  past 85% overlap and every access count follow the conv model.  One
  instance's cycles, accesses and MACs are ``groups`` times one group's;
  a network counts its latency, energy and utilization ``count`` times.
  """
  name: str
  M: int
  K: int
  N: int
  groups: int = 1
  operand: str = "weight"
  count: int = 1
  bits: Optional[int] = None  # the right operand's stored width

  def __post_init__(self):
    if self.operand not in OPERANDS:
      raise ValueError(f"operand must be one of {OPERANDS}, got "
                       f"{self.operand!r}")
    for field in ("M", "K", "N", "groups", "count"):
      if int(getattr(self, field)) < 1:
        raise ValueError(f"{field} must be at least 1, got "
                         f"{getattr(self, field)}")
    # widths 0 and 1 are the table's codes for the PE type's widths
    if self.bits is not None and int(self.bits) < 2:
      raise ValueError(f"bits must be at least 2, got {self.bits}")

  @property
  def shape(self) -> Tuple[int, int, int, int, str, Optional[int]]:
    """What identical layers share, whatever their names and counts."""
    return (self.M, self.K, self.N, self.groups, self.operand, self.bits)

  @property
  def macs(self) -> int:
    """MACs of one instance (every group)."""
    return self.groups * self.M * self.K * self.N


def layer_count(layer) -> int:
  """How many identical layers one entry stands for."""
  return getattr(layer, "count", 1)


@dataclasses.dataclass(frozen=True)
class AcceleratorConfig:
  """The hardware half of QUIDAM's input space (Fig. 2)."""
  pe_type: str = "INT16"
  pe_rows: int = 16
  pe_cols: int = 16
  sp_if: int = 12      # ifmap scratchpad entries (words)
  sp_fw: int = 224     # filter scratchpad entries
  sp_ps: int = 24      # psum scratchpad entries
  gbuf_kb: int = 128   # global buffer (KiB)
  bandwidth_gbps: float = 12.8  # DRAM link bandwidth

  @property
  def n_pe(self) -> int:
    return self.pe_rows * self.pe_cols

  @property
  def pe(self) -> pe_lib.PEType:
    return pe_lib.pe_type(self.pe_type)

  def hw_features(self) -> Tuple[float, ...]:
    return (float(self.sp_if), float(self.sp_ps), float(self.sp_fw),
            float(self.n_pe))

  def latency_hw_features(self) -> Tuple[float, ...]:
    return (float(self.sp_if), float(self.sp_ps), float(self.sp_fw),
            float(self.pe_rows), float(self.pe_cols), float(self.gbuf_kb))


@dataclasses.dataclass
class LayerStats:
  """Per-layer dataflow simulation output."""
  cycles: float
  compute_cycles: float
  dram_stall_cycles: float
  utilization: float
  macs: int
  # access counts (words) per memory level
  spad_reads: float
  spad_writes: float
  gbuf_reads: float
  gbuf_writes: float
  dram_reads: float
  dram_writes: float


def _right_bits(pe: pe_lib.PEType, layer) -> int:
  """Bits a word of the right operand (weights, or a cache) moves at."""
  if getattr(layer, "bits", None) is not None:
    return layer.bits
  if getattr(layer, "operand", "weight") == "cache":
    return pe.act_bits
  return pe.weight_bits


def _simulate_gemm(cfg: AcceleratorConfig, layer: GemmLayer,
                   clock_mhz: float) -> LayerStats:
  """One GemmLayer instance, by the mapping in its docstring."""
  pe = cfg.pe
  M, K, N, g = layer.M, layer.K, layer.N, layer.groups
  right_bits = _right_bits(pe, layer)

  # ---- spatial mapping: M rows over the columns, every row a set --------
  col_folds = math.ceil(M / cfg.pe_cols)
  cols_used = min(M, cfg.pe_cols)
  sets = cfg.pe_rows
  spatial_util = (sets * cols_used) / cfg.n_pe

  # ---- scratchpad-bounded tiling ----------------------------------------
  f_tile = max(1, min(N, cfg.sp_ps))
  k_tile = max(1, min(K, cfg.sp_fw // f_tile))
  k_tile = max(1, min(k_tile, max(cfg.sp_if, 1) * sets))
  n_k_passes_eff = math.ceil(math.ceil(K / k_tile) / sets)
  n_f_passes = math.ceil(N / f_tile)
  passes = n_k_passes_eff * n_f_passes * col_folds

  # ---- compute cycles of one group ---------------------------------------
  macs = M * K * N
  compute_cycles = passes * (k_tile * f_tile + (1 + cols_used))
  ideal_cycles = macs / cfg.n_pe
  compute_cycles = max(compute_cycles, ideal_cycles)
  utilization = min(1.0, ideal_cycles / max(compute_cycles, 1.0)) \
      * min(1.0, spatial_util + 1e-9)

  # ---- access counts of one group ----------------------------------------
  spad_reads = 3.0 * macs
  spad_writes = float(macs)
  gbuf_bits = cfg.gbuf_kb * 1024 * 8
  left_words = M * K
  left_fits = left_words * pe.act_bits <= 0.5 * gbuf_bits
  dram_if = left_words * (1 if left_fits else n_f_passes)
  right_words = K * N
  right_fits = right_words * right_bits <= 0.25 * gbuf_bits
  dram_w = right_words * (1 if right_fits else col_folds)
  of_words = M * N
  psum_spills = max(n_k_passes_eff - 1, 0)
  gbuf_reads = left_words * n_f_passes + right_words * col_folds \
      + of_words * psum_spills
  gbuf_writes = of_words * (psum_spills + 1.0)

  # ---- bandwidth bound ---------------------------------------------------
  cycle_s = 1e-6 / clock_mhz
  dram_bits = (dram_if * pe.act_bits + dram_w * right_bits
               + of_words * pe.psum_bits)
  dram_cycles = dram_bits / 8.0 / (cfg.bandwidth_gbps * 1e9) / cycle_s
  dram_stall = max(0.0, dram_cycles - 0.85 * compute_cycles)
  cycles = compute_cycles + dram_stall

  # ---- groups run one after another -------------------------------------
  return LayerStats(
      cycles=cycles * g, compute_cycles=compute_cycles * g,
      dram_stall_cycles=dram_stall * g, utilization=utilization,
      macs=layer.macs, spad_reads=spad_reads * g,
      spad_writes=spad_writes * g, gbuf_reads=gbuf_reads * g,
      gbuf_writes=gbuf_writes * g, dram_reads=float((dram_if + dram_w) * g),
      dram_writes=float(of_words * g))


def simulate_layer(cfg: AcceleratorConfig, layer: ConvLayer,
                   clock_mhz: float) -> LayerStats:
  """Cycle-approximate RS dataflow simulation of one layer (one instance
  of a :class:`GemmLayer`)."""
  if isinstance(layer, GemmLayer):
    return _simulate_gemm(cfg, layer, clock_mhz)
  pe = cfg.pe
  E = max(layer.out_dim, 1)
  K, C, F = layer.K, layer.C, layer.F

  # ---- spatial mapping -------------------------------------------------
  # columns host output rows (E), rows host filter rows (K)
  col_folds = math.ceil(E / cfg.pe_cols)
  cols_used = min(E, cfg.pe_cols)
  k_rows = min(K, cfg.pe_rows)
  row_folds = math.ceil(K / cfg.pe_rows)
  # leftover row capacity replicates additional (channel, filter) tiles
  sets_per_col = max(cfg.pe_rows // k_rows, 1) if row_folds == 1 else 1
  spatial_util = (k_rows * sets_per_col * cols_used) / cfg.n_pe
  if row_folds > 1:
    spatial_util = (cfg.pe_rows * cols_used) / cfg.n_pe

  # ---- scratchpad-bounded tiling ----------------------------------------
  f_tile = max(1, min(F, cfg.sp_ps))
  # filter spad holds K * C_tile * F_tile weights (one filter row per pass)
  c_tile = max(1, min(C, cfg.sp_fw // max(K * f_tile, 1)))
  # ifmap spad needs a K-deep sliding window per channel in flight
  c_tile = max(1, min(c_tile, max(cfg.sp_if // max(K, 1), 1) * sets_per_col))
  n_c_passes = math.ceil(C / c_tile)
  n_f_passes = math.ceil(F / f_tile)
  # replication across spare row capacity processes extra channel tiles in
  # parallel
  n_c_passes_eff = math.ceil(n_c_passes / sets_per_col)
  passes = n_c_passes_eff * n_f_passes * col_folds * row_folds

  # ---- compute cycles ----------------------------------------------------
  # per pass, each active PE performs E (out width) * K (kernel width) *
  # c_tile * f_tile MACs, 1 MAC/cycle; pipeline fill ~ K + cols_used.
  per_pass = E * K * c_tile * f_tile + (K + cols_used)
  compute_cycles = passes * per_pass
  ideal_cycles = layer.macs / cfg.n_pe
  compute_cycles = max(compute_cycles, ideal_cycles)
  utilization = min(1.0, ideal_cycles / max(compute_cycles, 1.0)) \
      * min(1.0, spatial_util + 1e-9)

  # ---- access counts -----------------------------------------------------
  macs = layer.macs
  # every MAC reads act + weight from its spads; the running psum lives in
  # an accumulator register and spills to the psum spad once per K MACs
  spad_reads = (2.0 + 1.0 / max(K, 1)) * macs
  spad_writes = macs / max(K, 1)
  # ifmap: DRAM -> gbuf once if it fits, else per filter-pass; gbuf -> array
  # once per filter pass (row-stationary reuses within a pass)
  ifmap_words = layer.ifmap_count
  gbuf_bits = cfg.gbuf_kb * 1024 * 8
  ifmap_fits = ifmap_words * pe.act_bits <= 0.5 * gbuf_bits
  dram_if = ifmap_words * (1 if ifmap_fits else n_f_passes)
  gbuf_if_reads = ifmap_words * n_f_passes * row_folds
  # weights: streamed from DRAM once per E-fold when they do not fit
  weight_words = layer.weight_count
  weights_fit = weight_words * pe.weight_bits <= 0.25 * gbuf_bits
  dram_w = weight_words * (1 if weights_fit else col_folds)
  gbuf_w_reads = weight_words * col_folds
  # psums: spill/refill between channel tiles
  of_words = layer.ofmap_count
  psum_spills = max(n_c_passes_eff - 1, 0)
  gbuf_ps = of_words * (2.0 * psum_spills + 1.0)
  dram_of = of_words  # final writeback
  gbuf_reads = gbuf_if_reads + gbuf_w_reads + of_words * psum_spills
  gbuf_writes = of_words * (psum_spills + 1.0)
  dram_reads = dram_if + dram_w
  dram_writes = float(dram_of)

  # ---- bandwidth bound ---------------------------------------------------
  cycle_s = 1e-6 / clock_mhz
  dram_bits = (dram_if * pe.act_bits + dram_w * pe.weight_bits
               + dram_of * pe.psum_bits)
  dram_time_s = dram_bits / 8.0 / (cfg.bandwidth_gbps * 1e9)
  dram_cycles = dram_time_s / cycle_s
  # compute/communication overlap: stalls only for the non-overlapped excess
  dram_stall = max(0.0, dram_cycles - 0.85 * compute_cycles)
  cycles = compute_cycles + dram_stall

  return LayerStats(
      cycles=cycles, compute_cycles=compute_cycles,
      dram_stall_cycles=dram_stall, utilization=utilization, macs=macs,
      spad_reads=spad_reads, spad_writes=spad_writes,
      gbuf_reads=gbuf_reads, gbuf_writes=gbuf_writes,
      dram_reads=float(dram_reads), dram_writes=dram_writes)


def layer_energy_pj(cfg: AcceleratorConfig, layer: ConvLayer,
                    stats: LayerStats, clock_mhz: float,
                    leakage_mw: float) -> float:
  """Eyeriss-style hierarchical energy model (pJ) for one layer."""
  pe = cfg.pe
  e = pe_lib.ENERGY_PJ
  right_bits = _right_bits(pe, layer)
  mac_e = stats.macs * pe.mac_energy_pj
  # scratchpad word widths differ per operand; use the mean of act/weight/
  # psum widths for reads (2 operand reads + 1 psum read) and psum for writes
  k = 1 if isinstance(layer, GemmLayer) else max(layer.K, 1)
  spad_read_bits = stats.macs * (pe.act_bits + right_bits
                                 + pe.psum_bits / k)
  spad_write_bits = stats.spad_writes * pe.psum_bits
  spad_e = (spad_read_bits + spad_write_bits) * e["spad_access_per_bit"]
  gbuf_bits = (stats.gbuf_reads + stats.gbuf_writes) * (
      (pe.act_bits + right_bits + pe.psum_bits) / 3.0)
  gbuf_e = gbuf_bits * e["gbuf_access_per_bit"]
  dram_bits = (stats.dram_reads * (pe.act_bits + right_bits) / 2.0
               + stats.dram_writes * pe.psum_bits)
  dram_e = dram_bits * e["dram_access_per_bit"]
  time_s = stats.cycles / (clock_mhz * 1e6)
  leak_e = leakage_mw * 1e-3 * time_s * 1e12  # mW * s -> pJ
  return mac_e + spad_e + gbuf_e + dram_e + leak_e


def simulate_network(cfg: AcceleratorConfig, layers: Sequence[ConvLayer],
                     clock_mhz: float, leakage_mw: float
                     ) -> Tuple[float, float, List[LayerStats]]:
  """Returns (total_latency_s, total_energy_mj, per-layer stats): one
  instance's stats per entry, its latency and energy counted ``count``
  times."""
  total_cycles = 0.0
  total_energy_pj = 0.0
  all_stats: List[LayerStats] = []
  for layer in layers:
    st = simulate_layer(cfg, layer, clock_mhz)
    n = layer_count(layer)
    total_cycles += n * st.cycles
    total_energy_pj += n * layer_energy_pj(cfg, layer, st, clock_mhz,
                                           leakage_mw)
    all_stats.append(st)
  latency_s = total_cycles / (clock_mhz * 1e6)
  return latency_s, total_energy_pj * 1e-9, all_stats  # pJ -> mJ


# ---------------------------------------------------------------------------
# vectorized siblings: N design points x one layer at a time
# ---------------------------------------------------------------------------
# The batch functions evaluate a whole ConfigTable (or its
# ``numeric_columns()`` dict) against one layer per call, mirroring the
# scalar control flow with xp.where / xp.minimum so the numpy path matches
# :func:`simulate_layer` bit for bit.  ``xp`` may be jax.numpy for the
# optional device path (approximate there: jax defaults to float32).


def _cols_of(table_or_cols) -> Dict[str, "np.ndarray"]:
  if hasattr(table_or_cols, "numeric_columns"):
    return table_or_cols.numeric_columns()
  return table_or_cols


@dataclasses.dataclass
class LayerStatsBatch:
  """Column form of :class:`LayerStats` for N design points.

  ``macs`` is an int for the one-layer path and an ``(n_archs, 1)`` array
  on the joint (LayerStack) path, where every stat column broadcasts to
  ``(n_archs, n_hw)``."""
  cycles: "np.ndarray"
  compute_cycles: "np.ndarray"
  dram_stall_cycles: "np.ndarray"
  utilization: "np.ndarray"
  macs: "int | np.ndarray"
  spad_reads: "np.ndarray"
  spad_writes: "np.ndarray"
  gbuf_reads: "np.ndarray"
  gbuf_writes: "np.ndarray"
  dram_reads: "np.ndarray"
  dram_writes: "np.ndarray"

  def row(self, i: int) -> LayerStats:
    """One design point's stats as the scalar dataclass."""
    return LayerStats(
        cycles=float(self.cycles[i]),
        compute_cycles=float(self.compute_cycles[i]),
        dram_stall_cycles=float(self.dram_stall_cycles[i]),
        utilization=float(self.utilization[i]), macs=self.macs,
        spad_reads=float(self.spad_reads[i]),
        spad_writes=float(self.spad_writes[i]),
        gbuf_reads=float(self.gbuf_reads[i]),
        gbuf_writes=float(self.gbuf_writes[i]),
        dram_reads=float(self.dram_reads[i]),
        dram_writes=float(self.dram_writes[i]))


def _layer_feats(layer: ConvLayer) -> Dict[str, float]:
  """The layer-side constants the batch formulas consume, as Python
  floats (one ConvLayer) — :class:`LayerStack` supplies the same keys as
  broadcastable ``(n_archs, 1)`` arrays.  A :class:`GemmLayer` adds the
  GEMM-only keys ``E_row`` (an output row's width), ``groups`` and
  ``cache``, the right operand's width code (:func:`width_code`); a conv
  layer has none of them, so its formulas trace exactly as before."""
  if isinstance(layer, GemmLayer):
    return {
        "E": float(layer.M), "E_row": 1.0, "K": 1.0,
        "C": float(layer.K), "F": float(layer.N),
        "macs": float(layer.M * layer.K * layer.N),
        "ifmap_words": float(layer.M * layer.K),
        "weight_words": float(layer.K * layer.N),
        "of_words": float(layer.M * layer.N),
        "groups": float(layer.groups),
        "cache": width_code(layer),
    }
  return {
      "E": float(max(layer.out_dim, 1)),
      "K": float(layer.K), "C": float(layer.C), "F": float(layer.F),
      "macs": float(layer.macs),
      "ifmap_words": float(layer.ifmap_count),
      "weight_words": float(layer.weight_count),
      "of_words": float(layer.ofmap_count),
  }


def width_code(layer: GemmLayer) -> float:
  """The ``cache`` feature of a GEMM entry, its right operand's width:
  0 for the PE type's ``weight_bits``, 1 for its ``act_bits`` (a cache
  without a width of its own), k > 1 for k bits stored."""
  if layer.bits is not None:
    return float(layer.bits)
  return 1.0 if layer.operand == "cache" else 0.0


def _right_bits_feats(c, f, xp):
  """Bits of the right operand per (layer, design point), decoded from
  the ``cache`` feature (:func:`width_code`).  Layers without one (every
  conv layer) take ``weight_bits`` at trace time."""
  if "cache" not in f:
    return c["weight_bits"]
  code = f["cache"]
  return xp.where(code > 1.0, code,
                  xp.where(code > 0.0, c["act_bits"], c["weight_bits"]))


def _simulate_layer_feats(c, f, clock_mhz, xp) -> LayerStatsBatch:
  """The batch RS-dataflow formulas over HW columns ``c`` x layer
  features ``f``.  ``f`` values are floats (one layer) or ``(n_archs, 1)``
  arrays (a LayerStack slot or a layer table, broadcasting against
  ``(n_hw,)`` columns to ``(n_archs, n_hw)``); the elementwise op sequence
  is identical either way, so the numpy path matches the scalar model bit
  for bit.  The GEMM-only features (``E_row``, ``groups``, ``cache``)
  enter only where ``f`` has them."""
  pe_rows, pe_cols, n_pe = c["pe_rows"], c["pe_cols"], c["n_pe"]
  E, K, C, F = f["E"], f["K"], f["C"], f["F"]
  right_bits = _right_bits_feats(c, f, xp)
  k_safe = xp.maximum(K, 1.0)

  # ---- spatial mapping -------------------------------------------------
  col_folds = xp.ceil(E / pe_cols)
  cols_used = xp.minimum(E, pe_cols)
  k_rows = xp.minimum(K, pe_rows)
  row_folds = xp.ceil(K / pe_rows)
  one_fold = row_folds == 1
  sets_per_col = xp.where(one_fold, xp.maximum(pe_rows // k_rows, 1.0), 1.0)
  spatial_util = xp.where(
      one_fold, (k_rows * sets_per_col * cols_used) / n_pe,
      (pe_rows * cols_used) / n_pe)

  # ---- scratchpad-bounded tiling ----------------------------------------
  f_tile = xp.maximum(1.0, xp.minimum(F, c["sp_ps"]))
  c_tile = xp.maximum(1.0, xp.minimum(
      C, c["sp_fw"] // xp.maximum(K * f_tile, 1.0)))
  c_tile = xp.maximum(1.0, xp.minimum(
      c_tile, xp.maximum(c["sp_if"] // k_safe, 1.0) * sets_per_col))
  n_c_passes = xp.ceil(C / c_tile)
  n_f_passes = xp.ceil(F / f_tile)
  n_c_passes_eff = xp.ceil(n_c_passes / sets_per_col)
  passes = n_c_passes_eff * n_f_passes * col_folds * row_folds

  # ---- compute cycles ----------------------------------------------------
  per_pass = f.get("E_row", E) * K * c_tile * f_tile + (K + cols_used)
  compute_cycles = passes * per_pass
  ideal_cycles = f["macs"] / n_pe
  compute_cycles = xp.maximum(compute_cycles, ideal_cycles)
  utilization = xp.minimum(1.0, ideal_cycles / xp.maximum(compute_cycles, 1.0)
                           ) * xp.minimum(1.0, spatial_util + 1e-9)

  # ---- access counts -----------------------------------------------------
  macs = f["macs"]
  spad_reads = (2.0 + 1.0 / k_safe) * macs + xp.zeros_like(n_pe)
  spad_writes = macs / k_safe + xp.zeros_like(n_pe)
  ifmap_words = f["ifmap_words"]
  gbuf_bits = c["gbuf_kb"] * 1024 * 8
  ifmap_fits = ifmap_words * c["act_bits"] <= 0.5 * gbuf_bits
  dram_if = ifmap_words * xp.where(ifmap_fits, 1.0, n_f_passes)
  gbuf_if_reads = ifmap_words * n_f_passes * row_folds
  weight_words = f["weight_words"]
  weights_fit = weight_words * right_bits <= 0.25 * gbuf_bits
  dram_w = weight_words * xp.where(weights_fit, 1.0, col_folds)
  gbuf_w_reads = weight_words * col_folds
  of_words = f["of_words"]
  psum_spills = xp.maximum(n_c_passes_eff - 1.0, 0.0)
  dram_of = of_words
  gbuf_reads = gbuf_if_reads + gbuf_w_reads + of_words * psum_spills
  gbuf_writes = of_words * (psum_spills + 1.0)
  dram_reads = dram_if + dram_w
  dram_writes = dram_of + xp.zeros_like(n_pe)

  # ---- bandwidth bound ---------------------------------------------------
  cycle_s = 1e-6 / clock_mhz
  dram_bits = (dram_if * c["act_bits"] + dram_w * right_bits
               + dram_of * c["psum_bits"])
  dram_time_s = dram_bits / 8.0 / (c["bandwidth_gbps"] * 1e9)
  dram_cycles = dram_time_s / cycle_s
  dram_stall = xp.maximum(0.0, dram_cycles - 0.85 * compute_cycles)
  cycles = compute_cycles + dram_stall

  if "groups" in f:  # groups run one after another
    g = f["groups"]
    cycles, compute_cycles, dram_stall = (
        cycles * g, compute_cycles * g, dram_stall * g)
    macs, spad_reads, spad_writes = macs * g, spad_reads * g, spad_writes * g
    gbuf_reads, gbuf_writes = gbuf_reads * g, gbuf_writes * g
    dram_reads, dram_writes = dram_reads * g, dram_writes * g
  return LayerStatsBatch(
      cycles=cycles, compute_cycles=compute_cycles,
      dram_stall_cycles=dram_stall, utilization=utilization, macs=macs,
      spad_reads=spad_reads, spad_writes=spad_writes,
      gbuf_reads=gbuf_reads, gbuf_writes=gbuf_writes,
      dram_reads=dram_reads, dram_writes=dram_writes)


def simulate_layer_batch(table, layer: ConvLayer, clock_mhz, xp=np
                         ) -> LayerStatsBatch:
  """Vectorized :func:`simulate_layer`: all table rows against one layer.

  ``clock_mhz`` is a per-row array (or scalar, broadcast).  Every branch of
  the scalar model becomes a masked select; integer tiling uses the same
  float ceil/floor expressions the scalar path evaluates, so results agree
  exactly on the numpy path.
  """
  st = _simulate_layer_feats(_cols_of(table), _layer_feats(layer),
                             clock_mhz, xp)
  st.macs = layer.macs  # exact int for LayerStatsBatch.row()
  return st


def _layer_energy_feats(c, f, stats: LayerStatsBatch, clock_mhz,
                        leakage_mw, xp):
  """Hierarchical energy formulas over HW columns x layer features (pJ),
  broadcasting like :func:`_simulate_layer_feats`."""
  e = pe_lib.ENERGY_PJ
  right_bits = _right_bits_feats(c, f, xp)
  mac_e = stats.macs * c["mac_energy_pj"]
  k = xp.maximum(f["K"], 1.0)
  spad_read_bits = stats.macs * (c["act_bits"] + right_bits
                                 + c["psum_bits"] / k)
  spad_write_bits = stats.spad_writes * c["psum_bits"]
  spad_e = (spad_read_bits + spad_write_bits) * e["spad_access_per_bit"]
  gbuf_bits = (stats.gbuf_reads + stats.gbuf_writes) * (
      (c["act_bits"] + right_bits + c["psum_bits"]) / 3.0)
  gbuf_e = gbuf_bits * e["gbuf_access_per_bit"]
  dram_bits = (stats.dram_reads * (c["act_bits"] + right_bits) / 2.0
               + stats.dram_writes * c["psum_bits"])
  dram_e = dram_bits * e["dram_access_per_bit"]
  time_s = stats.cycles / (clock_mhz * 1e6)
  leak_e = leakage_mw * 1e-3 * time_s * 1e12  # mW * s -> pJ
  return mac_e + spad_e + gbuf_e + dram_e + leak_e


def layer_energy_pj_batch(table, layer: ConvLayer, stats: LayerStatsBatch,
                          clock_mhz, leakage_mw, xp=np):
  """Vectorized :func:`layer_energy_pj` (pJ per design point)."""
  return _layer_energy_feats(_cols_of(table), _layer_feats(layer), stats,
                             clock_mhz, leakage_mw, xp)


def simulate_network_batch(table, layers: Sequence[ConvLayer],
                           clock_mhz, leakage_mw, xp=np):
  """Vectorized :func:`simulate_network` over a ConfigTable.

  Returns ``(latency_s, energy_mj, utilization)`` arrays, where
  utilization is the cycle-weighted mean the scalar
  :func:`repro.core.oracle.characterize` computes from per-layer stats.
  """
  c = _cols_of(table)
  total_cycles = 0.0
  total_energy_pj = 0.0
  util_weighted = 0.0
  for layer in layers:
    st = simulate_layer_batch(c, layer, clock_mhz, xp=xp)
    e_pj = layer_energy_pj_batch(c, layer, st, clock_mhz, leakage_mw, xp=xp)
    n = layer_count(layer)
    if n == 1:  # no multiply: conv programs trace as they always have
      total_cycles = total_cycles + st.cycles
      total_energy_pj = total_energy_pj + e_pj
      util_weighted = util_weighted + st.utilization * st.cycles
    else:
      total_cycles = total_cycles + n * st.cycles
      total_energy_pj = total_energy_pj + n * e_pj
      util_weighted = util_weighted + n * (st.utilization * st.cycles)
  latency_s = total_cycles / (clock_mhz * 1e6)
  utilization = util_weighted / xp.maximum(total_cycles, 1e-12)
  return latency_s, total_energy_pj * 1e-9, utilization  # pJ -> mJ


# ---------------------------------------------------------------------------
# layer tables: merged entries as arrays, one (n_entries, n_hw) pass
# ---------------------------------------------------------------------------

# the feature columns of a layer table, the keys of _layer_feats for a
# GemmLayer (a conv row takes E_row = E, groups = 1, cache = 0); the
# right operand's width rides in ``cache``, so it adds no column
TABLE_FEATURES = ("E", "E_row", "K", "C", "F", "macs", "ifmap_words",
                  "weight_words", "of_words", "groups", "cache")


def layer_table(layers: Sequence) -> Tuple[Dict[str, np.ndarray],
                                          np.ndarray]:
  """``(cols, counts)`` of a layer list, entry by entry in its order:
  each feature of :data:`TABLE_FEATURES` as an ``(n_entries, 1)`` float64
  column, and each entry's count as an ``(n_entries,)`` float64 array.
  Identical layers should already be merged into one entry
  (:func:`repro.core.workloads.from_config` does)."""
  rows = []
  for layer in layers:
    f = _layer_feats(layer)
    f.setdefault("E_row", f["E"])
    f.setdefault("groups", 1.0)
    f.setdefault("cache", 0.0)
    rows.append([f[k] for k in TABLE_FEATURES])
  feats = np.asarray(rows, np.float64).reshape(len(rows), len(TABLE_FEATURES))
  cols = {k: feats[:, i:i + 1].copy() for i, k in enumerate(TABLE_FEATURES)}
  counts = np.asarray([layer_count(l) for l in layers], np.float64)
  return cols, counts


def simulate_network_table(table, cols, counts, clock_mhz, leakage_mw,
                           xp=np):
  """:func:`simulate_network_batch` over a layer table: the dataflow and
  energy formulas run once over the ``(n_entries, n_hw)`` grid, then the
  entries add up in their order, each weighted by its count — the same
  additions, in the same order, as the per-layer loop, so the numpy path
  is bit-identical to it (a count of 1 multiplies exactly).  ``cols`` and
  ``counts`` come from :func:`layer_table`, and may be traced arrays: a
  program over them serves every table with as many entries."""
  c = _cols_of(table)
  st = _simulate_layer_feats(c, cols, clock_mhz, xp)
  e_pj = _layer_energy_feats(c, cols, st, clock_mhz, leakage_mw, xp)
  grids = (st.cycles, e_pj, st.utilization * st.cycles)

  def add(i, totals):
    n = counts[i]
    return tuple(t + n * g[i] for t, g in zip(totals, grids))

  totals = (0.0, 0.0, 0.0)
  if xp is np:
    for i in range(counts.shape[0]):
      totals = add(i, totals)
  else:  # a loop, not n copies of the step: the program stays small
    import jax
    zero = xp.zeros_like(grids[0][0])
    totals = jax.lax.fori_loop(0, counts.shape[0], add, (zero,) * 3)
  total_cycles, total_energy_pj, util_weighted = totals
  latency_s = total_cycles / (clock_mhz * 1e6)
  utilization = util_weighted / xp.maximum(total_cycles, 1e-12)
  return latency_s, total_energy_pj * 1e-9, utilization  # pJ -> mJ


# ---------------------------------------------------------------------------
# joint HW x NN batching: all architectures x all design points at once
# ---------------------------------------------------------------------------

# Padded layer slots use a benign 1x1x1 layer so every formula stays
# finite; the validity mask zeroes their contribution before accumulation
# (x + 0.0 == x exactly, so padding never perturbs the numpy-path bits).
_PAD_LAYER = ConvLayer("pad", A=1, C=1, F=1, K=1, S=1, P=0)

# ConvLayer int fields packed into the stack, in feature order
_STACK_FIELDS = ("A", "C", "F", "K", "S", "P", "rs", "ds")


@dataclasses.dataclass(eq=False)
class LayerStack:
  """Padded per-architecture layer features: ``(n_archs, max_layers)``
  int64 tensors per ConvLayer field plus a validity mask.

  Built once per co-exploration sweep (``from_layer_lists``); the derived
  quantities every dataflow formula needs (out_dim, MAC count, tensor
  word counts) are precomputed as float64 tensors so the per-layer-slot
  inner loop is pure array arithmetic.
  """
  A: np.ndarray
  C: np.ndarray
  F: np.ndarray
  K: np.ndarray
  S: np.ndarray
  P: np.ndarray
  rs: np.ndarray
  ds: np.ndarray
  valid: np.ndarray

  def __post_init__(self):
    for name in _STACK_FIELDS:
      setattr(self, name, np.asarray(getattr(self, name), np.int64))
    self.valid = np.asarray(self.valid, np.bool_)
    shape = self.A.shape
    if len(shape) != 2:
      raise ValueError(f"LayerStack fields must be 2-D, got shape {shape}")
    for name in _STACK_FIELDS + ("valid",):
      if getattr(self, name).shape != shape:
        raise ValueError(f"field {name!r} has shape "
                         f"{getattr(self, name).shape}, expected {shape}")
    # derived float64 tensors (all integer-valued, exact in float64)
    a, c, f, k = (x.astype(np.float64) for x in (self.A, self.C, self.F,
                                                 self.K))
    s, p = self.S.astype(np.float64), self.P.astype(np.float64)
    out = np.floor((a + 2.0 * p - k) / np.maximum(s, 1.0)) + 1.0
    self._E = np.maximum(out, 1.0)
    self._macs = out * out * k * k * c * f
    self._ifmap_words = a * a * c
    self._weight_words = k * k * c * f
    self._of_words = out * out * f

  @property
  def n_archs(self) -> int:
    return int(self.A.shape[0])

  @property
  def max_layers(self) -> int:
    return int(self.A.shape[1])

  def n_layers(self) -> np.ndarray:
    """Per-architecture true layer count."""
    return self.valid.sum(axis=1)

  @classmethod
  def from_layer_lists(cls, layer_lists: Sequence[Sequence[ConvLayer]]
                       ) -> "LayerStack":
    """Pack one ConvLayer list per architecture, right-padded to the
    longest network."""
    lists = [list(ls) for ls in layer_lists]
    n_max = max((len(ls) for ls in lists), default=0) or 1
    padded = [ls + [_PAD_LAYER] * (n_max - len(ls)) for ls in lists]
    cols = {name: np.asarray([[getattr(l, name) for l in ls]
                              for ls in padded], np.int64)
            for name in _STACK_FIELDS}
    valid = np.asarray([[True] * len(ls) + [False] * (n_max - len(ls))
                        for ls in lists], np.bool_)
    return cls(valid=valid, **cols)

  def slice_archs(self, lo: int, hi: int) -> "LayerStack":
    """Arch-range sub-stack (the streaming engine's unit of work).

    Row ``a`` of the slice is bit-identical to row ``lo + a`` of the full
    stack — padding columns are preserved, so per-slot accumulation order
    (and therefore every latency/energy sum) is unchanged.
    """
    sl = slice(lo, hi)
    return LayerStack(valid=self.valid[sl],
                      **{name: getattr(self, name)[sl]
                         for name in _STACK_FIELDS})

  def layers_of(self, arch_id: int) -> List[ConvLayer]:
    """Materialize one architecture's ConvLayer list (scalar escape)."""
    out = []
    for li in range(self.max_layers):
      if not self.valid[arch_id, li]:
        break
      out.append(ConvLayer(
          f"a{arch_id}l{li}",
          **{name: int(getattr(self, name)[arch_id, li])
             for name in _STACK_FIELDS}))
    return out

  def features(self) -> np.ndarray:
    """(n_archs, max_layers, 8) float64 layer-feature tensor in the
    paper's latency-model order (== ConvLayer.features())."""
    return np.stack([getattr(self, name).astype(np.float64)
                     for name in _STACK_FIELDS], axis=2)

  def feats_at(self, li: int) -> Dict[str, np.ndarray]:
    """Layer slot ``li`` as ``(n_archs, 1)`` broadcastable feature
    columns (the array twin of :func:`_layer_feats`)."""
    sl = slice(li, li + 1)
    return {
        "E": self._E[:, sl], "K": self.K[:, sl].astype(np.float64),
        "C": self.C[:, sl].astype(np.float64),
        "F": self.F[:, sl].astype(np.float64),
        "macs": self._macs[:, sl],
        "ifmap_words": self._ifmap_words[:, sl],
        "weight_words": self._weight_words[:, sl],
        "of_words": self._of_words[:, sl],
    }

  def fingerprint(self) -> str:
    """Content hash (jit-cache key for the device path)."""
    import hashlib
    h = hashlib.sha256()
    for name in _STACK_FIELDS + ("valid",):
      h.update(np.ascontiguousarray(getattr(self, name)).tobytes())
    return h.hexdigest()[:16]

  def dedup_slots(self) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Distinct-layer factorization: ``(unique_cols, slot_ids)``.

    Architectures drawn from one search space share most of their layers,
    so the ``n_archs x max_layers`` slot grid typically references only a
    few dozen *distinct* layer shapes.  ``unique_cols`` holds one
    ``(n_distinct, 1)`` float64 column per ConvLayer field (broadcastable
    against ``(n_hw,)`` HW columns exactly like :meth:`feats_at` rows);
    ``slot_ids[a, li]`` maps each slot to its distinct row.  The device
    path simulates each distinct layer once per HW chunk and *gathers*
    per slot — per-slot accumulation order is unchanged, so results stay
    bit-identical to the slot-by-slot evaluation (see
    :func:`simulate_network_stack_dedup`).
    """
    feats = np.stack([getattr(self, n).reshape(-1) for n in _STACK_FIELDS],
                     axis=1)
    uniq, inv = np.unique(feats, axis=0, return_inverse=True)
    slot_ids = inv.reshape(self.A.shape).astype(np.int32)
    cols = {n: uniq[:, i:i + 1].astype(np.float64)
            for i, n in enumerate(_STACK_FIELDS)}
    return cols, slot_ids

  def __repr__(self) -> str:
    return (f"LayerStack({self.n_archs} archs x <= {self.max_layers} "
            f"layers)")


def unique_layer_feats(cols: Dict[str, "np.ndarray"], xp=np
                       ) -> Dict[str, "np.ndarray"]:
  """Derived feature columns for :meth:`LayerStack.dedup_slots` rows —
  the same expressions LayerStack precomputes in ``__post_init__`` (and
  therefore bit-identical to :meth:`LayerStack.feats_at` values), written
  against ``xp`` so the device path can trace through them."""
  a, c, f, k = cols["A"], cols["C"], cols["F"], cols["K"]
  s, p = cols["S"], cols["P"]
  out = xp.floor((a + 2.0 * p - k) / xp.maximum(s, 1.0)) + 1.0
  return {"E": xp.maximum(out, 1.0), "K": k, "C": c, "F": f,
          "macs": out * out * k * k * c * f,
          "ifmap_words": a * a * c,
          "weight_words": k * k * c * f,
          "of_words": out * out * f}


def simulate_network_stack_dedup(table, unique_cols, slot_ids, valid,
                                 clock_mhz, leakage_mw, xp=np):
  """Distinct-layer twin of :func:`simulate_network_stack`.

  Evaluates the dataflow/energy formulas once per *distinct* layer
  (``(n_distinct, n_hw)`` grids) and accumulates per ``(arch, slot)`` by
  gathering the distinct rows — the hot restructure behind the exact
  device path: formula work drops from ``n_archs * max_layers`` slot
  evaluations to ``n_distinct`` (often 10-50x fewer), while the per-slot
  accumulation order (and thus every latency/energy/utilization bit on
  the numpy path) is exactly that of :func:`simulate_network_stack`'s
  masked branch — gathering reorders no additions.

  ``unique_cols``/``slot_ids`` come from :meth:`LayerStack.dedup_slots`;
  ``valid`` is the stack's validity mask.  Returns
  ``(latency_s, energy_mj, utilization)`` shaped ``(n_archs, n_hw)``.
  """
  c = _cols_of(table)
  f = unique_layer_feats(unique_cols, xp)
  st = _simulate_layer_feats(c, f, clock_mhz, xp)
  e_pj = _layer_energy_feats(c, f, st, clock_mhz, leakage_mw, xp)
  cyc = st.cycles
  util_cyc = st.utilization * cyc
  take = (lambda arr, ids: arr[ids]) if xp is np \
      else (lambda arr, ids: xp.take(arr, ids, axis=0))
  total_cycles = 0.0
  total_energy_pj = 0.0
  util_weighted = 0.0
  for li in range(slot_ids.shape[1]):
    ids = slot_ids[:, li]
    v = valid[:, li:li + 1]
    total_cycles = total_cycles + xp.where(v, take(cyc, ids), 0.0)
    total_energy_pj = total_energy_pj + xp.where(v, take(e_pj, ids), 0.0)
    util_weighted = util_weighted + xp.where(v, take(util_cyc, ids), 0.0)
  latency_s = total_cycles / (clock_mhz * 1e6)
  utilization = util_weighted / xp.maximum(total_cycles, 1e-12)
  return latency_s, total_energy_pj * 1e-9, utilization  # pJ -> mJ


def simulate_network_stack(table, stack: LayerStack, clock_mhz, leakage_mw,
                           xp=np):
  """Joint :func:`simulate_network_batch`: every architecture in ``stack``
  x every design point in ``table`` in one batched pass per layer slot.

  Returns ``(latency_s, energy_mj, utilization)`` shaped
  ``(n_archs, n_hw)``.  Row ``a`` is bit-identical (numpy path) to
  ``simulate_network_batch(table, stack.layers_of(a), ...)``: padded
  slots contribute exactly 0.0 and the per-slot accumulation order
  matches the scalar per-layer loop.
  """
  c = _cols_of(table)
  total_cycles = 0.0
  total_energy_pj = 0.0
  util_weighted = 0.0
  for li in range(stack.max_layers):
    f = stack.feats_at(li)
    st = _simulate_layer_feats(c, f, clock_mhz, xp)
    e_pj = _layer_energy_feats(c, f, st, clock_mhz, leakage_mw, xp)
    v = stack.valid[:, li:li + 1]
    if bool(np.all(v)):  # common fast path: no masking needed
      total_cycles = total_cycles + st.cycles
      total_energy_pj = total_energy_pj + e_pj
      util_weighted = util_weighted + st.utilization * st.cycles
    else:
      total_cycles = total_cycles + xp.where(v, st.cycles, 0.0)
      total_energy_pj = total_energy_pj + xp.where(v, e_pj, 0.0)
      util_weighted = util_weighted + xp.where(
          v, st.utilization * st.cycles, 0.0)
  latency_s = total_cycles / (clock_mhz * 1e6)
  utilization = util_weighted / xp.maximum(total_cycles, 1e-12)
  return latency_s, total_energy_pj * 1e-9, utilization  # pJ -> mJ
