"""Fused device programs for the streaming sweep engine.

The host streaming path (repro.explore.streaming) evaluates a chunk,
copies full latency/power/area arrays device->host (or allocates them on
host), and reduces in numpy.  This module moves the whole
evaluate -> derive-columns -> reduce pipeline into one jitted x64 program
per chunk so that only O(cap) floats cross the device boundary, the
plan's fixed survivor capacity, cut to each count on the host:

  pareto    an exact-superset non-dominated prefilter on device (grouped
            2-D staircase elimination when the objectives allow it, the
            block-decomposed dominance port from
            ``repro.kernels.pareto_front`` otherwise), survivors
            compacted with a sized ``nonzero`` and gathered
  top-k     ``jax.lax.top_k`` on the key column (ties resolve to the
            lowest index == the lowest global row id, exactly like
            ``stable_topk_indices``)
  stats     one (count, mean, M2, min, max) Welford partial per chunk
  histogram fixed-edge bin counts (identical binning to ``np.histogram``)

The host-side accumulators stay the cross-chunk merge (see
``Reducer.fold_payload``), so chunk-order invariance and the
pareto/top-k bit-identity guarantees carry over unchanged: survivor
*values* come from the exact x64 device path, survivor *sets* are exact
supersets (pareto) or exact stable selections (top-k), and the
accumulators re-run the same selection logic they apply to host chunks.

Fallback is per chunk and lazy: every program also returns the full
metric arrays as (unfetched) device buffers; only when a pareto survivor
count overflows ``DevicePlan.cap`` does the host fetch them and fold that
chunk through the ordinary full-frame path.
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Callable, Dict, Optional, Tuple

import numpy as np


def compiles_for_cpu() -> bool:
  """Will this process's jax programs compile for XLA:CPU?

  Decided without initializing a backend (that would latch XLA_FLAGS):
  an initialized backend answers directly; otherwise ``JAX_PLATFORMS``
  names the platform; otherwise jax picks an attached accelerator when
  the PCI bus shows one (the same probe jax runs before loading libtpu).
  """
  import sys
  xb = sys.modules.get("jax._src.xla_bridge")
  if xb is not None and getattr(xb, "_backends", None):
    import jax
    return jax.default_backend() == "cpu"
  platforms = os.environ.get("JAX_PLATFORMS", "").strip()
  if platforms:
    return platforms.split(",")[0].strip().lower() == "cpu"
  from jax._src import hardware_utils
  n_tpu, _ = hardware_utils.num_available_tpu_chips_and_device_id()
  return n_tpu == 0 and not hardware_utils.has_visible_nvidia_gpu()


def ensure_exact_cpu_codegen() -> None:
  """Make XLA:CPU arithmetic bit-compatible with numpy.

  Two default XLA rewrites are each 1 ulp away from numpy's
  separate-op IEEE arithmetic and must be off for the exact device path
  (the transcendental log2/pow divergences are already handled by
  host-precomputing those columns, see
  :func:`repro.core.oracle.batch_inputs`):

    * LLVM contracts ``a*b + c`` chains into FMA instructions — capping
      codegen at AVX (a pre-FMA ISA) disables that;
    * the HLO algebraic simplifier rewrites ``x / const`` into
      ``x * (1/const)`` and reassociates constant multiplies.

  XLA latches its flags at the process's first compilation, so this runs
  from ``VectorOracleBackend(jit=True)`` construction, which precedes
  our program builds.  If your process compiles other jax code first,
  set ``XLA_FLAGS="--xla_cpu_max_isa=AVX
  --xla_disable_hlo_passes=algsimp"`` in the environment yourself
  (``tests/conftest.py`` and ``benchmarks/run.py`` do exactly that).

  In a process that compiles for an accelerator (see
  :func:`compiles_for_cpu`) this does nothing.  XLA_FLAGS reach every
  backend's compiler, and the TPU compiler honours
  ``--xla_disable_hlo_passes=algsimp``, yet cannot become numpy-exact
  with it: the TPU has no float64 units and emulates float64, and on a
  v5e every float64 arithmetic op differs from numpy in the last bits
  with or without the flag.  With it, the joint program even fails to
  run ("HLO transpose is not implemented on TPU").  ``chip_smoke.py``
  measures what the chip gives, op by op.
  """
  if not compiles_for_cpu():
    return
  flags = os.environ.get("XLA_FLAGS", "")
  if "xla_cpu_max_isa" not in flags:
    flags = (flags + " --xla_cpu_max_isa=AVX").strip()
  if "xla_disable_hlo_passes" not in flags:
    flags = (flags + " --xla_disable_hlo_passes=algsimp").strip()
  os.environ["XLA_FLAGS"] = flags


# NOTE: deliberately NOT invoked at import — the float32 fast mode (and
# unrelated jax workloads sharing the process) should keep full codegen.
# The x64 entry points call it: VectorOracleBackend(jit=True,
# precision="x64").__init__, tests/conftest.py, benchmarks/run.py.

_EXACT_PROBE: Optional[bool] = None
_EXACT_WARNED = False

# ISAs without fused multiply-add: capping codegen at any of these keeps
# XLA's a*b+c bit-identical to numpy's two-op sequence.  AVX2 and up fuse.
_FMA_FREE_ISAS = frozenset({"SSE2", "SSE4_1", "SSE4_2", "AVX"})


def check_exact_codegen_env() -> Optional[str]:
  """Static pre-flight check of the exact-codegen environment.

  Unlike :func:`exact_codegen_active` this never compiles (so it cannot
  itself latch the wrong flags); it inspects ``XLA_FLAGS`` and the jax
  import state and returns a human-readable problem description, or
  ``None`` when the environment can deliver bit-parity.  Callers that
  need the contract (``tests/conftest.py``) should fail fast on a
  non-None return instead of discovering a ~1 ulp drift in a parity
  assertion minutes later.
  """
  import sys
  flags = os.environ.get("XLA_FLAGS", "")
  isas = re.findall(r"--xla_cpu_max_isa=(\S+)", flags)
  passes = re.findall(r"--xla_disable_hlo_passes=(\S+)", flags)
  if not isas or not passes:
    return ("XLA_FLAGS is missing the exact-codegen flags "
            "(--xla_cpu_max_isa / --xla_disable_hlo_passes); call "
            "ensure_exact_cpu_codegen() before jax compiles anything")
  if isas[-1].upper() not in _FMA_FREE_ISAS:
    return (f"XLA_FLAGS pins --xla_cpu_max_isa={isas[-1]}, an ISA with "
            "FMA contraction — a*b+c fuses to 1-ulp-different results; "
            "use AVX (or another of "
            f"{sorted(_FMA_FREE_ISAS)})")
  if not any("algsimp" in p.split(",") for p in passes):
    return (f"XLA_FLAGS disables HLO passes ({passes[-1]}) without "
            "including algsimp — the algebraic simplifier rewrites "
            "x/const into x*(1/const) and breaks bit-parity")
  if "jax" in sys.modules:
    # flags latch at the first backend initialization, not at import —
    # an already-initialized backend means they were read without ours
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and getattr(xb, "_backends", None):
      return ("a jax backend was initialized before the exact-codegen "
              "flags were set; XLA latched its flags (and the x64 "
              "default) at that first compilation — set XLA_FLAGS in "
              "the environment before the process starts")
  return None


def exact_codegen_active() -> bool:
  """Probe whether XLA is actually compiling numpy-bit-exact arithmetic.

  :func:`ensure_exact_cpu_codegen` cannot guarantee exactness: the user
  may carry conflicting XLA_FLAGS (e.g. ``--xla_cpu_max_isa=AVX512``),
  or another jax program may have compiled before the flags were set
  (XLA latches flags at the first compilation).  This compiles two
  sentinel expressions covering the known divergences (FMA contraction,
  divide-by-constant rewrite, constant reassociation) and compares
  against numpy.  Cached after the first call.
  """
  global _EXACT_PROBE
  if _EXACT_PROBE is None:
    import jax
    x = np.linspace(0.5, 1e6, 4096)
    y = x[::-1].copy()
    with jax.enable_x64(True):
      got = jax.jit(lambda a, b: (0.028 * a + 0.006 * b,
                                  a / 3.0, a * 0.3 * 0.7))(x, y)
      got = tuple(np.asarray(v) for v in got)
    want = (0.028 * x + 0.006 * y, x / 3.0, x * 0.3 * 0.7)
    _EXACT_PROBE = all(np.array_equal(g, w) for g, w in zip(got, want))
  return _EXACT_PROBE


def warn_if_inexact_codegen() -> None:
  """One-time warning when the exact x64 path cannot deliver bit-parity
  in this process (conflicting XLA_FLAGS / flags latched too late) —
  the backend still runs, but ``parity_max_rel_err == 0.0`` will not
  hold (expect ~1 ulp)."""
  global _EXACT_WARNED
  if _EXACT_WARNED or exact_codegen_active():
    return
  _EXACT_WARNED = True
  import warnings
  if not compiles_for_cpu():
    warnings.warn(
        "VectorOracleBackend(jit=True, precision='x64') is not "
        "bit-identical to numpy on this accelerator: it emulates float64, "
        "so results differ from numpy's in the last bits (chip_smoke.py "
        "measures the difference op by op).", RuntimeWarning, stacklevel=3)
    return
  warnings.warn(
      "VectorOracleBackend(jit=True, precision='x64') cannot be "
      "bit-identical to numpy in this process: XLA compiled with FMA "
      "contraction or algebraic simplification enabled (conflicting "
      "XLA_FLAGS, or another jax program compiled before "
      "ensure_exact_cpu_codegen ran).  Set XLA_FLAGS="
      "\"--xla_cpu_max_isa=AVX --xla_disable_hlo_passes=algsimp\" before "
      "the process's first jax compilation to restore exactness.",
      RuntimeWarning, stacklevel=3)

from repro.core import oracle
from repro.core.dataflow import ConvLayer
from repro.core.table import ConfigTable
from repro.explore import spans
from repro.explore.frame import BASE_COLUMNS, DERIVED_COLUMNS, ResultFrame

# columns the device programs can materialize (frame.column equivalents);
# top1/top1_err additionally need the joint path's per-arch accuracies
DEVICE_COLUMNS = BASE_COLUMNS + DERIVED_COLUMNS
JOINT_COLUMNS = DEVICE_COLUMNS + ("top1", "top1_err")

# columns constant along the HW axis of a joint block (functions of the
# architecture only) — the grouped prefilter may project them out
ARCH_CONSTANT_COLUMNS = frozenset({"top1", "top1_err"})

# default survivor capacity per pareto reducer per chunk; counts above it
# trigger the lazy full-frame fallback for that chunk
DEFAULT_SURVIVOR_CAP = 4096

# staircase elimination rounds: each round removes everything dominated by
# one more front point, so supersets tighten with every round and
# typical per-group fronts (~ln n points) converge well before 32
STAIRCASE_ROUNDS = 32

# block size for the generic (>=3 variable objectives) dominance prefilter
PREFILTER_BLOCK = 128


# ---------------------------------------------------------------------------
# plans: what the reducers need from the device
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParetoSpec:
  cols: Tuple[str, ...]
  maximize: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class TopKSpec:
  col: str
  k: int
  maximize: bool


@dataclasses.dataclass(frozen=True)
class StatsSpec:
  col: str


@dataclasses.dataclass(frozen=True)
class HistSpec:
  col: str
  lo: float
  hi: float
  bins: int


@dataclasses.dataclass(frozen=True)
class DevicePlan:
  """Per-reducer device requests, hashable (part of the jit cache key)."""
  specs: Tuple[Tuple[str, object], ...]  # (reducer name, spec)
  cap: int = DEFAULT_SURVIVOR_CAP

  def __iter__(self):
    return iter(self.specs)


def build_plan(reducers: Dict[str, object], joint: bool,
               cap: int = DEFAULT_SURVIVOR_CAP) -> Optional[DevicePlan]:
  """A DevicePlan covering every reducer, or None when any reducer (or
  any referenced column) is not device-fusable — callers then fall back
  to the plain per-chunk evaluation path."""
  allowed = set(JOINT_COLUMNS if joint else DEVICE_COLUMNS)
  specs = []
  for name, r in reducers.items():
    spec = getattr(r, "device_spec", lambda: None)()
    if spec is None:
      return None
    cols = spec.cols if isinstance(spec, ParetoSpec) else (spec.col,)
    if not set(cols) <= allowed:
      return None
    specs.append((name, spec))
  return DevicePlan(specs=tuple(specs), cap=int(cap))


# ---------------------------------------------------------------------------
# device-side column + prefilter machinery (everything below traces)
# ---------------------------------------------------------------------------

def _derive_columns(lat, pwr, area, jnp, accs=None):
  """The frame.column formulas, op for op (keeps survivor values
  bit-identical to the host frame's derived columns).  All grids are
  (G, M): one group per arch for joint blocks, a single group otherwise.
  """
  cols = {"latency_s": lat, "power_mw": pwr, "area_mm2": area}
  perf = 1.0 / jnp.maximum(lat, 1e-12)
  cols["perf"] = perf
  cols["perf_per_area"] = perf / jnp.maximum(area, 1e-12)
  cols["energy_mj"] = pwr * lat
  if accs is not None:
    top1 = jnp.broadcast_to(accs[:, None], lat.shape)
    cols["top1"] = top1
    cols["top1_err"] = 1.0 - top1
  return cols


def _staircase_mask(x, y, jnp, jax, rounds: Optional[int] = None):
  """(G, M) bool superset of each group's 2-D front (minimize x then y).

  Champion elimination: every round picks the lowest-x not-yet-processed
  survivor per group (i.e. walks the front in x order) and removes
  everything it dominates.  Only truly dominated points are ever removed
  (and champions dominate nobody they tie with), so the result is a
  front superset after ANY number of rounds; rounds only control how
  tight it is — after ``rounds >= front size`` the mask is the union of
  the exact front and points dominated by nothing processed, i.e. the
  exact front plus x-ties.
  """
  if rounds is None:
    rounds = STAIRCASE_ROUNDS
  g = x.shape[0]
  row = jnp.arange(g)

  def body(_, state):
    alive, processed = state
    key = jnp.where(alive & ~processed, x, jnp.inf)
    i = jnp.argmin(key, axis=1)
    cx = jnp.take_along_axis(x, i[:, None], axis=1)
    cy = jnp.take_along_axis(y, i[:, None], axis=1)
    dom = (cx <= x) & (cy <= y) & ((cx < x) | (cy < y))
    return alive & ~dom, processed.at[row, i].set(True)

  alive = jnp.ones(x.shape, bool)
  processed = jnp.zeros(x.shape, bool)
  alive, _ = jax.lax.fori_loop(0, rounds, body, (alive, processed))
  return alive


def _pareto_prefilter(cols, spec: ParetoSpec, grouped: bool, jnp, jax):
  """(G, M) bool exact-superset mask of the chunk front for ``spec``.

  Grouped blocks may project out arch-constant objectives (rows of one
  group tie on them, so within-group dominance on the remaining axes is
  full dominance); cross-group comparisons are only attempted by the
  generic block filter, which keeps every axis.
  """
  mx = set(spec.maximize)
  objs = {c: (-cols[c] if c in mx else cols[c]) for c in spec.cols}
  var = [objs[c] for c in spec.cols
         if not (grouped and c in ARCH_CONSTANT_COLUMNS)]
  if len(var) == 0:  # all objectives tie within every group
    return jnp.ones(next(iter(objs.values())).shape, bool)
  if len(var) == 1:
    v = var[0]
    return v == v.min(axis=1, keepdims=True)
  if len(var) == 2:
    return _staircase_mask(var[0], var[1], jnp, jax)
  from repro.kernels.pareto_front import ops as pf_ops
  obj = jnp.stack([o.reshape(-1) for o in objs.values()], axis=1)
  return pf_ops.block_prefilter_mask(obj, block=PREFILTER_BLOCK).reshape(
      var[0].shape)


def _histogram_counts(v, lo: float, hi: float, bins: int, jnp):
  """np.histogram-identical fixed-edge binning (half-open bins, last
  closed; values pre-clipped into range like HistogramAccumulator)."""
  # host np on purpose: lo/hi/bins are trace constants from the HistSpec,
  # and host-built edges keep binning bit-identical to np.histogram
  edges = np.linspace(float(lo), float(hi), int(bins) + 1)  # repro: ignore[JIT003]
  v = jnp.clip(v.reshape(-1), edges[0], edges[-1])
  idx = jnp.clip(jnp.searchsorted(jnp.asarray(edges), v, side="right") - 1,
                 0, bins - 1)
  return jnp.zeros(bins, jnp.int64 if v.dtype == jnp.float64
                   else jnp.int32).at[idx].add(1)


def _reduce_outputs(cols, plan: DevicePlan, grouped: bool, jnp, jax):
  """The per-reducer output pytree of a fused program."""
  n = cols["latency_s"].size
  base = tuple(cols[c].reshape(-1) for c in ("latency_s", "power_mw",
                                             "area_mm2"))
  out = {}
  for name, spec in plan:
    if isinstance(spec, ParetoSpec):
      mask = _pareto_prefilter(cols, spec, grouped, jnp, jax).reshape(-1)
      idx = jnp.nonzero(mask, size=plan.cap, fill_value=n)[0]
      out[name] = {
          "count": mask.sum(),  # repro: ignore[EXA003] — bool count: integer-exact under any order
          "idx": idx,
          "rows": tuple(jnp.take(b, idx, mode="fill", fill_value=0.0)
                        for b in base),
      }
    elif isinstance(spec, TopKSpec):
      key = cols[spec.col].reshape(-1)
      key = -key if not spec.maximize else key
      k = min(spec.k, n)
      _, idx = jax.lax.top_k(key, k)  # ties -> lowest index == lowest row id
      out[name] = {
          "idx": idx,
          "rows": tuple(jnp.take(b, idx) for b in base),
      }
    elif isinstance(spec, StatsSpec):
      v = cols[spec.col].reshape(-1)
      # Welford partials are outside the bit-identity contract (stats are
      # merge-order-dependent on the host path too); reassociation here
      # moves mean/m2 by ulps, never the survivor sets
      mean = v.mean()  # repro: ignore[EXA003]
      # n is a static trace constant: a single-row chunk has zero spread
      # by definition, and computing (v - mean)**2 for it would turn a
      # non-finite value into a NaN M2 partial (mirrors
      # StatsAccumulator.fold's n == 1 short-circuit)
      m2 = jnp.zeros(()) if n == 1 else ((v - mean) ** 2).sum()  # repro: ignore[EXA003]
      out[name] = {"n": n, "mean": mean, "m2": m2,
                   "min": v.min(), "max": v.max()}
    elif isinstance(spec, HistSpec):
      out[name] = {"counts": _histogram_counts(cols[spec.col], spec.lo,
                                               spec.hi, spec.bins, jnp)}
    else:  # pragma: no cover - build_plan only emits the specs above
      raise TypeError(f"unknown device spec {spec!r}")
  return out


# ---------------------------------------------------------------------------
# program builders (returned callables are pure: backend jits them)
# ---------------------------------------------------------------------------

def named(fn: Callable, name: str) -> Callable:
  """``fn`` under ``name``: jax names the jitted program ``jit_<name>``,
  so a device trace tells the programs apart."""
  fn.__name__ = fn.__qualname__ = name
  return fn


def make_eval_fn(layers: Tuple[ConvLayer, ...],
                 plan: Optional[DevicePlan]) -> Callable:
  """Plain-sweep program: inputs bundle -> (lat, pwr, area)[, reductions].

  With a plan the full metric arrays still come back as device outputs —
  they are the lazy overflow/Collect fallback and cost only their device
  materialization, never a transfer unless fetched.
  """
  import jax
  import jax.numpy as jnp

  def run(inputs):
    ch = oracle.characterize_batch(None, layers, xp=jnp, inputs=inputs)
    full = (ch.latency_s, ch.power_mw, ch.area_mm2)
    if plan is None:
      return full
    cols = _derive_columns(ch.latency_s[None, :], ch.power_mw[None, :],
                           ch.area_mm2[None, :], jnp)
    return full, _reduce_outputs(cols, plan, grouped=False, jnp=jnp, jax=jax)

  return named(run, "eval" if plan is None else "fused_eval")


def make_table_fn(plan: Optional[DevicePlan]) -> Callable:
  """Plain-sweep program over a layer table
  (:func:`repro.core.dataflow.layer_table`): (inputs, cols, counts) ->
  (lat, pwr, area)[, reductions].

  The entries' features and counts enter as arrays, so one jitted
  callable serves every network with as many entries (jax re-traces per
  entry count, not per network, layer shape or routing draw)."""
  import jax
  import jax.numpy as jnp

  def run(inputs, layer_cols, counts):
    ch = oracle.characterize_table(None, layer_cols, counts, xp=jnp,
                                   inputs=inputs)
    full = (ch.latency_s, ch.power_mw, ch.area_mm2)
    if plan is None:
      return full
    cols = _derive_columns(ch.latency_s[None, :], ch.power_mw[None, :],
                           ch.area_mm2[None, :], jnp)
    return full, _reduce_outputs(cols, plan, grouped=False, jnp=jnp, jax=jax)

  return named(run, "eval_table" if plan is None else "fused_eval_table")


def make_joint_fn(plan: Optional[DevicePlan]) -> Callable:
  """Joint-sweep program over the distinct-layer factorization:
  (inputs, unique_cols, slot_ids, valid, accs) ->
  (lat (A, H), pwr (H,), area (H,))[, reductions].

  Stack data enters as arrays (not trace constants), so ONE jitted
  callable serves every arch block of a streaming sweep — jax re-traces
  per shape, not per block.  ``accs`` is consumed only by fused plans;
  plan-less callers pass an empty array.
  """
  import jax
  import jax.numpy as jnp

  def run(inputs, unique_cols, slot_ids, valid, accs):
    ch = oracle.characterize_joint_dedup(None, unique_cols, slot_ids, valid,
                                         xp=jnp, inputs=inputs)
    full = (ch.latency_s, ch.power_mw, ch.area_mm2)
    if plan is None:
      return full
    lat = ch.latency_s
    cols = _derive_columns(
        lat, jnp.broadcast_to(ch.power_mw[None, :], lat.shape),
        jnp.broadcast_to(ch.area_mm2[None, :], lat.shape), jnp, accs=accs)
    return full, _reduce_outputs(cols, plan, grouped=True, jnp=jnp, jax=jax)

  return named(run, "joint" if plan is None else "fused_joint")


def joint_chunk_frame(lat: np.ndarray, pwr: np.ndarray, area: np.ndarray,
                      hw: ConfigTable, network: str, arch_lo: int,
                      accs: np.ndarray,
                      arch_lookup: Tuple[object, ...]) -> ResultFrame:
  """The ordinary full joint chunk frame (what
  ``co_evaluate_table`` + the streaming driver's arch postprocessing
  produce), built from raw (A, H)/(H,) metric arrays — shared by the
  non-fused pending path and the fused overflow fallback."""
  n_archs = lat.shape[0]
  joint = hw.cross(n_archs)
  ids = joint.arch_ids()
  return ResultFrame(
      lat.reshape(-1), np.tile(pwr, n_archs), np.tile(area, n_archs),
      joint.pe_type_strings(), (), network, table=joint,
      extra={"arch_id": ids + arch_lo,
             "top1": np.asarray(accs, np.float64)[ids]},
      arch_lookup=arch_lookup)


# ---------------------------------------------------------------------------
# pending chunks: async dispatch handles the host folds later
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FusedChunk:
  """Resolved fused-chunk result: one payload per reducer (see
  ``Reducer.fold_payload``) plus row counts for engine accounting —
  ``n_transferred`` is how many evaluated rows actually crossed the
  device boundary (the O(cap), cut on the host, not O(chunk_size),
  evidence: ``cap`` rows per in-cap pareto reducer, ``k`` per top-k);
  ``n_overflows`` counts pareto reducers whose survivor count blew the
  plan cap and fell back to the full chunk frame — the first rung of
  the graceful-degradation story (see repro.explore.resilience)."""
  payloads: Dict[str, tuple]
  n_rows: int
  n_transferred: int = 0
  n_overflows: int = 0


class _PendingBase:
  """A dispatched device chunk.  Construction dispatches the program
  (jax async); ``resolve()`` blocks on / fetches only what the reducers
  need.  The streaming engine keeps a small window of these in flight so
  host chunk materialization overlaps device execution."""

  _buffers = None  # output pytree backing is_ready, when tracked

  def resolve(self):
    raise NotImplementedError

  def is_ready(self) -> bool:
    """Non-blocking readiness: True once every tracked device output
    buffer has been computed (jax async dispatch exposes ``is_ready`` on
    arrays) — the fleet layer's straggler polling.  Handles without
    tracked buffers report False (unknown)."""
    if self._buffers is None:
      return False
    import jax
    return all(leaf.is_ready()
               for leaf in jax.tree_util.tree_leaves(self._buffers)
               if hasattr(leaf, "is_ready"))


class PendingFrame(_PendingBase):
  """Non-fused device chunk: resolves to the ordinary (frame, idx)."""

  def __init__(self, finalize: Callable[[], Tuple[ResultFrame, np.ndarray]],
               buffers=None):
    self._finalize = finalize
    self._buffers = buffers

  def resolve(self) -> Tuple[ResultFrame, np.ndarray]:
    return self._finalize()


class PendingFused(_PendingBase):
  """Fused device chunk: resolves to a :class:`FusedChunk`.

  ``full_frame`` builds the chunk's ordinary full frame from the device
  metric arrays — used by overflowing pareto reducers only.
  """

  def __init__(self, outputs, plan: DevicePlan, table: ConfigTable,
               indices: np.ndarray, network: str,
               n_hw: Optional[int] = None, arch_lo: int = 0,
               accs: Optional[np.ndarray] = None,
               arch_lookup: Tuple[object, ...] = ()):
    self._full, self._reduced = outputs
    self._buffers = outputs
    self.plan = plan
    self.table = table
    self.indices = np.asarray(indices, np.int64)
    self.network = network
    self.n_hw = len(table) if n_hw is None else int(n_hw)
    self.arch_lo = int(arch_lo)
    self.accs = accs
    self.arch_lookup = tuple(arch_lookup)
    self._joint = accs is not None

  # -- frame builders -------------------------------------------------------

  def _extras(self, local: np.ndarray):
    if not self._joint:
      return {}
    arch_local = local // self.n_hw
    return {"arch_id": arch_local + self.arch_lo,
            "top1": np.asarray(self.accs, np.float64)[arch_local]}

  def _mini_frame(self, local: np.ndarray, rows) -> ResultFrame:
    lat, pwr, area = (np.asarray(r, np.float64) for r in rows)
    hw_local = local % self.n_hw if self._joint else local
    sub = self.table.select(hw_local)
    return ResultFrame(lat, pwr, area, sub.pe_type_strings(), (),
                       self.network, extra=self._extras(local), table=sub,
                       arch_lookup=self.arch_lookup)

  def full_frame(self) -> Tuple[ResultFrame, np.ndarray]:
    """The chunk's ordinary full frame (lazy device->host fetch)."""
    lat, pwr, area = (np.asarray(a, np.float64) for a in self._full)
    if not self._joint:
      return (ResultFrame(lat, pwr, area, self.table.pe_type_strings(), (),
                          self.network, table=self.table), self.indices)
    return joint_chunk_frame(lat, pwr, area, self.table, self.network,
                             self.arch_lo, self.accs,
                             self.arch_lookup), self.indices

  # -- resolution -----------------------------------------------------------

  def resolve(self) -> FusedChunk:
    """Wait for the program, fetch what every reducer needs (each in-cap
    pareto reducer's survivors whole, at the plan's fixed ``cap``), cut
    those survivors to their count on the host, and build each payload:
    one span per phase, around the loop over reducers.  Cutting on the
    host rather than slicing device arrays keeps every survivor count
    from compiling a slice program of its own."""
    with spans.span("resolve") as resolving:
      with spans.span("wait"):  # the first blocking read
        counts = {name: int(self._reduced[name]["count"])
                  for name, spec in self.plan
                  if isinstance(spec, ParetoSpec)}
      resolving.note(survivors=sum(counts.values()))
      overflows = sum(c > self.plan.cap for c in counts.values())
      with spans.span("fetch"):
        # rare: a count over the cap folds the full chunk instead
        full = self.full_frame() if overflows else None
        transferred = len(self.indices) if overflows else 0
        host = {}
        for name, spec in self.plan:
          out = self._reduced[name]
          if isinstance(spec, TopKSpec) or (
              isinstance(spec, ParetoSpec) and counts[name] <= self.plan.cap):
            host[name] = (np.asarray(out["idx"], np.int64),
                          [np.asarray(r, np.float64) for r in out["rows"]])
            transferred += host[name][0].size
          elif isinstance(spec, StatsSpec):
            host[name] = {k: float(out[k]) if k != "n" else int(out[k])
                          for k in out}
          elif isinstance(spec, HistSpec):
            host[name] = np.asarray(out["counts"], np.int64)
      with spans.span("slice"):  # the padded tail past the count is unread
        for name, c in counts.items():
          if name in host:
            local, rows = host[name]
            host[name] = (local[:c], [r[:c] for r in rows])
      payloads: Dict[str, tuple] = {}
      for name, spec in self.plan:
        if isinstance(spec, StatsSpec):
          payloads[name] = ("stats", host[name])
        elif isinstance(spec, HistSpec):
          payloads[name] = ("hist", host[name])
        elif name not in host:  # an overflowing pareto reducer
          payloads[name] = ("rows",) + full
        else:
          local, rows = host[name]
          payloads[name] = ("rows", self._mini_frame(local, rows),
                            self.indices[local])
      return FusedChunk(payloads=payloads, n_rows=len(self.indices),
                        n_transferred=transferred, n_overflows=overflows)
