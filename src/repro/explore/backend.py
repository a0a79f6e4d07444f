"""Pluggable evaluation backends: how a design point gets its PPA numbers.

Three implementations of the :class:`EvaluationBackend` protocol:

  OracleBackend        slow, exact — full per-design characterization via
                       the synthesis stand-in (``repro.core.oracle``),
                       one Python call per design point
  VectorOracleBackend  the same oracle, array-at-a-time — consumes a
                       :class:`~repro.core.table.ConfigTable` in
                       bounded-memory chunks via the ``*_batch`` formulas;
                       bit-identical to OracleBackend on the numpy path,
                       ~2 orders of magnitude faster, with an optional
                       ``jax.jit`` device path
  PolynomialBackend    fast — QUIDAM's fit-once / evaluate-many polynomial
                       models (``repro.core.ppa``), with in-process fit
                       memoization and ``save``/``load`` to ``.npz`` so
                       sessions and benchmarks never refit; accepts config
                       lists or ConfigTables (the table path predicts
                       without building per-point objects)

All compose the global buffer the same way: the polynomial targets cover
the PE-array subsystem only (the paper's 4-feature vector cannot see GBS),
so the buffer adds on as a pre-characterized SRAM macro via
:func:`gbuf_overheads` (memoized, scalar) / :func:`gbuf_overheads_table`
(vectorized).
"""
from __future__ import annotations

import functools
import hashlib
import os
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core import oracle
from repro.core import ppa as ppa_lib
from repro.core.dataflow import (AcceleratorConfig, ConvLayer, GemmLayer,
                                 LayerStack, layer_table)
from repro.core.pe import PAPER_PE_TYPES
from repro.core.table import ConfigTable
from repro.explore import spans
from repro.explore.frame import ResultFrame

Configs = Union[Sequence[AcceleratorConfig], ConfigTable]

try:  # Protocol is typing-only; keep runtime deps minimal
  from typing import Protocol
except ImportError:  # pragma: no cover - py<3.8
  Protocol = object  # type: ignore[assignment]


class EvaluationBackend(Protocol):
  """Anything that turns (configs, workload) into a ResultFrame.

  ``cfgs`` may be a sequence of per-point dataclasses or a columnar
  :class:`ConfigTable`.  Backends that implement the optional
  ``evaluate_table(table, layers, network)`` method (and advertise
  ``prefers_table = True``) get handed ConfigTables directly by
  :class:`~repro.explore.ExplorationSession`, keeping million-point
  sweeps columnar end to end.
  """
  name: str

  def evaluate(self, cfgs: Configs, layers: Sequence[ConvLayer],
               network: str = "net") -> ResultFrame:
    ...


# ---------------------------------------------------------------------------
# shared global-buffer composition (the one memoized helper)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=65536)
def _gbuf_cached(cfg: AcceleratorConfig) -> Tuple[float, float]:
  return oracle.gbuf_power_mw(cfg), oracle.gbuf_area_mm2(cfg)


def gbuf_overheads(cfgs: Configs) -> Tuple[np.ndarray, np.ndarray]:
  """(power_mw, area_mm2) of the global-buffer SRAM macro per config,
  memoized per unique config across all backends and callers.  ConfigTable
  inputs take the vectorized (unmemoized — it is cheaper than the cache
  lookup loop) path."""
  if isinstance(cfgs, ConfigTable):
    return gbuf_overheads_table(cfgs)
  pwr = np.empty(len(cfgs))
  area = np.empty(len(cfgs))
  for i, c in enumerate(cfgs):
    pwr[i], area[i] = _gbuf_cached(c)
  return pwr, area


def gbuf_overheads_table(table: ConfigTable, xp=np
                         ) -> Tuple[np.ndarray, np.ndarray]:
  """Vectorized :func:`gbuf_overheads` over a ConfigTable."""
  inputs = oracle.batch_inputs(table)
  return (oracle.gbuf_power_mw_batch(table, xp=xp, inputs=inputs),
          oracle.gbuf_area_mm2_batch(table, xp=xp, inputs=inputs))


@functools.lru_cache(maxsize=16)
def _table_of(layers: Tuple) -> Tuple:
  """A GEMM network's layer table, once per layer tuple (the device path
  takes it as program arguments, see :func:`repro.explore.device
  .make_table_fn`)."""
  return layer_table(layers)


# ---------------------------------------------------------------------------
# oracle backends (exact): scalar loop + vectorized chunked sibling
# ---------------------------------------------------------------------------

class OracleBackend:
  """Full characterization per design — the synthesis stand-in."""
  name = "oracle"

  def evaluate(self, cfgs: Configs, layers: Sequence[ConvLayer],
               network: str = "net") -> ResultFrame:
    cfgs = list(cfgs)
    lat = np.empty(len(cfgs))
    pwr = np.empty(len(cfgs))
    area = np.empty(len(cfgs))
    for i, cfg in enumerate(cfgs):
      ch = oracle.characterize(cfg, layers)
      lat[i], pwr[i], area[i] = ch.latency_s, ch.power_mw, ch.area_mm2
    return ResultFrame(lat, pwr, area,
                       np.asarray([c.pe_type for c in cfgs]),
                       tuple(cfgs), network)


class _LRUCache:
  """Tiny LRU for compiled executables: long-lived sessions sweeping many
  networks must not accumulate one jitted program per layer tuple.
  Lock-guarded: streaming pool workers may share one backend."""

  def __init__(self, maxsize: int):
    import threading
    from collections import OrderedDict
    self.maxsize = int(maxsize)
    self._d: "OrderedDict" = OrderedDict()
    self._lock = threading.Lock()

  def __len__(self) -> int:
    return len(self._d)

  def get(self, key):
    with self._lock:
      if key not in self._d:
        return None
      self._d.move_to_end(key)
      return self._d[key]

  def put(self, key, value) -> None:
    with self._lock:
      self._d[key] = value
      self._d.move_to_end(key)
      while len(self._d) > self.maxsize:
        self._d.popitem(last=False)


class VectorOracleBackend:
  """The synthesis stand-in, array-at-a-time over ConfigTables.

  Evaluates design points in bounded-memory chunks of ``chunk_size`` rows
  through the vectorized oracle/dataflow formulas.  On the default numpy
  path results are bit-identical to :class:`OracleBackend`.

  ``jit=True`` runs the per-chunk formulas under ``jax.jit`` as a
  first-class exact backend: the default ``precision="x64"`` traces with
  float64 enabled and host-precomputed transcendental columns, and
  derives the variation columns on the device from their uint64 keys
  (see :func:`repro.core.oracle.batch_inputs`), so device results are
  **bit-identical** to the numpy path; ``precision="float32"`` keeps the
  old approximate fast mode, with no 64-bit integers, so its variation
  columns come from the host.  Joint sweeps compile the distinct-layer
  factorization with the stack as a traced input, so one executable
  serves every arch block of a streaming sweep.  A program runs on one
  device: the default one, or the device a
  :class:`repro.explore.fleet.DevicePool` pins the chunk to.

  The streaming engine additionally uses the ``*_pending`` entry points:
  chunks dispatch asynchronously (jax futures) and resolve later, and
  with a :class:`repro.explore.device.DevicePlan` the whole
  evaluate+reduce pipeline is fused on device so only O(cap) floats
  come back per chunk, cut to the survivors on the host.
  """
  name = "vector-oracle"
  prefers_table = True

  # compiled-program cache bound (stack/layers enter as traced inputs, so
  # entries are per (path, plan, precision), not per sweep content)
  JIT_CACHE_SIZE = 8

  def __init__(self, chunk_size: int = 65536, jit: bool = False,
               precision: str = "x64"):
    if chunk_size <= 0:
      raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    if precision not in ("x64", "float32"):
      raise ValueError(f"precision must be 'x64' or 'float32', "
                       f"got {precision!r}")
    self.chunk_size = chunk_size
    self.jit = jit
    self.precision = precision
    self._jit_cache = _LRUCache(self.JIT_CACHE_SIZE)
    import threading
    self._tls = threading.local()
    if jit and precision == "x64":
      # must precede this process's first XLA compilation (see device.py)
      from repro.explore.device import ensure_exact_cpu_codegen
      ensure_exact_cpu_codegen()

  def _scratch(self) -> Dict:
    """Per-worker-thread reusable feature-temporary buffers (numpy path
    only: the jit path hands jax freshly allocated arrays, which may be
    transferred asynchronously)."""
    d = getattr(self._tls, "scratch", None)
    if d is None:
      d = {}
      self._tls.scratch = d
    return d

  def _eval_chunk(self, chunk: ConfigTable, layers: Sequence[ConvLayer]):
    """numpy chunk evaluation, reusing this worker's scratch buffers."""
    inputs = oracle.batch_inputs(chunk, scratch=self._scratch())
    ch = oracle.characterize_batch(None, layers, inputs=inputs)
    return ch.latency_s, ch.power_mw, ch.area_mm2

  def _co_eval_chunk(self, chunk: ConfigTable, stack: LayerStack):
    """numpy joint chunk evaluation with scratch reuse."""
    inputs = oracle.batch_inputs(chunk, scratch=self._scratch())
    ch = oracle.characterize_joint(None, stack, inputs=inputs)
    return ch.latency_s, ch.power_mw, ch.area_mm2

  def evaluate(self, cfgs: Configs, layers: Sequence[ConvLayer],
               network: str = "net") -> ResultFrame:
    """Config lists are converted to a table; the frame keeps whichever
    design-point representation came in."""
    if isinstance(cfgs, ConfigTable):
      return self.evaluate_table(cfgs, layers, network)
    cfgs = list(cfgs)
    frame = self.evaluate_table(ConfigTable.from_configs(cfgs), layers,
                                network)
    frame.cfgs = tuple(cfgs)
    return frame

  def evaluate_table(self, table: ConfigTable, layers: Sequence[ConvLayer],
                     network: str = "net") -> ResultFrame:
    n = len(table)
    lat = np.empty(n)
    pwr = np.empty(n)
    area = np.empty(n)
    lo = 0
    for chunk in table.chunks(self.chunk_size):
      if self.jit:
        l, p, a = self._eval_chunk_jax(chunk, tuple(layers))
      else:
        l, p, a = self._eval_chunk(chunk, layers)
      hi = lo + len(chunk)
      lat[lo:hi], pwr[lo:hi], area[lo:hi] = l, p, a
      lo = hi
    return ResultFrame(lat, pwr, area, table.pe_type_strings(), (),
                       network, table=table)

  def co_evaluate_table(self, hw: ConfigTable, stack: LayerStack,
                        network: str = "coexplore") -> ResultFrame:
    """Joint HW x NN sweep: every stack architecture against every HW row.

    Evaluates ``characterize_joint`` over bounded-memory HW chunks (the
    working set is ``n_archs x hw_chunk`` where
    ``hw_chunk = chunk_size // n_archs``); clock/power/area are computed
    once per HW row, latency/energy once per pair.  Returns an arch-major
    joint frame (row ``a * n_hw + h``) carrying a lazy
    :class:`~repro.core.table.JointTable` plus an ``arch_id`` extra
    column — the caller (session) attaches ``top1`` and ``arch_lookup``.
    Bit-identical (numpy path) to the scalar per-(arch, hw) loop.
    """
    n_hw, n_archs = len(hw), stack.n_archs
    lat = np.empty((n_archs, n_hw))
    pwr = np.empty(n_hw)
    area = np.empty(n_hw)
    hw_chunk = max(1, self.chunk_size // max(n_archs, 1))
    dedup = stack.dedup_slots() if self.jit else None
    lo = 0
    for chunk in hw.chunks(hw_chunk):
      if self.jit:
        l, p, a = self._co_eval_chunk_jax(chunk, stack, dedup)
      else:
        l, p, a = self._co_eval_chunk(chunk, stack)
      hi = lo + len(chunk)
      lat[:, lo:hi], pwr[lo:hi], area[lo:hi] = l, p, a
      lo = hi
    joint = hw.cross(n_archs)
    return ResultFrame(
        lat.reshape(-1), np.tile(pwr, n_archs), np.tile(area, n_archs),
        joint.pe_type_strings(), (), network, table=joint,
        extra={"arch_id": joint.arch_ids()})

  # -- host fallback rungs --------------------------------------------------
  # The degradation ladder's terminal rung (repro.explore.resilience):
  # same formulas, numpy only — never touches jax even when ``jit=True``,
  # so a compile/OOM/transfer failure cannot recur here.  Bit-identical
  # to the device path by the exact-codegen parity contract.

  def host_evaluate_table(self, table: ConfigTable,
                          layers: Sequence[ConvLayer],
                          network: str = "net") -> ResultFrame:
    n = len(table)
    lat = np.empty(n)
    pwr = np.empty(n)
    area = np.empty(n)
    lo = 0
    for chunk in table.chunks(self.chunk_size):
      l, p, a = self._eval_chunk(chunk, layers)
      hi = lo + len(chunk)
      lat[lo:hi], pwr[lo:hi], area[lo:hi] = l, p, a
      lo = hi
    return ResultFrame(lat, pwr, area, table.pe_type_strings(), (),
                       network, table=table)

  def host_co_evaluate_table(self, hw: ConfigTable, stack: LayerStack,
                             network: str = "coexplore") -> ResultFrame:
    n_hw, n_archs = len(hw), stack.n_archs
    lat = np.empty((n_archs, n_hw))
    pwr = np.empty(n_hw)
    area = np.empty(n_hw)
    hw_chunk = max(1, self.chunk_size // max(n_archs, 1))
    lo = 0
    for chunk in hw.chunks(hw_chunk):
      l, p, a = self._co_eval_chunk(chunk, stack)
      hi = lo + len(chunk)
      lat[:, lo:hi], pwr[lo:hi], area[lo:hi] = l, p, a
      lo = hi
    joint = hw.cross(n_archs)
    return ResultFrame(
        lat.reshape(-1), np.tile(pwr, n_archs), np.tile(area, n_archs),
        joint.pe_type_strings(), (), network, table=joint,
        extra={"arch_id": joint.arch_ids()})

  # -- optional device path -------------------------------------------------
  # Joint programs take the sweep content (inputs bundle, dedup'd stack
  # arrays) as arguments — one LRU entry per (path kind, plan, precision),
  # jax handles shape specialization.  So do plain sweeps of a network
  # with GEMM layers: their layer table enters as arrays
  # (``eval_table`` / ``fused_eval_table``), one program per entry count.
  # Plain sweeps of a conv network still bake the layer tuple into the
  # trace (layer features are scalars there, and one sweep evaluates one
  # network), so their entries are per layer tuple and sessions sweeping
  # many networks recompile under LRU eviction.

  def _x64(self):
    """Precision context: trace/run with float64 for the exact path."""
    if self.precision == "x64":
      import jax
      return jax.enable_x64(True)
    import contextlib
    return contextlib.nullcontext()

  def _cached_fn(self, key, build):
    fn = self._jit_cache.get(key)
    if fn is None:
      if self.precision == "x64":
        from repro.explore.device import warn_if_inexact_codegen
        warn_if_inexact_codegen()
      fn = build()
      self._jit_cache.put(key, fn)
    return fn

  @staticmethod
  def _jit(fn):
    import jax
    kwargs = {}
    if jax.default_backend() != "cpu":
      # chunk input buffers are single-use: let XLA reuse their memory
      kwargs["donate_argnums"] = (0,)
    return jax.jit(fn, **kwargs)

  @staticmethod
  def _pinned():
    """The fleet layer's thread-local device pin (None: default
    placement) — see :func:`repro.explore.fleet.pin`."""
    from repro.explore import fleet
    return fleet.pinned_device()

  @staticmethod
  def _place(inputs, dev, commit: bool = False):
    """Copy a chunk's input columns (any pytree of arrays: the columns
    and a layer table) to the pinned device, so the jitted program
    executes there; with ``commit`` and no pin, to the default device,
    so that the copy is a stage of its own rather than part of the call.
    Unpinned, uncommitted inputs stay on the host (the jitted call
    copies them to the default device).  Must run inside the ``_x64``
    context — ``device_put`` canonicalizes dtypes, and float64 inputs
    would be silently downcast outside it."""
    if dev is None and not commit:
      return inputs
    import jax
    with spans.span("place"):
      spans.count_bytes(sum(v.nbytes
                            for v in jax.tree_util.tree_leaves(inputs)))
      return jax.device_put(inputs, dev)

  def _eval_fn(self, layers: Tuple[ConvLayer, ...], plan=None):
    from repro.explore import device as device_lib
    return self._cached_fn(
        ("eval", layers, plan, self.precision),
        lambda: self._jit(device_lib.make_eval_fn(layers, plan)))

  def _table_fn(self, plan=None):
    from repro.explore import device as device_lib
    return self._cached_fn(
        ("eval_table", plan, self.precision),
        lambda: self._jit(device_lib.make_table_fn(plan)))

  def _program(self, layers: Tuple[ConvLayer, ...], plan=None):
    """The plain-sweep program for ``layers`` and the arguments it takes
    after the input columns: a network with GEMM layers runs the table
    program over its layer table, a conv network the program its layers
    are baked into."""
    if any(isinstance(l, GemmLayer) for l in layers):
      return self._table_fn(plan), _table_of(layers)
    return self._eval_fn(layers, plan), ()

  def _joint_fn(self, plan=None):
    from repro.explore import device as device_lib
    return self._cached_fn(
        ("joint", plan, self.precision),
        lambda: self._jit(device_lib.make_joint_fn(plan)))

  def _inputs(self, table: ConfigTable, n_rows: int) -> Dict:
    """A device program's input bundle for ``table``, whose chunk has
    ``n_rows`` result rows.  An x64 program derives the variation
    columns itself; float32 runs without 64-bit integers and is handed
    the host's."""
    on_device = self.precision == "x64"
    inputs = oracle.batch_inputs(table, device_variations=on_device)
    if on_device:
      spans.count_var_rows(n_rows)
    return inputs

  def _eval_chunk_jax(self, chunk: ConfigTable,
                      layers: Tuple[ConvLayer, ...]):
    import jax
    inputs = self._inputs(chunk, len(chunk))
    with self._x64():
      fn, args = self._program(layers)
      l, p, a = fn(inputs, *args)
    return (np.asarray(jax.device_get(l), np.float64),
            np.asarray(jax.device_get(p), np.float64),
            np.asarray(jax.device_get(a), np.float64))

  def _co_eval_chunk_jax(self, chunk: ConfigTable, stack: LayerStack,
                         dedup=None):
    import jax
    inputs = self._inputs(chunk, len(chunk) * stack.n_archs)
    unique_cols, slot_ids = stack.dedup_slots() if dedup is None else dedup
    with self._x64():
      # accs is only consumed by fused plans; the joint program's
      # signature still takes it, so an empty array stands in
      l, p, a = self._joint_fn()(inputs, unique_cols, slot_ids,
                                 stack.valid, np.zeros(0))
    return (np.asarray(jax.device_get(l), np.float64),
            np.asarray(jax.device_get(p), np.float64),
            np.asarray(jax.device_get(a), np.float64))

  # -- streaming entry points: async dispatch + optional fused reduction ----

  def eval_pending(self, table: ConfigTable, layers: Sequence[ConvLayer],
                   network: str, idx: np.ndarray):
    """Dispatch one streaming chunk; the returned PendingFrame resolves
    to the same (frame, idx) the numpy task path produces."""
    import jax
    from repro.explore import device as device_lib
    layers = tuple(layers)
    with spans.span("dispatch"):
      with spans.span("batch_inputs"):
        inputs = self._inputs(table, len(table))
      dev = self._pinned()
      with self._x64():
        fn, args = self._program(layers)
        inputs, args = self._place((inputs, args), dev)
        with spans.span("launch"):
          out = fn(inputs, *args)

    def finalize():
      l, p, a = (np.asarray(jax.device_get(o), np.float64) for o in out)
      return ResultFrame(l, p, a, table.pe_type_strings(), (), network,
                         table=table), idx

    return device_lib.PendingFrame(finalize, buffers=out)

  def co_eval_pending(self, hw: ConfigTable, stack: LayerStack, network: str,
                      idx: np.ndarray, arch_lo: int, accs: np.ndarray,
                      arch_lookup: Tuple[object, ...], dedup=None):
    """Joint twin of :meth:`eval_pending` (arch columns attached on
    resolve, matching the host streaming task)."""
    import jax
    from repro.explore import device as device_lib
    with spans.span("dispatch"):
      with spans.span("batch_inputs"):
        inputs = self._inputs(hw, len(hw) * stack.n_archs)
      unique_cols, slot_ids = stack.dedup_slots() if dedup is None \
          else dedup
      dev = self._pinned()
      with self._x64():
        inputs = self._place(inputs, dev)
        with spans.span("launch"):
          out = self._joint_fn()(inputs, unique_cols, slot_ids,
                                 stack.valid, np.zeros(0))

    def finalize():
      lat, pwr, area = (np.asarray(jax.device_get(o), np.float64)
                        for o in out)
      return device_lib.joint_chunk_frame(
          lat, pwr, area, hw, network, arch_lo, accs, arch_lookup), idx

    return device_lib.PendingFrame(finalize, buffers=out)

  def fused_eval_pending(self, table: ConfigTable,
                         layers: Sequence[ConvLayer], network: str,
                         plan, idx: np.ndarray):
    """Dispatch one fused evaluate+reduce chunk (see
    :mod:`repro.explore.device`); resolves to per-reducer payloads with
    O(cap) device->host transfer, cut on the host."""
    from repro.explore import device as device_lib
    layers = tuple(layers)
    with spans.span("dispatch"):
      with spans.span("batch_inputs"):
        inputs = self._inputs(table, len(table))
      with self._x64():
        fn, args = self._program(layers, plan)
        inputs, args = self._place((inputs, args), self._pinned(),
                                   commit=True)
        with spans.span("launch"):
          outputs = fn(inputs, *args)
    return device_lib.PendingFused(outputs, plan, table, idx, network)

  def fused_co_eval_pending(self, hw: ConfigTable, stack: LayerStack,
                            network: str, plan, idx: np.ndarray,
                            arch_lo: int, accs: np.ndarray,
                            arch_lookup: Tuple[object, ...], dedup=None):
    """Joint twin of :meth:`fused_eval_pending`."""
    from repro.explore import device as device_lib
    accs = np.asarray(accs, np.float64)
    with spans.span("dispatch"):
      with spans.span("batch_inputs"):
        inputs = self._inputs(hw, len(hw) * stack.n_archs)
      unique_cols, slot_ids = stack.dedup_slots() if dedup is None \
          else dedup
      with self._x64():
        inputs = self._place(inputs, self._pinned(), commit=True)
        with spans.span("launch"):
          outputs = self._joint_fn(plan)(inputs, unique_cols, slot_ids,
                                         stack.valid, accs)
    return device_lib.PendingFused(outputs, plan, hw, idx, network,
                                   n_hw=len(hw), arch_lo=arch_lo, accs=accs,
                                   arch_lookup=arch_lookup)


# ---------------------------------------------------------------------------
# polynomial backend (fast, fit-once)
# ---------------------------------------------------------------------------

def _layers_fingerprint(layers: Optional[Sequence[ConvLayer]]) -> str:
  if layers is None:
    return "default-workloads"
  blob = repr(tuple((l.name, l.features()) for l in layers))
  return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _fit_key(pe_types: Tuple[str, ...], degree: int, n_train: int,
             seed: int, layers: Optional[Sequence[ConvLayer]]
             ) -> Tuple[str, ...]:
  # oracle.ORACLE_VERSION is part of the fingerprint: a cache fitted
  # against older oracle outputs must refit, not silently load
  return (",".join(pe_types), str(degree), str(n_train), str(seed),
          _layers_fingerprint(layers), f"oracle-v{oracle.ORACLE_VERSION}")


# in-process fit-once cache: identical fit requests share one model bundle
_FIT_CACHE: Dict[Tuple[str, ...], Dict[str, ppa_lib.PPAModels]] = {}

_MODEL_FIELDS = ("exponents", "col_scale", "coef")
_MODEL_SCALARS = ("degree", "y_scale", "log_target")
_TARGETS = ("power", "area", "latency")
_FORMAT_VERSION = 1


class PolynomialBackend:
  """QUIDAM's 3-4-orders-of-magnitude fast path over the PPA models."""
  name = "polynomial"

  def __init__(self, models: Dict[str, ppa_lib.PPAModels],
               loaded_from: Optional[str] = None):
    self.models = dict(models)
    self.loaded_from = loaded_from

  @property
  def pe_types(self) -> Tuple[str, ...]:
    return tuple(self.models)

  # -- fitting --------------------------------------------------------------

  @classmethod
  def fit(cls, pe_types: Sequence[str] = PAPER_PE_TYPES, degree: int = 5,
          n_train: int = 240, layers: Optional[Sequence[ConvLayer]] = None,
          seed: int = 0) -> "PolynomialBackend":
    """Characterize + fit once per PE type (seed offset i per type, like
    the legacy explorer); identical requests reuse the in-process cache."""
    pe_types = tuple(pe_types)
    key = _fit_key(pe_types, degree, n_train, seed, layers)
    if key not in _FIT_CACHE:
      _FIT_CACHE[key] = {
          t: ppa_lib.fit_ppa_models(t, degree=degree, n_train=n_train,
                                    layers=layers, seed=seed + i)
          for i, t in enumerate(pe_types)}
    return cls(_FIT_CACHE[key], loaded_from=None)

  @classmethod
  def fit_or_load(cls, path: str, pe_types: Sequence[str] = PAPER_PE_TYPES,
                  degree: int = 5, n_train: int = 240,
                  layers: Optional[Sequence[ConvLayer]] = None,
                  seed: int = 0) -> "PolynomialBackend":
    """Load fitted models from `path` when its fit fingerprint matches;
    otherwise fit fresh and save (benchmarks never refit across runs)."""
    want = "|".join(_fit_key(tuple(pe_types), degree, n_train, seed, layers))
    if os.path.exists(path):
      try:
        with np.load(path) as data:
          if str(data["meta/fit_key"]) == want:
            return cls._from_npz(data, path)
      # corrupt/stale/foreign cache file -> refit and overwrite below
      except Exception:  # repro: ignore[ROB001]
        pass
    backend = cls.fit(pe_types, degree, n_train, layers, seed)
    backend.save(path, fit_key=want)
    return backend

  # -- persistence ----------------------------------------------------------

  def save(self, path: str, fit_key: str = "") -> None:
    """Serialize every PolyModel exactly (float64 .npz: predictions after
    `load` are bit-identical)."""
    arrays: Dict[str, np.ndarray] = {
        "meta/version": np.asarray(_FORMAT_VERSION),
        "meta/pe_types": np.asarray(list(self.models)),
        "meta/fit_key": np.asarray(fit_key),
    }
    for t, bundle in self.models.items():
      arrays[f"{t}/degree"] = np.asarray(bundle.degree)
      for target in _TARGETS:
        model: ppa_lib.PolyModel = getattr(bundle, target)
        base = f"{t}/{target}"
        arrays[f"{base}/exponents"] = model.exponents
        arrays[f"{base}/col_scale"] = model.col_scale
        arrays[f"{base}/coef"] = model.coef
        arrays[f"{base}/degree"] = np.asarray(model.degree)
        arrays[f"{base}/y_scale"] = np.asarray(model.y_scale)
        arrays[f"{base}/log_target"] = np.asarray(model.log_target)
    d = os.path.dirname(path)
    if d:
      os.makedirs(d, exist_ok=True)
    np.savez(path, **arrays)

  @classmethod
  def load(cls, path: str) -> "PolynomialBackend":
    with np.load(path) as data:
      return cls._from_npz(data, path)

  @classmethod
  def _from_npz(cls, data, path: str) -> "PolynomialBackend":
    version = int(data["meta/version"])
    if version != _FORMAT_VERSION:
      raise ValueError(f"{path}: unsupported model-bundle version {version}")
    models = {}
    for t in data["meta/pe_types"]:
      t = str(t)
      parts = {}
      for target in _TARGETS:
        base = f"{t}/{target}"
        parts[target] = ppa_lib.PolyModel(
            degree=int(data[f"{base}/degree"]),
            exponents=data[f"{base}/exponents"],
            col_scale=data[f"{base}/col_scale"],
            coef=data[f"{base}/coef"],
            y_scale=float(data[f"{base}/y_scale"]),
            log_target=bool(data[f"{base}/log_target"]))
      models[t] = ppa_lib.PPAModels(pe_type=t, degree=int(data[f"{t}/degree"]),
                                    **parts)
    return cls(models, loaded_from=path)

  # -- evaluation -----------------------------------------------------------

  def evaluate(self, cfgs: Configs, layers: Sequence[ConvLayer],
               network: str = "net") -> ResultFrame:
    """Batched prediction, grouped by PE type (one model set per type).
    ConfigTables take the fully columnar path."""
    if isinstance(cfgs, ConfigTable):
      return self.evaluate_table(cfgs, layers, network)
    cfgs = list(cfgs)
    by_type: Dict[str, List[int]] = {}
    for i, c in enumerate(cfgs):
      by_type.setdefault(c.pe_type, []).append(i)
    missing = set(by_type) - set(self.models)
    if missing:
      raise KeyError(f"backend has no models for PE types {sorted(missing)}; "
                     f"fitted types: {sorted(self.models)}")
    lat = np.zeros(len(cfgs))
    pwr = np.zeros(len(cfgs))
    area = np.zeros(len(cfgs))
    for pe_type, idxs in by_type.items():
      sub = [cfgs[i] for i in idxs]
      m = self.models[pe_type]
      lat[idxs] = np.maximum(m.predict_network_latency_s(sub, layers), 1e-9)
      gb_p, gb_a = gbuf_overheads(sub)
      pwr[idxs] = np.maximum(m.predict_power_mw(sub), 1e-3) + gb_p
      area[idxs] = np.maximum(m.predict_area_mm2(sub), 1e-6) + gb_a
    return ResultFrame(lat, pwr, area,
                       np.asarray([c.pe_type for c in cfgs]),
                       tuple(cfgs), network)

  def evaluate_table(self, table: ConfigTable, layers: Sequence[ConvLayer],
                     network: str = "net",
                     chunk_size: int = 32768) -> ResultFrame:
    """Columnar prediction over a ConfigTable, per-PE-type model sets, in
    bounded-memory chunks (the latency feature matrix is rows x layers
    wide — chunking caps it at ``chunk_size * len(layers)`` rows)."""
    missing = {t for t, idx in table.groups_by_type()} - set(self.models)
    if missing:
      raise KeyError(f"backend has no models for PE types {sorted(missing)}; "
                     f"fitted types: {sorted(self.models)}")
    n = len(table)
    lat = np.zeros(n)
    pwr = np.zeros(n)
    area = np.zeros(n)
    for pe_type, idxs in table.groups_by_type():
      m = self.models[pe_type]
      for lo in range(0, idxs.size, chunk_size):
        sel = idxs[lo:lo + chunk_size]
        sub = table.select(sel)
        lat[sel] = np.maximum(
            m.predict_network_latency_s(sub, layers), 1e-9)
        gb_p, gb_a = gbuf_overheads_table(sub)
        pwr[sel] = np.maximum(m.predict_power_mw(sub), 1e-3) + gb_p
        area[sel] = np.maximum(m.predict_area_mm2(sub), 1e-6) + gb_a
    return ResultFrame(lat, pwr, area, table.pe_type_strings(), (),
                       network, table=table)

  def co_evaluate_table(self, hw: ConfigTable, stack: LayerStack,
                        network: str = "coexplore",
                        chunk_size: int = 32768) -> ResultFrame:
    """Joint HW x NN sweep through the fitted models.

    Power/area (+ the memoized global-buffer macro) are predicted once
    per HW row; latency is predicted per (arch, HW) pair from the stack's
    precomputed feature tensors — no per-pair Python objects, and the
    per-arch predictions are bit-identical to
    ``predict_network_latency_s(sub, arch_layers)`` on the scalar loop.
    Returns the same arch-major joint frame as
    :meth:`VectorOracleBackend.co_evaluate_table`.
    """
    missing = {t for t, idx in hw.groups_by_type()} - set(self.models)
    if missing:
      raise KeyError(f"backend has no models for PE types {sorted(missing)}; "
                     f"fitted types: {sorted(self.models)}")
    n_hw, n_archs = len(hw), stack.n_archs
    lat = np.zeros((n_archs, n_hw))
    pwr = np.zeros(n_hw)
    area = np.zeros(n_hw)
    feats = stack.features()
    n_layers = stack.n_layers()
    hw_chunk = max(1, chunk_size // max(stack.max_layers, 1))
    for pe_type, idxs in hw.groups_by_type():
      m = self.models[pe_type]
      for lo in range(0, idxs.size, hw_chunk):
        sel = idxs[lo:lo + hw_chunk]
        sub = hw.select(sel)
        gb_p, gb_a = gbuf_overheads_table(sub)
        pwr[sel] = np.maximum(m.predict_power_mw(sub), 1e-3) + gb_p
        area[sel] = np.maximum(m.predict_area_mm2(sub), 1e-6) + gb_a
        hw_feats = sub.latency_hw_features()
        for a in range(n_archs):
          lf = feats[a, :int(n_layers[a])]
          lat[a, sel] = np.maximum(
              m.predict_network_latency_feats(hw_feats, lf), 1e-9)
    joint = hw.cross(n_archs)
    return ResultFrame(
        lat.reshape(-1), np.tile(pwr, n_archs), np.tile(area, n_archs),
        joint.pe_type_strings(), (), network, table=joint,
        extra={"arch_id": joint.arch_ids()})
