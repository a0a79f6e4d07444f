"""repro.explore — the unified design-space exploration API.

This package is the single public entry point for QUIDAM-style
fit-once / evaluate-many DSE and HW x NN co-exploration:

  DesignSpace          declarative space spec: axes (from HW_RANGES), PE
                       types, constraints; grid/random/stratified sampling
                       with deterministic seeds; list or ConfigTable
                       materialization                          [space]
  ConfigTable          struct-of-arrays design points — the input-side
                       twin of ResultFrame (re-export of
                       repro.core.table)                        [table]
  JointTable           lazy archs x ConfigTable cross product for HW x NN
                       co-exploration (``table.cross(n_archs)``); pairs
                       exist only as integer index arithmetic   [table]
  LayerStack           padded (n_archs, max_layers) layer-feature tensors
                       feeding the joint batch dataflow model
                       (re-export of repro.core.dataflow)     [dataflow]
  EvaluationBackend    protocol turning (configs, workload) -> results
    OracleBackend      slow, exact per-design characterization
    VectorOracleBackend  the same oracle vectorized over ConfigTables in
                       bounded-memory chunks (optional jax.jit path)
    PolynomialBackend  fast polynomial PPA models; fit-once cached,
                       save/load to .npz; list or table inputs  [backend]
  ResultFrame          columnar (struct-of-arrays) results with vectorized
                       .pareto(), .normalize(), .stats(), .top_k() [frame]
  ExplorationSession   facade driving plain DSE and co-exploration over
                       the same backend + space                 [session]
  guided search        NSGA-II-style multi-objective optimizer over the
                       evaluate pipeline — one generation == one chunk
                       (device-resident on a jit backend), surrogate
                       screening by expected hypervolume gain, fronts
                       folded through ParetoAccumulator:
                       ``session.optimize(...)``; front-quality helpers
                       ``hypervolume``/``nondominated_ranks``/
                       ``crowding_distance``                     [search]
  streaming engine     constant-memory, parallel sweeps with online
                       reduction: ParetoAccumulator, TopKAccumulator,
                       StatsAccumulator, HistogramAccumulator fold lazy
                       chunks (``DesignSpace.iter_tables`` /
                       ``JointTable.block_slices``) into survivors-only
                       results — ``session.explore(stream=True,
                       reducers=...)`` / ``co_explore(stream=True)``
                                                              [streaming]
  device programs      the ``VectorOracleBackend(jit=True)`` streaming
                       path: exact x64 evaluation bit-identical to numpy,
                       fused on-device pareto/top-k/stats reduction with
                       O(cap) transfer, async dispatch-ahead
                       (imported lazily — see note below)        [device]
  resilience           fault-tolerant sweeps: chunk retry (RetryPolicy),
                       graceful device->host degradation + watchdog
                       (ResiliencePolicy), journaled checkpoint/resume
                       (SweepJournal + ``resume_from=``), deterministic
                       fault injection (FaultPlan) — results stay
                       bit-identical through all of it        [resilience]
  fleet execution      elastic device-fleet sweeps: one shared DevicePool
                       health registry (per-device EWMA latency +
                       circuit breakers), straggler speculation, elastic
                       resharding on device loss, and a silent-data-
                       corruption sentinel built on the exact-parity
                       contract — ``run_stream(..., pool=DevicePool())``
                       or ``stream_explore(..., pool=...)``       [fleet]
  exploration service  concurrent sessions over one shared executor:
                       admission control + typed backpressure, per-request
                       deadlines and cooperative cancellation, a shared
                       device circuit breaker, fair round-robin
                       interleaving (ExplorationService)         [service]
  result store         content-addressed crash-safe cache of finished
                       sweeps (atomic writes, sha256 self-checksums,
                       quarantine) + delta-sweeps re-evaluating only an
                       edited axis' new subgrid (ResultStore,
                       cached_stream_explore)                     [store]

Quickstart::

    from repro.explore import (DesignSpace, ExplorationSession,
                               PolynomialBackend, VectorOracleBackend)
    from repro.core.workloads import get_network

    layers = get_network("resnet20")
    backend = PolynomialBackend.fit(layers=layers)   # or .fit_or_load(path)
    frame = ExplorationSession(backend).explore(layers, "resnet20")
    ppa_n, energy_n = frame.normalize(ref="best-int16")
    best = frame.top_k(1, by="perf_per_area")

    # exact-oracle sweep over 1M design points, fully vectorized:
    session = ExplorationSession(VectorOracleBackend(chunk_size=65536))
    big = session.explore(layers, "resnet20", n_per_type=250_000)

    # joint HW x NN co-exploration, also vectorized (arch features stack
    # once; HW x arch pairs never become Python objects):
    joint = session.co_explore(arch_accs, n_hw_per_type=250)  # auto=joint
    front3 = joint.pareto(("top1_err", "energy_mj", "area_mm2"))

The legacy ``repro.core.dse`` / ``repro.core.coexplore`` modules remain as
thin compatibility shims over this package.  See ``docs/explore.md`` for
the full guide and ``docs/architecture.md`` for the paper-to-code map.
"""
from repro.core.dataflow import LayerStack
from repro.core.table import ConfigTable, JointTable
from repro.explore.backend import (EvaluationBackend, OracleBackend,
                                   PolynomialBackend, VectorOracleBackend,
                                   gbuf_overheads, gbuf_overheads_table)
# NOTE: repro.explore.device is intentionally NOT imported here — its
# import sets process-global XLA exactness flags (no FMA contraction, no
# algebraic simplifier), which mixed jax workloads may not want.  It
# loads automatically when a VectorOracleBackend(jit=True) is built or a
# streaming sweep hits the device path; import it explicitly (before any
# jax compilation) when you need the flags earlier.
from repro.explore.fleet import (DevicePool, device_topology, run_fleet,
                                 visible_devices)
from repro.explore.frame import (DesignPoint, Normalized, ResultFrame,
                                 pareto_mask, stable_topk_indices,
                                 summary_stats)
from repro.explore.resilience import (ChunkError, ChunkTask, Fault,
                                      FaultInjected, FaultPlan, InjectedHang,
                                      ResiliencePolicy, RetryPolicy, Rung,
                                      SweepJournal, SweepKilled, sweep_key)
from repro.explore.resilience import CircuitBreaker
from repro.explore.search import (crowding_distance, guided_search,
                                  hypervolume, nondominated_ranks,
                                  objective_matrix)
from repro.explore.service import (AdmissionRejected, BudgetExhausted,
                                   Deadline, DeadlineExceeded,
                                   ExplorationService, SessionCancelled,
                                   SessionHandle)
from repro.explore.session import ExplorationSession
from repro.explore.space import (AXIS_ORDER, Axis, DesignSpace,
                                 VectorConstraint, vector_constraint)
from repro.explore.streaming import (STREAM_AUTO_MIN_ROWS,
                                     CollectAccumulator,
                                     HistogramAccumulator, ParetoAccumulator,
                                     Reducer, StatsAccumulator, StreamResult,
                                     TopKAccumulator, stream_co_explore,
                                     stream_explore)
from repro.explore.store import (ResultStore, cached_stream_co_explore,
                                 cached_stream_explore)

__all__ = [
    "AXIS_ORDER", "AdmissionRejected", "Axis", "BudgetExhausted",
    "ChunkError", "ChunkTask", "CircuitBreaker", "CollectAccumulator",
    "ConfigTable", "Deadline", "DeadlineExceeded", "DesignPoint",
    "DesignSpace", "DevicePool", "EvaluationBackend", "ExplorationService",
    "ExplorationSession", "Fault", "FaultInjected", "FaultPlan",
    "HistogramAccumulator", "InjectedHang", "JointTable", "LayerStack",
    "Normalized", "OracleBackend", "ParetoAccumulator", "PolynomialBackend",
    "Reducer", "ResiliencePolicy", "ResultFrame", "ResultStore",
    "RetryPolicy", "Rung", "STREAM_AUTO_MIN_ROWS", "SessionCancelled",
    "SessionHandle", "StatsAccumulator", "StreamResult", "SweepJournal",
    "SweepKilled", "TopKAccumulator", "VectorConstraint",
    "VectorOracleBackend", "cached_stream_co_explore",
    "cached_stream_explore", "crowding_distance", "device_topology",
    "gbuf_overheads", "gbuf_overheads_table", "guided_search",
    "hypervolume", "nondominated_ranks", "objective_matrix", "pareto_mask",
    "run_fleet", "stable_topk_indices", "stream_co_explore",
    "stream_explore", "summary_stats", "sweep_key", "vector_constraint",
    "visible_devices",
]
