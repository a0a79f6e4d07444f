"""ExplorationSession: the one facade over plain DSE and HW x NN
co-exploration.

A session binds an :class:`EvaluationBackend` (how points are scored) to a
:class:`DesignSpace` (which points exist) and drives both exploration
flavours over the same machinery:

  explore(...)      sample hardware configs, evaluate one workload
                    -> ResultFrame (timings in frame.meta)
  co_explore(...)   pair sampled hardware with supernet-evaluated NN
                    architectures -> ResultFrame with top1/arch columns

``explore`` picks between two sampling materializations: the legacy
per-point config list, and the columnar :class:`ConfigTable` path for
backends that prefer it (``prefers_table = True``, e.g.
:class:`~repro.explore.VectorOracleBackend`) — million-point sweeps then
stay struct-of-arrays from sampling through evaluation to the frame.

Both methods also route into the streaming engine
(:mod:`repro.explore.streaming`): explicitly with ``stream=True`` +
``reducers`` (constant memory, survivors-only :class:`StreamResult`
out), or implicitly when ``vectorized="auto"`` sees a sweep of
``STREAM_AUTO_MIN_ROWS``+ rows on a table-capable backend — the engine
then evaluates chunks on a thread pool and reassembles the identical
full frame (parallel throughput, one-shot semantics).

On a ``VectorOracleBackend(jit=True)`` the streaming engine goes
device-resident: exact x64 evaluation under ``jax.jit`` (bit-identical
to the numpy path), asynchronous dispatch-ahead chunk scheduling, and —
when every reducer is device-fusable — fused on-device reduction so
only O(cap) floats come back per chunk, cut on the host
(:mod:`repro.explore.device`).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.dataflow import AcceleratorConfig, ConvLayer
from repro.explore.backend import EvaluationBackend, OracleBackend
from repro.explore.frame import ResultFrame
from repro.explore.space import DesignSpace
from repro.explore.streaming import (STREAM_AUTO_MIN_ROWS,
                                     CollectAccumulator, Reducer,
                                     StreamResult, stream_co_explore,
                                     stream_explore)


class ExplorationSession:
  """Fit-once / evaluate-many driver over a backend + space pair."""

  def __init__(self, backend: EvaluationBackend,
               space: Optional[DesignSpace] = None):
    self.backend = backend
    if space is None:
      pe_types = getattr(backend, "pe_types", None)
      space = DesignSpace(pe_types=pe_types) if pe_types else DesignSpace()
    self.space = space

  def evaluate(self, cfgs: Sequence[AcceleratorConfig],
               layers: Sequence[ConvLayer],
               network: str = "net") -> ResultFrame:
    """Score explicit configs through the session's backend."""
    return self.backend.evaluate(cfgs, layers, network)

  def explore(self, layers: Sequence[ConvLayer], network: str,
              n_per_type: int = 200, seed: int = 17,
              method: str = "random", measure_oracle: int = 0,
              vectorized: Union[bool, str] = "auto", stream: bool = False,
              reducers: Optional[Dict[str, Reducer]] = None,
              chunk_size: int = 65536, workers: Optional[int] = None,
              policy=None, resume_from=None, checkpoint_every: int = 1,
              store=None, pool=None) -> Union[ResultFrame, StreamResult]:
    """Sample the space, evaluate `network`; optionally time the oracle on
    the first `measure_oracle` configs for the paper's speedup claim.

    vectorized: "auto" (default) samples a columnar ConfigTable when the
    backend advertises ``prefers_table``; True forces the table path for
    any backend with ``evaluate_table``; False keeps the legacy per-point
    config list (bit-compatible with the pre-table sampler sequences).

    stream=True runs the constant-memory streaming engine instead and
    returns a :class:`StreamResult` of reducer outputs (default: one
    ParetoAccumulator) — survivors bit-identical to the one-shot frame's
    ``pareto``/``top_k`` on the numpy path.  With ``vectorized="auto"``
    and no explicit ``stream``, sweeps of ``STREAM_AUTO_MIN_ROWS``+ rows
    still go through the engine with a CollectAccumulator: parallel
    chunked evaluation, identical full ResultFrame out (meta carries
    ``streamed``/``workers``).

    frame.meta carries: eval_seconds, eval_us_per_design, and (when
    measured) oracle_seconds_per_design + speedup.

    ``policy`` / ``resume_from`` / ``checkpoint_every`` (stream=True
    only) enable chunk retry + graceful degradation and journaled
    resume — see :mod:`repro.explore.resilience`.
    """
    if reducers is not None and not stream:
      raise ValueError("reducers only apply to the streaming engine; "
                       "pass stream=True")
    if (policy is not None or resume_from is not None
        or pool is not None) and not stream:
      raise ValueError("policy/resume_from/pool apply to the streaming "
                       "engine; pass stream=True")
    if store is not None and not stream:
      raise ValueError("store applies to the streaming engine; "
                       "pass stream=True")
    if stream:
      if measure_oracle:
        raise ValueError("measure_oracle is a one-shot feature; "
                         "pass stream=False")
      if store is not None:
        from repro.explore.store import cached_stream_explore
        return cached_stream_explore(self.backend, self.space, layers,
                                     network, n_per_type=n_per_type,
                                     seed=seed, method=method,
                                     reducers=reducers,
                                     chunk_size=chunk_size, workers=workers,
                                     policy=policy,
                                     checkpoint_every=checkpoint_every,
                                     store=store, pool=pool)
      return stream_explore(self.backend, self.space, layers, network,
                            n_per_type=n_per_type, seed=seed, method=method,
                            reducers=reducers, chunk_size=chunk_size,
                            workers=workers, policy=policy,
                            resume_from=resume_from,
                            checkpoint_every=checkpoint_every, pool=pool)
    if vectorized == "auto":
      use_table = bool(getattr(self.backend, "prefers_table", False))
    else:
      use_table = bool(vectorized)
    if use_table and not hasattr(self.backend, "evaluate_table"):
      raise ValueError(f"backend {self.backend.name!r} has no "
                       "evaluate_table; pass vectorized=False")
    if (use_table and vectorized == "auto" and not measure_oracle
        and n_per_type * len(self.space.pe_types) >= STREAM_AUTO_MIN_ROWS):
      return self._explore_streamed_frame(layers, network, n_per_type, seed,
                                          method, chunk_size, workers)
    if use_table:
      cfgs = self.space.sample_table(n_per_type, seed=seed, method=method)
    else:
      cfgs = self.space.sample(n_per_type, seed=seed, method=method)
    t0 = time.perf_counter()
    frame = self.backend.evaluate(cfgs, layers, network)
    t_eval = time.perf_counter() - t0
    n = max(len(frame), 1)
    frame.meta["eval_seconds"] = t_eval
    frame.meta["eval_us_per_design"] = t_eval / n * 1e6
    if measure_oracle:
      k = min(measure_oracle, len(cfgs))
      sample = cfgs.select(slice(0, k)).to_configs() \
          if use_table else cfgs[:k]
      t1 = time.perf_counter()
      OracleBackend().evaluate(sample, layers, network)
      per_design = (time.perf_counter() - t1) / max(k, 1)
      frame.meta["oracle_seconds_per_design"] = per_design
      frame.meta["speedup"] = per_design / max(t_eval / n, 1e-12)
    return frame

  @staticmethod
  def _collected_frame(res: StreamResult) -> ResultFrame:
    """Unwrap a CollectAccumulator run: the identical full frame, tagged
    with how it was produced."""
    frame = res["frame"]
    frame.meta["streamed"] = 1.0
    frame.meta["workers"] = res.meta["workers"]
    return frame

  def _explore_streamed_frame(self, layers, network, n_per_type, seed,
                              method, chunk_size, workers) -> ResultFrame:
    """The auto above-threshold path: parallel chunked evaluation through
    the engine, identical full frame out (CollectAccumulator)."""
    res = stream_explore(self.backend, self.space, layers, network,
                         n_per_type=n_per_type, seed=seed, method=method,
                         reducers={"frame": CollectAccumulator()},
                         chunk_size=chunk_size, workers=workers)
    frame = self._collected_frame(res)
    frame.meta["eval_seconds"] = res.seconds
    frame.meta["eval_us_per_design"] = res.seconds / max(len(frame), 1) * 1e6
    return frame

  def optimize(self, layers: Optional[Sequence[ConvLayer]] = None,
               network: str = "search", *,
               arch_accs: Optional[Sequence[Tuple[object, float]]] = None,
               objectives: Optional[Sequence[str]] = None,
               maximize: Optional[Sequence[str]] = None,
               population: int = 32, generations: int = 12, seed: int = 17,
               image_size: int = 32, surrogate: bool = False,
               surrogate_pool: int = 4, crossover_rate: float = 0.9,
               mutation_rate: Optional[float] = None,
               reducers: Optional[Dict[str, Reducer]] = None,
               policy=None, resume_from=None, checkpoint_every: int = 1
               ) -> StreamResult:
    """Guided multi-objective search (:mod:`repro.explore.search`) instead
    of enumeration: an NSGA-II-style optimizer whose generations evaluate
    as single chunks through this session's backend, fronts folding
    through the chunk-order-invariant ParetoAccumulator — the same
    :class:`StreamResult` the streaming engine returns, same-seed reruns
    bit-identical.

    Two modes, like :meth:`explore` / :meth:`co_explore`:

      * HW-only (pass ``layers``): searches the DesignSpace for one
        workload; default objectives ``("perf_per_area", "energy_mj")``
        (the paper's front axes).  On a ``VectorOracleBackend(jit=True)``
        each generation is one device-resident ``eval_pending`` dispatch
        (exact x64: the search trajectory is bit-identical to numpy).
      * joint (pass ``arch_accs``): the architecture choice becomes one
        more integer gene, and each generation evaluates grouped by
        architecture through ``evaluate_table``; default objectives
        ``("top1_err", "energy_mj", "area_mm2")`` (the Fig. 12 front).
        Requires a non-jit backend — per-arch layer programs would
        thrash the bounded jit cache, so this path refuses rather than
        silently recompiling every generation.

    ``surrogate=True`` adds online polynomial screening (QAPPA-style
    models refit on all evaluated points each generation) — proposals
    are pre-ranked by expected hypervolume gain before spending budget.
    ``meta`` carries evaluations / generations / hypervolume.
    """
    from repro.explore import search as _search  # local: keep header lean
    if (layers is None) == (arch_accs is None):
      raise ValueError("pass exactly one of layers= (HW-only search) or "
                       "arch_accs= (joint search)")
    if arch_accs is None:
      if objectives is None:
        objectives = ("perf_per_area", "energy_mj")
      use_device = bool(getattr(self.backend, "jit", False)) \
          and hasattr(self.backend, "eval_pending")
      use_table = hasattr(self.backend, "evaluate_table")
      layer_key = tuple(layers)

      def evaluate(table, idx, arch):
        if use_device:
          return self.backend.eval_pending(table, layer_key, network, idx)
        if use_table:
          return self.backend.evaluate_table(table, layers, network), idx
        return self.backend.evaluate(table.to_configs(), layers, network), idx

      return _search.guided_search(
          self.space, evaluate, objectives, maximize=maximize,
          population=population, generations=generations, seed=seed,
          surrogate=surrogate, surrogate_pool=surrogate_pool,
          crossover_rate=crossover_rate, mutation_rate=mutation_rate,
          reducers=reducers, policy=policy, resume_from=resume_from,
          checkpoint_every=checkpoint_every)

    from repro.core.supernet import arch_to_layers  # deferred: pulls jax
    if objectives is None:
      objectives = ("top1_err", "energy_mj", "area_mm2")
    if getattr(self.backend, "jit", False):
      raise ValueError(
          "joint optimize() needs a non-jit backend: each generation "
          "evaluates per-architecture layer lists, which would thrash "
          "the bounded jit program cache; use VectorOracleBackend() or "
          "PolynomialBackend")
    use_table = hasattr(self.backend, "evaluate_table")
    archs = [arch for arch, _ in arch_accs]
    accs = np.asarray([float(acc) for _, acc in arch_accs], np.float64)
    arch_layers = [arch_to_layers(arch, image_size=image_size)
                   for arch in archs]

    def evaluate(table, idx, arch):
      # group rows by architecture gene (one evaluate_table per distinct
      # arch in the generation), then reassemble in genome row order
      parts: List[ResultFrame] = []
      rows: List[np.ndarray] = []
      for aid in np.unique(arch):
        sel = np.flatnonzero(arch == aid)
        sub = table.select(sel)
        if use_table:
          f = self.backend.evaluate_table(sub, arch_layers[aid], network)
        else:
          f = self.backend.evaluate(sub.to_configs(), arch_layers[aid],
                                    network)
        f.extra["top1"] = np.full(len(f), accs[aid])
        f.extra["arch_id"] = np.full(len(f), aid, np.int64)
        f.arch_lookup = tuple(archs)
        parts.append(f)
        rows.append(sel)
      frame = ResultFrame.concat(parts)
      perm = np.concatenate(rows)
      inv = np.empty_like(perm)
      inv[perm] = np.arange(perm.shape[0])
      return frame.select(inv), idx

    def features(table, arch):
      # the arch gene enters the surrogate as its accuracy (the quantity
      # the top1_err objective actually depends on), not as a raw id
      base = _search.default_features(table, None)
      return np.concatenate([base, accs[arch][:, None]], axis=1)

    return _search.guided_search(
        self.space, evaluate, objectives, maximize=maximize,
        population=population, generations=generations, seed=seed,
        surrogate=surrogate, surrogate_pool=surrogate_pool,
        features=features, crossover_rate=crossover_rate,
        mutation_rate=mutation_rate, n_archs=len(archs),
        reducers=reducers, policy=policy, resume_from=resume_from,
        checkpoint_every=checkpoint_every)

  def co_explore(self, arch_accs: Sequence[Tuple[object, float]],
                 n_hw_per_type: int = 20, seed: int = 3,
                 image_size: int = 32, method: str = "random",
                 vectorized: Union[bool, str] = "auto", stream: bool = False,
                 reducers: Optional[Dict[str, Reducer]] = None,
                 chunk_size: int = 65536, workers: Optional[int] = None,
                 policy=None, resume_from=None, checkpoint_every: int = 1,
                 store=None, pool=None) -> Union[ResultFrame, StreamResult]:
    """Sampled HW x supernet-evaluated archs -> joint frame (Fig. 12).

    Rows carry a ``top1`` float column and an integer ``arch_id`` column
    resolving through ``frame.arch_lookup`` (one entry per architecture,
    in ``arch_accs`` order); energy / area anchors come from
    frame.reference_index("energy"/"area"), and the 3-objective joint
    front is ``frame.pareto(("top1_err", "energy_mj", "area_mm2"))``.

    vectorized: "auto" (default) takes the joint-table path when the
    backend advertises ``prefers_table`` and implements
    ``co_evaluate_table`` — the whole archs x HW cross product evaluates
    array-at-a-time (arch layer features stacked once, HW sampled as
    ConfigTables), with power/area computed once per HW row instead of
    once per pair.  True forces that path for any backend implementing
    ``co_evaluate_table`` (e.g. PolynomialBackend); False keeps the
    legacy nested arch x HW loop of scalar ``backend.evaluate`` calls.
    Both paths emit rows in the same (pe_type, arch, hw) order; note
    ``method="random"`` samples different (each deterministic) HW
    sequences per path, exactly like :meth:`explore` — use
    ``grid``/``stratified`` when comparing paths point for point.

    stream=True runs the constant-memory streaming engine over lazy
    JointTable blocks and returns a :class:`StreamResult` (default
    reducer: the 3-objective joint-front ParetoAccumulator).  Like
    :meth:`explore`, ``vectorized="auto"`` sends
    ``STREAM_AUTO_MIN_ROWS``+-pair sweeps through the engine with a
    CollectAccumulator — parallel evaluation, identical joint frame out.
    """
    from repro.core.dataflow import LayerStack  # local: keep header lean
    if reducers is not None and not stream:
      raise ValueError("reducers only apply to the streaming engine; "
                       "pass stream=True")
    if (policy is not None or resume_from is not None
        or pool is not None) and not stream:
      raise ValueError("policy/resume_from/pool apply to the streaming "
                       "engine; pass stream=True")
    if store is not None and not stream:
      raise ValueError("store applies to the streaming engine; "
                       "pass stream=True")
    if stream:
      if not hasattr(self.backend, "co_evaluate_table"):
        raise ValueError(f"backend {self.backend.name!r} has no "
                         "co_evaluate_table; streaming needs the joint path")
      if store is not None:
        from repro.explore.store import cached_stream_co_explore
        return cached_stream_co_explore(self.backend, self.space, arch_accs,
                                        n_hw_per_type=n_hw_per_type,
                                        seed=seed, image_size=image_size,
                                        method=method, reducers=reducers,
                                        chunk_size=chunk_size,
                                        workers=workers, policy=policy,
                                        checkpoint_every=checkpoint_every,
                                        store=store, pool=pool)
      return stream_co_explore(self.backend, self.space, arch_accs,
                               n_hw_per_type=n_hw_per_type, seed=seed,
                               image_size=image_size, method=method,
                               reducers=reducers, chunk_size=chunk_size,
                               workers=workers, policy=policy,
                               resume_from=resume_from,
                               checkpoint_every=checkpoint_every, pool=pool)
    from repro.core.supernet import arch_to_layers  # deferred: pulls jax
    if vectorized == "auto":
      use_joint = bool(getattr(self.backend, "prefers_table", False)) \
          and hasattr(self.backend, "co_evaluate_table")
    else:
      use_joint = bool(vectorized)
    if use_joint and not hasattr(self.backend, "co_evaluate_table"):
      raise ValueError(f"backend {self.backend.name!r} has no "
                       "co_evaluate_table; pass vectorized=False")
    n_pairs_est = len(arch_accs) * n_hw_per_type * len(self.space.pe_types)
    if (use_joint and vectorized == "auto"
        and n_pairs_est >= STREAM_AUTO_MIN_ROWS):
      res = stream_co_explore(self.backend, self.space, arch_accs,
                              n_hw_per_type=n_hw_per_type, seed=seed,
                              image_size=image_size, method=method,
                              reducers={"frame": CollectAccumulator()},
                              chunk_size=chunk_size, workers=workers)
      return self._collected_frame(res)
    archs = [arch for arch, _ in arch_accs]
    accs = np.asarray([float(acc) for _, acc in arch_accs], np.float64)
    arch_layers = [arch_to_layers(arch, image_size=image_size)
                   for arch in archs]
    frames: List[ResultFrame] = []
    if use_joint:
      stack = LayerStack.from_layer_lists(arch_layers)
      for ti, pe_type in enumerate(self.space.pe_types):
        hw = self.space.sample_type_table(pe_type, n_hw_per_type,
                                          seed=seed + 17 * ti, method=method)
        f = self.backend.co_evaluate_table(hw, stack, network="coexplore")
        f.extra["top1"] = accs[f.extra["arch_id"]]
        f.arch_lookup = tuple(archs)
        frames.append(f)
      return ResultFrame.concat(frames)
    for ti, pe_type in enumerate(self.space.pe_types):
      cfgs = self.space.sample_type(pe_type, n_hw_per_type,
                                    seed=seed + 17 * ti, method=method)
      for aid, layers in enumerate(arch_layers):
        f = self.backend.evaluate(cfgs, layers, network="coexplore")
        f.extra["top1"] = np.full(len(f), accs[aid])
        f.extra["arch_id"] = np.full(len(f), aid, np.int64)
        f.arch_lookup = tuple(archs)
        frames.append(f)
    return ResultFrame.concat(frames)
