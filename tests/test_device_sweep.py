"""Device-resident sweep path: exactness, fused reducers, kernels.

Covers the PR-5 acceptance matrix:
  * x64 ``jit=True`` device evaluation is bit-identical to the numpy
    path (plain and joint, chunked);
  * fused on-device reducers fold to bit-identical Pareto/top-k frames
    (and identical histograms) versus the host-reducer stream, across
    shuffled chunk partitions and versus the one-shot frame;
  * the Pallas dominance-count kernel matches its pure-jnp ref in
    interpret mode;
  * satellite guards: the jit-program LRU stays bounded, the float32
    mode stays approximate-only, survivor-cap overflow falls back to
    exact full-chunk folds.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.cnn import SEARCH_SPACE, ArchChoice
from repro.core.dataflow import LayerStack
from repro.core.workloads import get_network
from repro.explore import (DesignSpace, ExplorationSession,
                           VectorOracleBackend)
from repro.explore.backend import _LRUCache
from repro.explore.streaming import (HistogramAccumulator,
                                     ParetoAccumulator, StatsAccumulator,
                                     TopKAccumulator, run_stream,
                                     stream_co_explore, stream_explore)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

METRICS = ("latency_s", "power_mw", "area_mm2")


@pytest.fixture(scope="module")
def layers():
  return get_network("resnet20")[:5]


@pytest.fixture(scope="module")
def space():
  return DesignSpace()


@pytest.fixture(scope="module")
def arch_accs():
  from repro.core.supernet import arch_to_layers
  rng = np.random.RandomState(7)
  archs = [ArchChoice(tuple((int(rng.choice(r)), int(rng.choice(c)))
                            for r, c in SEARCH_SPACE)) for _ in range(9)]
  accs = rng.uniform(0.5, 0.95, size=len(archs))
  # keep arch_to_layers importable once for the stack fixture below
  del arch_to_layers
  return list(zip(archs, accs))


@pytest.fixture(scope="module")
def stack(arch_accs):
  from repro.core.supernet import arch_to_layers
  lists = [arch_to_layers(a, image_size=16) for a, _ in arch_accs]
  lists[-1] = lists[-1][:3]  # ragged stack: exercises the validity mask
  return LayerStack.from_layer_lists(lists)


class TestExactDeviceEval:
  def test_plain_bit_identity(self, layers, space):
    tbl = space.sample_table(120, seed=11)
    base = VectorOracleBackend().evaluate_table(tbl, layers)
    dev = VectorOracleBackend(chunk_size=47, jit=True).evaluate_table(
        tbl, layers)
    for col in METRICS:
      assert np.array_equal(getattr(dev, col), getattr(base, col)), col

  def test_joint_bit_identity(self, stack, space):
    hw = space.sample_table(19, seed=5)
    base = VectorOracleBackend().co_evaluate_table(hw, stack)
    dev = VectorOracleBackend(chunk_size=130, jit=True).co_evaluate_table(
        hw, stack)
    for col in METRICS:
      assert np.array_equal(getattr(dev, col), getattr(base, col)), col
    assert np.array_equal(dev.extra["arch_id"], base.extra["arch_id"])

  def test_parity_max_rel_err_is_zero(self, layers, space):
    """The acceptance-criterion formulation: max relative error == 0."""
    tbl = space.sample_table(80, seed=2)
    base = VectorOracleBackend().evaluate_table(tbl, layers)
    dev = VectorOracleBackend(jit=True).evaluate_table(tbl, layers)
    rel = max(float(np.max(np.abs(getattr(dev, c) / getattr(base, c) - 1.0)))
              for c in METRICS)
    assert rel == 0.0

  def test_dedup_matches_stack_joint(self, stack, space):
    """The distinct-layer factorization is bit-identical on numpy too."""
    from repro.core import oracle
    hw = space.sample_table(11, seed=9)
    ref = oracle.characterize_joint(hw, stack)
    unique_cols, slot_ids = stack.dedup_slots()
    got = oracle.characterize_joint_dedup(hw, unique_cols, slot_ids,
                                          stack.valid)
    for col in ("latency_s", "energy_mj", "power_mw", "area_mm2",
                "utilization"):
      assert np.array_equal(getattr(ref, col), getattr(got, col)), col

  def test_float32_mode_is_approximate_only(self, layers, space):
    tbl = space.sample_table(40, seed=3)
    base = VectorOracleBackend().evaluate_table(tbl, layers)
    f32 = VectorOracleBackend(jit=True, precision="float32").evaluate_table(
        tbl, layers)
    for col in METRICS:
      np.testing.assert_allclose(getattr(f32, col), getattr(base, col),
                                 rtol=1e-3)

  def test_bad_precision_rejected(self):
    with pytest.raises(ValueError, match="precision"):
      VectorOracleBackend(precision="f16")


def _reducers():
  return {"pareto": ParetoAccumulator(),
          "top": TopKAccumulator(9, by="energy_mj"),
          "stats": StatsAccumulator("power_mw"),
          "hist": HistogramAccumulator("area_mm2", 0.0, 200.0, bins=32)}


def _joint_reducers():
  return {"pareto": ParetoAccumulator(("top1_err", "energy_mj",
                                       "area_mm2")),
          "top": TopKAccumulator(9, by="energy_mj")}


def _assert_frames_equal(a, b, ctx=""):
  for col in METRICS:
    assert np.array_equal(a.column(col), b.column(col)), (ctx, col)
  assert set(a.extra) == set(b.extra), ctx
  for k in a.extra:
    assert np.array_equal(a.extra[k], b.extra[k]), (ctx, k)


class TestFusedReducers:
  def test_plain_fused_matches_host(self, layers, space):
    host = stream_explore(VectorOracleBackend(), space, layers,
                          n_per_type=90, seed=4, reducers=_reducers(),
                          chunk_size=53)
    dev = stream_explore(VectorOracleBackend(jit=True), space, layers,
                         n_per_type=90, seed=4, reducers=_reducers(),
                         chunk_size=53)
    _assert_frames_equal(dev["pareto"], host["pareto"], "pareto")
    _assert_frames_equal(dev["top"], host["top"], "top")
    assert np.array_equal(dev["hist"]["counts"], host["hist"]["counts"])
    for k, v in host["stats"].items():
      assert dev["stats"][k] == pytest.approx(v, rel=1e-12), k

  def test_joint_fused_matches_host_and_one_shot(self, arch_accs, space):
    cols = ("top1_err", "energy_mj", "area_mm2")
    host = stream_co_explore(VectorOracleBackend(), space, arch_accs,
                             n_hw_per_type=13, seed=3, image_size=16,
                             reducers=_joint_reducers(), chunk_size=41)
    dev = stream_co_explore(VectorOracleBackend(jit=True), space, arch_accs,
                            n_hw_per_type=13, seed=3, image_size=16,
                            reducers=_joint_reducers(), chunk_size=41)
    _assert_frames_equal(dev["pareto"], host["pareto"], "pareto")
    _assert_frames_equal(dev["top"], host["top"], "top")
    # ... and both match the one-shot frame's pareto/top_k row for row
    session = ExplorationSession(VectorOracleBackend(), space)
    frame = session.co_explore(arch_accs, n_hw_per_type=13, seed=3,
                               image_size=16)
    want_front = frame.select(frame.pareto(cols))
    want_top = frame.top_k(9, by="energy_mj")
    for col in METRICS:
      assert np.array_equal(dev["pareto"].column(col),
                            want_front.column(col)), col
      assert np.array_equal(dev["top"].column(col),
                            want_top.column(col)), col

  def test_shuffled_partition_invariance(self, layers, space):
    """Fused chunks fold to the same state for any chunk partition and
    any fold order — the streaming engine's core invariant, exercised
    through run_stream directly with shuffled device tasks."""
    backend = VectorOracleBackend(jit=True)
    from repro.explore.device import build_plan
    tbl = space.sample_table(70, seed=8)
    base = VectorOracleBackend().evaluate_table(tbl, layers)
    want_front = base.select(base.pareto(("perf_per_area", "energy_mj")))
    want_top = base.top_k(9, by="energy_mj")

    rng = np.random.RandomState(0)
    for trial in range(3):
      reducers = _reducers()
      plan = build_plan(reducers, joint=False)
      assert plan is not None
      # random contiguous partition, then shuffled task order
      cuts = np.sort(rng.choice(np.arange(1, len(tbl)), size=4,
                                replace=False))
      bounds = [0, *cuts.tolist(), len(tbl)]
      pieces = [(tbl.select(slice(lo, hi)),
                 np.arange(lo, hi, dtype=np.int64))
                for lo, hi in zip(bounds[:-1], bounds[1:])]
      rng.shuffle(pieces)
      tasks = [
          (lambda chunk=c, idx=i: backend.fused_eval_pending(
              chunk, layers, "net", plan, idx)) for c, i in pieces]
      res = run_stream(iter(tasks), reducers)
      for col in METRICS:
        assert np.array_equal(res["pareto"].column(col),
                              want_front.column(col)), (trial, col)
        assert np.array_equal(res["top"].column(col),
                              want_top.column(col)), (trial, col)

  def test_survivor_cap_overflow_falls_back_exactly(self, layers, space):
    """A cap below the true front size forces the full-frame fallback;
    results stay exact.  The 3-objective columns also exercise the
    generic block-prefilter path (>= 3 variable objectives)."""
    from repro.explore import device as device_lib
    backend = VectorOracleBackend(jit=True)
    cols = ("latency_s", "power_mw", "area_mm2")
    tbl = space.sample_table(60, seed=6)
    base = VectorOracleBackend().evaluate_table(tbl, layers)
    want = base.select(base.pareto(cols))
    assert len(want) > 1  # otherwise cap=front-1 below cannot overflow
    reducers = {"pareto": ParetoAccumulator(cols)}
    plan = device_lib.build_plan(reducers, joint=False, cap=len(want) - 1)
    pend = backend.fused_eval_pending(tbl, layers, "net", plan,
                                      np.arange(len(tbl), dtype=np.int64))
    chunk = pend.resolve()
    kind, frame, idx = chunk.payloads["pareto"]
    assert kind == "rows" and len(frame) == len(tbl)  # full-chunk fallback
    reducers["pareto"].fold_payload(chunk.payloads["pareto"])
    got = reducers["pareto"].result()
    assert len(got) == len(want)
    for col in METRICS:
      assert np.array_equal(got.column(col), want.column(col)), col

  def test_collect_reducer_is_not_fusable(self):
    from repro.explore.device import build_plan
    from repro.explore.streaming import CollectAccumulator
    assert build_plan({"frame": CollectAccumulator()}, joint=False) is None

  def test_auto_stream_device_frame_identical(self, layers, space):
    """The non-fused pending path (CollectAccumulator route) returns the
    identical full frame."""
    from repro.explore.streaming import CollectAccumulator
    host = stream_explore(VectorOracleBackend(), space, layers,
                          n_per_type=40, seed=12,
                          reducers={"frame": CollectAccumulator()},
                          chunk_size=37)
    dev = stream_explore(VectorOracleBackend(jit=True), space, layers,
                         n_per_type=40, seed=12,
                         reducers={"frame": CollectAccumulator()},
                         chunk_size=37)
    _assert_frames_equal(dev["frame"], host["frame"], "collect")


class TestParetoFrontKernel:
  """Interpret-mode correctness of the Pallas dominance kernel."""

  @pytest.mark.parametrize("n,d", [(64, 2), (300, 3), (513, 4)])
  def test_counts_match_ref(self, n, d):
    from repro.kernels.pareto_front import ops
    from repro.kernels.pareto_front.ref import dominance_counts_ref
    rng = np.random.RandomState(n + d)
    obj = rng.uniform(size=(n, d)).astype(np.float32)
    obj[n // 3] = obj[2 * n // 3]  # duplicates: dominate nobody
    got = np.asarray(ops.dominance_counts(obj, interpret=True))
    want = np.asarray(dominance_counts_ref(obj))
    assert np.array_equal(got, want)

  def test_front_matches_host_pareto(self):
    from repro.explore.frame import pareto_mask
    from repro.kernels.pareto_front import ops
    rng = np.random.RandomState(0)
    obj = rng.uniform(size=(400, 3)).astype(np.float32)
    got = np.asarray(ops.pareto_front_mask(obj, interpret=True))
    assert np.array_equal(got, pareto_mask(obj.astype(np.float64)))

  @pytest.mark.parametrize("use_pallas", [False, True])
  def test_block_prefilter_is_front_superset(self, use_pallas):
    from repro.explore.frame import pareto_mask
    from repro.kernels.pareto_front import ops
    from repro.kernels.pareto_front.ref import block_dominance_counts_ref
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    obj = rng.uniform(size=(500, 3)).astype(np.float32)
    mask = np.asarray(ops.block_prefilter_mask(obj, block=128,
                                               use_pallas=use_pallas,
                                               interpret=True))
    front = pareto_mask(obj.astype(np.float64))
    assert not (front & ~mask).any()  # no front point is ever dropped
    # blockwise counts agree with the blockwise ref on padded input
    pad = np.full((12, 3), np.inf, np.float32)
    padded = jnp.asarray(np.concatenate([obj, pad]))
    want = np.asarray(block_dominance_counts_ref(padded, 128))
    got_pallas = np.asarray(ops.block_prefilter_mask(
        padded, block=128, use_pallas=True, interpret=True))
    assert np.array_equal(got_pallas, want == 0)

  def test_staircase_prefilter_is_front_superset(self):
    from repro.explore.device import _staircase_mask
    from repro.explore.frame import pareto_mask
    import jax.numpy as jnp
    rng = np.random.RandomState(2)
    x = rng.uniform(size=(5, 200))
    y = rng.uniform(size=(5, 200))
    keep = np.asarray(_staircase_mask(jnp.asarray(x), jnp.asarray(y),
                                      jnp, jax))
    for g in range(5):
      front = pareto_mask(np.stack([x[g], y[g]], axis=1))
      assert not (front & ~keep[g]).any(), g


class TestInterleavedSearchGenerations:
  """Guided-search generations interleave distinct fused plans through
  one backend: every generation must stay exact while the jit LRU churns,
  and cap overflow must degrade to the full-chunk fold, never to a wrong
  front."""

  def test_distinct_plans_stay_exact_under_lru_churn(self, layers, space):
    from repro.explore import device as device_lib
    backend = VectorOracleBackend(jit=True)
    cols = ("perf_per_area", "energy_mj")
    n_gens = backend.JIT_CACHE_SIZE + 3  # > maxsize: forces eviction
    overflow_hit = fused_hit = False
    for g in range(n_gens):
      tbl = space.sample_table(40, seed=100 + g)
      base = VectorOracleBackend().evaluate_table(tbl, layers)
      want = base.select(base.pareto(cols))
      if g == 0:
        assert len(want) > 1  # otherwise cap below cannot overflow
        cap = len(want) - 1   # generation 0: guaranteed overflow
      else:
        cap = len(tbl) + g    # distinct plan per generation, no overflow
      reducers = {"pareto": ParetoAccumulator(cols)}
      plan = device_lib.build_plan(reducers, joint=False, cap=cap)
      pend = backend.fused_eval_pending(tbl, layers, "net", plan,
                                        np.arange(len(tbl), dtype=np.int64))
      chunk = pend.resolve()
      kind, frame, _ = chunk.payloads["pareto"]
      assert kind == "rows"
      if cap < len(want):
        overflow_hit = True
        assert len(frame) == len(tbl)  # full-chunk fallback
      else:
        fused_hit = True
        assert len(frame) <= cap       # O(survivors) transfer
      reducers["pareto"].fold_payload(chunk.payloads["pareto"])
      got = reducers["pareto"].result()
      for col in METRICS:
        assert np.array_equal(got.column(col), want.column(col)), (g, col)
      assert len(backend._jit_cache) <= backend.JIT_CACHE_SIZE
    assert overflow_hit and fused_hit
    # 11 distinct plans passed through an 8-entry cache: it is full, and
    # eviction actually happened (the earliest plans are gone)
    assert len(backend._jit_cache) == backend.JIT_CACHE_SIZE

  def test_device_optimize_matches_numpy_optimize(self, layers, space):
    """The search trajectory itself is bit-identical across backends:
    every generation's fitness feeds selection, so one differing ulp
    would diverge the whole run."""
    kw = dict(objectives=("perf_per_area", "energy_mj"), population=12,
              generations=4, seed=5)
    host = ExplorationSession(VectorOracleBackend(), space).optimize(
        layers, **kw)
    dev = ExplorationSession(VectorOracleBackend(chunk_size=32, jit=True),
                             space).optimize(layers, **kw)
    assert host.n_rows == dev.n_rows
    a, b = host["pareto"], dev["pareto"]
    for col in ("perf_per_area", "energy_mj") + METRICS:
      assert np.array_equal(a.column(col), b.column(col)), col
    assert np.array_equal(a.table.pe_rows, b.table.pe_rows)
    assert list(a.pe_type) == list(b.pe_type)

  def test_fused_stats_single_row_chunk_has_zero_m2(self, layers, space):
    """Device mirror of StatsAccumulator's n == 1 short-circuit: a
    single-row chunk's fused stats payload carries M2 == 0.0 (a NaN here
    would poison every downstream Welford merge)."""
    from repro.explore import device as device_lib
    backend = VectorOracleBackend(jit=True)
    tbl = space.sample_type_table(space.pe_types[0], 1, seed=13)
    reducers = {"stats": StatsAccumulator("power_mw")}
    plan = device_lib.build_plan(reducers, joint=False)
    pend = backend.fused_eval_pending(tbl, layers, "net", plan,
                                      np.zeros(1, np.int64))
    kind, payload = pend.resolve().payloads["stats"]
    assert kind == "stats"
    assert payload["n"] == 1
    assert payload["m2"] == 0.0
    assert payload["min"] == payload["max"] == payload["mean"]
    # folding it must leave the accumulator NaN-free and mergeable
    reducers["stats"].fold_payload(("stats", payload))
    base = VectorOracleBackend().evaluate_table(tbl, layers)
    got = reducers["stats"].result()
    assert got["mean"] == float(base.power_mw[0])
    assert got["std"] == 0.0


class TestJitCacheBound:
  def test_lru_evicts_oldest(self):
    cache = _LRUCache(maxsize=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") == 1  # refresh a
    cache.put("c", 3)           # evicts b
    assert cache.get("b") is None
    assert cache.get("a") == 1 and cache.get("c") == 3
    assert len(cache) == 2

  def test_backend_cache_stays_bounded(self, space):
    """Sweeping many distinct networks must not leak executables."""
    backend = VectorOracleBackend(chunk_size=32, jit=True)
    tbl = space.sample_type_table(space.pe_types[0], 4, seed=0)
    nets = get_network("resnet20")
    for i in range(backend.JIT_CACHE_SIZE + 3):
      backend.evaluate_table(tbl, nets[i:i + 2], f"net{i}")
    assert len(backend._jit_cache) <= backend.JIT_CACHE_SIZE


def _fresh_python(code: str, **env) -> str:
  """Run ``code`` in a fresh interpreter (XLA flags and JAX's cache
  placement latch per process) and return its last stdout line."""
  import os
  import subprocess
  import sys
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  full = {k: v for k, v in os.environ.items()
          if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
  full["PYTHONPATH"] = os.path.join(root, "src")
  full.update(env)
  proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, env=full, timeout=120, cwd=root)
  assert proc.returncode == 0, proc.stderr[-2000:]
  return proc.stdout.strip().splitlines()[-1]


class TestChipPathSetup:
  """What a process sets up before its first compile: CPU exactness
  flags only where it compiles for the CPU, and one fixed compile-cache
  directory."""

  @pytest.mark.parametrize("platforms,want_flags", [
      ("cpu", True), ("tpu", False), ("tpu,cpu", False)])
  def test_exactness_flags_only_for_cpu_compiles(self, platforms,
                                                 want_flags):
    out = _fresh_python(
        "import os\n"
        "from repro.explore.device import (compiles_for_cpu,\n"
        "                                  ensure_exact_cpu_codegen)\n"
        "ensure_exact_cpu_codegen()\n"
        "print(compiles_for_cpu(), os.environ.get('XLA_FLAGS', ''))",
        JAX_PLATFORMS=platforms)
    on_cpu, flags = (out.split(" ", 1) + [""])[:2]
    assert on_cpu == str(want_flags)
    assert ("algsimp" in flags) == want_flags
    assert ("xla_cpu_max_isa=AVX" in flags) == want_flags

  def test_compile_cache_defaults_to_the_checkout(self):
    from repro.compile_cache import CHECKOUT_CACHE_DIR
    out = _fresh_python(
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)",
        JAX_PLATFORMS="cpu")
    assert out.split() == [CHECKOUT_CACHE_DIR, CHECKOUT_CACHE_DIR]
    assert CHECKOUT_CACHE_DIR.endswith(".jax_cache")

  def test_compile_cache_follows_the_environment(self, tmp_path):
    out = _fresh_python(
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)",
        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.split() == [str(tmp_path), str(tmp_path)]

  def test_fleet_benchmark_refuses_accelerator_hosts(self):
    out = _fresh_python(
        "import sys\n"
        "sys.path.insert(0, '.')\n"
        "from benchmarks.fleet_perf import refuse_on_accelerator\n"
        "try:\n"
        "  refuse_on_accelerator()\n"
        "  print('ran')\n"
        "except RuntimeError as e:\n"
        "  print('refused', 'chip_smoke.py --chips 4' in str(e))",
        JAX_PLATFORMS="tpu")
    assert out == "refused True"
