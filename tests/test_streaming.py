"""Streaming <-> one-shot equivalence for the constant-memory sweep engine.

Covers lazy chunked sampling (`DesignSpace.iter_tables` concatenation
bit-identical to `sample_table` for every method and chunk size), the
online reducers (ParetoAccumulator / TopKAccumulator folds over shuffled
chunk partitions equal the single-shot `pareto_mask` / `top_k`, including
empty-chunk and single-chunk edge cases; streaming stats/histograms),
the block-decomposed `_pareto_mask_nd` kernel, `stable_topk_indices`
(the argpartition `top_k` satellite), NaN-safe empty `summary_stats`,
JointTable block slicing, LayerStack arch slicing, and session-level
`stream=True` / auto-threshold routing on both vector backends.
"""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.cnn import SEARCH_SPACE, ArchChoice
from repro.core.table import ConfigTable
from repro.core.workloads import get_network
from repro.explore import (CollectAccumulator, DesignSpace,
                           ExplorationSession, HistogramAccumulator,
                           ParetoAccumulator, PolynomialBackend, ResultFrame,
                           StatsAccumulator, TopKAccumulator,
                           VectorOracleBackend, pareto_mask,
                           stable_topk_indices, summary_stats,
                           vector_constraint)
from repro.explore import frame as frame_mod


def random_frame(rng: np.random.RandomState, n: int,
                 with_top1: bool = False) -> ResultFrame:
  """Synthetic ResultFrame with deliberate ties (quantized values)."""
  extra = {}
  if with_top1:
    extra["top1"] = rng.randint(50, 96, n) / 100.0
    extra["arch_id"] = rng.randint(0, 5, n).astype(np.int64)
  return ResultFrame(
      latency_s=rng.randint(1, 30, n) * 1e-3,
      power_mw=rng.randint(1, 20, n) * 10.0,
      area_mm2=rng.randint(1, 15, n) * 0.5,
      pe_type=np.asarray(["INT16", "FP32"])[rng.randint(0, 2, n)],
      extra=extra)


def fold_partition(reducer, frame: ResultFrame, rng: np.random.RandomState,
                   n_chunks: int):
  """Fold `frame` into `reducer` as a shuffled partition of row chunks."""
  n = len(frame)
  perm = rng.permutation(n)
  bounds = np.sort(rng.randint(0, n + 1, size=max(n_chunks - 1, 0)))
  parts = np.split(perm, bounds)
  rng.shuffle(parts)
  for idx in parts:
    reducer.fold(frame.select(idx), idx)
  return reducer


# ---------------------------------------------------------------------------
# lazy chunked sampling
# ---------------------------------------------------------------------------

class TestIterTables:
  @pytest.mark.parametrize("method", ["random", "grid", "stratified"])
  @pytest.mark.parametrize("chunk_size", [1, 13, 100_000])
  def test_concat_equals_sample_table(self, method, chunk_size):
    space = DesignSpace()
    one = space.sample_table(83, seed=11, method=method)
    parts = list(space.iter_tables(83, seed=11, method=method,
                                   chunk_size=chunk_size))
    assert all(len(p) <= chunk_size for p in parts)
    assert ConfigTable.concat(parts).to_configs() == one.to_configs()

  def test_grid_subsampled_and_list_parity(self):
    """n < total grid: lazy linspace+dedup == one-shot np.unique(linspace),
    and the list path still enumerates the same sequence."""
    space = DesignSpace()
    one = space.sample_type_table("INT16", 60, method="grid")
    parts = list(space.iter_type_tables("INT16", 60, method="grid",
                                        chunk_size=7))
    assert ConfigTable.concat(parts).to_configs() == one.to_configs()
    assert space.sample_type("INT16", 60, method="grid") == one.to_configs()

  def test_constraints_filter_chunks(self):
    space = DesignSpace(constraints=[
        vector_constraint(lambda c: c.n_pe <= 256, lambda t: t.n_pe <= 256)])
    one = space.sample_type_table("INT16", 150, seed=2)
    parts = list(space.iter_type_tables("INT16", 150, seed=2, chunk_size=32))
    cat = ConfigTable.concat(parts)
    assert len(cat) == 150 and int(cat.n_pe.max()) <= 256
    assert cat.to_configs() == one.to_configs()

  def test_zero_and_bad_args(self):
    space = DesignSpace()
    assert list(space.iter_type_tables("INT16", 0, seed=0)) == []
    with pytest.raises(ValueError, match="chunk_size"):
      list(space.iter_type_tables("INT16", 5, chunk_size=0))
    with pytest.raises(ValueError, match="not in this space"):
      list(space.iter_type_tables("NOPE", 5))

  def test_impossible_constraint_raises(self):
    space = DesignSpace(constraints=[
        vector_constraint(lambda c: False,
                          lambda t: np.zeros(len(t), bool))])
    with pytest.raises(ValueError, match="constraints rejected"):
      list(space.iter_type_tables("INT16", 2, seed=0, chunk_size=64))


# ---------------------------------------------------------------------------
# online reducers vs one-shot
# ---------------------------------------------------------------------------

class TestParetoAccumulator:
  COLS = ("perf_per_area", "energy_mj")

  def one_shot(self, frame):
    return frame.select(frame.pareto(self.COLS))

  def check_equal(self, got, want):
    assert len(got) == len(want)
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(got, col), getattr(want, col)), col
    assert list(got.pe_type) == list(want.pe_type)

  def test_single_chunk(self):
    frame = random_frame(np.random.RandomState(0), 200)
    acc = ParetoAccumulator(self.COLS)
    acc.fold(frame, np.arange(len(frame)))
    self.check_equal(acc.result(), self.one_shot(frame))

  def test_empty_chunks_are_noops(self):
    frame = random_frame(np.random.RandomState(1), 120)
    acc = ParetoAccumulator(self.COLS)
    empty = frame.select(np.zeros(0, np.int64))
    acc.fold(empty, np.zeros(0, np.int64))
    acc.fold(frame, np.arange(len(frame)))
    acc.fold(empty, np.zeros(0, np.int64))
    self.check_equal(acc.result(), self.one_shot(frame))

  def test_no_folds_gives_empty_frame(self):
    assert len(ParetoAccumulator(self.COLS).result()) == 0
    assert len(TopKAccumulator(3).result()) == 0

  @given(st.integers(0, 10_000), st.integers(1, 200), st.integers(1, 8))
  @settings(max_examples=25, deadline=None)
  def test_shuffled_partitions_match_one_shot(self, seed, n, n_chunks):
    rng = np.random.RandomState(seed)
    frame = random_frame(rng, n)
    acc = fold_partition(ParetoAccumulator(self.COLS), frame, rng, n_chunks)
    want = self.one_shot(frame)
    self.check_equal(acc.result(), want)
    assert np.array_equal(
        acc.indices, np.flatnonzero(frame.pareto(self.COLS)))

  @given(st.integers(0, 10_000), st.integers(1, 150), st.integers(1, 6))
  @settings(max_examples=15, deadline=None)
  def test_3d_joint_front_partitions(self, seed, n, n_chunks):
    rng = np.random.RandomState(seed)
    frame = random_frame(rng, n, with_top1=True)
    cols = ("top1_err", "energy_mj", "area_mm2")
    acc = fold_partition(ParetoAccumulator(cols), frame, rng, n_chunks)
    want = frame.select(frame.pareto(cols))
    got = acc.result()
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(got, col), getattr(want, col)), col
    assert np.array_equal(got.extra["top1"], want.extra["top1"])


class TestTopKAccumulator:
  @given(st.integers(0, 10_000), st.integers(1, 200), st.integers(1, 8),
         st.integers(1, 30))
  @settings(max_examples=25, deadline=None)
  def test_shuffled_partitions_match_one_shot(self, seed, n, n_chunks, k):
    rng = np.random.RandomState(seed)
    frame = random_frame(rng, n)
    by = ("energy_mj", "perf_per_area")[seed % 2]
    acc = fold_partition(TopKAccumulator(k, by=by), frame, rng, n_chunks)
    want = frame.top_k(k, by=by)
    got = acc.result()
    assert len(got) == len(want) == min(k, n)
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(got, col), getattr(want, col)), col
    # ties resolve to the lowest global row id, like the stable one-shot
    key = frame.column(by)
    key = -key if by == "perf_per_area" else key
    assert np.array_equal(acc.indices,
                          stable_topk_indices(key, k))

  def test_bad_k(self):
    with pytest.raises(ValueError, match="k must be positive"):
      TopKAccumulator(0)


class TestStatsAndHistogram:
  @given(st.integers(0, 10_000), st.integers(1, 300), st.integers(1, 7))
  @settings(max_examples=15, deadline=None)
  def test_stats_match_numpy(self, seed, n, n_chunks):
    rng = np.random.RandomState(seed)
    frame = random_frame(rng, n)
    acc = fold_partition(StatsAccumulator("energy_mj"), frame, rng, n_chunks)
    v = frame.energy_mj
    got = acc.result()
    assert got["count"] == n
    assert got["min"] == v.min() and got["max"] == v.max()
    np.testing.assert_allclose(got["mean"], v.mean(), rtol=1e-12)
    np.testing.assert_allclose(got["std"], v.std(), rtol=1e-9)

  def test_stats_empty(self):
    out = StatsAccumulator("energy_mj").result()
    assert all(np.isnan(x) for x in out.values())

  def test_stats_single_row_chunks_match_one_shot(self):
    # row-at-a-time folding exercises the n == 1 zero-M2 short-circuit;
    # must agree with the one-shot fold (and numpy) instead of poisoning
    # the Welford merge with NaN partials
    rng = np.random.RandomState(8)
    frame = random_frame(rng, 37)
    acc = StatsAccumulator("energy_mj")
    for i in range(len(frame)):
      acc.fold(frame.select(np.asarray([i])), np.asarray([i]))
    got = acc.result()
    v = frame.energy_mj
    assert got["count"] == len(frame)
    assert got["min"] == v.min() and got["max"] == v.max()
    np.testing.assert_allclose(got["mean"], v.mean(), rtol=1e-12)
    np.testing.assert_allclose(got["std"], v.std(), rtol=1e-9)

  def test_stats_single_nonfinite_row_has_no_nan_partial(self):
    # a 1-row chunk holding inf used to yield m2 = (inf - inf)**2 = NaN,
    # and merging a +-inf mean into the empty state NaN'd the M2 term;
    # both paths must now stay NaN-free for count/min/max
    def one_row(val):
      return ResultFrame(np.asarray([val]), np.asarray([1.0]),
                         np.asarray([1.0]), np.asarray(["INT8"]))

    acc = StatsAccumulator("latency_s")
    acc.fold(one_row(np.inf), np.asarray([0]))
    acc.fold(one_row(2.0), np.asarray([1]))
    acc.fold(one_row(3.0), np.asarray([2]))
    got = acc.result()
    assert got["count"] == 3
    assert got["min"] == 2.0
    assert got["max"] == np.inf

  def test_stats_first_partial_adopted_bit_identically(self):
    # the n == 0 adopt-directly shortcut must be bit-identical to the
    # general Chan merge for finite inputs
    rng = np.random.RandomState(9)
    v = rng.rand(50) * 1e3
    frame = ResultFrame(v, np.ones(50), np.ones(50),
                        np.asarray(["INT8"] * 50))
    acc = StatsAccumulator("latency_s")
    acc.fold(frame, np.arange(50))
    mean_b = float(v.mean())
    m2_b = float(((v - mean_b) ** 2).sum())
    # what the general formula computes from the (0, 0.0, 0.0) state
    assert acc._mean == 0.0 + (mean_b - 0.0) * 50 / 50
    assert acc._m2 == m2_b + (mean_b - 0.0) ** 2 * 0 * 50 / 50
    assert acc.n == 50

  def test_histogram_counts_and_quantiles(self):
    rng = np.random.RandomState(3)
    frame = random_frame(rng, 500)
    v = frame.energy_mj
    acc = HistogramAccumulator("energy_mj", float(v.min()), float(v.max()),
                               bins=32)
    fold_partition(acc, frame, rng, 5)
    out = acc.result()
    assert out["counts"].sum() == 500
    want = np.histogram(v, bins=out["edges"])[0]
    assert np.array_equal(out["counts"], want)
    # approximate median within one bin width of the exact one
    bin_w = out["edges"][1] - out["edges"][0]
    assert abs(acc.quantile(0.5) - np.median(v)) <= bin_w
    with pytest.raises(ValueError, match="hi > lo"):
      HistogramAccumulator("energy_mj", 1.0, 1.0)

  def test_collect_reassembles_global_order(self):
    rng = np.random.RandomState(4)
    frame = random_frame(rng, 100)
    acc = fold_partition(CollectAccumulator(), frame, rng, 6)
    got = acc.result()
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(got, col), getattr(frame, col)), col


# ---------------------------------------------------------------------------
# frame-level satellites: top_k, empty stats, block-decomposed N-D pareto
# ---------------------------------------------------------------------------

class TestStableTopK:
  @given(st.integers(0, 10_000), st.integers(0, 120), st.integers(0, 140))
  @settings(max_examples=40, deadline=None)
  def test_matches_stable_argsort(self, seed, n, k):
    rng = np.random.RandomState(seed)
    v = rng.randint(0, 12, n).astype(np.float64)  # heavy ties
    assert np.array_equal(stable_topk_indices(v, k),
                          np.argsort(v, kind="stable")[:k])

  def test_nan_fallback(self):
    v = np.asarray([3.0, np.nan, 1.0, np.nan, 2.0])
    assert np.array_equal(stable_topk_indices(v, 2),
                          np.argsort(v, kind="stable")[:2])

  def test_frame_top_k_maximize_and_ties(self):
    rng = np.random.RandomState(0)
    frame = random_frame(rng, 300)
    for by, k in (("perf_per_area", 7), ("energy_mj", 25), ("latency_s", 0)):
      got = frame.top_k(k, by=by)
      key = frame.column(by)
      key = -key if by == "perf_per_area" else key
      want = frame.select(np.argsort(key, kind="stable")[:k])
      assert np.array_equal(got.latency_s, want.latency_s), by


class TestSummaryStatsEmpty:
  def test_empty_returns_nans(self):
    out = summary_stats(np.zeros(0))
    assert set(out) == {"min", "q1", "median", "q3", "max", "mean"}
    assert all(np.isnan(x) for x in out.values())

  def test_frame_stats_zero_row_mask(self):
    frame = random_frame(np.random.RandomState(0), 10)
    out = frame.stats("energy_mj", mask=np.zeros(10, np.bool_))
    assert all(np.isnan(x) for x in out.values())


def brute_force_front(obj: np.ndarray) -> np.ndarray:
  n = len(obj)
  return np.asarray(
      [not any(np.all(obj[j] <= obj[i]) and np.any(obj[j] < obj[i])
               for j in range(n)) for i in range(n)])


class TestBlockDecomposedParetoND:
  @given(st.integers(0, 10_000), st.integers(1, 120), st.integers(3, 4))
  @settings(max_examples=20, deadline=None)
  def test_blocked_matches_brute_force(self, seed, n, d):
    rng = np.random.RandomState(seed)
    obj = rng.randint(0, 6, size=(n, d)).astype(np.float64)  # many dups
    assert np.array_equal(pareto_mask(obj), brute_force_front(obj))

  def test_multi_block_recursion(self, monkeypatch):
    monkeypatch.setattr(frame_mod, "_ND_BLOCK", 16)
    rng = np.random.RandomState(1)
    obj = rng.uniform(size=(400, 3))
    obj[37] = obj[11]  # duplicate straddling blocks
    assert np.array_equal(frame_mod._pareto_mask_nd(obj),
                          brute_force_front(obj))

  def test_all_front_degenerate(self, monkeypatch):
    monkeypatch.setattr(frame_mod, "_ND_BLOCK", 8)
    # anti-correlated: every point non-dominated -> blocks make no progress
    t = np.linspace(0.0, 1.0, 40)
    obj = np.stack([t, 1.0 - t, np.ones_like(t)], axis=1)
    mask = frame_mod._pareto_mask_nd(obj)
    assert mask.all()


# ---------------------------------------------------------------------------
# table / stack block machinery
# ---------------------------------------------------------------------------

class TestJointBlocks:
  def test_block_slices_cover_exactly_once(self):
    hw = DesignSpace().sample_type_table("INT16", 23, seed=0)
    joint = hw.cross(9)
    seen = np.concatenate([joint.block_indices(a, h)
                           for a, h in joint.block_slices(50)])
    assert np.array_equal(np.sort(seen), np.arange(len(joint)))
    for a_sl, h_sl in joint.block_slices(50):
      n_rows = (a_sl.stop - a_sl.start) * (h_sl.stop - h_sl.start)
      assert n_rows <= 50
    assert list(hw.cross(0).block_slices(10)) == []
    with pytest.raises(ValueError, match="chunk_size"):
      list(joint.block_slices(0))

  def test_block_indices_are_arch_major(self):
    hw = DesignSpace().sample_type_table("INT16", 4, seed=0)
    joint = hw.cross(3)
    idx = joint.block_indices(slice(1, 3), slice(2, 4))
    assert idx.tolist() == [1 * 4 + 2, 1 * 4 + 3, 2 * 4 + 2, 2 * 4 + 3]


class TestLayerStackSlice:
  def test_slice_rows_bit_identical(self):
    from repro.core.dataflow import LayerStack
    from repro.core.supernet import arch_to_layers
    rng = np.random.RandomState(2)
    archs = [ArchChoice(tuple((int(rng.choice(r)), int(rng.choice(c)))
                              for r, c in SEARCH_SPACE)) for _ in range(5)]
    stack = LayerStack.from_layer_lists(
        [arch_to_layers(a, image_size=16) for a in archs])
    sub = stack.slice_archs(1, 4)
    assert sub.n_archs == 3 and sub.max_layers == stack.max_layers
    assert np.array_equal(sub.features(), stack.features()[1:4])
    assert np.array_equal(sub.valid, stack.valid[1:4])


# ---------------------------------------------------------------------------
# session-level streaming (end to end, both backends)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_layers():
  return get_network("resnet20")[:4]


@pytest.fixture(scope="module")
def arch_accs():
  rng = np.random.RandomState(7)
  archs = [ArchChoice(tuple((int(rng.choice(r)), int(rng.choice(c)))
                            for r, c in SEARCH_SPACE)) for _ in range(4)]
  return list(zip(archs, rng.uniform(0.5, 0.95, len(archs))))


class TestSessionStreaming:
  COLS = ("perf_per_area", "energy_mj")

  def test_stream_explore_matches_one_shot(self, small_layers):
    sess = ExplorationSession(VectorOracleBackend(chunk_size=64))
    frame = sess.explore(small_layers, "net", n_per_type=40, seed=4)
    res = sess.explore(
        small_layers, "net", n_per_type=40, seed=4, stream=True,
        reducers={"pareto": ParetoAccumulator(self.COLS),
                  "top": TopKAccumulator(9, by="energy_mj")},
        chunk_size=17, workers=3)
    assert res.n_rows == len(frame)
    want = frame.select(frame.pareto(self.COLS))
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(res["pareto"], col),
                            getattr(want, col)), col
    want_top = frame.top_k(9, by="energy_mj")
    assert np.array_equal(res["top"].latency_s, want_top.latency_s)
    # default reducer set + serial path
    res2 = sess.explore(small_layers, "net", n_per_type=40, seed=4,
                        stream=True, chunk_size=1000, workers=1)
    assert np.array_equal(res2["pareto"].latency_s, want.latency_s)

  def test_stream_co_explore_matches_one_shot(self, arch_accs):
    cols = ("top1_err", "energy_mj", "area_mm2")
    sess = ExplorationSession(VectorOracleBackend(chunk_size=512))
    frame = sess.co_explore(arch_accs, n_hw_per_type=10, seed=3,
                            image_size=16)
    res = sess.co_explore(
        arch_accs, n_hw_per_type=10, seed=3, image_size=16, stream=True,
        reducers={"pareto": ParetoAccumulator(cols),
                  "top": TopKAccumulator(7, by="energy_mj")},
        chunk_size=37, workers=4)
    assert res.n_rows == len(frame)
    want = frame.select(frame.pareto(cols))
    got = res["pareto"]
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(got, col), getattr(want, col)), col
    assert np.array_equal(got.extra["arch_id"], want.extra["arch_id"])
    assert got.arch_lookup == want.arch_lookup
    assert got.arch_at(0) is not None
    want_top = frame.top_k(7, by="energy_mj")
    assert np.array_equal(res["top"].latency_s, want_top.latency_s)

  def test_stream_polynomial_backend(self, small_layers, arch_accs):
    backend = PolynomialBackend.fit(pe_types=("INT16", "LightPE-1"),
                                    degree=3, n_train=80,
                                    layers=small_layers, seed=0)
    space = DesignSpace(pe_types=("INT16", "LightPE-1"))
    sess = ExplorationSession(backend, space)
    frame = sess.explore(small_layers, "net", n_per_type=30, seed=4,
                         vectorized=True)
    res = sess.explore(small_layers, "net", n_per_type=30, seed=4,
                       stream=True, chunk_size=13, workers=2)
    want = frame.select(frame.pareto(self.COLS))
    assert np.array_equal(res["pareto"].latency_s, want.latency_s)
    # joint streaming through the fitted models
    co = sess.co_explore(arch_accs, n_hw_per_type=6, seed=3, image_size=16,
                         vectorized=True)
    cols = ("top1_err", "energy_mj", "area_mm2")
    res_co = sess.co_explore(arch_accs, n_hw_per_type=6, seed=3,
                             image_size=16, stream=True, chunk_size=11,
                             workers=2)
    want_co = co.select(co.pareto(cols))
    assert np.array_equal(res_co["pareto"].latency_s, want_co.latency_s)

  def test_auto_threshold_routes_through_engine(self, small_layers,
                                                monkeypatch):
    import repro.explore.session as session_mod
    sess = ExplorationSession(VectorOracleBackend(chunk_size=64))
    base = sess.explore(small_layers, "net", n_per_type=25, seed=4)
    assert "streamed" not in base.meta
    monkeypatch.setattr(session_mod, "STREAM_AUTO_MIN_ROWS", 50)
    auto = sess.explore(small_layers, "net", n_per_type=25, seed=4)
    assert auto.meta["streamed"] == 1.0 and auto.meta["workers"] >= 1
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(auto, col), getattr(base, col)), col
    assert auto.table is not None
    assert auto.config_at(3) == base.config_at(3)

  def test_auto_threshold_co_explore(self, arch_accs, monkeypatch):
    import repro.explore.session as session_mod
    sess = ExplorationSession(VectorOracleBackend(chunk_size=512))
    base = sess.co_explore(arch_accs, n_hw_per_type=8, seed=3, image_size=16)
    monkeypatch.setattr(session_mod, "STREAM_AUTO_MIN_ROWS", 10)
    auto = sess.co_explore(arch_accs, n_hw_per_type=8, seed=3, image_size=16)
    assert auto.meta["streamed"] == 1.0
    assert len(auto) == len(base)
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(auto, col), getattr(base, col)), col
    assert np.array_equal(auto.extra["arch_id"], base.extra["arch_id"])

  def test_stream_requires_table_backend(self, small_layers):
    from repro.explore import OracleBackend
    sess = ExplorationSession(OracleBackend())
    with pytest.raises(ValueError, match="evaluate_table"):
      sess.explore(small_layers, "net", n_per_type=2, stream=True)
    with pytest.raises(ValueError, match="co_evaluate_table"):
      sess.co_explore([(object(), 0.9)], n_hw_per_type=2, stream=True)

  def test_reducers_require_stream(self, small_layers):
    sess = ExplorationSession(VectorOracleBackend())
    with pytest.raises(ValueError, match="stream=True"):
      sess.explore(small_layers, "net", n_per_type=2,
                   reducers={"p": ParetoAccumulator()})
    with pytest.raises(ValueError, match="stream=True"):
      sess.co_explore([(object(), 0.9)], n_hw_per_type=2,
                      reducers={"p": ParetoAccumulator()})

  def test_stream_rejects_measure_oracle(self, small_layers):
    sess = ExplorationSession(VectorOracleBackend())
    with pytest.raises(ValueError, match="one-shot"):
      sess.explore(small_layers, "net", n_per_type=2, stream=True,
                   measure_oracle=1)


# ---------------------------------------------------------------------------
# failure semantics: chunk-indexed errors, pool cancellation, accounting
# ---------------------------------------------------------------------------

class TestFailureSemantics:

  @staticmethod
  def tasks_with_bomb(n_chunks, bomb_at, rng_seed=0, rows=6):
    from repro.explore import ChunkTask, Rung
    rng = np.random.RandomState(rng_seed)
    frames = [random_frame(rng, rows) for _ in range(n_chunks)]

    def make(ci):
      def run():
        if ci == bomb_at:
          raise ValueError(f"chunk {ci} exploded")
        idx = np.arange(ci * rows, (ci + 1) * rows, dtype=np.int64)
        return frames[ci], idx
      return ChunkTask(index=ci, rungs=(Rung("numpy", run),))
    return [make(ci) for ci in range(n_chunks)]

  def test_serial_error_carries_chunk_index(self):
    from repro.explore import ChunkError
    from repro.explore.streaming import run_stream
    with pytest.raises(ChunkError) as err:
      run_stream(self.tasks_with_bomb(8, bomb_at=5),
                 {"pareto": ParetoAccumulator(("latency_s", "power_mw"))})
    assert err.value.chunk_index == 5
    assert "ValueError" in str(err.value)

  def test_pool_error_carries_chunk_index_and_cancels(self):
    from repro.explore import ChunkError
    from repro.explore.streaming import run_stream
    with pytest.raises(ChunkError) as err:
      run_stream(self.tasks_with_bomb(24, bomb_at=7),
                 {"pareto": ParetoAccumulator(("latency_s", "power_mw"))},
                 workers=3)
    assert err.value.chunk_index == 7

  def test_meta_failure_accounting_keys(self, small_layers):
    sess = ExplorationSession(VectorOracleBackend(chunk_size=64))
    res = sess.explore(small_layers, "net", n_per_type=20, seed=4,
                       stream=True, chunk_size=16)
    for key in ("n_retries", "n_demotions", "n_resumed_chunks",
                "n_overflows"):
      assert res.meta[key] == 0.0, key  # healthy run: all zero, all present


class TestSweepProgress:
  """``SweepProgress``, the one owner of a sweep's resume, folds,
  checkpoints and run stats for every chunk engine."""

  KEY = "p" * 64
  META_KEYS = {"seconds", "workers", "n_chunks", "rows_transferred",
               "rows_prefetched", "n_retries", "n_demotions",
               "n_resumed_chunks", "n_overflows"}

  @staticmethod
  def chunk(i, rows=5):
    frame = random_frame(np.random.RandomState(100 + i), rows)
    return frame, np.arange(i * rows, (i + 1) * rows, dtype=np.int64)

  @staticmethod
  def reducers():
    return {"pareto": ParetoAccumulator(("latency_s", "power_mw")),
            "stats": StatsAccumulator("power_mw")}

  def progress(self, tmp_path, writes=None, **kw):
    from repro.explore import SweepJournal
    from repro.explore.streaming import SweepProgress
    write = None if writes is None \
        else (lambda key, state: writes.append(sorted(state["done"])))
    return SweepProgress(self.reducers(), journal=SweepJournal(tmp_path),
                         key=self.KEY, write=write, **kw)

  def test_checkpoints_at_the_cadence_and_when_forced(self, tmp_path):
    writes = []
    progress = self.progress(tmp_path, writes, checkpoint_every=3)
    for i in range(7):
      progress.fold(i, self.chunk(i))
    assert writes == [[0, 1, 2], [0, 1, 2, 3, 4, 5]]
    progress.checkpoint(force=True)
    assert writes[-1] == list(range(7))

  def test_resumes_from_the_record_and_skips_folded_chunks(self, tmp_path):
    first = self.progress(tmp_path)
    for i in range(3):
      first.fold(i, self.chunk(i))
    again = self.progress(tmp_path)
    assert again.n_resumed == 3
    assert again.counters == first.counters
    tasks = [lambda i=i: self.chunk(i) for i in range(5)]
    assert [i for i, _ in again.todo(tasks)] == [3, 4]
    for i in (3, 4):
      again.fold(i, self.chunk(i))
    whole = self.progress(tmp_path / "whole")
    for i in range(5):
      whole.fold(i, self.chunk(i))
    got, want = again.result(0.0, 1), whole.result(0.0, 1)
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(got["pareto"], col),
                            getattr(want["pareto"], col)), col
    assert got["stats"] == want["stats"]
    assert got.meta["n_resumed_chunks"] == 3.0
    assert got.meta["n_chunks"] == want.meta["n_chunks"] == 5.0

  def test_fail_wraps_with_the_global_index_and_flushes(self, tmp_path):
    from repro.explore import ChunkError
    writes = []
    progress = self.progress(tmp_path, writes, checkpoint_every=100)
    cause = ValueError("bad chunk")
    with pytest.raises(ChunkError) as err:
      progress.fail(4, cause)
    assert err.value.chunk_index == 4
    assert err.value.__cause__ is cause
    assert len(writes) == 1  # forced, whatever the cadence
    typed = ChunkError(9, "already typed")
    with pytest.raises(ChunkError) as err:
      progress.fail(2, typed)
    assert err.value is typed and err.value.chunk_index == 9

  def test_a_failing_fold_is_not_marked_done(self, tmp_path):
    from repro.explore import ChunkError, SweepKilled

    class Broken:
      def __init__(self, exc):
        self.exc = exc

      def resolve(self):
        raise self.exc

    writes = []
    progress = self.progress(tmp_path, writes)
    with pytest.raises(ChunkError) as err:
      progress.fold(3, Broken(ValueError("resolve failed")))
    assert err.value.chunk_index == 3
    # a kill is never wrapped, and the journal is flushed on its way out
    with pytest.raises(SweepKilled):
      progress.fold(5, Broken(SweepKilled("kill -9")))
    assert writes == [[], []]
    assert progress.done == set()

  def test_result_meta_keys(self, tmp_path):
    from repro.explore import ResiliencePolicy
    from repro.explore.streaming import SweepProgress
    plain = SweepProgress(self.reducers()).result(1.5, 2)
    assert set(plain.meta) == self.META_KEYS
    assert plain.meta["workers"] == 2.0 and plain.meta["seconds"] == 1.5
    with_policy = SweepProgress(self.reducers(), ResiliencePolicy())
    res = with_policy.result(1.5, 1, extra_key=7.0)
    assert set(res.meta) == self.META_KEYS | {"n_leaked_watchdogs",
                                              "extra_key"}
