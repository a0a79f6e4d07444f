"""Fault-tolerant exploration: retry, degradation, resume, injection.

Covers the PR-8 acceptance matrix:
  * ``retrying``/``RetryPolicy`` back off through an injectable sleep
    (tests never wall-wait) and raise ``StepFailure`` on exhaustion;
  * ``FaultPlan`` schedules are exactly reproducible and fire each
    fault at most ``times`` times;
  * the ladder demotes past dead/hung rungs, never absorbs
    ``SweepKilled``, and counts every retry/demotion;
  * the ``SweepJournal`` round-trips reducer state atomically and
    treats corrupt/mismatched records as a fresh start;
  * killing a streamed co-exploration at *every* chunk boundary and
    resuming reproduces the uninterrupted reductions bit-identically,
    and a journal written by one engine (``run_stream``, ``run_fleet``,
    the exploration service) resumes under any other;
  * on a ``jit=True`` backend, injected device faults degrade chunks to
    the numpy rung with unchanged results (exact-codegen parity).
"""
import pickle
import threading

import numpy as np
import pytest

from repro.core.cnn import SEARCH_SPACE, ArchChoice
from repro.core.workloads import get_network
from repro.explore import (ChunkError, ChunkTask, DesignSpace, DevicePool,
                           ExplorationService, ExplorationSession, Fault,
                           FaultInjected, FaultPlan, InjectedHang,
                           ParetoAccumulator, ResiliencePolicy, ResultStore,
                           RetryPolicy, Rung, StatsAccumulator, SweepJournal,
                           SweepKilled, TopKAccumulator, VectorOracleBackend,
                           stream_co_explore, sweep_key)
from repro.explore.resilience import ChunkTimeout
from repro.explore.streaming import co_explore_sweep_key
from repro.train.fault_tolerance import StepFailure, retrying

METRICS = ("latency_s", "power_mw", "area_mm2")
COLS = ("perf_per_area", "energy_mj")


def no_wait() -> RetryPolicy:
  return RetryPolicy(sleep=lambda s: None)


def flaky(n_failures: int, result="ok", exc=RuntimeError):
  """Callable failing the first ``n_failures`` invocations."""
  state = {"calls": 0}

  def fn():
    state["calls"] += 1
    if state["calls"] <= n_failures:
      raise exc(f"transient #{state['calls']}")
    return result

  fn.state = state
  return fn


# ---------------------------------------------------------------------------
# the retry primitive (train.fault_tolerance.retrying + RetryPolicy)
# ---------------------------------------------------------------------------

class TestRetrying:

  def test_injected_sleep_sees_exponential_backoff(self):
    delays = []
    fn = flaky(2)
    out = retrying(fn, max_retries=2, sleep=delays.append,
                   base_delay=0.5, backoff=3.0)()
    assert out == "ok" and fn.state["calls"] == 3
    assert delays == [0.5, 1.5]

  def test_no_sleep_after_final_attempt(self):
    delays = []
    with pytest.raises(StepFailure):
      retrying(flaky(99), max_retries=2, sleep=delays.append)()
    assert len(delays) == 2  # backs off between attempts, not before raising

  def test_non_retryable_propagates_immediately(self):
    delays = []
    fn = flaky(1, exc=ValueError)
    with pytest.raises(ValueError):
      retrying(fn, max_retries=5, sleep=delays.append)()
    assert fn.state["calls"] == 1 and delays == []


class TestRetryPolicy:

  def test_on_retry_counts_reexecutions_exactly(self):
    seen = []
    out = no_wait().call(flaky(2), on_retry=lambda a, e: seen.append(a))
    assert out == "ok" and seen == [0, 1]

  def test_exhaustion_raises_stepfailure(self):
    seen = []
    with pytest.raises(StepFailure):
      no_wait().call(flaky(99), on_retry=lambda a, e: seen.append(a))
    assert seen == [0, 1]  # the terminal failure is not a retry


# ---------------------------------------------------------------------------
# deterministic fault injection
# ---------------------------------------------------------------------------

class TestFaultPlan:

  def test_seeded_schedule_reproducible(self):
    mk = lambda: FaultPlan.seeded(11, 50, p_raise=0.3, p_hang=0.2,
                                  p_kill=0.1)
    a, b = mk(), mk()
    assert a.faults == b.faults and len(a.faults) > 0
    assert FaultPlan.seeded(12, 50, p_raise=0.3).faults != a.faults

  def test_times_budget_exhausts(self):
    plan = FaultPlan([Fault("raise", 3, "device", times=2)])
    for _ in range(2):
      with pytest.raises(FaultInjected):
        plan.check("device", 3)
    plan.check("device", 3)  # budget spent: silent
    assert plan.n_fired == 2

  def test_layer_and_chunk_scoping(self):
    plan = FaultPlan([Fault("raise", 1, "device")])
    plan.check("backend", 1)  # wrong layer
    plan.check("device", 2)  # wrong chunk
    with pytest.raises(FaultInjected):
      plan.check("device", 1)

  def test_kill_and_hang_exception_types(self):
    plan = FaultPlan([Fault("kill", 0, "task"),
                      Fault("hang", 0, "device")])
    with pytest.raises(SweepKilled):
      plan.check("task", 0)
    with pytest.raises(InjectedHang):
      plan.check_resolve("device", 0)
    # SweepKilled must bypass retry-by-RuntimeError semantics entirely
    assert not issubclass(SweepKilled, RuntimeError)
    assert issubclass(FaultInjected, RuntimeError)
    assert issubclass(InjectedHang, ChunkTimeout)

  def test_validation(self):
    with pytest.raises(ValueError):
      Fault("explode", 0)
    with pytest.raises(ValueError):
      Fault("raise", 0, layer="cloud")
    with pytest.raises(ValueError):
      Fault("raise", 0, times=0)


# ---------------------------------------------------------------------------
# the degradation ladder (unit level, fake rungs)
# ---------------------------------------------------------------------------

class _FakePending:
  def __init__(self, fn):
    self._fn = fn

  def resolve(self):
    return self._fn()


def policy_of(**kw) -> ResiliencePolicy:
  kw.setdefault("retry", RetryPolicy(max_retries=1, sleep=lambda s: None))
  return ResiliencePolicy(**kw)


class TestLadder:

  def test_plain_callable_passes_through(self):
    assert policy_of().execute(lambda: 42) == 42

  def test_transient_healed_by_retry_alone(self):
    pol = policy_of()
    task = ChunkTask(0, (Rung("a", flaky(1, "healed")),))
    assert pol.execute(task) == "healed"
    assert pol.n_retries == 1 and pol.n_demotions == 0

  def test_dead_rung_demotes_to_next(self):
    pol = policy_of()
    task = ChunkTask(7, (Rung("device", flaky(99), layer="device"),
                         Rung("numpy", lambda: "fallback")))
    assert pol.execute(task) == "fallback"
    assert pol.n_demotions == 1
    assert pol.demotions == [(7, "device", "dispatch")]

  def test_all_rungs_dead_raises(self):
    pol = policy_of()
    task = ChunkTask(0, (Rung("a", flaky(99)), Rung("b", flaky(99))))
    with pytest.raises(StepFailure):
      pol.execute(task)
    assert pol.n_demotions == 1  # a -> b recorded; b's failure raised

  def test_sweepkilled_never_absorbed(self):
    def die():
      raise SweepKilled("kill -9")
    pol = policy_of()
    task = ChunkTask(0, (Rung("a", die), Rung("b", lambda: "nope")))
    with pytest.raises(SweepKilled):
      pol.execute(task)
    assert pol.n_retries == 0 and pol.n_demotions == 0

  def test_failed_resolution_demotes(self):
    boom = flaky(99)
    task = ChunkTask(4, (Rung("device", lambda: _FakePending(boom),
                              layer="device"),
                         Rung("numpy", lambda: "recomputed")))
    pol = policy_of()
    out = pol.execute(task)
    assert hasattr(out, "resolve")  # pending from a non-terminal rung
    assert out.resolve() == "recomputed"
    assert pol.demotions == [(4, "device", "resolve")]

  def test_injected_hang_demotes_without_waiting(self):
    plan = FaultPlan([Fault("hang", 2, "device")])
    task = ChunkTask(2, (Rung("device",
                              lambda: _FakePending(lambda: "from-device"),
                              layer="device"),
                         Rung("numpy", lambda: "from-host")))
    pol = policy_of(fault_plan=plan)
    assert pol.execute(task).resolve() == "from-host"
    assert pol.n_demotions == 1 and plan.n_fired == 1

  def test_watchdog_times_out_real_hang(self):
    hung = threading.Event()  # never set: resolve blocks forever

    def block():
      hung.wait(30.0)
      return "too-late"

    task = ChunkTask(0, (Rung("device", lambda: _FakePending(block),
                              layer="device"),
                         Rung("numpy", lambda: "rescued")))
    pol = policy_of(resolve_timeout=0.05)
    assert pol.execute(task).resolve() == "rescued"
    assert pol.demotions == [(0, "device", "resolve")]
    hung.set()  # unblock the abandoned daemon thread

  def test_terminal_rung_pending_not_guarded(self):
    # a pending from the LAST rung has nothing to demote to: it is
    # returned as-is (the engine resolves it in the dispatch window)
    pend = _FakePending(lambda: "direct")
    task = ChunkTask(0, (Rung("numpy", lambda: pend),))
    assert policy_of().execute(task) is pend


# ---------------------------------------------------------------------------
# the checkpoint journal
# ---------------------------------------------------------------------------

class TestJournal:

  def test_round_trip(self, tmp_path):
    j = SweepJournal(tmp_path)
    state = {"done": {0, 1}, "counters": {"n_rows": 64}}
    j.record("k" * 64, state)
    assert j.load("k" * 64) == state

  def test_missing_and_corrupt_are_fresh_starts(self, tmp_path):
    j = SweepJournal(tmp_path)
    assert j.load("a" * 64) is None
    j.record("a" * 64, {"done": set()})
    with open(j.path("a" * 64), "wb") as f:
      f.write(b"\x80truncated garbage")
    assert j.load("a" * 64) is None

  def test_key_and_version_mismatch_rejected(self, tmp_path):
    j = SweepJournal(tmp_path)
    key, other = "a" * 64, "b" * 64
    with open(j.path(key), "wb") as f:
      pickle.dump({"version": 1, "key": other, "state": {}}, f)
    assert j.load(key) is None
    with open(j.path(key), "wb") as f:
      pickle.dump({"version": 999, "key": key, "state": {}}, f)
    assert j.load(key) is None

  def test_sweep_key_sensitivity(self):
    base = dict(kind="explore", space_fp="s", reducers_fp="r",
                params={"seed": 3, "chunk_size": 64})
    k0 = sweep_key(**base)
    assert sweep_key(**base) == k0
    assert sweep_key("co-explore", "s", "r", base["params"]) != k0
    assert sweep_key("explore", "s2", "r", base["params"]) != k0
    assert sweep_key("explore", "s", "r", {"seed": 4,
                                           "chunk_size": 64}) != k0


# ---------------------------------------------------------------------------
# end to end: kill at every chunk boundary, resume bit-identically
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def arch_accs():
  rng = np.random.RandomState(7)
  archs = [ArchChoice(tuple((int(rng.choice(r)), int(rng.choice(c)))
                            for r, c in SEARCH_SPACE)) for _ in range(4)]
  return list(zip(archs, rng.uniform(0.5, 0.95, len(archs))))


def co_reducers():
  cols = ("top1_err", "energy_mj", "area_mm2")
  return {"pareto": ParetoAccumulator(cols),
          "top": TopKAccumulator(7, by="energy_mj"),
          "stats": StatsAccumulator("energy_mj")}


def run_co(sess, arch_accs, **kw):
  return sess.co_explore(arch_accs, n_hw_per_type=10, seed=3,
                         image_size=16, stream=True,
                         reducers=co_reducers(), chunk_size=13, **kw)


def assert_same_results(got, want):
  for name in ("pareto", "top"):
    for col in METRICS:
      assert np.array_equal(getattr(got[name], col),
                            getattr(want[name], col)), (name, col)
  assert np.array_equal(got["pareto"].extra["arch_id"],
                        want["pareto"].extra["arch_id"])
  assert got["stats"] == want["stats"]


class TestKillAndResume:

  def test_every_chunk_boundary(self, arch_accs, tmp_path):
    sess = ExplorationSession(VectorOracleBackend(chunk_size=512))
    ref = run_co(sess, arch_accs)
    n_chunks = int(ref.meta["n_chunks"])
    assert n_chunks >= 10  # the acceptance floor: a 10+-chunk sweep
    for k in range(n_chunks):
      jdir = tmp_path / f"kill-{k}"
      pol = ResiliencePolicy(retry=no_wait(),
                             fault_plan=FaultPlan([Fault("kill", k,
                                                         "task")]))
      with pytest.raises(ChunkError) as err:
        run_co(sess, arch_accs, policy=pol, resume_from=jdir)
      assert err.value.chunk_index == k
      res = run_co(sess, arch_accs, resume_from=jdir)
      assert_same_results(res, ref)
      assert res.meta["n_resumed_chunks"] == float(k)
      assert res.meta["n_chunks"] == float(n_chunks)

  def test_finished_journal_resumes_everything(self, arch_accs, tmp_path):
    sess = ExplorationSession(VectorOracleBackend(chunk_size=512))
    ref = run_co(sess, arch_accs, resume_from=tmp_path)
    res = run_co(sess, arch_accs, resume_from=tmp_path)
    assert_same_results(res, ref)
    assert res.meta["n_resumed_chunks"] == ref.meta["n_chunks"]

  def test_corrupt_journal_restarts_cleanly(self, arch_accs, tmp_path):
    sess = ExplorationSession(VectorOracleBackend(chunk_size=512))
    ref = run_co(sess, arch_accs, resume_from=tmp_path)
    for p in tmp_path.glob("sweep-*.pkl"):
      p.write_bytes(b"not a pickle")
    res = run_co(sess, arch_accs, resume_from=tmp_path)
    assert_same_results(res, ref)
    assert res.meta["n_resumed_chunks"] == 0.0

  def test_transient_faults_healed_in_place(self, arch_accs):
    sess = ExplorationSession(VectorOracleBackend(chunk_size=512))
    ref = run_co(sess, arch_accs)
    plan = FaultPlan([Fault("raise", 2, "task"),
                      Fault("raise", 5, "task")])
    pol = ResiliencePolicy(retry=no_wait(), fault_plan=plan)
    res = run_co(sess, arch_accs, policy=pol)
    assert_same_results(res, ref)
    assert res.meta["n_retries"] == 2.0
    assert res.meta["n_demotions"] == 0.0


# ---------------------------------------------------------------------------
# one journal, three engines: kill under one, resume under another
# ---------------------------------------------------------------------------

ENGINES = ("run_stream", "run_fleet", "service")
CO_KW = dict(n_hw_per_type=10, seed=3, image_size=16, chunk_size=13)


def run_engine(engine, arch_accs, store, fault_plan=None):
  """One streamed co-exploration journaled under ``store``'s journal:
  ``run_stream`` and ``run_fleet`` (a one-device ``DevicePool``) through
  ``stream_co_explore``, or a session of an ``ExplorationService`` over
  the store, which journals under the same sweep key."""
  backend = VectorOracleBackend(chunk_size=512)
  if engine == "service":
    svc = ExplorationService(backend, store=store, retry=no_wait(),
                             fault_plan=fault_plan)
    handle = svc.submit_co_explore(DesignSpace(), arch_accs,
                                   reducers=co_reducers(), **CO_KW)
    svc.drain()
    return handle.result()
  pool = DevicePool(devices=["dev0"]) if engine == "run_fleet" else None
  return stream_co_explore(
      backend, DesignSpace(), arch_accs, reducers=co_reducers(),
      policy=ResiliencePolicy(retry=no_wait(), fault_plan=fault_plan),
      resume_from=store.journal, pool=pool, **CO_KW)


@pytest.mark.parametrize("killed,resumed",
                         [(k, r) for k in ENGINES for r in ENGINES])
def test_resume_across_engines(killed, resumed, arch_accs, tmp_path):
  ref = stream_co_explore(VectorOracleBackend(chunk_size=512),
                          DesignSpace(), arch_accs, reducers=co_reducers(),
                          **CO_KW)
  n_chunks = int(ref.meta["n_chunks"])
  kill_at = n_chunks // 2
  plan = FaultPlan([Fault("kill", kill_at, "task")])
  # run_stream surfaces the kill as the chunk's ChunkError, the fleet and
  # the service as the SweepKilled itself
  with pytest.raises((ChunkError, SweepKilled)):
    run_engine(killed, arch_accs, ResultStore(tmp_path), plan)
  key = co_explore_sweep_key(DesignSpace(), co_reducers(), arch_accs,
                             n_hw_per_type=10, seed=3, image_size=16,
                             method="random", chunk_size=13)
  journaled = ResultStore(tmp_path).journal.load_state(key)["done"]
  # every chunk before the kill was folded, but for the one the fleet
  # still held in its dispatch window
  in_flight = 1 if killed == "run_fleet" else 0
  assert set(journaled) == set(range(kill_at - in_flight))
  res = run_engine(resumed, arch_accs, ResultStore(tmp_path))
  assert_same_results(res, ref)
  assert res.meta["n_resumed_chunks"] == float(len(journaled))
  assert res.meta["n_chunks"] == float(n_chunks)
  assert res.n_rows == ref.n_rows


# ---------------------------------------------------------------------------
# graceful degradation on the device path (jit backend)
# ---------------------------------------------------------------------------

class TestDeviceDegradation:

  def test_device_faults_degrade_to_numpy_bit_identically(self):
    pytest.importorskip("jax")
    layers = get_network("resnet20")[:4]
    sess = ExplorationSession(VectorOracleBackend(chunk_size=64, jit=True))

    def go(policy=None):
      # reducers are stateful accumulators: build fresh ones per run
      return sess.explore(
          layers, "net", n_per_type=40, seed=4, stream=True, chunk_size=32,
          policy=policy,
          reducers={"pareto": ParetoAccumulator(COLS),
                    "top": TopKAccumulator(5, by="energy_mj")})

    ref = go()
    # times=99: every device-layer dispatch for chunk 1 fails, so both
    # the fused and unfused device rungs exhaust and the chunk lands on
    # the numpy rung — whose rows are bit-identical (parity contract)
    plan = FaultPlan([Fault("raise", 1, "device", times=99)])
    pol = ResiliencePolicy(retry=no_wait(), fault_plan=plan)
    res = go(pol)
    assert res.meta["n_demotions"] > 0
    assert pol.demotions == [(1, "fused-device", "dispatch"),
                             (1, "device", "dispatch")]
    for name in ("pareto", "top"):
      for col in METRICS:
        assert np.array_equal(getattr(res[name], col),
                              getattr(ref[name], col)), (name, col)
