"""Elastic device-fleet sweeps: health registry, stragglers, SDC.

Covers the PR-10 acceptance matrix:
  * ``DevicePool`` admission prefers idle healthy devices, quarantines
    via per-device circuit breakers, and re-admits through the
    half-open probe;
  * fleet-layer fault injection (``slow`` / ``corrupt`` /
    ``device-lost``) is deterministic and device/chunk-targeted;
  * chaos property: a fault injected at *every* chunk boundary — one
    kind at a time and all three together — leaves the final fronts
    bit-identical to a solo single-device run, with the mitigation
    counters (``n_speculative`` / ``n_resharded`` /
    ``n_corruption_checks``) surfaced in ``StreamResult.meta``;
  * the SDC sentinel detects a silently-corrupting device by numpy-rung
    recomputation (parity is exact, so any mismatch is corruption),
    quarantines it, and replays its chunks;
  * watchdog threads are tracked, reaped, and reported as
    ``n_leaked_watchdogs`` (0 on every healthy path);
  * on a real 8-device jax host the fleet path reproduces the solo
    numpy front bit for bit (subprocess, ``slow`` marker).
"""
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.explore import (ChunkTask, DevicePool, Fault, FaultPlan,
                           ParetoAccumulator, ResiliencePolicy, RetryPolicy,
                           Rung, StatsAccumulator, SweepJournal,
                           TopKAccumulator, run_fleet)
from repro.explore.fleet import (_Shard, device_topology, pin,
                                 pinned_device, visible_devices)
from repro.explore.frame import ResultFrame
from repro.explore.resilience import ANY_CHUNK, WatchdogRegistry
from repro.explore.streaming import run_stream

METRICS = ("latency_s", "power_mw", "area_mm2")
ROWS = 6


def no_wait() -> RetryPolicy:
  return RetryPolicy(sleep=lambda s: None)


def chunk_result(i: int, n: int = ROWS):
  """Pure function of the chunk index — the fleet bit-identity premise."""
  rng = np.random.RandomState(1000 + i)
  frame = ResultFrame(rng.rand(n), rng.rand(n), rng.rand(n),
                      ["pe"] * n, (), "net")
  return frame, np.arange(i * n, (i + 1) * n, dtype=np.int64)


def fleet_tasks(n_chunks: int):
  """ChunkTasks whose 'device' rung is numpy under the hood, so the
  terminal-rung parity the SDC sentinel relies on is exact by
  construction (as it is for the real x64 device path)."""
  return [ChunkTask(i, (Rung("device", lambda i=i: chunk_result(i),
                             layer="device"),
                        Rung("numpy", lambda i=i: chunk_result(i))))
          for i in range(n_chunks)]


def make_pool(n_devices: int = 4, **kw) -> DevicePool:
  kw.setdefault("speculation_factor", 4.0)
  return DevicePool(devices=[f"fake{i}" for i in range(n_devices)], **kw)


def reducer_set():
  return {"pareto": ParetoAccumulator(),
          "top": TopKAccumulator(k=5, by="latency_s"),
          "stats": StatsAccumulator("latency_s")}


def solo_result(n_chunks: int):
  return run_stream(fleet_tasks(n_chunks), reducer_set())


def assert_fronts_identical(res, ref):
  for name in ("pareto", "top"):
    a, b = res.results[name], ref.results[name]
    for col in METRICS:
      assert np.array_equal(getattr(a, col), getattr(b, col)), (name, col)
  # Pareto/TopK are exactly chunk-order-invariant; Stats count/min/max
  # are too, but mean/std are only associativity-level under the reorder
  # a requeue introduces (documented on StatsAccumulator).
  s, r = res.results["stats"], ref.results["stats"]
  for key in ("count", "min", "max"):
    assert s[key] == r[key], key
  for key in ("mean", "std"):
    assert s[key] == pytest.approx(r[key], rel=1e-12), key
  assert res.n_rows == ref.n_rows


# ---------------------------------------------------------------------------
# pinning
# ---------------------------------------------------------------------------

class TestPin:

  def test_pin_nests_and_restores(self):
    assert pinned_device() is None
    with pin("d0"):
      assert pinned_device() == "d0"
      with pin("d1"):
        assert pinned_device() == "d1"
      assert pinned_device() == "d0"
    assert pinned_device() is None

  def test_pin_is_thread_local(self):
    seen = []
    with pin("main-dev"):
      t = threading.Thread(target=lambda: seen.append(pinned_device()))
      t.start()
      t.join(5.0)
    assert seen == [None]


class TestDeviceTopology:

  def test_names_the_devices_found(self):
    devs = visible_devices()
    assert device_topology() == {
        "platform": devs[0].platform, "n_devices": len(devs),
        "device_kinds": sorted({d.device_kind for d in devs})}

  def test_enumeration_error_propagates(self, monkeypatch):
    from repro.explore import fleet

    def no_backend():
      raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(fleet, "visible_devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
      fleet.device_topology()


# ---------------------------------------------------------------------------
# the health registry
# ---------------------------------------------------------------------------

class TestDevicePool:

  def test_validation(self):
    with pytest.raises(ValueError):
      DevicePool(devices=[])
    with pytest.raises(ValueError):
      make_pool(speculation_factor=1.0)
    with pytest.raises(ValueError):
      make_pool(sdc_check_every=-1)

  def test_checkout_balances_outstanding(self):
    pool = make_pool(3)
    picks = [pool.checkout() for _ in range(6)]
    assert sorted(picks[:3]) == [0, 1, 2]   # one each before any repeats
    assert sorted(picks[3:]) == [0, 1, 2]
    for i in picks:
      pool.checkin(i)

  def test_require_idle_excludes_busy_devices(self):
    pool = make_pool(2)
    a = pool.checkout()
    alt = pool.checkout(require_idle=True, exclude=(a,))
    assert alt is not None and alt != a
    assert pool.checkout(require_idle=True) is None  # both now busy

  def test_quarantine_skips_device_until_probe(self):
    pool = make_pool(2, breaker_cooldown=3, breaker_jitter=0)
    pool.quarantine(0)
    assert pool.meta()["n_quarantined_devices"] == 1.0
    # each refusal counts down the cooldown; the 3rd consult half-opens
    # and admits device 0 as the probe
    picks = []
    for _ in range(3):
      i = pool.checkout()
      picks.append(i)
      pool.checkin(i)
    assert picks == [1, 1, 0]

  def test_all_quarantined_checkout_returns_none(self):
    pool = make_pool(2, breaker_cooldown=50, breaker_jitter=0)
    pool.quarantine(0)
    pool.quarantine(1)
    assert pool.checkout() is None

  def test_lost_device_rejoins_via_half_open_probe(self):
    pool = make_pool(2, breaker_cooldown=2, breaker_jitter=0)
    pool.lose_device(0)
    assert pool.counters()["n_device_losses"] == 1
    # drain the cooldown with checkouts; device 0 must eventually probe
    seen = set()
    for _ in range(8):
      i = pool.checkout()
      if i is None:
        continue
      seen.add(i)
      pool.record_success(i)
      pool.checkin(i)
    assert 0 in seen

  def test_latency_feed_and_fleet_median(self):
    pool = make_pool(2, ewma_alpha=0.5)
    assert pool.fleet_latency() is None
    for _ in range(4):
      pool.record_latency(0, 1.0)
      pool.record_latency(1, 3.0)
    assert pool.ewma(0) == pytest.approx(1.0)
    med = pool.fleet_latency()
    assert med is not None and 1.0 <= med <= 3.0

  def test_meta_shape(self):
    pool = make_pool(3)
    meta = pool.meta()
    assert meta["fleet_devices"] == 3.0
    assert len(meta["fleet_device_states"]) == 3
    assert len(meta["fleet_device_ewma_s"]) == 3
    for key in ("n_speculative", "n_resharded", "n_corruption_checks",
                "n_corruptions_detected", "n_device_losses"):
      assert meta[key] == 0.0


# ---------------------------------------------------------------------------
# fleet fault injection
# ---------------------------------------------------------------------------

class TestFleetFaults:

  def test_kind_layer_validation(self):
    with pytest.raises(ValueError):
      Fault("slow", 0, "device")          # fleet kinds need layer=fleet
    with pytest.raises(ValueError):
      Fault("raise", 0, "fleet")          # and only fleet kinds may use it
    with pytest.raises(ValueError):
      Fault("raise", 0, "device", device=1)   # device targeting fleet-only
    with pytest.raises(ValueError):
      Fault("raise", ANY_CHUNK, "device")     # wildcard fleet-only

  def test_check_fleet_targets_device_and_chunk(self):
    plan = FaultPlan([Fault("slow", 3, "fleet", device=1)])
    assert plan.check_fleet(0, 3) is None
    assert plan.check_fleet(1, 2) is None
    assert plan.check_fleet(1, 3) == "slow"
    assert plan.check_fleet(1, 3) is None   # times budget spent
    assert plan.n_fired == 1

  def test_any_chunk_wildcard_models_sick_device(self):
    plan = FaultPlan([Fault("corrupt", ANY_CHUNK, "fleet", times=3,
                            device=2)])
    assert [plan.check_fleet(2, c) for c in (7, 11, 13, 17)] == \
        ["corrupt", "corrupt", "corrupt", None]

  def test_seeded_fleet_reproducible(self):
    mk = lambda: FaultPlan.seeded_fleet(9, 40, 4, p_slow=0.3,
                                        p_corrupt=0.2, p_lost=0.1)
    a, b = mk(), mk()
    assert a.faults == b.faults and len(a.faults) > 0
    assert all(f.layer == "fleet" for f in a.faults)
    assert FaultPlan.seeded_fleet(10, 40, 4, p_slow=0.3).faults != a.faults


# ---------------------------------------------------------------------------
# fleet execution: healthy path
# ---------------------------------------------------------------------------

class TestFleetHealthy:

  def test_fronts_match_solo_run(self):
    ref = solo_result(10)
    res = run_stream(fleet_tasks(10), reducer_set(), pool=make_pool(4))
    assert_fronts_identical(res, ref)

  def test_meta_carries_fleet_counters(self):
    res = run_stream(fleet_tasks(6), reducer_set(), pool=make_pool(2),
                     policy=ResiliencePolicy(retry=no_wait()))
    for key in ("n_speculative", "n_resharded", "n_corruption_checks",
                "fleet_devices", "fleet_device_states",
                "n_quarantined_devices"):
      assert key in res.meta
    assert res.meta["n_leaked_watchdogs"] == 0.0
    assert res.meta["fleet_devices"] == 2.0
    assert res.meta["n_chunks"] == 6.0

  def test_sdc_sentinel_zero_and_nonzero_overhead_paths(self):
    ref = solo_result(8)
    off = run_stream(fleet_tasks(8), reducer_set(),
                     pool=make_pool(3, sdc_check_every=0))
    on = run_stream(fleet_tasks(8), reducer_set(),
                    pool=make_pool(3, sdc_check_every=1))
    assert_fronts_identical(off, ref)
    assert_fronts_identical(on, ref)
    assert off.meta["n_corruption_checks"] == 0.0
    assert on.meta["n_corruption_checks"] > 0.0
    assert on.meta["n_corruptions_detected"] == 0.0

  def test_all_devices_quarantined_falls_back_to_terminal_rung(self):
    pool = make_pool(2, breaker_cooldown=100, breaker_jitter=0)
    pool.quarantine(0)
    pool.quarantine(1)
    ref = solo_result(5)
    res = run_stream(fleet_tasks(5), reducer_set(), pool=pool)
    assert_fronts_identical(res, ref)

  def test_resume_from_journal(self, tmp_path):
    ref = solo_result(7)
    jr = SweepJournal(tmp_path)
    key = "f" * 64
    half = run_fleet(fleet_tasks(7)[:3], reducer_set(), make_pool(2),
                     resume_from=jr, journal_key=key)
    assert half.meta["n_chunks"] == 3.0
    res = run_fleet(fleet_tasks(7), reducer_set(), make_pool(2),
                    resume_from=jr, journal_key=key)
    assert res.meta["n_resumed_chunks"] == 3.0
    assert_fronts_identical(res, ref)


# ---------------------------------------------------------------------------
# chaos: faults at every chunk boundary stay bit-identical
# ---------------------------------------------------------------------------

N_CHAOS_CHUNKS = 8


class TestFleetChaos:

  @pytest.mark.parametrize("kind", ["slow", "corrupt", "device-lost"])
  def test_single_fault_at_every_chunk_boundary(self, kind):
    ref = solo_result(N_CHAOS_CHUNKS)
    for chunk in range(N_CHAOS_CHUNKS):
      plan = FaultPlan([Fault(kind, chunk, "fleet")])
      pool = make_pool(4, sdc_check_every=1)
      res = run_stream(
          fleet_tasks(N_CHAOS_CHUNKS), reducer_set(), pool=pool,
          policy=ResiliencePolicy(retry=no_wait(), fault_plan=plan))
      assert_fronts_identical(res, ref)
      assert res.meta["n_leaked_watchdogs"] == 0.0
      if kind == "device-lost":
        assert plan.n_fired == 1
        assert res.meta["n_device_losses"] == 1.0
        assert res.meta["n_resharded"] >= 1.0
      if kind == "corrupt" and plan.n_fired:
        assert res.meta["n_corruptions_detected"] == 1.0
        assert res.meta["n_corruption_checks"] >= 1.0
        assert res.meta["n_resharded"] >= 1.0

  def test_straggler_speculation_fires_at_the_tail(self):
    # a slow shard near the end of the sweep, when idle devices exist
    ref = solo_result(6)
    plan = FaultPlan([Fault("slow", 5, "fleet")])
    pool = make_pool(3)
    res = run_stream(fleet_tasks(6), reducer_set(), pool=pool,
                     policy=ResiliencePolicy(retry=no_wait(),
                                             fault_plan=plan))
    assert_fronts_identical(res, ref)
    assert res.meta["n_speculative"] >= 1.0

  def test_silently_corrupting_device_quarantined_and_replayed(self):
    # a persistently sick device: every chunk it touches is corrupted
    ref = solo_result(N_CHAOS_CHUNKS)
    plan = FaultPlan([Fault("corrupt", ANY_CHUNK, "fleet", times=100,
                            device=1)])
    pool = make_pool(3, sdc_check_every=1, breaker_cooldown=50,
                     breaker_jitter=0)
    res = run_stream(
        fleet_tasks(N_CHAOS_CHUNKS), reducer_set(), pool=pool,
        policy=ResiliencePolicy(retry=no_wait(), fault_plan=plan))
    assert_fronts_identical(res, ref)
    assert res.meta["n_corruptions_detected"] >= 1.0
    assert "open" in res.meta["fleet_device_states"]

  def test_combined_chaos_run(self):
    # the acceptance scenario: 1 straggler + 1 device lost mid-sweep +
    # 1 corrupting device, all in one sweep
    n = 12
    ref = solo_result(n)
    plan = FaultPlan([Fault("slow", n - 1, "fleet"),
                      Fault("device-lost", 4, "fleet"),
                      Fault("corrupt", 7, "fleet")])
    pool = make_pool(4, sdc_check_every=1)
    res = run_stream(fleet_tasks(n), reducer_set(), pool=pool,
                     policy=ResiliencePolicy(retry=no_wait(),
                                             fault_plan=plan))
    assert_fronts_identical(res, ref)
    assert res.meta["n_device_losses"] == 1.0
    assert res.meta["n_resharded"] >= 1.0
    assert res.meta["n_corruptions_detected"] == 1.0
    assert res.meta["n_leaked_watchdogs"] == 0.0

  def test_seeded_chaos_storm(self):
    # seeded random faults of all three kinds across the whole sweep
    n = 16
    ref = solo_result(n)
    plan = FaultPlan.seeded_fleet(23, n, 4, p_slow=0.25, p_corrupt=0.25,
                                  p_lost=0.15)
    assert len(plan.faults) > 0
    pool = make_pool(4, sdc_check_every=1)
    res = run_stream(fleet_tasks(n), reducer_set(), pool=pool,
                     policy=ResiliencePolicy(retry=no_wait(),
                                             fault_plan=plan))
    assert_fronts_identical(res, ref)
    assert res.meta["n_leaked_watchdogs"] == 0.0


# ---------------------------------------------------------------------------
# watchdog thread accounting (satellite: the daemon-thread leak fix)
# ---------------------------------------------------------------------------

class _FakePending:
  def __init__(self, fn):
    self._fn = fn

  def resolve(self):
    return self._fn()


class TestWatchdogRegistry:

  def test_tracks_and_reaps(self):
    reg = WatchdogRegistry()
    gate = threading.Event()
    t = threading.Thread(target=gate.wait, daemon=True)
    t.start()
    reg.track(t)
    assert reg.n_live() == 1 and reg.n_spawned == 1
    gate.set()
    assert reg.drain(timeout=5.0) == 0
    assert reg.n_reaped == 1

  def test_hung_resolution_is_tracked_not_abandoned(self):
    gate = threading.Event()

    def block():
      gate.wait(30.0)
      return "too-late"

    task = ChunkTask(0, (Rung("device", lambda: _FakePending(block),
                              layer="device"),
                         Rung("numpy", lambda: "rescued")))
    pol = ResiliencePolicy(retry=no_wait(), resolve_timeout=0.05)
    assert pol.execute(task).resolve() == "rescued"
    assert pol.watchdogs.n_live() == 1     # the hung thread is referenced
    gate.set()
    assert pol.watchdogs.drain(timeout=5.0) == 0

  def test_run_stream_reports_zero_leaks_when_healthy(self):
    res = run_stream(fleet_tasks(4), {"pareto": ParetoAccumulator()},
                     policy=ResiliencePolicy(retry=no_wait()))
    assert res.meta["n_leaked_watchdogs"] == 0.0


# ---------------------------------------------------------------------------
# real multi-device bit-identity (subprocess: device count is
# process-start-only)
# ---------------------------------------------------------------------------

_REAL_FLEET_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
    # the exact-codegen flags must be set before visible_devices()
    # initializes the XLA client, or the parity contract is void
    from repro.explore.device import ensure_exact_cpu_codegen
    ensure_exact_cpu_codegen()
    import numpy as np
    from repro.core.workloads import get_network
    from repro.explore import (DesignSpace, DevicePool, FaultPlan, Fault,
                               ParetoAccumulator, ResiliencePolicy,
                               RetryPolicy, VectorOracleBackend,
                               stream_explore, visible_devices)
    from repro.explore.resilience import ANY_CHUNK

    assert len(visible_devices()) == 8, visible_devices()
    layers = get_network("resnet20")[:4]
    space = DesignSpace()
    mk = lambda: {"pareto": ParetoAccumulator()}
    solo = stream_explore(VectorOracleBackend(), space, layers,
                          n_per_type=120, seed=13, chunk_size=50,
                          reducers=mk(), workers=1)
    pool = DevicePool(sdc_check_every=2)
    plan = FaultPlan([Fault("device-lost", 1, "fleet"),
                      Fault("slow", 3, "fleet"),
                      Fault("corrupt", 2, "fleet")])
    res = stream_explore(
        VectorOracleBackend(jit=True), space, layers, n_per_type=120,
        seed=13, chunk_size=50, reducers=mk(), pool=pool,
        policy=ResiliencePolicy(retry=RetryPolicy(sleep=lambda s: None),
                                fault_plan=plan))
    a, b = res.results["pareto"], solo.results["pareto"]
    for col in ("latency_s", "power_mw", "area_mm2"):
      assert np.array_equal(getattr(a, col), getattr(b, col)), col
    assert res.n_rows == solo.n_rows
    assert res.meta["fleet_devices"] == 8.0
    assert res.meta["n_device_losses"] == 1.0
    assert res.meta["n_corruption_checks"] >= 1.0
    assert res.meta["n_leaked_watchdogs"] == 0.0
    print("FLEET-8DEV-OK", int(res.meta["n_chunks"]),
          int(res.meta["n_resharded"]))
""")


@pytest.mark.slow
def test_real_eight_device_fleet_bit_identity():
  pytest.importorskip("jax")
  env = dict(os.environ)
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(os.path.dirname(__file__), "..", "src"),
       env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
  env.pop("XLA_FLAGS", None)  # the child builds its own (8 forced devices)
  proc = subprocess.run([sys.executable, "-c", _REAL_FLEET_SCRIPT],
                        capture_output=True, text=True, env=env,
                        timeout=600)
  assert proc.returncode == 0, proc.stderr[-4000:]
  assert "FLEET-8DEV-OK" in proc.stdout
