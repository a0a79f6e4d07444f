#!/usr/bin/env python3
"""Prove that the device-resident exploration path runs on a TPU.

Run from the repository root:

  python chip_smoke.py            one TPU chip: exactness probe, plain DSE,
                                  joint co-exploration, guided search
  python chip_smoke.py --chips 4  a four-chip host: the joint sweep through a
                                  DevicePool, and a one-shot co_evaluate_table
                                  sharded over every chip by shard_map

Every phase goes through the entry points a user calls
(``ExplorationSession.explore`` / ``co_explore`` / ``optimize``, or
``VectorOracleBackend.co_evaluate_table``) on ``VectorOracleBackend(jit=True)``
and is compared with the same call on the numpy backend.  Earlier lines
report each phase's compile seconds, wall seconds, rows/s, transfer and
fallback counters, and the comparison.  The last line of stdout is one JSON
object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit
code is 0 only when ``ok`` is true.  There is no CPU fallback: without a TPU
the script fails.  JAX's persistent compile cache is placed by
``repro.compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` when set).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

METRICS = ("latency_s", "power_mw", "area_mm2")

# Where the chip's float64 is not bit-identical to numpy (the exactness
# probe says which ops differ), survivors must still carry numpy's global
# row ids, and their values may differ from numpy's by at most this much,
# relatively.  Emulated float64 loses a few bits per operation (on a v5e
# survivors differ from numpy by at most ~5e-14); the oracle's own
# modelled process variation is +-0.4-0.5% per design point, so a
# difference at 1e-9 cannot change what a designer reads off a front.
VALUE_REL_BOUND = 1e-9

# "well below n_rows": the fused programs return only per-chunk survivors
MAX_TRANSFER_FRACTION = 0.05

# phase sizes (the ROADMAP's first cells)
PLAIN_ROWS_PER_TYPE = 262_144       # x 4 PE types = 1,048,576 rows
PLAIN_CHUNK = 65_536                # 16 equal chunks: one program shape
JOINT_ARCHS, JOINT_HW_PER_TYPE = 1000, 2500   # x 4 PE types = 10M pairs
JOINT_CHUNK = 262_144
JOINT_IMAGE_SIZE = 16
TOP_K = 100
SEARCH_POPULATION, SEARCH_GENERATIONS = 32, 12
SHARD_ARCHS = 40                    # four chips: 40 archs x 2,500 HW rows


def log(msg: str) -> None:
  print(msg, flush=True)


def exactness_probe(jax) -> dict:
  """The sentinel expressions of ``device.exact_codegen_active`` plus one
  representative of each op class the oracle runs on device, in float64
  on the default device, each compared with numpy."""
  import jax.numpy as jnp

  from repro.core import oracle
  rng = np.random.RandomState(0)
  n = 65_536
  a = rng.uniform(0.5, 1e6, n)
  b = rng.uniform(1.0, 3e3, n)
  c = rng.uniform(-10.0, 10.0, n)
  # SRAM word counts (decoder levels): every power of two up to 2^20 (the
  # largest in the default space, gbuf_kb * 512, is 2^18) and 1..~2^16
  words = np.concatenate([2.0 ** np.arange(21), np.arange(1.0, n - 20.0)])
  ops = {
      # exact_codegen_active's sentinels
      "fma_contraction": lambda xp, a, b, c, w: 0.028 * a + 0.006 * b,
      "divide_by_const": lambda xp, a, b, c, w: a / 3.0,
      "const_reassoc": lambda xp, a, b, c, w: a * 0.3 * 0.7,
      # op classes of oracle.characterize_* / dataflow on device
      "divide": lambda xp, a, b, c, w: a / b,
      "reciprocal_clamp": lambda xp, a, b, c, w: 1.0 / xp.maximum(a, 1e-12),
      "floor": lambda xp, a, b, c, w: xp.floor(a / b),
      "ceil": lambda xp, a, b, c, w: xp.ceil(a / b),
      "min_max": lambda xp, a, b, c, w: xp.minimum(xp.maximum(a, 100.0 * b),
                                                   5e5),
      "multiply_add": lambda xp, a, b, c, w: a * b + c,
      "sqrt": lambda xp, a, b, c, w: xp.sqrt(a),
      "decoder_levels": lambda xp, a, b, c, w: oracle._decoder_levels_arr(
          w, xp),
  }
  out = {}
  args = (a, b, c, words)
  with jax.enable_x64(True):
    for name, f in ops.items():
      # operands are arguments, never trace constants: XLA would fold
      # constant expressions on the compiling host, not on the chip
      got = np.asarray(jax.jit(lambda *x, f=f: f(jnp, *x))(*args),
                       np.float64)
      want = f(np, *args)
      diff = got != want
      with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.abs(got[diff] / want[diff] - 1.0)
      out[name] = {"identical": not diff.any(), "n_diff": int(diff.sum()),
                   "max_rel": float(rel.max()) if rel.size else 0.0}
  return out


def compare(dev, ref, exact: bool) -> dict:
  """Survivors of one reducer: global row ids, then values (bit for bit
  when the chip is exact, else within VALUE_REL_BOUND)."""
  d, r = dev.result(), ref.result()
  ids = bool(np.array_equal(dev.indices, ref.indices))
  same_shape = len(d) == len(r)
  identical = same_shape and all(
      np.array_equal(getattr(d, c), getattr(r, c)) for c in METRICS)
  max_rel = float("inf")
  if same_shape:
    max_rel = max((float(np.max(np.abs(getattr(d, c) / getattr(r, c) - 1.0)))
                   if len(r) else 0.0) for c in METRICS)
  ok = ids and (identical if exact else max_rel <= VALUE_REL_BOUND)
  return {"n": len(r), "ids_identical": ids, "values_identical": identical,
          "max_rel": max_rel, "ok": ok}


def meta_ok(meta: dict, n_rows: int, fused: bool) -> dict:
  """No demotion, no open breaker or quarantined device, and (for fused
  sweeps) an O(survivors) device->host transfer."""
  checks = {"n_demotions": meta.get("n_demotions", 0.0) == 0.0,
            "breaker_closed": meta.get("breaker_state", "closed") == "closed",
            "no_quarantine": meta.get("n_quarantined_devices", 0.0) == 0.0}
  if fused:
    frac = meta["rows_transferred"] / max(n_rows, 1)
    checks["transfer_fraction"] = frac < MAX_TRANSFER_FRACTION
  return checks


def report(phase: str, wall: float, compile_s: float, n_compiles: int,
           res, ref_wall: float, comps: dict, checks: dict) -> bool:
  meta = res.meta
  ok = all(c["ok"] for c in comps.values()) and all(checks.values())
  log(f"phase={phase} ok={ok} wall_s={wall} compile_s={compile_s} "
      f"n_compiles={n_compiles} rows={res.n_rows} "
      f"rows_per_s={res.n_rows / wall} "
      f"rows_transferred={int(meta['rows_transferred'])} "
      f"n_chunks={int(meta['n_chunks'])} "
      f"n_demotions={int(meta['n_demotions'])} "
      f"n_overflows={int(meta.get('n_overflows', 0))} "
      f"numpy_wall_s={ref_wall}")
  for name, c in comps.items():
    log(f"  {phase}/{name}: n={c['n']} ids_identical={c['ids_identical']} "
        f"values_identical={c['values_identical']} max_rel={c['max_rel']}")
  log(f"  {phase}/checks: {checks}")
  return ok


def timed(fn):
  """``fn()``, its wall seconds, and the seconds and count of the XLA
  compiles the process made meanwhile (persistent-cache hits make none)."""
  from repro.explore.spans import compile_totals
  s0, n0 = compile_totals()
  t0 = time.perf_counter()
  out = fn()
  wall = time.perf_counter() - t0
  s1, n1 = compile_totals()
  return out, wall, s1 - s0, n1 - n0


def sweep_reducers(cols):
  from repro.explore import ParetoAccumulator, TopKAccumulator
  return {"pareto": ParetoAccumulator(cols),
          "top": TopKAccumulator(TOP_K, by="energy_mj")}


def supernet_archs():
  """The 1,000 random supernet architectures of the 10M-pair sweep, with
  seeded pseudo-accuracies (framework_perf.streaming_perf's sweep)."""
  from repro.core.cnn import SEARCH_SPACE, ArchChoice
  rng = np.random.RandomState(0)
  archs = [ArchChoice(tuple((int(rng.choice(reps)), int(rng.choice(chs)))
                            for reps, chs in SEARCH_SPACE))
           for _ in range(JOINT_ARCHS)]
  return list(zip(archs, rng.uniform(0.5, 0.95, size=JOINT_ARCHS)))


def on_device(device, task):
  """Run one chunk task pinned to ``device`` and wait for its result."""
  from repro.explore.fleet import pin
  with pin(device):
    return task().resolve()


def compile_ahead(jobs) -> None:
  """Dispatch one chunk of every program shape (and device) the phases
  will run, all at once.  The sweeps would compile them one after
  another; here XLA compiles them concurrently, and the sweeps find them
  compiled."""
  from concurrent.futures import ThreadPoolExecutor

  def all_jobs():
    with ThreadPoolExecutor(len(jobs)) as ex:
      for fut in [ex.submit(job) for job in jobs]:
        fut.result()

  _, wall, cs, nc = timed(all_jobs)
  log(f"compile_ahead programs={len(jobs)} wall_s={wall} "
      f"compile_s_summed={cs} n_compiles={nc}")


class PlainPhase:
  """Grid DSE on the full resnet20 layer list, streamed and fused."""
  name = "plain_dse_resnet20"
  cols = ("perf_per_area", "energy_mj")

  def __init__(self):
    from repro.core.workloads import get_network
    from repro.explore import VectorOracleBackend
    self.layers = get_network("resnet20")
    self.backend = VectorOracleBackend(chunk_size=PLAIN_CHUNK, jit=True)

  def warm_jobs(self):
    from repro.explore import DesignSpace
    from repro.explore.streaming import explore_tasks
    first = next(explore_tasks(
        self.backend, DesignSpace(), self.layers, "resnet20",
        PLAIN_ROWS_PER_TYPE, 17, "random", PLAIN_CHUNK,
        sweep_reducers(self.cols)))
    return [lambda: first().resolve()]

  def sweep(self, backend):
    from repro.explore import ExplorationSession
    red = sweep_reducers(self.cols)
    res = ExplorationSession(backend).explore(
        self.layers, "resnet20", n_per_type=PLAIN_ROWS_PER_TYPE, seed=17,
        stream=True, reducers=red, chunk_size=PLAIN_CHUNK)
    return res, red

  def run(self, exact: bool) -> bool:
    from repro.explore import VectorOracleBackend
    (res, red), wall, cs, nc = timed(lambda: self.sweep(self.backend))
    (_, ref), ref_wall, _, _ = timed(
        lambda: self.sweep(VectorOracleBackend(chunk_size=PLAIN_CHUNK)))
    comps = {k: compare(red[k], ref[k], exact) for k in red}
    return report(self.name, wall, cs, nc, res, ref_wall, comps,
                  meta_ok(res.meta, res.n_rows, fused=True))


class JointPhase:
  """The 10M-pair streamed co-exploration, optionally through a
  DevicePool over every chip."""
  cols = ("top1_err", "energy_mj", "area_mm2")

  def __init__(self, pool=None):
    from repro.explore import VectorOracleBackend
    self.pool = pool
    self.name = "joint_coexplore_10M" + ("_pool" if pool else "")
    self.arch_accs = supernet_archs()
    self.backend = VectorOracleBackend(chunk_size=JOINT_CHUNK, jit=True)

  def warm_jobs(self):
    """The first and the last arch block of the first PE type: the two
    block shapes of the sweep (1,000 archs are 9 blocks of 104 and one of
    64), on every pool device."""
    import itertools

    from repro.explore import DesignSpace
    from repro.explore.streaming import co_explore_tasks
    per_type = -(-JOINT_ARCHS // (JOINT_CHUNK // JOINT_HW_PER_TYPE))
    tasks = list(itertools.islice(co_explore_tasks(
        self.backend, DesignSpace(), self.arch_accs, JOINT_HW_PER_TYPE, 3,
        JOINT_IMAGE_SIZE, "random", JOINT_CHUNK, sweep_reducers(self.cols)),
        per_type))
    shapes = [tasks[0], tasks[-1]]
    if self.pool is None:
      return [lambda t=t: t().resolve() for t in shapes]
    return [lambda d=d, t=t: on_device(d, t)
            for d in self.pool.devices() for t in shapes]

  def sweep(self, backend, pool=None):
    from repro.explore import ExplorationSession
    red = sweep_reducers(self.cols)
    res = ExplorationSession(backend).co_explore(
        self.arch_accs, n_hw_per_type=JOINT_HW_PER_TYPE, seed=3,
        image_size=JOINT_IMAGE_SIZE, stream=True, reducers=red,
        chunk_size=JOINT_CHUNK, pool=pool)
    return res, red

  def run(self, exact: bool) -> bool:
    from repro.explore import VectorOracleBackend
    (res, red), wall, cs, nc = timed(
        lambda: self.sweep(self.backend, self.pool))
    (_, ref), ref_wall, _, _ = timed(
        lambda: self.sweep(VectorOracleBackend(chunk_size=JOINT_CHUNK)))
    comps = {k: compare(red[k], ref[k], exact) for k in red}
    checks = meta_ok(res.meta, res.n_rows, fused=True)
    if self.pool is not None:
      per_dev = res.meta["fleet_device_chunks"]
      log(f"  {self.name}/fleet: devices={int(res.meta['fleet_devices'])} "
          f"chunks_per_device={[int(x) for x in per_dev]} "
          f"states={res.meta['fleet_device_states']} "
          f"n_speculative={int(res.meta['n_speculative'])} "
          f"n_resharded={int(res.meta['n_resharded'])}")
      checks["every_device_took_chunks"] = (
          len(per_dev) == self.pool.n_devices and min(per_dev) > 0)
    return report(self.name, wall, cs, nc, res, ref_wall, comps, checks)


class SearchPhase:
  """Guided search at a fixed budget.  Each generation is one unfused
  ``eval_pending`` dispatch, and every evaluated row returns to the host
  for selection, so rows_transferred equals n_rows here by design."""
  name = "guided_search_resnet20"
  cols = ("perf_per_area", "energy_mj")

  def __init__(self):
    from repro.core.workloads import get_network
    from repro.explore import VectorOracleBackend
    self.layers = get_network("resnet20")
    self.backend = VectorOracleBackend(jit=True)

  def warm_jobs(self):
    from repro.explore import DesignSpace
    space = DesignSpace()
    # one generation's shape: SEARCH_POPULATION rows
    table = space.sample_table(SEARCH_POPULATION // len(space.pe_types),
                               seed=0)
    return [lambda: self.backend.eval_pending(
        table, tuple(self.layers), "resnet20",
        np.arange(len(table))).resolve()]

  def sweep(self, backend):
    from repro.explore import ExplorationSession, ParetoAccumulator
    red = {"pareto": ParetoAccumulator(self.cols)}
    res = ExplorationSession(backend).optimize(
        self.layers, "resnet20", population=SEARCH_POPULATION,
        generations=SEARCH_GENERATIONS, seed=17, reducers=red)
    return res, red

  def run(self, exact: bool) -> bool:
    from repro.explore import VectorOracleBackend
    (res, red), wall, cs, nc = timed(lambda: self.sweep(self.backend))
    (ref_res, ref), ref_wall, _, _ = timed(
        lambda: self.sweep(VectorOracleBackend()))
    comps = {"pareto": compare(red["pareto"], ref["pareto"], exact)}
    checks = meta_ok(res.meta, res.n_rows, fused=False)
    checks["same_evaluations"] = res.n_rows == ref_res.n_rows
    log(f"  {self.name}/hypervolume: device={res.meta.get('hypervolume')} "
        f"numpy={ref_res.meta.get('hypervolume')}")
    return report(self.name, wall, cs, nc, res, ref_wall, comps, checks)


class ShardedPhase:
  """A one-shot joint evaluation whose HW rows VectorOracleBackend shards
  over every visible device with shard_map."""
  name = "sharded_co_evaluate"

  def __init__(self):
    from repro.core.dataflow import LayerStack
    from repro.core.supernet import arch_to_layers
    from repro.explore import DesignSpace, VectorOracleBackend
    archs = [a for a, _ in supernet_archs()[:SHARD_ARCHS]]
    self.stack = LayerStack.from_layer_lists(
        [arch_to_layers(a, image_size=JOINT_IMAGE_SIZE) for a in archs])
    space = DesignSpace()
    self.hw = space.sample_type_table(space.pe_types[0], JOINT_HW_PER_TYPE,
                                      seed=3)
    self.backend = VectorOracleBackend(chunk_size=JOINT_CHUNK, jit=True)

  def warm_jobs(self):
    return [lambda: self.backend.co_evaluate_table(self.hw, self.stack)]

  def run(self, exact: bool) -> bool:
    from repro.core import oracle
    from repro.explore import VectorOracleBackend
    from repro.explore.fleet import visible_devices
    dev, wall, cs, nc = timed(
        lambda: self.backend.co_evaluate_table(self.hw, self.stack))
    ref = VectorOracleBackend(chunk_size=JOINT_CHUNK).co_evaluate_table(
        self.hw, self.stack)
    identical = all(np.array_equal(getattr(dev, c), getattr(ref, c))
                    for c in METRICS)
    max_rel = max(float(np.max(np.abs(getattr(dev, c) / getattr(ref, c)
                                      - 1.0))) for c in METRICS)
    # the devices the (cached) program's outputs live on: all of them, or
    # the rows never left device 0
    unique_cols, slot_ids = self.stack.dedup_slots()
    with self.backend._x64():
      out = self.backend._joint_fn()(oracle.batch_inputs(self.hw),
                                     unique_cols, slot_ids, self.stack.valid,
                                     np.zeros(0))
    n_out_devices = min(len(o.sharding.device_set) for o in out)
    ok = (identical if exact else max_rel <= VALUE_REL_BOUND) \
        and n_out_devices == len(visible_devices())
    log(f"phase={self.name} ok={ok} wall_s={wall} compile_s={cs} "
        f"n_compiles={nc} rows={len(dev)} rows_per_s={len(dev) / wall} "
        f"output_devices={n_out_devices} values_identical={identical} "
        f"max_rel={max_rel}")
    return ok


def run(chips: int) -> dict:
  import jax

  from repro.compile_cache import enable_compile_cache
  cache_dir = enable_compile_cache()
  devs = jax.devices()
  device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
  log(f"device platform={device['platform']} kind={device['kind']} "
      f"count={device['count']} jax={jax.__version__} "
      f"compile_cache={cache_dir}")
  if device["platform"] != "tpu":
    log("no TPU found: this smoke test has no CPU fallback")
    return {"ok": False, "device": device}
  if device["count"] != chips:
    log(f"expected {chips} chips, found {device['count']}")
    return {"ok": False, "device": device}
  from repro.explore.fleet import device_topology
  log(f"topology {device_topology()}")

  from repro.explore.spans import compile_totals
  (probe, wall, cs, _) = timed(lambda: exactness_probe(jax))
  for name, r in probe.items():
    log(f"exact op={name} identical={r['identical']} n_diff={r['n_diff']} "
        f"max_rel={r['max_rel']}")
  exact = all(r["identical"] for r in probe.values())
  log(f"exactness: chip_bit_identical={exact} -> survivors compared "
      + ("bit for bit" if exact else
         f"by global row id and within rel {VALUE_REL_BOUND}")
      + f" (probe wall_s={wall} compile_s={cs})")

  if chips == 1:
    phases = [PlainPhase(), JointPhase(), SearchPhase()]
  else:
    from repro.explore import DevicePool
    phases = [JointPhase(pool=DevicePool()), ShardedPhase()]
  compile_ahead([job for p in phases for job in p.warm_jobs()])
  oks = [p.run(exact) for p in phases]
  compile_s, n_compiles = compile_totals()
  log(f"compile_s_total={compile_s} n_compiles={n_compiles}")
  return {"ok": all(oks), "device": device}


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                  help="1 (default): the main path on one chip; 4: only the "
                       "multi-device paths on a four-chip host")
  args = ap.parse_args()
  try:
    result = run(args.chips)
  except Exception:  # any failure ends the run with ok=false, exit 1
    traceback.print_exc()
    result = {"ok": False}
  print(json.dumps(result), flush=True)
  sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
  main()
