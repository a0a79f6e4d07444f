"""The plain reference against the program on the CPU: the oracle on
spot rows against the program's scalar oracle, the fronts against brute
force, and whole tiny sweeps against the program's numpy path."""
import numpy as np
import pytest

from bench_cells import tiny_cell

from bench import compare, sweep
from bench.reference import oracle, sweeps


def brute_front(obj):
  n = len(obj)
  return np.array([not any(np.all(obj[j] <= obj[i]) and np.any(obj[j] < obj[i])
                           for j in range(n)) for i in range(n)])


@pytest.mark.parametrize("dims", [2, 3])
def test_fronts_match_brute_force(dims):
  rng = np.random.RandomState(dims)
  obj = rng.randint(0, 12, size=(400, dims)).astype(float)  # many ties
  want = brute_front(obj)
  got = sweeps.front_2d(obj[:, 0], obj[:, 1]) if dims == 2 \
      else sweeps.front_nd(obj, block=64)
  assert np.array_equal(got, want)
  assert np.array_equal(sweeps.front_nd(obj, block=50), want)


def test_top_k_breaks_ties_by_row_id():
  rng = np.random.RandomState(0)
  key = rng.randint(0, 20, size=500).astype(float)
  ids = rng.permutation(500) + 1000
  got = sweeps.top_k(key, ids, 37)
  assert np.array_equal(got, np.lexsort((ids, key))[:37])


def test_oracle_spot_rows_match_the_scalar_oracle():
  from repro.core import oracle as prog
  from repro.core.dataflow import AcceleratorConfig, ConvLayer
  from repro.core.ppa import HW_RANGES
  cell = tiny_cell("resnet20_cifar10.dse1m")
  layers = cell.workload["layers"][0]
  convs = [ConvLayer(f"l{i}", *l) for i, l in enumerate(layers)]
  for ti, pe_type in enumerate(cell.config["pe_types"]):
    hw = sweeps.sample_hw(HW_RANGES, 5, 40 + ti)
    c = oracle.columns(pe_type, hw)
    clock, pwr, area = oracle.hw_targets(c)
    lat = sweeps.network_latency(c, layers, clock)
    for i in range(5):
      cfg = AcceleratorConfig(pe_type=pe_type, **{
          k: (float(v[i]) if k == "bandwidth_gbps" else int(v[i]))
          for k, v in hw.items()})
      ch = prog.characterize(cfg, convs)
      assert (ch.latency_s, ch.power_mw, ch.area_mm2, ch.clock_mhz) == \
          (lat[i], pwr[i], area[i], clock[i])


@pytest.mark.parametrize("name", ["resnet20_cifar10.dse1m",
                                  "supernet_coexplore.stream10m"])
def test_tiny_sweep_matches_the_numpy_path(name):
  from repro.explore import ExplorationSession, VectorOracleBackend
  cell = tiny_cell(name)
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic)
  # the same sweep on the program's numpy backend
  driver.backend = VectorOracleBackend(chunk_size=cell.traffic["chunk_size"])
  driver.session = ExplorationSession(driver.backend, driver.space)
  for seed in (7, 2**31 - 1):
    got = driver.sweep(seed)
    ref = cell.driver.reference(cell.config, cell.workload, cell.traffic,
                                seed)
    assert got.n_rows == 4 * (cell.traffic.get("n_per_type") or
                              12 * cell.traffic["n_hw_per_type"])
    assert compare.readings(got.answers, ref, cell.traffic["reducers"]) == \
        {"ids_differ": 0, "value_rel_gap": 0.0}
    for name_, spec in cell.traffic["reducers"].items():
      assert len(ref[name_]["ids"]) >= (spec.get("k", 1) if
                                        spec["kind"] == "topk" else 1)
  assert set(got.answers) == set(sweep.make_reducers(
      cell.traffic["reducers"]))
