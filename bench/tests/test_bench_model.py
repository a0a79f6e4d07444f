"""The model-configuration cell on the CPU: it resolves its files, its
configuration file is what the program runs, the plain reference lowers
the step to the program's entries and its GEMM cycles to the program's
bit for bit, a tiny run reads ``correct``, and two planted faults in the
new formulas read it false: a cache moved at the weight width, and
every entry counted once."""
import dataclasses
import os

import numpy as np
import pytest

from bench_cells import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import compare, harness
from bench.reference import gemm, oracle, sweeps

CELL = "deepseek_v2_lite.decode32k"

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def no_cache_dir(monkeypatch):
  # keep this test process's compile-cache settings as they were
  monkeypatch.setattr("repro.compile_cache.enable_compile_cache",
                      lambda: "")


def tiny_cell() -> harness.Cell:
  """The cell cut to 2 MoE layers of 8 experts, 8 sequences at 256
  cached positions, 300 points per PE type in chunks of 128."""
  cell = harness.resolve(harness.load_benchmark(), CELL)
  cell.config.update(num_hidden_layers=3, n_routed_experts=8)
  cell.config["assumed"] = dict(cell.config["assumed"], decode_batch=8,
                                cached_positions=256)
  cell.traffic.update(n_per_type=300, chunk_size=128)
  module = harness.load_module(os.path.join(harness.BENCH_DIR, "configs",
                                            "deepseek_v2_lite.py"))
  cell.workload = module.workload(cell.config)
  return cell


def program_entries(cell):
  from repro.core.workloads import from_config
  layers = from_config(cell.driver.model_config(cell.config),
                       cell.driver.regime(cell.config))
  return [(l.M, l.K, l.N, l.groups, l.operand, l.count) for l in layers]


def test_cell_is_a_one_chip_plain_sweep_of_the_step():
  cell = harness.resolve(harness.load_benchmark(), CELL)
  assert cell.chips == 1 and cell.traffic["kind"] == "explore_model"
  assert cell.traffic["n_per_type"] * len(cell.config["pe_types"]) == 2**20
  assert {m["name"] for m in cell.per_layer} >= {
      "eval_table_ns_per_point_shape", "eval_table_hbm_pct",
      "device_idle_pct"}
  assert set(cell.limits) == {"ids_differ", "value_rel_gap"}


def test_configuration_is_what_the_program_runs():
  from repro.configs import get_config
  from repro.configs.shapes import SHAPES
  cell = harness.resolve(harness.load_benchmark(), CELL)
  assert cell.driver.model_config(cell.config) == \
      get_config("deepseek-v2-lite")
  spec, want = cell.driver.regime(cell.config), SHAPES["decode_32k"]
  assert (spec.seq_len, spec.global_batch, spec.mode) == \
      (want.seq_len, want.global_batch, want.mode)
  assert cell.config["reduced"] == []


@pytest.mark.parametrize("cut", ["tiny", "whole"])
def test_reference_lowering_is_the_programs(cut):
  cell = tiny_cell() if cut == "tiny" else \
      harness.resolve(harness.load_benchmark(), CELL)
  want = [tuple(e) for e in cell.workload["layers"][0]]
  assert program_entries(cell) == want
  if cut == "whole":
    assert len(want) == 117 and sum(e[5] for e in want) == 5286


def test_reference_cycles_match_the_scalar_oracle():
  from repro.core import oracle as prog
  from repro.core.dataflow import AcceleratorConfig, GemmLayer
  from repro.core.ppa import HW_RANGES
  cell = tiny_cell()
  entries = cell.workload["layers"][0]
  layers = [GemmLayer(f"e{i}", *e) for i, e in enumerate(entries)]
  for ti, pe_type in enumerate(cell.config["pe_types"]):
    hw = sweeps.sample_hw(HW_RANGES, 5, 60 + ti)
    c = oracle.columns(pe_type, hw)
    clock, pwr, area = oracle.hw_targets(c)
    lat = gemm.network_latency(c, entries, clock)
    for i in range(5):
      cfg = AcceleratorConfig(pe_type=pe_type, **{
          k: (float(v[i]) if k == "bandwidth_gbps" else int(v[i]))
          for k, v in hw.items()})
      ch = prog.characterize(cfg, layers)
      assert (ch.latency_s, ch.power_mw, ch.area_mm2) == \
          (lat[i], pwr[i], area[i])


def cache_at_weight_bits(monkeypatch):
  """The cache operand moves at the weight width."""
  from repro.core import dataflow
  monkeypatch.setattr(dataflow, "_right_bits_feats",
                      lambda c, f, xp: c["weight_bits"])


def counts_ignored(monkeypatch):
  """Every merged entry is counted once."""
  from repro.core.dataflow import layer_table
  from repro.explore import backend

  def once(layers):
    cols, counts = layer_table(layers)
    return cols, np.ones_like(counts)
  monkeypatch.setattr(backend, "_table_of", once)


@pytest.mark.parametrize("fault", [None, cache_at_weight_bits,
                                   counts_ignored],
                         ids=["sound", "cache_at_weight_bits",
                              "counts_ignored"])
def test_tiny_run_with_the_formulas_broken(fault, monkeypatch):
  cell = tiny_cell()
  if fault is not None:
    fault(monkeypatch)
  out = harness.run_cell(cell, 2**31 + 13, 0.3, False, 0.0,
                         require_chip=False)
  assert out["attempted"] >= 1 and out["failed"] == 0
  assert out["correct"] is (fault is None), out["checks"]
  assert np.isfinite(out["metrics"]["points_per_s"]["value"])


def test_numpy_path_matches_the_reference_exactly():
  from repro.explore import ExplorationSession, VectorOracleBackend
  cell = tiny_cell()
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic)
  driver.backend = VectorOracleBackend(chunk_size=cell.traffic["chunk_size"])
  driver.session = ExplorationSession(driver.backend, driver.space)
  got = driver.sweep(2**31 - 5)
  ref = cell.driver.reference(cell.config, cell.workload, cell.traffic,
                              2**31 - 5)
  assert compare.readings(got.answers, ref, cell.traffic["reducers"]) == \
      {"ids_differ": 0, "value_rel_gap": 0.0}
  assert got.meta["n_layer_shapes"] == len(cell.workload["layers"][0])


class Outcome:
  def __init__(self, meta):
    self.meta = meta


def ctx_with(meta, ops, traced_points=2_000_000):
  sweeps_ = [{"ok": True, "n_rows": 1_000_000, "outcome": Outcome(meta)}
             for _ in range(2)]
  return {"trace": None if ops is None else
          {"busy_s": [1.0], "window_s": 2.0, "device_ops": ops},
          "sweeps": sweeps_, "traced_points": traced_points}


def test_table_metrics_read_the_trace(monkeypatch):
  from bench import chip
  read = {name: harness.load_module(os.path.join(
      harness.BENCH_DIR, "metrics", name + ".py")).read
      for name in ("eval_table_ns_per_point_shape", "eval_table_hbm_pct")}
  monkeypatch.setattr(chip, "peaks", lambda kind: {"hbm_bytes_per_s": 1e9})
  meta = {"n_layer_shapes": 100.0, "bytes_to_device": 2.5e8,
          "rows_transferred": 1e4, "n_overflows": 0.0}
  ctx = ctx_with(meta, [["jit_fused_eval_table", 1.0], ["other", 5.0]])
  # 1 s over 2M points x 100 shapes
  assert read["eval_table_ns_per_point_shape"](ctx) == 5.0
  # (2.5e8 + 24e6 + 32e4) bytes a Mpoint, 2 Mpoints, over 1 s at 1 GB/s
  assert read["eval_table_hbm_pct"](ctx) == pytest.approx(
      100.0 * 2 * (2.5e8 + 24e6 + 32e4) / 1e9)
  overflowed = ctx_with(dict(meta, n_overflows=1.0),
                        [["jit_fused_eval_table", 1.0]])
  assert read["eval_table_hbm_pct"](overflowed) == pytest.approx(
      100.0 * 2 * (2.5e8 + 24e6) / 1e9)
  # untraced, a program without the table, or without the counters
  for quiet in (ctx_with(meta, None),
                ctx_with(meta, [["jit_fused_eval", 1.0]]),
                ctx_with({}, [["jit_fused_eval_table", 1.0]])):
    for r in read.values():
      assert r(quiet) is None


def test_lowering_shares_the_jit_program_across_routings():
  # a routing draw is data: the driver's program serves another draw
  from repro.explore import spans
  cell = tiny_cell()
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic)
  driver.sweep(3)
  base = driver.layers
  for seed in range(1, 60):
    model = dataclasses.replace(cell.driver.model_config(cell.config),
                                router_seed=seed)
    from repro.core.workloads import from_config
    other = from_config(model, cell.driver.regime(cell.config))
    if len(other) == len(base) and other != base:
      break
  driver.layers = other
  with spans.recording() as rec:
    driver.sweep(4)
  assert sum(v for k, v in rec.meta().items()
             if k.startswith("n_compiles_")) == 0
