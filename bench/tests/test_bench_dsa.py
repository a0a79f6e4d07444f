"""The DeepSeek-V3.2 cell on the CPU: it resolves its files, its
configuration file is what the program runs, the plain reference's
product list lowers to the program's entries (with the indexer choosing
fewer positions than are cached, and more) and its cycles to the scalar
oracle's bit for bit, a tiny run reads ``correct``, and planted faults
read it false: the indexer's keys at the activation width (in a sweep
of the wide PE types) or a stored width read as the weight width (at
the cell's own types), the attention
over every cached position, half the chunks left out of the fold, and
the float32 control."""
import dataclasses
import os

import numpy as np
import pytest

from bench_cells import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import compare, harness
from bench.reference import gemm_widths, oracle, sweeps

CELL = "deepseek_v3_2.decode128k"
METRICS = {"dsa_table_ns_per_point_shape": "eval_table_ns_per_point_shape",
           "dsa_table_hbm_pct": "eval_table_hbm_pct"}

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def no_cache_dir(monkeypatch):
  # keep this test process's compile-cache settings as they were
  monkeypatch.setattr("repro.compile_cache.enable_compile_cache",
                      lambda: "")


def tiny_cell(cached: int = 256) -> harness.Cell:
  """The cell cut to 1 dense and 2 MoE layers of 8 experts, 8 sequences
  at ``cached`` positions of which the indexer picks 64, and 300 points
  per PE type in chunks of 128."""
  cell = harness.resolve(harness.load_benchmark(), CELL)
  cell.config.update(num_hidden_layers=3, first_k_dense_replace=1,
                     n_routed_experts=8, index_topk=64)
  cell.config["assumed"] = dict(cell.config["assumed"], decode_batch=8,
                                cached_positions=cached)
  cell.traffic.update(n_per_type=300, chunk_size=128)
  module = harness.load_module(os.path.join(harness.BENCH_DIR, "configs",
                                            "deepseek_v3_2.py"))
  cell.workload = module.workload(cell.config)
  return cell


def program_entries(cell):
  from repro.core.workloads import from_config
  layers = from_config(cell.driver.model_config(cell.config),
                       cell.driver.regime(cell.config))
  return [(l.M, l.K, l.N, l.groups, l.operand, l.count, l.bits)
          for l in layers]


def test_cell_is_a_one_chip_plain_sweep_of_the_step():
  cell = harness.resolve(harness.load_benchmark(), CELL)
  assert cell.chips == 1 and cell.traffic["kind"] == "explore_model_widths"
  assert cell.traffic["n_per_type"] * len(cell.config["pe_types"]) == 2**20
  same = harness.load_json(harness.BENCH_DIR, "traffic", "model_dse1m.json")
  assert {k: v for k, v in cell.traffic.items() if k != "kind"} == \
      {k: v for k, v in same.items() if k != "kind"}
  names = {m["name"] for m in cell.per_layer}
  assert names >= set(METRICS) | {"device_idle_pct"}
  assert not names & set(METRICS.values())
  assert set(cell.limits) == {"ids_differ", "value_rel_gap"}


def test_configuration_is_what_the_program_runs():
  from repro.configs import get_config
  cell = harness.resolve(harness.load_benchmark(), CELL)
  assert cell.driver.model_config(cell.config) == \
      get_config("deepseek-v3.2")
  spec = cell.driver.regime(cell.config)
  assert (spec.seq_len, spec.global_batch, spec.mode) == \
      (131072, 32, "decode")
  assert cell.config["reduced"] == []
  assert (cell.config["num_hidden_layers"], cell.config["n_routed_experts"],
          cell.config["vocab_size"]) == (61, 256, 129280)


@pytest.mark.parametrize("cut", ["tiny", "tiny_short", "whole"])
def test_reference_lowering_is_the_programs(cut):
  cell = {"tiny": lambda: tiny_cell(256), "tiny_short": lambda: tiny_cell(32),
          "whole": lambda: harness.resolve(harness.load_benchmark(),
                                           CELL)}[cut]()
  want = [tuple(e) for e in cell.workload["layers"][0]]
  assert program_entries(cell) == want
  # the attention reads min(index_topk, L) positions of the latent cache
  topk = cell.config["index_topk"]
  cached = cell.config["assumed"]["cached_positions"]
  scores = [e for e in want if e[:2] == (128, 576)]
  assert [e[2] for e in scores] == [min(topk, cached)]
  assert [e[6] for e in want if e[6] is not None] == [8]
  if cut == "whole":
    assert len(want) == 45 and sum(e[5] for e in want) == 27000


def test_reference_cycles_match_the_scalar_oracle():
  from repro.core import oracle as prog
  from repro.core.dataflow import AcceleratorConfig, GemmLayer
  from repro.core.ppa import HW_RANGES
  cell = tiny_cell()
  entries = cell.workload["layers"][0]
  layers = [GemmLayer(f"e{i}", *e) for i, e in enumerate(entries)]
  for ti, pe_type in enumerate(cell.config["pe_types"]):
    hw = sweeps.sample_hw(HW_RANGES, 5, 70 + ti)
    c = oracle.columns(pe_type, hw)
    clock, pwr, area = oracle.hw_targets(c)
    lat = gemm_widths.network_latency(c, entries, clock)
    for i in range(5):
      cfg = AcceleratorConfig(pe_type=pe_type, **{
          k: (float(v[i]) if k == "bandwidth_gbps" else int(v[i]))
          for k, v in hw.items()})
      ch = prog.characterize(cfg, layers)
      assert (ch.latency_s, ch.power_mw, ch.area_mm2) == \
          (lat[i], pwr[i], area[i])


def index_keys_at_act_bits(monkeypatch, cell):
  """The indexer's key cache moves at the activation width."""
  from repro.core import workloads
  monkeypatch.setattr(workloads, "INDEX_KEY_BITS", None)


def stored_width_as_weight_bits(monkeypatch, cell):
  """The table codes a stored width as the PE type's weight width."""
  from repro.core import dataflow
  from repro.explore import backend
  code = dataflow.width_code
  monkeypatch.setattr(dataflow, "width_code", lambda layer: 0.0
                      if layer.bits is not None else code(layer))
  # the backend keeps one table per layer tuple: build this one afresh
  monkeypatch.setattr(backend, "_table_of", dataflow.layer_table)


def dense_attention(monkeypatch, cell):
  """The attention reads every cached position, not the indexer's pick."""
  build = cell.driver.model_config
  monkeypatch.setattr(cell.driver, "model_config", lambda config: dataclasses
                      .replace(build(config), index_topk=2**30))


def drop_half(monkeypatch, cell):
  """Every other chunk is never folded."""
  from repro.explore import streaming
  fold = streaming.fold_chunk
  seen = []

  def half(reducers, counters, result):
    seen.append(1)
    if len(seen) % 2:
      fold(reducers, counters, result)
  monkeypatch.setattr(streaming, "fold_chunk", half)


# LightPE-1 rows hold every answer of the cell, and their act_bits are
# the keys' 8 bits: keys at act_bits move only FP32 and INT16 rows, so
# that fault is planted in a sweep of those two types.  A stored width
# decoded as LightPE-1's 4 weight bits moves the cell's own answers.
WIDE = ["FP32", "INT16"]


@pytest.mark.parametrize("fault,pe_types", [
    (None, None), (None, WIDE), (index_keys_at_act_bits, WIDE),
    (stored_width_as_weight_bits, None), (dense_attention, None),
    (drop_half, None)],
    ids=["sound", "sound_wide_types", "index_keys_at_act_bits",
         "stored_width_as_weight_bits", "dense_attention", "half"])
def test_tiny_run_with_the_program_broken(fault, pe_types, monkeypatch):
  cell = tiny_cell()
  if pe_types is not None:
    cell.config["pe_types"] = pe_types
  if fault is not None:
    fault(monkeypatch, cell)
  out = harness.run_cell(cell, 2**31 + 17, 0.3, False, 0.0,
                         require_chip=False)
  assert out["attempted"] >= 1 and out["failed"] == 0
  assert out["correct"] is (fault is None), out["checks"]
  assert np.isfinite(out["metrics"]["points_per_s"]["value"])


def test_float32_control_fails():
  cell = tiny_cell()
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic,
                              precision="float32")
  got = driver.sweep(2**31 + 19)
  ref = cell.driver.reference(cell.config, cell.workload, cell.traffic,
                              2**31 + 19)
  checks = compare.verdict(compare.readings(got.answers, ref,
                                            cell.traffic["reducers"]),
                           cell.limits)
  assert not compare.within(checks)
  assert checks["value_rel_gap"]["value"] > \
      100 * checks["value_rel_gap"]["limit"]


def test_numpy_path_matches_the_reference_exactly():
  from repro.explore import ExplorationSession, VectorOracleBackend
  cell = tiny_cell()
  driver = cell.driver.Driver(cell.config, cell.workload, cell.traffic)
  driver.backend = VectorOracleBackend(chunk_size=cell.traffic["chunk_size"])
  driver.session = ExplorationSession(driver.backend, driver.space)
  got = driver.sweep(2**31 - 7)
  ref = cell.driver.reference(cell.config, cell.workload, cell.traffic,
                              2**31 - 7)
  assert compare.readings(got.answers, ref, cell.traffic["reducers"]) == \
      {"ids_differ": 0, "value_rel_gap": 0.0}
  entries = cell.workload["layers"][0]
  assert (got.meta["n_layer_shapes"], got.meta["n_cache_shapes"],
          got.meta["n_narrow_shapes"]) == \
      (len(entries), sum(e[4] == "cache" for e in entries), 1)


class Outcome:
  def __init__(self, meta):
    self.meta = meta


def ctx_with(meta, ops):
  sweeps_ = [{"ok": True, "n_rows": 1_000_000, "outcome": Outcome(meta)}
             for _ in range(2)]
  return {"trace": None if ops is None else
          {"busy_s": [1.0], "window_s": 2.0, "device_ops": ops},
          "sweeps": sweeps_, "traced_points": 2_000_000}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_table_metrics_read_the_trace(name, monkeypatch):
  from bench import chip
  read = harness.load_module(os.path.join(
      harness.BENCH_DIR, "metrics", name + ".py")).read
  monkeypatch.setattr(chip, "peaks", lambda kind: {"hbm_bytes_per_s": 1e9})
  meta = {"n_layer_shapes": 45.0, "bytes_to_device": 2.5e8,
          "rows_transferred": 1e4, "n_overflows": 0.0}
  ctx = ctx_with(meta, [["jit_fused_eval_table", 0.9], ["other", 5.0]])
  # 0.9 s over 2M points x 45 shapes; (2.5e8 + 24e6 + 32e4) bytes a
  # Mpoint, 2 Mpoints, over 0.9 s at 1 GB/s
  assert read(ctx) == pytest.approx(
      {"dsa_table_ns_per_point_shape": 10.0,
       "dsa_table_hbm_pct": 100.0 * 2 * (2.5e8 + 24e6 + 32e4) / 0.9e9}[name])
  # untraced, a program without the table, or without the counters
  for quiet in (ctx_with(meta, None),
                ctx_with(meta, [["jit_fused_eval", 1.0]]),
                ctx_with({}, [["jit_fused_eval_table", 1.0]])):
    assert read(quiet) is None
