"""The GEMM layer kind, the model lowering and the layer-table program.

  * ``GemmLayer``: the scalar model and the numpy batch formulas agree bit
    for bit on every ``LayerStats`` field and on the energy, for groups,
    cache operands, counts and both branches of each fit test;
  * ``from_config``: DeepSeek-V2-Lite's decode step lowers to the MACs and
    cache words counted from its config by hand, with routing that sends
    every token to ``n_experts_active`` experts;
  * the table program (numpy and ``jit=True`` on the CPU) folds the same
    fronts and top-k lists as the scalar oracle, compiles once per entry
    count, and leaves the conv programs' lowered text as it was.
"""
import dataclasses
import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config
from repro.configs.shapes import SHAPES, ShapeSpec
from repro.core import oracle
from repro.core.dataflow import (AcceleratorConfig, GemmLayer, LayerStack,
                                 layer_energy_pj, layer_energy_pj_batch,
                                 layer_table, simulate_layer,
                                 simulate_layer_batch)
from repro.core.pe import PAPER_PE_TYPES
from repro.core.workloads import (expert_token_counts, from_config,
                                  get_network)
from repro.explore import (DesignSpace, ExplorationSession,
                           VectorOracleBackend, spans)
from repro.explore.frame import ResultFrame
from repro.explore.streaming import ParetoAccumulator, TopKAccumulator

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

STATS = ("cycles", "compute_cycles", "dram_stall_cycles", "utilization",
         "macs", "spad_reads", "spad_writes", "gbuf_reads", "gbuf_writes",
         "dram_reads", "dram_writes")

# (name, layer, which fit branches it takes in a 64-512 KB buffer)
LAYERS = [
    GemmLayer("dense", 128, 2048, 3072),
    GemmLayer("small", 8, 64, 32),  # both operands fit
    GemmLayer("heads", 128, 128, 512, groups=16, count=27),
    GemmLayer("scores_fit", 16, 576, 64, groups=8, operand="cache"),
    GemmLayer("scores", 16, 576, 32768, groups=128, operand="cache",
              count=27),
    GemmLayer("av", 16, 32768, 512, groups=128, operand="cache", count=3),
    GemmLayer("expert", 3, 2048, 1408, count=268),
]

DEEPSEEK = get_config("deepseek-v2-lite")
DECODE = SHAPES["decode_32k"]
# 2 MoE layers of 8 experts, 8 sequences at 256 cached positions
TINY = dataclasses.replace(DEEPSEEK, n_layers=3, n_experts=8)
TINY_DECODE = ShapeSpec("tiny_decode", 256, 8, "decode")


def configs(n=240, seed=0):
  """Design points of every PE type across the whole design space."""
  space = DesignSpace()
  return space.sample_table(n // len(space.pe_types), seed=seed)


@pytest.mark.parametrize("layer", LAYERS, ids=[l.name for l in LAYERS])
def test_scalar_and_batch_agree_bit_for_bit(layer):
  table = configs()
  clock = oracle.clock_mhz_batch(table)
  leak = oracle.leakage_mw_batch(table)
  st = simulate_layer_batch(table, layer, clock)
  e = layer_energy_pj_batch(table, layer, st, clock, leak)
  for i in range(len(table)):
    cfg = table.config_at(i)
    want = simulate_layer(cfg, layer, float(clock[i]))
    got = st.row(i)
    for field in STATS:
      assert getattr(got, field) == getattr(want, field), (field, cfg)
    assert e[i] == layer_energy_pj(cfg, layer, want, float(clock[i]),
                                   float(leak[i]))


def test_fit_branches_are_all_taken():
  table = configs()
  gbuf_bits = table.gbuf_kb * 1024 * 8.0
  for pe_type in PAPER_PE_TYPES:
    pe = AcceleratorConfig(pe_type=pe_type).pe
    sel = table.pe_type_strings() == pe_type
    for layer in LAYERS[1:5]:
      bits = pe.act_bits if layer.operand == "cache" else pe.weight_bits
      fits = layer.K * layer.N * bits <= 0.25 * gbuf_bits[sel]
      left = layer.M * layer.K * pe.act_bits <= 0.5 * gbuf_bits[sel]
      if layer.name == "small":
        assert fits.all() and left.all()
      if layer.name == "scores":
        assert not fits.any() and left.any()
  # scores_fit fits some buffers and not others: both branches
  pe = AcceleratorConfig(pe_type="INT16").pe
  fits = 576 * 64 * pe.act_bits <= 0.25 * gbuf_bits
  assert fits.any() and not fits.all()


def test_cache_operand_moves_at_activation_bits():
  # LightPE-1: 8-bit activations, 4-bit weights
  cfg = AcceleratorConfig(pe_type="LightPE-1", gbuf_kb=64, pe_cols=16,
                          bandwidth_gbps=6.4)
  as_weight = GemmLayer("w", 2, 576, 4096, groups=4)
  as_cache = dataclasses.replace(as_weight, operand="cache")
  # a 1 GHz clock: each cache word costs more DRAM cycles than MAC cycles
  w = simulate_layer(cfg, as_weight, 1000.0)
  c = simulate_layer(cfg, as_cache, 1000.0)
  assert w.dram_reads == c.dram_reads  # the same words, every group's own
  assert c.dram_stall_cycles > w.dram_stall_cycles > 0.0  # at twice the bits
  assert layer_energy_pj(cfg, as_cache, c, 1000.0, 1.0) > \
      layer_energy_pj(cfg, as_weight, w, 1000.0, 1.0)
  one = simulate_layer(cfg, dataclasses.replace(as_cache, groups=1), 1000.0)
  assert c.dram_reads == 4 * one.dram_reads and c.cycles == 4 * one.cycles
  assert c.utilization == one.utilization


def test_counts_weight_the_network():
  cfg = AcceleratorConfig(pe_type="INT16")
  net = [LAYERS[0], LAYERS[2]]
  once = oracle.characterize(cfg, [dataclasses.replace(l, count=1)
                                   for l in net])
  ch = oracle.characterize(cfg, net)
  assert ch.per_layer_cycles == once.per_layer_cycles
  cyc = ch.per_layer_cycles
  assert ch.latency_s == (cyc[0] + 27 * cyc[1]) / (ch.clock_mhz * 1e6)
  expanded = oracle.characterize(
      cfg, [net[0]] + [dataclasses.replace(net[1], count=1)] * 27)
  assert ch.latency_s == pytest.approx(expanded.latency_s, rel=1e-12)
  assert ch.energy_mj == pytest.approx(expanded.energy_mj, rel=1e-12)
  assert ch.utilization == pytest.approx(expanded.utilization, rel=1e-12)


def weight_macs_per_token(c):
  """Weight MACs of one token, counted from the config's published
  widths (arXiv:2405.04434 Sec. 2.1-2.2), independent of the lowering."""
  d, h, r = c.d_model, c.n_heads, c.kv_lora_rank
  qk = c.head_dim + c.qk_rope_head_dim
  attn = (d * h * qk + h * c.head_dim * r + d * (r + c.qk_rope_head_dim)
          + h * r * c.v_head_dim + h * c.v_head_dim * d)
  dense = 3 * d * c.d_ff
  moe = (d * c.n_experts + 3 * d * c.d_ff_shared
         + c.n_experts_active * 3 * d * c.d_ff_expert)
  n_moe = c.n_layers - c.first_dense_layers
  return (c.n_layers * attn + c.first_dense_layers * dense + n_moe * moe
          + d * c.vocab_size)


def test_deepseek_decode_lowers_to_the_published_counts():
  entries = from_config(DEEPSEEK, DECODE)
  b, ctx = DECODE.global_batch, DECODE.seq_len
  weights = [l for l in entries if l.operand == "weight"]
  caches = [l for l in entries if l.operand == "cache"]
  assert sum(l.count * l.macs for l in weights) == \
      b * weight_macs_per_token(DEEPSEEK) == b * 2_451_308_544
  latent = DEEPSEEK.kv_lora_rank + DEEPSEEK.qk_rope_head_dim  # 576
  assert sum(l.count * l.macs for l in caches) == \
      b * ctx * 27 * 16 * (576 + 512) == b * 15_401_484_288
  assert sum(l.count * l.groups * l.K * l.N for l in caches) == \
      b * ctx * (latent + DEEPSEEK.kv_lora_rank) * 27  # 1.23e11 words
  # one entry per distinct shape, in first-appearance order
  assert len({l.shape for l in entries}) == len(entries)
  assert [l.name for l in entries[:7]] == [
      "attn.q_proj", "attn.q_absorb", "attn.kv_a_proj", "attn.scores",
      "attn.av", "attn.v_absorb", "attn.o_proj"]
  assert entries[-1].name == "lm_head" and entries[-1].N == 102400
  assert 110 <= len(entries) <= 130
  assert sum(l.count for l in entries) == 5286


def test_cache_reads_in_the_dram_model():
  # with M = 16 heads on 16 columns or more, each sequence's cache is
  # streamed from DRAM once: the reads are the lowering's cache words
  cfg = AcceleratorConfig(pe_type="INT16", pe_rows=16, pe_cols=16,
                          gbuf_kb=512)
  gbuf_bits = cfg.gbuf_kb * 1024 * 8
  n_f = lambda l: -(-l.N // min(l.N, cfg.sp_ps))  # noqa: E731
  total = 0
  for l in from_config(DEEPSEEK, DECODE):
    if l.operand != "cache":
      continue
    left = l.M * l.K
    left_reads = left if left * 16 <= 0.5 * gbuf_bits else left * n_f(l)
    st = simulate_layer(cfg, l, 300.0)
    total += l.count * (st.dram_reads - l.groups * left_reads)
  assert total == 128 * 32768 * (576 + 512) * 27


def test_routing_sends_each_token_to_k_experts():
  counts = expert_token_counts(DEEPSEEK, 128)
  assert len(counts) == 26
  for t in counts:
    assert t.shape == (64,) and t.sum() == 128 * 6 and t.max() <= 128
  assert [list(t) for t in counts] == \
      [list(t) for t in expert_token_counts(DEEPSEEK, 128)]
  distinct = {int(x) for t in counts for x in t if x}
  assert 40 <= len(distinct) <= 60
  # an explicit routing replaces the drawn one; an idle expert reads nothing
  even = [np.full(64, 12)] * 26
  entries = from_config(DEEPSEEK, DECODE, routing=even)
  experts = [l for l in entries if l.name.startswith("moe.expert")]
  assert [(l.M, l.count) for l in experts] == [(12, 64 * 26 * 2),
                                               (12, 64 * 26)]
  skewed = [np.r_[np.full(6, 128), np.zeros(58, int)]] * 26
  experts = [l for l in from_config(DEEPSEEK, DECODE, routing=skewed)
             if l.name.startswith("moe.expert")]
  assert sum(l.count for l in experts) == 6 * 26 * 3


def test_lowering_raises_for_what_it_does_not_lower():
  with pytest.raises(NotImplementedError, match="decode only"):
    from_config(DEEPSEEK, SHAPES["prefill_32k"])
  with pytest.raises(NotImplementedError, match="latent attention"):
    from_config(get_config("qwen2-moe-a2.7b"), DECODE)
  with pytest.raises(ValueError, match="routing"):
    from_config(DEEPSEEK, DECODE, routing=[np.zeros(64)] * 3)


def test_lowering_runs_under_its_span():
  with spans.recording() as rec:
    from_config(TINY, TINY_DECODE)
  assert rec.meta()["self_s_lower"] > 0.0


def scalar_answers(layers, n_per_type, seed, red):
  """Fold the scalar oracle's numbers for the sweep's points into
  fresh reducers: the one-shot sample is the streamed chunks in order."""
  table = DesignSpace().sample_table(n_per_type, seed=seed)
  rows = [oracle.characterize(table.config_at(i), layers)
          for i in range(len(table))]
  frame = ResultFrame(np.asarray([r.latency_s for r in rows]),
                      np.asarray([r.power_mw for r in rows]),
                      np.asarray([r.area_mm2 for r in rows]),
                      table.pe_type_strings(), (), "net", table=table)
  for r in red.values():
    r.fold(frame, np.arange(len(table), dtype=np.int64))
  return red


def reducers():
  return {"pareto": ParetoAccumulator(("perf_per_area", "energy_mj")),
          "top": TopKAccumulator(40, by="energy_mj")}


def answers(red):
  out = {}
  for name, r in red.items():
    f = r.result()
    out[name] = (np.asarray(r.indices),
                 [np.asarray(getattr(f, c)) for c in ("latency_s",
                                                      "power_mw",
                                                      "area_mm2")])
  return out


@pytest.mark.parametrize("which,n_per_type,chunk", [
    ("tiny", 60, 64), ("full", 75, 128)])
def test_table_program_folds_the_scalar_oracles_fronts(which, n_per_type,
                                                       chunk):
  layers = from_config(TINY, TINY_DECODE) if which == "tiny" \
      else from_config(DEEPSEEK, DECODE)
  want = answers(scalar_answers(layers, n_per_type, 9, reducers()))
  for jit in (False, True):
    red = reducers()
    res = ExplorationSession(VectorOracleBackend(chunk_size=chunk, jit=jit),
                             DesignSpace()).explore(
        layers, "net", n_per_type=n_per_type, seed=9, stream=True,
        reducers=red, chunk_size=chunk)
    assert res.meta["n_layer_shapes"] == len(layers)
    assert res.meta["n_layer_slots"] == sum(l.count for l in layers)
    got = answers(red)
    for name in want:
      np.testing.assert_array_equal(got[name][0], want[name][0])
      for g, w in zip(got[name][1], want[name][1]):
        np.testing.assert_array_equal(g, w)


def test_one_table_program_per_entry_count():
  # two routing seeds whose tables differ in their values alone
  seen = {}
  for seed in range(40):
    layers = from_config(dataclasses.replace(TINY, router_seed=seed),
                         TINY_DECODE)
    match = [l for l in seen.get(len(layers), []) if l != layers]
    if match:
      first, second = match[0], layers
      break
    seen.setdefault(len(layers), []).append(layers)
  assert first != second and len(first) == len(second)
  backend = VectorOracleBackend(chunk_size=64, jit=True)
  session = ExplorationSession(backend, DesignSpace())
  runs = [session.explore(layers, "net", n_per_type=16, seed=3, stream=True,
                          reducers=reducers(), chunk_size=64)
          for layers in (first, second)]
  compiles = [sum(v for k, v in r.meta.items() if k.startswith("n_compiles_"))
              for r in runs]
  assert compiles[0] >= 1 and compiles[1] == 0
  assert runs[1].meta["bytes_to_device"] > 0


def test_layer_table_copy_is_counted_in_place():
  layers = from_config(TINY, TINY_DECODE)
  backend = VectorOracleBackend(chunk_size=64, jit=True)
  res = ExplorationSession(backend, DesignSpace()).explore(
      layers, "net", n_per_type=32, seed=4, stream=True, reducers=reducers(),
      chunk_size=64)
  cols, counts = layer_table(tuple(layers))
  table_bytes = sum(v.nbytes for v in cols.values()) + counts.nbytes
  inputs = sum(v.nbytes for v in oracle.batch_inputs(
      DesignSpace().sample_table(8, seed=4),  # 32 rows
      device_variations=True).values())
  assert res.meta["n_chunks"] == 4  # one chunk of 32 points per PE type
  assert res.meta["bytes_to_device"] == 4 * (table_bytes + inputs)


def test_conv_networks_through_the_table_are_bit_identical():
  layers = get_network("resnet20")
  table = configs(120, seed=2)
  cols, counts = layer_table(layers)
  want = oracle.characterize_batch(table, layers)
  got = oracle.characterize_table(table, cols, counts)
  for f in ("latency_s", "energy_mj", "utilization"):
    np.testing.assert_array_equal(getattr(got, f), getattr(want, f))


def lowered_texts():
  """The lowered text of ResNet-20's fused plain program and of a fused
  joint program, both at a 64-row chunk."""
  from repro.core.cnn import ArchChoice
  from repro.core.supernet import arch_to_layers
  from repro.explore.device import build_plan, make_eval_fn, make_joint_fn
  table = next(DesignSpace().iter_tables(64, seed=3, method="random",
                                         chunk_size=64))
  inputs = oracle.batch_inputs(table)
  with jax.enable_x64(True):
    plan = build_plan({"pareto": ParetoAccumulator(("perf_per_area",
                                                    "energy_mj")),
                       "top": TopKAccumulator(100, by="energy_mj")},
                      joint=False)
    plain = jax.jit(make_eval_fn(tuple(get_network("resnet20")), plan)
                    ).lower(inputs).as_text()
    archs = [ArchChoice(((2, 64), (1, 128), (3, 256))),
             ArchChoice(((1, 32), (2, 64), (1, 128)))]
    stack = LayerStack.from_layer_lists([arch_to_layers(a, image_size=32)
                                         for a in archs])
    unique_cols, slot_ids = stack.dedup_slots()
    jplan = build_plan({"pareto": ParetoAccumulator(("top1_err", "energy_mj",
                                                     "area_mm2")),
                        "top": TopKAccumulator(100, by="energy_mj")},
                       joint=True)
    joint = jax.jit(make_joint_fn(jplan)).lower(
        inputs, unique_cols, slot_ids, stack.valid,
        np.asarray([0.5, 0.6])).as_text()
  return plain, joint


def test_conv_programs_lower_as_before_the_gemm_kind():
  # sha256 of the lowered text before GemmLayer existed: the GEMM-only
  # features are absent for conv layers, so nothing of them is traced
  plain, joint = lowered_texts()
  assert hashlib.sha256(plain.encode()).hexdigest() == \
      "993ce95d5b19279946453c4cb4ede1f94d00fb59e35e35289a261f23dc309422"
  assert hashlib.sha256(joint.encode()).hexdigest() == \
      "130033a8db1d9ef74426749326f5b7cfefbfd4528cd9df5809f2b0bb7b243295"
