"""DNN workload definitions for QUIDAM's DSE.

Provides the paper's evaluation networks — VGG-16, ResNet-20/34/50/56 on
CIFAR (32x32) and ImageNet (224x224) — as row-stationary workload layer
lists; :func:`from_config`, which lowers a ``repro.configs`` model in one
deployment regime into merged GEMM entries (:class:`GemmLayer`, exact
shapes, counts); and the older *bridge* :func:`lm_block_workload`, which
treats a transformer block's matmuls as 1x1 convolutions over a square
map (beyond-paper extension, see README.md "LM workloads bridge").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.dataflow import ConvLayer, GemmLayer


# ---------------------------------------------------------------------------
# VGG-16
# ---------------------------------------------------------------------------

_VGG16_PLAN = [  # (channels, repeats) per stage; maxpool between stages
    (64, 2), (128, 2), (256, 3), (512, 3), (512, 3),
]


def vgg16(input_dim: int = 32, in_ch: int = 3,
          plan: Sequence = _VGG16_PLAN) -> List[ConvLayer]:
  layers: List[ConvLayer] = []
  a, c = input_dim, in_ch
  for stage, (f, reps) in enumerate(plan):
    for r in range(reps):
      layers.append(ConvLayer(f"conv{stage + 1}_{r + 1}", A=a, C=c, F=f,
                              K=3, S=1, P=1))
      c = f
    a = max(a // 2, 1)  # maxpool 2x2
  return layers


# ---------------------------------------------------------------------------
# ResNets
# ---------------------------------------------------------------------------

def resnet_cifar(depth: int, input_dim: int = 32) -> List[ConvLayer]:
  """CIFAR ResNet-(6n+2): 3 stages of n basic blocks, widths 16/32/64."""
  assert (depth - 2) % 6 == 0, "CIFAR ResNet depth must be 6n+2"
  n = (depth - 2) // 6
  layers = [ConvLayer("conv1", A=input_dim, C=3, F=16, K=3, S=1, P=1)]
  a, c = input_dim, 16
  for stage, f in enumerate((16, 32, 64)):
    for b in range(n):
      s = 2 if (stage > 0 and b == 0) else 1
      ds = 1 if (stage > 0 and b == 0) else 0
      layers.append(ConvLayer(f"s{stage}b{b}c1", A=a, C=c, F=f, K=3, S=s,
                              P=1, rs=1 - ds, ds=ds))
      a_out = (a + 2 - 3) // s + 1
      layers.append(ConvLayer(f"s{stage}b{b}c2", A=a_out, C=f, F=f, K=3,
                              S=1, P=1, rs=1, ds=0))
      if ds:
        layers.append(ConvLayer(f"s{stage}b{b}proj", A=a, C=c, F=f, K=1,
                                S=s, P=0, rs=0, ds=1))
      a, c = a_out, f
  return layers


def resnet34(input_dim: int = 224) -> List[ConvLayer]:
  """ImageNet ResNet-34: basic blocks, widths 64/128/256/512, [3,4,6,3]."""
  layers = [ConvLayer("conv1", A=input_dim, C=3, F=64, K=7, S=2, P=3)]
  a = (input_dim + 6 - 7) // 2 + 1
  a = (a + 2 - 3) // 2 + 1  # maxpool 3x3 /2
  c = 64
  for stage, (f, reps) in enumerate(((64, 3), (128, 4), (256, 6), (512, 3))):
    for b in range(reps):
      s = 2 if (stage > 0 and b == 0) else 1
      ds = 1 if (stage > 0 and b == 0) else 0
      layers.append(ConvLayer(f"s{stage}b{b}c1", A=a, C=c, F=f, K=3, S=s,
                              P=1, rs=1 - ds, ds=ds))
      a_out = (a + 2 - 3) // s + 1
      layers.append(ConvLayer(f"s{stage}b{b}c2", A=a_out, C=f, F=f, K=3,
                              S=1, P=1, rs=1))
      if ds:
        layers.append(ConvLayer(f"s{stage}b{b}proj", A=a, C=c, F=f, K=1,
                                S=s, P=0, ds=1))
      a, c = a_out, f
  return layers


def resnet50(input_dim: int = 224) -> List[ConvLayer]:
  """ImageNet ResNet-50: bottleneck blocks [3,4,6,3]."""
  layers = [ConvLayer("conv1", A=input_dim, C=3, F=64, K=7, S=2, P=3)]
  a = (input_dim + 6 - 7) // 2 + 1
  a = (a + 2 - 3) // 2 + 1
  c = 64
  for stage, (f, reps) in enumerate(((64, 3), (128, 4), (256, 6), (512, 3))):
    for b in range(reps):
      s = 2 if (stage > 0 and b == 0) else 1
      ds = 1 if b == 0 else 0
      layers.append(ConvLayer(f"s{stage}b{b}r", A=a, C=c, F=f, K=1, S=1,
                              P=0, rs=1 - ds, ds=ds))
      layers.append(ConvLayer(f"s{stage}b{b}c", A=a, C=f, F=f, K=3, S=s,
                              P=1, rs=1 - ds, ds=ds))
      a_out = (a + 2 - 3) // s + 1
      layers.append(ConvLayer(f"s{stage}b{b}e", A=a_out, C=f, F=4 * f, K=1,
                              S=1, P=0, rs=1 - ds, ds=ds))
      if ds:
        layers.append(ConvLayer(f"s{stage}b{b}proj", A=a, C=c, F=4 * f,
                                K=1, S=s, P=0, ds=1))
      a, c = a_out, 4 * f
  return layers


def resnet20(input_dim: int = 32) -> List[ConvLayer]:
  return resnet_cifar(20, input_dim)


def resnet56(input_dim: int = 32) -> List[ConvLayer]:
  return resnet_cifar(56, input_dim)


# ---------------------------------------------------------------------------
# transformer bridge: matmul -> 1x1 conv workload
# ---------------------------------------------------------------------------

def matmul_layer(name: str, tokens: int, d_in: int, d_out: int) -> ConvLayer:
  """A (tokens, d_in) @ (d_in, d_out) GEMM as a 1x1 conv over sqrt(tokens)^2
  positions (RS dataflow treats output positions uniformly)."""
  a = max(int(math.ceil(math.sqrt(tokens))), 1)
  return ConvLayer(name, A=a, C=d_in, F=d_out, K=1, S=1, P=0)


def lm_block_workload(name: str, tokens: int, d_model: int, n_heads: int,
                      n_kv: int, head_dim: int, d_ff: int,
                      gated: bool = True, n_experts_active: int = 1
                      ) -> List[ConvLayer]:
  """One transformer block's GEMMs as workload layers (per token batch)."""
  layers = [
      matmul_layer(f"{name}.q", tokens, d_model, n_heads * head_dim),
      matmul_layer(f"{name}.kv", tokens, d_model, 2 * n_kv * head_dim),
      matmul_layer(f"{name}.o", tokens, n_heads * head_dim, d_model),
  ]
  ff_mats = 3 if gated else 2
  for i in range(ff_mats):
    d_in = d_model if i < ff_mats - 1 else d_ff
    d_out = d_ff if i < ff_mats - 1 else d_model
    layers.append(matmul_layer(f"{name}.ffn{i}",
                               tokens * n_experts_active, d_in, d_out))
  return layers


# ---------------------------------------------------------------------------
# model configs -> merged GEMM entries
# ---------------------------------------------------------------------------

def expert_token_counts(cfg, tokens: int) -> List[np.ndarray]:
  """Tokens each routed expert receives in one step, one int64 array of
  ``n_experts`` per MoE layer, by the config's synthetic routing.

  For MoE layer ``i`` (its index in the model): ``rng =
  numpy.random.RandomState(derive_seed("moe-routing", router_seed, i))``;
  ``perm = rng.permutation(n_experts)``; expert ``perm[r]`` has
  popularity ``(r + 1) ** -router_skew``, normalized to sum to 1; then
  each of the ``tokens`` tokens in turn draws ``rng.choice(n_experts,
  n_experts_active, replace=False, p=popularity)``.
  """
  from repro.core.seeding import derive_seed
  e, k = cfg.n_experts, cfg.n_experts_active
  weights = np.arange(1, e + 1, dtype=np.float64) ** -float(cfg.router_skew)
  out = []
  for i in range(cfg.first_dense_layers, cfg.n_layers):
    rng = np.random.RandomState(derive_seed("moe-routing", cfg.router_seed,
                                            i))
    perm = rng.permutation(e)
    p = np.empty(e)
    p[perm] = weights / weights.sum()
    t = np.zeros(e, np.int64)
    for _ in range(tokens):
      t[rng.choice(e, k, replace=False, p=p)] += 1
    out.append(t)
  return out


def _regime(shape):
  """A ``repro.configs.shapes`` regime, by name or as its ShapeSpec."""
  if isinstance(shape, str):
    from repro.configs.shapes import SHAPES  # deferred: imports jax
    return SHAPES[shape]
  return shape


# the lightning indexer's keys are cached in FP8 (DeepSeek-V3.2-Exp)
INDEX_KEY_BITS = 8


def from_config(cfg, shape, routing: Optional[Sequence] = None
                ) -> List[GemmLayer]:
  """One step of ``cfg`` in the regime ``shape`` as merged GEMM entries,
  in order of first appearance: identical layers (same M, K, N, groups,
  operand and stored width) are one entry whose ``count`` says how many.

  Lowered: ``decode`` (``global_batch`` sequences, one new token each,
  against ``seq_len`` cached positions) of a model with multi-head latent
  attention (``kv_lora_rank``), its queries compressed or not, with or
  without DeepSeek Sparse Attention's lightning indexer, and SwiGLU MLPs,
  dense or DeepSeekMoE.  B tokens, L cached positions, H heads, per
  layer:

    q_proj     B x d -> H (dn + dr)         (q_lora_rank 0), or
    q_a        B x d -> q_lora              (compressed queries)
    q_b        B x q_lora -> H (dn + dr)
    q_absorb   groups H: B x dn -> r            (W_UK absorbed)
    kv_a_proj  B x d -> r + dr                  (written to the cache)

  then, with an indexer (``index_topk`` > 0; Hi = ``index_n_heads``
  heads of di = ``index_head_dim``), whose scores pick the k =
  min(``index_topk``, L) positions the attention reads:

    index.q       B x q_lora -> Hi di           (from the query latent)
    index.k       B x d -> di                   (written to its key cache)
    index.w       B x d -> Hi                   (head weights)
    index.scores  groups B: Hi x di -> L        key cache, INDEX_KEY_BITS
    index.reduce  groups B: 1 x Hi -> L         cache: ReLU'd scores

  and without one k = L; last the attention itself:

    scores     groups B: H x (r + dr) -> k      cache operand
    av         groups B: H x k -> r             cache operand
    v_absorb   groups H: B x r -> dv            (W_UV absorbed)
    o_proj     B x H dv -> d

  then a dense MLP (``first_dense_layers`` leading layers) or an MoE
  layer: the router B x d -> n_experts, the shared experts as one MLP of
  width ``d_ff_shared``, and for each routed expert with t > 0 tokens
  gate and up t x d -> d_ff_expert and down t x d_ff_expert -> d (an
  expert with no tokens reads no weights).  Last, ``lm_head`` B x d ->
  vocab.  ``routing`` gives the routed tokens per expert, one sequence
  per MoE layer; None draws them by :func:`expert_token_counts`.

  Left out: softmax, the norms and the top-k selection (no MAC work
  here; the scores still go to DRAM and come back as the next product's
  input), the rope product, the Hadamard rotation and FP8 quantization
  of the indexer's inputs, the embedding lookup, and multi-token
  prediction modules, which a plain decode step does not run.  Any other
  regime or mechanism raises ``NotImplementedError``.
  """
  from repro.explore import spans  # deferred: explore imports this module
  with spans.span("lower"):
    spec = _regime(shape)
    if spec.mode != "decode":
      raise NotImplementedError(
          f"from_config lowers decode only, not {spec.mode!r} ({spec.name})")
    why = []
    if cfg.kv_lora_rank <= 0:
      why.append("attention other than multi-head latent attention")
    if cfg.index_topk > 0 and cfg.q_lora_rank <= 0:
      why.append("an indexer without compressed queries")
    if cfg.sliding_window:
      why.append("windowed attention")
    if cfg.family not in ("dense", "moe"):
      why.append(f"the {cfg.family!r} family")
    if cfg.mlp_variant != "swiglu":
      why.append(f"the {cfg.mlp_variant!r} MLP")
    if cfg.n_experts and cfg.moe_period != 1:
      why.append("MoE layers at a period other than 1")
    if why:
      raise NotImplementedError(f"from_config cannot lower {cfg.name}: "
                                + "; ".join(why))
    b, ctx = spec.global_batch, spec.seq_len
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv, r = (cfg.head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    q_lora, hi, di = cfg.q_lora_rank, cfg.index_n_heads, cfg.index_head_dim
    read = min(cfg.index_topk, ctx) if cfg.index_topk > 0 else ctx
    n_moe = cfg.n_layers - cfg.first_dense_layers if cfg.n_experts else 0
    if n_moe:
      counts = expert_token_counts(cfg, b) if routing is None \
          else [np.asarray(t, np.int64) for t in routing]
      if len(counts) != n_moe or any(len(t) != cfg.n_experts
                                     for t in counts):
        raise ValueError(f"routing needs {n_moe} layers of "
                         f"{cfg.n_experts} expert token counts")
    entries: List[GemmLayer] = []
    where: Dict[tuple, int] = {}

    def add(name, m, k, n, groups=1, operand="weight", count=1, bits=None):
      layer = GemmLayer(name, int(m), int(k), int(n), int(groups), operand,
                        count, bits)
      i = where.setdefault(layer.shape, len(entries))
      if i == len(entries):
        entries.append(layer)
      else:
        entries[i] = dataclasses.replace(entries[i],
                                         count=entries[i].count + count)

    def mlp(prefix, tokens, width):
      add(f"{prefix}.gate_up", tokens, d, width, count=2)
      add(f"{prefix}.down", tokens, width, d)

    for i in range(cfg.n_layers):
      if q_lora > 0:
        add("attn.q_a", b, d, q_lora)
        add("attn.q_b", b, q_lora, h * (dn + dr))
      else:
        add("attn.q_proj", b, d, h * (dn + dr))
      add("attn.q_absorb", b, dn, r, groups=h)
      add("attn.kv_a_proj", b, d, r + dr)
      if cfg.index_topk > 0:
        add("index.q", b, q_lora, hi * di)
        add("index.k", b, d, di)
        add("index.w", b, d, hi)
        add("index.scores", hi, di, ctx, groups=b, operand="cache",
            bits=INDEX_KEY_BITS)
        add("index.reduce", 1, hi, ctx, groups=b, operand="cache")
      add("attn.scores", h, r + dr, read, groups=b, operand="cache")
      add("attn.av", h, read, r, groups=b, operand="cache")
      add("attn.v_absorb", b, r, dv, groups=h)
      add("attn.o_proj", b, h * dv, d)
      if i < cfg.n_layers - n_moe:
        mlp("mlp", b, cfg.d_ff)
        continue
      add("moe.router", b, d, cfg.n_experts)
      if cfg.n_shared_experts:
        mlp("moe.shared", b, cfg.d_ff_shared)
      for t in counts[i - (cfg.n_layers - n_moe)]:
        if t > 0:
          mlp(f"moe.expert[t={int(t)}]", t, cfg.d_ff_expert)
    add("lm_head", b, d, cfg.vocab_size)
    return entries


# ---------------------------------------------------------------------------
# registry (paper networks; model-zoo bridging lives in repro.configs)
# ---------------------------------------------------------------------------

PAPER_NETWORKS: Dict[str, Sequence[ConvLayer]] = {}


def get_network(name: str) -> List[ConvLayer]:
  """Paper workloads: vgg16/resnet20/resnet56 (CIFAR), vgg16_imagenet,
  resnet34/resnet50 (ImageNet)."""
  table = {
      "vgg16": lambda: vgg16(32),
      "vgg16_imagenet": lambda: vgg16(224),
      "resnet20": lambda: resnet20(32),
      "resnet56": lambda: resnet56(32),
      "resnet34": lambda: resnet34(224),
      "resnet50": lambda: resnet50(224),
  }
  if name not in table:
    raise ValueError(f"unknown network {name!r}; known: {sorted(table)}")
  return table[name]()


# the paper's workload suites (Sec. 4.2)
CIFAR_SUITE = ("vgg16", "resnet20", "resnet56")
IMAGENET_SUITE = ("vgg16_imagenet", "resnet34", "resnet50")
