"""Plain reference of the deepseek_v3_2 configuration: one decode step
as the matrix products it runs, read from the published config's keys
(DeepSeek-V2, arXiv:2405.04434 Sec. 2.1 and 2.2; DeepSeek Sparse
Attention, the DeepSeek-V3.2-Exp report) and the assumed decode batch,
context and routing.

Each product is (M, K, N, groups, operand, bits): ``groups`` independent
(M x K) @ (K x N) products, the right operand a weight or a sequence's
cache, stored at ``bits`` where the source gives it a width of its own
(None: the PE type's weight or activation width).  With B tokens, L
cached positions, H heads, latent rank r, query latent rank q, rope
width dr, no-rope width dn, value width dv, and the lightning indexer's
Hi heads of width di choosing k = min(index_topk, L) positions, every
layer runs

  the query latent (B, d, q) and queries (B, q, H (dn + dr)); W_UK
  absorbed per head (B, dn, r); the new latent and rope key
  (B, d, r + dr); the indexer's queries (B, q, Hi di), its new key
  (B, d, di) and head weights (B, d, Hi); its scores per sequence
  against its 8-bit key cache (Hi, di, L) and their weighted sum over
  the heads, the ReLU'd scores read back (1, Hi, L); the attention's
  scores (H, r + dr, k) and values (H, k, r) per sequence against the k
  chosen latent-cache positions; W_UV absorbed per head (B, r, dv); o
  (B, H dv, d)

then a SwiGLU MLP (gate and up, then down) in the leading dense layers,
or the router (B, d, experts), the shared expert as one MLP, and each
routed expert's MLP at the tokens routed to it; last the output head
(B, d, vocab).  Identical products are merged into one entry
(M, K, N, groups, operand, count, bits), in order of first appearance.
Left out: the top-k selection, softmax, norms, the rope product, the
indexer inputs' rotation and FP8 quantization, the embedding lookup and
the multi-token prediction module.  The routing is deepseek_v2_lite's
rule (``routed_tokens`` there) under this file's numbers.
"""
from __future__ import annotations

import os

from bench import harness

INDEX_KEY_BITS = 8  # FP8 keys, per the source

_V2_LITE = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "deepseek_v2_lite.py"))


def products(config: dict) -> list:
  """Every product of the step, layer by layer, before merging."""
  a = config["assumed"]
  b, ctx = a["decode_batch"], a["cached_positions"]
  d, h = config["hidden_size"], config["num_attention_heads"]
  r, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
  dn, dv = config["qk_nope_head_dim"], config["v_head_dim"]
  q = config["q_lora_rank"]
  hi, di = config["index_n_heads"], config["index_head_dim"]
  k = min(config["index_topk"], ctx)
  out = []

  def swiglu(tokens, width):
    out.extend([(tokens, d, width, 1, "weight", None)] * 2)
    out.append((tokens, width, d, 1, "weight", None))

  for layer in range(config["num_hidden_layers"]):
    out += [(b, d, q, 1, "weight", None),
            (b, q, h * (dn + dr), 1, "weight", None),
            (b, dn, r, h, "weight", None),
            (b, d, r + dr, 1, "weight", None),
            (b, q, hi * di, 1, "weight", None),
            (b, d, di, 1, "weight", None),
            (b, d, hi, 1, "weight", None),
            (hi, di, ctx, b, "cache", INDEX_KEY_BITS),
            (1, hi, ctx, b, "cache", None),
            (h, r + dr, k, b, "cache", None),
            (h, k, r, b, "cache", None),
            (b, r, dv, h, "weight", None),
            (b, h * dv, d, 1, "weight", None)]
    if layer < config["first_k_dense_replace"]:
      swiglu(b, config["intermediate_size"])
      continue
    out.append((b, d, config["n_routed_experts"], 1, "weight", None))
    swiglu(b, config["n_shared_experts"] * config["moe_intermediate_size"])
    for t in _V2_LITE.routed_tokens(config, layer):
      if t:
        swiglu(int(t), config["moe_intermediate_size"])
  out.append((b, d, config["vocab_size"], 1, "weight", None))
  return out


def merged(config: dict) -> list:
  """(M, K, N, groups, operand, count, bits) per distinct product."""
  counts = {}
  for p in products(config):
    counts[p] = counts.get(p, 0) + 1  # dicts keep first-appearance order
  return [p[:5] + (n,) + p[5:] for p, n in counts.items()]


def workload(config: dict) -> dict:
  return {"archs": None, "accs": None, "layers": [merged(config)]}
