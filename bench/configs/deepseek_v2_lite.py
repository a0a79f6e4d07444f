"""Plain reference of the deepseek_v2_lite configuration: one decode step
as the matrix products it runs, read from the published config's keys
(DeepSeek-V2, arXiv:2405.04434 Sec. 2.1 and 2.2) and the assumed decode
batch, context and routing.

Each product is (M, K, N, groups, operand): ``groups`` independent
(M x K) @ (K x N) products, the right operand a weight or a sequence's
latent cache.  With B tokens, L cached positions, H heads, latent rank r,
rope width dr, no-rope width dn and value width dv, every layer runs

  q (B, d, H (dn + dr)); W_UK absorbed per head (B, dn, r); the new
  latent and rope key (B, d, r + dr); scores per sequence
  (H, r + dr, L) and values per sequence (H, L, r) against its cache;
  W_UV absorbed per head (B, r, dv); o (B, H dv, d)

then a SwiGLU MLP (gate and up, then down) in the leading dense layers,
or the router (B, d, experts), the shared experts as one MLP of their
summed width, and each routed expert's MLP at the tokens routed to it;
last the output head (B, d, vocab).  Identical products are merged into
one entry with a count, in order of first appearance.  Softmax, norms,
the rope product and the embedding lookup are not matrix products here
and are left out.
"""
from __future__ import annotations

import hashlib

import numpy as np


MASK64 = (1 << 64) - 1


def layer_seed(router_seed: int, layer: int) -> int:
  """The routing seed of one layer: the top 31 bits of a splitmix64
  chain from the label "moe-routing" over (router_seed, layer)."""
  h = int.from_bytes(hashlib.sha256(b"moe-routing").digest()[:8], "little")
  for part in (router_seed, layer):
    z = ((h + 0x9E3779B97F4A7C15) & MASK64) ^ (part & MASK64)
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
    h = z ^ (z >> 31)
  return h >> 33


def routed_tokens(config: dict, layer: int) -> np.ndarray:
  """Tokens each routed expert receives in MoE layer ``layer``, by the
  rule the configuration's ``assumed.routing`` states."""
  a = config["assumed"]
  e, k = config["n_routed_experts"], config["num_experts_per_tok"]
  rng = np.random.RandomState(layer_seed(a["router_seed"], layer))
  order = rng.permutation(e)  # order[rank] is the expert of that rank
  by_rank = (np.arange(e) + 1.0) ** -a["router_skew"]
  popularity = np.zeros(e)
  popularity[order] = by_rank / by_rank.sum()
  tokens = np.zeros(e, np.int64)
  for _ in range(a["decode_batch"]):
    for expert in rng.choice(e, k, replace=False, p=popularity):
      tokens[expert] += 1
  return tokens


def products(config: dict) -> list:
  """Every product of the step, layer by layer, before merging."""
  a = config["assumed"]
  b, ctx = a["decode_batch"], a["cached_positions"]
  d, h = config["hidden_size"], config["num_attention_heads"]
  r, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
  dn, dv = config["qk_nope_head_dim"], config["v_head_dim"]
  out = []

  def swiglu(tokens, width):
    out.extend([(tokens, d, width, 1, "weight")] * 2)
    out.append((tokens, width, d, 1, "weight"))

  for layer in range(config["num_hidden_layers"]):
    out += [(b, d, h * (dn + dr), 1, "weight"),
            (b, dn, r, h, "weight"),
            (b, d, r + dr, 1, "weight"),
            (h, r + dr, ctx, b, "cache"),
            (h, ctx, r, b, "cache"),
            (b, r, dv, h, "weight"),
            (b, h * dv, d, 1, "weight")]
    if layer < config["first_k_dense_replace"]:
      swiglu(b, config["intermediate_size"])
      continue
    out.append((b, d, config["n_routed_experts"], 1, "weight"))
    swiglu(b, config["n_shared_experts"] * config["moe_intermediate_size"])
    for t in routed_tokens(config, layer):
      if t:
        swiglu(int(t), config["moe_intermediate_size"])
  out.append((b, d, config["vocab_size"], 1, "weight"))
  return out


def merged(config: dict) -> list:
  """(M, K, N, groups, operand, count) per distinct product."""
  counts = {}
  for p in products(config):
    counts[p] = counts.get(p, 0) + 1  # dicts keep first-appearance order
  return [p + (n,) for p, n in counts.items()]


def workload(config: dict) -> dict:
  return {"archs": None, "accs": None, "layers": [merged(config)]}
