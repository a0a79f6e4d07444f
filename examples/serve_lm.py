"""Serving driver: batched requests through the continuous-batching engine
with an int8-quantized KV cache (QUIDAM's precision axis at decode time).

Run: PYTHONPATH=src python examples/serve_lm.py
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduce_for_smoke
from repro.models.model import build_model
from repro.serve.engine import EngineConfig, ServeEngine


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default="qwen3-0.6b")
  ap.add_argument("--requests", type=int, default=8)
  ap.add_argument("--new-tokens", type=int, default=16)
  ap.add_argument("--kv-quant", default="int8", choices=["none", "int8"])
  args = ap.parse_args()
  enable_compile_cache()

  cfg = reduce_for_smoke(get_config(args.arch), d_model=128, n_layers=4,
                         vocab_size=2048)
  cfg = dataclasses.replace(cfg, kv_quant=args.kv_quant)
  model = build_model(cfg)
  params = model.init(jax.random.PRNGKey(0))
  engine = ServeEngine(model, params, EngineConfig(
      batch_slots=4, max_len=256, prompt_bucket=32))

  rng = np.random.RandomState(0)
  t0 = time.time()
  for i in range(args.requests):
    engine.submit(rng.randint(0, cfg.vocab_size, size=10 + i),
                  max_new_tokens=args.new_tokens)
  results = engine.run_until_drained()
  dt = time.time() - t0
  total = sum(len(v) for v in results.values())
  print(f"served {len(results)} requests / {total} tokens in {dt:.1f}s "
        f"({total / dt:.1f} tok/s on CPU) kv_quant={args.kv_quant}")
  for uid, toks in sorted(results.items())[:3]:
    print(f"  request {uid}: {toks[:8]}...")
  assert len(results) == args.requests


if __name__ == "__main__":
  main()
