"""End-to-end driver: train a zoo architecture on the synthetic Markov
stream with QUIDAM QAT, checkpointing, and fault-tolerance telemetry.

Default: a reduced olmo-family model for 300 steps (CPU-friendly); pass
--arch/--steps/--pe-type to change.  Loss is asserted to decrease.

Run: PYTHONPATH=src python examples/train_lm.py --steps 300
"""
import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduce_for_smoke
from repro.data.synthetic import (DataCursor, MarkovTokenStream,
                                  TokenStreamConfig, token_batches)
from repro.models.model import build_model
from repro.quant.policy import QuantPolicy
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts_lib
from repro.train.trainer import Trainer, TrainerConfig


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default="olmo-1b")
  ap.add_argument("--steps", type=int, default=300)
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--seq", type=int, default=128)
  ap.add_argument("--pe-type", default="FP32",
                  help="QAT policy: FP32/INT16/INT8/LightPE-1/LightPE-2")
  ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
  ap.add_argument("--full-config", action="store_true",
                  help="use the full architecture (needs accelerators)")
  args = ap.parse_args()
  enable_compile_cache()

  cfg = get_config(args.arch)
  if not args.full_config:
    cfg = reduce_for_smoke(cfg, d_model=128, n_layers=4, d_ff=256,
                           vocab_size=2048)
  model = build_model(cfg)
  tcfg = ts_lib.TrainConfig(
      optimizer=opt_lib.AdamWConfig(lr=3e-3, warmup_steps=20,
                                    total_steps=args.steps),
      quant=QuantPolicy(pe_type=args.pe_type))
  stream = MarkovTokenStream(TokenStreamConfig(vocab_size=cfg.vocab_size,
                                               branching=6))
  cursor = DataCursor()
  trainer = Trainer(model, tcfg,
                    TrainerConfig(total_steps=args.steps, log_every=20,
                                  ckpt_every=100, ckpt_dir=args.ckpt_dir),
                    token_batches(stream, args.batch, args.seq, cursor),
                    cursor=cursor, key=jax.random.PRNGKey(0))
  resumed = trainer.maybe_restore()
  print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
        f"pe_type={args.pe_type} resumed={resumed}")
  hist = trainer.run(args.steps - trainer.step)
  first = sum(h["loss"] for h in hist[:10]) / max(len(hist[:10]), 1)
  last = sum(h["loss"] for h in hist[-10:]) / max(len(hist[-10:]), 1)
  print(f"loss {first:.3f} -> {last:.3f} over {len(hist)} steps")
  print("straggler report:", trainer.monitor.stragglers() or "none")
  assert last < first, "loss did not decrease"


if __name__ == "__main__":
  main()
