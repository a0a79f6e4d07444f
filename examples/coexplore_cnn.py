"""Full co-exploration demo (paper Sec. 4.5 / Fig. 12) via repro.explore:
train the weight-sharing VGG supernet over the Table-4 space, sample +
evaluate candidate architectures, pair with PPA-modeled hardware through
an ExplorationSession, and print the joint Pareto front.

Run: PYTHONPATH=src python examples/coexplore_cnn.py --steps 200
"""
import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.supernet import Supernet, SupernetConfig, space_size
from repro.explore import ExplorationSession, PolynomialBackend


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--steps", type=int, default=200)
  ap.add_argument("--archs", type=int, default=24)
  ap.add_argument("--hw-per-type", type=int, default=12)
  args = ap.parse_args()
  enable_compile_cache()

  print(f"search space: {space_size():,} architectures (Table 4)")
  sn = Supernet(SupernetConfig(steps=args.steps, batch=32, image_size=16))
  sn.train()
  arch_accs = sn.sample_and_evaluate(n_archs=args.archs, n_val=512)
  accs = [a for _, a in arch_accs]
  print(f"sampled {len(arch_accs)} archs; top-1 range "
        f"{min(accs):.3f}-{max(accs):.3f}")

  from repro.core.supernet import arch_to_layers
  layers = arch_to_layers(arch_accs[0][0])
  backend = PolynomialBackend.fit(degree=5, n_train=200, layers=layers)
  session = ExplorationSession(backend)
  # vectorized=True: the whole archs x HW cross product evaluates
  # array-at-a-time (JointTable + LayerStack; power/area once per HW row)
  frame = session.co_explore(arch_accs, n_hw_per_type=args.hw_per_type,
                             vectorized=True)
  front = frame.pareto(cols=("top1_err", "energy_mj"))
  print(f"\n{len(frame)} (HW, NN) pairs; energy-front breakdown:")
  for t in ("FP32", "INT16", "LightPE-2", "LightPE-1"):
    n_front = int(np.sum(front & frame.by_type(t)))
    print(f"  {t:12s}: {n_front} points on the joint Pareto front")
  lights = np.isin(frame.pe_type[front], ("LightPE-1", "LightPE-2"))
  print(f"\nLightPE share of the front: {lights.mean() * 100:.0f}% "
        "(paper: LightPEs consistently on the front)")
  front3 = frame.pareto(cols=("top1_err", "energy_mj", "area_mm2"))
  best = int(np.flatnonzero(front3)[0])
  print(f"3-objective (err, energy, area) front: {int(front3.sum())} "
        f"points; e.g. arch {frame.arch_at(best).stages} on "
        f"{frame.pe_type[best]}")


if __name__ == "__main__":
  main()
