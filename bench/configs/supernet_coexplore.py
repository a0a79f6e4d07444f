"""Plain reference of the supernet_coexplore configuration: the 1,000
architectures of the VGG supernet space, their pseudo-accuracies, and each
architecture's conv layers (A, C, F, K, S, P, rs, ds), stage by stage:
``repeats`` 3x3 convolutions at the stage's width, then a 2x downsample."""
from __future__ import annotations

import numpy as np


def layers_of(config: dict, stages) -> list:
  layers, a, c, k = [], config["image_size"], config["in_channels"], \
      config["kernel"]
  for reps, ch in stages:
    for _ in range(reps):
      layers.append((a, c, ch, k, 1, 1, 0, 0))
      c = ch
    a = max(a // 2, 1)
  return layers


def workload(config: dict) -> dict:
  rng = np.random.RandomState(config["arch_seed"])
  archs = [tuple((int(rng.choice(reps)), int(rng.choice(chs)))
                 for reps, chs in config["search_space"])
           for _ in range(config["n_archs"])]
  lo, hi = config["accuracy_range"]
  accs = rng.uniform(lo, hi, size=config["n_archs"])
  return {"archs": archs, "accs": [float(a) for a in accs],
          "layers": [layers_of(config, s) for s in archs]}
