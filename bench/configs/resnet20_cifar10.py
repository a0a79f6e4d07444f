"""Plain reference of the resnet20_cifar10 configuration: the weighted
layers (A, C, F, K, S, P, rs, ds) of a CIFAR ResNet-(6n+2) as He et al.
Sec. 4.2 build it: a 3x3 stem, three stages of n basic blocks of two 3x3
convolutions with identity shortcuts (option A: a block that halves the
map and widens the channels zero-pads its shortcut, so it has no
projection convolution; rs/ds flag a regular or a dimension-increasing
shortcut), then global average pooling and the 10-way fully connected
layer, a 1x1 convolution over the pooled 1x1 map: 6n+2 weighted layers."""
from __future__ import annotations


def layers_of(config: dict) -> list:
  n = (config["depth"] - 2) // 6
  a, c = config["image_size"], config["widths"][0]
  layers = [(a, config["in_channels"], c, 3, 1, 1, 0, 0)]
  for stage, f in enumerate(config["widths"]):
    for b in range(n):
      down = stage > 0 and b == 0
      s = 2 if down else 1
      a_out = (a + 2 - 3) // s + 1
      layers.append((a, c, f, 3, s, 1, 0 if down else 1, 1 if down else 0))
      layers.append((a_out, f, f, 3, 1, 1, 1, 0))
      a, c = a_out, f
  layers.append((1, c, config["classes"], 1, 1, 0, 0, 0))
  return layers


def workload(config: dict) -> dict:
  return {"archs": None, "accs": None, "layers": [layers_of(config)]}
