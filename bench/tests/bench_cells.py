"""Tiny versions of the benchmark's cells for tests on the CPU."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
  if path not in sys.path:
    sys.path.insert(0, path)

from bench import harness  # noqa: E402

# the traffic of each cell cut to a size a test run holds; the joint cell
# keeps 12 of its architectures, in two arch blocks of different shapes
TINY = {
    "resnet20_cifar10.dse1m": {"traffic": {"n_per_type": 300,
                                           "chunk_size": 128}},
    "supernet_coexplore.stream10m": {"traffic": {"n_hw_per_type": 40,
                                                 "chunk_size": 320},
                                     "config": {"n_archs": 12}},
}


def tiny_cell(name: str) -> harness.Cell:
  cell = harness.resolve(harness.load_benchmark(), name)
  cell.traffic.update(TINY[name]["traffic"])
  if "config" in TINY[name]:
    cell.config.update(TINY[name]["config"])
    module = harness.load_module(os.path.join(
        harness.BENCH_DIR, "configs", cell.name.split(".")[0] + ".py"))
    cell.workload = module.workload(cell.config)
  return cell
