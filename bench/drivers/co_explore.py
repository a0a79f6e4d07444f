"""Sweep driver: a streamed joint HW x NN co-exploration through
``ExplorationSession.co_explore(stream=True)`` on
``VectorOracleBackend(jit=True)``, through a ``DevicePool`` over every
chip when the traffic mix asks for one."""
from __future__ import annotations

import itertools

from bench import sweep
from bench.reference import sweeps as ref


class Driver:

  def __init__(self, config: dict, workload: dict, traffic: dict,
               precision: str = "x64"):
    from repro.core.cnn import ArchChoice
    from repro.explore import (DesignSpace, DevicePool, ExplorationSession,
                               VectorOracleBackend)
    self.config, self.workload, self.traffic = config, workload, traffic
    self.arch_accs = [(ArchChoice(tuple(tuple(s) for s in stages)), acc)
                      for stages, acc in zip(workload["archs"],
                                             workload["accs"])]
    self.space = DesignSpace(pe_types=config["pe_types"],
                             axes=config["hw_ranges"])
    self.backend = VectorOracleBackend(chunk_size=traffic["chunk_size"],
                                       jit=True, precision=precision)
    self.session = ExplorationSession(self.backend, self.space)
    self.pool = DevicePool() if traffic.get("pool") else None

  def _args(self, seed: int) -> dict:
    return dict(n_hw_per_type=self.traffic["n_hw_per_type"], seed=seed,
                image_size=self.config["image_size"],
                method=self.traffic["method"],
                chunk_size=self.traffic["chunk_size"])

  def warm_jobs(self, seed: int):
    """One chunk of each block shape of the first PE type (and of every
    device of a pool): arch blocks of ``chunk // n_hw`` archs, the last
    one shorter."""
    from repro.explore.streaming import co_explore_tasks
    n_archs, n_hw = len(self.arch_accs), self.traffic["n_hw_per_type"]
    chunk = self.traffic["chunk_size"]
    hw_chunk = min(n_hw, chunk)
    arch_block = max(1, chunk // hw_chunk)
    shapes = [(min(a + arch_block, n_archs) - a, min(h + hw_chunk, n_hw) - h)
              for a in range(0, n_archs, arch_block)
              for h in range(0, n_hw, hw_chunk)]
    picks = sweep.first_of_each_shape(shapes)
    a = self._args(seed)
    tasks = list(itertools.islice(co_explore_tasks(
        self.backend, self.space, self.arch_accs, a["n_hw_per_type"], seed,
        a["image_size"], a["method"], chunk,
        sweep.make_reducers(self.traffic["reducers"])), picks[-1] + 1))
    return sweep.pinned_jobs([tasks[i] for i in picks], self.pool)

  def sweep(self, seed: int) -> sweep.Outcome:
    red = sweep.make_reducers(self.traffic["reducers"])
    res = self.session.co_explore(self.arch_accs, stream=True, reducers=red,
                                  pool=self.pool, **self._args(seed))
    return sweep.Outcome(res.n_rows, res.meta, sweep.answers(red))


def reference(config: dict, workload: dict, traffic: dict, seed: int) -> dict:
  """The plain reference's answers for the sweep seeded ``seed``."""
  return ref.co_explore(config, workload["layers"], workload["accs"],
                        traffic["n_hw_per_type"], seed, traffic["reducers"])
