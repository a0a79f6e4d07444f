"""Sweep driver: a streamed plain design-space exploration of one network
through ``ExplorationSession.explore(stream=True)`` on
``VectorOracleBackend(jit=True)``, through a ``DevicePool`` over every
chip when the traffic mix asks for one."""
from __future__ import annotations

import itertools

from bench import sweep
from bench.reference import sweeps as ref


class Driver:

  def __init__(self, config: dict, workload: dict, traffic: dict,
               precision: str = "x64"):
    from repro.core.dataflow import ConvLayer
    from repro.explore import (DesignSpace, DevicePool, ExplorationSession,
                               VectorOracleBackend)
    self.config, self.workload, self.traffic = config, workload, traffic
    (layers,) = workload["layers"]
    self.layers = [ConvLayer(f"l{i}", *l) for i, l in enumerate(layers)]
    self.space = DesignSpace(pe_types=config["pe_types"],
                             axes=config["hw_ranges"])
    self.backend = VectorOracleBackend(chunk_size=traffic["chunk_size"],
                                       jit=True, precision=precision)
    self.session = ExplorationSession(self.backend, self.space)
    self.pool = DevicePool() if traffic.get("pool") else None

  def warm_jobs(self, seed: int):
    """One chunk of each chunk length of the first PE type (and of every
    device of a pool)."""
    from repro.explore.streaming import explore_tasks
    n, chunk = self.traffic["n_per_type"], self.traffic["chunk_size"]
    picks = sweep.first_of_each_shape(
        [min(chunk, n - lo) for lo in range(0, n, chunk)])
    tasks = list(itertools.islice(explore_tasks(
        self.backend, self.space, self.layers, self.config["network"], n,
        seed, self.traffic["method"], chunk,
        sweep.make_reducers(self.traffic["reducers"])), picks[-1] + 1))
    return sweep.pinned_jobs([tasks[i] for i in picks], self.pool)

  def sweep(self, seed: int) -> sweep.Outcome:
    red = sweep.make_reducers(self.traffic["reducers"])
    res = self.session.explore(
        self.layers, self.config["network"],
        n_per_type=self.traffic["n_per_type"], seed=seed,
        method=self.traffic["method"], stream=True, reducers=red,
        chunk_size=self.traffic["chunk_size"], pool=self.pool)
    return sweep.Outcome(res.n_rows, res.meta, sweep.answers(red))


def reference(config: dict, workload: dict, traffic: dict, seed: int) -> dict:
  """The plain reference's answers for the sweep seeded ``seed``."""
  (layers,) = workload["layers"]
  return ref.explore(config, layers, traffic["n_per_type"], seed,
                     traffic["reducers"])
