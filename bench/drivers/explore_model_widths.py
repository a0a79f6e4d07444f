"""Sweep driver: the ``explore_model`` sweep of a model step whose right
operands may be stored at a width of their own (DeepSeek Sparse
Attention's 8-bit indexer key cache).  The program's config also takes
the lightning indexer's published keys from the configuration file, and
the reference reads each entry's stored width
(``bench/reference/gemm_widths.py``)."""
from __future__ import annotations

import dataclasses

from bench.drivers import explore, explore_model
from bench.reference import gemm_widths

# the program's ModelConfig field of each published indexer key
INDEXER = ("index_n_heads", "index_head_dim", "index_topk")

regime = explore_model.regime


def model_config(config: dict):
  """``explore_model.model_config`` with the indexer's keys of the
  configuration file written over it too."""
  return dataclasses.replace(explore_model.model_config(config),
                             **{key: config[key] for key in INDEXER})


class Driver(explore.Driver):
  """``explore_model``'s driver, its step lowered once, from this
  module's config."""

  def __init__(self, config: dict, workload: dict, traffic: dict,
               precision: str = "x64"):
    from repro.core.workloads import from_config
    from repro.explore import (DesignSpace, DevicePool, ExplorationSession,
                               VectorOracleBackend)
    self.config, self.workload, self.traffic = config, workload, traffic
    self.layers = from_config(model_config(config), regime(config))
    self.space = DesignSpace(pe_types=config["pe_types"],
                             axes=config["hw_ranges"])
    self.backend = VectorOracleBackend(chunk_size=traffic["chunk_size"],
                                       jit=True, precision=precision)
    self.session = ExplorationSession(self.backend, self.space)
    self.pool = DevicePool() if traffic.get("pool") else None


def reference(config: dict, workload: dict, traffic: dict, seed: int) -> dict:
  """The plain reference's answers for the sweep seeded ``seed``."""
  (entries,) = workload["layers"]
  return gemm_widths.explore(config, entries, traffic["n_per_type"], seed,
                             traffic["reducers"])
