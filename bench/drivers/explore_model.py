"""Sweep driver: a streamed plain design-space exploration of one step of
a model configuration.  The step is lowered by the program's own
``repro.core.workloads.from_config`` into merged GEMM entries, and swept
through ``ExplorationSession.explore(stream=True)`` on
``VectorOracleBackend(jit=True)``, as the ``explore`` driver sweeps a
network; its reference is the configuration's plain lowering
(``bench/configs/<config>.py``) under ``bench/reference/gemm.py``."""
from __future__ import annotations

import dataclasses

from bench.drivers import explore
from bench.reference import gemm

# the program's ModelConfig field of each published key the step reads
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "qk_nope_head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "kv_lora_rank": "kv_lora_rank",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_experts", "num_experts_per_tok":
    "n_experts_active", "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "d_ff_expert",
    "first_k_dense_replace": "first_dense_layers",
}


def model_config(config: dict):
  """The program's registered config of ``config["program_config"]``,
  with every published key and assumed routing of the configuration
  file written over it (equal, for the file as committed)."""
  from repro.configs import get_config
  a = config["assumed"]
  return dataclasses.replace(
      get_config(config["program_config"]),
      **{field: config[key] for key, field in FIELDS.items()},
      q_lora_rank=config["q_lora_rank"] or 0,
      d_ff_shared=config["n_shared_experts"]
      * config["moe_intermediate_size"],
      router_skew=a["router_skew"], router_seed=a["router_seed"])


def regime(config: dict):
  """The decode regime the configuration file states."""
  from repro.configs.shapes import ShapeSpec
  a = config["assumed"]
  return ShapeSpec(config["regime"], a["cached_positions"],
                   a["decode_batch"], "decode")


class Driver(explore.Driver):

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
  return gemm.explore(config, entries, traffic["n_per_type"], seed,
                      traffic["reducers"])
