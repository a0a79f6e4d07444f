"""The fused exploration programs compile for a TPU v5e chip.

XLA's TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests guard the chip path without one.
They compile the programs the streaming engine dispatches on
``VectorOracleBackend(jit=True)`` — same reduction plans, same float64
inputs, same flags as on the chip — at a reduced block:

  * the fused joint program (3-D Pareto + top-100 by energy) at 4 archs x
    2,048 HW rows; the 10M-pair sweep's real block is 104 x 2,500, which
    takes minutes to compile;
  * the fused plain program (2-D Pareto + top-100) for all 21 resnet20
    layers at 4,096 rows; the real chunk is 65,536 rows;
  * the fused layer-table program (the same reducers) over DeepSeek-V3.2's
    decode step at 128k, 45 entries with an 8-bit key cache among them,
    at 4,096 rows.

Both take the x64 bundle, whose variation columns the program derives
from uint64 keys: the chip compiles the f64 -> u64 convert of the
integer knobs, but no f64 -> u64 bitcast, so the bandwidth's bit pattern
arrives from the host among the keys.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and test workers import every file.
"""
import os
import re

import numpy as np
import pytest

from repro.core import oracle
from repro.core.cnn import SEARCH_SPACE, ArchChoice
from repro.core.dataflow import LayerStack
from repro.core.supernet import arch_to_layers
from repro.core.workloads import get_network
from repro.explore import DesignSpace, ParetoAccumulator, TopKAccumulator
from repro.explore import device as device_lib

JOINT_ARCHS, JOINT_HW = 4, 2048
PLAIN_ROWS = 4096
V5E_HBM_BYTES = 16 * 1024 ** 3

# The test process carries the CPU exactness flags (tests/conftest.py),
# and XLA_FLAGS reach the TPU compiler too; the chip path carries none
# (device.ensure_exact_cpu_codegen), so each compile drops them again.
CHIP_PATH_OPTIONS = {"xla_disable_hlo_passes": ""}


@pytest.fixture(scope="module")
def topo():
  import jax
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  # a TPU program written to the persistent cache cannot be read back
  # without a chip, so the cache stays off around these compiles
  was_enabled = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    try:
      topology = topologies.get_topology_desc(platform="tpu",
                                              topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
      pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topology
  finally:
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
  from jax.sharding import SingleDeviceSharding
  return SingleDeviceSharding(topo.devices[0])


def _on_chip(a, one_chip, rows=None):
  """The ShapeDtypeStruct of ``a`` on the described chip; ``rows``
  replaces the leading (row) dimension."""
  import jax
  a = np.asarray(a)
  shape = a.shape if rows is None else (rows,) + a.shape[1:]
  return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)


def _inputs_bundle():
  """A real x64 ``oracle.batch_inputs`` bundle: its keys and dtypes are
  what the chip program receives."""
  space = DesignSpace()
  return oracle.batch_inputs(space.sample_type_table(space.pe_types[0], 8,
                                                     seed=3),
                             device_variations=True)


def _plain_program():
  plan = device_lib.build_plan(
      {"pareto": ParetoAccumulator(),
       "top": TopKAccumulator(100, by="energy_mj")}, joint=False)
  layers = tuple(get_network("resnet20"))
  assert len(layers) == 21
  return device_lib.make_eval_fn(layers, plan)


def _compile(fn, args):
  import jax
  with jax.enable_x64(True):
    return jax.jit(fn).lower(*args).compile(CHIP_PATH_OPTIONS)


def _check(compiled):
  mem = compiled.memory_analysis()
  used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
          + mem.temp_size_in_bytes)
  assert 0 < used < V5E_HBM_BYTES
  # the float64 program really is float64 on the chip
  assert "f64" in compiled.as_text()


def test_fused_joint_program_compiles_for_v5e(one_chip):
  rng = np.random.RandomState(0)
  archs = [ArchChoice(tuple((int(rng.choice(reps)), int(rng.choice(chs)))
                            for reps, chs in SEARCH_SPACE))
           for _ in range(JOINT_ARCHS)]
  stack = LayerStack.from_layer_lists(
      [arch_to_layers(a, image_size=16) for a in archs])
  unique_cols, slot_ids = stack.dedup_slots()
  plan = device_lib.build_plan(
      {"pareto": ParetoAccumulator(("top1_err", "energy_mj", "area_mm2")),
       "top": TopKAccumulator(100, by="energy_mj")}, joint=True)
  compiled = _compile(device_lib.make_joint_fn(plan), (
      {k: _on_chip(v, one_chip, JOINT_HW)
       for k, v in _inputs_bundle().items()},
      {k: _on_chip(v, one_chip) for k, v in unique_cols.items()},
      _on_chip(slot_ids, one_chip), _on_chip(stack.valid, one_chip),
      _on_chip(np.zeros(JOINT_ARCHS), one_chip)))
  _check(compiled)


def test_fused_plain_program_compiles_for_v5e(one_chip):
  compiled = _compile(_plain_program(), (
      {k: _on_chip(v, one_chip, PLAIN_ROWS)
       for k, v in _inputs_bundle().items()},))
  _check(compiled)


def test_fused_table_program_compiles_for_v5e(one_chip):
  from repro.configs import get_config
  from repro.configs.shapes import ShapeSpec
  from repro.core.dataflow import layer_table
  from repro.core.workloads import from_config
  layers = from_config(get_config("deepseek-v3.2"),
                       ShapeSpec("decode_128k", 131072, 32, "decode"))
  cols, counts = layer_table(layers)
  assert len(counts) == 45 and cols["cache"].max() == 8.0
  plan = device_lib.build_plan(
      {"pareto": ParetoAccumulator(),
       "top": TopKAccumulator(100, by="energy_mj")}, joint=False)
  compiled = _compile(device_lib.make_table_fn(plan), (
      {k: _on_chip(v, one_chip, PLAIN_ROWS)
       for k, v in _inputs_bundle().items()},
      {k: _on_chip(v, one_chip) for k, v in cols.items()},
      _on_chip(counts, one_chip)))
  _check(compiled)


def test_variation_keys_arrive_as_uint64_with_no_bitcast(one_chip):
  """The chip's compiler refuses ``bitcast_convert_type(f64 -> u64)``:
  the keys it cannot convert come from the host as one uint64 array, and
  the program converts, never bitcasts, a float64 column."""
  import jax
  bundle = _inputs_bundle()
  assert bundle["var_keys"].dtype == np.uint64
  assert bundle["var_keys"].shape == (8, 2)
  with jax.enable_x64(True):
    text = jax.jit(_plain_program()).lower(
        {k: _on_chip(v, one_chip, PLAIN_ROWS)
         for k, v in bundle.items()}).as_text()
  main = re.search(r"func\.func public @main\(.*", text).group(0)
  assert f"tensor<{PLAIN_ROWS}x2xui64>" in main
  assert re.search(r"stablehlo\.convert %\w+ : \(tensor<\d+xf64>\) -> "
                   r"tensor<\d+xui64>", text)
  assert not re.search(r"bitcast_convert.*f64.*->.*ui64", text)
