"""Distribution tests: sharding rules, compressed collectives, fault
tolerance, serving engine, supernet, co-exploration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import build_model
from repro.parallel import collectives, sharding as sh
from repro.train.fault_tolerance import (ElasticMeshPlanner,
                                         StragglerMonitor, StepFailure,
                                         retrying)

KEY = jax.random.PRNGKey(0)


def _fake_mesh(shape=(2, 2), axes=("data", "model")):
  devs = jax.devices()
  if len(devs) < np.prod(shape):
    # abstract mesh purely for spec computation; signature differs across
    # jax versions: (shape, axes) vs (((name, size), ...),)
    try:
      return jax.sharding.AbstractMesh(shape, axes)
    except TypeError:
      return jax.sharding.AbstractMesh(tuple(zip(axes, shape)))
  return jax.make_mesh(shape, axes,
                       axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                       devices=devs[: int(np.prod(shape))])


class TestParamSpecs:
  def test_adaptive_divisibility(self):
    mesh = _fake_mesh((2, 2))
    params = {"blocks": {"sub0": {"mix": {
        "wq": jnp.zeros((8, 4, 6)),     # stacked; 6 % 2 == 0 -> model
        "wkv": jnp.zeros((8, 4, 3)),    # 3 % 2 != 0 -> replicate dim
    }}}}
    specs = sh.param_specs(params, mesh)
    wq = specs["blocks"]["sub0"]["mix"]["wq"]
    wkv = specs["blocks"]["sub0"]["mix"]["wkv"]
    assert wq == P(None, "data", "model")
    assert wkv == P(None, "data", None)

  def test_embed_vocab_sharded(self):
    mesh = _fake_mesh((2, 2))
    specs = sh.param_specs({"embed": jnp.zeros((512, 64))}, mesh)
    assert specs["embed"] == P("model", "data")

  def test_every_leaf_gets_spec(self):
    cfg = reduce_for_smoke(get_config("jamba-1.5-large"))
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, KEY)
    mesh = _fake_mesh((2, 2))
    specs = sh.param_specs(shapes, mesh)
    n_params = len(jax.tree_util.tree_leaves(shapes))
    n_specs = len(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
    assert n_params == n_specs

  def test_cache_specs_long_context_seq_sharding(self):
    """batch=1 decode: cache seq dim shards on data."""
    mesh = _fake_mesh((4, 2))
    cache = {"layers": {"sub0": {
        "k": jax.ShapeDtypeStruct((3, 1, 2, 64, 8), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((3, 1, 2, 64, 8), jnp.bfloat16)}},
        "length": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = sh.cache_specs(cache, mesh, batch=1)
    assert specs["layers"]["sub0"]["k"] == P(None, None, "model", "data",
                                             None)


class TestCompressedCollectives:
  def test_quantize_dequantize_error_bound(self):
    x = jax.random.normal(KEY, (1000,))
    q = collectives.quantize_dequantize(x)
    # block absmax / 127 error bound
    assert float(jnp.max(jnp.abs(q - x))) <= float(
        jnp.max(jnp.abs(x))) / 127.0 + 1e-6

  def test_error_feedback_reduces_bias(self):
    """EF compression: accumulated compressed sum tracks the true sum."""
    ef = collectives.ErrorFeedback
    g_true = jax.random.normal(KEY, (512,)) * 1e-3
    res = ef.init({"g": g_true})
    acc_c = jnp.zeros_like(g_true)
    for i in range(20):
      comp, res = ef.apply({"g": g_true}, res)
      acc_c = acc_c + comp["g"]
    # relative error of accumulated compressed stream vs true
    rel = float(jnp.linalg.norm(acc_c - 20 * g_true)
                / jnp.linalg.norm(20 * g_true))
    assert rel < 0.02, rel

  @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >=2 devices")
  def test_compressed_psum_matches_psum(self):
    mesh = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])
    x = jax.random.normal(KEY, (2, 256))

    def f(x):
      return collectives.compressed_psum_int8(x[0], "data")

    got = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                        check_vma=False)(x)
    want = jnp.sum(x, axis=0)
    assert float(jnp.max(jnp.abs(got - want))) < float(
        jnp.max(jnp.abs(x))) / 40.0


class TestFaultTolerance:
  def test_straggler_detection(self):
    mon = StragglerMonitor(min_samples=5)
    for step in range(10):
      for h in range(8):
        t = 1.0 if h != 3 else 2.5   # host 3 is slow
        mon.record(f"host{h}", t + 0.01 * step)
    assert mon.stragglers() == ["host3"]

  def test_no_false_positives(self):
    mon = StragglerMonitor(min_samples=5)
    rng = np.random.RandomState(0)
    for step in range(20):
      for h in range(8):
        mon.record(f"host{h}", 1.0 + rng.normal(0, 0.02))
    assert mon.stragglers() == []

  def test_elastic_plan_keeps_tp(self):
    planner = ElasticMeshPlanner(model_parallel=16, global_batch=256,
                                 batch_per_dp=16)
    plan = planner.plan(healthy_devices=208)   # lost 3 hosts of 16 devs
    assert plan is not None
    assert plan.model == 16
    assert plan.data <= 13
    assert plan.devices <= 208
    assert 256 % (plan.data * plan.pods) == 0

  def test_elastic_plan_impossible(self):
    planner = ElasticMeshPlanner(model_parallel=16, global_batch=256,
                                 batch_per_dp=16)
    assert planner.plan(healthy_devices=8) is None

  def test_retrying_recovers(self):
    calls = {"n": 0}

    def flaky():
      calls["n"] += 1
      if calls["n"] < 3:
        raise RuntimeError("transient")
      return "ok"

    assert retrying(flaky, max_retries=3)() == "ok"

  def test_retrying_escalates(self):
    def always_fails():
      raise RuntimeError("hard")

    with pytest.raises(StepFailure):
      retrying(always_fails, max_retries=1)()


class TestServeEngine:
  def test_batched_requests_complete(self):
    from repro.serve.engine import EngineConfig, ServeEngine
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=2, max_len=64, prompt_bucket=16))
    rng = np.random.RandomState(0)
    uids = [eng.submit(rng.randint(0, cfg.vocab_size, size=8),
                       max_new_tokens=5) for _ in range(4)]
    out = eng.run_until_drained()
    assert set(out) == set(uids)
    assert all(len(v) == 5 for v in out.values())

  def test_deadline_evicts_queued_and_active(self):
    from repro.explore.service import Deadline
    from repro.serve.engine import EngineConfig, ServeEngine
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=1, max_len=64, prompt_bucket=16))
    rng = np.random.RandomState(0)
    t = {"now": 0.0}
    # u1 has a deadline that expires while it decodes; u2's expires
    # while it sits behind u1 in the queue; u3 is unconstrained
    u1 = eng.submit(rng.randint(0, cfg.vocab_size, size=8),
                    max_new_tokens=50,
                    deadline=Deadline(1.0, clock=lambda: t["now"]))
    u2 = eng.submit(rng.randint(0, cfg.vocab_size, size=8),
                    max_new_tokens=5,
                    deadline=Deadline(1.0, clock=lambda: t["now"]))
    u3 = eng.submit(rng.randint(0, cfg.vocab_size, size=8),
                    max_new_tokens=5)
    eng._admit()        # u1 takes the slot while the deadline is live
    t["now"] = 2.0      # both deadlines expire
    out = eng.run_until_drained()
    assert set(out) == {u1, u2, u3}
    assert 0 < len(out[u1]) < 50   # partial generation kept
    assert out[u2] == []           # evicted before any prefill
    assert len(out[u3]) == 5       # neighbor unaffected
    assert eng.n_evicted == 2

  def test_seconds_deadline_coerced(self):
    from repro.explore.service import Deadline
    from repro.serve.engine import EngineConfig, ServeEngine
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=1, max_len=64, prompt_bucket=16))
    uid = eng.submit(np.arange(8) % cfg.vocab_size, max_new_tokens=3,
                     deadline=30.0)
    assert isinstance(eng.queue[0].deadline, Deadline)
    out = eng.run_until_drained()
    assert len(out[uid]) == 3

  def test_greedy_determinism(self):
    from repro.serve.engine import EngineConfig, ServeEngine
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    prompt = np.arange(8) % cfg.vocab_size
    outs = []
    for _ in range(2):
      eng = ServeEngine(model, params, EngineConfig(
          batch_slots=1, max_len=64, prompt_bucket=16))
      eng.submit(prompt, max_new_tokens=6)
      outs.append(list(eng.run_until_drained().values())[0])
    assert outs[0] == outs[1]


class TestSupernetBridge:
  def test_arch_to_layers(self):
    from repro.core.cnn import max_arch
    from repro.core.supernet import arch_to_layers, space_size
    assert space_size() == 110592
    layers = arch_to_layers(max_arch(), image_size=32)
    assert len(layers) == 13   # VGG-16's conv count
    assert layers[0].C == 3 and layers[-1].F == 512

  @pytest.mark.slow
  def test_mask_equals_slice_semantics(self):
    """Masked supernet == manually sliced subnet (exactness property)."""
    from repro.core import cnn
    params = cnn.init_vgg_supernet(KEY, 10)
    arch = cnn.ArchChoice(((1, 40), (2, 96), (1, 160), (2, 320), (1, 320)))
    imgs = jax.random.normal(KEY, (2, 16, 16, 3))
    got = cnn.apply_vgg(params, imgs, arch)
    # manual slice reference
    x = imgs
    c_prev = 3
    for si, ((r_use, c_use), stage) in enumerate(zip(arch.stages,
                                                     params["stages"])):
      for r in range(r_use):
        w = stage[r]["w"]
        # full-width conv on zero-padded channels == sliced conv
        xw = jnp.pad(x, ((0, 0), (0, 0), (0, 0),
                         (0, w.shape[2] - x.shape[-1])))
        y = cnn.conv2d(xw, w)[..., :c_use]
        y = cnn.batch_norm(y, stage[r]["scale"][:c_use],
                           stage[r]["bias"][:c_use])
        x = jax.nn.relu(y)
      if x.shape[1] > 1:
        x = cnn.maxpool(x)
    feats = jnp.mean(x, axis=(1, 2))
    want = jnp.einsum("bc,cn->bn",
                      jnp.pad(feats, ((0, 0),
                                      (0, params["head"].shape[0]
                                       - feats.shape[-1]))),
                      params["head"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
