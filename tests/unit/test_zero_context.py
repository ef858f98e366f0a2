"""zero.Init / GatheredParameters / TiledLinear / sparse grads.

Parity targets: reference ``partition_parameters.py:529`` (Init),
``:1502`` (GatheredParameters), ``zero/tiling.py:27`` (TiledLinear),
``runtime/sparse_tensor.py`` + ``engine.py:2182`` (sparse allreduce).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
from deepspeed_tpu.parallel import zero

from . import reference_compare as compare
from .simple_model import token_batch


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def test_zero_init_materializes_sharded():
    mesh = mesh_mod.build_mesh({"fsdp": 8})
    mesh_mod.set_mesh(mesh)
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_embd=128, n_layer=2,
                                        n_head=4, n_positions=64))
    with zero.Init(mesh=mesh) as zinit:
        params = zinit.materialize(model, jax.random.PRNGKey(0),
                                   input_ids=jnp.zeros((1, 16), jnp.int32))
    # at least the big 2D+ leaves must actually be partitioned
    sharded = [l for l in jax.tree_util.tree_leaves(params)
               if np.ndim(l) >= 2 and not
               l.sharding.is_equivalent_to(
                   jax.sharding.NamedSharding(mesh, P()), np.ndim(l))]
    assert sharded, "zero.Init produced only replicated leaves"
    # logits usable directly
    out = compare.apply(model, params, jnp.zeros((1, 16), jnp.int32))
    assert out["logits"].shape[0] == 1


def test_gathered_parameters_roundtrip_on_engine():
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_embd=64, n_layer=2,
                                        n_head=4, n_positions=64))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}})
    engine.init_params()
    before_sharding = engine.params["wte"].sharding
    with zero.GatheredParameters(engine) as full:
        assert isinstance(full["wte"], np.ndarray)
        full["wte"][:4, :] = 0.0
    after = engine.params["wte"]
    assert after.sharding.is_equivalent_to(before_sharding, after.ndim)
    np.testing.assert_array_equal(np.asarray(after)[:4], 0.0)
    # engine still trains after surgery
    loss = float(engine.train_batch(token_batch(engine.train_batch_size, 16, 256)))
    assert np.isfinite(loss)


def test_gathered_parameters_raw_tree():
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    ctx = zero.GatheredParameters(params)
    with ctx as full:
        full["w"] *= 3.0
    np.testing.assert_array_equal(np.asarray(ctx.result["w"]), 3.0)


def test_tiled_linear_matches_dense():
    from deepspeed_tpu.parallel import TiledLinear

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 16)), jnp.float32)
    layer = TiledLinear(features=24, in_splits=4, out_splits=3)
    import flax.linen as nn

    vs = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    params = nn.meta.unbox(vs["params"])
    y = compare.apply(layer, params, x)
    assert y.shape == (3, 5, 24)
    # same math as an untiled matmul on the re-assembled kernel
    k = np.asarray(params["kernel"])            # (in_s, out_s, it, ot)
    dense = np.concatenate(
        [np.concatenate(list(k[i]), axis=-1) for i in range(k.shape[0])], axis=0)
    ref = np.asarray(x).reshape(-1, 16) @ dense + np.asarray(params["bias"])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 24), ref,
                               rtol=1e-5, atol=1e-5)
    # gradients flow through the scan
    g = jax.grad(lambda p: layer.apply({"params": p}, x).sum())(params)
    assert np.isfinite(np.asarray(g["kernel"])).all()


def test_tiled_linear_rejects_bad_splits():
    from deepspeed_tpu.parallel import TiledLinear

    with pytest.raises(ValueError, match="not\\s+divisible|not divisible"):
        TiledLinear(features=24, in_splits=5).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 16)))


def test_sparse_tensor_roundtrip_and_exactness():
    from deepspeed_tpu.ops import SparseTensor, to_sparse

    rng = np.random.default_rng(0)
    dense = np.zeros((64, 8), np.float32)
    rows = rng.choice(64, size=6, replace=False)
    dense[rows] = rng.normal(size=(6, 8))
    st = to_sparse(jnp.asarray(dense), max_rows=10)
    np.testing.assert_allclose(np.asarray(st.to_dense()), dense, rtol=1e-6)
    assert st.sparse_size < dense.size


def test_sparse_all_reduce_matches_psum():
    from deepspeed_tpu.ops import sparse_all_reduce

    mesh = mesh_mod.build_mesh({"dp": 8})
    mesh_mod.set_mesh(mesh)
    rng = np.random.default_rng(1)
    # 8 shards of a row-sparse grad: each worker touches <= 4 rows
    grads = np.zeros((8, 32, 4), np.float32)
    for w in range(8):
        rows = rng.choice(32, size=4, replace=False)
        grads[w, rows] = rng.normal(size=(4, 4))
    g = jnp.asarray(grads)

    from deepspeed_tpu.utils.compat import shard_map

    f = shard_map(
        lambda x: sparse_all_reduce(x[0], "dp", max_rows=4),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
        check_vma=False)  # replication over the size-1 axes isn't inferred
    out = np.asarray(f(g))
    np.testing.assert_allclose(out, grads.sum(0), rtol=1e-5, atol=1e-6)


def test_sparse_embedding_grad_applies():
    from deepspeed_tpu.ops.sparse_grads import (apply_sparse_rows,
                                                sparse_embedding_grad)

    table = jnp.zeros((16, 4))
    ids = jnp.asarray([[1, 3, 1]], jnp.int32)
    ct = jnp.ones((1, 3, 4))
    st = sparse_embedding_grad(table, ids, ct)
    new = apply_sparse_rows(table, st)
    expect = np.zeros((16, 4))
    expect[1] = 2.0  # id 1 hit twice → scatter-add
    expect[3] = 1.0
    np.testing.assert_allclose(np.asarray(new), expect)


def test_tiled_linear_init_matches_dense_fan():
    """Tiling must be a pure memory knob: init variance equals the untiled
    dense layer's (fan_in = in_features, not in_features*out_splits)."""
    from deepspeed_tpu.parallel import TiledLinear
    import flax.linen as nn

    layer = TiledLinear(features=256, in_splits=4, out_splits=4)
    params = nn.meta.unbox(
        jax.jit(layer.init)(jax.random.PRNGKey(0),
                            jnp.zeros((1, 256)))["params"])
    std = float(np.asarray(params["kernel"]).std())
    expect = 1.0 / np.sqrt(256)   # lecun_normal on fan_in=256
    assert abs(std - expect) / expect < 0.1, (std, expect)
