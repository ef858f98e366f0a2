"""DeepSpeedTransformerLayer drop-in API (reference
``ops/transformer/transformer.py:460``; parity role of
``tests/unit/test_cuda_forward.py``)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                           DeepSpeedTransformerLayer)


@pytest.mark.parametrize("pre_ln", [True, False])
def test_layer_runs_and_differentiates(pre_ln):
    cfg = DeepSpeedTransformerConfig(hidden_size=64, intermediate_size=256,
                                     heads=4, pre_layer_norm=pre_ln)
    layer = DeepSpeedTransformerLayer(cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 64)),
                    jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    y = jax.jit(layer.apply)({"params": params}, x)
    assert y.shape == (2, 16, 64)
    assert np.isfinite(np.asarray(y, np.float32)).all()

    g = jax.jit(jax.grad(lambda p: layer.apply(
        {"params": p}, x).astype(jnp.float32).sum()))(params)
    norms = [float(jnp.linalg.norm(l.astype(jnp.float32)))
             for l in jax.tree_util.tree_leaves(
                 jax.tree_util.tree_map(lambda z: getattr(z, "value", z), g,
                     is_leaf=lambda z: hasattr(z, "names")))]
    assert all(np.isfinite(n) for n in norms) and any(n > 0 for n in norms)


def test_layer_masking():
    """Masked-out positions must not influence kept positions."""
    cfg = DeepSpeedTransformerConfig(hidden_size=32, intermediate_size=64,
                                     heads=2)
    layer = DeepSpeedTransformerLayer(cfg)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 8, 32)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    # mask (B, 1, S, S): every query attends only positions < 4
    mask = jnp.broadcast_to(jnp.arange(8)[None, :] < 4, (8, 8))[None, None]
    y1 = jax.jit(layer.apply)({"params": params}, x, mask)
    x2 = x.at[:, 4:].set(rng.normal(size=(1, 4, 32)))   # perturb masked tail
    y2 = jax.jit(layer.apply)({"params": params}, x2, mask)
    np.testing.assert_allclose(np.asarray(y1[:, :4]), np.asarray(y2[:, :4]),
                               rtol=1e-5, atol=1e-5)


def test_layer_remat_matches():
    cfg = DeepSpeedTransformerConfig(hidden_size=32, intermediate_size=64,
                                     heads=2, normalize_invertible=True)
    cfg_plain = DeepSpeedTransformerConfig(hidden_size=32,
                                           intermediate_size=64, heads=2)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 32)),
                    jnp.float32)
    layer_r = DeepSpeedTransformerLayer(cfg)
    layer_p = DeepSpeedTransformerLayer(cfg_plain)
    params = jax.jit(layer_p.init)(jax.random.PRNGKey(0), x)["params"]
    yr = jax.jit(layer_r.apply)({"params": params}, x)
    yp = jax.jit(layer_p.apply)({"params": params}, x)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yp),
                               rtol=1e-6, atol=1e-6)


def test_return_tuple():
    cfg = DeepSpeedTransformerConfig(hidden_size=32, intermediate_size=64,
                                     heads=2, return_tuple=True)
    layer = DeepSpeedTransformerLayer(cfg)
    x = jnp.zeros((1, 4, 32), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    out = jax.jit(layer.apply)({"params": params}, x)
    assert isinstance(out, tuple) and out[0].shape == (1, 4, 32)
