"""MoE gating + layer tests — analog of reference ``tests/unit/test_moe.py``
plus gating-math checks the reference covers implicitly via Megatron runs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.parallel.moe import (
    MoEConfig, MoELayer, top1_gating, top2_gating,
)


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def naive_top1(logits, capacity):
    """Literal per-token loop implementing top-1 dispatch for comparison."""
    S, E = logits.shape
    gates = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    gates = np.asarray(gates)
    counts = np.zeros(E, int)
    combine = np.zeros((S, E, capacity))
    for s in range(S):
        e = int(np.argmax(logits[s]))
        if counts[e] < capacity:
            combine[s, e, counts[e]] = gates[s, e]
            counts[e] += 1
    return combine


def test_top1_gating_matches_naive():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(32, 4)).astype(np.float32)
    cap = 8
    l_aux, combine, dispatch = jax.jit(lambda l: top1_gating(l, cap))(logits)
    np.testing.assert_allclose(np.asarray(combine), naive_top1(logits, cap),
                               rtol=1e-5, atol=1e-6)
    assert np.asarray(dispatch).sum() <= 32
    assert float(l_aux) > 0


def test_top1_capacity_drops_tokens():
    # all tokens pick expert 0; capacity 4 → only 4 dispatched
    logits = np.zeros((16, 4), np.float32)
    logits[:, 0] = 10.0
    _, combine, dispatch = top1_gating(jnp.asarray(logits), 4)
    assert int(np.asarray(dispatch).sum()) == 4


def test_top2_gating_properties():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    l_aux, combine, dispatch = top2_gating(logits, capacity=32)
    combine = np.asarray(combine)
    # each token's combine weights sum to ~1 (both experts kept, normalized)
    sums = combine.sum(axis=(1, 2))
    kept_two = np.asarray(dispatch).sum(axis=(1, 2)) == 2
    np.testing.assert_allclose(sums[kept_two], 1.0, rtol=1e-5)
    # a token never uses the same expert twice
    per_expert = (combine > 0).sum(axis=2)
    assert per_expert.max() <= 1


def test_moe_layer_forward_and_shapes():
    mesh = build_mesh({"ep": 4, "dp": 2})
    mesh_mod.set_mesh(mesh)
    cfg = MoEConfig(num_experts=4, top_k=1, capacity_factor=2.0)
    layer = MoELayer(cfg, model_dim=16, hidden_dim=32, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 10, 16)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    (out, l_aux), _ = jax.jit(
        lambda p, x: (layer.apply(p, x, train=False), 0))(params, x)
    assert out.shape == x.shape
    assert np.isfinite(float(l_aux))


def test_moe_layer_residual():
    cfg = MoEConfig(num_experts=2, top_k=1, use_residual=True)
    layer = MoELayer(cfg, model_dim=8, hidden_dim=16, dtype=jnp.float32)
    x = jnp.ones((4, 8))
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    out, l_aux = layer.apply(params, x)
    assert out.shape == x.shape
    assert "coefficient" in params["params"]


def test_moe_capacity_scaling_all_dispatched():
    """With generous capacity every token must reach an expert (sum of
    dispatch == S) and MoE output must differ per expert choice."""
    cfg = MoEConfig(num_experts=4, top_k=1, capacity_factor=4.0)
    layer = MoELayer(cfg, model_dim=8, hidden_dim=8, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 8, 8)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    out, _ = layer.apply(params, x)
    assert not np.allclose(np.asarray(out), 0.0)


def test_moe_w8_experts_match_fp_on_dequantized_weights():
    """The int8 experts of the capacity dispatch (what a ``w8`` engine
    serves an MoE with) stay within quantization error of the fp experts
    on the same (quantized-then-dequantized) weights."""
    from deepspeed_tpu.ops.w8 import quantize_dense_tree, quantize_weight

    cfg = MoEConfig(num_experts=4, top_k=1, capacity_factor=4.0,
                    eval_capacity_factor=4.0)
    fp = MoELayer(cfg, model_dim=16, hidden_dim=32, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(5, 16)),
                    jnp.float32)
    params = jax.jit(fp.init)(jax.random.PRNGKey(1), x)
    qtree = quantize_dense_tree(
        jax.tree_util.tree_map(lambda l: getattr(l, "value", l), params,
                               is_leaf=lambda l: hasattr(l, "value")),
        group=128)
    q8 = MoELayer(cfg, model_dim=16, hidden_dim=32, dtype=jnp.float32,
                  w8=True)
    out_q, _ = q8.apply(qtree, x, train=False)
    # reference: dequantize the expert weights on the host, run fp path
    deq = jax.tree_util.tree_map(lambda l: getattr(l, "value", l), params,
                                 is_leaf=lambda l: hasattr(l, "value"))

    def dq(w):
        codes, scale = quantize_weight(jnp.asarray(w), 128)
        G = scale.shape[1]
        g = codes.shape[1] // G
        return np.asarray(
            (codes.reshape(codes.shape[0], G, g, -1).astype(jnp.float32)
             * scale[:, :, None, :]).reshape(codes.shape))

    deq["params"]["experts"]["wi"] = dq(deq["params"]["experts"]["wi"])
    deq["params"]["experts"]["wo"] = dq(deq["params"]["experts"]["wo"])
    out_ref, _ = fp.apply(deq, x, train=False)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-4)
