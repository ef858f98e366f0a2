"""Qwen3-Next's mechanisms alone (PR 48; ``test_qwen3next.py`` has the whole
model): the DeltaNet mixer, the gated attention and the expert layer against
``benchmark/reference/qwen3next.py`` and against each of its named faults,
under leaves moved off their initial values; the shares of an expert layer
adding up to the uncut layer with the gated shared expert counted once; the
row kernels at top-10.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.models.llama import (FULL_ATTENTION, GatedDeltaNet,
                                        LlamaAttention)
from deepspeed_tpu.ops.pallas import moe_rows
from deepspeed_tpu.parallel.moe import MoELayer
from tests.unit.test_qwen3next import (ROUTED, TOP_K, _config, _moe, _moved, _rel,
                            reference)


# ----------------------------------------------------------------------
# each mechanism alone against its fault
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _layers_alone():
    """``(cfg, moved leaves, a mixer's input a layer)``: seeded normal
    hidden states of 128 positions, two rows."""
    cfg = _config()
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, cfg.hidden_size))
    key, pos = jax.random.PRNGKey(0), jnp.arange(128)[None, :]
    params = _moved(meta.unbox({
        "layers_0": {"linear_attn": GatedDeltaNet(cfg).init(key, h)[
            "params"]},
        "layers_1": {"self_attn": LlamaAttention(cfg, FULL_ATTENTION).init(
            key, h, pos, None)["params"]}}))
    return cfg, params, [h, h * 0.7 + 0.1]


@pytest.mark.parametrize("fault", [None, *reference.LINEAR_FAULTS])
def test_the_deltanet_mixer_alone_against_each_named_fault(fault):
    cfg, params, mixer_in = _layers_alone()
    # decays slow enough for a state to outlive a chunk and a row
    p = dict(params["layers_0"]["linear_attn"],
             A_log=jnp.log(jnp.asarray([0.01, 0.05, 0.2, 0.5])))
    h = mixer_in[0]
    got = GatedDeltaNet(cfg).apply({"params": p}, h)
    want = reference.linear_attention(
        p, h, n_k_heads=2, n_v_heads=4, eps=cfg.rms_norm_eps, fault=fault)
    err = _rel(got, want)
    if fault is None:
        assert err < 1e-4, err
        return
    assert err > 0.02, (fault, err)


@pytest.mark.parametrize("fault", [None, *reference.FAULTS])
def test_gated_attention_alone_against_each_named_fault(fault):
    cfg, params, mixer_in = _layers_alone()
    p, h = params["layers_1"]["self_attn"], mixer_in[1]
    pos = jnp.arange(h.shape[1])[None, :]
    got = LlamaAttention(cfg, FULL_ATTENTION).apply({"params": p}, h, pos,
                                                    None)
    want = reference.attention(
        FULL_ATTENTION, p, h, n_head=4, n_kv_head=2, head_dim=16,
        rope_theta=100.0, partial_rotary_factor=0.25, eps=cfg.rms_norm_eps,
        fault=fault)
    err = _rel(got, want)
    if fault is None:
        assert err < 1e-4, err
        return
    assert err > 0.02, (fault, err)


def _expert_layer(R=ROUTED, k=TOP_K):
    M, I = 32, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    full = dataclasses.replace(_moe(), num_experts=R, top_k=k)
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = _moved(meta.unbox(whole.init(jax.random.PRNGKey(0), x)["params"]),
               scale=20.0)
    return x, full, whole, p


@pytest.mark.parametrize("fault", [None, *reference.EXPERT_FAULTS])
def test_the_expert_layer_alone_against_each_named_fault(fault):
    x, full, whole, p = _expert_layer()
    got = whole.apply({"params": p}, x)[0]
    want = reference.expert_ffn(p, x, top_k=TOP_K, first_expert=0,
                                fault=fault)
    err = _rel(got, want)
    if fault is None:
        assert err < 1e-4, err
        return
    assert err > 0.02, (fault, err)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of 4 experts give, plus the
    gated shared expert ONCE (every share computes it whole), are the
    uncut reference's 16-expert layer; program and reference agree on every
    share; every pair is multiplied somewhere exactly once."""
    x, full, whole, p = _expert_layer()
    uncut = reference.expert_ffn(p, x, top_k=TOP_K, first_expert=0)
    np.testing.assert_allclose(whole.apply({"params": p}, x)[0], uncut,
                               atol=5e-5)
    no_experts = dict(p, experts={n: w[:0] for n, w in p["experts"].items()})
    shared = reference.expert_ffn(no_experts, x, top_k=TOP_K, first_expert=0)
    assert float(jnp.abs(shared).max()) > 1e-3
    total, multiplied = 0.0, 0
    for first in range(0, ROUTED, 4):
        cfg = dataclasses.replace(full, num_experts=4, routed_experts=ROUTED,
                                  first_expert=first)
        mine = dict(p, experts={n: w[first:first + 4]
                                for n, w in p["experts"].items()})
        part, _, stats = MoELayer(cfg, model_dim=32, hidden_dim=16,
                                  dtype=jnp.float32).apply(
            {"params": mine}, x, return_stats=True)
        np.testing.assert_allclose(
            part, reference.expert_ffn(mine, x, top_k=TOP_K,
                                       first_expert=first), atol=5e-5)
        assert int(stats["dropped"]) == 0
        held = int(stats["tokens_per_expert"][first:first + 4].sum())
        assert int(stats["elsewhere"]) == 128 * TOP_K - held
        total, multiplied = total + (part - shared), multiplied + held
    assert multiplied == 128 * TOP_K
    np.testing.assert_allclose(total + shared, uncut, atol=1e-4)


def test_the_row_kernels_take_top_10():
    """``moe_rows`` at top-10 (the combine deals a token's choices as 16,
    six of them "no row" pairs of weight 0) against ``jnp.take``: the
    weighted sum back into tokens, and the weights' gradient."""
    Sn, K, M = 512, 10, 256
    assert moe_rows.supported(Sn, K, M, jnp.bfloat16) is None
    assert moe_rows.supported(32768, 10, 2048, jnp.bfloat16) is None
    assert "top-17" in moe_rows.supported(Sn, 17, M, jnp.bfloat16)
    rng = np.random.default_rng(0)
    R = Sn * K
    live = R // 16
    order = rng.permutation(R).astype(np.int32)
    inv = np.full(R, R, np.int32)               # R: the pair has no row
    inv[order[:live]] = np.arange(live, dtype=np.int32)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(ks[0], (R, M)).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[1], (Sn, K), jnp.float32)
    g = jax.random.normal(ks[2], (Sn, M)).astype(jnp.bfloat16)
    packed = moe_rows.pack_rows(y, jnp.array([live], jnp.int32),
                                name="moe_rows_back", interpret=True)
    rows = jnp.take(y, jnp.asarray(inv), axis=0, mode="fill",
                    fill_value=0).reshape(Sn, K, M).astype(jnp.float32)
    for got, want in (
            (moe_rows.combine_rows(packed, jnp.asarray(inv), w,
                                   name="moe_rows_back", interpret=True),
             jnp.einsum("skm,sk->sm", rows, w)),
            (moe_rows.combine_rows(packed, jnp.asarray(inv), w, g,
                                   name="moe_rows_back", interpret=True),
             jnp.einsum("skm,sm->sk", rows, g.astype(jnp.float32)))):
        a, b = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert a.shape == b.shape and np.isfinite(a).all()
        assert np.abs(a - b).max() <= 8e-3 * np.abs(b).max()


