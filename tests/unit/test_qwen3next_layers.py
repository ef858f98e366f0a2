"""Qwen3-Next's mechanisms alone (PR 48; ``test_qwen3next.py`` has the whole
model): the DeltaNet mixer, the gated attention and the expert layer against
``benchmark/reference/qwen3next.py`` and against each of its named faults,
under leaves moved off their initial values; the shares of an expert layer
adding up to the uncut layer with the gated shared expert counted once; the
row kernels at top-10; since PR 53 the mixer with its two per-head norms
made on the rows (``ops/pallas/qk_rows.py``) against the mixer on the
``(B, S, H, d)`` view, and what a linear block's jaxpr holds then.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import (FULL_ATTENTION, GatedDeltaNet,
                                        LlamaAttention, LlamaBlock)
from deepspeed_tpu.ops import attention, gated_delta, rotary
from deepspeed_tpu.ops.pallas import moe_rows
from deepspeed_tpu.parallel.moe import MoELayer
from tests.unit.test_qk_rows import _eqns
from tests.unit.test_qwen3next import (ROUTED, TOP_K, _config, _moe, _moved,
                                       reference)

from . import reference_compare as compare
from .reference_compare import rel as _rel


# ----------------------------------------------------------------------
# each mechanism alone against its fault
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _layers_alone():
    """``(cfg, (leaves, input, the program's output) of the DeltaNet mixer and
    of the gated attention)``: seeded normal hidden states of 128 positions,
    two rows; each output compiled once for every fault it is held against."""
    cfg = _config()
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, cfg.hidden_size))
    pos = jnp.arange(128)[None, :]
    mixer, attention = GatedDeltaNet(cfg), LlamaAttention(cfg, FULL_ATTENTION)
    params = _moved({"linear_attn": compare.init(mixer, h),
                     "self_attn": compare.init(attention, h, pos, None)})
    # decays slow enough for a state to outlive a chunk and a row
    lin = dict(params["linear_attn"],
               A_log=jnp.log(jnp.asarray([0.01, 0.05, 0.2, 0.5])))
    attn, h1 = params["self_attn"], h * 0.7 + 0.1
    return cfg, (lin, h, compare.apply(mixer, lin, h)), (
        attn, h1, compare.apply(attention, attn, h1, pos, None))


@pytest.mark.parametrize("fault", [None, *reference.LINEAR_FAULTS])
def test_the_deltanet_mixer_alone_against_each_named_fault(fault):
    cfg, (p, h, got), _ = _layers_alone()
    want = reference.linear_attention(
        p, h, n_k_heads=2, n_v_heads=4, eps=cfg.rms_norm_eps, fault=fault)
    err = _rel(got, want)
    if fault is None:
        assert err < 1e-4, err
        return
    assert err > 0.02, (fault, err)


MIXER_PARTS = ("output", "A_log", "conv_kernel", "dt_bias", "in_proj_ba",
               "in_proj_qkvz_kernel", "o_norm", "out_proj", "input")


@functools.lru_cache(maxsize=None)
def _mixer_on_rows():
    """``MIXER_PARTS`` - the output and the gradients of every leaf and of
    the input - of the mixer at heads of 128 x 128 on the ``(B, S, H, d)``
    view, and with both norms' plans forced (the kernels in the
    interpreter)."""
    cfg = _config(linear_key_head_dim=128, linear_value_head_dim=128)
    mixer = GatedDeltaNet(cfg)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    p = _moved(compare.init(mixer, h))
    ct = jax.random.normal(jax.random.PRNGKey(6), h.shape)

    def measure():
        return jax.tree_util.tree_leaves(jax.jit(lambda p, h: (
            mixer.apply({"params": p}, h), jax.grad(
                lambda p, h: (mixer.apply({"params": p}, h) * ct).sum(),
                argnums=(0, 1))(p, h)))(p, h))

    view = measure()
    mp = pytest.MonkeyPatch()
    try:
        for module, plan in ((rotary, "rows_plan"),
                             (gated_delta, "gated_norm_plan")):
            mp.setattr(module, plan, lambda *a, **kw: ("direct", None))
        for part in ("normalised_heads", "gated_norm"):
            mp.setattr(gated_delta, part, functools.partial(
                getattr(gated_delta, part), interpret=True))
        rows = measure()
    finally:
        mp.undo()
    assert len(view) == len(rows) == len(MIXER_PARTS)
    return dict(zip(MIXER_PARTS, zip(view, rows)))


@pytest.mark.parametrize("part", MIXER_PARTS)
def test_the_mixer_with_both_norms_on_the_rows_is_the_mixer(part):
    view, rows = _mixer_on_rows()[part]
    assert view.shape == rows.shape and view.dtype == rows.dtype
    assert np.isfinite(np.asarray(rows)).all()
    # the interpreter's approximate reciprocal: 2^-17 of a sigmoid
    assert _rel(rows, view) < 1e-4


def test_no_norm_of_a_linear_block_sees_b_s_h_d(monkeypatch):
    """Forward + backward of one remat ``"linear_attention"`` block at heads
    of 128 x 128 as the chip traces it: both norms are ``pallas_call``
    equations on the rows (the forward, the remat's forward, the backward),
    and no operation at all has a ``(B, S, heads, 128)`` operand or
    result."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = _config(linear_key_head_dim=128, linear_value_head_dim=128,
                  linear_chunk_size=64, dtype=jnp.bfloat16)
    block = LlamaBlock(cfg, kind="linear_attention")
    B, s = 2, 256
    x = jax.ShapeDtypeStruct((B, s, cfg.hidden_size), cfg.dtype)
    pos = jax.ShapeDtypeStruct((B, s), jnp.int32)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    try:
        params = meta.unbox(jax.eval_shape(
            block.init, jax.random.PRNGKey(0), x, (pos, None))["params"])

        @jax.checkpoint
        def layer(p, x, pos):
            return block.apply({"params": p}, x, (pos, None))[0]

        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p, x, pos: (layer(p, x, pos).astype(jnp.float32) ** 2
                               ).mean(), argnums=(0, 1)))(params, x, pos)
    finally:
        mesh_lib.set_mesh(prev)
    heads = {cfg.linear_num_key_heads, cfg.linear_num_value_heads}
    seen, four_d = {}, set()
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            seen[name] = seen.get(name, 0) + 1
        for var in (*eqn.invars, *eqn.outvars):
            shape = getattr(var.aval, "shape", ())
            if len(shape) == 4 and shape[:2] == (B, s) \
                    and shape[2] in heads and shape[3] == 128:
                four_d.add(eqn.primitive.name)
    assert not four_d, four_d
    rows = {k: n for k, n in seen.items() if "rows" in k}
    assert rows == {"causal_conv_rows": 2, "causal_conv_rows_back": 1,
                    "qk_rows": 2, "qk_rows_back": 1, "gated_norm_rows": 2,
                    "gated_norm_rows_back": 1}, seen


@pytest.mark.parametrize("fault", [None, *reference.FAULTS])
def test_gated_attention_alone_against_each_named_fault(fault):
    cfg, _, (p, h, got) = _layers_alone()
    want = reference.attention(
        FULL_ATTENTION, p, h, n_head=4, n_kv_head=2, head_dim=16,
        rope_theta=100.0, partial_rotary_factor=0.25, eps=cfg.rms_norm_eps,
        fault=fault)
    err = _rel(got, want)
    if fault is None:
        assert err < 1e-4, err
        return
    assert err > 0.02, (fault, err)


@functools.lru_cache(maxsize=None)
def _expert_layer(R=ROUTED, k=TOP_K):
    M, I = 32, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    full = dataclasses.replace(_moe(), num_experts=R, top_k=k)
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = _moved(compare.init(whole, x), scale=20.0)
    return x, full, whole, p, compare.apply(whole, p, x)[0]


@pytest.mark.parametrize("fault", [None, *reference.EXPERT_FAULTS])
def test_the_expert_layer_alone_against_each_named_fault(fault):
    x, full, whole, p, got = _expert_layer()
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
    x, full, whole, p, got = _expert_layer()
    uncut = reference.expert_ffn(p, x, top_k=TOP_K, first_expert=0)
    np.testing.assert_allclose(got, uncut, atol=5e-5)
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


