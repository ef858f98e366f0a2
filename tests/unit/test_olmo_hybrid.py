"""Olmo-Hybrid as a ``LlamaConfig`` (PR 52) against ``benchmark/reference/
olmo_hybrid.py`` on seeded weights at a small size: Gated DeltaNet states of
12 x 24 (key and value heads of different widths), ``beta = 2 sigmoid(b)``
with a share above 1.5, position-free attention under whole-projection q and
k norms, a dense SwiGLU in every block (``moe=None`` beside ``layer_types``)
and the reordered norm; logits, loss and every leaf kind's gradient over a
(linear, full) pair of layers (the cell's rehearsal runs the whole period of
four); each new field refused or honoured by name; the device scopes and the
``gated_delta_state_elems`` gauge.  One model, one set of weights and one
compiled forward a module.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.models.llama import (FULL_ATTENTION, LINEAR, GatedDeltaNet,
                                        LlamaAttention, LlamaBlock,
                                        LlamaConfig, LlamaForCausalLM)
from deepspeed_tpu.telemetry import get_registry

from . import reference_compare as compare
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "olmo_hybrid")

KINDS = [LINEAR, FULL_ATTENTION]
S, VOCAB, E, DK, DV = 32, 160, 32, 12, 24


def _config(**kw):
    base = dict(vocab_size=VOCAB, hidden_size=E, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, head_dim=8,
                intermediate_size=40, max_position_embeddings=S,
                rms_norm_eps=1e-6, layer_types=KINDS, linear_num_key_heads=2,
                linear_num_value_heads=2, linear_key_head_dim=DK,
                linear_value_head_dim=DV, linear_conv_kernel_dim=4,
                linear_allow_neg_eigval=True, linear_chunk_size=8,
                reordered_norm=True, qk_norm=True, rope_layer_types=(),
                scan_layers=False, dtype=jnp.float32, attn_impl="jnp",
                vocab_pad_multiple=32)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                vocab_size=cfg.vocab_size, layer_types=KINDS,
                n_k_heads=cfg.linear_num_key_heads,
                n_v_heads=cfg.linear_num_value_heads,
                key_dim=cfg.linear_key_head_dim, eps=cfg.rms_norm_eps)


def _linear_kwargs(cfg):
    return dict(n_k_heads=cfg.linear_num_key_heads,
                n_v_heads=cfg.linear_num_value_heads,
                key_dim=cfg.linear_key_head_dim, eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, S)),
                       jnp.int32)


@pytest.fixture(scope="module")
def built(ids):
    """``(cfg, model, fresh leaves, moved leaves)``: matrices scaled up (b
    spreads, so that beta fills (0, 2); attention not near-uniform), every
    1-D leaf moved off its initial value."""
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    fresh = compare.init(model, ids)
    rng = np.random.default_rng(7)
    moved = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2
        else a + jnp.asarray(rng.normal(0, 0.3, a.shape), a.dtype), fresh)
    # layer 0 reads the embedding itself (no norm before a branch): its b
    # stretched until beta = 2 sigmoid(b) fills (0, 2)
    lin = moved["layers_0"]["linear_attn"]
    lin["in_proj_ba_kernel"] = lin["in_proj_ba_kernel"].at[:, :2].multiply(20)
    # ... and decays under which a state lives for tens of positions (at
    # the initial A ~ U(0, 16) a head forgets within a token or two)
    lin["A_log"] = jnp.log(jnp.asarray([0.05, 0.3], jnp.float32))
    return cfg, model, fresh, moved


@pytest.fixture(scope="module")
def hidden(built, ids):
    """The reference forward's residual stream before each block and
    before each block's FFN."""
    cfg, _, _, params = built
    block_in, ffn_in = [], []
    reference.logits(params, ids, **_reference_kwargs(cfg),
                     block_inputs=block_in, ffn_inputs=ffn_in)
    return block_in, ffn_in


# ----------------------------------------------------------------------
# model against reference
# ----------------------------------------------------------------------
def test_the_leaves_are_the_released_ones_under_the_reordered_norm(built):
    cfg, _, fresh, _ = built
    lin, full = fresh["layers_0"], fresh["layers_1"]
    assert set(lin) == {"linear_attn", "post_attention_norm", "post_mlp_norm",
                        "gate_proj_kernel", "up_proj_kernel",
                        "down_proj_kernel"}          # no input_norm, no moe
    assert set(full) == (set(lin) - {"linear_attn"}) | {"self_attn"}
    assert {k: v.shape for k, v in lin["linear_attn"].items()} == {
        "in_proj_qkvz_kernel": (E, 2 * 2 * DK + 2 * 2 * DV),
        "in_proj_ba_kernel": (E, 4), "conv_kernel": (2 * 2 * DK + 2 * DV, 4),
        "A_log": (2,), "dt_bias": (2,), "o_norm": (DV,),
        "out_proj_kernel": (2 * DV, E)}
    attn = full["self_attn"]
    assert set(attn) == {"q_proj_kernel", "k_proj_kernel", "v_proj_kernel",
                         "o_proj_kernel", "q_norm", "k_norm"}    # no gate
    # ONE norm over the whole projection, a plain weight from ones
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape \
        == (4 * 8,)
    for leaf in (attn["q_norm"]["scale"], lin["post_mlp_norm"]["scale"],
                 fresh["norm"]["scale"], lin["linear_attn"]["o_norm"],
                 lin["linear_attn"]["dt_bias"]):
        assert (np.asarray(leaf) == 1).all()
    a = np.exp(np.asarray(lin["linear_attn"]["A_log"]))
    assert ((a > 0) & (a <= 16)).all()
    assert fresh["lm_head"].shape == (E, cfg.padded_vocab_size)   # untied


def test_logits_loss_and_every_leaf_kinds_gradient_match_the_reference(
        built, ids, hidden):
    cfg, model, _, params = built
    kw = _reference_kwargs(cfg)
    # beta above 1.5 is in the comparison
    b = np.asarray(hidden[0][0]) @ np.asarray(
        params["layers_0"]["linear_attn"]["in_proj_ba_kernel"])[:, :2]
    assert (2 / (1 + np.exp(-b)) > 1.5).mean() > 0.2
    forward = jax.jit(lambda p: dict(model.apply({"params": p}, ids,
                                                 labels=ids))).lower(
        params).compile()
    out = forward(params)
    np.testing.assert_allclose(
        out["logits"][..., :VOCAB],
        reference.logits(params, ids, **kw)[..., :VOCAB], atol=2e-4)
    np.testing.assert_allclose(out["loss"],
                               reference.training_loss(params, ids, **kw),
                               rtol=1e-5)
    assert "aux_loss" not in out           # cross-entropy alone
    # the device scopes of what this model runs
    text = forward.as_text()
    for scope in ("mlp_dense", "attn/qk_norm", "linear_attn/delta_rule",
                  "linear_attn/conv", "linear_attn/gated_norm",
                  "self_attn_full", "loss_head"):
        assert scope in text, scope
    assert "rope" not in text
    # ... and which shape of state the traced rule ran
    family = get_registry().snapshot()["gated_delta_state_elems"]
    assert {(s["labels"]["dk"], s["labels"]["dv"]): s["value"]
            for s in family["samples"]}[(str(DK), str(DV))] == DK * DV
    got = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, ids, labels=ids)["loss"]))(params)
    # the reference's side bare: op by op its lines are the cheaper
    ref = jax.grad(lambda p: reference.training_loss(p, ids, **kw))(params)
    paths, _ = compare.compare_leaves(got, ref, tol=5e-4, measure="max")
    assert len(paths) == len(jax.tree_util.tree_leaves(params))


# ----------------------------------------------------------------------
# each mechanism alone against its named faults
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _mixer(cfg):
    return jax.jit(lambda p, h: GatedDeltaNet(cfg).apply({"params": p}, h))


@pytest.mark.parametrize("fault", reference.LINEAR_FAULTS)
def test_the_deltanet_mixer_is_the_reference_and_no_named_fault(
        built, hidden, fault):
    """On two rows of 128 positions (the named chunk is 64: a state that
    resets at its edge or leaks into the next row has somewhere to show)."""
    cfg, _, _, params = built
    p = params["layers_0"]["linear_attn"]
    h = jnp.asarray(np.random.default_rng(5).normal(0, 0.1, (2, 128, E)),
                    jnp.float32)
    got = _mixer(cfg)(p, h)
    want = reference.linear_attention(p, h, **_linear_kwargs(cfg))
    assert _rel(got, want) < 2e-5
    wrong = reference.linear_attention(p, h, **_linear_kwargs(cfg),
                                       fault=fault)
    assert _rel(got, wrong) > (5e-4 if fault == "state_bf16" else 2e-3), \
        fault


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_attention_is_position_free_under_whole_projection_norms(
        built, hidden, fault):
    cfg, _, _, params = built
    p, h = params["layers_1"]["self_attn"], hidden[0][1]
    pos = jnp.arange(S)[None]
    module = LlamaAttention(cfg, FULL_ATTENTION)
    run = jax.jit(lambda p, h, pos: module.apply({"params": p}, h, pos, None))
    got = run(p, h, pos)
    kw = dict(n_head=4, n_kv_head=4, head_dim=8, eps=cfg.rms_norm_eps)
    assert _rel(got, reference.attention(FULL_ATTENTION, p, h, **kw)) < 2e-5
    assert _rel(got, reference.attention(FULL_ATTENTION, p, h, **kw,
                                         fault=fault)) > 2e-3, fault
    # no position reaches it: other position ids change nothing
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(run(p, h, pos + 5)))


@pytest.mark.parametrize("kind,layer", [(LINEAR, 0), (FULL_ATTENTION, 1)])
def test_a_block_normalises_after_each_branch_and_not_before(built, hidden,
                                                             kind, layer):
    cfg, _, _, params = built
    p, x = params[f"layers_{layer}"], hidden[0][layer]
    pos = jnp.arange(S)[None]
    got = jax.jit(lambda p, x: LlamaBlock(cfg, kind=kind).apply(
        {"params": p}, x, (pos, None))[0])(p, x)
    kw = dict(kind=kind, **_linear_kwargs(cfg), n_head=4, n_kv_head=4,
              head_dim=8)
    assert _rel(got, reference.block(p, x, **kw)) < 2e-5
    assert _rel(got, reference.block(p, x, **kw, fault="pre_norm")) > 1e-2
    np.testing.assert_allclose(
        reference.dense_ffn(p, hidden[1][layer]),
        jnp.dot(jax.nn.silu(hidden[1][layer] @ p["gate_proj_kernel"])
                * (hidden[1][layer] @ p["up_proj_kernel"]),
                p["down_proj_kernel"]), rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------
# each new field refused or honoured by name
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw,error,said", [
    (dict(sandwich_norm=True), ValueError,
     "reordered_norm .* beside sandwich_norm"),
    (dict(decode=True), NotImplementedError, "decode=True with reordered_norm"),
    (dict(decode=True, reordered_norm=False), NotImplementedError,
     "decode=True with a linear_attention layer"),
    (dict(diffusion={"block_length": 4, "mask_token_id": 1}),
     NotImplementedError,
     "block-diffusion training\\) with a linear_attention layer"),
    # latent attention beside linear-state layers runs since PR 58; with
    # the family's rope_layer_types it does not
    (dict(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8, qk_norm=False),
     NotImplementedError, "latent attention with qk_norm, a gate a channel"),
    (dict(scan_layers=True), NotImplementedError,
     "scan_layers=True with a linear_attention layer"),
    (dict(linear_value_head_dim=0), ValueError, "at least one channel a head"),
    (dict(num_dense_layers=1), ValueError, "there is no moe"),
])
def test_what_is_not_written_raises_by_name(kw, error, said):
    with pytest.raises(error, match=said):
        _config(**kw)


@pytest.mark.parametrize("field,value", [
    ("linear_allow_neg_eigval", False), ("reordered_norm", False),
    ("rope_layer_types", None), ("qk_norm", "head")])
def test_each_new_field_is_honoured(built, ids, field, value):
    """The source's keys and the program's fields for what it has no key
    for each change the function: another value gives other logits (and,
    where it names other leaves, says so)."""
    cfg, model, _, params = built
    other = LlamaForCausalLM(_config(**{field: value}))
    if field in ("reordered_norm", "qk_norm"):      # other leaves
        shapes = jax.eval_shape(other.init, jax.random.PRNGKey(0), ids)
        names = {jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
        if field == "reordered_norm":
            assert any("input_norm" in n for n in names)
            assert not any("post_mlp_norm" in n for n in names)
        else:
            assert meta.unbox(shapes)["params"]["layers_1"]["self_attn"][
                "q_norm"]["scale"].shape == (8,)
        return
    base = model.apply({"params": params}, ids)["logits"]
    got = other.apply({"params": params}, ids)["logits"]
    assert _rel(got, base) > 1e-3


def test_flops_per_token_counts_unequal_key_and_value_widths():
    cfg = _config()
    Hk = Hv = 2
    conv_dim = 2 * Hk * DK + Hv * DV
    linear = (E * (conv_dim + Hv * DV) + E * 2 * Hv + conv_dim * 4
              + Hv * DV * E)
    attn = 2 * E * 32 + 2 * E * 32
    ffn = 3 * E * 40
    n = (2 * cfg.padded_vocab_size * E + (linear + 3 * Hv * DK * DV) + attn
         + 2 * ffn)
    want = 6.0 * n + 6 * 4 * 2 * 8 * S          # one attention layer's keys
    assert LlamaForCausalLM(cfg).flops_per_token() == pytest.approx(want)
