"""Qwen3-Next as a ``LlamaConfig`` (PR 48) against ``benchmark/reference/
qwen3next.py`` on seeded weights at a small size, **under norm weights,
``A_log`` and ``dt_bias`` moved off their initial values** (at ``w = 0`` a
zero-centred norm and a plain one from ones are one function): logits, loss
and every gradient over a (linear, full) pair of layers (the cell's rehearsal
runs the whole period of four);
what is refused; an older cell's block traced as the parent traced it.
``test_qwen3next_layers.py`` holds each mechanism alone against its named
faults, the share test and the row kernels at top-10;
``test_qwen3next_engine.py`` the engine training it under ZeRO-3.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import (FULL_ATTENTION, LINEAR, SLIDING,
                                        LlamaBlock, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.parallel.moe import MoEConfig

from . import reference_compare as compare

reference = load_module(ROOT, "reference", "qwen3next")

KINDS = [LINEAR, FULL_ATTENTION] * 2
S, VOCAB, ROUTED, TOP_K = 32, 160, 16, 3


def _moe(first=0, held=ROUTED, **kw):
    return MoEConfig(**{**dict(
        num_experts=held, top_k=TOP_K, drop_tokens=False, expert_act="swiglu",
        norm_topk_prob=True, aux_loss_weight=0.1, num_shared_experts=1,
        shared_expert_gate=True,
        routed_experts=None if held == ROUTED else ROUTED,
        first_expert=first), **kw})


def _config(first=0, held=ROUTED, **kw):
    base = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=40, moe_intermediate_size=24,
                max_position_embeddings=S, rms_norm_eps=1e-6,
                layer_types=KINDS, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=8, linear_conv_kernel_dim=4,
                linear_chunk_size=8, partial_rotary_factor=0.25,
                norm_zero_centered=True, rope_theta=100.0,
                moe=_moe(first, held), qk_norm="head", attn_gate=True,
                scan_layers=False, dtype=jnp.float32, attn_impl="jnp",
                vocab_pad_multiple=32)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                vocab_size=cfg.vocab_size, top_k=TOP_K, layer_types=KINDS,
                n_k_heads=cfg.linear_num_key_heads,
                n_v_heads=cfg.linear_num_value_heads, rope_theta=100.0,
                partial_rotary_factor=cfg.partial_rotary_factor,
                eps=cfg.rms_norm_eps, first_expert=cfg.moe.first_expert,
                aux_loss_weight=cfg.moe.aux_loss_weight)


def _moved(tree, seed=7, scale=6.0):
    """Matrices scaled up (attention not near-uniform, routing no near-
    ties); every 1-D leaf moved off its initial value."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim >= 2
        else a + jnp.asarray(rng.normal(0, 0.3, a.shape), a.dtype), tree)


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, S)),
                       jnp.int32)


# ----------------------------------------------------------------------
# model against reference
# ----------------------------------------------------------------------
def test_logits_loss_and_every_gradient_match_the_reference(ids):
    cfg = _config(4, 4)
    model = LlamaForCausalLM(cfg)
    fresh = compare.init(model, ids)
    # w from zeros under norm_zero_centered; the DeltaNet layer's gated norm
    # from ones whatever the flag says; A = exp(A_log) in (0, 16]
    for leaf in (fresh["norm"]["scale"],
                 fresh["layers_0"]["input_norm"]["scale"],
                 fresh["layers_1"]["self_attn"]["q_norm"]["scale"]):
        assert not np.any(np.asarray(leaf))
    lin = fresh["layers_0"]["linear_attn"]
    assert (np.asarray(lin["o_norm"]) == 1).all()
    assert (np.asarray(lin["dt_bias"]) == 1).all()
    a = np.exp(np.asarray(lin["A_log"]))
    assert ((a > 0) & (a <= 16)).all()
    params = _moved(fresh)
    assert set(params["layers_0"]["linear_attn"]) == {
        "in_proj_qkvz_kernel", "in_proj_ba_kernel", "conv_kernel", "A_log",
        "dt_bias", "o_norm", "out_proj_kernel"}
    assert "self_attn" in params["layers_1"] and "linear_attn" not in \
        params["layers_1"] and "self_attn" not in params["layers_0"]
    assert params["layers_1"]["self_attn"]["gate_proj_kernel"].shape == \
        (32, 64)
    assert params["layers_0"]["moe"]["shared"]["token_gate"].shape == (32,)
    kw = _reference_kwargs(cfg)
    out, got = compare.forward_and_gradients(
        lambda p: model.apply({"params": p}, ids, labels=ids), params)
    np.testing.assert_allclose(out["logits"][..., :VOCAB],
                               reference.logits(params, ids, **{
                                   k: v for k, v in kw.items()
                                   if k != "aux_loss_weight"})[..., :VOCAB],
                               atol=2e-4)
    np.testing.assert_allclose(out["loss"],
                               reference.training_loss(params, ids, **kw),
                               rtol=1e-5)
    assert float(out["aux_loss"]) > 0
    # the reference's side bare: op by op its lines are the cheaper
    ref = jax.grad(lambda p: reference.training_loss(p, ids, **kw))(params)
    compare.compare_leaves(got, ref, tol=5e-4, measure="max")


# ----------------------------------------------------------------------
# what is not written raises by name
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw,error,said", [
    (dict(decode=True), NotImplementedError,
     "decode=True with a linear_attention layer"),
    (dict(diffusion={"block_length": 4, "mask_token_id": 1}),
     NotImplementedError,
     "block-diffusion training\\) with a linear_attention layer"),
    # latent attention beside linear-state layers runs since PR 58; with
    # the family's gate a channel it does not
    (dict(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8, qk_norm=False,
          partial_rotary_factor=1.0),
     NotImplementedError, "a gate a channel \\(attn_gate=True"),
    (dict(scan_layers=True), NotImplementedError,
     "scan_layers=True with a linear_attention layer"),
    (dict(linear_num_value_heads=3), ValueError,
     "linear_num_value_heads 3 is no multiple of linear_num_key_heads 2"),
    (dict(linear_key_head_dim=0), ValueError, "at least one channel a head"),
    (dict(linear_conv_kernel_dim=0), ValueError, "at least one tap"),
    (dict(partial_rotary_factor=0.0), ValueError, "a share in \\(0, 1\\]"),
    (dict(partial_rotary_factor=0.5, rope_interleave=True,
          layer_types=[FULL_ATTENTION] * 2), NotImplementedError,
     "partial_rotary_factor with rope_interleave"),
    (dict(layer_types=["linear_attention", "mamba"] * 2), ValueError,
     "'linear_attention' are written"),
    (dict(moe=dict(shared_expert_gate=True, num_shared_experts=0)),
     ValueError, "shared_expert_gate without a shared expert"),
])
def test_what_is_not_written_raises_by_name(kw, error, said):
    with pytest.raises(error, match=said):
        if isinstance(kw.get("moe"), dict):
            _moe(**kw["moe"])
        _config(**kw)


def test_the_new_fields_at_their_defaults_trace_nothing_new():
    """A block of an older cell's kind (Trinity's: sliding window, per-head
    norm, output gate, sandwich norms, sigmoid routing under a bias, a
    shared expert, a share) traces, forward and backward, to the jaxpr the
    parent commit traced (sha256 computed on cb6d753 with this function)."""
    moe = MoEConfig(num_experts=4, top_k=2, drop_tokens=False,
                    expert_act="swiglu", norm_topk_prob=True,
                    score_func="sigmoid", bias_update_rate=0.01,
                    num_shared_experts=1, routed_experts=8, first_expert=4,
                    aux_loss_weight=0.0)
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=48, moe_intermediate_size=24,
        max_position_embeddings=32, layer_types=[SLIDING, FULL_ATTENTION],
        sliding_window=8, moe=moe, qk_norm="head", attn_gate=True,
        rope_layer_types=[SLIDING], sandwich_norm=True, scan_layers=False,
        attn_impl="jnp")
    mesh_lib.set_mesh(None)     # another test's mesh would shard the rows
    block = LlamaBlock(cfg, True, SLIDING)
    x = jnp.zeros((2, 16, 32), cfg.dtype)
    pos = jnp.arange(16)[None]
    params = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x, (pos, None)))

    def fn(p, x):
        return jax.grad(lambda p, x: block.apply(p, x, (pos, None))[
            0].astype(jnp.float32).sum())(p, x)

    text = str(jax.make_jaxpr(fn)(params, x))
    digest = hashlib.sha256(re.sub(r" at /\S+:\d+", "", text).encode()
                            ).hexdigest()
    assert digest == ("63aa7b19c30dc940ac1c29a8563f19d1956b3bae49f79b5ea073fc"
                      "66cf8fe53f")
    assert cfg.rotary_dim == cfg.head_dim and not cfg.norm_zero_centered


def test_flops_per_token_counts_the_deltanet_layers():
    cfg = _config()
    E, d, Hk, Hv = 32, 8, 2, 4
    conv_dim = (2 * Hk + Hv) * d
    linear = E * (conv_dim + Hv * d) + E * 2 * Hv + conv_dim * 4 + Hv * d * E
    attn = 3 * E * 64 + 2 * E * 32
    ffn = 3 * E * 24 * (TOP_K + 1) + E * ROUTED
    n = (2 * cfg.padded_vocab_size * E + (linear + 3 * Hv * d * d) + attn
         + 2 * ffn)
    want = 6.0 * n + 6 * 4 * 2 * 16 * S         # one attention layer's keys
    assert LlamaForCausalLM(cfg).flops_per_token() == pytest.approx(want)
