"""LFM2-MoE as a ``LlamaConfig`` (PR 45) against ``benchmark/reference/
lfm2.py`` on seeded weights at a small size: logits, loss and every
gradient over a dense layer and a period of (conv, full_attention, conv,
conv) expert layers with a tied table; the conv mixer, the attention layer
and the expert layer alone against each named fault; the four shares of an
expert layer adding up to the uncut layer; the engine training it under
ZeRO-3 with the three new leaves sharded; what is refused.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import (CONV, FULL_ATTENTION, LlamaAttention,
                                        LlamaConfig, LlamaForCausalLM,
                                        ShortConv)
from deepspeed_tpu.parallel.moe import (STATE_LEAF, MoEConfig, MoELayer,
                                        topk_routing)

from . import reference_compare as compare
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "lfm2")

KINDS = [CONV, CONV, FULL_ATTENTION, CONV] * 2
S, VOCAB, ROUTED, TOP_K, RATE = 48, 160, 8, 4, 0.001
ROUTING = dict(score_func="sigmoid", norm_topk_prob=True, norm_topk_eps=1e-6,
               bias_update_rate=RATE)


def _moe(first=0, held=ROUTED, **kw):
    return MoEConfig(**{**dict(
        num_experts=held, top_k=TOP_K, drop_tokens=False, expert_act="swiglu",
        aux_loss_weight=0.0, routed_experts=None if held == ROUTED else ROUTED,
        first_expert=first), **ROUTING, **kw})


def _config(first=0, held=ROUTED, **kw):
    base = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=5,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=40, moe_intermediate_size=24,
                max_position_embeddings=S, rms_norm_eps=1e-5,
                layer_types=KINDS, conv_L_cache=3, tie_word_embeddings=True,
                rope_parameters={"rope_theta": 100.0, "rope_type": "default"},
                moe=_moe(first, held), num_dense_layers=1, qk_norm="head",
                scan_layers=False, dtype=jnp.float32, attn_impl="jnp",
                vocab_pad_multiple=32)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                vocab_size=cfg.vocab_size, top_k=TOP_K, layer_types=KINDS,
                num_dense_layers=cfg.num_dense_layers,
                route_scale=cfg.moe.route_scale, rope_theta=100.0,
                eps=cfg.rms_norm_eps, first_expert=cfg.moe.first_expert)


def _bias(layer, scale=0.2):
    return jnp.asarray(np.random.default_rng(layer).normal(0, scale, ROUTED),
                       jnp.float32)


def _params(model, ids, scale=6.0):
    """Seeded weights, scaled up so that attention is not near-uniform and
    the router's choices are not near-ties; a bias that is not zero."""
    params = compare.init(model, ids, scale=scale)
    for i in range(model.cfg.num_dense_layers, model.cfg.num_hidden_layers):
        params[f"layers_{i}"]["moe"]["gate"][STATE_LEAF] = _bias(i)
    return params


@pytest.fixture(scope="module")
def ids():
    # the upper half of the vocabulary never appears as an input
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB // 2,
                                                         (2, S)), jnp.int32)


# ----------------------------------------------------------------------
# model against reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("first,held", [(0, ROUTED), (2, 4)])
def test_logits_loss_and_every_gradient_match_the_reference(ids, first, held):
    cfg = _config(first, held)
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids)
    assert "lm_head" not in params                  # the head is the table
    assert set(params["layers_0"]["conv"]) == {
        "in_proj_kernel", "conv_kernel", "out_proj_kernel"}
    assert "self_attn" in params["layers_2"] and "conv" not in \
        params["layers_2"] and "self_attn" not in params["layers_1"]
    assert "moe" not in params["layers_0"] and "moe" in params["layers_1"]
    kw = _reference_kwargs(cfg)
    out, got = compare.forward_and_gradients(
        lambda p: model.apply({"params": p}, ids, labels=ids), params)
    counts = []
    want = reference.logits(params, ids, counts=counts, **kw)
    np.testing.assert_allclose(out["logits"][..., :VOCAB], want[..., :VOCAB],
                               atol=2e-4)
    np.testing.assert_array_equal(out["stats"]["tokens_per_expert"],
                                  np.stack(counts))
    assert float(out["aux_loss"]) == 0.0      # cross-entropy alone
    np.testing.assert_allclose(out["loss"],
                               reference.training_loss(params, ids, **kw),
                               rtol=1e-5)
    assert out["stats"]["tokens_per_expert"].shape == (4, ROUTED)
    # the reference's side bare: op by op its lines are the cheaper
    ref = jax.grad(lambda p: reference.training_loss(p, ids, **kw))(params)
    compare.compare_leaves(got, ref, tol=2e-4, measure="max",
                           no_gradient=(STATE_LEAF,))
    # the tied table's gradient is the sum of both uses: rows no input
    # ever embedded still move, through the head alone
    table = np.asarray(got["embed_tokens"])
    assert np.abs(table[VOCAB // 2:VOCAB]).max() > 0
    untied = LlamaForCausalLM(_config(first, held, tie_word_embeddings=False))
    p2 = dict(params, lm_head=params["embed_tokens"].T)
    g2 = jax.jit(jax.grad(lambda p: untied.apply(
        {"params": p}, ids, labels=ids)["loss"]))(p2)
    assert not np.any(np.asarray(g2["embed_tokens"])[VOCAB // 2:VOCAB])
    np.testing.assert_allclose(
        table, np.asarray(g2["embed_tokens"]) + np.asarray(g2["lm_head"]).T,
        atol=2e-6)


def test_the_chunked_head_and_bf16_follow(ids):
    cfg = _config(loss_chunk=16, dtype=jnp.bfloat16)
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids, scale=1.0)
    out = compare.apply(model, params, ids, labels=ids)
    assert "logits" not in out
    want = reference.training_loss(params, ids, **_reference_kwargs(cfg))
    assert abs(float(out["loss"]) - float(want)) < 0.03


# ----------------------------------------------------------------------
# each mechanism alone against its fault
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def layers_alone(ids):
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids)
    hidden = []
    reference.logits(params, ids, mixer_inputs=hidden,
                     **_reference_kwargs(cfg))
    attn = params["layers_2"]["self_attn"]      # scales away from 1, so
    rng = np.random.default_rng(2)              # that WHERE the norm runs shows
    for name in ("q_norm", "k_norm"):
        attn[name]["scale"] = jnp.asarray(rng.uniform(0.5, 3.0, 8),
                                          jnp.float32)
    # the program's side once for every fault: two conv mixers, the attention
    got = {layer: compare.apply(ShortConv(cfg), params[f"layers_{layer}"][
        "conv"], hidden[layer]) for layer in (0, 3)}
    got[2] = compare.apply(LlamaAttention(cfg, FULL_ATTENTION), attn,
                           hidden[2], jnp.arange(S)[None, :], None)
    return cfg, params, hidden, got


@pytest.mark.parametrize("fault", [None, *reference.CONV_FAULTS])
def test_the_conv_mixer_alone_against_each_named_fault(layers_alone, fault):
    cfg, params, hidden, got = layers_alone
    for layer in (0, 3):        # the dense block's and a sparse block's
        p, h = params[f"layers_{layer}"]["conv"], hidden[layer]
        assert h.shape == (2, S, 32)    # two rows: a leak between them shows
        err = _rel(got[layer], reference.short_conv(p, h, fault=fault))
        if fault is None:
            assert err < 1e-5, (layer, err)
        else:
            assert err > 1e-2, (fault, layer, err)
    if fault is None:           # and its backward, every leaf
        probe = jax.random.normal(jax.random.PRNGKey(5), h.shape)
        dh, dp = jax.jit(jax.grad(lambda h, p: (ShortConv(cfg).apply(
            {"params": p}, h) * probe).sum(), (0, 1)))(h, p)
        rh, rp = reference.short_conv_grads(p, h, probe)
        assert _rel(dh, rh) < 1e-5
        for leaf in p:
            assert _rel(dp[leaf], rp[leaf]) < 1e-5, leaf


@pytest.mark.parametrize("fault", [None, *reference.FAULTS])
def test_attention_alone_against_each_named_fault(layers_alone, fault):
    cfg, params, hidden, got = layers_alone
    p, h = params["layers_2"]["self_attn"], hidden[2]
    err = _rel(got[2], reference.attention(
        FULL_ATTENTION, p, h, n_head=4, n_kv_head=2, head_dim=8,
        rope_theta=100.0, eps=cfg.rms_norm_eps, fault=fault))
    assert (err < 1e-5) if fault is None else (err > 1e-2), (fault, err)


@pytest.mark.parametrize("fault", [None, *reference.EXPERT_FAULTS])
def test_the_expert_layer_alone_against_each_named_fault(fault):
    M, I = 32, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    layer = MoELayer(_moe(2, 4), model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = compare.init(layer, x)
    p = jax.tree_util.tree_map(lambda a: a * 20 if a.ndim >= 2 else a, p)
    p["gate"][STATE_LEAF] = _bias(7, 0.3)
    assert "shared" not in p                    # no shared expert
    got = layer.apply({"params": p}, x)[0]
    want = reference.expert_ffn(p, x, top_k=TOP_K, first_expert=2,
                                fault=fault)
    if fault is None:
        np.testing.assert_allclose(got, want, atol=2e-4)
    else:
        assert _rel(got, want) > 1e-2, fault


def test_the_routings_denominator_is_computed_and_not_argued_away():
    """``norm_topk_eps`` is in the sum the chosen scores are divided by:
    at scores this small it is most of it."""
    logits = jnp.asarray([[-14.0, -15.0, -30.0, -13.5]])
    s = jax.nn.sigmoid(logits)[0]
    w, e, *_ = topk_routing(logits, 2, True, score_func="sigmoid",
                            norm_eps=1e-6)
    assert e.tolist() == [[3, 0]]
    np.testing.assert_allclose(w[0], s[e[0]] / (s[e[0]].sum() + 1e-6),
                               rtol=1e-6)
    assert float(w.sum()) < 0.75
    w0, *_ = topk_routing(logits, 2, True, score_func="sigmoid")
    np.testing.assert_allclose(float(w0.sum()), 1.0, rtol=1e-6)
    assert "norm_topk_eps" in _moe().afmoe_fields


# ----------------------------------------------------------------------
# the shares add up
# ----------------------------------------------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """The parts that the four shares of 4 experts give are the uncut
    reference's 16-expert layer under a seeded bias that is not zero (there
    is no shared expert: nothing is computed alike on every share, so
    nothing is counted once); program and reference agree on every share;
    every pair is multiplied somewhere exactly once."""
    M, I, R, k = 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    full = dataclasses.replace(_moe(), num_experts=R, top_k=k)
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = compare.init(whole, x)
    p = jax.tree_util.tree_map(lambda a: a * 20 if a.ndim >= 2 else a, p)
    p["gate"][STATE_LEAF] = jnp.asarray(
        np.random.default_rng(3).normal(0, 0.3, R), jnp.float32)
    uncut = reference.expert_ffn(p, x, top_k=k, first_expert=0)
    np.testing.assert_allclose(whole.apply({"params": p}, x)[0], uncut,
                               atol=5e-5)
    total, multiplied = 0.0, 0
    for first in range(0, R, 4):
        cfg = dataclasses.replace(full, num_experts=4, routed_experts=R,
                                  first_expert=first)
        mine = dict(p, experts={n: w[first:first + 4]
                                for n, w in p["experts"].items()})
        part, _, stats = MoELayer(cfg, model_dim=M, hidden_dim=I,
                                  dtype=jnp.float32).apply(
            {"params": mine}, x, return_stats=True)
        np.testing.assert_allclose(
            part, reference.expert_ffn(mine, x, top_k=k, first_expert=first),
            atol=5e-5)
        assert int(stats["dropped"]) == 0
        held = int(stats["tokens_per_expert"][first:first + 4].sum())
        assert int(stats["elsewhere"]) == 128 * k - held
        total, multiplied = total + part, multiplied + held
    assert multiplied == 128 * k
    np.testing.assert_allclose(total, uncut, atol=1e-4)


# ----------------------------------------------------------------------
# the normal path
# ----------------------------------------------------------------------
def test_the_engine_trains_it_under_zero3_with_the_new_leaves_sharded():
    import deepspeed_tpu

    mesh_lib.set_mesh(None)
    cfg = _config(2, 4, dtype=jnp.bfloat16, loss_chunk=16, remat=True,
                  remat_policy="dots_saveable+flash", attn_impl="auto")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw8bit",
                          "params": {"lr": 1e-2, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 3}, "gradient_clipping": 1.0,
            "mesh": {"fsdp": -1}, "steps_per_print": 10**9})
    engine.init_params()
    n = engine.dp_world
    assert n == jax.device_count() > 1
    conv = engine.state.params["layers_1"]["conv"]
    for leaf, shape in (("in_proj_kernel", (32, 96)), ("conv_kernel", (32, 3)),
                        ("out_proj_kernel", (32, 32))):
        assert conv[leaf].shape == shape
        shard = conv[leaf].addressable_shards[0].data.shape
        assert int(np.prod(shard)) * n == int(np.prod(shape)), (leaf, shard)
    assert "lm_head" not in engine.state.params

    def batches():          # the same rows every step: something to learn
        ids = np.random.default_rng(0).integers(
            0, VOCAB, (engine.train_batch_size, S)).astype(np.int32)
        while True:
            yield {"input_ids": ids, "labels": ids}

    data = batches()
    before = np.asarray(conv["conv_kernel"])
    losses = [float(engine.train_batch(data_iter=data)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    after = engine.state.params["layers_1"]
    assert np.abs(np.asarray(after["conv"]["conv_kernel"]) - before).max() > 0
    moved = np.abs(np.asarray(after["moe"]["gate"][STATE_LEAF])).max()
    assert RATE * 0.99 <= moved <= 6 * RATE * 1.01  # by the rate, each step
    engine.drain_step_stats(wait=True)
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    assert any(s == "short_conv" and i == "shift" and n
               for s, i, _, n in dispatch_report())


# ----------------------------------------------------------------------
# what is not written raises by name
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw,error,said", [
    (dict(decode=True), NotImplementedError, "decode=True with a conv layer"),
    (dict(conv_bias=True), NotImplementedError, "conv_bias=True"),
    (dict(diffusion={"block_length": 4, "mask_token_id": 1}, moe=None,
          num_dense_layers=0), NotImplementedError,
     "block-diffusion training\\) with a conv layer"),
    # latent attention beside a conv layer runs since PR 58 (the layer
    # type picks the mixer); with the family's per-head norm it does not
    (dict(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8),
     NotImplementedError, "latent attention with qk_norm"),
    (dict(conv_L_cache=0), ValueError, "at least one tap"),
    (dict(layer_types=["conv", "mamba"] * 3), ValueError,
     "'conv' and 'linear_attention' are written"),
])
def test_what_is_not_written_raises_by_name(kw, error, said):
    with pytest.raises(error, match=said):
        _config(**kw)


def test_a_scanned_stack_cannot_carry_three_kinds_of_block(ids):
    model = LlamaForCausalLM(_config(scan_layers=True))
    with pytest.raises(NotImplementedError, match="scans one kind of block"):
        model.init(jax.random.PRNGKey(0), ids)
    # conv layers without any other field of the family are fine
    plain = LlamaConfig(vocab_size=64, hidden_size=16, num_hidden_layers=2,
                        num_attention_heads=2, intermediate_size=24,
                        layer_types=[CONV, FULL_ATTENTION],
                        scan_layers=False, max_position_embeddings=16)
    assert plain.kinds == (CONV, FULL_ATTENTION)
    assert plain.window(CONV) is None and plain.rotary(CONV) is None


def test_flops_per_token_counts_a_conv_layer_and_the_table_once():
    cfg = _config()
    E, L = 32, 5
    conv = 3 * E * E + E * E + E * 3
    attn = 2 * E * 32 + 2 * E * 16
    ffn = 3 * E * 24 * TOP_K + E * ROUTED
    n = (cfg.padded_vocab_size * E + 4 * conv + attn + 3 * E * 40 + 4 * ffn)
    want = 6.0 * n + 6 * 4 * 2 * 8 * S          # one attention layer's keys
    assert LlamaForCausalLM(cfg).flops_per_token() == pytest.approx(want)
    untied = LlamaForCausalLM(_config(tie_word_embeddings=False))
    assert untied.flops_per_token() - want == pytest.approx(
        6.0 * cfg.padded_vocab_size * E)
