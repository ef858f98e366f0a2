"""JoyAI-LLM-Flash (the DeepSeek-V3 family) as a ``LlamaConfig`` (PR 38)
against ``benchmark/reference/joyai.py`` on seeded weights at a small size
that keeps every ratio: the loss, its two parts and the gradient of every
leaf (the table and the head shared by both losses: one summed gradient);
one attention layer, one expert layer and the prediction block alone, each
``assumed`` item against its named fault; the shares of an expert layer
adding up to the uncut layer; the selection bias as a state leaf in the
prediction block too; what is refused; and the older cells' blocks
lowering to the parent's StableHLO.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                        LlamaLatentAttention, MTPModule)
from deepspeed_tpu.parallel.moe import STATE_LEAF, MoEConfig, MoELayer
from deepspeed_tpu.runtime import state_leaves

from . import reference_compare as compare
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "joyai")

S, VOCAB, ROUTED, HELD, TOP_K, RATE, EPS = 64, 160, 16, 4, 4, 0.001, 1e-6
MLA = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True)
ROUTING = dict(score_func="sigmoid", norm_topk_prob=True, route_scale=2.5,
               bias_update_rate=RATE, num_shared_experts=1)


def _moe(first=0, held=ROUTED):
    return MoEConfig(num_experts=held, top_k=TOP_K, drop_tokens=False,
                     expert_act="swiglu", aux_loss_weight=0.0,
                     routed_experts=None if held == ROUTED else ROUTED,
                     first_expert=first, **ROUTING)


def _config(first=0, held=ROUTED, **kw):
    base = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, head_dim=8, intermediate_size=112,
                moe_intermediate_size=24, max_position_embeddings=S,
                rms_norm_eps=EPS, rope_theta=32e6, moe=_moe(first, held),
                num_dense_layers=1, num_nextn_predict_layers=1,
                scan_layers=False, dtype=jnp.float32, attn_impl="jnp",
                vocab_pad_multiple=32, **MLA)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, vocab_size=cfg.vocab_size,
                top_k=TOP_K, num_dense_layers=cfg.num_dense_layers,
                route_scale=cfg.moe.route_scale, rope_theta=cfg.rope_theta,
                eps=cfg.rms_norm_eps, first_expert=cfg.moe.first_expert)


def _bias(layer, scale=0.2):
    return jnp.asarray(np.random.default_rng(layer).normal(0, scale, ROUTED),
                       jnp.float32)


def _gates(params):
    """``(row of the step's statistics, the gate's leaves)`` of every
    biased layer, the prediction block's last."""
    names = sorted(n for n in params if n.startswith("layers_")
                   and "moe" in params[n])
    out = [params[n]["moe"]["gate"] for n in names]
    if "mtp_0" in params:
        out.append(params["mtp_0"]["block"]["moe"]["gate"])
    return out


def _params(model, ids, scale=6.0):
    """Seeded weights, scaled up so that attention is not near-uniform and
    the router's choices are not near-ties; a bias that is not zero."""
    params = compare.init(model, ids, labels=ids, scale=scale)
    for i, gate in enumerate(_gates(params)):
        gate[STATE_LEAF] = _bias(i)
    return params


@pytest.fixture(scope="module")
def setup():
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, S)),
                      jnp.int32)
    return cfg, model, ids, _params(model, ids)


@pytest.fixture(scope="module")
def program(setup):
    """``(out, grads)``: the forward's outputs and the gradient of every
    trained leaf, one compiled program for the tests that read either."""
    _, model, ids, params = setup
    trained, held = state_leaves.split(params, model.is_state_leaf)
    return compare.forward_and_gradients(lambda p: model.apply(
        {"params": state_leaves.merge(p, held)}, ids, labels=ids), trained)


def test_loss_and_both_parts_match_the_reference(setup, program):
    cfg, model, ids, params = setup
    out = program[0]
    main, second = reference.loss_parts(params, ids, **_reference_kwargs(cfg))
    assert abs(float(out["lm_loss"]) - float(main)) < 2e-5
    assert abs(float(out["mtp_loss"]) - float(second)) < 2e-5
    want = reference.training_loss(params, ids, mtp_weight=0.3,
                                   **_reference_kwargs(cfg))
    assert abs(float(out["loss"]) - float(want)) < 2e-5
    assert float(out["mtp_loss"]) != pytest.approx(float(out["lm_loss"]),
                                                   abs=1e-3)
    # both leave the step with the routing statistics
    assert set(out["stats"]) >= {"lm_loss", "mtp_loss", "tokens_per_expert"}
    # two sparse layers and the prediction block's, in that order
    assert out["stats"]["tokens_per_expert"].shape == (3, ROUTED)


def test_chunked_head_gives_the_same_two_losses(setup, program):
    cfg, _, ids, params = setup
    whole = program[0]
    chunked = compare.apply(LlamaForCausalLM(_config(loss_chunk=32)), params,
                            ids, labels=ids)
    for key in ("loss", "lm_loss", "mtp_loss"):
        assert float(chunked[key]) == pytest.approx(float(whole[key]),
                                                    abs=1e-5)
    assert "logits" not in chunked


def test_every_gradient_matches_the_reference(setup, program):
    """Every leaf, the table and the head among them: each is used by both
    losses and has ONE gradient, the sum."""
    cfg, model, ids, params = setup
    trained, held = state_leaves.split(params, model.is_state_leaf)
    # the reference's side bare: op by op its lines are the cheaper
    want = jax.grad(lambda p: reference.training_loss(
        state_leaves.merge(p, held), ids, mtp_weight=0.3,
        **_reference_kwargs(cfg)))(trained)
    paths, _ = compare.compare_leaves(program[1], want, tol=2e-3,
                                      measure="norm")
    assert len(paths) > 40
    # the head's gradient is not the main loss's alone
    alone = jax.jit(jax.grad(lambda p: model.apply(
        {"params": state_leaves.merge(p, held)}, ids,
        labels=ids)["lm_loss"]))(trained)
    for leaf in ("embed_tokens", "lm_head"):
        assert _rel(alone[leaf], want[leaf]) > 1e-2


def _attention_alone(cfg, p_attn, h):
    pos = jnp.arange(h.shape[1])[None, :]
    return compare.apply(LlamaLatentAttention(cfg), p_attn, h, pos, None)


@pytest.fixture(scope="module")
def attention_1(setup, hiddens):
    """The program's layer-1 attention, once for every fault it refuses."""
    return _attention_alone(setup[0], setup[3]["layers_1"]["self_attn"],
                            hiddens[1][1])


def _attn_ref(cfg, p_attn, h, fault=None):
    kw = _reference_kwargs(cfg)
    return reference.attention(
        h, p_attn, n_head=kw["n_head"], kv_lora_rank=kw["kv_lora_rank"],
        qk_nope_head_dim=kw["qk_nope_head_dim"],
        qk_rope_head_dim=kw["qk_rope_head_dim"], v_head_dim=kw["v_head_dim"],
        rope_theta=kw["rope_theta"], eps=EPS, fault=fault)


@pytest.fixture(scope="module")
def hiddens(setup):
    cfg, _, ids, params = setup
    attn_in, ffn_in = [], []
    h = reference.hidden(params, ids, attn_inputs=attn_in, ffn_inputs=ffn_in,
                         **_reference_kwargs(cfg))
    return h, attn_in, ffn_in


def test_one_attention_layer_alone_matches(setup, hiddens):
    cfg, _, _, params = setup
    for i in (0, 1):
        p = params[f"layers_{i}"]["self_attn"]
        assert _rel(_attention_alone(cfg, p, hiddens[1][i]),
                    _attn_ref(cfg, p, hiddens[1][i])) < 1e-5


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_attention_refuses_each_assumed_item_done_wrong(
        setup, hiddens, attention_1, fault):
    """Rotary on the nope channels, halves where pairs are meant on q or on
    k alone, the scale of the nope width, the rope key of the next
    position, a latent norm left out, bf16 accumulation."""
    cfg, _, _, params = setup
    p, h = params["layers_1"]["self_attn"], hiddens[1][1]
    err = _rel(attention_1, _attn_ref(cfg, p, h, fault))
    assert err > (1e-3 if fault == "bf16_accumulation" else 2e-2), err


def test_halves_on_both_q_and_k_is_the_same_attention(setup, hiddens):
    """What the file states under ``assumed``: the released code rotates
    halves of DE-INTERLEAVED channels; a permutation that q's and k's rope
    channels share changes no score.  Here: interleaved pairs equal halves
    after permuting the rope columns of both up-projections alike."""
    cfg, _, _, params = setup
    H, Dn, Dr = 4, 16, 8
    p = dict(params["layers_1"]["self_attn"])
    perm = np.concatenate([np.arange(0, Dr, 2), np.arange(1, Dr, 2)])
    qb = np.asarray(p["q_b_proj_kernel"]).copy()
    rope = qb[:, H * Dn:].reshape(-1, H, Dr)[:, :, perm]
    qb[:, H * Dn:] = rope.reshape(qb.shape[0], H * Dr)
    kva = np.asarray(p["kv_a_proj_with_mqa_kernel"]).copy()
    kva[:, 16:] = kva[:, 16:][:, perm]
    p["q_b_proj_kernel"], p["kv_a_proj_with_mqa_kernel"] = qb, kva
    halves = LlamaLatentAttention(_config(rope_interleave=False)).apply(
        {"params": p}, hiddens[1][1], jnp.arange(S)[None, :], None)
    pairs = _attention_alone(cfg, params["layers_1"]["self_attn"],
                             hiddens[1][1])
    assert _rel(halves, pairs) < 1e-5


def _expert_layer(cfg, p_moe, h):
    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    return layer.apply({"params": p_moe}, h)[0]


def test_one_expert_layer_alone_matches_and_refuses_its_faults(setup,
                                                               hiddens):
    cfg, _, _, params = setup
    p, h = params["layers_2"]["moe"], hiddens[2][2]
    got = _expert_layer(cfg, p, h)
    kw = dict(top_k=TOP_K, route_scale=2.5)
    assert _rel(got, reference.sparse_ffn(p, h, **kw)) < 1e-5
    for fault in reference.EXPERT_FAULTS:
        if fault != "held_denominator":     # a share's: the next test but two
            assert _rel(got, reference.sparse_ffn(p, h, fault=fault,
                                                  **kw)) > 1e-2, fault


def test_the_dense_ffn_alone_matches(setup, hiddens):
    cfg, model, ids, params = setup
    p, h = params["layers_0"], hiddens[2][0]
    want = reference.dense_ffn(p, h)
    got = jax.nn.silu(h @ p["gate_proj_kernel"]) * (h @ p["up_proj_kernel"]) \
        @ p["down_proj_kernel"]
    assert _rel(got, want) < 1e-5
    assert _rel(got, reference.dense_ffn(p, h, fault="gate_up_swapped")) > .1


def _mtp_nll(cfg, params, h, ids, table=None):
    """The program's prediction block alone, from ``h``: per-position
    negative log-likelihood of the token two ahead."""
    table = params["embed_tokens"] if table is None else table
    nxt = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
    x, _ = MTPModule(cfg).apply({"params": params["mtp_0"]}, h, table[nxt],
                                (jnp.arange(S)[None, :], None))
    logits = x @ params["lm_head"]
    logits = jnp.where(jnp.arange(logits.shape[-1]) < VOCAB, logits, -jnp.inf)
    logp = jax.nn.log_softmax(logits[:, :-2], -1)
    return -jnp.take_along_axis(logp, ids[:, 2:, None], -1)[..., 0]


def test_the_prediction_block_alone_matches_and_refuses_its_faults(setup,
                                                                   hiddens):
    """A label shift of one, a table or a head that is not the main
    model's, [h ; e] where [e ; h] is stated: each is refused."""
    cfg, _, ids, params = setup
    kw = _reference_kwargs(cfg)
    got = _mtp_nll(cfg, params, hiddens[0], ids)
    assert _rel(got, reference.mtp(hiddens[0], ids, params, **kw)) < 1e-5
    for fault in reference.MTP_FAULTS:
        err = _rel(got, reference.mtp(hiddens[0], ids, params, fault=fault,
                                      **kw))
        assert err > 2e-2, (fault, err)


def test_the_shares_add_up_to_the_uncut_layer(setup, hiddens):
    """Four shares of four experts each: their outputs, the shared expert
    counted once, are the uncut reference layer's."""
    cfg, _, _, params = setup
    p, h = params["layers_1"]["moe"], hiddens[2][1]
    whole = reference.sparse_ffn(p, h, top_k=TOP_K, route_scale=2.5)
    shared = (jax.nn.silu(h @ p["shared"]["gate"]) * (h @ p["shared"]["up"])
              ) @ p["shared"]["down"]
    total = 0.0
    for first in range(0, ROUTED, HELD):
        share = _config(first, HELD)
        cut = dict(p, experts={k: v[first:first + HELD]
                               for k, v in p["experts"].items()})
        out = _expert_layer(share, cut, h)
        assert _rel(out, reference.sparse_ffn(
            cut, h, top_k=TOP_K, route_scale=2.5, first_expert=first)) < 1e-5
        assert _rel(out, reference.sparse_ffn(
            cut, h, top_k=TOP_K, route_scale=2.5, first_expert=first,
            fault="held_denominator")) > 1e-2
        total = total + out - shared
    assert _rel(total + shared, whole) < 1e-5


def test_the_bias_is_a_state_leaf_in_the_prediction_block_too(setup):
    """No gradient, no moments, no decay, no part in the clipped norm - and
    the step moves every one of them by the reference's rule."""
    import deepspeed_tpu

    cfg = _config(loss_chunk=32)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(2).integers(0, VOCAB, (8, S)).astype(np.int32)
    mesh_lib.set_mesh(None)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1, "steps_per_print": 10**9,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3,
                                                  "weight_decay": 0.1}},
        "gradient_clipping": 1.0, "zero_optimization": {"stage": 3},
        "mesh": {"fsdp": -1}})
    engine.init_params()
    before = [np.asarray(g[STATE_LEAF]) for g in _gates(engine.state.params)]
    assert len(before) == 3 and all((b == 0).all() for b in before)
    opt_paths = {jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_leaves_with_path(engine.state.opt_state)}
    assert not any(STATE_LEAF in p for p in opt_paths)
    steps = []
    from deepspeed_tpu.parallel import moe as moe_lib

    booked = moe_lib.record_stats
    moe_lib.record_stats = lambda stats: (
        steps.append(np.asarray(stats["tokens_per_expert"])), booked(stats))
    try:
        engine.train_batch(batch={"input_ids": ids, "labels": ids})
        engine.drain_step_stats(wait=True)
    finally:
        moe_lib.record_stats = booked
    counts = steps[0].reshape(3, ROUTED)
    for row, gate in enumerate(_gates(engine.state.params)):
        want = reference.bias_update(counts[row], before[row], RATE)
        np.testing.assert_array_equal(np.asarray(gate[STATE_LEAF]), want)
        assert np.ptp(want) > 0
    from deepspeed_tpu.telemetry import get_registry

    snap = get_registry().snapshot()
    assert snap["mtp_loss"]["samples"][0]["labels"] == {"depth": "1"}
    assert snap["lm_loss"]["samples"][0]["value"] > 0
    layers = {s["labels"]["layer"] for s in
              snap["moe_tokens_per_expert"]["samples"]}
    assert {"0", "1", "2"} <= layers       # the block's own label, the last


@pytest.mark.parametrize("field", ["kv_lora_rank", "num_nextn_predict_layers"])
def test_decode_raises_naming_the_field(field):
    with pytest.raises(NotImplementedError, match=field):
        _config(decode=True)


def test_half_a_latent_attention_is_refused():
    with pytest.raises(ValueError, match="together"):
        LlamaConfig(kv_lora_rank=16)
    with pytest.raises(NotImplementedError, match="one multi-token"):
        _config(num_nextn_predict_layers=2)


# -- the older cells' programs stand ------------------------------------

# sha256 of the StableHLO of one block (forward + backward, bf16, the
# cell's own fields at small widths) of each of the four older
# configurations, taken at the parent commit (2de5075) with this file's
# ``_older_block``: a new field that leaks into an old path changes one
PARENT_BLOCKS = {
    "llama": "446f73a05c0a95091036d09bf190eabde132ae74257dacc64e3bcfd333037421",
    "olmoe": "a654ee6285f397f2a2e84ab1a91d88667f93dac1e4175523432e808f88af508f",
    "mellum2":
        "85a4f0042f3c91de153613b7f8800d9679e65f17a3096c837dfefc7d98c74022",
    "trinity":
        "bcd12cd8e29089eb2d9ec83ae8d5e75cf5ff1ba047a35ce374f37bb9cf459073",
}


def _older_block(name):
    """``(module text)`` of value_and_grad of one block of an older
    configuration's kind."""
    from deepspeed_tpu.models.llama import LlamaBlock

    mesh_lib.set_mesh(None)     # an engine of an earlier test leaves its own
    moe = dict(num_experts=4, top_k=2, drop_tokens=False,
               expert_act="swiglu")
    kinds = {
        "llama": dict(),
        "olmoe": dict(moe=MoEConfig(norm_topk_prob=False,
                                    aux_loss_weight=0.01, z_loss_weight=0.001,
                                    **moe), qk_norm=True),
        "mellum2": dict(moe=MoEConfig(routed_experts=8, first_expert=4,
                                      norm_topk_prob=True, **moe),
                        layer_types=["sliding_attention"], sliding_window=16,
                        moe_intermediate_size=24, num_key_value_heads=2,
                        rope_parameters={"sliding_attention": {
                            "rope_type": "default", "rope_theta": 1e4}}),
        "trinity": dict(moe=MoEConfig(routed_experts=8, first_expert=4,
                                      score_func="sigmoid",
                                      norm_topk_prob=True, route_scale=2.826,
                                      bias_update_rate=0.001,
                                      num_shared_experts=1,
                                      aux_loss_weight=0.0, **moe),
                        layer_types=["sliding_attention"], sliding_window=16,
                        moe_intermediate_size=24, num_key_value_heads=2,
                        qk_norm="head", attn_gate=True, sandwich_norm=True,
                        rope_layer_types=["sliding_attention"]),
    }
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=1,
                      num_attention_heads=4, head_dim=16,
                      intermediate_size=40, max_position_embeddings=32,
                      scan_layers=False, attn_impl="jnp", **kinds[name])
    block = LlamaBlock(cfg, True, *cfg.kinds[:1])
    x = jnp.ones((2, 32, 32), jnp.bfloat16)
    inputs = (jnp.arange(32)[None, :], None)
    params = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x, inputs))["params"]
    params = meta.unbox(params)

    def loss(p, x):
        return block.apply({"params": p}, x, inputs)[0].astype(
            jnp.float32).sum()

    return jax.jit(jax.value_and_grad(loss)).lower(params, x).as_text()


@pytest.mark.parametrize("name", ["llama", "olmoe", "mellum2", "trinity"])
def test_an_older_configurations_block_lowers_to_the_parents_program(name):
    """Their compile-cache keys and ``op_name``s stand, and none of the new
    scopes is emitted where the MLA / MTP fields are not set."""
    text = _older_block(name)
    for scope in ("mla", "mtp"):
        assert scope not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_BLOCKS[name]


def test_the_new_scopes_are_emitted_where_the_fields_are_set(setup):
    cfg, model, ids, params = setup
    text = jax.jit(lambda p: model.apply({"params": p}, ids, labels=ids)[
        "loss"]).lower(params).as_text(debug_info=True)
    for scope in ("attn/mla_q", "attn/mla_kv", "rope/mla", "self_attn_mla",
                  "mtp/embed_proj", "mtp/block", "mtp/loss_head"):
        assert scope in text, scope
