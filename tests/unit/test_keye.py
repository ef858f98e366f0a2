"""Keye-VL-2.0's language model (a learned sparse selection on every
attention layer, PR 54) as a ``LlamaConfig`` against
``benchmark/reference/keye.py`` on seeded weights at a small size: the
three parts of the loss and the gradient of every leaf; the two
stop-gradients (the indexer's leaves learn from the indexer's loss alone,
every other leaf from the rest); each named fault of the reference moving
what it should; the statistics and gauges; the eight shares of an expert
layer adding up to the uncut layer; what is refused, by name; and the
older configurations tracing as before.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                        SparseAttentionConfig)
from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer
from deepspeed_tpu.telemetry import get_registry

from . import reference_compare as compare

reference = load_module(ROOT, "reference", "keye")

S, VOCAB, ROUTED, HELD, TOP_K, EPS, TOPK = 64, 500, 8, 4, 2, 1e-6, 12
THETA, AUX, NI, DI = 1e7, 0.01, 4, 16
SA = {"indexer_head_dim": DI, "indexer_num_heads": NI,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
      "topk": TOPK}


def _config(**kw):
    moe = MoEConfig(num_experts=HELD, top_k=TOP_K, drop_tokens=False,
                    norm_topk_prob=True, expert_act="swiglu",
                    aux_loss_weight=AUX, routed_experts=ROUTED, first_expert=2)
    base = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                intermediate_size=999, moe_intermediate_size=32,
                max_position_embeddings=S, rms_norm_eps=EPS, rope_theta=THETA,
                qk_norm="head", moe=moe, scan_layers=False,
                dtype=jnp.float32, attn_impl="jnp", vocab_pad_multiple=128,
                sa_config=dict(SA))
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                n_index_head=NI, topk=TOPK, vocab_size=cfg.vocab_size,
                top_k=TOP_K, rope_theta=THETA, eps=EPS, routed_experts=ROUTED,
                first_expert=cfg.moe.first_expert, aux_loss_weight=AUX,
                indexer_loss_weight=cfg.indexer_loss_weight)


@pytest.fixture(scope="module")
def setup():
    mesh_lib.set_mesh(None)
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, VOCAB, (2, S)), jnp.int32)
    params = compare.init(model, ids, labels=ids)
    # norms away from 1 and matrices large enough that every part shows
    params = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(3), x.shape),
        params)
    return cfg, model, params, ids


@functools.partial(jax.jit, static_argnums=0)
def _parts(model, params, ids):
    out = model.apply({"params": params}, ids, labels=ids)
    return out["loss"], out["aux_loss"], out["indexer_loss"]


@pytest.fixture(scope="module")
def losses(setup):
    cfg, model, params, ids = setup
    kw = _reference_kwargs(cfg)
    want, g_want = jax.jit(lambda p: reference.loss_and_grads(
        p, ids, **kw))(params)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: _parts(model, p, ids)[0]))(params)
    return want, g_want, got, g_got, kw


def test_the_three_parts_of_the_loss_match_the_reference(setup, losses):
    cfg, model, params, ids = setup
    want, _, got, _, kw = losses
    ce, aux, idx = reference.loss_parts(params, ids, **kw)
    loss, p_aux, p_idx = _parts(model, params, ids)
    assert abs(float(got) - float(want)) < 2e-5
    assert abs(float(p_aux) - float(aux)) < 1e-6
    assert abs(float(p_idx) - float(idx)) < 2e-5
    assert abs(float(loss - p_aux - p_idx) - float(ce)) < 2e-5
    assert float(idx) > 0.05            # the indexer's loss is in the loss
    # the weight is on the SUM over the layers
    half = LlamaForCausalLM(_config(indexer_loss_weight=0.5))
    assert abs(float(_parts(half, params, ids)[0])
               - float(loss - 0.5 * p_idx)) < 2e-5


LEAVES = sorted(
    jax.tree_util.keystr(path) for path, _ in
    jax.tree_util.tree_flatten_with_path(jax.eval_shape(
        lambda: meta.unbox(LlamaForCausalLM(_config()).init(
            jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32))["params"])
    ))[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_gradient_of_a_leaf_matches_the_reference(losses, leaf):
    _, g_want, _, g_got, _ = losses
    want = dict((jax.tree_util.keystr(p), x) for p, x in
                jax.tree_util.tree_flatten_with_path(g_want)[0])[leaf]
    got = dict((jax.tree_util.keystr(p), x) for p, x in
               jax.tree_util.tree_flatten_with_path(g_got)[0])[leaf]
    compare.compare_leaves({leaf: got}, {leaf: want}, tol=2e-4,
                           measure="norm")


def test_the_two_stop_gradients(setup):
    """The indexer's leaves get nothing from the cross-entropy and the
    router losses; every other leaf gets nothing from the indexer's loss."""
    cfg, model, params, ids = setup

    def is_indexer(path):
        return "indexer" in jax.tree_util.keystr(path)

    from_rest = jax.jit(jax.grad(lambda p: (
        lambda l, a, i: l - cfg.indexer_loss_weight * i)(
            *_parts(model, p, ids))))(params)
    from_indexer = jax.jit(jax.grad(
        lambda p: _parts(model, p, ids)[2]))(params)
    seen = 0
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(from_rest)[0],
            jax.tree_util.tree_leaves(from_indexer)):
        mine, other = (b, a) if is_indexer(path) else (a, b)
        assert float(jnp.abs(other).max()) == 0.0, path
        assert float(jnp.abs(mine).max()) > 0.0, path
        seen += is_indexer(path)
    assert seen == 5 * cfg.num_hidden_layers


MOVES = {"dense": "ce", "kv_mod": "ce", "noncausal_topk": "ce",
         "half_topk": "ce", "no_relu": "indexer", "no_w": "indexer",
         "no_key_norm": "indexer", "no_indexer_rope": "indexer",
         "loss_all_causal": "indexer"}


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_named_fault_of_the_reference_moves_its_part(setup, losses, fault):
    cfg, model, params, ids = setup
    kw = losses[4]
    ce, _, idx = (float(x) for x in reference.loss_parts(params, ids, **kw))
    f_ce, _, f_idx = (float(x) for x in reference.loss_parts(
        params, ids, **kw, fault=fault))
    moved = abs(f_ce - ce) if MOVES[fault] == "ce" else abs(f_idx - idx)
    assert moved > 1e-4, (fault, f_ce - ce, f_idx - idx)
    if fault == "loss_all_causal":      # the selection stands: CE as it was
        assert f_ce == ce


def test_float8_operands_move_every_part(setup, losses):
    cfg, model, params, ids = setup
    kw = losses[4]
    sound = reference.loss_parts(params, ids, **kw)
    fp8 = reference.loss_parts(params, ids, **kw, operand_bits=(4, 3))
    assert abs(float(fp8[0]) - float(sound[0])) > 1e-3
    assert abs(float(fp8[2]) - float(sound[2])) > 1e-4


def test_the_statistics_reach_the_registry(setup):
    cfg, model, params, ids = setup
    out = compare.apply(model, params, ids, labels=ids)
    stats = jax.tree_util.tree_map(np.asarray, out["stats"])
    assert float(stats["indexer_loss"]) == float(out["indexer_loss"])
    kept = sum(min(t + 1, TOPK) for t in range(S)) / (S * (S + 1) / 2)
    assert abs(float(stats["indexer_kept_share"]) - kept) < 1e-6
    assert float(stats["indexer_live_tile_share"]) == 1.0
    model.record_step_stats(stats)
    snap = get_registry().snapshot()
    for name, want in (("indexer_loss", stats["indexer_loss"]),
                       ("sparse_attention_kept_share", kept),
                       ("sparse_attention_live_tile_share", 1.0)):
        assert abs(snap[name]["samples"][-1]["value"] - float(want)) < 1e-6


def test_a_scanned_stack_is_the_unrolled_one(setup):
    cfg, model, params, ids = setup
    scanned = LlamaForCausalLM(_config(scan_layers=True))
    stacked = {k: v for k, v in params.items() if not k.startswith("layers_")}
    stacked["layers"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *(params[f"layers_{i}"] for i in range(cfg.num_hidden_layers)))
    want = _parts(model, params, ids)
    got = _parts(scanned, stacked, ids)
    for a, b in zip(got, want):
        assert abs(float(a) - float(b)) < 1e-5


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One chip's share is a term of the whole (model-configs guide,
    section 4): eight shares of two experts, router and renormalisation
    counted once, sum to the uncut reference's layer, and the program
    agrees with this reference on every share."""
    import dataclasses

    M, I, R, k = 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, M))
    full = MoEConfig(num_experts=R, top_k=k, drop_tokens=False,
                     norm_topk_prob=True, expert_act="swiglu")
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = compare.init(whole, x)
    p = {"gate": {"wg": p["gate"]["wg"] * 30},
         "experts": {n: w * 20 for n, w in p["experts"].items()}}
    uncut = reference.expert_ffn(p, x, top_k=k, first_expert=0)
    total = 0.0
    for first in range(0, R, 2):
        cfg = dataclasses.replace(full, num_experts=2, routed_experts=R,
                                  first_expert=first)
        mine = {"gate": p["gate"], "experts": {
            n: w[first:first + 2] for n, w in p["experts"].items()}}
        part = MoELayer(cfg, model_dim=M, hidden_dim=I,
                        dtype=jnp.float32).apply({"params": mine}, x)[0]
        np.testing.assert_allclose(
            part, reference.expert_ffn(mine, x, top_k=k, first_expert=first),
            atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_the_section_is_a_dict_or_a_dataclass():
    cfg = _config()
    assert cfg.sa_config == SparseAttentionConfig(
        indexer_head_dim=DI, indexer_num_heads=NI, topk=TOPK)
    assert hash(cfg) == hash(_config())
    assert _config(sa_config=SparseAttentionConfig()).sa_config.topk == 2048
    with pytest.raises(ValueError, match="one indexer key head"):
        _config(sa_config=dict(SA, indexer_num_kv_heads=2))


@pytest.mark.parametrize("what,kw", [
    ("indexer's keys are no leaf", dict(decode=True)),
    ("latent attention", dict(
        q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, qk_norm=False)),
    ("sliding window", dict(layer_types=["sliding_attention"] * 2,
                            sliding_window=8)),
    ("block-diffusion", dict(diffusion={"block_length": 4,
                                        "mask_token_id": 1})),
    ("sequence parallelism", dict(attn_impl="ring")),
    ("sequence parallelism", dict(attn_impl="ulysses")),
    ("conv or linear_attention", dict(layer_types=["conv",
                                                   "full_attention"])),
    ("multi-token-prediction", dict(num_nextn_predict_layers=1)),
])
def test_what_is_not_built_is_refused_by_name(what, kw):
    with pytest.raises(NotImplementedError, match=what):
        _config(**kw)


def test_the_scopes_are_emitted_where_the_section_is_set(setup):
    cfg, model, params, ids = setup

    def scopes(m, p):
        text = jax.jit(lambda p: m.apply({"params": p}, ids, labels=ids)[
            "loss"]).lower(p).as_text(debug_info=True)
        return {s for s in ("attn/indexer", "attn/indexer_loss")
                if s + "/" in text or s + '"' in text}

    assert scopes(model, params) == {"attn/indexer", "attn/indexer_loss"}
    plain_cfg = _config(sa_config=None)
    plain = LlamaForCausalLM(plain_cfg)
    p = compare.init(plain, ids)
    assert scopes(plain, p) == set()
    assert "indexer" not in p["layers_0"]["self_attn"]
    out = compare.apply(plain, p, ids, labels=ids)
    assert "indexer_loss" not in out and "indexer_loss" not in out["stats"]
