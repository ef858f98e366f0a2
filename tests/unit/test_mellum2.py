"""Mellum 2 as a ``LlamaConfig`` (PR 30) against ``benchmark/reference/
mellum2.py`` on seeded weights at a small size: loss and the gradient of
every kind of leaf over two periods of (sliding, sliding, sliding, full)
with the window shorter than the row; the YaRN table's constants; the four
shares of an expert layer adding up to the uncut layer; what is refused.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.models.llama import (FULL_ATTENTION, SLIDING, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.ops.grouped_matmul import TILES, _tiles
from deepspeed_tpu.ops.rotary import rotary_table
from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer, record_stats

from . import reference_compare as compare

reference = load_module(ROOT, "reference", "mellum2")

ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                       "original_max_position_embeddings": 8192,
                       "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
# a table whose ramp falls inside 8 channel pairs, so that a small model's
# full layers really interpolate
SMALL_ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 100.0, "factor": 4,
                       "original_max_position_embeddings": 16,
                       "beta_fast": 2, "beta_slow": 0.25},
    "sliding_attention": {"rope_type": "default", "rope_theta": 100.0}}
KINDS = [SLIDING, SLIDING, SLIDING, FULL_ATTENTION] * 2
S, WINDOW, VOCAB, ROUTED, TOP_K = 48, 12, 160, 8, 4


def _config(first=0, held=ROUTED, **kw):
    moe = MoEConfig(num_experts=held, top_k=TOP_K, drop_tokens=False,
                    norm_topk_prob=True, expert_act="swiglu",
                    aux_loss_weight=0.001,
                    routed_experts=None if held == ROUTED else ROUTED,
                    first_expert=first)
    base = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=8,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=999, moe_intermediate_size=24,
                max_position_embeddings=S, rms_norm_eps=1e-6,
                layer_types=KINDS, sliding_window=WINDOW,
                rope_parameters=SMALL_ROPE, moe=moe, scan_layers=False,
                dtype=jnp.float32, attn_impl="jnp", vocab_pad_multiple=32)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                vocab_size=cfg.vocab_size, top_k=TOP_K, norm_topk_prob=True,
                layer_types=KINDS, sliding_window=WINDOW,
                rope_parameters=SMALL_ROPE, eps=cfg.rms_norm_eps,
                first_expert=cfg.moe.first_expert)


def _params(model, ids, scale=6.0):
    """Seeded weights, scaled up so that attention is not near-uniform and
    the router's choices are not near-ties."""
    return compare.init(model, ids, scale=scale)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, VOCAB)


@pytest.mark.parametrize("first,held", [(0, ROUTED), (2, 2)])
def test_loss_and_every_leaf_kinds_gradient_match_the_reference(ids, first,
                                                                held):
    cfg = _config(first, held)
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids)
    assert params["layers_0"]["self_attn"]["q_proj_kernel"].shape == (32, 64)
    assert params["layers_0"]["self_attn"]["k_proj_kernel"].shape == (32, 32)
    assert params["layers_0"]["moe"]["experts"]["gate"].shape == (held, 32, 24)
    assert params["layers_0"]["moe"]["gate"]["wg"].shape == (32, ROUTED)

    out, grads = compare.forward_and_gradients(
        lambda p: model.apply({"params": p}, ids, labels=ids), params)
    # the reference's side bare: op by op its lines are the cheaper
    want, wants = jax.value_and_grad(lambda p: reference.training_loss(
        p, ids, **_reference_kwargs(cfg)))(params)
    # float32 on both sides: what is left is the order of the sums
    assert abs(float(out["loss"]) - float(want)) < 2e-5, (out["loss"], want)
    paths, _ = compare.compare_leaves(grads, wants, tol=2e-3, measure="norm")
    kinds = {jax.tree_util.keystr(path[-2:]) for path in paths}
    assert len(kinds) >= 11     # embed, head, 3 norms, q k v o, wg, 3 experts


def test_every_tile_kind_occurs_and_the_window_matters(ids):
    """Window 12 of 48 positions: a sliding layer drops keys, so the same
    weights without ``layer_types`` give another loss, and with the full
    layers on the default table another again."""
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids)
    loss = lambda c: float(compare.apply(LlamaForCausalLM(c), params, ids,
                                         labels=ids)["loss"])
    base = loss(cfg)
    no_window = dataclasses.replace(cfg, layer_types=None, rope_parameters=None,
                                    rope_theta=100.0)
    one_table = dataclasses.replace(
        cfg, rope_parameters=SMALL_ROPE["sliding_attention"])
    assert abs(loss(no_window) - base) > 1e-3
    assert abs(loss(one_table) - base) > 1e-4


def test_yarn_constants_of_the_published_table():
    t = rotary_table(128, **ROPE["full_attention"])
    inv = np.asarray(t.inv_freq)
    th = 500000.0
    assert t.scale == 1.2772588722239782 == 0.1 * np.log(16) + 1
    # low 18, high 35: below keeps theta^(-2m/128), above is slowed 16x
    np.testing.assert_allclose(inv[0], 1.0)
    np.testing.assert_allclose(inv[18], th ** (-36 / 128), rtol=1e-12)
    np.testing.assert_allclose(inv[35], th ** (-70 / 128) / 16, rtol=1e-12)
    np.testing.assert_allclose(inv[63], th ** (-126 / 128) / 16, rtol=1e-12)
    ramp = (19 - 18) / 17
    np.testing.assert_allclose(
        inv[19], th ** (-38 / 128) * ((1 - ramp) + ramp / 16), rtol=1e-12)
    c = lambda r: 128 * np.log(8192 / (2 * np.pi * r)) / (2 * np.log(th))
    assert (int(np.floor(c(32))), int(np.ceil(c(1)))) == (18, 35)
    # the reference computes its own, and agrees
    ref_inv, ref_scale = reference.rotary_table("full_attention", ROPE, 128)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-12)
    assert ref_scale == t.scale
    plain = rotary_table(128, **ROPE["sliding_attention"])
    np.testing.assert_allclose(plain.inv_freq[5], th ** (-10 / 128))
    assert plain.scale == 1.0
    # the attention factor a table leaves out is 0.1 ln(factor) + 1
    entry = {k: v for k, v in ROPE["full_attention"].items()
             if k != "attention_factor"}
    assert rotary_table(128, **entry).scale == pytest.approx(t.scale)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One chip's share is a term of the whole: the parts that the four
    shares of 16 experts give, router and renormalisation counted once,
    sum to the uncut reference's layer; program and reference agree on
    every share; every pair is multiplied somewhere exactly once."""
    M, I, R, k = 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    full = MoEConfig(num_experts=R, top_k=k, drop_tokens=False,
                     norm_topk_prob=True, expert_act="swiglu")
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = jax.tree_util.tree_map(
        lambda a: a.value if hasattr(a, "value") else a,
        jax.jit(whole.init)(jax.random.PRNGKey(0), x)["params"],
        is_leaf=lambda a: hasattr(a, "value"))
    p = {"gate": {"wg": p["gate"]["wg"] * 30},
         "experts": {n: w * 20 for n, w in p["experts"].items()}}
    uncut = reference.expert_ffn(p, x, top_k=k, first_expert=0)
    np.testing.assert_allclose(whole.apply({"params": p}, x)[0], uncut,
                               atol=2e-5)
    total, multiplied = 0.0, 0
    for first in range(0, R, 4):
        cfg = dataclasses.replace(full, num_experts=4, routed_experts=R,
                                  first_expert=first)
        mine = {"gate": p["gate"], "experts": {
            n: w[first:first + 4] for n, w in p["experts"].items()}}
        part, _, stats = MoELayer(cfg, model_dim=M, hidden_dim=I,
                                  dtype=jnp.float32).apply(
            {"params": mine}, x, return_stats=True)
        np.testing.assert_allclose(
            part, reference.expert_ffn(mine, x, top_k=k, first_expert=first),
            atol=2e-5)
        assert int(stats["dropped"]) == 0
        held = int(stats["tokens_per_expert"][first:first + 4].sum())
        assert int(stats["elsewhere"]) == 128 * k - held
        assert stats["tokens_per_expert"].shape == (R,)
        total, multiplied = total + part, multiplied + held
    assert multiplied == 128 * k
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_pairs_held_elsewhere_are_booked_apart_from_dropped_ones():
    """A share's buffer has a row for every pair: however the routing
    falls, nothing is dropped; what other shares hold is counted apart."""
    M, I, R, k, T = 16, 8, 8, 2, 1024
    cfg = MoEConfig(num_experts=2, routed_experts=R, first_expert=2, top_k=k,
                    drop_tokens=False, expert_act="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(2), (T, M))
    layer = MoELayer(cfg, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    _, _, stats = layer.apply(params, x, return_stats=True)
    held = int(stats["tokens_per_expert"][2:4].sum())
    assert 0 < held < T * k and int(stats["dropped"]) == 0
    assert int(stats["elsewhere"]) == T * k - held
    from deepspeed_tpu.telemetry import get_registry

    before = get_registry().snapshot().get("moe_pairs_elsewhere_total")
    before = before["samples"][0]["value"] if before else 0.0
    record_stats({name: np.asarray(v)[None] for name, v in stats.items()})
    after = get_registry().snapshot()["moe_pairs_elsewhere_total"]
    assert after["samples"][0]["value"] - before == T * k - held


def test_rows_the_grouped_matmul_skips_reach_no_token(monkeypatch):
    """The chip's grouped matmul writes no row past the last group, forward
    or backward (``ragged_dot`` on the CPU writes zeros there): whatever
    such rows hold, the share's output and gradients are what they are
    with zeros there."""
    from deepspeed_tpu.parallel import moe

    M, I, R, k, T = 16, 8, 8, 2, 256
    cfg = MoEConfig(num_experts=2, routed_experts=R, first_expert=4, top_k=k,
                    drop_tokens=False, norm_topk_prob=True,
                    expert_act="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(3), (T, M))
    layer = MoELayer(cfg, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

    def loss(params, x):
        out, aux = layer.apply(params, x, train=True)
        return (out ** 2).sum() + aux

    want = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
    clean = moe.grouped_matmul

    def fill(ahead, back):
        """Rows past the groups hold ``ahead`` going forward and their
        cotangent ``back`` going backward."""
        @jax.custom_vjp
        def f(rows, live):
            return jnp.where(live, rows, ahead)

        f.defvjp(lambda rows, live: (f(rows, live), live),
                 lambda live, g: (jnp.where(live, g, back), None))
        return f

    def skips_rows(lhs, rhs, sizes, **kw):
        live = (jnp.arange(lhs.shape[0]) < sizes.sum())[:, None]
        # reads no such row of lhs or of the output's cotangent; writes
        # none of the output or of lhs's cotangent
        return fill(jnp.nan, 0.0)(
            clean(fill(0.0, jnp.nan)(lhs, live), rhs, sizes, **kw), live)

    monkeypatch.setattr(moe, "grouped_matmul", skips_rows)
    # another function object: what the first call compiled is not reused
    got = jax.jit(jax.grad(lambda params, x: loss(params, x),
                           argnums=(0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_what_is_not_written_raises_by_name(ids):
    with pytest.raises(NotImplementedError, match="sliding window"):
        _config(decode=True)
    with pytest.raises(NotImplementedError, match="share of the experts"):
        _config(2, 2, decode=True, layer_types=None, rope_parameters=None)
    with pytest.raises(NotImplementedError, match="rotary table"):
        _config(decode=True, layer_types=None)
    scanned = LlamaForCausalLM(_config(scan_layers=True))
    with pytest.raises(NotImplementedError, match="layer_types mixes"):
        scanned.init(jax.random.PRNGKey(0), ids)
    with pytest.raises(ValueError, match="sliding_window"):
        _config(sliding_window=None)
    with pytest.raises(ValueError, match="layer_types names"):
        _config(layer_types=KINDS[:3])
    with pytest.raises(ValueError, match="are not among"):
        MoEConfig(num_experts=4, routed_experts=8, first_expert=6,
                  drop_tokens=False)
    with pytest.raises(NotImplementedError, match="dropless"):
        MoEConfig(num_experts=4, routed_experts=8)
    # one kind of layer scans: all sliding
    one_kind = LlamaForCausalLM(_config(
        scan_layers=True, layer_types=[SLIDING] * 8))
    out = compare.apply(one_kind, compare.init(one_kind, ids), ids,
                        labels=ids)
    assert np.isfinite(float(out["loss"]))


def test_defaults_leave_the_older_models_as_they_were():
    cfg = LlamaConfig(hidden_size=2048, num_attention_heads=16)
    assert cfg.head_dim == 128 and cfg.kinds == () and not cfg.per_layer_type
    assert cfg.rotary(None) is None and cfg.window(None) is None
    assert cfg.expert_size == cfg.intermediate_size
    assert dataclasses.replace(cfg, remat=True).head_dim == 128
    assert hash(_config()) == hash(_config())
    assert MoEConfig(num_experts=64).holds_all


def test_flops_per_token_counts_head_dim_band_and_held_experts():
    with open(os.path.join(
            ROOT, "benchmark/configs/mellum2-12b-a2.5b-z3-8bit.json")) as f:
        conf = json.load(f)
    model, cfg = load_module(ROOT, "drivers", "train_lm").model_config(
        {k: v for k, v in conf.items() if k != "rehearse"})
    E, H, KV, D, I, L = 2304, 32, 4, 128, 896, 4
    Smax = cfg.max_position_embeddings
    n = 2 * 24576 * E + L * (2 * E * H * D + 2 * E * KV * D
                             + 3 * E * I * 8 * 16 / 64 + E * 64)
    want = 6.0 * n + 12 * H * D * (3 * 1024 + Smax)
    assert model.flops_per_token() == pytest.approx(want)
    # a dense LLaMA reads what it read before head_dim was a field
    plain = LlamaForCausalLM(LlamaConfig())
    c = plain.cfg
    assert plain.flops_per_token() == 6.0 * (
        2 * c.padded_vocab_size * 2048 + 16 * (2 * 2048 ** 2 + 2 * 2048 * 2048
                                               + 3 * 2048 * 5632)) \
        + 12 * 16 * 2048 * 2048


def test_tiles_take_the_largest_multiple_of_128_that_divides():
    assert _tiles(65536, 2048, 1024) == TILES == (512, 1024, 1024)
    assert _tiles(65536, 1024, 2048) == TILES
    assert _tiles(131072, 2304, 896) == (512, 768, 896)
    assert _tiles(131072, 896, 2304) == (512, 896, 768)
    assert _tiles(100, 64, 64) is None
