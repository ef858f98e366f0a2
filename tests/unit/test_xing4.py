"""Xing4.0-29B-A4B as a ``LlamaConfig`` (PR 62) against
``benchmark/reference/xing4.py`` on seeded weights at a small size that
keeps every ratio (hidden 64, four lanes, 3 blocks + the prediction block,
4 of 16 experts a share, top-4): the loss, its two parts and the gradient of
every leaf UNDER SEEDED GAINS AND BIASES of the size of the scores' spread
(at the initial gains of 0.01 a program that ignores the dynamic part of the
maps reads sound); every sublayer's hyper-connection alone and each of its
named faults; the lane-to-lane map doubly stochastic after 20 sweeps and not
after one; the clamp; one lane under pinned maps as the plain residual; YaRN
latent attention and its three faults; the shares of an expert layer adding
up to the uncut layer at top-4 of 16; what weight decay skips; what is
refused; and Mellum 2's YaRN table bit for bit what it was.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.models.llama import (LlamaBlock, LlamaConfig,
                                        LlamaForCausalLM,
                                        LlamaLatentAttention)
from deepspeed_tpu.ops import hyper_connection as mhc
from deepspeed_tpu.ops.rotary import rotary_table, yarn_mscale
from deepspeed_tpu.parallel.moe import STATE_LEAF, MoEConfig, MoELayer
from deepspeed_tpu.runtime import state_leaves
from deepspeed_tpu.runtime.optimizers import decay_mask

from . import reference_compare as compare
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "xing4")

S, VOCAB, ROUTED, HELD, TOP_K, EPS, LANES = 128, 160, 16, 4, 4, 1e-6, 4
MLA = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True)
# Xing's entry at a context the small rows exceed: pairs 0-1 keep their
# frequency, pair 3 is slowed 64-fold, pair 2 lies on the ramp
YARN = dict(type="yarn", factor=64, original_max_position_embeddings=16,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
HC = dict(hc_mult=LANES, hc_sinkhorn_iters=20, hc_eps=1e-6,
          mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
ROUTING = dict(score_func="sigmoid", norm_topk_prob=True, route_scale=2.0,
               bias_update_rate=0.001, num_shared_experts=1)


def _moe(first=0, held=ROUTED):
    return MoEConfig(num_experts=held, top_k=TOP_K, drop_tokens=False,
                     expert_act="swiglu", aux_loss_weight=0.0,
                     routed_experts=None if held == ROUTED else ROUTED,
                     first_expert=first, **ROUTING)


def _config(first=0, held=ROUTED, **kw):
    base = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, head_dim=8, intermediate_size=112,
                moe_intermediate_size=24, max_position_embeddings=S,
                rms_norm_eps=EPS, rope_theta=1e4, rope_scaling=YARN,
                moe=_moe(first, held), num_dense_layers=1,
                num_nextn_predict_layers=1, scan_layers=False,
                dtype=jnp.float32, attn_impl="jnp", vocab_pad_multiple=32,
                **MLA, **HC)
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
                rope_scaling=YARN, eps=cfg.rms_norm_eps,
                first_expert=cfg.moe.first_expert, **HC)


def _blocks(params):
    return [params[n] for n in sorted(params) if n.startswith("layers_")] \
        + [params["mtp_0"]["block"]]


def seed_maps(params, seed=0):
    """Gains and biases of the size of the scores' spread in every
    sublayer, so that the maps move with the token."""
    rng = np.random.default_rng(seed)
    for block in _blocks(params):
        for name in ("attn_hc", "mlp_hc"):
            hc = block[name]
            for a in ("a_pre", "a_post", "a_res"):
                hc[a] = jnp.asarray(rng.uniform(0.3, 0.6, 1)
                                    * rng.choice([-1.0, 1.0]), jnp.float32)
            hc["b_pre"] = jnp.asarray(rng.normal(0, 1, LANES), jnp.float32)
            hc["b_post"] = jnp.asarray(rng.normal(0, 1, LANES), jnp.float32)
            hc["b_res"] = jnp.asarray(rng.normal(0, 2, (LANES, LANES)),
                                      jnp.float32)
    return params


def _params(model, ids, scale=6.0):
    params = compare.init(model, ids, labels=ids, scale=scale)
    for i, block in enumerate(b for b in _blocks(params) if "moe" in b):
        block["moe"]["gate"][STATE_LEAF] = jnp.asarray(
            np.random.default_rng(i).normal(0, 0.2, ROUTED), jnp.float32)
    return seed_maps(params)


@pytest.fixture(scope="module")
def setup():
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, S)),
                      jnp.int32)
    return cfg, model, ids, _params(model, ids)


@pytest.fixture(scope="module")
def forward(setup):
    """The reference forward's inputs of every attention, FFN and
    hyper-connection, the prediction block's last."""
    cfg, _, ids, params = setup
    attn_in, ffn_in, hc_in = [], [], []
    kw = _reference_kwargs(cfg)
    h = reference.hidden(params, ids, attn_inputs=attn_in, ffn_inputs=ffn_in,
                         hc_inputs=hc_in, **kw)
    reference.mtp(h, ids, params, attn_inputs=attn_in, ffn_inputs=ffn_in,
                  hc_inputs=hc_in, **kw)
    return h, attn_in, ffn_in, hc_in


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
    assert abs(float(out["lm_loss"]) - float(main)) < 3e-5
    assert abs(float(out["mtp_loss"]) - float(second)) < 3e-5
    want = reference.training_loss(params, ids, mtp_weight=0.3,
                                   **_reference_kwargs(cfg))
    assert abs(float(out["loss"]) - float(want)) < 3e-5
    # the gauges: a value a block, the prediction block's last
    stats = out["stats"]
    assert stats["tokens_per_expert"].shape == (3, ROUTED)
    for key in ("mhc_res_marginal_err", "mhc_res_offdiag", "mhc_pre_mean",
                "mhc_post_mean"):
        assert stats[key].shape == (4,), key
    # what 20 sweeps leave of logits that spread over +-6: the columns
    # were normalised last, the rows are a little off
    assert 1e-6 < float(stats["mhc_res_marginal_err"].max()) < 0.1
    # seeded maps mix the lanes: nothing like the near-identity start
    assert float(stats["mhc_res_offdiag"].min()) > 0.3
    # and the chunked head reads the same lane sum
    chunked = compare.apply(LlamaForCausalLM(_config(loss_chunk=32)), params,
                            ids, labels=ids)
    assert float(chunked["loss"]) == pytest.approx(float(out["loss"]),
                                                   abs=1e-5)


def test_every_gradient_matches_the_reference(setup, program):
    """Every leaf, the hyper-connections' seven a sublayer among them."""
    cfg, model, ids, params = setup
    trained, held = state_leaves.split(params, model.is_state_leaf)
    # the reference's side bare: 10.5 s against 12.8 s compiled
    want = jax.grad(lambda p: reference.training_loss(
        state_leaves.merge(p, held), ids, mtp_weight=0.3,
        **_reference_kwargs(cfg)))(trained)
    # twelve leaves have a gradient that is zero but for what the sweeps leave
    # (|w| < 1e-6 against 1e-3 and more elsewhere): where every lane is the
    # same row (the first sublayer of the stack and of the prediction
    # block) H_res and the sum of H_pre alone count, and the last
    # sublayer's H_res is summed over its columns, which sum to 1
    paths, small = compare.compare_leaves(
        program[1], want, tol=2e-3, measure="norm", vanishing=(1e-6, 2e-8))
    assert len(paths) + len(small) > 40 + 7 * 8
    assert len(small) <= 12 and all("_hc" in k for k in small), small


def _program_sublayer(cfg, p_hc, X, y):
    """``{pre, post, res, u, out}`` of the program's module on stream ``X``
    (B, S, n, E), in the reference's layout: the cell's driver's runner."""
    run = load_module(ROOT, "drivers", "train_xing4").program_sublayer(cfg)
    return run(p_hc, X.reshape(*X.shape[:2], -1).astype(cfg.dtype),
               y.astype(cfg.dtype))


def test_every_sublayer_alone_matches(setup, forward):
    cfg, _, _, params = setup
    hc_in = forward[3]
    leaves = [b[name] for b in _blocks(params)
              for name in ("attn_hc", "mlp_hc")]
    assert len(leaves) == len(hc_in) == 8
    for p, (X, y) in zip(leaves, hc_in):
        got = _program_sublayer(cfg, p, X, y)
        want = reference.hyper_connection(p, X, y, eps=EPS, **HC)
        for key in ("pre", "post", "res", "u", "out"):
            assert _rel(got[key], want[key]) < 2e-5, key
        assert float(jnp.abs(got["res"].sum(-2) - 1).max()) < 1e-4


@pytest.mark.parametrize("fault", reference.MHC_FAULTS)
def test_a_sublayer_refuses_each_fault(setup, forward, fault):
    """One sweep for 20, rows only, H_post without its 2, softmax for the
    sigmoid of H_pre, H_res transposed, the norm's rsqrt left out, the clamp
    left out where two raw entries of a row lie beyond 30."""
    cfg, _, _, params = setup
    X, y = forward[3][3]
    p = dict(_blocks(params)[1]["mlp_hc"])
    if fault == "no_clamp":
        p["b_res"] = p["b_res"].at[0, 0].set(36.0).at[0, 1].set(32.0)
    got = _program_sublayer(cfg, p, X, y)
    sound = reference.hyper_connection(p, X, y, eps=EPS, **HC)
    wrong = reference.hyper_connection(p, X, y, eps=EPS, fault=fault, **HC)

    def err(want):
        return max(_rel(got[key], want[key]) for key in got)

    assert err(sound) < 2e-5
    assert err(wrong) > 1e-2, fault


def test_the_map_is_doubly_stochastic_after_20_sweeps_and_not_after_one():
    raw = jnp.asarray(np.random.default_rng(0).normal(0, 1, (4, 4, 256)),
                      jnp.float32)

    def worst(m):
        return max(float(jnp.abs(m.sum(1) - 1).max()),
                   float(jnp.abs(m.sum(0) - 1).max()))

    assert worst(mhc.sinkhorn(jnp.exp(raw), 20, 1e-6)) < 1e-4
    assert worst(mhc.sinkhorn(jnp.exp(raw), 1, 1e-6)) > 1e-2
    # the loop differentiates: a static trip count scans back
    g = jax.grad(lambda r: mhc.sinkhorn(jnp.exp(r), 20, 1e-6)[0, 1].sum())(raw)
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).max()) > 0


def test_the_clamp_bites_at_a_raw_entry_of_40():
    n, E, T = 4, 16, 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (1, T, n * E)), jnp.float32)
    phi = jnp.zeros((n * E, n * n + 2 * n), jnp.float32)
    zeros, one = jnp.zeros((n,)), jnp.ones((1,))

    def res(entry, clamp=(-30.0, 30.0)):
        b = jnp.zeros((n, n)).at[0, 0].set(entry).at[0, 1].set(28.0)
        return mhc.maps(x, phi, (one, one, one), (zeros, zeros, b), n=n,
                        iters=20, eps=1e-6, clamp=clamp, rms_eps=1e-6).res

    np.testing.assert_array_equal(res(40.0), res(30.0))
    assert float(jnp.abs(res(40.0) - res(40.0, (-50.0, 50.0))).max()) > 0.05
    assert np.isfinite(np.asarray(res(1e4))).all()


def test_one_lane_under_pinned_maps_is_the_plain_residual():
    """H_pre = H_post = H_res = 1 at n = 1: ``u = x`` and ``x + y``; and
    ``hc_mult`` 1 (or None) builds the plain block, leaf for leaf."""
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(rng.normal(0, 1, (2, 8, 16)), jnp.float32)
            for _ in range(2))
    ones = jnp.ones((1, 16))
    np.testing.assert_array_equal(mhc.pre(x, ones), x)
    np.testing.assert_array_equal(mhc.post(x, y, ones[None], ones), x + y)
    np.testing.assert_array_equal(mhc.collapse(mhc.widen(x, 1), 1), x)
    pos = (jnp.arange(8)[None, :], None)
    h = jnp.asarray(rng.normal(0, 1, (2, 8, 64)), jnp.float32)
    outs = []
    for lanes in (None, 1):
        block = LlamaBlock(_config(hc_mult=lanes), sparse=False)
        p = jax.jit(block.init)(jax.random.PRNGKey(0), h, pos)["params"]
        assert "attn_hc" not in p and "mlp_hc" not in p
        outs.append(block.apply({"params": p}, h, pos)[0])
    np.testing.assert_array_equal(*outs)


def _attention_alone(cfg, p_attn, h):
    pos = jnp.arange(h.shape[1])[None, :]
    return compare.apply(LlamaLatentAttention(cfg), p_attn, h, pos, None)


@pytest.fixture(scope="module")
def attention_1(setup, forward):
    """The program's layer-1 attention, once for every fault it refuses."""
    return _attention_alone(setup[0], setup[3]["layers_1"]["self_attn"],
                            forward[1][1])


def _attn_ref(cfg, p_attn, h, fault=None):
    kw = _reference_kwargs(cfg)
    return reference.attention(h, p_attn, fault=fault, **{k: kw[k] for k in (
        "n_head", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "rope_scaling", "eps")})


def test_yarn_latent_attention_matches_and_the_plain_table_does_not(
        setup, forward):
    cfg, _, _, params = setup
    for i in (0, 1):
        p, h = params[f"layers_{i}"]["self_attn"], forward[1][i]
        assert _rel(_attention_alone(cfg, p, h), _attn_ref(cfg, p, h)) < 1e-5
    plain = _attention_alone(_config(rope_scaling=None), p, h)
    assert _rel(plain, _attn_ref(cfg, p, h)) > 2e-2
    table, scaled = cfg.latent_rotary
    assert table.scale == 1.0
    assert scaled == pytest.approx((0.1 * np.log(64) + 1) ** 2)
    assert yarn_mscale(64, 1) == pytest.approx(1.4159, abs=1e-4)
    assert _config(rope_scaling=None).latent_rotary == (None, 1.0)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_attention_refuses_each_assumed_item_done_wrong(
        setup, forward, attention_1, fault):
    """JoyAI's eight, and YaRN's three: the table's default factor on cos
    and sin, the scale without mscale^2, rope_theta's plain frequencies."""
    cfg, _, _, params = setup
    p, h = params["layers_1"]["self_attn"], forward[1][1]
    err = _rel(attention_1, _attn_ref(cfg, p, h, fault))
    assert err > (1e-3 if fault == "bf16_accumulation" else 2e-2), err


def _expert_layer(cfg, p_moe, h):
    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    return layer.apply({"params": p_moe}, h)[0]


def test_the_shares_add_up_to_the_uncut_layer(setup, forward):
    """Xing's routing, top-4 of 16 at route scale 2: four shares of four
    experts each, the shared expert counted once, are the uncut layer."""
    cfg, _, _, params = setup
    p, h = params["layers_1"]["moe"], forward[2][1]
    kw = dict(top_k=TOP_K, route_scale=2.0)
    whole = reference.sparse_ffn(p, h, **kw)
    assert _rel(_expert_layer(cfg, p, h), whole) < 1e-5
    shared = (jax.nn.silu(h @ p["shared"]["gate"]) * (h @ p["shared"]["up"])
              ) @ p["shared"]["down"]
    total = 0.0
    for first in range(0, ROUTED, HELD):
        cut = dict(p, experts={k: v[first:first + HELD]
                               for k, v in p["experts"].items()})
        out = _expert_layer(_config(first, HELD), cut, h)
        assert _rel(out, reference.sparse_ffn(cut, h, first_expert=first,
                                              **kw)) < 1e-5
        total = total + out - shared
    assert _rel(total + shared, whole) < 1e-5


def test_weight_decay_skips_the_gains_and_biases_alone(setup):
    cfg, model, _, params = setup
    trained, _ = state_leaves.split(params, model.is_state_leaf)
    mask = decay_mask(model)(trained)
    skipped = {jax.tree_util.keystr(path)
               for path, keep in jax.tree_util.tree_leaves_with_path(mask)
               if not keep}
    assert len(skipped) == 4 * 2 * 6
    assert all(("attn_hc" in s or "mlp_hc" in s) and "phi" not in s
               for s in skipped)
    # one lane: no mask, and the chain is built as it always was
    assert decay_mask(LlamaForCausalLM(_config(hc_mult=None))) is None
    assert decay_mask(object()) is None


@pytest.mark.parametrize("kw, named", [
    (dict(decode=True), "decode=True"),
    (dict(scan_layers=True), "scan_layers=True"),
    (dict(sa_config={"topk": 4}), "sa_config"),
    (dict(diffusion={"block_length": 4}), "diffusion"),
    (dict(attn_impl="ring"), "attn_impl 'ring'"),
    (dict(attn_impl="ulysses"), "attn_impl 'ulysses'"),
    (dict(sandwich_norm=True), "sandwich_norm")])
def test_what_the_lanes_cannot_run_yet_is_refused_by_name(kw, named):
    base = dict(hidden_size=64, num_attention_heads=4, num_hidden_layers=2,
                scan_layers=False, hc_mult=4)
    base.update(kw)
    with pytest.raises(NotImplementedError, match="hc_mult 4.*" + named):
        LlamaConfig(**base)
    LlamaConfig(**dict(base, hc_mult=1))        # one lane: as before


def test_a_scaled_table_without_latent_attention_is_refused():
    with pytest.raises(NotImplementedError, match="rope_parameters"):
        LlamaConfig(hidden_size=64, num_attention_heads=4, rope_scaling=YARN)
    # a source's entry that scales nothing passes (Keye-VL's)
    LlamaConfig(hidden_size=64, num_attention_heads=4, rope_scaling={
        "type": "default", "rope_type": "default",
        "mrope_section": [16, 24, 24]})


def test_mellum2s_full_layer_table_is_bit_for_bit_what_it_was():
    """``LlamaConfig.rotary`` of the Mellum 2 file's full_attention entry
    (the grouped-query path's YaRN: ``attention_factor`` on cos and sin):
    the digest of its frequencies and factor, taken at the parent commit."""
    import json
    import os

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b-z3-8bit.json")) as f:
        conf = json.load(f)
    cfg = LlamaConfig(hidden_size=conf["hidden_size"], num_hidden_layers=1,
                      num_attention_heads=conf["num_attention_heads"],
                      head_dim=conf["head_dim"],
                      rope_parameters=conf["rope_parameters"],
                      layer_types=["full_attention"])
    table = cfg.rotary("full_attention")
    assert table.scale == 1.2772588722239782
    said = hashlib.sha256(np.asarray(table.inv_freq, np.float64).tobytes()
                          + np.float64(table.scale).tobytes()).hexdigest()
    assert said == ("ece7ff017e9d35a667e937bad1f1d1b2"
                    "e77c9ebba25b27a58d7c819669357785")
    # the latent path passes its own factor, 1 here, never this default
    assert rotary_table(64, "yarn", rope_theta=1e4, factor=64.0,
                        original_max_position_embeddings=4096).scale \
        == pytest.approx(1.4159, abs=1e-4)
