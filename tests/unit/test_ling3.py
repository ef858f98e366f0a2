"""Ling-3.0-flash (``model_type: bailing_hybrid``, PR 58) at the rehearsal
size against its plain reference (``benchmark/reference/ling3.py``): five
Kimi-Delta-Attention mixers (the delta rule under a decay a key channel) to
one latent-attention layer without a query latent and with a gate a head,
group-limited routing over a share of the experts, the prediction block.
Float32 throughout: what is compared is the mathematics, not a rounding.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.drivers import train_lm
from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.models.llama import (FULL_ATTENTION, KDA,
                                        KimiDeltaAttention, LlamaConfig,
                                        LlamaForCausalLM,
                                        LlamaLatentAttention)
from deepspeed_tpu.parallel import moe as moe_lib
from deepspeed_tpu.parallel.moe import (MoEConfig, MoELayer, group_limit,
                                        topk_routing)
from deepspeed_tpu.telemetry import get_registry

from . import reference_compare as compare
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "ling3")
FILE = os.path.join(ROOT, "benchmark", "configs",
                    "ling-3.0-flash-z3-8bit.json")
ROUTED, HELD, GROUPS, KEPT, TOP_K = 16, 4, 4, 2, 4


def _file() -> dict:
    with open(FILE) as f:
        return json.load(f)


def _moe(first=0, held=HELD, **kw):
    return MoEConfig(**{**dict(
        num_experts=held, top_k=TOP_K, drop_tokens=False,
        expert_act="swiglu", norm_topk_prob=True, score_func="sigmoid",
        route_scale=2.5, bias_update_rate=0.02, num_shared_experts=1,
        routed_experts=ROUTED, first_expert=first, n_group=GROUPS,
        topk_group=KEPT, aux_loss_weight=0.0), **kw})


def _config(mtp=0, **kw):
    """The file's rehearsal size in float32, six layers: KDA x 5 : MLA."""
    base = dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=6,
        num_attention_heads=4, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32,
        layer_types=[KDA] * 5 + [FULL_ATTENTION], kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_interleave=True, partial_rotary_factor=0.5, attn_gate="head",
        rope_theta=6e6, linear_chunk_size=8, moe=_moe(), num_dense_layers=1,
        scan_layers=False, num_nextn_predict_layers=mtp, mtp_loss_weight=0.3,
        rms_norm_eps=1e-6, max_position_embeddings=64, dtype=jnp.float32)
    return LlamaConfig(**{**base, **kw})


def _reference_kwargs(cfg, **kw):
    return dict(
        n_layer=cfg.num_hidden_layers, layer_types=cfg.kinds,
        num_dense_layers=cfg.num_dense_layers, n_head=cfg.num_attention_heads,
        lower_bound=cfg.kda_lower_bound, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, eps=cfg.rms_norm_eps, top_k=cfg.moe.top_k,
        route_scale=cfg.moe.route_scale, n_group=cfg.moe.n_group,
        topk_group=cfg.moe.topk_group, first_expert=cfg.moe.first_expert,
        vocab_size=cfg.vocab_size, mtp_layers=cfg.num_nextn_predict_layers,
        **kw)


def _moved(params, seed=0):
    """Every 1-D leaf off its initial value (the decays over the whole of
    (lower bound, 0), the biases and norms anywhere but 0 and 1)."""
    rng = np.random.default_rng(seed)

    def one(path, x):
        name = jax.tree_util.keystr(path)
        if x.ndim != 1:
            return x
        if name.endswith("['A_log']"):
            return jnp.asarray(np.log(rng.uniform(0.5, 2.0, x.shape)),
                               x.dtype)
        if name.endswith("['dt_bias']"):
            return jnp.asarray(rng.normal(0.0, 1.0, x.shape), x.dtype)
        # the routers' scores spread ~0.04 here: a bias of their size
        scale = 0.03 if name.endswith("['expert_bias']") else 0.2
        return x + jnp.asarray(rng.normal(0.0, scale, x.shape), x.dtype)

    return jax.tree_util.tree_map_with_path(one, params)


def _setup(mtp):
    cfg = _config(mtp)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 32)),
                      jnp.int32)
    params = _moved(compare.init(model, ids, labels=ids))
    # the hidden states reach the routers spread out, as under init_scale
    params = dict(params, embed_tokens=params["embed_tokens"] * 50.0)
    return cfg, model, ids, params


@pytest.fixture(scope="module", params=[0, 1], ids=["no_mtp", "mtp"])
def setup(request):
    return _setup(request.param)


@pytest.fixture(scope="module")
def program(setup):
    """``(out, grads)`` of the model on the fixture's weights: the forward
    and the backward of both tests below, one compiled program a case."""
    _, model, ids, params = setup
    with jax.default_matmul_precision("highest"):
        return compare.forward_and_gradients(
            lambda p: model.apply({"params": p}, ids, labels=ids), params)


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------
def test_loss_and_logits_are_the_references(setup, program):
    cfg, model, ids, params = setup
    kw = _reference_kwargs(cfg)
    out = program[0]
    with jax.default_matmul_precision("highest"):
        main, second = reference.loss_parts(params, ids, **kw)
        want = reference.logits(params, ids, **{
            k: v for k, v in kw.items() if k != "mtp_layers"})
    lam = 0.3 if cfg.num_nextn_predict_layers else 0.0
    np.testing.assert_allclose(float(out["loss"]),
                               float(main) + lam * float(second), rtol=2e-5)
    if cfg.num_nextn_predict_layers:
        np.testing.assert_allclose(float(out["lm_loss"]), float(main),
                                   rtol=2e-5)
        np.testing.assert_allclose(float(out["mtp_loss"]), float(second),
                                   rtol=2e-5)
    got = np.asarray(out["logits"])[..., :cfg.vocab_size]
    np.testing.assert_allclose(got, np.asarray(want)[..., :cfg.vocab_size],
                               atol=2e-4 * float(np.abs(got).max()))


def test_every_gradient_is_the_references(setup, program):
    cfg, model, ids, params = setup
    kw = _reference_kwargs(cfg)
    # the reference's side bare: op by op its lines are the cheaper
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference.training_loss(
            p, ids, mtp_weight=0.3, **kw))(params)
    paths, _ = compare.compare_leaves(
        program[1], want, tol=5e-4, measure="max",
        no_gradient=("['expert_bias']",))       # picks, never weighs
    assert {"A_log", "dt_bias", "f_proj_kernel", "b_proj_kernel",
            "g_proj_kernel", "conv_kernel", "o_norm", "gate_proj_kernel",
            "q_proj_kernel", "kv_b_proj_kernel", "wg"} <= {
        path[-1].key for path in paths}


def test_the_mixer_alone_is_the_references_recurrence():
    """``KimiDeltaAttention`` at a chunk of 64 in four solve blocks, two
    rows of two chunks, against the recurrence one position a step: output
    and the gradient of a seeded scalar with respect to the input and every
    leaf."""
    cfg = _config(linear_chunk_size=64)
    module = KimiDeltaAttention(cfg)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.float32)
    probe = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.float32)
    p = _moved(compare.init(module, h, seed=1), 1)

    @jax.jit
    def run(h, p):
        y, pull = jax.vjp(lambda h, p: module.apply({"params": p}, h), h, p)
        return (y, *pull(probe))

    with jax.default_matmul_precision("highest"):
        y, dh, dp = run(h, p)
        ry, rh, rp = reference.kda_grads(p, h, probe, n_head=4,
                                         lower_bound=-5.0, eps=1e-6)
    assert _rel(y, ry) < 2e-5 and _rel(dh, rh) < 2e-5
    assert set(dp) == set(rp) == {
        "A_log", "dt_bias", "o_norm", "conv_kernel", "q_proj_kernel",
        "k_proj_kernel", "v_proj_kernel", "f_proj_kernel", "b_proj_kernel",
        "g_proj_kernel", "o_proj_kernel"}
    for leaf in rp:
        assert _rel(dp[leaf], rp[leaf]) < 5e-5, leaf
    # the decays spread over the range and sit at neither end alone
    g = -5.0 * jax.nn.sigmoid(jnp.repeat(jnp.exp(p["A_log"]), 16) * (
        h @ p["f_proj_kernel"] + p["dt_bias"]))
    assert float(g.min()) < -4.5 and float(g.max()) > -0.5


@functools.lru_cache(maxsize=None)
def _mixer_at_a_chunk_of_16():
    """``(leaves, input, output)``, compiled once for the four faults."""
    module = KimiDeltaAttention(_config(linear_chunk_size=16))
    h = jnp.asarray(np.random.default_rng(3).standard_normal((1, 64, 64)),
                    jnp.float32)
    p = _moved(compare.init(module, h, seed=1), 1)
    with jax.default_matmul_precision("highest"):
        return p, h, compare.apply(module, p, h)


@pytest.mark.parametrize("fault", ["decay_head_mean", "gate_silu",
                                   "no_dt_bias", "softplus_gate"])
def test_the_mixer_is_not_a_named_wrong_thing(fault):
    p, h, y = _mixer_at_a_chunk_of_16()
    with jax.default_matmul_precision("highest"):
        wrong = reference.kda(p, h, n_head=4, lower_bound=-5.0, fault=fault)
    assert _rel(y, wrong) > 0.02, fault


def test_latent_attention_without_a_query_latent_and_with_a_gate_a_head():
    cfg = _config()
    module = LlamaLatentAttention(cfg)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((2, 64, 64)), jnp.float32)
    pos = jnp.arange(64)[None, :]
    p = _moved(compare.init(module, h, pos, None, seed=2), 2)
    assert set(p) == {"q_proj_kernel", "kv_a_proj_with_mqa_kernel",
                      "kv_a_layernorm", "kv_b_proj_kernel", "gate_proj_kernel",
                      "o_proj_kernel"}
    assert p["q_proj_kernel"].shape == (64, 4 * 24)
    assert p["gate_proj_kernel"].shape == (64, 4)
    kw = dict(n_head=4, kv_lora_rank=16, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, rope_theta=6e6, eps=1e-6)
    with jax.default_matmul_precision("highest"):
        y = compare.apply(module, p, h, pos, None)
        assert _rel(y, reference.attention(h, p, **kw)) < 2e-5
        for fault in ("no_gate", "gate_before_softmax_scale"):
            assert _rel(y, reference.attention(h, p, fault=fault, **kw)) > .05


# ----------------------------------------------------------------------
# the routing
# ----------------------------------------------------------------------
def _logits(S=64, E=ROUTED, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((S, E)) * 2.0, jnp.float32),
            jnp.asarray(rng.standard_normal(E) * 0.3, jnp.float32))


def test_one_group_of_which_one_is_todays_routing_bit_for_bit():
    logits, bias = _logits()
    kw = dict(score_func="sigmoid", bias=bias, route_scale=2.5)

    def today(l):
        return topk_routing(l, TOP_K, True, **kw)

    def grouped(l):
        return topk_routing(l, TOP_K, True, n_group=1, topk_group=1, **kw)

    assert str(jax.make_jaxpr(today)(logits)) \
        == str(jax.make_jaxpr(grouped)(logits))
    for a, b in zip(today(logits), grouped(logits)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and every group kept is no limit at all
    free = topk_routing(logits, TOP_K, True, **kw)
    full = topk_routing(logits, TOP_K, True, n_group=GROUPS,
                        topk_group=GROUPS, return_kept=True, **kw)
    for a, b in zip(free[:3], full[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(full[5]) == 1.0


def test_the_group_limit_against_a_case_made_by_hand():
    """Four groups of two; a group scores the sum of its two best: group 0
    = 0.9 + 0.1, group 1 = 0.6 + 0.5, group 2 = 0.8 + 0.0, group 3 = 0.45 +
    0.45.  Two groups stay: 1 (1.1) and 0 (1.0), although group 2 holds the
    second best expert; top-3 among experts 0-3: 0 (0.9), 2 (0.6), 3 (0.5).
    Without groups: 0, 4 (0.8), 2 - one pair of three is moved."""
    scores = jnp.asarray([[0.9, 0.1, 0.6, 0.5, 0.8, 0.0, 0.45, 0.45]])
    allowed, kept = group_limit(scores, 3, 4, 2)
    np.testing.assert_array_equal(
        np.asarray(allowed[0]), [True] * 4 + [False] * 4)
    np.testing.assert_allclose(float(kept), 2 / 3, rtol=1e-6)
    logit = jnp.log(scores / (1 - scores + 1e-9) + 1e-9)
    w, experts, counts, _, _, kept = topk_routing(
        logit, 3, True, score_func="sigmoid", n_group=4, topk_group=2,
        return_kept=True)
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 2, 3]
    np.testing.assert_allclose(
        sorted(np.asarray(w[0]).tolist()),
        sorted((np.asarray([0.9, 0.6, 0.5]) / 2.0).tolist()), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts),
                                  [1, 0, 1, 1, 0, 0, 0, 0])
    # the bias picks the groups too, and weighs nothing
    bias = jnp.asarray([0, 0, 0, 0, 0, 0.9, 0, 0], jnp.float32)
    w, experts, *_ = topk_routing(logit, 3, True, score_func="sigmoid",
                                  bias=bias, n_group=4, topk_group=2)
    assert sorted(np.asarray(experts[0]).tolist()) == [2, 4, 5]
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=1e-5)


def test_the_programs_limit_is_the_references():
    logits, bias = _logits(S=256, seed=4)
    p = {"gate": {"wg": jnp.eye(ROUTED), "expert_bias": bias}}
    with jax.default_matmul_precision("highest"):
        chosen, weight, changed = reference._route(
            p, logits, TOP_K, 2.5, GROUPS, KEPT, None, None)
    w, experts, counts, _, _, kept = topk_routing(
        logits, TOP_K, True, score_func="sigmoid", bias=bias,
        route_scale=2.5, n_group=GROUPS, topk_group=KEPT, return_kept=True)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(chosen.sum(0)))
    dense = np.zeros((256, ROUTED), np.float32)
    np.put_along_axis(dense, np.asarray(experts), np.asarray(w), 1)
    np.testing.assert_allclose(dense, np.asarray(weight), rtol=1e-5,
                               atol=1e-7)
    assert 0.0 < float(changed) < 1.0
    np.testing.assert_allclose(float(kept), 1.0 - float(changed), atol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The partial sums of the four shares of four experts each, with what
    every share computes alike (the shared expert) counted once, are the
    reference's layer with all sixteen experts held."""
    cfg = _config()
    whole = MoELayer(_moe(0, ROUTED), model_dim=64, hidden_dim=32,
                     dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(6).standard_normal((2, 48, 64)),
                    jnp.float32)
    p = compare.init(whole, h, seed=3)
    p["gate"]["expert_bias"] = _logits(seed=8)[1]
    route = dict(top_k=TOP_K, route_scale=2.5, n_group=GROUPS,
                 topk_group=KEPT)
    with jax.default_matmul_precision("highest"):
        uncut = reference.sparse_ffn(p, h, first_expert=0, **route)
        alone = reference.sparse_ffn(
            dict(p, experts=jax.tree_util.tree_map(lambda x: x[:0],
                                                   p["experts"])),
            h, first_expert=0, **route)         # the shared expert, once
        total = alone
        for first in range(0, ROUTED, HELD):
            share = MoELayer(_moe(first), model_dim=64, hidden_dim=32,
                             dtype=jnp.float32)
            held = dict(p, experts=jax.tree_util.tree_map(
                lambda x: x[first:first + HELD], p["experts"]))
            out, _, stats = share.apply({"params": held}, h,
                                        return_stats=True)
            total = total + (out - alone)
            assert int(stats["dropped"]) == 0
            assert int(stats["elsewhere"]) + int(stats["tokens_per_expert"][
                first:first + HELD].sum()) == 2 * 48 * TOP_K
            assert 0.0 < float(stats["group_kept_share"]) < 1.0
    assert float(jnp.abs(alone).max()) > 0
    assert _rel(total, uncut) < 2e-5
    assert cfg.moe.routed == ROUTED


def test_the_gauge_of_the_group_limit_is_booked():
    stats = {"tokens_per_expert": np.ones((2, ROUTED), np.int32),
             "dropped": np.zeros(2), "balance_loss": np.ones(2),
             "router_z": np.ones(2),
             "group_kept_share": np.asarray([0.75, 0.5], np.float32)}
    moe_lib.record_stats(stats)
    family = get_registry().snapshot()["moe_group_kept_share"]
    booked = {s["labels"]["layer"]: s["value"] for s in family["samples"]}
    assert (booked["0"], booked["1"]) == (0.75, 0.5)


# ----------------------------------------------------------------------
# spans and counters
# ----------------------------------------------------------------------
def test_the_new_scopes_and_counters_show_in_one_step(setup, program):
    cfg, model, ids, params = setup
    text = jax.jit(lambda p: model.apply({"params": p}, ids, labels=ids)[
        "loss"]).lower(params).as_text(debug_info=True)
    for scope in ("linear_attn/in_proj", "linear_attn/conv",
                  "linear_attn/decay_gate", "linear_attn/delta_rule",
                  "linear_attn/gated_norm", "linear_attn/out_proj",
                  "attn/mla_q", "attn/mla_kv", "attn/mla_gate", "rope/mla",
                  "self_attn_mla", "moe/route"):
        assert scope in text, scope
    snap = get_registry().snapshot()
    assert snap["gated_delta_decay_channels"]["samples"][0]["value"] == 16
    assert any(s["labels"] == {"dk": "16", "dv": "16"}
               for s in snap["gated_delta_state_elems"]["samples"])
    out = program[0]
    n_sparse = 5 + cfg.num_nextn_predict_layers
    assert out["stats"]["group_kept_share"].shape == (n_sparse,)
    LlamaForCausalLM.record_step_stats(jax.device_get(out["stats"]))
    assert len(get_registry().snapshot()["moe_group_kept_share"][
        "samples"]) >= n_sparse


@pytest.mark.parametrize("head_dim,impl,said", [
    (128, "pallas", "4 chunks of 64 x 2 key heads x 1 value heads of 128, a "
                    "decay a key channel, fused; one device"),
    (16, "xla", "a decay a key channel (2 heads x 16): heads of 16 and 16 "
                "channels under a decay a key channel"),
])
def test_the_mixer_on_a_tpu_takes_the_channel_kernels(monkeypatch, head_dim,
                                                      impl, said):
    """``KimiDeltaAttention`` as the chip traces it (the guards see a TPU;
    nothing is lowered): heads of 128 channels in bf16 resolve site
    ``gated_delta`` to the kernels of a decay a key channel and the gauge
    reads 128; heads of 16, the rehearsal's, keep XLA's form by the guard's
    name and the gauge reads 16."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = _config(num_attention_heads=2, head_dim=head_dim,
                  qk_rope_head_dim=head_dim // 2, linear_chunk_size=64,
                  dtype=jnp.bfloat16)
    module = KimiDeltaAttention(cfg)
    h = jax.ShapeDtypeStruct((1, 256, 64), jnp.bfloat16)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    before = {r[:3]: r[3] for r in dispatch_report()}
    try:
        params = meta.unbox(jax.eval_shape(
            module.init, jax.random.PRNGKey(0), h)["params"])
        jax.eval_shape(jax.grad(lambda p, h: module.apply(
            {"params": p}, h).astype(jnp.float32).sum()), params, h)
    finally:
        mesh_lib.set_mesh(prev)
    ran = {r[1:3] for r in dispatch_report() if r[0] == "gated_delta"
           and r[3] > before.get(r[:3], 0)}
    assert len(ran) == 1, ran
    got_impl, got_said = ran.pop()
    assert got_impl == impl and got_said.startswith(said), got_said
    snap = get_registry().snapshot()
    assert snap["gated_delta_decay_channels"]["samples"][0][
        "value"] == head_dim


# ----------------------------------------------------------------------
# what is not written raises by name
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw,error,said", [
    (dict(decode=True), NotImplementedError,
     "decode=True with a kda_attention layer"),
    (dict(scan_layers=True), NotImplementedError,
     "scan_layers=True with a kda_attention layer"),
    (dict(diffusion={"block_length": 4, "mask_token_id": 1}),
     NotImplementedError,
     "block-diffusion training\\) with a kda_attention layer"),
    (dict(kda_safe_gate=False), NotImplementedError, "kda_safe_gate=False"),
    (dict(kda_lower_bound=-8.0), ValueError, "kda_lower_bound -8.0"),
    (dict(short_conv_kernel_size=0), ValueError, "at least one tap"),
    (dict(attn_impl="ring"), NotImplementedError,
     "kda_attention layer under sequence parallelism"),
    (dict(expert_swiglu_limit_list=[0, 0, 0, 4, 0, 0]), NotImplementedError,
     "expert_swiglu_limit_list\\[3\\] = 4"),
    (dict(share_expert_swiglu_limit_list=[0] * 5 + [7]), NotImplementedError,
     "share_expert_swiglu_limit_list\\[5\\] = 7"),
    (dict(attn_gate=True), NotImplementedError, "a gate a channel"),
    (dict(attn_gate="head", kv_lora_rank=None, qk_nope_head_dim=None,
          qk_rope_head_dim=None, v_head_dim=None, rope_interleave=False,
          partial_rotary_factor=1.0), NotImplementedError,
     "attn_gate='head' \\(one gate a head\\) without latent attention"),
    (dict(partial_rotary_factor=0.25), NotImplementedError,
     "is not qk_rope_head_dim / head_dim"),
    (dict(qk_norm="head"), NotImplementedError, "latent attention with "
     "qk_norm"),
    (dict(v_head_dim=None), ValueError, "latent attention takes"),
    (dict(sa_config={"topk": 8}), NotImplementedError, "sa_config"),
    (dict(layer_types=["kda_attention", "mamba"] * 3), ValueError,
     "'conv' and 'linear_attention' are written"),
])
def test_what_is_not_written_raises_by_name(kw, error, said):
    with pytest.raises(error, match=said):
        _config(**kw)


def test_a_limit_beyond_the_depth_built_is_no_refusal():
    _config(expert_swiglu_limit_list=[0] * 6 + [4] * 3,
            share_expert_swiglu_limit_list=[0] * 6 + [5])


@pytest.mark.parametrize("kw,said", [
    (dict(n_group=3), "routed experts in whole groups"),
    (dict(n_group=4, topk_group=5), "keeps 1 to n_group"),
    (dict(n_group=8, topk_group=1), "a group has at least two"),
    (dict(n_group=4, topk_group=2, drop_tokens=True), "dropless"),
])
def test_what_the_group_limit_refuses(kw, said):
    with pytest.raises((ValueError, NotImplementedError), match=said):
        _moe(**{**dict(n_group=1, topk_group=1), **kw})


def test_the_published_depth_builds_up_to_the_first_clamped_layer():
    """The benchmark's file at the published 42 layers, 2 dense ones and
    all 512 experts: the config object is built, and what refuses it is
    layer 34's clamp, by name; the 34 layers before it build."""
    conf = {k: v for k, v in _file().items() if k != "rehearse"}
    conf.update(num_hidden_layers=42, first_k_dense_replace=2,
                num_dense_layers=2, num_experts=512, vocab_size=157184,
                moe=dict(conf["moe"], first_expert=0))
    with pytest.raises(NotImplementedError,
                       match="share_expert_swiglu_limit_list\\[34\\] = 5"):
        train_lm.model_config(conf)
    conf["num_hidden_layers"] = 34
    model, cfg = train_lm.model_config(conf)
    assert cfg.kinds.count(KDA) == 29 and cfg.kinds.count(FULL_ATTENTION) == 5
    assert all(k == FULL_ATTENTION for k in cfg.kinds[5::6])
    assert cfg.moe.holds_all and cfg.moe.n_group == 8
    assert (cfg.q_lora_rank, cfg.attn_gate, cfg.kda_lower_bound) \
        == (None, "head", -5)


def test_the_models_own_count_of_operations_knows_the_new_layers():
    six = LlamaForCausalLM(_config()).flops_per_token()
    attention_only = LlamaForCausalLM(_config(
        layer_types=[FULL_ATTENTION] * 6)).flops_per_token()
    E, H, D = 64, 4, 16
    kda = 6 * E * H * D + E * H + 3 * H * D * 4 + 3 * H * D * D
    mla = E * H * 24 + E * 24 + 16 * H * 32 + H * 16 * E + E * H
    # five mixers differ, and five layers' causal keys
    assert six - attention_only == pytest.approx(
        6.0 * 5 * (kda - mla) - 6 * H * 40 * 5 * 64)


def test_at_the_published_loss_factor_no_prediction_block_is_built():
    """``mtp_loss_scaling_factor`` 0 (``mtp_loss_weight`` 0.0) beside
    ``num_nextn_predict_layers`` 1: the loss and the gradients are those of
    the model without the block, and none of its leaves is declared."""
    cfg = _config(1, mtp_loss_weight=0.0)
    assert (cfg.num_nextn_predict_layers, cfg.mtp_blocks) == (1, 0)
    assert _config(1).mtp_blocks == 1
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 32)),
                      jnp.int32)
    with_key, without = (LlamaForCausalLM(c) for c in (cfg, _config(0)))
    params = compare.init(with_key, ids, labels=ids)
    assert "mtp_0" not in params
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(meta.unbox(jax.eval_shape(
            without.init, jax.random.PRNGKey(0), ids, labels=ids)["params"]))
    a, b = (compare.apply(m, params, ids, labels=ids)
            for m in (with_key, without))
    assert float(a["loss"]) == float(b["loss"]) and "mtp_loss" not in a
    assert with_key.flops_per_token() == without.flops_per_token()
