"""Trinity (AFMoE) as a ``LlamaConfig`` (PR 33) against ``benchmark/reference/
trinity.py`` on seeded weights at a small size: logits, loss and every
gradient over a dense layer and a period of (sliding, sliding, full,
sliding) expert layers; each mechanism the config has no key for, alone
against its named fault; the eight shares of an expert layer adding up to
the uncut layer; the selection bias as a state leaf of the engine; what is
refused; and the older cells' blocks lowering to the parent's StableHLO.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import (FULL_ATTENTION, SLIDING,
                                        LlamaAttention, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.parallel import moe as moe_lib
from deepspeed_tpu.parallel.moe import (STATE_LEAF, MoEConfig, MoELayer,
                                        SharedExpert, bias_update,
                                        topk_routing)
from deepspeed_tpu.runtime import state_leaves

from . import reference_compare as compare
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "trinity")

KINDS = [SLIDING, SLIDING, SLIDING, FULL_ATTENTION] * 2
S, WINDOW, VOCAB, ROUTED, TOP_K, RATE = 48, 12, 160, 8, 4, 0.001
AFMOE = dict(score_func="sigmoid", norm_topk_prob=True, route_scale=2.826,
             bias_update_rate=RATE, num_shared_experts=1)


def _moe(first=0, held=ROUTED, **kw):
    return MoEConfig(**{**dict(
        num_experts=held, top_k=TOP_K, drop_tokens=False, expert_act="swiglu",
        aux_loss_weight=0.0, routed_experts=None if held == ROUTED else ROUTED,
        first_expert=first), **AFMOE, **kw})


def _config(first=0, held=ROUTED, **kw):
    base = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=5,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=40, moe_intermediate_size=24,
                max_position_embeddings=S, rms_norm_eps=1e-5,
                layer_types=KINDS, sliding_window=WINDOW, rope_theta=100.0,
                moe=_moe(first, held), num_dense_layers=1, mup_enabled=True,
                qk_norm="head", attn_gate=True, rope_layer_types=[SLIDING],
                sandwich_norm=True, scan_layers=False, dtype=jnp.float32,
                attn_impl="jnp", vocab_pad_multiple=32)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                vocab_size=cfg.vocab_size, top_k=TOP_K, layer_types=KINDS,
                sliding_window=WINDOW, num_dense_layers=cfg.num_dense_layers,
                route_scale=cfg.moe.route_scale, rope_theta=cfg.rope_theta,
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
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, S)),
                       jnp.int32)


# ----------------------------------------------------------------------
# model against reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("first,held", [(0, ROUTED), (2, 4)])
def test_logits_loss_and_every_gradient_match_the_reference(ids, first, held):
    cfg = _config(first, held)
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids)
    kw = _reference_kwargs(cfg)
    out, got = compare.forward_and_gradients(
        lambda p: model.apply({"params": p}, ids, labels=ids), params)
    counts = []
    want = reference.logits(params, ids, counts=counts, **kw)
    np.testing.assert_allclose(out["logits"][..., :VOCAB], want[..., :VOCAB],
                               atol=2e-4)
    # what the bias update reads: the pairs each of ALL routed experts got
    np.testing.assert_array_equal(out["stats"]["tokens_per_expert"],
                                  np.stack(counts))
    assert float(out["aux_loss"]) == 0.0      # cross-entropy alone
    np.testing.assert_allclose(out["loss"],
                               reference.training_loss(params, ids, **kw),
                               rtol=1e-5)
    # the per-layer statistics are stacked over the MoE layers alone
    assert out["stats"]["tokens_per_expert"].shape == (4, ROUTED)
    assert out["stats"][STATE_LEAF].shape == (4, ROUTED)
    # the reference's side bare: op by op its lines are the cheaper
    ref = jax.grad(lambda p: reference.training_loss(p, ids, **kw))(params)
    compare.compare_leaves(got, ref, tol=2e-4, measure="max",
                           no_gradient=(STATE_LEAF,))


def test_the_chunked_head_and_bf16_follow(ids):
    cfg = _config(loss_chunk=16, dtype=jnp.bfloat16)
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids, scale=1.0)
    out = compare.apply(model, params, ids, labels=ids)
    want = reference.training_loss(params, ids, **_reference_kwargs(cfg))
    assert abs(float(out["loss"]) - float(want)) < 0.03


# ----------------------------------------------------------------------
# each mechanism alone against its fault
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def attention_alone(ids):
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    params = _params(model, ids)
    hidden = []
    reference.logits(params, ids, attn_inputs=hidden,
                     **_reference_kwargs(cfg))
    # q and k scales away from 1, so that WHERE the norm runs shows
    for i in range(cfg.num_hidden_layers):
        attn = params[f"layers_{i}"]["self_attn"]
        rng = np.random.default_rng(i)
        attn["q_norm"]["scale"] = jnp.asarray(rng.uniform(0.5, 3.0, 16),
                                              jnp.float32)
        attn["k_norm"]["scale"] = jnp.asarray(rng.uniform(0.5, 3.0, 16),
                                              jnp.float32)
    # the program's side once for every fault: a sliding and a full layer
    got = {kind: compare.apply(
        LlamaAttention(cfg, kind), params[f"layers_{layer}"]["self_attn"],
        hidden[layer], jnp.arange(S)[None, :], None)
        for kind, layer in ((SLIDING, 0), (FULL_ATTENTION, 3))}
    return cfg, params, hidden, got


@pytest.mark.parametrize("fault", [None, *reference.FAULTS])
def test_attention_alone_against_each_named_fault(attention_alone, fault):
    cfg, params, hidden, got = attention_alone
    akw = dict(n_head=4, n_kv_head=2, head_dim=16, sliding_window=WINDOW,
               rope_theta=cfg.rope_theta, eps=cfg.rms_norm_eps)
    for kind, layer in ((SLIDING, 0), (FULL_ATTENTION, 3)):
        p, h = params[f"layers_{layer}"]["self_attn"], hidden[layer]
        err = _rel(got[kind], reference.attention(kind, p, h, fault=fault,
                                                  **akw))
        applies = fault is not None and not (
            fault in ("rope_on_full",) and kind == SLIDING
            or fault in ("no_rope_on_sliding", "window+1")
            and kind == FULL_ATTENTION)
        if applies:
            assert err > 1e-2, (fault, kind, err)
        else:
            assert err < 1e-5, (fault, kind, err)


@pytest.mark.parametrize("fault", [None, *reference.EXPERT_FAULTS])
def test_the_expert_layer_alone_against_each_named_fault(fault):
    M, I = 32, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    cfg = _moe(2, 4)
    layer = MoELayer(cfg, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = compare.init(layer, x)
    p = jax.tree_util.tree_map(lambda a: a * 20 if a.ndim >= 2 else a, p)
    p["gate"][STATE_LEAF] = _bias(7, 0.3)
    got = layer.apply({"params": p}, x)[0]
    want = reference.expert_ffn(p, x, top_k=TOP_K, route_scale=2.826,
                                first_expert=2, fault=fault)
    if fault is None:
        np.testing.assert_allclose(got, want, atol=2e-4)
        # at b = 0 a layer that ignores the bias reads sound: why the
        # cell's comparison runs under a seeded one
        p0 = dict(p, gate=dict(p["gate"], **{STATE_LEAF: jnp.zeros(ROUTED)}))
        np.testing.assert_allclose(
            layer.apply({"params": p0}, x)[0],
            reference.expert_ffn(p0, x, top_k=TOP_K, route_scale=2.826,
                                 first_expert=2, fault="bias_ignored"),
            atol=2e-4)
    else:
        assert _rel(got, want) > 1e-2, fault


def test_routing_fields_by_hand():
    logits = jnp.asarray([[2.0, 0.0, -1.0, 1.0], [0.0, 0.0, 3.0, -3.0]])
    s = jax.nn.sigmoid(logits)
    bias = jnp.asarray([-10.0, 0.0, 10.0, 0.0])
    w, e, counts, _, _ = topk_routing(
        logits, 2, norm_topk_prob=True, score_func="sigmoid", bias=bias,
        route_scale=2.0)
    # the bias picks expert 2 everywhere and never expert 0 ...
    assert e.tolist() == [[2, 3], [2, 1]] and counts.tolist() == [0, 1, 2, 1]
    # ... and the weights are the scores of the picked, not score + bias
    for t in range(2):
        chosen = s[t, e[t]]
        np.testing.assert_allclose(w[t], 2.0 * chosen / (chosen.sum() + 1e-20),
                                   rtol=1e-6)
    # the defaults are the routing the older models trace
    w0, e0, *_ = topk_routing(logits, 2)
    np.testing.assert_allclose(w0, jax.lax.top_k(jax.nn.softmax(logits), 2)[0])
    # the update: up where an expert got fewer pairs than the mean
    b = bias_update(jnp.asarray([0, 1, 2, 1]), jnp.zeros(4), 0.001)
    np.testing.assert_array_equal(b, np.float32([0.001, 0, -0.001, 0]))
    np.testing.assert_array_equal(
        b, reference.bias_update([0, 1, 2, 1], np.zeros(4), 0.001))


# ----------------------------------------------------------------------
# the shares add up
# ----------------------------------------------------------------------
def test_the_eight_shares_and_the_shared_expert_once_add_up():
    """The routed parts that the eight shares of 2 experts give, plus the
    shared expert counted once, are the uncut reference's layer; program
    and reference agree on every share (shared expert whole in each);
    every pair is multiplied somewhere exactly once."""
    M, I, R, k = 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    full = dataclasses.replace(_moe(), num_experts=R, top_k=k)
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = compare.init(whole, x)
    p = jax.tree_util.tree_map(lambda a: a * 20 if a.ndim >= 2 else a, p)
    p["gate"][STATE_LEAF] = jnp.asarray(
        np.random.default_rng(3).normal(0, 0.3, R), jnp.float32)
    ref = dict(top_k=k, route_scale=2.826)
    uncut = reference.expert_ffn(p, x, first_expert=0, **ref)
    np.testing.assert_allclose(whole.apply({"params": p}, x)[0], uncut,
                               atol=5e-5)
    shared = SharedExpert(M, I, dtype=jnp.float32).apply(
        {"params": p["shared"]}, x)
    total, multiplied = shared, 0
    for first in range(0, R, 2):
        cfg = dataclasses.replace(full, num_experts=2, routed_experts=R,
                                  first_expert=first)
        mine = dict(p, experts={n: w[first:first + 2]
                                for n, w in p["experts"].items()})
        part, _, stats = MoELayer(cfg, model_dim=M, hidden_dim=I,
                                  dtype=jnp.float32).apply(
            {"params": mine}, x, return_stats=True)
        np.testing.assert_allclose(
            part, reference.expert_ffn(mine, x, first_expert=first, **ref),
            atol=5e-5)
        assert int(stats["dropped"]) == 0
        held = int(stats["tokens_per_expert"][first:first + 2].sum())
        assert int(stats["elsewhere"]) == 128 * k - held
        total, multiplied = total + (part - shared), multiplied + held
    assert multiplied == 128 * k
    np.testing.assert_allclose(total, uncut, atol=1e-4)


# ----------------------------------------------------------------------
# the bias as a state leaf of the engine
# ----------------------------------------------------------------------
def _engine(gas=1, **opt):
    import deepspeed_tpu

    mesh_lib.set_mesh(None)
    cfg = _config(2, 4, dtype=jnp.bfloat16, loss_chunk=16)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "adamw8bit",
                          "params": {"lr": 1e-2, "weight_decay": 0.5, **opt}},
            "zero_optimization": {"stage": 3}, "gradient_clipping": 1.0,
            "mesh": {"fsdp": -1}, "steps_per_print": 10**9})
    engine.init_params()
    rows = engine.train_batch_size
    ids = np.random.default_rng(0).integers(0, VOCAB, (rows, S)).astype(
        np.int32)
    return engine, cfg, {"input_ids": ids, "labels": ids}


@pytest.fixture(scope="module")
def built():
    """ONE engine at ``gas`` 1 and a copy of its state as built (the step
    donates what it is given): a test that trains takes it back to that
    state (:func:`_as_built`) and compiles no step a second time."""
    engine, cfg, batch = _engine()
    return engine, cfg, batch, jax.tree_util.tree_map(jnp.copy, engine.state)


def _as_built(built):
    engine, cfg, batch, state = built
    engine.drain_step_stats(wait=True)
    engine._state = jax.tree_util.tree_map(jnp.copy, state)
    return engine, cfg, batch


def _biases(engine):
    return np.stack([np.asarray(
        engine.state.params[f"layers_{i}"]["moe"]["gate"][STATE_LEAF])
        for i in range(1, 5)])


def _seed_biases(engine):
    params = jax.tree_util.tree_map(lambda a: a, engine.state.params)
    for i in range(1, 5):
        gate = params[f"layers_{i}"]["moe"]["gate"]
        gate[STATE_LEAF] = jax.device_put(_bias(i, 0.05),
                                          gate[STATE_LEAF].sharding)
    engine._state = engine.state.replace(params=params)


def test_the_optimizer_never_sees_the_state_leaf(built):
    engine = built[0]
    trained, held = engine._split_state_leaves(engine.state.params)
    assert [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(held)[0]] == [
        f"['layers_{i}']['moe']['gate']['{STATE_LEAF}']" for i in range(1, 5)]
    assert state_leaves.merge(trained, held).keys() == \
        engine.state.params.keys()
    n_params = len(jax.tree_util.tree_leaves(engine.state.params))
    for tree, what in ((engine.state.opt_state, "moments"),
                       (engine._grad_specs, "gradients"),
                       (engine._opt_specs, "optimizer specs")):
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(
                     tree, is_leaf=lambda x: x is None)[0]]
        assert not any(STATE_LEAF in p for p in paths), what
    # int8 moments, uint8 roots and two scales a trained leaf, two counters
    assert len(jax.tree_util.tree_leaves(engine.state.opt_state)) == \
        4 * (n_params - 4) + 2
    # a model that declares none keeps the whole tree, object for object
    other = object.__new__(type(engine))
    other.model = object()
    tree = {"a": {"b": 1}}
    assert other._split_state_leaves(tree) == (tree, {})
    assert other._split_state_leaves(tree)[0] is tree


@pytest.mark.parametrize("gas", [1, 2])
def test_the_step_moves_the_bias_by_the_rate_and_nothing_else_does(
        gas, monkeypatch, built):
    """Exactly ``reference.bias_update`` of the step's counts, summed over
    the micro-batches BEFORE the sign, from a bias that is not zero: weight
    decay (0.5 here) or an Adam update would show in the last bit."""
    engine, cfg, batch = _as_built(built) if gas == 1 else _engine(gas)
    _seed_biases(engine)
    steps, book = [], moe_lib.record_stats
    monkeypatch.setattr(moe_lib, "record_stats", lambda stats: (
        steps.append(np.asarray(stats["tokens_per_expert"])), book(stats)))
    before = _biases(engine)
    loss0 = float(engine.eval_batch(batch))
    np.testing.assert_array_equal(_biases(engine), before)     # eval: no move
    for _ in range(3):
        loss = float(engine.train_batch(batch))
    engine.drain_step_stats(wait=True)
    assert np.isfinite(loss) and loss < loss0
    assert len(steps) == 3 and steps[0].shape == (4, ROUTED)
    # all the step's pairs: micro-batches x rows x tokens x top-k a layer
    assert (steps[0].sum(1) == engine.train_batch_size * S * TOP_K).all()
    want = before.copy()
    for counts in steps:
        want = np.stack([reference.bias_update(c, b, RATE)
                         for c, b in zip(counts, want)])
    got = _biases(engine)
    np.testing.assert_array_equal(got, want)
    moved = np.abs(got - before)
    assert moved.max() <= 3 * RATE * 1.001 and moved.max() > 0
    # every other leaf did move (lr 1e-2): the step is one step
    assert float(engine.state.step) == 3


@pytest.fixture()
def empty_registry():
    """The program's registry emptied for one test and put back after it:
    other modules keep handles to metrics they made at import (the goodput
    gauges), which a bare ``clear()`` would orphan for the tests that run
    later in the same process."""
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    yield reg
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


def test_the_registry_and_the_dispatch_report_show_the_state_leaf(
        empty_registry):
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    engine, cfg, batch = _engine()
    for _ in range(2):
        engine.train_batch(batch)
    engine.drain_step_stats(wait=True)
    rows = {(s, i): n for s, i, _, n in dispatch_report()}
    assert rows[("state_leaf", "compiled_step")] == 4       # staged once
    snap = empty_registry.snapshot()
    assert snap["moe_bias_updates_total"]["samples"][0]["value"] == 8
    gauge = {(s["labels"]["layer"], s["labels"]["stat"]): s["value"]
             for s in snap["moe_expert_bias"]["samples"]}
    assert len(gauge) == 8
    # the gauge is the bias the last finished step selected WITH: one update
    for layer in "0123":
        assert gauge[(layer, "max")] == pytest.approx(RATE)
        assert gauge[(layer, "min")] == pytest.approx(-RATE)
    assert snap["moe_tokens_per_expert"]["samples"]
    assert snap["moe_dropped_tokens_total"]["samples"][0]["value"] == 0


def test_the_bias_round_trips_a_checkpoint(tmp_path, built):
    engine, cfg, batch = _as_built(built)
    for _ in range(2):
        engine.train_batch(batch)
    want = _biases(engine)
    assert np.abs(want).max() > 0
    engine.save_checkpoint(str(tmp_path), tag="t")
    fresh, _, _ = _as_built(built)
    assert not np.any(_biases(fresh)) and float(fresh.state.step) == 0
    fresh.load_checkpoint(str(tmp_path), tag="t")
    np.testing.assert_array_equal(_biases(fresh), want)
    assert float(fresh.state.step) == 2
    # and trains on from there
    assert np.isfinite(float(fresh.train_batch(batch)))


def test_the_models_rule_on_an_unrolled_and_on_a_scanned_stack():
    """``update_state_leaves`` maps a layer's leaf to its row of the
    statistics: by the block's number less the leading dense blocks in an
    unrolled stack, by the leading layer axis in a scanned one."""
    counts = jnp.asarray(np.random.default_rng(0).integers(0, 9, (4, ROUTED)))
    stats = {"tokens_per_expert": counts}
    model = LlamaForCausalLM(_config())
    held = {f"layers_{i}": {"moe": {"gate": {STATE_LEAF: _bias(i)}}}
            for i in range(1, 5)}
    new = model.update_state_leaves(held, stats)
    assert new.keys() == held.keys()
    for i in range(1, 5):
        np.testing.assert_array_equal(
            new[f"layers_{i}"]["moe"]["gate"][STATE_LEAF],
            reference.bias_update(counts[i - 1], _bias(i), RATE))
    scanned = LlamaForCausalLM(_config(
        scan_layers=True, num_dense_layers=0, num_hidden_layers=4,
        layer_types=None, sliding_window=None, rope_layer_types=None))
    stacked = jnp.stack([_bias(i) for i in range(1, 5)])
    new = scanned.update_state_leaves(
        {"layers": {"moe": {"gate": {STATE_LEAF: stacked}}}}, stats)
    for i in range(4):
        np.testing.assert_array_equal(
            new["layers"]["moe"]["gate"][STATE_LEAF][i],
            reference.bias_update(counts[i], stacked[i], RATE))
    # and the scanned stack's leaf is found by the same name
    ids = jnp.zeros((1, 8), jnp.int32)
    shapes = meta.unbox(jax.eval_shape(scanned.init, jax.random.PRNGKey(0),
                                       ids)["params"])
    rest, found = state_leaves.split(shapes, scanned.is_state_leaf)
    assert found["layers"]["moe"]["gate"][STATE_LEAF].shape == (4, ROUTED)
    assert STATE_LEAF not in rest["layers"]["moe"]["gate"]


def test_split_and_merge_are_inverse_on_nested_dicts():
    tree = {"a": {"x": 1, "s": 2}, "b": {"c": {"s": 3}}, "d": 4}
    rest, held = state_leaves.split(tree, lambda path: path[-1] == "s")
    assert rest == {"a": {"x": 1}, "d": 4}
    assert held == {"a": {"s": 2}, "b": {"c": {"s": 3}}}
    assert state_leaves.merge(rest, held) == tree
    assert state_leaves.split(tree, lambda path: False) == (tree, {})


# ----------------------------------------------------------------------
# what is refused
# ----------------------------------------------------------------------
@pytest.mark.parametrize("field,kw", [
    ("num_dense_layers", dict(num_dense_layers=1)),
    ("mup_enabled", dict(mup_enabled=True)),
    ("qk_norm", dict(qk_norm="head")),
    ("attn_gate", dict(attn_gate=True)),
    ("rope_layer_types", dict(rope_layer_types=[SLIDING])),
    ("sandwich_norm", dict(sandwich_norm=True)),
    ("moe.score_func", dict(moe=_moe(**{**AFMOE, "bias_update_rate": None,
                                        "num_shared_experts": 0,
                                        "route_scale": 1.0}))),
    ("moe.bias_update_rate", dict(moe=MoEConfig(
        num_experts=ROUTED, top_k=TOP_K, drop_tokens=False,
        expert_act="swiglu", bias_update_rate=RATE))),
    ("moe.num_shared_experts", dict(moe=MoEConfig(
        num_experts=ROUTED, top_k=TOP_K, drop_tokens=False,
        expert_act="swiglu", num_shared_experts=1))),
])
def test_decode_with_a_new_field_raises_by_name(field, kw):
    base = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=40,
                moe=MoEConfig(num_experts=ROUTED, top_k=TOP_K,
                              drop_tokens=False, expert_act="swiglu"))
    LlamaConfig(**{**base, **kw})                       # trains
    with pytest.raises(NotImplementedError, match=field.split(".")[-1]):
        LlamaConfig(**{**base, **kw}, decode=True)


def test_what_else_is_not_written_raises():
    with pytest.raises(NotImplementedError, match="dropless"):
        MoEConfig(num_experts=8, top_k=2, score_func="sigmoid")
    with pytest.raises(ValueError, match="score_func"):
        MoEConfig(num_experts=8, drop_tokens=False, score_func="tanh")
    with pytest.raises(NotImplementedError, match="SwiGLU"):
        MoEConfig(num_experts=8, drop_tokens=False, num_shared_experts=1)
    with pytest.raises(ValueError, match="qk_norm"):
        LlamaConfig(qk_norm="rows")
    with pytest.raises(ValueError, match="no moe"):
        LlamaConfig(num_dense_layers=1)
    cfg = _config(scan_layers=True, layer_types=None, sliding_window=None,
                  rope_layer_types=None)
    with pytest.raises(NotImplementedError, match="leading dense"):
        LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))


def test_flops_per_token_counts_the_dense_layer_the_gate_and_the_shared():
    cfg = _config(2, 4)
    E, H, D, KV, I, F = 32, 4, 16, 2, 24, 40
    attn = 3 * E * H * D + 2 * E * KV * D
    sparse = 3 * E * I * (TOP_K * 4 / ROUTED + 1) + E * ROUTED
    n = 2 * cfg.padded_vocab_size * E + 5 * attn + 3 * E * F + 4 * sparse
    keys = 4 * min(S, WINDOW) + S
    assert LlamaForCausalLM(cfg).flops_per_token() == \
        6.0 * n + 12 * H * D * keys


# ----------------------------------------------------------------------
# the older cells' traced programs
# ----------------------------------------------------------------------
# sha256 of one remat block forward + backward (value_and_grad with the
# stats), StableHLO less locations.  Pinned by PR 33 to its parent 1945007;
# pinned again, on purpose, to PR 36's own lowering (parent d6ba3a1 reads
# olmoe 76ffe05e..., mellum2 856fc992...): that PR changed what every
# dropless router traces (``_count_ids`` for both ``jnp.bincount``s, the
# masked sum for ``lax.top_k``'s values); and to PR 39's (parent 0f53bdd
# reads olmoe a4404718..., mellum2 168196c5...), which changed what every
# chunked head traces (``_fused_ce``: ``dh`` and ``dW`` made in the forward
# rule).  The point stands: a later
# model_config PR's new branches must not reach the older cells' programs
PARENT_STABLEHLO = {
    "olmoe": "46a9075039aef6b65dcb1ed43409f3824c7d90a410b27b7f651504d6e3b5b44c",
    "mellum2": "68de2c369a27798f5bdafa062805e3327b58755c5d6834175a20ef395bd9971d",
}


def _older_cell(name):
    common = dict(vocab_size=160, hidden_size=32, num_hidden_layers=1,
                  num_attention_heads=4, max_position_embeddings=48,
                  loss_chunk=16, scan_layers=False, remat=True,
                  remat_policy="dots_saveable+flash", vocab_pad_multiple=32)
    if name == "olmoe":
        return LlamaConfig(**common, intermediate_size=24, qk_norm=True,
                           moe=MoEConfig(
                               num_experts=8, top_k=4, drop_tokens=False,
                               expert_act="swiglu", aux_loss_weight=0.01,
                               z_loss_weight=0.001))
    return LlamaConfig(
        **common, num_key_value_heads=2, head_dim=16, intermediate_size=999,
        moe_intermediate_size=24, rms_norm_eps=1e-6,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=12, remat_prevent_cse=True, rope_parameters={
            "full_attention": {"rope_type": "yarn", "rope_theta": 100.0,
                               "factor": 4, "beta_fast": 2, "beta_slow": 0.25,
                               "original_max_position_embeddings": 16},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 100.0}},
        moe=MoEConfig(num_experts=4, top_k=4, drop_tokens=False,
                      norm_topk_prob=True, expert_act="swiglu",
                      aux_loss_weight=0.1, routed_experts=8, first_expert=2))


@pytest.mark.parametrize("name", sorted(PARENT_STABLEHLO))
def test_the_older_cells_blocks_lower_to_the_parents_stablehlo(name):
    """Every new branch hangs on a field the older configurations leave at
    its default, so what they trace is what the parent traced: op for op,
    scope for scope (PR 31 was refused for set-up seconds of code that an
    older cell traced)."""
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(None)
    try:
        model = LlamaForCausalLM(_older_cell(name))
        ids = jnp.zeros((2, 48), jnp.int32)
        shapes = meta.unbox(jax.eval_shape(
            model.init, jax.random.PRNGKey(0), ids)["params"])

        def loss(p, ids):
            out = model.apply({"params": p}, ids, labels=ids,
                              deterministic=False)
            return out["loss"], out["stats"]

        text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
            shapes, ids).as_text()
    finally:
        mesh_lib.set_mesh(prev)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STABLEHLO[name]
    assert not model.cfg.afmoe_fields
    assert not any(LlamaForCausalLM.is_state_leaf(
        tuple(str(getattr(k, "key", k)) for k in path))
        for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0])
