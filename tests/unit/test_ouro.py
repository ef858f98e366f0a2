"""Ouro-2.6B as a ``LlamaConfig`` (PR 64: ``total_ut_steps``, a looped
stack) against ``benchmark/reference/ouro.py`` on seeded weights at a small
size (hidden 32, 2 heads of 16, 3 layers run 4 times): the loss, the exit
distribution, each exit's nll and the gradient of every leaf UNDER A SEEDED
GATE (at g = 1/2 everywhere half the named faults read sound), unrolled and
scanned; the sharing itself, a block leaf's gradient the sum of four copies';
one pass as today's model; the chunked head's per-row form; every named
fault; ZeRO-3 on the CPU mesh with ``adamw8bit``; what is refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models import common
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.runtime.optimizers import decay_mask
from deepspeed_tpu.telemetry import get_registry

from . import reference_compare as compare
from .flash_parent_sweep import traced_digest
from .reference_compare import rel as _rel

reference = load_module(ROOT, "reference", "ouro")

S, VOCAB, E, L, T, EPS, BETA = 32, 120, 32, 3, 4, 1e-6, 0.05
TOL = 2e-4


def _config(**kw):
    base = dict(vocab_size=VOCAB, hidden_size=E, num_hidden_layers=L,
                num_attention_heads=2, head_dim=16, intermediate_size=48,
                max_position_embeddings=S, rms_norm_eps=EPS, rope_theta=1e6,
                sandwich_norm=True, total_ut_steps=T,
                exit_entropy_weight=BETA, scan_layers=False, remat=True,
                remat_policy="dots_saveable", loss_chunk=40,
                dtype=jnp.float32, attn_impl="jnp", vocab_pad_multiple=32)
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg, **kw):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                head_dim=cfg.head_dim, vocab_size=cfg.vocab_size,
                eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                ut_steps=cfg.total_ut_steps, beta=cfg.exit_entropy_weight,
                **kw)


def seed_gate(params, seed=0, width=E):
    """A gate whose g spreads over (0.1, 0.9): on a normed stream the logit
    ``h . w`` has a standard deviation of ~1.2 at these weights."""
    rng = np.random.default_rng([seed, 64])
    params["exit_gate"] = {
        "kernel": jnp.asarray(rng.normal(0, 1.2 / np.sqrt(width),
                                         (width, 1)), jnp.float32),
        "bias": jnp.asarray([0.3], jnp.float32)}
    return params


def _stacked(params):
    """The unrolled tree as the scanned stack holds it."""
    out = {k: v for k, v in params.items() if not k.startswith("layers_")}
    out["layers"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[params[f"layers_{i}"] for i in range(L)])
    return out


def _unstacked(tree):
    out = {k: v for k, v in tree.items() if k != "layers"}
    for i in range(L):
        out[f"layers_{i}"] = jax.tree_util.tree_map(lambda x: x[i],
                                                    tree["layers"])
    return out


@pytest.fixture(scope="module")
def setup():
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, S)),
                      jnp.int32)
    params = seed_gate(compare.init(model, ids, labels=ids, scale=6.0))
    # norm weights off their ones: a norm left out or put elsewhere shows
    rng = np.random.default_rng(2)
    params = jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(rng.normal(0, 0.2, a.shape), a.dtype)
        if a.ndim == 1 and a.shape[0] == E else a, params)
    return cfg, model, ids, params


@pytest.fixture(scope="module")
def wanted(setup):
    """The reference's parts and the gradient of every leaf."""
    cfg, _, ids, params = setup
    kw = _reference_kwargs(cfg)
    parts = reference.loss_parts(params, ids, **kw)
    grads = jax.grad(reference.training_loss)(params, ids, **kw)
    return parts, grads


def _program(cfg, params, ids):
    model = LlamaForCausalLM(cfg)
    return compare.forward_and_gradients(
        lambda p: model.apply({"params": p}, ids, labels=ids), params)


@pytest.fixture(scope="module")
def program(setup):
    cfg, _, ids, params = setup
    return _program(cfg, params, ids)


def _check_parts(out, parts):
    stats = out["stats"]
    np.testing.assert_allclose(out["loss"], parts["loss"], rtol=TOL)
    np.testing.assert_allclose(stats["exit_p"], parts["exit_p"], rtol=TOL)
    np.testing.assert_allclose(stats["exit_nll"], parts["exit_nll"], rtol=TOL)
    assert stats["lm_loss"] == stats["exit_nll"][-1]
    np.testing.assert_allclose(
        stats["exit_step_mean"],
        (np.asarray(parts["exit_p"]) * np.arange(1, T + 1)).sum(), rtol=TOL)


def test_the_gate_is_spread_and_the_exits_differ(wanted):
    parts, _ = wanted
    p = np.asarray(parts["exit_p"])
    assert abs(p.sum() - 1.0) < 1e-5 and p.min() > 0.03, p
    nll = np.asarray(parts["exit_nll"])
    assert np.ptp(nll) > 10 * TOL * nll.mean(), nll


def test_loss_exits_and_every_gradient_match_the_reference(program, wanted):
    out, grads = program
    parts, want = wanted
    _check_parts(out, parts)
    paths, _ = compare.compare_leaves(grads, want, tol=TOL, measure="norm")
    assert len(paths) == 3 + 2 + L * 11


def test_the_scanned_stack_runs_the_same_loop(setup, wanted):
    cfg, _, ids, params = setup
    out, grads = _program(_config(scan_layers=True), _stacked(params), ids)
    parts, want = wanted
    _check_parts(out, parts)
    compare.compare_leaves(_unstacked(grads), want, tol=TOL, measure="norm")


def test_a_shared_leafs_gradient_is_the_sum_of_four_copies(setup, program):
    """A model whose four passes are given four separate copies of the
    stack's leaves holding the same values (the reference, ``copies``): the
    program's gradient of a block leaf and of the final norm is the SUM of
    the four."""
    cfg, _, ids, params = setup
    shared = {k: v for k, v in params.items()
              if k.startswith("layers_") or k == "norm"}
    kw = _reference_kwargs(cfg)
    each = jax.grad(lambda copies: reference.training_loss(
        params, ids, copies=copies, **kw))([shared] * T)
    total = jax.tree_util.tree_map(lambda *g: sum(g), *each)
    _, grads = program
    compare.compare_leaves({k: grads[k] for k in shared}, total, tol=TOL,
                           measure="norm")
    one = np.asarray(each[0]["layers_1"]["up_proj_kernel"])
    assert _rel(one, total["layers_1"]["up_proj_kernel"]) > 0.3


def test_the_tree_is_one_passs_tree_plus_the_gate(setup):
    cfg, model, ids, params = setup
    plain = jax.eval_shape(lambda: LlamaForCausalLM(_config(
        total_ut_steps=1)).init(jax.random.PRNGKey(0), ids, labels=ids))
    plain = compare.meta.unbox(plain["params"])
    assert set(params) - set(plain) == {"exit_gate"}
    count = lambda t: sum(int(np.prod(x.shape))
                          for x in jax.tree_util.tree_leaves(t))
    assert count(params) == count(plain) + E + 1
    assert jax.tree_util.tree_structure(
        {k: v for k, v in params.items() if k != "exit_gate"}) \
        == jax.tree_util.tree_structure(plain)


def test_one_pass_is_todays_model_to_the_bit(setup):
    """``total_ut_steps`` 1 written out, with the entropy's weight at 0, is
    the model with neither set: the same traced initialiser (so the same
    leaves), the same traced loss and gradients, the same loss to the bit;
    and no ``ut/`` scope or gate in it."""
    _, _, ids, _ = setup
    unset = dict(vocab_size=VOCAB, hidden_size=E, num_hidden_layers=L,
                 num_attention_heads=2, head_dim=16, intermediate_size=48,
                 max_position_embeddings=S, sandwich_norm=True,
                 scan_layers=False, remat=True, loss_chunk=40,
                 dtype=jnp.float32, attn_impl="jnp", vocab_pad_multiple=32)
    a = LlamaForCausalLM(LlamaConfig(**unset))
    b = LlamaForCausalLM(LlamaConfig(**unset, total_ut_steps=1,
                                     exit_entropy_weight=0.0))
    init = lambda m: lambda key: m.init(key, ids, labels=ids)
    key = jax.random.PRNGKey(0)
    assert traced_digest(init(a), key) == traced_digest(init(b), key)
    params = compare.init(a, ids, labels=ids)
    step = lambda m: lambda p: jax.value_and_grad(
        lambda p: m.apply({"params": p}, ids, labels=ids)["loss"])(p)
    assert traced_digest(step(a), params) == traced_digest(step(b), params)
    text = str(jax.make_jaxpr(step(b))(params))
    assert "ut/" not in text and "exit_gate" not in text
    assert compare.apply(a, params, ids, labels=ids)["loss"] \
        == compare.apply(b, params, ids, labels=ids)["loss"]


# ----------------------------------------------------------------------
# the head's per-row form
# ----------------------------------------------------------------------
def _head_case(seed=0, B=3, rows=20, width=16, V=50, VP=64):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((B, rows, width)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((VP, width)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, (B, rows)), jnp.int32
                    ).at[0, :5].set(-100)
    wt = jnp.asarray(rng.random((B, rows)), jnp.float32)
    return h, w, t, wt, dict(vocab_size=V, padded_vocab_size=VP,
                             dtype=jnp.float32)


@pytest.mark.parametrize("chunk", [16, 25, 4096])
def test_the_per_row_form_against_full_logits(chunk):
    """Value, ``dh``, ``dW`` and - through the term a caller with learned
    weights adds - the gradient to the weights, against
    ``cross_entropy_loss`` on full logits."""
    h, w, t, wt, kw = _head_case()
    V, VP = kw["vocab_size"], kw["padded_vocab_size"]
    count = float((t != -100).sum())

    def fused(h, w, wt):
        held = jax.lax.stop_gradient(wt)
        loss, nll = common.chunked_lm_loss(
            h, w, t, chunk=chunk, weights=held, denominator=count, rows=True,
            **kw)
        return loss + ((wt - held) * nll).sum() / count, nll

    def full(h, w, wt):
        lg = jnp.where(jnp.arange(VP) < V, h @ w.T, -jnp.inf)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, jnp.where(t < 0, 0, t)[..., None], -1)[..., 0]
        nll = jnp.where(t < 0, 0.0, nll)
        return (nll * wt).sum() / count, nll

    (got, rows), g = jax.jit(jax.value_and_grad(fused, (0, 1, 2),
                                                has_aux=True))(h, w, wt)
    (want, nll), wg = jax.jit(jax.value_and_grad(full, (0, 1, 2),
                                                 has_aux=True))(h, w, wt)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(rows, nll, atol=1e-5)
    assert not np.asarray(rows)[0, :5].any()
    for a, b in zip(g, wg):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # the dense head's own weighted form reads the same value
    lg = jnp.where(jnp.arange(VP) < V, h @ w.T, jnp.finfo(jnp.float32).min)
    np.testing.assert_allclose(common.cross_entropy_loss(
        lg, t, weights=wt, denominator=count), want, rtol=1e-6)


# sha256 of what the callers without ``rows`` traced to at the parent commit
# (d97a2d3, ``traced_digest`` over value and gradients at _head_case())
PARENT_HEAD = {
    "unweighted": "6fd3335c26afb860b3ea9cc21f15d460bd810e4945ed3f9bc8817025b765d713",
    "weighted": "d39d3410915aa5819a6339a54e7a65a2411837cbfc201fd6ed20d3b90e5fd5c4",
}


@pytest.mark.parametrize("caller", sorted(PARENT_HEAD))
def test_the_old_callers_trace_to_what_they_did(caller):
    """The unweighted (every cell's head, the prediction block's) and the
    weighted (block diffusion's) callers: the same jaxpr as at the parent
    commit, so the same numbers bit for bit."""
    h, w, t, wt, kw = _head_case()
    extra = {} if caller == "unweighted" \
        else dict(weights=wt, denominator=60.0)
    common._fused_ce.cache_clear()
    fn = jax.value_and_grad(lambda h, w: common.chunked_lm_loss(
        h, w, t, chunk=16, **extra, **kw), (0, 1))
    assert traced_digest(fn, h, w) == PARENT_HEAD[caller]


# ----------------------------------------------------------------------
# the named faults
# ----------------------------------------------------------------------
@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_named_fault_is_refused(setup, program, fault):
    """The reference computed wrong in one named way differs from the
    program by more than the tolerance in the loss, an exit's share or an
    exit's nll."""
    cfg, _, ids, params = setup
    out, _ = program
    wrong = reference.loss_parts(params, ids, fault=fault,
                                 **_reference_kwargs(cfg))
    stats = out["stats"]
    errs = [abs(float(out["loss"]) / float(wrong["loss"]) - 1.0)]
    errs += list(np.abs(np.asarray(stats["exit_p"])
                        / np.asarray(wrong["exit_p"]) - 1.0))
    errs += list(np.abs(np.asarray(stats["exit_nll"])
                        / np.asarray(wrong["exit_nll"]) - 1.0))
    assert max(errs) > 10 * TOL, (fault, errs)


# ----------------------------------------------------------------------
# the engine: ZeRO-3 over the CPU mesh, int8 AdamW
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine(setup):
    """The engine over the eight CPU devices holding ``setup``'s weights,
    and ``setup``'s two rows four times over: a mean over the labelled
    tokens, so the loss and the gradients are the two rows'."""
    import deepspeed_tpu

    mesh_lib.set_mesh(None)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(_config()), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw8bit",
                          "params": {"lr": 1e-2, "weight_decay": 0.5}},
            "zero_optimization": {"stage": 3}, "gradient_clipping": 1.0,
            "mesh": {"fsdp": -1}, "steps_per_print": 10**9})
    eng.init_params()
    # (host copies: the step donates the state it is given)
    params = jax.tree_util.tree_map(np.asarray, setup[3])
    eng._state = eng.state.replace(params=jax.tree_util.tree_map(
        lambda a, old: jax.device_put(a, old.sharding), params,
        eng.state.params))
    ids = np.tile(np.asarray(setup[2]), (eng.train_batch_size // 2, 1))
    yield eng, params, {"input_ids": ids, "labels": ids}
    mesh_lib.set_mesh(None)


def test_zero3_on_the_mesh_gives_one_devices_loss_and_gradients(engine,
                                                                program):
    eng, params, batch = engine
    assert eng.mesh.shape["fsdp"] == len(jax.devices()) == 8
    eng.forward(batch)
    loss, grads = eng._pending
    eng._pending = None
    out, wgrads = program
    np.testing.assert_allclose(loss, out["loss"], rtol=1e-5)
    compare.compare_leaves(jax.tree_util.tree_map(np.asarray, grads), wgrads,
                           tol=1e-4, measure="norm")


def test_adamw8bit_takes_a_step_over_the_tree_and_books_the_gauges(engine):
    eng, params, batch = engine
    loss = float(eng.train_batch(batch))
    eng.drain_step_stats(wait=True)
    after = jax.tree_util.tree_map(np.asarray, eng.state.params)
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(np.isfinite(b).all() and (a != b).any()),
        params, after)
    assert np.isfinite(loss) and jax.tree_util.tree_all(moved), moved
    snap = get_registry().snapshot()
    shares = {s["labels"]["step"]: s["value"]
              for s in snap["ut_exit_p"]["samples"]}
    assert sorted(shares) == ["0", "1", "2", "3"]
    assert abs(sum(shares.values()) - 1.0) < 1e-5
    assert len(snap["ut_exit_nll"]["samples"]) == T
    mean = snap["ut_exit_step_mean"]["samples"][0]["value"]
    assert abs(mean - sum((int(t) + 1) * v for t, v in shares.items())) < 1e-5
    assert snap["ut_exit_entropy"]["samples"][0]["value"] > 0
    assert snap["lm_loss"]["samples"][0]["value"] > 0


def test_weight_decay_skips_the_gates_bias_alone(setup):
    _, model, _, params = setup
    mask = decay_mask(model)(params)
    skipped = [jax.tree_util.keystr(p) for p, on in
               jax.tree_util.tree_flatten_with_path(mask)[0] if not on]
    assert skipped == ["['exit_gate']['bias']"]
    assert decay_mask(LlamaForCausalLM(_config(total_ut_steps=1))) is None


# ----------------------------------------------------------------------
# what is refused
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw, named", [
    (dict(decode=True), "decode=True"),
    (dict(moe="routed"), "moe"),
    (dict(num_nextn_predict_layers=1), "multi-token-prediction"),
    (dict(diffusion=dict(block_length=4, mask_token_id=7)), "diffusion"),
    (dict(sa_config=dict(indexer_num_heads=2, indexer_head_dim=8,
                         indexer_num_kv_heads=1, topk=4)), "sa_config"),
    (dict(hc_mult=4, sandwich_norm=False), "hc_mult 4"),
    (dict(attn_impl="ring"), "attn_impl 'ring'"),
    (dict(attn_impl="ulysses"), "attn_impl 'ulysses'"),
])
def test_what_the_loop_cannot_run_yet_is_refused_by_name(kw, named):
    if kw.get("moe") == "routed":
        from deepspeed_tpu.parallel.moe import MoEConfig

        kw = dict(moe=MoEConfig(num_experts=4, top_k=2))
    with pytest.raises(NotImplementedError) as err:
        _config(**kw)
    assert "total_ut_steps 4 (a looped stack)" in str(err.value)
    assert named in str(err.value), str(err.value)


def test_pipeline_stages_have_no_way_in():
    """The refusals name pipeline stages too: the family has no
    ``pipeline_fns``, which is what the engine's pipeline path asks for."""
    assert not hasattr(LlamaForCausalLM, "pipeline_fns")
    with pytest.raises(ValueError, match="at least one pass"):
        _config(total_ut_steps=0)
