"""``drivers/train_lm.py`` for Keye-VL-2.0's language model (a learned
sparse selection on every attention layer): the same engine, data path,
window, fences and ``observed`` keys (its ``run``, unchanged), with the
set-up's comparison against the plain reference widened to what the loss
cannot see, and the selection's counters read after the window.  What this
module adds is checks, not a step of its own.

Before the window (``reference_check`` of the configuration file has each
limit and its readings), on one seeded row a rank:

a. ``eval_batch`` against the reference's cross-entropy + router losses +
   the sum over the layers of the indexer's loss, and its cross-entropy and
   its indexer losses each alone against the reference's (a second
   ``eval_batch`` of the same row with every label ignored is the losses
   that read no label: the same executable, nothing compiled);
b. every expert layer alone against the reference's share
   (``train_mellum2.check_experts``);

and on the first layer, on the reference forward's normalised hidden states
rounded to the compute type:

c. the indexer's scores alone (the program's ``Indexer`` leaves and the
   plain product of its bf16 operands) against the reference's, over the
   causal pairs: norm of the difference over norm;
d. the selection: over the queries that have more causal keys than they
   keep, the pairs that the reference and the program's kernels
   (``ops/indexed_attention.py select``: tau and cut) BOTH keep, over the
   larger of the two kept sets (a reference that keeps fewer pairs, or
   others, reads low either way);
e. the whole attention layer (``LlamaAttention``: projections, per-head
   norm, rotary, indexer, kernels, ``o_proj``) against
   ``reference.attention``, output and the layer's indexer loss;
f. the attention kernels alone on the reference's q, k, v rounded to the
   compute type against ``reference.attention_core``: the output, the
   gradients of q, k and v under a seeded cotangent, the indexer's loss and
   its gradients with respect to qI, kI and w (the only ones the indexer's
   leaves get), UNDER THE REFERENCE'S SELECTION (a pair that flips at the
   threshold is then not booked to the kernels), and all eight again under
   the kernels' OWN selection: the backward that the window runs.

After it, beside ``train_lm.run``'s own window checks: the kernels were
dispatched for every layer and the plain form for none.
"""
from __future__ import annotations

import concurrent.futures
import sys

import numpy as np

from benchmark import loadgen
from benchmark.drivers import train_lm, train_mellum2, train_trinity

FAMILIES = train_lm.FAMILIES
model_config = train_lm.model_config
_rel_err = train_mellum2._rel_err
_in_place_of = train_trinity._in_place_of
# the program's side of a comparison is staged and run on this thread while
# the reference's compiles on the caller's (XLA compiles outside the
# interpreter's lock)
_BESIDE = concurrent.futures.ThreadPoolExecutor(1)
GAUGES = {"indexer_loss": "indexer_loss",
          "sparse_kept_share": "sparse_attention_kept_share",
          "sparse_live_tile_share": "sparse_attention_live_tile_share"}
SCOPES = ("attn/indexer", "attn/select", "attn/indexer_loss")


def reference_kwargs(conf: dict) -> dict:
    kw = train_mellum2.reference_kwargs(conf)
    kw["n_index_head"] = int(conf["sa_config"]["indexer_num_heads"])
    kw["topk"] = int(conf["sa_config"]["topk"])
    kw["indexer_loss_weight"] = float(
        conf["model_options"].get("indexer_loss_weight", 1.0))
    return kw


def _attn_kwargs(kw: dict) -> dict:
    return {k: kw[k] for k in ("n_head", "n_kv_head", "head_dim",
                               "n_index_head", "topk", "rope_theta", "eps")}


def program_indexer(cfg, p_attn, h):
    """``(qI, kI, w)`` of the program's ``Indexer`` on ``h`` (B, S, E)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import Indexer

    pos = jnp.arange(h.shape[1])[None, :]
    return jax.jit(lambda p, h: Indexer(cfg).apply({"params": p}, h, pos))(
        p_attn["indexer"], h)


def read_indexer(cfg, reference, mine, theirs, topk: int, rows: int) -> dict:
    """Comparisons c and d over blocks of ``rows`` queries: ``{"scores":
    err, "overlap": pairs both keep over the larger kept set}`` of the
    program's indexer operands ``mine`` and
    the reference's ``theirs`` (each ``(qI, kI, w)``; ``theirs`` may come
    from a faulty reference, with its mask as a fourth entry)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import on_tpu
    from deepspeed_tpu.ops.indexed_attention import indexer_scores, select

    qi, ki, w = mine
    S = qi.shape[1]
    tau, cut = jax.jit(lambda *a: select(
        *a, topk, interpret=not on_tpu()))(qi, ki, w)
    mask = theirs[3]
    pos = jnp.arange(S)

    @jax.jit
    def block(t0, qi, ki, w, tau, cut, ref, mask):
        def at(x):
            return jax.lax.dynamic_slice_in_dim(x, t0, rows, 1)

        t = t0 + jnp.arange(rows)
        causal = pos[None, :] <= t[:, None]
        got = indexer_scores(at(qi), ki, at(w))
        want = reference.indexer_scores(*ref, t0, rows)
        d = jnp.where(causal, got - want, 0.0)
        tau_b, cut_b = at(tau)[..., None], at(cut)[..., None]
        kept = causal & ((got > tau_b) | ((got == tau_b)
                                          & (pos[None, None, :] <= cut_b)))
        judged = (t >= topk)[None, :, None]
        theirs, kept = at(mask) & judged, kept & judged
        return ((d * d).sum(), jnp.where(causal, want * want, 0.0).sum(),
                (theirs & kept).sum(), theirs.sum(), kept.sum())

    sums = np.zeros(5)
    for t0 in range(0, S, rows):
        sums += np.asarray(jax.device_get(block(
            t0, qi, ki, w, tau, cut, theirs[:3], mask)), np.float64)
    larger = max(sums[3], sums[4])
    return {"scores": float(np.sqrt(sums[0] / sums[1])),
            "overlap": float(sums[2] / larger) if larger else 1.0}


def read_layer(cfg, reference, p_attn, h, kw: dict, **wrong) -> dict:
    """Comparison e: ``{"out": err, "indexer_loss": difference}``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    module = LlamaAttention(cfg)
    pos = jnp.arange(h.shape[1])[None, :]
    got, ys = jax.jit(lambda p, h: module.apply({"params": p}, h, pos, None))(
        p_attn, h)
    want, l_i = reference.attention(p_attn, h, **_attn_kwargs(kw), **wrong)
    return {"out": _rel_err(got, want),
            "indexer_loss": float(ys["indexer_loss"]) - float(l_i)}


CORE_NAMES = ("out", "dq", "dk", "dv", "L_I", "dqI", "dkI", "dw")


def read_core(cfg, reference, qkv, mine, theirs, topk: int, seed: int,
              **wrong) -> dict:
    """Comparison f: ``{"out": err, "dq": .., "dk": .., "dv": .., "L_I":
    difference, "dqI": .., "dkI": .., "dw": .., "own out": .., "own dq": ..,
    ...}`` of the kernels on ``qkv`` (the reference's, rounded to the compute
    type) and the program's indexer operands ``mine``, against the
    reference's core on its own ``theirs``: once under the reference's
    selection and once (``own ...``) under the kernels' own, the backward
    the step runs.  ``wrong`` goes to the reference."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.indexed_attention import (impl_of,
                                                     indexed_attention)

    q, k, v = (t.astype(cfg.dtype) for t in qkv)
    cot = jax.random.normal(jax.random.PRNGKey(seed % (2**31 - 1)), q.shape,
                            jnp.float32).astype(cfg.dtype)
    impl = impl_of(cfg.attn_impl)

    @jax.jit
    def program(mask, cot, *ops):
        """As ``reference.attention_core`` returns them."""
        def both(*ops):
            r = indexed_attention(*ops, topk=topk, impl=impl, selection=mask)
            return r.out, r.kl
        (out, kl), vjp = jax.vjp(both, *ops)
        grads = vjp((cot, jnp.full_like(kl, 1.0 / kl.shape[1])))
        return (out,) + grads[:3] + (kl.mean(),) + grads[3:]

    def read(got, want):
        return {name: float(a) - float(b) if name == "L_I" else _rel_err(a, b)
                for name, a, b in zip(CORE_NAMES, got, want)}

    got = _BESIDE.submit(program, theirs[3], cot, q, k, v, *mine)
    want = reference.attention_core(q, k, v, *theirs[:3], cot, topk=topk,
                                    selection=theirs[3], **wrong)
    out = read(got.result(), want)
    own = read(program(None, cot, q, k, v, *mine), want)
    out.update({"own " + name: e for name, e in own.items()})
    return out


def core_limit(tol: dict, name: str) -> float:
    """The limit of ``reference_check`` that reading ``name`` of
    :func:`read_core` is held to."""
    own, _, what = name.rpartition(" ")
    if what == "L_I":
        return tol["layer_indexer_loss_abs_tol"]
    kind = ("" if what == "out" else "indexer_grad_"
            if what in ("dqI", "dkI", "dw") else "grad_")
    return tol[("own_selection_" if own else "core_") + kind + "rel_tol"]


def check_attention(ctx, cfg, conf, reference, params, h) -> None:
    """Comparisons c to f on the first layer's normalised input ``h``."""
    tol = conf["reference_check"]
    kw = reference_kwargs(conf)
    topk = kw["topk"]
    p_attn = next(iter(reference.layers(params, 1)))["self_attn"]
    h = h.astype(cfg.dtype)
    mine = program_indexer(cfg, p_attn, h)
    theirs = reference.indexer(p_attn, h, **kw)
    theirs += (reference.selection(*theirs, topk=topk),)
    read = read_indexer(cfg, reference, mine, theirs, topk,
                        min(int(tol["check_rows"]), h.shape[1]))
    ctx.log(f"indexer check: scores |program - reference| / |reference| "
            f"{read['scores']:.5f}; both keep {read['overlap']:.5f} of the "
            f"larger kept set")
    ctx.check(np.isfinite(read["scores"])
              and read["scores"] <= tol["indexer_score_rel_tol"],
              f"layer 0: the indexer's scores differ from the reference's "
              f"by {read['scores']:.5f} of their norm, more than "
              f"{tol['indexer_score_rel_tol']}")
    ctx.check(read["overlap"] >= tol["selection_overlap_floor"],
              f"layer 0: the program and the reference both keep "
              f"{read['overlap']:.5f} of the larger kept set, under "
              f"{tol['selection_overlap_floor']}")
    layer = read_layer(cfg, reference, p_attn, h, kw)
    ctx.log(f"attention check: layer 0 |program - reference| / |reference| "
            f"{layer['out']:.5f}, its indexer loss differs by "
            f"{layer['indexer_loss']:+.6f}")
    ctx.check(np.isfinite(layer["out"])
              and layer["out"] <= tol["attention_rel_tol"],
              f"layer 0: the attention layer's output differs from the "
              f"reference's by {layer['out']:.5f} of its norm, more than "
              f"{tol['attention_rel_tol']}")
    ctx.check(abs(layer["indexer_loss"]) <= tol["layer_indexer_loss_abs_tol"],
              f"layer 0: the indexer's loss differs from the reference's by "
              f"{layer['indexer_loss']:+.6f}, more than "
              f"{tol['layer_indexer_loss_abs_tol']}")
    core = read_core(cfg, reference, reference.qkv(p_attn, h, **kw), mine,
                     theirs, topk, ctx.seed)
    ctx.log("attention core check, under the reference's selection and "
            "under the kernels' own: "
            + " ".join(f"{n} {e:.5f}" for n, e in core.items()))
    for name, e in core.items():
        limit = core_limit(tol, name)
        ctx.check(np.isfinite(e) and abs(e) <= limit,
                  f"attention core, {name}: differs from the reference's by "
                  f"{e:.5f}, more than {limit}")


def program_losses(engine, ids):
    """``(eval_batch's loss, its cross-entropy alone, its router and indexer
    losses together)``: the second call ignores every label, which leaves
    the losses that read no label, and runs the executable of the first."""
    loss = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    rest = float(engine.eval_batch({
        "input_ids": ids, "labels": np.full_like(ids, -100)}))
    return loss, loss - rest, rest


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons a to f."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = _BESIDE.submit(program_losses, engine, ids)
    ffn_in, attn_in = [], []
    kw = reference_kwargs(conf)
    ce, aux, idx = (float(x) for x in reference.loss_parts(
        engine.state.params, ids, **kw, ffn_inputs=ffn_in,
        attn_inputs=attn_in))
    want = ce + aux + idx
    got, p_ce, rest = got.result()
    p_idx = rest - aux      # less the reference's router losses
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy {ce:.6f} + router losses {aux:.6f} + indexer "
            f"losses {idx:.6f})  difference {got - want:+.6f}; the model's "
            f"parts: cross-entropy {p_ce:.6f} ({p_ce - ce:+.6f}), indexer "
            f"losses {p_idx:.6f} ({p_idx - idx:+.6f})")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    ctx.check(abs(p_ce - ce) <= tol["ce_abs_tol"],
              f"the cross-entropy {p_ce} differs from the reference's {ce} "
              f"by more than {tol['ce_abs_tol']}")
    ctx.check(abs(p_idx - idx) <= tol["indexer_loss_abs_tol"],
              f"the indexer's loss {p_idx} differs from the reference's "
              f"{idx} by more than {tol['indexer_loss_abs_tol']}")
    train_mellum2.check_experts(ctx, cfg, conf, reference,
                                engine.state.params, ffn_in)
    h = attn_in[0]
    del ffn_in, attn_in
    check_attention(ctx, cfg, conf, reference, engine.state.params, h)
    return got


def gauges() -> dict:
    """The selection's gauges as the program last set them, by this
    module's names; a name without a sample is left out."""
    from deepspeed_tpu.telemetry import get_registry

    snap = get_registry().snapshot()
    out = {}
    for mine, theirs in GAUGES.items():
        samples = (snap.get(theirs) or {}).get("samples")
        if samples:
            out[mine] = float(samples[-1]["value"])
    return out


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under the indexer's three scopes, under them
    together and of the whole step, from a short profiler session of its
    own after the window (``engine.profile_device_scopes``)."""
    table = engine.profile_device_scopes(batches, steps=3, depth=4)
    out = {"step": table["device_ms_a_step"]}
    for scope in SCOPES:
        out[scope] = sum(
            r["ms_a_step"] for r in table["scopes"]
            if scope + "/" in r["scope"] + "/")
    out["indexer"] = sum(out[scope] for scope in SCOPES)
    ctx.log("device ms a step under " + ", ".join(
        f"{scope} {out[scope]:.3f}" for scope in SCOPES)
        + f" of {out['step']:.3f}")
    rest = sorted(table["scopes"], key=lambda r: -r["ms_a_step"])
    ctx.log("the heaviest scopes (ms a step): " + ", ".join(
        f"{r['scope']} {r['ms_a_step']:.2f}" for r in rest[:24]))
    return out


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own
    and the engine kept for the counters and the scopes."""
    import dataclasses
    import importlib

    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    if "sa_config" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no sa_config: "
                 "it cannot run a learned sparse selection "
                 f"({ctx.cell.name})")
    built = []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    with _in_place_of(train_lm, check_reference=check_reference,
                      build=build) as theirs:
        out = train_lm.run(ctx, reference)
    engine, cfg, conf = built[-1]
    train_mellum2.count_what_was_routed_here(ctx, out)
    obs = out["observed"]
    flops = importlib.import_module("benchmark." + conf["flops"])
    seq = int(ctx.sized(ctx.cell.traffic)["seq_len"])
    step_tokens = obs["tokens"] // obs["steps"] // obs["n_devices"]
    # the FORWARD scores alone: the three scopes' time holds no more (the
    # scores' backward runs inside the attention kernels)
    obs["indexer_flops_per_step"] = step_tokens \
        * flops.indexer_flops_per_token(conf, seq, 1)
    obs["indexer_bytes_per_step"] = step_tokens \
        * flops.indexer_bytes_per_token(conf, 1)
    obs.update(gauges())
    ctx.check(all(name in obs for name in GAUGES),
              f"the program set no {sorted(set(GAUGES) - set(obs))}")
    want = flops.kept_pair_share(seq, cfg.sa_config.topk)
    ctx.check(abs(obs.get("sparse_kept_share", want) - want) < 1e-6,
              f"the kernels kept {obs.get('sparse_kept_share')} of the "
              f"causal pairs; top-{cfg.sa_config.topk} of rows of {seq} "
              f"keeps {want}")
    report = [r for r in dispatch_report() if r[3]]
    if not ctx.rehearse:
        pallas = sum(n for s, i, r, n in report
                     if (s, i) == ("indexed_attention", "pallas"))
        ctx.check(pallas >= cfg.num_hidden_layers,
                  f"the indexed-attention kernels were dispatched {pallas} "
                  f"times for {cfg.num_hidden_layers} blocks: {report}")
        plain = [r for r in report if r[:2] == ("indexed_attention", "jnp")]
        ctx.check(not plain, f"attention took the plain form: {plain}")
    if ctx.trace and not ctx.rehearse:
        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size)
        obs["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
