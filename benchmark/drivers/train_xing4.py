"""``drivers/train_lm.py`` for Xing4.0-29B-A4B: the same engine, data,
window, fences and ``observed`` keys (its ``run``, unchanged), with
``drivers/train_joyai.py``'s comparisons against the plain reference (the
DeepSeek-V3 family's block is this model's too) and what a residual stream
of several lanes adds.

Before the window, on one seeded row a rank (``reference_check`` of the
configuration file has each limit and its readings):

a. ``eval_batch`` against the reference's CE_main + 0.3 CE_mtp, and the
   model's two losses each alone against the reference's;
b. one sparse block's attention and the prediction block's attention alone
   (``LlamaLatentAttention`` under the YaRN table and the scaled softmax)
   against ``reference.attention`` on the same normalised hidden states;
c. every expert layer alone, the prediction block's too, under a seeded
   bias that is not zero, shared expert included;
d. the prediction block alone from the reference's lane sum ``h``;
e. the leading dense FFN alone;
f. EVERY sublayer's hyper-connection alone (``HyperConnection``: the maps,
   what the sublayer reads, the stream after it) against
   ``reference.hyper_connection`` on the reference forward's lanes rounded
   to bf16, UNDER SEEDED GAINS AND BIASES of the size of the scores' spread
   with two logits of one row beyond the clamp (at the gains of 0.01 the
   weights are made with, a program that ignores the token reads sound):
   the largest of the 12.

After it: each layer's bias against ``reference.bias_update``
(``train_joyai.check_bias``), the kernels' dispatch, and on a traced run
the device time under the ``mhc/`` scopes from a short profiler session of
the engine's own.

The train step's executable is made on a thread of the engine's beside all
of this (``Engine.prepare_train_step``, started by this driver's ``build``
and joined before the warm-up's first step).
"""
from __future__ import annotations

import sys

import numpy as np

from benchmark.drivers import train_joyai, train_lm

FAMILIES = train_lm.FAMILIES
model_config = train_lm.model_config
reference_kwargs = train_joyai.reference_kwargs
_rel_err = train_joyai._rel_err
_in_place_of = train_joyai._in_place_of
# (``run`` puts this module's in its place for the duration)
_joyai_check = train_joyai.check_reference
HC_NAMES = ("attn_hc", "mlp_hc")
MHC_PARTS = ("pre", "post", "res", "u", "out")


class _OnTheHost(list):
    """What ``reference.hidden(hc_inputs=...)`` appends to: each sublayer's
    ``(stream, output)`` rounded to bf16 and kept on the host (twelve
    streams of 8192 x 14,336 do not wait on the device)."""

    def __iadd__(self, pairs):
        import jax.numpy as jnp

        for X, y in pairs:
            self.append(tuple(np.asarray(t.astype(jnp.bfloat16))
                              for t in (X, y)))
        return self


def seeded_maps(seed: int, index: int, p_hc: dict, lanes: int) -> dict:
    """A sublayer's leaves with gains and biases that make the maps move
    with the token: gains of +-0.2 to 0.4 (``m`` spreads ~2.4 at the
    initialiser's ``phi``), biases of the spread of the logits, and in row 0
    of ``b_res`` two entries beyond the clamp's 30, which the clamp makes
    equal and its absence does not."""
    import jax.numpy as jnp

    rng = np.random.default_rng([seed % (2**31 - 1), 62, index])
    out = dict(p_hc)
    for a in ("a_pre", "a_post", "a_res"):
        out[a] = jnp.asarray(rng.uniform(0.2, 0.4, 1)
                             * rng.choice([-1.0, 1.0]), jnp.float32)
    out["b_pre"] = jnp.asarray(rng.normal(0, 1, lanes), jnp.float32)
    out["b_post"] = jnp.asarray(rng.normal(0, 1, lanes), jnp.float32)
    b_res = rng.normal(0, 1.5, (lanes, lanes))
    b_res[0, :2] = 36.0, 33.0
    out["b_res"] = jnp.asarray(b_res, jnp.float32)
    return out


def program_sublayer(cfg):
    """``f(p_hc, X (B, S, n*E), y (B, S, E)) -> {pre, post, res, u, out}``:
    the program's module on one sublayer's leaves, in the reference's
    layout (tokens first)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import HyperConnection

    n = cfg.lanes

    def run(p, X, y):
        B, S = X.shape[:2]
        u, maps = HyperConnection(cfg).apply({"params": p}, X)
        out = HyperConnection.post(X, y, maps)
        return {"pre": maps.pre.T.reshape(B, S, n),
                "post": maps.post.T.reshape(B, S, n),
                "res": jnp.moveaxis(maps.res, -1, 0).reshape(B, S, n, n),
                "u": u, "out": out.reshape(B, S, n, -1)}

    return jax.jit(run)


def read_mhc(seed, cfg, conf, reference, leaves, hc_in, **ref_extra) -> list:
    """Comparison f: one error a sublayer (attention's then the FFN's of
    each block, the prediction block's last), the largest relative error
    of the five parts."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def worst(got, want):       # on the device: a stream is 470 MB in float32
        def rel(a, b):
            a, b = (t.astype(jnp.float32).ravel() for t in (a, b))
            return jnp.linalg.norm(a - b) / jnp.linalg.norm(b)

        return jnp.stack([rel(got[k], want[k]) for k in MHC_PARTS]).max()

    kw = reference_kwargs(conf)
    run = program_sublayer(cfg)
    errs = []
    for i, (X, y) in enumerate(hc_in):
        p = seeded_maps(seed, i, leaves[i // 2][HC_NAMES[i % 2]], cfg.lanes)
        X, y = jnp.asarray(X), jnp.asarray(y)
        got = run(p, X.reshape(*X.shape[:2], -1).astype(cfg.dtype),
                  y.astype(cfg.dtype))
        want = reference.hyper_connection(p, X, y, **dict(kw, **ref_extra))
        errs.append(float(worst(got, {k: want[k] for k in MHC_PARTS})))
    return errs


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons a to e as ``train_joyai.check_reference`` makes them (its
    reference forward also handing every sublayer's stream to the host, its
    attention reader the YaRN entry), then f; returns the engine's loss."""
    hc_in = _OnTheHost()

    def forward(reference, params, ids, conf, **extra):
        return theirs["reference_forward"](reference, params, ids, conf,
                                           hc_inputs=hc_in, **extra)

    def attn_kwargs(kw):
        return dict(theirs["_attn_kwargs"](kw),
                    rope_scaling=kw["rope_scaling"])

    with _in_place_of(train_joyai, reference_forward=forward,
                      _attn_kwargs=attn_kwargs) as theirs:
        got = _joyai_check(ctx, engine, cfg, conf, reference, batches)
    # f
    tol = conf["reference_check"]
    leaves = train_joyai.blocks(reference, engine.state.params, cfg)
    errs = read_mhc(ctx.seed, cfg, conf, reference, leaves, hc_in)
    ctx.log("hyper-connection check: the largest of |program - reference| / "
            "|reference| over H_pre, H_post, H_res, u and the stream after, "
            "a sublayer (the prediction block's two last), under seeded "
            "gains and biases " + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(len(errs) == 2 * len(leaves) and all(np.isfinite(errs))
              and max(errs) <= tol["mhc_rel_tol"],
              f"a sublayer's hyper-connection differs from the reference's by "
              f"{max(errs):.5f} of a part's norm, more than "
              f"{tol['mhc_rel_tol']} ({len(errs)} sublayers of "
              f"{2 * len(leaves)})")
    return got


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under the ``mhc/`` scopes, each and together, under
    ``mlp_dense`` and of the whole step, from a short profiler session of
    its own after the window (``engine.profile_device_scopes``)."""
    table = engine.profile_device_scopes(batches, steps=4, depth=8)
    out = {"step": table["device_ms_a_step"], "mhc": 0.0,
           # what ``dense_ffn_share_pct`` reads: the leading dense block's FFN
           "mlp_dense": sum(r["ms_a_step"] for r in table["scopes"]
                            if "mlp_dense" in r["scope"])}
    for r in table["scopes"]:
        if "mhc/" in r["scope"]:
            part = "mhc/" + r["scope"].split("mhc/", 1)[1].split("/")[0]
            out[part] = out.get(part, 0.0) + r["ms_a_step"]
            out["mhc"] += r["ms_a_step"]
    ctx.log("device ms a step under " + ", ".join(
        f"{k} {v:.3f}" for k, v in out.items() if k != "step")
        + f" of {out['step']:.3f}")
    by_pass = {}
    for r in table["scopes"]:
        if "mhc/" in r["scope"]:
            by_pass[r["pass"]] = by_pass.get(r["pass"], 0.0) + r["ms_a_step"]
    ctx.log("mhc by pass (ms a step): " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(by_pass.items())))
    coarse = {}
    for r in table["scopes"]:
        key = "/".join(r["scope"].split("/")[:4])
        coarse[key] = coarse.get(key, 0.0) + r["ms_a_step"]
    ctx.log("the largest scopes (ms a step): " + ", ".join(
        f"{k} {v:.2f}" for k, v in sorted(coarse.items(),
                                          key=lambda kv: -kv[1])[:24]))
    return out


def run(ctx, reference) -> dict:
    """``train_joyai.run`` with this module's comparison before the window
    in place of its own; on a traced run the scopes after it."""
    import dataclasses
    import importlib

    from deepspeed_tpu.models.llama import LlamaConfig

    if "hc_mult" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no residual "
                 "stream of several lanes (hc_mult): it cannot run a "
                 f"hyper-connected block ({ctx.cell.name})")
    built, preparing = [], []

    def build(ctx):
        """The engine, with the train step's executable being made on a
        thread of the engine's beside the weights and the comparisons
        (``Engine.prepare_train_step``): from an empty compile cache the
        step compiles ~115 s, and a run has 360."""
        import jax
        import jax.numpy as jnp

        built.append(theirs["build"](ctx))
        engine = built[-1][0]
        rows = engine.train_batch_size
        seq = int(ctx.sized(ctx.cell.traffic)["seq_len"])
        preparing.append(engine.prepare_train_step({
            name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
            for name in ("input_ids", "labels")}))
        return built[-1]

    def check(*args):
        got = check_reference(*args)
        for thread in preparing:        # the warm-up's first step is next
            thread.join()
        return got

    with _in_place_of(train_lm, build=build) as theirs, \
            _in_place_of(train_joyai, check_reference=check):
        out = train_joyai.run(ctx, reference)
    engine, cfg, conf = built[-1]
    obs = out["observed"]
    flops = importlib.import_module("benchmark." + conf["flops"])
    step_tokens = obs["tokens"] // max(obs["steps"], 1) // obs["n_devices"]
    obs["mhc_flops_per_step"] = flops.mhc_flops_per_step(conf, step_tokens)
    obs["mhc_bytes_per_step"] = flops.mhc_bytes_per_step(conf, step_tokens)
    if ctx.trace and not ctx.rehearse:
        from benchmark import loadgen

        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size)
        obs["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
