"""``drivers/train_lm.py`` for Ouro: the same engine, data, window, fences
and ``observed`` keys (its ``run``, unchanged), with the set-up's comparison
against the plain reference widened to what a looped stack adds, and the
device's time by the program's ``ut/`` scopes for the readers this cell
reports.

Before the window, on one seeded row a rank and UNDER A SEEDED GATE
(:func:`seeded`: ``w_gate`` drawn so that the logit has a standard deviation
of 1.2 on a normed stream and ``b_gate`` 0.3, so g spreads over (0.1, 0.9):
at the initial g = 1/2 everywhere a program that reads the gate before the
norm, or gives the last pass ``g_T`` times what is left, reads sound), from
ONE compiled forward of the engine's own model over its master weights
(:func:`program`; ``reference_check`` of the configuration file has each
limit and its readings):

a. the loss (the expected cross-entropy less the entropy's share) against
   the reference's;
b. each of the four exits' mean nll, and each pass's mean share of the exit
   distribution;
c. the normed stream after each pass against the reference's, as a share of
   its norm (the ``norm`` module's outputs, captured);
d. d loss / d ``w_gate`` and ``b_gate``: the one gradient no other cell's
   path makes (the reference's from its own streams and nll, which do not
   depend on the gate), the difference as a share of what the tokens' terms
   would come to if all pulled one way;

and, under norm weights moved off their ones (``train_olmo_hybrid.moved``),
the FIRST block alone on the reference forward's own input to the LAST pass,
rounded to bf16 (the first pass reads the bare table):

e. its attention (rotary at theta 1e6, flash at 16 heads on 16) against
   ``reference.attention``;
f. its dense SwiGLU, ``LlamaBlock._dense_ffn`` on the block's own leaves
   (the method the window times under ``mlp_dense``);
g. the whole block under the sandwich norm against ``reference.block``.

After the window: flash resolved to what the file expects, and on a traced
run the device time under ``ut/pass_<t>``, ``ut/exit_gate``, ``loss_head``
and ``mlp_dense`` from a short profiler session of the driver's own.

The train step's executable is made on a thread of the engine's beside all
of this (``Engine.prepare_train_step``).
"""
from __future__ import annotations

import sys

import numpy as np

from benchmark.drivers import (train_lm, train_mellum2, train_olmo_hybrid,
                               train_trinity)

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
_in_place_of = train_trinity._in_place_of
_BESIDE = train_olmo_hybrid._BESIDE
moved = train_olmo_hybrid.moved
model_config = train_lm.model_config
reference_kwargs = train_lm.reference_kwargs
FULL = "full_attention"
GATE_LOGIT_STD, GATE_BIAS = 1.2, 0.3
SCOPES = ("loss_head", "ut/exit_gate", "mlp_dense")


class _Only(list):
    """What a reference forward appends to, keeping entry ``index`` alone
    (24 streams of 8192 x 2048 in float32 do not wait on the device)."""

    def __init__(self, index: int):
        super().__init__()
        self.index, self.seen = index, 0

    def append(self, x):
        if self.seen == self.index:
            super().append(x)
        self.seen += 1


def seeded(seed: int, params: dict) -> dict:
    """``params`` with an ``exit_gate`` whose g spreads over (0.1, 0.9)."""
    import jax.numpy as jnp

    width = params["exit_gate"]["kernel"].shape[0]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 64])
    return dict(params, exit_gate={
        "kernel": jnp.asarray(rng.normal(
            0.0, GATE_LOGIT_STD / np.sqrt(width), (width, 1)), jnp.float32),
        "bias": jnp.asarray([GATE_BIAS], jnp.float32)})


def program(model):
    """``f(params, ids) -> {loss, exit_p, exit_nll, hs, dgate}`` of the
    engine's own model, one executable: the loss and its parts as the train
    step computes them, the normed stream after each pass (the ``norm``
    module's outputs) and the gradient of the loss with respect to the
    gate's leaves (nothing of the stack is walked back for it)."""
    import jax

    def parts(params, ids):
        def loss(gate):
            out, kept = model.apply(
                {"params": dict(params, exit_gate=gate)}, ids, labels=ids,
                capture_intermediates=lambda m, _: m.name == "norm",
                mutable=["intermediates"])
            return out["loss"], (out["stats"],
                                 kept["intermediates"]["norm"]["__call__"])

        (value, (stats, hs)), dgate = jax.value_and_grad(
            loss, has_aux=True)(params["exit_gate"])
        return {"loss": value, "exit_p": stats["exit_p"],
                "exit_nll": stats["exit_nll"], "hs": list(hs),
                "dgate": dgate}

    return jax.jit(parts)


def reference_parts(reference, params, ids, conf, kept=(), **wrong) -> dict:
    """The reference's loss, parts, streams and gate gradient; ``kept``
    names lists (``block_inputs`` ...) that receive the block applications'
    inputs."""
    kw = dict(reference_kwargs(conf), **wrong)
    parts = reference.loss_parts(params, ids, **kw, **dict(kept))
    parts["dgate"] = reference.gate_grads(params, parts, beta=kw["beta"],
                                          fault=kw.get("fault"))
    return parts


def compare_parts(got: dict, want: dict) -> dict:
    """Comparisons a to d: ``{loss, exit_nll, exit_p}`` the largest absolute
    difference, ``pass`` each stream's relative error (computed on the
    device: a stream is 64 MB), ``gate_grad`` each leaf's ``|program -
    reference|`` as a share of what the tokens' terms of the reference's
    gradient would come to if all pulled one way (``reference.gate_grads``'
    ``scale``: the sum itself cancels to anything below that, by the seed,
    and the bf16 path's nll is off by some thousandths an exit, so the
    plain relative error, kept under ``gate_grad_plain``, read 0.01 to 0.8
    over five sound seeds on the chip)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rel(a, b):
        a, b = (t.astype(jnp.float32).ravel() for t in (a, b))
        return jnp.linalg.norm(a - b) / jnp.linalg.norm(b)

    return {
        "loss": abs(float(got["loss"]) - float(want["loss"])),
        "exit_nll": float(np.abs(np.asarray(got["exit_nll"])
                                 - np.asarray(want["exit_nll"])).max()),
        "exit_p": float(np.abs(np.asarray(got["exit_p"])
                               - np.asarray(want["exit_p"])).max()),
        "pass": [float(rel(g, w)) for g, w in zip(got["hs"], want["hs"])],
        "gate_grad": [float(np.linalg.norm(
            np.asarray(got["dgate"][k], np.float32)
            - np.asarray(want["dgate"][k])) / float(want["dgate"]["scale"][k]))
            for k in ("kernel", "bias")],
        "gate_grad_plain": [float(rel(got["dgate"][k], want["dgate"][k]))
                            for k in ("kernel", "bias")]}


def head_kwargs(cfg) -> dict:
    return {"n_head": cfg.num_attention_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta}


def read_attention(cfg, reference, p_attn, h, **wrong) -> float:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    module = LlamaAttention(cfg, FULL)
    pos = jnp.arange(h.shape[1])[None, :]
    got = _BESIDE.submit(jax.jit(
        lambda p, h: module.apply({"params": p}, h, pos, None)), p_attn, h)
    want = reference.attention(p_attn, h, **head_kwargs(cfg), **wrong)
    return _rel_err(got.result(), want)


def read_dense(cfg, reference, p_layer, h, **wrong) -> float:
    return train_olmo_hybrid.read_dense(cfg, reference, p_layer, h, FULL,
                                        **wrong)


def read_block(cfg, reference, p_layer, x, **wrong) -> float:
    """One whole ``LlamaBlock`` from ``x`` against ``reference.block``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaBlock

    module = LlamaBlock(cfg, kind=FULL)
    pos = jnp.arange(x.shape[1])[None, :]
    got = _BESIDE.submit(jax.jit(
        lambda p, x: module.apply({"params": p}, x, (pos, None))[0]),
        p_layer, x.astype(cfg.dtype))
    # the reference reads the same rounded stream, in float32
    want = reference.block(p_layer, x.astype(cfg.dtype), **head_kwargs(cfg),
                           eps=cfg.rms_norm_eps, **wrong)
    return _rel_err(got.result(), want)


def last_pass_inputs(cfg) -> dict:
    """Lists for :func:`reference_parts` that keep the FIRST block's inputs
    of the LAST pass."""
    first = (cfg.total_ut_steps - 1) * cfg.num_hidden_layers
    return {name: _Only(first)
            for name in ("block_inputs", "attn_inputs", "ffn_inputs")}


def check_block(ctx, cfg, conf, reference, p_layer, kept) -> None:
    """Comparisons e, f and g."""
    tol = conf["reference_check"]
    p = moved(ctx.seed, 0, p_layer)
    (x,), (u,), (m,) = (kept[k] for k in ("block_inputs", "attn_inputs",
                                          "ffn_inputs"))
    for what, err, key in (
            ("attention", read_attention(cfg, reference, p["self_attn"],
                                         u.astype(cfg.dtype)),
             "attention_rel_tol"),
            ("dense SwiGLU", read_dense(cfg, reference, p,
                                        m.astype(cfg.dtype)),
             "dense_rel_tol"),
            ("block (the sandwich norm)", read_block(cfg, reference, p, x),
             "block_rel_tol")):
        ctx.log(f"{what} check: the first block on the last pass's input "
                f"|program - reference| / |reference| {err:.5f} (limit "
                f"{tol[key]})")
        ctx.check(np.isfinite(err) and err <= tol[key],
                  f"the first block's {what} differs from the reference's "
                  f"by {err:.5f} of its norm, more than {tol[key]}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons a to g; returns the engine's loss."""
    tol = conf["reference_check"]
    ids = next(batches)["input_ids"][:engine.dp_world]
    params = seeded(ctx.seed, engine.state.params)
    got = _BESIDE.submit(program(engine.model), params, ids)
    kept = last_pass_inputs(cfg)
    want = reference_parts(reference, params, ids, conf, kept)
    got = got.result()
    errs = compare_parts(got, want)
    ctx.log(f"reference check: program loss {float(got['loss']):.6f}  "
            f"reference {float(want['loss']):.6f}  difference "
            f"{errs['loss']:.6f} (limit {tol['loss_abs_tol']})")
    for name in ("exit_nll", "exit_p"):
        ctx.log(f"reference check: {name} program "
                + " ".join(f"{v:.6f}" for v in np.asarray(got[name]))
                + "  reference "
                + " ".join(f"{v:.6f}" for v in np.asarray(want[name]))
                + f"  largest difference {errs[name]:.6f} (limit "
                f"{tol[name + '_abs_tol']})")
    ctx.log("reference check: |h_t - reference| / |reference| after each "
            "pass " + " ".join(f"{e:.5f}" for e in errs["pass"])
            + f" (limit {tol['pass_rel_tol']}); d loss / d w_gate, b_gate "
            + " ".join(f"{e:.5f}" for e in errs["gate_grad"])
            + f" of the tokens' terms (limit {tol['gate_grad_rel_tol']}; of "
            "the reference's own norm "
            + " ".join(f"{e:.4f}" for e in errs["gate_grad_plain"]) + ")")
    for key, err, what in (
            ("loss_abs_tol", errs["loss"], "the loss"),
            ("exit_nll_abs_tol", errs["exit_nll"], "an exit's mean nll"),
            ("exit_p_abs_tol", errs["exit_p"], "a pass's mean share of the "
             "exit distribution"),
            ("pass_rel_tol", max(errs["pass"]), "the stream after a pass "
             "(share of its norm)"),
            ("gate_grad_rel_tol", max(errs["gate_grad"]), "the gate's "
             "gradient (share of its tokens' terms)")):
        ctx.check(np.isfinite(err) and err <= tol[key],
                  f"{what} differs from the reference's by {err:.6f}, more "
                  f"than {tol[key]}")
    check_block(ctx, cfg, conf, reference,
                next(iter(reference.layers(params, 1))), kept)
    return float(got["loss"])


def scope_split(ctx, engine, batches, steps: int = 4) -> dict:
    """Device ms a step under ``loss_head``, ``ut/exit_gate``, ``mlp_dense``,
    each ``ut/pass_<t>`` (``"ut/pass"``: a list, forward, recompute and
    backward together) and of the whole step, from a short profiler session
    of its own after the window.  By the instructions' own ``op_name``:
    ``engine.profile_device_scopes`` folds ``pass_0`` .. ``pass_3`` into
    ``pass_*``, which is the breakdown's name and no use to the spread."""
    import jax

    from benchmark import trace_reduce
    from deepspeed_tpu.telemetry import device_scopes

    def run():
        for _ in range(steps):
            loss = engine.train_batch(data_iter=batches)
        jax.block_until_ready(loss)

    by_device = device_scopes.capture(run)
    events = by_device[min(by_device)]
    names = device_scopes.instruction_scopes(engine.compiled_step())
    T = engine.model.cfg.total_ut_steps
    out = dict.fromkeys(SCOPES, 0.0)
    out.update({"step": 0.0, "ut/pass": [0.0] * T})
    for name, _, dur in trace_reduce.self_times(events):
        ms = dur / steps / 1e6
        out["step"] += ms
        op = names.get(str(name), "")
        for scope in SCOPES:
            if f"/{scope}/" in op:
                out[scope] += ms
        for t in range(T):
            if f"/ut/pass_{t}/" in op:
                out["ut/pass"][t] += ms
    ctx.log("device ms a step under " + ", ".join(
        f"{k} {out[k]:.3f}" for k in SCOPES) + ", the passes "
        + " ".join(f"{v:.3f}" for v in out["ut/pass"])
        + f" of {out['step']:.3f}")
    table = device_scopes.scope_table(events, names, steps, depth=4)
    ctx.log("the largest scopes (ms a step): " + ", ".join(
        f"{r['scope']} [{r['pass']}] {r['ms_a_step']:.2f}"
        for r in table["scopes"][:24]))
    return out


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own,
    the train step made beside it, and the engine kept for the scopes."""
    import dataclasses

    from deepspeed_tpu.models.llama import LlamaConfig

    if "total_ut_steps" not in {
            f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no "
                 "total_ut_steps field: it cannot run a looped stack "
                 f"({ctx.cell.name})")
    built, preparing = [], []

    def build(ctx):
        import jax
        import jax.numpy as jnp

        built.append(theirs["build"](ctx))
        engine = built[-1][0]
        seq = int(ctx.sized(ctx.cell.traffic)["seq_len"])
        preparing.append(engine.prepare_train_step({
            name: jax.ShapeDtypeStruct((engine.train_batch_size, seq),
                                       jnp.int32)
            for name in ("input_ids", "labels")}))
        return built[-1]

    def check(*args):
        got = check_reference(*args)
        for thread in preparing:        # the warm-up's first step is next
            thread.join()
        return got

    with _in_place_of(train_lm, build=build, check_reference=check) as theirs:
        out = train_lm.run(ctx, reference)
    engine, cfg, _ = built[-1]
    if ctx.trace and not ctx.rehearse:
        from benchmark import loadgen

        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size)
        out["observed"]["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
