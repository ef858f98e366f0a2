"""``drivers/train_lm.py`` for Trinity (AFMoE): the same engine, data,
window, fences and ``observed`` keys (its ``run``, unchanged), with the
set-up's comparison against the plain reference widened to what this
model's loss cannot see, and one comparison after the window.

Before the window, on one seeded row a rank:

1. ``eval_batch`` against the reference's loss (cross-entropy alone: there
   is no router loss);
2. every expert layer alone (router, selection bias, shared expert, the
   held experts) against ``reference.expert_ffn`` with the same share and
   **a bias that is not zero**: a seeded one of the size of the scores'
   spread.  At b = 0, as the weights are made, a layer that ignores the
   bias or adds it to the weights reads sound;
3. the leading dense layer's FFN alone, ``LlamaBlock._dense_ffn`` on the
   block's own leaves (the method the window times under ``mlp_dense``),
   against ``reference.dense_ffn``;
4. the first sliding layer and the full layer alone against
   ``reference.attention`` (``train_mellum2.check_attention``'s form).

After it:

5. each layer's bias equals what ``reference.bias_update`` makes of zero
   over every step this process trained (warm-up and window; this driver
   keeps each step's counts as the program books them), exactly: the bias
   moves by multiples of the rate in float32 on both sides;
6. the sorted dispatch moved its rows with the Pallas row kernels (on the
   chip), beside ``train_lm.run``'s own window checks.

It also logs whether the window was timed at rest (:func:`log_balance`).
"""
from __future__ import annotations

import contextlib

import numpy as np

from benchmark.drivers import train_lm, train_mellum2

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
model_config = train_lm.model_config


def reference_kwargs(conf: dict) -> dict:
    kw = {arg: conf[key] for arg, key in conf["reference_args"].items()}
    kw["first_expert"] = int(conf["moe"].get("first_expert", 0))
    return kw


def seeded_bias(seed: int, layer: int, p_moe, h) -> np.ndarray:
    """A selection bias of the size of the scores' spread over the
    experts, from the seed."""
    wg = np.asarray(p_moe["gate"]["wg"], np.float32)
    logits = np.asarray(h, np.float32).reshape(-1, wg.shape[0])[:512] @ wg
    spread = float((1.0 / (1.0 + np.exp(-logits))).std())
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 33, layer])
    return rng.normal(0.0, spread, wg.shape[1]).astype(np.float32)


def check_experts(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 2: ``train_mellum2.check_experts`` under a seeded bias."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    tol = conf["reference_check"]["expert_rel_tol"]
    errs = []
    leaves = list(reference.layers(params, len(hidden)))
    for i in range(cfg.num_dense_layers, len(hidden)):
        h = hidden[i].astype(cfg.dtype)
        p = dict(leaves[i]["moe"])
        p["gate"] = dict(p["gate"],
                         expert_bias=seeded_bias(ctx.seed, i, p, h))
        want = reference.expert_ffn(
            p, h, top_k=conf["num_experts_per_tok"],
            route_scale=conf["route_scale"],
            first_expert=cfg.moe.first_expert)
        errs.append(_rel_err(run(p, h), want))
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer, under a seeded bias " + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(max(errs) <= tol and all(np.isfinite(errs)),
              f"an expert layer's output differs from the reference's sparse "
              f"FFN by {max(errs):.5f} of its norm, more than {tol}")


def check_dense(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 3: the leading dense layers' FFN alone, on their
    normalised inputs.  It is ``LlamaBlock._dense_ffn`` that runs, on the
    block's own leaves: a block whose ``__call__`` is that method and
    nothing else (flax declares parameters only under a compact method,
    and the block's own also runs attention and the norms)."""
    import flax.linen as nn
    import jax

    from deepspeed_tpu.models.llama import LlamaBlock

    class DenseFFN(LlamaBlock):
        @nn.compact
        def __call__(self, h):
            return self._dense_ffn(h)

    tol = conf["reference_check"]["dense_rel_tol"]
    kinds = list(cfg.kinds)
    leaves = list(reference.layers(params, cfg.num_dense_layers))
    for i, p in enumerate(leaves):
        ffn = DenseFFN(cfg, kind=kinds[i] if kinds else None, sparse=False)
        h = hidden[i].astype(cfg.dtype)
        got = jax.jit(lambda p, h: ffn.apply({"params": p}, h))(p, h)
        err = _rel_err(got, reference.dense_ffn(p, h))
        ctx.log(f"dense check: layer {i} |program - reference| / |reference| "
                f"{err:.5f}")
        ctx.check(np.isfinite(err) and err <= tol,
                  f"layer {i}: the dense FFN's output differs from the "
                  f"reference's by {err:.5f} of its norm, more than {tol}")


def check_attention(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 4: the first layer of each type alone, the program's
    attention module (bf16 compute, per-head QK-norm, rotary on the sliding
    type alone, that type's flash kernel, the output gate) against
    ``reference.attention`` on the same normalised hidden states."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    tol = conf["reference_check"]["attention_rel_tol"]
    kinds = list(cfg.kinds)
    leaves = list(reference.layers(params, len(hidden)))
    for kind in dict.fromkeys(kinds):          # each type once, in order
        i = kinds.index(kind)
        module = LlamaAttention(cfg, kind)
        h = hidden[i].astype(cfg.dtype)
        pos = jnp.arange(h.shape[1])[None, :]
        got = jax.jit(lambda p, h: module.apply({"params": p}, h, pos, None))(
            leaves[i]["self_attn"], h)
        want = reference.attention(
            kind, leaves[i]["self_attn"], h,
            n_head=cfg.num_attention_heads, n_kv_head=cfg.kv_heads,
            head_dim=cfg.head_dim, sliding_window=conf["sliding_window"],
            rope_theta=conf["rope_theta"], eps=conf["rms_norm_eps"])
        err = _rel_err(got, want)
        ctx.log(f"attention check: layer {i} ({kind}) |program - reference| "
                f"/ |reference| {err:.5f}")
        ctx.check(np.isfinite(err) and err <= tol,
                  f"layer {i} ({kind}): the attention layer's output differs "
                  f"from the reference's by {err:.5f} of its norm, more "
                  f"than {tol}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons 1 to 4; returns the engine's loss."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    ffn_in, attn_in = [], []
    want = float(reference.loss_parts(
        engine.state.params, ids, **reference_kwargs(conf),
        ffn_inputs=ffn_in, attn_inputs=attn_in)[0])
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy alone)  difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    params = engine.state.params
    check_experts(ctx, cfg, conf, reference, params, ffn_in)
    check_dense(ctx, cfg, conf, reference, params, ffn_in)
    check_attention(ctx, cfg, conf, reference, params, attn_in)
    return got


def check_bias(ctx, engine, cfg, conf, reference, steps) -> None:
    """Comparison 5.  The weights are made with a zero bias; ``steps``
    holds the (layers, experts) counts of every step since, oldest first."""
    engine.drain_step_stats(wait=True)
    rate = float(conf["moe"]["bias_update_rate"])
    ok = ctx.check(0 < len(steps) == engine.global_steps,
                   f"the driver saw the counts of {len(steps)} steps of "
                   f"the {engine.global_steps} the engine trained")
    if not ok:
        return
    params = engine.state.params
    moved = []
    for layer in range(cfg.num_dense_layers, cfg.num_hidden_layers):
        got = np.asarray(
            params[f"layers_{layer}"]["moe"]["gate"]["expert_bias"])
        want = np.zeros_like(got)
        for counts in steps:
            want = reference.bias_update(
                counts[layer - cfg.num_dense_layers], want, rate)
        moved.append(float(np.ptp(got)))
        ctx.check(np.array_equal(got, want),
                  f"layer {layer}: the selection bias after {len(steps)} "
                  f"steps differs from reference.bias_update over their "
                  f"counts at {int((got != want).sum())} of {got.size} "
                  f"experts (largest difference "
                  f"{float(np.abs(got - want).max()):.6f})")
    ctx.log(f"bias check: {len(steps)} steps x {len(moved)} layers against "
            f"reference.bias_update, exactly; max - min a layer "
            + " ".join(f"{m:.4f}" for m in moved))


def log_balance(ctx, cfg, steps, n_window: int) -> None:
    """Whether the window was timed at rest: the share of the pairs routed
    to the experts held here and a layer's max / mean pairs an expert (the
    median over steps and layers), over the window's first tenth, its last
    tenth and the whole of it."""
    first, held = cfg.moe.first_expert, cfg.moe.num_experts
    window = np.stack(steps[-n_window:]).astype(np.float64)  # (steps, L, E)
    tenth = max(1, n_window // 10)

    def read(part):
        share = part[..., first:first + held].sum() / part.sum()
        return 100 * share, float(np.median(part.max(-1) / part.mean(-1)))

    ctx.log("balancing in the window (held share %, max / mean pairs an "
            "expert): " + "; ".join(
                f"{name} {share:.2f} {ratio:.2f}" for name, (share, ratio) in (
                    ("first tenth", read(window[:tenth])),
                    ("last tenth", read(window[-tenth:])),
                    ("whole", read(window)))))


@contextlib.contextmanager
def _in_place_of(module, **names):
    """``module``'s functions replaced for the duration: ``train_lm.run``
    looks its helpers up in its own module at each call."""
    theirs = {name: getattr(module, name) for name in names}
    for name, mine in names.items():
        setattr(module, name, mine)
    try:
        yield theirs
    finally:
        for name, fn in theirs.items():
            setattr(module, name, fn)


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own,
    the engine kept for the comparison after the window, and every step's
    counts kept as the program books them (the bias moves by the sign of
    EACH step's counts, which the counters' totals cannot give back)."""
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.parallel import moe

    built, steps = [], []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    def record_stats(stats):
        counts = np.asarray(stats["tokens_per_expert"])
        steps.append(counts.reshape(-1, counts.shape[-1]))
        booked["record_stats"](stats)

    with _in_place_of(train_lm, check_reference=check_reference,
                      build=build) as theirs, \
            _in_place_of(moe, record_stats=record_stats) as booked:
        out = train_lm.run(ctx, reference)
        engine, cfg, conf = built[-1]
        check_bias(ctx, engine, cfg, conf, reference, steps)
    if 0 < out["attempted"] <= len(steps):
        log_balance(ctx, cfg, steps, out["attempted"])
    rows = {(s, i) for s, i, _, n in dispatch_report() if n}
    ctx.check(ctx.rehearse or ("moe_rows", "pallas") in rows,
              f"the share's rows never moved through the Pallas row "
              f"kernels: {sorted(rows)}")
    train_mellum2.count_what_was_routed_here(ctx, out)
    return out
