"""``drivers/train_lm.py`` for SDAR (block-diffusion training): the same
engine, data path, window, fences and ``observed`` keys (its ``run``,
unchanged: ``train_batch(data_iter)`` with the noise drawn inside the
step), with the set-up's comparison against the plain reference made on a
GIVEN mask and given levels and widened to what the loss cannot see, and
the window's noise counted.  What this module adds is checks, not a step
of its own.

Before the window (``reference_check`` of the configuration file has each
limit and its readings), on one seeded row a rank under a seeded mask and
seeded levels:

a. ``eval_batch`` with ``diffusion_mask`` / ``diffusion_t`` in the batch
   against the reference's weighted loss plus its router losses;
b. every expert layer alone against the reference's share, on the
   reference forward's normalised hidden states of all 2L positions
   (``train_mellum2.check_experts``);

c. the first layer's attention alone on that row (``LlamaAttention``:
   bf16 projections, the per-head norm, rotary at the doubled positions,
   the two flash calls and the own-block term) against
   ``reference.attention``, the noisy and the clean half each alone;

and on two rows with different content, at the cell's shape:

d. the attention core alone (``ops/attention.py
   block_diffusion_attention``) on the reference's q, k, v of that layer
   rounded to the compute type, forward and the gradients of q, k and v
   under a seeded cotangent, each half alone, against
   ``reference.attention_core`` (a row at a time: float32 scores of 256
   queries against all 2L keys).

After it, beside ``train_lm.run``'s own window checks: attention resolved
to the flash path with the block length in its reason and the XLA path for
no layer, and the masked share of the window's data tokens inside
``masked_pct_range``.

Data ids are drawn over the slice LESS the mask id (its last id): the
generator is handed ``vocab_size - 1``.
"""
from __future__ import annotations

import concurrent.futures
import sys

import numpy as np

from benchmark import loadgen
from benchmark.drivers import train_lm, train_mellum2, train_trinity
from benchmark.layer_metrics import diffusion_masked_pct

FAMILIES = train_lm.FAMILIES
model_config = train_lm.model_config
_rel_err = train_mellum2._rel_err
_in_place_of = train_trinity._in_place_of
# the program's side of a comparison is staged and run on this thread while
# the reference's compiles on the caller's: XLA compiles outside the
# interpreter's lock, and from an empty compile cache the two sides of the
# loss and of the core are ~60 s of a set-up that has 360 s with its window
_BESIDE = concurrent.futures.ThreadPoolExecutor(1)


def reference_kwargs(conf: dict) -> dict:
    kw = train_mellum2.reference_kwargs(conf)
    kw["block_length"] = int(conf["diffusion"]["block_length"])
    kw["mask_token_id"] = int(conf["diffusion"]["mask_token_id"])
    return kw


def _attn_kwargs(kw: dict) -> dict:
    return {k: kw[k] for k in ("n_head", "n_kv_head", "head_dim",
                               "rope_theta", "eps")}


def seeded_noise(seed: int, rows: int, length: int, conf: dict):
    """``(mask (rows, L) bool, t (rows, L / g) float32)`` from the seed, as
    the model draws them: a level a block in ``(t_min, 1]``, each token of
    the block masked with that probability."""
    dif = conf["diffusion"]
    g, t_min = int(dif["block_length"]), float(dif["t_min"])
    rng = np.random.default_rng([int(seed) % (2**31 - 1), 40])
    t = (1.0 - rng.random((rows, length // g)) * (1.0 - t_min)
         ).astype(np.float32)
    mask = rng.random((rows, length)) < np.repeat(t, g, axis=1)
    return mask, t


def _halves(x):
    n = x.shape[1] // 2
    return x[:, :n], x[:, n:]


def read_attention(cfg, reference, p_attn, h, kw: dict, **wrong) -> list:
    """Comparison c: ``[noisy half, clean half]`` of |program - reference|
    / |reference| of the attention layer on ``h`` (1, 2L, E); ``wrong``
    (``fault=`` or ``operand_bits=``) goes to the reference."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    L = h.shape[1] // 2
    module = LlamaAttention(cfg, None, True)
    pos = jnp.concatenate([jnp.arange(L), jnp.arange(L)])[None, :]
    got = jax.jit(lambda p, h: module.apply({"params": p}, h, pos, None))(
        p_attn, h)
    want = reference.attention(p_attn, h, block_length=kw["block_length"],
                               **_attn_kwargs(kw), **wrong)
    return [_rel_err(a, b) for a, b in zip(_halves(got), _halves(want))]


def read_core(cfg, reference, p_attn, h, kw: dict, seed: int,
              **wrong) -> dict:
    """Comparison d: ``{"out noisy": err, ..., "dv clean": err}`` of the
    attention core on the reference's q, k, v of ``h`` (B, 2L, E) rounded
    to the compute type, under a seeded cotangent; the reference a row at
    a time."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import block_diffusion_attention

    g = kw["block_length"]
    q, k, v = (jnp.concatenate(t).astype(cfg.dtype) for t in zip(*(
        reference.qkv(p_attn, h[r:r + 1], **_attn_kwargs(kw))
        for r in range(h.shape[0]))))      # a row at a time: one compile
    cot = jax.random.normal(jax.random.PRNGKey(seed % (2**31 - 1)),
                            q.shape, jnp.float32).astype(cfg.dtype)

    @jax.jit
    def program(q, k, v, cot):
        out, vjp = jax.vjp(lambda q, k, v: block_diffusion_attention(
            q, k, v, block=g, impl=cfg.attn_impl), q, k, v)
        return (out,) + vjp(cot)

    got = _BESIDE.submit(program, q, k, v, cot)
    per_row = [reference.attention_core(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                        cot[r:r + 1], block_length=g, **wrong)
               for r in range(q.shape[0])]
    want = [jnp.concatenate(parts) for parts in zip(*per_row)]
    got = got.result()
    read = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        for half, x, y in zip(("noisy", "clean"), _halves(a), _halves(b)):
            read[f"{name} {half}"] = _rel_err(x, y)
    return read


def check_attention(ctx, cfg, conf, reference, params, ids, mask) -> None:
    """Comparisons c (the first row) and d (both rows ``ids`` (2, L))."""
    tol = conf["reference_check"]
    kw = reference_kwargs(conf)
    p_attn = next(iter(reference.layers(params, 1)))["self_attn"]
    h = reference.first_attention_input(
        params, ids, mask, mask_token_id=kw["mask_token_id"],
        eps=kw["eps"]).astype(cfg.dtype)                    # (2, 2L, E)
    errs = read_attention(cfg, reference, p_attn, h[:1], kw)
    ctx.log("attention check: layer 0 |program - reference| / |reference| "
            f"noisy half {errs[0]:.5f} clean half {errs[1]:.5f}")
    ctx.check(all(np.isfinite(errs)) and max(errs) <= tol["attention_rel_tol"],
              f"layer 0: the attention layer's output differs from the "
              f"reference's by {errs[0]:.5f} (noisy half) and {errs[1]:.5f} "
              f"(clean half) of its norm, more than "
              f"{tol['attention_rel_tol']}")
    read = read_core(cfg, reference, p_attn, h, kw, ctx.seed)
    ctx.log(f"attention core check on {h.shape[0]} rows of {h.shape[1]} "
            f"positions: " + " ".join(f"{n} {e:.5f}" for n, e in read.items()))
    for name, e in read.items():
        limit = tol["core_rel_tol" if name.startswith("out")
                    else "core_grad_rel_tol"]
        ctx.check(np.isfinite(e) and e <= limit,
                  f"attention core, {name}: differs from the reference's by "
                  f"{e:.5f} of its norm, more than {limit}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons a to d."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    batch = next(batches)["input_ids"]
    ids = batch[:rows]
    mask, t = seeded_noise(ctx.seed, max(rows, 2), ids.shape[1], conf)
    got = _BESIDE.submit(engine.eval_batch, {
        "input_ids": ids, "labels": ids, "diffusion_mask": mask[:rows],
        "diffusion_t": t[:rows]})
    ffn_in = []
    ce, aux = reference.loss_parts(
        engine.state.params, ids, mask[:rows], t[:rows],
        **reference_kwargs(conf), ffn_inputs=ffn_in)
    want, got = float(ce) + float(aux), float(got.result())
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(weighted cross-entropy {float(ce):.6f} + router losses "
            f"{float(aux):.6f})  difference {got - want:+.6f}; "
            f"{100 * mask[:rows].mean():.2f}% of the row masked")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    train_mellum2.check_experts(ctx, cfg, conf, reference,
                                engine.state.params, ffn_in)
    del ffn_in
    check_attention(ctx, cfg, conf, reference, engine.state.params,
                    batch[:2], mask[:2])       # a step has two rows a chip
    return got


def _data_batches(theirs):
    """``loadgen.packed_batches`` over the slice less the mask id."""
    def packed_batches(mix, seed, rows, vocab_size):
        return theirs(mix, seed, rows, vocab_size - 1)
    return packed_batches


def count_the_windows_noise(ctx, out: dict, before, conf: dict) -> None:
    """The masked and kept data tokens the window's steps booked, held to
    the configuration's range, and the head's required operations counted
    from them (``train_mellum2.count_what_was_routed_here`` has counted
    the experts' from the pairs routed here, and the head at the schedule's
    mean share)."""
    import importlib

    obs = out["observed"]
    after = diffusion_masked_pct.totals()
    if not ctx.check(bool(before and after),
                     "the program booked no diffusion_tokens_total"):
        return
    window = {k: after[k] - before[k] for k in after}
    n = window["masked"] + window["kept"]
    obs["diffusion_tokens"] = window
    share = diffusion_masked_pct.share(window)
    lo, hi = conf["reference_check"]["masked_pct_range"]
    ctx.log(f"{window['masked']:.0f} of {n:.0f} data tokens of the window "
            f"were masked")
    ctx.check(share is not None and lo <= 100 * share <= hi,
              f"the window masked {share} of its data tokens, outside "
              f"{lo}-{hi}%")
    ctx.check(n == obs["tokens"],
              f"the program counted {n:.0f} data tokens in the window, the "
              f"driver {obs['tokens']}")
    if share is not None:
        flops = importlib.import_module("benchmark." + conf["flops"])
        obs["flops_per_token"] += 6.0 * (flops.head_params(conf, share)
                                         - flops.head_params(conf))


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under ``diffusion/`` and of the whole step, from a
    short profiler session of its own after the window (the v5e's device
    events carry no scope: ``engine.profile_device_scopes``)."""
    table = engine.profile_device_scopes(batches, steps=4, depth=1)
    mine = sum(r["ms_a_step"] for r in table["scopes"]
               if r["scope"].split("/")[0] == "diffusion")
    ctx.log(f"device ms a step under diffusion/: {mine:.3f} of "
            f"{table['device_ms_a_step']:.3f}")
    return {"diffusion": mine, "step": table["device_ms_a_step"]}


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison and data in place of
    its own, and the window's noise counted around it."""
    import dataclasses

    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    if "diffusion" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no diffusion "
                 "section: it cannot run block-diffusion training "
                 f"({ctx.cell.name})")
    built, before = [], []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    start_trace = ctx.start_trace

    def start_window():
        # every warm-up step booked, then the count the window starts from
        built[-1][0].drain_step_stats(wait=True)
        before.append(diffusion_masked_pct.totals())
        start_trace()

    ctx.start_trace = start_window
    try:
        with _in_place_of(train_lm, check_reference=check_reference,
                          build=build) as theirs, \
                _in_place_of(loadgen, packed_batches=_data_batches(
                    loadgen.packed_batches)):
            out = train_lm.run(ctx, reference)
    finally:
        ctx.start_trace = start_trace
    engine, cfg, conf = built[-1]
    train_mellum2.count_what_was_routed_here(ctx, out)
    count_the_windows_noise(ctx, out, before[-1] if before else None, conf)
    report = [r for r in dispatch_report() if r[3]]
    if not ctx.rehearse:
        flash = sum(n for s, i, r, n in report
                    if (s, i) == ("attention", "flash") and "block diffusion "
                    f"over [noisy ; clean], block length "
                    f"{cfg.diffusion.block_length}" in r)
        ctx.check(flash >= cfg.num_hidden_layers,
                  f"the block-diffusion flash path was dispatched {flash} "
                  f"times for {cfg.num_hidden_layers} blocks: {report}")
        xla = [r for r in report if r[:2] == ("attention", "jnp")]
        ctx.check(not xla, f"attention took the XLA path: {xla}")
    if ctx.trace and not ctx.rehearse:
        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size - 1)
        out["observed"]["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
