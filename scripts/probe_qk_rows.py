#!/usr/bin/env python3
"""The rotation of q and k on the chip, three ways, at a cell's own shape:
today's ``apply_rotary`` in the ``(B, S, H, D)`` view, a plain-XLA form on
the flat ``(B, S, H*D)`` rows (two rolls and a select by ``lane % D <
D/2``), and the Pallas pass of ``ops/pallas/qk_rows.py`` - forward and
forward + backward, device time from a profiler trace.  ``--cell`` then
runs Mellum 2's step with the Pallas pass and with the plain-XLA form in
its place and prints both steps by scope (``probe_mellum2_scopes.py``'s
method): alone, XLA picks the layouts that suit the rotation; in the step
the projections and the flash kernels pick them.

    chiprun -- python3 scripts/probe_qk_rows.py [--cell] [--steps 6]
"""
import argparse
import functools
import json
import os
import shutil

from mellum2_cell import ROOT, build

# (rows, sequence, query heads, key-value heads, head_dim, per-head norm)
SHAPES = {"mellum2": (4, 8192, 32, 4, 128, False),
          "trinity": (3, 8192, 32, 4, 128, True),
          "olmoe": (2, 4096, 16, 16, 128, False)}


def xla_rows(q, k, table, q_scale, k_scale, head_dim, eps=0.0,
             interpret=False):
    """``ops/pallas/qk_rows.py qk_rows`` in plain XLA on the flat rows."""
    import jax.numpy as jnp
    from jax import lax

    D = head_dim
    if table is not None:
        cos, sin = table[..., :D // 2], table[..., D // 2:]
        cos = jnp.concatenate([cos, cos], axis=-1)
        sin = jnp.concatenate([-sin, sin], axis=-1)

    def one(x, scale):
        W = x.shape[-1]
        y = x.astype(jnp.float32)
        if scale is not None:   # a head's mean of squares, in its own lanes
            heads = y.reshape(*y.shape[:-1], W // D, D)
            var = jnp.mean(heads * heads, axis=-1, keepdims=True)
            y = (heads * lax.rsqrt(var + eps) * scale).reshape(y.shape)
        if table is not None:
            first = (jnp.arange(W) % D) < D // 2
            turned = jnp.where(first, jnp.roll(y, -(D // 2), axis=-1),
                               jnp.roll(y, D // 2, axis=-1))
            y = y * jnp.tile(cos, (1, 1, W // D)) \
                + turned * jnp.tile(sin, (1, 1, W // D))
        return y.astype(x.dtype)

    return one(q, q_scale), one(k, k_scale)


def device_ms(fn, args, name, reps=10):
    """Device time of one call of jitted ``fn``, all its instructions, from
    a trace of ``reps`` calls."""
    import jax

    from benchmark import trace_reduce

    out = os.path.join(ROOT, ".bench_out", "probe_qk_rows")
    jax.block_until_ready(fn(*args))
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for _ in range(reps):
        res = fn(*args)
    jax.block_until_ready(res)
    jax.profiler.stop_trace()
    dev_ops, _, _ = trace_reduce.read_xplane(trace_reduce.find_xplane(out))
    events = next(iter(dev_ops.values()))
    by = {}
    for op, _, dur in trace_reduce.self_times(events):
        by[op] = by.get(op, 0) + dur
    shutil.rmtree(out, ignore_errors=True)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:4]
    return {"what": name, "ms": round(sum(by.values()) / reps / 1e6, 3),
            "largest": {k: round(v / reps / 1e6, 3) for k, v in top}}


def micro(which):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.common import rms_norm
    from deepspeed_tpu.ops.pallas.qk_rows import qk_rows
    from deepspeed_tpu.ops.rotary import (apply_rotary, rotary_angles,
                                          row_table)

    B, S, H, KV, D, norm = SHAPES[which]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (B, S, H * D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV * D), jnp.bfloat16)
    gq = jax.random.normal(ks[2], (B, S, H * D), jnp.bfloat16)
    gk = jax.random.normal(ks[3], (B, S, KV * D), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    scales = (1 + 0.1 * jax.random.normal(ks[4], (2, D))) if norm else None
    eps = 1e-5

    def today(q, k, scales):
        cos, sin = rotary_angles(pos, D)
        q4, k4 = q.reshape(B, S, H, D), k.reshape(B, S, KV, D)
        if norm:
            q4 = rms_norm(q4, scales[0], eps)
            k4 = rms_norm(k4, scales[1], eps)
        q4, k4 = apply_rotary(q4, cos, sin), apply_rotary(k4, cos, sin)
        return q4.reshape(B, S, H * D), k4.reshape(B, S, KV * D)

    def flat(impl, q, k, scales):
        qs, ks_ = (scales[0], scales[1]) if norm else (None, None)
        return impl(q, k, row_table(pos, D), qs, ks_, D, eps)

    forms = {"today (B, S, H, D)": today,
             "plain XLA, flat rows": functools.partial(flat, xla_rows),
             "pallas qk_rows": functools.partial(flat, qk_rows)}
    moved = 2 * 2 * (q.size + k.size)       # read and write, bf16
    print(json.dumps({"shape": which, "q": q.shape, "k": k.shape,
                      "norm": norm, "a_pass_at_819GBs_ms":
                          round(moved / 819e9 * 1e3, 3)}))
    results = {}
    for name, fn in forms.items():
        fwd = jax.jit(fn)

        def both(q, k, scales, fn=fn):
            out, vjp = jax.vjp(fn, q, k, scales)
            return out, vjp((gq, gk))

        print(json.dumps(device_ms(fwd, (q, k, scales), name + ", forward")))
        print(json.dumps(device_ms(jax.jit(both), (q, k, scales),
                                   name + ", forward + backward")))
        results[name] = jax.tree_util.tree_leaves(
            jax.jit(both)(q, k, scales))
    # what the chip computed: each form's worst distance from today's,
    # as a share of the array's largest value
    base = results["today (B, S, H, D)"]
    for name, leaves in results.items():
        print(json.dumps({"against today": name, "worst": [
            round(float(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)).max()
                        / jnp.abs(b.astype(jnp.float32)).max()), 5)
            for a, b in zip(leaves, base)]}))


def cell_steps(steps, seed):
    import jax

    import probe_mellum2_scopes as scopes
    from deepspeed_tpu.ops.pallas import qk_rows as module

    for name, impl in (("pallas qk_rows", None),
                       ("plain XLA, flat rows", xla_rows)):
        kernel = module.qk_rows
        if impl is not None:
            module.qk_rows = impl
        try:
            _, _, engine, _, _, batches = build(seed)
            for _ in range(3):
                jax.block_until_ready(engine.train_batch(data_iter=batches))
            lines = scopes.measure(engine, batches, steps)
        finally:
            module.qk_rows = kernel
        print(json.dumps({"step with": name, **lines[0]}))
        for line in lines[1:]:
            if line.get("scope", "").startswith("rope"):
                print(json.dumps(line))
        del engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="store_true")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=3000000029)
    ap.add_argument("--shapes", default="mellum2,trinity,olmoe")
    args = ap.parse_args()
    for which in args.shapes.split(","):
        if which:
            micro(which)
    if args.cell:
        cell_steps(args.steps, args.seed)


if __name__ == "__main__":
    main()
