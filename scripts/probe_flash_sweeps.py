#!/usr/bin/env python
"""Device time a call of the flash kernels, forward and backward apart, under
several builds of their sweeps, in one process on one seed (PR 43).

Builds: ``parent`` (the module of a parent checkout, ``--parent DIR``: one
FULL tile a loop trip, a ``lax.cond`` around a masked tile that only some
programs meet), ``change`` (this tree), and two more of this tree's module
that answer "which part did it": ``single`` (one tile a loop trip: the
straight-line windowed sweeps alone) and ``other`` (the odd FULL tile left
over by the paired loop placed the other way: under a ``lax.cond`` forward,
computed void in the masked tiles' block backward; ``_fold_run``); and
``looped`` (not in the default list: ``STRAIGHT_MAX`` 0, the windowed sweeps
as loops over pairs with their masked tiles computed void); and, for what
a tile's exponentials cost (PR 47: nothing), ``noexp`` (``x / 2 + 1`` in
their place: wrong numbers, the time alone), ``polyexp`` (all of them a
polynomial on the vector unit) and ``split128`` / ``split256`` (that share
of a tile's columns).  ``load(name, root, edits)`` makes a build from the
module's source with pieces replaced, which is how PR 47 found the sums over
lanes and the transposed contractions.  Every kernel runs under a device scope of its own, so
one profiler trace holds them all; the time is the custom call's, from the
trace (`benchmark/trace_reduce.py read_xplane`), never a wall clock.

    python3 scripts/probe_flash_sweeps.py --parent .chip_archive/parent
    python3 scripts/probe_flash_sweeps.py --rehearse       # CPU, tiny, no times

Cases are the cells' shapes: ``w1024`` Mellum 2's window layers (4 x 8192,
32 / 4 heads of 128), ``w2048`` Trinity's (3 rows), ``full`` their full
layers, ``mla`` JoyAI's two-product kernels (2 x 8192, 32 heads of 128 + 64),
``halves`` SDAR's [noisy ; clean] rows (2 x 16384), ``olmoe`` (2 x 4096, 16
heads of 128), ``xl`` (2 x 1024, 25 heads of 64), ``lfm2`` (4 x 8192, 32
heads of 64, the key-value heads repeated as the model does).
"""
import argparse
import glob
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

MODULE = "deepspeed_tpu/ops/pallas/flash_attention.py"
# case: (rows, S, heads, kv heads, head_dim, window, kind)
CASES = {
    "w1024": (4, 8192, 32, 4, 128, 1024, "flash"),
    "w2048": (3, 8192, 32, 4, 128, 2048, "flash"),
    "full": (4, 8192, 32, 4, 128, None, "flash"),
    "mla": (2, 8192, 32, 32, 128, None, "mla"),
    "halves": (2, 16384, 32, 4, 128, None, "halves"),
    "olmoe": (2, 4096, 16, 16, 128, None, "flash"),
    "xl": (2, 1024, 25, 25, 64, None, "flash"),
    "lfm2": (4, 8192, 32, 32, 64, None, "flash"),
}


def load(name: str, root: str, edits=(), module: str = MODULE):
    """The flash module (or another ``module`` of the kernels' package) of
    the checkout at ``root`` under a name of its own (its relative imports
    resolve in this tree's package): fresh jits, so no build answers from
    another's trace cache.  ``edits`` are ``(old, new)`` pairs replaced in
    its source first, each of which must be there: a build that leaves a
    piece of a tile's work out, for its time alone."""
    path = os.path.join(root, module)
    spec = importlib.util.spec_from_file_location(
        f"deepspeed_tpu.ops.pallas._probe_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    with open(path) as f:
        source = f.read()
    for old, new in edits:
        assert old in source, old
        source = source.replace(old, new)
    exec(compile(source, path, "exec"), mod.__dict__)
    return mod


def poly_exp(x):
    """exp(x) for x <= 0 on the vector unit alone (Cephes' expf: two-part
    reduction by ln 2, a degree-5 polynomial, the power of two added to the
    exponent bits): what a tile's exponentials cost off the EUP."""
    f32 = jnp.float32
    xc = jnp.maximum(x, f32(-87.0))
    n = jnp.floor(xc * f32(1.44269504088896341) + f32(0.5))
    r = xc - n * f32(0.693359375) - n * f32(-2.12194440e-4)
    p = f32(1.9875691500e-4)
    for c in (1.3981999507e-3, 8.3334519073e-3, 4.1665795894e-2,
              1.6666665459e-1, 5.0000001201e-1):
        p = p * r + f32(c)
    y = p * (r * r) + r + f32(1.0)
    two_n = jax.lax.bitcast_convert_type(
        jax.lax.shift_left(n.astype(jnp.int32) + 127, 23), f32)
    return jnp.where(x < f32(-87.0), f32(0.0), y * two_n)


class _Numpy:
    """``jax.numpy`` with another ``exp``: given to a build's module."""

    def __init__(self, exp):
        self.exp = exp

    def __getattr__(self, name):
        return getattr(jnp, name)


def split_exp(lanes: int):
    """The first ``lanes`` columns of a score tile by :func:`poly_exp`, the
    rest (and every smaller array) by the EUP."""
    def exp(x):
        if x.ndim != 2 or x.shape[1] <= lanes:
            return jnp.exp(x)
        if not lanes:
            return poly_exp(x)
        return jnp.concatenate([poly_exp(x[:, :lanes]),
                                jnp.exp(x[:, lanes:])], axis=1)
    return exp


def builds(parent: str):
    out = {}
    if parent:
        out["parent"] = load("parent", parent)
    out["change"] = load("change", ROOT)
    single = out["single"] = load("single", ROOT)
    single._tiles_a_body = lambda heads: 1
    looped = out["looped"] = load("looped", ROOT)
    looped.STRAIGHT_MAX = 0     # the windowed sweeps as loops over pairs
    other = out["other"] = load("other", ROOT)
    fold_run = other._fold_run
    other._fold_run = lambda *a, branch=False, **k: fold_run(
        *a, branch=not branch, **k)
    # what a tile's exponentials cost: none (wrong numbers, the time
    # alone), all on the vector unit, a share of the tile's columns there
    for name, exp in (("noexp", lambda x: x * 0.5 + 1.0),
                      ("polyexp", split_exp(0)), ("split128", split_exp(128)),
                      ("split256", split_exp(256))):
        out[name] = load(name, ROOT)
        out[name].jnp = _Numpy(exp)
    return out


def kernels(fa, case, tag, rng, interpret, shrink):
    """``(fwd, bwd, ops, rest)``: the jitted forward and backward calls of a
    case, each under the device scope ``<tag>_fwd`` / ``<tag>_bwd``, the
    forward's operands and what the backward takes besides, from the
    forward's results."""
    B, S, H, KV, D, window, kind = CASES[case]
    block = 512
    if shrink:      # the same schedule in 128-position tiles
        B, S, block, H, KV = 1, S // 8, 128, min(H, 2), min(KV, 2)
        window = window and window // 8
        if case == "xl":
            KV = H

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    shape = (B, S // 2 if kind == "halves" else S, H, D)

    def like(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    rope = (like(B, S, H, 64), like(B, S, 1, 64)) if kind == "mla" else ()
    scale, bq, bk, lanes, terms = fa._prepare(
        like(*shape), like(*shape[:2], KV, D), None, block, block,
        like(*shape[:2], KV, D), *rope)
    qs, ks = (normal(B, S, H * D),), (normal(B, S, KV * D),)
    if rope:
        qs, ks = qs + (normal(B, S, H * 64),), ks + (normal(B, S,
                                                            terms[1].block),)
    ops = (qs, ks, normal(B, S, KV * D))
    static = dict(causal=True, scale=scale, block_q=bq, block_k=bk,
                  lanes=lanes, terms=terms, interpret=interpret,
                  window=window,
                  diag=(4, fa.HALVES) if kind == "halves" else None)
    fwd_call, bwd_call = fa._fwd_call, fa._bwd_call

    def fwd(*ops):
        with jax.named_scope(f"{tag}_fwd"):
            return fwd_call(*ops, **static)

    def bwd(*ops):
        with jax.named_scope(f"{tag}_bwd"):
            return bwd_call(*ops, **static)

    def rest(out, lse):
        do = normal(*out.shape)
        return do, lse, jax.jit(
            lambda do, out: fa._delta(do, out, lanes))(do, out)

    return jax.jit(fwd), jax.jit(bwd), ops, rest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default="")
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--builds", default="parent,change,single,other")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2718281828)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "probe_flash_sweeps"))
    args = ap.parse_args()
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: times come from the chip (--rehearse "
                         "runs the control flow here)")
    mods = {k: v for k, v in builds(args.parent).items()
            if k in args.builds.split(",")}
    runs = []
    for case in args.cases.split(","):
        first = ops = rest = None       # a case's builds share their operands
        for build, fa in mods.items():
            rng = np.random.default_rng(args.seed)
            tag = f"{case}_{build}"
            fwd, bwd, new_ops, make_rest = kernels(
                fa, case, tag, rng, args.rehearse, args.rehearse)
            if ops is None:
                ops = new_ops
                rest = make_rest(*fwd(*ops))
            got = jax.tree.leaves((fwd(*ops), bwd(*ops, *rest)))
            first = first or got
            # the largest difference of any output from the first build's
            diff = max(float(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)).max())
                       for a, b in zip(got, first))
            print(json.dumps({"case": case, "build": build,
                              "max_abs_diff_from_first_build": diff}),
                  flush=True)
            runs.append((tag, fwd, bwd, ops, rest))
    if args.rehearse:
        return
    os.makedirs(args.out, exist_ok=True)
    with jax.profiler.trace(args.out):
        for _ in range(args.reps):      # builds interleaved within a rep
            for tag, fwd, bwd, ops, rest in runs:
                jax.block_until_ready(fwd(*ops))
                jax.block_until_ready(bwd(*ops, *rest))
    from benchmark.trace_reduce import read_xplane

    path = max(glob.glob(os.path.join(args.out, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    dev_ops, _, _ = read_xplane(path)
    times = {}
    for name, _, dur in next(iter(dev_ops.values())):
        times.setdefault(name, []).append(dur)
    with open(os.path.join(args.out, "op_names.json"), "w") as f:
        json.dump({n: len(d) for n, d in times.items()}, f)
    for tag, *_ in runs:
        line = {"kernel": tag}
        for pass_ in ("fwd", "bwd"):
            durs = [d for name, ds in times.items()
                    if f"{tag}_{pass_}" in name for d in ds]
            if len(durs) != args.reps:      # say what the trace did hold
                line[f"{pass_}_events"] = len(durs)
                line["names"] = sorted(n for n in times if tag in n)
            if durs:
                line[f"{pass_}_ms"] = float(np.median(durs)) / 1e6
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
