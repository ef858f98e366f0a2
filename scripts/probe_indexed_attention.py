#!/usr/bin/env python
"""Device time a call of the three indexed-attention kernels
(``indexed_attn_fwd | dq | dkv``) at the tenth cell's shape, (1, 32768,
32 / 4, 128) with 2,048 keys a query, under several builds of
``ops/pallas/indexed_attention.py`` in one process and one profiler trace
(PR 57; ``probe_flash_sweeps.py load`` makes a build from the source with
pieces replaced: WRONG numbers, for the time alone).

Builds of a parent checkout (``--parent DIR``, the kernels as PR 54 wrote
them): ``parent``; ``p_heads2`` (part 1 alone: two heads a trip of every
heads' loop); the controls ``p_noexp`` (``x / 2 + 1`` for every exponential)
and ``p_nolanes`` (every sum and maximum over lanes a first column: what
parts 2 and 3 could give at most).  Builds of this tree: ``change``;
``c_heads1`` (one head a trip: part 2 alone forward and in dq, part 3 alone
in dkv); ``c_lanesum`` (a sum over lanes where a partial sum a lane is kept:
part 1 alone forward and in dq); ``c_heads2`` / ``c_heads8`` (that many
heads a trip where this tree runs four); ``c_maxblocks`` (the row maximum
over the tile's 128-lane column blocks first, then over lanes: Mosaic does
that already, the time is the change's); ``c_noexp`` and ``c_nolanes``, the
controls again, for what is left.  Every build's custom calls carry its name, and the time is the
custom call's, from the trace; a build whose kernel body equals another's
is answered from the compile cache and runs under the other's name.

    python3 scripts/probe_indexed_attention.py --parent .chip_archive/parent
    python3 scripts/probe_indexed_attention.py --rehearse   # CPU, tiny, no times
"""
import argparse
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from probe_flash_sweeps import _Numpy, load

MODULE = "deepspeed_tpu/ops/pallas/indexed_attention.py"
KERNELS = ("fwd", "dq", "dkv")
_TWO = "lambda t, c: head(2 * t + 1, head(2 * t, c))"
# part 1 in the parent's three heads' loops
HEADS2 = (
    ("        jax.lax.fori_loop(0, heads, head, 0)",
     f"        jax.lax.fori_loop(0, heads // 2, {_TWO}, 0)"),
    ("        pbar = jax.lax.fori_loop(\n            0, heads, head,",
     f"        pbar = jax.lax.fori_loop(\n            0, heads // 2, {_TWO},"),
    ("    return jax.lax.fori_loop(0, heads, head,\n",
     f"    return jax.lax.fori_loop(0, heads // 2, {_TWO},\n"),
)


def _first_column(x, axis=None, keepdims=False, **kw):
    """``jnp.sum`` with a row sum left out: the first column in its place."""
    if axis == 1 and keepdims:
        return x[:, :1]
    return jnp.sum(x, axis=axis, keepdims=keepdims, **kw)


class _NoLaneSums(_Numpy):
    def __init__(self, exp=jnp.exp):
        super().__init__(exp)
        self.sum = _first_column


def lane_blocks_max(s):
    out = s[:, :128]
    for c in range(128, s.shape[1], 128):
        out = jnp.maximum(out, s[:, c:c + 128])
    return out


def build(name, root, edits=(), no_lanes=False, exp=None, **attrs):
    """A build of the module at ``root`` whose custom calls are named
    ``<name>__indexed_attn_*``."""
    path = os.path.join(root, MODULE)
    with open(path) as f:
        source = f.read()
    edits = list(edits) + [(f'name="indexed_attn_{k}"',
                            f'name="{name}__indexed_attn_{k}"')
                           for k in KERNELS]
    if no_lanes:    # x.max(axis=1, ...) and x.sum(axis=1, ...): a column
        for old in sorted(set(re.findall(
                r"[\w\[\]]+\.(?:max|sum)\(axis=1, keepdims=True\)", source))):
            edits.append((old, old.split(".")[0] + "[:, :1]"))
    mod = load(name, root, edits, module=MODULE)
    if no_lanes or exp:
        mod.jnp = (_NoLaneSums if no_lanes else _Numpy)(exp or jnp.exp)
    for attr, value in attrs.items():
        setattr(mod, attr, value)
    return mod


def builds(parent):
    noexp = lambda x: x * 0.5 + 1.0     # noqa: E731
    out = {}
    if parent:
        out["parent"] = build("parent", parent)
        out["p_heads2"] = build("p_heads2", parent, HEADS2)
        out["p_noexp"] = build("p_noexp", parent, exp=noexp)
        out["p_nolanes"] = build("p_nolanes", parent, no_lanes=True)
    out["change"] = build("change", ROOT)
    out["c_heads1"] = build("c_heads1", ROOT, _heads_a_trip=lambda heads: 1)
    out["c_lanesum"] = build(
        "c_lanesum", ROOT,
        _lane_blocks_sum=lambda p: p.sum(axis=1, keepdims=True))
    out["c_noexp"] = build("c_noexp", ROOT, exp=noexp)
    for n in (2, 8):
        out[f"c_heads{n}"] = build(f"c_heads{n}", ROOT,
                                   _heads_a_trip=lambda heads, n=n: n)
    out["c_maxblocks"] = build(
        "c_maxblocks", ROOT,
        [("s.max(axis=1, keepdims=True)",
          "_lane_blocks_max(s).max(axis=1, keepdims=True)")],
        _lane_blocks_max=lane_blocks_max)
    out["c_nolanes"] = build("c_nolanes", ROOT, no_lanes=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default="")
    ap.add_argument("--builds", default="")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=57)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "probe_indexed_attention"))
    args = ap.parse_args()
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: times come from the chip (--rehearse "
                         "runs the control flow here)")
    B, S, H, KV, D, NI, DI, K, bq, bk = 1, 32768, 32, 4, 128, 16, 64, 2048, \
        256, 512
    if args.rehearse:
        S, H, KV, NI, K, bq, bk = 256, 8, 2, 4, 48, 64, 128
    mods = builds(args.parent)
    if args.builds:
        mods = {k: mods[k] for k in args.builds.split(",")}
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    bf = jnp.bfloat16
    q, do = (jax.random.normal(kk, (B, S, H * D), jnp.float32).astype(bf)
             for kk in ks[:2])
    k, v = (jax.random.normal(kk, (B, S, KV * D), jnp.float32).astype(bf)
            for kk in ks[2:4])
    qi = jax.random.normal(ks[4], (B, NI, S, DI), jnp.float32).astype(bf)
    ki = jax.random.normal(ks[5], (B, S, DI), jnp.float32).astype(bf)
    kit = jnp.swapaxes(ki, 1, 2)
    w = jax.random.normal(ks[6], (B, S, NI), jnp.float32) * (NI * DI) ** -0.5
    dkl = jax.random.uniform(ks[7], (B, S, 1), jnp.float32)
    blocks = dict(block_q=bq, block_k=bk, interpret=args.rehearse)
    static = dict(heads=H, kv_heads=KV, scale=D ** -0.5, **blocks)
    # the residuals every build's backward reads are this tree's
    here = mods.get("change") or build("residuals", ROOT)
    sel = tuple(here.select_call(qi, kit, w, topk=K, **blocks))
    out, lse, _, lse_i, _ = here.forward_call(q, k, v, qi, kit, w, sel,
                                              **static)
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        B, S, H, D).sum(-1)
    runs, first = [], None
    for name, mod in mods.items():
        calls = (
            jax.jit(functools.partial(mod.forward_call, **static)),
            jax.jit(functools.partial(mod.dq_call, **static)),
            jax.jit(functools.partial(mod.dkv_call, **static)))
        ops = ((q, k, v, qi, kit, w, sel),
               (q, k, v, do, lse, delta, qi, kit, ki, w, lse_i, dkl, sel),
               (q, k, v, do, lse, delta, qi, kit, w, lse_i, dkl, sel))
        t0 = time.perf_counter()    # the three compile here
        got = jax.block_until_ready(
            jax.tree.leaves([f(*o) for f, o in zip(calls, ops)]))
        first_call_s = time.perf_counter() - t0
        first = first or got
        diff = max(float(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)).max())
                   for a, b in zip(got, first))
        print(json.dumps({"build": name, "first_call_s": first_call_s,
                          "max_abs_diff_from_first_build": diff}), flush=True)
        runs.append((name, calls, ops))
    if args.rehearse:
        return
    from chip_smoke import _traced_op_times

    os.makedirs(args.out, exist_ok=True)
    with jax.profiler.trace(args.out):
        for _ in range(args.reps):      # builds interleaved within a rep
            for _, calls, ops in runs:
                for f, o in zip(calls, ops):
                    jax.block_until_ready(f(*o))
    times = _traced_op_times(args.out)
    table = {}
    for name, _, _ in runs:
        line = {"build": name}
        for kernel in KERNELS:
            ns = times.get(f"{name}__indexed_attn_{kernel}", [])
            if len(ns) != args.reps:
                line[f"{kernel}_events"] = len(ns)
            if ns:
                line[f"{kernel}_ms"] = float(np.median(ns)) / 1e6
        table[name] = line
        print(json.dumps(line), flush=True)
    base = table.get("parent") or table.get("change")
    for name, line in table.items():    # each against the first build
        print(name.ljust(10) + "  ".join(
            f"{kernel} {line[f'{kernel}_ms']:7.2f} ms "
            f"({100 * (line[f'{kernel}_ms'] / base[f'{kernel}_ms'] - 1):+5.1f}%)"
            for kernel in KERNELS if f"{kernel}_ms" in line), flush=True)


if __name__ == "__main__":
    main()
