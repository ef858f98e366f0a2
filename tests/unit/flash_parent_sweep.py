"""The flash kernels' sweeps as they were before PR 43: one FULL tile a
``fori_loop`` trip, a masked tile that only some programs meet under a
``lax.cond``.  Kept beside the tests as the reference that the sweeps of
``ops/pallas/flash_attention.py`` must equal EXACTLY (they fold the same
tiles in the same order; a tile computed void adds exact zeros); not in the
package.  :func:`parent_sweeps` puts it in the kernels' place."""
import collections
import contextlib
import functools
import hashlib
import importlib
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# the package re-exports the function under the module's name
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


def _halves_sweep(own, sched, *, own_is_q):
    b, g = sched.block_q, sched.diag[0]
    n = sched.S // (2 * b)
    subs = dict(sched.diagonal)
    noisy = own < n
    r = jnp.where(noisy, own, own - n)

    def sweep(carry, full_tile, diagonal_tile):
        def own_block(c):
            return diagonal_tile(r * b, 0, subs[fa.OWN_BLOCK], c)

        if own_is_q:
            carry = jax.lax.cond(noisy, own_block, lambda c: c, carry)
            carry = jax.lax.fori_loop(
                0, r, lambda t, c: full_tile((n + t) * b, c), carry)
            return diagonal_tile((n + r) * b, jnp.where(noisy, -g, 0),
                                 subs[fa.BLOCK_DIAGONAL], carry)

        def clean_keys(c):
            def past(u, c):
                t = r + 1 + u
                return full_tile(jnp.where(t < n, t, t + r + 1) * b, c)

            c = jax.lax.fori_loop(0, 2 * (n - 1 - r), past, c)
            return jax.lax.fori_loop(
                0, 2, lambda h, c: diagonal_tile(
                    (h * n + r) * b, (h - 1) * g, subs[fa.BLOCK_DIAGONAL], c),
                c)

        return jax.lax.cond(noisy, own_block, clean_keys, carry)

    return sweep


def _for_program(own, sched, program, *, own_is_q, heads=1):
    if sched.halves:
        return program(_halves_sweep(own, sched, own_is_q=own_is_q), True)
    bq, bk = sched.block_q, sched.block_k
    own_block, swept_block = (bq, bk) if own_is_q else (bk, bq)
    n_own = (sched.S // bq) if own_is_q else (sched.Sk // bk)
    n_swept = (sched.Sk // bk) if own_is_q else (sched.S // bq)
    sign = 1 if own_is_q else -1

    def diagonal_of(o):
        met = [(o * own_block - sign * d0, d0, subs)
               for d0, subs in sched.diagonal]
        return [m for m in met if m[0] % swept_block == 0
                and 0 <= m[0] < n_swept * swept_block]

    def static_sweep(o):
        def sweep(carry, full_tile, diagonal_tile):
            for t in range(*fa._full_tiles(o, sched, own_is_q=own_is_q)):
                carry = full_tile(t * swept_block, carry)
            for t0, d0, subs in diagonal_of(o):
                carry = diagonal_tile(t0, d0, subs, carry)
            return carry
        return sweep

    def dynamic_sweep(carry, full_tile, diagonal_tile):
        lo, hi = fa._full_tiles(own, sched, own_is_q=own_is_q)
        carry = jax.lax.fori_loop(
            lo, hi, lambda t, c: full_tile(t * swept_block, c), carry)
        for d0, subs in sched.diagonal:
            meets = [any(m[1] == d0 for m in diagonal_of(o))
                     for o in range(n_own)]
            t0 = own * own_block - sign * d0
            step = functools.partial(diagonal_tile, t0, d0, subs)
            if all(meets):
                carry = step(carry)
            elif any(meets):
                carry = jax.lax.cond(
                    (t0 >= 0) & (t0 % swept_block == 0)
                    & (t0 < n_swept * swept_block), step, lambda c: c, carry)
        return carry

    if fa._is_looped(sched, own_is_q=own_is_q):
        program(dynamic_sweep, True)
    elif not sched.causal or n_own == 1:
        program(static_sweep(0), False)
    else:
        for o in range(n_own):
            pl.when(own == o)(
                functools.partial(program, static_sweep(o), False))


_CALLS = ("_fwd_call", "_bwd_call")


@contextlib.contextmanager
def parent_sweeps():
    """Inside, the flash kernels are built with the sweeps above.  The
    jitted calls are wrapped anew, so that no trace of the package's sweeps
    answers from jit's cache (nor one of these afterwards)."""
    kept = {name: getattr(fa, name) for name in (*_CALLS, "_for_program")}

    def fresh(name):
        inner = kept[name].__wrapped__
        return jax.jit(lambda *a, **k: inner(*a, **k),
                       static_argnames=fa._STATIC, inline=True)

    try:
        fa._for_program = _for_program
        for name in _CALLS:
            setattr(fa, name, fresh(name))
        yield
    finally:
        for name, value in kept.items():
            setattr(fa, name, value)


def passes(q, k, v, do, *, block, window=None, causal=True, halves=None):
    """``(out, lse, dq, dk, dv)`` of one forward and one backward call in
    interpret mode, operands ``(B, S, H, D)`` (``k``, ``v`` at the key-value
    heads); ``halves`` is the block length of ``[noisy ; clean]`` rows."""
    B = q.shape[0]
    if halves is None:
        scale, bq, bk, lanes, terms = fa._prepare(q, k, None, block, block)
        diag = None
    else:
        half = lambda x: jax.ShapeDtypeStruct(
            (B, x.shape[1] // 2) + x.shape[2:], x.dtype)
        scale, bq, bk, lanes, terms = fa._prepare(half(q), half(k), None,
                                                  block, block)
        diag = (halves, fa.HALVES)
    static = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
                  lanes=lanes, terms=terms, interpret=True, window=window,
                  diag=diag)
    q, k, v, do = (fa._pack(x, lanes) for x in (q, k, v, do))
    out, lse = fa._fwd_call((q,), (k,), v, **static)
    grads = fa._bwd_call((q,), (k,), v, do, lse, fa._delta(do, out, lanes),
                         **static)
    return (out, lse, *grads)


def mla_passes(qn, qr, kn, kr, v, do, *, block):
    """``(out, lse, dq_nope, dq_rope, dk_nope, dk_rope, dv)`` of the
    two-product kernels, operands ``(B, S, H, ·)`` and ONE rope key."""
    B, S, H, D = qn.shape
    R = qr.shape[-1]
    scale, bq, bk, lanes, terms = fa._prepare(qn, kn, None, block, block, v,
                                              qr, kr)
    static = dict(causal=True, scale=scale, block_q=bq, block_k=bk,
                  lanes=lanes, terms=terms, interpret=True)
    qs = (qn.reshape(B, S, H * D), qr.reshape(B, S, H * R))
    ks = (kn.reshape(B, S, H * D),
          jnp.tile(kr.reshape(B, S, R), (1, 1, terms[1].heads)))
    v, do = v.reshape(B, S, H * D), do.reshape(B, S, H * D)
    out, lse = fa._fwd_call(qs, ks, v, **static)
    return (out, lse, *fa._bwd_call(qs, ks, v, do, lse,
                                    fa._delta(do, out, lanes), **static))


def assert_equal_to_the_parents(run, names):
    """``run()`` under this tree's sweeps and under the parent's: every
    result equal, entry for entry."""
    new = run()
    with parent_sweeps():
        old = run()
    assert len(new) == len(old) == len(names)
    for name, a, b in zip(names, new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def traced_digest(fn, *args) -> str:
    """sha256 of the jaxpr that ``fn(*args)`` traces to, as text without
    source lines and with a ``shard_map``'s set of axes in order (a set
    prints in the order of its strings' hashes, a process its own): what
    a pin computed on another commit can be held to."""
    # a function of its own: see kernel_primitives
    text = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    text = re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(m.group(1).split(", "))), text)
    return hashlib.sha256(re.sub(r" at /\S+:\d+", "", text).encode()
                          ).hexdigest()


def kernel_primitives(fn, *args, names=("cond", "while", "scan")):
    """How often each primitive of ``names`` (the control-flow ones, unless
    told otherwise) appears inside the Pallas kernels that ``fn(*args)``
    traces."""
    found = collections.Counter()

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if inside and name in names:
                found[name] += 1
            for sub in _subjaxprs(eqn):
                walk(sub, inside or name == "pallas_call")

    # a function of its own: make_jaxpr answers from jit's cache for one
    # it has traced, whatever sweeps the kernels had then
    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr, False)
    return dict(found)


def kernel_jaxprs(fn, *args):
    """The jaxpr of every Pallas kernel that ``fn(*args)`` traces, in the
    order of their calls."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["jaxpr"])
                continue
            for sub in _subjaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    return found


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for sub in (value if isinstance(value, (tuple, list)) else (value,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _inner_names(eqn, names):
    """For each jaxpr inside ``eqn``, its variables' names taken from the
    names of ``eqn``'s operands (``names``: variable -> name)."""
    ins, p = eqn.invars, eqn.params
    if eqn.primitive.name == "cond":
        pairs = [(b.jaxpr, ins[1:]) for b in p["branches"]]
    elif eqn.primitive.name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        pairs = [(p["cond_jaxpr"].jaxpr, ins[:nc] + ins[nc + nb:]),
                 (p["body_jaxpr"].jaxpr, ins[nc:])]
    else:
        pairs = [(sub, ins) for sub in _subjaxprs(eqn)]
    for sub, outer in pairs:
        assert len(sub.invars) == len(outer), eqn.primitive.name
        yield sub, {i: names[o] for i, o in zip(sub.invars, outer)
                    if not hasattr(o, "val") and o in names}


class Products(NamedTuple):
    """What a kernel's jaxpr does around the MXU."""
    dots: list      # (lhs type, rhs type, result type) of every dot_general
    widened: set    # refs a value loaded from which is cast to float32 whole
    narrowed: int   # casts from a float type to a narrower one
    turned: int     # products that contract their left operand over its
    #                 rows (transposed: the chip turns the operand first)


def kernel_products_and_casts(kernel, ref_names) -> Products:
    """:class:`Products` of a kernel's jaxpr; ``ref_names`` names its
    leading refs in order."""
    dots, widened, narrowed, turned = [], set(), 0, 0

    def walk(jaxpr, refs):
        nonlocal narrowed, turned
        loaded = {}
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "get" and eqn.invars[0] in refs:
                loaded[eqn.outvars[0]] = refs[eqn.invars[0]]
            elif name == "dot_general":
                dots.append((*(str(v.aval.dtype) for v in eqn.invars),
                             str(eqn.outvars[0].aval.dtype)))
                (lhs, _), _ = eqn.params["dimension_numbers"]
                turned += tuple(lhs) == (0,)
            elif name == "convert_element_type":
                src, to = eqn.invars[0].aval.dtype, eqn.params["new_dtype"]
                if (jnp.issubdtype(src, jnp.floating)
                        and jnp.issubdtype(to, jnp.floating)
                        and jnp.dtype(to).itemsize < src.itemsize):
                    narrowed += 1
                if to == jnp.float32 and eqn.invars[0] in loaded:
                    widened.add(loaded[eqn.invars[0]])
            for sub, inner in _inner_names(eqn, refs):
                walk(sub, inner)

    walk(kernel, dict(zip(kernel.invars, ref_names)))
    return Products(dots, widened, narrowed, turned)


def mxu_operand_counts():
    """``flash_mxu_operands_total`` as ``{(pass, dtype): count}``."""
    from deepspeed_tpu.telemetry import get_registry

    entry = get_registry().snapshot().get("flash_mxu_operands_total")
    return collections.Counter() if not entry else collections.Counter({
        (s["labels"]["pass"], s["labels"]["dtype"]): s["value"]
        for s in entry["samples"]})


@contextlib.contextmanager
def traced_operand_types():
    """Yields a set that holds, after the block, the operand types of the
    products of every flash kernel body traced inside it
    (``flash_mxu_operands_total``)."""
    types, before = set(), mxu_operand_counts()
    yield types
    types.update(dtype for (_, dtype), n in
                 (mxu_operand_counts() - before).items() if n)
