"""Gradients in the dtype the backward wrote them in.

A model that keeps fp32 master weights and casts a leaf to bf16 where it
uses it gets that leaf's gradient as ``convert(bf16 cotangent) -> fp32``:
the transpose of its own cast.  XLA writes the bf16 result of the
weight-gradient matmul and fuses the convert into whatever reads it, but
it fuses nothing into the operand of a Pallas call: a kernel handed the
fp32 gradient costs a gradient-sized fp32 buffer first (2 + 4 + 4 bytes a
parameter where 2 were enough; PERF.md section 6, PR 27).

:func:`narrow_grads` reads the equations the backward was staged as
instead of asking the model: a gradient that is nothing but an exact
up-cast of a narrower float (through reshapes, transposes, sharding
constraints and the ``remat`` / ``jit`` / ``scan`` call it came out of) is
cast back down, which XLA folds with the up-cast to the buffer the matmul
wrote.  A leaf the model uses uncast (a LayerNorm, a float32 router), a
tied leaf whose gradient is a sum, a gradient accumulated in fp32: all
keep fp32, and no number changes anywhere.

It looks at the gradient tracers' producing equations (``tracer.parent``,
the staging trace's own record; JAX has no public reader for it).  Tracing
the backward a second way to get a jaxpr to read (``make_jaxpr`` inside
the step's trace) was measured: +10 s of the 48-layer step's 22 s of
tracing (v5e host, PR 27).  Where the record is absent (not under ``jit``,
or a JAX that keeps it elsewhere) every gradient keeps its dtype, which is
correct and slower; ``tests/unit/test_grad_origin.py`` fails then.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

# value-preserving, one operand
_MOVES = frozenset({"reshape", "transpose", "squeeze", "sharding_constraint",
                    "copy", "copy_p"})
# equations whose outputs are the outputs of the jaxpr they hold, in order
_CALLS = frozenset({"pjit", "jit", "remat2", "checkpoint", "closed_call",
                    "core_call", "scan"})


def _exact_upcast(src, dst):
    """``src`` where every ``src`` value is a ``dst`` value, else None."""
    if not (jnp.issubdtype(src, jnp.floating)
            and jnp.issubdtype(dst, jnp.floating)):
        return None
    a, b = jnp.finfo(src), jnp.finfo(dst)
    if a.bits < b.bits and a.nmant <= b.nmant and a.maxexp <= b.maxexp:
        return src
    return None


def written_dtype(jaxpr, var):
    """The narrower float dtype ``var`` is an exact up-cast of, or
    ``None``.  ``var`` is a variable of ``jaxpr`` (not an input)."""
    producers = {o: e for e in jaxpr.eqns for o in e.outvars}
    while not isinstance(var, jex_core.Literal):
        eqn = producers.get(var)
        if eqn is None:
            return None
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            narrow = _exact_upcast(src, var.aval.dtype)
            if narrow is not None or src != var.aval.dtype:
                return narrow
            var = eqn.invars[0]
        elif name in _MOVES:
            var = eqn.invars[0]
        elif name in _CALLS:
            inner = eqn.params.get("jaxpr")
            inner = getattr(inner, "jaxpr", inner)
            if inner is None:
                return None
            return written_dtype(inner,
                                 inner.outvars[eqn.outvars.index(var)])
        else:
            return None
    return None


def _tracer_written_dtype(tracer):
    """:func:`written_dtype` for a value of the trace being staged."""
    while True:
        eqn = getattr(tracer, "parent", None)
        if eqn is None:
            return None
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.in_tracers[0]
            narrow = _exact_upcast(src.aval.dtype, tracer.aval.dtype)
            if narrow is not None or src.aval.dtype != tracer.aval.dtype:
                return narrow
            tracer = src
        elif name in _MOVES:
            tracer = eqn.in_tracers[0]
        elif name in _CALLS:
            inner = eqn.params.get("jaxpr")
            inner = getattr(inner, "jaxpr", inner)
            at = [i for i, v in enumerate(eqn.outvars) if v is tracer.val]
            if inner is None or len(at) != 1:
                return None
            return written_dtype(inner, inner.outvars[at[0]])
        else:
            return None


def narrow_grads(grads):
    """``grads`` (the values of a backward being staged under ``jit``) with
    every leaf that is an exact up-cast cast back to the dtype it was
    computed in."""
    def narrow(g):
        dt = _tracer_written_dtype(g)
        return g if dt is None else g.astype(dt)

    return jax.tree_util.tree_map(narrow, grads)
