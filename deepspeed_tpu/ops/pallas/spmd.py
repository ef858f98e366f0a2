"""Shared SPMD dispatch for Pallas kernels.

A ``pallas_call`` is opaque to XLA's SPMD partitioner: on a sharded mesh
it must be wrapped in ``shard_map`` (or XLA gathers the operands), and on
a multi-device process with no registered mesh the only safe answer is
"don't use the kernel".  Every kernel wrapper shares this decision logic
so mesh-axis policy lives in ONE place.

Verdicts:
- ``("direct", None)`` — single device: call the kernel directly.
- ``("shard", batch_axes)`` — wrap in full-manual shard_map, batch dim
  sharded over ``batch_axes`` (+ optionally heads over ``tp``).
- ``(None, None)`` — the mesh refuses the kernel (caller takes the XLA
  path and says so through :func:`note_dispatch`).

A guard (a shape check, or the mesh plan above) may choose the XLA path;
once it has said yes, the kernel's exceptions propagate — no dispatch
site catches them and substitutes a reference.  What each site resolved
to, and why, is counted at trace time in ``kernel_dispatch_total`` and
read back by :func:`dispatch_report`.

A kernel family's way in is :func:`plan` (the family's shape guard in, the
verdict out, one ``kernel_dispatch_total`` row booked) and
:func:`over_batch` (the call under that verdict: itself, or the
``shard_map`` over the batch axes).  ``ops/attention.py`` alone adds heads
over ``tp`` to the verdict and keeps a ``shard_map`` of its own for it.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from ...comm.mesh import DATA_AXES, get_mesh
from ...telemetry import registry as _registry


def kernel_mesh_plan(batch_size: int, *, heads: Optional[int] = None,
                     allow_tp: bool = False, sp: bool = False, mesh=None
                     ) -> Tuple[Optional[str], Optional[tuple]]:
    """Decide how a batch-parallel Pallas kernel may run under the mesh.

    ``pp`` meshes refuse: pipeline code is already inside a manual
    shard_map over ``pp`` (nesting full-manual would throw).  ``sp``
    refuses too unless the kernel IS sequence-parallel (``sp=True`` — the
    ring engine, which handles the sequence dim itself); batch-parallel
    kernels cannot split it.  ``tp`` is allowed only when the kernel
    shards heads (``allow_tp``).
    """
    import jax

    if mesh is None:
        mesh = get_mesh(required=False)
    if mesh is None:
        if jax.device_count() > 1:
            return None, None   # unknown shardings: kernel would be opaque
        return "direct", None
    n_dev = int(np.prod(list(mesh.shape.values())))
    if n_dev == 1:
        return "direct", None
    if mesh.shape.get("pp", 1) > 1:
        return None, None
    if not sp and mesh.shape.get("sp", 1) > 1:
        return None, None
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and not (allow_tp and heads is not None and heads % tp == 0):
        return None, None
    batch_axes = tuple(a for a in DATA_AXES if mesh.shape.get(a, 1) > 1)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    if batch_size % bsz:
        return None, None
    return "shard", batch_axes


def on_tpu() -> bool:
    """``ops/attention.py on_tpu``, looked up when asked: the one name a
    test patches to stand in for the chip."""
    from .. import attention

    return attention.on_tpu()


class Plan(NamedTuple):
    """How a family's kernels run a call: ``kernel_mesh_plan``'s verdict
    (``"direct"`` or ``"shard"``) and, under ``"shard"``, the batch axes."""
    verdict: str
    axes: Optional[tuple] = None


def mesh_said(verdict: Optional[str], axes: Optional[tuple]) -> str:
    """``kernel_mesh_plan``'s answer as a dispatch row's reason ends on it."""
    if verdict is None:
        return "kernel_mesh_plan refused the mesh"
    return "one device" if verdict == "direct" \
        else f"shard_map over batch axes {axes}"


def plan(site: str, batch: Optional[int], refusal: Optional[str], what: str,
         *, fallback: str = "xla", kernel: str = "pallas", tpu: bool = True,
         shard: bool = True, must: bool = False) -> Optional[Plan]:
    """The one decision of a kernel family's dispatch.  ``refusal`` is the
    family's own guard (why the shapes keep the ``fallback`` form, or
    None); past it a family that is not ``tpu``-blind wants a TPU, and
    ``kernel_mesh_plan`` is asked about ``batch`` rows (None: the caller
    is inside a ``shard_map`` or on one device and says so).  A family
    whose kernels are written for one device's own operands says
    ``shard=False``.  Books one ``kernel_dispatch_total{site}`` row: the
    ``kernel`` with ``what`` and how the mesh runs it, or the ``fallback``
    with the guard that refused - which under ``must`` (the kernels were
    asked for by name) raises instead.  Returns the :class:`Plan`, or None
    where the fallback runs."""
    verdict, axes = "direct", None
    if refusal is None and tpu and not on_tpu():
        refusal = "no TPU"
    if refusal is None and batch is not None:
        verdict, axes = kernel_mesh_plan(batch)
        if verdict is None:
            refusal = mesh_said(verdict, axes)
        elif verdict == "shard" and not shard:
            refusal = ("the kernels take one device's own operands, the "
                       "mesh a " + mesh_said(verdict, axes))
    if refusal is not None:
        if must:
            raise NotImplementedError(f"{site} impl={kernel!r}: {refusal}")
        note_dispatch(site, fallback, refusal)
        return None
    note_dispatch(site, kernel, what if batch is None
                  else f"{what}; {mesh_said(verdict, axes)}")
    return Plan(verdict, axes)


def over_batch(fn, plan: Tuple[str, Optional[tuple]], args: tuple,
               outs: int = 1, whole: Tuple[int, ...] = ()):
    """``fn(*args)`` under a :func:`plan`: itself on one device, else a
    ``shard_map`` over the plan's batch axes.  Every argument (None where
    absent) and each of the ``outs`` results carries the batch in its
    leading dimension and is split there, but for the arguments at the
    positions ``whole`` - taps, scales, a table that serves every row -
    which go to every rank as they are."""
    verdict, axes = plan
    if verdict == "direct":
        return fn(*args)
    import jax
    from jax.sharding import PartitionSpec as P

    def rows(ndim):
        return P(axes if axes else None, *(None,) * (ndim - 1))

    specs = tuple(None if a is None else P() if i in whole else rows(a.ndim)
                  for i, a in enumerate(args))
    out = rows(args[0].ndim)
    return jax.shard_map(fn, mesh=get_mesh(), in_specs=specs,
                         out_specs=(out,) * outs if outs > 1 else out,
                         check_vma=False)(*args)


def _dispatch_counter():
    return _registry.counter(
        "kernel_dispatch_total",
        "what each kernel dispatch site resolved to, and why (counted at "
        "trace time, not per call)", labelnames=("site", "impl", "reason"))


def note_dispatch(site: str, impl: str, reason: str) -> None:
    """Record that dispatch ``site`` resolved to ``impl`` because of
    ``reason`` (the guard that decided)."""
    _dispatch_counter().labels(site, impl, reason).inc()


def dispatch_report() -> List[Tuple[str, str, str, int]]:
    """``(site, impl, reason, count)`` rows, sorted by site."""
    return sorted((*labels, int(child.value))
                  for labels, child in _dispatch_counter().samples())
