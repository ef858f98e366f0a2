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
"""
from __future__ import annotations

from typing import List, Optional, Tuple

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
