"""ZeRO as sharding policy.

The reference implements ZeRO with ~7k lines of imperative partition
bookkeeping (``runtime/zero/stage_1_and_2.py``, ``stage3.py``,
``partition_parameters.py``, ``partitioned_param_coordinator.py``): flatten
params into per-rank flat buffers, hook every grad, bucket + reduce-scatter
on side streams, allgather updated partitions, trace module execution to
prefetch.  On TPU every one of those mechanisms is a *sharding decision*
handed to XLA:

=======  =====================================  ==============================
stage    reference mechanism                    TPU-native policy
=======  =====================================  ==============================
0        bucketed grad allreduce                grads psum'd by XLA (pure DP)
1        optimizer-state partitions (:1425)     opt-state leaves sharded on
                                                ``fsdp``; XLA reduce-scatters
                                                grads into the update and
                                                all-gathers new params
2        + grad partitions w/ hooks (:783)      + grad-accumulation buffer
                                                sharded on ``fsdp``
3        + param partitions, per-module         + params sharded on ``fsdp``;
         gather/release + prefetch              XLA all-gathers per layer
         (stage3.py:1084, coordinator)          inside the scanned block and
                                                frees after use (remat scan =
                                                the "coordinator")
=======  =====================================  ==============================

``zero.Init`` (``partition_parameters.py:529`` — monkey-patching
``nn.Module.__init__`` to shard at construction) becomes: initialize under
``jax.jit`` with sharded ``out_shardings``, so full params NEVER
materialize on one device.  No patching required.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.common import TP_RULES
from ..utils.logging import logger


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        size = 1
        for a in axis:
            size *= mesh.shape[a]
        return size
    return mesh.shape[axis]


def add_fsdp_to_spec(spec: P, shape: tuple, mesh, axis: str = "fsdp") -> P:
    """Add the ``fsdp`` mesh axis to the best-fitting dim of ``spec``.

    Picks the largest dim whose size is divisible by fsdp×(already-assigned
    axes); leaves the spec unchanged if nothing fits (small params stay
    replicated — the same params the reference keeps in
    ``persistent_parameters``, ``stage3.py`` param-persistence threshold).
    """
    fsdp_size = mesh.shape[axis]
    if fsdp_size == 1 or not shape:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best_dim, best_size = None, 0
    for d, dim_size in enumerate(shape):
        existing = entries[d]
        if existing is not None:
            existing_axes = existing if isinstance(existing, tuple) else (existing,)
            if axis in existing_axes:
                return spec
            divisor = _axis_size(mesh, existing_axes) * fsdp_size
        else:
            divisor = fsdp_size
        if dim_size % divisor == 0 and dim_size > best_size:
            best_dim, best_size = d, dim_size
    if best_dim is None:
        return spec
    existing = entries[best_dim]
    if existing is None:
        entries[best_dim] = axis
    else:
        existing_axes = existing if isinstance(existing, tuple) else (existing,)
        entries[best_dim] = (*existing_axes, axis)
    return P(*entries)


def logical_spec(leaf) -> P:
    """PartitionSpec of logical names from a flax ``Partitioned`` box (or P())."""
    names = getattr(leaf, "names", None)
    if names is None:
        return P()
    return P(*names)


def resolve_tp(spec: P, shape: tuple, mesh, rules: dict) -> P:
    """Map logical names → mesh axes through ``rules``, with divisibility checks."""
    entries = []
    for d, name in enumerate(spec):
        axis = rules.get(name) if name is not None else None
        if axis is not None:
            size = _axis_size(mesh, axis)
            if d < len(shape) and shape[d] % size != 0:
                if name == "layers":
                    # heterogeneous pipeline partitioning: an uneven
                    # stacked-layer dim cannot shard over pp (pjit wants
                    # even splits), so the stored stack stays replicated;
                    # the pipeline step zero-pads to ceil and reshards
                    # into the manual-pp shard_map per step.  Divisible
                    # layer counts keep the memory-optimal pp sharding.
                    entries.append(None)
                    continue
                raise ValueError(
                    f"param dim {d} (logical {name!r}, size {shape[d]}) not divisible "
                    f"by mesh axis {axis!r} size {size}")
        entries.append(axis)
    return P(*entries)


def param_partition_specs(abstract_params, mesh, zero_stage: int,
                          rules: Optional[dict] = None):
    """PartitionSpec tree for *parameters* given ZeRO stage + TP rules.

    ``abstract_params``: pytree of ShapeDtypeStruct, possibly boxed in
    ``flax.linen.Partitioned`` metadata carrying logical axis names.
    """
    rules = dict(TP_RULES if rules is None else rules)

    def spec_for(leaf) -> P:
        value = getattr(leaf, "value", leaf)  # unbox Partitioned
        shape = np.shape(value) if not hasattr(value, "shape") else value.shape
        spec = resolve_tp(logical_spec(leaf), shape, mesh, rules)
        if zero_stage >= 3:
            spec = add_fsdp_to_spec(spec, shape, mesh)
        return spec

    return jax.tree_util.tree_map(
        spec_for, abstract_params,
        is_leaf=lambda x: hasattr(x, "names") and hasattr(x, "value"))


def shard_like_stage3(abstract_params, mesh, rules: Optional[dict] = None):
    """Stage-3-style specs regardless of configured stage — used for
    optimizer-state (stage ≥1) and grad-accumulator (stage ≥2) placement."""
    return param_partition_specs(abstract_params, mesh, zero_stage=3, rules=rules)


def opt_state_specs(optimizer, abstract_params, param_like_specs):
    """PartitionSpec tree for the optax state.

    Param-shaped leaves (Adam mu/nu, …) follow ``param_like_specs``;
    scalars (step counts) replicate.  This is the whole of the reference's
    optimizer-state partitioning (``stage_1_and_2.py:1425``
    ``_partition_base_optimizer_state``).
    """
    import optax

    unboxed = jax.tree_util.tree_map(
        lambda x: getattr(x, "value", x), abstract_params,
        is_leaf=lambda x: hasattr(x, "names") and hasattr(x, "value"))
    abstract_opt = jax.eval_shape(optimizer.init, unboxed)
    try:
        return optax.tree_map_params(
            optimizer,
            lambda _, spec: spec,
            abstract_opt,
            param_like_specs,
            transform_non_params=lambda _: P(),
        )
    except (ValueError, TypeError, AttributeError):
        # custom transforms (ops/adam8bit.py) keep param-SHAPED state the
        # placeholder protocol can't see; shard any state leaf that shares
        # a param's shape like that param, replicate the rest (count,
        # per-row scales).  Scoped to states that actually carry the
        # custom transform — a mapping failure for a standard optimizer is
        # a real bug and must surface.
        from ..ops.adam8bit import Adam8bitState

        def subtrees(t):
            yield t
            if isinstance(t, (tuple, list)):
                for c in t:
                    yield from subtrees(c)

        if not any(isinstance(t, Adam8bitState)
                   for t in subtrees(abstract_opt)):
            raise
        # structure-match param-shaped subtrees against the param tree
        # (NOT by leaf shape: two same-shaped params with different specs
        # would silently share the first param's spec)
        pstruct = jax.tree_util.tree_structure(unboxed)

        def walk(node):
            if isinstance(node, Adam8bitState):
                return Adam8bitState(
                    count=P(),
                    m_codes=param_like_specs,
                    r_codes=param_like_specs,
                    # (…, 1) row scales replicate (can't inherit a
                    # row-sharded spec on their squeezed dim)
                    scales=jax.tree_util.tree_map(lambda _: P(),
                                                  node.scales))
            try:
                if jax.tree_util.tree_structure(node) == pstruct:
                    return param_like_specs
            except (ValueError, TypeError):
                pass
            if isinstance(node, tuple):
                parts = [walk(c) for c in node]
                return type(node)(*parts) if hasattr(node, "_fields") \
                    else tuple(parts)
            return jax.tree_util.tree_map(lambda _: P(), node)

        return walk(abstract_opt)


def named_shardings(mesh, spec_tree):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), spec_tree,
                                  is_leaf=lambda x: isinstance(x, P))


def scatter_grads(grads, mesh, grad_specs):
    """State the gradient partitioning of stages 2 and 3 inside the
    compiled step: each gradient leaf constrained to its shard's spec, which
    XLA implements as the reduce-scatter.  The constraint carries the device
    scope ``zero/scatter``.  There is no ``zero/gather`` to match it: no line
    of this program gathers a parameter; the SPMD partitioner places each
    all-gather at the operation that consumes the shard, and it carries
    that operation's module path (``h_3/attn/...``)."""
    from ..telemetry import trace

    with trace.device_span("zero/scatter"):
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)), grads, grad_specs)


def required_recv_bytes(abstract_leaves, spec_tree, mesh, dtype=None,
                        axis: str = "fsdp") -> int:
    """Bytes ONE device has to receive to hold (a gather) or to have
    reduced (a scatter) every leaf that ``spec_tree`` shards over ``axis``,
    ONCE, by the partition alone: the leaf's bytes in ``dtype`` (its own
    where ``None`` or not floating), less what the other axes of its spec
    keep elsewhere, times ``(n - 1) / n``.  What the compiled step really
    moves is in its executable (``telemetry/device_scopes.py
    collective_ledger``); the ratio of the two is how many times it moves
    it."""
    n = mesh.shape[axis]
    total = 0
    leaves = jax.tree_util.tree_leaves(abstract_leaves)
    specs = jax.tree_util.tree_leaves(spec_tree,
                                      is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(leaves, specs):
        axes = [a for entry in spec if entry is not None
                for a in (entry if isinstance(entry, tuple) else (entry,))]
        if axis not in axes:
            continue
        narrowed = dtype is not None and jax.numpy.issubdtype(
            leaf.dtype, jax.numpy.floating)
        itemsize = np.dtype(dtype if narrowed else leaf.dtype).itemsize
        held_elsewhere = _axis_size(mesh, [a for a in axes if a != axis])
        total += int(np.prod(leaf.shape)) * itemsize // held_elsewhere
    return total * (n - 1) // n


def record_required_recv(abstract_params, param_specs, abstract_grads,
                         grad_specs, mesh, compute_dtype, grad_dtype,
                         registry=None) -> None:
    """Book ``zero_required_recv_bytes{what=gather|scatter}`` when the
    engine places its state: one gather pass of the sharded parameters in
    the compute type, one scatter of the sharded gradients in the type
    they are reduced in.  Nothing on a mesh whose ``fsdp`` axis is 1."""
    if mesh.shape["fsdp"] <= 1:
        return
    from ..telemetry import get_registry

    gauge = (registry or get_registry()).gauge(
        "zero_required_recv_bytes",
        "bytes one device must receive for ONE pass over the ZeRO "
        "partition", labelnames=("what",))
    gauge.labels(what="gather").set(float(required_recv_bytes(
        abstract_params, param_specs, mesh, compute_dtype)))
    gauge.labels(what="scatter").set(float(required_recv_bytes(
        abstract_grads, grad_specs, mesh, grad_dtype)))


def validate_stage_mesh(zero_stage: int, mesh) -> None:
    if zero_stage >= 1 and mesh.shape["fsdp"] == 1 and mesh.shape["dp"] > 1:
        logger.warning(
            f"ZeRO stage {zero_stage} requested but mesh has fsdp=1, dp="
            f"{mesh.shape['dp']}: optimizer/param sharding will be a no-op. "
            "Put data-parallel devices on the 'fsdp' axis (the engine does "
            "this automatically when it builds the mesh).")


# ---------------------------------------------------------------------------
# zero.Init / GatheredParameters — the user-facing partition_parameters API
# ---------------------------------------------------------------------------

class Init:
    """Sharded-at-construction parameter init (reference ``zero.Init``,
    ``partition_parameters.py:529``).

    The reference monkey-patches ``nn.Module.__init__`` so every parameter
    is partitioned the moment it is created.  In JAX, construction and
    materialization are already separate: flax modules are metadata until
    ``init`` runs, so this context simply runs ``model.init`` under ``jit``
    with sharded ``out_shardings`` — the full tree NEVER exists on one
    device, which is the whole point of the reference context.

    The engine's ``init_params`` runs the same sharded-init recipe (plus
    optimizer-state/loss-scale placement, via the shared
    :func:`param_partition_specs`); this explicit form is for custom
    loops::

        with zero.Init(mesh=mesh) as zinit:
            params = zinit.materialize(model, rng, **model.dummy_inputs())
    """

    def __init__(self, mesh=None, zero_stage: int = 3,
                 rules: Optional[dict] = None, config_dict_or_path=None,
                 remote_device: Optional[str] = None, pin_memory: bool = False,
                 enabled: bool = True, dtype=None, mpu=None):
        from ..comm import mesh as mesh_mod

        self.mesh = mesh if mesh is not None else mesh_mod.get_mesh(required=False)
        self.zero_stage = zero_stage if enabled else 0
        self.rules = dict(TP_RULES if rules is None else rules)
        self.dtype = dtype
        # remote_device/pin_memory/mpu accepted for reference-signature
        # parity; host placement is the swap_tensor module's job
        if remote_device not in (None, "none"):
            logger.warning("zero.Init(remote_device=...) is handled by the "
                           "offload config on TPU; ignoring here")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def materialize(self, model, rng, **inputs):
        """``model.init`` with per-leaf sharded out_shardings; returns the
        UNBOXED param tree (leaves are sharded ``jax.Array``s)."""
        import flax.linen as nn
        import jax.numpy as jnp

        if self.mesh is None:
            raise ValueError("zero.Init needs a mesh (init_distributed first "
                             "or pass mesh=)")
        def _fake(x):
            # only array-like leaves are zero-faked; Python scalars/flags
            # (e.g. deterministic=True) must pass through verbatim or the
            # traced init would take the wrong branch
            if isinstance(x, (bool, int, float, str)) or x is None:
                return x
            return jnp.zeros(np.shape(x), getattr(x, "dtype", None)
                             or np.asarray(x).dtype)

        fake = jax.tree_util.tree_map(_fake, inputs)
        abstract = jax.eval_shape(lambda r: model.init(r, **fake), rng)["params"]
        specs = param_partition_specs(abstract, self.mesh, self.zero_stage,
                                      rules=self.rules)
        shardings = named_shardings(self.mesh, specs)

        def _init(r):
            params = nn.meta.unbox(model.init(r, **fake)["params"])
            if self.dtype is not None:
                params = jax.tree_util.tree_map(
                    lambda p: p.astype(self.dtype), params)
            return params

        # dstpu-lint: disable-next-line=DSTPU005 -- one-shot sharded param init at engine construction; the executable is intentionally single-use
        return jax.jit(_init, out_shardings=shardings)(rng)


class GatheredParameters:
    """Context yielding the FULL (host-gathered, mutable) parameter tree;
    modifications re-shard on exit (reference ``GatheredParameters``,
    ``partition_parameters.py:1502`` with ``modifier_rank``).

    Works on an :class:`~deepspeed_tpu.runtime.engine.Engine` (writes the
    modified tree back into engine state) or a raw param tree (read the
    re-sharded result from ``.result`` after the block)::

        with GatheredParameters(engine) as full:
            full["wte"][:4] = 0.0            # numpy, fully materialized

        with GatheredParameters(params) as full:
            full["w"] *= 2
        params = ctx.result

    ``enabled=False`` (reference pattern ``enabled=(stage == 3)``) is a
    true no-op: the block receives the ORIGINAL tree — sharded, immutable
    ``jax.Array`` leaves, not mutable numpy — and nothing is written back
    on exit.  Unlike torch, the un-gathered leaves are never mutable, so
    code that writes through the context must run with ``enabled=True``.
    """

    def __init__(self, source, modifier_rank=0, fwd_module=None, enabled=True):
        self._engine = source if hasattr(source, "_state") else None
        self._params = None if self._engine is not None else source
        # ``enabled=False`` is a no-op switch (reference semantics: callers
        # write ``enabled=(stage == 3)`` to skip the expensive gather):
        # __enter__ yields the unmodified source tree and __exit__ writes
        # nothing back.
        self.enabled = enabled
        self.result = None
        # reference modifier_rank semantics (partition_parameters.py:1502):
        # only the modifier rank's writes persist — __exit__ broadcasts its
        # host tree, so other processes' mutations are discarded.
        self.modifier_rank = modifier_rank

    def __enter__(self):
        self._orig = self._source_tree()
        if not self.enabled:
            self.result = self._orig
            return self._orig
        # leaf-at-a-time gather: only ONE leaf is ever fully replicated on
        # device before its host copy lands and the replica is dropped, so
        # peak device memory is bounded by the largest leaf, not the model
        self._host = jax.tree_util.tree_map(_gather_to_host, self._orig)
        return self._host

    def _source_tree(self):
        if self._engine is not None:
            return self._engine.params
        return self._params

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None or not self.enabled:
            return False
        if jax.process_count() > 1 and self.modifier_rank is not None:
            # only the modifier rank's edits survive (reference
            # modifier_rank contract) — host-plane broadcast keeps every
            # process's re-sharded tree identical.  modifier_rank=None is
            # the reference's "all ranks modified identically" mode: no
            # broadcast.
            from .. import comm as _comm

            self._host = _comm.host_broadcast(self._host,
                                              src=self.modifier_rank)
        resharded = jax.tree_util.tree_map(
            lambda h, o: jax.device_put(
                jnp_asarray(h, getattr(o, "dtype", None)),
                getattr(o, "sharding", None)),
            self._host, self._orig)
        self.result = resharded
        if self._engine is not None:
            import dataclasses as _dc

            stored = resharded
            if getattr(self._engine, "_has_store_transform", False):
                # the context works in canonical (global) layer order —
                # engine storage may be local-slot permuted (interleaved)
                # and/or padded+placed (balanced/uneven partitioning)
                stored = self._engine._to_stored_params(stored)
            self._engine._state = _dc.replace(self._engine._state,
                                              params=stored)
        return False


def jnp_asarray(x, dtype):
    import jax.numpy as jnp

    return jnp.asarray(x, dtype)


def _gather_to_host(x) -> np.ndarray:
    """Full host copy of a (possibly cross-host sharded) array.

    ``np.array`` on an array spanning non-addressable devices raises, so
    replicate on-device first (a collective every process participates in)
    — then copy to host and DROP the device replica immediately, so a
    tree-wide gather holds at most one replicated leaf on device."""
    if isinstance(x, jax.Array) and isinstance(x.sharding, NamedSharding) \
            and not x.is_fully_replicated:
        repl = jax.device_put(x, NamedSharding(x.sharding.mesh, P()))
        host = np.array(repl)
        repl.delete()
        return host
    return np.array(x)


def register_external_parameter(module, param) -> None:
    """Reference ``partition_parameters.py:91`` registers params used outside
    their owning module so the ZeRO-3 coordinator gathers them.  XLA's
    dataflow analysis sees every use of every sharded array, so there is
    nothing to register — kept as an explicit no-op for API parity."""
    del module, param
