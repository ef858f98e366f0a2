"""Recompilation watchdog: catch silent XLA recompiles in hot loops.

The single most expensive silent failure mode on TPU: a shape or dtype
that drifts between steps (a ragged final batch, a python float that
became an array, a cache that grew) makes ``jax.jit`` trace + compile a
NEW executable — seconds to minutes of stall that looks like "training
got slow" with no error anywhere.  The reference has nothing comparable
(CUDA eager mode doesn't recompile); on XLA it is the first thing to
rule out.

:func:`watch` wraps a jitted callable.  Each call computes a cheap
host-side signature — the args pytree structure plus every leaf's
(shape, dtype) — the shape/dtype part of the key ``jax.jit``'s C++
cache dispatches on; the part it cannot see (shardings, layouts) is
covered by a post-call ``_cache_size()`` cross-check: executable-count
growth on an already-known signature is also flagged as a recompile.
The FIRST distinct signature per watched site is the expected warm-up
compile; every NEW signature after that means the hot loop recompiled:

- ``xla_recompiles_total{site=...}`` increments (once per new signature);
- a rate-limited warning names the site and the offending leaf shapes,
  diffed against the previously seen signature when possible;
- where the wrapped function exposes ``_cache_size()`` (jitted
  callables do), the executable count is cross-checked into the log.

Sites whose signatures legitimately vary (chunked prefill compiles one
executable per power-of-two chunk BY DESIGN) pass ``warn=False``: their
compile population lands in ``xla_compiled_signatures_total`` only, so
``xla_recompiles_total`` stays a clean page-the-oncall alert metric.

Disable globally with ``DSTPU_RECOMPILE_WATCHDOG=0`` (``watch`` then
returns the callable unwrapped).

The watchdog sees only the jits it wraps.  :func:`install_compile_events`
(called once, at telemetry import) sees every executable the process
builds, eager ``dynamic_slice`` programs included: listeners over
``jax.monitoring`` add each compile's seconds to
``xla_compile_seconds_total{phase=trace|lower|backend|fetch, span}`` and
count it in ``xla_executables_total{how=built|fetched, span}``, where
``span`` is the innermost :mod:`.trace` span open on the compiling thread
(``none`` outside any), and write each backend compile into the tracer's
ring as a ``compile/backend`` child of that span.  So "where did set-up
go" and "which step compiled" are one counter read and one span query.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Optional

from ..utils.logging import logger
from . import registry as _registry
from . import trace as _trace

__all__ = ["watch", "RecompileWatchdog", "total_recompiles", "WATCHDOG_ENV",
           "install_compile_events"]

WATCHDOG_ENV = "DSTPU_RECOMPILE_WATCHDOG"

_WARN_INTERVAL_S = 30.0


def _leaf_sig(leaf: Any):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype),
                bool(getattr(leaf, "weak_type", False)))
    # python scalars trace as weak-typed values: the VALUE does not key
    # the jit cache, the python type does
    return type(leaf).__name__


def _tree_sig(tree):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


def _leaf_sigs_of(sig):
    out = []
    for part in sig:
        if part is not None:
            out.extend(part[1])
    return out


def _describe(sig) -> str:
    shapes = [f"{s[0]}:{s[1]}" for s in _leaf_sigs_of(sig)
              if isinstance(s, tuple)]
    head = ", ".join(shapes[:8])
    if len(shapes) > 8:
        head += f", … +{len(shapes) - 8} more"
    return head


def _diff(old_sig, new_sig) -> Optional[str]:
    """First differing leaf between two signatures with the same tree
    structure — usually THE offending argument."""
    if old_sig is None:
        return None
    old_parts = [p[0] for p in old_sig if p is not None]
    new_parts = [p[0] for p in new_sig if p is not None]
    if old_parts != new_parts:
        return None
    for i, (a, b) in enumerate(zip(_leaf_sigs_of(old_sig),
                                   _leaf_sigs_of(new_sig))):
        if a != b:
            return f"leaf #{i}: {a} -> {b}"
    return None


class _Watched:
    """Transparent wrapper: forwards ``__call__`` through the signature
    check, everything else (``lower``, ``_cache_size`` …) to the wrapped
    callable."""

    __slots__ = ("_fn", "_name", "_warn", "_dog", "_sigs", "_last_sig",
                 "_arg0_obj", "_arg0_sig", "_max_cache_size", "_settled")

    def __init__(self, fn, name: str, warn: bool, dog: "RecompileWatchdog"):
        self._fn = fn
        self._name = name
        self._warn = warn
        self._dog = dog
        self._sigs = set()
        self._last_sig = None          # signature of the PREVIOUS call —
        self._arg0_obj = None          # the loop that was actually running
        self._arg0_sig = None
        self._max_cache_size = None
        self._settled = False          # saw >=1 call with NO cache growth

    def _signature_of(self, args, kwargs):
        # (head, rest) pair: the first positional arg signed separately
        # with an identity memo — serving passes the same params tree
        # every tick; skip re-flattening its hundreds of leaves
        if args and args[0] is self._arg0_obj:
            head = self._arg0_sig
        elif args:
            head = _tree_sig((args[0],))
            self._arg0_obj = args[0]   # strong ref: pins the python tree
            self._arg0_sig = head      # (donated buffers are already
        else:                          # deleted; only wrappers persist)
            head = None
        return (head, _tree_sig((args[1:], kwargs)))

    def __call__(self, *args, **kwargs):
        try:
            sig = self._signature_of(args, kwargs)
        except Exception:
            sig = None   # unhashable leaf etc.: never break the hot path
        is_new = sig is not None and sig not in self._sigs
        if is_new:
            first = not self._sigs
            self._sigs.add(sig)
            self._dog._on_new_signature(self, sig, self._last_sig, first)
        self._last_sig = sig
        if is_new:
            # a new signature means this call pays trace+compile before
            # dispatch returns — bill it to the goodput "recompile" phase
            # (warm-up included: compile time is not goodput either way)
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            try:
                from . import goodput

                goodput.note_compile(time.perf_counter() - t0)
            except Exception:
                pass
        else:
            out = self._fn(*args, **kwargs)
        # cross-check: jax.jit's C++ cache also keys on SHARDINGS and
        # layouts, which the host-side signature cannot see — if the
        # executable count grew on an already-known signature, the loop
        # recompiled anyway (e.g. a resharded state after checkpoint load)
        try:
            cs = self._fn._cache_size()
        except Exception:
            cs = None
        if cs is not None:
            if self._max_cache_size is not None and cs > self._max_cache_size:
                # growth counts only once the site has SETTLED (seen a
                # call with no growth): the warm-up phase legitimately
                # compiles per-layout variants as eager-built buffers are
                # replaced by committed jit outputs
                if self._settled and not is_new and sig is not None:
                    self._dog._on_hidden_recompile(self, cs)
            elif self._max_cache_size is not None:
                self._settled = True
            if self._max_cache_size is None or cs > self._max_cache_size:
                self._max_cache_size = cs
        return out

    def __getattr__(self, attr):
        return getattr(self._fn, attr)

    @property
    def signatures_seen(self) -> int:
        return len(self._sigs)


class RecompileWatchdog:
    def __init__(self, registry: Optional[_registry.Registry] = None,
                 warn_interval_s: float = _WARN_INTERVAL_S):
        self._registry = registry or _registry.get_registry()
        self._warn_interval_s = warn_interval_s
        self._last_warn: dict = {}
        self._recompiles = self._registry.counter(
            "xla_recompiles_total",
            "post-warm-up distinct jit signatures per watched site",
            labelnames=("site",))
        self._compiles = self._registry.counter(
            "xla_compiled_signatures_total",
            "all distinct jit signatures per watched site (warm-up "
            "included)", labelnames=("site",))

    def enabled(self) -> bool:
        return os.environ.get(WATCHDOG_ENV, "1") != "0"

    def watch(self, fn, name: str, warn: bool = True):
        """Wrap ``fn``; returns ``fn`` unchanged when the watchdog is
        disabled.  ``warn=False`` counts signatures without warning
        (for sites whose shapes vary by design)."""
        if not self.enabled():
            return fn
        return _Watched(fn, name, warn, self)

    def _on_new_signature(self, watched: _Watched, sig, prev_call_sig,
                          first: bool):
        self._compiles.labels(site=watched._name).inc()
        if first or not watched._warn:
            # warn=False sites vary by design: their compile population
            # stays out of the alert counter, which must mean "a hot loop
            # recompiled unexpectedly" and nothing else
            return
        self._recompiles.labels(site=watched._name).inc()
        if not self._should_warn(watched._name):
            return
        # diff against the PREVIOUS CALL's signature — the loop that was
        # actually running — not the last novel one
        diff = _diff(prev_call_sig, sig)
        cache_size = ""
        try:
            cs = watched._fn._cache_size()
            cache_size = f"; jit cache held {cs} executable(s) before this call"
        except Exception:
            pass
        detail = diff if diff is not None else \
            f"arg shapes now [{_describe(sig)}]"
        logger.warning(
            f"XLA RECOMPILE in hot loop {watched._name!r}: signature "
            f"#{len(watched._sigs)} after warm-up ({detail}){cache_size}. "
            f"Each recompile stalls the loop for the full compile time — "
            f"check for drifting batch/cache shapes or dtype flips.")

    def _on_hidden_recompile(self, watched: _Watched, cache_size: int):
        """Executable count grew on an already-known arg signature: the
        jit cache keys on shardings/layouts too, so the loop recompiled
        for a reason the shape signature cannot show."""
        self._compiles.labels(site=watched._name).inc()
        if not watched._warn:
            # by-design-varying sites (per-width placement etc.) hit this
            # legitimately — e.g. an uncommitted initial buffer becoming a
            # committed jit output; keep them out of the alert counter
            return
        self._recompiles.labels(site=watched._name).inc()
        if not self._should_warn(watched._name):
            return
        logger.warning(
            f"XLA RECOMPILE in hot loop {watched._name!r}: executable "
            f"count grew to {cache_size} with UNCHANGED arg shapes/dtypes "
            f"— the jit cache also keys on shardings and layouts; check "
            f"for a resharded params/state tree (e.g. after checkpoint "
            f"load or a mesh change).")

    def _should_warn(self, site: str) -> bool:
        now = time.monotonic()
        if now - self._last_warn.get(site, -1e18) < self._warn_interval_s:
            return False
        self._last_warn[site] = now
        return True


_default_watchdog: Optional[RecompileWatchdog] = None


def _get_default() -> RecompileWatchdog:
    global _default_watchdog
    if _default_watchdog is None:
        _default_watchdog = RecompileWatchdog()
    return _default_watchdog


def watch(fn, name: str, warn: bool = True):
    """Module-level convenience over the default watchdog."""
    return _get_default().watch(fn, name, warn=warn)


def total_recompiles() -> float:
    """Sum of ``xla_recompiles_total`` across sites (0.0 when nothing
    recompiled or the watchdog never armed)."""
    return _get_default()._recompiles.total()


# ----------------------------------------------------------------------
# compile events: seconds and executables by phase and by open span
# ----------------------------------------------------------------------
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_compile_tls = threading.local()
_compile_events_installed = False


def _span_label() -> str:
    cur = _trace.current()
    return "none" if cur is None else cur.name


def _on_compile_duration(event: str, secs: float, **kw) -> None:
    """``jax.monitoring`` duration listener.  The phases do not overlap:
    a jit traced inside another's trace is counted once (in the outer
    one's seconds), and ``backend_compile_duration``, which JAX takes
    around build-or-fetch, is booked as ``backend`` less the ``fetch``
    seconds of the same executable."""
    tls = _compile_tls
    if event == _TRACE_EVENT:
        # ``open_traces`` holds, for each trace open on this thread, the
        # seconds of the traces that ended inside it (_on_compile_begin
        # pushes): book this one's seconds less those, once
        stack = getattr(tls, "open_traces", None)
        inside = stack.pop() if stack else 0.0
        if stack:
            stack[-1] += secs
        phase, secs = "trace", max(0.0, secs - inside)
    elif event == _LOWER_EVENT:
        phase = "lower"
    elif event == _FETCH_EVENT:
        tls.fetch_s = secs
        phase = "fetch"
    elif event == _BACKEND_EVENT:
        fetched = getattr(tls, "hit", False)
        fetch_s = getattr(tls, "fetch_s", 0.0) if fetched else 0.0
        tls.hit, tls.fetch_s = False, 0.0
        how = "fetched" if fetched else "built"
        _executables().labels(how=how, span=_span_label()).inc()
        _trace.record("compile/backend", secs, fun=kw.get("fun_name"),
                      how=how)
        phase, secs = "backend", max(0.0, secs - fetch_s)
    else:
        return
    _seconds().labels(phase=phase, span=_span_label()).inc(secs)


def _on_compile_begin(event: str, value, **kw) -> None:
    # JAX records a scalar (the start time) when a timed phase begins
    if event == _TRACE_EVENT:
        try:
            _compile_tls.open_traces.append(0.0)
        except AttributeError:
            _compile_tls.open_traces = [0.0]


def _on_compile_event(event: str, **kw) -> None:
    # a persistent-cache hit is announced before the fetch's duration and
    # the enclosing backend_compile_duration of the same executable
    if event == _HIT_EVENT:
        _compile_tls.hit = True


def _seconds():
    return _registry.counter(
        "xla_compile_seconds_total",
        "seconds JAX spent making executables, by phase (trace, lower, "
        "backend compile, persistent-cache fetch) and innermost open span",
        labelnames=("phase", "span"))


def _executables():
    return _registry.counter(
        "xla_executables_total",
        "executables made, built by the compiler or fetched from the "
        "persistent cache, by innermost open span",
        labelnames=("how", "span"))


def install_compile_events() -> None:
    """Register the listeners (idempotent; telemetry import calls it):
    durations, cache hits, and the scalar JAX records when a timed phase
    begins, which is what lets nested traces be told from siblings.
    Listening touches no backend."""
    global _compile_events_installed
    if _compile_events_installed:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
    jax.monitoring.register_event_listener(_on_compile_event)
    jax.monitoring.register_scalar_listener(_on_compile_begin)
    _compile_events_installed = True

