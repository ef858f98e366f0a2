"""Recompilation watchdog: catch silent XLA recompiles in hot loops.

The single most expensive silent failure mode on TPU: a shape or dtype
that drifts between steps (a ragged final batch, a python float that
became an array, a cache that grew) makes ``jax.jit`` trace + compile a
NEW executable — seconds to minutes of stall that looks like "training
got slow" with no error anywhere.  The reference has nothing comparable
(CUDA eager mode doesn't recompile); on XLA it is the first thing to
rule out.

:func:`watch` wraps a jitted callable and OBSERVES compiles; it does not
predict them.  :func:`install_compile_events` keeps, for every thread,
a count of the executables that thread has made and no watched call has
claimed.  Around each call the wrapper reads that count: if it did not
move, the call returns — no flatten, no hash.  Only a call that made an executable signs its
arguments (pytree structure plus every leaf's shape, dtype and weak
type; read after the call, all three survive donation) and applies the
rules:

- the site's first compiling call is the expected warm-up;
- a signature the site has not compiled before is a recompile:
  ``xla_recompiles_total{site=...}`` increments and a rate-limited
  warning names the leaf that changed, diffed against the nearest
  signature the site has compiled;
- a known signature that compiles AGAIN changed something the
  signature cannot show (an input's sharding or layout, a static
  argument's value): a recompile once the site has had one call that
  compiled nothing, warm-up churn before that (eager-built buffers
  being replaced by committed jit outputs).

``watch(..., staged=True)`` (the training engine's steps) also KEEPS what
it observes: such a site makes its executable itself, through
``fn.trace(*args).lower().compile()`` (one trace, one lowering and one
build-or-fetch a signature, under the persistent-cache key a plain call
has), calls the kept ``Compiled`` from then on, and books its memory
once (:func:`memory.record_compiled`) and, where it runs on more than one
device, its collectives (:func:`device_scopes.record_collectives`).  Only
a call the kept executable refuses (its own argument check: ``TypeError``
/ ``ValueError`` before anything is donated) is signed; it then goes to an executable the site
made earlier for that signature, or makes a new one under the rules
above.  ``handle.compiled`` is the executable that runs.

Sites whose signatures legitimately vary (chunked prefill compiles one
executable per power-of-two chunk BY DESIGN) pass ``warn=False``: their
compile population lands in ``xla_compiled_signatures_total`` only, so
``xla_recompiles_total`` stays a clean page-the-oncall alert metric.
An executable made inside a nested watched call is the inner site's.

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

import threading
import time
from typing import Any, Optional

from ..utils.logging import logger
from . import device_scopes as _device_scopes
from . import goodput as _goodput
from . import memory as _memory
from . import registry as _registry
from . import trace as _trace

__all__ = ["watch", "RecompileWatchdog", "total_recompiles",
           "install_compile_events"]

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


def _describe(sig) -> str:
    shapes = [f"{s[0]}:{s[1]}" for s in sig[1] if isinstance(s, tuple)]
    head = ", ".join(shapes[:8])
    if len(shapes) > 8:
        head += f", … +{len(shapes) - 8} more"
    return head


def _nearest_diff(compiled, new_sig) -> tuple:
    """``(indices, old_leaves)``: the leaves in which ``new_sig`` differs
    from the nearest (fewest differing leaves) signature of the same tree
    structure among ``compiled``, and that signature's leaves; ``([],
    None)`` when none shares the structure."""
    best, best_old = [], None
    for old in compiled:
        if old[0] != new_sig[0]:
            continue
        idx = [i for i, (a, b) in enumerate(zip(old[1], new_sig[1]))
               if a != b]
        if best_old is None or len(idx) < len(best):
            best, best_old = idx, old[1]
    return best, best_old


def _what_changed(compiled, sig, args, kwargs) -> str:
    """The warning's detail: the first leaf in which the call's ``sig``
    differs from the nearest signature the site has ``compiled``, named
    by its path in ``(args, kwargs)``."""
    idx, old_leaves = _nearest_diff(compiled, sig)
    if not idx:
        return f"arg shapes now [{_describe(sig)}]"
    import jax

    paths = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    i = idx[0]
    more = f", +{len(idx) - 1} more" if len(idx) > 1 else ""
    return (f"leaf #{i} args{jax.tree_util.keystr(paths[i][0])}: "
            f"{old_leaves[i]} -> {sig[1][i]}{more}")


class _Watched:
    """Transparent wrapper: forwards ``__call__`` through the compile
    observer, everything else (``lower``, ``_cache_size`` …) to the
    wrapped callable."""

    __slots__ = ("_fn", "_name", "_warn", "_dog", "_sigs", "_settled")

    def __init__(self, fn, name: str, warn: bool, dog: "RecompileWatchdog"):
        self._fn = fn
        self._name = name
        self._warn = warn
        self._dog = dog
        self._sigs = set()             # signatures this site has compiled
        self._settled = False          # saw >=1 call that compiled nothing

    def __call__(self, *args, **kwargs):
        tls = _compile_tls
        before = tls.unclaimed
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if tls.unclaimed == before:
            self._settled = True
            return out
        # this call made an executable; taking the count back to where it
        # stood keeps a watched call that encloses this one from claiming
        # the same executable
        tls.unclaimed = before
        # compile time is not goodput, warm-up included
        _goodput.note_compile(time.perf_counter() - t0)
        self._dog._on_compile(self, args, kwargs)
        return out

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


class _Staged(_Watched):
    """A watched ``jax.jit`` that keeps the executable it runs.  The
    steady call is the kept ``Compiled`` and nothing else: no flatten, no
    hash, no signature (its C++ fast path checks the arguments as
    ``jax.jit``'s does).

    :meth:`prepare` makes an executable AHEAD of the first call, on the
    caller's thread (one of its own, beside other set-up): the first call
    whose arguments it accepts runs it instead of compiling, and books it
    as that call's compile (signature, memory, collectives) as if it had
    made it."""

    __slots__ = ("compiled", "_made", "_prepared")

    def __init__(self, fn, name: str, warn: bool, dog: "RecompileWatchdog"):
        super().__init__(fn, name, warn, dog)
        self.compiled = None           # the executable the last call ran
        self._made = {}                # signature -> executables made for it
        self._prepared = []            # made ahead of a call, not yet run

    def prepare(self, *args, **kwargs) -> None:
        """Trace, lower and compile for arguments like these: arrays, or
        ``jax.ShapeDtypeStruct``s that carry the shardings the call's will
        have.  What a later call does not accept costs that call nothing but
        its own compile."""
        self._prepared.append(self._fn.trace(*args, **kwargs).lower().compile())

    def __call__(self, *args, **kwargs):
        compiled = self.compiled
        if compiled is not None:
            try:
                out = compiled(*args, **kwargs)
            except (TypeError, ValueError):
                # the argument check refused: another signature, sharding
                # or layout, or tracers; nothing was donated
                pass
            else:
                self._settled = True
                return out
        return self._refused(args, kwargs)

    def _refused(self, args, kwargs):
        import jax

        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves((args, kwargs))):
            # under someone's trace (the flops profiler lowers a function
            # that calls the step): the jit inlines itself
            return self._fn(*args, **kwargs)
        sig = _tree_sig((args, kwargs))
        for compiled in self._made.get(sig, ()):
            if compiled is self.compiled:
                continue
            try:
                out = compiled(*args, **kwargs)
            except (TypeError, ValueError):
                continue
            self.compiled = compiled
            self._settled = True
            return out
        tls = _compile_tls
        before = tls.unclaimed
        t0 = time.perf_counter()
        compiled = None
        for ready in list(self._prepared):
            try:        # the argument check refuses before anything is donated
                out = ready(*args, **kwargs)
            except (TypeError, ValueError):
                continue
            self._prepared.remove(ready)
            compiled = ready
            break
        if compiled is None:
            compiled = self._fn.trace(*args, **kwargs).lower().compile()
            out = compiled(*args, **kwargs)
        self._made.setdefault(sig, []).append(compiled)
        self.compiled = compiled
        tls.unclaimed = before         # as _Watched: the executable is ours
        _goodput.note_compile(time.perf_counter() - t0)
        self._dog._on_compile(self, args, kwargs, sig)
        _memory.record_compiled(compiled, site=self._name,
                                registry=self._dog._registry)
        _device_scopes.record_collectives(compiled, site=self._name,
                                          registry=self._dog._registry)
        return out


class RecompileWatchdog:
    def __init__(self, registry: Optional[_registry.Registry] = None,
                 warn_interval_s: float = _WARN_INTERVAL_S):
        self._registry = registry or _registry.get_registry()
        self._warn_interval_s = warn_interval_s
        self._last_warn: dict = {}

    # get-or-create at every use (a compile is rare): a handle kept from
    # construction would count into nothing after ``Registry.clear()``
    @property
    def _recompiles(self):
        return self._registry.counter(
            "xla_recompiles_total",
            "post-warm-up distinct jit signatures per watched site",
            labelnames=("site",))

    @property
    def _compiles(self):
        return self._registry.counter(
            "xla_compiled_signatures_total",
            "all distinct jit signatures per watched site (warm-up "
            "included)", labelnames=("site",))

    def watch(self, fn, name: str, warn: bool = True, staged: bool = False):
        """Wrap ``fn``.  ``warn=False`` counts signatures without
        warning (for sites whose shapes vary by design).  ``staged``:
        ``fn`` is a ``jax.jit`` with no static arguments, and the site
        keeps the executable it runs (:class:`_Staged`)."""
        return (_Staged if staged else _Watched)(fn, name, warn, self)

    def _on_compile(self, watched: _Watched, args, kwargs, sig=None):
        """``watched``'s call just made an executable: sign what it was
        called with (donated leaves keep shape, dtype and weak type),
        unless the caller has, and say which kind of compile it was."""
        if sig is None:
            sig = _tree_sig((args, kwargs))
        site = watched._name
        if sig not in watched._sigs:
            self._compiles.labels(site=site).inc()
            # the site's first compile is warm-up; warn=False sites vary
            # by design: their compile population stays out of the alert
            # counter, which must mean "a hot loop recompiled
            # unexpectedly" and nothing else
            if watched._sigs and watched._warn:
                self._recompiles.labels(site=site).inc()
                if self._should_warn(site):
                    logger.warning(
                        f"XLA RECOMPILE in hot loop {site!r}: signature "
                        f"#{len(watched._sigs) + 1} after warm-up "
                        f"({_what_changed(watched._sigs, sig, args, kwargs)})"
                        f". Each recompile stalls the loop for the full "
                        f"compile time — check for drifting batch/cache "
                        f"shapes or dtype flips.")
            watched._sigs.add(sig)
            return
        if not watched._settled:
            # warm-up legitimately compiles per-layout variants of one
            # signature as eager-built buffers are replaced by committed
            # jit outputs
            return
        self._compiles.labels(site=site).inc()
        if not watched._warn:
            return
        self._recompiles.labels(site=site).inc()
        if self._should_warn(site):
            logger.warning(
                f"XLA RECOMPILE in hot loop {site!r}: a new executable "
                f"with UNCHANGED arg shapes/dtypes — the jit cache also "
                f"keys on shardings, layouts and static arguments; check "
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


def watch(fn, name: str, warn: bool = True, staged: bool = False):
    """Module-level convenience over the default watchdog."""
    return _get_default().watch(fn, name, warn=warn, staged=staged)


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



class _CompileTLS(threading.local):
    unclaimed = 0  # executables this thread has made (built or fetched)
                   # that no watched call has taken as its own
    hit = False    # the executable being made was found in the persistent
    fetch_s = 0.0  # cache, and fetching it took this long


_compile_tls = _CompileTLS()
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
        fetched = tls.hit
        fetch_s = tls.fetch_s if fetched else 0.0
        tls.hit, tls.fetch_s = False, 0.0
        how = "fetched" if fetched else "built"
        tls.unclaimed += 1
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

