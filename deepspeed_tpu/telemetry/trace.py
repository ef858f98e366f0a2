"""The program's one tracer: host spans the device trace can see.

``with trace.span("train/dispatch", step=7):`` times a piece of HOST work
— pulling a batch, the device put, the dispatch of the compiled step,
admission, a prefill batch, the fetch that fences a decode window — and
records it three ways at once:

- **always**, into a bounded ring of finished spans (:data:`RING_SIZE`)
  and into cumulative totals by name.  A span carries its name, start and
  end on the ``time.perf_counter()`` axis, an id, the id and name of the
  span that was open on its thread when it began (its cause), and its
  small args (``step=``, ``uids=``; a request's spans share its uid).
  Read them back with :func:`spans` (by name prefix and time window) and
  :func:`totals` (count, seconds, self seconds = duration minus what child
  spans cover): tests, the benchmark's per-layer readers, the flight
  recorder's dump and ``/statusz`` all use this one API;
- **always**, into ``jax.profiler.TraceAnnotation(name)``: outside a
  profiler session that is a no-op of about half a microsecond; inside
  one the span lands on ``/host:CPU`` of the same xplane as
  ``/device:TPU:n``, so program spans and device operations share one
  clock by construction and every idle gap of the device can be put down
  to what the host was doing;
- **when enabled** (:func:`enable`, or ``DSTPU_TRACE=/path/to/trace.json``,
  written on interpreter exit and on :func:`save`), into a Chrome-trace
  event list an operator opens in ``ui.perfetto.dev``; each event carries
  ``span_id`` / ``parent_id``.

Cost with the Chrome recorder off, measured in a 200k-iteration loop on
this repo's CPU sandbox (PERF.md section 6, PR 24): 3.2 us for a span
goodput does not classify and 5.0 us for one it does (the goodput
observer's histogram and counter are the difference), against 0.46 us
for a bare ``TraceAnnotation``; the tracer before PR 24 read 2.0 and
6.6 us in the same loop and kept nothing.  Every span is timed, always:
there is no "off".

:func:`device_span` is the other half: a ``jax.named_scope`` for use
INSIDE traced code (``loss_head``, ``grad_clip``, ``optimizer``,
``zero/scatter``, the pipeline stages).  The name lands in the HLO's
``op_name`` metadata, where a device trace attributes operations to it;
it changes no instruction.  There is no ``zero/gather``: no line of the
program gathers a ZeRO shard, the partitioner places each all-gather at
the operation that consumes it, so a gather's time is booked under that
module's scope (``h_3/attn``), and ``device_scopes.collective_ledger``
lists the gathers of a compiled step by it.
"""
from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from collections import deque, namedtuple
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation as _Annotation

__all__ = ["span", "device_span", "spans", "totals", "record", "current",
           "Span", "RING_SIZE", "enable", "disable", "enabled", "clear",
           "save", "to_json", "add_span_observer", "remove_span_observer",
           "perf_to_trace_us", "perf_to_unix", "TRACE_ENV"]

TRACE_ENV = "DSTPU_TRACE"

RING_SIZE = 32768        # finished spans kept; ~10 min of a 50-span/s loop
_MAX_EVENTS = 500_000    # hard cap: a forgotten enable() must not OOM the host

_perf_ns = time.perf_counter_ns
_get_ident = threading.get_ident

class Span(namedtuple("Span",
                      "name start_s end_s id parent_id parent args tid")):
    """One finished span as :func:`spans` returns it.  Times are seconds
    on the ``time.perf_counter()`` axis; ``parent_id`` is 0 and ``parent``
    None for a span with no span open above it on its thread."""

    __slots__ = ()

    @property
    def dur_s(self) -> float:
        return self.end_s - self.start_s


class _Tracer:
    def __init__(self):
        self.enabled = False
        self.events: list = []          # Chrome events, only while enabled
        self.dropped = 0
        # REENTRANT: the flight recorder's signal handler reads the ring
        # on the main thread, possibly interrupting a span's own hold
        self.lock = threading.RLock()
        self.pid = os.getpid()
        # perf_counter has no defined epoch; one process-wide origin keeps
        # the Chrome file's timestamps small, and one offset maps the
        # ring's times to the wall clock for the flight dump
        self.t0_ns = _perf_ns()
        self.unix_minus_perf = time.time() - time.perf_counter()
        # (name, t0_ns, t1_ns, id, parent_id, parent_name, args, tid)
        self.ring: deque = deque(maxlen=RING_SIZE)
        self.totals: Dict[str, list] = {}   # name -> [count, ns, self_ns]
        self.ids = itertools.count(1)


_tracer = _Tracer()
_tls = threading.local()

# Span observers: objects with ``span_enter(name)`` / ``span_exit(name,
# dur_s, args)`` notified on every span.  The goodput phase tracker rides
# span boundaries this way.  An observer raising never breaks the
# instrumented code path.
_observers: list = []


def add_span_observer(obs) -> None:
    if obs not in _observers:
        _observers.append(obs)


def remove_span_observer(obs) -> None:
    if obs in _observers:
        _observers.remove(obs)


def _finish(name, t0, t1, sid, parent, args, child_ns) -> None:
    """Book one finished interval: ring, totals, the parent's covered
    time, and the Chrome list when it is on."""
    dur = t1 - t0
    if parent is not None:
        parent._child_ns += dur
        pid, pname = parent.id, parent.name
    else:
        pid, pname = 0, None
    tid = _get_ident()
    tr = _tracer
    with tr.lock:
        tr.ring.append((name, t0, t1, sid, pid, pname, args, tid))
        tot = tr.totals.get(name)
        if tot is None:
            tot = tr.totals[name] = [0, 0, 0]
        tot[0] += 1
        tot[1] += dur
        tot[2] += dur - child_ns
        if tr.enabled:
            if len(tr.events) < _MAX_EVENTS:
                ev = {"name": name, "ph": "X", "ts": (t0 - tr.t0_ns) / 1e3,
                      "dur": dur / 1e3, "pid": tr.pid, "tid": tid,
                      "span_id": sid, "parent_id": pid}
                if args:
                    ev["args"] = args
                tr.events.append(ev)
            else:
                tr.dropped += 1


class span:
    """Context manager / decorator timing one piece of host work.

    ``args`` (small JSON-ables only) ride the ring record and the Chrome
    event's ``args`` dict."""

    __slots__ = ("name", "args", "id", "_parent", "_t0", "_child_ns", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args or None
        self.id = 0
        self._t0 = None

    def __enter__(self):
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self.id = next(_tracer.ids)
        self._child_ns = 0
        ann = self._ann = _Annotation(self.name)
        ann.__enter__()
        for obs in _observers:
            try:
                obs.span_enter(self.name)
            except Exception:
                pass
        self._t0 = _perf_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _perf_ns()
        t0 = self._t0
        if t0 is None:
            return False
        self._t0 = None
        self._ann.__exit__(exc_type, exc, tb)
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:      # exits out of order (a generator's span)
            stack.remove(self)
        _finish(self.name, t0, t1, self.id, self._parent, self.args,
                self._child_ns)
        for obs in _observers:
            try:
                obs.span_exit(self.name, (t1 - t0) / 1e9, self.args)
            except Exception:
                pass
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with span(self.name, **(self.args or {})):
                return fn(*a, **kw)

        return wrapped


def current() -> Optional[span]:
    """The innermost span open on this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def record(name: str, dur_s: float, **args) -> None:
    """Book an interval that ended NOW and lasted ``dur_s`` as a finished
    child of this thread's innermost open span, for work whose length is
    only known afterwards (``compile/backend``, reported by
    ``jax.monitoring`` when a compile ends).  It reaches the ring, the
    totals and the Chrome file, not the observers and not the profiler."""
    t1 = _perf_ns()
    _finish(name, t1 - int(dur_s * 1e9), t1, next(_tracer.ids), current(),
            args or None, 0)


def spans(prefix: Optional[str] = None, since_s: Optional[float] = None,
          until_s: Optional[float] = None) -> List[Span]:
    """Finished spans still in the ring, oldest first: those whose name
    starts with ``prefix`` and whose START lies in ``[since_s, until_s]``
    on the ``time.perf_counter()`` axis (each bound optional)."""
    with _tracer.lock:
        recs = list(_tracer.ring)
    lo = None if since_s is None else since_s * 1e9
    hi = None if until_s is None else until_s * 1e9
    return [Span(n, t0 / 1e9, t1 / 1e9, sid, pid, pname, args, tid)
            for n, t0, t1, sid, pid, pname, args, tid in recs
            if (prefix is None or n.startswith(prefix))
            and (lo is None or t0 >= lo) and (hi is None or t0 <= hi)]


def totals() -> Dict[str, dict]:
    """Cumulative ``{name: {count, seconds, self_seconds}}`` since process
    start (or :func:`clear`); unlike the ring it forgets nothing."""
    with _tracer.lock:
        return {n: {"count": c, "seconds": ns / 1e9, "self_seconds": sns / 1e9}
                for n, (c, ns, sns) in _tracer.totals.items()}


def perf_to_trace_us(t_s: float) -> float:
    """Map a ``time.perf_counter()`` timestamp (seconds) onto this
    tracer's Chrome-trace microsecond axis.  The request tracer
    (``telemetry/reqtrace.py``) collects lifecycle timestamps from
    ``perf_counter`` and renders them through this helper, so retained
    request traces and the process span file share ONE Perfetto
    timeline."""
    return (t_s * 1e9 - _tracer.t0_ns) / 1e3


def perf_to_unix(t_s: float) -> float:
    """A ``time.perf_counter()`` timestamp as unix seconds (the offset is
    taken once, at import)."""
    return t_s + _tracer.unix_minus_perf


def device_span(name: str):
    """``jax.named_scope`` for use INSIDE jitted/traced code (host spans
    measure nothing there — tracing runs once).  The name lands in HLO op
    metadata, so XLA profiles and compiler dumps attribute work to it."""
    return jax.named_scope(name)


def enable() -> None:
    """Start keeping Chrome-trace events (the ring and the profiler
    annotations are always on)."""
    _tracer.enabled = True


def disable() -> None:
    _tracer.enabled = False


def enabled() -> bool:
    return _tracer.enabled


def clear() -> None:
    """Forget everything recorded: Chrome events, the ring, the totals."""
    with _tracer.lock:
        _tracer.events.clear()
        _tracer.dropped = 0
        _tracer.ring.clear()
        _tracer.totals.clear()


def to_json() -> dict:
    """Chrome-trace JSON object (the ``traceEvents`` wrapper form)."""
    with _tracer.lock:
        events = list(_tracer.events)
        dropped = _tracer.dropped
    meta = {"displayTimeUnit": "ms", "traceEvents": events}
    if dropped:
        meta["dstpu_dropped_events"] = dropped
    return meta


def save(path: str) -> str:
    """Write the trace JSON to ``path`` (atomic rename); returns the
    path.  Loadable with ``json.load`` and in Perfetto as-is."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(to_json(), fh)
    os.replace(tmp, path)
    return path


def _maybe_autostart() -> None:
    path = os.environ.get(TRACE_ENV)
    if not path:
        return
    enable()

    def _dump():
        try:
            p = path
            if "{rank}" in p:
                # multi-rank launches: one trace file per worker
                p = p.format(rank=os.environ.get("DSTPU_PROCESS_ID", "0"))
            save(p)
        except Exception:
            pass

    atexit.register(_dump)


_maybe_autostart()
