"""Goodput attribution: classify step wall time into phases.

A train/serving tick's wall time is one undifferentiated number in the
throughput log; operations wants to know WHERE it went — is the job
compute-bound (good), input-bound (fix the loader), stuck compiling
(fix the shape drift), or blocked writing checkpoints?  This module
splits the host wall clock into phases:

- ``compute``    — fwd/bwd dispatch + decode ticks (the useful work),
- ``data_wait``  — batch load + host→device put, serving admission,
- ``checkpoint`` — save/restore wall time,
- ``recompile``  — jit trace+compile time (warm-up AND drift; reported
  by the recompilation watchdog),
- ``idle``       — wall time covered by none of the above (derived).

Attribution rides the tracer's span boundaries (:mod:`.trace` notifies a
span observer whether or not Chrome-trace recording is on), so the
engine/serving loops need no extra instrumentation, and it is
EXCLUSIVE: a ``train/checkpoint`` span nested inside a ``train/dispatch``
span bills the checkpoint seconds to ``checkpoint`` only, and compile
seconds reported mid-span are subtracted from the enclosing phase.

Export surface (the metrics registry): per-phase time histograms
(``goodput_phase_seconds{phase=...}``), cumulative per-phase totals
(``goodput_phase_seconds_total``), and — refreshed by a registered
collector on every scrape — ``goodput_ratio`` (compute / total wall
since the first observation) plus ``goodput_idle_seconds_total``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from . import registry as _registry

__all__ = ["GoodputTracker", "get_tracker", "install", "phase",
           "note_compile", "note_step", "last_step_age", "summary",
           "PHASES", "SPAN_PHASE"]

PHASES = ("compute", "data_wait", "checkpoint", "recompile")

# span name -> phase.  Admission is host-side scheduling/queueing work
# (the serving analog of waiting on input); prefill/decode are the
# useful serving compute.
SPAN_PHASE = {
    # the call of the compiled step: its enqueue, and under run-ahead the
    # wait for the device to take it — the nearest host-side stand-in for
    # "the device is computing"
    "train/dispatch": "compute",
    "train/apply-step": "compute",
    "train/next-batch": "data_wait",
    "train/device-put": "data_wait",
    "train/checkpoint": "checkpoint",
    "serve/prefill": "compute",
    "serve/decode-tick": "compute",
    "serve/verify-tick": "compute",   # speculative batched verify forward
    "serve/admit": "data_wait",
    "serve/prefill-batch": "data_wait",   # less its serve/prefill children
}

_tls = threading.local()


def _stack():
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class GoodputTracker:
    """Span observer + manual ``phase(...)`` API accumulating per-phase
    wall seconds; registered into :mod:`.trace` by :func:`install`."""

    def __init__(self, registry: Optional[_registry.Registry] = None,
                 span_phase: Optional[dict] = None):
        reg = registry or _registry.get_registry()
        self._span_phase = dict(SPAN_PHASE if span_phase is None
                                else span_phase)
        # RLock: the flight-recorder signal handler reads summary() from
        # the main thread, possibly interrupting note_step mid-hold
        self._lock = threading.RLock()
        self._totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._t0: Optional[float] = None       # first observation (mono)
        self._last_step_mono: Optional[float] = None
        self._last_step_wall: Optional[float] = None
        self._steps_by_kind: Dict[str, int] = {}
        self._by_phase: dict = {}    # phase -> (histogram, counter) child
        self._h = reg.histogram(
            "goodput_phase_seconds",
            "per-occurrence wall time by phase (exclusive attribution)",
            labelnames=("phase",), buckets=_registry.SECONDS_BUCKETS)
        self._c = reg.counter(
            "goodput_phase_seconds_total",
            "cumulative wall seconds by phase", labelnames=("phase",))
        self._ratio = reg.gauge(
            "goodput_ratio",
            "compute seconds / total wall seconds since first observation")
        self._idle = reg.gauge(
            "goodput_idle_seconds_total",
            "wall seconds attributed to no phase since first observation")
        self._wall = reg.gauge(
            "goodput_wall_seconds_total",
            "wall seconds since the first observed phase")

    # -- span observer protocol (see trace.add_span_observer) ----------
    def span_enter(self, name: str) -> None:
        _stack().append(0.0)    # seconds already billed by nested phases

    def span_exit(self, name: str, dur_s: float, args) -> None:
        stack = _stack()
        billed_children = stack.pop() if stack else 0.0
        ph = self._span_phase.get(name)
        if ph is not None:
            self._observe(ph, max(0.0, dur_s - billed_children))
            claimed = dur_s          # whole interval now accounted for
        else:
            claimed = billed_children   # propagate nested claims upward
        if stack:
            stack[-1] += claimed

    # -- accumulation ---------------------------------------------------
    def _observe(self, ph: str, dur_s: float) -> None:
        with self._lock:
            if self._t0 is None:
                self._t0 = time.monotonic() - dur_s
            self._totals[ph] = self._totals.get(ph, 0.0) + dur_s
        hc = self._by_phase.get(ph)
        if hc is None:    # label lookup costs a microsecond: once a phase
            hc = self._by_phase[ph] = (self._h.labels(phase=ph),
                                       self._c.labels(phase=ph))
        hc[0].observe(dur_s)
        hc[1].inc(dur_s)

    def note_compile(self, dur_s: float) -> None:
        """Bill ``dur_s`` of jit trace+compile time to ``recompile`` and
        subtract it from the enclosing span's phase (the compile happens
        INSIDE e.g. a ``train/dispatch`` interval)."""
        self._observe("recompile", dur_s)
        stack = _stack()
        if stack:
            stack[-1] += dur_s

    def phase(self, name: str):
        """Manual attribution context for code outside the pre-wired
        spans: ``with goodput.phase("compute"): ...``."""
        from . import trace as _trace

        if name not in PHASES:
            raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
        self._span_phase.setdefault(f"goodput/{name}", name)
        return _trace.span(f"goodput/{name}")

    def note_step(self, kind: str = "train",
                  context: Optional[dict] = None) -> None:
        """Record that a step/tick completed — powers the ``/healthz``
        last-step-age check and the flight recorder's metric-delta marks.
        ``context`` (small JSON-ables, e.g. serving's in-flight request
        uids) rides the flight-recorder delta entry for postmortems."""
        with self._lock:
            self._last_step_mono = time.monotonic()
            self._last_step_wall = time.time()
            self._steps_by_kind[kind] = self._steps_by_kind.get(kind, 0) + 1
        try:
            from . import flightrec

            flightrec.mark(kind, context)
        except Exception:
            pass
        try:
            # anomaly detectors evaluate at step cadence (throttled to
            # ~1/s inside observe) — no extra thread, no extra sync
            from . import anomaly

            anomaly.observe()
        except Exception:
            pass

    def last_step_age(self) -> Optional[float]:
        """Seconds since the last completed step, None before the first."""
        with self._lock:
            if self._last_step_mono is None:
                return None
            return time.monotonic() - self._last_step_mono

    # -- export ---------------------------------------------------------
    def refresh_gauges(self) -> None:
        """Recompute ratio/idle/wall gauges (collector; runs per scrape)."""
        with self._lock:
            if self._t0 is None:
                return
            total = max(time.monotonic() - self._t0, 1e-9)
            tracked = sum(self._totals.values())
            compute = self._totals.get("compute", 0.0)
        self._wall.set(total)
        self._idle.set(max(0.0, total - tracked))
        self._ratio.set(min(1.0, compute / total))

    def summary(self) -> dict:
        """Phase breakdown + ratio as a JSON-able dict (statusz/probe)."""
        self.refresh_gauges()
        with self._lock:
            out = {f"{p}_s": round(self._totals.get(p, 0.0), 6)
                   for p in PHASES}
            t0 = self._t0
            total = (time.monotonic() - t0) if t0 is not None else 0.0
            out["steps"] = dict(self._steps_by_kind)
        out["wall_s"] = round(total, 6)
        out["idle_s"] = round(max(0.0, total - sum(
            out[f"{p}_s"] for p in PHASES)), 6)
        out["goodput_ratio"] = (
            min(1.0, out["compute_s"] / total) if total > 0 else None)
        age = self.last_step_age()
        out["last_step_age_s"] = None if age is None else round(age, 3)
        return out


_default: Optional[GoodputTracker] = None


def get_tracker() -> GoodputTracker:
    global _default
    if _default is None:
        _default = GoodputTracker()
    return _default


_installed = False


def install() -> GoodputTracker:
    """Arm the default tracker: subscribe to span boundaries and register
    the ratio-refresh collector.  Idempotent; called on telemetry import."""
    global _installed
    t = get_tracker()
    if not _installed:
        from . import trace as _trace

        _trace.add_span_observer(t)
        _registry.register_collector(t.refresh_gauges)
        _installed = True
    return t


# module-level conveniences over the default tracker
def phase(name: str):
    return get_tracker().phase(name)


def note_compile(dur_s: float) -> None:
    get_tracker().note_compile(dur_s)


def note_step(kind: str = "train", context: Optional[dict] = None) -> None:
    get_tracker().note_step(kind, context)


def last_step_age() -> Optional[float]:
    return get_tracker().last_step_age()


def summary() -> dict:
    return get_tracker().summary()
