"""Anomaly detectors over the live telemetry: structured, thresholded
alerts.

The registry exports raw counters for a human to eyeball; admission
control and load shedding (ROADMAP item 2) need the telemetry plane to
*raise signals*.  This module runs rolling detectors over registry
series and emits structured alert events:

- **recompile storm** — ``xla_recompiles_total`` (the recompile
  watchdog) moved ≥ N in the window: a hot loop is recompiling.
- **slo_burn** — TTFT/TPOT SLO burn rate (the PR-7 retire-time
  tagging): violations / retirements in the window above the budget.
- **queue_runaway** — ``serving_queue_depth`` monotonically climbing
  across K observations above a floor: arrivals outrun service.
- **acceptance_collapse** — ``specdec_acceptance_rate`` under the
  floor while verify ticks are still being paid.
- **goodput_drop** — ``goodput_ratio`` under the floor on a warmed-up
  process.

Every fire/clear transition lands in FOUR places: the
``alerts_total{rule}`` counter + ``alerts_firing{rule}`` gauge, the
``/alertz`` endpoint (active + recent events), a ``logger.warning``
(which rides the flight recorder's log ring, so a crash dump shows
what was alerting — the dump also embeds :func:`status` directly), and
every :func:`subscribe` callback — the explicit seam an admission
controller / load shedder consumes.

Detectors are hysteresis state machines (``fire_after`` consecutive
bad evaluations to fire, ``clear_after`` good ones to clear), so a
single noisy sample neither pages nor flaps.  Thresholds come from
``DSTPU_ALERT_*`` env knobs (see each detector).  Evaluation is
throttled to ~1/s and rides ``goodput.note_step`` plus every registry
scrape — no extra thread.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..utils.logging import logger
from . import registry as _registry

__all__ = [
    "Series", "Detector", "RecompileStormDetector", "SloBurnDetector",
    "QueueRunawayDetector", "AcceptanceCollapseDetector",
    "GoodputDropDetector", "LossSpikeDetector",
    "GradNormExplosionDetector", "AnomalyEngine",
    "get_engine", "observe", "subscribe", "active", "recent", "status",
    "install",
]


def _envf(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _envi(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class Series:
    """Bounded rolling (t, value) samples of one registry series."""

    def __init__(self, maxlen: int = 240):
        self._xs: deque = deque(maxlen=maxlen)

    def add(self, t: float, v: float) -> None:
        self._xs.append((float(t), float(v)))

    def __len__(self) -> int:
        return len(self._xs)

    def last(self) -> Optional[float]:
        return self._xs[-1][1] if self._xs else None

    def delta(self, window_s: float, now: Optional[float] = None
              ) -> Optional[float]:
        """value movement across the trailing window: last sample minus
        the OLDEST sample inside ``[now - window_s, now]``.  None with
        fewer than two in-window samples (a delta needs an interval)."""
        if len(self._xs) < 2:
            return None
        now = self._xs[-1][0] if now is None else now
        lo = now - window_s
        inside = [(t, v) for t, v in self._xs if t >= lo]
        if len(inside) < 2:
            return None
        return inside[-1][1] - inside[0][1]

    def increasing_run(self, k: int) -> bool:
        """True when the last ``k`` consecutive steps (k+1 samples) are
        STRICTLY increasing."""
        if len(self._xs) < k + 1:
            return False
        tail = [v for _, v in list(self._xs)[-(k + 1):]]
        return all(b > a for a, b in zip(tail, tail[1:]))

    def tail(self, n: int) -> List[float]:
        """The last ``n`` values (fewer when the series is shorter)."""
        return [v for _, v in list(self._xs)[-n:]]

    def clear(self) -> None:
        self._xs.clear()


def _metric_total(name: str) -> Optional[float]:
    """Sum of a registry metric's samples WITHOUT get-or-create: a
    reader must never pre-register a name with the wrong labelset (the
    later real declaration would raise)."""
    reg = _registry.get_registry()
    with reg._lock:
        m = reg._metrics.get(name)
    if m is None:
        return None
    return sum(c.value for _, c in m.samples())


class Detector:
    """Hysteresis state machine over one violation predicate.

    Subclasses implement :meth:`check` returning a violation dict
    ``{"value", "threshold", "detail"}`` or None.  ``step`` turns
    consecutive check results into at most one fire event and one clear
    event per transition."""

    name = "detector"
    fire_after = 1
    clear_after = 3

    def __init__(self):
        self.firing = False
        self._bad = 0
        self._good = 0
        self._last_violation: Optional[dict] = None

    def check(self, engine: "AnomalyEngine", now: float) -> Optional[dict]:
        raise NotImplementedError

    def thresholds(self) -> dict:
        return {}

    def step(self, engine: "AnomalyEngine", now: float) -> List[dict]:
        violation = self.check(engine, now)
        events: List[dict] = []
        if violation is not None:
            self._bad += 1
            self._good = 0
            self._last_violation = violation
            if not self.firing and self._bad >= self.fire_after:
                self.firing = True
                events.append(self._event("firing", now, violation))
        else:
            self._good += 1
            self._bad = 0
            if self.firing and self._good >= self.clear_after:
                self.firing = False
                events.append(self._event(
                    "cleared", now, self._last_violation or {}))
        return events

    def _event(self, state: str, now: float, violation: dict) -> dict:
        return {"rule": self.name, "state": state, "t": now,
                "value": violation.get("value"),
                "threshold": violation.get("threshold"),
                "detail": violation.get("detail", {})}

    def reset(self) -> None:
        """Back to the quiescent state WITHOUT emitting a clear event —
        the TrainGuard calls this after a rollback (the pre-rollback
        samples are no longer evidence about the restored state)."""
        self.firing = False
        self._bad = 0
        self._good = 0
        self._last_violation = None


class RecompileStormDetector(Detector):
    """``xla_recompiles_total`` moved ≥ ``n`` inside ``window_s``.
    Knobs: ``DSTPU_ALERT_RECOMPILE_N`` (3),
    ``DSTPU_ALERT_RECOMPILE_WINDOW_S`` (60)."""

    name = "recompile_storm"
    fire_after = 1
    clear_after = 2

    def __init__(self, n: Optional[int] = None,
                 window_s: Optional[float] = None):
        super().__init__()
        self.n = _envi("DSTPU_ALERT_RECOMPILE_N", 3) if n is None else n
        self.window_s = _envf("DSTPU_ALERT_RECOMPILE_WINDOW_S", 60.0) \
            if window_s is None else window_s

    def thresholds(self) -> dict:
        return {"n": self.n, "window_s": self.window_s}

    def check(self, engine, now):
        d = engine.series["recompiles"].delta(self.window_s, now)
        if d is not None and d >= self.n:
            return {"value": d, "threshold": self.n,
                    "detail": {"window_s": self.window_s}}
        return None


class SloBurnDetector(Detector):
    """SLO burn rate: violations / retirements inside the window above
    ``burn`` with at least ``min_events`` retirements (a 1-of-1
    violation is noise, not a burn).  Knobs: ``DSTPU_ALERT_SLO_BURN``
    (0.5), ``DSTPU_ALERT_SLO_WINDOW_S`` (60),
    ``DSTPU_ALERT_SLO_MIN_EVENTS`` (8)."""

    name = "slo_burn"
    fire_after = 1
    clear_after = 3

    def __init__(self, burn: Optional[float] = None,
                 window_s: Optional[float] = None,
                 min_events: Optional[int] = None):
        super().__init__()
        self.burn = _envf("DSTPU_ALERT_SLO_BURN", 0.5) \
            if burn is None else burn
        self.window_s = _envf("DSTPU_ALERT_SLO_WINDOW_S", 60.0) \
            if window_s is None else window_s
        self.min_events = _envi("DSTPU_ALERT_SLO_MIN_EVENTS", 8) \
            if min_events is None else min_events

    def thresholds(self) -> dict:
        return {"burn": self.burn, "window_s": self.window_s,
                "min_events": self.min_events}

    @staticmethod
    def burn_rate(met_delta: Optional[float],
                  viol_delta: Optional[float]) -> Optional[tuple]:
        """(burn_rate, events) over one window; None when either series
        is absent.  Pure — the unit-test fixture surface."""
        if met_delta is None or viol_delta is None:
            return None
        events = met_delta + viol_delta
        if events <= 0:
            return (0.0, 0.0)
        return (viol_delta / events, events)

    def check(self, engine, now):
        br = self.burn_rate(
            engine.series["slo_met"].delta(self.window_s, now),
            engine.series["slo_violations"].delta(self.window_s, now))
        if br is None:
            return None
        rate, events = br
        if events >= self.min_events and rate >= self.burn:
            return {"value": rate, "threshold": self.burn,
                    "detail": {"events": events,
                               "window_s": self.window_s}}
        return None


class QueueRunawayDetector(Detector):
    """``serving_queue_depth`` strictly increased across ``run``
    consecutive observations AND sits ≥ ``min_depth``.  Knobs:
    ``DSTPU_ALERT_QUEUE_RUN`` (5), ``DSTPU_ALERT_QUEUE_DEPTH`` (32)."""

    name = "queue_runaway"
    fire_after = 1
    clear_after = 2

    def __init__(self, run: Optional[int] = None,
                 min_depth: Optional[float] = None):
        super().__init__()
        self.run = _envi("DSTPU_ALERT_QUEUE_RUN", 5) if run is None else run
        self.min_depth = _envf("DSTPU_ALERT_QUEUE_DEPTH", 32.0) \
            if min_depth is None else min_depth

    def thresholds(self) -> dict:
        return {"run": self.run, "min_depth": self.min_depth}

    def check(self, engine, now):
        s = engine.series["queue_depth"]
        last = s.last()
        if last is not None and last >= self.min_depth \
                and s.increasing_run(self.run):
            return {"value": last, "threshold": self.min_depth,
                    "detail": {"run": self.run}}
        return None


class AcceptanceCollapseDetector(Detector):
    """``specdec_acceptance_rate`` under ``min_rate`` while verify
    ticks MOVED in the window (paying verify forwards for rejected
    drafts).  Knobs: ``DSTPU_ALERT_ACCEPT_MIN`` (0.15),
    ``DSTPU_ALERT_ACCEPT_WINDOW_S`` (60)."""

    name = "acceptance_collapse"
    fire_after = 2
    clear_after = 2

    def __init__(self, min_rate: Optional[float] = None,
                 window_s: Optional[float] = None):
        super().__init__()
        self.min_rate = _envf("DSTPU_ALERT_ACCEPT_MIN", 0.15) \
            if min_rate is None else min_rate
        self.window_s = _envf("DSTPU_ALERT_ACCEPT_WINDOW_S", 60.0) \
            if window_s is None else window_s

    def thresholds(self) -> dict:
        return {"min_rate": self.min_rate, "window_s": self.window_s}

    def check(self, engine, now):
        ticks = engine.series["verify_ticks"].delta(self.window_s, now)
        rate = engine.series["acceptance_rate"].last()
        if ticks and ticks > 0 and rate is not None \
                and rate < self.min_rate:
            return {"value": rate, "threshold": self.min_rate,
                    "detail": {"verify_ticks": ticks}}
        return None


class GoodputDropDetector(Detector):
    """``goodput_ratio`` under ``min_ratio`` once the process has been
    observing for ``min_wall_s`` (warm-up compiles legitimately crater
    the early ratio).  Knobs: ``DSTPU_ALERT_GOODPUT_MIN`` (0.2),
    ``DSTPU_ALERT_GOODPUT_WARMUP_S`` (120)."""

    name = "goodput_drop"
    fire_after = 2
    clear_after = 3

    def __init__(self, min_ratio: Optional[float] = None,
                 min_wall_s: Optional[float] = None):
        super().__init__()
        self.min_ratio = _envf("DSTPU_ALERT_GOODPUT_MIN", 0.2) \
            if min_ratio is None else min_ratio
        self.min_wall_s = _envf("DSTPU_ALERT_GOODPUT_WARMUP_S", 120.0) \
            if min_wall_s is None else min_wall_s

    def thresholds(self) -> dict:
        return {"min_ratio": self.min_ratio, "min_wall_s": self.min_wall_s}

    def check(self, engine, now):
        ratio = engine.series["goodput_ratio"].last()
        wall = engine.series["goodput_wall"].last()
        if ratio is not None and wall is not None \
                and wall >= self.min_wall_s and ratio < self.min_ratio:
            return {"value": ratio, "threshold": self.min_ratio,
                    "detail": {"wall_s": wall}}
        return None


def _finite_median(xs: List[float]) -> Optional[float]:
    import math

    vals = sorted(v for v in xs if math.isfinite(v))
    if not vals:
        return None
    return vals[len(vals) // 2]


class _TrailingRatioDetector(Detector):
    """Shared machinery for the train-series guard rules: the series'
    last sample went non-finite, or rose more than
    ``ratio × max(|baseline|, min_scale)`` ABOVE the baseline (median
    of the trailing ``history`` finite samples, excluding the suspect
    sample itself).  The deviation-from-baseline form stays meaningful
    for negative objectives (ELBO/log-likelihood losses, where a plain
    ``last > ratio·median`` fires on every healthy step) and the
    ``min_scale`` floor keeps a converged near-zero baseline from
    flagging numeric jitter.  The series moves per-step only while a
    ``TrainGuard`` is attached (the per-step device fetch is the
    guard's cost); otherwise it moves at the engine's report cadence
    and the rule stays quiet."""

    fire_after = 2
    clear_after = 3
    series_name = ""            # subclass: which engine.series to read
    env_prefix = ""             # subclass: DSTPU_ALERT_<prefix>_{RATIO,HISTORY}
    default_ratio = 3.0
    min_scale = 1e-3

    def __init__(self, ratio: Optional[float] = None,
                 history: Optional[int] = None):
        super().__init__()
        self.ratio = _envf(f"DSTPU_ALERT_{self.env_prefix}_RATIO",
                           self.default_ratio) if ratio is None else ratio
        self.history = max(4, _envi(f"DSTPU_ALERT_{self.env_prefix}_HISTORY",
                                    8) if history is None else history)

    def thresholds(self) -> dict:
        return {"ratio": self.ratio, "history": self.history}

    def check(self, engine, now):
        import math

        s = engine.series[self.series_name]
        last = s.last()
        if last is None:
            return None
        if not math.isfinite(last):
            return {"value": last, "threshold": None,
                    "detail": {"nonfinite": True}}
        tail = s.tail(self.history + 1)[:-1]      # exclude the suspect
        if len(tail) < self.history // 2:
            return None                            # not enough baseline
        base = _finite_median(tail)
        if base is None:
            return None
        threshold = base + self.ratio * max(abs(base), self.min_scale)
        if last > threshold:
            return {"value": last, "threshold": threshold,
                    "detail": {"median": base, "ratio": self.ratio}}
        return None


class LossSpikeDetector(_TrailingRatioDetector):
    """``train_loss`` non-finite or ``ratio``× above trailing-median.
    Knobs: ``DSTPU_ALERT_LOSS_SPIKE_RATIO`` (3.0),
    ``DSTPU_ALERT_LOSS_SPIKE_HISTORY`` (8, min 4)."""

    name = "loss_spike"
    series_name = "train_loss"
    env_prefix = "LOSS_SPIKE"
    default_ratio = 3.0


class GradNormExplosionDetector(_TrailingRatioDetector):
    """``train_grad_norm`` non-finite or ``ratio``× above
    trailing-median — the fp16 ``overflow``-skip signal generalized:
    under bf16/fp32 nothing else stops a NaN from reaching the
    optimizer.  Knobs: ``DSTPU_ALERT_GRAD_NORM_RATIO`` (10.0),
    ``DSTPU_ALERT_GRAD_NORM_HISTORY`` (8)."""

    name = "grad_norm_explosion"
    series_name = "train_grad_norm"
    env_prefix = "GRAD_NORM"
    default_ratio = 10.0


def default_detectors() -> List[Detector]:
    return [RecompileStormDetector(), SloBurnDetector(),
            QueueRunawayDetector(), AcceptanceCollapseDetector(),
            GoodputDropDetector(),
            LossSpikeDetector(), GradNormExplosionDetector()]


_SOURCES = ("recompiles", "slo_met", "slo_violations", "queue_depth",
            "acceptance_rate", "verify_ticks", "goodput_ratio",
            "goodput_wall", "train_loss", "train_grad_norm")

_MIN_OBSERVE_INTERVAL_S = 1.0
_EVENT_RING = 256


class AnomalyEngine:
    """Samples registry series, runs the detectors, dispatches alert
    events (counters/gauges, ring, subscribers, warning log)."""

    def __init__(self, detectors: Optional[List[Detector]] = None,
                 registry: Optional[_registry.Registry] = None):
        reg = registry or _registry.get_registry()
        self.detectors = default_detectors() if detectors is None \
            else list(detectors)
        self.series: Dict[str, Series] = {n: Series() for n in _SOURCES}
        self.events: deque = deque(maxlen=_EVENT_RING)
        self._active: Dict[str, dict] = {}
        self._subs: List[Callable] = []
        # RLock: the flight recorder's signal handler reads status()
        # from the main thread, possibly mid-observe
        self._lock = threading.RLock()
        self._last_obs = 0.0
        self._m_alerts = reg.counter(
            "alerts_total", "structured alert firings", labelnames=("rule",))
        self._m_firing = reg.gauge(
            "alerts_firing", "1 while the rule's alert is active",
            labelnames=("rule",))

    # -- sampling -------------------------------------------------------
    def _sample(self, now: float) -> None:
        from . import recompile as _recompile

        def put(name: str, v: Optional[float]) -> None:
            if v is not None:
                self.series[name].add(now, v)

        put("recompiles", _recompile.total_recompiles())
        put("slo_met", _metric_total("serving_slo_met_total"))
        put("slo_violations", _metric_total("serving_slo_violations_total"))
        put("queue_depth", _metric_total("serving_queue_depth"))
        put("acceptance_rate", _metric_total("specdec_acceptance_rate"))
        put("verify_ticks", _metric_total("specdec_verify_ticks_total"))
        put("train_loss", _metric_total("train_loss"))
        put("train_grad_norm", _metric_total("train_grad_norm"))
        try:
            from . import goodput as _goodput

            tracker = _goodput.get_tracker()
            with tracker._lock:
                t0 = tracker._t0
                compute = tracker._totals.get("compute", 0.0)
            if t0 is not None:
                wall = max(time.monotonic() - t0, 1e-9)
                put("goodput_ratio", min(1.0, compute / wall))
                put("goodput_wall", wall)
        except Exception:
            pass

    # -- evaluation -----------------------------------------------------
    def observe(self, now: Optional[float] = None,
                force: bool = False) -> List[dict]:
        """Sample + evaluate (throttled to ~1/s unless ``force``);
        returns the transition events this evaluation produced.

        The engine lock covers ONLY sampling, detector evaluation, and
        the ring/active-set updates; metrics, the warning log, and the
        subscriber fan-out run after it is released.  A slow subscriber
        (the admission-controller seam) must never hold the lock the
        flight recorder's signal-handler dump path (``status()``) needs
        from another thread — that would hang the crash forensics."""
        with self._lock:
            mono = time.monotonic()
            if not force and mono - self._last_obs < _MIN_OBSERVE_INTERVAL_S:
                return []
            self._last_obs = mono
            now = time.time() if now is None else now
            self._sample(now)
            events: List[dict] = []
            for d in self.detectors:
                try:
                    events.extend(d.step(self, now))
                except Exception as e:     # one broken detector ≠ no alerts
                    logger.debug(f"anomaly: detector {d.name} failed: {e!r}")
            for ev in events:
                self._record(ev)
        for ev in events:
            self._emit(ev)
        return events

    def _record(self, ev: dict) -> None:
        """State mutation only (caller holds the lock): the event ring
        and the active set.  The active set is keyed by ``ev["key"]``
        when present (external emitters like the fleet aggregator track
        one alert PER REPLICA under one rule name) and by the rule
        otherwise."""
        self.events.append(ev)
        key = ev.get("key", ev["rule"])
        if ev["state"] == "firing":
            self._active[key] = ev
        else:
            self._active.pop(key, None)

    def _emit(self, ev: dict) -> None:
        """Side effects OUTSIDE the engine lock: registry metrics (own
        lock), warning log, subscriber callbacks."""
        # with keyed (per-replica) alerts, the rule's firing gauge stays
        # 1 until the LAST active key under that rule clears
        rule_firing = any(e["rule"] == ev["rule"]
                          for e in self.active().values())
        if ev["state"] == "firing":
            self._m_alerts.labels(rule=ev["rule"]).inc()
            self._m_firing.labels(rule=ev["rule"]).set(
                1.0 if rule_firing else 0.0)
            logger.warning(
                f"ALERT {ev['rule']} firing: value={ev['value']} "
                f"threshold={ev['threshold']} detail={ev['detail']}")
        else:
            self._m_firing.labels(rule=ev["rule"]).set(
                1.0 if rule_firing else 0.0)
            logger.warning(f"ALERT {ev['rule']} cleared")
        for fn in list(self._subs):
            try:
                fn(ev)
            except Exception:
                pass          # a subscriber must never break telemetry

    def emit_event(self, rule: str, state: str, *, value=None,
                   threshold=None, detail: Optional[dict] = None,
                   key: Optional[str] = None,
                   now: Optional[float] = None) -> dict:
        """Record + dispatch an externally-produced alert transition —
        the seam for state machines that live OUTSIDE the detector loop
        (the fleet aggregator's replica health transitions).  The event
        rides the exact machinery detector transitions do:
        ``alerts_total{rule}`` / ``alerts_firing{rule}``, the event ring
        + ``/alertz`` active set (keyed by ``key`` so one rule can track
        N replicas), the warning log, and every subscriber."""
        ev = {"rule": rule, "state": state,
              "t": time.time() if now is None else now,
              "value": value, "threshold": threshold,
              "detail": detail or {}}
        if key is not None:
            ev["key"] = key
        with self._lock:
            self._record(ev)
        self._emit(ev)
        return ev

    def reset_rules(self, names, series=()) -> None:
        """Quiesce the named rules (and optionally clear source series)
        WITHOUT emitting clear transitions: after a TrainGuard rollback
        the pre-rollback samples say nothing about the restored state,
        and a synthetic "cleared" event would unwind subscribers that
        never saw the firing resolve for real."""
        wanted = set(names)
        with self._lock:
            for d in self.detectors:
                if d.name in wanted:
                    d.reset()
            for key in [k for k, ev in self._active.items()
                        if ev["rule"] in wanted]:
                self._active.pop(key, None)
            for s in series:
                if s in self.series:
                    self.series[s].clear()
        for name in wanted:
            self._m_firing.labels(rule=name).set(0.0)

    # -- the consumer seam ---------------------------------------------
    def subscribe(self, fn: Callable[[dict], None]) -> Callable[[], None]:
        """Register ``fn(event)`` for every alert transition — the seam
        the admission controller / load shedder consumes.  Returns a
        zero-arg remover."""
        self._subs.append(fn)

        def remove():
            if fn in self._subs:
                self._subs.remove(fn)
        return remove

    # -- export ---------------------------------------------------------
    def active(self) -> Dict[str, dict]:
        with self._lock:
            return dict(self._active)

    def recent(self, n: int = 20) -> List[dict]:
        with self._lock:
            return list(self.events)[-n:]

    def status(self) -> dict:
        """The ``/alertz`` payload (also the ``/statusz`` ``alerts``
        section and the flight dump's ``alerts`` entry)."""
        with self._lock:
            return {
                "active": list(self._active.values()),
                "recent": list(self.events)[-20:],
                "rules": {d.name: {"firing": d.firing,
                                   **d.thresholds()}
                          for d in self.detectors},
            }


_default: Optional[AnomalyEngine] = None


def get_engine() -> AnomalyEngine:
    global _default
    if _default is None:
        _default = AnomalyEngine()
    return _default


def observe(now: Optional[float] = None, force: bool = False) -> List[dict]:
    return get_engine().observe(now=now, force=force)


def subscribe(fn: Callable[[dict], None]) -> Callable[[], None]:
    return get_engine().subscribe(fn)


def active() -> Dict[str, dict]:
    return get_engine().active()


def recent(n: int = 20) -> List[dict]:
    return get_engine().recent(n)


def status() -> dict:
    return get_engine().status()


_installed = False


def install() -> AnomalyEngine:
    """Arm the default engine: evaluate on every scrape (collector) and
    publish the ``/statusz`` ``alerts`` section.  Idempotent; called on
    telemetry import.  Per-step evaluation additionally rides
    ``goodput.note_step`` (throttled inside :meth:`observe`)."""
    global _installed
    eng = get_engine()
    if not _installed:
        from . import exporter as _exporter

        # resolve the singleton at CALL time (tests swap it)
        _registry.register_collector(lambda: get_engine().observe())
        _exporter.register_status_provider(
            "alerts", lambda: get_engine().status())
        _installed = True
    return eng
