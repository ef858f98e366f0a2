"""Crash flight recorder: a per-rank ring buffer dumped on death.

A crashed worker today leaves nothing: the metrics exit dump needs a
clean ``atexit``, the trace file needs tracing enabled, and the launcher
only sees an exit code or a stale heartbeat.  The flight recorder keeps
the last N ``deepspeed_tpu`` log records (a ``logging.Handler``) and
recent metric deltas (counter movement between throttled ``mark()``
calls — wired off ``goodput.note_step`` and the heartbeat), and writes
them, with the last N finished spans read from the tracer's always-on
ring (``trace.spans()`` — there is one span ring, the tracer's), to
``<metrics_dir>/flight_<rank>.json`` from:

- ``atexit`` (clean exits — the dump doubles as a "last run" record),
- SIGTERM / SIGABRT handlers (the launcher killing a stale worker, a
  preemption, an XLA abort) — which ALSO flush the per-rank metrics
  snapshot (``registry.flush_exit_dump``) that a signal death would
  otherwise lose, then re-deliver the signal so exit semantics hold,
- an unhandled-exception hook (``sys.excepthook`` chain) that captures
  the traceback into the dump.

Armed automatically when ``DSTPU_METRICS_DIR`` is set (the launcher's
``--metrics_dir``); ``launcher/runner.py`` pretty-prints the newest dump
when it restarts a dead worker.  Everything here is best-effort: a
failing dump must never mask the original death.
"""
from __future__ import annotations

import atexit
import json
import logging as _logging
import os
import signal
import sys
import threading
import time
import traceback
from collections import deque
from typing import Optional

from ..utils.logging import logger
from . import registry as _registry
from . import trace as _trace

__all__ = ["FlightRecorder", "get_recorder", "maybe_install", "mark",
           "dump", "pretty", "add_sigterm_hook", "sigterm_managed",
           "FLIGHT_DIR_ENV"]

# separate override for the rare case flight dumps should land away from
# the metrics dir; defaults to DSTPU_METRICS_DIR
FLIGHT_DIR_ENV = "DSTPU_FLIGHT_DIR"

_DUMP_SPANS = 256      # newest spans of the tracer's ring a dump carries
_LOG_RING = 200
_DELTA_RING = 120
_MARK_MIN_INTERVAL_S = 1.0


class _RingLogHandler(_logging.Handler):
    def __init__(self, ring: deque):
        super().__init__()
        self._ring = ring

    def emit(self, record) -> None:
        try:
            self._ring.append({
                "t": record.created,
                "level": record.levelname,
                "msg": record.getMessage()[:2000],
            })
        except Exception:
            pass


class FlightRecorder:
    def __init__(self, directory: str):
        self.directory = directory
        self._t0_mono = time.monotonic()
        self._t0_unix = time.time()
        self.logs: deque = deque(maxlen=_LOG_RING)
        self.deltas: deque = deque(maxlen=_DELTA_RING)
        # RLock: a SIGTERM landing inside mark() must not deadlock the
        # handler's own registry walk
        self._mark_lock = threading.RLock()
        self._last_mark = 0.0
        self._last_counters: dict = {}
        self._dumped_reasons: set = set()
        self._log_handler = _RingLogHandler(self.logs)

    @staticmethod
    def _last_spans() -> list:
        """The newest finished spans of the tracer's ring in the dump's
        format (``t``: unix time the span ended)."""
        return [{"t": _trace.perf_to_unix(s.end_s), "name": s.name,
                 "dur_ms": round(s.dur_s * 1e3, 3),
                 **({"args": s.args} if s.args else {})}
                for s in _trace.spans()[-_DUMP_SPANS:]]

    # -- metric deltas ---------------------------------------------------
    def _counter_totals(self) -> dict:
        reg = _registry.get_registry()
        out = {}
        with reg._lock:
            metrics = list(reg._metrics.values())
        for m in metrics:
            if m.kind == "counter":
                out[m.name] = sum(c.value for _, c in m.samples())
        return out

    def mark(self, label: str = "", context: Optional[dict] = None) -> None:
        """Record counter movement since the previous mark (throttled to
        one per second — wired off ``goodput.note_step`` and the
        heartbeat, so a busy loop costs a dict diff per second).

        ``context`` (small JSON-ables — e.g. the serving loop's in-flight
        request uids) is stored on the delta entry, so a postmortem can
        name WHOSE work the counters were moving for at crash time."""
        now = time.monotonic()
        with self._mark_lock:
            if now - self._last_mark < _MARK_MIN_INTERVAL_S:
                return
            self._last_mark = now
            cur = self._counter_totals()
            prev, self._last_counters = self._last_counters, cur
        delta = {k: round(v - prev.get(k, 0.0), 6)
                 for k, v in cur.items() if v != prev.get(k, 0.0)}
        if delta:
            entry = {"t": time.time(), "label": label, "deltas": delta}
            if context:
                entry["ctx"] = context
            self.deltas.append(entry)

    # -- dumping ---------------------------------------------------------
    def dump(self, reason: str, exc: Optional[BaseException] = None
             ) -> Optional[str]:
        """Write the flight dump; returns the path (None on failure).

        A clean-exit (``atexit``) dump never overwrites a crash dump
        already written this process: the excepthook fires before
        interpreter shutdown, and the forensics of the crash are the
        valuable copy."""
        if reason == "atexit" and self._dumped_reasons:
            return None
        try:
            from . import goodput
            from ..utils import heartbeat

            _registry.run_collectors()
            hb_age = heartbeat.last_beat_age()
            payload = {
                "reason": reason,
                "time_unix": time.time(),
                "rank": _registry._rank(),
                "pid": os.getpid(),
                "argv": list(sys.argv),
                "uptime_s": round(time.monotonic() - self._t0_mono, 3),
                "heartbeat_age_s":
                    None if hb_age is None else round(hb_age, 3),
                "goodput": goodput.summary(),
                "spans": self._last_spans(),
                "logs": list(self.logs),
                "metric_deltas": list(self.deltas),
                "metrics": _registry.get_registry().snapshot(),
            }
            # what was alerting at death: the anomaly engine's active and
            # recent alerts ride every dump
            try:
                from . import anomaly as _anomaly

                a = _anomaly.get_engine().status()
                if a["active"] or a["recent"]:
                    payload["alerts"] = {"active": a["active"],
                                         "recent": a["recent"]}
            except Exception:
                pass
            # retained request traces (telemetry/reqtrace.py): the
            # tail-retention index (promoted SLO-violating / alert-
            # coincident summaries first) rides the dump, so a crash
            # mid-load names not just WHICH uids were in flight but
            # what each outlier's span walls looked like
            try:
                from . import reqtrace as _reqtrace

                idx = _reqtrace.flight_index()
                if idx:
                    payload["reqtrace"] = idx
            except Exception:
                pass
            if exc is not None:
                payload["exception"] = {
                    "type": type(exc).__name__,
                    "value": str(exc)[:4000],
                    "traceback": traceback.format_exception(
                        type(exc), exc, exc.__traceback__)[-50:],
                }
            path = os.path.join(
                self.directory, f"flight_{_registry._rank()}.json")
            os.makedirs(self.directory, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(payload, fh, indent=1, default=str)
            os.replace(tmp, path)
            self._dumped_reasons.add(reason.split(":")[0])
            return path
        except Exception:
            return None   # forensics must never mask the original death


_recorder: Optional[FlightRecorder] = None
_prev_handlers: dict = {}
_prev_excepthook = None
_atexit_done = False


def get_recorder() -> Optional[FlightRecorder]:
    return _recorder


def disarm() -> None:
    """Drop the recorder so dumps become no-ops (installed signal/atexit
    hooks stay but fall through).  The LAUNCHER calls this: with
    ``DSTPU_METRICS_DIR`` exported operator-side, the import-armed
    recorder in the launcher process would otherwise overwrite worker
    rank 0's forensics at launcher exit."""
    global _recorder
    if _recorder is not None:
        logger.removeHandler(_recorder._log_handler)
    _recorder = None


def mark(label: str = "", context: Optional[dict] = None) -> None:
    if _recorder is not None:
        _recorder.mark(label, context)


def dump(reason: str, exc: Optional[BaseException] = None) -> Optional[str]:
    return _recorder.dump(reason, exc) if _recorder is not None else None


_sigterm_hooks: list = []


def sigterm_managed() -> bool:
    """True while the flight recorder's handler owns SIGTERM — the
    signal an :func:`add_sigterm_hook` hook will actually run under.
    Subsystems that want a SIGTERM side effect (the
    ``AsyncCheckpointManager`` preemption save) check this first:
    when the recorder owns the signal they must REGISTER A HOOK, not
    ``signal.signal`` over the handler (which would silently drop the
    dump/flush/drain chain — and every other registered hook)."""
    try:
        return signal.getsignal(signal.SIGTERM) is _on_signal
    except (ValueError, OSError):
        return False


def add_sigterm_hook(fn):
    """Run ``fn()`` on SIGTERM BEFORE the flight dump — the graceful-
    drain seam: a replica being terminated by the launcher finishes its
    in-flight requests (``ContinuousBatcher.drain``), then the dump
    snapshots the drained state.  SIGTERM only: SIGABRT means the
    process is wedged, and a drain could hang the abort.  Hooks are
    best-effort (exceptions swallowed — forensics must never mask the
    shutdown); returns a zero-arg remover."""
    _sigterm_hooks.append(fn)

    def remove():
        if fn in _sigterm_hooks:
            _sigterm_hooks.remove(fn)
    return remove


def _on_signal(signum, frame):
    name = signal.Signals(signum).name if signum in list(signal.Signals) \
        else str(signum)
    if signum == signal.SIGTERM:
        for fn in list(_sigterm_hooks):
            try:
                fn()
            except Exception:
                pass
    dump(reason=f"signal:{name}")
    # the satellite fix: metrics must survive the launcher's SIGTERM
    # (atexit never runs under default signal death)
    _registry.flush_exit_dump()
    prev = _prev_handlers.get(signum)
    if callable(prev):
        prev(signum, frame)
    elif prev == signal.SIG_IGN:
        return
    else:
        # restore default disposition and re-deliver so the exit status
        # still says "killed by signal" (the launcher keys off it)
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)


def _on_exception(exc_type, exc, tb):
    try:
        dump(reason="exception", exc=exc)
        _registry.flush_exit_dump()
    finally:
        (_prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)


def _on_atexit():
    dump(reason="atexit")


def maybe_install(directory: Optional[str] = None) -> Optional[FlightRecorder]:
    """Arm the flight recorder when a dump directory is configured
    (``DSTPU_FLIGHT_DIR``, falling back to ``DSTPU_METRICS_DIR``).
    Idempotent; called on telemetry import.  Returns the recorder."""
    global _recorder, _prev_excepthook, _atexit_done
    directory = directory or os.environ.get(FLIGHT_DIR_ENV) \
        or os.environ.get(_registry.METRICS_DIR_ENV)
    if not directory:
        return None
    if _recorder is not None:
        _recorder.directory = directory
        return _recorder
    _recorder = FlightRecorder(directory)
    logger.addHandler(_recorder._log_handler)
    if not _atexit_done:
        atexit.register(_on_atexit)
        _atexit_done = True
    if _prev_excepthook is None:
        _prev_excepthook = sys.excepthook
        sys.excepthook = _on_exception
    for signum in (signal.SIGTERM, signal.SIGABRT):
        try:
            # only from the main thread; a custom handler someone already
            # installed is chained, not replaced.  Re-arming after
            # disarm() finds our own handler still installed: chaining it
            # to itself would recurse forever on the signal
            if signal.getsignal(signum) is not _on_signal:
                _prev_handlers[signum] = signal.getsignal(signum)
                signal.signal(signum, _on_signal)
        except (ValueError, OSError):     # non-main thread / exotic env
            _prev_handlers.pop(signum, None)
    return _recorder


# ----------------------------------------------------------------------
# pretty-printing (the launcher's postmortem view)
# ----------------------------------------------------------------------
def pretty(path_or_payload, max_spans: int = 8, max_logs: int = 8) -> str:
    """Human-readable postmortem of a flight dump — what the launcher
    prints when it restarts a dead worker."""
    if isinstance(path_or_payload, str):
        with open(path_or_payload) as fh:
            p = json.load(fh)
    else:
        p = path_or_payload
    t_dump = p.get("time_unix", 0.0)
    when = time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(t_dump))
    lines = [f"flight dump: rank {p.get('rank')} pid {p.get('pid')} "
             f"reason={p.get('reason')} at {when} "
             f"(uptime {p.get('uptime_s')}s)"]
    gp = p.get("goodput") or {}
    if gp.get("last_step_age_s") is not None:
        lines.append(f"  last step {gp['last_step_age_s']}s before dump; "
                     f"goodput_ratio={gp.get('goodput_ratio')}")
    if p.get("heartbeat_age_s") is not None:
        lines.append(f"  last heartbeat {p['heartbeat_age_s']}s before dump")
    exc = p.get("exception")
    if exc:
        lines.append(f"  died on {exc['type']}: {exc['value']}")
        for tb_line in exc.get("traceback", [])[-3:]:
            lines.append("    " + tb_line.rstrip().replace("\n", "\n    "))
    spans = p.get("spans", [])[-max_spans:]
    if spans:
        lines.append(f"  last {len(spans)} spans:")
        for s in spans:
            ago = round(t_dump - s["t"], 3)
            args = f" {s['args']}" if s.get("args") else ""
            lines.append(f"    -{ago}s {s['name']} "
                         f"{s['dur_ms']}ms{args}")
    logs = p.get("logs", [])[-max_logs:]
    if logs:
        lines.append(f"  last {len(logs)} log records:")
        for r in logs:
            ago = round(t_dump - r["t"], 3)
            lines.append(f"    -{ago}s [{r['level']}] {r['msg']}")
    deltas = p.get("metric_deltas", [])[-3:]
    if deltas:
        lines.append("  recent metric deltas:")
        for d in deltas:
            ago = round(t_dump - d["t"], 3)
            ctx = f" ctx={d['ctx']}" if d.get("ctx") else ""
            lines.append(f"    -{ago}s {d.get('label', '')} "
                         f"{d['deltas']}{ctx}")
    # in-flight request attribution: the last serving mark's context and
    # any span args carrying uids name the requests on the pool at death
    in_flight = None
    for d in reversed(p.get("metric_deltas", [])):
        if (d.get("ctx") or {}).get("uids"):
            in_flight = d["ctx"]["uids"]
            break
    if in_flight is None:
        for s in reversed(p.get("spans", [])):
            if (s.get("args") or {}).get("uids"):
                in_flight = s["args"]["uids"]
                break
    if in_flight:
        lines.append(f"  in-flight request uids at last mark: {in_flight}")
    # retained request traces: the SLO-violating (or alert-coincident)
    # outliers with their per-phase span walls — "why was this one
    # slow" without leaving the postmortem
    viol = [s for s in (p.get("reqtrace") or {}).get("retained", [])
            if s.get("retained") in ("slo_violation", "alert")]
    if viol:
        lines.append(f"  retained SLO-violating traces "
                     f"({len(viol)}, newest first):")
        for s in viol[:4]:
            walls = " ".join(f"{k}={v}ms" for k, v in
                             (s.get("span_walls_ms") or {}).items())
            tpot = s.get("tpot_ms")
            lines.append(
                f"    {s['trace_id'][:12]}… uid={s.get('uid')} "
                f"[{s.get('retained')}] ttft={s.get('ttft_ms')}ms "
                f"tpot={'-' if tpot is None else tpot}ms "
                f"n_out={s.get('n_out')} {walls}")
    # what was firing: active alerts first, then recent transitions —
    # the "was anything alerting when it died" question
    alerts = p.get("alerts") or {}
    act = alerts.get("active") or []
    if act:
        lines.append(f"  ACTIVE alerts at dump ({len(act)}):")
        for a in act:
            ago = round(t_dump - a.get("t", t_dump), 3)
            lines.append(
                f"    {a['rule']} firing since -{ago}s "
                f"value={a.get('value')} threshold={a.get('threshold')} "
                f"{a.get('detail') or ''}")
    elif alerts.get("recent"):
        last = alerts["recent"][-1]
        ago = round(t_dump - last.get("t", t_dump), 3)
        lines.append(f"  no active alerts; last transition -{ago}s: "
                     f"{last['rule']} {last['state']}")
    key = {}
    for name in ("train_steps_total", "serving_decode_ticks_total",
                 "serving_requests_completed_total", "xla_recompiles_total",
                 "heartbeat_beats_total"):
        entry = (p.get("metrics") or {}).get(name)
        if entry:
            key[name] = sum(s.get("value", 0) for s in entry["samples"])
    if key:
        lines.append("  key counters: " + " ".join(
            f"{k}={v:g}" for k, v in key.items()))
    return "\n".join(lines)


def newest_dump(directory: str,
                since: Optional[float] = None) -> Optional[str]:
    """Flight dump to show for a failed run (None when there is none) —
    the launcher's collection hook.

    ``since`` (a unix mtime) STRICTLY drops dumps from a previous
    restart attempt — a stale dump presented as this failure's
    postmortem would send the operator debugging the wrong death.
    Among current dumps, a CRASH dump (exception / SIGABRT) wins over
    ``signal:SIGTERM`` ones even when older: when one rank dies, the
    launcher SIGTERMs the healthy rest, whose dumps land LATER —
    newest-by-mtime alone would show a victim, not the cause."""
    try:
        cands = [os.path.join(directory, f) for f in os.listdir(directory)
                 if f.startswith("flight_") and f.endswith(".json")]
        if since is not None:
            cands = [p for p in cands if os.path.getmtime(p) >= since]
        if not cands:
            return None
        cands.sort(key=os.path.getmtime, reverse=True)
        for path in cands:
            try:
                with open(path) as fh:
                    if json.load(fh).get("reason") != "signal:SIGTERM":
                        return path
            except Exception:
                continue
        return cands[0]
    except OSError:
        return None
