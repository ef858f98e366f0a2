"""Per-rank HTTP exporter: ``/metrics``, ``/healthz``, ``/statusz``.

PR 1's telemetry is pull-at-exit: the registry dumps when a rank dies
cleanly.  Production scraping wants a LIVE endpoint per rank.  This is
the smallest one that works — a stdlib ``http.server`` thread serving:

- ``/metrics`` — the registry's Prometheus text render, with scrape-time
  collectors (live-HBM sampling, the goodput ratio) refreshed first;
- ``/healthz`` — JSON liveness: heartbeat freshness and last-step age
  (503 when ``DSTPU_HEALTHZ_STALE_S`` is set and both are stale);
- ``/statusz`` — JSON operational state: the exporter's base fields
  (rank/pid/uptime/recompile counts/goodput breakdown) merged with
  named provider sections the engine, the serving batcher, the
  inference engine and the monitor register at init;
- ``/alertz`` — active + recent structured alerts and detector
  thresholds (``telemetry/anomaly.py``);
- ``/tracez`` — retained request traces (``telemetry/reqtrace.py``):
  the index, ``?trace_id=`` for one trace's span tree, ``?full=1`` for
  every retained trace with spans (the fleet stitcher's fetch).

Opt-in: ``dstpu --telemetry_port P`` injects ``DSTPU_TELEMETRY_PORT``;
rank ``k`` serves on ``P + k`` (one process per host, so ports collide
only in local multi-process emulation — exactly where the offset
matters).  ``P = 0`` asks the OS for a free port per rank (the assigned
port is logged and published as the ``telemetry_exporter_port`` gauge).
No env/flag → no server thread at all.
"""
from __future__ import annotations

import json
import os
import threading
import time
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional

from ..testing import chaos as _chaos
from ..utils.logging import logger
from . import registry as _registry

__all__ = ["TelemetryExporter", "maybe_start", "get_exporter",
           "register_status_provider", "unregister_status_provider",
           "register_status_owner", "write_discovery",
           "TELEMETRY_PORT_ENV", "TELEMETRY_HOST_ENV",
           "HEALTHZ_STALE_ENV"]

TELEMETRY_PORT_ENV = "DSTPU_TELEMETRY_PORT"
TELEMETRY_HOST_ENV = "DSTPU_TELEMETRY_HOST"
HEALTHZ_STALE_ENV = "DSTPU_HEALTHZ_STALE_S"

_status_providers: Dict[str, Callable[[], Optional[dict]]] = {}


def register_status_provider(name: str,
                             fn: Callable[[], Optional[dict]]) -> None:
    """Register a ``/statusz`` section: ``fn()`` returns a JSON-able dict
    (or None to be omitted).  Last registration under a name wins — a
    rebuilt engine/batcher simply replaces its section."""
    _status_providers[name] = fn


def unregister_status_provider(name: str) -> None:
    _status_providers.pop(name, None)


def register_status_owner(name: str, owner, method: str) -> None:
    """Register ``owner.<method>()`` as a section WITHOUT pinning the
    owner alive: a strong ref from the process-lifetime provider table to
    an engine would pin its params (HBM!) after the caller dropped it."""
    ref = weakref.ref(owner)

    def provider():
        o = ref()
        if o is None:
            unregister_status_provider(name)
            return None
        return getattr(o, method)()

    register_status_provider(name, provider)


def _collect_status() -> dict:
    from . import goodput, recompile, trace

    out: dict = {
        "rank": _registry._rank(),
        "pid": os.getpid(),
        "start_unixtime": _START_WALL,
        "uptime_s": round(time.monotonic() - _START_MONO, 3),
        "xla_recompiles_total": recompile.total_recompiles(),
        "goodput": goodput.summary(),
        # count / seconds / self seconds by span name since process start
        "spans": trace.totals(),
    }
    for name, fn in list(_status_providers.items()):
        try:
            section = fn()
        except Exception as e:       # one broken provider ≠ broken statusz
            section = {"error": repr(e)}
        if section is not None:
            out[name] = section
    return out


def _health() -> tuple:
    """(http_status, payload) for /healthz."""
    from ..utils import heartbeat
    from . import goodput

    hb_age = heartbeat.last_beat_age()
    step_age = goodput.last_step_age()
    payload = {
        "ok": True,
        "unix_time": time.time(),
        "rank": _registry._rank(),
        "pid": os.getpid(),
        "uptime_s": round(time.monotonic() - _START_MONO, 3),
        "heartbeat_age_s": None if hb_age is None else round(hb_age, 3),
        "last_step_age_s": None if step_age is None else round(step_age, 3),
    }
    try:
        stale_after = float(os.environ.get(HEALTHZ_STALE_ENV, "0") or 0)
    except ValueError:
        # a typo'd threshold must degrade to "no staleness gate", not
        # turn every probe into a 500 that restarts healthy workers
        stale_after = 0.0
    if stale_after > 0:
        ages = [a for a in (hb_age, step_age) if a is not None]
        # before any beat/step, age = uptime (a worker stuck in init is
        # just as dead as one stuck mid-loop)
        activity_age = min(ages) if ages else payload["uptime_s"]
        if activity_age > stale_after:
            payload["ok"] = False
            payload["stale_after_s"] = stale_after
            return 503, payload
    return 200, payload


class _Handler(BaseHTTPRequestHandler):
    registry: _registry.Registry = None  # type: ignore[assignment]
    # request tracer serving /tracez; None = resolve the reqtrace
    # module singleton at request time (a tracer installed AFTER the
    # exporter started must still be served)
    tracer = None

    def _send(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802 (http.server API)
        path = self.path.split("?", 1)[0]
        if _chaos.maybe_fire("exporter_blackhole") is not None:
            # injected wedged-observer fault: the scrape fails, serving
            # must not notice (a fleet aggregator sees the replica
            # degrade — HTTPError is a response, not a scrape failure)
            try:
                self._send(503, b"chaos: exporter blackhole\n",
                           "text/plain")
            except Exception:
                pass
            return
        try:
            if path == "/metrics":
                _registry.run_collectors()
                body = self.registry.render_prometheus().encode()
                self._send(200, body,
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                code, payload = _health()
                self._send(code, json.dumps(payload).encode(),
                           "application/json")
            elif path == "/statusz":
                self._send(200, json.dumps(_collect_status()).encode(),
                           "application/json")
            elif path == "/alertz":
                # evaluate (throttled) so a scrape never reads detectors
                # staler than ~1s, then serve active + recent alerts
                from . import anomaly

                anomaly.observe()
                self._send(200, json.dumps(anomaly.status()).encode(),
                           "application/json")
            elif path == "/tracez":
                # retained request traces (telemetry/reqtrace.py): the
                # tail-retention ring's index; ?trace_id= serves one
                # span tree, ?full=1 everything with spans (what the
                # fleet stitcher fetches)
                from urllib.parse import parse_qs, urlparse

                from . import reqtrace

                tracer = self.tracer or reqtrace.get_tracer()
                q = parse_qs(urlparse(self.path).query)
                if tracer is None:
                    self._send(200, json.dumps(
                        {"enabled": False, "retained": []}).encode(),
                        "application/json")
                elif "trace_id" in q:
                    tr = tracer.get_trace(q["trace_id"][0])
                    if tr is None:
                        self._send(404, json.dumps(
                            {"error": "trace not retained",
                             "trace_id": q["trace_id"][0]}).encode(),
                            "application/json")
                    else:
                        self._send(200, json.dumps(tr).encode(),
                                   "application/json")
                else:
                    payload = tracer.payload(full="full" in q)
                    self._send(200, json.dumps(payload).encode(),
                               "application/json")
            else:
                self._send(404, b"not found: try /metrics /healthz /statusz"
                                b" /alertz /tracez\n",
                           "text/plain")
        except BrokenPipeError:
            pass                     # scraper went away mid-response
        except Exception as e:       # a scrape must never kill the worker
            try:
                self._send(500, repr(e).encode(), "text/plain")
            except Exception:
                pass

    def log_message(self, fmt, *args):   # route access logs off stdout
        logger.debug("telemetry exporter: " + fmt % args)


class TelemetryExporter:
    """One daemon HTTP server thread over the (default) registry."""

    def __init__(self, port: int = 0, host: Optional[str] = None,
                 registry: Optional[_registry.Registry] = None,
                 tracer=None):
        self._requested_port = int(port)
        self.host = host if host is not None else \
            os.environ.get(TELEMETRY_HOST_ENV, "127.0.0.1")
        self.registry = registry or _registry.get_registry()
        # /tracez source; None = the reqtrace module singleton at
        # request time.  Explicit tracers exist for multi-exporter
        # emulation in one process (the fleet stitch tests).
        self.tracer = tracer
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> Optional[int]:
        return self._server.server_address[1] if self._server else None

    @property
    def url(self) -> Optional[str]:
        return f"http://{self.host}:{self.port}" if self._server else None

    def start(self) -> "TelemetryExporter":
        if self._server is not None:
            return self
        handler = type("_BoundHandler", (_Handler,),
                       {"registry": self.registry, "tracer": self.tracer})
        self._server = ThreadingHTTPServer(
            (self.host, self._requested_port), handler)
        self._server.daemon_threads = True
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="dstpu-telemetry",
            daemon=True)
        self._thread.start()
        self.registry.gauge(
            "telemetry_exporter_port",
            "bound port of this rank's telemetry HTTP server"
        ).set(float(self.port))
        logger.info(f"telemetry exporter serving /metrics /healthz "
                    f"/statusz /alertz /tracez on {self.url}")
        return self

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            self._thread = None


def write_discovery(ex: "TelemetryExporter", rank: int,
                    directory: Optional[str] = None) -> Optional[str]:
    """Publish this rank's BOUND exporter address as
    ``<dir>/telemetry_rank<k>.json`` (host, port, pid).

    With ``--telemetry_port 0`` (OS-assigned) the actual port is
    unknowable to any scraper; this file is how the fleet plane learns
    it — the launcher aggregates every rank's file into the single
    ``fleet.json`` discovery file ``telemetry/fleet.py`` watches.
    ``directory`` defaults to ``DSTPU_METRICS_DIR`` (launcher-injected);
    no directory → no file.  Atomic rename so a mid-write scan never
    reads a torn JSON; best-effort (returns the path or None)."""
    directory = directory or os.environ.get(_registry.METRICS_DIR_ENV)
    if not directory or ex is None or ex.port is None:
        return None
    try:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"telemetry_rank{rank}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({"rank": rank, "host": ex.host, "port": ex.port,
                       "pid": os.getpid(), "unix_time": time.time()}, fh)
        os.replace(tmp, path)
        return path
    except Exception as e:   # discovery is best-effort, never fatal
        logger.warning(f"telemetry: could not write discovery file: {e!r}")
        return None


_START_MONO = time.monotonic()
_START_WALL = time.time()
_exporter: Optional[TelemetryExporter] = None


def get_exporter() -> Optional[TelemetryExporter]:
    return _exporter


def disarm() -> None:
    """Stop and forget the module exporter (the launcher's guard against
    squatting a worker's port); ``maybe_start`` can arm a fresh one."""
    global _exporter
    if _exporter is not None:
        _exporter.stop()
        _exporter = None


def maybe_start(port: Optional[int] = None) -> Optional[TelemetryExporter]:
    """Start the per-rank exporter when configured; idempotent.

    ``port`` defaults to ``DSTPU_TELEMETRY_PORT`` (launcher-injected);
    unset/empty → no server.  A positive base port is rank-offset
    (rank k binds ``port + k``); 0 asks the OS for a free port."""
    global _exporter
    if _exporter is not None:
        if _exporter._server is not None:
            return _exporter
        _exporter = None        # a stopped exporter is not "armed"
    if port is None:
        env = os.environ.get(TELEMETRY_PORT_ENV)
        if env is None or env == "":
            return None
        try:
            port = int(env)
        except ValueError:
            logger.warning(f"ignoring non-integer {TELEMETRY_PORT_ENV}="
                           f"{env!r}")
            return None
    if port < 0:
        return None
    # rank from ENV ONLY: this runs at `import deepspeed_tpu`, and the
    # registry's jax.process_index() fallback would initialize the jax
    # backends before the user script can call jax.distributed.initialize()
    # (fatal on multi-host).  On real pods (one process per host, no
    # DSTPU_PROCESS_ID) every host correctly binds the base port.
    try:
        rank = int(os.environ.get("DSTPU_PROCESS_ID", "0"))
    except ValueError:
        rank = 0
    bound = port + rank if port > 0 else 0
    _chaos.maybe_install_env()   # exporter-only processes resolve the
    try:                         # DSTPU_CHAOS_PLAN here
        _exporter = TelemetryExporter(port=bound).start()
    except OSError as e:
        logger.warning(f"telemetry exporter failed to bind port {bound}: "
                       f"{e}; continuing without one")
        _exporter = None
    if _exporter is not None:
        # fleet discovery: publish the BOUND port (essential with
        # port 0) where the launcher's fleet.json aggregation reads it
        write_discovery(_exporter, rank)
    return _exporter
