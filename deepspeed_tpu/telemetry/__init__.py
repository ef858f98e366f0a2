"""Unified telemetry layer: metrics registry + step tracer + recompile
watchdog + live observability plane.

Seven coordinated surfaces replacing the reference's scattered
``monitor/`` / ``utils/timer.py`` / profiler observability:

- :mod:`.registry` — process-wide counters/gauges/histograms with JSON
  (``snapshot()``) and Prometheus-text export; every subsystem
  (``MonitorMaster`` events, ``ThroughputTimer``, serving latency,
  heartbeats, the watchdog) publishes here.
- :mod:`.trace` — the one tracer.  Every host span (``init/engine``,
  ``init/params``, ``eval/dispatch``; ``train/step`` with children
  ``train/next-batch``, ``train/device-put``, ``train/dispatch``;
  ``serve/step`` with ``serve/admit``, ``serve/prefill-batch`` →
  ``serve/prefill``, ``serve/decode-tick`` → ``serve/fetch``,
  ``serve/retire``) is always kept in a bounded ring with its parent
  (read API: ``trace.spans(prefix, since_s, until_s)`` on the
  ``perf_counter`` axis, ``trace.totals()``), always mirrored into
  ``jax.profiler.TraceAnnotation`` so a profiler session shows it on the
  device trace's clock, and written as Chrome-trace JSON when enabled.
  Measured cost with the Chrome recorder off: 3-5 us a span.
  ``device_span`` stamps HLO metadata inside compiled code
  (``loss_head``, ``grad_clip``, ``optimizer``, ``embed``,
  ``zero/scatter``, the pipeline stages; a ZeRO gather has no scope of
  its own: its time is booked under the module that consumes the shard).
- :mod:`.recompile` — watchdog over jitted hot loops: a call that made
  an executable signs its arguments, is counted, and warns when a warm
  loop recompiled (a call that made none costs two counter reads); and
  the compile-event listeners: seconds by phase
  (``xla_compile_seconds_total{phase,span}``) and executables
  (``xla_executables_total{how,span}``) by innermost open span, for
  every executable the process makes, plus a ``compile/backend`` record
  in the tracer's ring under the span that compiled.  A ``staged``
  site (the training engine's steps) keeps the executable it runs and
  books its memory when it is made.
- :mod:`.device_scopes` — device time by the program's own scopes: the
  instruction → ``op_name`` map of a kept executable and the reduction
  of a profiler trace over it (``engine.profile_device_scopes``); and,
  from the same parse, the ledger of the executable's collectives
  (``collective_ledger``: op, group, bytes a device receives, consumer
  scope and pass), booked for every executable of more than one device
  as ``step_collectives`` / ``step_collective_recv_bytes{site, op}``
  beside ``parallel/zero.py``'s ``zero_required_recv_bytes{what}``.
- :mod:`.exporter` — per-rank HTTP server (``/metrics`` Prometheus
  text, ``/healthz`` liveness JSON, ``/statusz`` operational JSON,
  ``/alertz``, ``/tracez``);
  opt-in via ``dstpu --telemetry_port`` / ``DSTPU_TELEMETRY_PORT``.
- :mod:`.goodput` — step-phase wall-time attribution (compute /
  data-wait / checkpoint / recompile / idle) + ``goodput_ratio``.
- :mod:`.memory` — per-executable HBM accounting
  (``compiled.memory_analysis()`` normalized behind ONE helper) and
  live-array memory gauges sampled at scrape time.
- :mod:`.flightrec` — crash flight recorder (last logs / metric deltas,
  and the newest spans of the tracer's ring) dumped on atexit,
  SIGTERM/SIGABRT, and unhandled exceptions; the launcher pretty-prints
  it on restart.
- :mod:`.anomaly` — rolling detectors over registry series (recompile
  storm, SLO burn, queue runaway, acceptance collapse, goodput drop,
  loss spike, grad-norm explosion) raising structured alerts:
  ``alerts_total{rule}``, ``/alertz``, ``subscribe`` (the admission
  ladder and the ``TrainGuard`` consume them).
- :mod:`.fleet` — the multi-replica rollup: scrapes N per-rank
  exporters (static list / env / the launcher-written ``fleet.json``),
  merges them per metric kind, runs a per-replica health state
  machine, and serves ``/fleetz`` + a federated ``/metrics`` — the
  ``FleetView`` seam the multi-replica router steers by.
- :mod:`.reqtrace` — request-scoped distributed tracing: per-request
  span trees off the serving lifecycle observers (``traceparent``
  propagation, deterministic trace ids), tail-based retention (head
  sampling plus unconditional promotion of SLO-violating and
  alert-coincident requests), ``/tracez``, Perfetto export on the
  ``trace.py`` time axis, and the fleet stitcher.  Opt-in via
  ``DSTPU_REQTRACE=1``.

Launcher integration: ``dstpu --metrics_dir DIR`` injects
``DSTPU_METRICS_DIR`` so every rank dumps ``metrics_rank<k>.json`` on
exit (and, with the flight recorder, on SIGTERM) plus
``flight_<k>.json`` forensics; ``dstpu --telemetry_port P`` serves the
live endpoints on ``P + rank``; ``DSTPU_TRACE=/path.json`` also keeps
Chrome-trace events and writes the file on exit (use ``{rank}`` in the
path for multi-rank runs).
"""
from . import recompile, trace  # noqa: F401
from .registry import (  # noqa: F401
    Counter, Gauge, Histogram, Registry, counter, gauge, get_registry,
    histogram, maybe_install_exit_dump,
)
from . import goodput, memory  # noqa: F401  (need registry+trace above)
from . import exporter, flightrec  # noqa: F401
from . import anomaly  # noqa: F401  (needs exporter above)
from . import fleet  # noqa: F401  (needs registry + anomaly above)
from . import reqtrace  # noqa: F401  (needs registry + trace above)

# arm the per-rank exit dump when the launcher asked for one
maybe_install_exit_dump()
# goodput attribution rides span boundaries; always on
goodput.install()
# compile seconds and executables by phase and open span; always on
recompile.install_compile_events()
# live-HBM gauges refresh on every scrape/dump
from .registry import register_collector as _register_collector  # noqa: E402

_register_collector(memory.sample_live_hbm)
# anomaly/alert detectors (/alertz, evaluated on scrapes and step
# boundaries)
anomaly.install()
# crash forensics when a dump dir is configured; live endpoints when a
# port is configured
flightrec.maybe_install()
exporter.maybe_start()
