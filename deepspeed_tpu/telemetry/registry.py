"""Process-wide metrics registry: counters, gauges, histograms.

The reference scatters observability across ``monitor/`` (event fan-out
to TensorBoard/W&B/CSV), ``utils/timer.py`` (log-line throughput) and the
FLOPs profiler — each with private state and no export surface.  This
module is the shared substrate: every subsystem publishes named metrics
into ONE registry, which renders to JSON (``snapshot()``) and to the
Prometheus text exposition format (``render_prometheus()``), so a
production deployment scrapes a single endpoint / reads a single per-rank
dump file instead of tailing logs.

Design notes
- Metric handles are get-or-create and idempotent: calling
  ``registry.counter("x")`` twice returns the same object; re-registering
  a name with a different type/labelset raises (a silent re-type would
  corrupt downstream dashboards).
- All mutation is lock-protected but O(dict lookup + float add): cheap
  enough for per-train-step / per-decode-tick increments.
- Histograms are fixed-bucket (Prometheus semantics: cumulative
  ``le``-bucket counts + ``_sum`` + ``_count``); no quantile sketching,
  so merging across ranks is exact addition.
- Per-rank export on exit: the launcher injects ``DSTPU_METRICS_DIR``;
  :func:`maybe_install_exit_dump` (called on ``telemetry`` import)
  registers an ``atexit`` writer of ``metrics_rank<k>.json`` there.
"""
from __future__ import annotations

import atexit
import bisect
import json
import math
import os
import threading
from typing import Dict, Iterable, Optional, Sequence, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "get_registry", "counter", "gauge", "histogram",
    "maybe_install_exit_dump", "flush_exit_dump", "register_collector",
    "run_collectors", "METRICS_DIR_ENV", "pct",
    "render_prometheus_snapshot",
    "SECONDS_BUCKETS", "MS_BUCKETS", "TPOT_MS_BUCKETS",
    "ACCEPT_LEN_BUCKETS", "BYTES_BUCKETS", "BUCKET_SCHEMAS",
]

METRICS_DIR_ENV = "DSTPU_METRICS_DIR"


def pct(sorted_xs, q: float) -> float:
    """THE repo-wide percentile convention — nearest-rank over an
    ascending sequence, NaN on empty.  ``ContinuousBatcher``
    (``latency_stats``/``/statusz``) and ``telemetry/loadgen.py`` both
    import this one function, so the serving surfaces and the load
    report cannot disagree on a tail."""
    if not sorted_xs:
        return float("nan")
    return sorted_xs[min(len(sorted_xs) - 1, int(q * len(sorted_xs)))]

# Prometheus default buckets skew web-request-sized; these cover both
# decode ticks (sub-ms) and train steps / checkpoint writes (minutes).
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

# -- named bucket schemas ----------------------------------------------
# Every ``histogram()`` call site references ONE of these by name instead
# of declaring ad-hoc tuples: the fleet aggregator
# (``telemetry/fleet.py``) merges histograms bucket-wise across replicas
# and can only assert "one schema per metric family" if the schemas are
# declared once.  ``serving_tpot_ms`` growing sub-ms buckets while other
# ms-histograms kept defaults is exactly the drift this centralization
# ends.
#
# seconds-denominated wall times (train steps, TTFT, checkpoint writes)
SECONDS_BUCKETS: Tuple[float, ...] = DEFAULT_BUCKETS
# ms-denominated wall times with a web-ish floor (scrape round-trips,
# queueing delays): 0.1 ms .. minutes
MS_BUCKETS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
    500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0)
# ms-denominated per-output-token latency: fused+paged decode on real
# chips lands in the tens of MICROseconds (below MS_BUCKETS' 0.1 floor,
# which collapsed the p50/p99 the anomaly detectors read), CPU-mesh
# tests in seconds
TPOT_MS_BUCKETS: Tuple[float, ...] = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0)
# accepted drafts per slot per verify tick land in [0, k]; covers any
# sane k without re-registering per config
ACCEPT_LEN_BUCKETS: Tuple[float, ...] = (
    0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0)
# byte-sized payloads (checkpoint writes, parked caches): KiB test
# fixtures through TiB-scale production checkpoints
BYTES_BUCKETS: Tuple[float, ...] = (
    1 << 10, 64 << 10, 1 << 20, 16 << 20, 128 << 20, 1 << 30,
    8 << 30, 64 << 30, 512 << 30, 1 << 42)

BUCKET_SCHEMAS: Dict[str, Tuple[float, ...]] = {
    "seconds": SECONDS_BUCKETS,
    "ms": MS_BUCKETS,
    "tpot_ms": TPOT_MS_BUCKETS,
    "accept_len": ACCEPT_LEN_BUCKETS,
    "bytes": BYTES_BUCKETS,
}


def _escape_label_value(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_value(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Child:
    """One labelset's value cell."""

    __slots__ = ("_lock", "_value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self._value = 0.0

    @property
    def value(self) -> float:
        return self._value


class _CounterChild(_Child):
    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter increment must be >= 0, got {amount}")
        with self._lock:
            self._value += amount


class _GaugeChild(_Child):
    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value -= amount


class _HistogramChild:
    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, lock: threading.Lock, buckets: Tuple[float, ...]):
        self._lock = lock
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)   # +1 for the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        value = float(value)
        if value != value:          # NaN observations poison sum/percentiles
            return
        with self._lock:
            # non-cumulative per-bucket counts internally; rendered
            # cumulatively (Prometheus ``le`` semantics) on export
            self.counts[bisect.bisect_left(self.buckets, value)] += 1
            self.sum += value
            self.count += 1

    def cumulative(self) -> Iterable[Tuple[float, int]]:
        acc = 0
        for b, c in zip(self.buckets, self.counts):
            acc += c
            yield b, acc
        yield float("inf"), acc + self.counts[-1]


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Tuple[str, ...],
                 lock: threading.Lock, **kwargs):
        self.name = name
        self.help = help
        self.labelnames = labelnames
        self._lock = lock
        self._kwargs = kwargs
        self._children: Dict[Tuple[str, ...], object] = {}

    def _make_child(self):
        raise NotImplementedError

    def labels(self, *values, **kv):
        if kv:
            if values:
                raise ValueError("pass label values positionally OR by name")
            try:
                values = tuple(str(kv[n]) for n in self.labelnames)
            except KeyError as e:
                raise ValueError(
                    f"metric {self.name} labels are {self.labelnames}") from e
            if len(kv) != len(self.labelnames):
                raise ValueError(
                    f"metric {self.name} labels are {self.labelnames}, "
                    f"got {sorted(kv)}")
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"metric {self.name} takes {len(self.labelnames)} label "
                f"value(s) {self.labelnames}, got {len(values)}")
        with self._lock:
            child = self._children.get(values)
            if child is None:
                child = self._children[values] = self._make_child()
        return child

    def _default_child(self):
        if self.labelnames:
            raise ValueError(
                f"metric {self.name} has labels {self.labelnames}; call "
                f".labels(...) first")
        return self.labels()

    def samples(self):
        with self._lock:
            items = list(self._children.items())
        return items


class Counter(_Metric):
    kind = "counter"

    def _make_child(self):
        return _CounterChild(self._lock)

    def inc(self, amount: float = 1.0) -> None:
        self._default_child().inc(amount)

    @property
    def value(self) -> float:
        return self._default_child().value

    def total(self) -> float:
        """Sum over every labelset (convenience for tests/assertions)."""
        return sum(c.value for _, c in self.samples())


class Gauge(_Metric):
    kind = "gauge"

    def _make_child(self):
        return _GaugeChild(self._lock)

    def set(self, value: float) -> None:
        self._default_child().set(value)

    def inc(self, amount: float = 1.0) -> None:
        self._default_child().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self._default_child().dec(amount)

    @property
    def value(self) -> float:
        return self._default_child().value


class Histogram(_Metric):
    kind = "histogram"

    def _make_child(self):
        return _HistogramChild(self._lock, self._kwargs["buckets"])

    def observe(self, value: float) -> None:
        self._default_child().observe(value)


class Registry:
    """Named metric store with JSON + Prometheus export."""

    def __init__(self):
        # REENTRANT: the flight recorder's SIGTERM handler runs on the
        # main thread and reads the registry; a plain Lock held by the
        # interrupted increment would deadlock shutdown and lose the
        # forensics the handler exists to save
        self._lock = threading.RLock()
        self._metrics: Dict[str, _Metric] = {}

    # -- get-or-create handles ----------------------------------------
    def _get(self, cls, name: str, help: str,
             labelnames: Sequence[str], **kwargs) -> _Metric:
        labelnames = tuple(labelnames)
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, labelnames,
                                              self._lock, **kwargs)
                return m
        if not isinstance(m, cls):
            raise ValueError(
                f"metric {name!r} already registered as {m.kind}, "
                f"requested {cls.kind}")
        if m.labelnames != labelnames and labelnames:
            raise ValueError(
                f"metric {name!r} already registered with labels "
                f"{m.labelnames}, requested {labelnames}")
        if kwargs and m._kwargs != kwargs:
            # e.g. histogram buckets: observations landing in a different
            # bucket layout than the caller expects would silently corrupt
            # downstream dashboards — same failure class as a re-type
            raise ValueError(
                f"metric {name!r} already registered with "
                f"{m._kwargs}, requested {kwargs}")
        return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, labelnames,
                         buckets=tuple(sorted(buckets)))

    # -- export --------------------------------------------------------
    def snapshot(self) -> dict:
        """JSON-able view of every metric: counters/gauges as plain
        values, histograms as cumulative ``le``-bucket maps + sum/count."""
        out: dict = {}
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            entry: dict = {"type": m.kind, "help": m.help,
                           "labelnames": list(m.labelnames), "samples": []}
            for labelvalues, child in m.samples():
                labels = dict(zip(m.labelnames, labelvalues))
                if m.kind == "histogram":
                    entry["samples"].append({
                        "labels": labels,
                        "buckets": {_fmt_value(le): c
                                    for le, c in child.cumulative()},
                        "sum": child.sum, "count": child.count})
                else:
                    entry["samples"].append(
                        {"labels": labels, "value": child.value})
            out[m.name] = entry
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition format (v0.0.4)."""
        return render_prometheus_snapshot(self.snapshot())

    def dump(self, path: str) -> None:
        """Write ``snapshot()`` as JSON (atomic rename)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.snapshot(), fh, indent=1)
        os.replace(tmp, path)

    def clear(self) -> None:
        """Drop every metric (test isolation helper)."""
        with self._lock:
            self._metrics.clear()


def render_prometheus_snapshot(snap: dict) -> str:
    """Prometheus text exposition for a ``snapshot()``-shaped dict.

    Module-level (not a ``Registry`` method) because the fleet
    aggregator (``telemetry/fleet.py``) renders structures it PARSED
    from remote replicas' ``/metrics`` with this same function —
    ``parse_prometheus(render_prometheus())`` round-trips
    byte-equivalently only because both directions share one renderer."""
    lines = []
    for name, entry in snap.items():
        if entry["help"]:
            lines.append(f"# HELP {name} {entry['help']}")
        lines.append(f"# TYPE {name} {entry['type']}")
        for s in entry["samples"]:
            base_labels = ",".join(
                f'{k}="{_escape_label_value(v)}"'
                for k, v in s["labels"].items())
            if entry["type"] == "histogram":
                for le, c in s["buckets"].items():
                    ls = (base_labels + "," if base_labels else "") \
                        + f'le="{le}"'
                    lines.append(f"{name}_bucket{{{ls}}} {c}")
                suffix = f"{{{base_labels}}}" if base_labels else ""
                lines.append(
                    f"{name}_sum{suffix} {_fmt_value(s['sum'])}")
                lines.append(f"{name}_count{suffix} {s['count']}")
            else:
                suffix = f"{{{base_labels}}}" if base_labels else ""
                lines.append(
                    f"{name}{suffix} {_fmt_value(s['value'])}")
    return "\n".join(lines) + ("\n" if lines else "")


_default_registry = Registry()


def get_registry() -> Registry:
    return _default_registry


def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> Counter:
    return _default_registry.counter(name, help, labelnames)


def gauge(name: str, help: str = "",
          labelnames: Sequence[str] = ()) -> Gauge:
    return _default_registry.gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return _default_registry.histogram(name, help, labelnames, buckets)


def _rank() -> int:
    # launcher-injected rank first (set before jax initializes); fall back
    # to jax.process_index() only if jax is already imported (never force
    # the import from an atexit path)
    env = os.environ.get("DSTPU_PROCESS_ID")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            pass
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index()
        except Exception:
            pass
    return 0


# -- scrape-time collectors --------------------------------------------
# Gauges that must be SAMPLED rather than pushed (live-array HBM, the
# goodput ratio) register a collector; every export surface (the HTTP
# exporter, the exit dump, the flight recorder) refreshes them via
# run_collectors() immediately before reading the registry, so a scrape
# never serves a value staler than the previous scrape.
_collectors: list = []


def register_collector(fn) -> None:
    """Register ``fn()`` to run before every export/scrape (idempotent)."""
    if fn not in _collectors:
        _collectors.append(fn)


def run_collectors() -> None:
    """Run every registered collector; one failing collector never takes
    down a scrape (or interpreter shutdown)."""
    for fn in list(_collectors):
        try:
            fn()
        except Exception:
            pass


_exit_dump_installed: Optional[str] = None


def disarm_exit_dump() -> None:
    """Make the (already-registered) exit dump a no-op — the launcher
    process must not clobber worker rank 0's ``metrics_rank0.json``
    when the operator exported ``DSTPU_METRICS_DIR`` shell-wide."""
    global _exit_dump_installed
    _exit_dump_installed = None


def flush_exit_dump() -> Optional[str]:
    """Write the per-rank exit dump NOW (refreshing collectors first).

    Callable from signal handlers as well as ``atexit`` — SIGTERM (the
    launcher killing a stale worker, or a preemption) does not run
    ``atexit`` hooks, so the flight recorder's SIGTERM handler calls this
    to keep the rank's final snapshot from being lost.  No-op when no
    dump directory was ever armed; returns the written path."""
    if not _exit_dump_installed:
        return None
    try:
        run_collectors()
        path = os.path.join(_exit_dump_installed,
                            f"metrics_rank{_rank()}.json")
        _default_registry.dump(path)
        return path
    except Exception:
        return None   # never let a metrics dump break shutdown paths


def maybe_install_exit_dump(directory: Optional[str] = None) -> Optional[str]:
    """Register an ``atexit`` dump of the default registry to
    ``<dir>/metrics_rank<k>.json``.  ``directory`` defaults to the
    ``DSTPU_METRICS_DIR`` env var (injected by the launcher); no-op when
    neither is set.  Returns the target directory (or None).

    The rank — and so the file name — resolves at DUMP time, not here:
    this usually runs at ``import deepspeed_tpu``, before jax has
    initialized, and a launcher-less multi-host job would otherwise bake
    rank 0 into every host and have them clobber one file."""
    global _exit_dump_installed
    directory = directory or os.environ.get(METRICS_DIR_ENV)
    if not directory:
        return None
    if _exit_dump_installed == directory:
        return directory
    already_armed = _exit_dump_installed is not None
    _exit_dump_installed = directory
    if not already_armed:
        atexit.register(flush_exit_dump)
    return directory
