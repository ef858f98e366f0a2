"""Per-executable roofline attribution: device-time verdicts.

The stack can *measure* (registry counters, spans, goodput, the load
harness) but until now could not *attribute*: ``bench.py``'s
``bw_floor_frac`` was one hand-derived number for one executable, and
the W8A16 regression sat unexplained for six bench rounds until a
manual profile traced it to launch-count asymmetry.  This module makes
that attribution automatic, for every hot executable:

- **Costs** — ``compiled.cost_analysis()`` FLOPs / bytes-accessed are
  harvested wherever a ``Compiled`` handle already exists
  (:func:`telemetry.memory.record_compiled` forwards every AOT site:
  ``engine.record_memory_profile``, serving ``warmup_windows`` /
  ``_warmup_admission`` incl. place/retire, the flops profiler), plus a
  lazy one-shot ``lower().compile()`` harvest (:meth:`ensure_costs`)
  for executables that only materialize inside the hot loop (specdec
  verify widths, prefill chunks).
- **Measured time** — sampled timing windows: 1-in-N ticks/steps
  (``DSTPU_ATTRIBUTION_SAMPLE``, default 8) record host wall time for
  the executable, behind the opt-in ``DSTPU_ATTRIBUTION=1`` flag.
  Serving windows are already fenced by their token fetch, so sampling
  there costs a dict update; the train step and prefill chunks fence
  via ``block_until_ready`` only on sampled iterations.
- **Verdicts** — each (costs, timing) pair yields ``mfu`` (flops vs the
  chip's peak), ``bw_frac`` (bytes vs the chip's HBM bandwidth), and a
  bound-class verdict: ``compute-bound`` / ``hbm-bound`` /
  ``overhead-bound`` (neither roof within reach — dispatch/launch
  overhead dominates, the W8A16 failure class).

Export surfaces: ``/profilez`` (the full per-executable table), the
``/statusz`` ``attribution`` section, ``attribution_*`` registry
gauges, and the flight dump (a crash postmortem shows what was slow).
This module also owns THE device physics tables (peak FLOPs, HBM
bytes/s) — ``bench.py`` and ``profiling/flops_profiler.py`` read them
from here, so the bench and the live plane can never report different
physics for the same executable.
"""
from __future__ import annotations

import os
import statistics
import sys
import threading
import time
from collections import deque
from typing import Optional

from ..utils.logging import logger
from . import registry as _registry

__all__ = [
    "ATTRIBUTION_ENV", "SAMPLE_ENV", "PEAK_FLOPS", "HBM_BYTES_S",
    "HBM_BYTES", "device_known", "device_peak_flops",
    "device_hbm_bytes_s", "device_hbm_bytes", "harvest_costs",
    "roofline", "decode_stream_floor", "AttributionPlane", "get_plane",
    "enabled", "enable", "should_sample", "note_compiled", "note_measured",
    "note_window", "ensure_costs", "timed_jit_call", "snapshot", "status",
    "install", "capture_trace",
]

ATTRIBUTION_ENV = "DSTPU_ATTRIBUTION"
SAMPLE_ENV = "DSTPU_ATTRIBUTION_SAMPLE"

# -- device physics (THE one copy; bench.py, the flops profiler and the
# autotuner read these).  Keyed by a substring of ``device_kind``.  A
# device that is not in the table is an error, not a default: a CPU run
# has no peak, and a number against an invented one is noise.
# Source: Google Cloud TPU documentation, system architecture pages
# (v4, v5e, v5p, v6e): bf16 peak FLOP/s, HBM bytes/s, HBM bytes per chip.
PEAK_FLOPS = {"v4": 275e12, "v5 lite": 197e12, "v5e": 197e12,
              "v5p": 459e12, "v6 lite": 918e12, "v6e": 918e12}

# HBM bandwidth per chip (bytes/s) — the decode bandwidth-floor
# denominator: a decode tick streams every weight byte plus the live KV
# cache, so floor_ms = bytes / BW is the physics bound serving numbers
# are judged against.
HBM_BYTES_S = {"v4": 1228e9, "v5 lite": 819e9, "v5e": 819e9,
               "v5p": 2765e9, "v6 lite": 1640e9, "v6e": 1640e9}

# HBM capacity per chip (bytes) — the autotuner's fit budget.
HBM_BYTES = {"v4": 32e9, "v5 lite": 16e9, "v5e": 16e9,
             "v5p": 95e9, "v6 lite": 32e9, "v6e": 32e9}

# verdict threshold: a roof (mfu or bw_frac) must explain at least this
# fraction of the measured time to call the executable bound by it;
# below both roofs the time is going to dispatch/launch overhead.
_OVERHEAD_FRAC_ENV = "DSTPU_ATTRIBUTION_OVERHEAD_FRAC"
_DEFAULT_OVERHEAD_FRAC = 0.10

_SAMPLE_WINDOW = 32        # timing samples retained per site (median)


def device_known(dev) -> bool:
    """Whether ``dev``'s ``device_kind`` has a row in the physics tables
    (callers that run legitimately off-TPU skip their roofline numbers
    when it does not)."""
    kind = dev.device_kind.lower()
    return any(key in kind for key in PEAK_FLOPS)


def _device_lookup(dev, table: dict, what: str) -> float:
    if dev is None:
        dev = _device0()
    kind = dev.device_kind.lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise ValueError(
        f"no {what} known for device_kind {dev.device_kind!r}; add the "
        f"chip (with its source) to telemetry/attribution.py")


def device_peak_flops(dev=None) -> float:
    """Peak bf16 FLOPs/s of ``dev`` (device 0 when None) from
    :data:`PEAK_FLOPS`; raises for a ``device_kind`` not in the table."""
    return _device_lookup(dev, PEAK_FLOPS, "peak FLOP/s")


def device_hbm_bytes_s(dev=None) -> float:
    """HBM bandwidth (bytes/s) of ``dev`` from :data:`HBM_BYTES_S`."""
    return _device_lookup(dev, HBM_BYTES_S, "HBM bandwidth")


def device_hbm_bytes(dev=None) -> float:
    """HBM capacity (bytes) of ``dev`` from :data:`HBM_BYTES`."""
    return _device_lookup(dev, HBM_BYTES, "HBM capacity")


def _device0():
    import jax

    return jax.local_devices()[0]


def harvest_costs(compiled) -> Optional[dict]:
    """THE ``cost_analysis()`` normalizer: ``{"flops", "bytes_accessed",
    "transcendentals"}`` (floats) or None when the backend exposes no
    analysis.  ``profiling/flops_profiler.py`` delegates here — the
    profiler, the bench, and the live plane share one reading of the
    compiler's numbers."""
    try:
        costs = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(costs, (list, tuple)):     # some backends: [dict]
        costs = costs[0] if costs else None
    if costs is None:
        return None
    costs = dict(costs)
    return {
        "flops": float(costs.get("flops", 0.0)),
        "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
        "transcendentals": float(costs.get("transcendentals", 0.0)),
    }


def roofline(flops: float, hbm_bytes: float, seconds: float,
             peak_flops: float, hbm_bytes_s: float,
             overhead_frac: Optional[float] = None) -> dict:
    """Roofline verdict for one executable invocation.

    ``mfu`` = achieved FLOPs/s over peak; ``bw_frac`` = achieved
    bytes/s over HBM bandwidth.  The verdict names the roof the
    measured time is actually pressed against:

    - ``compute-bound`` — mfu is the binding (larger) fraction;
    - ``hbm-bound``     — bw_frac is the binding fraction;
    - ``overhead-bound`` — NEITHER roof explains ``overhead_frac`` of
      the time: the executable is dominated by dispatch/launch/host
      overhead (the W8A16 launch-asymmetry class), and streaming fewer
      bytes or doing fewer FLOPs will not make it faster.
    """
    if overhead_frac is None:
        try:
            overhead_frac = float(os.environ.get(
                _OVERHEAD_FRAC_ENV, _DEFAULT_OVERHEAD_FRAC))
        except ValueError:
            overhead_frac = _DEFAULT_OVERHEAD_FRAC
    seconds = max(float(seconds), 1e-12)
    mfu = flops / (seconds * peak_flops) if peak_flops else 0.0
    bw_frac = hbm_bytes / (seconds * hbm_bytes_s) if hbm_bytes_s else 0.0
    if max(mfu, bw_frac) < overhead_frac:
        verdict = "overhead-bound"
    elif bw_frac >= mfu:
        verdict = "hbm-bound"
    else:
        verdict = "compute-bound"
    return {"mfu": mfu, "bw_frac": bw_frac, "verdict": verdict}


def decode_stream_floor(params, slot_cache, n_slots: int, dev=None) -> dict:
    """The decode-tick HBM bandwidth floor: every stored weight byte
    plus the slots' KV caches must stream from HBM each tick, so
    ``bw_floor_ms_per_tick`` is the physics bound a measured
    ms-per-tick is judged against.  ``slot_cache`` is a ONE-slot cache
    tree (arrays or ``ShapeDtypeStruct``\\ s — ``eval_shape`` is fine).
    This is ``bench.py --mode serving``'s accounting, shared so the
    bench and the live plane cannot disagree on the same executable's
    physics."""
    from . import memory as _memory

    weight_bytes = _memory.tree_bytes(params)
    kv_bytes = int(n_slots) * _memory.tree_bytes(slot_cache)
    bw = device_hbm_bytes_s(dev)
    return {
        "weight_stream_bytes": int(weight_bytes),
        "kv_stream_bytes_per_tick": int(kv_bytes),
        "hbm_bytes_s": float(bw),
        "bw_floor_ms_per_tick": 1000.0 * (weight_bytes + kv_bytes) / bw,
    }


class AttributionPlane:
    """Process-wide per-executable (site) cost + timing store.

    Sites are the recompile-watchdog names (``serving.decode[16g]``,
    ``serving.verify[4g]``, ``engine.train_step`` …), so every surface
    — watchdog warnings, HBM gauges, this table — speaks one naming
    scheme."""

    def __init__(self, registry: Optional[_registry.Registry] = None):
        reg = registry or _registry.get_registry()
        # RLock: the flight recorder's signal handler snapshots from the
        # main thread, possibly interrupting note_measured mid-hold
        self._lock = threading.RLock()
        self._sites: dict = {}
        self._tick_counts: dict = {}
        self._cost_failed: set = set()
        self._first_skipped: set = set()
        self._forced: Optional[bool] = None
        self._physics: Optional[tuple] = None    # (kind, peak, bw)
        self._m_samples = reg.counter(
            "attribution_samples_total",
            "timed executable windows recorded", labelnames=("site",))
        self._m_ms = reg.gauge(
            "attribution_measured_ms",
            "median sampled wall ms of the executable",
            labelnames=("site",))
        self._m_mfu = reg.gauge(
            "attribution_mfu",
            "achieved FLOPs/s over device peak", labelnames=("site",))
        self._m_bw = reg.gauge(
            "attribution_bw_frac",
            "achieved bytes/s over device HBM bandwidth",
            labelnames=("site",))

    # -- enablement ----------------------------------------------------
    def enabled(self) -> bool:
        if self._forced is not None:
            return self._forced
        return os.environ.get(ATTRIBUTION_ENV, "") not in ("", "0")

    def enable(self, on: Optional[bool] = True) -> None:
        """Programmatic override of ``DSTPU_ATTRIBUTION`` (None defers
        back to the env)."""
        self._forced = on

    def sample_every(self) -> int:
        try:
            return max(1, int(os.environ.get(SAMPLE_ENV, "8")))
        except ValueError:
            return 8

    def should_sample(self, site: str) -> bool:
        """1-in-N per-site sampling decision; the FIRST call per site
        always samples (deterministic warm coverage)."""
        with self._lock:
            n = self._tick_counts.get(site, 0)
            self._tick_counts[site] = n + 1
        return n % self.sample_every() == 0

    # -- costs ---------------------------------------------------------
    def _site(self, site: str) -> dict:
        with self._lock:
            s = self._sites.get(site)
            if s is None:
                s = self._sites[site] = {
                    # dstpu-lint: disable-next-line=DSTPU006 -- hbm_bytes is the /profilez row KEY (the ISSUE-specified field name), not a registry metric
                    "flops": None, "hbm_bytes": None, "costs_src": None,
                    "samples": deque(maxlen=_SAMPLE_WINDOW), "calls": 0}
            return s

    def note_costs(self, site: str, flops: float, hbm_bytes: float,
                   src: str = "aot") -> None:
        s = self._site(site)
        with self._lock:
            s["flops"] = float(flops)
            # dstpu-lint: disable-next-line=DSTPU006 -- hbm_bytes is the /profilez row KEY, not a registry metric
            s["hbm_bytes"] = float(hbm_bytes)
            s["costs_src"] = src

    def note_compiled(self, compiled, site: str, src: str = "aot"
                      ) -> Optional[dict]:
        """Harvest ``compiled.cost_analysis()`` into the site (no-op
        when the backend exposes none).  Called by
        ``telemetry.memory.record_compiled`` at every AOT point, so
        existing compile sites feed the table for free."""
        costs = harvest_costs(compiled)
        if costs is None:
            return None
        self.note_costs(site, costs["flops"], costs["bytes_accessed"],
                        src=src)
        return costs

    def ensure_costs(self, site: str, jitfn, *args) -> None:
        """One-shot lazy cost harvest for executables with no AOT
        compile point: ``jitfn.lower(*args).compile()`` (abstract — no
        execution, donation-safe) then harvest.  A site that ever fails
        is never retried (one warning, not a per-window stall)."""
        s = self._site(site)
        with self._lock:
            if s["flops"] is not None or site in self._cost_failed:
                return
            self._cost_failed.add(site)    # claim: only one attempt ever
        try:
            compiled = jitfn.lower(*args).compile()
        except Exception as e:
            logger.debug(f"attribution: cost harvest failed for "
                         f"{site!r}: {e!r}")
            return
        if self.note_compiled(compiled, site, src="lazy") is None:
            logger.debug(f"attribution: no cost_analysis for {site!r}")

    # -- measured time -------------------------------------------------
    def note_measured(self, site: str, wall_s: float, calls: int = 1
                      ) -> None:
        """Record one sampled timing window for ``site`` (host wall
        seconds per executable invocation) and refresh the site's
        gauges/verdict."""
        ms = 1000.0 * float(wall_s) / max(1, int(calls))
        s = self._site(site)
        with self._lock:
            s["samples"].append(ms)
            s["calls"] += int(calls)
        self._m_samples.labels(site=site).inc(calls)
        row = self._row(site, s)
        self._m_ms.labels(site=site).set(row["measured_ms"])
        if row["mfu"] is not None:
            self._m_mfu.labels(site=site).set(row["mfu"])
            self._m_bw.labels(site=site).set(row["bw_frac"])

    def _should_record(self, site: str, jitfn, sigs_before) -> bool:
        """Was this window a STEADY-STATE execution (no trace+compile
        inside the call)?  Compile wall is not device time, and one
        warm-up outlier would poison a once-only site's verdict.

        Primary signal: the recompile watchdog's ``signatures_seen``
        (unchanged across the call ⇒ no compile).  When the watchdog is
        disabled the wrapper — and the signal — is absent; falling back
        to "record everything" would record exactly the first sampled
        call, which is the one that pays the full XLA compile.  So with
        no signature visibility the FIRST sampled call per site is
        skipped and later ones recorded (post-warm-up compiles are the
        rare case the watchdog exists to catch anyway)."""
        if sigs_before is not None:
            return getattr(jitfn, "signatures_seen", None) == sigs_before
        with self._lock:
            if site in self._first_skipped:
                return True
            self._first_skipped.add(site)
            return False

    def note_window(self, site: str, wall_s: float, jitfn=None,
                    sigs_before=None, args: tuple = ()) -> bool:
        """Record one already-measured window for ``site`` if it was
        steady-state (see :meth:`_should_record`); on a recorded window
        with ``args``, ALSO run the one-shot lazy cost harvest — AFTER
        the timed interval, on a warm executable, so the harvest's
        ``lower().compile()`` never lands inside a measurement and
        never doubles a cold compile.  ``lower`` only reads avals, so
        donated/deleted buffers in ``args`` are safe.  Returns whether
        the sample was recorded."""
        if not self._should_record(site, jitfn, sigs_before):
            return False
        self.note_measured(site, wall_s)
        if jitfn is not None and args:
            self.ensure_costs(site, jitfn, *args)
        return True

    def timed_jit_call(self, site: str, jitfn, *args):
        """Call ``jitfn(*args)``; on sampled iterations, fence the
        result (``block_until_ready``), record the wall time, and —
        only once the site runs steady — harvest its costs.  The
        non-sampled path is one counter increment."""
        if not self.should_sample(site):
            return jitfn(*args)
        import jax

        sigs0 = getattr(jitfn, "signatures_seen", None)
        t0 = time.perf_counter()
        out = jitfn(*args)
        jax.block_until_ready(out)
        self.note_window(site, time.perf_counter() - t0, jitfn, sigs0,
                         args)
        return out

    # -- export --------------------------------------------------------
    def _get_physics(self) -> tuple:
        """(device_kind, peak_flops, hbm_bytes_s), cached.  A device
        without a row in the tables (a CPU mesh) gets ``(kind, None,
        None)``: its rows keep their costs and times and carry no
        roofline."""
        if self._physics is None:
            dev = _device0()
            known = device_known(dev)
            self._physics = (
                dev.device_kind,
                device_peak_flops(dev) if known else None,
                device_hbm_bytes_s(dev) if known else None)
        return self._physics

    def _row(self, site: str, s: dict) -> dict:
        _, peak, bw = self._get_physics()
        with self._lock:
            samples = list(s["samples"])
            # dstpu-lint: disable-next-line=DSTPU006 -- hbm_bytes is the /profilez row KEY, not a registry metric
            flops, hbm_bytes = s["flops"], s["hbm_bytes"]
            calls, src = s["calls"], s["costs_src"]
        ms = statistics.median(samples) if samples else None
        # dstpu-lint: disable-next-line=DSTPU006 -- hbm_bytes is the /profilez row KEY, not a registry metric
        row = {"site": site, "flops": flops, "hbm_bytes": hbm_bytes,
               "measured_ms": None if ms is None else round(ms, 4),
               "calls": calls, "costs_src": src,
               "mfu": None, "bw_frac": None}
        if ms is None:
            row["verdict"] = "unmeasured"
        elif flops is None:
            row["verdict"] = "uninstrumented"
        elif peak is None:
            row["verdict"] = "unknown-device"
        else:
            rl = roofline(flops, hbm_bytes or 0.0, ms / 1000.0, peak, bw)
            # 9 decimals: small mfus (1e-4..1e-6) must stay recomputable
            # from the row's own fields to ~1e-3 relative
            row["mfu"] = round(rl["mfu"], 9)
            row["bw_frac"] = round(rl["bw_frac"], 9)
            row["verdict"] = rl["verdict"]
        return row

    def snapshot(self) -> dict:
        """The ``/profilez`` payload: device physics + one row per
        site, measured rows first (slowest first)."""
        with self._lock:
            sites = list(self._sites.items())
        # no site, no device probe: a process that never compiled (the
        # launcher parent, a crash dump before start-up) stays off the
        # backend
        kind, peak, bw = self._get_physics() if sites \
            else (None, None, None)
        rows = [self._row(site, s) for site, s in sites]
        rows.sort(key=lambda r: (r["measured_ms"] is None,
                                 -(r["measured_ms"] or 0.0)))
        return {"enabled": self.enabled(), "device": kind,
                "peak_flops": peak, "hbm_bytes_s": bw,
                "sample_every": self.sample_every(), "rows": rows}

    def verdicts(self) -> dict:
        """{site: verdict} over MEASURED rows only — the anomaly
        plane's drift-detector input."""
        snap = self.snapshot()
        return {r["site"]: r["verdict"] for r in snap["rows"]
                if r["measured_ms"] is not None
                and r["verdict"] not in ("unmeasured", "uninstrumented",
                                         "unknown-device")}

    def status(self) -> dict:
        """Compact ``/statusz`` ``attribution`` section."""
        snap = self.snapshot()
        measured = [r for r in snap["rows"] if r["measured_ms"] is not None]
        return {"enabled": snap["enabled"], "device": snap["device"],
                "sites": len(snap["rows"]), "measured": len(measured),
                "top": [{k: r[k] for k in
                         ("site", "verdict", "measured_ms", "mfu",
                          "bw_frac")} for r in measured[:5]]}

    def clear(self) -> None:
        """Drop every site (test isolation helper)."""
        with self._lock:
            self._sites.clear()
            self._tick_counts.clear()
            self._cost_failed.clear()
            self._first_skipped.clear()
            self._physics = None


_default: Optional[AttributionPlane] = None


def get_plane() -> AttributionPlane:
    global _default
    if _default is None:
        _default = AttributionPlane()
    return _default


# module-level conveniences over the default plane ----------------------
def enabled() -> bool:
    return get_plane().enabled()


def enable(on: Optional[bool] = True) -> None:
    get_plane().enable(on)


def should_sample(site: str) -> bool:
    return get_plane().should_sample(site)


def note_compiled(compiled, site: str, src: str = "aot") -> Optional[dict]:
    return get_plane().note_compiled(compiled, site, src=src)


def note_measured(site: str, wall_s: float, calls: int = 1) -> None:
    get_plane().note_measured(site, wall_s, calls=calls)


def note_window(site: str, wall_s: float, jitfn=None, sigs_before=None,
                args: tuple = ()) -> bool:
    return get_plane().note_window(site, wall_s, jitfn, sigs_before, args)


def ensure_costs(site: str, jitfn, *args) -> None:
    get_plane().ensure_costs(site, jitfn, *args)


def timed_jit_call(site: str, jitfn, *args):
    return get_plane().timed_jit_call(site, jitfn, *args)


def snapshot() -> dict:
    return get_plane().snapshot()


def status() -> dict:
    return get_plane().status()


_installed = False


def install() -> AttributionPlane:
    """Register the ``/statusz`` section; idempotent (telemetry
    import)."""
    global _installed
    plane = get_plane()
    if not _installed:
        from . import exporter as _exporter

        # resolve the singleton at CALL time: tests (and a future
        # reset) may swap the default plane after install
        _exporter.register_status_provider(
            "attribution", lambda: get_plane().status())
        _installed = True
    return plane


# -- on-demand jax.profiler capture -------------------------------------
_capture_lock = threading.Lock()


def capture_trace(duration_ms: int = 1000,
                  logdir: Optional[str] = None) -> Optional[str]:
    """Capture a ``jax.profiler`` device trace for ``duration_ms`` while
    the workload keeps running (serving ticks on other threads land in
    the capture).  Returns the trace directory (None when a capture is
    already in flight or jax is not up).  Wired to
    ``/profilez?capture_ms=N``; the result opens in TensorBoard /
    Perfetto."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    if not _capture_lock.acquire(blocking=False):
        return None          # one capture at a time
    try:
        if logdir is None:
            base = os.environ.get(_registry.METRICS_DIR_ENV) or "."
            logdir = os.path.join(base, "jax_profile")
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
        time.sleep(max(0, int(duration_ms)) / 1000.0)
        jax.profiler.stop_trace()
        logger.info(f"attribution: jax profiler trace captured to "
                    f"{logdir} ({duration_ms} ms)")
        return logdir
    except Exception as e:
        logger.warning(f"attribution: trace capture failed: {e!r}")
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        return None
    finally:
        _capture_lock.release()
