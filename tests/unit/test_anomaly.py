"""Fast host units for the perf-attribution plane: roofline math +
anomaly detectors (telemetry/attribution.py, telemetry/anomaly.py).

Everything here is hand-built series / tiny-jit work — no models, no
mesh — so the file stays cheap inside the tier-1 window.  The serving
e2e (CPU-mesh run publishing real attribution rows, induced alert
storms) lives z-sorted in ``test_zattribution.py``.
"""
import time

import numpy as np
import pytest

from deepspeed_tpu.telemetry import anomaly, attribution
from deepspeed_tpu.telemetry import registry as telemetry_registry
from deepspeed_tpu.telemetry.anomaly import (
    AcceptanceCollapseDetector, AnomalyEngine, AttributionDriftDetector,
    Detector, GoodputDropDetector, QueueRunawayDetector,
    RecompileStormDetector, Series, SloBurnDetector)


# ----------------------------------------------------------------------
# roofline math
# ----------------------------------------------------------------------
def test_roofline_compute_bound():
    # 1e12 flops in 1 s on a 2e12 peak = mfu 0.5; tiny bytes
    r = attribution.roofline(1e12, 1e9, 1.0, 2e12, 1e12,
                             overhead_frac=0.1)
    assert r["verdict"] == "compute-bound"
    assert r["mfu"] == pytest.approx(0.5)
    assert r["bw_frac"] == pytest.approx(1e9 / 1e12)


def test_roofline_hbm_bound():
    r = attribution.roofline(1e9, 8e11, 1.0, 2e12, 1e12,
                             overhead_frac=0.1)
    assert r["verdict"] == "hbm-bound"
    assert r["bw_frac"] == pytest.approx(0.8)


def test_roofline_overhead_bound():
    # neither roof within 10% of explaining the time
    r = attribution.roofline(1e9, 1e9, 1.0, 2e12, 1e12,
                             overhead_frac=0.1)
    assert r["verdict"] == "overhead-bound"
    assert max(r["mfu"], r["bw_frac"]) < 0.1


def test_roofline_tie_goes_to_hbm():
    # equal fractions: streaming is the actionable bound
    r = attribution.roofline(1e12, 5e11, 1.0, 2e12, 1e12,
                             overhead_frac=0.1)
    assert r["mfu"] == pytest.approx(r["bw_frac"])
    assert r["verdict"] == "hbm-bound"


def test_device_tables_shared_and_chips_only():
    # bench.py/flops_profiler/autotuner read THESE tables; they carry
    # chips only, and a device that is not in them is an error
    import jax

    from deepspeed_tpu.profiling import flops_profiler

    assert flops_profiler.PEAK_TFLOPS is attribution.PEAK_FLOPS
    for table in (attribution.PEAK_FLOPS, attribution.HBM_BYTES_S,
                  attribution.HBM_BYTES):
        assert "cpu" not in table
    cpu = jax.devices()[0]
    assert not attribution.device_known(cpu)
    for lookup in (attribution.device_peak_flops,
                   attribution.device_hbm_bytes_s,
                   attribution.device_hbm_bytes):
        with pytest.raises(ValueError, match="device_kind"):
            lookup(cpu)
        with pytest.raises(ValueError, match="device_kind"):
            lookup()            # device 0 of the CPU mesh


def test_unknown_device_rows_carry_no_roofline():
    plane = attribution.AttributionPlane()
    plane.note_costs("s.a", flops=2e9, hbm_bytes=4e8)
    plane.note_measured("s.a", 0.010)
    snap = plane.snapshot()
    assert snap["peak_flops"] is None and snap["hbm_bytes_s"] is None
    (row,) = snap["rows"]
    assert row["verdict"] == "unknown-device"
    assert row["mfu"] is None and row["bw_frac"] is None
    assert plane.verdicts() == {}


def test_decode_stream_floor_hand_math(nominal_cpu_physics):
    params = {"w": np.zeros((10, 10), np.float32)}        # 400 B
    slot_cache = {"k": np.zeros((4, 8), np.float32)}      # 128 B
    d = attribution.decode_stream_floor(params, slot_cache, n_slots=2,
                                        dev=None)
    assert d["weight_stream_bytes"] == 400
    assert d["kv_stream_bytes_per_tick"] == 256
    assert d["bw_floor_ms_per_tick"] == pytest.approx(
        1000.0 * (400 + 256) / d["hbm_bytes_s"])


def test_harvest_costs_real_compiled():
    import jax
    import jax.numpy as jnp

    c = jax.jit(lambda x: x @ x).lower(jnp.ones((32, 32))).compile()
    costs = attribution.harvest_costs(c)
    assert costs is not None
    assert costs["flops"] > 0
    assert costs["bytes_accessed"] > 0


# ----------------------------------------------------------------------
# attribution plane
# ----------------------------------------------------------------------
def test_plane_snapshot_self_consistent(nominal_cpu_physics):
    plane = attribution.AttributionPlane()
    plane.note_costs("s.a", flops=2e9, hbm_bytes=4e8)
    plane.note_measured("s.a", 0.010)        # 10 ms
    snap = plane.snapshot()
    (row,) = snap["rows"]
    assert row["site"] == "s.a"
    assert row["measured_ms"] == pytest.approx(10.0)
    # self-consistency: the row's fractions recompute from its own
    # fields and the snapshot's physics
    assert row["mfu"] == pytest.approx(
        row["flops"] / (row["measured_ms"] / 1e3 * snap["peak_flops"]),
        rel=1e-4)
    assert row["bw_frac"] == pytest.approx(
        row["hbm_bytes"] / (row["measured_ms"] / 1e3 * snap["hbm_bytes_s"]),
        rel=1e-4)
    assert row["verdict"] in ("compute-bound", "hbm-bound",
                              "overhead-bound")


def test_plane_unmeasured_and_uninstrumented_rows():
    plane = attribution.AttributionPlane()
    plane.note_costs("cost.only", flops=1.0, hbm_bytes=1.0)
    plane.note_measured("time.only", 0.001)
    by_site = {r["site"]: r for r in plane.snapshot()["rows"]}
    assert by_site["cost.only"]["verdict"] == "unmeasured"
    assert by_site["time.only"]["verdict"] == "uninstrumented"
    # measured rows only in the drift-detector input
    assert plane.verdicts() == {}


def test_plane_should_sample_cadence(monkeypatch):
    monkeypatch.setenv(attribution.SAMPLE_ENV, "4")
    plane = attribution.AttributionPlane()
    hits = [plane.should_sample("s") for _ in range(9)]
    assert hits == [True, False, False, False, True, False, False,
                    False, True]


def test_plane_enable_overrides_env(monkeypatch):
    monkeypatch.delenv(attribution.ATTRIBUTION_ENV, raising=False)
    plane = attribution.AttributionPlane()
    assert not plane.enabled()
    plane.enable(True)
    assert plane.enabled()
    plane.enable(None)
    monkeypatch.setenv(attribution.ATTRIBUTION_ENV, "1")
    assert plane.enabled()
    monkeypatch.setenv(attribution.ATTRIBUTION_ENV, "0")
    assert not plane.enabled()


def test_should_record_skips_first_without_watchdog_signal():
    plane = attribution.AttributionPlane()
    # watchdog disabled ⇒ no signatures_seen: the first sampled call
    # per site (the one that pays the XLA compile) is skipped, later
    # ones record — compile wall must never become measured_ms
    assert not plane._should_record("s", object(), None)
    assert plane._should_record("s", object(), None)

    # with signature visibility: record iff the call didn't compile
    class _Fn:
        signatures_seen = 3

    fn = _Fn()
    assert plane._should_record("t", fn, 3)
    fn.signatures_seen = 4
    assert not plane._should_record("t", fn, 3)


def test_note_window_records_and_harvests_after_steady():
    import jax
    import jax.numpy as jnp

    plane = attribution.AttributionPlane()
    fn = jax.jit(lambda x: x @ x)
    x = jnp.ones((16, 16))
    fn(x)         # warm
    # steady window (no sigs available → first skipped, second records
    # AND lazily harvests costs from the warm executable)
    assert not plane.note_window("w", 0.001, fn, None, (x,))
    assert plane.note_window("w", 0.001, fn, None, (x,))
    (row,) = plane.snapshot()["rows"]
    assert row["flops"] > 0 and row["measured_ms"] is not None
    assert row["costs_src"] == "lazy"


def test_plane_median_washes_out_one_outlier():
    plane = attribution.AttributionPlane()
    plane.note_costs("s", flops=1e9, hbm_bytes=1e9)
    plane.note_measured("s", 2.0)            # one 2 s outlier
    for _ in range(8):
        plane.note_measured("s", 0.004)
    (row,) = plane.snapshot()["rows"]
    assert row["measured_ms"] == pytest.approx(4.0)


# ----------------------------------------------------------------------
# series
# ----------------------------------------------------------------------
def test_series_delta_window():
    s = Series()
    for t, v in [(0, 0), (10, 5), (20, 9), (30, 12)]:
        s.add(t, v)
    assert s.delta(15, now=30) == pytest.approx(3)     # 12 - 9
    assert s.delta(100, now=30) == pytest.approx(12)   # 12 - 0
    assert Series().delta(10) is None
    s1 = Series()
    s1.add(0, 1)
    assert s1.delta(10, now=0) is None                 # one sample


def test_series_increasing_run():
    s = Series()
    for t, v in enumerate([1, 2, 3, 4]):
        s.add(t, v)
    assert s.increasing_run(3)
    s.add(4, 4)          # plateau breaks strictness
    assert not s.increasing_run(3)
    assert not Series().increasing_run(1)


# ----------------------------------------------------------------------
# detector hysteresis
# ----------------------------------------------------------------------
class _Scripted(Detector):
    """check() replays a scripted list of violations/None."""

    name = "scripted"

    def __init__(self, script, fire_after=1, clear_after=3):
        super().__init__()
        self.fire_after = fire_after
        self.clear_after = clear_after
        self._script = list(script)

    def check(self, engine, now):
        return self._script.pop(0) if self._script else None


class _NoSampleEngine(AnomalyEngine):
    """Evaluation-only engine: series are hand-built by the test."""

    def _sample(self, now):
        pass


def _drain(det, engine, evals):
    out = []
    for i in range(evals):
        out.extend(det.step(engine, float(i)))
    return out


def test_hysteresis_fire_after_and_clear_after():
    bad = {"value": 1.0, "threshold": 0.5}
    det = _Scripted([bad, bad, bad, None, None, None, None],
                    fire_after=2, clear_after=3)
    eng = _NoSampleEngine(detectors=[])
    evs = _drain(det, eng, 7)
    # fires on the 2nd bad eval, clears on the 3rd good one — exactly
    # one transition each; the 3rd bad eval emits nothing
    assert [(e["state"]) for e in evs] == ["firing", "cleared"]
    assert evs[0]["t"] == 1.0 and evs[1]["t"] == 5.0


def test_hysteresis_flap_suppression():
    bad = {"value": 1.0, "threshold": 0.5}
    # bad/good alternation with clear_after=3 never clears (and never
    # re-fires): one firing event total
    det = _Scripted([bad, None, bad, None, bad, None], fire_after=1,
                    clear_after=3)
    eng = _NoSampleEngine(detectors=[])
    evs = _drain(det, eng, 6)
    assert [e["state"] for e in evs] == ["firing"]
    assert det.firing


def test_recompile_storm_fires_exactly_once():
    det = RecompileStormDetector(n=3, window_s=60)
    eng = _NoSampleEngine(detectors=[det])
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(10.0, 5.0)        # 5 recompiles in 10 s
    evs = eng.observe(now=10.0, force=True)
    evs += eng.observe(now=11.0, force=True)       # still storming
    fires = [e for e in evs if e["state"] == "firing"]
    assert len(fires) == 1
    assert fires[0]["rule"] == "recompile_storm"
    assert fires[0]["value"] == pytest.approx(5.0)
    assert eng.active().get("recompile_storm") is not None


def test_recompile_storm_clears_when_window_quiets():
    det = RecompileStormDetector(n=3, window_s=20)
    eng = _NoSampleEngine(detectors=[det])
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(5.0, 5.0)
    eng.observe(now=5.0, force=True)
    assert det.firing
    # the storm samples age out of the window; flat counter since
    for t in (30.0, 31.0, 32.0):
        eng.series["recompiles"].add(t, 5.0)
        eng.observe(now=t, force=True)
    assert not det.firing
    assert eng.active() == {}


def test_burn_rate_fixture_math():
    # hand-computed: 6 met + 2 violations = 0.25 burn over 8 events
    rate, events = SloBurnDetector.burn_rate(6.0, 2.0)
    assert rate == pytest.approx(0.25)
    assert events == 8.0
    assert SloBurnDetector.burn_rate(None, 2.0) is None
    assert SloBurnDetector.burn_rate(0.0, 0.0) == (0.0, 0.0)


def test_slo_burn_respects_min_events():
    det = SloBurnDetector(burn=0.5, window_s=60, min_events=8)
    eng = _NoSampleEngine(detectors=[det])
    # 3 retirements, all violations: 100% burn but below min_events
    eng.series["slo_met"].add(0.0, 0.0)
    eng.series["slo_met"].add(10.0, 0.0)
    eng.series["slo_violations"].add(0.0, 0.0)
    eng.series["slo_violations"].add(10.0, 3.0)
    assert eng.observe(now=10.0, force=True) == []
    # 10 retirements, 6 violations: 60% burn over enough events
    eng.series["slo_met"].add(20.0, 4.0)
    eng.series["slo_violations"].add(20.0, 6.0)
    evs = eng.observe(now=20.0, force=True)
    assert [e["rule"] for e in evs] == ["slo_burn"]
    assert evs[0]["value"] == pytest.approx(0.6)


def test_queue_runaway_needs_run_and_floor():
    det = QueueRunawayDetector(run=3, min_depth=10)
    eng = _NoSampleEngine(detectors=[det])
    for t, v in enumerate([1, 2, 3, 4]):       # increasing but shallow
        eng.series["queue_depth"].add(float(t), float(v))
    assert eng.observe(now=3.0, force=True) == []
    for t, v in enumerate([11, 14, 18, 25], start=4):
        eng.series["queue_depth"].add(float(t), float(v))
    evs = eng.observe(now=7.0, force=True)
    assert [e["rule"] for e in evs] == ["queue_runaway"]


def test_acceptance_collapse_requires_moving_verify_ticks():
    det = AcceptanceCollapseDetector(min_rate=0.2, window_s=60)
    det.fire_after = 1
    eng = _NoSampleEngine(detectors=[det])
    eng.series["acceptance_rate"].add(0.0, 0.05)
    # no verify ticks moving: speculation is idle, not collapsing
    assert eng.observe(now=0.0, force=True) == []
    eng.series["verify_ticks"].add(0.0, 0.0)
    eng.series["verify_ticks"].add(10.0, 12.0)
    eng.series["acceptance_rate"].add(10.0, 0.05)
    evs = eng.observe(now=10.0, force=True)
    assert [e["rule"] for e in evs] == ["acceptance_collapse"]


def test_goodput_drop_waits_for_warmup():
    det = GoodputDropDetector(min_ratio=0.5, min_wall_s=100)
    det.fire_after = 1
    eng = _NoSampleEngine(detectors=[det])
    eng.series["goodput_ratio"].add(0.0, 0.1)
    eng.series["goodput_wall"].add(0.0, 10.0)      # still warming up
    assert eng.observe(now=0.0, force=True) == []
    eng.series["goodput_ratio"].add(1.0, 0.1)
    eng.series["goodput_wall"].add(1.0, 200.0)
    evs = eng.observe(now=1.0, force=True)
    assert [e["rule"] for e in evs] == ["goodput_drop"]


def test_attribution_drift_pulses_per_flip(monkeypatch, nominal_cpu_physics):
    plane = attribution.AttributionPlane()
    monkeypatch.setattr(attribution, "_default", plane)
    plane.note_costs("s.x", flops=1e15, hbm_bytes=1.0)
    plane.note_measured("s.x", 0.001)          # huge mfu: compute-bound
    det = AttributionDriftDetector()
    eng = _NoSampleEngine(detectors=[det])
    assert eng.observe(now=0.0, force=True) == []     # baseline learn
    # flops drop 6 orders: the verdict flips to overhead-bound
    plane.note_costs("s.x", flops=1e6, hbm_bytes=1.0)
    plane.note_measured("s.x", 0.001)
    evs = eng.observe(now=1.0, force=True)
    assert len(evs) == 1
    assert evs[0]["rule"] == "attribution_drift"
    assert evs[0]["detail"]["site"] == "s.x"
    assert evs[0]["detail"]["from"] == "compute-bound"
    assert evs[0]["detail"]["to"] == "overhead-bound"
    # pulse semantics: never active, no repeat without another flip
    assert eng.active() == {}
    assert eng.observe(now=2.0, force=True) == []


# ----------------------------------------------------------------------
# engine dispatch: metrics, ring, subscribers
# ----------------------------------------------------------------------
def test_dispatch_counters_gauge_ring_and_subscribers():
    det = RecompileStormDetector(n=2, window_s=60)
    det.clear_after = 1
    eng = _NoSampleEngine(detectors=[det])
    reg = telemetry_registry.get_registry()
    c0 = reg.counter("alerts_total", labelnames=("rule",)).labels(
        rule="recompile_storm").value
    got = []
    remove = eng.subscribe(got.append)
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(1.0, 4.0)
    eng.observe(now=1.0, force=True)
    assert reg.counter("alerts_total", labelnames=("rule",)).labels(
        rule="recompile_storm").value == c0 + 1
    assert reg.gauge("alerts_firing", labelnames=("rule",)).labels(
        rule="recompile_storm").value == 1.0
    assert [e["state"] for e in got] == ["firing"]
    # quiet window → cleared; unsubscribed callback sees nothing more
    remove()
    for t in (100.0, 101.0):
        eng.series["recompiles"].add(t, 4.0)
        eng.observe(now=t, force=True)
    assert reg.gauge("alerts_firing", labelnames=("rule",)).labels(
        rule="recompile_storm").value == 0.0
    assert len(got) == 1
    states = [e["state"] for e in eng.recent()]
    assert states == ["firing", "cleared"]
    st = eng.status()
    assert "recompile_storm" in st["rules"]
    assert st["rules"]["recompile_storm"]["n"] == 2


def test_broken_subscriber_and_detector_isolated():
    class _Boom(Detector):
        name = "boom"

        def check(self, engine, now):
            raise RuntimeError("detector bug")

    det = RecompileStormDetector(n=1, window_s=60)
    eng = _NoSampleEngine(detectors=[_Boom(), det])
    eng.subscribe(lambda ev: 1 / 0)
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(1.0, 3.0)
    evs = eng.observe(now=1.0, force=True)   # neither failure propagates
    assert [e["rule"] for e in evs] == ["recompile_storm"]


def test_observe_throttle_and_real_sample_smoke():
    eng = AnomalyEngine()        # the REAL sampler against the registry
    evs = eng.observe(force=True)
    assert isinstance(evs, list)
    # throttled second call (within 1 s) is a no-op
    assert eng.observe() == []
    assert len(eng.series["recompiles"]) >= 1


def test_env_knob_overrides(monkeypatch):
    monkeypatch.setenv("DSTPU_ALERT_RECOMPILE_N", "7")
    monkeypatch.setenv("DSTPU_ALERT_SLO_BURN", "0.9")
    assert RecompileStormDetector().n == 7
    assert SloBurnDetector().burn == pytest.approx(0.9)
    monkeypatch.setenv("DSTPU_ALERT_RECOMPILE_N", "garbage")
    assert RecompileStormDetector().n == 3       # bad value → default


def test_metric_total_never_creates():
    name = "zz_probe_nonexistent_total"
    assert anomaly._metric_total(name) is None
    reg = telemetry_registry.get_registry()
    with reg._lock:
        assert name not in reg._metrics
