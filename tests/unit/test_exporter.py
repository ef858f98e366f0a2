"""Live observability plane: HTTP exporter scrape endpoints (loopback,
port-0 auto-assign, absent by default), goodput phase attribution,
the shared ``memory_analysis`` normalizer + live-HBM gauges, launcher
flag plumbing, and the crash flight recorder (in-process dump/pretty +
a real SIGTERM subprocess leaving both forensics files behind)."""
import gc
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry import (exporter, flightrec, goodput,
                                     memory as tmemory, trace)
from deepspeed_tpu.telemetry.registry import Registry, get_registry


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode()


# ----------------------------------------------------------------------
# exporter
# ----------------------------------------------------------------------
def test_exporter_absent_by_default(monkeypatch):
    monkeypatch.delenv(exporter.TELEMETRY_PORT_ENV, raising=False)
    assert exporter.get_exporter() is None     # nothing armed by import
    assert exporter.maybe_start() is None      # and none without the env


def test_exporter_port0_scrape_endpoints():
    ex = exporter.TelemetryExporter(port=0).start()
    try:
        assert ex.port > 0                     # OS assigned a real port
        get_registry().counter("exporter_unit_total", "test").inc(3)

        code, body = _get(ex.port, "/metrics")
        assert code == 200
        assert "exporter_unit_total 3" in body
        # collector-backed gauges are refreshed by the scrape itself
        assert "goodput_ratio" in body
        assert "live_hbm_bytes" in body

        code, body = _get(ex.port, "/healthz")
        health = json.loads(body)
        assert code == 200 and health["ok"] is True
        assert "heartbeat_age_s" in health and "last_step_age_s" in health

        exporter.register_status_provider("unit", lambda: {"x": 1})
        with trace.span("unit/statusz"):
            pass
        code, body = _get(ex.port, "/statusz")
        status = json.loads(body)
        assert code == 200
        assert status["unit"] == {"x": 1}
        assert status["pid"] == os.getpid()
        assert "goodput" in status and "xla_recompiles_total" in status
        # the tracer's totals, through the same read API tests use
        assert status["spans"]["unit/statusz"]["count"] >= 1

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ex.port, "/nope")
        assert ei.value.code == 404
    finally:
        exporter.unregister_status_provider("unit")
        ex.stop()


def test_healthz_stale_returns_503(monkeypatch):
    ex = exporter.TelemetryExporter(port=0).start()
    try:
        monkeypatch.setenv(exporter.HEALTHZ_STALE_ENV, "1e-9")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ex.port, "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["ok"] is False
        monkeypatch.delenv(exporter.HEALTHZ_STALE_ENV)
        code, _ = _get(ex.port, "/healthz")
        assert code == 200
    finally:
        ex.stop()


def test_statusz_weak_provider_drops_dead_owner():
    class Owner:
        def section(self):
            return {"alive": True}

    o = Owner()
    exporter.register_status_owner("unit_weak", o, "section")
    assert exporter._collect_status()["unit_weak"] == {"alive": True}
    del o
    gc.collect()
    status = exporter._collect_status()
    assert "unit_weak" not in status           # owner not pinned alive


# ----------------------------------------------------------------------
# goodput phase attribution
# ----------------------------------------------------------------------
def _run_span(tracker, name, secs, inner=None):
    tracker.span_enter(name)
    if inner:
        _run_span(tracker, *inner)
    tracker.span_exit(name, secs, None)


def test_goodput_span_classification():
    t = goodput.GoodputTracker(registry=Registry())
    _run_span(t, "train/next-batch", 0.25)
    _run_span(t, "train/dispatch", 1.0)
    s = t.summary()
    assert s["data_wait_s"] == pytest.approx(0.25)
    assert s["compute_s"] == pytest.approx(1.0)
    assert 0 < s["goodput_ratio"] <= 1.0


def test_goodput_nested_exclusive_attribution():
    """A checkpoint span nested inside dispatch bills checkpoint, not
    compute; an unclassified middle span propagates its children up."""
    t = goodput.GoodputTracker(registry=Registry())
    # dispatch(1.0s) > unclassified(0.5s) > checkpoint(0.4s)
    t.span_enter("train/dispatch")
    t.span_enter("unclassified")
    t.span_enter("train/checkpoint")
    t.span_exit("train/checkpoint", 0.4, None)
    t.span_exit("unclassified", 0.5, None)
    t.span_exit("train/dispatch", 1.0, None)
    s = t.summary()
    assert s["checkpoint_s"] == pytest.approx(0.4)
    assert s["compute_s"] == pytest.approx(0.6)    # 1.0 - nested 0.4


def test_goodput_note_compile_subtracts_from_enclosing():
    t = goodput.GoodputTracker(registry=Registry())
    t.span_enter("train/dispatch")
    t.note_compile(0.7)
    t.span_exit("train/dispatch", 1.0, None)
    s = t.summary()
    assert s["recompile_s"] == pytest.approx(0.7)
    assert s["compute_s"] == pytest.approx(0.3)


def test_goodput_rides_real_spans():
    """The default tracker observes trace.span boundaries even with
    Chrome-trace recording OFF (the production configuration)."""
    assert not trace.enabled()
    before = goodput.summary()["compute_s"]
    with trace.span("serve/decode-tick"):
        time.sleep(0.01)
    after = goodput.summary()["compute_s"]
    assert after - before >= 0.008


def test_goodput_note_step_feeds_last_step_age():
    goodput.note_step("unit")
    age = goodput.last_step_age()
    assert age is not None and age < 5.0


# ----------------------------------------------------------------------
# memory accounting
# ----------------------------------------------------------------------
def test_memory_breakdown_is_the_one_normalizer():
    compiled = jax.jit(lambda x: x * 2 + 1).lower(
        jnp.zeros((64, 64), jnp.float32)).compile()
    bd = tmemory.memory_breakdown(compiled)
    assert bd is not None
    assert set(bd) - {"peak"} == {"args", "output", "alias", "temp",
                                  "generated_code", "total", "reserved"}
    assert bd["total"] == bd["args"] + bd["output"] + bd["temp"]
    assert bd["reserved"] == bd["total"] - bd["alias"]
    assert bd["args"] >= 64 * 64 * 4
    assert tmemory.peak_bytes(compiled) == bd["total"]


def test_record_compiled_publishes_site_gauges():
    reg = Registry()
    compiled = jax.jit(lambda x: x + 1).lower(
        jnp.zeros((8, 8), jnp.float32)).compile()
    bd = tmemory.record_compiled(compiled, site="unit.site", registry=reg)
    g = reg.gauge("hbm_exec_total_bytes", labelnames=("site",))
    assert g.labels(site="unit.site").value == bd["total"]
    text = reg.render_prometheus()
    assert 'hbm_exec_args_bytes{site="unit.site"}' in text


def test_sample_live_hbm_sees_pinned_arrays():
    reg = Registry()
    keep = jnp.ones((256, 256), jnp.float32)    # pinned during the sample
    out = tmemory.sample_live_hbm(registry=reg)
    assert out["live_hbm_bytes"] >= keep.nbytes
    assert out["live_hbm_arrays"] >= 1
    del keep


# ----------------------------------------------------------------------
# launcher plumbing
# ----------------------------------------------------------------------
def test_launcher_telemetry_port_flag(tmp_path):
    from deepspeed_tpu.launcher.runner import _build_parser

    args = _build_parser().parse_args(["train.py"])
    assert args.telemetry_port is None          # exporter off by default
    args = _build_parser().parse_args(["--telemetry_port", "0", "train.py"])
    assert args.telemetry_port == 0


def test_heartbeat_monitor_ages(tmp_path):
    from deepspeed_tpu.launcher.runner import HeartbeatMonitor

    f0, f1 = str(tmp_path / "hb_0"), str(tmp_path / "hb_1")
    mon = HeartbeatMonitor([f0, f1], timeout=60.0)
    assert mon.ages() == [None, None]           # nothing beat yet
    open(f0, "w").write("x")
    mon.stale()                                 # fold the observation in
    ages = mon.ages()
    assert ages[0] is not None and ages[0] < 5.0
    assert ages[1] is None


def test_heartbeat_last_beat_age(tmp_path, monkeypatch):
    from deepspeed_tpu.utils import heartbeat

    monkeypatch.setenv(heartbeat.ENV_VAR, str(tmp_path / "hb"))
    monkeypatch.setattr(heartbeat, "_last_beat", 0.0)
    assert heartbeat.beat()
    age = heartbeat.last_beat_age()
    assert age is not None and age < 5.0


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------
def test_flightrec_dump_and_pretty(tmp_path):
    fr = flightrec.maybe_install(str(tmp_path))
    assert fr is not None
    with trace.span("unit/flight", idx=1):
        time.sleep(0.002)
    get_registry().counter("flight_unit_total", "test").inc()
    fr._last_mark = 0.0                         # bypass the 1s throttle
    flightrec.mark("unit")
    path = flightrec.dump("unit-test")
    assert path == str(tmp_path / "flight_0.json")
    payload = json.load(open(path))
    assert payload["reason"] == "unit-test"
    assert any(s["name"] == "unit/flight" for s in payload["spans"])
    assert any("flight_unit_total" in d["deltas"]
               for d in payload["metric_deltas"])
    assert "flight_unit_total" in payload["metrics"]
    text = flightrec.pretty(path)
    assert "unit/flight" in text and "reason=unit-test" in text
    assert flightrec.newest_dump(str(tmp_path)) == path


def test_flightrec_excepthook_captures_traceback(tmp_path):
    fr = flightrec.maybe_install(str(tmp_path))
    try:
        raise RuntimeError("simulated crash")
    except RuntimeError as e:
        # what the installed sys.excepthook chain runs on an unhandled
        # exception (invoking sys.excepthook itself would re-raise into
        # pytest's machinery)
        path = fr.dump("exception", exc=e)
    payload = json.load(open(path))
    assert payload["exception"]["type"] == "RuntimeError"
    assert "simulated crash" in payload["exception"]["value"]
    assert any("simulated crash" in line
               for line in payload["exception"]["traceback"])
    assert "RuntimeError" in flightrec.pretty(path)


def test_flightrec_rearm_does_not_chain_to_itself(tmp_path):
    """disarm() leaves the signal hooks installed; arming again must not
    record our own handler as the previous one (SIGTERM then recursed
    until the stack ran out, whichever test armed first)."""
    import signal

    flightrec.maybe_install(str(tmp_path))
    flightrec.disarm()
    flightrec.maybe_install(str(tmp_path))
    assert signal.getsignal(signal.SIGTERM) is flightrec._on_signal
    assert flightrec._prev_handlers[signal.SIGTERM] \
        is not flightrec._on_signal


def test_flightrec_sigterm_subprocess_leaves_forensics(tmp_path):
    """The acceptance path: SIGTERM (the launcher killing a worker) must
    leave BOTH a final metrics snapshot and a flight dump that replays
    the last spans, and the exit status must still say 'killed'."""
    child = tmp_path / "child.py"
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    child.write_text(
        "import os, sys, time\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        f"sys.path.insert(0, {repo!r})\n"
        "import deepspeed_tpu\n"
        "from deepspeed_tpu.telemetry import registry, trace\n"
        "registry.counter('child_work_total').inc(7)\n"
        "with trace.span('child/work'):\n"
        "    time.sleep(0.005)\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n")
    env = dict(os.environ, DSTPU_METRICS_DIR=str(tmp_path),
               DSTPU_PROCESS_ID="0", JAX_PLATFORMS="cpu")
    env.pop("DSTPU_TELEMETRY_PORT", None)
    proc = subprocess.Popen([sys.executable, str(child)], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == -signal.SIGTERM                # exit semantics preserved
    flight = json.load(open(tmp_path / "flight_0.json"))
    assert flight["reason"] == "signal:SIGTERM"
    assert any(s["name"] == "child/work" for s in flight["spans"])
    metrics = json.load(open(tmp_path / "metrics_rank0.json"))
    assert metrics["child_work_total"]["samples"][0]["value"] == 7
