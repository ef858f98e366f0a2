"""The six per-layer metrics read from inside the program (PR 24): the
``program_span`` readers over the tracer's ring, the ``program_counter``
readers over the compile-seconds counter.  Host-only; durations are real
(sleeps), the counter snapshot is hand-made."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import manifest as M
from benchmark.layer_metrics import _program
from deepspeed_tpu.telemetry import trace

ROOT = M.ROOT
CELL = "train-xl-z3-1chip"
NEW = ["train_host_ms", "train_input_ms", "train_dispatch_ms",
       "setup_trace_lower_s", "setup_backend_compile_s",
       "setup_init_params_s"]


@pytest.fixture(scope="module")
def cell():
    return M.load_cell(M.load_manifest(ROOT), CELL, ROOT)


@pytest.fixture
def ring():
    """Six hand-nested train steps; the window opens when the second one
    is ready, so the steps that START in it are numbers 2 to 5."""
    trace.clear()
    with trace.span("init/params"):
        time.sleep(0.004)
    ready = []
    for step in range(6):
        with trace.span("train/step", step=step):
            with trace.span("train/next-batch", step=step):
                time.sleep(0.001)
            with trace.span("train/device-put", step=step):
                time.sleep(0.001)
            with trace.span("train/dispatch", step=step):
                time.sleep(0.003 if step != 3 else 0.03)     # one outlier
            time.sleep(0.001)                                 # self time
        ready.append(time.perf_counter())
    yield {"step_ready_t": ready[1:]}
    trace.clear()


SNAPSHOT = {_program.COMPILE_SECONDS: {"kind": "counter", "samples": [
    {"labels": {"phase": "trace", "span": "train/dispatch"}, "value": 30.0},
    {"labels": {"phase": "trace", "span": "none"}, "value": 2.0},
    {"labels": {"phase": "lower", "span": "train/dispatch"}, "value": 6.0},
    {"labels": {"phase": "backend", "span": "train/dispatch"}, "value": 1.5},
    {"labels": {"phase": "backend", "span": "init/params"}, "value": 0.5},
    {"labels": {"phase": "trace", "span": "init/params"}, "value": 12.0},
    {"labels": {"phase": "fetch", "span": "train/dispatch"}, "value": 4.0},
]}}


def test_the_manifest_names_the_six_and_only_appends(cell):
    names = [m["name"] for m in cell.per_layer]
    assert names[-6:] == NEW
    assert names[:5] == ["train_step_ms", "train_mfu_pct", "flash_share_pct",
                         "flash_roofline", "device_idle_pct.train"]
    for m in cell.per_layer[-6:]:
        assert m["layer"] == "trainer" and m["better"] == "lower"
        assert m["source"] in ("program_span", "program_counter")
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                              else "train_tokens_per_s_chip")


def test_span_readers_on_a_hand_nested_ring(cell, ring):
    steps = [s for s in trace.spans(prefix="train/step")
             if s.args["step"] >= 2]
    assert len(steps) == 4
    host = cell.reader("train_host_ms")(ring)
    dispatch = cell.reader("train_dispatch_ms")(ring)
    inputs = cell.reader("train_input_ms")(ring)
    durs = sorted(s.dur_s for s in steps)
    assert host == pytest.approx((durs[1] + durs[2]) / 2 * 1e3)
    assert 2.0 <= inputs < dispatch < host < 20.0    # the outlier is no median
    assert inputs + dispatch <= host
    assert cell.reader("setup_init_params_s")(ring) == pytest.approx(
        trace.totals()["init/params"]["seconds"])
    assert cell.reader("setup_init_params_s")(ring) >= 0.004


def test_counter_readers_on_a_hand_made_snapshot(cell, ring, monkeypatch):
    monkeypatch.setattr(_program, "registry_snapshot", lambda: SNAPSHOT)
    # what init/params compiled is in setup_init_params_s, not here
    assert cell.reader("setup_trace_lower_s")(ring) == pytest.approx(38.0)
    assert cell.reader("setup_backend_compile_s")(ring) == pytest.approx(5.5)
    monkeypatch.setattr(_program, "registry_snapshot", lambda: {})
    assert cell.reader("setup_trace_lower_s")(ring) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_none_without_step_ready_t(cell, ring, name):
    assert cell.reader(name)({}) is None
    assert cell.reader(name)({"step_ready_t": [1.0]}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_none_on_a_program_without_the_ring(
        cell, ring, monkeypatch, name):
    """The parent commit's tracer keeps nothing: the metric is left out
    of the line, the traced run does not fail."""
    monkeypatch.setattr(_program, "tracer", lambda: None)
    assert cell.reader(name)(ring) is None


def test_rehearsal_of_the_traced_train_cell_passes_with_the_new_entries():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 11), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0
