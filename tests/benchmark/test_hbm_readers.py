"""``peak_hbm_gib`` and ``step_temp_hbm_gib`` (PR 35): two per-layer
metrics read from the gauges the program sets when it makes the train
step's executable (``hbm_exec_reserved_bytes`` / ``hbm_exec_temp_bytes``
``{site="engine.train_step"}``), reported in every cell.  Host-only.

They are the first metrics appended to every cell's list at once, which
three older tests pin to their day (``test_program_readers.py``: the XL
cell's list ends with PR 24's six; ``test_mellum2_cell.py``,
``test_trinity_cell.py``: a cell's list is its predecessors' plus its
own).  ``tests/conftest.py`` marks those three as expected to fail,
strictly, and this file holds their versions over what a cell added when
it came and what every cell gained since, which the next such metric
needs no copy of.
"""
import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import manifest as M
from benchmark.layer_metrics import _program, peak_hbm_gib, step_temp_hbm_gib

ROOT = M.ROOT
CELLS = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip"]
NEW = ["peak_hbm_gib", "step_temp_hbm_gib"]
GAUGES = {"peak_hbm_gib": "hbm_exec_reserved_bytes",
          "step_temp_hbm_gib": "hbm_exec_temp_bytes"}
# what each cell added to its own list when it came, in order
SINCE = {
    "train-xl-z3-1chip": [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s", "setup_backend_compile_s",
        "setup_init_params_s"],
    "train-olmoe-z3-1chip": ["expert_gemm_share_pct", "expert_gemm_roofline",
                             "moe_load_imbalance"],
    "train-mellum2-8k-1chip": ["flash_window_roofline", "flash_full_roofline",
                               "flash_window_share_pct", "moe_held_pair_pct"],
    "train-trinity-mini-8k-1chip": ["moe_expert_bias_spread"],
}
# what every cell gained at once, after the last cell came
EVERY_CELL = NEW


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


OBS = {"step_ready_t": [1.0, 2.0]}       # a measured window


def _snapshot(**gauges):
    """A registry snapshot with ``name=(site, bytes)`` gauges."""
    return {name: {"kind": "gauge", "samples": [
        {"labels": {"site": site}, "value": float(value)}]}
        for name, (site, value) in gauges.items()}


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_loads_the_two_entries(manifest, cell_name):
    cell = M.load_cell(manifest, cell_name, ROOT)
    mine = [m for m in cell.per_layer if m["name"] in NEW]
    assert [m["name"] for m in mine] == NEW
    for m in mine:
        assert m == {"name": m["name"], "unit": "GiB", "better": "lower",
                     "source": "program_counter", "layer": "trainer",
                     "moves": "train_tokens_per_s_chip", "workloads": CELLS}
        assert callable(cell.reader(m["name"]))
    assert cell.reader("peak_hbm_gib").__module__.endswith("peak_hbm_gib")
    assert cell.reader("step_temp_hbm_gib").__module__.endswith(
        "step_temp_hbm_gib")


def test_the_two_entries_are_appended_and_nothing_else_moved(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-2:] == NEW
    assert names[:-2] == [n for cell in CELLS for n in SINCE[cell]]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert all(m["moves"] in e2e for m in manifest["per_layer"])
    assert [w["name"] for w in manifest["workloads"]] == CELLS


def test_no_metric_lost_a_cell_and_each_cell_kept_its_own(manifest):
    """A cell's list is what the cells before it added, what it added,
    and what every cell gained since: the version of the two older tests
    of this name that a metric reported in every cell does not break."""
    so_far = []
    for cell_name in CELLS:
        so_far = so_far + SINCE[cell_name]
        names = [m["name"] for m in
                 M.load_cell(manifest, cell_name, ROOT).per_layer]
        assert names == so_far + EVERY_CELL, cell_name
    assert len(SINCE["train-xl-z3-1chip"]) == 11


def test_the_xl_cell_names_pr24s_six_and_only_appends(manifest):
    cell = M.load_cell(manifest, "train-xl-z3-1chip", ROOT)
    names = [m["name"] for m in cell.per_layer
             if m["name"] not in EVERY_CELL]
    six = ["train_host_ms", "train_input_ms", "train_dispatch_ms",
           "setup_trace_lower_s", "setup_backend_compile_s",
           "setup_init_params_s"]
    assert names[-6:] == six
    assert names[:5] == ["train_step_ms", "train_mfu_pct", "flash_share_pct",
                         "flash_roofline", "device_idle_pct.train"]
    for m in cell.per_layer:
        if m["name"] in six:
            assert m["layer"] == "trainer" and m["better"] == "lower"
            assert m["source"] in ("program_span", "program_counter")
            assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                                  else "train_tokens_per_s_chip")


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_none_without_the_gauge(manifest, monkeypatch, name):
    """The parent commit's program has no such gauge: the metric is left
    out of the line, nothing raises."""
    read = M.load_cell(manifest, CELLS[0], ROOT).reader(name)
    monkeypatch.setattr(_program, "registry_snapshot", lambda: {})
    assert read(OBS) is None
    # the parent's gauges, which it sets only from record_memory_profile
    monkeypatch.setattr(_program, "registry_snapshot", lambda: _snapshot(
        hbm_exec_total_bytes=("engine.train_step", 3 * 2**30)))
    assert read(OBS) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_the_train_steps_gauge_in_gib(manifest, monkeypatch,
                                                     name):
    read = M.load_cell(manifest, CELLS[-1], ROOT).reader(name)
    monkeypatch.setattr(_program, "registry_snapshot", lambda: _snapshot(**{
        GAUGES[name]: ("engine.train_step", 14783522304)}))
    assert read(OBS) == pytest.approx(14783522304 / 2**30)
    assert 13.76 < read(OBS) < 13.78
    # no measured window (the harness probing what a reader does with
    # nothing): nothing, whatever an earlier engine of the process booked
    assert read({"cell": None, "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_takes_no_other_sites_executable(manifest, monkeypatch,
                                                  name):
    read = M.load_cell(manifest, CELLS[1], ROOT).reader(name)
    monkeypatch.setattr(_program, "registry_snapshot", lambda: _snapshot(**{
        GAUGES[name]: ("engine.eval_step", 2**30)}))
    assert read(OBS) is None


def test_a_program_without_the_registry_gives_none(monkeypatch):
    def gone():
        raise ImportError("no module named deepspeed_tpu.telemetry")

    monkeypatch.setattr(_program, "registry_snapshot", gone)
    assert peak_hbm_gib.read(OBS) is None
    assert step_temp_hbm_gib.read(OBS) is None


def test_the_readers_over_what_the_program_books(monkeypatch):
    """End to end in one process: a staged site named as the engine's
    train step makes an executable, the watchdog books it, the readers
    divide by 2**30."""
    from deepspeed_tpu.telemetry import memory, recompile
    from deepspeed_tpu.telemetry.registry import Registry

    reg = Registry()
    step = recompile.RecompileWatchdog(registry=reg).watch(
        jax.jit(lambda s, x: (s + x.sum(), x.mean()), donate_argnums=(0,)),
        "engine.train_step", staged=True)
    step(jnp.zeros((256, 256)), jnp.ones((64,)))
    monkeypatch.setattr(_program, "registry_snapshot", reg.snapshot)
    bd = memory.memory_breakdown(step.compiled)
    assert bd["alias"] >= 256 * 256 * 4
    assert peak_hbm_gib.read(OBS) == pytest.approx(
        (bd["args"] + bd["output"] - bd["alias"] + bd["temp"]) / 2**30)
    assert step_temp_hbm_gib.read(OBS) == pytest.approx(bd["temp"] / 2**30)
    assert peak_hbm_gib.read(OBS) < bd["total"] / 2**30   # a state once
