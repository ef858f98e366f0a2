"""``train-xl-adamw-z3-4chip`` (PR 50), the first cell on four chips:
GPT-2-XL under ZeRO stage 3 over ``fsdp 4`` with stock AdamW.  It ran on the
chip and printed ``correct``, and is NOT admitted: from an empty compile
cache its set-up alone is longer than a run may take (PERF.md section 7 row
1), so its entries wait in ``benchmark/pending_cells/`` and
``benchmark/harness/pending.py`` merges them.  Its configuration file is
the first cell's with the optimizer, the rows a chip and the trace's names
changed and nothing else; merged, the manifest passes its own check with
the configuration, the cell and the three ``collectives`` metrics behind
everything it has; ``BENCHMARK.json`` itself is as it was, every accepted
cell there with its values; the three new readers on a hand-made
``TraceSummary`` and registry, and ``None`` without the gauges; the
``--rehearse`` line ``correct`` on four forced host devices, from a staged
directory.  Nothing here pins a list's END.
"""
import json
import os
import subprocess
import sys

import pytest

from benchmark import trace_reduce
from benchmark.harness import manifest as M
from benchmark.harness import pending
from benchmark.layer_metrics import _program

ROOT = M.ROOT
CELL = "train-xl-adamw-z3-4chip"
CONFIG = "gpt2-xl-z3-adamw"
SOURCE = "https://huggingface.co/openai-community/gpt2-xl/blob/main/config.json"
# every accepted cell: (config, traffic, chips)
ACCEPTED = {
    "train-xl-z3-1chip": ("gpt2-xl-z3-8bit", "packed-1k", 1),
    "train-olmoe-z3-1chip": ("olmoe-1b-7b-z3-8bit", "packed-4k", 1),
    "train-mellum2-8k-1chip": ("mellum2-12b-a2.5b-z3-8bit", "packed-8k", 1),
    "train-trinity-mini-8k-1chip": ("trinity-mini-z3-8bit", "packed-8k-25k",
                                    1),
    "train-joyai-flash-8k-1chip": ("joyai-llm-flash-z3-8bit", "packed-8k-16k",
                                   1),
    "train-sdar-blockdiff-8k-1chip": ("sdar-30b-a3b-z3-8bit", "packed-8k-19k",
                                      1),
    "train-lfm2-hybrid-8k-1chip": ("lfm2-24b-a2b-z3-8bit", "packed-8k-16384",
                                   1),
    "train-qwen3next-gdn-8k-1chip": ("qwen3-next-80b-a3b-z3-8bit",
                                     "packed-8k-18992", 1),
}
OLDER = list(ACCEPTED)
JOINED = ["train_step_ms", "train_mfu_pct", "flash_share_pct",
          "flash_roofline", "device_idle_pct.train", "train_host_ms",
          "train_input_ms", "train_dispatch_ms", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_init_params_s", "peak_hbm_gib",
          "step_temp_hbm_gib"]
NOT_JOINED = ["expert_gemm_share_pct", "expert_gemm_roofline",
              "moe_load_imbalance", "flash_window_roofline",
              "flash_full_roofline", "flash_window_share_pct",
              "moe_held_pair_pct", "moe_expert_bias_spread",
              "mtp_loss_excess", "diffusion_masked_pct",
              "diffusion_prep_share_pct", "short_conv_share_pct",
              "short_conv_filter_roofline", "linear_attn_share_pct",
              "gated_delta_roofline"]
NEW = {
    "collective_named_exposed_pct": {"unit": "%", "source": "device_trace"},
    "collective_recv_gib_step": {"unit": "GiB", "source": "program_counter"},
    "zero_gather_passes": {"unit": "ratio", "source": "program_counter"}}


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def merged(manifest):
    return pending.merged(manifest, CELL, ROOT)


@pytest.fixture(scope="module")
def cell(merged):
    return M.load_cell(merged, CELL, ROOT)


def _entry(entries, name):
    return next(e for e in entries if e["name"] == name)


def _names(entries):
    return [e["name"] for e in entries]


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
def test_the_manifest_does_not_hold_the_cell_and_merged_it_does(manifest,
                                                                 merged):
    assert CELL not in _names(manifest["workloads"])
    assert CONFIG not in _names(manifest["configs"])
    assert not set(NEW) & set(_names(manifest["per_layer"]))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        n = len(manifest[key])
        assert _names(merged[key])[:n] == _names(manifest[key])
    assert _names(merged["workloads"])[len(manifest["workloads"]):] == [CELL]
    entry = _entry(merged["workloads"], CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "packed-1k",
                     "chips": 4, "why": entry["why"]}
    # a four-chip cell says what exists only across chips
    assert len(entry["why"]) <= 200 and "4 chips because" in entry["why"]
    conf = _entry(merged["configs"], CONFIG)
    assert conf == {"name": CONFIG, "source": SOURCE,
                    "file": f"benchmark/configs/{CONFIG}.json",
                    "reduced": [], "why": conf["why"]}
    assert len(conf["why"]) <= 200
    files = [c["file"] for c in merged["configs"]]
    assert len(set(files)) == len(files)
    e2e = {m["name"]: m for m in merged["end_to_end"]}
    assert e2e["train_tokens_per_s_chip"]["workloads"][-1] == CELL
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert merged["run_seconds"] == manifest["run_seconds"] == 50
    assert len(json.dumps(merged)) < 64 * 1024
    assert pending.entries(CELL, ROOT)["joins"] == \
        ["train_tokens_per_s_chip"] + JOINED
    # why it waits is said where the next builder looks first
    assert "371.7 s" in pending.entries(CELL, ROOT)["what"]


def test_four_chip_cells_keep_to_their_share(merged):
    """A quarter of the cells, rounded down, and one always; each says in
    its ``why`` what it measures that one chip has not."""
    four = [w for w in merged["workloads"] if w["chips"] == 4]
    assert _names(four) == [CELL]
    assert len(four) <= max(1, len(merged["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in merged["workloads"])


@pytest.mark.parametrize("name", OLDER)
def test_every_accepted_cell_is_still_there_with_its_values(manifest, name):
    config, traffic, chips = ACCEPTED[name]
    entry = _entry(manifest["workloads"], name)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        config, traffic, chips)
    assert _names(manifest["workloads"]).index(name) == OLDER.index(name)
    loaded = M.load_cell(manifest, name, ROOT)
    got = _names(loaded.per_layer)
    assert not set(NEW) & set(got)              # it reads none of the three
    assert {"train_step_ms", "peak_hbm_gib"} <= set(got)
    assert _names(loaded.end_to_end) == ["train_tokens_per_s_chip", "setup_s"]
    assert loaded.config["micro_per_device"] >= 2
    assert "collective" not in loaded.config.get("trace_names", {})


@pytest.mark.parametrize("name", JOINED)
def test_a_metric_the_cell_joins_lists_it_behind_the_older_cells(manifest,
                                                                 merged,
                                                                 name):
    cells = _entry(merged["per_layer"], name)["workloads"]
    assert cells == _entry(manifest["per_layer"], name)["workloads"] + [CELL]
    assert cells[:len(OLDER)] == OLDER


@pytest.mark.parametrize("name", NOT_JOINED)
def test_a_metric_with_nothing_to_read_here_does_not_list_the_cell(merged,
                                                                   name):
    assert CELL not in _entry(merged["per_layer"], name)["workloads"]


def test_the_three_new_metrics_and_what_the_cell_reports(merged, cell):
    manifest = merged
    names = _names(manifest["per_layer"])
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and at[-1] - at[0] == len(NEW) - 1
    assert max(names.index(n) for n in JOINED + NOT_JOINED) < at[0]
    for name, rest in NEW.items():
        assert _entry(manifest["per_layer"], name) == dict(
            name=name, better="lower", layer="collectives",
            moves="train_tokens_per_s_chip", workloads=[CELL], **rest)
        assert callable(cell.reader(name))
    assert _names(cell.per_layer) == JOINED + list(NEW)
    assert _names(cell.end_to_end) == ["train_tokens_per_s_chip", "setup_s"]
    # no share of a peak is added: peaks.json has the chip's published
    # 1,600 Gbit/s over four ports, not what a chip of a 2x2 may use
    assert not [n for n in NEW if "roofline" in n or "mfu" in n]


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
def test_the_configuration_is_the_first_cells_with_adamw_and_four_rows(
        manifest, cell):
    first = M.load_cell(manifest, "train-xl-z3-1chip", ROOT).config
    mine = cell.config
    differs = {k for k in set(first) | set(mine) if first.get(k) != mine.get(k)}
    assert differs == {"name", "stands_for", "assumed", "engine",
                       "micro_per_device", "trace_names"}
    assert mine["reduced"] == [] and mine["source"] == SOURCE
    # published width and depth
    assert (mine["n_embd"], mine["n_layer"], mine["n_head"],
            mine["n_positions"], mine["vocab_size"]) == (
        1600, 48, 25, 1024, 50257)
    assert mine["micro_per_device"] == 4 and first["micro_per_device"] == 2
    engine = dict(mine["engine"])
    assert engine.pop("optimizer") == {
        "type": "adamw", "params": {"lr": 1e-4, "weight_decay": 0.1}}
    theirs = dict(first["engine"])
    assert theirs.pop("optimizer")["type"] == "adamw8bit"
    assert engine == theirs == {"zero_optimization": {"stage": 3},
                                "gradient_clipping": 1.0,
                                "mesh": {"fsdp": -1}}
    assert (mine["driver"], mine["reference"]) == ("train", "gpt2")
    assert mine["reference_check"] == first["reference_check"]
    assert set(mine["assumed"]) - set(first["assumed"]) == {
        "trace_names", "setup"}
    # the reservation is the chip's own, not PR 23's rehearsal
    assert "13,698,610,688" in mine["assumed"]["memory"]
    assert {k: v for k, v in mine["assumed"].items()
            if k in first["assumed"] and k not in ("recipe", "memory")} == {
        k: v for k, v in first["assumed"].items()
        if k not in ("recipe", "memory")}
    assert set(mine["trace_names"]) == {"flash", "train_module", "collective"}
    assert mine["trace_names"]["train_module"] == \
        first["trace_names"]["train_module"]
    for said in ("four-chip", "ZeRO stage 3", "AdamW", "4 packed"):
        assert said in mine["stands_for"], said
    assert cell.traffic["seq_len"] == 1024 and cell.traffic["trace_seconds"] == 5


# what the v5e's trace calls an instruction, trailing number cut
# (benchmark/trace_reduce.py _op_name), and whether the pattern takes it
TRACE_NAMES = [
    ("all-gather", True), ("all-reduce", True), ("reduce-scatter", True),
    ("all-to-all", True), ("async-collective-start", True),
    ("async-collective-done", True), ("collective-permute-start", True),
    ("collective-permute-done", True), ("all-gather-start", True),
    ("all-reduce-done", True),
    ("fusion", False), ("shard_map", False), ("copy-start", False),
    ("slice-done", False), ("all-gather-fusion", False),
    ("convert_reduce_fusion", False), ("custom-call", False),
]


@pytest.mark.parametrize("name, taken", TRACE_NAMES,
                         ids=[n for n, _ in TRACE_NAMES])
def test_the_collective_pattern_takes_the_kinds_the_trace_names(cell, name,
                                                                taken):
    import re

    names = cell.config["trace_names"]
    assert bool(re.search(names["collective"], name)) is taken
    assert not re.search(names["flash"], name) or name == "shard_map"


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def _summary(op_self_s, busy_s=0.4):
    return trace_reduce.TraceSummary(
        n_devices=4, window_s=0.5, busy_s=busy_s, op_self_s=op_self_s,
        module_s={}, module_runs={}, gap_s_by_span={}, longest_gaps=[])


def _snapshot(**gauges):
    return {name: {"samples": [{"labels": labels, "value": value}
                               for labels, value in samples]}
            for name, samples in gauges.items()}


SITE = {"site": "engine.train_step"}
LEDGER = _snapshot(
    step_collective_recv_bytes=[
        (dict(SITE, op="all-gather"), 4.5 * 2**30),
        (dict(SITE, op="reduce-scatter"), 1.5 * 2**30),
        (dict(SITE, op="all-reduce"), 1.0 * 2**30),
        (dict(SITE, op="collective-permute"), 0.25 * 2**30),
        # another site's executable is not the train step's
        ({"site": "engine.eval_step", "op": "all-gather"}, 2.0 * 2**30)],
    zero_required_recv_bytes=[({"what": "gather"}, 2.25 * 2**30),
                              ({"what": "scatter"}, 4.5 * 2**30)])
WINDOW = {"step_ready_t": [0.0, 0.35, 0.7]}


def test_collective_named_exposed_pct_on_a_hand_made_trace(cell):
    read = cell.reader("collective_named_exposed_pct")
    trace = _summary({"fusion": 0.25, "shard_map": 0.04, "all-gather": 0.004,
                      "async-collective-start": 0.001,
                      "async-collective-done": 0.02, "all-reduce": 0.01,
                      "collective-permute-done": 0.005, "copy-done": 0.002})
    assert read({"cell": cell, "trace": trace}) == pytest.approx(
        100 * 0.04 / 0.4)
    # nothing that the pattern names ran: nothing to report
    assert read({"cell": cell, "trace": _summary({"fusion": 0.3})}) is None
    assert read({"cell": cell, "trace": None}) is None
    # a configuration that names no collective (every one-chip cell)
    one_chip = M.load_cell(M.load_manifest(ROOT), "train-xl-z3-1chip", ROOT)
    assert read({"cell": one_chip, "trace": trace}) is None
    # the flash readers find the kernels under the mesh's name
    assert cell.reader("flash_share_pct")(
        {"cell": cell, "trace": trace}) == pytest.approx(10.0)


def test_the_program_counter_readers_on_a_hand_made_registry(cell,
                                                             monkeypatch):
    monkeypatch.setattr(_program, "registry_snapshot", lambda: LEDGER)
    obs = dict(WINDOW, cell=cell, trace=None)
    assert cell.reader("collective_recv_gib_step")(obs) == pytest.approx(7.25)
    assert cell.reader("zero_gather_passes")(obs) == pytest.approx(2.0)


@pytest.mark.parametrize("snapshot", [
    {}, _snapshot(step_collective_recv_bytes=[]),
    _snapshot(hbm_exec_reserved_bytes=[(SITE, 13.0 * 2**30)]),
    _snapshot(step_collective_recv_bytes=[
        ({"site": "engine.eval_step", "op": "all-gather"}, 1.0)]),
], ids=["empty", "no sample", "a parent's gauges", "another site's"])
def test_a_program_without_the_gauges_reads_none(cell, monkeypatch, snapshot):
    monkeypatch.setattr(_program, "registry_snapshot", lambda: snapshot)
    obs = dict(WINDOW, cell=cell, trace=None)
    assert cell.reader("collective_recv_gib_step")(obs) is None
    assert cell.reader("zero_gather_passes")(obs) is None


def test_the_passes_need_both_gauges_and_a_window(cell, monkeypatch):
    moved = {k: v for k, v in LEDGER.items()
             if k == "step_collective_recv_bytes"}
    monkeypatch.setattr(_program, "registry_snapshot", lambda: moved)
    obs = dict(WINDOW, cell=cell, trace=None)
    assert cell.reader("collective_recv_gib_step")(obs) == pytest.approx(7.25)
    assert cell.reader("zero_gather_passes")(obs) is None     # no requirement
    monkeypatch.setattr(_program, "registry_snapshot", lambda: LEDGER)
    for reader in ("collective_recv_gib_step", "zero_gather_passes"):
        assert cell.reader(reader)({"cell": cell, "trace": None}) is None


def test_the_readers_read_what_the_program_books(cell, monkeypatch):
    """The gauges' names, labels and the site are the program's own: a
    registry the program's functions filled reads the same."""
    from deepspeed_tpu.telemetry import device_scopes
    from deepspeed_tpu.telemetry.registry import Registry

    registry = Registry()
    for op, recv in (("all-gather", 3.0 * 2**30), ("all-reduce", 2**30)):
        registry.gauge("step_collective_recv_bytes", "",
                       labelnames=("site", "op")).labels(
            site="engine.train_step", op=op).set(recv)
    registry.gauge("zero_required_recv_bytes", "", labelnames=("what",)
                   ).labels(what="gather").set(2.0 * 2**30)
    monkeypatch.setattr(_program, "registry_snapshot", registry.snapshot)
    obs = dict(WINDOW, cell=cell, trace=None)
    assert cell.reader("collective_recv_gib_step")(obs) == pytest.approx(4.0)
    assert cell.reader("zero_gather_passes")(obs) == pytest.approx(1.5)
    assert "step_collective_recv_bytes" in \
        device_scopes.record_collectives.__doc__


# ----------------------------------------------------------------------
# control flow, on four forced host devices
# ----------------------------------------------------------------------
def test_rehearsal_of_the_four_chip_cell_prints_a_correct_line(tmp_path):
    """From a staged directory (the merged manifest and links to the
    tree's code), as a pending cell runs on the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    staged = pending.stage(CELL, str(tmp_path / "staged"), ROOT)
    out = subprocess.run(
        [sys.executable, os.path.join(staged, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "0", "--rehearse"],
        cwd=staged, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == CELL and line["correct"] is True, line
    assert line["failed"] == 0 and line["compiles_in_window"] == 0
    assert "on 4 x cpu" in out.stderr and "'fsdp': 4" in out.stderr
    assert "global batch 16 x" in out.stderr           # 4 rows a chip
