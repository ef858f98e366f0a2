"""The benchmark's own tests: host-only, no accelerator, nothing timed.

They hold the harness to what later PRs rely on: every name in
``BENCHMARK.json`` resolves to a file, a new cell is data, the same seed is
the same work, and the arithmetic (percentiles, censoring, FLOPs, the trace
reduction) is what ``PERF.md`` says it is.
"""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops, loadgen, trace_reduce
from benchmark.harness import manifest as M
from benchmark.harness import runner

ROOT = M.ROOT


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def with_pending(manifest):
    """The manifest with the entries of ``benchmark/pending/`` merged in:
    cells whose files are here and proven to run, but which are not
    admitted yet (PERF.md section 7)."""
    m = copy.deepcopy(manifest)
    folder = os.path.join(ROOT, "benchmark", "pending")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            p = json.load(f)
        m["configs"].append(p["config"])
        m["workloads"].append(p["workload"])
        m["end_to_end"].insert(0, p["end_to_end"])
        m["per_layer"].extend(p["per_layer"])
    M.check_manifest(m)
    return m


# ----------------------------------------------------------------------
# the manifest and the files it names
# ----------------------------------------------------------------------
def test_manifest_has_exactly_the_contract_keys(manifest):
    assert sorted(manifest) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert manifest["command"][-1] == "benchmark/run.py"
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for m in manifest["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in manifest["workloads"] + manifest["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("which", ["manifest", "with_pending"])
def test_every_cell_config_mix_reader_driver_and_reference_loads(which, request):
    manifest = request.getfixturevalue(which)
    used = set()
    for w in manifest["workloads"]:
        cell = M.load_cell(manifest, w["name"], ROOT)
        used.add(cell.config_name)
        assert cell.config["reduced"] == next(
            c["reduced"] for c in manifest["configs"]
            if c["name"] == cell.config_name)
        assert [m["name"] for m in cell.end_to_end].count("setup_s") == 1
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        assert callable(cell.driver().run)
        assert callable(cell.reference().logits)
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        # depth and widths are the source's; only the context length is cut
        assert set(c["reduced"]) <= {"n_positions"}


@pytest.mark.parametrize("bad", ["has space", "a/b", "", "x" * 65, "-lead",
                                 "naïve"])
def test_names_outside_the_alphabet_are_refused(manifest, bad):
    m = copy.deepcopy(manifest)
    m["per_layer"][0]["name"] = bad
    with pytest.raises(M.ManifestError):
        M.check_manifest(m)


@pytest.mark.parametrize("unit", ["tokens per second", "", "x" * 17, "µs"])
def test_bad_units_are_refused(manifest, unit):
    m = copy.deepcopy(manifest)
    m["end_to_end"][0]["unit"] = unit
    with pytest.raises(M.ManifestError):
        M.check_manifest(m)


def test_a_name_without_a_file_is_refused(manifest, tmp_path):
    m = copy.deepcopy(manifest)
    m["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(M.ManifestError, match="no file"):
        M.load_cell(m, m["workloads"][0]["name"], ROOT)
    m = copy.deepcopy(manifest)
    cell = m["workloads"][0]["name"]
    m["per_layer"].append({"name": "no_such_reader", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "trainer", "workloads": [cell],
                           "moves": "setup_s"})
    with pytest.raises(M.ManifestError, match="no layer_metrics file"):
        M.load_cell(m, cell, ROOT)


def test_a_layer_metric_must_move_a_metric_its_cells_report(with_pending):
    m = copy.deepcopy(with_pending)
    serve = next(w["name"] for w in m["workloads"] if "serve" in w["name"])
    m["per_layer"][0]["workloads"] = [serve]
    m["per_layer"][0]["moves"] = "train_tokens_per_s_chip"
    with pytest.raises(M.ManifestError, match="is not"):
        M.check_manifest(m)


def test_a_config_mix_cell_and_layer_metric_are_added_as_files_alone(
        manifest, tmp_path):
    """A later PR adds files and entries and edits nothing that is there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in _files(root)}
    base = M.load_cell(manifest, manifest["workloads"][0]["name"], ROOT)
    cfg = dict(base.config, name="dummy-config", micro_per_device=1)
    mix = dict(base.traffic, seq_len=512)
    _write(root, "benchmark/configs/dummy-config.json", json.dumps(cfg))
    _write(root, "benchmark/traffic/dummy-mix.json", json.dumps(mix))
    _write(root, "benchmark/layer_metrics/dummy_count.py",
           "def read(obs):\n    return obs.get('steps')\n")
    m = copy.deepcopy(manifest)
    m["configs"].append({"name": "dummy-config", "source": "https://x.test",
                         "file": "benchmark/configs/dummy-config.json",
                         "reduced": [], "why": "a dummy"})
    m["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                           "traffic": "dummy-mix", "chips": 1, "why": "dummy"})
    m["end_to_end"][0]["workloads"].append("dummy-cell")
    m["per_layer"].append({"name": "dummy_count.a", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "trainer", "workloads": ["dummy-cell"],
                           "moves": m["end_to_end"][0]["name"]})
    _write(root, "BENCHMARK.json", json.dumps(m))
    cell = M.load_cell(M.load_manifest(root), "dummy-cell", root)
    assert cell.traffic["seq_len"] == 512 and cell.config["micro_per_device"] == 1
    assert cell.reader("dummy_count.a")({"steps": 7}) == 7
    assert [x["name"] for x in cell.per_layer] == ["dummy_count.a"]
    assert {p: open(p, "rb").read() for p in before} == before


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]


def _write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)


# ----------------------------------------------------------------------
# traffic: the same seed is the same work
# ----------------------------------------------------------------------
def _serve_mix():
    with open(os.path.join(ROOT, "benchmark/traffic/chat-mixed-overload.json")) as f:
        return json.load(f)


def _train_mix():
    with open(os.path.join(ROOT, "benchmark/traffic/packed-1k.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_same_seed_same_trace_other_seed_other_trace(seed):
    mix = _serve_mix()
    a = loadgen.serve_trace(mix, seed, 10.0, 50257)
    b = loadgen.serve_trace(mix, seed, 10.0, 50257)
    c = loadgen.serve_trace(mix, seed + 1, 10.0, 50257)
    assert loadgen.trace_sha256(a) == loadgen.trace_sha256(b)
    assert loadgen.trace_sha256(a) != loadgen.trace_sha256(c)
    lead = loadgen.serve_trace(mix, seed, 10.0, 50257, lead_in=True)
    assert loadgen.trace_sha256(lead) != loadgen.trace_sha256(a)


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_a_serve_trace_is_the_mix_it_was_asked_for(seed):
    """Sizes, arrivals and token ids are all the seed's; every trace keeps
    to the mix's rate, lengths, limits and shared system prompt."""
    mix = _serve_mix()
    seconds = 40.0
    t = loadgen.serve_trace(mix, seed, seconds, 50257)
    other = loadgen.serve_trace(mix, seed + 1, seconds, 50257)
    assert [len(r.prompt) for r in t[:50]] != [len(r.prompt) for r in other[:50]]
    assert [r.arrival_s for r in t[:50]] != [r.arrival_s for r in other[:50]]
    n = mix["rate_rps"] * seconds
    assert abs(len(t) - n) < 4 * n ** 0.5                  # Poisson
    arr = [r.arrival_s for r in t]
    assert arr == sorted(arr) and 0 < arr[0] and arr[-1] < seconds
    lo = min(m for m, _ in mix["prompt_len_mix"]) * (1 - mix["prompt_len_jitter"])
    assert all(lo <= len(r.prompt) < mix["max_total_len"] for r in t)
    assert len({len(r.prompt) % 64 for r in t}) > 32       # free lengths
    assert all(mix["gen_len_min"] <= r.max_new_tokens <= mix["gen_len_max"]
               or len(r.prompt) + r.max_new_tokens == mix["max_total_len"]
               for r in t)
    assert all(len(r.prompt) + r.max_new_tokens <= mix["max_total_len"]
               for r in t)
    shared = [r for r in t if r.shared_prefix]
    assert abs(len(shared) / len(t) - mix["shared_prefix_ratio"]) < 0.1
    head = shared[0].prompt[:mix["shared_prefix_len"]]
    assert all(len(r.prompt) > len(head) and (r.prompt[:len(head)] == head).all()
               for r in shared)
    lead = loadgen.serve_trace(mix, seed, 8.0, 50257, lead_in=True)
    assert all((r.prompt[:len(head)] == head).all()
               for r in lead if r.shared_prefix)


def test_same_seed_same_batch_stream_with_no_padding():
    mix = _train_mix()
    take = lambda seed: [next(it)["input_ids"].copy() for it in
                         [loadgen.packed_batches(mix, seed, 2, 50257)]
                         for _ in range(3)]
    a, b, c = take(5), take(5), take(6)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not all((x == y).all() for x, y in zip(a, c))
    it = loadgen.packed_batches(mix, 5, 2, 50257)
    rows = np.concatenate([next(it)["input_ids"] for _ in range(8)])
    assert rows.shape == (16, mix["seq_len"]) and rows.dtype == np.int32
    assert 0 <= rows.min() and rows.max() < 50257
    # documents end in eos and are packed end to end: a median-400 mix
    # puts a few ends in every 1024 tokens
    ends = (rows == mix["eos_token_id"]).sum()
    assert 16 <= ends <= 16 * 12


# ----------------------------------------------------------------------
# arithmetic on records
# ----------------------------------------------------------------------
def test_percentile_is_nearest_rank():
    xs = sorted(range(1, 101))
    assert loadgen.pct(xs, 0.95) == 96
    assert loadgen.pct(xs, 0.5) == 51
    assert loadgen.pct([7.0], 0.95) == 7.0
    assert loadgen.tail([], 0.95) is None


def test_tpot_is_the_mean_gap_after_the_first_token():
    records = [{"n_out": 5, "first_token": 1.0, "last_emit": 1.2},
               {"n_out": 1, "first_token": 1.0, "last_emit": 1.0},
               {"n_out": 0}]
    assert loadgen.tpot_ms(records) == pytest.approx([50.0])



# ----------------------------------------------------------------------
# required FLOPs and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers():
    # GPT-2-XL: 12*1600^2*48 = 1,474,560,000; head 50257*1600 = 80,411,200
    assert flops.matmul_params(1600, 48, 50257) == 1_554_971_200
    # 6 * 1,554,971,200 + 6*48*1600*1024 = 9,329,827,200 + 471,859,200
    assert flops.train_flops_per_token(1600, 48, 50257, 1024) == \
        pytest.approx(9_801_686_400)
    # GPT-3 Large widths: 12*1536^2*24 = 679,477,248; head 77,194,752
    assert flops.matmul_params(1536, 24, 50257) == 756_672_000
    assert flops.kv_bytes_per_token(1536, 24) == 147_456
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a decode tick of 64 rows over 20,000 live positions is memory-bound
    nbytes = flops.decode_tick_bytes(1536, 24, 50257, 20_000)
    assert nbytes == pytest.approx(2 * 756_672_000 + 20_000 * 147_456)
    secs, bound = flops.roofline_seconds(
        flops.decode_tick_flops(1536, 24, 50257, 64, 20_000), nbytes, peak)
    assert bound == "memory" and secs == pytest.approx(nbytes / 819e9)
    secs, bound = flops.roofline_seconds(1e15, 1e9, peak)
    assert bound == "compute" and secs == pytest.approx(1e15 / 197e12)


# ----------------------------------------------------------------------
# the trace reduction, on a hand-made event list
# ----------------------------------------------------------------------
def _synthetic():
    us = 1e3
    ops = [("fusion", 0 * us, 10 * us),
           ("while", 20 * us, 40 * us),             # encloses the next two
           ("flash_fwd", 22 * us, 8 * us),
           ("all-gather", 40 * us, 10 * us),
           ("all-reduce", 70 * us, 5 * us),
           ("fusion", 90 * us, 10 * us)]
    modules = [("jit_step_fn(123)", 0 * us, 60 * us),
               ("jit_step_fn(123)", 70 * us, 30 * us)]
    spans = [("bench/window", 0.0, 100 * us),
             ("bench/train_batch", 8 * us, 14 * us),   # covers gap 10..20
             ("bench/wait_device", 58 * us, 10 * us),  # most of gap 60..70
             ("bench/sleep", 74 * us, 18 * us)]        # gap 75..90
    return ops, modules, spans


def test_trace_reduction_busy_union_self_time_and_gaps():
    ops, modules, spans = _synthetic()
    s = trace_reduce.summarize({0: ops, 1: ops}, {0: modules, 1: modules}, spans)
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(100e-6)
    # busy: [0,10] + [20,60] + [70,75] + [90,100] = 65 us
    assert s.busy_s == pytest.approx(65e-6)
    assert s.op_self_s["while"] == pytest.approx(22e-6)   # 40 - 8 - 10
    assert s.op_self_s["fusion"] == pytest.approx(20e-6)
    assert s.ops_matching("flash") == pytest.approx(8e-6)
    t, runs = s.modules_matching("^jit_step_fn$")
    assert t == pytest.approx(90e-6) and runs == 2
    assert s.gap_s_by_span == pytest.approx(
        {"bench/train_batch": 10e-6, "bench/wait_device": 10e-6,
         "bench/sleep": 15e-6})
    assert s.longest_gaps[0] == ("bench/sleep", pytest.approx(15e-6))
    b = s.breakdown()
    assert b["device_ops"][0][0] == "while" and len(b["idle_gaps"]) == 3


def test_trace_reduction_clips_to_the_window_and_needs_a_device_op():
    ops, modules, spans = _synthetic()
    s = trace_reduce.summarize({0: ops}, {0: modules}, spans,
                               window=(50e3, 100e3))
    # [50,60] + [70,75] + [90,100]
    assert s.busy_s == pytest.approx(25e-6)
    with pytest.raises(ValueError, match="no operation ran"):
        trace_reduce.summarize({0: ops}, {}, spans, window=(200e3, 300e3))


def test_merge_and_gaps():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert trace_reduce.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]


# ----------------------------------------------------------------------
# per-layer readers and the result line
# ----------------------------------------------------------------------
def test_a_reader_that_finds_nothing_returns_nothing(with_pending):
    manifest = with_pending
    for w in manifest["workloads"]:
        cell = M.load_cell(manifest, w["name"], ROOT)
        for m in cell.per_layer:
            assert cell.reader(m["name"])({"cell": cell, "trace": None}) is None


def test_readers_on_hand_made_observations(with_pending):
    manifest = with_pending
    train = M.load_cell(manifest, "train-xl-z3-1chip", ROOT)
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    obs = {"cell": train, "peak": peak, "window_s": 10.0, "tokens": 90_000,
           "n_devices": 1, "flops_per_token": 9_801_686_400.0,
           "step_ready_t": [0.0, 0.2, 0.41, 0.6, 0.8]}
    assert train.reader("train_step_ms")(obs) == pytest.approx(200.0)
    assert train.reader("train_mfu_pct")(obs) == pytest.approx(
        100 * 9_801_686_400.0 * 9_000 / 197e12)
    serve = M.load_cell(manifest, "serve-760m-chat-overload", ROOT)
    recs = [{"tag": "lead", "due": -3.0, "submit": -2.9, "prefill_start": -1.0,
             "first_token": -0.9, "last_emit": 1.1, "n_out": 41},
            {"tag": "lead", "due": -1.0, "submit": -0.99, "prefill_start": 0.01,
             "first_token": 0.05, "last_emit": 1.05, "n_out": 11},
            {"tag": "win", "due": 0.5, "submit": 0.504, "prefill_start": 1.2,
             "first_token": 1.26, "last_emit": 1.86, "n_out": 5},
            {"tag": "win", "due": 1.0, "submit": 1.002}]
    obs = {"cell": serve, "records": recs, "window": (0.0, 2.0),
           "backlog_end": 3, "window_compiles": 2,
           "counters": {"prefix_cache_hit_tokens_total": 128,
                        "prefix_cache_miss_tokens_total": 384}}
    assert serve.reader("prefill_p50_ms")(obs) == pytest.approx(60.0)
    assert serve.reader("tpot_p50_ms")(obs) == pytest.approx(150.0)
    assert serve.reader("loadgen_late_p95_ms")(obs) == pytest.approx(4.0)
    assert serve.reader("prefix_hit_pct")(obs) == pytest.approx(25.0)
    assert serve.reader("backlog_end")(obs) == 3
    assert serve.reader("window_compiles")(obs) == 2


def test_result_line_has_exactly_the_contract_keys(manifest):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 123}

    cell = M.load_cell(manifest, "train-xl-z3-1chip", ROOT)
    ctx = runner.Context(cell, 1, 10.0, False, False, None, 0.0)
    out = {"setup_s": 50.0, "window_s": 10.0, "attempted": 40, "failed": 0,
           "end_to_end": {"train_tokens_per_s_chip": 9000.0}, "observed": {}}
    line = runner.result_line(ctx, out, [Dev()], "end_to_end")
    assert sorted(line) == sorted(runner.RESULT_KEYS)
    assert line["correct"] is True and line["attempted"] == 40
    assert sorted(line["metrics"]) == ["setup_s", "train_tokens_per_s_chip"]
    assert line["metrics"]["setup_s"] == {"value": 50.0, "unit": "s"}
    assert sorted(line["device"]) == ["count", "kind", "memory_peak_bytes",
                                      "platform"]
    json.dumps(line)


# ----------------------------------------------------------------------
# the command without a TPU
# ----------------------------------------------------------------------
def test_run_without_a_tpu_exits_nonzero_and_prints_no_result_line():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "train-xl-z3-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
