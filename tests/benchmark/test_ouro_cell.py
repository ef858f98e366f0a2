"""The benchmark's thirteenth cell, ``train-ouro-loop4-8k-1chip`` (PR 64):
its names resolve to files, its configuration is the catalog row cut in depth
and vocabulary alone with ``total_ut_steps`` as published, the parameter
count from the program's own shapes is the file's, its operation and byte
counts are what a hand computes, its readers give nothing (and do not raise)
where there is nothing to read, the loop's comparison refuses each named
fault at the rehearsal's sizes, a program without the loop is turned away by
name, and the rehearsal passes on the CPU.  Host-only, nothing timed.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_ouro as F
from benchmark.harness import manifest as M
from benchmark.layer_metrics import (dense_ffn_share_pct, exit_head_share_pct,
                                     ut_exit_step_mean, ut_pass_spread_pct)

from .test_joyai_cell import CUTS, is_a_width

ROOT = M.ROOT
CELL = "train-ouro-loop4-8k-1chip"
CONFIG = "ouro-2.6b-z3-8bit"
NEW = ("dense_ffn_share_pct.ouro", "exit_head_share_pct",
       "ut_pass_spread_pct", "ut_exit_step_mean")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
PARAMETERS = 333_500_417
LAYER = 51_388_416
FAULTS = M.load_module(ROOT, "reference", "ouro").FAULTS


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


def test_the_manifest_holds_the_cell_its_lists_and_its_four_entries(
        manifest, cell):
    w = next(x for x in manifest["workloads"] if x["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "packed-8k-6144", 1)
    assert len(w["why"]) <= 200 and "1 packed 8k row a step" in w["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["why"]) <= 200
    # one chip: nothing it measures exists only across chips
    assert not [x for x in manifest["workloads"] if x["chips"] != 1]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_tokens_per_s_chip"
        assert by_name[name]["layer"] == "model"
    # in this order among themselves, behind every entry an older cell
    # brought, at no pinned position: the next cell appends behind them
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index(NEW[0]) > names.index("mhc_res_marginal_err")
    assert [by_name[n]["source"] for n in NEW] == [
        "device_trace", "device_trace", "device_trace", "program_counter"]
    mine = [m["name"] for m in cell.per_layer]
    assert mine == [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s", "setup_backend_compile_s",
        "setup_init_params_s", "peak_hbm_gib", "step_temp_hbm_gib", *NEW]
    # a suffixed entry reads the file named before the first '.'; the
    # unsuffixed one stays Olmo-Hybrid's alone
    assert cell.reader("dense_ffn_share_pct.ouro").__module__.endswith(
        "dense_ffn_share_pct")
    assert by_name["dense_ffn_share_pct"]["workloads"] == [
        "train-olmo-hybrid-8k-1chip"]
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_chip", "setup_s"]


def test_the_twelfth_cells_five_entries_stand_as_they_were(manifest):
    """Xing's five lists hold that cell alone, in their order, and the
    cell's own list ends on them: appending a cell moved none."""
    xing = "train-xing4-mhc-8k-1chip"
    five = ("mtp_loss_excess.xing4", "dense_ffn_share_pct.xing4",
            "mhc_share_pct", "mhc_roofline", "mhc_res_marginal_err")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in five:
        assert by_name[name]["workloads"] == [xing], name
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in five] == list(five)
    theirs = M.load_cell(manifest, xing, ROOT)
    assert [m["name"] for m in theirs.per_layer][-5:] == list(five)
    # thirteen cells, every one a training cell on one chip
    assert len(manifest["workloads"]) >= 13
    tokens = next(m for m in manifest["end_to_end"]
                  if m["name"] == "train_tokens_per_s_chip")
    assert tokens["workloads"][11:13] == [xing, CELL]


def test_the_configuration_file_is_the_catalog_row_cut_two_ways(cell):
    conf = cell.config
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {"num_hidden_layers",
                                               "vocab_size"}
    assert set(conf["reduced"]) <= CUTS
    assert not any(is_a_width(k) for k in conf["reduced"])
    assert (conf["num_hidden_layers"], conf["vocab_size"]) == (6, 6144)
    for key in conf["reduced"]:
        assert conf["published"][key] == PUBLISHED[key]
    # the loop is no cut: as published, and never in ``reduced``
    assert conf["total_ut_steps"] == 4 and conf["early_exit_threshold"] == 1
    # floors: all layers alike, so the period is one layer and the floor
    # four; an eighth of the vocabulary; an eighth of the layers beside it
    assert conf["num_hidden_layers"] >= 4
    assert conf["vocab_size"] * 8 == 49152 and conf["vocab_size"] % 128 == 0
    assert conf["num_hidden_layers"] * 8 == 48
    assert "eight-stage pipeline" in conf["stands_for"] \
        and "RING" in conf["stands_for"]
    for star in ("sandwich_norm", "rotary", "loop", "beta", "init",
                 "document_mask"):
        assert "the config has no key" in conf["assumed"][star], star
    for key in ("bias", "exit_distribution", "cut", "remat", "rows"):
        assert len(conf["assumed"][key]) > 20, key
    assert "(i)" in conf["assumed"]["rows"]
    assert "12.928" in conf["compile_said"]
    assert conf["exit_entropy_weight"] == 0.05
    assert cell.traffic["seq_len"] == 8192 and conf["micro_per_device"] == 1
    assert cell.traffic["eos_token_id"] == 6143 < conf["vocab_size"]
    assert cell.traffic["mix_seed"] != json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "packed-8k-12544.json")))["mix_seed"]
    tol = conf["reference_check"]
    for key in ("loss_abs_tol", "exit_nll_abs_tol", "exit_p_abs_tol",
                "pass_rel_tol", "attention_rel_tol", "dense_rel_tol",
                "block_rel_tol", "gate_grad_rel_tol"):
        assert 0 < tol[key] < 0.1, key
        assert 0 < conf["rehearse"]["reference_check"][key] <= 0.1, key
    assert len(tol["reason"]) > 400 and "float8" in tol["reason"]
    assert conf["trace_names"]["flash"] == "^self_attn_full$"
    assert "unrolled" in conf["trace_names"]["loop"]
    r = conf["rehearse"]
    assert (r["hidden_size"], r["num_attention_heads"], r["head_dim"],
            r["intermediate_size"], r["num_hidden_layers"],
            r["vocab_size"]) == (64, 4, 16, 96, 3, 512)


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    import jax

    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.intermediate_size, cfg.vocab_size,
            cfg.rope_theta, cfg.rms_norm_eps) == (
        2048, 6, 16, 16, 128, 5632, 6144, 1e6, 1e-6)
    assert (cfg.total_ut_steps, cfg.exit_entropy_weight, cfg.sandwich_norm,
            cfg.loss_chunk, cfg.scan_layers, cfg.moe) == (
        4, 0.05, True, 8192, False, None)
    assert cfg.kinds == ("full_attention",) * 6 and cfg.rope_scaling is None
    assert driver.reference_kwargs(conf) == dict(
        n_layer=6, n_head=16, head_dim=128, vocab_size=6144, eps=1e-6,
        rope_theta=1000000, ut_steps=4, beta=0.05)
    # the parameters held here, from the program's own shapes: the file's
    ids = np.zeros((1, 128), np.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, labels=ids))["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    E = 2048
    assert 4 * E * E + 3 * E * 5632 + 4 * E == LAYER
    assert n == 6 * LAYER + 2 * 6144 * E + E + (E + 1) == PARAMETERS
    assert f"{PARAMETERS:,}" in conf["published"]["parameters"]
    # one pass holds the same tree less the gate's 2,049
    plain, _ = driver.model_config(dict(conf, total_ut_steps=1))
    shapes = jax.eval_shape(lambda: plain.init(
        jax.random.PRNGKey(0), ids, labels=ids))["params"]
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == PARAMETERS - 2049
    # 6 B a parameter resident, 12 in the step
    assert PARAMETERS * 6 / 2**30 == pytest.approx(1.86, abs=0.01)
    assert PARAMETERS * 12 / 2**30 == pytest.approx(3.73, abs=0.01)
    skip = model.is_undecayed_leaf
    assert skip(("exit_gate", "bias")) and not skip(("exit_gate", "kernel")) \
        and not skip(("layers_0", "input_norm", "scale"))


def test_flops_and_bytes_against_hand_computed_numbers(cell):
    conf = cell.config
    E = 2048
    assert F.passes(conf) == 4 and F.applications(conf) == 24
    block = 4 * E * E + 3 * E * 5632
    assert F.block_matmul_params(conf) == block == 51_380_224
    exits = 4 * 6144 * E + 3 * E
    assert F.active_matmul_products(conf) == 24 * block + exits
    attention = 24 * 4.0 * 16 * 128 * 4096.5
    assert F.attention_flops_per_token(conf, 8192) == attention
    assert F.causal_attention_flops_per_token(conf, 8192, 3) == 3 * attention
    total = F.train_flops_per_token(conf, 8192)
    assert total == 6.0 * (24 * block + exits) + 3 * attention
    # the issue's count: 2.47 + 0.80 + 0.10 GFLOP forward, 10.1 in all,
    # 83 TFLOP a row
    assert 2 * 24 * block == pytest.approx(2.466e9, rel=1e-3)
    assert attention == pytest.approx(0.805e9, rel=1e-3)
    assert 2 * exits == pytest.approx(0.1007e9, rel=1e-3)
    assert total == pytest.approx(10.12e9, rel=1e-3)
    assert total * 8192 == pytest.approx(82.9e12, rel=1e-3)
    # six vectors of H D and six of KV D a flash call, 24 calls a token
    assert F.flash_train_bytes_per_token(conf) == 6 * 24 * 32 * 128 * 2
    # flash is bound by the MXU at 8k: ~0.1 s a row at the chip's peak
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    t, bound = F.roofline_seconds(3 * attention * 8192,
                                  F.flash_train_bytes_per_token(conf) * 8192,
                                  peak)
    assert bound == "compute" and t == pytest.approx(0.1005, abs=1e-3)
    # a looped stack's optimizer pass is a quarter of a plain model's at the
    # same FLOPs: the leaves of 6 layers for 24 applications
    assert F.applications(conf) / int(conf["num_hidden_layers"]) == 4


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
@pytest.fixture()
def empty_registry():
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    yield reg
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


def test_the_mean_exit_pass_from_the_programs_gauge(cell, empty_registry):
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    read = cell.reader("ut_exit_step_mean")
    assert read({"cell": cell}) is None         # a program without it
    p = np.float32([0.5, 0.25, 0.125, 0.125])
    LlamaForCausalLM.record_step_stats({
        "exit_p": p, "exit_nll": np.float32([8.7, 8.7, 8.7, 8.7]),
        "exit_step_mean": np.float32((p * [1, 2, 3, 4]).sum()),
        "exit_entropy": np.float32(1.2130), "lm_loss": np.float32(8.7)})
    assert read({"cell": cell}) == pytest.approx(1.875)
    snap = empty_registry.snapshot()
    assert {"ut_exit_p", "ut_exit_nll", "ut_exit_step_mean",
            "ut_exit_entropy", "lm_loss"} <= set(snap)
    assert [s["labels"]["step"] for s in snap["ut_exit_p"]["samples"]] \
        == ["0", "1", "2", "3"]
    assert ut_exit_step_mean.GAUGE == "ut_exit_step_mean"


@pytest.mark.parametrize("obs", [
    {}, {"device_scope_ms": None}, {"device_scope_ms": {}},
    {"device_scope_ms": {"step": 900.0}},
    {"device_scope_ms": {"step": 900.0, "mlp_dense": 1.0, "ut/pass": []}}])
def test_the_shares_read_nothing_where_there_is_nothing(obs):
    """The parent has no ``ut/`` scope and a rehearsal no device plane."""
    assert exit_head_share_pct.read(obs) is None
    assert ut_pass_spread_pct.read(obs) is None


def test_the_shares_from_the_drivers_scope_split():
    ms = {"step": 900.0, "loss_head": 16.0, "ut/exit_gate": 2.0,
          "mlp_dense": 300.0, "ut/pass": [210.0, 200.0, 205.0, 205.0]}
    obs = {"device_scope_ms": ms}
    assert exit_head_share_pct.read(obs) == pytest.approx(2.0)
    assert ut_pass_spread_pct.read(obs) == pytest.approx(100 * 10 / 205)
    assert dense_ffn_share_pct.read(obs) == pytest.approx(100 / 3)
    even = dict(ms, **{"ut/pass": [200.0] * 4})
    assert ut_pass_spread_pct.read({"device_scope_ms": even}) == 0.0


# ----------------------------------------------------------------------
# the comparison, at the rehearsal's sizes
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def small(cell):
    import jax
    from flax.core import meta

    sys.path.insert(0, ROOT)
    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    model, cfg = driver.model_config(conf)
    ids = np.random.default_rng(0).integers(0, 512, (1, 128)).astype(np.int32)
    params = meta.unbox(jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), ids, labels=ids))()["params"])
    params = driver.seeded(3, jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, params))
    got = driver.program(model)(params, ids)
    return driver, reference, cfg, conf, params, ids, got


def _worst(errs):
    return {"loss": errs["loss"], "exit_nll": errs["exit_nll"],
            "exit_p": errs["exit_p"], "pass": max(errs["pass"]),
            "gate_grad": max(errs["gate_grad"])}


@pytest.mark.parametrize("wrong", [None, "float8", *FAULTS])
def test_the_loops_comparison_refuses_each_named_fault(small, wrong):
    """Comparisons a to d of the driver through its own readers: sound
    under every limit of the rehearsal's section; float8 and every named
    fault over at least one of them."""
    driver, reference, cfg, conf, params, ids, got = small
    assert len(FAULTS) == 10
    extra = {} if wrong is None else {"operand_bits": (4, 3)} \
        if wrong == "float8" else {"fault": wrong}
    want = driver.reference_parts(reference, params, ids, conf, **extra)
    errs = _worst(driver.compare_parts(got, want))
    tol = conf["reference_check"]
    limits = {"loss": tol["loss_abs_tol"], "exit_nll": tol["exit_nll_abs_tol"],
              "exit_p": tol["exit_p_abs_tol"], "pass": tol["pass_rel_tol"],
              "gate_grad": tol["gate_grad_rel_tol"]}
    over = [k for k in limits if not errs[k] <= limits[k]]
    assert (not over) == (wrong is None), (wrong, errs, limits)


def test_the_block_checks_read_the_last_passs_first_block(small):
    driver, reference, cfg, conf, params, ids, _ = small
    kept = driver.last_pass_inputs(cfg)
    driver.reference_parts(reference, params, ids, conf, kept)
    assert [len(v) for v in kept.values()] == [1, 1, 1]
    assert kept["block_inputs"].seen == 4 * 3       # every application
    p = driver.moved(3, 0, params["layers_0"])
    x, u = kept["block_inputs"][0], kept["attn_inputs"][0]
    assert driver.read_block(cfg, reference, p, x) < 0.02
    assert driver.read_block(cfg, reference, p, x,
                             fault="no_post_norms") > 0.2
    assert driver.read_attention(cfg, reference, p["self_attn"],
                                 u.astype(cfg.dtype)) < 0.02
    assert driver.read_attention(cfg, reference, p["self_attn"],
                                 u.astype(cfg.dtype),
                                 operand_bits=(4, 3)) > 0.2


def test_a_program_without_the_loop_is_turned_away_by_name(cell, monkeypatch):
    """What the parent commit does with this PR's benchmark files: the
    driver exits at once, before any engine is built."""
    import dataclasses

    from deepspeed_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Older:
        hidden_size: int = 64

    monkeypatch.setattr(llama, "LlamaConfig", Older)
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit, match="total_ut_steps"):
        cell.driver().run(ctx, None)


def test_rehearsal_of_the_ouro_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check: program loss", "exit_nll program",
                 "exit_p program", "after each pass", "d loss / d w_gate",
                 "attention check:", "dense SwiGLU check:",
                 "block (the sandwich norm) check:"):
        assert said in r.stderr, said
