"""The benchmark's twelfth cell, ``train-xing4-mhc-8k-1chip`` (PR 62): its
names resolve to files, its configuration is the catalog row cut as the
guide allows, the parameter count from the program's own shapes is the
file's, its operation and byte counts are what a hand computes, its three
readers give nothing (and do not raise) where there is nothing to read, the
hyper-connection comparison refuses each named fault at the rehearsal's
sizes, a program without lanes is turned away by name, and the rehearsal
passes on the CPU.  Host-only, nothing timed.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_xing4 as F
from benchmark.harness import manifest as M
from benchmark.layer_metrics import (mhc_res_marginal_err, mhc_roofline,
                                     mhc_share_pct)

ROOT = M.ROOT
CELL = "train-xing4-mhc-8k-1chip"
CONFIG = "xing4.0-29b-a4b-z3-8bit"
NEW = ("mtp_loss_excess.xing4", "dense_ffn_share_pct.xing4", "mhc_share_pct",
       "mhc_roofline", "mhc_res_marginal_err")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
PARAMETERS = 913_473_668


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


def test_the_manifest_holds_the_cell_its_lists_and_its_five_entries(
        manifest, cell):
    w = next(x for x in manifest["workloads"] if x["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "packed-8k-16384-ep8", 1)
    assert len(w["why"]) <= 200 and "lanes" in w["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "n_routed_experts", "vocab_size"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["why"]) <= 200
    # one chip: nothing it measures exists only across chips
    assert not [x for x in manifest["workloads"] if x["chips"] != 1]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_tokens_per_s_chip"
    # in this order among themselves, behind every entry an older cell
    # brought, at no pinned position: the next cell appends behind them
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index(NEW[0]) > names.index("moe_group_kept_pct")
    assert (by_name["mhc_roofline"]["unit"], by_name["mhc_roofline"]["layer"],
            by_name["mhc_roofline"]["source"]) == ("%", "kernels",
                                                   "device_trace")
    assert (by_name["mhc_share_pct"]["layer"],
            by_name["mhc_res_marginal_err"]["source"]) == ("model",
                                                           "program_counter")
    mine = [m["name"] for m in cell.per_layer]
    assert mine == [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s", "setup_backend_compile_s",
        "setup_init_params_s", "expert_gemm_share_pct",
        "expert_gemm_roofline", "moe_load_imbalance", "moe_held_pair_pct",
        "moe_expert_bias_spread", "peak_hbm_gib", "step_temp_hbm_gib",
        *NEW]
    # a suffixed entry reads the file named before the first '.'
    assert cell.reader("mtp_loss_excess.xing4").__module__.endswith(
        "mtp_loss_excess")
    assert cell.reader("dense_ffn_share_pct.xing4").__module__.endswith(
        "dense_ffn_share_pct")
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_chip", "setup_s"]


def test_the_eleventh_cells_four_entries_stand_as_they_were(manifest):
    """What ``test_ling3_cell.py``'s tail-pinned test held (superseded:
    ``tests/conftest.py``), by name: each of Ling's four lists that cell
    alone, in its order, and the cell's own list ends on them."""
    ling, four = "train-ling3-kda-8k-1chip", {
        "linear_attn_share_pct.kda": ("%", "lower", "device_trace", "model"),
        "kda_roofline": ("%", "higher", "device_trace", "kernels"),
        "kda_gate_share_pct": ("%", "lower", "device_trace", "model"),
        "moe_group_kept_pct": ("%", "higher", "program_counter", "experts")}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better, source, layer) in four.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "train_tokens_per_s_chip",
            "workloads": [ling]}
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in four] == list(four)
    theirs = M.load_cell(manifest, ling, ROOT)
    assert [m["name"] for m in theirs.per_layer][-4:] == list(four)
    assert all(callable(theirs.reader(n)) for n in four)


def test_the_configuration_file_is_the_catalog_row_cut_four_ways(cell):
    conf = cell.config
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert (conf["num_hidden_layers"], conf["first_k_dense_replace"],
            conf["n_routed_experts"], conf["vocab_size"]) == (5, 1, 8, 16384)
    for key in conf["reduced"]:
        assert conf["published"][key] == PUBLISHED[key]
    # floors: the leading dense layers once and four of the layers that
    # follow, >= 8 experts, >= 1/8 of the vocabulary; no width touched
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] == 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 == 131072
    assert conf["num_nextn_predict_layers"] == 1
    assert "eight" in conf["stands_for"] and "8-15" in conf["stands_for"]
    moe = conf["moe"]
    assert conf["num_experts"] == conf["n_routed_experts"] == 8
    assert conf["routed_experts"] == moe["routed_experts"] == 64
    assert conf["num_dense_layers"] == conf["first_k_dense_replace"] == 1
    assert moe["route_scale"] == conf["routed_scaling_factor"] == 2
    assert moe["score_func"] == conf["scoring_func"] == "sigmoid"
    assert moe["num_shared_experts"] == conf["n_shared_experts"] == 1
    assert moe["aux_loss_weight"] == 0.0 and moe["first_expert"] == 8
    assert conf["model_options"]["mtp_loss_weight"] == 0.3
    # what the config has no key for is written out, each with its reason
    for star in ("stream_ends", "sinkhorn_order", "hc_gains", "hc_init",
                 "mtp_lanes", "mtp_loss_weight", "bias_update",
                 "initializer_range"):
        assert "the config has no key" in conf["assumed"][star], star
    for key in ("rope", "yarn", "layout", "eos_token_id", "document_mask",
                "cut", "recipe", "rows", "warmup_steps", "unused_keys"):
        assert len(conf["assumed"][key]) > 20, key
    assert "is_undecayed_leaf" in conf["assumed"]["recipe"]
    assert "(i)" in conf["assumed"]["rows"]
    assert len(conf["compile_said"]) >= 2
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["eos_token_id"] == 16383 < conf["vocab_size"]
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02
    for key in ("expert_rel_tol", "dense_rel_tol", "attention_rel_tol",
                "mtp_rel_tol", "mhc_rel_tol"):
        assert 0 < tol[key] < 0.1
    for why in ("reason", "expert_reason", "dense_reason",
                "attention_reason", "mtp_reason", "mhc_reason"):
        assert len(tol[why]) > 40
    assert conf["trace_names"] == {"flash": "^self_attn_mla$",
                                   "train_module": "^jit_step_fn$",
                                   "expert_gemm": "^t?gmm$"}


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    import jax

    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_dense_layers,
            cfg.num_attention_heads, cfg.intermediate_size, cfg.expert_size,
            cfg.vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == (
        3584, 5, 1, 32, 9216, 1024, 16384, 1e4, 1e-6)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rope_interleave) == (
        768, 512, 128, 64, 128, True)
    assert (cfg.lanes, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max) == (
        4, 20, 1e-6, -30, 30)
    table, scaled = cfg.latent_rotary
    assert table.scale == 1.0 and len(table.inv_freq) == 32
    assert scaled == pytest.approx(2.0048, abs=1e-4)
    assert cfg.num_nextn_predict_layers == 1 and cfg.mtp_loss_weight == 0.3
    assert cfg.loss_chunk == 8192 and not cfg.scan_layers
    moe = cfg.moe
    assert (moe.num_experts, moe.routed, moe.first_expert, moe.top_k,
            moe.score_func, moe.route_scale, moe.num_shared_experts) == (
        8, 64, 8, 4, "sigmoid", 2, 1)
    kw = driver.reference_kwargs(conf)
    assert kw["first_expert"] == 8 and kw["routed_experts"] == 64
    assert kw["hc_mult"] == 4 and kw["rope_scaling"]["factor"] == 64
    # the parameters held here, from the program's own shapes: the file's
    ids = np.zeros((1, 128), np.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, labels=ids))["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    E = 3584
    attn = (E * 768 + 768 + 768 * 32 * 192 + E * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * E)
    assert attn == 28_411_136
    lanes = 2 * (4 * E * 24 + 3 + 4 + 4 + 16)      # two sublayers' maps
    assert lanes == 688_182
    dense = attn + 2 * E + lanes + 3 * E * 9216
    sparse = attn + 2 * E + lanes + E * 64 + 64 + 9 * 3 * E * 1024
    mtp = 2 * E * E + 3 * E + sparse
    assert n == dense + 4 * sparse + mtp + 2 * 16384 * E + E == PARAMETERS
    assert f"{PARAMETERS:,}" in conf["published"]["parameters"]
    # the issue's 912.6 M left the dense block's maps, the norms and the
    # bias out: the same count to 0.1%
    assert n == pytest.approx(912.6e6, rel=1e-3)
    # and which leaves weight decay leaves alone
    skip = model.is_undecayed_leaf
    assert skip(("layers_0", "attn_hc", "b_res")) \
        and skip(("mtp_0", "block", "mlp_hc", "a_pre")) \
        and not skip(("layers_0", "attn_hc", "phi")) \
        and not skip(("layers_0", "input_norm", "scale"))


def test_flops_and_bytes_against_hand_computed_numbers(cell):
    conf = cell.config
    E, n, k = 3584, 4, 24
    assert F.blocks(conf) == 6 and F.sparse_layers(conf) == 5
    assert F.sublayers(conf) == 12
    attn = (E * 768 + 768 * 32 * 192 + E * 576 + 512 * 32 * 256
            + 32 * 128 * E)
    sparse = E * 64 + (1 + 4 * 8 / 64) * 3 * E * 1024
    active = (6 * attn + 3 * E * 9216 + 5 * sparse + 2 * E * E
              + 2 * 16384 * E)
    assert F.active_matmul_params(conf) == active
    forward = (2 * n * E * (1 + k) + 2 * n * E + 2 * n * n * E + 2 * n * E
               + 4 * n * n * 20)
    assert F.mhc_flops_per_token(conf) == 12 * forward
    assert F.mhc_flops_per_step(conf, 8192) == 3 * 12 * forward * 8192
    assert F.mhc_matmul_params(conf) == 12 * 4 * E * 24
    # each lane read once a pass and written once a sublayer: 14 E forward,
    # 15 E backward, bf16
    assert F.mhc_bytes_per_step(conf, 8192) == 29 * E * 2 * 8192 * 12
    assert F.mhc_bytes_per_step(conf, 8192) == pytest.approx(20.4e9, rel=5e-3)
    attention = 6 * 3 * 2.0 * 32 * 320 * 4096.5
    total = F.train_flops_per_token(conf, 8192)
    assert total == 6.0 * active + attention + 3 * 12 * forward
    # the mechanism is HBM-bound: ~25 ms at the chip's 819 GB/s, its
    # operations a thirtieth of that
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    t, bound = F.roofline_seconds(F.mhc_flops_per_step(conf, 8192),
                                  F.mhc_bytes_per_step(conf, 8192), peak)
    assert bound == "memory" and t == pytest.approx(0.0249, abs=2e-4)
    assert F.expert_rows_per_step(conf, 8192) == 8192 * 4 / 8 == 4096
    assert F.held_share(conf) == 0.125


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


def test_the_marginal_error_from_the_programs_gauge(cell, empty_registry):
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    read = cell.reader("mhc_res_marginal_err")
    assert read({"cell": cell}) is None         # a program without it
    LlamaForCausalLM.record_step_stats({
        "mhc_res_marginal_err": np.float32([1e-5, 3e-4, 2e-5]),
        "mhc_res_offdiag": np.float32([0.1, 0.2, 0.3]),
        "mhc_pre_mean": np.float32([0.25] * 3),
        "mhc_post_mean": np.float32([1.0] * 3)})
    assert read({"cell": cell}) == pytest.approx(3e-4)
    snap = empty_registry.snapshot()
    assert {"mhc_res_marginal_err", "mhc_res_offdiag", "mhc_pre_mean",
            "mhc_post_mean"} <= set(snap)
    assert [s["labels"]["layer"] for s in
            snap["mhc_res_offdiag"]["samples"]] == ["0", "1", "2"]
    assert mhc_res_marginal_err.GAUGE == "mhc_res_marginal_err"


def test_share_and_roofline_read_the_scopes_or_the_kernels_or_nothing(cell):
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    work = {"mhc_flops_per_step": F.mhc_flops_per_step(cell.config, 8192),
            "mhc_bytes_per_step": F.mhc_bytes_per_step(cell.config, 8192)}
    for obs in ({}, {"device_scope_ms": None}, {"device_scope_ms": {}},
                {"device_scope_ms": {"step": 500.0}}):
        assert mhc_share_pct.read(obs) is None
    assert mhc_share_pct.read(
        {"device_scope_ms": {"step": 500.0, "mhc": 100.0}}) == 20.0
    # the parent has neither the counts nor the scopes: nothing, no raise
    assert mhc_roofline.read({"peak": peak, "cell": cell}) is None
    assert mhc_roofline.read(dict(work, peak=None, cell=cell)) is None
    assert mhc_roofline.read(dict(work, peak=peak, cell=cell)) is None
    got = mhc_roofline.read(dict(
        work, peak=peak, cell=cell,
        device_scope_ms={"step": 500.0, "mhc": 100.0}))
    assert got == pytest.approx(24.9, abs=0.2) and got < 100
    # kernels of its own, by the configuration's name for them: the same
    # required work over their time in the window's trace
    named = types.SimpleNamespace(config=dict(cell.config, trace_names=dict(
        cell.config["trace_names"], mhc="^mhc_")))
    trace = types.SimpleNamespace(window_s=2.0,
                                  ops_matching=lambda name: 0.1 * 8)
    got = mhc_roofline.read(dict(work, peak=peak, cell=named, trace=trace,
                                 steps=16, window_s=4.0))
    assert got == pytest.approx(24.9, abs=0.2)


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
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids,
                                   labels=ids)["params"])
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, params)
    hc_in = driver._OnTheHost()
    driver.train_joyai.reference_forward(reference, params, ids, conf,
                                         hc_inputs=hc_in)
    leaves = driver.train_joyai.blocks(reference, params, cfg)
    return driver, reference, cfg, conf, leaves, hc_in


@pytest.mark.parametrize("fault", [None, "one_sweep", "rows_only",
                                   "post_without_2", "softmax_pre",
                                   "res_transposed", "no_rsqrt", "no_clamp"])
def test_hyper_connection_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, leaves, hc_in = small
    assert len(hc_in) == 8 and hc_in[0][0].dtype.name == "bfloat16"
    errs = driver.read_mhc(3, cfg, conf, reference, leaves, hc_in,
                           **({"fault": fault} if fault else {}))
    assert len(errs) == 8               # 3 blocks + the prediction's, x 2
    assert (max(errs) < 0.02) == (fault is None), (fault, errs)
    if fault is not None:
        assert min(errs) > 0.02, (fault, errs)      # every sublayer shows it


def test_float8_operands_are_refused_by_the_hyper_connection_check(small):
    driver, reference, cfg, conf, leaves, hc_in = small
    errs = driver.read_mhc(3, cfg, conf, reference, leaves, hc_in,
                           operand_bits=(4, 3))
    assert min(errs) > 0.02, errs


def test_a_program_without_lanes_is_turned_away_by_name(cell, monkeypatch):
    """What the parent commit does with this PR's benchmark files: the
    driver exits at once, before any engine is built."""
    import dataclasses

    from deepspeed_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Older:
        hidden_size: int = 64

    monkeypatch.setattr(llama, "LlamaConfig", Older)
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit, match="hc_mult"):
        cell.driver().run(ctx, None)


def test_rehearsal_of_the_xing4_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check:", "attention check: prediction block",
                 "expert check:", "prediction-block check:", "dense check:",
                 "hyper-connection check:", "bias check:"):
        assert said in r.stderr, said
