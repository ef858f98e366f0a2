"""The eleventh cell, ``train-ling3-kda-8k-1chip`` (PR 58): Ling-3.0-flash's
language model, five Kimi-Delta-Attention mixers (the delta rule under a
decay a key channel) to one gated latent-attention layer, 8 of 512
group-routed experts held, one packed 8,192-token row a step.  Its
configuration file is the catalog row cut four ways; the driver builds the
model from the file as data and the parameter count is recounted from the
program's own shapes; ``flops_ling3.py`` against hand-computed numbers; the
three new readers against made-up observations; each named fault refused by
a check at the rehearsal's sizes; the ``--rehearse`` line ``correct``; and
the manifest gained the cell at the end of every list it joins and nothing
else moved.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_ling3 as F
from benchmark.harness import manifest as M

ROOT = M.ROOT
CELL = "train-ling3-kda-8k-1chip"
CONFIG = "ling-3.0-flash-z3-8bit"
OLDER = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip",
         "train-joyai-flash-8k-1chip", "train-sdar-blockdiff-8k-1chip",
         "train-lfm2-hybrid-8k-1chip", "train-qwen3next-gdn-8k-1chip",
         "train-olmo-hybrid-8k-1chip", "train-keye-dsa-32k-1chip"]
JOINED = ["train_step_ms", "train_mfu_pct", "flash_share_pct",
          "flash_roofline", "device_idle_pct.train", "train_host_ms",
          "train_input_ms", "train_dispatch_ms", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_init_params_s",
          "expert_gemm_share_pct", "expert_gemm_roofline",
          "moe_load_imbalance", "moe_held_pair_pct",
          "moe_expert_bias_spread", "peak_hbm_gib", "step_temp_hbm_gib"]
NOT_JOINED = ["flash_window_roofline", "flash_full_roofline",
              "flash_window_share_pct", "mtp_loss_excess",
              "diffusion_masked_pct", "diffusion_prep_share_pct",
              "short_conv_share_pct", "short_conv_filter_roofline",
              "linear_attn_share_pct", "gated_delta_roofline",
              "linear_attn_share_pct.96x192", "gated_delta_roofline.96x192",
              "dense_ffn_share_pct", "indexer_share_pct", "indexer_roofline",
              "sparse_kept_pct", "sparse_live_tile_pct", "indexer_loss"]
NEW = ["linear_attn_share_pct.kda", "kda_roofline", "kda_gate_share_pct",
       "moe_group_kept_pct"]
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]
S = 8192
E, H, D, W = 2560, 32, 128, 4096
# the issue's arithmetic, from the config's widths
KDA_MIXER = 6 * E * W + E * H + 3 * W * 4 + H + W + D           # 63,049,888
MLA_MIXER = (E * 6144 + E * 576 + 512 + 512 * 8192 + W * E
             + E * H)                                           # 31,965,696
SPARSE_FFN = E * 512 + 512 + 3 * E * 768 + 8 * 3 * E * 768      # 54,395,392
DENSE_FFN = 3 * E * 6144                                        # 47,185,920
HELD = (KDA_MIXER + DENSE_FFN + 2 * E + 4 * (KDA_MIXER + SPARSE_FFN + 2 * E)
        + MLA_MIXER + SPARSE_FFN + 2 * E + 2 * 19648 * E + E)


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
def test_the_manifest_gained_one_configuration_and_one_cell(manifest):
    assert [w["name"] for w in manifest["workloads"]][:len(OLDER) + 1] \
        == OLDER + [CELL]
    at = len(OLDER)
    assert manifest["workloads"][at] == {
        "name": CELL, "config": CONFIG, "traffic": "packed-8k-19648",
        "chips": 1, "why": manifest["workloads"][at]["why"]}
    why = manifest["workloads"][at]["why"]
    assert len(why) <= 200 and "1/64" in why and "128 rows" in why
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/inclusionAI/" \
        "Ling-3.0-flash/blob/main/config.json"
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [c["name"] for c in manifest["configs"]].index(CONFIG) \
        == len(manifest["configs"][:11]) - 1
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["train_tokens_per_s_chip"]["workloads"][:at + 1] \
        == OLDER + [CELL]
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert manifest["run_seconds"] == 50
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 0
    M.check_manifest(manifest)


@pytest.mark.parametrize("name", JOINED)
def test_a_metric_the_cell_joins_lists_it_behind_the_older_cells(manifest,
                                                                 name):
    metric = next(m for m in manifest["per_layer"] if m["name"] == name)
    cells = metric["workloads"]
    assert CELL in cells
    older = cells[:cells.index(CELL)]
    assert older == [c for c in OLDER if c in older] and len(older) >= 3
    assert metric["moves"] in ("train_tokens_per_s_chip", "setup_s")


@pytest.mark.parametrize("name", NOT_JOINED)
def test_a_metric_with_nothing_to_read_here_does_not_list_the_cell(manifest,
                                                                   name):
    metric = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert CELL not in metric["workloads"]


def test_the_four_new_metrics_list_this_cell_alone(manifest, cell):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    want = {"linear_attn_share_pct.kda": ("%", "lower", "device_trace",
                                          "model"),
            "kda_roofline": ("%", "higher", "device_trace", "kernels"),
            "kda_gate_share_pct": ("%", "lower", "device_trace", "model"),
            "moe_group_kept_pct": ("%", "higher", "program_counter",
                                   "experts")}
    for name, (unit, better, source, layer) in want.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "train_tokens_per_s_chip",
            "workloads": [CELL]}
        assert callable(cell.reader(name))
    assert [m["name"] for m in manifest["per_layer"]][-4:] == NEW
    assert [m["name"] for m in cell.per_layer] == [
        n for n in JOINED if n not in ("peak_hbm_gib", "step_temp_hbm_gib")
    ][:11] + ["expert_gemm_share_pct", "expert_gemm_roofline",
              "moe_load_imbalance", "moe_held_pair_pct",
              "moe_expert_bias_spread", "peak_hbm_gib",
              "step_temp_hbm_gib"] + NEW
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_chip", "setup_s"]
    # the suffixed name reads the reader that stands
    assert cell.reader("linear_attn_share_pct.kda").__module__.endswith(
        "linear_attn_share_pct")


@pytest.mark.parametrize("older", OLDER)
def test_an_older_cell_reads_no_new_metric(manifest, older):
    got = [m["name"] for m in M.load_cell(manifest, older, ROOT).per_layer]
    assert not set(NEW) & set(got)
    assert "train_step_ms" in got and "peak_hbm_gib" in got


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
def test_the_configuration_file_is_the_catalog_row_cut_four_ways(cell):
    conf = cell.config
    assert set(PUBLISHED) <= set(conf)
    differs = [k for k in REDUCED if conf[k] != PUBLISHED[k]]
    assert {k for k, v in PUBLISHED.items() if conf[k] != v} == set(REDUCED)
    assert differs == REDUCED == conf["reduced"]
    assert [conf[k] for k in REDUCED] == [6, 1, 8, 19648]
    # the prediction block stays as published, and so does the factor 0
    # under which the program builds none (LlamaConfig.mtp_blocks)
    assert (conf["num_nextn_predict_layers"], conf["mtp_loss_scaling_factor"],
            conf["model_options"]["mtp_loss_weight"]) == (1, 0, 0.0)
    for key in REDUCED:
        assert conf["published"][key] == PUBLISHED[key]
    # floors: a whole period with five layers behind the dense one, 8
    # routed experts, an eighth of the vocabulary
    assert conf["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert conf["num_hidden_layers"] == conf["layer_group_size"]
    kinds = conf["layer_types"]
    assert len(kinds) == 42 and kinds == [
        "full_attention" if (i + 1) % conf["layer_group_size"] == 0
        else "kda_attention" for i in range(42)]
    assert kinds[:6].count("kda_attention") == 5
    assert conf["routed_experts"] == conf["moe"]["routed_experts"] == 512
    assert (conf["moe"]["first_expert"], conf["moe"]["n_group"],
            conf["moe"]["topk_group"]) == (0, 8, 4)
    assert conf["num_dense_layers"] == conf["first_k_dense_replace"] == 1
    for said in ("SIXTY-FOUR", "expert-parallel 64", "experts 0-7",
                 "504 absent experts", "pipeline stages"):
        assert said in conf["stands_for"], said
    for key in ("layer_pattern", "kda_gate", "kda_init", "kda_output_norm",
                "kda_unused", "attn_gate", "qk_norm", "rope", "routing",
                "bias_update", "sequence_aux_loss", "swiglu_limits", "mtp",
                "initializer_range", "eos_token_id", "document_mask",
                "recipe", "rows", "cut", "warmup_steps", "lr_schedule"):
        assert len(conf["assumed"][key]) > 20, key
    for name in ("layer_types", "num_dense_layers", "routed_experts",
                 "attn_gate", "linear_chunk_size", "mtp_loss_weight",
                 "moe.n_group"):
        assert name in conf["program_names"], name
    assert "767,009,056" in conf["published"]["parameters"]
    opts = conf["model_options"]
    assert (opts["attn_gate"], opts["remat_policy"], opts["loss_chunk"],
            opts["remat_prevent_cse"], opts["scan_layers"]) == (
        "head", "dots_saveable+flash", 8192, True, False)
    assert conf["init_scale"] == {"embed_tokens": 50.0}
    assert conf["micro_per_device"] == 1
    opt = conf["engine"]["optimizer"]
    assert (opt["type"], opt["params"]["lr"], opt["params"]["weight_decay"],
            conf["engine"]["gradient_clipping"],
            conf["engine"]["zero_optimization"]["stage"]) == (
        "adamw8bit", 1e-4, 0.1, 1.0, 3)
    assert cell.traffic["seq_len"] == S and cell.traffic["kind"] \
        == "train_packed"
    assert cell.traffic["doc_len_lognormal"] == {"median": 400, "sigma": 1.0}
    assert (cell.traffic["doc_len_min"], cell.traffic["doc_len_max"],
            cell.traffic["token_zipf_a"], cell.traffic["eos_token_id"]) == (
        16, S, 1.1, 19647)
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02
    for key in ("kda_rel_tol", "attention_rel_tol", "expert_rel_tol",
                "dense_rel_tol"):
        assert 0 < tol[key] < 0.1, key
    assert 0 < tol["kda_grad_rel_tol"] < 0.1
    assert 0 < tol["kda_decay_grad_rel_tol"] < 0.5
    for why in ("reason", "kda_reason", "attention_reason", "expert_reason",
                "dense_reason"):
        assert len(tol[why]) > 40, why
    assert conf["trace_names"]["flash"] == "^self_attn_mla$"
    assert (conf["driver"], conf["reference"], conf["flops"]) == (
        "train_ling3", "ling3", "flops_ling3")
    # the cell holds the mechanism its ``why`` names, a decay a KEY CHANNEL,
    # and no implementation of the delta rule
    assert conf["expect_gated_delta_decay_channels"] == conf["head_dim"] \
        == 128
    small = conf["rehearse"]
    assert small["expect_gated_delta_decay_channels"] == small["head_dim"]
    assert not [k for k in conf if k.startswith("expect_gated_delta")
                and k.endswith("_impl")]
    assert len(conf["compile_said"]) > 40 and "16.01" in conf["compile_said"]


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.expert_size,
            cfg.vocab_size, cfg.padded_vocab_size, cfg.rope_theta,
            cfg.rms_norm_eps) == (2560, 6, 32, 128, 6144, 768, 19648, 19712,
                                  6e6, 1e-6)
    assert cfg.kinds == ("kda_attention",) * 5 + ("full_attention",)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rope_interleave,
            cfg.attn_gate) == (None, 512, 128, 64, 128, True, "head")
    assert (cfg.kda_lower_bound, cfg.kda_safe_gate,
            cfg.short_conv_kernel_size, cfg.linear_chunk_size) == (
        -5, True, 4, 64)
    assert (cfg.num_dense_layers, cfg.num_nextn_predict_layers,
            cfg.mtp_loss_weight, cfg.mtp_blocks) == (1, 1, 0.0, 0)
    assert (cfg.moe.num_experts, cfg.moe.routed, cfg.moe.first_expert,
            cfg.moe.top_k, cfg.moe.norm_topk_prob, cfg.moe.n_group,
            cfg.moe.topk_group, cfg.moe.route_scale, cfg.moe.score_func,
            cfg.moe.num_shared_experts, cfg.moe.bias_update_rate) == (
        8, 512, 0, 8, True, 8, 4, 2.5, "sigmoid", 1, 0.02)
    kw = driver.reference_kwargs(conf)
    assert kw.pop("layer_types")[:6] == cfg.kinds
    assert kw == {"n_layer": 6, "num_dense_layers": 1, "n_head": 32,
                  "lower_bound": -5, "kv_lora_rank": 512,
                  "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                  "v_head_dim": 128, "rope_theta": 6000000, "eps": 1e-6,
                  "top_k": 8, "route_scale": 2.5, "n_group": 8,
                  "topk_group": 4, "routed_experts": 512,
                  "vocab_size": 19648, "mtp_layers": 0, "first_expert": 0}
    # the parameters held, recounted from the program's own shapes: the
    # issue's 767,009,056 and the 64 rows that pad the table and the head
    # to a multiple of 128 (19,712 of 19,648)
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               jnp.zeros(ids.shape, jnp.int32),
                                               labels=jnp.zeros(
                                                   ids.shape, jnp.int32)))
    leaves = {jax.tree_util.keystr(p): int(np.prod(x.shape))
              for p, x in jax.tree_util.tree_flatten_with_path(
                  shapes["params"])[0]}
    count = sum(leaves.values())
    assert HELD == 767_009_056
    assert count == HELD + 2 * 64 * E == 767_336_736
    assert sum(n for p, n in leaves.items()
               if p.startswith("['layers_4']['kda_attn']")) == KDA_MIXER \
        == 63_049_888
    assert sum(n for p, n in leaves.items()
               if p.startswith("['layers_5']['self_attn']")) == MLA_MIXER \
        == 31_965_696
    assert sum(n for p, n in leaves.items()
               if p.startswith("['layers_3']['moe']")) == SPARSE_FFN \
        == 54_395_392
    small = dict(conf, **cell.config["rehearse"])
    _, tiny = driver.model_config(small)
    assert (tiny.moe.routed, tiny.moe.n_group, tiny.moe.topk_group,
            tiny.linear_chunk_size, tiny.head_dim) == (16, 4, 2, 8, 16)


# ----------------------------------------------------------------------
# required operations and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    assert (F.kda_layers(conf), F.mla_blocks(conf), F.sparse_layers(conf),
            F.mtp_blocks(conf)) == (5, 1, 5, 0)
    assert F.kda_matmul_params(conf) == 6 * E * W + E * H == 62_996_480
    assert F.mla_matmul_params(conf) == (
        E * 6144 + E * 576 + 512 * 8192 + W * E + E * H) == 31_965_184
    sparse = E * 512 + 3 * E * 768 + 8 * (8 / 512) * 3 * E * 768
    params = (5 * 62_996_480 + 31_965_184 + 3 * E * 6144 + 5 * sparse
              + 19648 * E)
    assert F.active_matmul_params(conf) == pytest.approx(params)
    # the rule by the recurrence: 7 d d a head a layer forward
    assert F.kda_flops_per_token(conf) == 7 * D * D * H * 5 == 18_350_080
    assert F.kda_flops_per_step(conf, S) == 3 * 18_350_080 * S
    # ~3.7 MFLOP a token a layer forward against ~126 MFLOP of projections
    assert 7 * D * D * H == 3_670_016
    forward = 3 * W * 2 + W * 2 + (W + H) * 4           # 49,280
    backward = forward + W * 2 + 3 * W * 2 + (W + H) * 4
    assert forward + backward == 147_840
    assert F.kda_bytes_per_step(conf, S) == 147_840 * S * 5
    # bound by memory about threefold on the v5e
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = F.roofline_seconds(F.kda_flops_per_step(conf, S),
                                      F.kda_bytes_per_step(conf, S), peak)
    assert least == pytest.approx(147_840 * S * 5 / 819e9) \
        == pytest.approx(7.39e-3, rel=1e-2)
    assert least > 3 * 3 * 18_350_080 * S / 197e12 and bound == "memory"
    # latent attention: one block, 192 + 128 channels a kept key a head
    keys = (S + 1) / 2
    attn = 3 * 2 * H * (128 + 64 + 128) * keys
    assert F.attention_flops_per_token(conf, S, 3) == pytest.approx(attn)
    assert F.causal_attention_flops_per_token(conf, S, 3) \
        == F.attention_flops_per_token(conf, S, 3)
    assert F.flash_train_bytes_per_token(conf) == 2 * (
        9 * W + 3 * W + 3 * H * 64 + 3 * 64)
    assert F.train_flops_per_token(conf, S) == pytest.approx(
        6 * params + attn + 3 * 18_350_080)
    assert F.train_flops_per_token(conf, S, held=1 / 32) \
        - F.train_flops_per_token(conf, S) == pytest.approx(
            6 * 5 * 8 * (1 / 32 - 1 / 64) * 3 * E * 768)
    rows = F.expert_rows_per_step(conf, S)
    assert rows == S * 8 / 64 == 1024           # 128 a held expert
    assert F.expert_gemm_flops_per_step(conf, S) == pytest.approx(
        9 * 2 * 1024 * E * 768 * 5)
    # weight-bound: the experts' matrices are 7x the rows they multiply
    weights, moved = 8 * E * 768, 1024 * (E + 768)
    assert weights > 4 * moved
    assert F.expert_gemm_bytes_per_step(conf, S) == pytest.approx(
        9 * (weights + moved) * 2 * 5)
    with_mtp = dict(conf, mtp_loss_scaling_factor=0.3)
    assert (F.mla_blocks(with_mtp), F.sparse_layers(with_mtp)) == (2, 6)
    assert F.active_matmul_params(with_mtp) - params == pytest.approx(
        31_965_184 + sparse + 2 * E * E + 19648 * E)


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_the_share_readers_read_the_drivers_split(cell):
    share, gate = (cell.reader(n) for n in ("linear_attn_share_pct.kda",
                                            "kda_gate_share_pct"))
    for read in (share, gate):
        assert read({}) is None and read({"device_scope_ms": {}}) is None
        assert read({"device_scope_ms": {"step": 800.0}}) is None
    ms = {"step": 800.0, "linear_attn": 480.0,
          "linear_attn/decay_gate": 24.0}
    assert share({"device_scope_ms": ms}) == pytest.approx(60.0)
    assert gate({"device_scope_ms": ms}) == pytest.approx(3.0)


def test_the_roofline_reader_reads_the_scope_or_the_kernels(cell):
    roof = cell.reader("kda_roofline")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    conf = cell.config
    obs = {"peak": peak, "cell": cell, "steps": 10, "window_s": 8.0,
           "kda_flops_per_step": F.kda_flops_per_step(conf, S),
           "kda_bytes_per_step": F.kda_bytes_per_step(conf, S),
           "device_scope_ms": {"step": 800.0,
                               "linear_attn/delta_rule": 400.0}}
    least = 147_840 * S * 5 / 819e9
    assert roof(obs) == pytest.approx(100 * least * 1e3 / 400.0)
    assert 0 < roof(obs) < 100
    assert roof(dict(obs, peak=None)) is None
    assert roof({"peak": peak, "cell": cell}) is None
    assert roof(dict(obs, device_scope_ms={"step": 800.0})) is None
    # where the rule is a kernel of its own the trace's name decides
    named = types.SimpleNamespace(
        config=dict(conf, trace_names=dict(conf["trace_names"],
                                           kda="^kda_(fwd|bwd)$")))
    trace = types.SimpleNamespace(
        window_s=4.0, ops_matching=lambda name: 0.5
        if name == "^kda_(fwd|bwd)$" else 0.0)
    assert roof(dict(obs, cell=named, trace=trace)) == pytest.approx(
        100 * least * 5 / 0.5)


def test_the_group_limits_reader_reads_the_programs_gauge(cell):
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.telemetry import get_registry

    read = cell.reader("moe_group_kept_pct")
    family = get_registry().snapshot().get("moe_group_kept_share")
    if not family or not family["samples"]:
        assert read({}) is None
    gauge = get_registry().gauge("moe_group_kept_share", "test",
                                 ("layer",))
    for child in list(get_registry().snapshot().get(
            "moe_group_kept_share", {"samples": []})["samples"]):
        gauge.labels(child["labels"]["layer"]).set(0.9)
    for layer, share in enumerate((0.7, 0.9, 0.8)):
        gauge.labels(layer).set(share)
    assert 70.0 <= read({}) <= 90.0


def test_the_drivers_split_sums_the_six_scopes():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_ling3

    table = {"device_ms_a_step": 800.0, "scopes": [
        {"scope": "layers_0/kda_attn/linear_attn/delta_rule",
         "ms_a_step": 70.0},
        {"scope": "layers_1/kda_attn/linear_attn/delta_rule",
         "ms_a_step": 80.0},
        {"scope": "layers_0/kda_attn/linear_attn/decay_gate",
         "ms_a_step": 4.0},
        {"scope": "layers_0/kda_attn/linear_attn/in_proj", "ms_a_step": 9.0},
        {"scope": "layers_5/self_attn/self_attn_mla", "ms_a_step": 30.0},
        {"scope": "loss_head", "ms_a_step": 20.0}]}
    engine = types.SimpleNamespace(
        profile_device_scopes=lambda batches, steps, depth: table)
    ctx = types.SimpleNamespace(log=lambda msg: None)
    assert train_ling3.scope_split(ctx, engine, None) == {
        "step": 800.0, "linear_attn/in_proj": 9.0, "linear_attn/conv": 0,
        "linear_attn/decay_gate": 4.0, "linear_attn/delta_rule": 150.0,
        "linear_attn/gated_norm": 0, "linear_attn/out_proj": 0,
        "linear_attn": 163.0}


KDA_REASON = ("a decay a key channel (32 heads x 128): the kernels take one "
              "decay a head")
OTHER_SITES = [("attention", "flash", "128 + 64 shared rope lanes", 3),
               ("moe_rows", "pallas", "rows 65536 x 2560", 30)]


def _stub_snapshot(channels):
    """What the registry's snapshot is to the check: with the gauge
    ``ops/gated_delta.py _note_state`` sets, or without it."""
    return {} if channels is None else {"gated_delta_decay_channels": {
        "type": "gauge", "samples": [{"labels": {}, "value": channels}]}}


@pytest.mark.parametrize("rows, channels, refused", [
    ([("gated_delta", "xla", KDA_REASON, 21)], 128.0, None),
    ([("gated_delta", "pallas", "128 chunks of 64 x 32 key heads x 1 value "
       "heads of 128, a decay a key channel, fused; one device", 21)], 128.0,
     None),
    ([("gated_delta", "pallas", "fused", 14),
      ("gated_delta", "xla", "impl='xla' asked for", 7)], 128.0, None),
    ([("gated_delta", "xla", KDA_REASON, 21)], 1.0, "not the 128"),
    ([("gated_delta", "pallas", "fused", 21)], 1.0, "not the 128"),
    ([], 128.0, "never resolved"),
    ([("gated_delta", "pallas", "fused", 21)], None, "under None"),
], ids=["xla", "pallas", "both", "one-decay-a-head-xla",
        "one-decay-a-head-pallas", "site-never-resolved",
        "program-without-the-gauge"])
def test_the_window_holds_the_delta_rules_mechanism_not_its_implementation(
        cell, rows, channels, refused):
    """The check of the mechanism passes whichever implementation resolved
    site ``gated_delta``, refuses one decay a head (the gauge reads 1) and
    refuses a run in which the site never resolved."""
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_ling3

    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    notes, logged = [], []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: bool(ok) or notes.append(what),
        log=logged.append)
    said = train_ling3.check_delta_rule(
        ctx, conf, OTHER_SITES + rows,
        train_ling3.decay_channels(_stub_snapshot(channels)))
    if refused is None:
        assert notes == []
        for _, impl, reason, n in rows:
            assert f"{impl} x {n} ({reason})" in said
        assert said in logged[-1]
    else:
        assert len(notes) == 1 and refused in notes[0]
    assert "attention" not in said and "moe_rows" not in said


@pytest.mark.parametrize("per_channel", [True, False],
                         ids=["a-decay-a-key-channel", "a-decay-a-head"])
def test_the_programs_gauge_tells_the_two_rules_apart(cell, per_channel):
    """The program's own rule at a tiny shape, on the CPU: under a decay a
    key channel the gauge reads the key head's channels and the check
    passes; under one decay a head it reads 1 and the check refuses."""
    import jax.numpy as jnp

    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_ling3
    from deepspeed_tpu.ops.gated_delta import gated_delta_rule
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.telemetry import get_registry

    B, S, H, D = 1, 16, 2, 16
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H * D)) / 4.0,
                           jnp.float32) for _ in range(3))
    g = -jnp.asarray(rng.uniform(0.1, 2.0, (B, S, H, D) if per_channel
                                 else (B, S, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (B, S, H)), jnp.float32)
    out = gated_delta_rule(q, k, v, g, beta, chunk=8)
    assert out.shape == (B, S, H * D) and bool(jnp.isfinite(out).all())
    notes = []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: bool(ok) or notes.append(what),
        log=lambda msg: None)
    conf = {"expect_gated_delta_decay_channels": D}
    train_ling3.check_delta_rule(
        ctx, conf, [r for r in dispatch_report() if r[3]],
        train_ling3.decay_channels(get_registry().snapshot()))
    if per_channel:
        assert notes == []
    else:
        assert len(notes) == 1 and f"not the {D}" in notes[0]


def test_a_program_without_the_layer_type_fails_soon_and_cleanly(
        cell, monkeypatch):
    """On a commit from before ``kda_attention`` the driver exits non-zero
    before it builds anything: the cell is then measured on the change
    alone."""
    import dataclasses

    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import llama

    driver = cell.driver()
    fields = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda c: [
        f for f in fields(c) if not (c is llama.LlamaConfig
                                     and f.name == "kda_lower_bound")])
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit) as e:
        driver.run(ctx, None)
    assert "kda_attention" in str(e.value.code) and e.value.code != 0


# ----------------------------------------------------------------------
# the comparison, at the rehearsal's sizes
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def small(cell):
    """The driver's own pieces on a seeded tiny model."""
    import jax
    from flax.core import meta

    sys.path.insert(0, ROOT)
    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    model, cfg = driver.model_config(conf)
    ids = np.random.default_rng(0).integers(0, 511, (1, 128)).astype(np.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids,
                                   labels=ids)["params"])
    params = dict(params, embed_tokens=params["embed_tokens"] * 50.0)
    kw = driver.reference_kwargs(conf)
    mixer_in, ffn_in = [], []
    reference.loss_parts(params, ids, **kw, mixer_inputs=mixer_in,
                         ffn_inputs=ffn_in)
    return driver, reference, model, cfg, conf, params, ids, kw, mixer_in, \
        ffn_in


@pytest.mark.parametrize("fault", [None, "decay_head_mean", "no_decay",
                                   "beta_one", "q_unscaled", "gate_silu",
                                   "no_dt_bias", "softplus_gate",
                                   "taps_reversed", "chunk_reset",
                                   "gate_before_norm"])
def test_the_mixers_check_refuses_each_named_fault(small, fault):
    driver, reference, model, cfg, conf, params, ids, kw, mixer_in, _ = small
    tol = conf["reference_check"]
    ctx = types.SimpleNamespace(seed=7)
    p = driver.moved(7, 4, params["layers_4"]["kda_attn"])
    h = driver.two_rows(mixer_in[4]).astype(cfg.dtype)
    errs = driver.read_kda_grads(ctx, cfg, reference, p, h, 4, kw,
                                 **({"fault": fault} if fault else {}))
    y = errs.pop("y")
    through_g = {n: errs.pop(n) for n in driver.DECAY_SIDE}
    held = (y < tol["kda_rel_tol"]
            and max(errs.values()) < tol["kda_grad_rel_tol"]
            and max(through_g.values()) < tol["kda_decay_grad_rel_tol"])
    assert held == (fault is None), (fault, y, errs, through_g)
    assert set(errs) | set(through_g) == {"dh"} | {"d" + leaf for leaf in p}


def test_the_moved_leaves_spread_the_decays_over_their_range(small):
    import jax
    import jax.numpy as jnp

    driver, reference, model, cfg, conf, params, ids, kw, mixer_in, _ = small
    p = driver.moved(7, 4, params["layers_4"]["kda_attn"])
    assert set(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(a, b)) == (a.ndim != 1),
        params["layers_4"]["kda_attn"], p))) == {True}
    g = -5.0 * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(p["A_log"]), cfg.head_dim) * (
            mixer_in[4] @ p["f_proj_kernel"] + p["dt_bias"]))
    share = [float(((g > lo) & (g <= lo + 1)).mean()) for lo in range(-5, 0)]
    assert min(share) > 0.05, share


@pytest.mark.parametrize("fault", [None, "no_gate", "rope_on_nope",
                                   "halves_on_q", "scale_nope",
                                   "no_kv_latent_norm"])
def test_the_attention_check_refuses_each_named_fault(small, fault):
    driver, reference, model, cfg, conf, params, ids, kw, mixer_in, _ = small
    tol = conf["reference_check"]["attention_rel_tol"]
    p = driver.moved(7, 5, params["layers_5"]["self_attn"])
    # fresh queries of 0.02 score every key alike: eight times as large, a
    # wrong score shows at 128 positions as it does at 8,192
    p = dict(p, q_proj_kernel=p["q_proj_kernel"] * 8.0)
    err = driver.read_attention(cfg, reference, p,
                                mixer_in[5].astype(cfg.dtype), kw,
                                **({"fault": fault} if fault else {}))
    assert (err < tol) == (fault is None), (fault, err)


@pytest.mark.parametrize("fault", [None, "no_groups", "group_max",
                                   "bias_ignored", "bias_in_weights",
                                   "no_scale", "no_shared"])
def test_the_expert_check_refuses_each_named_fault(small, fault):
    driver, reference, model, cfg, conf, params, ids, kw, _, ffn_in = small
    tol = conf["reference_check"]["expert_rel_tol"]
    leaves = driver.blocks(reference, params, cfg)
    errs, changed = driver.read_experts(
        7, cfg, reference, leaves, ffn_in, kw,
        **({"fault": fault} if fault else {}))
    assert len(errs) == 5
    if fault is None:
        assert max(errs) < tol, errs
        assert min(changed) > 0, changed        # the limit binds
    else:
        assert max(errs) > tol, (fault, errs)


def test_the_dense_and_the_loss_checks_hold(small):
    driver, reference, model, cfg, conf, params, ids, kw, _, ffn_in = small
    notes = []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: (ok or notes.append(what), ok)[1],
        log=lambda msg: None)
    driver.check_dense(ctx, cfg, conf, reference, params, ffn_in)
    assert notes == []
    wrong = types.SimpleNamespace(
        layers=reference.layers,
        dense_ffn=lambda p, h: reference.dense_ffn(
            p, h, fault="gate_up_swapped"))
    driver.check_dense(ctx, cfg, conf, wrong, params, ffn_in)
    assert len(notes) == 1 and "dense FFN" in notes[0]
    out = model.apply({"params": params}, ids, labels=ids)
    main, second = reference.loss_parts(params, ids, **kw)
    assert float(second) == 0.0
    assert abs(float(out["loss"]) - float(main)) \
        < conf["reference_check"]["loss_abs_tol"]


def test_rehearsal_of_the_ling3_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check:", "KDA check:", "KDA gradient check:",
                 "attention check:", "expert check:", "the group limit moved",
                 "dense check:", "bias check:"):
        assert said in r.stderr, said
