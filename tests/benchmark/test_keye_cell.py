"""The tenth cell, ``train-keye-dsa-32k-1chip`` (PR 54): the language model
of Keye-VL-2.0-30B-A3B, a learned top-2048 selection on every attention
layer, one packed 32,768-token row a step.  Its configuration file is the
catalog row cut three ways; the driver builds the model from the file as
data; ``flops_keye.py`` against hand-computed numbers (attention required
over the SELECTED pairs, the indexer over the causal ones); the five new
readers; each named fault refused by a check at the rehearsal's sizes; the
``--rehearse`` line ``correct``; and the manifest gained the cell at the end
of every list it joins and nothing else moved.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_keye as F
from benchmark.harness import manifest as M

ROOT = M.ROOT
CELL = "train-keye-dsa-32k-1chip"
CONFIG = "keye-vl2-30b-a3b-z3-8bit"
OLDER = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip",
         "train-joyai-flash-8k-1chip", "train-sdar-blockdiff-8k-1chip",
         "train-lfm2-hybrid-8k-1chip", "train-qwen3next-gdn-8k-1chip",
         "train-olmo-hybrid-8k-1chip"]
JOINED = ["train_step_ms", "train_mfu_pct", "flash_share_pct",
          "flash_roofline", "device_idle_pct.train", "train_host_ms",
          "train_input_ms", "train_dispatch_ms", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_init_params_s",
          "expert_gemm_share_pct", "expert_gemm_roofline",
          "moe_load_imbalance", "moe_held_pair_pct", "peak_hbm_gib",
          "step_temp_hbm_gib"]
NOT_JOINED = ["flash_window_roofline", "flash_full_roofline",
              "flash_window_share_pct", "moe_expert_bias_spread",
              "mtp_loss_excess", "diffusion_masked_pct",
              "diffusion_prep_share_pct", "short_conv_share_pct",
              "linear_attn_share_pct", "gated_delta_roofline",
              "dense_ffn_share_pct"]
NEW = ["indexer_share_pct", "indexer_roofline", "sparse_kept_pct",
       "sparse_live_tile_pct", "indexer_loss"]
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
S, K = 32768, 2048


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
        "name": CELL, "config": CONFIG, "traffic": "packed-32k-18992",
        "chips": 1, "why": manifest["workloads"][at]["why"]}
    assert len(manifest["workloads"][at]["why"]) <= 200
    assert "12.1%" in manifest["workloads"][at]["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/Kwai-Keye/" \
        "Keye-VL-2.0-30B-A3B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["train_tokens_per_s_chip"]["workloads"][:at + 1] \
        == OLDER + [CELL]
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert manifest["run_seconds"] == 50
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 0


@pytest.mark.parametrize("name", JOINED)
def test_a_metric_the_cell_joins_lists_it_behind_the_older_cells(manifest,
                                                                 name):
    metric = next(m for m in manifest["per_layer"] if m["name"] == name)
    cells = metric["workloads"]
    assert CELL in cells
    older = cells[:cells.index(CELL)]
    assert older == [c for c in OLDER if c in older] and len(older) >= 6
    assert metric["moves"] in ("train_tokens_per_s_chip", "setup_s")


@pytest.mark.parametrize("name", NOT_JOINED)
def test_a_metric_with_nothing_to_read_here_does_not_list_the_cell(manifest,
                                                                   name):
    metric = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert CELL not in metric["workloads"]


def test_the_five_new_metrics_list_this_cell_alone(manifest, cell):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    want = {"indexer_share_pct": ("%", "lower", "device_trace", "model"),
            "indexer_roofline": ("%", "higher", "device_trace", "kernels"),
            "sparse_kept_pct": ("%", "lower", "program_counter", "kernels"),
            "sparse_live_tile_pct": ("%", "lower", "program_counter",
                                     "kernels"),
            "indexer_loss": ("nats", "lower", "program_counter", "trainer")}
    for name, (unit, better, source, layer) in want.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "train_tokens_per_s_chip",
            "workloads": [CELL]}
        assert callable(cell.reader(name))
    assert [m["name"] for m in cell.per_layer] == JOINED + NEW
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_chip", "setup_s"]


@pytest.mark.parametrize("older", OLDER)
def test_an_older_cell_reads_no_new_metric(manifest, older):
    got = [m["name"] for m in M.load_cell(manifest, older, ROOT).per_layer]
    assert not set(NEW) & set(got)
    assert "train_step_ms" in got and "peak_hbm_gib" in got


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
def test_the_configuration_file_is_the_catalog_row_cut_three_ways(cell):
    conf = cell.config
    assert set(PUBLISHED) <= set(conf)
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == {"num_hidden_layers", "num_experts",
                       "vocab_size"} == set(conf["reduced"])
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (4, 16, 18992)
    for key in conf["reduced"]:
        assert conf["published"][key] == PUBLISHED[key]
    # floors: four layers (all alike), >= 8 experts, >= 1/8 of the vocabulary
    assert conf["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert conf["routed_experts"] == conf["moe"]["routed_experts"] == 128
    assert conf["moe"]["first_expert"] == 16
    assert "eight" in conf["stands_for"] and "16-31" in conf["stands_for"]
    assert "vision tower" in conf["stands_for"]
    assert "vision tower" in conf["published"]["left_out"]
    for key in ("qk_norm", "rope", "indexer", "indexer_scales", "selection",
                "chunk_sizes", "indexer_loss", "router_aux_loss_coef",
                "document_mask", "initializer_range", "recipe", "cut",
                "rows", "warmup_steps", "num_local_experts"):
        assert len(conf["assumed"][key]) > 20, key
    assert conf["model_options"]["qk_norm"] == "head"
    assert conf["model_options"]["remat_prevent_cse"] is True
    assert conf["model_options"]["indexer_loss_weight"] == 1.0
    assert cell.traffic["seq_len"] == S and cell.traffic["kind"] \
        == "train_packed"
    assert cell.traffic["doc_len_lognormal"] == {"median": 2000, "sigma": 1.2}
    assert (cell.traffic["doc_len_min"], cell.traffic["doc_len_max"],
            cell.traffic["doc_pool"], cell.traffic["token_zipf_a"]) == (
        16, S, 4096, 1.1)
    assert conf["micro_per_device"] == 1
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02 and 0 < tol["ce_abs_tol"] <= 0.02
    assert 0 < tol["indexer_loss_abs_tol"] <= 0.02
    for key in ("expert_rel_tol", "indexer_score_rel_tol", "core_rel_tol",
                "core_grad_rel_tol", "own_selection_rel_tol",
                "core_indexer_grad_rel_tol",
                "own_selection_indexer_grad_rel_tol"):
        assert 0 < tol[key] < 0.1
    # the flipped pairs' terms of dk and dv add up with no common direction
    assert 0 < tol["own_selection_grad_rel_tol"] < 0.5
    assert 0.5 < tol["selection_overlap_floor"] < 1.0
    for why in ("reason", "expert_reason", "indexer_reason",
                "selection_reason", "attention_reason", "core_reason"):
        assert len(tol[why]) > 40, why
    assert conf["trace_names"]["flash"] == "^indexed_attn_(fwd|dq|dkv)$"
    assert (conf["driver"], conf["reference"], conf["flops"]) == (
        "train_keye", "keye", "flops_keye")
    assert len(conf["compile_said"]) > 40


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.expert_size, cfg.vocab_size,
            cfg.padded_vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == (
        2048, 4, 32, 4, 128, 768, 18992, 19072, 1e7, 1e-6)
    assert cfg.qk_norm == "head" and not cfg.kinds and not cfg.mla_fields
    assert (cfg.moe.num_experts, cfg.moe.routed, cfg.moe.first_expert,
            cfg.moe.top_k, cfg.moe.norm_topk_prob) == (16, 128, 16, 8, True)
    sa = cfg.sa_config
    assert (sa.indexer_num_heads, sa.indexer_head_dim,
            sa.indexer_num_kv_heads, sa.topk) == (16, 64, 1, 2048)
    assert cfg.indexer_loss_weight == 1.0 and model.rng_streams == ()
    kw = driver.reference_kwargs(conf)
    assert kw == {"n_layer": 4, "n_head": 32, "n_kv_head": 4, "head_dim": 128,
                  "vocab_size": 18992, "top_k": 8, "norm_topk_prob": True,
                  "eps": 1e-6, "rope_theta": 10000000, "routed_experts": 128,
                  "aux_loss_weight": 0.1, "z_loss_weight": 0.0,
                  "first_expert": 16, "n_index_head": 16, "topk": 2048,
                  "indexer_loss_weight": 1.0}
    small = dict(conf, **cell.config["rehearse"])
    assert driver.model_config(small)[1].sa_config.topk == 32


# ----------------------------------------------------------------------
# required operations and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    pairs = sum(min(t + 1, K) for t in range(S))
    assert pairs == K * (K + 1) // 2 + (S - K) * K == 65012736
    assert F.kept_keys_per_token(S, K) == pytest.approx(pairs / S)
    assert F.kept_pair_share(S, K) == pytest.approx(
        pairs / (S * (S + 1) / 2)) == pytest.approx(0.1211, abs=1e-4)
    assert F.kept_keys_per_token(64, 100) == 32.5       # a short row: causal
    # a layer: QK^T and AV over the SELECTED pairs, forward + backward
    selected = F.selected_attention_flops_per_token(conf, S, 3)
    assert selected == pytest.approx(3 * 4 * 32 * 128 * (pairs / S) * 4)
    # the indexer over ALL causal pairs: 16 heads x 64 channels
    assert F.indexer_flops_per_token(conf, S, 3) == pytest.approx(
        3 * 2 * 16 * 64 * (S + 1) / 2 * 4)
    # what the attention kernels are required to do (flash_roofline): the
    # selected pairs and the two backward passes of the indexer's scores,
    # which run inside them; forward alone, the selected pairs
    assert F.causal_attention_flops_per_token(conf, S, 3) == pytest.approx(
        selected + F.indexer_flops_per_token(conf, S, 2))
    assert F.causal_attention_flops_per_token(conf, S, 1) \
        == F.selected_attention_flops_per_token(conf, S, 1)
    assert F.indexer_params(conf) == 2048 * (1024 + 64 + 16) == 2260992
    block = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2260992 + 2048 * 128
             + 3 * 2048 * 768)
    params = 4 * block + 18992 * 2048
    assert F.active_matmul_params(conf) == pytest.approx(params)
    total = F.train_flops_per_token(conf, S)
    assert total == pytest.approx(
        6 * params + selected + F.indexer_flops_per_token(conf, S, 3))
    # what a dense causal sweep multiplies is ~8x what is required
    dense = 3 * 4 * 32 * 128 * (S + 1) / 2 * 4
    assert dense / selected == pytest.approx(1 / 0.1211, rel=1e-3)
    assert F.train_flops_per_token(conf, S, held=0.25) - total \
        == pytest.approx(6 * 4 * 8 * 0.125 * 3 * 2048 * 768)
    assert F.flash_train_bytes_per_token(conf) \
        == 6 * 4 * (32 + 4) * 128 * 2 + F.indexer_bytes_per_token(conf, 2)
    assert F.indexer_bytes_per_token(conf, 3) == 4 * 3 * (
        (1024 + 64) * 2 + 16 * 4)
    rows = F.expert_rows_per_step(conf, S)
    assert rows == S * 8 / 8 == 32768           # 2,048 a held expert
    assert F.expert_gemm_flops_per_step(conf, S) == pytest.approx(
        9 * 2 * 32768 * 2048 * 768 * 4)
    assert F.expert_gemm_bytes_per_step(conf, S) == pytest.approx(
        9 * (16 * 2048 * 768 + 32768 * (2048 + 768)) * 2 * 4)


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_the_counter_readers_read_the_drivers_copies_of_the_gauges(cell):
    kept, live, loss = (cell.reader(n) for n in (
        "sparse_kept_pct", "sparse_live_tile_pct", "indexer_loss"))
    assert kept({}) is None and live({}) is None and loss({}) is None
    obs = {"sparse_kept_share": 0.1211, "sparse_live_tile_share": 1.0,
           "indexer_loss": 1.75}
    assert kept(obs) == pytest.approx(12.11)
    assert live(obs) == 100.0 and loss(obs) == 1.75


def test_the_driver_copies_the_gauges_the_model_sets():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_keye
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    LlamaForCausalLM.record_step_stats({
        "indexer_loss": np.float32(1.5),
        "indexer_kept_share": np.float32(0.25),
        "indexer_live_tile_share": np.float32(0.75)})
    assert train_keye.gauges() == {
        "indexer_loss": 1.5, "sparse_kept_share": 0.25,
        "sparse_live_tile_share": 0.75}


def test_the_share_and_the_roofline_from_the_drivers_split(cell):
    share, roof = cell.reader("indexer_share_pct"), cell.reader(
        "indexer_roofline")
    assert share({}) is None and share({"device_scope_ms": {}}) is None
    assert share({"device_scope_ms": {"step": 2000.0}}) is None
    assert share({"device_scope_ms": {"indexer": 50.0, "step": 2000.0}}) \
        == pytest.approx(2.5)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    conf = cell.config
    obs = {"peak": peak, "device_scope_ms": {"indexer": 50.0, "step": 2000.0},
           "indexer_flops_per_step": S * F.indexer_flops_per_token(conf, S, 1),
           "indexer_bytes_per_step": S * F.indexer_bytes_per_token(conf)}
    least = S * F.indexer_flops_per_token(conf, S, 1) / 197e12
    assert least == pytest.approx(0.0223, abs=2e-4)     # 4.4 TFLOP a step
    assert roof(obs) == pytest.approx(100 * least * 1e3 / 50.0)
    assert 0 < roof(obs) < 100
    assert roof(dict(obs, peak=None)) is None
    assert roof({"peak": peak, "device_scope_ms": {"step": 1.0}}) is None


def test_the_drivers_split_sums_the_three_scopes():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_keye

    table = {"device_ms_a_step": 2000.0, "scopes": [
        {"scope": "layers_0/self_attn/attn/indexer", "ms_a_step": 3.0},
        {"scope": "layers_1/self_attn/attn/indexer", "ms_a_step": 2.0},
        {"scope": "layers_0/self_attn/attn/select", "ms_a_step": 30.0},
        {"scope": "layers_0/self_attn/attn/indexer_loss", "ms_a_step": 0.5},
        {"scope": "layers_0/self_attn/self_attn_indexed", "ms_a_step": 400.0},
        {"scope": "loss_head", "ms_a_step": 20.0}]}
    engine = types.SimpleNamespace(
        profile_device_scopes=lambda batches, steps, depth: table)
    ctx = types.SimpleNamespace(log=lambda msg: None)
    assert train_keye.scope_split(ctx, engine, None) == {
        "step": 2000.0, "attn/indexer": 5.0, "attn/select": 30.0,
        "attn/indexer_loss": 0.5, "indexer": 35.5}


def test_a_program_without_the_section_fails_soon_and_cleanly(cell,
                                                              monkeypatch):
    """On a commit from before ``sa_config`` the driver exits non-zero
    before it builds anything: the cell is then measured on the change
    alone."""
    import dataclasses

    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import llama

    driver = cell.driver()
    fields = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda c: [
        f for f in fields(c) if not (c is llama.LlamaConfig
                                     and f.name == "sa_config")])
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit) as e:
        driver.run(ctx, None)
    assert "sa_config" in str(e.value.code) and e.value.code != 0


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
    ids = np.random.default_rng(0).integers(0, 511, (1, 256)).astype(np.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids,
                                   labels=ids)["params"])
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, params)
    kw = driver.reference_kwargs(conf)
    attn_in = []
    reference.loss_parts(params, ids, **kw, attn_inputs=attn_in)
    h = attn_in[0].astype(cfg.dtype)
    p_attn = params["layers_0"]["self_attn"]
    mine = driver.program_indexer(cfg, p_attn, h)
    return (driver, reference, model, cfg, conf, params, ids, kw, h, p_attn,
            mine)


def _theirs(reference, p_attn, h, kw, **wrong):
    t = reference.indexer(p_attn, h, **kw, **wrong)
    return t + (reference.selection(*t, topk=kw["topk"], **wrong),)


@pytest.mark.parametrize("fault", [None, "noncausal_topk", "half_topk",
                                   "no_relu", "no_w", "no_key_norm",
                                   "no_indexer_rope"])
def test_the_selection_check_refuses_each_indexer_fault(small, fault):
    driver, reference, model, cfg, conf, params, ids, kw, h, p_attn, mine \
        = small
    wrong = {"fault": fault} if fault else {}
    tol = conf["reference_check"]
    read = driver.read_indexer(cfg, reference, mine,
                               _theirs(reference, p_attn, h, kw, **wrong),
                               kw["topk"], 128)
    if fault is None:
        assert read["overlap"] > tol["selection_overlap_floor"]
        assert read["scores"] < tol["indexer_score_rel_tol"]
    else:
        assert read["overlap"] < tol["selection_overlap_floor"], read


@pytest.mark.parametrize("fault", [None, "dense", "kv_mod", "loss_all_causal",
                                   "no_relu"])
def test_the_core_check_refuses_what_the_loss_cannot_see(small, fault):
    """Under the reference's selection the kernels' side is exact to the
    compute type, and under their own it is the backward the window runs.
    The selection ignored, or the wrong key-value head, shows in the output
    and in every gradient under both; the indexer's loss over all causal
    keys, or its ReLU left out, shows in the indexer's loss and in the
    gradients of qI, kI and w alone, which nothing else in the cell
    differentiates."""
    driver, reference, model, cfg, conf, params, ids, kw, h, p_attn, mine \
        = small
    wrong = {"fault": fault} if fault else {}
    tol = conf["reference_check"]
    core = driver.read_core(cfg, reference, reference.qkv(p_attn, h, **kw),
                            mine, _theirs(reference, p_attn, h, kw),
                            kw["topk"], 3, **wrong)
    assert set(core) == {own + name for own in ("", "own ")
                         for name in driver.CORE_NAMES}
    over = {n for n, e in core.items()
            if not abs(e) <= driver.core_limit(tol, n)}
    indexers = {own + name for own in ("", "own ")
                for name in ("L_I", "dqI", "dkI", "dw")}
    if fault is None:
        assert not over, core
        assert max(core[n] for n in ("out", "dq", "dk", "dv")) \
            < tol["core_rel_tol"] / 2
    elif fault in ("dense", "kv_mod"):
        assert over >= set(core) - {"L_I", "own L_I"}, core
        assert min(core[n] for n in ("out", "dq", "dk", "dv")) \
            > 5 * tol["core_grad_rel_tol"], core
    else:
        assert over == indexers, core


@pytest.mark.parametrize("fault", [None, "loss_all_causal", "dense"])
def test_the_layer_check_holds_the_output_and_the_indexers_loss(small, fault):
    driver, reference, model, cfg, conf, params, ids, kw, h, p_attn, mine \
        = small
    wrong = {"fault": fault} if fault else {}
    tol = conf["reference_check"]
    read = driver.read_layer(cfg, reference, p_attn, h, kw, **wrong)
    if fault is None:
        assert read["out"] < tol["attention_rel_tol"]
        assert abs(read["indexer_loss"]) < tol["layer_indexer_loss_abs_tol"]
    elif fault == "loss_all_causal":
        assert abs(read["indexer_loss"]) > tol["layer_indexer_loss_abs_tol"]
    else:
        assert read["out"] > tol["attention_rel_tol"]


@pytest.mark.parametrize("fault", [None, "loss_all_causal"])
def test_the_loss_check_sees_the_indexers_loss_alone(small, fault):
    driver, reference, model, cfg, conf, params, ids, kw, h, p_attn, mine \
        = small
    out = model.apply({"params": params}, ids, labels=ids)
    ce, aux, idx = (float(x) for x in reference.loss_parts(
        params, ids, **kw, **({"fault": fault} if fault else {})))
    tol = conf["reference_check"]
    off = abs(float(out["indexer_loss"]) - idx)
    if fault is None:
        assert off < tol["indexer_loss_abs_tol"] / 4
        assert abs(float(out["loss"]) - ce - aux - idx) \
            < tol["loss_abs_tol"] / 2
    else:
        assert off > tol["indexer_loss_abs_tol"]


def test_the_expert_check_holds_every_layer_to_the_share(small):
    driver, reference, model, cfg, conf, params, ids, kw, h, p_attn, mine \
        = small
    ffn_in = []
    reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in)
    assert len(ffn_in) == 4 and ffn_in[0].shape == (1, 256, 64)
    notes = []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: (ok or notes.append(what), ok)[1],
        log=lambda msg: None)
    from benchmark.drivers import train_mellum2

    train_mellum2.check_experts(ctx, cfg, conf, reference, params, ffn_in)
    assert notes == []
    other = types.SimpleNamespace(
        layers=reference.layers,
        expert_ffn=lambda p, h, **kw: reference.expert_ffn(
            p, h, **dict(kw, first_expert=kw["first_expert"] + 4)))
    train_mellum2.check_experts(ctx, cfg, conf, other, params, ffn_in)
    assert len(notes) == 1 and "sparse FFN" in notes[0]


def test_rehearsal_of_the_keye_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check:", "expert check:", "indexer check:",
                 "attention check:", "attention core check, under the "
                 "reference's selection"):
        assert said in r.stderr, said
