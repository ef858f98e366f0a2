"""The sixth cell, ``train-sdar-blockdiff-8k-1chip`` (PR 40): SDAR-30B-A3B
under block-diffusion training.  Its configuration file is the catalog row
cut three ways with the objective's section beside it; the driver builds
the model from the file as data; ``flops_sdar.py`` against hand-computed
numbers (a data token runs two positions, ``L (L + g)`` kept pairs a row,
the head at the masked positions alone); both new readers on a made-up
registry and split; each named fault refused by its check at the
rehearsal's sizes; the ``--rehearse`` line ``correct``; and the manifest
gained the cell at the end of every list it joins and nothing else moved.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_sdar as F
from benchmark.harness import manifest as M
from benchmark.layer_metrics import (diffusion_masked_pct,
                                     diffusion_prep_share_pct)

ROOT = M.ROOT
CELL = "train-sdar-blockdiff-8k-1chip"
CONFIG = "sdar-30b-a3b-z3-8bit"
OLDER = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip",
         "train-joyai-flash-8k-1chip"]
JOINED = ["train_step_ms", "train_mfu_pct", "flash_share_pct",
          "flash_roofline", "device_idle_pct.train", "train_host_ms",
          "train_input_ms", "train_dispatch_ms", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_init_params_s",
          "expert_gemm_share_pct", "expert_gemm_roofline",
          "moe_load_imbalance", "moe_held_pair_pct", "peak_hbm_gib",
          "step_temp_hbm_gib"]
NOT_JOINED = ["flash_window_roofline", "flash_full_roofline",
              "flash_window_share_pct", "moe_expert_bias_spread",
              "mtp_loss_excess"]
NEW = ["diffusion_masked_pct", "diffusion_prep_share_pct"]
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


def is_prefix(short, long) -> bool:
    return list(long[:len(short)]) == list(short)


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
def test_the_manifest_gained_one_configuration_and_one_cell(manifest):
    assert is_prefix(OLDER, [w["name"] for w in manifest["workloads"]])
    at = len(OLDER)
    assert manifest["workloads"][at] == {
        "name": CELL, "config": CONFIG, "traffic": "packed-8k-19k",
        "chips": 1, "why": manifest["workloads"][at]["why"]}
    assert len(manifest["workloads"][at]["why"]) <= 200
    entry = manifest["configs"][at]
    assert entry["name"] == CONFIG and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/JetLM/" \
        "SDAR-30B-A3B-Chat/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["train_tokens_per_s_chip"]["workloads"][:at + 1] \
        == OLDER + [CELL]
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert manifest["run_seconds"] == 50


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


def test_the_two_new_metrics_stand_last_and_list_this_cell_alone(manifest,
                                                                 cell):
    assert [m["name"] for m in manifest["per_layer"][-2:]] == NEW
    assert manifest["per_layer"][-2] == {
        "name": "diffusion_masked_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s_chip", "workloads": [CELL]}
    assert manifest["per_layer"][-1] == {
        "name": "diffusion_prep_share_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "model",
        "moves": "train_tokens_per_s_chip", "workloads": [CELL]}
    assert [m["name"] for m in cell.per_layer] == JOINED + NEW
    for name in NEW:
        assert callable(cell.reader(name))
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_chip", "setup_s"]


@pytest.mark.parametrize("older", OLDER)
def test_an_older_cell_reads_neither_new_metric(manifest, older):
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
    assert "INNER stage" in conf["stands_for"]
    dif = conf["diffusion"]
    assert dif == {"block_length": 4, "mask_token_id": 18991, "t_min": 0.001,
                   "loss_weight": "inv_t"}
    assert cell.traffic["eos_token_id"] == 18990 < dif["mask_token_id"] \
        < conf["vocab_size"]
    for key in ("block_length", "noise_schedule", "loss_weight", "no_shift",
                "mask_token_id", "eos_token_id", "qk_norm",
                "router_aux_loss_coef", "document_mask", "initializer_range",
                "recipe", "cut", "rows"):
        assert len(conf["assumed"][key]) > 20, key
    assert "the config has no key" in conf["assumed"]["qk_norm"]
    assert "not_given" in conf["assumed"]["block_length"]
    assert "not_given" in conf["assumed"]["noise_schedule"]
    assert conf["model_options"]["qk_norm"] == "head"
    assert conf["model_options"]["remat_prevent_cse"] is True
    assert conf["model_options"]["loss_chunk"] == 8192
    assert cell.traffic["seq_len"] == 8192 and cell.traffic["kind"] \
        == "train_packed"
    assert conf["micro_per_device"] * cell.traffic["seq_len"] == 16384
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02
    for key in ("expert_rel_tol", "attention_rel_tol", "core_rel_tol",
                "core_grad_rel_tol"):
        assert 0 < tol[key] < 0.1
    for why in ("reason", "expert_reason", "attention_reason", "core_reason"):
        assert len(tol[why]) > 40
    assert tol["masked_pct_range"] == [45.0, 55.0]
    assert conf["trace_names"] == {"flash": "^self_attn_blockdiff$",
                                   "train_module": "^jit_step_fn$",
                                   "expert_gemm": "^t?gmm$"}
    assert (conf["driver"], conf["reference"], conf["flops"]) == (
        "train_sdar", "sdar", "flops_sdar")


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.expert_size, cfg.vocab_size,
            cfg.padded_vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == (
        2048, 4, 32, 4, 128, 768, 18992, 19072, 1e6, 1e-6)
    assert cfg.qk_norm == "head" and not cfg.kinds and not cfg.mla_fields
    assert (cfg.moe.num_experts, cfg.moe.routed, cfg.moe.first_expert,
            cfg.moe.top_k, cfg.moe.norm_topk_prob) == (16, 128, 16, 8, True)
    dif = cfg.diffusion
    assert (dif.block_length, dif.mask_token_id, dif.t_min,
            dif.loss_weight) == (4, 18991, 0.001, "inv_t")
    assert model.rng_streams == ("diffusion",)
    kw = driver.reference_kwargs(conf)
    assert kw == {"n_layer": 4, "n_head": 32, "n_kv_head": 4, "head_dim": 128,
                  "vocab_size": 18992, "top_k": 8, "norm_topk_prob": True,
                  "eps": 1e-6, "rope_theta": 1000000, "routed_experts": 128,
                  "aux_loss_weight": 0.1, "z_loss_weight": 0.0,
                  "first_expert": 16, "block_length": 4,
                  "mask_token_id": 18991}
    # the rehearsal's sizes build too, with the section
    small = dict(conf, **cell.config["rehearse"])
    assert driver.model_config(small)[1].diffusion.mask_token_id == 511


def test_the_data_never_draws_the_mask_id(cell):
    sys.path.insert(0, ROOT)
    from benchmark import loadgen

    driver = cell.driver()
    seen = []

    def theirs(mix, seed, rows, vocab_size):
        seen.append(vocab_size)
        return iter(())

    driver._data_batches(theirs)({}, 1, 2, 18992)
    assert seen == [18991]
    mix = dict(cell.traffic, **cell.traffic["rehearse"])
    batch = next(driver._data_batches(loadgen.packed_batches)(mix, 3, 4, 512))
    assert batch["input_ids"].max() <= 510 and batch["input_ids"].shape \
        == (4, 128)


def test_the_seeded_noise_is_the_models_kind_of_noise(cell):
    driver = cell.driver()
    conf = cell.config
    mask, t = driver.seeded_noise(2**31 + 5, 2, 8192, conf)
    assert mask.shape == (2, 8192) and t.shape == (2, 2048)
    assert mask.dtype == bool and t.dtype == np.float32
    assert 0.001 < t.min() and t.max() <= 1.0
    assert 0.48 < mask.mean() < 0.52 and abs(t.mean() - 0.5) < 0.02
    again = driver.seeded_noise(2**31 + 5, 2, 8192, conf)
    assert (again[0] == mask).all() and (again[1] == t).all()
    assert (driver.seeded_noise(7, 2, 8192, conf)[0] != mask).any()


# ----------------------------------------------------------------------
# required operations and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    L, g = 8192, 4
    assert F.kept_pairs_per_row(L, g) == L * (L + g) == 67141632
    assert F.kept_pairs_per_row(L, g) == (L * (L + g) // 2
                                          + L * (L - g) // 2 + L * g)
    assert F.kept_keys_per_token(L, g) == 8196
    # brute force at a small size: the mask's kept entries
    sys.path.insert(0, ROOT)
    reference = cell.reference()
    assert reference.attention_mask(64, 4).sum() == F.kept_pairs_per_row(64, 4)
    assert reference.attention_mask(96, 32).sum() \
        == F.kept_pairs_per_row(96, 32)
    # a layer: 4 * 32 * 128 * 8196 * 3 forward + backward
    assert F.attention_flops_per_token(conf, L, 3) == pytest.approx(
        4 * 4 * 32 * 128 * 8196 * 3)
    assert F.attention_flops_per_token(conf, L, 3) / 4 == pytest.approx(
        4.03e8, rel=1e-3)
    assert F.causal_attention_flops_per_token(conf, L, 3) \
        == F.attention_flops_per_token(conf, L, 3)
    # a position of a block: q, o 2 x 2048 x 4096; k, v 2 x 2048 x 512; the
    # router 2048 x 128; one expert's worth (8 x 16/128) of 3 x 2048 x 768
    block = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 + 3 * 2048 * 768
    assert F.block_matmul_params(conf) == block == 23855104
    assert F.masked_share(conf) == pytest.approx(0.5005)
    params = 2 * 4 * block + 0.5005 * 18992 * 2048
    assert F.active_matmul_params(conf) == pytest.approx(params)
    total = F.train_flops_per_token(conf, L)
    assert total == pytest.approx(6 * params + 4 * 4 * 32 * 128 * 8196 * 3)
    assert total == pytest.approx(2.87e9, rel=5e-3)
    assert F.attention_flops_per_token(conf, L, 3) / total == pytest.approx(
        0.56, abs=0.01)
    # what the counters read moves what was required
    assert F.train_flops_per_token(conf, L, held=0.25) - total \
        == pytest.approx(6 * 2 * 4 * 8 * 0.125 * 3 * 2048 * 768)
    assert F.train_flops_per_token(conf, L, masked=0.25) - total \
        == pytest.approx(6 * (0.25 - 0.5005) * 18992 * 2048)
    assert F.flash_train_bytes_per_token(conf) \
        == 2 * 6 * 4 * (32 + 4) * 128 * 2
    rows = F.expert_rows_per_step(conf, 16384)
    assert rows == 2 * 16384 * 8 / 8 == 32768     # 2,048 a held expert
    assert F.expert_gemm_flops_per_step(conf, 16384) == pytest.approx(
        9 * 2 * 32768 * 2048 * 768 * 4)
    assert F.expert_gemm_bytes_per_step(conf, 16384) == pytest.approx(
        9 * (16 * 2048 * 768 + 32768 * (2048 + 768)) * 2 * 4)
    assert F.expert_gemm_flops_per_step(conf, 16384, held=0.25) \
        == 2 * F.expert_gemm_flops_per_step(conf, 16384)


# ----------------------------------------------------------------------
# the two readers
# ----------------------------------------------------------------------
@pytest.fixture()
def empty_registry():
    """The program's registry emptied for one test and put back after it."""
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    yield reg
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


def test_the_masked_share_from_the_programs_counter(cell, empty_registry):
    read = cell.reader("diffusion_masked_pct")
    assert read.__module__.endswith("diffusion_masked_pct")
    assert read({}) is None                     # a program without it
    tokens = empty_registry.counter("diffusion_tokens_total", "x", ("kind",))
    tokens.labels("masked").inc(0)
    assert read({}) is None                     # booked, nothing counted
    tokens.labels("masked").inc(300)
    tokens.labels("kept").inc(700)
    assert read({}) == pytest.approx(30.0)
    # the driver's difference over the window wins over the totals
    assert read({"diffusion_tokens": {"masked": 51.0, "kept": 49.0}}) \
        == pytest.approx(51.0)
    assert diffusion_masked_pct.totals() == {"masked": 300.0, "kept": 700.0}


def test_the_model_books_the_counter_and_the_gauge(empty_registry):
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    for _ in range(2):
        LlamaForCausalLM.record_step_stats({
            "diffusion_masked": np.int32(40), "diffusion_kept": np.int32(60),
            "diffusion_t_mean": np.float32(0.41)})
    assert diffusion_masked_pct.totals() == {"masked": 80.0, "kept": 120.0}
    snap = empty_registry.snapshot()
    assert snap["diffusion_t_mean"]["samples"][0]["value"] \
        == pytest.approx(0.41)


def test_the_prep_share_from_the_drivers_split(cell):
    read = cell.reader("diffusion_prep_share_pct")
    assert read.__module__.endswith("diffusion_prep_share_pct")
    assert read({}) is None and read({"device_scope_ms": {}}) is None
    assert read({"device_scope_ms": {"step": 0.0}}) is None
    assert read({"device_scope_ms": {"diffusion": 1.5, "step": 600.0}}) \
        == pytest.approx(0.25)
    assert read({"device_scope_ms": {"step": 600.0}}) == 0.0
    assert diffusion_prep_share_pct.read is read or True


def test_the_drivers_split_sums_the_diffusion_scopes():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_sdar

    table = {"device_ms_a_step": 500.0, "scopes": [
        {"scope": "layers_*", "pass": "forward", "ms_a_step": 300.0},
        {"scope": "diffusion", "pass": "forward", "ms_a_step": 1.0},
        {"scope": "diffusion", "pass": "backward", "ms_a_step": 0.5},
        {"scope": "loss_head", "pass": "forward", "ms_a_step": 20.0}]}
    engine = types.SimpleNamespace(
        profile_device_scopes=lambda batches, steps, depth: table)
    ctx = types.SimpleNamespace(log=lambda msg: None)
    assert train_sdar.scope_split(ctx, engine, None) == {
        "diffusion": 1.5, "step": 500.0}


def test_a_program_without_the_registry_gives_none(monkeypatch):
    import builtins

    real = builtins.__import__

    def refuse(name, *a, **k):
        if name.startswith("deepspeed_tpu.telemetry"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", refuse)
    assert diffusion_masked_pct.read({}) is None


def test_a_program_without_the_section_fails_soon_and_cleanly(cell,
                                                              monkeypatch):
    """On a commit from before the objective the driver exits non-zero
    before it builds anything: the cell is then measured on the change
    alone."""
    import dataclasses

    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import llama

    driver = cell.driver()
    fields = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda c: [
        f for f in fields(c) if not (c is llama.LlamaConfig
                                     and f.name == "diffusion")])
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit) as e:
        driver.run(ctx, None)
    assert "diffusion" in str(e.value.code) and e.value.code != 0


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
    ids = np.random.default_rng(0).integers(0, 511, (2, 128)).astype(np.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids,
                                   labels=ids)["params"])
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, params)
    mask, t = driver.seeded_noise(5, 2, 128, conf)
    kw = driver.reference_kwargs(conf)
    h = reference.first_attention_input(
        params, ids, mask, mask_token_id=kw["mask_token_id"],
        eps=kw["eps"]).astype(cfg.dtype)
    return driver, reference, model, cfg, conf, params, ids, mask, t, kw, h


@pytest.mark.parametrize("fault", [None, "clean_causal",
                                   "noisy_sees_own_clean", "kv_mod"])
def test_the_attention_checks_refuse_each_named_fault(small, fault):
    """The layer and the core alone, each half read apart: the clean half
    made plain causal shows in the clean half alone, the noisy half shown
    its own block's clean keys in the noisy half alone."""
    driver, reference, model, cfg, conf, params, ids, mask, t, kw, h = small
    wrong = {"fault": fault} if fault else {}
    p_attn = params["layers_0"]["self_attn"]
    limit = conf["reference_check"]["attention_rel_tol"]
    noisy, clean = driver.read_attention(cfg, reference, p_attn, h[:1], kw,
                                         **wrong)
    core = driver.read_core(cfg, reference, p_attn, h, kw, 3, **wrong)
    assert set(core) == {f"{n} {half}" for n in ("out", "dq", "dk", "dv")
                         for half in ("noisy", "clean")}
    if fault is None:
        assert max(noisy, clean) < limit / 2 and max(core.values()) < limit / 2
    elif fault == "clean_causal":
        assert clean > limit and noisy < limit / 2
        assert core["out clean"] > limit and core["out noisy"] < limit / 2
        assert core["dq clean"] > limit and core["dk clean"] > limit
    elif fault == "noisy_sees_own_clean":
        assert noisy > limit and clean < limit / 2
        assert core["out noisy"] > limit and core["out clean"] < limit / 2
        assert core["dq noisy"] > limit and core["dk clean"] > limit
    else:
        assert min(noisy, clean) > limit and min(core.values()) > limit


@pytest.mark.parametrize("fault", [None, "no_weight", "clean_loss",
                                   "clean_causal", "noisy_sees_own_clean"])
def test_the_loss_check_refuses_the_faults_it_can_see(small, fault):
    """The weight dropped and the loss read from the clean half fail the
    loss's own limit; the two mask faults are the attention checks'."""
    driver, reference, model, cfg, conf, params, ids, mask, t, kw, h = small
    got = float(model.apply({"params": params}, ids, labels=ids,
                            diffusion_mask=mask, diffusion_t=t)["loss"])
    ce, aux = reference.loss_parts(params, ids, mask, t, **kw,
                                   **({"fault": fault} if fault else {}))
    off = abs(got - float(ce) - float(aux))
    limit = conf["reference_check"]["loss_abs_tol"]
    if fault in ("no_weight", "clean_loss"):
        assert off > limit, (fault, off)
    elif fault is None:
        assert off < limit / 4, off


def test_the_expert_check_holds_every_layer_to_the_share(small):
    driver, reference, model, cfg, conf, params, ids, mask, t, kw, h = small
    ffn_in = []
    reference.loss_parts(params, ids[:1], mask[:1], t[:1], **kw,
                         ffn_inputs=ffn_in)
    assert len(ffn_in) == 4 and ffn_in[0].shape == (1, 256, 64)
    notes = []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: (ok or notes.append(what), ok)[1],
        log=lambda msg: None)
    from benchmark.drivers import train_mellum2

    train_mellum2.check_experts(ctx, cfg, conf, reference, params, ffn_in)
    assert notes == []
    # a reference that holds the NEXT share: refused
    other = types.SimpleNamespace(
        layers=reference.layers,
        expert_ffn=lambda p, h, **kw: reference.expert_ffn(
            p, h, **dict(kw, first_expert=kw["first_expert"] + 4)))
    train_mellum2.check_experts(ctx, cfg, conf, other, params, ffn_in)
    assert len(notes) == 1 and "sparse FFN" in notes[0]


def test_the_windows_count_is_held_to_the_range(cell, empty_registry):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = dict(cell.config)
    tokens = empty_registry.counter("diffusion_tokens_total", "x", ("kind",))
    tokens.labels("masked").inc(1000)
    tokens.labels("kept").inc(1000)
    before = diffusion_masked_pct.totals()
    tokens.labels("masked").inc(8192 * 2 * 10 * 0.3)
    tokens.labels("kept").inc(8192 * 2 * 10 * 0.7)
    notes = []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: (ok or notes.append(what), bool(ok))[1],
        log=lambda msg: None, cell=cell,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    out = {"observed": {"tokens": 8192 * 2 * 10, "steps": 10, "n_devices": 1,
                        "flops_per_token": F.train_flops_per_token(conf,
                                                                   8192)}}
    driver.count_the_windows_noise(ctx, out, before, conf)
    assert len(notes) == 1 and "outside 45.0-55.0%" in notes[0]
    obs = out["observed"]
    assert obs["diffusion_tokens"]["masked"] == pytest.approx(49152)
    assert diffusion_masked_pct.read(obs) == pytest.approx(30.0)
    assert obs["flops_per_token"] == pytest.approx(
        F.train_flops_per_token(conf, 8192, None, 0.3))
    driver.count_the_windows_noise(ctx, out, None, conf)
    assert len(notes) == 2 and "booked no" in notes[1]


def test_rehearsal_of_the_sdar_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check:", "expert check:", "attention check:",
                 "attention core check on 2 rows", "data tokens of the "
                 "window were masked"):
        assert said in r.stderr, said
