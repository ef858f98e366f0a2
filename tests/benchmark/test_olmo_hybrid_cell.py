"""The ninth cell, ``train-olmo-hybrid-8k-1chip`` (PR 52): Olmo-Hybrid-7B,
the first stack whose delta-rule states are not square (96 x 192) and the
first ``layer_types`` stack with no experts.  Its configuration file is the
catalog row cut two ways (depth, vocabulary) and in no width; the
parameters held are recounted from the program's own shapes; the driver
builds the model from the file as data; ``flops_olmo_hybrid.py`` against
hand-computed numbers; the new reader on made-up observations; each named
fault refused by its check at the rehearsal's sizes; the parent's refusal
soon and clean; the ``--rehearse`` line ``correct``; and the manifest gained
the cell behind the older ones in every list it joins.  Nothing here pins a
list's END: a later cell appends behind this one and these tests stand.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_olmo_hybrid as F
from benchmark.harness import manifest as M

ROOT = M.ROOT
CELL = "train-olmo-hybrid-8k-1chip"
CONFIG = "olmo-hybrid-7b-z3-8bit"
OLDER = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip",
         "train-joyai-flash-8k-1chip", "train-sdar-blockdiff-8k-1chip",
         "train-lfm2-hybrid-8k-1chip", "train-qwen3next-gdn-8k-1chip"]
JOINED = ["train_step_ms", "train_mfu_pct", "flash_share_pct",
          "flash_roofline", "device_idle_pct.train", "train_host_ms",
          "train_input_ms", "train_dispatch_ms", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_init_params_s", "peak_hbm_gib",
          "step_temp_hbm_gib"]
NOT_JOINED = ["expert_gemm_share_pct", "expert_gemm_roofline",
              "moe_load_imbalance", "moe_held_pair_pct",
              "flash_window_roofline", "flash_full_roofline",
              "flash_window_share_pct", "moe_expert_bias_spread",
              "mtp_loss_excess", "diffusion_masked_pct",
              "diffusion_prep_share_pct", "short_conv_share_pct",
              "short_conv_filter_roofline",
              # the eighth cell's own two: its test holds them to that cell
              # alone, so the ninth reads them under the names below
              "linear_attn_share_pct", "gated_delta_roofline"]
MODEL = {"unit": "%", "better": "lower", "source": "device_trace",
         "layer": "model", "moves": "train_tokens_per_s_chip"}
NEW = {
    "linear_attn_share_pct.96x192": MODEL,
    "gated_delta_roofline.96x192": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "train_tokens_per_s_chip"},
    "dense_ffn_share_pct": MODEL}
LINEAR, FULL = "linear_attention", "full_attention"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": [LINEAR] * 3 + [FULL],
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
PUBLISHED["layer_types"] = PUBLISHED["layer_types"] * 8
CUT = {"num_hidden_layers": 4, "vocab_size": 12544}
E, I, S = 3840, 11008, 8192


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


def _entry(entries, name):
    return next(e for e in entries if e["name"] == name)


def _names(entries):
    return [e["name"] for e in entries]


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
def test_the_manifest_gained_one_configuration_and_one_cell(manifest):
    cells = _names(manifest["workloads"])
    assert cells[:len(OLDER)] == OLDER and cells[len(OLDER)] == CELL
    entry = _entry(manifest["workloads"], CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "packed-8k-12544", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "96 x 192" in entry["why"] \
        and "head 5%" in entry["why"]
    assert _names(manifest["configs"]).index(CONFIG) == len(OLDER)
    conf = _entry(manifest["configs"], CONFIG)
    assert conf["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/" \
        "blob/main/config.json" and len(conf["why"]) <= 200
    assert conf["reduced"] == list(CUT)
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["train_tokens_per_s_chip"]["workloads"][:len(OLDER) + 1] \
        == OLDER + [CELL]
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert manifest["run_seconds"] == 50
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 0


@pytest.mark.parametrize("name", JOINED)
def test_a_metric_the_cell_joins_lists_it_behind_the_older_cells(manifest,
                                                                 name):
    cells = _entry(manifest["per_layer"], name)["workloads"]
    assert CELL in cells
    older = cells[:cells.index(CELL)]
    assert older == [c for c in OLDER if c in older] and len(older) >= 2


@pytest.mark.parametrize("name", NOT_JOINED)
def test_a_metric_with_nothing_to_read_here_does_not_list_the_cell(manifest,
                                                                   name):
    assert CELL not in _entry(manifest["per_layer"], name)["workloads"]


def test_the_new_metrics_stand_behind_what_the_cell_joined(manifest, cell):
    """What this cell brought keeps its order, stands behind every metric it
    joined, and lists this cell alone.  The delta rule's two are the eighth
    cell's readers under a suffixed name (``Cell.reader`` takes the part of
    a name before the first '.'): the same files read both cells."""
    names = _names(manifest["per_layer"])
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and at[-1] - at[0] == len(NEW) - 1
    assert max(names.index(n) for n in JOINED + NOT_JOINED) < at[0]
    for name, rest in NEW.items():
        assert _entry(manifest["per_layer"], name) == dict(
            name=name, **rest, workloads=[CELL])
        assert callable(cell.reader(name))
    eighth = M.load_cell(manifest, OLDER[-1], ROOT)
    for name in ("linear_attn_share_pct", "gated_delta_roofline"):
        assert cell.reader(name + ".96x192").__code__.co_filename \
            == eighth.reader(name).__code__.co_filename
        old, new = (dict(_entry(manifest["per_layer"], n)) for n in (
            name, name + ".96x192"))
        for e in (old, new):
            del e["name"], e["workloads"]
        assert old == new
    assert _names(cell.per_layer) == JOINED + list(NEW)
    assert _names(cell.end_to_end) == ["train_tokens_per_s_chip", "setup_s"]


@pytest.mark.parametrize("older", OLDER)
def test_an_older_cell_reads_no_new_metric(manifest, older):
    got = _names(M.load_cell(manifest, older, ROOT).per_layer)
    assert not set(NEW) & set(got) and "dense_ffn_share_pct" not in got
    assert "train_step_ms" in got and "peak_hbm_gib" in got


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
def test_the_configuration_file_is_the_catalog_row_cut_two_ways(cell):
    conf = cell.config
    assert set(PUBLISHED) <= set(conf)
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == set(CUT) == set(conf["reduced"])
    assert {k: conf[k] for k in CUT} == CUT
    assert {k: conf["published"][k] for k in CUT} == {
        k: PUBLISHED[k] for k in CUT}
    # no width among the cuts (the contract's list)
    for key in conf["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == \
            "vocab_size"
        assert "head" not in key
    # one whole period in the published 3 : 1, an eighth of the vocabulary
    assert conf["layer_types"][:4] == [LINEAR] * 3 + [FULL]
    assert conf["vocab_size"] * 8 == PUBLISHED["vocab_size"] \
        and conf["vocab_size"] % 128 == 0
    assert conf["head_dim"] == conf["hidden_size"] \
        // conf["num_attention_heads"] == 128
    for key, text in conf["assumed"].items():
        assert isinstance(text, str) and text, key
    for key in ("reordered_norm", "rotary", "qk_norm", "conv_taps",
                "conv_act", "beta_and_g", "l2norm", "gated_norm",
                "column_order", "init", "aux_loss", "document_mask", "remat",
                "rows"):
        assert key in conf["assumed"], key
        if key not in ("column_order", "remat", "rows"):
            assert "the config has no key" in conf["assumed"][key], key
    assert "not read from released code" in conf["assumed"]["reordered_norm"]
    for key in ("stands_for", "compile_said", "program_names"):
        assert len(conf[key]) > 80, key
    assert "eight-stage pipeline" in conf["stands_for"] \
        and "arXiv:2411.05288" in conf["stands_for"]
    assert "928,862,196" in conf["published"]["parameters"]
    assert "init_scale" not in conf
    tol = conf["reference_check"]
    for key in ("loss_abs_tol", "linear_attn_rel_tol",
                "linear_attn_grad_rel_tol", "attention_rel_tol",
                "dense_rel_tol", "block_rel_tol"):
        assert 0 < tol[key] < 0.1, key
    assert tol["b_std"] == 2.0 and 0.1 <= tol["beta_high_share_min"] < 0.3
    for said in ("beta_no_two", "scale_dv", "gate_before_norm", "pre_norm",
                 "rope_on", "norm_per_head", "row_leak", "chunk_reset",
                 "state_bf16", "float8"):
        assert said in tol["reason"], said
    # the roofline's time is the scope's, as the eighth cell's is
    assert "gated_delta" not in conf["trace_names"]
    assert conf["trace_names"]["flash"] == "^self_attn_full$"
    assert (conf["expect_gated_delta_impl"], conf["expect_short_conv_impl"],
            conf["expect_attention_impl"]) == ("pallas", "pallas", "flash")
    assert conf["micro_per_device"] == 2
    engine = conf["engine"]
    assert engine["optimizer"] == {"type": "adamw8bit", "params": {
        "lr": 0.0001, "weight_decay": 0.1}}
    assert engine["zero_optimization"] == {"stage": 3} \
        and engine["gradient_clipping"] == 1.0
    mix = cell.traffic
    assert (mix["seq_len"], mix["eos_token_id"], mix["token_zipf_a"],
            mix["doc_len_lognormal"], mix["doc_len_min"], mix["doc_len_max"],
            mix["trace_seconds"]) == (
        8192, 12543, 1.1, {"median": 400, "sigma": 1.0}, 16, 8192, 8)
    assert "2 rows" in mix["who"]


def test_the_parameters_held_recounted_from_the_programs_own_shapes(cell):
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    sys.path.insert(0, ROOT)
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = cell.driver().model_config(conf)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                       ids)["params"])
    sizes = {jax.tree_util.keystr(p): int(np.prod(s.shape)) for p, s in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert sum(sizes.values()) == 928_862_196       # 12,544 needs no pad
    assert cfg.padded_vocab_size == cfg.vocab_size == 12544

    def under(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    mixer = (E * (2880 + 2880 + 5760 + 5760) + E * 60 + 11520 * 4 + 30 + 30
             + 192 + 5760 * E)
    ffn = 3 * E * I
    attn = 4 * E * E + 2 * E
    assert (mixer, ffn, attn) == (88_750_332, 126_812_160, 58_990_080)
    linear_block, full_block = mixer + ffn + 2 * E, attn + ffn + 2 * E
    assert (linear_block, full_block) == (215_570_172, 185_809_920)
    assert 3 * linear_block + full_block == 832_520_436        # one period
    for i in (0, 1, 2):
        assert under(f"['layers_{i}']") == linear_block
    assert under("['layers_3']") == full_block
    assert sizes["['embed_tokens']"] == sizes["['lm_head']"] == 12544 * E
    assert sizes["['norm']['scale']"] == E
    assert not any("input_norm" in k or "moe" in k for k in sizes)
    assert all(s.dtype == jnp.float32 for s in jax.tree_util.tree_leaves(
        shapes))


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.rms_norm_eps) == (E, 4, 30, 30, 128, I,
                                                  12544, 1e-6)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_chunk_size,
            cfg.linear_allow_neg_eigval) == (30, 30, 96, 192, 4, 64, True)
    assert cfg.kinds == (LINEAR, LINEAR, LINEAR, FULL)
    assert cfg.reordered_norm and not cfg.sandwich_norm \
        and cfg.qk_norm is True and not cfg.attn_gate and cfg.moe is None \
        and not cfg.norm_zero_centered and not cfg.tie_word_embeddings
    assert cfg.rope_layer_types == () and not cfg.rotates(FULL)
    assert (cfg.remat, cfg.remat_policy, cfg.loss_chunk, cfg.scan_layers) == (
        True, conf["model_options"]["remat_policy"], 8192, False)
    kw = driver.reference_kwargs(conf)
    assert kw == {"n_layer": 4, "n_head": 30, "n_kv_head": 30,
                  "head_dim": 128, "vocab_size": 12544, "eps": 1e-6,
                  "layer_types": conf["layer_types"], "n_k_heads": 30,
                  "n_v_heads": 30, "key_dim": 96}
    small = dict(conf, **cell.config["rehearse"])
    tiny = driver.model_config(small)[1]
    # the CPU's preset keeps the widths unequal and off any lane multiple
    assert (tiny.linear_key_head_dim, tiny.linear_value_head_dim) == (12, 24)
    assert tiny.kinds[3] == FULL and tiny.linear_chunk_size == 8


# ----------------------------------------------------------------------
# required operations and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    assert F.linear_layers(conf) == 3
    mixer = E * (2 * 2880 + 2 * 5760) + E * 60 + 5760 * E
    attn = 4 * E * E
    params = 3 * mixer + attn + 4 * 3 * E * I + 12544 * E
    assert F.active_matmul_params(conf) == params
    scores = 4.0 * 30 * 128 * (S + 1) / 2.0
    rule = 9 * 2.0 * 96 * 192 * 30              # a token a layer, fwd + bwd
    assert rule == 9_953_280
    assert F.gated_delta_flops_per_token(conf, 3) == 3 * rule
    assert F.train_flops_per_token(conf, S) == pytest.approx(
        6.0 * params + 3 * scores + 3 * rule)
    # the issue's shares of the ~5.5 GFLOP a token required
    total = F.train_flops_per_token(conf, S)
    assert total == pytest.approx(5.50e9, rel=0.005)
    assert (6.0 * (mixer + 3 * E * I) + rule) / 1e9 == pytest.approx(
        1.30, abs=0.01)                                   # a linear block
    assert (6.0 * (attn + 3 * E * I) + 3 * scores) / 1e9 == pytest.approx(
        1.30, abs=0.01)                                   # the full block
    assert 3 * scores / 1e9 == pytest.approx(0.19, abs=0.005)
    assert 6.0 * 12544 * E / 1e9 == pytest.approx(0.29, abs=0.005)
    assert 6.0 * 12544 * E / total == pytest.approx(0.05, abs=0.005)
    assert 6.0 * 4 * 3 * E * I / total == pytest.approx(0.55, abs=0.01)
    tokens = 16384
    assert F.gated_delta_flops_per_step(conf, tokens) == 3 * rule * tokens
    forward = 2 * 2880 * 2 + 2 * 5760 * 2 + 2 * 30 * 4
    backward = forward + 5760 * 2 + (2 * 2880 * 2 + 5760 * 2 + 2 * 30 * 4)
    assert (forward, backward, forward + backward) == (34_800, 69_600,
                                                       104_400)
    assert F.gated_delta_bytes_per_step(conf, tokens) == \
        104_400.0 * tokens * 3
    # bound by memory ~2.5-fold on the v5e: 127 ns against 51 ns
    assert 104_400 / 819e9 == pytest.approx(127e-9, rel=0.01)
    assert rule / 197e12 == pytest.approx(51e-9, rel=0.02)
    # K, V, dK and dV move at all 30 heads
    assert F.flash_train_bytes_per_token(conf) == 6.0 * (30 + 30) * 128 * 2
    assert F.attention_flops_per_token(conf, S, 1) == scores
    assert F.causal_attention_flops_per_token(conf, S, 3) == 3 * scores
    # at square states the general count is the eighth cell's
    from benchmark import flops_qwen3next as Q

    eighth = M.load_cell(M.load_manifest(ROOT), OLDER[-1], ROOT).config
    assert F.gated_delta_flops_per_step(eighth, 7) \
        == Q.gated_delta_flops_per_step(eighth, 7)
    assert F.gated_delta_bytes_per_step(eighth, 7) \
        == Q.gated_delta_bytes_per_step(eighth, 7)


def _obs(cell, **kw):
    return dict({"cell": cell, "steps": 56, "window_s": 50.0,
                 "peak": M.load_peaks(ROOT)["TPU v5 lite"]}, **kw)


def test_the_readers_on_made_up_observations(cell):
    from benchmark import flops

    dense = cell.reader("dense_ffn_share_pct")
    assert dense(_obs(cell)) is None
    assert dense(_obs(cell, device_scope_ms={"step": 900.0})) is None
    assert dense(_obs(cell, device_scope_ms={
        "step": 900.0, "mlp_dense": 450.0})) == pytest.approx(50.0)
    share = cell.reader("linear_attn_share_pct.96x192")
    assert share(_obs(cell, device_scope_ms={
        "step": 900.0, "linear_attn": 225.0})) == pytest.approx(25.0)
    roof = cell.reader("gated_delta_roofline.96x192")
    need = {"gated_delta_flops_per_step": F.gated_delta_flops_per_step(
        cell.config, 16384), "gated_delta_bytes_per_step":
        F.gated_delta_bytes_per_step(cell.config, 16384)}
    assert roof(_obs(cell, **need)) is None     # no time to divide by
    least, bound = flops.roofline_seconds(
        *need.values(), M.load_peaks(ROOT)["TPU v5 lite"])
    assert bound == "memory" and least == pytest.approx(
        3 * 16384 * 104_400 / 819e9)
    got = roof(_obs(cell, device_scope_ms={
        "step": 900.0, "linear_attn/delta_rule": 120.0}, **need))
    assert got == pytest.approx(100.0 * least * 1e3 / 120.0) and got < 100


def test_the_drivers_split_sums_the_five_scopes_and_the_dense_one():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_olmo_hybrid as D

    table = {"device_ms_a_step": 900.0, "scopes": [
        {"scope": f"layers_{i}/{scope}", "ms_a_step": 1.0 + j}
        for i in range(3) for j, scope in enumerate(D.SCOPES)] + [
        {"scope": f"layers_{i}/mlp_dense", "ms_a_step": 100.0}
        for i in range(4)] + [
        {"scope": "layers_3/self_attn_full", "ms_a_step": 70.0}]}
    engine = types.SimpleNamespace(
        profile_device_scopes=lambda batches, steps, depth: table)
    ctx = types.SimpleNamespace(log=lambda msg: None)
    out = D.scope_split(ctx, engine, None)
    assert out["step"] == 900.0 and out["mlp_dense"] == 400.0
    assert [out[s] for s in D.SCOPES] == [3.0, 6.0, 9.0, 12.0, 15.0]
    assert out["linear_attn"] == 45.0


def test_the_parents_refusal_is_soon_and_clean(cell, monkeypatch):
    """On the parent commit (a ``LlamaConfig`` without ``reordered_norm``)
    the driver exits with a message and a non-zero code before anything is
    built: no compile started, nothing to hang."""
    import dataclasses

    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Parent:
        hidden_size: int = 1
        linear_num_value_heads: int = 32    # the eighth cell's was there

    monkeypatch.setattr(llama, "LlamaConfig", Parent)
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit) as e:
        cell.driver().run(ctx, None)
    assert "reordered_norm" in str(e.value) and e.value.code != 0


# ----------------------------------------------------------------------
# the comparisons at the rehearsal's sizes
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def small(cell):
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    sys.path.insert(0, ROOT)
    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    model, cfg = driver.model_config(conf)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (1, 128)), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    block_in, ffn_in = [], []
    reference.loss_parts(params, ids, **driver.reference_kwargs(conf),
                         block_inputs=block_in, ffn_inputs=ffn_in)
    leaves = list(reference.layers(params, 4))
    ctx = types.SimpleNamespace(seed=3000000019, log=lambda msg: None)
    return driver, reference, cfg, conf, leaves, block_in, ffn_in, ctx


@pytest.mark.parametrize("fault", [None, "beta_no_two", "scale_dv",
                                   "gate_before_norm", "chunk_reset",
                                   "row_leak"])
def test_the_linear_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, leaves, block_in, _, ctx = small
    i, p, h = driver.linear_operands(ctx.seed, cfg, conf, leaves, block_in)
    assert i == 2 and h.shape[0] == 2
    tol = conf["reference_check"]
    if fault is None:
        assert driver.beta_high_share(p, h, cfg) >= \
            tol["beta_high_share_min"]
        grads = driver.read_linear_grads(ctx, cfg, reference, p, h, i)
        assert grads.pop("y") < tol["linear_attn_rel_tol"], grads
        assert set(grads) == {"dh", "dA_log", "dconv_kernel", "ddt_bias",
                              "din_proj_ba_kernel", "din_proj_qkvz_kernel",
                              "do_norm", "dout_proj_kernel"}
        assert max(grads.values()) < tol["linear_attn_grad_rel_tol"], grads
        return
    if fault == "gate_before_norm":
        # at 64 channels and taps of 0.02 the rule's output is ~1e-4 a
        # channel, mean(o^2) far under eps = 1e-6, where the norm is a
        # constant factor and commutes with the gate: the taps are stretched
        # until o is what it is at the published widths
        p = dict(p, conv_kernel=p["conv_kernel"] * 50.0)
    err = driver.read_linear(cfg, reference, p, h, fault=fault)
    assert err > 1.5 * tol["linear_attn_rel_tol"], (fault, err)


@pytest.mark.parametrize("fault", [None, "rope_on", "norm_per_head"])
def test_the_attention_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, leaves, block_in, _, ctx = small
    p = driver.moved(ctx.seed, 3, leaves[3])["self_attn"]
    wrong = {} if fault is None else {"fault": fault}
    err = driver.read_attention(cfg, reference, p,
                                block_in[3].astype(cfg.dtype), **wrong)
    tol = conf["reference_check"]["attention_rel_tol"]
    assert (err < tol) if fault is None else (err > 1.5 * tol), (fault, err)


@pytest.mark.parametrize("fault", [None, "pre_norm"])
def test_the_block_and_dense_checks(small, fault):
    driver, reference, cfg, conf, leaves, block_in, ffn_in, ctx = small
    p = driver.moved(ctx.seed, 3, leaves[3])
    tol = conf["reference_check"]
    if fault is None:
        assert driver.read_dense(cfg, reference, p,
                                 ffn_in[3].astype(cfg.dtype), FULL) \
            < tol["dense_rel_tol"]
        assert driver.read_block(cfg, reference, p, block_in[3], FULL) \
            < tol["block_rel_tol"]
        return
    err = driver.read_block(cfg, reference, p, block_in[3], FULL, fault=fault)
    assert err > 1.5 * tol["block_rel_tol"], err


def test_rehearsal_of_the_olmo_hybrid_cell_prints_a_correct_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == CELL and line["correct"] is True, line
    assert line["failed"] == 0 and line["compiles_in_window"] == 0
    for said in ("% of beta above 1.5", "linear attention check",
                 "linear attention gradient check", "attention check",
                 "dense check", "block check"):
        assert said in out.stderr, said
