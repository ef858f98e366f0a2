"""The eighth cell, ``train-qwen3next-gdn-8k-1chip`` (PR 48): Qwen3-Next-
80B-A3B-Instruct, the first stack with a state carried along the sequence.
Its configuration file is the catalog row cut three ways (depth, experts
held, vocabulary) and in no width; the parameters held are recounted from
the program's own shapes; the driver builds the model from the file as
data; ``flops_qwen3next.py`` against hand-computed numbers; both new
readers on made-up observations; each named fault refused by its check at
the rehearsal's sizes; the ``--rehearse`` line ``correct``; and the
manifest gained the cell behind the older ones in every list it joins.
Nothing here pins a list's END: a later cell appends behind this one and
these tests stand.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_qwen3next as F
from benchmark.harness import manifest as M

ROOT = M.ROOT
CELL = "train-qwen3next-gdn-8k-1chip"
CONFIG = "qwen3-next-80b-a3b-z3-8bit"
OLDER = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip",
         "train-joyai-flash-8k-1chip", "train-sdar-blockdiff-8k-1chip",
         "train-lfm2-hybrid-8k-1chip"]
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
              "short_conv_filter_roofline"]
NEW = {
    "linear_attn_share_pct": {
        "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "model", "moves": "train_tokens_per_s_chip"},
    "gated_delta_roofline": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "train_tokens_per_s_chip"}}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
LINEAR, FULL = "linear_attention", "full_attention"


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
                     "traffic": "packed-8k-18992", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "x share" in entry["why"]
    assert _names(manifest["configs"]).index(CONFIG) == len(OLDER)
    conf = _entry(manifest["configs"], CONFIG)
    assert conf["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-" \
        "A3B-Instruct/blob/main/config.json" and len(conf["why"]) <= 200
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


def test_the_two_new_metrics_stand_behind_what_the_cell_joined(manifest, cell):
    """What this cell brought keeps its order, stands behind every metric
    it joined and behind what the cell before it brought, and lists this
    cell alone (``test_lfm2_cell.py`` holds the general form)."""
    names = _names(manifest["per_layer"])
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and at[1] - at[0] == 1
    assert max(names.index(n) for n in JOINED + NOT_JOINED) < at[0]
    for name, rest in NEW.items():
        assert _entry(manifest["per_layer"], name) == dict(
            name=name, **rest, workloads=[CELL])
        assert callable(cell.reader(name))
    assert _names(cell.per_layer) == JOINED + list(NEW)
    assert _names(cell.end_to_end) == ["train_tokens_per_s_chip", "setup_s"]


@pytest.mark.parametrize("older", OLDER)
def test_an_older_cell_reads_neither_new_metric(manifest, older):
    got = _names(M.load_cell(manifest, older, ROOT).per_layer)
    assert not set(NEW) & set(got)
    assert "train_step_ms" in got and "peak_hbm_gib" in got


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
def test_the_configuration_file_is_the_catalog_row_cut_three_ways(cell):
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
        assert "head" not in key and key != "num_experts_per_tok"
    kinds = conf["layer_types"]
    assert len(kinds) == 48 and all(
        k == (FULL if (i + 1) % conf["full_attention_interval"] == 0
              else LINEAR) for i, k in enumerate(kinds))
    assert conf["source"].endswith("Qwen3-Next-80B-A3B-Instruct/blob/main/"
                                   "config.json")
    moe = conf["moe"]
    assert (moe["routed_experts"], moe["first_expert"], moe["score_func"],
            moe["num_shared_experts"], moe["shared_expert_gate"],
            moe["drop_tokens"]) == (512, 32, "softmax", 1, True, False)
    assert conf["routed_experts"] == PUBLISHED["num_experts"]
    for key, text in conf["assumed"].items():
        assert isinstance(text, str) and text, key
    for key in ("norms", "q_and_gate", "qk_norm", "conv_taps", "conv_act",
                "beta_and_g", "l2norm", "key_heads", "column_order", "init",
                "shared_expert_gate", "balancing", "mtp", "dropout",
                "document_mask", "rows"):
        assert key in conf["assumed"], key
    assert "the config has no key" in conf["assumed"]["norms"]
    for key in ("stands_for", "compile_said", "init_scale_reason"):
        assert len(conf[key]) > 80, key
    assert "sixteen" in conf["stands_for"] and "eight" in conf["stands_for"]
    assert f"{conf['micro_per_device'] * 160} rows a layer" in \
        conf["stands_for"]
    tol = conf["reference_check"]
    for key in ("loss_abs_tol", "linear_attn_rel_tol",
                "linear_attn_grad_rel_tol", "attention_rel_tol",
                "expert_rel_tol"):
        assert 0 < tol[key] < 0.1, key
    # the kernels that ship are the rule's sequential part alone: the
    # roofline's time is the scope's, not theirs
    assert "gated_delta" not in conf["trace_names"]
    assert conf["trace_names"]["flash"] == "^self_attn_full$"
    assert conf["expect_gated_delta_impl"] == "pallas"
    assert conf["micro_per_device"] in (2, 3, 4)
    mix = cell.traffic
    assert (mix["seq_len"], mix["eos_token_id"], mix["token_zipf_a"],
            mix["doc_len_lognormal"], mix["trace_seconds"]) == (
        8192, 18991, 1.1, {"median": 400, "sigma": 1.0}, 8)
    assert str(conf["micro_per_device"]) + " rows" in mix["who"]


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
    assert sum(sizes.values()) == 625_994_816       # padded to 19,072 rows
    padding = 2 * (cfg.padded_vocab_size - cfg.vocab_size) * 2048
    assert sum(sizes.values()) - padding == 625_667_136
    for text in ("625,667,136", "625,994,816"):
        assert text in conf["published"]["parameters"]

    def under(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    E = 2048
    deltanet = E * 12288 + E * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * E
    attn = E * 8192 + 2 * E * 512 + 4096 * E + 2 * 256
    ffn = E * 512 + 3 * E * 512 + E + 32 * 3 * E * 512
    assert deltanet == 33_718_464 and attn == 27_263_488 \
        and ffn == 104_859_648
    for i in (0, 1, 2):
        assert under(f"['layers_{i}']") == deltanet + ffn + 2 * E
    assert under("['layers_3']") == attn + ffn + 2 * E
    assert sizes["['embed_tokens']"] == sizes["['lm_head']"] == 19072 * E
    assert all(s.dtype == jnp.float32 for s in jax.tree_util.tree_leaves(
        shapes))


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.expert_size, cfg.vocab_size,
            cfg.padded_vocab_size, cfg.rms_norm_eps, cfg.rotary_dim,
            cfg.rope_theta) == (2048, 4, 16, 2, 256, 512, 18992, 19072, 1e-6,
                                64, 1e7)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_chunk_size) == (
        16, 32, 128, 128, 4, 64)
    assert cfg.kinds == (LINEAR, LINEAR, LINEAR, FULL)
    assert cfg.norm_zero_centered and cfg.qk_norm == "head" \
        and cfg.attn_gate and not cfg.tie_word_embeddings \
        and not cfg.mla_fields and cfg.num_dense_layers == 0
    moe = cfg.moe
    assert (moe.num_experts, moe.routed, moe.first_expert, moe.top_k,
            moe.norm_topk_prob, moe.score_func, moe.num_shared_experts,
            moe.shared_expert_gate) == (32, 512, 32, 10, True, "softmax", 1,
                                        True)
    kw = driver.reference_kwargs(conf)
    assert {k: kw[k] for k in ("n_layer", "n_head", "n_kv_head", "head_dim",
                               "vocab_size", "top_k", "eps", "n_k_heads",
                               "n_v_heads", "rope_theta",
                               "partial_rotary_factor", "routed_experts",
                               "first_expert")} == {
        "n_layer": 4, "n_head": 16, "n_kv_head": 2, "head_dim": 256,
        "vocab_size": 18992, "top_k": 10, "eps": 1e-6, "n_k_heads": 16,
        "n_v_heads": 32, "rope_theta": 10000000,
        "partial_rotary_factor": 0.25, "routed_experts": 512,
        "first_expert": 32}
    assert kw["aux_loss_weight"] == conf["moe"]["aux_loss_weight"]
    small = dict(conf, **cell.config["rehearse"])
    tiny = driver.model_config(small)[1]
    assert tiny.kinds[3] == FULL and tiny.linear_chunk_size == 8


# ----------------------------------------------------------------------
# required operations and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    E, S = 2048, 8192
    assert F.linear_layers(conf) == 3
    deltanet = E * 12288 + E * 64 + 4096 * E
    attn = 3 * E * 4096 + 2 * E * 512
    share = 32 / 512
    sparse = E * 512 + 3 * E * 512 + E + 10 * share * 3 * E * 512
    params = 3 * deltanet + attn + 4 * sparse + 18992 * E
    assert F.active_matmul_params(conf) == pytest.approx(params)
    scores = 4.0 * 16 * 256 * (S + 1) / 2.0
    rule = 3 * 2.0 * 128 * 128 * 32 * 3            # a token, three layers
    assert F.gated_delta_flops_per_token(conf) == rule
    assert rule / 3 == pytest.approx(3.146e6, rel=1e-3)
    assert F.train_flops_per_token(conf, S) == pytest.approx(
        6.0 * params + 3 * scores + 3 * rule)
    # the issue's shares of the required forward work a token, about
    forward = 2.0 * params + scores + rule
    assert forward == pytest.approx(468e6, rel=0.02)
    assert 3 * 2.0 * deltanet / forward == pytest.approx(0.43, abs=0.01)
    assert rule / forward == pytest.approx(0.02, abs=0.005)
    assert scores / forward == pytest.approx(0.14, abs=0.01)
    assert 2.0 * 18992 * E / forward == pytest.approx(0.17, abs=0.01)
    # more pairs routed here: more required, in the step and the matmuls
    assert F.train_flops_per_token(conf, S, 0.125) > \
        F.train_flops_per_token(conf, S)
    tokens = 32768
    assert F.expert_rows_per_step(conf, tokens) == tokens * 10 / 16
    assert F.expert_rows_per_step(conf, tokens) / 32 == 640     # an expert
    assert F.gated_delta_flops_per_step(conf, tokens) == 3 * rule * tokens
    forward_bytes = 2 * 2048 * 2 + 2 * 4096 * 2 + 2 * 32 * 4
    backward_bytes = forward_bytes + 4096 * 2 + (2 * 2048 * 2 + 4096 * 2
                                                 + 2 * 32 * 4)
    assert forward_bytes + backward_bytes == 74_496
    assert F.gated_delta_bytes_per_step(conf, tokens) == \
        74_496.0 * tokens * 3
    # bound by memory: its bytes take longer than its operations
    assert 74_496 / 819e9 > 9 * 2 * 128 * 128 * 32 / 197e12
    assert F.flash_train_bytes_per_token(conf) == 6.0 * (16 + 2) * 256 * 2
    assert F.attention_flops_per_token(conf, S, 1) == scores


def _obs(cell, **kw):
    return dict({"cell": cell, "steps": 80, "window_s": 50.0,
                 "peak": M.load_peaks(ROOT)["TPU v5 lite"]}, **kw)


def test_the_share_from_the_drivers_split(cell):
    read = cell.reader("linear_attn_share_pct")
    assert read(_obs(cell)) is None
    assert read(_obs(cell, device_scope_ms={"step": 500.0})) is None
    assert read(_obs(cell, device_scope_ms={
        "step": 500.0, "linear_attn": 125.0})) == pytest.approx(25.0)


def test_the_roofline_from_the_kernels_own_name_or_the_scope(cell):
    from benchmark import flops

    read = cell.reader("gated_delta_roofline")
    need = {"gated_delta_flops_per_step": F.gated_delta_flops_per_step(
        cell.config, 32768), "gated_delta_bytes_per_step":
        F.gated_delta_bytes_per_step(cell.config, 32768)}
    assert read(_obs(cell)) is None
    assert read(_obs(cell, **need)) is None     # no time to divide by
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    least, bound = flops.roofline_seconds(
        need["gated_delta_flops_per_step"],
        need["gated_delta_bytes_per_step"], peak)
    assert bound == "memory" and least == pytest.approx(
        need["gated_delta_bytes_per_step"] / 819e9)
    scope = {"step": 500.0, "linear_attn/delta_rule": 40.0}
    got = read(_obs(cell, device_scope_ms=scope, **need))
    assert got == pytest.approx(100.0 * least * 1e3 / 40.0) and got < 100

    class Trace:
        window_s = 5.0

        def __init__(self, t):
            self.t = t

        def ops_matching(self, pattern):
            assert pattern == "^gated_delta_(fwd|bwd)$"
            return self.t

    # this cell's file names no kernel (what ships is the sequential part
    # alone): a trace changes nothing
    got = read(_obs(cell, trace=Trace(0.25), device_scope_ms=scope, **need))
    assert got == pytest.approx(100.0 * least * 1e3 / 40.0)
    # a configuration that names whole-rule kernels reads them, and falls
    # back to the scope where the trace holds none
    named = types.SimpleNamespace(config=dict(cell.config, trace_names=dict(
        cell.config["trace_names"], gated_delta="^gated_delta_(fwd|bwd)$")))
    got = read(_obs(named, trace=Trace(0.25), device_scope_ms=scope, **need))
    assert got == pytest.approx(100.0 * least * 8 / 0.25)
    got = read(_obs(named, trace=Trace(0.0), device_scope_ms=scope, **need))
    assert got == pytest.approx(100.0 * least * 1e3 / 40.0)


def test_the_drivers_split_sums_the_five_scopes():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_qwen3next as D

    table = {"device_ms_a_step": 500.0, "scopes": [
        {"scope": f"layers_{i}/{scope}", "ms_a_step": 1.0 + j}
        for i in range(3) for j, scope in enumerate(D.SCOPES)] + [
        {"scope": "layers_3/self_attn_full", "ms_a_step": 70.0}]}
    engine = types.SimpleNamespace(
        profile_device_scopes=lambda batches, steps, depth: table)
    ctx = types.SimpleNamespace(log=lambda msg: None)
    out = D.scope_split(ctx, engine, None)
    assert out["step"] == 500.0
    assert [out[s] for s in D.SCOPES] == [3.0, 6.0, 9.0, 12.0, 15.0]
    assert out["linear_attn"] == 45.0


def test_a_program_without_the_layer_type_fails_soon_and_cleanly(cell,
                                                                 monkeypatch):
    """On a commit from before the layer type the driver exits with a
    message and a non-zero code before anything is built."""
    import dataclasses

    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Older:
        hidden_size: int = 1

    monkeypatch.setattr(llama, "LlamaConfig", Older)
    ctx = types.SimpleNamespace(cell=cell)
    with pytest.raises(SystemExit) as e:
        cell.driver().run(ctx, None)
    assert "linear_attention" in str(e.value) and e.value.code != 0


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
    params["embed_tokens"] = params["embed_tokens"] * 50.0
    ffn_in, mixer_in = [], []
    reference.loss_parts(params, ids, **driver.reference_kwargs(conf),
                         ffn_inputs=ffn_in, mixer_inputs=mixer_in)
    ctx = types.SimpleNamespace(seed=3000000019, log=lambda msg: None)
    return driver, reference, cfg, conf, params, ffn_in, mixer_in, ctx


@pytest.mark.parametrize("fault", [None, "no_decay", "k_head_mod",
                                   "chunk_reset", "row_leak",
                                   "norm_zero_centred"])
def test_the_linear_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, _, mixer_in, ctx = small
    p = driver.moved(ctx.seed, 0, params["layers_0"]["linear_attn"])
    h = driver.two_rows(mixer_in[0]).astype(cfg.dtype)
    tol = conf["reference_check"]["linear_attn_rel_tol"]
    if fault is None:
        grads = driver.read_linear_grads(ctx, cfg, reference, p, h, 0)
        assert grads.pop("y") < tol, grads
        assert set(grads) == {"dh", "dA_log", "dconv_kernel", "ddt_bias",
                              "din_proj_ba_kernel", "din_proj_qkvz_kernel",
                              "do_norm", "dout_proj_kernel"}
        assert max(grads.values()) < \
            conf["reference_check"]["linear_attn_grad_rel_tol"], grads
        return
    err = driver.read_linear(cfg, reference, p, h, fault=fault)
    assert err > 1.5 * tol, (fault, err)


@pytest.mark.parametrize("fault", [None, "rope_all", "rope_last", "no_gate",
                                   "norm_plain", "kv_mod"])
def test_the_attention_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, _, mixer_in, ctx = small
    p = driver.moved(ctx.seed, 3, params["layers_3"]["self_attn"])
    wrong = {} if fault is None else {"fault": fault}
    err = driver.read_attention(cfg, reference, p,
                                mixer_in[3].astype(cfg.dtype), conf, **wrong)
    tol = conf["reference_check"]["attention_rel_tol"]
    assert (err < tol) if fault is None else (err > 1.5 * tol), (fault, err)


@pytest.mark.parametrize("fault", [None, "no_renorm", "no_shared_gate",
                                   "shared_per_expert"])
def test_the_expert_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, ffn_in, _, ctx = small
    wrong = {} if fault is None else {"fault": fault}
    errs = driver.read_experts(ctx, cfg, conf, reference, params, ffn_in,
                               **wrong)
    tol = conf["reference_check"]["expert_rel_tol"]
    assert len(errs) == 4
    assert (max(errs) < tol) if fault is None else (min(errs) > 1.5 * tol), (
        fault, errs)


def test_rehearsal_of_the_qwen3next_cell_prints_a_correct_line():
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
    for said in ("linear attention check", "linear attention gradient check",
                 "attention check", "expert check",
                 "of the window's pairs were routed to the experts held"):
        assert said in out.stderr, said
