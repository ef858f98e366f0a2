"""The seventh cell, ``train-lfm2-hybrid-8k-1chip`` (PR 45): LFM2-24B-A2B,
a stack whose layers are not all attention.  Its configuration file is the
catalog row cut four ways (depth, leading dense layers, experts held,
vocabulary) and in no width; the parameters held are recounted from the
program's own shapes; the driver builds the model from the file as data;
``flops_lfm2.py`` against hand-computed numbers; both new readers on
made-up observations; each named fault refused by its check at the
rehearsal's sizes; the ``--rehearse`` line ``correct``; and the manifest
gained the cell behind the older ones in every list it joins.  Nothing
here pins a list's END: a later cell appends behind this one and these
tests stand.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_lfm2 as F
from benchmark.harness import manifest as M

ROOT = M.ROOT
CELL = "train-lfm2-hybrid-8k-1chip"
CONFIG = "lfm2-24b-a2b-z3-8bit"
OLDER = ["train-xl-z3-1chip", "train-olmoe-z3-1chip",
         "train-mellum2-8k-1chip", "train-trinity-mini-8k-1chip",
         "train-joyai-flash-8k-1chip", "train-sdar-blockdiff-8k-1chip"]
JOINED = ["train_step_ms", "train_mfu_pct", "flash_share_pct",
          "flash_roofline", "device_idle_pct.train", "train_host_ms",
          "train_input_ms", "train_dispatch_ms", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_init_params_s",
          "expert_gemm_share_pct", "expert_gemm_roofline",
          "moe_load_imbalance", "moe_held_pair_pct",
          "moe_expert_bias_spread", "peak_hbm_gib", "step_temp_hbm_gib"]
NOT_JOINED = ["flash_window_roofline", "flash_full_roofline",
              "flash_window_share_pct", "mtp_loss_excess",
              "diffusion_masked_pct", "diffusion_prep_share_pct"]
NEW = {
    "short_conv_share_pct": {
        "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "model", "moves": "train_tokens_per_s_chip"},
    "short_conv_filter_roofline": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "train_tokens_per_s_chip"}}
# what each cell that brought metrics of its own brought, oldest first: a
# cell's own stand behind everything an older cell reads and list it alone
BROUGHT = {"train-sdar-blockdiff-8k-1chip": ["diffusion_masked_pct",
                                            "diffusion_prep_share_pct"],
           CELL: list(NEW)}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
       "vocab_size": 16384}
# no key that is a width may differ (the contract's list)
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "conv_L_cache",
          "num_experts_per_tok")


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
                     "traffic": "packed-8k-16384", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "4x" in entry["why"]
    assert _names(manifest["configs"]).index(CONFIG) == len(OLDER)
    conf = _entry(manifest["configs"], CONFIG)
    assert conf["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/" \
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


@pytest.mark.parametrize("owner", sorted(BROUGHT))
def test_what_a_cell_brought_stands_behind_the_older_metrics_and_lists_it_alone(
        manifest, owner):
    """The position-free form of ``test_sdar_cell.py``'s test of the
    manifest's last two entries (marked in ``tests/conftest.py``): a cell's
    own metrics keep their order, stand behind every metric an older cell
    brought or joined, and are read in that cell alone."""
    names = _names(manifest["per_layer"])
    mine = BROUGHT[owner]
    at = [names.index(n) for n in mine]
    assert at == sorted(at) and at[-1] - at[0] == len(mine) - 1
    cells = _names(manifest["workloads"])
    for other, theirs in BROUGHT.items():
        if cells.index(other) < cells.index(owner):
            assert max(names.index(n) for n in theirs) < at[0]
    assert max(names.index(n) for n in JOINED) < at[0]
    for n in mine:
        assert _entry(manifest["per_layer"], n)["workloads"] == [owner]
    loaded = M.load_cell(manifest, owner, ROOT)
    assert _names(loaded.per_layer)[-len(mine):] == mine
    for n in mine:
        assert callable(loaded.reader(n))


def test_the_two_new_metrics_and_what_the_cell_reports(manifest, cell):
    for name, rest in NEW.items():
        assert _entry(manifest["per_layer"], name) == dict(
            name=name, **rest, workloads=[CELL])
    assert _names(cell.per_layer) == JOINED + list(NEW)
    assert _names(cell.end_to_end) == ["train_tokens_per_s_chip", "setup_s"]
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert {"model", "kernels"} <= layers       # names the benchmark had


@pytest.mark.parametrize("older", OLDER)
def test_an_older_cell_reads_neither_new_metric(manifest, older):
    got = _names(M.load_cell(manifest, older, ROOT).per_layer)
    assert not set(NEW) & set(got)
    assert "train_step_ms" in got and "peak_hbm_gib" in got


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
def test_the_configuration_file_is_the_catalog_row_cut_four_ways(cell):
    conf = cell.config
    assert set(PUBLISHED) <= set(conf)
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == set(CUT) == set(conf["reduced"])
    assert not differs & set(WIDTHS)
    for key, here in CUT.items():
        assert conf[key] == here
        assert conf["published"][key] == PUBLISHED[key]
    assert conf["layer_types"][:5] == ["conv", "conv", "full_attention",
                                       "conv", "conv"]
    # floors: a whole period behind the one dense layer, >= 8 experts, >=
    # an eighth of the vocabulary
    sparse = conf["layer_types"][conf["num_dense_layers"]:
                                 conf["num_hidden_layers"]]
    assert sorted(sparse) == sorted(PUBLISHED["layer_types"][:4])
    assert conf["num_experts"] >= 8
    assert conf["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert conf["routed_experts"] == conf["moe"]["routed_experts"] == 64
    assert conf["moe"]["first_expert"] == 16
    for said in ("four-chip", "16-31", "expert-parallel 4", "2,048 rows",
                 "nothing stands in"):
        assert said in conf["stands_for"], said
    assert (conf["head_dim"], conf["tie_word_embeddings"]) == (64, True)
    for key in ("head_dim", "tie_word_embeddings", "qk_norm", "conv_chunks",
                "conv_taps", "norms", "expert_bias", "expert_weight",
                "balancing", "initializer_range", "dropout", "document_mask",
                "eos_token_id", "recipe", "rows"):
        assert len(conf["assumed"][key]) > 20, key
    for key in ("head_dim", "tie_word_embeddings", "qk_norm", "conv_chunks",
                "conv_taps", "norms", "expert_bias", "expert_weight",
                "balancing", "initializer_range", "dropout"):
        assert "the config has no key" in conf["assumed"][key], key
    assert conf["init_scale"] == {} and "tied" in conf["init_scale_reason"]
    moe = conf["moe"]
    assert (moe["score_func"], moe["route_scale"], moe["num_shared_experts"],
            moe["aux_loss_weight"], moe["drop_tokens"], moe["norm_topk_eps"]
            ) == ("sigmoid", 1.0, 0, 0.0, False, 1e-6)
    assert moe["bias_update_rate"] > 0
    opts = conf["model_options"]
    assert (opts["qk_norm"], opts["remat_prevent_cse"], opts["loss_chunk"],
            opts["rms_norm_eps"], opts["scan_layers"]) == (
        "head", True, 8192, conf["norm_eps"], False)
    assert cell.traffic["seq_len"] == 8192 \
        and cell.traffic["kind"] == "train_packed"
    assert cell.traffic["eos_token_id"] == conf["vocab_size"] - 1
    assert conf["micro_per_device"] * cell.traffic["seq_len"] == 32768
    # the rows a held expert sees against its deployment load
    assert 32768 * conf["num_experts_per_tok"] // conf["routed_experts"] \
        == 2048
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02
    for key in ("conv_rel_tol", "conv_grad_rel_tol", "attention_rel_tol",
                "expert_rel_tol", "dense_rel_tol"):
        assert 0 < tol[key] < 0.1, key
    for why in ("reason", "conv_reason", "conv_grad_reason",
                "attention_reason", "expert_reason", "dense_reason"):
        assert len(tol[why]) > 40 and "PR 45" in tol[why], why
    assert conf["trace_names"] == {
        "flash": "^self_attn_full$", "train_module": "^jit_step_fn$",
        "expert_gemm": "^t?gmm$",
        "short_conv_filter": "^short_conv_rows(_back)?$"}
    assert (conf["driver"], conf["reference"], conf["flops"]) == (
        "train_lfm2", "lfm2", "flops_lfm2")
    assert (conf["expect_attention_impl"], conf["expect_grouped_matmul_impl"],
            conf["expect_short_conv_impl"]) == ("flash", "megablox", "pallas")
    assert "GiB" in conf["compile_said"] and "15.75" in conf["compile_said"]


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
    assert sum(sizes.values()) == 788_052_352
    assert "788,052,352" in conf["published"]["parameters"]

    def under(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    E = 2048
    conv = E * 3 * E + E * E + E * 3
    attn = 2 * E * E + 2 * E * 512 + 2 * 64
    sparse = 16 * 3 * E * 1536 + E * 64 + 64        # experts, router, bias
    assert under("['layers_0']") == conv + 2 * E + 3 * E * 11776 == 89_139_200
    for i in (1, 3, 4):
        assert under(f"['layers_{i}']") == conv + 2 * E + sparse
    assert under("['layers_2']") == attn + 2 * E + sparse
    assert sizes["['embed_tokens']"] == 16384 * E
    assert not any("lm_head" in k for k in sizes)   # tied: the table once
    state = sum(n for k, n in sizes.items() if "expert_bias" in k)
    assert state == 4 * 64
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
            cfg.expert_size, cfg.vocab_size, cfg.padded_vocab_size,
            cfg.rms_norm_eps, cfg.conv_L_cache, cfg.num_dense_layers) == (
        2048, 5, 32, 8, 64, 11776, 1536, 16384, 16384, 1e-5, 3, 1)
    assert cfg.kinds == ("conv", "conv", "full_attention", "conv", "conv")
    assert cfg.tie_word_embeddings and cfg.qk_norm == "head" \
        and not cfg.conv_bias and not cfg.mla_fields
    assert cfg.rotary("full_attention").inv_freq[1] == pytest.approx(
        1e6 ** (-2 / 64))
    moe = cfg.moe
    assert (moe.num_experts, moe.routed, moe.first_expert, moe.top_k,
            moe.norm_topk_prob, moe.norm_topk_eps, moe.score_func,
            moe.num_shared_experts) == (16, 64, 16, 4, True, 1e-6,
                                        "sigmoid", 0)
    assert driver.reference_kwargs(conf) == {
        "n_layer": 5, "n_head": 32, "n_kv_head": 8, "head_dim": 64,
        "vocab_size": 16384, "top_k": 4, "eps": 1e-5,
        "layer_types": conf["layer_types"], "num_dense_layers": 1,
        "route_scale": 1, "routed_experts": 64, "first_expert": 16,
        "rope_theta": 1e6}
    small = dict(conf, **cell.config["rehearse"])
    assert driver.model_config(small)[1].kinds[2] == "full_attention"


# ----------------------------------------------------------------------
# required operations and bytes
# ----------------------------------------------------------------------
def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    E, S = 2048, 8192
    assert F.conv_layers(conf) == 4 and F.sparse_layers(conf) == 4
    conv = E * 6144 + E * E                     # the two projections
    attn = 2 * E * E + 2 * E * 512
    dense = 3 * E * 11776
    sparse = E * 64 + 4 * 0.25 * 3 * E * 1536   # router + one expert's worth
    params = 4 * conv + attn + dense + 4 * sparse + 16384 * E
    assert F.active_matmul_params(conf) == pytest.approx(params)
    assert params == pytest.approx(221.77e6, rel=1e-4)
    # one attention layer of five: causal keys (S + 1) / 2 a query
    scores = 4 * 32 * 64 * (S + 1) / 2
    assert F.attention_flops_per_token(conf, S, 1) == pytest.approx(scores)
    assert F.causal_attention_flops_per_token(conf, S, 3) \
        == pytest.approx(3 * scores)
    forward = 2 * params + scores
    assert forward == pytest.approx(477.1e6, rel=1e-3)      # the issue's
    assert F.train_flops_per_token(conf, S) == pytest.approx(3 * forward)
    for part, share in ((2 * 4 * conv, 0.28), (2 * attn + scores, 0.11),
                        (2 * dense, 0.30), (2 * 4 * sparse, 0.16),
                        (2 * 16384 * E, 0.14)):
        assert part / forward == pytest.approx(share, abs=0.007)
    # keys and values move at their own 8 heads, one layer
    assert F.flash_train_bytes_per_token(conf) == 6 * (32 + 8) * 64 * 2
    # what the counter read moves what was required
    assert F.train_flops_per_token(conf, S, held=0.5) \
        - F.train_flops_per_token(conf, S) == pytest.approx(
            6 * 4 * 4 * 0.25 * 3 * E * 1536)
    rows = F.expert_rows_per_step(conf, 32768)
    assert rows == 32768 and rows / 16 == 2048      # a held expert's rows
    assert F.expert_gemm_flops_per_step(conf, 32768) == pytest.approx(
        9 * 2 * 32768 * E * 1536 * 4)
    assert F.expert_gemm_bytes_per_step(conf, 32768) == pytest.approx(
        9 * (16 * E * 1536 + 32768 * (E + 1536)) * 2 * 4)
    # the filter: 4 + 7 vectors of bf16 a token a conv layer, 2L + 2
    # multiply-adds a channel a pass
    assert F.short_conv_filter_bytes_per_step(conf, 32768) \
        == 11 * E * 2 * 32768 * 4 == 5_905_580_032
    assert F.short_conv_filter_flops_per_step(conf, 32768) \
        == 3 * 2 * 8 * E * 32768 * 4
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    least, bound = F.roofline_seconds(
        F.short_conv_filter_flops_per_step(conf, 32768),
        F.short_conv_filter_bytes_per_step(conf, 32768), peak)
    assert bound == "memory" and least == pytest.approx(7.21e-3, rel=1e-3)


# ----------------------------------------------------------------------
# the two readers
# ----------------------------------------------------------------------
def test_the_share_from_the_drivers_split(cell):
    read = cell.reader("short_conv_share_pct")
    assert read.__module__.endswith("short_conv_share_pct")
    assert read({}) is None and read({"device_scope_ms": {}}) is None
    assert read({"device_scope_ms": {"step": 0.0, "short_conv": 1.0}}) is None
    assert read({"device_scope_ms": {"step": 500.0}}) is None   # no such scope
    assert read({"device_scope_ms": {"short_conv": 125.0, "step": 500.0}}) \
        == pytest.approx(25.0)


def test_the_roofline_from_the_kernels_own_name_or_the_scope(cell):
    read = cell.reader("short_conv_filter_roofline")
    assert read.__module__.endswith("short_conv_filter_roofline")
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    required = {
        "short_conv_filter_flops_per_step":
            F.short_conv_filter_flops_per_step(cell.config, 32768),
        "short_conv_filter_bytes_per_step":
            F.short_conv_filter_bytes_per_step(cell.config, 32768),
        "peak": peak, "cell": cell, "steps": 80, "window_s": 40.0}
    assert read({}) is None and read(dict(required)) is None
    assert read(dict(required, peak=None, device_scope_ms={
        "short_conv/filter": 10.0})) is None
    # a program whose filter is XLA's: the scope's ms a step
    assert read(dict(required, device_scope_ms={
        "short_conv/filter": 14.42})) == pytest.approx(50.0, rel=1e-3)
    # the kernels' own name in the window's trace: 8 s of a 40 s window
    seen = []

    def ops_matching(pattern):
        seen.append(pattern)
        return 0.1442                   # seconds over 16 traced steps

    tr = types.SimpleNamespace(window_s=8.0, ops_matching=ops_matching)
    assert read(dict(required, trace=tr)) == pytest.approx(
        100 * 16 * 7.2107e-3 / 0.1442, rel=1e-3)
    assert seen == ["^short_conv_rows(_back)?$"]
    silent = types.SimpleNamespace(window_s=8.0, ops_matching=lambda p: 0.0)
    assert read(dict(required, trace=silent)) is None
    assert read(dict(required, trace=silent, device_scope_ms={
        "short_conv/filter": 14.42})) == pytest.approx(50.0, rel=1e-3)


def test_the_drivers_split_sums_the_three_scopes():
    sys.path.insert(0, ROOT)
    from benchmark.drivers import train_lfm2

    table = {"device_ms_a_step": 500.0, "scopes": [
        {"scope": "layers_*/conv/short_conv/in_proj", "pass": "forward",
         "ms_a_step": 30.0},
        {"scope": "layers_*/conv/short_conv/in_proj", "pass": "backward",
         "ms_a_step": 60.0},
        {"scope": "layers_*/conv/short_conv/filter", "pass": "recompute",
         "ms_a_step": 4.0},
        {"scope": "layers_*/conv/short_conv/filter", "pass": "backward",
         "ms_a_step": 6.0},
        {"scope": "layers_*/conv/short_conv/out_proj", "pass": "forward",
         "ms_a_step": 10.0},
        {"scope": "layers_*/self_attn/self_attn_full", "pass": "forward",
         "ms_a_step": 20.0},
        {"scope": "loss_head", "pass": "forward", "ms_a_step": 20.0}]}
    asked = []

    def profile(batches, steps, depth):
        asked.append((steps, depth))
        return table

    engine = types.SimpleNamespace(profile_device_scopes=profile)
    ctx = types.SimpleNamespace(log=lambda msg: None)
    assert train_lfm2.scope_split(ctx, engine, None) == {
        "step": 500.0, "short_conv/in_proj": 90.0, "short_conv/filter": 10.0,
        "short_conv/out_proj": 10.0, "short_conv": 110.0}
    assert asked == [(4, 4)]        # four names deep: .../short_conv/filter


def test_a_program_without_the_layer_type_fails_soon_and_cleanly(cell,
                                                                 monkeypatch):
    """On a commit from before the conv layer the driver exits non-zero
    before it builds anything: the cell is then measured on the change
    alone."""
    import dataclasses

    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import llama

    driver = cell.driver()
    fields = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda c: [
        f for f in fields(c) if not (c is llama.LlamaConfig
                                     and f.name == "conv_L_cache")])
    with pytest.raises(SystemExit) as e:
        driver.run(types.SimpleNamespace(cell=cell), None)
    assert "conv" in str(e.value.code) and e.value.code != 0


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
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, params)
    kw = driver.reference_kwargs(conf)
    ffn_in, mixer_in = [], []
    reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in,
                         mixer_inputs=mixer_in)
    ctx = types.SimpleNamespace(seed=5)
    return driver, reference, cfg, conf, params, kw, ffn_in, mixer_in, ctx


@pytest.mark.parametrize("fault", [None, "taps_reversed", "centred", "L-1",
                                   "c_before_filter", "no_b", "row_leak"])
def test_the_conv_checks_refuse_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, kw, ffn_in, mixer_in, ctx = small
    assert set(reference.CONV_FAULTS) == {
        "taps_reversed", "centred", "L-1", "c_before_filter", "no_b",
        "row_leak"}
    limit = conf["reference_check"]["conv_rel_tol"]
    wrong = {"fault": fault} if fault else {}
    p = params["layers_1"]["conv"]
    h = driver.two_rows(mixer_in[1]).astype(cfg.dtype)
    assert h.shape == (2, 128, 64)
    np.testing.assert_array_equal(np.asarray(h[1], np.float32),
                                  np.asarray(h[0], np.float32)[::-1])
    whole, heads = driver.read_conv(cfg, reference, p, h, **wrong)
    grads = driver.read_conv_grads(ctx, cfg, reference, p, h, 1, **wrong)
    assert set(grads) == {"dh", "din_proj_kernel", "dconv_kernel",
                          "dout_proj_kernel"}
    if fault is None:
        assert max(whole, heads, *grads.values()) < limit / 2
    else:
        assert max(whole, heads) > limit, (fault, whole, heads)
        assert max(grads.values()) > limit, (fault, grads)
    if fault == "row_leak":     # two positions of 256: the heads read it
        assert heads > 2 * whole


@pytest.mark.parametrize("fault", [None, "kv_mod", "qk_norm_whole",
                                   "no_rope", "theta_1e4"])
def test_the_attention_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, kw, ffn_in, mixer_in, ctx = small
    assert set(reference.FAULTS) == {"kv_mod", "qk_norm_whole", "no_rope",
                                     "theta_1e4"}
    p = dict(params["layers_2"]["self_attn"])
    rng = np.random.default_rng(2)      # scales away from 1: WHERE it runs
    for name in ("q_norm", "k_norm"):
        p[name] = {"scale": rng.uniform(0.5, 3.0, 16).astype(np.float32)}
    err = driver.read_attention(cfg, reference, p,
                                mixer_in[2].astype(cfg.dtype), kw,
                                **({"fault": fault} if fault else {}))
    limit = conf["reference_check"]["attention_rel_tol"]
    assert (err < limit / 2) if fault is None else (err > limit), (fault, err)


@pytest.mark.parametrize("fault", [None, "bias_ignored", "bias_in_weights",
                                   "softmax", "held_denominator", "top_2k"])
def test_the_expert_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, kw, ffn_in, mixer_in, ctx = small
    assert set(reference.EXPERT_FAULTS) == {
        "bias_ignored", "bias_in_weights", "softmax", "held_denominator",
        "top_2k"}
    errs = driver.read_experts(ctx, cfg, conf, reference, params, ffn_in,
                               **({"fault": fault} if fault else {}))
    assert len(errs) == 4
    limit = conf["reference_check"]["expert_rel_tol"]
    if fault is None:
        assert max(errs) < limit / 2
    else:
        assert min(errs) > limit / 2 and max(errs) > limit, (fault, errs)


def test_the_whole_comparison_passes_and_names_what_it_refuses(small, cell):
    driver, reference, cfg, conf, params, kw, ffn_in, mixer_in, ctx = small
    notes, said = [], []
    ctx = types.SimpleNamespace(
        seed=5, log=said.append,
        check=lambda ok, what: (ok or notes.append(what), bool(ok))[1])
    driver.check_conv(ctx, cfg, conf, reference, params, mixer_in)
    driver.check_attention(ctx, cfg, conf, reference, params, mixer_in)
    driver.check_experts(ctx, cfg, conf, reference, params, ffn_in)
    assert notes == []
    # the dense block's mixer and the first sparse block's
    assert [s.split()[3] for s in said if s.startswith("conv check")] \
        == ["0", "1"]
    leaky = types.SimpleNamespace(
        layers=reference.layers, short_conv_grads=reference.short_conv_grads,
        short_conv=lambda p, h, **k: reference.short_conv(
            p, h, fault="row_leak", **k))
    driver.check_conv(ctx, cfg, conf, leaky, params, mixer_in)
    assert len(notes) == 2 and all("conv mixer's output" in n for n in notes)


def test_rehearsal_of_the_lfm2_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check:", "conv check: layer 0",
                 "conv gradient check: layer 1", "attention check: layer 2",
                 "expert check:", "dense check: layer 0", "bias check:",
                 "balancing in the window"):
        assert said in r.stderr, said
