"""The benchmark's third cell, ``train-mellum2-8k-1chip`` (PR 30): its
names resolve to files, its configuration is the catalog row cut as the
guide allows, its operation counts are what a hand computes, its readers
give nothing (and do not raise) where there is nothing to read, its
attention check sees what its loss check cannot, and its rehearsal passes
on the CPU.  Host-only, nothing timed.

The three tests at the top are **position-free**: they hold every cell the
manifest has, wherever it stands in its list, so the next configuration
adds its own file and no copy of these (``tests/conftest.py`` says which
three older tests they replace).
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_mellum2 as F
from benchmark.harness import manifest as M
from benchmark.layer_metrics import (flash_full_roofline, flash_roofline,
                                     flash_window_roofline,
                                     flash_window_share_pct,
                                     moe_held_pair_pct, moe_load_imbalance)

ROOT = M.ROOT
CELL = "train-mellum2-8k-1chip"
CONFIG = "mellum2-12b-a2.5b-z3-8bit"
# what a cut may name (model-configs guide, section 4): depth, context, the
# experts held here, the vocabulary slice; never a width
CUTS = {"n_positions", "num_hidden_layers", "n_layer",
        "max_position_embeddings", "num_experts", "vocab_size"}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok", "n_embd", "sliding_window")
# every cell an accepted PR added, with what it was accepted at
ACCEPTED = {
    "train-xl-z3-1chip": ("gpt2-xl-z3-8bit", "packed-1k", 1, []),
    "train-olmoe-z3-1chip": ("olmoe-1b-7b-z3-8bit", "packed-4k", 1,
                             ["num_hidden_layers"]),
    CELL: (CONFIG, "packed-8k", 1,
           ["num_hidden_layers", "num_experts", "vocab_size"]),
}
OLMOE_METRICS = ["expert_gemm_share_pct", "expert_gemm_roofline",
                 "moe_load_imbalance"]
NEW_METRICS = ["flash_window_roofline", "flash_full_roofline",
               "flash_window_share_pct", "moe_held_pair_pct"]


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


@pytest.fixture(scope="module")
def with_pending(manifest):
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


@pytest.mark.parametrize("which", ["manifest", "with_pending"])
def test_every_cell_loads_and_is_cut_only_as_the_guide_allows(which, request):
    manifest = request.getfixturevalue(which)
    used = set()
    for w in manifest["workloads"]:
        c = M.load_cell(manifest, w["name"], ROOT)
        used.add(c.config_name)
        assert c.config["reduced"] == next(
            x["reduced"] for x in manifest["configs"]
            if x["name"] == c.config_name)
        assert [m["name"] for m in c.end_to_end].count("setup_s") == 1
        assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
        assert callable(c.driver().run)
        assert callable(c.reference().logits)
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        assert set(c["reduced"]) <= CUTS
        assert not set(c["reduced"]) & set(WIDTHS)


def test_every_accepted_cell_is_still_there_with_its_values(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    for name, (config, traffic, chips, reduced) in ACCEPTED.items():
        w = cells[name]
        assert (w["config"], w["traffic"], w["chips"]) == (config, traffic,
                                                           chips)
        assert configs[config]["reduced"] == reduced
    # the order of what was there is the order it was accepted in
    assert [w["name"] for w in manifest["workloads"]][:3] == list(ACCEPTED)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    trained = by_name["train_step_ms"]["workloads"]
    assert trained == list(ACCEPTED)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["train_tokens_per_s_chip"]["workloads"] == trained
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and manifest["run_seconds"] == 50
    for name in OLMOE_METRICS:      # the sparse cells report them
        assert by_name[name]["workloads"] == ["train-olmoe-z3-1chip", CELL]
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_tokens_per_s_chip"
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e


def test_no_metric_lost_a_cell_and_the_xl_cell_kept_its_own(manifest):
    xl = M.load_cell(manifest, "train-xl-z3-1chip", ROOT)
    olmoe = M.load_cell(manifest, "train-olmoe-z3-1chip", ROOT)
    mine = M.load_cell(manifest, CELL, ROOT)
    names = lambda c: [m["name"] for m in c.per_layer]
    assert names(olmoe) == names(xl) + OLMOE_METRICS
    assert names(mine) == names(olmoe) + NEW_METRICS
    assert len(names(xl)) == 11


def test_the_configuration_file_is_the_catalog_row_cut_three_ways(cell):
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    conf = cell.config
    differs = {k for k, v in published.items() if conf[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(conf["reduced"])
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (4, 16, 24576)
    assert conf["published"]["num_experts"] == conf["routed_experts"] == 64
    assert conf["published"]["vocab_size"] == 98304
    # nested groups whole: 28 layer types, of which the first period runs
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert conf["layer_types"] == period * 7
    assert conf["mlp_layer_types"] == ["sparse"] * 28
    yarn = conf["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["rope_theta"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["attention_factor"]) == (
        "yarn", 16, 500000, 8192, 32, 1, 1.2772588722239782)
    assert conf["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}
    # floors: a whole period and four layers, >= 8 experts, >= 1/8 vocabulary
    assert conf["num_experts"] >= 8 and conf["vocab_size"] * 8 >= 98304
    assert "four-chip" in conf["stands_for"] and "16" in conf["stands_for"]
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["eos_token_id"] == 24575 < conf["vocab_size"]
    assert conf["micro_per_device"] * cell.traffic["seq_len"] == 32768
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02 and 0 < tol["expert_rel_tol"] < 0.1
    assert 0 < tol["attention_rel_tol"] < 0.1
    for why in ("reason", "expert_reason", "attention_reason"):
        assert len(tol[why]) > 40


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.head_dim, cfg.kv_heads,
            cfg.expert_size, cfg.vocab_size, cfg.sliding_window) == (
        2304, 4, 128, 4, 896, 24576, 1024)
    assert cfg.kinds == ("sliding_attention",) * 3 + ("full_attention",)
    assert not cfg.qk_norm and cfg.loss_chunk == 8192 and not cfg.scan_layers
    assert cfg.rotary("full_attention").scale == 1.2772588722239782
    assert cfg.rotary("sliding_attention").scale == 1.0
    moe = cfg.moe
    assert (moe.num_experts, moe.routed, moe.first_expert, moe.top_k,
            moe.drop_tokens, moe.norm_topk_prob, moe.expert_act) == (
        16, 64, 16, 8, False, True, "swiglu")
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.1, 0.0)
    assert conf["warmup_steps"] == 70
    kw = driver.reference_kwargs(conf)
    assert kw["first_expert"] == 16 and kw["routed_experts"] == 64
    assert kw["n_kv_head"] == 4 and kw["head_dim"] == 128
    assert kw["layer_types"] == conf["layer_types"]
    # 595.2 M parameters held here, as the issue reckoned
    shapes = __import__("jax").eval_shape(
        model.init, __import__("jax").random.PRNGKey(0),
        np.zeros((1, 128), np.int32))
    n = sum(int(np.prod(x.shape)) for x in
            __import__("jax").tree_util.tree_leaves(shapes))
    assert n == pytest.approx(595.2e6, rel=2e-3)


def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    E, H, KV, D, I = 2304, 32, 4, 128, 896
    block = 2 * E * H * D + 2 * E * KV * D + E * 64 + 8 * (16 / 64) * 3 * E * I
    assert block == 18_874_368 + 2_359_296 + 147_456 + 12_386_304
    assert F.active_matmul_params(conf) == 4 * block + 24576 * E
    # keys a query keeps: sum_i min(i + 1, 1024) and sum_i (i + 1), over 8192
    banded = sum(min(i + 1, 1024) for i in range(8192)) / 8192
    full = sum(i + 1 for i in range(8192)) / 8192
    assert F.kept_keys_per_token(8192, 1024) == banded == 960.0625
    assert F.kept_keys_per_token(8192) == full == 4096.5
    assert F.kept_keys_per_token(512, 1024) == 256.5
    one = lambda keys: 3 * 4.0 * H * D * keys
    assert F.attention_flops_per_token(conf, 8192, 3, F.SLIDING) == \
        3 * one(banded)
    assert F.attention_flops_per_token(conf, 8192, 3, F.FULL) == one(full)
    attn = 3 * one(banded) + one(full)
    assert F.causal_attention_flops_per_token(conf, 8192, 3) == attn
    assert attn == pytest.approx(343e6, rel=5e-3)      # the issue's 343 MFLOP
    assert one(full) / 3 == pytest.approx(67e6, rel=5e-3)      # forward
    assert one(banded) / 3 == pytest.approx(16e6, rel=2e-2)
    assert F.train_flops_per_token(conf, 8192) == \
        6.0 * F.active_matmul_params(conf) + attn == pytest.approx(1.49e9,
                                                                    rel=1e-2)
    # bytes: q, o, do, dq, q, o at 32 heads; k, v, dk, dv, k, v at 4
    assert F.flash_train_bytes_per_token(conf) == 6 * 4 * (32 + 4) * 128 * 2
    assert F.flash_train_bytes_per_token(conf, kind=F.SLIDING) == \
        6 * 3 * 36 * 128 * 2
    assert F.flash_train_bytes_per_token(conf, kind=F.FULL) == 6 * 36 * 128 * 2
    # expert rows: the even share of the pairs
    T = 32768
    assert F.expert_rows_per_step(conf, T) == T * 8 * 16 / 64 == 65536
    assert F.expert_gemm_flops_per_step(conf, T) == \
        4 * 9 * 2.0 * 65536 * E * I
    assert F.expert_gemm_bytes_per_step(conf, T) == \
        4 * 9.0 * (16 * E * I + 65536 * (E + I)) * 2
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    t, bound = F.roofline_seconds(F.expert_gemm_flops_per_step(conf, T),
                                  F.expert_gemm_bytes_per_step(conf, T), peak)
    assert bound == "compute"


class _Trace:
    busy_s, window_s = 4.0, 5.0

    def __init__(self, by_name):
        self.by_name = by_name

    def ops_matching(self, pattern):
        import re

        return sum(v for k, v in self.by_name.items() if re.search(pattern, k))


def test_attention_readers_on_hand_made_observations(cell, manifest):
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    conf = cell.config
    # 10 traced steps of 32,768 tokens in a 5 s window, all of it traced
    obs = {"cell": cell, "peak": peak, "tokens": 327680, "n_devices": 1,
           "window_s": 5.0,
           "trace": _Trace({"self_attn_window": 1.0, "self_attn_full": 0.5,
                            "self_attn": 9.0, "gmm": 3.0}),
           "attention_flops_per_token":
               F.causal_attention_flops_per_token(conf, 8192, 3),
           "attention_bytes_per_token": F.flash_train_bytes_per_token(conf)}
    assert flash_window_share_pct.read(obs) == 25.0
    least_w = F.roofline_seconds(
        F.attention_flops_per_token(conf, 8192, 3, F.SLIDING) * 327680,
        F.flash_train_bytes_per_token(conf, kind=F.SLIDING) * 327680, peak)[0]
    least_f = F.roofline_seconds(
        F.attention_flops_per_token(conf, 8192, 3, F.FULL) * 327680,
        F.flash_train_bytes_per_token(conf, kind=F.FULL) * 327680, peak)[0]
    assert flash_window_roofline.read(obs) == pytest.approx(100 * least_w / 1.0)
    assert flash_full_roofline.read(obs) == pytest.approx(100 * least_f / 0.5)
    # flash_roofline reads both kernels together through trace_names.flash
    assert flash_roofline.read(obs) == pytest.approx(
        100 * (least_w + least_f) / 1.5, rel=1e-6)
    # a window is compute-bound at 960 keys a query, as the full layer is
    assert 0 < flash_window_roofline.read(obs) < 100
    # nothing to read: no trace, no such kernel in it, a cell without names
    for hole in ({"trace": None}, {"trace": _Trace({"gmm": 1.0})},
                 {"peak": None}):
        for reader in (flash_window_roofline, flash_full_roofline):
            assert reader.read(dict(obs, **hole)) is None
    assert flash_window_share_pct.read(dict(obs, trace=None)) is None
    assert flash_window_share_pct.read(
        dict(obs, trace=_Trace({"gmm": 1.0}))) is None
    for other in ("train-xl-z3-1chip", "train-olmoe-z3-1chip"):
        c = M.load_cell(manifest, other, ROOT)
        for reader in (flash_window_roofline, flash_full_roofline,
                       flash_window_share_pct, moe_held_pair_pct):
            assert reader.read(dict(obs, cell=c)) is None


def test_held_pair_share_from_the_programs_counter(cell):
    """Held experts are columns 16-31 of the 64 the counter has a layer."""
    a = np.zeros((2, 64))
    even = np.full((2, 64), 4096.0)
    skew = even.copy()
    skew[0, 16:32] *= 2                                      # 40% held
    b, c = a + even, a + even + skew
    obs = {"cell": cell, moe_load_imbalance.COUNTER: [a, b, b, c, None]}
    # layer shares over two steps: .25, .25, .4, .25 -> median .25
    assert moe_held_pair_pct.read(obs) == pytest.approx(25.0)
    # the driver's required operations come from the window's totals, as
    # the device time does: (3 + 2) x 16 x 4096 of (9 + 8) x 16 x 4096
    assert moe_held_pair_pct.of_the_window(obs) == pytest.approx(5 / 17)
    obs[moe_load_imbalance.COUNTER] = [a, a + skew]
    assert moe_held_pair_pct.read(obs) == pytest.approx(100 * (0.4 + 0.25) / 2)
    for reader in (moe_held_pair_pct.read, moe_held_pair_pct.of_the_window):
        for nothing in ([], [None, a], [a]):
            assert reader(
                {"cell": cell, moe_load_imbalance.COUNTER: nothing}) is None
        assert reader({"cell": cell}) is None
        # a counter narrower than the share (another program's): nothing
        assert reader({"cell": cell, moe_load_imbalance.COUNTER:
                       [a[:, :16], b[:, :16]]}) is None


class _Ctx:
    def __init__(self):
        self.notes, self.lines = [], []

    def log(self, msg):
        self.lines.append(msg)

    def check(self, ok, what):
        if not ok:
            self.notes.append(what)


FAULTS = [None, "window+1", "no_window", "default_rope",
          "no_attention_factor", "kv_mod"]


@pytest.mark.parametrize("fault", FAULTS)
def test_attention_check_sees_what_the_loss_check_cannot(cell, fault,
                                                         monkeypatch):
    """The driver's ``check_attention`` at the rehearsal's widths.  A sound
    program passes; a reference that computes a window one key too long, no
    window, the default table on the full layer, no attention factor, or
    key-value head ``h % kv`` for ``h // group`` is refused, which is the
    same comparison with the fault on the other side."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    model, cfg = driver.model_config(conf)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, cfg.max_position_embeddings)), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    params["embed_tokens"] = params["embed_tokens"] * \
        conf["init_scale"]["embed_tokens"]
    # at 64 wide a 0.02 initialiser gives scores near 0 and a softmax that
    # is uniform whatever the table: give q and k the scale they have at
    # the published width (scores of order 1), so that rotary shows
    for i in range(4):
        attn = params[f"layers_{i}"]["self_attn"]
        for name in ("q_proj_kernel", "k_proj_kernel"):
            attn[name] = attn[name] * 12.0
    hidden = []
    reference.loss_parts(params, ids, attn_inputs=hidden,
                         **driver.reference_kwargs(conf))
    assert len(hidden) == 4
    if fault is not None:
        real = reference.attention
        monkeypatch.setattr(
            reference, "attention",
            lambda kind, *a, **kw: real(kind, *a, **dict(kw, fault=fault))
            if not (fault in ("window+1", "no_window")
                    and kind == "full_attention") else real(kind, *a, **kw))
    ctx = _Ctx()
    driver.check_attention(ctx, cfg, conf, reference, params, hidden)
    assert len(ctx.lines) == 2 and "sliding_attention" in ctx.lines[0] \
        and "full_attention" in ctx.lines[1]
    if fault is None:
        assert ctx.notes == []
    elif fault in ("window+1", "no_window"):
        assert len(ctx.notes) == 1 and "sliding_attention" in ctx.notes[0]
    elif fault in ("default_rope", "no_attention_factor"):
        assert len(ctx.notes) == 1 and "full_attention" in ctx.notes[0]
    else:
        assert len(ctx.notes) == 2


def test_rehearsal_of_the_mellum2_cell_prints_a_correct_line():
    """The whole control flow on the CPU at tiny widths that keep the
    shape (head_dim apart from hidden / heads, 4 query heads on 2 key-value
    heads, window 32 of 128, 4 of 16 experts held from expert 4, top-4)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["workload"] == CELL
    assert line["correct"] is True and line["notes"] == []
    assert line["failed"] == 0 and line["compiles_in_window"] == 0
    assert line["attempted"] >= 3
    for check in ("reference check: engine loss", "expert check",
                  "attention check: layer 0 (sliding_attention)",
                  "attention check: layer 3 (full_attention)"):
        assert check in p.stderr
