"""A traced run's ``breakdown`` names XLA's instructions by the program's
scopes (PR 59): ``benchmark/trace_reduce.py`` books a device event under its
kernel's name where it is a Pallas custom call, under the scope of its
``op_name`` where the step's ``{instruction name: op_name}`` map has one, and
under its cut instruction name where it has none; what every per-layer reader
sees (``op_self_s``, ``ops_matching``) is what it was, to the digit, with or
without the map; and only a traced run asks the program for the map.
"""
import glob
import json
import os
import sys
import types

import pytest

from benchmark import trace_reduce
from benchmark.harness import manifest as M
from benchmark.harness import runner

ROOT = M.ROOT
us = 1e3
STEP = "jit(step_fn)/jit(main)/"
# the step's map as ``telemetry/device_scopes.py instruction_scopes`` gives
# it: fusions under the program's scopes, a Pallas custom call whose op_name
# ends in ``pallas_call``, and no entry for the compiler's own copies
SCOPES = {
    "fusion.412": STEP + "jvp(LlamaForCausalLM)/layers_3/kda_attn/"
                         "linear_attn/delta_rule/dot_general",
    "fusion.7": STEP + "transpose(jvp(LlamaForCausalLM))/layers_0/"
                       "kda_attn/linear_attn/delta_rule/mul",
    "convolution_bitcast_fusion.2": STEP + "jvp(LlamaForCausalLM)/layers_1/"
                                           "moe/moe/route/gate/dot_general",
    "multiply_reduce_fusion": STEP + "jvp(LlamaForCausalLM)/loss_head/"
                                     "reduce_sum",
    "fusion.9": STEP + "optimizer/adam8bit/add",
    "fusion.11": STEP + "add",
    "while.3": STEP + "jvp(LlamaForCausalLM)/layers_2/linear_attn/"
                      "linear_attn/delta_rule/while",
    "gmm.3": STEP + "jvp(LlamaForCausalLM)/layers_1/moe/moe/experts/gmm/"
                    "pallas_call",
    "self_attn_mla.12": STEP + "jvp(LlamaForCausalLM)/layers_5/self_attn/"
                               "self_attn_mla/pallas_call",
    "adam8bit.40": STEP + "optimizer/adam8bit/pallas_call",
}
KERNELS = ["gmm.3", "tgmm.1", "self_attn_mla.12", "self_attn_window.2",
           "self_attn_full.1", "self_attn_blockdiff", "attn.5", "self_attn.3",
           "short_conv_rows.1", "short_conv_rows_back.1", "gated_delta_fwd.4",
           "gated_delta_bwd.2", "indexer_select.1", "indexed_attn_fwd.1",
           "indexed_attn_dq.1", "indexed_attn_dkv.1", "adam8bit.40",
           "moe_rows_back.6", "shard_map.2", "all-gather-start.1",
           "all-reduce.4", "jit_step_fn"]


def _events():
    """One device's ``XLA Ops`` line as ``read_xplane`` hands it on: names
    cut, each with its whole instruction name beside it."""
    names = ["fusion.412", "fusion.7", "convolution_bitcast_fusion.2",
             "multiply_reduce_fusion", "fusion.9", "fusion.11", "copy.31",
             "copy.32", "fusion.999"] + KERNELS
    evs, t = [], 0.0
    for i, name in enumerate(names):
        dur = (7 + 3 * i % 11) * us
        evs.append((trace_reduce.OpName(name), t, dur))
        t += dur + 1 * us
    # a loop that encloses two of its body's fusions on the same line
    evs.append((trace_reduce.OpName("while.3"), t, 40 * us))
    evs.append((trace_reduce.OpName("fusion.412"), t + 2 * us, 8 * us))
    evs.append((trace_reduce.OpName("copy.31"), t + 20 * us, 10 * us))
    return evs


def _summary(scopes):
    return trace_reduce.summarize({0: _events()}, {}, [], scopes=scopes)


def test_an_event_keeps_its_instruction_beside_the_cut_name():
    event = types.SimpleNamespace(
        name="%fusion.412 = f32[8,128]{1,0} fusion(%p), kind=kLoop")
    name = trace_reduce._op_name(event)
    assert name == "fusion" and isinstance(name, str)
    assert name.instruction == "fusion.412"
    for whole, cut in [("gmm", "gmm"), ("copy-done.3", "copy-done"),
                       ("self_attn_mla.12", "self_attn_mla"),
                       ("all-gather-start_7", "all-gather-start"),
                       ("loop_fusion-2", "loop_fusion")]:
        assert trace_reduce.OpName(whole) == cut
        assert trace_reduce.OpName(whole).instruction == whole
    assert json.dumps({name: 1.0}) == '{"fusion": 1.0}'


@pytest.mark.parametrize("instruction, booked", [
    ("fusion.412", "kda_attn/linear_attn/delta_rule"),  # the index cut
    ("fusion.7", "kda_attn/linear_attn/delta_rule"),    # backward, layer 0
    ("while.3", "linear_attn/delta_rule"),      # Gated DeltaNet's module
    ("convolution_bitcast_fusion.2", "moe/route/gate"),  # moe/moe folds
    ("multiply_reduce_fusion", "loss_head"),
    ("fusion.9", "optimizer/adam8bit"),
    ("fusion.11", "(step)"),                        # an op_name of no scope
    ("gmm.3", "gmm"),                               # a Pallas custom call
    ("self_attn_mla.12", "self_attn_mla"),
    ("adam8bit.40", "adam8bit"),
    ("copy.31", "copy"),                            # no op_name at all
    ("fusion.999", "fusion"),
])
def test_an_instruction_is_booked_by_kernel_then_scope_then_cut_name(
        instruction, booked):
    name = trace_reduce.OpName(instruction)
    times = trace_reduce.booked_times([(name, 0.0, 5.0)], SCOPES)
    assert times == {booked: 5.0} and type(next(iter(times))) is str
    assert (trace_reduce.xla_scope(instruction, SCOPES) is None) == (
        booked == name)
    # a hand-made event's plain name is looked up as it stands
    assert set(trace_reduce.booked_times([(instruction, 0.0, 5.0)], SCOPES)
               ) <= {booked, instruction}


def test_the_breakdown_adds_up_the_layers_under_one_scope():
    s = _summary(SCOPES)
    top = dict(s.breakdown()["device_ops"])
    own = {e[0].instruction: 0.0 for e in _events()}
    for name, _, dur in trace_reduce.self_times(_events()):
        own[name.instruction] += dur * 1e-9
    assert top["kda_attn/linear_attn/delta_rule"] == pytest.approx(
        own["fusion.412"] + own["fusion.7"])
    assert top["linear_attn/delta_rule"] == pytest.approx(own["while.3"])
    assert own["while.3"] == pytest.approx(22e-6)       # 40 - 8 - 10
    assert s.booked_self_s["gmm"] == pytest.approx(own["gmm.3"])
    assert s.booked_self_s["copy"] == pytest.approx(
        own["copy.31"] + own["copy.32"])
    assert s.booked_self_s["fusion"] == pytest.approx(own["fusion.999"])
    assert sum(s.booked_self_s.values()) == pytest.approx(
        sum(s.op_self_s.values()))
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and b["idle_gaps"] == \
        _summary(None).breakdown()["idle_gaps"]
    assert [v for _, v in b["device_ops"]] == sorted(
        (v for _, v in b["device_ops"]), reverse=True)
    json.dumps(b)


def test_a_kernel_keeps_its_name_to_itself_where_its_scope_has_the_same():
    """GPT-2's flash custom call is ``attn`` and stands in the scope
    ``h_<i>/attn``: XLA's instructions of that scope do not join it."""
    scopes = {"attn.2": STEP + "jvp(M)/h_3/attn/attn/pallas_call",
              "attn.9": STEP + "transpose(jvp(M))/h_0/attn/attn/pallas_call",
              "fusion.1": STEP + "jvp(M)/h_3/attn/split",
              "fusion.2": STEP + "jvp(M)/h_3/attn/c_attn/dot_general",
              "fusion.3": STEP + "jvp(M)/h_1/mlp/c_fc/dot_general"}
    evs = [(trace_reduce.OpName(n), 10.0 * i * us, d * us)
           for i, (n, d) in enumerate([("fusion.1", 5), ("attn.2", 7),
                                       ("fusion.2", 9), ("attn.9", 4),
                                       ("fusion.3", 3), ("copy.3", 1)])]
    s = trace_reduce.summarize({0: evs}, {}, [], scopes=scopes)
    assert s.booked_self_s == pytest.approx(
        {"attn": 11e-6, "attn/(xla)": 5e-6, "attn/c_attn": 9e-6,
         "mlp/c_fc": 3e-6, "copy": 1e-6})
    assert s.ops_matching("^attn$") == pytest.approx(11e-6)


@pytest.mark.parametrize("scopes", [None, {}], ids=["no-map", "empty-map"])
def test_without_a_map_the_breakdown_is_todays_byte_for_byte(scopes):
    s = _summary(scopes)
    assert s.booked_self_s == {}
    today = {"device_ops": [[k, v] for k, v in sorted(
                 s.op_self_s.items(), key=lambda kv: -kv[1])[:10]],
             "idle_gaps": [[k, v] for k, v in s.longest_gaps[:10]]}
    assert json.dumps(s.breakdown()) == json.dumps(today)
    plain = trace_reduce.summarize(
        {0: [(str(n), t, d) for n, t, d in _events()]}, {}, [])
    assert json.dumps(plain.breakdown()) == json.dumps(s.breakdown())


def _trace_names():
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs",
                                              "*.json"))):
        with open(path) as f:
            names = json.load(f).get("trace_names", {})
        out += [pytest.param(pattern, id=f"{os.path.basename(path)[:-5]}-{key}")
                for key, pattern in sorted(names.items())]
    return out


@pytest.mark.parametrize("pattern", _trace_names())
def test_a_reader_matches_what_it_matched_with_and_without_the_map(pattern):
    """Every ``trace_names`` pattern of every configuration file reads the
    same seconds, to the digit, whether or not the breakdown has a map."""
    with_map, without = _summary(SCOPES), _summary(None)
    assert with_map.ops_matching(pattern) == without.ops_matching(pattern)
    assert with_map.op_self_s == without.op_self_s
    assert with_map.busy_s == without.busy_s
    plain = trace_reduce.summarize(
        {0: [(str(n), t, d) for n, t, d in _events()]}, {}, [])
    assert plain.ops_matching(pattern) == with_map.ops_matching(pattern)


def test_a_pattern_of_every_kind_finds_its_kernels_in_the_synthetic_line():
    """The lines above compare something: the patterns find events."""
    found = {p.values[0] for p in _trace_names()
             if _summary(SCOPES).ops_matching(p.values[0]) > 0}
    assert len(found) >= 10, found


@pytest.mark.parametrize("op_name", sorted(set(SCOPES.values())) + [
    STEP + "jvp(M)/h_11/attn/attn/pallas_call",
    STEP + "transpose(jvp(M))/checkpoint/rematted_computation/layers_2/moe/"
           "moe/experts/gmm/pallas_call",
    STEP + "jvp(M)/mtp_0/mtp/block/self_attn/attn/mla_q/dot_general",
    STEP + "jvp(M)/layers_4/mul", "jit(step_fn)/grad_clip/sqrt", "x"])
def test_the_yardsticks_cut_is_the_programs_less_the_layers_index(op_name):
    """``scope_name`` is ``device_scopes.scope_of`` (copied: the yardstick
    keeps its own) with the leading indexed scope dropped, three names
    deep."""
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.telemetry.device_scopes import scope_of

    theirs = scope_of(op_name, 99).split("/")
    if len(theirs) > 1 and theirs[0].endswith("_*"):
        theirs = theirs[1:]
    assert trace_reduce.scope_name(op_name) == "/".join(theirs[:3])
    assert not trace_reduce.scope_name(op_name).startswith(
        ("layers_", "h_", "mtp_")) or "/" not in \
        trace_reduce.scope_name(op_name)


def _context(trace, logged):
    manifest = M.load_manifest(ROOT)
    cell = M.load_cell(manifest, "train-xl-z3-1chip", ROOT)
    ctx = runner.Context(cell, 1, 10.0, trace, False, None, 0.0)
    ctx.log = logged.append
    return ctx


def test_an_untraced_run_never_asks_for_the_map(monkeypatch):
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.telemetry import device_scopes

    asked = []
    monkeypatch.setattr(device_scopes, "instruction_scopes",
                        lambda compiled: asked.append(compiled) or SCOPES)
    engine = types.SimpleNamespace(compiled_step=lambda: "the step")
    logged = []
    assert _context(False, logged).step_scopes(engine) == {}
    assert asked == [] and logged == []
    assert _context(True, logged).step_scopes(engine) == SCOPES
    assert asked == ["the step"] and len(logged) == 1
    assert f"{len(SCOPES)} instructions" in logged[0]


@pytest.mark.parametrize("driver", ["train", "train_lm"])
def test_a_driver_asks_once_after_the_window(driver):
    """Both places that own a window ask through ``ctx.step_scopes``, after
    ``setup_s`` is taken and the trace is stopped."""
    with open(os.path.join(ROOT, "benchmark", "drivers",
                           driver + ".py")) as f:
        text = f.read()
    assert text.count("ctx.step_scopes(engine)") == 1
    assert text.index("setup_s = time.perf_counter()") \
        < text.index("ctx.stop_trace()") \
        < text.index("ctx.step_scopes(engine)")
    assert "instruction_scopes(" not in text.replace(
        '"instruction_scopes": ctx.step_scopes(engine)', "")


def test_the_result_line_says_what_a_driver_said_in_words():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 123}

    ctx = _context(False, [])
    out = {"setup_s": 50.0, "window_s": 10.0, "attempted": 40, "failed": 0,
           "end_to_end": {"train_tokens_per_s_chip": 9000.0},
           "observed": {"steps": 40, "instruction_scopes": {},
                        "gated_delta_impl": "xla x 21 (a decay a key "
                                            "channel)"}}
    line = runner.result_line(ctx, out, [Dev()], "end_to_end")
    assert line["said"] == {"gated_delta_impl": "xla x 21 (a decay a key "
                                                "channel)"}
    assert list(line)[:len(runner.RESULT_KEYS)] == list(runner.RESULT_KEYS)
    out["observed"] = {"steps": 40}
    assert "said" not in runner.result_line(ctx, out, [Dev()], "end_to_end")
