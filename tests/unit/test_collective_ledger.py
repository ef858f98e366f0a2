"""``telemetry/device_scopes.py``'s ledger of an executable's collectives:
the parse on hand-written HLO lines (the standard spellings and the v5e
compiler's own, copied from the optimized HLO of GPT-2-XL's ZeRO-3 step
compiled for a described ``v5e:2x2``, PR 50), the gauges booked beside
``hbm_exec_*`` for an executable of more than one device, ZeRO's required
bytes, ``scope_table``'s ``collectives`` on a hand-made event list, and a
two-layer GPT-2 under ZeRO-3 on four of the tests' forced host devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import device_scopes
from deepspeed_tpu.telemetry.registry import Registry

STEP = "jit(step_fn)/"
FWD = STEP + "jvp(GPT2LMHeadModel)/"
BWD = STEP + "transpose(jvp(GPT2LMHeadModel))/jvp(GPT2LMHeadModel)/checkpoint/"


def meta(op_name):
    return f'metadata={{op_name="{op_name}" stack_frame_id=7}}'


def module(*entry_lines, before=""):
    return ("HloModule jit_step_fn, is_scheduled=true, num_partitions=4\n\n"
            + before
            + "ENTRY %main.1_spmd (param.1: f32[8]) -> f32[8] {\n"
            + "".join(f"  {line}\n" for line in entry_lines) + "}\n")


def ledger_of(text):
    return device_scopes._parse_text(text)[1]


# (one instruction's line, what the ledger holds for it)
LINES = [
    ("all-gather, explicit groups",
     '%all-gather.3 = bf16[1600,4800]{0,1} all-gather(%p.1), channel_id=2, '
     'replica_groups={{0,1,2,3}}, dimensions={1}, use_global_device_ids=true, '
     + meta(FWD + "h_3/attn/dot_general"),
     dict(instruction="all-gather.3", op="all-gather", n=4,
          bytes=1600 * 4800 * 2, recv_bytes=1600 * 4800 * 2 * 3 / 4,
          scope="h_*/attn", done=None, times=1, **{"pass": "forward"})),
    ("all-gather, iota groups of the whole mesh",
     '%all-gather.4 = f32[64,256]{1,0:T(8,128)S(1)} all-gather(%p.1), '
     'channel_id=3, replica_groups=[1,4]<=[4], dimensions={1}, '
     + meta(BWD + "rematted_computation/h_0/mlp/dot_general"),
     dict(op="all-gather", n=4, bytes=64 * 256 * 4,
          recv_bytes=64 * 256 * 4 * 3 / 4, scope="h_*/mlp",
          **{"pass": "recompute"})),
    ("all-gather, iota groups along one side of the 2x2",
     '%all-gather.282 = bf16[2,4096,1600]{1,2,0:T(8,128)(2,1)S(1)} '
     'all-gather(%gte.720), channel_id=12, replica_groups=[2,2]<=[2,2]T(1,0), '
     'dimensions={0}, ' + meta(FWD + "loss_head/while/body/dynamic_slice"),
     dict(op="all-gather", n=2, bytes=2 * 4096 * 1600 * 2,
          recv_bytes=4096 * 1600 * 2, scope="loss_head")),
    ("all-gather, no groups written: every partition",
     '%all-gather.5 = s32[8192]{0} all-gather(%p.1), channel_id=4, '
     'replica_groups={}, dimensions={0}, ' + meta(FWD + "embed/gather"),
     dict(op="all-gather", n=4, bytes=8192 * 4, recv_bytes=8192 * 3,
          scope="embed")),
    ("reduce-scatter",
     '%reduce-scatter.9 = f32[400,6400]{1,0} reduce-scatter(%p.1), '
     'channel_id=5, replica_groups={{0,1,2,3}}, dimensions={0}, '
     'to_apply=%add.1, ' + meta(STEP + "zero/scatter/sharding_constraint"),
     dict(op="reduce-scatter", n=4, bytes=400 * 6400 * 4,
          recv_bytes=400 * 6400 * 4 * 3, scope="zero/scatter",
          **{"pass": "forward"})),
    ("all-reduce of a tuple (the combiner's)",
     '%all-reduce.7 = (f32[64,192]{1,0}, f32[192]{0}) all-reduce(%a.1, %b.2), '
     'channel_id=6, replica_groups=[1,4]<=[4], to_apply=%add.1, '
     + meta(BWD + "h_0/attn/dot_general"),
     dict(op="all-reduce", n=4, bytes=(64 * 192 + 192) * 4,
          recv_bytes=2 * (64 * 192 + 192) * 4 * 3 / 4, scope="h_*/attn",
          **{"pass": "backward"})),
    ("all-reduce, no op_name",
     '%all-reduce.8 = f32[]{:T(128)} all-reduce(%p.1), channel_id=7, '
     'replica_groups={{0,1},{2,3}}, to_apply=%add.1, backend_config={}',
     dict(op="all-reduce", n=2, bytes=4, recv_bytes=4.0,
          scope="(no op_name)", **{"pass": ""})),
    ("all-to-all",
     '%all-to-all.2 = bf16[4,512,2048]{2,1,0} all-to-all(%p.1), channel_id=8, '
     'replica_groups={{0,1,2,3}}, dimensions={0}, '
     + meta(STEP + "jvp(LlamaForCausalLM)/layers_2/moe/moe/dispatch/a2a"),
     dict(op="all-to-all", n=4, bytes=4 * 512 * 2048 * 2,
          recv_bytes=4 * 512 * 2048 * 2 * 3 / 4,
          scope="layers_*/moe/dispatch")),
    ("collective-permute",
     '%collective-permute.1 = bf16[576,1600]{1,0} collective-permute(%p.1), '
     'channel_id=9, source_target_pairs={{0,1},{1,2},{2,3}}, '
     + meta(BWD + "h_1/attn/dot_general"),
     dict(op="collective-permute", n=None, bytes=576 * 1600 * 2,
          recv_bytes=576 * 1600 * 2.0, scope="h_*/attn")),
]


@pytest.mark.parametrize("line, want", [c[1:] for c in LINES],
                         ids=[c[0] for c in LINES])
def test_one_collective_line(line, want):
    [rec] = ledger_of(module(line))
    assert {k: rec[k] for k in want} == want
    assert rec["recv_bytes"] == pytest.approx(want["recv_bytes"])


STARTS = [
    ("all-gather-start: the gathered element of (operand, result)",
     ['%all-gather-start.3 = (bf16[1600,1200]{0,1}, bf16[1600,4800]{0,1}) '
      'all-gather-start(%p.1), channel_id=2, replica_groups=[1,4]<=[4], '
      'dimensions={1}, ' + meta(FWD + "h_3/mlp/dot_general"),
      '%fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fc.1',
      '%all-gather-done.3 = bf16[1600,4800]{0,1} '
      'all-gather-done(%all-gather-start.3), ' + meta(FWD + "h_3/mlp/dot")],
     dict(instruction="all-gather-start.3", done="all-gather-done.3",
          op="all-gather", n=4, bytes=1600 * 4800 * 2, scope="h_*/mlp")),
    ("all-gather-start of two operands: ((operands), (results))",
     ['%all-gather-start.4 = ((f32[16]{0}, f32[4,8]{1,0}), (f32[64]{0}, '
      'f32[16,8]{1,0})) all-gather-start(%a.1, %b.1), channel_id=2, '
      'replica_groups={{0,1,2,3}}, dimensions={0}, ' + meta(FWD + "ln_f/mul"),
      '%all-gather-done.4 = (f32[64]{0}, f32[16,8]{1,0}) '
      'all-gather-done(%all-gather-start.4)'],
     dict(instruction="all-gather-start.4", done="all-gather-done.4",
          op="all-gather", bytes=(64 + 128) * 4, scope="ln_f")),
    ("all-reduce-start: its result is the reduced array",
     ['%all-reduce-start.1 = f32[1600,6400]{1,0} all-reduce-start(%p.1), '
      'channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add.1, '
      + meta(BWD + "h_5/mlp/dot_general"),
      '%all-reduce-done.1 = f32[1600,6400]{1,0} '
      'all-reduce-done(%all-reduce-start.1)'],
     dict(instruction="all-reduce-start.1", done="all-reduce-done.1",
          op="all-reduce", bytes=1600 * 6400 * 4,
          recv_bytes=2 * 1600 * 6400 * 4 * 3 / 4, **{"pass": "backward"})),
    ("collective-permute-start with its two contexts (the v5e's)",
     ['%collective-permute-start.6 = (bf16[576,1600]{1,0:T(8,128)(2,1)}, '
      'bf16[576,1600]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) '
      'collective-permute-start(%slice.33), channel_id=175, '
      'source_target_pairs={{0,1},{1,2},{2,3}}, backend_config={}',
      '%collective-permute-done.6 = bf16[576,1600]{1,0:T(8,128)(2,1)} '
      'collective-permute-done(%collective-permute-start.6)'],
     dict(instruction="collective-permute-start.6",
          done="collective-permute-done.6", op="collective-permute",
          bytes=576 * 1600 * 2, recv_bytes=576 * 1600 * 2.0,
          scope="(no op_name)")),
]


@pytest.mark.parametrize("lines, want", [c[1:] for c in STARTS],
                         ids=[c[0] for c in STARTS])
def test_a_start_is_folded_into_its_base_and_names_its_done(lines, want):
    [rec] = ledger_of(module(*lines))
    assert {k: rec[k] for k in want} == want


# what the v5e's compiler writes for an asynchronous all-gather: two
# fusions, each of whose computations holds the collective (one channel)
V5E_ASYNC = (
    "%fused_computation.567 (param_0.1830: bf16[1600,1200]) -> "
    "(bf16[1600,1200], bf16[1600,4800], s32[2], u32[]) {\n"
    "  %param_0.1830 = bf16[1600,1200]{0,1:T(8,128)(2,1)S(1)} parameter(0)\n"
    "  %all-gather.262 = bf16[1600,4800]{0,1:T(8,128)(2,1)} "
    "all-gather(%param_0.1830), channel_id=69, replica_groups=[1,4]<=[4], "
    "dimensions={1}, use_global_device_ids=true, "
    + meta(BWD + "h_0/attn/dot_general") + "\n"
    "  ROOT %custom-call.56 = (bf16[1600,1200]{0,1:T(8,128)(2,1)S(1)}, "
    "bf16[1600,4800]{0,1:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}, u32[]{:S(2)}) "
    "custom-call(%all-gather.262), "
    'custom_call_target="AsyncCollectiveStart"\n}\n\n'
    "%fused_computation.570 (param_0.1843: bf16[1600,1200], param_1.2365: "
    "bf16[1600,4800]) -> bf16[1600,4800] {\n"
    "  %param_0.1843 = bf16[1600,1200]{0,1:T(8,128)(2,1)S(1)} parameter(0)\n"
    "  %all-gather.268 = bf16[1600,4800]{0,1:T(8,128)(2,1)} "
    "all-gather(%param_0.1843), channel_id=69, replica_groups=[1,4]<=[4], "
    "dimensions={1}, use_global_device_ids=true, "
    + meta(BWD + "h_0/attn/dot_general") + "\n"
    "  ROOT %custom-call.58 = bf16[1600,4800]{0,1:T(8,128)(2,1)S(1)} "
    "custom-call(%param_0.1843, %all-gather.268), "
    'custom_call_target="AsyncCollectiveDone"\n}\n\n')
V5E_ASYNC_ENTRY = [
    "%async-collective-start.10 = (bf16[1600,1200]{0,1:T(8,128)(2,1)S(1)}, "
    "bf16[1600,4800]{0,1:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}, u32[]{:S(2)}) "
    "fusion(%custom-call.82), kind=kCustom, "
    "output_to_operand_aliasing={{0}: (0, {})}, "
    "calls=%fused_computation.567, backend_config={}",
    "%fusion.77 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fc.1",
    "%async-collective-done.10 = bf16[1600,4800]{0,1:T(8,128)(2,1)S(1)} "
    "fusion(%get-tuple-element.1523, %get-tuple-element.1524), kind=kCustom, "
    "calls=%fused_computation.570, " + meta(BWD + "h_0/attn/dot_general"),
]
# ... and for a reduce-scatter: one fusion of an all-reduce and the slice
# of it that this device keeps
V5E_SCATTER = (
    "%all-reduce-scatter.2 (input.2: bf16[1600,4800]) -> bf16[1600,1280] {\n"
    "  %input.2 = bf16[1600,4800]{0,1:T(8,128)(2,1)} parameter(0)\n"
    "  %constant.622 = bf16[] constant(0)\n"
    "  %pad.8 = bf16[1600,5120]{0,1:T(8,128)(2,1)} pad(%input.2, "
    "%constant.622), padding=0_0x0_320\n"
    "  %all-reduce.124 = bf16[1600,5120]{0,1:T(8,128)(2,1)} "
    "all-reduce(%pad.8), channel_id=164, replica_groups={{0,1,2,3}}, "
    "use_global_device_ids=true, to_apply=%add.7.clone, "
    'frontend_attributes={from-cross-replica-sharding="true"}, '
    "backend_config={}\n"
    "  %partition-id.32 = u32[] partition-id()\n"
    "  ROOT %dynamic-slice.117 = bf16[1600,1280]{0,1:T(8,128)(2,1)S(1)} "
    "dynamic-slice(%all-reduce.124, %constant.623, %multiply.223), "
    "dynamic_slice_sizes={1600,1280}\n}\n\n")
V5E_SCATTER_ENTRY = [
    "%fusion.9 = bf16[1600,1280]{0,1:T(8,128)(2,1)S(1)} "
    "fusion(%get-tuple-element.1556), kind=kCustom, "
    "calls=%all-reduce-scatter.2, " + meta(BWD + "h_0/attn/dot_general")]


def test_the_v5e_writes_an_asynchronous_gather_as_two_fusions():
    [rec] = ledger_of(module(*V5E_ASYNC_ENTRY, before=V5E_ASYNC))
    assert rec["instruction"] == "async-collective-start.10"
    assert rec["done"] == "async-collective-done.10"
    assert (rec["op"], rec["n"], rec["bytes"]) == (
        "all-gather", 4, 1600 * 4800 * 2)
    assert rec["recv_bytes"] == 1600 * 4800 * 2 * 3 / 4     # counted once
    assert (rec["scope"], rec["pass"]) == ("h_*/attn", "backward")


def test_the_v5e_writes_a_reduce_scatter_as_a_fusion_of_an_all_reduce():
    [rec] = ledger_of(module(*V5E_SCATTER_ENTRY, before=V5E_SCATTER))
    # the trace shows the fusion; the all-reduce has no op_name of its own
    assert rec["instruction"] == "fusion.9" and rec["done"] is None
    assert (rec["op"], rec["n"]) == ("reduce-scatter", 4)
    assert rec["bytes"] == 1600 * 1280 * 2               # the padded shard
    assert rec["recv_bytes"] == 1600 * 1280 * 2 * 3
    assert (rec["scope"], rec["pass"]) == ("h_*/attn", "backward")


def test_a_fused_computations_inner_instruction_keeps_its_op_name():
    scopes, _ = device_scopes._parse_text(
        module(*V5E_ASYNC_ENTRY, before=V5E_ASYNC))
    assert scopes["all-gather.262"] == BWD + "h_0/attn/dot_general"
    assert "async-collective-start.10" not in scopes      # it has none
    assert scopes["async-collective-done.10"].endswith("attn/dot_general")


LOOP = (
    "%cond.1 (p.9: (s32[], f32[8])) -> pred[] {\n"
    "  %constant.1611 = s32[]{:T(128)} constant(3)\n"
    "  %p.9 = (s32[]{:T(128)}, f32[8]{0}) parameter(0)\n"
    "  %gte.631 = s32[]{:T(128)} get-tuple-element(%p.9), index=0\n"
    "  ROOT %lt.56 = pred[]{:T(512)} compare(%gte.631, %constant.1611), "
    "direction=LT\n}\n\n"
    "%body.1 (p.8: (s32[], f32[8])) -> (s32[], f32[8]) {\n"
    "  %p.8 = (s32[]{:T(128)}, f32[8]{0}) parameter(0)\n"
    "  %all-gather.281 = f32[2,8]{1,0} all-gather(%gte.1), channel_id=1, "
    "replica_groups=[2,2]<=[4], dimensions={0}, "
    + meta(FWD + "loss_head/while/body/dynamic_slice") + "\n"
    "  ROOT %tuple.1 = (s32[]{:T(128)}, f32[8]{0}) tuple(%a.1, %b.1)\n}\n\n")


@pytest.mark.parametrize("attrs, trips", [
    ("condition=%cond.1, body=%body.1", 3),
    ('condition=%cond.1, body=%body.1, backend_config='
     '{"known_trip_count":{"n":"12"}}', 12),
    ("condition=%elsewhere, body=%body.1", 1),
], ids=["the condition's i < N", "known_trip_count", "unknown: once"])
def test_a_loops_collective_counts_once_a_trip(attrs, trips):
    ledger = ledger_of(module(
        "%while.15 = (s32[]{:T(128)}, f32[8]{0}) while(%tuple.230), " + attrs,
        before=LOOP))
    [rec] = ledger
    assert (rec["instruction"], rec["times"], rec["n"]) == (
        "all-gather.281", trips, 2)
    assert device_scopes.ledger_totals(ledger) == {
        "all-gather": (trips, trips * 2 * 8 * 4 / 2)}


def test_a_pallas_custom_calls_megabyte_line_is_not_searched_for_one():
    kernel = ('%attn.5 = bf16[4,1024,1600]{2,1,0} custom-call(%q.1, %k.1), '
              'custom_call_target="tpu_custom_call", '
              + meta(FWD + "h_0/attn/attn/pallas_call")
              + ', backend_config={"custom_call_config": {"body": "'
              + "TUlMIGJ5dGVjb2Rl all-gather(" * 80_000 + '"}}')
    assert len(kernel) > 2_000_000
    scopes, ledger = device_scopes._parse_text(module(kernel, LINES[0][1]))
    assert [rec["instruction"] for rec in ledger] == ["all-gather.3"]
    assert scopes["attn.5"].endswith("attn/attn/pallas_call")


def test_a_computation_nothing_calls_is_not_in_the_ledger():
    dead = ("%dead.1 (p.1: f32[8]) -> f32[32] {\n  " + LINES[1][1] + "\n}\n\n")
    assert ledger_of(module("%add.5 = f32[8]{0} add(%p.1, %p.1)",
                            before=dead)) == []


def test_ledger_totals_by_op():
    ledger = ledger_of(module(*(c[1] for c in LINES)))
    totals = device_scopes.ledger_totals(ledger)
    assert {op: n for op, (n, _) in totals.items()} == {
        "all-gather": 4, "reduce-scatter": 1, "all-reduce": 2,
        "all-to-all": 1, "collective-permute": 1}
    assert totals["all-gather"][1] == pytest.approx(
        sum(c[2]["recv_bytes"] for c in LINES if c[2]["op"] == "all-gather"))


# ---------------------------------------------------------------------------
# scope_table's collectives
# ---------------------------------------------------------------------------

def test_scope_table_books_a_dones_wait_to_its_starts_row():
    ms = 1e6
    text = module(*V5E_ASYNC_ENTRY, *V5E_SCATTER_ENTRY, LINES[2][1],
                  before=V5E_ASYNC + V5E_SCATTER)
    scopes, ledger = device_scopes._parse_text(text)
    one_step = [
        ("async-collective-start.10", 0 * ms, 0.01 * ms),
        ("fusion.77", 1 * ms, 4 * ms),                # compute hides it ...
        ("async-collective-done.10", 5 * ms, 2 * ms),     # ... but for 2 ms
        ("fusion.9", 8 * ms, 3 * ms),           # synchronous: all of it
        ("all-gather.282", 12 * ms, 0.5 * ms),
    ]
    events = one_step + [(n, s + 50 * ms, d) for n, s, d in one_step]
    table = device_scopes.scope_table(events, scopes, steps=2, ledger=ledger)
    rows = {(r["op"], r["scope"], r["pass"]): r for r in table["collectives"]}
    assert set(rows) == {("all-gather", "h_*/attn", "backward"),
                         ("reduce-scatter", "h_*/attn", "backward"),
                         ("all-gather", "loss_head", "forward")}
    gather = rows["all-gather", "h_*/attn", "backward"]
    assert gather["ms_a_step"] == pytest.approx(2.01)
    assert gather["instructions"] == 1
    assert gather["recv_mib_a_step"] == pytest.approx(
        1600 * 4800 * 2 * 0.75 / 2**20)
    assert rows["reduce-scatter", "h_*/attn", "backward"]["ms_a_step"] == \
        pytest.approx(3.0)
    assert table["collectives"][0]["op"] == "reduce-scatter"   # largest first
    assert table["collective_ms_a_step"] == pytest.approx(5.51)
    # the same events stay in the table by scope: nothing leaves it
    assert table["device_ms_a_step"] == pytest.approx(9.51)


def test_scope_table_without_a_ledger_has_no_collective_row():
    table = device_scopes.scope_table([("fusion.1", 0.0, 1e6)], {}, steps=1)
    assert table["collectives"] == [] and table["collective_ms_a_step"] == 0


# ---------------------------------------------------------------------------
# the gauges, and an executable of one device
# ---------------------------------------------------------------------------

class _Sharding:
    def __init__(self, n):
        self.device_set = set(range(n))


class _Compiled:
    """What ``record_collectives`` asks an executable for."""

    def __init__(self, n_devices, text=""):
        self.input_shardings = ((_Sharding(n_devices),), {})
        self.output_shardings = _Sharding(n_devices)
        self._text, self.asked = text, 0

    def as_text(self):
        self.asked += 1
        return self._text


def samples(registry, name):
    entry = registry.snapshot().get(name) or {"samples": []}
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in entry["samples"]}


def test_an_executable_of_one_device_is_never_asked_for_its_text():
    registry, compiled = Registry(), _Compiled(1, module(LINES[0][1]))
    device_scopes.record_collectives(compiled, "unit.step", registry)
    assert compiled.asked == 0
    assert not [k for k in registry.snapshot() if k.startswith("step_coll")]


def test_an_executable_of_four_books_its_ledger_once():
    registry = Registry()
    compiled = _Compiled(4, module(*(c[1] for c in LINES)))
    device_scopes.record_collectives(compiled, "unit.step", registry)
    assert compiled.asked == 1
    count = samples(registry, "step_collectives")
    assert count[("op", "all-gather"), ("site", "unit.step")] == 4
    assert count[("op", "reduce-scatter"), ("site", "unit.step")] == 1
    recv = samples(registry, "step_collective_recv_bytes")
    assert recv[("op", "reduce-scatter"), ("site", "unit.step")] == \
        400 * 6400 * 4 * 3
    [seconds] = samples(registry, "step_collective_parse_seconds").values()
    assert 0 < seconds < 5
    # the scope map comes from the same parse: the text is not asked again
    assert device_scopes.instruction_scopes(compiled)["all-gather.3"]
    assert device_scopes.collective_ledger(compiled) is \
        device_scopes.collective_ledger(compiled)
    assert compiled.asked == 1


def _engine(mesh, remat=False, **config):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    cfg = gpt2_config("gpt2-tiny", dtype=jnp.float32, n_layer=2, remat=remat,
                      scan_layers=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "steps_per_print": 10**9, **config})
    engine.init_params()
    ids = np.zeros((engine.train_batch_size, 128), np.int32)

    def batches():
        while True:
            yield {"input_ids": ids, "labels": ids}

    return engine, batches()


@pytest.fixture()
def fresh_registry():
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.telemetry import get_registry

    mesh_mod.set_mesh(None)
    reg = get_registry()
    with reg._lock:     # emptied for the test, then as it was: a metric
        kept = dict(reg._metrics)   # another test's module holds (the
        reg._metrics.clear()        # goodput gauges) must outlive this one
    yield reg
    mesh_mod.set_mesh(None)
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


def test_a_one_device_engine_books_nothing_and_fetches_no_text(
        fresh_registry, monkeypatch):
    from deepspeed_tpu.comm import mesh as mesh_mod

    asked = []
    real = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self, *a, **k: asked.append(self)
                        or real(self, *a, **k))
    engine, batches = _engine(mesh_mod.build_mesh(
        {"dp": 1}, devices=jax.devices()[:1]))
    engine.train_batch(data_iter=batches)
    float(engine.eval_batch(next(batches)))
    assert engine.compiled_step() is not None and asked == []
    held = fresh_registry.snapshot()
    assert not [k for k in held
                if k.startswith(("step_collective", "zero_required"))]
    assert samples(fresh_registry, "hbm_exec_reserved_bytes")  # as before
    assert device_scopes.spans_devices(engine.compiled_step()) == 1


def _leaf_bytes(tree, *path):
    for key in path:
        tree = tree[key]
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_two_layers_under_zero3_on_four_devices(fresh_registry, remat):
    """What XLA's CPU partitioner makes of ZeRO-3 at this size (float32,
    2 x 128 tokens a device): every block's weights gathered once forward
    (and kept for the backward; the remat's second forward gathers the
    vectors again and no matrix), the tied table gathered for the
    embedding and for the head, and the gradients reduced by ONE combined
    all-reduce (the table's two contributions apart), of which each
    device keeps its slice: twice a reduce-scatter's bytes."""
    from deepspeed_tpu.comm import mesh as mesh_mod

    engine, batches = _engine(mesh_mod.build_mesh(
        {"fsdp": 4, "dp": 1}, devices=jax.devices()[:4]), remat=remat)
    engine.train_batch(data_iter=batches)
    params = engine.state.params
    every = _leaf_bytes(params)
    required = samples(fresh_registry, "zero_required_recv_bytes")
    # every leaf of this model is sharded: 3/4 of it comes from elsewhere
    assert required == {(("what", "gather"),): every * 3 / 4,
                        (("what", "scatter"),): every * 3 / 4}

    ledger = device_scopes.collective_ledger(engine.compiled_step())
    assert device_scopes.spans_devices(engine.compiled_step()) == 4
    gathers = [r for r in ledger if r["op"] == "all-gather"]
    modules = {"embed", "h_*/attn", "h_*/mlp", "h_*/ln_*", "ln_f",
               "loss_head"}
    assert {r["scope"] for r in gathers} == modules
    assert all(r["n"] == 4 and r["times"] == 1 for r in ledger)

    def received(scope, pass_):
        return sum(r["recv_bytes"] for r in gathers
                   if (r["scope"], r["pass"]) == (scope, pass_))

    blocks = [params[f"h_{i}"] for i in range(2)]
    for module_ in ("attn", "mlp"):
        one_pass = sum(_leaf_bytes(b, module_) for b in blocks) * 3 / 4
        assert received(f"h_*/{module_}", "forward") == one_pass
        assert received(f"h_*/{module_}", "backward") == 0
        # the second forward gathers bias vectors again and no matrix
        again = received(f"h_*/{module_}", "recompute")
        assert 0 < again < 0.02 * one_pass if remat else again == 0
    table = _leaf_bytes(params, "wte") * 3 / 4
    assert received("embed", "forward") == table
    assert received("loss_head", "forward") == table

    totals = device_scopes.ledger_totals(ledger)
    assert set(totals) == {"all-gather", "all-reduce"}
    booked = samples(fresh_registry, "step_collective_recv_bytes")
    assert booked == {(("op", op), ("site", "engine.train_step")): recv
                      for op, (_, recv) in totals.items()}
    # zero_gather_passes, as the benchmark reads it: one pass and the
    # table's second gather, less the position table (all-reduced instead)
    passes = totals["all-gather"][1] / required[("what", "gather"),]
    assert 1.0 < passes < 1.25
    # the gradients: an all-reduce moves twice what a reduce-scatter does
    grads = max(ledger, key=lambda r: r["recv_bytes"])
    assert grads["op"] == "all-reduce" and grads["pass"] == "backward"
    assert grads["recv_bytes"] == 2 * (every * 3 / 4 + table)
    assert samples(fresh_registry, "step_collective_parse_seconds")


def test_required_recv_bytes_counts_what_the_spec_shards():
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.parallel import zero

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(1, 1, 4, 1, 1, 2),
                ("pp", "dp", "fsdp", "ep", "sp", "tp"))
    leaves = {"w": jax.ShapeDtypeStruct((64, 256), jnp.float32),
              "tp_w": jax.ShapeDtypeStruct((64, 256), jnp.float32),
              "small": jax.ShapeDtypeStruct((7,), jnp.float32),
              "ids": jax.ShapeDtypeStruct((64,), jnp.int32)}
    specs = {"w": P(None, "fsdp"), "tp_w": P("tp", "fsdp"), "small": P(),
             "ids": P("fsdp")}
    full = 64 * 256
    assert zero.required_recv_bytes(leaves, specs, mesh) == \
        (full * 4 + full * 4 // 2 + 64 * 4) * 3 // 4
    # in the compute type: floating leaves narrow, the ids do not
    assert zero.required_recv_bytes(leaves, specs, mesh, jnp.bfloat16) == \
        (full * 2 + full * 2 // 2 + 64 * 4) * 3 // 4
