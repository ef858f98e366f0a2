"""Kernels of the main path compiled at real widths for a v5e chip that is
described, not attached (the on-chip-measurement guide, section 2): what
the TPU compiler would refuse on the chip (a tile that overflows VMEM, a
block off the tiling) it refuses here, at no chip time.  Nothing runs.

The topology is described inside a fixture and only in this file: the
TPU library loads in the one worker that is given these tests.
"""
import functools
import math

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


# sha256 of what the flash call's loss and gradients trace to at the cells'
# attention shapes (``flash_parent_sweep.traced_digest`` of the functions the
# tests below compile), computed on PR 47's tree (parent 4f98df5), which
# changes every flash kernel by design: the forward keeps its softmax
# denominator a partial sum a lane and the backward holds its score tiles
# keys by queries.  A later refactor that must not change a cell's kernels
# is held to these.  The primitives of the fifth cell's kernels are counted
# as on the parent of PR 44: the change adds and removes none of them.
PARENT_TRACES = {
    # (rows, positions, heads, head_dim[, window | "halves" | rope lanes])
    (2, 1024, 25, 64):
        "05459b753fc63e1b0ef2ab48c2a748e4c965f79afc99a4ea3345904317b05f70",
    (2, 4096, 16, 128):
        "12afc74b316c7f9ca9ea0fa2d16c62a3c0f20fbc571785947cf603ffedcc4838",
    (2, 2048, 8, 256):
        "4c9d615ce06da68ed71982de8c3bded7d55935774cc953052223385a8d96ce71",
    (4, 8192, 32, 128, 1024):
        "3caf6196e6658a815b3c60700f1575c8f9202943777540c8e8ef76bcbc78a5d6",
    (4, 8192, 32, 128, None):
        "f7bcfdf1aef826a03a834506131cc4aaccd9c22c9f9369f546d87ccfe495c91e",
    (3, 8192, 32, 128, 2048):
        "df28ec4b5f0af00ca013bf18cbce241a2ed77b4b46a713b3db1bca124f8e3f28",
    (3, 8192, 32, 128, None):
        "b1a8ddd710fbb5ac9a8ef3ba5a0b950c29d96dd72aeb9320f912bb580bb54b6a",
    (2, 16384, 32, 128, "halves"):
        "cdc952787974ef68870d6b88057c4f186778673ac821fd0322b159404fc6a491",
    (2, 8192, 32, 128, 64):
        "28ef601ac1a77e0702959e2e66f3db06e4801fbd3ce3121e07bbbc04b6c906a6",
}
PARENT_TWO_PRODUCTS = {"while": 2, "cond": 5, "dot_general": 60, "exp": 14}


def _parents_trace(grads, *args):
    """``traced_digest`` of a cell's flash call, with every product of the
    kernel bodies it traces counted under float32 operands
    (``flash_mxu_operands_total``): PR 47 measured the products with bf16
    operands on the chip, found them no faster (the MXU rounds a float32
    operand itself, at a bf16 one's rate) and a backward call 3 to 8%
    slower, and left every product's operands as they were."""
    from tests.unit.flash_parent_sweep import (traced_digest,
                                               traced_operand_types)

    with traced_operand_types() as types:
        digest = traced_digest(grads, *args)
    assert types == {"float32"}
    return digest


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_grouped_matmul_compiles_at_olmoe_widths(one_chip, k, n):
    """The expert matmuls of ``train-olmoe-z3-1chip``: 8192 tokens x top-8
    rows over 64 experts, forward and both backward kernels, at the tiles
    ``ops/grouped_matmul.py`` picks for the shape."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from deepspeed_tpu.ops.grouped_matmul import TILES, _tiles

    rows, experts = 8192 * 8, 64
    tiles = _tiles(rows, k, n)
    assert tiles == TILES

    def loss(lhs, rhs, sizes):
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
                   tiling=tiles).astype(jnp.float32).sum()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        arg((rows, k), jnp.bfloat16), arg((experts, k, n), jnp.bfloat16),
        arg((experts,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_two_product_flash_compiles_at_the_fifth_cells_shape(one_chip):
    """The latent attention of ``train-joyai-flash-8k-1chip`` (PR 38): two
    8192-token rows of 32 heads, 128 nope + 64 rope channels with one rope
    key for all heads, values 128 wide; the forward and the one backward
    kernel, whose whole-sequence float32 scratch (dq_nope, dq_rope and the
    heads' dk_rope) and double-buffered panels ask Mosaic for more VMEM
    than its default.  Since PR 44 a second term of the one kernel family:
    its kernels hold the loops, branches, products and exponentials that
    the two-product family's held on the parent commit (counted there)."""
    from tests.unit.flash_parent_sweep import kernel_primitives

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D, R = 2, 8192, 32, 128, 64

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(qn, qr, kn, kr, v):
        return flash_attention(qn, kn, v, q_rope=qr, k_rope=kr).astype(
            jnp.float32).sum()

    grads = jax.value_and_grad(loss, argnums=range(5))
    args = (arg(B, S, H, D), arg(B, S, H, R), arg(B, S, H, D),
            arg(B, S, 1, R), arg(B, S, H, D))
    assert _parents_trace(grads, *args) == PARENT_TRACES[B, S, H, D, R]
    assert kernel_primitives(grads, *args, names=(
        "cond", "while", "scan", "dot_general", "exp")) == PARENT_TWO_PRODUCTS
    compiled = jax.jit(grads).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_sorted_dispatch_compiles_per_rank_on_four_chips(topo, monkeypatch):
    """``fsdp 4`` over the host's 2x2 chips, OLMoE's widths, 2 x 4096 tokens
    a chip: every rank runs the Pallas grouped matmuls on its own 65,536
    sorted rows inside a ``shard_map`` (nine kernels forward and backward),
    the ZeRO-sharded expert leaves are gathered for it and their gradients
    summed over the ranks; no XLA ragged dot is left in the program."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.parallel.moe import sorted_dispatch

    # the dispatch asks jax.devices() for the platform and would see the CPU
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4, 1, 1, 1),
                mesh_mod.MESH_AXES)
    monkeypatch.setattr(mesh_mod, "_CURRENT_MESH", mesh)
    tokens, k, experts, embed, mlp = 4 * 8192, 8, 64, 2048, 1024

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    def loss(x, weights, chosen, *ws):
        return sorted_dispatch(x, weights, chosen, ws, "swiglu"
                               ).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 3, 4, 5))).lower(
        arg((tokens, embed), jnp.bfloat16, "fsdp"),
        arg((tokens, k), jnp.float32, "fsdp"),
        arg((tokens, k), jnp.int32, "fsdp"),
        arg((experts, embed, mlp), jnp.bfloat16, None, "fsdp"),
        arg((experts, embed, mlp), jnp.bfloat16, None, "fsdp"),
        arg((experts, mlp, embed), jnp.bfloat16, None, None, "fsdp"),
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 9
    assert "ragged" not in text
    assert "all-gather" in text and ("reduce-scatter" in text
                                     or "all-reduce" in text)


@pytest.mark.parametrize("first_expert,kernels", [(16, True), (None, False)])
def test_a_shares_dispatch_moves_rows_with_the_row_kernels(
        topo, monkeypatch, first_expert, kernels):
    """One expert layer forward and backward on one described v5e at
    Mellum 2's widths (8192 tokens x top-8 of 64, rows of 2304, experts of
    896).  A share (16 held): the rows move through the custom calls
    ``moe_rows_out`` / ``moe_rows_back`` and no XLA gather or scatter of a
    ``bf16[S k, M]`` or ``bf16[S, k, M]`` array is left beside them.  Every
    expert held (a full permutation): the guard keeps XLA's gathers, and
    says why."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.parallel.moe import sorted_dispatch

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1, 1, 1, 1),
                mesh_mod.MESH_AXES)
    monkeypatch.setattr(mesh_mod, "_CURRENT_MESH", mesh)
    tokens, k, embed, mlp = 8192, 8, 2304, 896
    experts = 16 if kernels else 64

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    def loss(x, weights, chosen, *ws):
        return sorted_dispatch(x, weights, chosen, ws, "swiglu", first_expert
                               ).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        arg((tokens, embed), jnp.bfloat16), arg((tokens, k), jnp.float32),
        arg((tokens, k), jnp.int32),
        arg((experts, embed, mlp), jnp.bfloat16),
        arg((experts, embed, mlp), jnp.bfloat16),
        arg((experts, mlp, embed), jnp.bfloat16)).compile().as_text()
    calls = re.findall(r"%(moe_rows_\w+?)[.\d]* = ", text)
    rows = rf"bf16\[(?:{tokens * k},{embed}|{tokens},{k},{embed})\]"
    moved_by_xla = [line for line in text.splitlines()
                    if re.search(rf"= {rows}\S* (?:gather|scatter)\(", line)]
    reasons = {(impl, reason) for site, impl, reason, _ in dispatch_report()
               if site == "moe_rows"}
    if not kernels:
        assert not calls and moved_by_xla
        assert ("xla", "every row holds a pair") in reasons
        return
    # forward: x packed and gathered out, y packed and combined; backward:
    # the cotangent packed and gathered out scaled, the d-weights, and the
    # dispatch's d-tokens (packed and combined)
    assert calls.count("moe_rows_out") == 4, calls
    assert calls.count("moe_rows_back") == 5, calls
    assert not moved_by_xla, moved_by_xla
    assert ("pallas", f"rows {tokens * k} x {embed}, block 1024") in reasons


@pytest.mark.parametrize("kernel", ["gather", "gather_scaled", "combine",
                                    "combine_dw"])
@pytest.mark.parametrize("tokens,k,width", [(32768, 8, 2304),
                                            (32768, 4, 2048)])
def test_the_row_kernels_compile_at_the_share_cells_shapes(
        one_chip, tokens, k, width, kernel):
    """Mellum 2's and the seventh cell's rows: each body of PR 46 (a trip's
    vector work with the next sub-block's starts dealt through it, and
    bare; a group of rows a wait) through Mosaic for the described v5e."""
    from deepspeed_tpu.ops.pallas import moe_rows

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = tokens * k
    idx, live = arg((rows,), jnp.int32), arg((1,), jnp.int32)
    weights = arg((tokens, k), jnp.float32)
    fn, args = {
        "gather": (moe_rows.gather_rows, (
            arg((tokens, 1, width // 2), jnp.uint32), idx, live)),
        "gather_scaled": (moe_rows.gather_rows, (
            arg((tokens, 1, width // 2), jnp.uint32), idx, live,
            arg((rows, 1), jnp.float32))),
        "combine": (moe_rows.combine_rows, (
            arg((rows, 1, width // 2), jnp.uint32), idx, weights)),
        "combine_dw": (moe_rows.combine_rows, (
            arg((rows, 1, width // 2), jnp.uint32), idx, weights,
            arg((tokens, width), jnp.bfloat16))),
    }[kernel]
    text = jax.jit(lambda *a: fn(*a, name="moe_rows_back")).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows,width", [(262144, 896), (196608, 1024),
                                        (262144, 768), (131072, 1536),
                                        (245760, 512)])
def test_the_swiglu_row_kernels_compile_at_the_share_cells_shapes(
        one_chip, rows, width):
    """PR 61: the SwiGLU between a share's grouped matmuls, forward and
    backward, over ``[a | b]`` of Mellum 2, Trinity, SDAR (Keye, JoyAI and
    Ling are as wide), LFM2 and Qwen3-Next through Mosaic for the described
    v5e; the backward writes ``d[a | b]`` into ``[a | b]``'s buffer."""
    from deepspeed_tpu.ops.pallas import moe_rows

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ab, live = arg((rows, 2 * width)), arg((1,), jnp.int32)
    forward = jax.jit(moe_rows.swiglu_rows).lower(ab, live).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    backward = jax.jit(moe_rows.swiglu_rows_back, donate_argnums=1).lower(
        arg((rows, width)), ab, live).compile()
    assert backward.as_text().count("tpu_custom_call") == 1
    assert backward.memory_analysis().temp_size_in_bytes == 0


# The matrices of the two train cells: GPT-2-XL's block, table and
# positions; OLMoE's expert stacks, attention projections, table and head.
# (6400, 1600), (50304, 1600) and (1024, 1600) are stored column-major on
# the chip, (2048, 50304) has rows too wide for a 128-row block.
XL_LEAVES = [(1600, 4800), (1600, 1600), (1600, 6400), (6400, 1600),
             (50304, 1600), (1024, 1600), (1600,)]
OLMOE_LEAVES = [(64, 2048, 1024), (64, 1024, 2048), (2048, 2048),
                (50304, 2048), (2048, 50304), (2048, 64), (2048,)]


def _compile_update(one_chip, monkeypatch, shapes):
    """The int8 AdamW update alone (``engine._apply_grads``' optimizer
    half: global norm, clip, ``kernel_apply_factory``), bf16 gradients,
    donated state, compiled for the described chip."""
    import optax

    from deepspeed_tpu.ops import adam8bit as a8, attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     a8.adamw_8bit(1e-4, weight_decay=0.1))
    apply = a8.kernel_apply_factory(learning_rate=1e-4, b1=0.9, b2=0.999,
                                    eps=1e-8, weight_decay=0.1, clip=1.0)
    params = {f"leaf{i}": jax.ShapeDtypeStruct(s, jnp.float32)
              for i, s in enumerate(shapes)}
    grads = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
             for k, v in params.items()}
    opt = jax.eval_shape(tx.init, params)

    def update(grads, params, opt):
        norm = optax.global_norm(jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads))
        return apply(grads, params, opt, norm, jnp.float32(1.0))

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    return jax.jit(update, donate_argnums=(1, 2)).lower(
        on_chip(grads), on_chip(params), on_chip(opt)).compile()


def _entry(text):
    """``{name: (result shape, opcode, operand names)}`` of the entry
    computation of an optimized HLO module."""
    import re

    ops = {}
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)(?=\s[\w\-]+\()"
                     r"|\S+) ([\w\-]+)\((.*)", line)
        if m:
            body = m.group(4).split("custom_call_target")[0]
            ops[m.group(1)] = (m.group(2), m.group(3),
                               re.findall(r"%([\w.\-]+)", body))
    return ops


@pytest.mark.parametrize("B,S,H,D,layout", [
    (2, 1024, 25, 64, "rows layout, 2 heads a 128-lane block"),
    (2, 4096, 16, 128, "rows layout, 1 head a 128-lane block"),
    (2, 2048, 8, 256, "rows layout, 1 head a 256-lane block")])
def test_flash_kernels_compile_at_both_cells_shapes(one_chip, B, S, H, D,
                                                    layout):
    """The flash forward and backward of ``train-xl-z3-1chip`` (12.5 lane
    blocks: the ragged edge) and ``train-olmoe-z3-1chip`` (the largest
    panels in VMEM: q, dO, the bf16 dq block and its float32 scratch), on
    operands shaped as the projections write them; and GPT-J's heads at
    its context, the widest lane block (it fits in 256-row key blocks)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          flash_lanes)

    assert flash_lanes(H, D).reason == layout

    def loss(q, k, v):
        split = lambda x: x.reshape(B, S, H, D)
        return flash_attention(split(q), split(k), split(v)).astype(
            jnp.float32).sum()

    arg = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    grads = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _parents_trace(grads, arg, arg, arg) == PARENT_TRACES[B, S, H, D]
    text = jax.jit(grads).lower(arg, arg, arg).compile().as_text()
    results = [shape for shape, op, _ in _entry(text).values()
               if op == "custom-call" and shape.startswith("(")]
    assert len(results) == 2
    # o and lse; dq, dk and dv in the operands' type, none in float32
    assert results[0].count(f"bf16[{B},{S},{H * D}]") == 1
    assert results[1].count(f"bf16[{B},{S},{H * D}]") == 3
    assert f"f32[{B},{S}," not in results[1]


def test_xl_step_has_no_layout_copy_around_flash(topo, one_chip, monkeypatch):
    """Loss and gradient of a 2-layer GPT-2 at XL's widths with the cell's
    ``model_options``, compiled for one described chip: the flash calls
    take q, k, v and dO and give o, dq, dk and dv as ``[2,1024,1600]``, so
    the optimized HLO holds no ``[2,1024,25,64]`` copy (before PR 29:
    eight a layer) and no ``f32[50,1024,64]`` kernel output (three a
    layer), and each call keeps the name ``attn`` that the benchmark's
    ``trace_names.flash`` finds it by."""
    import re

    import flax
    import numpy as np
    from jax.sharding import Mesh

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
    from deepspeed_tpu.ops import attention

    # the dispatch asks jax.devices() and would see eight CPUs and no mesh
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(mesh_mod, "_CURRENT_MESH", Mesh(
        np.asarray(topo.devices[:1]).reshape((1,) * len(mesh_mod.MESH_AXES)),
        mesh_mod.MESH_AXES))
    model = GPT2LMHeadModel(gpt2_config(
        "gpt2-xl", n_layer=2, scan_layers=False, remat=True,
        remat_policy="dots_saveable+flash", attn_impl="auto",
        loss_chunk=8192))
    ids = jnp.zeros((2, 1024), jnp.int32)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids))))

    def loss(params, ids):
        return model.apply(params, ids, labels=ids)["loss"]

    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, jax.ShapeDtypeStruct(ids.shape, ids.dtype, sharding=one_chip)
    ).compile().as_text()
    ops = _entry(text)
    calls = [n for n, v in ops.items()
             if v[1] == "custom-call" and re.fullmatch(r"attn(\.\d+)?", n)]
    assert len(calls) == 4, sorted(ops)     # forward and backward a layer
    assert not [n for n, v in ops.items()
                if v[1] == "copy" and "[2,1024,25,64]" in v[0]]
    assert "f32[50,1024,64]" not in text and "[50,1024,64]" not in text


@pytest.mark.parametrize("cell,shapes,kernels", [
    ("xl", XL_LEAVES, 6), ("olmoe", OLMOE_LEAVES, 5)])
def test_adam8bit_update_is_one_in_place_pass_a_leaf(one_chip, monkeypatch,
                                                     cell, shapes, kernels):
    """What made the earlier one-pass kernel lose (PERF.md section 6,
    PR 27), asserted on the executable at no chip time: one custom call a
    leaf of a block or more; the bf16 gradient reaches it as it is (no
    leaf-shaped fp32 buffer written by a convert or multiply fusion); no
    layout copy in or out of a call (scales lane-dense, column-major
    leaves taken as their transpose); state aliased in to out, so the
    compiler's temporaries are blocks, not leaves."""
    import re

    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    def booked():
        return {(impl, reason): n for site, impl, reason, n
                in dispatch_report() if site == "adam8bit"}

    before = booked()
    compiled = _compile_update(one_chip, monkeypatch, shapes)
    new = {k: n - before.get(k, 0) for k, n in booked().items()
           if n > before.get(k, 0)}
    assert new == {("kernel", "one device, whole leaves"): kernels,
                   ("xla", "leaf under one block"): len(shapes) - kernels}
    ops = _entry(compiled.as_text())
    calls = {n: v for n, v in ops.items()
             if v[1] == "custom-call" and n.startswith("adam8bit")}
    assert len(calls) == kernels, sorted(calls)
    leaf_dims = {",".join(map(str, s)) for s in shapes if len(s) > 1}
    leaf_dims |= {",".join(map(str, s[::-1])) for s in shapes if len(s) == 2}
    for name, (shape, op, _) in ops.items():
        m = re.match(r"f32\[([\d,]+)\]", shape)
        if m and m.group(1) in leaf_dims:
            assert op not in ("fusion", "convert", "multiply", "copy"), \
                f"{name}: a leaf-shaped fp32 buffer written by {op}"

    def through(name):      # views cost nothing; follow them
        while ops.get(name, ("", "", []))[1] in ("bitcast",
                                                 "get-tuple-element"):
            name = ops[name][2][0]
        return name

    for name, (_, _, operands) in calls.items():
        for o in operands:
            assert ops.get(through(o), ("", "parameter"))[1] != "copy", \
                f"{name}: operand {o} is a copy"
    for name, (_, op, operands) in ops.items():
        if op == "copy":
            assert not through(operands[0]).startswith("adam8bit"), \
                f"{name} copies a result of {through(operands[0])}"
    # 64 MB is the issue's bound for the two expert stacks; the XLA chain
    # reads 0.85 MB there, the kernel as it was before PR 27 1,208.5 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_stored_transposed_is_the_chips_own_layout(one_chip):
    """``adam8bit_kernel.stored_transposed`` predicts which 2-D leaves the
    TPU keeps column-major (the minor dimension is the one that pads less
    on 128 lanes); a wrong guess is not wrong numbers but seven transposing
    copies around a call.  Held to the compiler's entry layouts, for the
    three dtypes of a leaf's arrays."""
    import re

    from deepspeed_tpu.ops.pallas.adam8bit_kernel import stored_transposed

    shapes = [s for s in XL_LEAVES + OLMOE_LEAVES if len(s) == 2] + [
        (4800, 1600), (1600, 50304), (384, 1000), (1000, 384), (300, 200),
        (200, 300), (768, 3072), (3072, 768), (50304, 768), (4096, 11008),
        (11008, 4096), (5120, 13824)]
    for dtype in (jnp.float32, jnp.bfloat16, jnp.int8):
        args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
                for s in shapes]
        text = jax.jit(lambda *a: [x + 1 for x in a]).lower(
            *args).compile().as_text()
        layouts = re.search(r"entry_computation_layout=\{\((.*?)\)->", text,
                            re.S).group(1)
        minor_to_major = re.findall(r"\w+\[[\d,]*\]\{([\d,]+)", layouts)
        assert len(minor_to_major) == len(shapes)
        for s, order in zip(shapes, minor_to_major):
            assert stored_transposed(s) == (order == "0,1"), (s, dtype, order)


@pytest.mark.parametrize("window,B", [(1024, 4), (None, 4), (2048, 3),
                                      (None, 3)])
def test_flash_kernels_compile_at_the_third_cells_shape(one_chip, window, B):
    """The flash forward and backward of ``train-mellum2-8k-1chip``: 4 rows
    of 8192, 32 query heads on 4 key-value heads of 128, with the 1024-key
    window and without; and of ``train-trinity-mini-8k-1chip``'s window
    layers (3 rows, 2048 keys) and full ones.  Since PR 43 a windowed sweep is ONE
    straight-line block of three (five) tiles and the loop over full tiles
    folds two a trip: the bodies' values must still fit the VMEM the calls
    ask for.  The backward's panels of q, dO and dq beside the
    float32 sums of a key-value head's dk and dv pass Mosaic's default 16
    MB of VMEM, so the call asks for what it holds; k, v, dk and dv are
    ``[B,8192,512]`` on both sides of both calls and nothing key- or
    value-shaped is 4096 wide; the calls carry the layer type's name."""
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          flash_lanes)

    S, H, KV, D = 8192, 32, 4, 128
    assert flash_lanes(H, D).reason == "rows layout, 1 head a 128-lane block"
    scope = "self_attn_window" if window else "self_attn_full"

    def loss(q, k, v):
        # as in the model: the layer type's scope inside the module's
        with jax.named_scope("self_attn"), jax.named_scope(scope):
            out = flash_attention(q.reshape(B, S, H, D),
                                  k.reshape(B, S, KV, D),
                                  v.reshape(B, S, KV, D), window=window)
        return out.astype(jnp.float32).sum()

    wide = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((B, S, KV * D), jnp.bfloat16,
                                  sharding=one_chip)
    grads = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _parents_trace(grads, wide, narrow, narrow) \
        == PARENT_TRACES[B, S, H, D, window]
    text = jax.jit(grads).lower(wide, narrow, narrow).compile().as_text()
    calls = {name: (shape, ops) for name, (shape, op, ops)
             in _entry(text).items() if op == "custom-call"
             and shape.startswith("(")}
    assert len(calls) == 2 and all(n.startswith(scope) for n in calls)
    fwd, bwd = sorted(calls.values(), key=lambda c: c[0].count("bf16["))
    assert fwd[0].count(f"bf16[{B},{S},{H * D}]") == 1          # o
    assert bwd[0].count(f"bf16[{B},{S},{H * D}]") == 1          # dq
    assert bwd[0].count(f"bf16[{B},{S},{KV * D}]") == 2         # dk, dv
    # nothing 4096 wide is made from k or v: no repeat to the query heads
    entry = _entry(text)
    params = {n for n, (_, op, _) in entry.items() if op == "parameter"}
    assert {"k.1", "v.1"} <= params, params
    for name, (shape, op, operands) in entry.items():
        if op != "custom-call" and f"{H * D}]" in shape.split("{")[0]:
            assert not {"k.1", "v.1"} & set(operands), (name, shape)
    assert f"[{B},{S},{KV},{H // KV},{D}]" not in text


@pytest.mark.parametrize("family,rows,seq,heads,kv,fields", [
    ("olmoe", 2, 4096, 16, 16, dict(qk_norm=True)),
    ("mellum2", 4, 8192, 32, 4, dict(
        layer_types=("sliding_attention",), sliding_window=1024,
        rope_parameters={"rope_type": "yarn", "rope_theta": 500000.0,
                         "factor": 16.0,
                         "original_max_position_embeddings": 8192})),
    ("trinity", 3, 8192, 32, 4, dict(
        layer_types=("sliding_attention",), sliding_window=2048,
        qk_norm="head", attn_gate=True)),
])
def test_q_and_k_stay_rows_from_projection_to_flash(topo, one_chip,
                                                    monkeypatch, family, rows,
                                                    seq, heads, kv, fields):
    """Loss and gradient of one remat block at a cell's attention shape
    (its FFN cut to 1024), compiled for one described chip (PR 34): the
    rotation (and Trinity's per-head norm) is the ``qk_rows`` custom call,
    forward, the remat's forward and ``qk_rows_back``, between the
    projection and the flash call; the optimized HLO holds no copy and no
    64-lane half of anything ``(rows, seq, heads, 128)``-shaped (before:
    a reshape copy each way and four half slices a pass), and the flash
    calls keep their names."""
    import re

    import flax
    import numpy as np
    from jax.sharding import Mesh

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.llama import LlamaBlock, LlamaConfig
    from deepspeed_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(mesh_mod, "_CURRENT_MESH", Mesh(
        np.asarray(topo.devices[:1]).reshape((1,) * len(mesh_mod.MESH_AXES)),
        mesh_mod.MESH_AXES))
    cfg = LlamaConfig(vocab_size=1024, hidden_size=2048,
                      intermediate_size=1024, num_hidden_layers=1,
                      num_attention_heads=heads, num_key_value_heads=kv,
                      head_dim=128, max_position_embeddings=seq, **fields)
    kind = (cfg.layer_types or (None,))[0]
    block = LlamaBlock(cfg, kind=kind)
    x = jax.ShapeDtypeStruct((rows, seq, cfg.hidden_size), cfg.dtype,
                             sharding=one_chip)
    pos = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            block.init, jax.random.PRNGKey(0), x, (pos, None))["params"]))

    @jax.checkpoint
    def layer(p, x, pos):
        return block.apply({"params": p}, x, (pos, None))[0]

    def loss(p, x, pos):
        return (layer(p, x, pos).astype(jnp.float32) ** 2).mean()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x, pos).compile().as_text()
    ops = _entry(text)
    calls = sorted(re.sub(r"\.\d+$", "", n) for n, v in ops.items()
                   if v[1] == "custom-call")
    scope = "self_attn_window" if kind else "self_attn"
    assert [c for c in calls if c.startswith("qk_rows")] == \
        ["qk_rows", "qk_rows", "qk_rows_back"], calls
    assert len([c for c in calls if c.startswith(scope)]) == 3, calls
    four_d = re.compile(rf"\[{rows},{seq},({heads}|{kv}),(128|64)\]")
    assert not [(n, v[0]) for n, v in ops.items()
                if v[1] in ("copy", "slice", "concatenate")
                and four_d.search(v[0])]
    assert not re.search(rf"bf16\[{rows},{seq},({heads}|{kv}),64\]", text)


@pytest.mark.parametrize("chunk", [128, 8192])
@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_the_head_multiplies_its_logits_out_once(one_chip, family, chunk):
    """Loss and gradient of a 2-layer model whose chunked head runs over
    2 x 256 tokens in four chunks of 128, or in one (a scan of one trip is
    inlined, and XLA's scheduler is then free to put ``dW`` off and make
    the logits a second time for it), compiled for one described chip: the
    optimized HLO holds three matrix products under ``loss_head`` — the
    logits, ``dh`` and ``dW`` — all in the forward rule's scope and none in
    the backward's (before PR 39: one and three), the eval step holds the
    one of the logits, and ``lm_head_products_total`` says the same of the
    rules as they are traced."""
    import re

    import flax

    from deepspeed_tpu.telemetry import registry

    if family == "llama":
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=1000, hidden_size=256, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=256,
            loss_chunk=chunk))
    else:
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

        model = GPT2LMHeadModel(gpt2_config(
            "gpt2-tiny", n_layer=2, n_positions=256, scan_layers=False,
            loss_chunk=chunk))
    ids = jnp.zeros((2, 256), jnp.int32)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids))))
    ids = jax.ShapeDtypeStruct(ids.shape, ids.dtype, sharding=one_chip)

    def loss(params, ids):
        return model.apply(params, ids, labels=ids)["loss"]

    counter = registry.counter("lm_head_products_total", labelnames=("pass",))

    def products():
        return {p: counter.labels(p).value
                for p in ("primal", "forward", "backward")}

    def head_dots(fn):
        before = products()
        text = jax.jit(fn).lower(params, ids).compile().as_text()
        traced = {k: v - before[k] for k, v in products().items()}
        return traced, re.findall(
            r' (?:convolution|dot)\(.*op_name="([^"]*loss_head[^"]*)"', text)

    traced, dots = head_dots(jax.value_and_grad(loss))
    assert len(dots) == 3, dots
    assert not [d for d in dots if "transpose(" in d], dots
    assert traced == {"primal": 0, "forward": 3, "backward": 0}
    traced, dots = head_dots(loss)
    assert len(dots) == 1, dots
    assert traced == {"primal": 1, "forward": 0, "backward": 0}


def test_block_diffusion_leaves_nothing_of_the_own_block_to_xla(
        topo, one_chip, monkeypatch):
    """Loss and gradient of a two-layer model with ``diffusion`` set,
    lowered for one described chip (PR 41): under the ``self_attn_blockdiff``
    scope there are one custom call a layer and pass and what every flash
    call has around it: the free reshapes of the rows, the name of the saved
    output (a ``reduce_precision`` to its own type) and, in the backward,
    ``_delta``'s row sums of dO * O (a multiply and one product with a 0/1
    matrix a layer).  No ``exponential``, no ``divide``, no ``concatenate``,
    no ``slice`` of q, k or v and no other ``dot_general``: the own block's
    scores, the log-sum-exp merge and the halves' slices are the kernels'.
    Mosaic then takes the whole step."""
    import re

    import flax
    import numpy as np
    from jax.sharding import Mesh

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(mesh_mod, "_CURRENT_MESH", Mesh(
        np.asarray(topo.devices[:1]).reshape((1,) * len(mesh_mod.MESH_AXES)),
        mesh_mod.MESH_AXES))
    rows, seq, heads, kv, layers = 2, 256, 4, 2, 2
    cfg = LlamaConfig(vocab_size=1024, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=layers, num_attention_heads=heads,
                      num_key_value_heads=kv, head_dim=128,
                      max_position_embeddings=seq, qk_norm="head",
                      scan_layers=False, remat=True,
                      remat_policy="dots_saveable+flash", loss_chunk=256,
                      diffusion={"block_length": 4, "mask_token_id": 1023})
    model = LlamaForCausalLM(cfg)
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        flax.core.meta.unbox(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((rows, seq), jnp.int32),
                               labels=jnp.zeros((rows, seq), jnp.int32))
        )["params"]))

    def loss(p, ids, key):
        return model.apply({"params": p}, ids, labels=ids,
                           rngs={"diffusion": key})["loss"]

    lowered = jax.jit(jax.value_and_grad(loss)).lower(params, ids, key)
    text = lowered.as_text(debug_info=True)
    where = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    under = [(op, where[loc]) for op, loc in re.findall(
        r'= "?((?:stablehlo|chlo)\.[\w.]+)"?.*loc\((#loc\d+)\)\s*$', text, re.M)
        if "self_attn_blockdiff" in where.get(loc, "")]
    ops = [op.split(".", 1)[1] for op, _ in under]
    # the forward (its output saved: "+flash") and the backward, a layer
    assert ops.count("custom_call") == 2 * layers, sorted(set(ops))
    assert ops.count("reduce_precision") == layers
    assert [name.rsplit("/", 2)[1] for op, name in under
            if op.endswith("dot_general")] == ["nsw,hw->nhs"] * layers
    for gone in ("exponential", "divide", "concatenate", "slice",
                 "dynamic_slice", "maximum", "log", "reduce", "select"):
        assert gone not in ops, (gone, sorted(set(ops)))
    # q, k and v go in as the projections wrote them: 2 * seq rows
    assert f"tensor<{rows}x{2 * seq}x{heads * 128}xbf16>" in text
    assert f"tensor<{rows}x{seq}x{heads}x128x" not in text
    compiled = lowered.compile().as_text()
    assert compiled.count("self_attn_blockdiff") >= 2 * layers


@pytest.mark.slow
def test_the_sixth_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-sdar-blockdiff-8k-1chip`` (PR 40) as the benchmark builds it,
    its whole train step compiled for the described chip: Mosaic takes the
    one flash call a layer and pass over all (2, 2 x 8192, 32 / 4, 128) rows
    (PR 41; two calls of 8192 rows before), each named
    ``self_attn_blockdiff``; k and v stay 512 wide; and what the step
    reserves (arguments + outputs - aliases + temporaries) stays under the
    chip's 15.75 GiB with the room the set-up's comparisons need."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-sdar-blockdiff-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        compiled = engine._compiled_train_step.lower(
            engine.abstract_state(batch), batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.diffusion.block_length) == (2, 8192, 4)
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 4.0 < reserved < 12.3, reserved      # 12.3 at PR 40; 15.75 a chip
    text = compiled.as_text()
    calls = re.findall(r"self_attn_blockdiff[.\d]* = (\(.*?\)) custom-call\(",
                       text)
    # a layer: the forward (under the remat too) and the backward
    fwd = [c for c in calls if "f32[2,32,1,16384]" in c]
    bwd = [c for c in calls if c.count("bf16[2,16384,512]") == 2]
    assert len(bwd) == cfg.num_hidden_layers, len(calls)
    assert len(fwd) in (len(bwd), 2 * len(bwd)), len(calls)
    assert len(calls) == len(fwd) + len(bwd)
    assert not re.search(r"bf16\[2,(8192|16384),4,8,128\]", text)   # no k/v repeat
    assert "bf16[2,8192,4096]" not in text       # no half of q sliced out


def test_the_halves_kernels_compile_at_the_sixth_cells_shape(one_chip):
    """The flash forward and backward of ``train-sdar-blockdiff-8k-1chip``
    alone: 2 rows of ``[noisy ; clean]`` = 16,384 positions, 32 query heads
    on 4 key-value heads of 128, blocks of 4.  Their loops over clean FULL
    tiles fold two a trip (PR 43) beside 2L-row panels that already ask
    Mosaic for 68 MB of VMEM."""
    from deepspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_halves

    B, L, H, KV, D = 2, 8192, 32, 4, 128

    def loss(q, k, v):
        return flash_attention_halves(
            q.reshape(B, 2 * L, H, D), k.reshape(B, 2 * L, KV, D),
            v.reshape(B, 2 * L, KV, D), block=4).astype(jnp.float32).sum()

    wide = jax.ShapeDtypeStruct((B, 2 * L, H * D), jnp.bfloat16,
                                sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((B, 2 * L, KV * D), jnp.bfloat16,
                                  sharding=one_chip)
    grads = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _parents_trace(grads, wide, narrow, narrow) \
        == PARENT_TRACES[B, 2 * L, H, D, "halves"]
    compiled = jax.jit(grads).lower(wide, narrow, narrow).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_the_first_cells_flash_kernels_are_the_parents(one_chip):
    """``train-xl-z3-1chip``'s attention, (2, 1024, 25, 64) in 512-tiles: a
    sweep of two tiles is no loop and takes neither new form (PR 43), so the
    kernels trace to the parent's jaxprs, to the letter (the parent's sweeps
    are kept beside the tests), and still compile."""
    from tests.unit.flash_parent_sweep import (kernel_primitives,
                                               parent_sweeps)

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D = 2, 1024, 25, 64
    arg = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    def text():
        return str(jax.make_jaxpr(lambda *a: grads(*a))(arg, arg, arg))

    new = text()
    with parent_sweeps():
        old = text()
    assert new == old and new.count("pallas_call") == 2
    found = kernel_primitives(grads, arg, arg, arg)
    assert "while" not in found and "scan" not in found
    jax.jit(grads).lower(arg, arg, arg).compile()


def test_the_short_conv_kernels_compile_at_the_seventh_cells_shape(one_chip):
    """The row kernels of ``ops/pallas/short_conv.py`` (PR 45) at
    ``train-lfm2-hybrid-8k-1chip``'s shape: four rows of 8192 positions,
    3 x 2048 channels as ``in_proj`` wrote them, 3 taps; the forward and the
    one backward kernel, whose float32 scratches (a block and its 8 rows of
    halo, twice in the backward) sit beside double-buffered 256-row blocks
    of the whole 6144-lane row."""
    from deepspeed_tpu.ops.pallas import short_conv as kernel

    B, S, C, L = 4, 8192, 2048, 3
    assert kernel.supported(S, C, L, jnp.bfloat16) is None

    def loss(bcu, w):
        return kernel.short_conv_rows(bcu, w).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((B, S, 3 * C), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((C, L), jnp.float32, sharding=one_chip)
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "short_conv_rows_back" in text
    # d rows come back whole, as wide as the projection's output
    assert f"bf16[{B},{S},{3 * C}]" in text


def test_the_ungated_conv_kernels_compile_at_the_eighth_cells_shape(
        one_chip, monkeypatch):
    """The same body without the gates and with silu after the filter
    (PR 51) at ``train-qwen3next-gdn-8k-1chip``'s shape: three rows of 8192
    positions, the 8192 channels of ``[q ; k ; v]``, 4 taps, through
    ``ops/short_conv.py causal_conv_rows`` as ``GatedDeltaNet`` calls it.  The
    channels go by blocks of 2048 on a third grid axis, so the float32
    scratches cost what the seventh cell's do; the custom calls have names of
    their own, which LFM2's ``trace_names`` cannot match."""
    import re

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import short_conv as kernel
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.ops.short_conv import causal_conv_rows

    B, S, C, L = 3, 8192, 8192, 4
    assert kernel._grid(B, S, C, False) == ((B, S // kernel.BLOCK, 4), 2048)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)

    def loss(x, w):
        return causal_conv_rows(x, w, "silu").astype(jnp.float32).sum()

    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            jax.ShapeDtypeStruct((B, S, C), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((C, L), jnp.float32, sharding=one_chip)
        ).compile()
    finally:
        mesh_lib.set_mesh(None)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    calls = set(re.findall(r"%(\w+?)[.\d]* = [^=]*? custom-call\(", text))
    assert calls == {"causal_conv_rows", "causal_conv_rows_back"}, calls
    assert not re.search("^short_conv_rows(_back)?$", "causal_conv_rows")
    assert any((s, i) == ("short_conv", "pallas") and r == (
        "ungated, rows 8192 x 8192, 4 taps, silu; one device")
        for s, i, r, n in dispatch_report() if n)


@pytest.mark.slow
def test_the_seventh_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-lfm2-hybrid-8k-1chip`` (PR 45) as the benchmark builds it,
    its whole train step compiled for the described chip: three kinds of
    block in one unrolled stack; each of the four conv layers runs the
    filter's forward kernel (under the remat too) and its backward once, on
    ``in_proj``'s ``(4, 8192, 6144)`` rows as they lie; the one attention
    layer's flash kernels take grouped queries at head_dim 64 with k and v
    repeated to 32 heads; the tied table has no second leaf; and what the
    step reserves stays under the chip's 15.75 GiB with the room the
    set-up's comparisons need."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-lfm2-hybrid-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.kinds.count("conv")) == (4, 8192, 4)
    assert "lm_head" not in state.params and "embed_tokens" in state.params
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 4.0 < reserved < 12.6, reserved      # 12.33 at PR 45; 15.75 a chip
    text = compiled.as_text()
    forward = re.findall(r"short_conv_rows[.\d]* = (\S+) custom-call\(", text)
    backward = re.findall(r"short_conv_rows_back[.\d]* = (\(.*?\)) "
                          r"custom-call\(", text)
    assert len(backward) == 4 and len(forward) == 8, (forward, backward)
    assert all(f.startswith("bf16[4,8192,2048]") for f in forward)
    assert all("bf16[4,8192,6144]" in b for b in backward)
    assert len(re.findall(r"self_attn_full[.\d]* = ", text)) >= 2
    # the report is the process's: a worker may have run other files first
    sites = [(s, i, r) for s, i, r, n in dispatch_report() if n]
    assert any((s, i) == ("attention", "flash") and "k and v repeated 4x" in r
               for s, i, r in sites), sites
    assert any((s, i) == ("short_conv", "pallas")
               and "rows 8192 x 3 x 2048, 3 taps; one device" in r
               for s, i, r in sites), sites


def test_the_gated_delta_kernels_compile_at_the_eighth_cells_shape(one_chip):
    """The two fused kernels of ``ops/pallas/gated_delta.py`` (PR 49) at
    ``train-qwen3next-gdn-8k-1chip``'s shape, one row of the batch as the
    rule walks it: 16 key heads x 2 value heads of 128 channels, 128 chunks
    of 64 positions, four chunks a grid step, operands in the layout the
    layer writes.  Forward and backward are three custom calls:
    ``gated_delta_fwd`` for ``o``, ``gated_delta_fwd`` again for the 64 KB
    state entering each chunk (written once, read once), ``gated_delta_bwd``.
    What the preparation makes stays in VMEM: the compiled text holds no
    ``U`` / ``W`` / solve operand (``f32[.., 64, 256]`` or ``[.., 64, 128]``
    a chunk) and no ``P`` or ``A`` (``[.., 64, 64]``)."""
    import re

    from deepspeed_tpu.ops import gated_delta as ops
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    B, S, Hk, Hv, d, C = 1, 8192, 16, 32, 128, 64
    N = S // C
    assert kernel.supported(N, C, d, d, jnp.bfloat16, Hv // Hk) is None

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((B, S, Hk * d), jnp.bfloat16), sd((B, S, Hk * d), jnp.bfloat16),
            sd((B, S, Hv * d), jnp.bfloat16), sd((B, S, Hv), jnp.float32),
            sd((B, S, Hv), jnp.float32))
    text = jax.jit(jax.value_and_grad(
        lambda *a: ops._rule(*a, C, False).astype(jnp.float32).sum(),
        range(5))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert len(re.findall(r"gated_delta_fwd[.\d]* = ", text)) == 2
    assert len(re.findall(r"gated_delta_bwd[.\d]* = ", text)) == 1
    states = f"f32[{B},{Hv},{N},{d},{d}]"
    assert len(re.findall(r"gated_delta_fwd[.\d]* = " + re.escape(states),
                          text)) == 1                   # kept once
    assert not re.search(rf"\[[\d,]*{N},{C},({C}|{d}|{2 * d})\]", text)


@pytest.mark.parametrize("site", ["qk_rows", "gated_norm_rows"])
def test_the_norms_row_kernels_compile_at_the_eighth_cells_shape(
        one_chip, monkeypatch, site):
    """A Gated DeltaNet layer's two per-head norms on the rows (PR 53) at
    ``train-qwen3next-gdn-8k-1chip``'s shape, through the guards as the
    model calls them.  ``qk_rows``: the l2-norms of q and k, 16 heads of 128
    each, as the norm pass under constant scales and no table - a signature
    of ``qk_rows`` / ``qk_rows_back`` that no attention layer has.
    ``gated_norm_rows``: ``rms_norm(o, w) * silu(z)`` over 32 value heads,
    the pair ``gated_norm_rows`` / ``gated_norm_rows_back``, ``dw`` a grid
    step's own sum.  No operand or result is a ``(3, 8192, heads, 128)``
    array, which on the chip would be a copy either way."""
    import re

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.ops import attention, gated_delta, rotary
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    B, S, d = 3, 8192, 128
    monkeypatch.setattr(attention, "on_tpu", lambda: True)

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if site == "qk_rows":
        args = (sd((B, S, 16 * d)), sd((B, S, 16 * d)))

        def loss(q, k):
            plan = rotary.rows_plan(q, k, d, norm=True)
            q, k = rotary.rotate_rows(
                q, k, None, d, plan,
                q_scale=jnp.full((d,), 1 / d, jnp.float32),
                k_scale=jnp.full((d,), d ** -0.5, jnp.float32), eps=1e-6 / d)
            return (q.astype(jnp.float32) * k.astype(jnp.float32)).sum()

        said = f"head_dim {d}, rows 2048 + 2048; one device"
    else:
        args = (sd((B, S, 32 * d)), sd((B, S, 32 * d)), sd((d,), jnp.float32))

        def loss(o, z, w):
            plan = gated_delta.gated_norm_plan(o, d)
            y = gated_delta.gated_norm_rows(o, z, w, d, plan, eps=1e-6)
            return (y.astype(jnp.float32) ** 2).sum()

        said = f"head_dim {d}, rows 4096; one device"
    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        text = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))))).lower(*args).compile(
                ).as_text()
    finally:
        mesh_lib.set_mesh(None)
    calls = re.findall(r"%(\w+?)[.\d]* = [^=]*? custom-call\(", text)
    assert sorted(calls) == [site, site + "_back"], calls
    assert not re.search(rf"\[{B},{S},\d+,{d}\]", text)
    assert (site, "pallas", said) in {r[:3] for r in dispatch_report()
                                      if r[3]}


def test_flash_kernels_compile_at_the_eighth_cells_shape(one_chip):
    """The flash forward and backward of ``train-qwen3next-gdn-8k-1chip``'s
    one attention layer: 3 rows of 8192, 16 query heads on 2 key-value
    heads of 256 channels, one head a 256-lane block, the backward's key
    block cut to 256; k, v, dk and dv stay ``[3,8192,512]``."""
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          flash_lanes)

    B, S, H, KV, D = 3, 8192, 16, 2, 256
    assert flash_lanes(H, D).reason == "rows layout, 1 head a 256-lane block"

    def loss(q, k, v):
        with jax.named_scope("self_attn"), jax.named_scope("self_attn_full"):
            out = flash_attention(q.reshape(B, S, H, D),
                                  k.reshape(B, S, KV, D),
                                  v.reshape(B, S, KV, D))
        return out.astype(jnp.float32).sum()

    wide = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((B, S, KV * D), jnp.bfloat16,
                                  sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        wide, narrow, narrow).compile().as_text()
    calls = {name: shape for name, (shape, op, _) in _entry(text).items()
             if op == "custom-call" and shape.startswith("(")}
    assert len(calls) == 2 and all(n.startswith("self_attn_full")
                                   for n in calls)
    fwd, bwd = sorted(calls.values(), key=lambda c: c.count("bf16["))
    assert bwd.count(f"bf16[{B},{S},{KV * D}]") == 2            # dk, dv
    assert f"[{B},{S},{KV},{H // KV},{D}]" not in text   # no k / v repeat


@pytest.mark.slow
def test_the_eighth_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-qwen3next-gdn-8k-1chip`` (PR 48) as the benchmark builds it,
    its whole train step compiled for the described chip: two kinds of
    block in one unrolled stack of four; each Gated DeltaNet layer runs
    ``gated_delta_fwd`` and ``gated_delta_bwd`` a row at a time (inside a
    while loop: once in the text a pass); the attention layer's flash
    kernels take grouped queries at 256 lanes a head; the rows move
    through the row kernels at top-10; and what the step reserves stays
    under the chip's 15.75 GiB.  Marked slow: the compile takes ~3 minutes
    of the ~25 the tier-1 command may take, in the file the command runs
    last; ``compile_said`` in the configuration file holds its reading."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-qwen3next-gdn-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.kinds.count("linear_attention")) == (3, 8192, 3)
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 4.0 < reserved < 15.75, reserved
    text = compiled.as_text()
    assert "gated_delta_fwd" in text and "gated_delta_bwd" in text
    assert "self_attn_full" in text and "moe_rows_back" in text
    # the filter over [q ; k ; v] is the row kernels' since PR 51: forward,
    # the remat's forward and one backward a Gated DeltaNet layer
    assert len(re.findall(r"causal_conv_rows[.\d]* = ", text)) == 6
    assert len(re.findall(r"causal_conv_rows_back[.\d]* = ", text)) == 3
    # both per-head norms of those layers are row kernels since PR 53
    for name, n in (("qk_rows", 6), ("qk_rows_back", 3),
                    ("gated_norm_rows", 6), ("gated_norm_rows_back", 3)):
        assert len(re.findall(rf"{name}[.\d]* = ", text)) == n, name
    sites = {(s, i) for s, i, _, n in dispatch_report() if n}
    assert {("attention", "flash"), ("gated_delta", "pallas"),
            ("moe_rows", "pallas"), ("short_conv", "pallas"),
            ("qk_rows", "pallas"), ("gated_norm_rows", "pallas")} <= sites, \
        sites


def test_the_kernels_compile_at_the_ninth_cells_shape(one_chip, monkeypatch):
    """``train-olmo-hybrid-8k-1chip``'s two new shapes (PR 52), one row as
    the layer walks it.  The delta rule at 30 key heads of 96 and 30 value
    heads of 192 channels reaches the kernels in lane slots: the custom
    calls read ``bf16[1,8192,3840]`` (30 x 128) and ``[1,8192,7680]`` (30 x
    256), the saved states are ``f32[1,30,128,128,256]``, and ``o`` and the
    cotangents come back at 2880 and 5760.  The filter over the 11,520
    channels of ``[q ; k ; v]`` (90 lane tiles, 22.5 x 512) goes by six
    blocks of 1920 channels, 384 lanes a chunk."""
    import re

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops import gated_delta as ops
    from deepspeed_tpu.ops.pallas import gated_delta as kernel
    from deepspeed_tpu.ops.pallas import short_conv
    from deepspeed_tpu.ops.short_conv import causal_conv_rows

    B, S, H, dk, dv, C = 1, 8192, 30, 96, 192, 64
    N = S // C
    assert kernel.supported(N, C, dk, dv, jnp.bfloat16, 1) is None

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((B, S, H * dk), jnp.bfloat16), sd((B, S, H * dk), jnp.bfloat16),
            sd((B, S, H * dv), jnp.bfloat16), sd((B, S, H), jnp.float32),
            sd((B, S, H), jnp.float32))
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: ops._rule(*a, C, False, H).astype(jnp.float32).sum(),
        range(5))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert len(re.findall(r"gated_delta_fwd[.\d]* = ", text)) == 2
    assert len(re.findall(r"gated_delta_bwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"gated_delta_fwd[.\d]* = " + re.escape(
        f"f32[{B},{H},{N},128,256]"), text)) == 1             # kept once
    assert f"bf16[{B},{S},{H * 128}]" in text and f"bf16[{B},{S},{H * 256}]" \
        in text
    # the filter
    Cq = 2 * H * dk + H * dv
    assert short_conv._grid(2, S, Cq, False) == ((2, S // short_conv.BLOCK, 6),
                                                 1920)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        text = jax.jit(jax.value_and_grad(
            lambda x, w: causal_conv_rows(x, w, "silu").astype(
                jnp.float32).sum(), argnums=(0, 1))).lower(
            sd((2, S, Cq), jnp.bfloat16), sd((Cq, 4), jnp.float32)
        ).compile().as_text()
    finally:
        mesh_lib.set_mesh(None)
    calls = set(re.findall(r"%(\w+?)[.\d]* = [^=]*? custom-call\(", text))
    assert calls == {"causal_conv_rows", "causal_conv_rows_back"}, calls
    # the heads in lane slots from the filter to out_proj (PR 55): one row
    # kernel reads the filter's 11,520 lanes and writes q, k (3,840: 30
    # slots of 128) and v (7,680: 30 of 256); the gated norm reads o from
    # its slots and the gate and the result as rows of 5,760 lanes
    from deepspeed_tpu.ops import gated_delta

    B = 2
    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        def slots(x):
            plan = gated_delta.slots_plan(x, H, dk, H, dv, C)
            return sum((o.astype(jnp.float32) ** 2).sum() for o in
                       gated_delta.slot_rows(x, H, dk, H, dv, plan))

        def norm(o, z, w):
            y = gated_delta.gated_norm_rows(o, z, w, dv, ("direct", None),
                                       eps=1e-6)
            return (y.astype(jnp.float32) ** 2).sum()

        texts = [jax.jit(jax.value_and_grad(fn, argnums=tuple(range(len(
            args))))).lower(*args).compile().as_text() for fn, args in (
                (slots, (sd((B, S, Cq), jnp.bfloat16),)),
                (norm, (sd((B, S, H * 256), jnp.bfloat16),
                        sd((B, S, H * dv), jnp.bfloat16),
                        sd((dv,), jnp.float32))))]
    finally:
        mesh_lib.set_mesh(None)
    for text, pair, shapes in zip(
            texts, (("slot_rows", "slot_rows_back"),
                    ("gated_norm_rows", "gated_norm_rows_back")),
            ((Cq, H * 128, H * 256), (H * 256, H * dv))):
        calls = re.findall(r"%(\w+?)[.\d]* = [^=]*? custom-call\(", text)
        assert sorted(calls) == sorted(pair), calls
        assert all(f"bf16[{B},{S},{n}]" in text for n in shapes)
        assert not re.search(rf"\[{B},{S},{H},\d+\]", text)
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    said = {r[:3] for r in dispatch_report() if r[3]}
    assert ("qk_rows", "pallas", "heads of 96 and 192 in slots of 128 and "
            "256, rows 11520; one device") in said
    assert ("gated_norm_rows", "pallas", "head_dim 192 in slots of 256, rows "
            "5760; one device") in said


@pytest.mark.slow
def test_the_ninth_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-olmo-hybrid-8k-1chip`` (PR 52) as the benchmark builds it, its
    whole train step compiled for the described chip: four blocks under the
    reordered norm, three Gated DeltaNet layers at 96 x 192 states (the
    kernels a row at a time: once in the text a pass) behind the Pallas
    filter over 11,520 channels, one position-free attention layer through
    flash at 30 heads on 30, a dense SwiGLU everywhere; 928,862,196
    parameters in the leaves; and what the step reserves stays under the
    chip's 15.75 GiB.  Marked slow, as the eighth's is: ~1 minute of
    compile; ``compile_said`` in the configuration file holds its
    reading."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-olmo-hybrid-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.kinds.count("linear_attention")) == (2, 8192, 3)
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        state.params)) == 928_862_196
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 10.0 < reserved < 15.75, reserved
    text = compiled.as_text()
    assert "gated_delta_fwd" in text and "gated_delta_bwd" in text
    assert "self_attn_full" in text
    assert len(re.findall(r"causal_conv_rows[.\d]* = ", text)) == 6
    assert len(re.findall(r"causal_conv_rows_back[.\d]* = ", text)) == 3
    sites = {(s, i) for s, i, _, n in dispatch_report() if n}
    assert {("attention", "flash"), ("gated_delta", "pallas"),
            ("short_conv", "pallas")} <= sites, sites
    # heads of 96 and 192 channels lie in lane slots from the filter to
    # out_proj (PR 55): the slot kernel and the gated norm's pair stand
    # where the filter does, and nothing pads or views a head
    for name, n in (("slot_rows", 6), ("slot_rows_back", 3),
                    ("gated_norm_rows", 6), ("gated_norm_rows_back", 3)):
        assert len(re.findall(rf"{name}[.\d]* = ", text)) == n, name
    assert not re.search(r"\[2,8192,30,(96|128|192|256)\]", text)
    said = {r[:3] for r in dispatch_report() if r[3]}
    assert ("qk_rows", "pallas", "heads of 96 and 192 in slots of 128 and "
            "256, rows 11520; one device") in said
    assert ("gated_norm_rows", "pallas", "head_dim 192 in slots of 256, rows "
            "5760; one device") in said
    assert ("gated_delta", "pallas", "128 chunks of 64 x 30 key heads of 96 "
            "x 1 value heads of 192, fused; one device") in said
    print(f"reserved {reserved:.3f} GiB")


def test_the_kernels_compile_at_the_tenth_cells_shape(one_chip):
    """``train-keye-dsa-32k-1chip``'s attention (PR 54), one row of 32,768
    positions as the layer walks it: 32 query heads on 4 key-value heads of
    128, an indexer of 16 heads of 64 channels with one key, 2,048 keys a
    query.  Four custom calls, each once forward and backward: the
    selection (a ``(256, 32768)`` int32 panel of sortable keys in VMEM),
    the two-phase forward, and the backward a block of queries and a block
    of keys at a time; nothing of ``S x S`` among the step's buffers."""
    import re

    from deepspeed_tpu.ops.indexed_attention import indexed_attention

    B, S, H, KV, D, NI, DI, K = 1, 32768, 32, 4, 128, 16, 64, 2048

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*ops):
        r = indexed_attention(*ops, topk=K, impl="pallas")
        return r.out.astype(jnp.float32).sum() + r.kl.sum()

    args = (sd((B, S, H, D)), sd((B, S, KV, D)), sd((B, S, KV, D)),
            sd((B, S, NI, DI)), sd((B, S, DI)), sd((B, S, NI), jnp.float32))
    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("indexer_select", "indexed_attn_fwd", "indexed_attn_dq",
                 "indexed_attn_dkv"):
        assert len(re.findall(name + r"[.\d]* = ", text)) == 1, name
    assert f"[{B},{S},{S}]" not in text and f"[{S},{S}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
    # what each kernel asks of VMEM: its scratch (PR 57: the forward's
    # softmax denominators a (heads, block_q, 128) partial sum a lane)
    # beside two buffers of every block, in whole (8, 128) tiles
    from deepspeed_tpu.ops.pallas.indexed_attention import _VMEM_LIMIT

    def tiled(ref):
        *lead, rows, lanes = ref.aval.shape
        return (math.prod(lead) * -(-rows // 8) * 8 * -(-lanes // 128) * 128
                * max(ref.aval.dtype.itemsize, 4))

    asked = {}
    stack = list(jax.make_jaxpr(jax.grad(loss, argnums=range(6)))(
        *args).jaxpr.eqns)
    while stack:
        e = stack.pop()
        if e.primitive.name == "pallas_call":
            refs = e.params["jaxpr"].invars
            n = e.params["grid_mapping"].num_scratch_operands
            asked[e.params["name"]] = (
                [r.aval.shape for r in refs[-n:]],
                sum(map(tiled, refs[-n:])) + 2 * sum(map(tiled, refs[:-n])))
        for sub in jax.core.jaxprs_in_params(e.params):
            stack.extend(sub.eqns)
    assert (H, 256, 128) in asked["indexed_attn_fwd"][0]
    for name, (_, need) in asked.items():
        assert need < _VMEM_LIMIT, (name, need)
    # the second stage under a selection from outside: an int8 mask tile in
    # tau's and cut's place (Mosaic refused the mask's compare until the
    # tile was widened first: my chip run, PR 54)
    S = 4096

    def given(*ops):
        r = indexed_attention(*ops[:6], topk=K, impl="pallas",
                              selection=ops[6])
        return r.out.astype(jnp.float32).sum() + r.kl.sum()

    text = jax.jit(jax.value_and_grad(given, argnums=range(6))).lower(
        sd((B, S, H, D)), sd((B, S, KV, D)), sd((B, S, KV, D)),
        sd((B, S, NI, DI)), sd((B, S, DI)), sd((B, S, NI), jnp.float32),
        sd((B, S, S), jnp.bool_)).compile().as_text()
    assert text.count("tpu_custom_call") == 3 and "indexer_select" not in text


@pytest.mark.parametrize("policy,forward_kernels", [
    ("dots_saveable+flash", 2),     # the cell's: a kernel once a layer
    ("dots_saveable", 4),           # the names not kept: the forward twice
])
def test_a_blocks_backward_runs_no_indexed_forward_kernel_again(
        topo, one_chip, monkeypatch, policy, forward_kernels):
    """What ``test_the_tenth_cells_step_compiles_and_fits_the_chip`` counts
    in the compiled step (marked slow), at a size that lowers in seconds:
    a two-layer model under the cell's remat policy, fenced as the cell's,
    lowered for the described chip.  The forward kernels' results (the
    output, both ``lse`` and the selection's ``tau`` and ``cut``) are named
    as the flash kernels' residuals, so ``dots_saveable+flash`` keeps them
    and each of the four kernels stands in the gradient's program once a
    layer; under the policy without the names the selection and the
    forward run again in the backward, which is what an edit to that naming
    would bring back silently."""
    import re

    from flax.core import meta

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.parallel.moe import MoEConfig

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    S = 1024
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        intermediate_size=512, moe_intermediate_size=128,
        max_position_embeddings=S, qk_norm="head", scan_layers=False,
        dtype=jnp.bfloat16, attn_impl="auto", vocab_pad_multiple=128,
        remat=True, remat_policy=policy, remat_prevent_cse=True,
        moe=MoEConfig(num_experts=4, top_k=2, drop_tokens=False,
                      expert_act="swiglu", routed_experts=8, first_expert=2),
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "topk": 256})
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((1, S), jnp.int32)
    shapes = jax.eval_shape(lambda: meta.unbox(model.init(
        jax.random.PRNGKey(0), ids, labels=ids)["params"]))

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=topo.devices[:1]))
    try:
        text = jax.jit(jax.grad(lambda p, ids: model.apply(
            {"params": p}, ids, labels=ids)["loss"])).lower(
            jax.tree_util.tree_map(on_chip, shapes), on_chip(ids)).as_text()
    finally:
        mesh_lib.set_mesh(None)

    def count(name):
        return len(re.findall(f'kernel_name = "{name}"', text))

    assert (count("indexer_select"), count("indexed_attn_fwd")) == (
        forward_kernels, forward_kernels)
    assert (count("indexed_attn_dq"), count("indexed_attn_dkv")) == (2, 2)


@pytest.mark.slow
def test_the_tenth_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-keye-dsa-32k-1chip`` (PR 54) as the benchmark builds it, its
    whole train step compiled for the described chip: four sparse blocks
    whose attention keeps 2,048 keys a query of one 32,768-token row, 16 of
    128 experts held; 465,718,784 parameters in the leaves; each of the
    four kernels once a layer (under ``dots_saveable+flash`` a block's
    backward runs no forward kernel again); and what the step reserves stays
    under the chip's 15.75 GiB.  Marked slow, as the ninth's is: ~1 minute
    of compile; ``compile_said`` in the configuration file holds its
    reading."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-keye-dsa-32k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.sa_config.topk) == (1, 32768, 2048)
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        state.params)) == 465_718_784
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 0.25 * 15.75 < reserved < 15.75, reserved
    text = compiled.as_text()
    for name in ("indexer_select", "indexed_attn_fwd", "indexed_attn_dq",
                 "indexed_attn_dkv"):
        assert len(re.findall(name + r"[.\d]* = ", text)) == 4, name
    sites = {(s, i) for s, i, _, n in dispatch_report() if n}
    assert {("indexed_attention", "pallas"), ("grouped_matmul", "megablox"),
            ("qk_rows", "pallas"), ("moe_rows", "pallas")} <= sites, sites
    assert ("indexed_attention", "jnp") not in sites
    print(f"reserved {reserved:.3f} GiB")


# (chunk, key heads, value heads, dk, dv, positions): the eleventh cell's own
# shape, the three corners a reviewer of PR 60 named (chunk 128; two value
# heads a key head; chunk 32), then - slow - the widest heads
# ``channel_supported`` admits a chunk and a ratio, where its estimate of the
# VMEM a grid step takes is nearest its limit
_CHANNEL_CORNERS = [
    (64, 32, 32, 128, 128, 8192), (128, 2, 2, 128, 128, 2048),
    (64, 2, 4, 128, 128, 2048), (32, 2, 2, 128, 128, 2048)] + [
    pytest.param(*c, 2048, marks=pytest.mark.slow) for c in (
        (128, 2, 2, 256, 256), (128, 2, 4, 128, 128), (64, 2, 2, 512, 256),
        (64, 2, 2, 256, 512), (64, 2, 4, 256, 256), (64, 2, 8, 128, 128),
        (32, 2, 2, 512, 512), (32, 2, 4, 512, 128), (32, 2, 4, 256, 256),
        (32, 2, 8, 256, 128), (32, 2, 8, 128, 256), (32, 2, 16, 128, 128))]


@pytest.mark.parametrize("C,Hk,Hv,dk,dv,S", _CHANNEL_CORNERS)
def test_the_channel_kernels_compile_at_every_corner_the_guard_admits(
        one_chip, C, Hk, Hv, dk, dv, S):
    """What ``channel_supported`` admits Mosaic compiles (PR 60): before the
    kernels every shape under a decay a key channel ran XLA's form under
    ``auto``, so a shape the guard lets through and VMEM does not hold would
    fail to compile where it used to fall back.  Forward and backward are
    three custom calls (``gated_delta_channel_fwd`` for ``o`` and again for
    the states entering the chunks, ``gated_delta_channel_bwd``)."""
    import re

    from deepspeed_tpu.ops import gated_delta as ops
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    assert kernel.channel_supported(S // C, C, dk, dv, jnp.bfloat16,
                                    Hv // Hk) is None

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((1, S, Hk * dk), jnp.bfloat16), sd((1, S, Hk * dk),
                                                  jnp.bfloat16),
            sd((1, S, Hv * dv), jnp.bfloat16), sd((1, S, Hv, dk), jnp.float32),
            sd((1, S, Hv), jnp.float32))
    text = jax.jit(jax.value_and_grad(
        lambda *a: ops._rule(*a, C, False, Hk).astype(jnp.float32).sum(),
        range(5))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert len(re.findall(r"gated_delta_channel_fwd[.\d]* = ", text)) == 2
    assert len(re.findall(r"gated_delta_channel_bwd[.\d]* = ", text)) == 1


@pytest.mark.slow
def test_the_eleventh_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-ling3-kda-8k-1chip`` (PR 58) as the benchmark builds it, its
    whole train step compiled for the described chip: five
    Kimi-Delta-Attention blocks whose delta rule (a decay a key channel) is
    the channel kernels ``gated_delta_channel_fwd`` / ``_bwd`` since PR 60
    (XLA's program before), one gated latent-attention block through the
    two-product flash kernels, 8 of 512 group-routed experts held; 767,336,736
    parameters in the leaves (the issue's 767,009,056 and the 64 padded rows
    of the table and the head); and what the step reserves at one packed
    8,192-token row stays under the chip's 15.75 GiB (two rows ask 16.01:
    ``compile_said`` in the configuration file holds both readings).
    Marked slow, as the ninth's and the tenth's are: ~100 s of compile."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-ling3-kda-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.kinds.count("kda_attention"), cfg.mtp_blocks) == (
        1, 8192, 5, 0)
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        state.params)) == 767_336_736
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 0.25 * 15.75 < reserved < 15.75, reserved
    text = compiled.as_text()
    # the latent-attention layer's kernels once a pass; the delta rule's
    # under a decay a key channel, and none of a decay a head's
    assert len(re.findall(r'kernel_name = "self_attn_mla', text)) >= 1 \
        or "self_attn_mla" in text
    assert "gated_delta_channel_fwd" in text \
        and "gated_delta_channel_bwd" in text
    assert "gated_delta_fwd" not in text and "gated_delta_bwd" not in text
    rows_of = {(s, i): r for s, i, r, n in dispatch_report() if n}
    assert {("attention", "flash"), ("grouped_matmul", "megablox"),
            ("moe_rows", "pallas"), ("qk_rows", "pallas"),
            ("short_conv", "pallas"), ("gated_delta", "pallas")} <= set(
                rows_of)
    assert ("gated_delta", "xla") not in rows_of
    assert rows_of[("gated_delta", "pallas")].startswith(
        "128 chunks of 64 x 32 key heads x 1 value heads of 128, a decay a "
        "key channel, fused")
    assert "shared rope lanes" in rows_of[("attention", "flash")]
    assert ("attention", "jnp") not in rows_of
    print(f"reserved {reserved:.3f} GiB")


@pytest.mark.parametrize("kernel", ["gather", "gather_scaled", "combine",
                                    "combine_dw", "mhc_read", "mhc_post",
                                    "mhc_post_back", "mhc_read_back"])
def test_the_row_kernels_compile_at_the_twelfth_cells_width(one_chip, kernel):
    """Rows of 3,584 channels, the widest a cell moves (PR 62; 2,560 before):
    ``gather_rows``' double-buffered ``(1024, 3584)`` block and its stages
    asked 17.5 MiB of Mosaic's 16 MiB default scope, so ``_wide_rows`` gives
    the call 32; at the older widths it adds nothing and the calls are what
    they were.  And the hyper-connections' four passes over one packed row
    of four such lanes (PR 65, ``ops/pallas/mhc_rows.py``)."""
    from deepspeed_tpu.ops.pallas import mhc_rows, moe_rows

    assert moe_rows._wide_rows(2560 // 2) == {} \
        and moe_rows._wide_rows(2304 // 2) == {}
    assert moe_rows._wide_rows(3584 // 2)[
        "compiler_params"].vmem_limit_bytes == 32 << 20
    tokens, k, width = 8192, 4, 3584

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = tokens * k
    idx, live = arg((rows,), jnp.int32), arg((1,), jnp.int32)
    weights = arg((tokens, k), jnp.float32)
    fn, args = {
        "gather": (moe_rows.gather_rows, (
            arg((tokens, 1, width // 2), jnp.uint32), idx, live)),
        "gather_scaled": (moe_rows.gather_rows, (
            arg((tokens, 1, width // 2), jnp.uint32), idx, live,
            arg((rows, 1), jnp.float32))),
        "combine": (moe_rows.combine_rows, (
            arg((rows, 1, width // 2), jnp.uint32), idx, weights)),
        "combine_dw": (moe_rows.combine_rows, (
            arg((rows, 1, width // 2), jnp.uint32), idx, weights,
            arg((tokens, width), jnp.bfloat16))),
        **{"mhc_" + name: (functools.partial(call, n=4, **more), args)
           for name, call, more, args in _mhc_calls(arg, mhc_rows)},
    }[kernel]
    if not kernel.startswith("mhc_"):
        fn = functools.partial(fn, name="moe_rows_back")
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def _mhc_calls(arg, mhc_rows, B=1, S=8192, n=4, E=3584):
    """``(name, call, its other keywords, the operands' shapes)`` of the
    hyper-connections' four row kernels at the twelfth cell's shape."""
    stream, lane = arg((B, S, n * E), jnp.bfloat16), arg((B, S, E),
                                                         jnp.bfloat16)
    row = arg((B, S, mhc_rows.COLS), jnp.float32)
    phi_t = arg((mhc_rows.COLS, n * E), jnp.bfloat16)
    ab = arg((2, mhc_rows.COLS), jnp.float32)
    return (("read", mhc_rows.read_call, {"rms_eps": 1e-6},
             (stream, phi_t, ab)),
            ("post", mhc_rows.post_call, {}, (stream, lane, row)),
            ("post_back", mhc_rows.post_back_call, {},
             (stream, stream, lane, row)),
            ("read_back", mhc_rows.read_back_call, {},
             (stream, stream, lane, row, row, row, phi_t, ab)))


def test_a_hyper_connection_compiles_with_one_loop_a_pass(one_chip,
                                                          monkeypatch):
    """One sublayer's hyper-connection at the twelfth cell's shape, (1,
    8192, 4 x 3584) in bf16, forward and backward for the described v5e: the
    20 Sinkhorn sweeps are ONE while loop a pass (forward and its reverse
    scan), not 20 unrolled copies, no ``(T, 4, 4)`` array is formed - the
    maps keep tokens on the lane axis - and the passes over the lanes are
    the four row kernels (PR 65): read and write back, and the backward of
    each."""
    import re

    from deepspeed_tpu.models.llama import HyperConnection, LlamaConfig
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import spmd

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(spmd, "kernel_mesh_plan",
                        lambda batch, **kw: ("direct", None))
    cfg = LlamaConfig(hidden_size=3584, num_attention_heads=32, hc_mult=4,
                      scan_layers=False, rms_norm_eps=1e-6)
    module = HyperConnection(cfg)
    X = jax.ShapeDtypeStruct((1, 8192, 4 * 3584), jnp.bfloat16,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((1, 8192, 3584), jnp.bfloat16, sharding=one_chip)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros(X.shape, X.dtype)))["params"]
    from flax.core import meta

    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        meta.unbox(shapes))

    def loss(p, X, y):
        u, maps = module.apply({"params": p}, X)
        out = HyperConnection.post(X, (y + u).astype(X.dtype), maps)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        params, X, y).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 2
    assert not re.search(r"f32\[8192,4,4\]", text)
    assert text.count("tpu_custom_call") == 4
    for name in ("mhc_read", "mhc_post", "mhc_post_back", "mhc_read_back"):
        assert len(re.findall(rf'/{name}/pallas_call"', text)) == 1, name
    # every kernel, the two of the backward too, under a scope that says mhc/
    assert len(re.findall(r'op_name="[^"]*mhc/(maps|post)[^"]*/pallas_call"',
                          text)) == 4


@pytest.mark.slow
def test_the_twelfth_cells_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """``train-xing4-mhc-8k-1chip`` (PR 62) as the benchmark builds it, its
    whole train step compiled for the described chip: six latent-attention
    blocks (the prediction block's among them) through the two-product flash
    kernels, each sublayer under a hyper-connection over four lanes, 8 of 64
    experts held; 913,473,668 parameters in the leaves; one packed
    8,192-token row reserves 11.78 of the chip's 15.75 GiB (12.225 before
    PR 65's row kernels; ``compile_said`` in the configuration file holds
    the older reading and two rows' 18.10).
    Marked slow, as the ninth's to the eleventh's are: ~95 s of compile."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-xing4-mhc-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.lanes, cfg.mtp_blocks) == (1, 8192, 4, 1)
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        state.params)) == 913_473_668
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 11.5 < reserved < 12.0, reserved     # 12.225 before PR 65
    text = compiled.as_text()
    assert "self_attn_mla" in text
    # a Sinkhorn loop a sublayer a pass (12 x forward, recompute, backward)
    # beside the head's and the kernels' own loops: tens, not hundreds
    assert 36 <= len(re.findall(r" while\(", text)) <= 64
    rows_of = {(s, i) for s, i, r, n in dispatch_report() if n}
    assert {("attention", "flash"), ("grouped_matmul", "megablox"),
            ("moe_rows", "pallas"), ("mhc_rows", "pallas")} <= rows_of
    # the four row kernels a sublayer (PR 65): the read pass forward and
    # again under remat, the write back again only where the block goes on
    # to read its result (a block's last is the next block's kept input),
    # and the backward of each once
    for name, calls in (("mhc_read", 24), ("mhc_post", 19),
                        ("mhc_post_back", 12), ("mhc_read_back", 12)):
        assert len(re.findall(rf'/{name}/pallas_call"', text)) == calls, name
    print(f"reserved {reserved:.3f} GiB")


@pytest.mark.slow
def test_the_thirteenth_cells_step_compiles_and_fits_the_chip(topo,
                                                              monkeypatch):
    """``train-ouro-loop4-8k-1chip`` (PR 64) as the benchmark builds it, its
    whole train step compiled for the described chip: six blocks applied four
    times over the same leaves (24 flash forward calls, and no second copy of
    a weight a pass), a norm and a gate after every pass, the four exits
    through ONE chunked head (one ``while``); 333,500,417 parameters in the
    leaves; one packed 8,192-token row reserves 12.9 of the chip's 15.75 GiB,
    11.1 of it temporaries - the activations, not the parameters
    (``compile_said`` in the configuration file: two rows ask 20.05).  Marked
    slow, as the ninth's to the twelfth's are: ~1 min of compile."""
    import re
    import types

    from benchmark.harness import manifest as M
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    devs = topo.devices[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cell = M.load_cell(M.load_manifest(M.ROOT), "train-ouro-loop4-8k-1chip",
                       M.ROOT)
    ctx = types.SimpleNamespace(
        seed=1, cell=cell, rehearse=False,
        sized=lambda sec: {k: v for k, v in sec.items() if k != "rehearse"})
    try:
        engine, cfg, conf = cell.driver().train_lm.build(ctx)
        rows, seq = conf["micro_per_device"], cell.traffic["seq_len"]
        batch = {name: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for name in ("input_ids", "labels")}
        state = engine.abstract_state(batch)
        compiled = engine._compiled_train_step.lower(state, batch).compile()
    finally:
        mesh_lib.set_mesh(None)
    assert (rows, seq, cfg.total_ut_steps, cfg.num_hidden_layers) == (
        1, 8192, 4, 6)
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        state.params)) == 333_500_417
    ma = compiled.memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert 12.6 < reserved < 13.3, reserved
    assert ma.argument_size_in_bytes / 2**30 < 1.9      # 6 B a parameter
    text = compiled.as_text()
    for t in range(4):
        assert f"ut/pass_{t}/" in text
    assert "ut/exit_gate" in text and "loss_head" in text
    assert len(re.findall(r" while\(", text)) == 1      # the head's chunks
    # no (rows, V) float32 logits of all four exits together
    assert not re.search(r"f32\[32768,6144\]", text)
    rows_of = {(s, i) for s, i, r, n in dispatch_report() if n}
    assert ("attention", "flash") in rows_of
    print(f"reserved {reserved:.3f} GiB")
