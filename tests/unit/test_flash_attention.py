"""Pallas flash attention vs dense reference (interpret mode on CPU) —
the kernel-parity seam of ``test_cuda_forward.py``/``test_cuda_backward.py``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import _jnp_attention
from deepspeed_tpu.ops.pallas.flash_attention import (
    DIAGONAL, FULL, VOID, _full_tiles, flash_attention,
    flash_attention_with_lse, score_tile_schedule)
from deepspeed_tpu.telemetry import registry


def _qkv(B=1, S=256, H=2, D=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    return mk(), mk(), mk()


def _ref(q, k, v, causal):
    return _jnp_attention(q, k, v, causal=causal, bias=None, mask=None,
                          dropout_rate=0.0, dropout_rng=None, scale=None)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    q, k, v = _qkv(S=128, seed=1)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, causal) ** 2)

    g_flash = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_cross_attention_lengths():
    # S_q != S_kv (e.g. prefix cross-attention), non-causal
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = _ref(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_tolerance():
    q, k, v = _qkv(dtype=jnp.bfloat16, seed=3)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2)


def test_flash_ragged_seq_uses_full_block():
    """Non-power-of-two S falls back to a full-sequence block (legal on
    TPU: block == full array dim) and stays correct."""
    import numpy as np

    from deepspeed_tpu.ops.attention import _jnp_attention
    from deepspeed_tpu.ops.pallas.flash_attention import _largest_dividing_block

    assert _largest_dividing_block(1536, 1024) == 512
    assert _largest_dividing_block(1152, 1024) == 128
    assert _largest_dividing_block(100, 1024) == 100
    q, k, v = _qkv(S=100)
    out = flash_attention(q, k, v, interpret=True)
    ref = _jnp_attention(q, k, v, causal=True, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_spmd_on_mesh():
    """flash kernel under shard_map on a dp×tp mesh (interpret mode) must
    match the single-device kernel — the multi-chip dispatch path."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops.attention import (_jnp_attention,
                                             dot_product_attention)

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"dp": 4, "tp": 2})
    mesh_mod.set_mesh(mesh)
    try:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(4, 128, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(4, 128, 4, 64)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(4, 128, 4, 64)), jnp.float32)
        out = dot_product_attention(q, k, v, impl="flash", interpret=True)
        ref = _jnp_attention(q, k, v, causal=True, bias=None, mask=None,
                             dropout_rate=0.0, dropout_rng=None, scale=None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        mesh_mod.set_mesh(None)


ROWS_2, HEAD_MAJOR_96 = ("rows layout, 2 heads a 128-lane block",
                         "head-major: head_dim 96 does not tile 128 lanes")


@pytest.fixture()
def flash_dispatch(monkeypatch):
    """``dot_product_attention(impl="flash")`` on one CPU device, the
    kernels in interpret mode; returns ``(attend, booked)`` where
    ``booked()`` maps the reasons ``kernel_dispatch_total`` holds for the
    flash kernel to their counts."""
    import functools

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))

    def booked():
        return {reason: n for site, impl, reason, n in dispatch_report()
                if (site, impl) == ("attention", "flash")}

    yield functools.partial(attention.dot_product_attention, impl="flash",
                            interpret=True), booked
    mesh_mod.set_mesh(None)


# (25, 64): GPT-2-XL's heads, 12.5 lane blocks: the last one is ragged and
# the interpreter fills its lanes past H·D with NaN, as it does every
# scratch; (4, 64): whole blocks; (3, 32): three heads in one 96-lane
# block; (2, 128): a head a block; (2, 96): no tiling, one panel a head
@pytest.mark.parametrize("S,Sk,causal", [(128, 128, True), (128, 128, False),
                                         (128, 256, True), (128, 256, False)])
@pytest.mark.parametrize("H,D,layout", [
    (25, 64, ROWS_2), (4, 64, ROWS_2),
    (3, 32, "rows layout, 3 heads a 96-lane block"),
    (2, 128, "rows layout, 1 head a 128-lane block"),
    (2, 96, HEAD_MAJOR_96)])
def test_flash_layout_parity(flash_dispatch, H, D, layout, S, Sk, causal):
    """Output and all three gradients of every layout the kernels choose
    from the shape, against the dense reference, and the layout by name
    in ``dispatch_report()``.  What lies past an operand's last lane must
    not reach a result: every value stays finite and equal."""
    from jax._src.pallas import primitives

    assert np.isnan(primitives.uninitialized_value((), jnp.float32))
    attend, booked = flash_dispatch
    rng = np.random.default_rng(H * D + S + Sk)
    q = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, Sk, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Sk, H, D)), jnp.float32)
    # the kernels count query and key positions from 0 both
    keep = jnp.arange(S)[:, None] >= jnp.arange(Sk)[None, :] if causal \
        else None

    def ref(q, k, v):
        return _jnp_attention(q, k, v, causal=False, bias=None, mask=keep,
                              dropout_rate=0.0, dropout_rng=None, scale=None)

    before = booked()
    out = attend(q, k, v, causal=causal)
    new = {r for r, n in booked().items() if n > before.get(r, 0)}
    assert len(new) == 1 and new.pop().endswith("one device; " + layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    w = jnp.asarray(rng.normal(size=out.shape), jnp.float32)
    g_flash = jax.jit(jax.grad(
        lambda *a: jnp.sum(attend(*a, causal=causal) * w),
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(ref(*a) * w),
                             argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_backward_writes_its_consumers_types():
    """dq, dk and dv leave the backward kernel in q's, k's and v's own
    types: nothing casts or scales them outside it."""
    q, k, v = _qkv(S=128, H=3, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, interpret=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    assert [o.aval.dtype for o in calls[1].outvars] == [jnp.bfloat16] * 3
    assert [o.aval.shape for o in calls[1].outvars] == [(1, 128, 192)] * 3


# S=128/512: one diagonal tile; 1024/1536: the unrolled sweep with void,
# full and diagonal tiles; 2560: the fori_loop sweep
_SCHEDULE_SEQS = [128, 512, 1024, 1536, 2560]


def _assert_grads_close(f_flash, f_ref, args):
    g_flash = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(*args)
    g_ref = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(*args)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", _SCHEDULE_SEQS)
def test_flash_schedule_parity(S, causal):
    """Output and all three gradients against the dense reference on
    every tile schedule: single tile, unrolled, fori_loop."""
    q, k, v = _qkv(S=S, H=1, seed=S)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(q, k, v, causal)),
                               rtol=2e-5, atol=2e-5)
    _assert_grads_close(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, interpret=True) ** 2),
        lambda q, k, v: jnp.sum(_ref(q, k, v, causal) ** 2), (q, k, v))


def _ref_with_lse(q, k, v, causal):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        S, Sk = s.shape[-2:]
        s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(Sk)[None, :],
                      s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)               # (B,H,S)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return out, lse.transpose(0, 2, 1)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", _SCHEDULE_SEQS)
def test_flash_with_lse_schedule_parity(S, causal):
    """The LSE-exposing variant (ring attention's building block) with a
    NON-ZERO lse cotangent, on every tile schedule."""
    q, k, v = _qkv(S=S, H=1, seed=S + 1)
    w = jnp.asarray(np.random.default_rng(S).normal(size=(1, S, 1)),
                    jnp.float32)
    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        interpret=True)
    ref_out, ref_lse = _ref_with_lse(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(lse * w)
        return f

    _assert_grads_close(
        loss(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal, interpret=True)),
        loss(lambda q, k, v: _ref_with_lse(q, k, v, causal)), (q, k, v))


def _tile_counts():
    fam = registry.counter("flash_score_tiles_total",
                           labelnames=("pass", "kind"))
    return {labels: child.value for labels, child in fam.samples()}


@pytest.mark.parametrize("causal,want", [
    (True, {"fwd": {VOID: 1, FULL: 1, DIAGONAL: 2},
            "bwd": {VOID: 6, FULL: 6, DIAGONAL: 4}}),
    (False, {"fwd": {FULL: 4}, "bwd": {FULL: 16}}),
])
def test_flash_score_tiles_counter(causal, want):
    """At the benchmark cell's shape (S=1024, 512-blocks) one traced
    forward and one traced backward each count the sub-tiles of a
    head-sequence, in their own units: the backward halves its diagonal
    tiles (256-units: void 6 / full 6 / diagonal 4), the forward leaves
    them whole (512-units: void 1 / full 1 / diagonal 2)."""
    sched = score_tile_schedule(1024, 1024, 512, 512, causal, True)
    assert (sched.sub_q, sched.sub_k) == (256, 256)
    q, k, v = _qkv(S=1024, H=1)
    before = _tile_counts()
    jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, interpret=True))))(q, k, v)
    after = _tile_counts()
    delta = {key: after[key] - before.get(key, 0) for key in after}
    assert {key: n for key, n in delta.items() if n} == {
        (pass_, kind): n for pass_, kinds in want.items()
        for kind, n in kinds.items()}


@pytest.mark.parametrize("halve", [False, True])
@pytest.mark.parametrize("S,Sk,bq,bk", [
    (1024, 1024, 512, 512), (128, 128, 128, 128), (2560, 2560, 512, 512),
    (1024, 1024, 256, 512), (1024, 1024, 512, 128), (768, 768, 384, 256),
    (512, 1024, 512, 512), (1024, 512, 256, 512), (100, 100, 100, 100),
])
def test_score_tile_schedule_covers_the_triangle(S, Sk, bq, bk, halve):
    """VOID sub-tiles hold no ``q_pos >= k_pos`` entry, FULL ones nothing
    else, DIAGONAL ones both; together they tile the score matrix once.
    What the kernels walk (the per-offset plan of a diagonal tile and the
    full-tile range of each program) is that same list."""
    sched = score_tile_schedule(S, Sk, bq, bk, True, halve)
    sq, sk = sched.sub_q, sched.sub_k
    keep = np.arange(S)[:, None] >= np.arange(Sk)[None, :]
    seen = np.zeros((S, Sk), np.int32)
    for q0, k0, kind in sched.tiles:
        part = keep[q0:q0 + sq, k0:k0 + sk]
        seen[q0:q0 + sq, k0:k0 + sk] += 1
        assert {VOID: not part.any(), FULL: part.all(),
                DIAGONAL: part.any() and not part.all()}[kind]
    assert (seen == 1).all()
    kinds = {(q0, k0): kind for q0, k0, kind in sched.tiles}
    plan = dict(sched.diagonal)
    for qi in range(S // bq):
        lo, hi = _full_tiles(qi, sched, own_is_q=True)
        for kj in range(Sk // bk):
            subs = {kinds[qi * bq + r0, kj * bk + c0]
                    for r0 in range(0, bq, sq) for c0 in range(0, bk, sk)}
            d0 = qi * bq - kj * bk
            if lo <= kj < hi:
                assert subs == {FULL} and d0 not in plan
            elif d0 in plan:        # the kernel walks its live sub-tiles
                assert {(r0, c0): kind for r0, c0, kind in plan[d0]} == {
                    (r0, c0): kinds[qi * bq + r0, kj * bk + c0]
                    for r0 in range(0, bq, sq) for c0 in range(0, bk, sk)
                    if kinds[qi * bq + r0, kj * bk + c0] != VOID}
            else:                   # no code runs
                assert subs == {VOID}
    for kj in range(Sk // bk):      # the backward's view of the same tiles
        lo, hi = _full_tiles(kj, sched, own_is_q=False)
        for qi in range(S // bq):
            full = {kinds[qi * bq + r0, kj * bk + c0]
                    for r0 in range(0, bq, sq)
                    for c0 in range(0, bk, sk)} == {FULL}
            assert full == (lo <= qi < hi)


def test_score_tile_schedule_non_causal_is_all_full():
    sched = score_tile_schedule(1024, 512, 512, 512, False, True)
    assert sched.diagonal == ()
    assert {kind for _, _, kind in sched.tiles} == {FULL}
    assert _full_tiles(0, sched, own_is_q=True) == (0, 1)
    assert _full_tiles(0, sched, own_is_q=False) == (0, 2)


@pytest.mark.parametrize("S,bq,bk", [(1024, 256, 512), (1024, 512, 128),
                                     (768, 384, 256), (2560, 256, 512)])
def test_flash_unequal_blocks_parity(S, bq, bk):
    """block_q != block_k: diagonal tiles sit at several offsets, and the
    two kernels' sweeps differ in length (one unrolled, one looped)."""
    q, k, v = _qkv(S=S, H=1, seed=S + bq)
    kw = dict(causal=True, block_q=bq, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(flash_attention(q, k, v, **kw)),
                               np.asarray(_ref(q, k, v, True)),
                               rtol=2e-5, atol=2e-5)
    _assert_grads_close(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw) ** 2),
        lambda q, k, v: jnp.sum(_ref(q, k, v, True) ** 2), (q, k, v))


# --- the paired loops against the parent's one tile a trip (PR 43) ---------

# (S, Sk, bq, bk, causal, H, D): eight tiles a row, so a causal program
# meets 0..7 FULL tiles, odd and even counts, and the odd one left over is
# computed void in the diagonal tile's block; seven (an odd static count
# without the diagonal); cross lengths; two heads a lane block (head_dim 64
# keeps one tile a trip); unequal blocks, where only some programs meet a
# diagonal offset and the others compute it void instead of branching
_PAIRED = [(1024, 1024, 128, 128, True, 1, 128),
           (896, 896, 128, 128, True, 1, 128),
           (1024, 1024, 128, 128, False, 1, 128),
           (640, 896, 128, 128, False, 1, 128),
           (1024, 1024, 128, 128, True, 2, 64),
           (1024, 1024, 256, 128, True, 1, 128),
           (1024, 1024, 128, 256, True, 1, 128)]


def _paired_passes(S, Sk, bq, bk, causal, H, D):
    from tests.unit.flash_parent_sweep import fa as fa_, passes

    q, k, v = _qkv(B=2, S=S, H=H, D=D, seed=11)
    do = q[::-1] * 0.5
    if Sk != S:
        k = jax.random.normal(jax.random.PRNGKey(12), (2, Sk, H, D))
        v = jax.random.normal(jax.random.PRNGKey(13), (2, Sk, H, D))
    if bq == bk:
        return lambda: passes(q, k, v, do, block=bq, causal=causal)

    def run():      # the public call picks the blocks it is given
        out, vjp = jax.vjp(lambda *a: fa_.flash_attention(
            *a, causal=causal, block_q=bq, block_k=bk, interpret=True),
            q, k, v)
        return (out, *vjp(do))

    return run


@pytest.mark.parametrize("S,Sk,bq,bk,causal,H,D", _PAIRED)
def test_paired_sweeps_equal_the_parents_exactly(S, Sk, bq, bk, causal, H, D):
    from tests.unit.flash_parent_sweep import assert_equal_to_the_parents

    names = ("out", "lse", "dq", "dk", "dv") if bq == bk else (
        "out", "dq", "dk", "dv")
    assert_equal_to_the_parents(_paired_passes(S, Sk, bq, bk, causal, H, D),
                                names)


def test_a_short_sweep_is_the_parents_program():
    """S 1024 in 512-tiles (the first cell): no loop and no new form, the
    kernels' jaxprs are the parent's to the letter."""
    from tests.unit.flash_parent_sweep import kernel_primitives, parent_sweeps

    q, k, v = _qkv(B=1, S=1024, H=3, D=64)

    def text():
        return str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
            lambda *a: flash_attention(*a, interpret=True), q, k, v)[1](q))(
                q, k, v))

    new = text()
    with parent_sweeps():
        old = text()
    assert new == old and "pallas_call" in new
    run = lambda: jax.jit(jax.grad(lambda q: flash_attention(
        q, k, v, interpret=True).sum()))(q)
    assert "while" not in kernel_primitives(run)


# --- the type of the products' operands (PR 47) ----------------------------

def _form(name, dtype, seed=47):
    """``(fn, operands, terms)`` of one form of the kernels at a small
    shape: ``fn(*operands)`` is the interpreted flash call."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_halves)

    rng = np.random.default_rng(seed)

    def normal(*shape):     # bf16 values, so every type holds the same ones
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16).astype(dtype)

    kw = dict(interpret=True, block_q=128, block_k=128)
    if name in ("plain64", "plain128"):
        D = int(name[5:])
        ops = [normal(1, 256, 2, D) for _ in range(3)]
        return (lambda q, k, v: flash_attention(q, k, v, **kw)), ops, 1
    if name == "window_gqa":
        ops = [normal(1, 512, 4, 128), normal(1, 512, 2, 128),
               normal(1, 512, 2, 128)]
        return (lambda q, k, v: flash_attention(q, k, v, window=192, **kw)
                ), ops, 1
    if name == "two_products":
        ops = [normal(1, 256, 2, 128), normal(1, 256, 2, 64),
               normal(1, 256, 2, 128), normal(1, 256, 1, 64),
               normal(1, 256, 2, 128)]
        return (lambda qn, qr, kn, kr, v: flash_attention(
            qn, kn, v, q_rope=qr, k_rope=kr, **kw)), ops, 2
    assert name == "halves"
    ops = [normal(1, 512, 4, 128), normal(1, 512, 2, 128),
           normal(1, 512, 2, 128)]
    return (lambda q, k, v: flash_attention_halves(q, k, v, block=4, **kw)
            ), ops, 1


def _form_grads(name, dtype):
    fn, ops, terms = _form(name, dtype)
    ct = _form(name, jnp.float32, seed=48)[1][0]    # shaped as the output

    def both(*ops):
        out, vjp = jax.vjp(fn, *ops)
        return (out, *vjp(ct.astype(out.dtype)))

    return both, ops, terms


FORMS = ("plain64", "plain128", "window_gqa", "two_products", "halves")
# Products of a bf16 call's kernels whose operands are bf16: none.  Every
# product keeps float32 operands (ISSUE 47, point 6) by two readings on the
# chip (PERF.md section 6, PR 47): the v5e rounds a float32 operand to bf16
# on its way into the MXU, bit for bit what ``astype`` gives and at a bf16
# operand's rate, so the narrow form buys nothing; and a transposed
# contraction (p^T dO, dS^T q) of a packed bf16 operand ran a backward call
# 3 to 8% SLOWER at the seven cells' shapes (the backward has held its
# score tiles keys by queries since, and contracts none transposed: not read
# again).  A later change that narrows a product says here which,
# {form: {pass: count}}, and reads the chip again.
NARROW = {}


@pytest.mark.parametrize("name", FORMS)
def test_products_take_the_operands_the_chip_read_fastest(name):
    """In the kernels a bf16 call traces to every product has a float32 sum
    and two operands of one type: float32, but for the products ``NARROW``
    lists, and ``flash_mxu_operands_total`` counts each by that type; the
    backward contracts no operand transposed (it turns dS itself, once a
    tile and head); in a float32 call's kernels there is no narrowing cast;
    the bf16 call stays within the bf16 tolerance of the float32 one,
    forward and every gradient."""
    from tests.unit.flash_parent_sweep import (
        kernel_jaxprs, kernel_products_and_casts, mxu_operand_counts)

    both, ops, terms = _form_grads(name, jnp.bfloat16)
    refs = {"fwd": [f"q{t}" for t in range(terms)]
            + [f"k{t}" for t in range(terms)] + ["v"]}
    refs["bwd"] = refs["fwd"] + ["do"]
    before = mxu_operand_counts()
    kernels = dict(zip(("fwd", "bwd"), kernel_jaxprs(both, *ops)))
    counted = mxu_operand_counts() - before
    assert set(kernels) == {"fwd", "bwd"}
    for pass_, kernel in kernels.items():
        dots, widened, _, turned = kernel_products_and_casts(
            kernel, refs[pass_])
        assert turned == 0, pass_
        narrow = NARROW.get(name, {}).get(pass_, 0)
        assert dots.count(("bfloat16", "bfloat16", "float32")) == narrow
        assert dots.count(("float32",) * 3) == len(dots) - narrow > 0
        # the counter saw each product of the traced body, by type
        assert counted[pass_, "bfloat16"] == narrow
        assert counted[pass_, "float32"] == len(dots) - narrow
        if not narrow:      # every operand is widened as it is loaded
            assert widened == set(refs[pass_]), pass_

    wide, ops32, _ = _form_grads(name, jnp.float32)
    before = mxu_operand_counts()
    for kernel in kernel_jaxprs(wide, *ops32):
        dots, _, narrowed, _ = kernel_products_and_casts(kernel, ())
        assert set(dots) == {("float32",) * 3} and narrowed == 0
    assert {d for _, d in mxu_operand_counts() - before} == {"float32"}

    for got, want in zip(both(*ops), wide(*ops32)):
        assert got.dtype == jnp.bfloat16
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
