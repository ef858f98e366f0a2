"""The flash kernels with a sliding window and grouped queries (PR 30):
interpret mode against ``_jnp_attention``, forward and dq / dk / dv; the
tile schedule at the third cell's shape by hand; and the two older cells'
schedules as they were.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import _jnp_attention, dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import (
    BAND_EDGE, CROSSED, DIAGONAL, FULL, VOID, _full_tiles, _tile_kind,
    flash_attention, flash_lanes, grouped_in_kernel, score_tile_schedule)


def _qkv(S, H, KV, D=128, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, KV, D)),
            jax.random.normal(ks[2], (B, S, KV, D)),
            jax.random.normal(ks[3], (B, S, H, D)))


def _xla(q, k, v, window):
    return _jnp_attention(q, k, v, causal=True, bias=None, mask=None,
                          dropout_rate=0.0, dropout_rng=None, scale=None,
                          window=window)


# S a multiple of the 256 block, and "ragged": 640 = 5 x 128 falls to
# 128-blocks, whose window-200 tiles are crossed by both edges at once
@pytest.mark.parametrize("S,block", [(768, 256), (640, 256)])
@pytest.mark.parametrize("H,KV", [(4, 1), (2, 2)])
@pytest.mark.parametrize("window", [200, 300, None])
def test_flash_matches_xla_forward_and_backward(S, block, H, KV, window):
    q, k, v, g = _qkv(S, H, KV)
    kw = dict(window=window, interpret=True, block_q=block, block_k=block)
    got = flash_attention(q, k, v, **kw)
    want = _xla(q, k, v, window)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = jax.jit(jax.grad(lambda *a: (flash_attention(*a, **kw) * g).sum(),
                             (0, 1, 2)))(q, k, v)
    wants = jax.jit(jax.grad(lambda *a: (_xla(*a, window) * g).sum(),
                             (0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", grads, wants):
        assert a.shape == b.shape       # dk, dv at the key-value heads
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=f"d{name}")


def test_looped_sweep_with_a_window_and_a_group():
    """S 3072 in 512-blocks: six tiles a sweep, so both kernels loop, the
    backward sums a key tile's dk and dv in VMEM a head and then over the
    four query heads of the key-value head."""
    q, k, v, g = _qkv(3072, 4, 1, seed=3)
    kw = dict(window=1024, interpret=True)
    np.testing.assert_allclose(flash_attention(q, k, v, **kw),
                               _xla(q, k, v, 1024), atol=2e-5)
    grads = jax.jit(jax.grad(lambda *a: (flash_attention(*a, **kw) * g).sum(),
                             (0, 1, 2)))(q, k, v)
    wants = jax.jit(jax.grad(lambda *a: (_xla(*a, 1024) * g).sum(),
                             (0, 1, 2)))(q, k, v)
    for a, b in zip(grads, wants):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_xla_path_groups_and_windows_like_a_plain_reference():
    q, k, v, _ = _qkv(96, 4, 2, D=16)
    rep = lambda x: jnp.repeat(x, 2, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, rep(k)) * 16 ** -0.5
    back = jnp.arange(96)[:, None] - jnp.arange(96)[None, :]
    s = jnp.where((back >= 0) & (back < 10), s, -jnp.inf)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), rep(v))
    got = dot_product_attention(q, k, v, window=10, impl="jnp")
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_what_cannot_take_a_window_says_so():
    q, k, v, _ = _qkv(128, 2, 2, D=64)
    with pytest.raises(NotImplementedError, match="sliding window"):
        dot_product_attention(q, k, v, window=16, impl="ring")
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, window=16, causal=False, impl="jnp")
    with pytest.raises(ValueError, match="causal"):
        score_tile_schedule(256, 256, 128, 128, False, False, 64)
    # the kernel groups whole 128-lane heads only; the dispatcher repeats
    # k and v for any other head_dim and says so
    assert grouped_in_kernel(128) and not grouped_in_kernel(64)
    with pytest.raises(ValueError, match="repeat k and v"):
        flash_attention(q, k[:, :, :1], v[:, :, :1], interpret=True)


def test_schedule_at_the_third_cells_shape_by_hand():
    """S 8192, 512-blocks, window 1024.  Forward (whole tiles): each of the
    16 query blocks meets one diagonal tile, one full tile (but the
    first) and one band-edge tile (but the first two); everything else is
    void.  Backward (256 sub-tiles): a diagonal tile is 1 void + 1 full +
    2 masked quarters, a band-edge tile 1 void + 1 full + 2 masked."""
    fwd = score_tile_schedule(8192, 8192, 512, 512, True, False, 1024)
    kinds = collections.Counter(t[2] for t in fwd.tiles)
    assert kinds == {DIAGONAL: 16, FULL: 15, BAND_EDGE: 14, VOID: 256 - 45}
    assert [d0 for d0, _ in fwd.diagonal] == [0, 1024]
    for i in range(16):     # the full run of each program: one tile, i - 1
        lo, hi = _full_tiles(i, fwd, own_is_q=True)
        assert list(range(lo, hi)) == ([i - 1] if i else [])
    bwd = score_tile_schedule(8192, 8192, 512, 512, True, True, 1024)
    assert (bwd.sub_q, bwd.sub_k) == (256, 256)
    kinds = collections.Counter(t[2] for t in bwd.tiles)
    assert kinds[DIAGONAL] == 2 * 16 and kinds[BAND_EDGE] == 2 * 14
    assert kinds[FULL] == 16 + 4 * 15 + 14
    assert sum(kinds.values()) == 32 * 32 and CROSSED not in kinds
    for j in range(16):
        lo, hi = _full_tiles(j, bwd, own_is_q=False)
        assert list(range(lo, hi)) == ([j + 1] if j < 15 else [])
    # without the window the same shape runs 4.3x the tiles
    plain = collections.Counter(t[2] for t in score_tile_schedule(
        8192, 8192, 512, 512, True, False).tiles)
    assert plain == {DIAGONAL: 16, FULL: 120, VOID: 120}
    assert (16 + 120) / 45 > 3


@pytest.mark.parametrize("S,halve", [(1024, False), (1024, True),
                                     (4096, False), (4096, True)])
def test_the_older_cells_schedules_are_what_they_were(S, halve):
    """XL (S 1024) and OLMoE (S 4096) run no window: the tuples their
    kernels are built from are those of the schedule before it knew one."""
    sched = score_tile_schedule(S, S, 512, 512, True, halve)
    assert sched.window is None
    n = S // 512
    sub = 256 if halve else 512
    want = []
    for q0 in range(0, S, 512):
        for k0 in range(0, S, 512):
            for r0 in range(0, 512, sub):
                for c0 in range(0, 512, sub):
                    d = q0 + r0 - k0 - c0
                    kind = FULL if d >= sub - 1 else \
                        VOID if d <= -sub else DIAGONAL
                    want.append((q0 + r0, k0 + c0, kind))
    assert list(sched.tiles) == want
    assert [d0 for d0, _ in sched.diagonal] == [0]
    assert _full_tiles(n - 1, sched, own_is_q=True) == (0, n - 1)
    assert _full_tiles(0, sched, own_is_q=False) == (1, n)
    assert flash_lanes(25, 64).heads == 2 and flash_lanes(16, 128).heads == 1


@pytest.mark.parametrize("d,rows,cols,window,kind", [
    (0, 512, 512, 1024, DIAGONAL), (512, 512, 512, 1024, FULL),
    (1024, 512, 512, 1024, BAND_EDGE), (1536, 512, 512, 1024, VOID),
    (1535, 512, 512, 1024, VOID), (-512, 512, 512, 1024, VOID),
    (0, 128, 128, 100, CROSSED), (128, 128, 128, 100, BAND_EDGE),
    (0, 128, 128, 128, DIAGONAL), (127, 128, 128, None, FULL),
])
def test_tile_kind(d, rows, cols, window, kind):
    assert _tile_kind(d, rows, cols, True, window) == kind
    back = (d + np.arange(rows))[:, None] - np.arange(cols)[None, :]
    keep = (back >= 0) & (back < (window or 10 ** 9))
    assert {VOID: not keep.any(), FULL: keep.all()}.get(
        kind, keep.any() and not keep.all())


# --- the straight-line windowed sweeps against the parent's (PR 43) --------

def _passes(S, H, KV, block, window, B=2, seed=5):
    from tests.unit.flash_parent_sweep import passes

    q, k, v, do = _qkv(S, H, KV, B=B, seed=seed)
    return lambda: passes(q, k, v, do, block=block, window=window)


# the cells' schedules (S 8192 in 512-blocks) cut to 128-blocks: a window
# under a block (every masked tile crossed by both edges), of one block
# (Mellum 2's 1024: one FULL, one DIAGONAL, one BAND_EDGE tile a program),
# of no whole number of blocks, of two (Trinity's 2048: three FULL tiles),
# and one of seven tiles a program, past STRAIGHT_MAX: a loop, its masked
# tiles void where a program does not meet them.  Every program is in the
# comparison: the first query tiles and the last key tiles, whose void
# tiles are computed now, too.
@pytest.mark.parametrize("H,KV", [(2, 1), (2, 2)])
@pytest.mark.parametrize("S,window", [(1024, 64), (1024, 128), (1024, 200),
                                      (1024, 256), (896, 256), (1024, 768)])
def test_windowed_sweeps_equal_the_parents_exactly(S, window, H, KV):
    """out, lse, dq, dk and dv of the straight-line sweep, entry for entry:
    it folds the parent's tiles in the parent's order, and a tile computed
    void adds exact zeros."""
    from tests.unit.flash_parent_sweep import assert_equal_to_the_parents

    assert_equal_to_the_parents(_passes(S, H, KV, 128, window),
                                ("out", "lse", "dq", "dk", "dv"))


@pytest.mark.parametrize("window,straight", [(64, True), (128, True),
                                             (256, True), (768, False)])
def test_no_branch_and_no_one_trip_loop_is_left_in_a_windowed_sweep(
        window, straight):
    """The kernels' jaxprs: the parent's sweep holds a ``while`` a pass
    (the loop over FULL tiles, one trip at a window of one block) and a
    ``cond`` a pass around the band-edge tile; the change's holds neither
    up to STRAIGHT_MAX tiles a program, and past it the loop alone.  The
    two ``cond`` that stay are the backward's ``pl.when`` around dq's
    scratch, once a program."""
    from tests.unit.flash_parent_sweep import kernel_primitives, parent_sweeps

    run = _passes(1024, 2, 1, 128, window, B=1)
    assert kernel_primitives(run) == (
        {"cond": 2} if straight else {"cond": 2, "while": 2})
    with parent_sweeps():
        assert kernel_primitives(run) == {"cond": 4, "while": 2}


def _blocks(S, window, heads=1):
    from deepspeed_tpu.ops.pallas.flash_attention import sweep_blocks

    return {pass_: sweep_blocks(
        score_tile_schedule(S, S, 512, 512, True, pass_ == "bwd", window),
        own_is_q=pass_ == "fwd", heads=heads) for pass_ in ("fwd", "bwd")}


@pytest.mark.parametrize("cell,window,full_pairs", [
    ("train-mellum2-8k-1chip", 1024, 56),
    ("train-trinity-mini-8k-1chip", 2048, 56),
    ("train-joyai-flash-8k-1chip", None, 56)])
def test_sweep_blocks_at_the_cells_shapes(cell, window, full_pairs):
    """``flash_sweep_blocks_total`` a head-sequence of 8192 in 512-tiles, one
    head a lane block: a window layer is 16 straight-line blocks a pass and
    nothing else (the parent's: 16 + 15 loop trips + 14 taken branches at
    window 1024 for the same 45 tiles); a full layer (the two-product
    kernels' too) loops over pairs, sum of floor(n / 2) for n = 0..15, and
    ends every program in one straight-line block; the backward's odd tile
    out is the one branch left (``_fold_run`` has both readings)."""
    from deepspeed_tpu.telemetry import get_registry

    if window is not None:
        assert _blocks(8192, window) == {"fwd": {"straight": 16},
                                         "bwd": {"straight": 16}}
    want = {"straight": 16, "paired_loop": full_pairs}
    # the backward's odd tile out sits under a branch: 8 of its 16 programs
    assert _blocks(8192, None) == {"fwd": want, "bwd": {**want, "branch": 8}}
    # two heads a lane block are two chains already: one tile a trip
    assert _blocks(8192, None, heads=2)["fwd"] == {"straight": 16,
                                                   "single_loop": 120}

    def counts():
        entry = get_registry().snapshot().get("flash_sweep_blocks_total")
        return {} if not entry else {
            (s["labels"]["pass"], s["labels"]["form"]): s["value"]
            for s in entry["samples"]}

    q, k, v, _ = _qkv(8192, 2, 1)
    before = counts()
    jax.eval_shape(jax.grad(lambda q: flash_attention(
        q, k, v, window=window).sum()), q)
    delta = {key: n - before.get(key, 0) for key, n in counts().items()}
    want = {"straight": 16} if window else {"straight": 16,
                                            "paired_loop": full_pairs}
    assert delta == {(p, form): {**want, "branch": 0 if window or p == "fwd"
                                 else 8}.get(form, 0) for p in ("fwd", "bwd")
                     for form in ("straight", "paired_loop", "single_loop",
                                  "branch")}
