"""The flash kernels under block diffusion's mask (PR 40) in interpret
mode against the dense mask: the whole ``[noisy ; clean]`` attention
(``ops/attention.py block_diffusion_attention``: one flash call a pass
over all ``2L`` rows, the noisy queries' own blocks a tile of the kernels'
schedule, PR 41; both strictnesses of the block-granular diagonal ``q // g
>= k // g`` are its clean keys' tiles) against ``block_diffusion_mask``:
block lengths 4, 16 and 32, sequences that are no multiple of the default tile, one tile a half
and several, the backward's halved tiles, grouped and ungrouped heads,
forward and all three gradients on two rows of different content, the
first block's rows (which keep no clean key) apart, the halves' sweep
against its schedule tile by tile, the tile counter's classes against a
brute-force count of kept entries, and what is refused by name.
"""
import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.ops import attention as attention_lib
from deepspeed_tpu.ops.pallas.spmd import dispatch_report
from deepspeed_tpu.telemetry import get_registry

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


@pytest.fixture
def one_device():
    """The dispatcher hands a kernel its operands only where it knows their
    sharding: a mesh of one device (the tests' eight have none set)."""
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    yield
    mesh_lib.set_mesh(None)


def _flash_dispatches(g):
    return sum(n for s, i, r, n in dispatch_report()
               if (s, i) == ("attention", "flash")
               and f"block length {g}" in r)


def _operands(B, S, H, KV, D, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((B, S, H, D), (B, S, KV, D), (B, S, KV, D), (B, S, H, D))
    return [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]


def _keep(S, g, strict):
    qb, kb = np.arange(S)[:, None] // g, np.arange(S)[None, :] // g
    return qb > kb if strict else qb >= kb


def _plain(q, k, v, keep):
    """Dense masked softmax attention; a row that keeps nothing gives 0."""
    H, KV = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision="highest") \
        * q.shape[-1] ** -0.5
    s = jnp.where(jnp.asarray(keep), s, -jnp.inf)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    l = p.sum(-1, keepdims=True)
    return jnp.einsum("bhst,bthd->bshd", p / jnp.where(l == 0, 1.0, l), v,
                      precision="highest")


@pytest.mark.parametrize("g,strict", [(4, False), (16, True), (32, False),
                                      (128, True)])
def test_tile_classes_match_a_brute_force_count(g, strict):
    """Each sub-tile's class against the kept entries counted one by one:
    void keeps none, full all, block_diagonal some; forward (whole tiles)
    and backward (the crossed tile in halves)."""
    S, bq = 1024, 256
    keep = _keep(S, g, strict)
    for halve in (False, True):
        sched = fa.score_tile_schedule(S, S, bq, bq, True, halve, None,
                                       (g, strict))
        rows, cols = sched.sub_q, sched.sub_k
        assert len(sched.tiles) == (S // rows) * (S // cols)
        for q0, k0, kind in sched.tiles:
            kept = int(keep[q0:q0 + rows, k0:k0 + cols].sum())
            want = fa.VOID if kept == 0 else fa.FULL \
                if kept == rows * cols else fa.BLOCK_DIAGONAL
            assert kind == want, (q0, k0, kind, kept)


@pytest.mark.parametrize("g", [4, 16, 32])
@pytest.mark.parametrize("L,block", [(512, 512), (1024, 256), (384, 128)])
def test_the_counter_names_the_own_tiles_kind(L, block, g):
    """``flash_score_tiles_total{kind="own_block"}`` of one head-sequence
    of ``[noisy ; clean]`` rows: ``L / block`` own tiles forward, the
    sub-tiles on their diagonals backward (two a tile where the backward
    halves it), beside the two block diagonals of the clean keys."""
    q, k, v, w = _operands(1, 2 * L, 2, 1, 128, seed=g)

    def count():
        entry = get_registry().snapshot().get("flash_score_tiles_total")
        out = collections.Counter()
        for s in (entry or {"samples": []})["samples"]:
            out[s["labels"]["pass"], s["labels"]["kind"]] += s["value"]
        return out

    before = count()
    jax.make_jaxpr(jax.grad(lambda q: (fa.flash_attention_halves(
        q, k, v, interpret=True, block=g, block_q=block, block_k=block)
        * w).sum()))(q)
    got = count() - before
    n = L // block
    halved = 2 if block % 256 == 0 else 1
    assert got["fwd", "own_block"] == n
    assert got["bwd", "own_block"] == halved * n
    assert got["fwd", "block_diagonal"] == 2 * n
    assert got["bwd", "block_diagonal"] == 2 * halved * n
    assert got["fwd", "full"] == n * (n - 1)
    assert got["fwd", "void"] == 4 * n * n - 3 * n - n * (n - 1)
    assert got["fwd", "diagonal"] == got["bwd", "diagonal"] == 0


@pytest.mark.parametrize("g", [4, 16, 32, 128])
@pytest.mark.parametrize("L,block", [(512, 256), (384, 128), (512, 512)])
def test_halves_tile_classes_match_a_brute_force_count(L, block, g):
    """Each sub-tile of the halves' schedule against the kept entries of
    ``block_diffusion_mask`` counted one by one, forward and backward: void
    keeps none, full all, own_block some of noisy keys, block_diagonal some
    of clean ones.  A block as long as a sub-tile is the one place where
    the schedule masks a tile that keeps all or nothing (one body serves
    both halves: ``_halves_tile_kind``)."""
    keep = np.asarray(attention_lib.block_diffusion_mask(L, g))
    assert keep.sum() == L * (L + g)
    for halve in (False, True):
        sched = fa.score_tile_schedule(2 * L, 2 * L, block, block, True,
                                       halve, None, (g, fa.HALVES))
        rows, cols = sched.sub_q, sched.sub_k
        assert len(sched.tiles) == (2 * L // rows) * (2 * L // cols)
        kept_by_class = collections.Counter()
        for q0, k0, kind in sched.tiles:
            kept = int(keep[q0:q0 + rows, k0:k0 + cols].sum())
            kept_by_class[kind] += kept
            masked = fa.OWN_BLOCK if k0 < L else fa.BLOCK_DIAGONAL
            want = fa.VOID if kept == 0 else fa.FULL \
                if kept == rows * cols else masked
            if g == rows and kind == masked:
                continue        # masked under both halves, whatever it keeps
            assert kind == want, (q0, k0, kind, kept)
        assert kept_by_class[fa.VOID] == 0
        assert kept_by_class[fa.OWN_BLOCK] == L * g
        assert sum(kept_by_class.values()) == L * (L + g)


@pytest.mark.parametrize("per", [1, 2])
@pytest.mark.parametrize("own_is_q", [True, False])
@pytest.mark.parametrize("L,block,halve", [(512, 128, False), (1024, 512, True),
                                           (256, 256, True), (896, 128, False)])
def test_the_halves_sweep_visits_what_the_schedule_lists(L, block, halve,
                                                         own_is_q, per):
    """``_halves_sweep`` for every program of the grid (a query tile
    forward, a key tile backward): the full tiles it loops over, the masked
    tiles it places and the strictness it hands their masks, against the
    schedule's own tiles of that row or column; one FULL tile a loop trip
    and two (PR 43: the odd one left over is folded void)."""
    g = 4
    sched = fa.score_tile_schedule(2 * L, 2 * L, block, block, True,
                                   halve and not own_is_q, None,
                                   (g, fa.HALVES))
    n2 = 2 * L // block
    whole = {(q0 // block, k0 // block): kind
             for q0, k0, kind in fa.score_tile_schedule(
                 2 * L, 2 * L, block, block, True, False, None,
                 (g, fa.HALVES)).tiles}

    def visited(own):
        def full_tile(t0, c, live=None):    # a void tile counts nothing
            return c.at[t0 // block, 0].add(
                1 if live is None else live.astype(jnp.int32))

        def diagonal_tile(t0, d0, subs, c):
            kinds = {kind for _, _, kind in subs} - {fa.FULL}
            assert len(kinds) == 1
            own_block = kinds == {fa.OWN_BLOCK}
            return c.at[t0 // block, 1 + own_block].add(1).at[
                t0 // block, 3].add(d0)

        return np.asarray(jax.jit(lambda o: fa._halves_sweep(
            o, sched, own_is_q=own_is_q, per=per)(
            jnp.zeros((n2, 4), jnp.int32), full_tile, diagonal_tile))(own))

    for own in range(n2):
        want = np.zeros((n2, 4), np.int32)
        for t in range(n2):
            q_tile, k_tile = (own, t) if own_is_q else (t, own)
            kind = whole[q_tile, k_tile]
            if kind == fa.FULL:
                want[t, 0] = 1
            elif kind == fa.BLOCK_DIAGONAL:
                want[t, 1] = 1
                want[t, 3] = -g if q_tile < n2 // 2 else 0     # strict
            elif kind == fa.OWN_BLOCK:
                want[t, 2] = 1
        np.testing.assert_array_equal(visited(jnp.int32(own)), want,
                                      err_msg=f"program {own}")


# (L, H, KV, D, g): grouped in the kernel (head_dim 128); head_dim 64, where
# k and v are repeated first; L = 256, 384 and 512 are one tile a half, 640
# five of 128, 768 three of 256 and 1024 two of 512 (the last two and 256,
# 512 with the backward's halved tiles)
HALVES = [(256, 4, 2, 128, 4), (384, 2, 2, 64, 16), (256, 4, 1, 64, 32),
          (640, 2, 1, 128, 4), (640, 2, 2, 64, 16), (768, 2, 1, 128, 32),
          (1024, 2, 2, 128, 4), (512, 4, 2, 128, 16), (1024, 2, 1, 128, 32)]
# ... and with a tile: the shapes that the per-quadrant entry of PR 40
# (``flash_attention(block=, strict=)``, gone with PR 44) was tested at, a
# half each: three 128-tiles (no multiple of 512); grouped heads; an
# eight-tile looped sweep; the backward's halved tile with blocks of 32
HALVES = [(*case, 512) for case in HALVES] + [
    (384, 2, 2, 64, 4, 512), (384, 4, 2, 128, 16, 512),
    (1024, 2, 1, 128, 4, 128), (1024, 2, 2, 64, 32, 512),
    (512, 2, 2, 64, 16, 512), (640, 2, 1, 128, 32, 128)]


@pytest.mark.parametrize("L,H,KV,D,g,tile", HALVES)
def test_noisy_and_clean_halves_match_the_dense_mask(one_device, L, H, KV,
                                                     D, g, tile):
    """Forward and gradients of all ``2L`` rows; the clean keys' tiles are
    the block-granular diagonal, strict under the noisy queries and loose
    under the clean ones."""
    q, k, v, w = _operands(2, 2 * L, H, KV, D, seed=L + g)
    mask = np.asarray(attention_lib.block_diffusion_mask(L, g))
    before = _flash_dispatches(g)
    kern = lambda *a: attention_lib.dot_product_attention(
        *a, block_diffusion=g, impl="flash", interpret=True,
        flash_opts={"block_q": tile, "block_k": tile})
    plain = lambda *a: _plain(*a, mask)

    def run(f):     # forward and the gradients of (out * w).sum(), one program
        def both(q, k, v):
            out, pull = jax.vjp(f, q, k, v)
            return out, pull(w)
        return jax.jit(both)(q, k, v)

    (out, got), (want, want_grads) = run(kern), run(plain)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert _flash_dispatches(g) == before + 1       # the kernels ran
    # the first block's rows keep their own block's keys and no clean key
    np.testing.assert_allclose(out[:, :g], want[:, :g], atol=2e-5,
                               err_msg="first block")
    assert float(jnp.abs(out[0] - out[1]).max()) > 0.1      # two contents
    for name, a, b in zip("qkv", got, want_grads):
        assert bool(jnp.isfinite(a).all()), name
        for half, x, y in zip(("noisy", "clean"), np.split(a, 2, 1),
                              np.split(b, 2, 1)):
            np.testing.assert_allclose(x, y, atol=5e-5,
                                       err_msg=f"d{name} {half}")
        np.testing.assert_allclose(a[:, :g], b[:, :g], atol=5e-5,
                                   err_msg=f"d{name} first block")
        assert float(np.abs(b[:, :g]).max()) > 1e-3


def test_the_mask_is_the_four_sentences():
    """L 4, g 2 written out by hand: rows and columns [noisy ; clean]."""
    want = np.array([
        [1, 1, 0, 0, 0, 0, 0, 0],       # noisy block 0: its own noisy block
        [1, 1, 0, 0, 0, 0, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],       # noisy block 1: + clean block 0
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],       # clean block 0: clean block 0
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 1, 1],       # clean block 1: clean blocks 0, 1
        [0, 0, 0, 0, 1, 1, 1, 1]], bool)
    got = np.asarray(attention_lib.block_diffusion_mask(4, 2))
    assert (got == want).all()
    for L, g in ((64, 4), (96, 32)):
        m = np.asarray(attention_lib.block_diffusion_mask(L, g))
        assert m.sum() == L * (L + g)       # kept pairs of a data row
        assert m[:L, :L].sum() == L * g and not m[L:, :L].any()


def test_xla_path_takes_the_same_mask_and_rows_do_not_mix(one_device):
    L, g = 128, 4
    q, k, v, _ = _operands(2, 2 * L, 4, 2, 32, seed=5)
    both = attention_lib.block_diffusion_attention(q, k, v, block=g,
                                                   impl="jnp")
    mask = np.asarray(attention_lib.block_diffusion_mask(L, g))
    np.testing.assert_allclose(both, _plain(q, k, v, mask), atol=2e-5)
    alone = attention_lib.block_diffusion_attention(
        q[1:], k[1:], v[1:], block=g, impl="jnp")
    np.testing.assert_allclose(both[1:], alone, atol=1e-6)
    kern = attention_lib.block_diffusion_attention(
        q, k, v, block=g, impl="flash", interpret=True)
    np.testing.assert_allclose(kern[1:], attention_lib.block_diffusion_attention(
        q[1:], k[1:], v[1:], block=g, impl="flash", interpret=True), atol=1e-6)
    assert float(jnp.abs(kern[0] - kern[1]).max()) > 0.1


@pytest.mark.parametrize("g", [3, 24, 48, 256, 0])
def test_a_block_length_that_does_not_divide_128_raises(g):
    q, k, v, _ = _operands(1, 768, 2, 2, 64)
    with pytest.raises(ValueError, match="128"):
        fa.flash_attention_halves(q, k, v, interpret=True, block=g)
    if g:
        with pytest.raises(ValueError):
            attention_lib.block_diffusion_attention(q, k, v, block=g,
                                                    impl="jnp")


def test_what_is_not_written_raises_by_name():
    q, k, v, _ = _operands(1, 256, 2, 2, 64)
    with pytest.raises(NotImplementedError, match="sliding window"):
        fa.score_tile_schedule(256, 256, 128, 128, True, False, 64, (4, False))
    with pytest.raises(NotImplementedError, match="causal"):
        fa.score_tile_schedule(256, 256, 128, 128, False, False, None,
                               (4, False))
    with pytest.raises(NotImplementedError, match="sliding window"):
        attention_lib.dot_product_attention(q, k, v, block_diffusion=4,
                                            window=64)
    for impl in ("ring", "ulysses"):
        with pytest.raises(NotImplementedError, match="sequence-parallel"):
            attention_lib.block_diffusion_attention(q, k, v, block=4,
                                                    impl=impl)
    with pytest.raises(ValueError, match="whole blocks"):
        attention_lib.block_diffusion_attention(q[:, :250], k[:, :250],
                                                v[:, :250], block=4)


def test_without_a_block_the_schedule_is_the_causal_one():
    """The block-granular forms are keyed apart: a schedule without
    ``diag`` is the one every other model runs, tile for tile."""
    plain = fa.score_tile_schedule(1024, 1024, 512, 512, True, True)
    assert plain.diag is None
    assert {t[2] for t in plain.tiles} == {fa.VOID, fa.FULL, fa.DIAGONAL}
    blockwise = fa.score_tile_schedule(1024, 1024, 512, 512, True, True, None,
                                       (4, False))
    assert [t[:2] for t in plain.tiles] == [t[:2] for t in blockwise.tiles]
    assert [t[2].replace("block_", "") for t in blockwise.tiles] \
        == [t[2] for t in plain.tiles]


@pytest.mark.parametrize("L", [1024, 896])
def test_the_halves_paired_sweeps_equal_the_parents_exactly(L):
    """The halves' loops over clean FULL tiles fold two a trip (PR 43): out,
    lse, dq, dk and dv equal the parent's one-tile-a-trip sweeps entry for
    entry, at eight and at seven tiles a half (noisy and clean programs
    with odd and even counts of FULL tiles before them)."""
    from tests.unit.flash_parent_sweep import assert_equal_to_the_parents, passes

    q, k, v, do = _operands(2, 2 * L, 2, 1, 128, seed=9)
    assert_equal_to_the_parents(
        lambda: passes(q, k, v, do, block=128, halves=4),
        ("out", "lse", "dq", "dk", "dv"))
