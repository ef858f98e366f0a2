"""The flash kernels under a score of two products (PR 38; since PR 44 a
second term of the one kernel family, ``flash_attention(q, k, v, q_rope=,
k_rope=)``; latent attention: ``q_nope_h · k_nope_h + q_rope_h · k_rope``,
ONE rope key for all heads) in interpret mode against ``jnp``: the forward and all five gradients, causal, a
sequence that is no multiple of the default block, a looped key sweep, two
rows with different contents (nothing else of the model holds a batch
dimension), the score-tile counter as at the same S without the second
product, and the dispatcher's word on what ran.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.ops import attention as attention_lib
from deepspeed_tpu.ops.pallas.spmd import dispatch_report
from deepspeed_tpu.telemetry import get_registry

# the package re-exports the function under the module's name
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

H, D, R = 4, 128, 64
NAMES = ("q_nope", "q_rope", "k_nope", "k_rope", "v")


def _operands(B, S, seed=0, heads=H, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((B, S, heads, D), (B, S, heads, R), (B, S, heads, D),
              (B, S, 1, R), (B, S, heads, D), (B, S, heads, D))
    return [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]


def _two_products(qn, qr, kn, kr, v, **kw):
    return fa.flash_attention(qn, kn, v, q_rope=qr, k_rope=kr, **kw)


def _plain(qn, qr, kn, kr, v):
    s = (jnp.einsum("bshd,bthd->bhst", qn, kn, precision="highest")
         + jnp.einsum("bshr,btr->bhst", qr, kr[:, :, 0], precision="highest")
         ) * (D + R) ** -0.5
    S = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                      precision="highest")


# (rows, S, block): two short tiles a row; a sweep of five tiles (looped);
# S 384 = three 128-blocks, no multiple of the default 512
CASES = [(2, 256, 128), (1, 640, 128), (2, 384, 512)]


@pytest.mark.parametrize("B,S,block", CASES)
def test_forward_and_all_five_gradients_match_jnp(B, S, block):
    *ops, w = _operands(B, S, seed=S)
    kern = lambda *a: _two_products(
        *a, interpret=True, block_q=block, block_k=block)
    np.testing.assert_allclose(kern(*ops), _plain(*ops), atol=2e-5)
    got = jax.jit(jax.grad(lambda *a: (kern(*a) * w).sum(),
                           argnums=range(5)))(*ops)
    want = jax.jit(jax.grad(lambda *a: (_plain(*a) * w).sum(),
                            argnums=range(5)))(*ops)
    for name, g, x in zip(NAMES, got, want):
        assert g.shape == x.shape, name
        np.testing.assert_allclose(g, x, atol=5e-5, err_msg=name)


def test_two_rows_with_different_contents_do_not_mix():
    """Row 1 alone gives row 1 of the pair, forward and dk_rope (the sum
    over heads lives in a scratch that the next row must not inherit)."""
    *ops, w = _operands(2, 256, seed=7)
    kern = lambda *a: _two_products(*a, interpret=True, block_q=128,
                                    block_k=128)
    one = [x[1:] for x in ops]
    np.testing.assert_allclose(kern(*ops)[1:], kern(*one), atol=1e-6)
    both = jax.jit(jax.grad(lambda *a: (kern(*a) * w).sum(), argnums=3))(*ops)
    alone = jax.jit(jax.grad(lambda *a: (kern(*a) * w[1:]).sum(),
                             argnums=3))(*one)
    np.testing.assert_allclose(both[1:], alone, atol=1e-5)
    assert float(jnp.abs(both[0] - both[1]).max()) > 0.1


def test_bf16_operands_keep_their_types():
    *ops, _ = _operands(1, 256, seed=3, dtype=jnp.bfloat16)
    kern = lambda *a: _two_products(*a, interpret=True)
    out = kern(*ops)
    assert out.dtype == jnp.bfloat16
    grads = jax.jit(jax.grad(lambda *a: kern(*a).astype(jnp.float32).sum(),
                             argnums=range(5)))(*ops)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 5
    want = _plain(*[x.astype(jnp.float32) for x in ops])
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < 3e-2


def _tiles():
    entry = get_registry().snapshot().get("flash_score_tiles_total")
    return {} if not entry else {
        (s["labels"]["pass"], s["labels"]["kind"]): s["value"]
        for s in entry["samples"]}


def test_the_tile_counter_reads_as_at_the_same_S_without_the_second_product():
    def delta(run):
        before = _tiles()
        run()
        after = _tiles()
        return {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)}

    S = 768      # its own length: tiles are counted when a call is traced
    *ops, _ = _operands(1, S, seed=1)
    q, k, v = ops[0], ops[2], ops[4]
    plain = delta(lambda: jax.jit(jax.grad(lambda q: fa.flash_attention(
        q, k, v, interpret=True).sum()))(q))
    two = delta(lambda: jax.jit(jax.grad(lambda q: _two_products(
        q, ops[1], k, ops[3], v, interpret=True).sum()))(q))
    assert plain == two and plain


@pytest.mark.parametrize("H_,D_,R_,Dv,ok", [
    (32, 128, 64, 128, True), (4, 128, 128, 128, True),
    (32, 128, 64, 256, False),      # values wider than the keys
    (32, 192, 64, 192, False),      # no whole lane block a head
    (3, 128, 64, 128, False),       # a rope block would hold half a head pair
    (4, 16, 8, 16, False)])
def test_which_widths_the_kernels_take(H_, D_, R_, Dv, ok):
    """``mla_lanes``: the second term as the head programs read it (a
    128-lane block of ``q_rope`` holds ``128 // R`` heads; every head reads
    the one key), or None; the entry raises where it is None."""
    term = fa.mla_lanes(H_, D_, R_, Dv)
    assert (term is not None) == ok
    if ok:
        per = max(1, 128 // R_)
        assert term == fa.Term(block=per * R_, keys=H_, heads=per)
        return
    with pytest.raises(ValueError, match="no two-product kernel"):
        fa.flash_attention(*(jnp.zeros((1, 128, H_, w)) for w in (D_, D_, Dv)),
                           q_rope=jnp.zeros((1, 128, H_, R_)),
                           k_rope=jnp.zeros((1, 128, 1, R_)), interpret=True)


def test_the_dispatcher_says_what_ran_and_why():
    """``dot_product_attention(..., q_rope=, k_rope=)``: the XLA path on the
    CPU, by name; the kernels where asked for (interpret mode here), with
    the layout in the reason; both agree."""
    *ops, _ = _operands(1, 256, seed=5)
    qn, qr, kn, kr, v = ops
    auto = attention_lib.dot_product_attention(qn, kn, v, q_rope=qr,
                                               k_rope=kr)
    np.testing.assert_allclose(auto, _plain(*ops), atol=2e-5)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    try:
        flash = attention_lib.dot_product_attention(
            qn, kn, v, q_rope=qr, k_rope=kr, interpret=True)
        # eight rows over the eight devices of the test mesh: a shard_map
        mesh_lib.set_mesh(mesh_lib.build_mesh({"fsdp": 8}))
        qn8, kn8, v8, qr8, kr8 = (jnp.concatenate([x] * 8)
                                  for x in (qn, kn, v, qr, kr))
        sharded = attention_lib.dot_product_attention(
            qn8, kn8, v8, q_rope=qr8, k_rope=kr8, interpret=True)
    finally:
        mesh_lib.set_mesh(None)
    np.testing.assert_allclose(flash, auto, atol=2e-5)
    np.testing.assert_allclose(sharded[3:4], auto, atol=2e-5)
    rows = {(s, i, r) for s, i, r, n in dispatch_report() if n}
    assert ("attention", "jnp", "auto: not a TPU") in rows
    for plan in ("one device", "shard_map over batch axes ('fsdp',)"):
        assert ("attention", "flash",
                f"auto: TPU, seq >= 128, head_dim tiles; {plan}; rows layout, "
                f"1 head a 128-lane block; 128 + 64 shared rope lanes, v 128"
                ) in rows, rows
    with pytest.raises(NotImplementedError, match="ring"):
        attention_lib.dot_product_attention(qn, kn, v, q_rope=qr, k_rope=kr,
                                            impl="ring")
    small = [x[..., :16] for x in (qn, kn, v)] + [qr[..., :8], kr[..., :8]]
    attention_lib.dot_product_attention(*small[:3], q_rope=small[3],
                                        k_rope=small[4], impl="auto")
    with pytest.raises(ValueError, match="no two-product kernel"):
        fa.flash_attention(*small[:3], q_rope=small[3], k_rope=small[4],
                           interpret=True)


@pytest.mark.parametrize("S", [1024, 896])
def test_paired_sweeps_equal_the_parents_exactly(S):
    """The two-product kernels fold two FULL tiles a loop trip (PR 43): all
    seven results equal the parent's one-tile-a-trip sweep entry for entry,
    at eight tiles a row (programs with odd and even counts of FULL tiles)
    and at seven."""
    from tests.unit.flash_parent_sweep import assert_equal_to_the_parents, mla_passes

    ops = _operands(2, S, seed=7, heads=2)
    assert_equal_to_the_parents(
        lambda: mla_passes(*ops, block=128),
        ("out", "lse", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"))
