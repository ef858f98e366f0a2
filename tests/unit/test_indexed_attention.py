"""``ops/indexed_attention.py``: the Pallas kernels (interpreted on the CPU)
against the plain ``jax.numpy`` form, which the model-level tests hold to
``benchmark/reference/keye.py``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.indexed_attention import (indexed_attention,
                                                 indexer_scores,
                                                 plain_selection, select)

indexed_attention_module = importlib.import_module(
    "deepspeed_tpu.ops.indexed_attention")
H, KV, D, NI, DI = 4, 2, 128, 4, 64


def _operands(B, S, seed=0, dtype=jnp.bfloat16, heads=H, kv_heads=KV):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, S, heads, D), dtype)
    k = jax.random.normal(ks[1], (B, S, kv_heads, D), dtype)
    v = jax.random.normal(ks[2], (B, S, kv_heads, D), dtype)
    qi = jax.random.normal(ks[3], (B, S, NI, DI), dtype)
    ki = jax.random.normal(ks[4], (B, S, DI), dtype)
    w = jax.random.normal(ks[5], (B, S, NI), jnp.float32) * 0.1
    return (q, k, v, qi, ki, w), ks[6], ks[7]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _both(impl, ops, topk, cot, ckl, selection=None, **kw):
    def loss(*ops):
        r = indexed_attention(*ops, topk=topk, impl=impl,
                              selection=selection, **kw)
        return ((r.out.astype(jnp.float32) * cot).sum()
                + (r.kl * ckl).sum(), r)

    (_, r), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(6), has_aux=True))(*ops)
    return r, grads


KERNEL = dict(interpret=True)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several blocks of queries and of keys in rows this short."""
    monkeypatch.setattr(indexed_attention_module, "BLOCK_Q", 64)
    monkeypatch.setattr(indexed_attention_module, "BLOCK_K", 128)


@pytest.mark.parametrize("B,S,topk,heads,kv_heads,block_k", [
    (2, 256, 48, H, KV, 128),   # fewer keys than the row; two rows of data
    (1, 128, 200, H, KV, 128),  # a row shorter than topk: every key is kept
    (1, 256, 5, H, KV, 128),    # a handful of keys a query
    (1, 256, 48, 3, 1, 128),    # an odd head count: one head a trip
    (1, 256, 48, 6, 3, 128),    # two a trip, on one key-value head
    (1, 256, 48, 4, 4, 128),    # a trip's four heads on four key-value heads
    (1, 256, 48, H, KV, 64),    # no whole 128-lane block of keys a tile
])
def test_the_kernels_are_the_plain_form(B, S, topk, heads, kv_heads, block_k,
                                        monkeypatch):
    monkeypatch.setattr(indexed_attention_module, "BLOCK_K", block_k)
    ops, kc, kk = _operands(B, S, heads=heads, kv_heads=kv_heads)
    cot = jax.random.normal(kc, (B, S, heads, D), jnp.float32)
    ckl = jax.random.uniform(kk, (B, S), jnp.float32)
    want, g_want = _both("jnp", ops, topk, cot, ckl)
    got, g_got = _both("pallas", ops, topk, cot, ckl, **KERNEL)
    assert np.array_equal(got.tile_counts, want.tile_counts)
    assert int(got.tile_counts.sum()) == B * sum(
        min(t + 1, topk) for t in range(S))
    assert _rel(got.out, want.out) < 6e-3
    assert _rel(got.kl, want.kl) < 1e-4
    for name, a, b in zip("q k v qI kI w".split(), g_got, g_want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _rel(a, b) < 8e-3, (name, _rel(a, b))
    if B > 1:       # nothing of the second row is the first's
        alone, _ = _both("pallas", tuple(x[1:] for x in ops), topk, cot[1:],
                         ckl[1:], **KERNEL)
        assert np.array_equal(np.asarray(alone.out, np.float32),
                              np.asarray(got.out[1:], np.float32))


def test_selection_is_top_k_with_ties_to_the_lower_position():
    """Scores that tie: heads' weights that make whole runs of keys score
    alike (a constant kI), so the kept set is decided by position."""
    B, S, topk = 1, 256, 40
    ops, _, _ = _operands(B, S, seed=3)
    q, k, v, qi, ki, w = ops
    ki = jnp.broadcast_to(ki[:, :1], ki.shape)      # every key scores alike
    ki = ki.at[:, 7::16].set(ops[4][:, 7::16])      # but one in sixteen
    tau, cut = jax.jit(lambda *a: select(*a, topk, interpret=True))(
        qi, ki, w)
    assert int((np.asarray(cut) < S).sum()) > 100    # ties were cut
    # the plain form's scores tie where the kernels' do, and lax.top_k
    # takes the lower position: its mask, handed to the kernels, must give
    # what their own selection gives, bit for bit
    want = plain_selection(qi, ki, w, topk)
    pos = np.arange(S)
    assert (np.asarray(want).sum(-1) == np.minimum(pos + 1, topk)).all()
    scores = np.asarray(indexer_scores(qi, ki, w))[0]
    tied = [(scores[t, :t + 1] == np.sort(scores[t, :t + 1])[-topk]).sum()
            for t in range(topk, S)]
    assert np.mean(np.asarray(tied) > 1) > 0.9   # rows tie at their threshold
    own = indexed_attention(q, k, v, qi, ki, w, topk=topk, impl="pallas",
                            **KERNEL)
    given = indexed_attention(q, k, v, qi, ki, w, topk=topk, impl="pallas",
                              selection=want, **KERNEL)
    assert np.array_equal(own.tile_counts, given.tile_counts)
    assert np.array_equal(np.asarray(own.out, np.float32),
                          np.asarray(given.out, np.float32))
    assert np.array_equal(np.asarray(own.kl), np.asarray(given.kl))


def test_a_selection_from_outside_is_used_as_given():
    """The second stage under a mask: the kernels and the plain form agree
    on a selection that is NOT the indexer's, and its non-causal part is
    ignored."""
    B, S, topk = 2, 256, 32
    ops, kc, kk = _operands(B, S, seed=5)
    cot = jax.random.normal(kc, (B, S, H, D), jnp.float32)
    ckl = jax.random.uniform(kk, (B, S), jnp.float32)
    mask = jax.random.uniform(jax.random.PRNGKey(9), (B, S, S)) < 0.2
    mask = mask | jnp.eye(S, dtype=bool)[None]      # a key a query at least
    want, g_want = _both("jnp", ops, topk, cot, ckl, selection=mask)
    got, g_got = _both("pallas", ops, topk, cot, ckl, selection=mask,
                       **KERNEL)
    assert np.array_equal(got.tile_counts, want.tile_counts)
    assert int(got.tile_counts.sum()) == int(
        (np.asarray(mask) & np.tril(np.ones((S, S), bool))[None]).sum())
    assert _rel(got.out, want.out) < 6e-3
    assert _rel(got.kl, want.kl) < 1e-4
    for name, a, b in zip("q k v qI kI w".split(), g_got, g_want):
        assert _rel(a, b) < 8e-3, (name, _rel(a, b))


def test_the_two_stop_gradients():
    """q, k, v learn from the output alone; the indexer's operands from the
    KL alone; the selection passes nothing."""
    B, S, topk = 1, 128, 16
    ops, kc, _ = _operands(B, S, seed=7)
    cot = jax.random.normal(kc, (B, S, H, D), jnp.float32)
    for impl, kw in (("jnp", {}), ("pallas", KERNEL)):
        _, from_out = _both(impl, ops, topk, cot, jnp.zeros((B, S)), **kw)
        _, from_kl = _both(impl, ops, topk, jnp.zeros_like(cot),
                           jnp.ones((B, S)), **kw)
        for g in from_out[3:]:
            assert not np.asarray(g, np.float32).any(), impl
        for g in from_kl[:3]:
            assert not np.asarray(g, np.float32).any(), impl
        for g in from_out[:3] + from_kl[3:]:
            assert np.asarray(g, np.float32).any(), impl


def test_auto_takes_the_plain_form_off_the_chip_and_says_so():
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    ops, _, _ = _operands(1, 128)
    before = {r[:3]: r[3] for r in dispatch_report()}
    indexed_attention(*ops, topk=8)
    after = {r[:3]: r[3] for r in dispatch_report()}
    key = ("indexed_attention", "jnp", "no TPU")
    assert after[key] == before.get(key, 0) + 1
    with pytest.raises(ValueError, match="impl"):
        indexed_attention(*ops, topk=8, impl="flash")


# --------------------------------------------------------------------------
# what a head's tile asks of the unit that moves data across lanes (PR 57)


def _kernel_jaxprs(heads, kv_heads, S=384):
    """``{fwd | dq | dkv: the kernel body's jaxpr}`` traced (not run) for
    ``heads`` on ``kv_heads``, and what tracing them added to
    ``indexed_attn_tile_ops_total``.  A row length no other test has: a
    call that an earlier test traced is answered from jit's cache, and
    nothing is counted."""
    from deepspeed_tpu.telemetry import registry

    def samples():
        entry = registry.get_registry().snapshot().get(
            "indexed_attn_tile_ops_total", {"samples": []})
        return {(x["labels"]["kernel"], x["labels"]["op"]): x["value"]
                for x in entry["samples"]}

    ops, kc, kk = _operands(1, S, heads=heads, kv_heads=kv_heads)

    def loss(*ops):
        r = indexed_attention(*ops, topk=16, impl="pallas", **KERNEL)
        return r.out.astype(jnp.float32).sum() + r.kl.sum()

    before = samples()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(6)))(*ops)
    after = samples()
    bodies = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                bodies[name.replace("indexed_attn_", "")] = eqn.params["jaxpr"]
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return bodies, {k: after[k] - before.get(k, 0) for k in after}


def _eqns(jaxpr, inside_loop=False):
    """``(primitive name, eqn, whether under a loop)`` of every equation."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, eqn, inside_loop
        loop = inside_loop or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, loop)


@pytest.mark.parametrize("heads,kv_heads,per", [(32, 4, 4), (6, 3, 2),
                                                  (3, 1, 1)])
def test_a_tile_keeps_off_the_cross_lane_unit(heads, kv_heads, per):
    """At the cell's head counts (and where only two or one divide the
    count): four heads a trip of every heads' loop, one
    reduction over lanes a head a tile forward (the maximum) and none
    backward, and no product of ``indexed_attn_dkv`` contracts dimension 0
    of both operands (its tile stands keys by queries), nor does it sum a
    masked block to read a column."""
    bodies, counted = _kernel_jaxprs(heads, kv_heads)
    assert set(bodies) >= {"fwd", "dq", "dkv"}
    for kernel in ("fwd", "dq", "dkv"):
        traces = counted[kernel, "heads_a_trip"] // per
        assert traces >= 1 and counted[kernel, "heads_a_trip"] == per * traces
        assert counted[kernel, "lane_reduction_a_head"] == (
            traces if kernel == "fwd" else 0)
        assert counted[kernel, "transposed_contraction"] == 0
    # what the counter says is what the bodies hold
    in_loops = {k: [n for n, _, loop in _eqns(b) if loop and n.startswith(
        ("reduce_", "argm"))] for k, b in bodies.items()}
    assert [in_loops[k] for k in ("fwd", "dq", "dkv")] == [
        ["reduce_max"] * per, [], []]
    dkv = list(_eqns(bodies["dkv"]))
    assert not [n for n, _, _ in dkv if n.startswith("reduce_")]
    dots = [e.params["dimension_numbers"][0] for n, e, _ in dkv
            if n == "dot_general"]
    assert dots and all(c != ((0,), (0,)) for c in dots)
    # every trip's loop runs heads / per times
    trips = [e.params["length"] for n, e, _ in _eqns(bodies["dq"])
             if n == "scan"]
    assert trips == [heads // per]
