"""Shared-prefix KV reuse (inference/kvreuse.py): paged pool host
semantics, gather/donate page movement, radix-tree exactness, eviction
safety, and the resolve surface (config + env).

``z``-prefixed like ``test_zdecode_fused_e2e`` so the module's batcher
compiles land late in the alphabetical tier-1 order and the window's
breadth is preserved; the fast admission-path regression coverage lives
early in ``test_prefill_bucketing.py``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference import kvreuse
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.models import common as model_common

from .simple_model import tiny_gpt2_engine


def _make_engine(**cfg_over):
    return tiny_gpt2_engine(cfg_over)


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    engine = _make_engine()
    yield engine
    mesh_mod.set_mesh(None)


def _pc(eng, page_tokens=4, n_pages=16):
    return kvreuse.resolve_prefix_cache(
        eng, {"page_tokens": page_tokens, "n_pages": n_pages})


def test_pool_alloc_free_lru(eng):
    pool = kvreuse.PagedKVPool(eng, n_pages=4, page_tokens=4)
    a = pool.alloc(3)
    assert sorted(a) == [0, 1, 2] and pool.free_pages == 1
    assert pool.alloc(2) is None            # short: no partial grants
    pool.free([a[1]])
    pool.free([a[0]])
    # LRU free list: oldest-freed pops first
    assert pool.alloc(2) == [3, a[1]]
    with pytest.raises(ValueError):
        pool.free([99])
    assert pool.page_bytes > 0
    assert pool.pool_bytes == pool.page_bytes * 4


def test_gather_donate_roundtrip(eng):
    """Donated prompt pages gathered back must be bit-identical to the
    prefill cache they came from, with the write head at the match."""
    pt = 4
    pc = _pc(eng, page_tokens=pt, n_pages=8)
    prompt = np.random.default_rng(7).integers(
        0, 512, size=(16,)).astype(np.int32)
    cache = eng.init_cache(1)
    positions = jnp.arange(16)[None, :]
    _, cache = eng._compiled_prefill(eng.params, cache,
                                     jnp.asarray(prompt)[None], positions)
    # lift to the slot-stacked layout donation reads from (slot axis 0)
    slot_cache = jax.tree_util.tree_map(lambda l: l[None], cache)
    assert pc.donate(slot_cache, 0, prompt) == 4
    # one extra token so match() may cover all 16 prompt tokens
    m, pids, _ = pc.match(np.concatenate([prompt, [0]]).astype(np.int32))
    assert m == 16 and len(pids) == 4
    gathered = pc.gather(eng.init_cache(1), pids)
    src = jax.tree_util.tree_flatten_with_path(cache)[0]
    got = jax.tree_util.tree_flatten_with_path(gathered)[0]
    for (path, a), (_, b) in zip(src, got):
        kind = model_common.cache_leaf_kind(path)
        if kind == "index":
            np.testing.assert_array_equal(np.asarray(b), 16)
            continue
        tokdim = pc.pool._meta[jax.tree_util.keystr(path)].tokdim
        sl = tuple(slice(None) if d != tokdim else slice(0, 16)
                   for d in range(a.ndim))
        np.testing.assert_array_equal(np.asarray(a[sl]), np.asarray(b[sl]))


def test_radix_match_is_block_granular_and_capped(eng):
    pc = _pc(eng, page_tokens=4, n_pages=8)
    prompt = np.arange(12, dtype=np.int32)
    cache = eng.init_cache(1)
    _, cache = eng._compiled_prefill(eng.params, cache,
                                     jnp.asarray(prompt)[None],
                                     jnp.arange(12)[None, :])
    pc.donate(jax.tree_util.tree_map(lambda l: l[None], cache), 0, prompt)
    # exact-prefix block matches only
    m, pids, _ = pc.match(np.arange(12, dtype=np.int32))
    assert m == 8          # capped one short of the prompt: 2 of 3 pages
    m, _, _ = pc.match(np.arange(13, dtype=np.int32))
    assert m == 12         # one spare token: all 3 pages
    m, _, _ = pc.match(np.asarray([0, 1, 2, 9, 9, 9, 9, 9], np.int32))
    assert m == 0          # diverges inside the first block
    divergent = np.concatenate(
        [np.arange(4), [99], np.arange(5, 12)]).astype(np.int32)
    m, _, _ = pc.match(divergent)
    assert m == 4          # first block reused, second diverges
    # re-donating a fully cached prompt adds nothing
    assert pc.donate(jax.tree_util.tree_map(lambda l: l[None], cache),
                     0, prompt) == 0


def test_pin_blocks_eviction(eng):
    pc = _pc(eng, page_tokens=4, n_pages=2)
    prompt = np.arange(8, dtype=np.int32)
    cache = eng.init_cache(1)
    _, cache = eng._compiled_prefill(eng.params, cache,
                                     jnp.asarray(prompt)[None],
                                     jnp.arange(8)[None, :])
    slot = jax.tree_util.tree_map(lambda l: l[None], cache)
    assert pc.donate(slot, 0, prompt) == 2
    _, _, nodes = pc.match(np.arange(9, dtype=np.int32))
    pc.pin(nodes)
    assert pc._alloc(1) is None           # everything pinned: no victim
    pc.unpin(nodes)
    assert pc._alloc(1) is not None       # LRU leaf evicts now
    assert pc._m_evict.total() >= 1


def test_donate_never_orphans_attachment_node(eng):
    """Extending a cached prefix under a budget too tight to evict
    around must NOT evict the attachment node itself: the donation is
    skipped and the existing chain stays reachable (regression — the
    eviction sweep used to pick the walked node, hanging new pages off
    a detached subtree)."""
    pc = _pc(eng, page_tokens=4, n_pages=2)

    def slot_for(prompt):
        cache = eng.init_cache(1)
        _, cache = eng._compiled_prefill(
            eng.params, cache, jnp.asarray(prompt)[None],
            jnp.arange(len(prompt))[None, :])
        return jax.tree_util.tree_map(lambda l: l[None], cache)

    a = np.arange(8, dtype=np.int32)
    assert pc.donate(slot_for(a), 0, a) == 2          # chain n1 -> n2
    # shares only block 0 with `a`; needs 2 pages with 1 evictable
    b = np.concatenate([np.arange(4), np.arange(100, 108)]).astype(np.int32)
    assert pc.donate(slot_for(b), 0, b) == 0          # skipped, not corrupted
    m, _, _ = pc.match(np.arange(9, dtype=np.int32))
    assert m == 4, "attachment node evicted out from under the donor"
    assert pc.pool.pages_in_use == len(pc._nodes) == 1


def test_prefix_cache_e2e_exact_with_hits(eng):
    """Shared-system-prompt workload: cache-on tokens must equal the
    cache-off run exactly, with hits on the repeat pass."""
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, 512, size=(12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 512, size=(s,)).astype(np.int32)])
               for s in (2, 5, 3, 6)]
    base = ContinuousBatcher(eng, n_slots=2).run(prompts, max_new_tokens=6)
    pc = _pc(eng, page_tokens=4, n_pages=16)
    hits0 = pc._m_hit.total()             # the registry is process-global
    on = ContinuousBatcher(eng, n_slots=2, prefix_cache=pc)
    first = on.run(prompts, max_new_tokens=6)
    hits_after_first = pc._m_hit.total()
    again = on.run(prompts, max_new_tokens=6)
    for want, a, b in zip(base, first, again):
        np.testing.assert_array_equal(want, a)
        np.testing.assert_array_equal(want, b)
    # every repeat matched the whole 12-token (3-page) shared prefix
    assert pc._m_hit.total() - hits_after_first >= 4 * 12
    assert hits_after_first >= hits0
    status = pc._telemetry_status()
    assert status["pages_in_use"] > 0 and status["nodes"] > 0


def test_eviction_tight_budget_never_corrupts_active_slot(eng):
    """Two-page budget + distinct prompts = constant eviction churn
    while other slots are mid-decode; outputs must stay exact and the
    pool must never exceed its budget."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 512, size=(int(s),)).astype(np.int32)
               for s in rng.integers(9, 20, size=8)]
    base = ContinuousBatcher(eng, n_slots=3).run(prompts, max_new_tokens=7)
    pc = _pc(eng, page_tokens=4, n_pages=2)
    evict0 = pc._m_evict.total()          # the registry is process-global
    on = ContinuousBatcher(eng, n_slots=3, prefix_cache=pc)
    for outs in (on.run(prompts, max_new_tokens=7),
                 on.run(prompts, max_new_tokens=7)):
        for want, got in zip(base, outs):
            np.testing.assert_array_equal(want, got)
    assert pc._m_evict.total() > evict0
    assert pc.pool.pages_in_use <= 2


def test_scan_stacked_cache_layout():
    """scan_layers stacks cache leaves (batch axis at 1): the pool's
    derived layout must still reuse exactly."""
    mesh_mod.set_mesh(None)
    engine = _make_engine(scan_layers=True)
    rng = np.random.default_rng(17)
    prefix = rng.integers(0, 512, size=(8,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 512, size=(s,)).astype(np.int32)])
               for s in (3, 5)]
    base = ContinuousBatcher(engine, n_slots=2).run(prompts,
                                                    max_new_tokens=5)
    pc = _pc(engine, page_tokens=4, n_pages=8)
    on = ContinuousBatcher(engine, n_slots=2, prefix_cache=pc)
    on.run(prompts, max_new_tokens=5)
    outs = on.run(prompts, max_new_tokens=5)
    for want, got in zip(base, outs):
        np.testing.assert_array_equal(want, got)
    assert pc._m_hit.total() >= 2 * 8
    mesh_mod.set_mesh(None)


def test_resolve_config_and_env(eng, monkeypatch):
    # default: off, and the batcher carries no cache
    monkeypatch.delenv(kvreuse.PREFIX_CACHE_ENV, raising=False)
    assert kvreuse.resolve_prefix_cache(eng) is None
    assert ContinuousBatcher(eng, n_slots=1).prefix_cache is None
    # env force-on / force-off beat the per-call setting
    monkeypatch.setenv(kvreuse.PREFIX_CACHE_ENV, "1")
    assert isinstance(kvreuse.resolve_prefix_cache(eng),
                      kvreuse.RadixPrefixCache)
    # env=1 enables defaults but an EXPLICIT False stays off
    assert kvreuse.resolve_prefix_cache(eng, False) is None
    monkeypatch.setenv(kvreuse.PREFIX_CACHE_ENV, "0")
    assert kvreuse.resolve_prefix_cache(
        eng, {"page_tokens": 4, "n_pages": 4}) is None
    monkeypatch.delenv(kvreuse.PREFIX_CACHE_ENV, raising=False)
    # False is an explicit off; a ready instance passes through
    assert kvreuse.resolve_prefix_cache(eng, False) is None
    pc = _pc(eng, page_tokens=4, n_pages=4)
    assert kvreuse.resolve_prefix_cache(eng, pc) is pc
    # budget sizing: n_pages derived from budget_bytes // page_bytes
    sized = kvreuse.resolve_prefix_cache(
        eng, {"page_tokens": 4, "budget_bytes": pc.pool.page_bytes * 3})
    assert sized.pool.n_pages == 3
    # an EMPTY dict is still an explicit enable (defaults)
    assert isinstance(kvreuse.resolve_prefix_cache(eng, {}),
                      kvreuse.RadixPrefixCache)


def test_init_inference_prefix_cache_config():
    """init_inference(prefix_cache=...) flows through to the batcher."""
    mesh_mod.set_mesh(None)
    engine = tiny_gpt2_engine(prefix_cache={"page_tokens": 4, "n_pages": 4})
    b = ContinuousBatcher(engine, n_slots=1)
    assert isinstance(b.prefix_cache, kvreuse.RadixPrefixCache)
    assert b.prefix_cache.pool.n_pages == 4
    mesh_mod.set_mesh(None)


def test_page_tokens_exceeding_cache_rejected(eng):
    with pytest.raises(ValueError):
        kvreuse.PagedKVPool(eng, n_pages=2, page_tokens=10_000)
    # resolve degrades to disabled instead of raising
    assert kvreuse.resolve_prefix_cache(
        eng, {"page_tokens": 10_000}) is None
