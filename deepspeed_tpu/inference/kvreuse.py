"""Shared-prefix KV reuse: a paged KV pool + a radix prefix cache.

Production traffic is dominated by requests sharing a long system-prompt
/ few-shot prefix, yet every admission into :class:`~.serving.
ContinuousBatcher` prefills its full prompt from scratch.  This module
adds the vLLM/SGLang-style reuse layer, TPU-native:

- :class:`PagedKVPool` — a fixed device-resident arena of KV *pages*
  (``page_tokens`` tokens of every layer's K/V), laid out by deriving
  each page buffer from the model's own cache tree
  (``models/common.append_kv_cache`` — the one layout both the XLA and
  fused decode paths share, so the pool cannot drift from either).
  Alloc/free is a host-side free list; page data moves through two
  jitted ops compiled once per pow2 *bucket width* of the page count:
  ``gather_pages`` (pool → a fresh admission cache, write head set to
  the match length) and ``donate_pages`` (a retiring slot's prompt
  region → pool).

- :class:`RadixPrefixCache` — a host-side radix tree over token-ID
  blocks whose nodes own page refs.  Admission looks up the longest
  cached prefix (exact block match only — reuse is bit-exact, never
  approximate), gathers the matched pages into the request's cache and
  prefills only the unmatched suffix; a retiring request donates its
  prompt-prefix pages back to the tree.  Eviction walks refcount-0
  leaves in LRU order under the page budget; an active admission pins
  its matched nodes, so eviction can never free a page mid-gather (and
  reuse is copy-based — an evicted page never aliases a live slot's
  cache).

Off by default: a batcher without a prefix cache takes byte-for-byte
the pre-existing admission path.  Enable per call
(``ContinuousBatcher(..., prefix_cache=...)``), per engine
(``init_inference(prefix_cache=True | {...})``) or process-wide with
``DSTPU_PREFIX_CACHE=1`` (``0`` force-disables over any config; ``1``
enables defaults but never overrides an explicit ``False`` — see
:func:`resolve_prefix_cache`).
"""
from __future__ import annotations

import dataclasses
import heapq
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import common as model_common
from ..telemetry import (memory as telemetry_memory, recompile,
                         registry as telemetry_registry)
from ..utils.logging import logger

__all__ = ["PagedKVPool", "RadixPrefixCache", "PagedServingState",
           "resolve_prefix_cache", "resolve_paged_decode",
           "PREFIX_CACHE_ENV", "PAGED_DECODE_ENV"]

PREFIX_CACHE_ENV = "DSTPU_PREFIX_CACHE"
# page-resident serving (paged decode attention): default ON whenever a
# prefix cache is resolved; =0 is the operator kill switch back to the
# gather-then-contiguous admission path
PAGED_DECODE_ENV = "DSTPU_PAGED_DECODE"

_DEFAULT_PAGE_TOKENS = 16
_DEFAULT_BUDGET_BYTES = 64 << 20
# host bookkeeping (one tree node + free-list slot per page) stays
# trivial up to here; a larger budget should raise page_tokens instead
_MAX_PAGES = 16384


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class _LeafMeta:
    """Static per-KV-leaf geometry of the PER-ROW cache tree."""

    bdim: int      # batch axis (scan-stacked layers put it at 1)
    tokdim: int    # token axis — always bdim + 1 in append_kv_cache's layout
    page_shape: tuple   # ONE page's slice shape (batch axis = 1)
    dtype: object


def _derive_meta(engine, page_tokens: int) -> Dict[str, _LeafMeta]:
    """Per-KV-leaf page geometry from ``engine``'s ABSTRACT cache tree
    (no device allocation — the sizing math in resolve_prefix_cache and
    the pool construction share this).  The batch axis is found by
    diffing 1-row vs 2-row shapes (the ContinuousBatcher technique);
    token axis = batch axis + 1 (append_kv_cache's (B, L, H, D)).
    Raises ValueError for cache layouts outside that contract."""
    c1 = jax.eval_shape(lambda: engine.init_cache(1))
    c2 = jax.eval_shape(lambda: engine.init_cache(2))
    meta: Dict[str, _LeafMeta] = {}
    for (path, l1), (_, l2) in zip(
            jax.tree_util.tree_flatten_with_path(c1)[0],
            jax.tree_util.tree_flatten_with_path(c2)[0]):
        kind = model_common.cache_leaf_kind(path)
        if kind == "index":
            continue
        if kind != "kv":
            raise ValueError(
                f"cache leaf {jax.tree_util.keystr(path)} is outside "
                f"the append_kv_cache layout; prefix caching is not "
                f"supported for this model")
        bdim = next(d for d in range(len(l1.shape))
                    if l1.shape[d] != l2.shape[d])
        tokdim = bdim + 1
        if l1.shape[tokdim] < page_tokens:
            raise ValueError(
                f"page_tokens={page_tokens} exceeds the cache length "
                f"{l1.shape[tokdim]} of {jax.tree_util.keystr(path)}")
        shape = list(l1.shape)
        shape[bdim] = 1
        shape[tokdim] = page_tokens
        meta[jax.tree_util.keystr(path)] = _LeafMeta(
            bdim, tokdim, tuple(shape), l1.dtype)
    if not meta:
        raise ValueError("model has no K/V cache leaves to page")
    return meta


def _page_bytes(meta: Dict[str, _LeafMeta]) -> int:
    return telemetry_memory.tree_bytes(
        {k: jax.ShapeDtypeStruct(m.page_shape, m.dtype)
         for k, m in meta.items()})


class PagedKVPool:
    """Fixed arena of ``n_pages`` KV pages derived from ``engine``'s
    cache tree; host free list + jitted page movement.

    Pages hold every layer's K/V for ``page_tokens`` consecutive
    positions: one page buffer per ``cached_key``/``cached_value`` leaf,
    shaped like the per-row cache leaf with the batch axis widened to
    ``n_pages`` and the token axis narrowed to ``page_tokens``.
    """

    def __init__(self, engine, n_pages: int, page_tokens: int,
                 meta: Optional[Dict[str, _LeafMeta]] = None):
        if n_pages < 1 or page_tokens < 1:
            raise ValueError(
                f"need n_pages >= 1 and page_tokens >= 1, got "
                f"{n_pages}/{page_tokens}")
        self.engine = engine
        self.n_pages = int(n_pages)
        self.page_tokens = int(page_tokens)
        # resolve_prefix_cache passes its already-derived meta so the
        # abstract cache traces run once, not twice
        self._meta = meta if meta is not None \
            else _derive_meta(engine, page_tokens)
        # one jitted builder: a per-leaf eager zeros would dispatch once
        # per layer (the engine._zero_cache_fn lesson)
        def arena_shape(m):
            return (m.page_shape[:m.bdim] + (self.n_pages,)
                    + m.page_shape[m.bdim + 1:])

        metas = sorted(self._meta.items())
        # dstpu-lint: disable-next-line=DSTPU005 -- one-shot arena build at pool construction; the executable is intentionally single-use
        self.pages: Dict[str, jax.Array] = jax.jit(lambda: {
            k: jnp.zeros(arena_shape(m), m.dtype) for k, m in metas})()
        self.page_bytes = _page_bytes(self._meta)
        self.pool_bytes = self.page_bytes * self.n_pages
        # LRU free list: free() appends, alloc() pops the oldest-freed
        self._free: List[int] = list(range(self.n_pages))
        self._op_memo: Dict[tuple, object] = {}
        # the copy-tax witness: page-resident serving must keep this at
        # ZERO on the steady-state path (asserted by the paged e2e test
        # and reported by the bench paged-vs-gather block)
        self._m_gather = telemetry_registry.counter(
            "serving_gather_pages_total",
            "admission-time page materializations (arena pages copied "
            "into a contiguous admission cache; 0 under paged decode)")

    # -- host-side page accounting -------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` page ids off the free list (None if short — the
        radix cache evicts and retries; the pool itself never blocks)."""
        if n > len(self._free):
            return None
        got, self._free = self._free[:n], self._free[n:]
        return got

    def free(self, pids) -> None:
        for pid in pids:
            if not 0 <= pid < self.n_pages:
                raise ValueError(f"bad page id {pid}")
        self._free.extend(pids)

    # -- jitted page movement ------------------------------------------
    def _pad(self, pids, offs) -> tuple:
        """Pad (page ids, token offsets) to the pow2 bucket width by
        REPEATING the last real entry: the duplicate write replays the
        same page at the same offset (idempotent), so padding can never
        touch tokens outside the real range — sequential pad offsets
        would clamp at the cache edge and corrupt real pages whenever
        ``cache_len`` is not a bucket multiple."""
        w = _pow2(len(pids))
        pid_arr = np.full((w,), pids[-1], np.int32)
        off_arr = np.full((w,), offs[-1], np.int32)
        pid_arr[:len(pids)] = pids
        off_arr[:len(offs)] = offs
        return jnp.asarray(pid_arr), jnp.asarray(off_arr)

    def _gather_fn(self, w: int):
        """pool pages → a fresh admission cache: page ``i`` lands at
        token offset ``offs[i]``; every ``cache_index`` leaf is set to
        the match length so the suffix prefill appends right after the
        reused prefix.  One executable per bucket width (jit
        re-specializes per batch width like the other admission ops)."""
        key = ("gather", w)
        if key in self._op_memo:
            return self._op_memo[key]
        meta = self._meta
        pt = self.page_tokens

        def run(pages, cache, pids, offs, n_tokens):
            def leaf_fn(path, leaf):
                if model_common.cache_leaf_kind(path) == "index":
                    return leaf          # rewound below via set_cache_index
                m = meta[jax.tree_util.keystr(path)]
                tgt = leaf.shape[:m.tokdim] + (pt,) + leaf.shape[m.tokdim + 1:]
                for i in range(w):
                    page = jax.lax.dynamic_index_in_dim(
                        pages[jax.tree_util.keystr(path)], pids[i],
                        axis=m.bdim, keepdims=True)
                    # dstpu-lint: disable-next-line=DSTPU003 -- paged-pool page movement sits BELOW the append abstraction; offsets are page-aligned by construction and the layout is derived from cache_leaf_kind
                    leaf = jax.lax.dynamic_update_slice_in_dim(
                        leaf, jnp.broadcast_to(page, tgt).astype(leaf.dtype),
                        offs[i], axis=m.tokdim)
                return leaf

            cache = jax.tree_util.tree_map_with_path(leaf_fn, cache)
            # write head → match length through THE rewind discipline
            return model_common.set_cache_index(cache, n_tokens)

        fn = recompile.watch(jax.jit(run, donate_argnums=(1,)),
                             name=f"serving.gather_pages[{w}]", warn=False)
        self._op_memo[key] = fn
        return fn

    def gather(self, cache, pids, n_tokens: int):
        """Write pages ``pids`` into rows ``[0, B)`` of ``cache`` at
        ``[0, len(pids)*page_tokens)`` and set the write head to
        ``n_tokens``; returns the updated cache (input donated)."""
        pt = self.page_tokens
        offs = [i * pt for i in range(len(pids))]
        pid_arr, off_arr = self._pad(list(pids), offs)
        self._m_gather.inc()
        return self._gather_fn(int(pid_arr.shape[0]))(
            self.pages, cache, pid_arr, off_arr, n_tokens)

    def _donate_fn(self, w: int):
        """One slot row's prompt-prefix K/V → pool pages (the reverse of
        gather; pool buffers donated so the arena updates in place)."""
        key = ("donate", w)
        if key in self._op_memo:
            return self._op_memo[key]
        meta = self._meta
        pt = self.page_tokens

        def run(pages, slot_cache, row, pids, offs):
            new = dict(pages)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    slot_cache)[0]:
                if model_common.cache_leaf_kind(path) != "kv":
                    continue
                k = jax.tree_util.keystr(path)
                m = meta[k]
                # slot-stacked leaves carry a leading slot axis on top of
                # the per-row geometry: extract the row first
                src = jax.lax.dynamic_index_in_dim(leaf, row, axis=0,
                                                   keepdims=False)
                for i in range(w):
                    chunk = jax.lax.dynamic_slice_in_dim(
                        src, offs[i], pt, axis=m.tokdim)
                    # dstpu-lint: disable-next-line=DSTPU003 -- writes into the pool ARENA (page axis), not a model cache leaf; the arena layout is derived from the contract's page geometry
                    new[k] = jax.lax.dynamic_update_slice_in_dim(
                        new[k], chunk.astype(m.dtype), pids[i], axis=m.bdim)
            return new

        fn = recompile.watch(jax.jit(run, donate_argnums=(0,)),
                             name=f"serving.donate_pages[{w}]", warn=False)
        self._op_memo[key] = fn
        return fn

    def donate_from_slot(self, slot_cache, row: int, start_tok: int,
                         pids) -> None:
        """Copy ``[start_tok, start_tok + len(pids)*page_tokens)`` of
        slot ``row``'s K/V into pages ``pids`` (in place)."""
        pt = self.page_tokens
        offs = [start_tok + i * pt for i in range(len(pids))]
        pid_arr, off_arr = self._pad(list(pids), offs)
        self.pages = self._donate_fn(int(pid_arr.shape[0]))(
            self.pages, slot_cache, row, pid_arr, off_arr)


class _Node:
    __slots__ = ("key", "page", "parent", "children", "refs", "last_used")

    def __init__(self, key, page, parent):
        self.key = key          # the page's token block (tuple of ints)
        self.page = page        # pool page id
        self.parent = parent
        self.children: dict = {}
        self.refs = 0           # pins from in-flight admissions
        self.last_used = 0


class RadixPrefixCache:
    """Host-side radix tree over ``page_tokens``-sized token blocks;
    nodes own pool pages.  Single-threaded by construction (driven from
    the batcher's admission/retire transitions)."""

    def __init__(self, pool: PagedKVPool):
        self.pool = pool
        self.page_tokens = pool.page_tokens
        self._root = _Node(None, None, None)
        self._nodes: set = set()
        self._clock = 0
        # lazy LRU heap of (last_used, seq, node) eviction candidates:
        # entries are pushed whenever a node BECOMES evictable (created
        # as a leaf, parent turned leaf by an eviction, refs dropping to
        # 0) and validated at pop time, so eviction is O(log n) instead
        # of a full-tree scan per freed page on the serving thread
        self._lru_heap: List[tuple] = []
        self._heap_seq = 0
        self._m_hit = telemetry_registry.counter(
            "prefix_cache_hit_tokens_total",
            "prompt tokens served from cached prefix pages")
        self._m_miss = telemetry_registry.counter(
            "prefix_cache_miss_tokens_total",
            "prompt tokens prefilled (no cached prefix covered them)")
        self._m_evict = telemetry_registry.counter(
            "prefix_cache_evictions_total", "pages evicted under budget")
        self._m_donated = telemetry_registry.counter(
            "prefix_cache_donated_pages_total",
            "pages donated by retiring requests")
        self._m_in_use = telemetry_registry.gauge(
            "prefix_cache_pages_in_use", "pool pages owned by tree nodes")
        telemetry_registry.gauge(
            "prefix_cache_pages_total", "pool page capacity"
        ).set(float(pool.n_pages))
        telemetry_registry.gauge(
            "prefix_cache_pool_bytes",
            "device bytes reserved by the paged KV arena"
        ).set(float(pool.pool_bytes))
        from ..telemetry import exporter as telemetry_exporter

        telemetry_exporter.register_status_owner(
            "prefix_cache", self, "_telemetry_status")

    # ------------------------------------------------------------------
    def _blocks(self, prompt, n: int) -> List[tuple]:
        pt = self.page_tokens
        return [tuple(int(t) for t in prompt[i * pt:(i + 1) * pt])
                for i in range(n)]

    def match(self, prompt) -> Tuple[int, tuple, tuple]:
        """Longest cached prefix of ``prompt`` at page granularity:
        ``(matched_tokens, page_ids, nodes)``.  Capped one token short of
        the prompt — the suffix prefill must still produce the real last
        token's logits to sample from.  Blocks are built lazily: this
        runs per queued request per admission pass, and a cold tree must
        cost O(one block), not O(prompt)."""
        pt = self.page_tokens
        limit = (len(prompt) - 1) // pt
        self._clock += 1
        node, pages, nodes = self._root, [], []
        for i in range(limit):
            key = tuple(int(t) for t in prompt[i * pt:(i + 1) * pt])
            child = node.children.get(key)
            if child is None:
                break
            node = child
            node.last_used = self._clock
            pages.append(node.page)
            nodes.append(node)
        if nodes:
            # the touch staled any heap entry for the deepest node (the
            # only possible leaf on the chain); re-offer it
            self._push_candidate(nodes[-1])
        return len(pages) * pt, tuple(pages), tuple(nodes)

    def pin(self, nodes) -> None:
        """Hold ``nodes``' pages against eviction while an admission is
        between match and gather (its pages must stay immutable until
        the copy into the request's cache is dispatched)."""
        for nd in nodes:
            nd.refs += 1

    def unpin(self, nodes) -> None:
        for nd in nodes:
            nd.refs -= 1
            if nd.refs == 0:
                self._push_candidate(nd)   # may have become evictable

    def gather(self, cache, pids):
        """Pool pages → the admission cache (write head set to the match
        length); returns the updated cache."""
        return self.pool.gather(cache, pids,
                                len(pids) * self.page_tokens)

    def note_tokens(self, hit: int, miss: int) -> None:
        if hit:
            self._m_hit.inc(hit)
        if miss:
            self._m_miss.inc(miss)

    # ------------------------------------------------------------------
    def _push_candidate(self, node) -> None:
        """Offer ``node`` to the eviction heap if it is evictable NOW
        (a non-root refcount-0 leaf); entries are validated again at pop
        time, so over-offering is harmless and under-offering is caught
        by the scan fallback in :meth:`_evict_one`."""
        if node is not self._root and node in self._nodes \
                and not node.children and node.refs == 0:
            self._heap_seq += 1
            heapq.heappush(self._lru_heap,
                           (node.last_used, self._heap_seq, node))

    def _evict_one(self) -> bool:
        """Free the LRU refcount-0 leaf's page.  Interior nodes become
        leaves as their children go, so repeated calls peel a cold
        branch back to the root.  O(log n) via the lazy heap; a linear
        scan backstops it so a missed push can only cost time, never
        refuse an eviction that is actually possible."""
        victim = None
        while self._lru_heap:
            lu, _, nd = heapq.heappop(self._lru_heap)
            if nd in self._nodes and nd.last_used == lu \
                    and not nd.children and nd.refs == 0:
                victim = nd
                break
        if victim is None:
            for nd in self._nodes:
                if nd.children or nd.refs > 0:
                    continue
                if victim is None or nd.last_used < victim.last_used:
                    victim = nd
        if victim is None:
            return False
        victim.parent.children.pop(victim.key, None)
        self._nodes.discard(victim)
        self.pool.free([victim.page])
        self._m_evict.inc()
        self._m_in_use.set(float(self.pool.pages_in_use))
        if victim.parent is not self._root:
            self._push_candidate(victim.parent)   # may have turned leaf
        return True

    def _alloc(self, n: int) -> Optional[List[int]]:
        if n > self.pool.n_pages:
            return None   # can never fit: don't flush the tree for nothing
        while self.pool.free_pages < n:
            if not self._evict_one():
                return None   # everything left is pinned or interior
        return self.pool.alloc(n)

    def donate(self, slot_cache, row: int, prompt) -> int:
        """A retiring request donates its prompt-prefix pages: copy the
        blocks not already in the tree out of slot ``row``'s cache and
        chain them under the deepest existing match.  Returns pages
        added (0 when fully cached already, the prompt is shorter than a
        page, or the budget cannot yield enough pages)."""
        pt = self.page_tokens
        n_target = len(prompt) // pt
        if n_target == 0:
            return 0
        keys = self._blocks(prompt, n_target)
        self._clock += 1
        node, depth, walked = self._root, 0, []
        while depth < n_target and keys[depth] in node.children:
            node = node.children[keys[depth]]
            node.last_used = self._clock
            walked.append(node)
            depth += 1
        if depth == n_target:
            if walked:
                self._push_candidate(walked[-1])   # touch staled its entry
            return 0
        # pin the walked chain across _alloc: under a tight budget the
        # eviction sweep could otherwise pick the attachment node itself
        # (a refcount-0 leaf) and the new chain would hang off a detached
        # subtree — donated pages unreachable, pages_in_use inflated
        self.pin(walked)
        try:
            pids = self._alloc(n_target - depth)
        finally:
            self.unpin(walked)
        if pids is None:
            return 0
        self.pool.donate_from_slot(slot_cache, row, depth * pt, pids)
        for key, pid in zip(keys[depth:], pids):
            child = _Node(key, pid, node)
            child.last_used = self._clock
            node.children[key] = child
            self._nodes.add(child)
            node = child
        self._push_candidate(node)   # the new chain's tip is a leaf
        self._m_donated.inc(len(pids))
        self._m_in_use.set(float(self.pool.pages_in_use))
        return len(pids)

    def absorb(self, prompt, own_pages, first_own: int) -> set:
        """ZERO-COPY donation — the page-resident retirement path: a
        retiring slot's full-prompt pages attach to the tree BY
        REFERENCE (ownership transfers; nothing moves on device).  The
        slot's blocks ``[0, first_own)`` are the tree's own matched
        chain (still pinned by the caller at this point), so attachment
        starts at the deepest existing match and block ``d`` takes
        ``own_pages[d - first_own]``.  Returns the page ids the tree
        took; the caller frees the rest.  Correctness rests on the paged
        write discipline: a slot's prompt-prefix pages are written once
        by its suffix prefill and never touched again (decode appends at
        positions >= prompt_len, overshoot resolves to trash entries),
        so the absorbed pages hold exactly the K/V a fresh prefill of
        those blocks would produce."""
        pt = self.page_tokens
        n_target = len(prompt) // pt
        if n_target <= first_own:
            return set()     # prompt region fully covered by hit pages
        keys = self._blocks(prompt, n_target)
        self._clock += 1
        node, depth, walked = self._root, 0, []
        while depth < n_target and keys[depth] in node.children:
            node = node.children[keys[depth]]
            node.last_used = self._clock
            walked.append(node)
            depth += 1
        if depth == n_target or depth < first_own:
            # fully cached already (a sibling retired the same prefix
            # first), or the walk ended inside the pinned hit chain
            # (impossible while pinned — defensive: attaching here would
            # alias tree-owned pages)
            if walked:
                self._push_candidate(walked[-1])
            return set()
        absorbed = set()
        for d in range(depth, n_target):
            pid = own_pages[d - first_own]
            child = _Node(keys[d], pid, node)
            child.last_used = self._clock
            node.children[keys[d]] = child
            self._nodes.add(child)
            node = child
            absorbed.add(pid)
        self._push_candidate(node)   # the new chain's tip is a leaf
        self._m_donated.inc(len(absorbed))
        self._m_in_use.set(float(self.pool.pages_in_use))
        return absorbed

    # ------------------------------------------------------------------
    def _telemetry_status(self) -> dict:
        return {
            "page_tokens": self.page_tokens,
            "n_pages": self.pool.n_pages,
            "pages_in_use": self.pool.pages_in_use,
            "nodes": len(self._nodes),
            "pool_bytes": self.pool.pool_bytes,
            "page_bytes": self.pool.page_bytes,
            "hit_tokens": self._m_hit.total(),
            "miss_tokens": self._m_miss.total(),
            "evictions": self._m_evict.total(),
        }


def resolve_prefix_cache(engine, override=None) -> Optional[RadixPrefixCache]:
    """Resolve the batcher's prefix-cache setting.

    Precedence: ``DSTPU_PREFIX_CACHE=0`` is the operator kill switch —
    it disables over ANY config.  An explicit ``False`` (the
    ``ContinuousBatcher(prefix_cache=...)`` argument or the engine
    config) is a programmatic opt-out and stays off even under
    ``DSTPU_PREFIX_CACHE=1``; the env ``1`` only enables where nothing
    explicitly disabled.  Otherwise the argument wins over the engine
    config.  Accepted values: ``None`` (defer), ``False`` (off),
    ``True`` (on, default sizing), a dict with ``page_tokens`` /
    ``n_pages`` / ``budget_bytes``, or a ready
    :class:`RadixPrefixCache`.  Returns None when disabled or when the
    model's cache layout is unsupported (warned, never fatal — serving
    falls back to full prefills)."""
    env = os.environ.get(PREFIX_CACHE_ENV, "").strip().lower()
    if env in ("0", "false", "off"):
        return None   # kill switch FIRST: a ready instance must not bypass it
    if isinstance(override, RadixPrefixCache):
        return override
    cfg = override if override is not None else \
        getattr(engine.config, "prefix_cache", None)
    if cfg is False:
        return None
    # ANY dict is an explicit enable — {} means "defaults", and bool({})
    # being falsy must not silently turn the request into a no-op
    if not (isinstance(cfg, dict) or bool(cfg) or env in ("1", "true", "on")):
        return None
    opts = dict(cfg) if isinstance(cfg, dict) else {}
    unknown = set(opts) - {"page_tokens", "n_pages", "budget_bytes"}
    if unknown:
        logger.warning(f"prefix_cache: ignoring unknown keys "
                       f"{sorted(unknown)}")
    page_tokens = int(opts.get("page_tokens", _DEFAULT_PAGE_TOKENS))
    try:
        meta = _derive_meta(engine, page_tokens)
    except ValueError as e:
        logger.warning(f"prefix cache disabled: {e}")
        return None
    n_pages = opts.get("n_pages")
    if n_pages is None:
        budget = int(opts.get("budget_bytes", _DEFAULT_BUDGET_BYTES))
        n_pages = max(1, min(_MAX_PAGES,
                             budget // max(1, _page_bytes(meta))))
    pool = PagedKVPool(engine, int(n_pages), page_tokens, meta=meta)
    return RadixPrefixCache(pool)


# ---------------------------------------------------------------------------
# Page-resident serving (paged decode attention)
# ---------------------------------------------------------------------------
#
# With the paged attention kernel (ops/pallas/paged_attention.py) the
# batcher no longer materializes a contiguous per-slot cache at all: the
# slot's K/V lives in the POOL ARENA for its whole life.  Admission
# becomes page-ref bookkeeping (hit pages are referenced, not copied; the
# suffix prefill writes straight into freshly allocated pages), decode
# attention reads the arena through a per-slot page table, and retirement
# donates the prompt's pages to the radix tree BY REFERENCE.  The two
# O(history) device copies of the gather path — gather_pages at admission,
# donate_pages at retirement — both disappear.


@dataclasses.dataclass
class _SlotPages:
    """Page ownership of one page-resident request (parked or slotted)."""

    own: list            # pages allocated for the suffix + generation span
    nodes: tuple         # pinned radix nodes backing the hit prefix
    m0: int              # matched prefix tokens (page-aligned)
    prompt_len: int
    table_row: np.ndarray    # (T,) int32, trash-padded past the span


class PagedServingState:
    """Host-side page bookkeeping + paged-cache-tree plumbing for a
    :class:`~.serving.ContinuousBatcher` running page-resident slots.

    Owns: the reserved trash page (overshoot writes resolve there — a
    retired or bucket-padded row's head past its allocation must never
    touch another slot's pages), the live ``(n_slots, T)`` page table and
    per-slot lengths the decode windows are built from, and the per-slot
    :class:`_SlotPages` metadata.  The POOL becomes this batcher's
    property in paged mode: every jitted window donates the arena buffers
    and :meth:`adopt` rebinds them, so a second batcher sharing the pool
    would read freed buffers.
    """

    def __init__(self, cache: RadixPrefixCache, engine, n_slots: int):
        self.cache = cache
        self.pool = cache.pool
        self.pt = self.pool.page_tokens
        self.gen_limit = int(engine._gen_limit)
        self.T = -(-self.gen_limit // self.pt)
        self.n_slots = int(n_slots)
        need = self.n_slots * self.T + 1
        if self.pool.n_pages < need:
            raise ValueError(
                f"pool holds {self.pool.n_pages} pages but page-resident "
                f"slots need n_slots*ceil(gen_limit/page_tokens)+1 = "
                f"{self.n_slots}*{self.T}+1 = {need} worst-case; raise "
                f"n_pages/budget_bytes or lower max_tokens")
        trash = cache._alloc(1)
        if trash is None:
            raise ValueError("could not reserve the overshoot trash page")
        self.trash = int(trash[0])
        self.table = np.full((self.n_slots, self.T), self.trash, np.int32)
        self.lengths = np.zeros((self.n_slots,), np.int32)
        self.slot_meta = [None] * self.n_slots
        self._tpl_memo: Dict[int, list] = {}
        self._slot_pages_n = 0
        self._bytes_per_token = max(1, self.pool.page_bytes // self.pt)
        # per-INSTANCE tallies for /statusz: registry counters are
        # process-wide (a second batcher would report the first's
        # totals — the specdec statusz convention)
        self._admissions = 0
        self._copy_bytes_saved = 0
        self._ref_donated = 0
        self._m_admit = telemetry_registry.counter(
            "paged_attn_admissions_total",
            "requests admitted page-resident (no gather, no contiguous "
            "admission cache)")
        self._m_saved = telemetry_registry.counter(
            "paged_attn_copy_bytes_saved_total",
            "device copy bytes eliminated vs the gather path (admission "
            "gathers + retirement donates that became page-ref moves)")
        self._m_ref_donated = telemetry_registry.counter(
            "paged_attn_ref_donated_pages_total",
            "pages donated to the radix tree by reference (zero-copy)")
        self._m_slot_pages = telemetry_registry.gauge(
            "paged_attn_slot_pages",
            "arena pages owned by parked/active page-resident requests")
        from ..telemetry import exporter as telemetry_exporter

        telemetry_exporter.register_status_owner(
            "paged_decode", self, "_telemetry_status")

    # -- admission / placement / retirement ----------------------------
    def try_admit(self, prompt, max_new: int, m0: int, nodes, pids,
                  span_tokens: int) -> Optional[_SlotPages]:
        """Allocate the request's own pages covering
        ``[m0, span_tokens)`` and build its table row; None when the
        budget (after eviction) cannot supply them — the caller applies
        backpressure.  ``span_tokens`` covers both the bucket-padded
        prefill writes and the generation span, so the table never has
        to change mid-flight."""
        first_own = m0 // self.pt
        n_own = -(-span_tokens // self.pt) - first_own
        # pin BEFORE _alloc, for the request's LIFETIME: _alloc's
        # eviction sweep could otherwise recycle the matched chain this
        # very admission is about to read every tick
        self.cache.pin(nodes)
        own = self.cache._alloc(n_own) if n_own > 0 else []
        if own is None:
            self.cache.unpin(nodes)
            return None
        row = np.full((self.T,), self.trash, np.int32)
        row[:first_own] = pids
        row[first_own:first_own + len(own)] = own
        meta = _SlotPages(own=list(own), nodes=tuple(nodes), m0=int(m0),
                          prompt_len=int(len(prompt)), table_row=row)
        self._m_admit.inc()
        self._admissions += 1
        # the gather path would copy the m0 hit tokens into a fresh cache
        self._m_saved.inc(int(m0) * self._bytes_per_token)
        self._copy_bytes_saved += int(m0) * self._bytes_per_token
        self._slot_pages_n += len(own)
        self._m_slot_pages.set(float(self._slot_pages_n))
        return meta

    def place(self, i: int, meta: _SlotPages) -> None:
        self.slot_meta[i] = meta
        self.table[i, :] = meta.table_row
        self.lengths[i] = meta.prompt_len

    def retire_slot(self, i: int, prompt) -> None:
        meta = self.slot_meta[i]
        self.slot_meta[i] = None
        self.table[i, :] = self.trash
        self.lengths[i] = 0
        if meta is not None:
            self._release(meta, prompt)

    def finish_unslotted(self, meta: _SlotPages, prompt) -> None:
        """A request retired by its first token releases its pages
        without ever holding a slot (prompt pages still donate)."""
        self._release(meta, prompt)

    def abort_admit(self, meta: _SlotPages) -> None:
        """Roll back a ``try_admit`` whose prefill never completed: free
        the own pages and unpin the hit chain WITHOUT absorbing — the
        pages hold no (or partial) K/V, so attaching them to the tree
        would serve garbage to the next hit."""
        self.cache.unpin(meta.nodes)
        if meta.own:
            self.pool.free(meta.own)
            self.cache._m_in_use.set(float(self.pool.pages_in_use))
        self._slot_pages_n -= len(meta.own)
        self._m_slot_pages.set(float(self._slot_pages_n))

    def _release(self, meta: _SlotPages, prompt) -> None:
        absorbed = self.cache.absorb(prompt, meta.own,
                                     meta.m0 // self.pt)
        self.cache.unpin(meta.nodes)
        leftover = [p for p in meta.own if p not in absorbed]
        if leftover:
            self.pool.free(leftover)
            self.cache._m_in_use.set(float(self.pool.pages_in_use))
        if absorbed:
            self._m_ref_donated.inc(len(absorbed))
            self._ref_donated += len(absorbed)
            # the gather path's donate_pages would have COPIED these
            self._m_saved.inc(len(absorbed) * self.pt
                              * self._bytes_per_token)
            self._copy_bytes_saved += len(absorbed) * self.pt \
                * self._bytes_per_token
        self._slot_pages_n -= len(meta.own)
        self._m_slot_pages.set(float(self._slot_pages_n))

    def note_window(self, ticks: int) -> None:
        """Mirror the decode window's on-device head advance: EVERY row
        (free slots included — their writes resolve to trash) appends
        one token per tick."""
        self.lengths += int(ticks)

    # -- paged cache trees ---------------------------------------------
    def _template(self, B: int) -> list:
        """Per-batch-width cache-tree recipe: (dict-key path, kind,
        keystr, contiguous leaf shape) per leaf of the model's abstract
        cache — eval_shape runs once per width, not per window."""
        if B not in self._tpl_memo:
            tpl = jax.eval_shape(lambda: self.pool.engine.init_cache(B))
            entries = []
            for path, leaf in jax.tree_util.tree_flatten_with_path(tpl)[0]:
                kind = model_common.cache_leaf_kind(path)
                keys = tuple(p.key for p in path)
                entries.append((keys, kind, jax.tree_util.keystr(path),
                                tuple(leaf.shape)))
            self._tpl_memo[B] = entries
        return self._tpl_memo[B]

    def build_cache(self, lengths_np, table_np):
        """The paged cache tree a decode window / suffix prefill applies
        with: KV leaves ARE the pool arena (by reference — zero copy),
        ``cache_index`` carries per-row lengths, and a ``page_table``
        leaf rides next to it (scan-stacked models broadcast both across
        the layer axis, which ``nn.scan`` splits per layer)."""
        B, T = table_np.shape
        lengths_np = np.asarray(lengths_np, np.int32)
        table_np = np.asarray(table_np, np.int32)
        root: dict = {}

        def insert(keys, val):
            d = root
            for k in keys[:-1]:
                d = d.setdefault(k, {})
            d[keys[-1]] = val

        for keys, kind, kstr, shape in self._template(B):
            if kind == "kv":
                insert(keys, self.pool.pages[kstr])
            elif kind == "index":
                insert(keys, jnp.asarray(
                    np.broadcast_to(lengths_np, shape + (B,))))
                insert(keys[:-1] + (model_common.PAGE_TABLE_LEAF,),
                       jnp.asarray(
                           np.broadcast_to(table_np, shape + (B, T))))
            else:     # unreachable: pool construction validated the tree
                raise ValueError(f"cache leaf {kstr} outside the "
                                 f"append_kv_cache contract")
        return root

    def decode_cache(self):
        return self.build_cache(self.lengths, self.table)

    def adopt(self, cache) -> None:
        """Rebind the pool arena to the buffers a jitted call returned —
        required after every call that took the arena donated (suffix
        prefills, decode windows): the donated inputs are dead."""
        pages = self.pool.pages
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
            if model_common.cache_leaf_kind(path) == "kv":
                pages[jax.tree_util.keystr(path)] = leaf

    # ------------------------------------------------------------------
    def _telemetry_status(self) -> dict:
        return {
            "page_tokens": self.pt,
            "table_width": self.T,
            "gen_limit": self.gen_limit,
            "slot_pages": self._slot_pages_n,
            "lengths": [int(x) for x in self.lengths],
            # per-INSTANCE ints, not registry totals: counters are
            # process-wide and a second batcher must not report this
            # one's work
            "admissions": self._admissions,
            "copy_bytes_saved": self._copy_bytes_saved,
            "ref_donated_pages": self._ref_donated,
        }


def resolve_paged_decode(engine, prefix_cache, n_slots: int, specdec=None,
                         override=None) -> Optional[PagedServingState]:
    """Resolve the batcher's page-resident serving mode.

    Default ON whenever a prefix cache is resolved — the arena already
    exists, and reading it in place strictly dominates materializing
    contiguous copies.  ``DSTPU_PAGED_DECODE=0`` is the operator kill
    switch back to the gather path; an explicit ``False`` (the
    ``ContinuousBatcher(paged_decode=...)`` argument or the engine
    config) opts out programmatically.  Falls back (warned, never fatal)
    when the pool is too small for ``n_slots`` worst-case page chains,
    when speculative decoding is active (its verify step drives the
    contiguous slot-cache layout), or when the model family's decode
    path cannot consume a paged cache (the abstract-trace probe below)."""
    from ..ops.pallas.spmd import note_dispatch

    def gather(reason: str, warn: bool = True) -> None:
        if warn:
            logger.warning(f"paged decode disabled: {reason}; slots keep "
                           f"the gather path")
        note_dispatch("paged_decode", "gather", reason)

    env = os.environ.get(PAGED_DECODE_ENV, "").strip().lower()
    if env in ("0", "false", "off"):
        return gather(f"{PAGED_DECODE_ENV}={env}", warn=False)
    if prefix_cache is None:
        return gather("no prefix cache resolved (no page arena)", warn=False)
    cfg = override if override is not None else \
        getattr(engine.config, "paged_decode", None)
    if cfg is False:
        return gather("paged_decode=False (argument or engine config)",
                      warn=False)
    if specdec is not None:
        return gather("speculative decoding's verify step drives the "
                      "contiguous slot-cache layout")
    try:
        state = PagedServingState(prefix_cache, engine, n_slots)
    except ValueError as e:
        return gather(str(e))
    # contract probe: a family that consumes the appended cache leaves
    # DIRECTLY instead of through cached_decode_attention (gptneo's
    # windowed-mask math) crashes on the PagedKV carriers the paged
    # append returns — trace ONE abstract decode tick over the paged
    # tree and fall back to the (correct, pre-existing) gather path
    # rather than failing at first admission
    def _probe(p, c, t, q):
        out, vars_ = engine._decode_model.apply(
            {"params": p, "cache": c}, t, position_ids=q[:, None],
            mutable=["cache"])
        return out["logits"], vars_     # plain JAX types for eval_shape

    try:
        jax.eval_shape(
            _probe, engine.params, state.decode_cache(),
            jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_slots,), jnp.int32))
    except Exception as e:
        state.pool.free([state.trash])   # roll back the reservation
        return gather(f"this model family's decode path does not consume "
                      f"a paged cache ({type(e).__name__}: {str(e)[:160]})")
    note_dispatch("paged_decode", "paged",
                  "prefix cache resolved, pool holds every slot's chain, "
                  "decode trace consumes the paged cache")
    return state
