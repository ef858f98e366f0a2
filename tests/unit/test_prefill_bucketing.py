"""Direct coverage of ``_prefill_batch`` bucket grouping (serving.py):
pad-to-bucket batching, per-row real-last-token logits and the
``cache_index`` rewind were previously exercised only through the
late-sorted e2e module, so a regression surfaced minutes into tier-1
instead of seconds."""
import numpy as np
import pytest

import jax

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.models import common as model_common

from .simple_model import tiny_gpt2_engine


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    yield tiny_gpt2_engine()
    mesh_mod.set_mesh(None)


def _spy_prefills(batcher):
    """Record every ``_prefill`` call's (rows, width, start)."""
    calls = []
    orig = batcher._prefill

    def spy(ids, cache=None, start=0, **kw):
        calls.append((int(ids.shape[0]), int(ids.shape[1]), int(start)))
        return orig(ids, cache=cache, start=start, **kw)

    batcher._prefill = spy
    return calls


def _slot_cache_indices(batcher):
    """Per-slot ``cache_index`` values (any one leaf — they agree)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            batcher._cache)[0]:
        if model_common.cache_leaf_kind(path) == "index":
            arr = np.asarray(leaf)
            return arr.reshape(arr.shape[0], -1)[:, 0]
    raise AssertionError("no cache_index leaf")


def test_mixed_lengths_group_into_one_padded_prefill(eng):
    """Lengths 5/7/8 share the pow2 bucket 8: ONE (3, 8) prefill, and
    placement rewinds each slot's write head to the REAL length."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, size=(s,)).astype(np.int32)
               for s in (5, 7, 8)]
    b = ContinuousBatcher(eng, n_slots=4)
    calls = _spy_prefills(b)
    for p in prompts:
        b.submit(p, max_new_tokens=4)
    b._admit()                       # place without running a decode tick
    assert calls == [(3, 8, 0)], calls
    np.testing.assert_array_equal(_slot_cache_indices(b)[:3], [5, 7, 8])
    # and the padded batch must still sample from each row's REAL last
    # token: finished outputs equal the single-request path exactly
    singles = [np.asarray(eng.generate(p[None], max_new_tokens=4))[0]
               for p in prompts]
    while len(b._finished) < 3:
        b.step(ticks=2)
    for uid, want in enumerate(singles):
        np.testing.assert_array_equal(b._finished[uid], want)


def test_distinct_buckets_split_groups(eng):
    """4-token and 9-token prompts land in different pow2 buckets and
    must NOT share a padded prefill."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, size=(s,)).astype(np.int32)
               for s in (4, 4, 9)]
    b = ContinuousBatcher(eng, n_slots=4)
    calls = _spy_prefills(b)
    for p in prompts:
        b.submit(p, max_new_tokens=3)
    b._admit()
    assert calls == [(2, 4, 0), (1, 9, 0)], calls


def test_unchunked_groups_require_exact_length(eng):
    """chunked_prefill=False keeps the pre-bucketing rule: only
    exactly-equal lengths batch."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, size=(s,)).astype(np.int32)
               for s in (6, 6, 7)]
    b = ContinuousBatcher(eng, n_slots=4, chunked_prefill=False)
    calls = _spy_prefills(b)
    for p in prompts:
        b.submit(p, max_new_tokens=3)
    b._admit()
    assert calls == [(2, 6, 0), (1, 7, 0)], calls


def test_parked_bytes_gauge_tracks_parked_caches(eng):
    """The B-row caches pinned by parked rows are metered while parked
    and released (gauge back to 0) once every row places."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 512, size=(6,)).astype(np.int32)
               for _ in range(4)]
    b = ContinuousBatcher(eng, n_slots=2)
    for p in prompts:
        b.submit(p, max_new_tokens=8)
    b.step(ticks=2)                  # 2 decode, 2 prefilled-ahead + parked
    if b._parked:
        assert b._m_parked_bytes.value > 0
        assert b._telemetry_status()["parked_bytes"] > 0
    while any(u not in b._finished for u in range(4)):
        b.step(ticks=4)
    assert b._m_parked_bytes.value == 0
