"""Speculative decoding host-side units (inference/specdec.py): n-gram
drafter proposals, the resolve surface (config + env precedence), the
acceptance controller's fallback math, and the offset-prefill guard.

Device-side verify-step semantics (accept chains, EOS-in-span, mixed
per-slot acceptance, byte-identity e2e) live in ``test_zspecdec.py`` —
the z-sorted convention keeps batcher compiles late in the tier-1
alphabetical window."""
import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference import specdec
from deepspeed_tpu.inference.serving import ContinuousBatcher

from .simple_model import tiny_gpt2_engine


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    engine = tiny_gpt2_engine()
    yield engine
    mesh_mod.set_mesh(None)


# -- NGramDrafter -----------------------------------------------------------

def test_ngram_proposes_continuation():
    d = specdec.NGramDrafter(max_ngram=3)
    ctx = np.asarray([1, 2, 3, 4, 5, 1, 2, 3], np.int32)
    # suffix [1,2,3] recurs at position 0 → continuation [4,5,1]
    np.testing.assert_array_equal(d.propose(ctx, 3), [4, 5, 1])
    # k caps the proposal
    np.testing.assert_array_equal(d.propose(ctx, 1), [4])


def test_ngram_prefers_most_recent_occurrence():
    d = specdec.NGramDrafter(max_ngram=2)
    ctx = np.asarray([7, 8, 1, 7, 8, 2, 7, 8], np.int32)
    # [7,8] occurs at 0 (→1) and 3 (→2); the most recent prior wins
    np.testing.assert_array_equal(d.propose(ctx, 1), [2])


def test_ngram_falls_back_to_shorter_ngram():
    d = specdec.NGramDrafter(max_ngram=3, min_ngram=1)
    ctx = np.asarray([5, 9, 1, 2, 9], np.int32)
    # no 3/2-gram recurrence ending at the suffix; 1-gram [9] → [1]
    np.testing.assert_array_equal(d.propose(ctx, 2), [1, 2])


def test_ngram_no_match_is_empty():
    d = specdec.NGramDrafter()
    assert d.propose(np.arange(10, dtype=np.int32), 4).size == 0
    assert d.propose(np.asarray([3], np.int32), 4).size == 0
    assert d.propose(np.asarray([1, 2, 1, 2], np.int32), 0).size == 0


def test_ngram_validates_config():
    with pytest.raises(ValueError):
        specdec.NGramDrafter(max_ngram=0)
    with pytest.raises(ValueError):
        specdec.NGramDrafter(max_ngram=2, min_ngram=3)


# -- resolve surface --------------------------------------------------------

def test_resolve_default_off(eng, monkeypatch):
    monkeypatch.delenv(specdec.SPECDEC_ENV, raising=False)
    assert specdec.resolve_specdec(eng, None) is None


def test_resolve_dict_and_empty_dict_enable(eng, monkeypatch):
    monkeypatch.delenv(specdec.SPECDEC_ENV, raising=False)
    sd = specdec.resolve_specdec(eng, {})
    assert isinstance(sd, specdec.SpecDecoder)        # {} means defaults
    sd = specdec.resolve_specdec(eng, {"k": 2, "max_ngram": 2})
    assert sd.cfg.k == 2 and sd.drafter.max_ngram == 2


def test_resolve_env_kill_switch_beats_instance(eng, monkeypatch):
    monkeypatch.delenv(specdec.SPECDEC_ENV, raising=False)
    ready = specdec.resolve_specdec(eng, True)
    assert ready is not None
    monkeypatch.setenv(specdec.SPECDEC_ENV, "0")
    assert specdec.resolve_specdec(eng, ready) is None
    assert specdec.resolve_specdec(eng, True) is None


def test_resolve_env_enables_but_explicit_false_wins(eng, monkeypatch):
    monkeypatch.setenv(specdec.SPECDEC_ENV, "1")
    assert specdec.resolve_specdec(eng, None) is not None
    assert specdec.resolve_specdec(eng, False) is None


def test_resolve_engine_config(monkeypatch):
    monkeypatch.delenv(specdec.SPECDEC_ENV, raising=False)
    mesh_mod.set_mesh(None)
    engine = tiny_gpt2_engine(specdec={"k": 3})
    try:
        sd = specdec.resolve_specdec(engine, None)
        assert sd is not None and sd.cfg.k == 3
        # the batcher argument wins over the engine config
        assert specdec.resolve_specdec(engine, False) is None
    finally:
        mesh_mod.set_mesh(None)


def test_resolve_ready_instance_via_argument_and_engine_config(
        eng, monkeypatch):
    monkeypatch.delenv(specdec.SPECDEC_ENV, raising=False)
    ready = specdec.SpecDecoder(specdec.SpecDecodeConfig(k=7),
                                specdec.NGramDrafter())
    assert specdec.resolve_specdec(eng, ready) is ready
    # a ready instance carried by the ENGINE CONFIG must be honored too,
    # not silently replaced by a default-built decoder
    eng.config.specdec = ready
    try:
        assert specdec.resolve_specdec(eng, None) is ready
    finally:
        eng.config.specdec = None


def test_resolve_unsupported_warns_and_disables(eng, monkeypatch, caplog):
    monkeypatch.delenv(specdec.SPECDEC_ENV, raising=False)
    assert specdec.resolve_specdec(eng, {"drafter": "nope"}) is None
    assert specdec.resolve_specdec(eng, {"k": 0}) is None
    assert specdec.resolve_specdec(eng, {"drafter": object()}) is None
    sd = specdec.resolve_specdec(eng, {"k": 2, "bogus_key": 1})
    assert sd is not None and sd.cfg.k == 2   # unknown keys warn, not fail


# -- controller -------------------------------------------------------------

def test_controller_cooldown_and_recovery():
    sd = specdec.SpecDecoder(
        specdec.SpecDecodeConfig(k=4, window=3, cooldown=5,
                                 min_accept=0.5),
        specdec.NGramDrafter())
    assert sd.active()
    for _ in range(3):                       # 3 all-miss verify ticks
        sd.note_verify(4, 0, [0])
    assert not sd.active() and sd.cooldown == 5
    sd.note_plain(2)
    assert sd.cooldown == 3 and not sd.active()
    sd.note_plain(10)                        # drains, never negative
    assert sd.cooldown == 0 and sd.active()
    for _ in range(10):                      # healthy acceptance: stays on
        sd.note_verify(4, 4, [4])
    assert sd.active()


def test_controller_empty_proposals_count_as_misses():
    sd = specdec.SpecDecoder(
        specdec.SpecDecodeConfig(window=2, cooldown=4, min_accept=0.5),
        specdec.NGramDrafter())
    sd.note_empty()
    sd.note_empty()
    assert not sd.active()


# -- offset-prefill guard ---------------------------------------------------

def test_prefill_offset_without_cache_raises(eng):
    b = ContinuousBatcher(eng, n_slots=2)
    ids = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="offset prefill"):
        b._prefill(ids, cache=None, start=4)
    # start=0 without a cache stays the normal fresh-cache path
    logits, cache = b._prefill(ids, cache=None, start=0)
    assert logits.shape[0] == 1
