"""Speculative decoding device-side semantics (inference/specdec.py):
verify-window edge cases, byte-identity vs spec-off serving on gpt2 and
llama(GQA), the acceptance controller e2e, and the draft-model drafter.

``z``-prefixed like ``test_zkvreuse``: these build engines and compile
serving executables, so they sort late in the alphabetical tier-1 order
to preserve the fixed window's breadth; the fast host-side units live in
``test_specdec.py``."""
import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference import specdec
from deepspeed_tpu.inference.serving import ContinuousBatcher

from .simple_model import seeded_params, tiny_gpt2_engine

VOCAB = 512


def _make_llama_engine():
    from deepspeed_tpu.models.llama import LlamaForCausalLM, llama_config

    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    return deepspeed_tpu.init_inference(model=model, mp_size=1,
                                        dtype=jnp.float32,
                                        params=seeded_params(model))


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    engine = tiny_gpt2_engine()
    yield engine
    mesh_mod.set_mesh(None)


class _ScriptedDrafter:
    """Proposes from recorded full sequences: ``mode='oracle'`` returns
    the true continuation (forces full acceptance), ``mode='anti'``
    returns provably-wrong tokens (forces full rejection).  Per-sequence
    modes drive the mixed-acceptance case."""

    name = "scripted"

    def __init__(self, fulls, modes):
        self.fulls = [np.asarray(f, np.int32) for f in fulls]
        self.modes = list(modes)

    def propose(self, context, k):
        L = len(context)
        for f, mode in zip(self.fulls, self.modes):
            if len(f) > L and np.array_equal(f[:L], context):
                nxt = f[L:L + k]
                if mode == "oracle":
                    return nxt
                return (nxt + 1) % VOCAB      # never the greedy choice
        return np.empty((0,), np.int32)


def _repetitive_prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [np.tile(rng.integers(0, VOCAB, size=(4,)).astype(np.int32), 4)
            for _ in range(n)]


# -- e2e byte-identity ------------------------------------------------------

def test_gpt2_ngram_byte_identical_with_acceptance(eng):
    prompts = _repetitive_prompts(5)
    base = ContinuousBatcher(eng, n_slots=4).run(prompts, max_new_tokens=24)
    b = ContinuousBatcher(eng, n_slots=4, specdec={"k": 4})
    outs = b.run(prompts, max_new_tokens=24)
    for want, got in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    st = b.specdec._telemetry_status()
    # the greedy loop of a repetitive workload must actually speculate
    assert st["accepted_tokens"] > 0 and st["verify_ticks"] > 0
    # tpot satellite: the histogram observed real windows
    assert b._telemetry_status()["tpot_ms"] is not None


def test_llama_gqa_full_accept_byte_identical():
    mesh_mod.set_mesh(None)
    leng = _make_llama_engine()
    try:
        prompts = _repetitive_prompts(3, seed=1)
        base = ContinuousBatcher(leng, n_slots=2).run(prompts,
                                                      max_new_tokens=16)
        drafter = _ScriptedDrafter(base, ["oracle"] * len(base))
        b = ContinuousBatcher(leng, n_slots=2, specdec={
            "k": 4, "drafter": drafter, "window": 10_000})
        outs = b.run(prompts, max_new_tokens=16)
        for want, got in zip(base, outs):
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(got))
        st = b.specdec._telemetry_status()
        assert st["accepted_tokens"] == st["draft_tokens"] > 0
    finally:
        mesh_mod.set_mesh(None)


# -- verify-window edge cases ----------------------------------------------

def test_all_rejected_still_emits_one_token_per_tick(eng):
    prompts = _repetitive_prompts(1, seed=2)
    max_new = 12
    base = ContinuousBatcher(eng, n_slots=1).run(prompts,
                                                 max_new_tokens=max_new)
    drafter = _ScriptedDrafter(base, ["anti"])
    b = ContinuousBatcher(eng, n_slots=1, specdec={
        "k": 3, "drafter": drafter, "window": 10_000})
    outs = b.run(prompts, max_new_tokens=max_new)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(outs[0]))
    st = b.specdec._telemetry_status()
    assert st["accepted_tokens"] == 0
    # every verify tick emitted exactly the one correction token: the
    # first token comes from prefill, the LAST from a plain tick (with
    # one token remaining there is no draft budget — r-1 = 0), and each
    # of the max_new-2 in between from one all-rejected verify tick
    assert st["verify_ticks"] == max_new - 2
    assert st["fallback_ticks"] >= 1


def test_full_accept_emits_k_plus_one_per_tick(eng):
    prompts = _repetitive_prompts(1, seed=3)
    max_new = 16
    base = ContinuousBatcher(eng, n_slots=1).run(prompts,
                                                 max_new_tokens=max_new)
    drafter = _ScriptedDrafter(base, ["oracle"])
    b = ContinuousBatcher(eng, n_slots=1, specdec={
        "k": 4, "drafter": drafter, "window": 10_000})
    outs = b.run(prompts, max_new_tokens=max_new)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(outs[0]))
    st = b.specdec._telemetry_status()
    assert st["accepted_tokens"] == st["draft_tokens"] > 0
    # 15 post-prefill tokens at up to 5/tick → at most ceil(15/5)+1 ticks
    assert st["verify_ticks"] <= (max_new - 1 + 4) // 5 + 1


def test_eos_inside_accepted_span(eng):
    # find a workload whose greedy stream has a token FIRST occurring at
    # generation index 2..4 — inside the first k=4 oracle verify span
    # (a cycling tiny model may repeat early, so search a few seeds)
    max_new = 16
    for seed in range(30):
        prompts = _repetitive_prompts(1, seed=seed)
        base_no_eos = ContinuousBatcher(eng, n_slots=1).run(
            prompts, max_new_tokens=max_new)
        gen = np.asarray(base_no_eos[0])[len(prompts[0]):]
        cand = [int(t) for i, t in enumerate(gen)
                if 2 <= i <= 4 and int(t) not in gen[:i].tolist()]
        if cand:
            eos = cand[0]
            break
    else:
        pytest.skip("no mid-span first-occurrence token found")
    base = ContinuousBatcher(eng, n_slots=1, eos_token_id=eos).run(
        prompts, max_new_tokens=max_new)
    drafter = _ScriptedDrafter(base_no_eos, ["oracle"])
    b = ContinuousBatcher(eng, n_slots=1, eos_token_id=eos, specdec={
        "k": 4, "drafter": drafter, "window": 10_000})
    outs = b.run(prompts, max_new_tokens=max_new)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(outs[0]))
    assert int(np.asarray(outs[0])[-1]) == eos     # retired AT the eos
    assert b.pending == 0


def test_k0_verify_degenerates_to_plain_tick(eng):
    """A width-0 verify (no drafts) must be a plain decode tick:
    same token, one emission, same advanced state."""
    b = ContinuousBatcher(eng, n_slots=2, specdec={"k": 4})
    b.submit(_repetitive_prompts(1, seed=5)[0], max_new_tokens=8)
    b._admit()
    params = b.engine.params
    slot_ids = jnp.arange(b.n_slots)
    args = (b._cache, b._token, b._pos, slot_ids, b._temp, b._top_p,
            b._rep, b._seen, b._done)
    toks_p, *_ = b._multi_step(1, True)(
        params, *args, jnp.int32(b._tick_no), jnp.int32(b.eos),
        jnp.int32(b.pad))
    toks_v, n_v, _, token_v, pos_v, _, _ = b.specdec.verify_step(0, True)(
        params, b._cache, b._token, b._pos, slot_ids, b._temp, b._top_p,
        b._rep, b._seen, b._done,
        jnp.zeros((b.n_slots, 0), jnp.int32), jnp.int32(b._tick_no),
        jnp.int32(b.eos), jnp.int32(b.pad))
    # slot 0 is active: same single token; free slot 1 emits nothing
    assert int(n_v[0]) == 1 and int(n_v[1]) == 0
    assert int(toks_v[0, 0]) == int(toks_p[0, 0, 0])
    assert int(token_v[0, 0, 0]) == int(toks_p[0, 0, 0])
    assert int(pos_v[0]) == int(b._pos[0]) + 1


def test_mixed_per_slot_acceptance_one_batched_verify(eng):
    prompts = _repetitive_prompts(2, seed=6)
    max_new = 12
    base = ContinuousBatcher(eng, n_slots=2).run(prompts,
                                                 max_new_tokens=max_new)
    drafter = _ScriptedDrafter(base, ["oracle", "anti"])
    b = ContinuousBatcher(eng, n_slots=2, specdec={
        "k": 3, "drafter": drafter, "window": 10_000})
    outs = b.run(prompts, max_new_tokens=max_new)
    for want, got in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    st = b.specdec._telemetry_status()
    # the oracle slot accepted, the anti slot never did — both inside
    # the SAME batched verify ticks
    assert 0 < st["accepted_tokens"] < st["draft_tokens"]


# -- controller + robustness ------------------------------------------------

def test_bad_drafter_degrades_gracefully(eng):
    prompts = _repetitive_prompts(2, seed=7)
    base = ContinuousBatcher(eng, n_slots=2).run(prompts,
                                                 max_new_tokens=16)
    drafter = _ScriptedDrafter(base, ["anti", "anti"])
    b = ContinuousBatcher(eng, n_slots=2, specdec={
        "k": 3, "drafter": drafter, "window": 3, "cooldown": 8,
        "min_accept": 0.5})
    outs = b.run(prompts, max_new_tokens=16)
    for want, got in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    st = b.specdec._telemetry_status()
    assert st["fallback_ticks"] > 0        # the controller actually bailed


def test_out_of_vocab_proposals_are_dropped(eng):
    class _Bad:
        name = "bad"

        def propose(self, context, k):
            return np.full((k,), VOCAB + 7, np.int32)

    prompts = _repetitive_prompts(1, seed=8)
    base = ContinuousBatcher(eng, n_slots=1).run(prompts, max_new_tokens=8)
    b = ContinuousBatcher(eng, n_slots=1,
                          specdec={"k": 3, "drafter": _Bad()})
    outs = b.run(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(outs[0]))


def test_sampled_mode_runs_and_retires(eng):
    prompts = _repetitive_prompts(2, seed=9)
    b = ContinuousBatcher(eng, n_slots=2, specdec={"k": 3})
    outs = b.run(prompts, max_new_tokens=10, temperature=0.8, top_p=0.9)
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        assert o.min() >= 0 and o.max() < VOCAB
        assert len(p) < len(o) <= len(p) + 10
    assert b.pending == 0


def test_draft_model_drafter_full_accept(eng):
    # the target as its own draft model: greedy proposals are the true
    # continuation, so everything accepts (the drafter e2e contract)
    drafter = specdec.DraftModelDrafter(eng)
    prompts = _repetitive_prompts(1, seed=10)
    base = ContinuousBatcher(eng, n_slots=1).run(prompts, max_new_tokens=8)
    b = ContinuousBatcher(eng, n_slots=1, specdec={
        "k": 3, "drafter": drafter, "window": 10_000})
    outs = b.run(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(outs[0]))
    st = b.specdec._telemetry_status()
    assert st["accepted_tokens"] == st["draft_tokens"] > 0
