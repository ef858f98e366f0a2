"""Continuous-batching serve loop on the compiled decode step.

The reference serves one static batch per ``InferenceEngine.forward``
(``inference/engine.py:392``) — batching across requests is left to the
caller.  Production decoding wants *continuous* batching (Orca-style):
a fixed pool of KV-cache slots, requests admitted into free slots as
others retire, one fused decode tick advancing every active slot.

TPU-native realization: the per-slot decode step is the engine's B=1
cached forward, ``jax.vmap``-ed over the slot dimension and jitted ONCE —
each slot carries its own KV cache tree (including its own scalar
``cache_index``, which vmap makes per-slot), position, RNG lane, sampling
params, repetition-penalty ``seen`` mask, and ``done`` flag.  Admission
runs the engine's compiled prefill at the prompt's exact length (XLA
caches one executable per distinct length; bucket prompt lengths upstream
if admission-compile cost matters) and scatters the resulting cache into
the slot.  Retired slots keep emitting ``pad`` under ``done=True`` until
reused, so the hot loop never recompiles or reshapes.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import common as model_common
from ..telemetry import (flightrec as telemetry_flightrec, goodput,
                         memory as telemetry_memory,
                         recompile, registry as telemetry_registry,
                         reqtrace as telemetry_reqtrace, trace)
from ..telemetry.registry import pct as _pct
from ..testing import chaos as chaos_mod
from . import admission as admission_mod
from . import kvreuse
from . import specdec as specdec_mod
from .engine import InferenceEngine, _sample
from ..utils.logging import logger

# per-output-token latency lands anywhere from tens of MICROseconds
# (fused+paged decode at 8 slots on real chips) to seconds (CPU-mesh
# tests); the schema lives in registry.BUCKET_SCHEMAS so the fleet
# aggregator can assert one bucket layout per metric family
_TPOT_BUCKETS = telemetry_registry.TPOT_MS_BUCKETS


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    # admission-control fields (inference/admission.py): lower number =
    # higher priority (0 is the default, highest, class); deadline_ms
    # bounds submit -> retire (None defers to the policy default).
    # Inert without a resolved AdmissionController.
    priority: int = 0
    deadline_ms: Optional[float] = None


@dataclasses.dataclass
class _Active:
    req: Request
    emitted: List[int]


class ContinuousBatcher:
    """Slot-pool scheduler over an :class:`InferenceEngine`.

    ``top_k`` and ``eos_token_id`` are pool-wide (``top_k`` is static in
    the compiled sampler); temperature/top_p/repetition_penalty are
    per-request.
    """

    def __init__(self, engine: InferenceEngine, n_slots: int = 4, *,
                 top_k: int = 0, eos_token_id: Optional[int] = None,
                 pad_token_id: Optional[int] = None, seed: int = 0,
                 chunked_prefill: bool = True,
                 prefill_ahead: Optional[int] = None,
                 prefix_cache=None, specdec=None, paged_decode=None,
                 slo=None, admission=None):
        if engine.params is None:
            raise RuntimeError("engine has no parameters loaded")
        self.engine = engine
        self.n_slots = n_slots
        # shared-prefix KV reuse (inference/kvreuse.py): None defers to
        # the engine config / DSTPU_PREFIX_CACHE env; the resolved cache
        # is None when disabled — and then every path below is
        # byte-for-byte the cache-less admission
        self.prefix_cache = kvreuse.resolve_prefix_cache(engine,
                                                         prefix_cache)
        self.top_k = int(top_k)
        self.eos = -1 if eos_token_id is None else int(eos_token_id)
        self.pad = int(pad_token_id if pad_token_id is not None
                       else (eos_token_id if eos_token_id is not None else 0))
        self.seed = seed
        self.chunked_prefill = chunked_prefill
        # speculative decoding (inference/specdec.py): None defers to
        # the engine config / DSTPU_SPECDEC env; when the resolved
        # decoder is None every decode path below is byte-for-byte the
        # pre-existing plain-tick loop
        self.specdec = specdec_mod.resolve_specdec(engine, specdec)
        if self.specdec is not None:
            self.specdec.attach(self)
        # page-resident serving (inference/kvreuse.py + the paged
        # attention kernel): slots keep their K/V in the prefix cache's
        # page arena for their whole life — admission gathers nothing
        # and builds no contiguous admission cache, decode attention
        # reads the arena in place, retirement donates pages by
        # reference.  None when disabled or unsupported — and then every
        # path below is byte-for-byte the pre-existing contiguous
        # machinery.
        self.paged = kvreuse.resolve_paged_decode(
            engine, self.prefix_cache, n_slots, self.specdec, paged_decode)
        # SLO-aware admission control (inference/admission.py): None
        # when disabled (DSTPU_ADMISSION unset and no admission= /
        # engine-config entry) — and then submit/step/wait are
        # byte-for-byte the controller-less batcher
        self.admission = admission_mod.resolve_admission(engine, admission)
        # seeded fault injection (testing/chaos.py): resolves the
        # DSTPU_CHAOS_PLAN env once; with no plan installed every site
        # is a single attribute load
        chaos_mod.maybe_install_env()
        cfg = engine.decode_cfg
        self._vocab = int(getattr(cfg, "padded_vocab_size", None)
                          or cfg.vocab_size)

        # per-leaf batch axis of the engine cache (scan-stacked layers put
        # batch at dim 1, plain stacks at dim 0, cache_index is a scalar):
        # diff the abstract shapes of a 1-row vs 2-row cache
        c1_sds = jax.eval_shape(lambda: engine.init_cache(1))
        c2_sds = jax.eval_shape(lambda: engine.init_cache(2))
        self._cache_bdims = jax.tree_util.tree_map(
            lambda a, b: next((d for d in range(len(a.shape))
                               if a.shape[d] != b.shape[d]), None),
            c1_sds, c2_sds)
        if self.paged is None:
            cache1 = engine.init_cache(1)
            self._cache = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (n_slots,) + l.shape)
                + jnp.zeros_like(l), cache1)
        else:
            # the slots' K/V lives in the pool arena: allocating the
            # n_slots × gen-limit contiguous cache would double the HBM
            # the paged layout exists to reclaim
            self._cache = None
        self._token = jnp.zeros((n_slots, 1, 1), jnp.int32)
        self._pos = jnp.zeros((n_slots,), jnp.int32)
        self._temp = jnp.zeros((n_slots,), jnp.float32)
        self._top_p = jnp.ones((n_slots,), jnp.float32)
        self._rep = jnp.ones((n_slots,), jnp.float32)
        self._seen = jnp.zeros((n_slots, 1, self._vocab), bool)
        self._done = jnp.ones((n_slots, 1), bool)      # free ⇒ done
        self._slots: List[Optional[_Active]] = [None] * n_slots
        self._queue: deque = deque()
        # prefill-ahead (the TTFT lever): queued requests are prefilled
        # and their FIRST token sampled while every slot is still busy;
        # the results park here until a slot frees.  TTFT becomes
        # queueing-for-prefill + prefill, decoupled from how long the
        # current wave keeps decoding.  HBM residency: a batched prefill's
        # parked rows share ONE B-row gen-limit KV cache BY REFERENCE, and
        # that whole cache stays live until its LAST row is placed — so
        # one slow-to-place row pins all B rows (worst case ``B × one
        # gen-limit cache``, not one).  ``_shrink_parked`` trims the tail:
        # once a batch is down to a single pending row, that row is
        # sliced into its own 1-row cache and the B-row buffer is
        # released.  ``prefill_ahead`` bounds how many rows may park at
        # once; 0 disables.
        self._parked: deque = deque()
        # page-resident mode: parked/active page ownership rides keyed by
        # uid (the parked tuple keeps the contiguous shape with cacheB
        # None, so every shared code path unpacks identically)
        self._parked_meta: Dict[int, object] = {}
        self.prefill_ahead = n_slots if prefill_ahead is None \
            else int(prefill_ahead)
        self._tick_no = 0
        self._next_uid = 0
        self._finished: Dict[int, np.ndarray] = {}
        # shed requests: uid -> rejection reason.  A shed is a FIRST-
        # CLASS outcome (its own lifecycle event + metrics), never an
        # exception: the caller holds a uid that will never appear in
        # ``_finished``, and ``wait()``/``run()`` treat it as terminal.
        # Bounded like the latency window — a long-lived server's
        # memory stays O(window).
        self._rejected: Dict[int, str] = {}
        # requests the deadline sweep retired early — tags the retire
        # lifecycle event so observers can tell a deadline retirement
        # from a natural one
        self._deadline_hits: set = set()
        self._draining = False
        self._in_step = False
        # per-request latency bookkeeping (submit → first token → done),
        # the serving-metrics surface production schedulers expose; TTFT
        # here covers queueing + prefill + first sample (reference has no
        # batcher, so no analog — BASELINE.json names "inference p50 TTFT").
        # In-flight times live keyed by uid; at retirement they collapse
        # into a bounded (ttft, e2e) window so a long-lived server's
        # memory stays O(window), not O(requests served).
        self._t_submit: Dict[int, float] = {}
        self._t_first: Dict[int, float] = {}
        self._lat: deque = deque(maxlen=4096)
        # registry surface (telemetry/registry.py): counters/histograms a
        # scraper reads without calling latency_stats()
        self._m_submitted = telemetry_registry.counter(
            "serving_requests_submitted_total", "requests accepted")
        self._m_completed = telemetry_registry.counter(
            "serving_requests_completed_total", "requests retired")
        self._m_ticks = telemetry_registry.counter(
            "serving_decode_ticks_total", "decode ticks executed")
        self._m_ttft = telemetry_registry.histogram(
            "serving_ttft_seconds", "submit -> first token on host",
            buckets=telemetry_registry.SECONDS_BUCKETS)
        self._m_e2e = telemetry_registry.histogram(
            "serving_e2e_seconds", "submit -> retirement",
            buckets=telemetry_registry.SECONDS_BUCKETS)
        # TPOT (time per output token): decode-window wall time divided
        # by tokens actually emitted in that window — the denominator
        # speculative decoding moves, so its win shows up on /metrics
        # right next to TTFT
        self._m_tpot = telemetry_registry.histogram(
            "serving_tpot_ms",
            "decode wall ms per emitted token per decode/verify window",
            buckets=_TPOT_BUCKETS)
        self._tpot_window: deque = deque(maxlen=512)   # /statusz mean
        self._m_active = telemetry_registry.gauge(
            "serving_active_slots", "occupied decode slots")
        self._m_queue = telemetry_registry.gauge(
            "serving_queue_depth", "queued + parked requests")
        # queue wait (submit → prefill start) as a first-class
        # histogram: previously only derivable from loadgen waterfalls,
        # invisible to /metrics and the fleet rollup.  MS_BUCKETS — the
        # declared schema, so the fleet merge can assert one layout.
        self._m_queue_wait = telemetry_registry.histogram(
            "serving_queue_wait_ms",
            "submit -> prefill start (queueing for admission), ms",
            buckets=telemetry_registry.MS_BUCKETS)
        # the _shrink_parked hazard, metered: parked rows pin their whole
        # B-row prefill cache BY REFERENCE, so the bytes held alive can be
        # B× what the parked-row count suggests
        self._m_parked_bytes = telemetry_registry.gauge(
            "serving_parked_bytes",
            "bytes pinned by parked prefill caches (deduped by buffer)")
        # retire-time SLO tagging (telemetry/loadgen.py sets the bounds
        # for load runs; any deployment can set them via ``slo=`` /
        # ``set_slo``): a request that finished but blew its latency
        # budget is counted as a violation, the substrate of the
        # goodput-under-SLO report.  Registry counters are process-wide;
        # the per-instance ints feed /statusz (cross-batcher pollution).
        self._m_slo_met = telemetry_registry.counter(
            "serving_slo_met_total",
            "retired requests meeting the configured TTFT/TPOT SLO")
        self._m_slo_viol = telemetry_registry.counter(
            "serving_slo_violations_total",
            "retired requests violating the configured SLO",
            labelnames=("bound",))
        self._slo_ttft_ms: Optional[float] = None
        self._slo_tpot_ms: Optional[float] = None
        self._slo_met_n = 0
        self._slo_viol_n = 0
        if slo is not None:
            self.set_slo(getattr(slo, "ttft_ms", None)
                         if not isinstance(slo, dict) else slo.get("ttft_ms"),
                         getattr(slo, "tpot_ms", None)
                         if not isinstance(slo, dict) else slo.get("tpot_ms"))
        # per-request lifecycle observers (telemetry/loadgen.py): each
        # gets (t, uid, event, extra) at submit / prefill_start /
        # first_token / place / emit / retire.  Empty list = zero cost
        # on the hot path (one truthiness check).
        self._lifecycle_observers: List = []
        self._m_prefill_tokens = telemetry_registry.counter(
            "serving_prefill_tokens_total",
            "tokens run through prefill (padding included — compute, "
            "not admission, tokens)")
        # /statusz section (weakly held: a dropped batcher must not be
        # pinned — it holds the engine and therefore the params in HBM)
        from ..telemetry import exporter as telemetry_exporter

        telemetry_exporter.register_status_owner(
            "serving", self, "_telemetry_status")

        decode_model = engine._decode_model
        top_k_static = self.top_k
        base_seed = seed

        # params are an explicit broadcast argument (in_axes=None), NOT a
        # closure capture: captured arrays become literals of the
        # compiled program (a copy of the weights per executable)
        def sample_row(greedy, logits, slot_id, temp, top_p, rep, seen,
                       done, tick, eos, pad):
            """THE per-row sampling step — fold_in key discipline, the
            greedy override, done→pad masking, EOS latch, seen scatter —
            shared by the slot-vmapped contiguous step AND the batched
            paged step, so paged↔gather byte-identity cannot drift on a
            one-sided edit.  Greedy pools take the STATIC temperature=0
            sampler: with traced temp/top_p the nucleus path stays live
            and costs a (V,)-sort per slot per tick — ~10 ms/tick of
            pure dead code at 8×50k vocab when every request is greedy
            anyway."""
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(base_seed), tick),
                slot_id)
            nxt = _sample(logits, key, 0.0 if greedy else temp,
                          top_k_static, 1.0 if greedy else top_p,
                          rep, seen)
            nxt = jnp.where(done, pad, nxt)
            new_done = jnp.logical_or(done, nxt == eos)
            seen = seen.at[jnp.arange(1), nxt].set(True)
            return nxt, seen, new_done

        def make_slot_step(greedy: bool):
            def slot_step(params, cache, token, pos, slot_id, temp, top_p,
                          rep, seen, done, tick, eos, pad):
                out, vars_ = decode_model.apply(
                    {"params": params, "cache": cache}, token,
                    position_ids=jnp.full((1, 1), pos, jnp.int32),
                    mutable=["cache"])
                logits = out["logits"][:, -1, :].astype(jnp.float32)  # (1,V)
                nxt, seen, new_done = sample_row(
                    greedy, logits, slot_id, temp, top_p, rep, seen,
                    done, tick, eos, pad)
                return nxt, vars_["cache"], seen, new_done
            return slot_step

        self._vmapped_steps = {
            greedy: jax.vmap(
                make_slot_step(greedy),
                in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, None, None, None))
            for greedy in (False, True)}

        # N ticks per host round-trip: a lax.scan over the vmapped tick,
        # emitting (ticks, slots) tokens in ONE device fetch — the lever
        # for high-RTT links where each sync costs a round trip
        @functools.lru_cache(maxsize=None)   # executables are cheap vs a
        def multi_step(ticks: int, greedy: bool = False):
            vstep = self._vmapped_steps[greedy]

            def run(params, cache, token, pos, slot_ids, temp, top_p, rep,
                    seen, done, tick0, eos, pad):
                def body(carry, t):
                    cache, token, pos, seen, done = carry
                    tok, cache, seen, done = vstep(
                        params, cache, token, pos, slot_ids, temp, top_p,
                        rep, seen, done, tick0 + t, eos, pad)
                    return (cache, tok[:, :, None], pos + 1, seen, done), tok
                (cache, token, pos, seen, done), toks = jax.lax.scan(
                    body, (cache, token, pos, seen, done),
                    jnp.arange(ticks))
                return toks, cache, token, pos, seen, done

            # each (ticks, greedy) window is its own executable BY DESIGN;
            # per-window watchdog names so only intra-window drift (cache/
            # sampling-state shape changes) counts as a hot-loop recompile
            return recompile.watch(
                jax.jit(run),
                name=f"serving.decode[{ticks}{'g' if greedy else 's'}]")

        self._multi_step = multi_step

        # admission is two jitted phases so the first token can be
        # produced BEFORE a slot frees (prefill-ahead — the TTFT lever):
        # (1) sample the first token from the prefill logits; (2) scatter
        # the parked cache + sampling state into slot ``i``.  Both keep
        # every index TRACED (a python-int index would bake into the
        # program and recompile per slot/uid).
        def first_token_fn(last_logits, prompt_seen, uid, r_temp, r_top_p,
                           r_rep):
            key = jax.random.fold_in(jax.random.PRNGKey(base_seed), uid)
            first = _sample(last_logits.astype(jnp.float32), key,
                            r_temp, top_k_static, r_top_p, r_rep,
                            prompt_seen)
            seen1 = prompt_seen.at[jnp.arange(1), first].set(True)
            return first, seen1

        # one executable per batch width; a per-ROW jit + device_get costs
        # one host sync per request — the batch samples in ONE call and
        # the caller fetches every first token in ONE device_get
        self._first_token_batch = recompile.watch(
            jax.jit(jax.vmap(first_token_fn)),
            name="serving.first_token", warn=False)   # varies per width

        cache_bdims = self._cache_bdims

        def slice_parked_row(cacheB, firstB, seen1B, row):
            """Row ``row`` of a parked B-row prefill batch as 1-row
            arrays — the ONE slicing convention shared by placement and
            the shrink path (divergence would extract a stale row)."""
            cache1 = jax.tree_util.tree_map(
                lambda l, bd: l if bd is None
                else jax.lax.dynamic_slice_in_dim(l, row, 1, bd),
                cacheB, cache_bdims)
            first1 = jax.lax.dynamic_slice_in_dim(firstB, row, 1, 0)
            seen1 = jax.lax.dynamic_slice_in_dim(seen1B, row, 1, 0)
            return cache1, first1, seen1

        def place_fn(cache, token, pos, temp, top_p, rep, seen, done,
                     cacheB, firstB, seen1B, row, prompt_len, i,
                     r_temp, r_top_p, r_rep):
            # row-extraction happens HERE, inside the jit: slicing the
            # parked batch eagerly costs one dispatch per cache leaf per
            # request
            cache1, first1, seen1B_row = slice_parked_row(
                cacheB, firstB, seen1B, row)
            # bucket-padded prefill leaves the write head at the PADDED
            # width with K/V garbage at [prompt_len, bucket): rewind to
            # the real length so decode ticks overwrite the garbage in
            # place — the attention length mask (cur+1) then never reads
            # past the last real write.  Exact-length prefills rewind to
            # the value already there (a no-op).
            cache1 = model_common.set_cache_index(cache1, prompt_len)
            first = first1[0]
            seen1 = seen1B_row[0]

            def put(big, small):
                return jax.lax.dynamic_update_slice(
                    big, small[None].astype(big.dtype),
                    (i,) + (0,) * small.ndim)

            cache = jax.tree_util.tree_map(put, cache, cache1)
            token = put(token, first[:, None])
            pos = put(pos, jnp.int32(prompt_len))
            temp = put(temp, r_temp)
            top_p = put(top_p, r_top_p)
            rep = put(rep, r_rep)
            seen = put(seen, seen1)
            done = put(done, first == jnp.int32(self.eos))
            return cache, token, pos, temp, top_p, rep, seen, done

        # one executable per parked-batch width (B-row cacheB operand)
        self._place_fn = recompile.watch(jax.jit(place_fn),
                                         name="serving.place", warn=False)

        # last-pending-row extraction (see _shrink_parked): slice one row
        # of a parked B-row prefill batch into standalone 1-row arrays so
        # the B-row cache can be freed; one executable per batch width
        self._extract_row_fn = recompile.watch(
            jax.jit(slice_parked_row), name="serving.extract_row",
            warn=False)

        # retire: freeze the slot AND rewind its pos/cache_index to 0, so a
        # frozen slot's continued (discarded) decode writes at position 0
        # instead of marching past the cache length.  (Round-up sub-windows
        # can still overshoot a not-yet-retired slot past its budget; those
        # writes clamp at the cache edge and touch only the slot's own
        # finished row, which placement overwrites.)  ``i`` is traced
        # (python int → weak scalar), so one executable serves every slot.
        def retire_fn(done, pos, cache, i):
            done = done.at[i, 0].set(True)
            pos = pos.at[i].set(0)

            def reset(path, leaf):
                if model_common.cache_leaf_kind(path) == "index":
                    # dstpu-lint: disable-next-line=DSTPU003 -- per-SLOT head rewind on the slot-stacked cache; set_cache_index rewinds every row (classified through cache_leaf_kind, same contract)
                    return leaf.at[i].set(0)
                return leaf

            return done, pos, jax.tree_util.tree_map_with_path(reset, cache)

        self._retire_fn = recompile.watch(
            jax.jit(retire_fn, donate_argnums=(2,)), name="serving.retire")

        if self.paged is not None:
            # -- page-resident decode path -----------------------------
            # One BATCHED model forward per tick instead of the slot
            # vmap: the shared page arena cannot ride a vmapped cache
            # (each lane would get its own mutated copy), so the paged
            # cache tree — arena by reference + per-row lengths + page
            # table — applies at B=n_slots and only the SAMPLER is
            # vmapped, reproducing make_slot_step's per-row semantics
            # (same fold_in keys, same _sample) token-for-token.
            def make_paged_step(greedy: bool):
                # the SAME sample_row as the contiguous slot step —
                # vmapped over rows here instead of riding the slot vmap
                row_sample = functools.partial(sample_row, greedy)

                def paged_step(params, cache, token, pos, slot_ids, temp,
                               top_p, rep, seen, done, tick, eos, pad):
                    out, vars_ = decode_model.apply(
                        {"params": params, "cache": cache},
                        token[:, :, 0], position_ids=pos[:, None],
                        mutable=["cache"])
                    logits = out["logits"][:, -1:, :].astype(jnp.float32)
                    nxt, seen, new_done = jax.vmap(
                        row_sample,
                        in_axes=(0, 0, 0, 0, 0, 0, 0, None, None, None))(
                        logits, slot_ids, temp, top_p, rep, seen, done,
                        tick, eos, pad)
                    return nxt, vars_["cache"], seen, new_done
                return paged_step

            paged_steps = {g: make_paged_step(g) for g in (False, True)}

            # the sampling loop state (token/pos/seen/done) cycles
            # between three producers — place, retire, decode window —
            # and XLA's sharding propagation is free to shard a
            # singleton axis differently in each (observed: the window
            # returned ``done`` as P(None, 'tp') while place returned
            # P()), which costs one spurious window recompile per
            # (ticks, greedy) site.  Force every producer's loop-state
            # OUTPUTS replicated via out_shardings — a
            # with_sharding_constraint does not work here: sharding a
            # size-1 axis is "compatible" with replicated, so GSPMD may
            # still pick the sharded form for the executable's output
            # signature.  These are (n_slots,)-small arrays; replication
            # is free.
            _repl = jax.sharding.NamedSharding(
                engine.mesh, jax.sharding.PartitionSpec())

            @functools.lru_cache(maxsize=None)
            def paged_multi_step(ticks: int, greedy: bool = False):
                pstep = paged_steps[greedy]

                def run(params, cache, token, pos, slot_ids, temp, top_p,
                        rep, seen, done, tick0, eos, pad):
                    def body(carry, t):
                        cache, token, pos, seen, done = carry
                        tok, cache, seen, done = pstep(
                            params, cache, token, pos, slot_ids, temp,
                            top_p, rep, seen, done, tick0 + t, eos, pad)
                        return (cache, tok[:, :, None], pos + 1, seen,
                                done), tok
                    (cache, token, pos, seen, done), toks = jax.lax.scan(
                        body, (cache, token, pos, seen, done),
                        jnp.arange(ticks))
                    return toks, cache, token, pos, seen, done

                # the cache (and with it the ARENA) is DONATED: the
                # append must bufferize in place — without donation XLA
                # copies the whole arena per window, the exact copy tax
                # paged attention removes.  The caller rebinds via
                # PagedServingState.adopt.
                return recompile.watch(
                    jax.jit(run, donate_argnums=(1,),
                            out_shardings=(None, None, _repl, _repl,
                                           _repl, _repl)),
                    name=f"serving.decode_paged"
                         f"[{ticks}{'g' if greedy else 's'}]")

            self._paged_multi_step = paged_multi_step

            def paged_place_fn(token, pos, temp, top_p, rep, seen, done,
                               firstB, seen1B, row, prompt_len, i,
                               r_temp, r_top_p, r_rep):
                # no cache scatter: the request's K/V is ALREADY in the
                # arena (its suffix prefill wrote it there) — placement
                # is sampling-state bookkeeping only
                first1 = jax.lax.dynamic_slice_in_dim(firstB, row, 1, 0)
                seen1 = jax.lax.dynamic_slice_in_dim(seen1B, row, 1, 0)
                first = first1[0]
                seen_row = seen1[0]

                def put(big, small):
                    return jax.lax.dynamic_update_slice(
                        big, small[None].astype(big.dtype),
                        (i,) + (0,) * small.ndim)

                token = put(token, first[:, None])
                pos = put(pos, jnp.int32(prompt_len))
                temp = put(temp, r_temp)
                top_p = put(top_p, r_top_p)
                rep = put(rep, r_rep)
                seen = put(seen, seen_row)
                done = put(done, first == jnp.int32(self.eos))
                return token, pos, temp, top_p, rep, seen, done

            self._paged_place_fn = recompile.watch(
                jax.jit(paged_place_fn, out_shardings=_repl),
                name="serving.place_paged", warn=False)

            def paged_retire_fn(done, pos, i):
                # the cache-side rewind is host bookkeeping (table row →
                # trash, length → 0) in PagedServingState.retire_slot
                return done.at[i, 0].set(True), pos.at[i].set(0)

            self._paged_retire_fn = recompile.watch(
                jax.jit(paged_retire_fn, out_shardings=_repl),
                name="serving.retire_paged")

        # request-scoped tracing (telemetry/reqtrace.py): attach the
        # env-configured tracer as a lifecycle observer.  Off by
        # default — no observer registers, every _note_lifecycle stays
        # one truthiness check (the DSTPU002 zero-cost contract).
        telemetry_reqtrace.maybe_attach(self)
        if self.admission is not None:
            self.admission.attach(self)
        # graceful termination: the launcher's SIGTERM drains in-flight
        # work (bounded by DSTPU_DRAIN_TIMEOUT_S, default 5s; 0
        # disables) BEFORE the flight recorder dumps, so the dump
        # snapshots a drained replica and no request is silently lost
        # to a rolling restart.  Weakly bound, and the weakref's GC
        # callback unregisters the hook — a process that builds many
        # batchers (every test suite) must not grow the module hook
        # list one dead closure per construction (the reqtrace
        # observer-leak lesson).  Skipped when the signal lands
        # mid-step (slot state would be mid-mutation) or mid-drain.
        hook_remover: list = []
        ref = weakref.ref(
            self, lambda _r: hook_remover and hook_remover[0]())

        def _drain_on_term():
            b = ref()
            if b is None or b._in_step or b._draining:
                return
            try:
                timeout = float(os.environ.get("DSTPU_DRAIN_TIMEOUT_S",
                                               "5"))
            except ValueError:
                timeout = 5.0
            if timeout > 0 and b.pending:
                b.drain(ticks=4, timeout_s=timeout, flush=False)

        self._remove_drain_hook = telemetry_flightrec.add_sigterm_hook(
            _drain_on_term)
        hook_remover.append(self._remove_drain_hook)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, temperature: float = 0.0,
               top_p: float = 1.0, repetition_penalty: float = 1.0,
               trace_context=None, priority: int = 0,
               deadline_ms: Optional[float] = None) -> int:
        """Queue a request; returns its uid.

        ``trace_context`` (a ``traceparent`` string, a ``{"traceparent":
        ...}`` dict, or a ``reqtrace.TraceContext``) joins this request
        to an EXISTING distributed trace — the propagation seam a
        multi-replica router uses when forwarding a request, so one
        trace id survives the process hop.  It rides the ``submit``
        lifecycle event; with no observers registered it costs
        nothing.

        With a resolved admission controller (``admission=`` /
        ``DSTPU_ADMISSION``), the request may be SHED instead of
        queued: the returned uid then never appears in the finished
        set, :attr:`rejected` maps it to the rejection reason, and a
        ``rejected`` lifecycle event + ``admission_rejected_total``
        fire.  ``priority`` (lower = more important, 0 default) orders
        the admission queue and picks shed victims; ``deadline_ms``
        bounds submit→retire (the deadline sweep retires a past-budget
        request wherever it is — queued, parked, or on a slot)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if prompt.min() < 0 or prompt.max() >= self._vocab:
            raise ValueError(
                f"prompt token ids must be in [0, {self._vocab}); got "
                f"range [{prompt.min()}, {prompt.max()}]")
        if len(prompt) + max_new_tokens > self.engine._gen_limit:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new_tokens({max_new_tokens}) "
                f"exceeds the generation limit {self.engine._gen_limit}")
        # no paged-capacity check needed here: the gen-limit guard above
        # caps any request's page chain at ceil(gen_limit/page_tokens)
        # pages, and PagedServingState's construction floor guarantees
        # the pool holds n_slots of those — a request that passes the
        # gen-limit check always fits
        if self._draining:
            return self._reject_submit("draining")
        adm = self.admission
        if adm is not None:
            depth = len(self._queue) + len(self._parked)
            # class/estimate shed FIRST: an arrival doomed either way
            # must not evict a queued victim on the way out
            reason = adm.check_submit(depth, priority, deadline_ms,
                                      self._slo_ttft_ms)
            if reason is not None:
                return self._reject_submit(reason)
            if depth >= adm.policy.max_queue_depth:
                # bounded admission queue: shed the LOWEST-priority
                # request — the arrival, unless a strictly lower-
                # priority request is already queued (evict that one,
                # admit this one)
                victim = None
                for r in self._queue:
                    if r.priority > priority and (
                            victim is None
                            or r.priority > victim.priority):
                        victim = r
                if victim is None:
                    return self._reject_submit("queue_full")
                self._queue.remove(victim)
                self._reject_queued(victim, "queue_full")
            max_new_tokens = adm.cap_max_new(max_new_tokens)
        uid = self._next_uid
        self._next_uid += 1
        req = Request(uid, prompt, max_new_tokens, temperature, top_p,
                      repetition_penalty, priority, deadline_ms)
        # the depth THIS request saw (pre-insert, queued+parked, post-
        # eviction) — the estimator's learning denominator, same basis
        # check_submit sheds against
        depth_seen = len(self._queue) + len(self._parked)
        if adm is not None:
            # priority-ordered insertion (stable within a class, FIFO
            # when every priority matches); the admission-off path
            # appends unconditionally — the pre-existing behavior
            pos = next((k for k, r in enumerate(self._queue)
                        if r.priority > priority), len(self._queue))
            self._queue.insert(pos, req)
        else:
            self._queue.append(req)
        now = time.perf_counter()
        self._t_submit[uid] = now
        if adm is not None:
            adm.note_admitted(uid, now, deadline_ms, depth=depth_seen)
        self._m_submitted.inc()
        self._note_lifecycle(uid, "submit", queued=len(self._queue),
                             **({"trace_context": trace_context}
                                if trace_context is not None else {}))
        self._update_occupancy_gauges()
        return uid

    # -- load shedding (inference/admission.py) ------------------------
    @property
    def rejected(self) -> Dict[int, str]:
        """uid → rejection reason for shed requests (bounded window)."""
        return self._rejected

    def _note_rejected(self, uid: int, reason: str, **extra) -> None:
        self._rejected[uid] = reason
        while len(self._rejected) > 8192:     # bounded, like _lat
            self._rejected.pop(next(iter(self._rejected)))
        if self.admission is not None:
            self.admission.note_rejected(reason)
        self._note_lifecycle(uid, "rejected", reason=reason, **extra)

    def _reject_submit(self, reason: str) -> int:
        """Shed at submit: the uid is allocated (the caller gets a
        handle to look up the outcome) but the request never queues."""
        uid = self._next_uid
        self._next_uid += 1
        self._note_rejected(uid, reason,
                            queued=len(self._queue) + len(self._parked))
        return uid

    def _reject_queued(self, req: Request, reason: str) -> None:
        """Shed a request that was admitted but never prefilled (queue
        eviction / expired-in-queue): same ``rejected`` outcome, plus
        the submit-side bookkeeping is unwound."""
        self._t_submit.pop(req.uid, None)
        if self.admission is not None:
            self.admission.deadlines.pop(req.uid, None)
        self._note_rejected(req.uid, reason, where="queued")

    @property
    def pending(self) -> int:
        return (len(self._queue) + len(self._parked)
                + sum(s is not None for s in self._slots))

    def _update_occupancy_gauges(self) -> None:
        """Refresh ``serving_queue_depth``/``serving_active_slots``.

        Called from EVERY path that moves a request between queue, parked
        set, slots, and finished (submit / prefill-park / place / retire /
        unslotted-finish) — not just ``submit`` — so a scrape between
        submits never reads a stale depth."""
        self._m_queue.set(len(self._queue) + len(self._parked))
        self._m_active.set(sum(s is not None for s in self._slots))
        seen_bufs, parked_bytes = set(), 0
        for entry in self._parked:
            if id(entry[1]) not in seen_bufs:     # rows share cacheB
                seen_bufs.add(id(entry[1]))
                parked_bytes += telemetry_memory.tree_bytes(entry[1])
        self._m_parked_bytes.set(float(parked_bytes))

    # -- per-request lifecycle + SLO ----------------------------------
    def add_lifecycle_observer(self, fn):
        """Register ``fn(t, uid, event, extra)`` for every request
        lifecycle event; returns a zero-arg remover.  Events: ``submit``
        (extra: queued, trace_context when propagated), ``prefill_start``
        (extra: hit_tokens/prefill_tokens/batch/batch_uids — the
        co-members sharing the batched prefill), ``first_token``,
        ``place`` (extra: slot), ``emit`` (extra: kind=decode|verify, n,
        tick — the window-END tick counter), ``retire`` (extra: n_out,
        ttft_ms, tpot_ms, slo_ok).  Per uid, ``retire`` is always the LAST
        event — a pending emit window is flushed before it — so an
        observer may finalize a request's record at retire."""
        self._lifecycle_observers.append(fn)

        def remove():
            if fn in self._lifecycle_observers:
                self._lifecycle_observers.remove(fn)
        return remove

    def _note_lifecycle(self, uid: int, event: str, **extra) -> None:
        if not self._lifecycle_observers:
            return
        t = time.perf_counter()
        for fn in list(self._lifecycle_observers):
            try:
                fn(t, uid, event, extra)
            except Exception:
                pass            # an observer must never break serving

    def set_slo(self, ttft_ms: Optional[float],
                tpot_ms: Optional[float]) -> None:
        """Configure (or clear, with None) the retire-time SLO bounds:
        TTFT = submit → first token, TPOT = first token → retirement per
        output token, both milliseconds."""
        self._slo_ttft_ms = None if ttft_ms is None else float(ttft_ms)
        self._slo_tpot_ms = None if tpot_ms is None else float(tpot_ms)

    def _active_uids(self) -> List[int]:
        return [a.req.uid for a in self._slots if a is not None]

    def _telemetry_status(self) -> dict:
        """The ``/statusz`` ``serving`` section (telemetry/exporter.py)."""
        ttfts = sorted(t for t, _ in self._lat if t == t)
        tpots = sorted(self._tpot_window)
        return {
            "n_slots": self.n_slots,
            "active_slots": sum(s is not None for s in self._slots),
            "queued": len(self._queue),
            "parked": len(self._parked),
            "pending": self.pending,
            "ticks": self._tick_no,
            "submitted": self._next_uid,
            "finished_buffered": len(self._finished),
            "prefill_ahead": self.prefill_ahead,
            "gen_limit": int(self.engine._gen_limit),
            "parked_bytes": int(self._m_parked_bytes.value),
            "prefix_cache": self.prefix_cache is not None,
            "paged_decode": self.paged is not None,
            "specdec": self.specdec is not None,
            "admission": self.admission is not None,
            "rejected": len(self._rejected),
            "draining": self._draining,
            "in_flight_uids": self._active_uids(),
            "tpot_ms": None if not self._tpot_window else round(
                sum(self._tpot_window) / len(self._tpot_window), 3),
            # tail percentiles from the SAME bounded windows the load
            # report reads, so /statusz and loadgen agree on tail latency
            "tpot_p50_ms": None if not tpots else round(
                _pct(tpots, 0.50), 3),
            "tpot_p99_ms": None if not tpots else round(
                _pct(tpots, 0.99), 3),
            "ttft_p99_ms": None if not ttfts else round(
                1e3 * _pct(ttfts, 0.99), 3),
            "slo": None if self._slo_ttft_ms is None
            and self._slo_tpot_ms is None else {
                "ttft_ms": self._slo_ttft_ms,
                "tpot_ms": self._slo_tpot_ms,
                "met": self._slo_met_n,
                "violated": self._slo_viol_n,
            },
        }

    def _note_tpot(self, wall_s: float, tokens: int) -> None:
        """One decode/verify window's per-output-token latency."""
        ms = wall_s * 1000.0 / tokens
        self._m_tpot.observe(ms)
        self._tpot_window.append(ms)

    # ------------------------------------------------------------------
    def _prefill(self, ids, cache=None, start: int = 0, uids=None,
                 donate: bool = False):
        """Prefill of ``ids`` (B, S) — B prompts of equal length — into
        ``cache`` (a fresh B-row cache when None) at positions
        ``[start, start + S)``.

        ``start > 0`` is the prefix-cache path: the cache arrives with
        its first ``start`` positions gathered from pooled pages and its
        write head already at ``start``, so only the suffix is computed.
        Positions are an ARGUMENT of the compiled prefill, so offset
        prefills reuse the same executables as the from-zero path.

        ``chunked_prefill`` feeds the prompt as DESCENDING power-of-two
        chunks (the binary decomposition of its length), so across every
        prompt length the compile cache holds at most log2(max_len)
        prefill executables per batch width instead of one per distinct
        length — each chunk appends at its exact positions, so the cache
        stays exact (no pad pollution).  Returns (last-chunk logits,
        cache).

        ``donate=True`` runs the cache-donating prefill executable — the
        page-resident path, whose cache tree carries the SHARED page
        arena: without donation every chunk would copy the whole arena
        to apply an O(chunk) append.  The caller must rebind the arena
        from the returned cache (``PagedServingState.adopt``)."""
        if chaos_mod.maybe_fire("prefill_failure") is not None:
            # injected BEFORE any chunk dispatch, so no donated buffer
            # has been consumed — the admission paths' rollback
            # (contiguous pin/unpin finally, paged abort_admit) runs
            # against intact device state, exactly like a dispatch-time
            # device fault
            raise chaos_mod.ChaosFault(
                "injected prefill failure (chaos site prefill_failure)")
        eng = self.engine
        prefill_fn = eng._compiled_prefill_donated if donate \
            else eng._compiled_prefill
        S = ids.shape[1]
        if start and cache is None:
            # an offset prefill writes at positions [start, start+S) of a
            # cache whose first ``start`` rows it assumes are already
            # populated; a fresh cache has none — decode would attend to
            # zero-filled K/V and silently produce garbage
            raise ValueError(
                f"offset prefill (start={start}) requires the cache that "
                f"already holds positions [0, {start}); pass cache=")
        # ``uids`` (the admitted requests' ids) land in the span args and
        # therefore in the flight recorder's span ring: a crash mid-
        # prefill names the requests it was admitting
        with trace.span("serve/prefill", rows=int(ids.shape[0]), len=int(S),
                        start=int(start),
                        **({"uids": list(uids)} if uids else {})):
            if cache is None:
                cache = eng.init_cache(ids.shape[0])
            self._m_prefill_tokens.inc(int(ids.shape[0]) * int(S))
            if not self.chunked_prefill:
                positions = jnp.asarray(
                    np.arange(start, start + S, dtype=np.int32))[None, :]
                return prefill_fn(eng.params, cache, ids, positions)
            pos = 0
            logits = None
            chunk = 1 << (S.bit_length() - 1)
            while chunk:
                if S & chunk:
                    seg = ids[:, pos:pos + chunk]
                    positions = jnp.asarray(np.arange(
                        start + pos, start + pos + chunk,
                        dtype=np.int32))[None, :]
                    logits, cache = prefill_fn(eng.params, cache, seg,
                                               positions)
                    pos += chunk
                chunk >>= 1
            return logits, cache

    def _prefill_batch(self, max_new: int):
        """Prefill up to ``max_new`` queued requests and PARK the results.

        Prompts at the queue head share ONE batched prefill (one compiled
        forward at (B, chunk) instead of B serial B=1 prefills — the
        round-2 serial-admission fix); the first token is sampled here, so
        TTFT lands NOW even if every slot is busy.  With
        ``chunked_prefill`` the executables are already pow2-bucketed, so
        the group is ANY run of prompts sharing a pow2 bucket: mixed
        lengths right-pad to the bucket (pads embed but are never
        attended — their K/V garbage sits past each row's rewound write
        head, see ``place_fn`` — and each row samples from its REAL last
        token's logits).  Mixed-length bursts stop degenerating into B
        serial prefills.  Without ``chunked_prefill`` only exactly-equal
        lengths group (the pre-bucketing behavior).  A request finished by
        its first token (eos or max_new_tokens<=1) completes without ever
        occupying a slot.

        With a prefix cache, the longest cached prefix is looked up per
        request and only the unmatched SUFFIX is prefilled (the matched
        pages are gathered into the cache first, write head at the match
        length).  Grouping then keys on (matched pages, suffix bucket):
        a burst sharing a system prompt matches the same pages and still
        batches into one prefill.  Reuse is exact-match only, and the
        match is capped one token short of the prompt — the real last
        token always runs through prefill to produce sampling logits.

        Page-resident mode (``self.paged``) takes
        :meth:`_prefill_batch_paged` instead: the suffix prefill writes
        STRAIGHT into freshly allocated arena pages through the
        request's page table, and the hit prefix is never copied at
        all — admission is page-ref bookkeeping.

        One ``serve/prefill-batch`` span a call (``rows`` = requests taken
        off the queue); each group's chunked forward is a ``serve/prefill``
        child carrying the group's rows, length and uids."""
        queued = len(self._queue)
        with trace.span("serve/prefill-batch", max_new=int(max_new),
                        queued=queued) as sp:
            if self.paged is not None:
                self._prefill_batch_paged(max_new)
            else:
                self._prefill_batch_contiguous(max_new)
            sp.args["rows"] = queued - len(self._queue)

    def _prefill_batch_contiguous(self, max_new: int):
        pc = self.prefix_cache
        while self._queue and max_new > 0:
            if pc is not None:
                m0, pids0, nodes0 = pc.match(self._queue[0].prompt)
            else:
                m0, pids0, nodes0 = 0, (), ()
            sfx0 = len(self._queue[0].prompt) - m0
            bucket = 1 << (sfx0 - 1).bit_length()
            bucketed = self.chunked_prefill and \
                m0 + bucket <= self.engine._gen_limit

            def same_group(r):
                if pc is not None:
                    m, pids, _ = pc.match(r.prompt)
                    if pids != pids0:
                        return False
                else:
                    m = 0
                s = len(r.prompt) - m
                if bucketed:
                    return 1 << (s - 1).bit_length() == bucket
                return s == sfx0

            reqs = [self._queue.popleft()]
            while (self._queue and len(reqs) < max_new
                   and same_group(self._queue[0])):
                reqs.append(self._queue.popleft())
            max_new -= len(reqs)
            B = len(reqs)
            # suffix lengths: with no prefix cache (or no match) the
            # suffix IS the whole prompt and everything below reduces to
            # the pre-existing path
            lens = np.asarray([len(r.prompt) - m0 for r in reqs], np.int32)
            # lifecycle: the queue→prefill boundary, with the prefix-
            # cache outcome (hit_tokens=0 ⇒ miss) — the waterfall's
            # "queued" phase ends here for every request in the group.
            # ``batch_uids`` (the co-members sharing this prefill) land
            # as request-trace span attributes; the queue-wait histogram
            # makes the submit→prefill gap scrapeable.
            t_pf = time.perf_counter()
            batch_uids = [r.uid for r in reqs]
            for row, r in enumerate(reqs):
                t_sub = self._t_submit.get(r.uid)
                if t_sub is not None:
                    self._m_queue_wait.observe((t_pf - t_sub) * 1e3)
                self._note_lifecycle(r.uid, "prefill_start",
                                     hit_tokens=int(m0),
                                     prefill_tokens=int(lens[row]),
                                     batch=B, batch_uids=batch_uids)
            cacheB = None
            try:
                if m0:
                    # matched pages → rows [0, B) of a fresh cache; pin
                    # the nodes until the copy is dispatched so eviction
                    # (driven by a donation on this thread) cannot
                    # recycle them first — unpinned in the finally so a
                    # failing prefill can't leak the pins and strand the
                    # pages unevictable
                    pc.pin(nodes0)
                    cacheB = pc.gather(self.engine.init_cache(B), pids0)
                if bucketed and (lens != lens[0]).any():
                    ids_np = np.full((B, bucket), self.pad, np.int32)
                    for row, r in enumerate(reqs):
                        ids_np[row, :lens[row]] = r.prompt[m0:]
                    logits, cacheB = self._prefill(
                        jnp.asarray(ids_np), cache=cacheB, start=m0,
                        uids=[r.uid for r in reqs])
                    # per-row REAL last-token logits (the pad positions'
                    # logits are sampling garbage)
                    last = logits[np.arange(B),
                                  np.asarray(lens) - 1][:, None]
                else:   # uniform length: exact prefill, no pad compute
                    ids = jnp.asarray(np.stack([r.prompt[m0:]
                                                for r in reqs]))
                    logits, cacheB = self._prefill(
                        ids, cache=cacheB, start=m0,
                        uids=[r.uid for r in reqs])
                    last = logits[:, -1:, :]
            except chaos_mod.ChaosFault:
                # transient admission fault (chaos site
                # prefill_failure): the group returns to the queue head
                # IN ORDER and retries next step — an injected failure
                # must never lose requests (the finally below still
                # unpins the hit chain)
                self._queue.extendleft(reversed(reqs))
                self._update_occupancy_gauges()
                return
            finally:
                if m0:
                    pc.unpin(nodes0)
            if pc is not None:
                pc.note_tokens(hit=m0 * B, miss=int(lens.sum()))
            # fixed shapes only reach the jitted sampler: the last-token
            # logits rows and a HOST-built (B, 1, V) prompt mask — so it
            # compiles once per batch width across all prompt lengths
            prompt_seen = np.zeros((B, 1, self._vocab), bool)
            for row, req in enumerate(reqs):
                prompt_seen[row, 0, req.prompt] = True
            firstB, seen1B = self._first_token_batch(
                last, jnp.asarray(prompt_seen),
                jnp.asarray([r.uid for r in reqs], jnp.int32),
                jnp.asarray([r.temperature for r in reqs], jnp.float32),
                jnp.asarray([r.top_p for r in reqs], jnp.float32),
                jnp.asarray([r.repetition_penalty for r in reqs],
                            jnp.float32))
            first_hostB = np.asarray(jax.device_get(firstB))[:, 0]
            t_first = time.perf_counter()
            for row, req in enumerate(reqs):
                self._t_first[req.uid] = t_first
                self._note_lifecycle(req.uid, "first_token")
                first_host = int(first_hostB[row])
                if first_host == self.eos or req.max_new_tokens <= 1:
                    self._finish_unslotted(req, [first_host])
                    continue
                # park the WHOLE batch by reference; _place_fn slices the
                # row on device (no eager per-row dispatches here)
                self._parked.append(
                    (req, cacheB, row, firstB, seen1B, first_host))
        self._update_occupancy_gauges()

    def _prefill_batch_paged(self, max_new: int):
        """Page-resident admission (the ``_prefill_batch`` analog): no
        ``gather_pages``, no contiguous admission cache.

        Per group (same matched pages + same suffix pow2 bucket, exactly
        the contiguous grouping rule): each request allocates its own
        pages covering ``[m0, prompt+max_new)`` (``try_admit`` — the hit
        chain is pinned for the request's lifetime), the batched suffix
        prefill applies a cache tree whose K/V leaves ARE the pool arena
        (by reference, donated — the append scatters O(suffix) rows into
        the new pages in place), and the parked entry carries only the
        sampling-side arrays: placement is bookkeeping, the K/V never
        moves again.  Page exhaustion re-queues the un-admitted tail and
        stops (backpressure; ``submit`` already rejected requests that
        could never fit)."""
        pc = self.prefix_cache
        pg = self.paged
        blocked = False
        while self._queue and max_new > 0 and not blocked:
            m0, pids0, nodes0 = pc.match(self._queue[0].prompt)
            sfx0 = len(self._queue[0].prompt) - m0
            bucket = 1 << (sfx0 - 1).bit_length()
            bucketed = self.chunked_prefill and \
                m0 + bucket <= self.engine._gen_limit

            def same_group(r):
                m, pids, _ = pc.match(r.prompt)
                if pids != pids0:
                    return False
                s = len(r.prompt) - m
                if bucketed:
                    return 1 << (s - 1).bit_length() == bucket
                return s == sfx0

            reqs = [self._queue.popleft()]
            while (self._queue and len(reqs) < max_new
                   and same_group(self._queue[0])):
                reqs.append(self._queue.popleft())
            max_new -= len(reqs)
            admitted, metas = [], []
            while reqs:
                r = reqs[0]
                if chaos_mod.maybe_fire("page_pool_exhaustion") is not None:
                    # injected empty pool: identical to a real
                    # allocation failure — the backpressure path below
                    # re-queues the tail in order
                    meta = None
                else:
                    # span covers prompt + generation; bucket-pad
                    # overshoot past it resolves to the table's trash
                    # entries
                    meta = pg.try_admit(
                        r.prompt, r.max_new_tokens, m0, nodes0, pids0,
                        span_tokens=min(len(r.prompt) + r.max_new_tokens,
                                        pg.gen_limit))
                if meta is None:
                    # out of pages even after eviction: return the tail
                    # to the queue head IN ORDER and stop admitting
                    self._queue.extendleft(reversed(reqs))
                    blocked = True
                    break
                admitted.append(reqs.pop(0))
                metas.append(meta)
            if not admitted:
                break
            B = len(admitted)
            lens = np.asarray([len(r.prompt) - m0 for r in admitted],
                              np.int32)
            t_pf = time.perf_counter()
            batch_uids = [r.uid for r in admitted]
            for row, r in enumerate(admitted):
                t_sub = self._t_submit.get(r.uid)
                if t_sub is not None:
                    self._m_queue_wait.observe((t_pf - t_sub) * 1e3)
                self._note_lifecycle(r.uid, "prefill_start",
                                     hit_tokens=int(m0),
                                     prefill_tokens=int(lens[row]),
                                     batch=B, batch_uids=batch_uids)
            # metas[:consumed] have found an owner (parked or released);
            # an exception anywhere before that — prefill, sampling, the
            # device fetch — rolls the REST back (free + unpin, NO tree
            # absorb: pre-prefill the pages hold no/partial K/V,
            # post-prefill the tree simply never learns about them), or
            # a transient flake leaks lifetime-pinned radix nodes and
            # arena pages until admission deadlocks.  The rollback
            # recovers HOST bookkeeping only: if the failure happened
            # after the prefill executable consumed the DONATED arena
            # (mid-chunk device fault), pool.pages holds dead buffers
            # and this batcher cannot continue — the except warns
            # loudly; rebuild engine+batcher (the bench _retry pattern,
            # same hazard class as the contiguous path's donated decode
            # windows).
            consumed = 0
            try:
                # the suffix-prefill cache tree: arena by reference,
                # per-row write head at m0, each request's table row
                cacheB = pg.build_cache(
                    np.full((B,), m0, np.int32),
                    np.stack([m.table_row for m in metas]))
                if bucketed and (lens != lens[0]).any():
                    ids_np = np.full((B, bucket), self.pad, np.int32)
                    for row, r in enumerate(admitted):
                        ids_np[row, :lens[row]] = r.prompt[m0:]
                    logits, cacheB = self._prefill(
                        jnp.asarray(ids_np), cache=cacheB, start=m0,
                        uids=[r.uid for r in admitted], donate=True)
                    last = logits[np.arange(B),
                                  np.asarray(lens) - 1][:, None]
                else:
                    ids = jnp.asarray(np.stack([r.prompt[m0:]
                                                for r in admitted]))
                    logits, cacheB = self._prefill(
                        ids, cache=cacheB, start=m0,
                        uids=[r.uid for r in admitted], donate=True)
                    last = logits[:, -1:, :]
                # the donated arena is dead; rebind to the returned buffers
                pg.adopt(cacheB)
                pc.note_tokens(hit=m0 * B, miss=int(lens.sum()))
                prompt_seen = np.zeros((B, 1, self._vocab), bool)
                for row, req in enumerate(admitted):
                    prompt_seen[row, 0, req.prompt] = True
                firstB, seen1B = self._first_token_batch(
                    last, jnp.asarray(prompt_seen),
                    jnp.asarray([r.uid for r in admitted], jnp.int32),
                    jnp.asarray([r.temperature for r in admitted],
                                jnp.float32),
                    jnp.asarray([r.top_p for r in admitted], jnp.float32),
                    jnp.asarray([r.repetition_penalty for r in admitted],
                                jnp.float32))
                first_hostB = np.asarray(jax.device_get(firstB))[:, 0]
                t_first = time.perf_counter()
                for row, req in enumerate(admitted):
                    self._t_first[req.uid] = t_first
                    self._note_lifecycle(req.uid, "first_token")
                    first_host = int(first_hostB[row])
                    if first_host == self.eos or req.max_new_tokens <= 1:
                        pg.finish_unslotted(metas[row], req.prompt)
                        consumed = row + 1
                        self._finish_unslotted(req, [first_host])
                        continue
                    self._parked_meta[req.uid] = metas[row]
                    consumed = row + 1
                    # no cacheB in the parked entry: the K/V already
                    # lives in the arena, owned by the meta in
                    # _parked_meta
                    self._parked.append(
                        (req, None, row, firstB, seen1B, first_host))
            except chaos_mod.ChaosFault:
                # injected prefill failure: run the REAL rollback
                # (abort_admit frees own pages + unpins the hit chain —
                # no tree absorb), then re-queue the un-consumed
                # requests and keep serving; the arena is intact (the
                # fault fires before any chunk dispatch)
                for meta in metas[consumed:]:
                    pg.abort_admit(meta)
                self._queue.extendleft(reversed(admitted[consumed:]))
                self._update_occupancy_gauges()
                return
            except Exception:
                for meta in metas[consumed:]:
                    pg.abort_admit(meta)
                if any(getattr(l, "is_deleted", lambda: False)()
                       for l in pg.pool.pages.values()):
                    logger.warning(
                        "paged admission failed AFTER the prefill "
                        "consumed the donated page arena: this batcher "
                        "cannot continue serving — rebuild the engine "
                        "and batcher before retrying")
                raise
        self._update_occupancy_gauges()

    def _record_latency(self, uid: int, n_out: int = 0) -> None:
        """Collapse a retired request's in-flight timestamps into the
        bounded (ttft, e2e) window and the registry histograms, tag the
        retirement against the configured SLO (``set_slo``), and emit
        the ``retire`` lifecycle event."""
        t_sub = self._t_submit.pop(uid, None)
        t_first = self._t_first.pop(uid, None)
        self._m_completed.inc()
        deadline_expired = uid in self._deadline_hits
        self._deadline_hits.discard(uid)
        if self.admission is not None:
            self.admission.deadlines.pop(uid, None)
        if t_sub is None:
            return
        now = time.perf_counter()
        ttft = t_first - t_sub if t_first is not None else float("nan")
        e2e = now - t_sub
        self._lat.append((ttft, e2e))
        self._m_ttft.observe(ttft)   # NaN observations are dropped
        self._m_e2e.observe(e2e)
        ttft_ms = ttft * 1e3
        # decode-phase per-output-token latency; None for single-token
        # requests (no decode phase to bound)
        tpot_ms = None
        if t_first is not None and n_out > 1:
            tpot_ms = (now - t_first) * 1e3 / (n_out - 1)
        slo_ok: Optional[bool] = None
        if self._slo_ttft_ms is not None or self._slo_tpot_ms is not None:
            slo_ok = True
            if self._slo_ttft_ms is not None and \
                    not (ttft_ms <= self._slo_ttft_ms):   # NaN violates
                slo_ok = False
                self._m_slo_viol.labels(bound="ttft").inc()
            if self._slo_tpot_ms is not None and tpot_ms is not None \
                    and tpot_ms > self._slo_tpot_ms:
                slo_ok = False
                self._m_slo_viol.labels(bound="tpot").inc()
            if slo_ok:
                self._m_slo_met.inc()
                self._slo_met_n += 1
            else:
                self._slo_viol_n += 1
        self._note_lifecycle(uid, "retire", n_out=int(n_out),
                             ttft_ms=round(ttft_ms, 3),
                             tpot_ms=None if tpot_ms is None
                             else round(tpot_ms, 4),
                             slo_ok=slo_ok,
                             **({"deadline_expired": True}
                                if deadline_expired else {}))

    def _finish_unslotted(self, req: Request, emitted: List[int]):
        self._finished[req.uid] = np.concatenate(
            [req.prompt, np.asarray(emitted, np.int32)])
        self._record_latency(req.uid, n_out=len(emitted))
        self._update_occupancy_gauges()

    def _deadline_sweep(self):
        """Retire/shed every request past its deadline, wherever the
        sweep finds it (runs at step boundaries, host bookkeeping
        only):

        - **queued** — never admitted, can no longer meet its budget:
          shed (``rejected`` outcome, reason ``deadline_expired``);
        - **parked** — its first token exists: finished unslotted with
          that partial output (paged page ownership released);
        - **on a slot** — retired with whatever it emitted, freeing the
          slot and its paged KV through the existing retire/donate
          discipline, so a long-running request past budget stops
          stealing ticks from requests that can still meet theirs.

        Slot/parked retirements tag their ``retire`` lifecycle event
        with ``deadline_expired=True``."""
        adm = self.admission
        if adm is None or not adm.deadlines:
            return
        now = time.perf_counter()
        for r in [r for r in self._queue
                  if adm.deadlines.get(r.uid, now) < now]:
            self._queue.remove(r)
            adm.note_deadline_expired(r.uid, "queued")
            self._reject_queued(r, "deadline_expired")
        shrunk = False
        for entry in [e for e in self._parked
                      if adm.deadlines.get(e[0].uid, now) < now]:
            req = entry[0]
            self._parked.remove(entry)
            shrunk = True
            adm.note_deadline_expired(req.uid, "parked")
            self._deadline_hits.add(req.uid)
            if self.paged is not None:
                meta = self._parked_meta.pop(req.uid, None)
                if meta is not None:
                    self.paged.finish_unslotted(meta, req.prompt)
            self._finish_unslotted(req, [entry[5]])
        if shrunk:
            self._shrink_parked()
        for i, act in enumerate(self._slots):
            if act is None:
                continue
            dl = adm.deadlines.get(act.req.uid)
            if dl is not None and dl < now:
                adm.note_deadline_expired(act.req.uid, "slot")
                self._deadline_hits.add(act.req.uid)
                self._retire(i)
        self._update_occupancy_gauges()

    def _admit(self):
        """Place parked (already-prefilled) requests into free slots;
        prefill directly for any remaining free capacity."""
        free = [i for i in range(self.n_slots) if self._slots[i] is None]
        if len(self._parked) < len(free):
            self._prefill_batch(len(free) - len(self._parked))
        with trace.span("serve/admit", free=len(free),
                        parked=len(self._parked)) as sp:
            sp.args["uids"] = self._place_parked(free)
            self._shrink_parked()
        self._update_occupancy_gauges()

    def _place_parked(self, free: List[int]) -> List[int]:
        """Place parked requests into the ``free`` slots (taken from the
        front); returns the uids placed."""
        placed = []
        while self._parked and free:
            req, cacheB, row, firstB, seen1B, first_host = \
                self._parked.popleft()
            i = free.pop(0)
            if self.paged is not None:
                # K/V is already page-resident: placement scatters only
                # the sampling state, then binds the slot's table row
                (self._token, self._pos, self._temp, self._top_p,
                 self._rep, self._seen, self._done) = \
                    self._paged_place_fn(
                        self._token, self._pos, self._temp, self._top_p,
                        self._rep, self._seen, self._done,
                        firstB, seen1B, row, len(req.prompt), i,
                        req.temperature, req.top_p,
                        req.repetition_penalty)
                self.paged.place(i, self._parked_meta.pop(req.uid))
            else:
                (self._cache, self._token, self._pos, self._temp,
                 self._top_p, self._rep, self._seen, self._done) = \
                    self._place_fn(
                        self._cache, self._token, self._pos, self._temp,
                        self._top_p, self._rep, self._seen, self._done,
                        cacheB, firstB, seen1B, row, len(req.prompt), i,
                        req.temperature, req.top_p,
                        req.repetition_penalty)
            self._slots[i] = _Active(req, [first_host])
            self._note_lifecycle(req.uid, "place", slot=i)
            placed.append(req.uid)
        return placed

    def _shrink_parked(self):
        """Release B-row prefill buffers that only one parked row still
        pins: parked entries hold their batch's cache BY REFERENCE, so the
        whole B-row cache (B gen-limit KV caches of HBM) stays live until
        its last row places.  Once a batch is down to ONE pending row,
        slice that row into a standalone 1-row cache and drop the batch
        reference — worst-case parked residency falls from B rows to 1
        per drained batch.  (One extra device dispatch per batch, paid
        only when B > 1.)"""
        if self.paged is not None:
            # paged parked entries hold no cacheB — their K/V is arena-
            # resident; only the small (B, 1[, V]) sampling arrays park
            return
        refs: Dict[int, int] = {}
        for entry in self._parked:
            refs[id(entry[1])] = refs.get(id(entry[1]), 0) + 1
        for idx, entry in enumerate(self._parked):
            req, cacheB, row, firstB, seen1B, first_host = entry
            if refs[id(cacheB)] == 1 and int(firstB.shape[0]) > 1:
                cache1, first1, seen1 = self._extract_row_fn(
                    cacheB, firstB, seen1B, row)
                self._parked[idx] = (req, cache1, 0, first1, seen1,
                                     first_host)

    def _retire(self, i: int):
        with trace.span("serve/retire", slot=int(i),
                        uids=[self._slots[i].req.uid]):
            self._retire_slot(i)

    def _retire_slot(self, i: int):
        act = self._slots[i]
        self._finished[act.req.uid] = np.concatenate(
            [act.req.prompt, np.asarray(act.emitted, np.int32)])
        self._record_latency(act.req.uid, n_out=len(act.emitted))
        self._slots[i] = None
        if self.paged is not None:
            # zero-copy retirement: the prompt pages ATTACH to the radix
            # tree by reference (absorb), the rest free; the device side
            # is untouched — next window's table/lengths simply stop
            # naming this slot
            self.paged.retire_slot(i, act.req.prompt)
            self._done, self._pos = self._paged_retire_fn(
                self._done, self._pos, i)
            self._update_occupancy_gauges()
            return
        if self.prefix_cache is not None:
            # donate the prompt-prefix pages BEFORE retire_fn: retire
            # donates the cache buffer to XLA, and the copy must read
            # slot i's prompt region first (dispatch order guarantees
            # it).  The region is intact — decode only ever writes at
            # positions >= prompt_len, overshoot writes clamp at the
            # cache edge, and both stay past the prefix.
            self.prefix_cache.donate(self._cache, i, act.req.prompt)
        self._done, self._pos, self._cache = self._retire_fn(
            self._done, self._pos, self._cache, i)
        self._update_occupancy_gauges()

    # ------------------------------------------------------------------
    def _spec_tick(self, greedy: bool) -> bool:
        """One speculative verify tick: draft on host, verify every slot
        in ONE batched forward, append/retire the accepted tokens.

        Per-slot proposals are capped at ``min(k, remaining-1,
        cache headroom)`` and the pool verify width is the pow2 round-up
        of the longest real proposal, clamped to the TIGHTEST slot's
        cache headroom — the verify forward writes ``w+1`` K/V rows into
        EVERY slot's cache (dynamic_update_slice clamps the chunk START,
        so an oversized chunk near the cache edge would overwrite valid
        history, unlike the single-token overshoot which only clamps
        past it).  Returns False when no slot drafted (the caller runs a
        plain window instead — a silent drafter costs nothing)."""
        spec = self.specdec
        k = spec.cfg.k
        limit = int(self.engine._gen_limit)
        props: List[np.ndarray] = [np.empty((0,), np.int32)] * self.n_slots
        pool_cap: Optional[int] = None
        for i, act in enumerate(self._slots):
            if act is None:
                continue
            # pos_i = the position of the slot's last emitted token (the
            # next input); the verify chunk occupies [pos_i, pos_i + w]
            pos_i = len(act.req.prompt) + len(act.emitted) - 1
            cap_i = limit - pos_i - 1
            pool_cap = cap_i if pool_cap is None else min(pool_cap, cap_i)
        if not pool_cap or pool_cap <= 0:
            return False
        for i, act in enumerate(self._slots):
            if act is None:
                continue
            r = act.req.max_new_tokens - len(act.emitted)
            cap = min(k, pool_cap, r - 1)   # drafts past r-1 are wasted:
            if cap <= 0:                    # the bonus token is the r-th
                continue
            ctx = np.concatenate([act.req.prompt,
                                  np.asarray(act.emitted, np.int32)])
            try:
                if chaos_mod.maybe_fire("drafter_exception") is not None:
                    raise chaos_mod.ChaosFault(
                        "injected drafter failure "
                        "(chaos site drafter_exception)")
                p = np.asarray(spec.drafter.propose(ctx, cap),
                               np.int32).reshape(-1)[:cap]
            except Exception as e:
                # a crashing drafter degrades to an empty proposal (the
                # slot takes plain ticks; all-empty falls back to a
                # plain window) — drafting is an optimization, never a
                # correctness dependency the serve loop may die on
                logger.warning(
                    f"specdec drafter "
                    f"{getattr(spec.drafter, 'name', '?')} raised "
                    f"{e!r}; slot {i} degrades to plain decode")
                p = np.empty((0,), np.int32)
            bad = (p < 0) | (p >= self._vocab)
            if bad.any():   # a buggy drafter must not poison the embed
                p = p[:int(np.argmax(bad))]
            props[i] = p
        if max(len(p) for p in props) == 0:
            spec.note_empty()
            return False
        w = 1 << (max(len(p) for p in props) - 1).bit_length()
        if w > pool_cap:   # pow2 round-up may not breach the cache bound
            w = 1 << (pool_cap.bit_length() - 1)
            props = [p[:w] for p in props]
        # tally AFTER the clamp: a truncated proposal's dropped tokens
        # were never verified, so counting them would report phantom
        # misses and bias the controller's EWMA toward cooldown
        drafted = sum(len(p) for p in props)
        # padded draft entries can only ACCEPT when the target's own
        # token happens to equal the pad — correct by construction, and
        # excluded from the drafted/accepted accounting below
        drafts_np = np.full((self.n_slots, w), self.pad, np.int32)
        for i, p in enumerate(props):
            drafts_np[i, :len(p)] = p
        t_window = time.perf_counter()
        verify_fn = spec.verify_step(int(w), greedy)
        with trace.span("serve/verify-tick", width=int(w),
                        active=sum(s is not None for s in self._slots),
                        uids=self._active_uids()):
            toks, n_emit, self._cache, self._token, self._pos, \
                self._seen, self._done = verify_fn(
                    self.engine.params, self._cache, self._token,
                    self._pos, np.arange(self.n_slots), self._temp,
                    self._top_p, self._rep, self._seen, self._done,
                    jnp.asarray(drafts_np), jnp.int32(self._tick_no),
                    jnp.int32(self.eos), jnp.int32(self.pad))
            self._tick_no += 1
            tok_h = np.asarray(jax.device_get(toks))   # (slots, w+1)
            n_h = np.asarray(jax.device_get(n_emit))   # (slots,)
        self._m_ticks.inc(1)
        appended = 0
        accepted_total = 0
        per_slot: List[int] = []
        for i in range(self.n_slots):
            act = self._slots[i]
            if act is None:
                continue
            n_i = int(n_h[i])
            acc_i = min(max(0, n_i - 1), len(props[i]))
            per_slot.append(acc_i)
            accepted_total += acc_i
            emitted_i = 0
            retire_slot = False
            for t in range(n_i):
                tokv = int(tok_h[i, t])
                act.emitted.append(tokv)
                appended += 1
                emitted_i += 1
                if (self.eos >= 0 and tokv == self.eos) or \
                        len(act.emitted) >= act.req.max_new_tokens:
                    retire_slot = True
                    break
            # emit precedes retire — observers may treat retire as
            # terminal for the uid
            if emitted_i:
                self._note_lifecycle(act.req.uid, "emit", kind="verify",
                                     n=emitted_i, tick=self._tick_no)
            if retire_slot:
                self._retire(i)
        if appended:
            self._note_tpot(time.perf_counter() - t_window, appended)
        spec.note_verify(drafted, accepted_total, per_slot)
        return True

    # ------------------------------------------------------------------
    def step(self, ticks: int = 1) -> Dict[int, np.ndarray]:
        """Admit, decode up to ``ticks`` ticks, retire finished slots.

        TTFT-oriented scheduling (round-3 verdict: requests waited out
        whole windows, p50 TTFT = seconds): with waiters present the
        window splits at the next CERTAIN retirement (a slot reaching its
        max_new_tokens) so freed slots refill immediately, and queued
        requests are prefilled ahead (``_prefill_batch``) so their first
        token — the TTFT clock-stop — is produced while slots are still
        busy.  Sub-window lengths round down to powers of two, so the
        executable cache stays at log2(ticks) entries instead of one per
        distinct remaining-token count (each compile costs seconds).
        With no waiters the full window runs in one
        round trip exactly as before — the idle-path throughput is
        untouched.  EOS retirements are only observed at sub-window
        boundaries (the done flag freezes the slot on device, so padding
        is discarded, not mis-emitted).

        With a resolved speculative decoder (``specdec=``), iterations
        take batched verify ticks in place of plain windows while the
        acceptance controller allows: a verify tick counts as ONE tick
        against ``ticks`` but may emit up to k+1 tokens per slot.
        Returns {uid: full token array} for requests completed during
        this call.

        Host spans: one ``serve/step`` with children ``serve/admit``
        (placing parked requests into free slots), ``serve/prefill-batch``
        (grouping and prefilling queued requests; its chunk calls are
        ``serve/prefill``), ``serve/decode-tick`` (a window's dispatch,
        and inside it ``serve/fetch``: the token fetch that fences the
        window) and ``serve/retire``."""
        if ticks < 1:
            raise ValueError(f"ticks must be >= 1, got {ticks}")
        with trace.span("serve/step", ticks=int(ticks),
                        queued=len(self._queue), parked=len(self._parked)):
            return self._step(ticks)

    def _step(self, ticks: int) -> Dict[int, np.ndarray]:
        before = set(self._finished)
        remaining = int(ticks)
        # the SIGTERM drain hook must not re-enter a half-advanced
        # step; the finally guarantees an exception escaping a
        # window can never permanently disable graceful drain
        self._in_step = True
        try:
            while remaining > 0:
                if self.admission is not None:
                    # ladder evaluation (throttled ~1/s) + the deadline
                    # sweep: expired slots free BEFORE admission so their
                    # capacity is reusable this very step
                    self.admission.maybe_step()
                    self._deadline_sweep()
                self._admit()
                if self.prefill_ahead and self._queue:
                    self._prefill_batch(
                        self.prefill_ahead - len(self._parked))
                active = [a for a in self._slots if a is not None]
                self._update_occupancy_gauges()
                if not active:
                    break
                greedy = all(a.req.temperature <= 0.0 for a in active)
                # speculative verify tick (inference/specdec.py): one drafted
                # k-wide verify forward in place of this iteration's window;
                # counts as ONE tick.  _spec_tick returns False when no slot
                # produced a draft — fall through to a plain window (k=0
                # degenerates gracefully, never a wasted verify dispatch).
                if self.specdec is not None and self.specdec.active() and \
                        (self.admission is None
                         or self.admission.allow_specdec()) and \
                        self._spec_tick(greedy):
                    remaining -= 1
                    continue
                sub = remaining
                if self._queue or self._parked:
                    t2r = min(a.req.max_new_tokens - len(a.emitted)
                              for a in active)
                    sub = max(1, min(remaining, t2r))
                    if sub & (sub - 1):
                        # pow2 windows keep the executable cache bounded; round
                        # UP, not down: overshoot ticks decode discarded pads
                        # (~ms each) while every extra window costs a full
                        # host round-trip (rounding 63 down fragmented it
                        # into six windows).
                        # Cap at the largest pow2 <= remaining so every window
                        # stays a warmed-up pow2 executable.  A slot past its
                        # max_new_tokens keeps decoding until the boundary;
                        # its cache writes clamp at the cache edge, corrupting
                        # only its own finished (discarded) row, which
                        # placement fully overwrites.
                        sub = min(1 << sub.bit_length(),
                                  1 << (remaining.bit_length() - 1))
                slot_ids = np.arange(self.n_slots)
                fault = chaos_mod.maybe_fire("slow_tick")
                if fault is not None:
                    # a straggler device / preempted core: the window
                    # stalls, every queued request's TTFT clock keeps
                    # running — the input that drives real slo_burn
                    time.sleep(fault.arg if fault.arg is not None else 0.05)
                t_window = time.perf_counter()
                with trace.span("serve/decode-tick", ticks=int(sub),
                                active=len(active),
                                uids=self._active_uids()):
                    if self.paged is not None:
                        # one BATCHED forward over the arena-backed paged
                        # cache tree; the arena rides in donated and comes
                        # back rebound (adopt).  note_window mirrors the
                        # on-device head advance into the host lengths.
                        toks, cache, self._token, self._pos, self._seen, \
                            done = self._paged_multi_step(int(sub), greedy)(
                                self.engine.params, self.paged.decode_cache(),
                                self._token, self._pos, slot_ids, self._temp,
                                self._top_p, self._rep, self._seen,
                                self._done, jnp.int32(self._tick_no),
                                jnp.int32(self.eos), jnp.int32(self.pad))
                        self.paged.adopt(cache)
                        self.paged.note_window(int(sub))
                    else:
                        toks, self._cache, self._token, self._pos, \
                            self._seen, done = self._multi_step(
                                int(sub), greedy)(
                                self.engine.params, self._cache, self._token,
                                self._pos, slot_ids, self._temp, self._top_p,
                                self._rep, self._seen, self._done,
                                jnp.int32(self._tick_no), jnp.int32(self.eos),
                                jnp.int32(self.pad))
                    self._tick_no += int(sub)
                    self._done = done
                    # the fetch is part of the tick's host wall time:
                    # it waits for the window the dispatch above enqueued
                    with trace.span("serve/fetch", ticks=int(sub)):
                        tok_h = np.asarray(jax.device_get(toks))[:, :, 0]
                self._m_ticks.inc(int(sub))
                appended = 0
                emitted_by_uid: Dict[int, int] = {}
                for t in range(int(sub)):
                    for i, act in enumerate(self._slots):
                        if act is None:
                            continue
                        tokv = int(tok_h[t, i])
                        act.emitted.append(tokv)
                        appended += 1
                        if self._lifecycle_observers:
                            emitted_by_uid[act.req.uid] = \
                                emitted_by_uid.get(act.req.uid, 0) + 1
                        if (self.eos >= 0 and tokv == self.eos) or \
                                len(act.emitted) >= act.req.max_new_tokens:
                            # flush this request's emit BEFORE retire —
                            # observers may treat retire as terminal
                            n_emit = emitted_by_uid.pop(act.req.uid, 0)
                            if n_emit:
                                self._note_lifecycle(act.req.uid, "emit",
                                                     kind="decode", n=n_emit,
                                                     tick=self._tick_no)
                            self._retire(i)
                if self._lifecycle_observers:
                    for uid, n_emit in emitted_by_uid.items():
                        self._note_lifecycle(uid, "emit", kind="decode",
                                             n=n_emit, tick=self._tick_no)
                if appended:
                    self._note_tpot(time.perf_counter() - t_window, appended)
                if self.specdec is not None:
                    self.specdec.note_plain(int(sub))
                remaining -= int(sub)
        finally:
            self._in_step = False
        in_flight = self._active_uids()
        # /healthz last-step age; the in-flight uids ride the flight
        # recorder's counter-delta context so a postmortem names the
        # requests that were on the pool when the process died
        goodput.note_step("serving",
                          context={"uids": in_flight} if in_flight else None)
        new = {u: self._finished[u] for u in self._finished if u not in before}
        return new

    def leak_counts(self) -> Dict[str, int]:
        """Resources still owned by in-flight requests: occupied slots,
        parked entries, and (paged mode) arena pages owned by
        parked/active requests.  All three must be zero after a
        completed drain or a finished trace — the ONE leak-check seam
        ``drain()`` and the chaos harness's post-trace assertion share,
        so a bookkeeping change cannot silently split them."""
        return {
            "slots": sum(s is not None for s in self._slots),
            "parked": len(self._parked),
            "pages": 0 if self.paged is None
            else int(self.paged._slot_pages_n),
        }

    def _live_uids(self) -> set:
        """Every uid that can still make progress (queued, parked, or
        on a slot)."""
        live = {r.uid for r in self._queue}
        live.update(e[0].uid for e in self._parked)
        live.update(a.req.uid for a in self._slots if a is not None)
        return live

    def wait(self, uids=None, *, ticks: int = 4,
             timeout_s: Optional[float] = None,
             max_ticks: Optional[int] = None,
             partial: bool = False) -> Dict[int, np.ndarray]:
        """Step until every requested uid reaches a TERMINAL state
        (finished or rejected); returns {uid: tokens} for the finished
        ones.  ``uids=None`` waits for everything currently in flight.

        Replaces the unbounded busy-spin callers used to write by hand
        (``while uid not in finished: step()``), which deadlocks the
        moment a uid was shed or can otherwise never finish.  Guards:

        - a uid that is neither finished, nor rejected, nor live in the
          batcher can NEVER complete → ``RuntimeError`` immediately
          (with ``partial=True``: return what finished instead);
        - ``timeout_s`` / ``max_ticks`` bound the wait →
          ``TimeoutError`` naming the unfinished uids (or the partial
          result with ``partial=True``);
        - rejected uids are a terminal outcome, not an error: they are
          simply absent from the returned dict (``rejected`` maps them
          to the shed reason)."""
        targets = list(self._live_uids()) if uids is None else list(uids)
        t0 = time.perf_counter()
        ticks_done = 0
        while True:
            outstanding = [u for u in targets if u not in self._finished
                           and u not in self._rejected]
            if not outstanding:
                break
            live = self._live_uids()
            dead = [u for u in outstanding if u not in live]
            if dead:
                if partial:
                    break
                raise RuntimeError(
                    f"uids {dead} are neither pending nor finished nor "
                    f"rejected — they can never complete (unknown uid, "
                    f"or state lost); pass partial=True to collect "
                    f"what did finish")
            if timeout_s is not None and \
                    time.perf_counter() - t0 >= timeout_s:
                if partial:
                    break
                raise TimeoutError(
                    f"wait(timeout_s={timeout_s}) expired with "
                    f"{len(outstanding)} unfinished uids "
                    f"{outstanding[:8]}")
            if max_ticks is not None and ticks_done >= max_ticks:
                if partial:
                    break
                raise TimeoutError(
                    f"wait(max_ticks={max_ticks}) exhausted with "
                    f"{len(outstanding)} unfinished uids "
                    f"{outstanding[:8]}")
            self.step(ticks=ticks)
            ticks_done += int(ticks)
        return {u: self._finished[u] for u in targets
                if u in self._finished}

    def cancel(self, uid: int) -> str:
        """Best-effort cancel (the ``/cancel`` route of the per-replica
        serve endpoint).  A queued request is shed (``rejected``
        outcome, reason ``cancelled``); a parked or slotted request is
        finished IMMEDIATELY with its partial output through the
        normal retire/donate discipline (slot freed, paged KV
        returned) — the drain-force semantics, per request.  Returns
        one of ``cancelled`` / ``finished_partial`` / ``done`` /
        ``rejected`` (already terminal) / ``unknown``."""
        if uid in self._finished:
            return "done"
        if uid in self._rejected:
            return "rejected"
        for r in self._queue:
            if r.uid == uid:
                self._queue.remove(r)
                self._reject_queued(r, "cancelled")
                self._update_occupancy_gauges()
                return "cancelled"
        for entry in list(self._parked):
            if entry[0].uid == uid:
                self._parked.remove(entry)
                if self.paged is not None:
                    meta = self._parked_meta.pop(uid, None)
                    if meta is not None:
                        self.paged.finish_unslotted(meta, entry[0].prompt)
                self._finish_unslotted(entry[0], [entry[5]])
                self._shrink_parked()
                return "finished_partial"
        for i, act in enumerate(self._slots):
            if act is not None and act.req.uid == uid:
                self._retire(i)
                self._update_occupancy_gauges()
                return "finished_partial"
        return "unknown"

    def run(self, prompts, ticks: int = 1,
            timeout_s: Optional[float] = None,
            **gen_kwargs) -> List[Optional[np.ndarray]]:
        """Convenience: submit every prompt, step until drained, return
        outputs in submission order (``None`` for a request the
        admission controller shed — impossible with admission off, so
        the historical all-arrays return type is unchanged there)."""
        uids = [self.submit(p, **gen_kwargs) for p in prompts]
        self.wait(uids, ticks=ticks, timeout_s=timeout_s)
        return [self._finished.get(u) for u in uids]

    def drain(self, *, ticks: int = 8, timeout_s: Optional[float] = None,
              flush: bool = True) -> dict:
        """Graceful shutdown: stop admitting, finish in-flight work,
        release every resource, flush forensics — the replica-restart
        building block (SIGTERM in a flight-recorder-armed process runs
        this automatically before the flight dump).

        - new ``submit`` calls shed (``rejected`` outcome, reason
          ``draining``) from the moment drain starts;
        - queued/parked/slotted requests run to completion (or their
          deadline) within ``timeout_s``; past the timeout the
          remainder is FORCED out — queued requests shed
          (``drain_timeout``), parked/slotted requests finished with
          their partial output — so the batcher always ends with zero
          leaked pages and zero occupied slots (paged KV refs return
          to the radix tree through the normal retire/donate
          discipline);
        - ``flush`` writes the flight dump (reason ``drain``) and the
          per-rank metrics exit dump, so a rolling restart keeps the
          replica's final state.

        Returns a summary dict (wall_s, completed, forced, leaks)."""
        t0 = time.perf_counter()
        self._draining = True
        done0 = len(self._finished)
        while self.pending:
            if timeout_s is not None and \
                    time.perf_counter() - t0 >= timeout_s:
                break
            self.step(ticks=ticks)
        forced = 0
        # graceful completions only: the force block below ALSO lands
        # requests in _finished, and reporting those as "completed"
        # would tell an operator a timed-out drain finished cleanly
        completed = len(self._finished) - done0
        if self.pending:
            for r in list(self._queue):
                self._queue.remove(r)
                self._reject_queued(r, "drain_timeout")
                forced += 1
            for entry in list(self._parked):
                req = entry[0]
                self._parked.remove(entry)
                if self.paged is not None:
                    meta = self._parked_meta.pop(req.uid, None)
                    if meta is not None:
                        self.paged.finish_unslotted(meta, req.prompt)
                self._finish_unslotted(req, [entry[5]])
                forced += 1
            for i, act in enumerate(self._slots):
                if act is not None:
                    self._retire(i)
                    forced += 1
        self._update_occupancy_gauges()
        summary = {
            "wall_s": round(time.perf_counter() - t0, 4),
            "completed": completed,
            "forced": forced,
            **{f"leaked_{k}": v for k, v in self.leak_counts().items()},
        }
        if flush:
            try:
                self.latency_stats()     # refresh the percentile gauges
            except Exception:
                pass
            telemetry_flightrec.dump("drain")
            telemetry_registry.flush_exit_dump()
        logger.info(f"batcher drained: {summary}")
        return summary

    def warmup_windows(self, ticks: int, greedy: bool = True,
                       admission: bool = True) -> None:
        """AOT-compile every pow2 sub-window executable ≤ ``ticks``.

        Sub-window scheduling picks pow2 window lengths; without this,
        the first occurrence of each length compiles INSIDE the serving
        path (seconds per compile).  Feeds the XLA
        compilation cache, so the serving-path jit resolves quickly.
        ``greedy`` picks the sampler variant to warm (the all-greedy pool
        executable by default; a pool with any sampled request lazily
        compiles the general variant on first use — call again with
        ``greedy=False`` to pre-warm it too).

        ``admission=True`` additionally warms the admission-side
        executables — ``serving.first_token`` / ``serving.place`` /
        ``serving.extract_row`` at the common batch widths (1 and
        ``n_slots``): those compile per parked-batch width, and without
        the warmup the FIRST burst pays all three compiles inside TTFT
        (the decode windows alone left seconds of admission compile in
        the measured first-token path)."""
        s = 1
        while s <= int(ticks):
            if self.paged is not None:
                compiled = self._paged_multi_step(s, greedy).lower(
                    self.engine.params, self.paged.decode_cache(),
                    self._token, self._pos, np.arange(self.n_slots),
                    self._temp, self._top_p, self._rep, self._seen,
                    self._done, jnp.int32(0), jnp.int32(self.eos),
                    jnp.int32(self.pad)).compile()
                site = f"serving.decode_paged[{s}{'g' if greedy else 's'}]"
            else:
                compiled = self._multi_step(s, greedy).lower(
                    self.engine.params, self._cache, self._token,
                    self._pos, np.arange(self.n_slots), self._temp,
                    self._top_p, self._rep, self._seen, self._done,
                    jnp.int32(0), jnp.int32(self.eos),
                    jnp.int32(self.pad)).compile()
                site = f"serving.decode[{s}{'g' if greedy else 's'}]"
            # the AOT compile is the one place a Compiled handle exists:
            # publish its per-device HBM breakdown (telemetry/memory.py)
            telemetry_memory.record_compiled(compiled, site=site)
            s <<= 1
        if admission:
            self._warmup_admission()

    def _warmup_admission(self) -> None:
        """Pre-compile the admission executables for batch widths 1 and
        ``n_slots``.  Scalar args mirror the live call sites exactly
        (python ints/floats → weak-typed scalars; a strongly-typed dummy
        would compile a DIFFERENT executable and the warmup would miss).
        """
        V = self._vocab
        dtype = self.engine.model_cfg.dtype
        sds = jax.ShapeDtypeStruct
        for B in sorted({1, self.n_slots}):
            # abstract operands only: .lower() needs shapes, and a real
            # init_cache(B) would zero-fill a full B-row KV cache in HBM
            # just to compile
            logits = sds((B, 1, V), dtype)
            seen = sds((B, 1, V), jnp.bool_)
            uids = sds((B,), jnp.int32)
            f32 = sds((B,), jnp.float32)
            telemetry_memory.record_compiled(
                self._first_token_batch.lower(
                    logits, seen, uids, f32, f32, f32).compile(),
                site=f"serving.first_token[{B}]")
            firstB = sds((B, 1), jnp.int32)
            if self.paged is not None:
                # no cacheB operands in paged placement (no admission
                # cache exists); extract_row never runs either
                telemetry_memory.record_compiled(
                    self._paged_place_fn.lower(
                        self._token, self._pos, self._temp, self._top_p,
                        self._rep, self._seen, self._done,
                        firstB, seen, 0, 1, 0, 0.0, 1.0, 1.0).compile(),
                    site=f"serving.place_paged[{B}]")
                continue
            cacheB = jax.eval_shape(lambda: self.engine.init_cache(B))
            telemetry_memory.record_compiled(
                self._place_fn.lower(
                    self._cache, self._token, self._pos, self._temp,
                    self._top_p, self._rep, self._seen, self._done,
                    cacheB, firstB, seen, 0, 1, 0, 0.0, 1.0, 1.0).compile(),
                site=f"serving.place[{B}]")
            if B > 1:
                telemetry_memory.record_compiled(
                    self._extract_row_fn.lower(
                        cacheB, firstB, seen, 0).compile(),
                    site=f"serving.extract_row[{B}]")
        # retire is the remaining admission-side executable: lower it
        # abstractly too (donation never fires — lower/compile do not
        # execute)
        if self.paged is not None:
            telemetry_memory.record_compiled(
                self._paged_retire_fn.lower(
                    self._done, self._pos, 0).compile(),
                site="serving.retire_paged")
        else:
            telemetry_memory.record_compiled(
                self._retire_fn.lower(
                    self._done, self._pos, self._cache, 0).compile(),
                site="serving.retire")

    # ------------------------------------------------------------------
    def reset_latency_stats(self) -> None:
        """Drop the finished-request latency window (e.g. after warm-up,
        so compile-time TTFTs stay out of a measurement)."""
        self._lat.clear()

    def latency_stats(self) -> Dict[str, float]:
        """Per-request latency percentiles over the retired-request
        window (last ≤4096): ``ttft`` (submit → first token on host,
        covers queueing + prefill) and ``e2e`` (submit → retirement),
        seconds; plus decode-window TPOT percentiles (ms per output
        token, from the same bounded window ``/statusz`` reads)."""
        ttfts = sorted(t for t, _ in self._lat if t == t)
        e2es = sorted(e for _, e in self._lat)
        tpots = sorted(self._tpot_window)

        stats = {"n": len(self._lat),
                 "ttft_p50_s": _pct(ttfts, 0.50),
                 "ttft_p90_s": _pct(ttfts, 0.90),
                 "ttft_p99_s": _pct(ttfts, 0.99),
                 "e2e_p50_s": _pct(e2es, 0.50),
                 "e2e_p90_s": _pct(e2es, 0.90),
                 "e2e_p99_s": _pct(e2es, 0.99),
                 "tpot_p50_ms": _pct(tpots, 0.50),
                 "tpot_p99_ms": _pct(tpots, 0.99)}
        # mirror the percentile view into the registry (histograms carry
        # the full distributions; these gauges are the human-named cut)
        for key, value in stats.items():
            if key != "n" and value == value:
                telemetry_registry.gauge(
                    f"serving_{key}", "latency percentile snapshot"
                ).set(value)
        return stats
