"""Speculative decoding: pluggable drafters + batched verify ticks.

Decode is memory-bandwidth-bound — every output token costs one full
forward pass over the weights.  Speculative decoding (Leviathan et al.
2023; Chen et al. 2023) turns ``k`` cheap *draft* tokens plus ONE
batched verify forward into up to ``k + 1`` accepted tokens with
provably unchanged outputs: greedy mode is argmax-exact token-for-token,
sampled mode uses rejection sampling that preserves the target
distribution.

Pieces, TPU-native:

- **Drafters** (host side, pluggable): :class:`NGramDrafter` is
  prompt-lookup decoding — propose the continuation of the most recent
  prior occurrence of the context's suffix n-gram (no second model, no
  device work, CPU-mesh testable; shines on repetitive/extractive
  text).  :class:`DraftModelDrafter` wraps a small
  :class:`~.engine.InferenceEngine` and proposes its greedy
  continuation.  Anything with ``propose(context, k) -> np.ndarray``
  plugs in.

- **Verify step** (device side): a jitted, slot-vmapped forward that
  feeds each slot's last token plus its ``w`` drafts as ONE ``(1, w+1)``
  chunk through the decode model (the same cached multi-token path
  chunked prefill rides), then runs the accept chain on device: per row,
  the target's own token is computed with the batcher's exact sampler
  semantics (repetition penalty + ``seen`` mask threaded token by
  token), drafts are accepted while they match (greedy) or pass the
  rejection test (sampled), and the first divergence emits the target's
  correction token — so every verify tick emits between 1 and ``w + 1``
  tokens.  ``cache_index`` and ``pos`` rewind to the accepted length via
  :func:`~..models.common.set_cache_index` (the same
  ``cache_leaf_kind`` rewind discipline placement/retire use), so
  rejected drafts' K/V rows are simply overwritten by the next tick.
  Executables are memoized per ``(pow2 draft width, greedy)`` — the
  decode-window discipline, bounded at ``log2(k)`` entries per sampler
  variant.

- **Controller**: an acceptance-rate EWMA.  When recent acceptance
  drops below ``min_accept``, speculation enters a ``cooldown`` of
  plain decode ticks (graceful degradation — a misconfigured drafter
  costs a bounded number of wasted verify ticks, never a permanently
  slower pool), then retries.

Off by default: a batcher without a resolved SpecDecoder takes
byte-for-byte the pre-existing decode path.  Enable per call
(``ContinuousBatcher(..., specdec=...)``), per engine
(``init_inference(specdec=True | {...})``) or process-wide with
``DSTPU_SPECDEC=1`` (``0`` force-disables over any config; ``1`` never
overrides an explicit ``False`` — the
:func:`~.kvreuse.resolve_prefix_cache` precedence).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import common as model_common
from ..telemetry import recompile, registry as telemetry_registry
from ..utils.logging import logger
from .engine import (InferenceEngine, _filtered_logits, _penalized_logits,
                     _sample)

__all__ = ["NGramDrafter", "DraftModelDrafter", "SpecDecodeConfig",
           "SpecDecoder", "resolve_specdec", "SPECDEC_ENV"]

SPECDEC_ENV = "DSTPU_SPECDEC"


# accepted drafts per slot per verify tick land in [0, k]; the schema is
# declared ONCE in registry.BUCKET_SCHEMAS (fleet bucket-wise merge
# asserts one layout per family)


# ---------------------------------------------------------------------------
# Drafters (host side)
# ---------------------------------------------------------------------------

class NGramDrafter:
    """Prompt-lookup drafter: no second model, pure host work.

    Proposes the tokens that followed the most recent PRIOR occurrence
    of the context's suffix n-gram, trying ``max_ngram`` down to
    ``min_ngram`` (longer matches first — they predict better).  Returns
    an empty proposal when no suffix recurs; the batcher then takes a
    plain decode tick for free.
    """

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"{min_ngram}/{max_ngram}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        context = np.asarray(context, np.int32).reshape(-1)
        L = len(context)
        if k <= 0 or L < self.min_ngram + 1:
            return np.empty((0,), np.int32)
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            suffix = context[L - n:]
            # windows at starts [0, L-n]; the last IS the suffix — exclude
            windows = np.lib.stride_tricks.sliding_window_view(context, n)
            hits = np.nonzero((windows[:-1] == suffix).all(axis=1))[0]
            if hits.size:
                p = int(hits[-1])          # most recent prior occurrence
                return context[p + n:p + n + k].astype(np.int32)
        return np.empty((0,), np.int32)


class DraftModelDrafter:
    """Drafter wrapping a small :class:`~.engine.InferenceEngine`: the
    draft model's greedy ``k``-token continuation of the context.

    Reference implementation: every ``propose`` prefills the (truncated)
    context through the draft engine's compiled ``generate`` — exact and
    CPU-mesh testable, but the draft prefill cost recurs per verify tick
    and each distinct context length compiles a draft prefill
    executable.  Production drafting wants a persistent draft-side KV
    cache; until then prefer :class:`NGramDrafter` unless the draft
    model is tiny relative to the target.  Draft quality only affects
    ACCEPTANCE, never correctness — the verify step rejects anything
    the target would not have produced.
    """

    name = "draft_model"

    def __init__(self, engine: InferenceEngine):
        if engine.params is None:
            raise RuntimeError("draft engine has no parameters loaded")
        self.engine = engine
        cfg = engine.decode_cfg
        self._vocab = int(getattr(cfg, "padded_vocab_size", None)
                          or cfg.vocab_size)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        if k <= 0:
            return np.empty((0,), np.int32)
        ctx = np.asarray(context, np.int32).reshape(-1)
        if ctx.size == 0 or ctx.max() >= self._vocab or ctx.min() < 0:
            return np.empty((0,), np.int32)   # outside the draft vocab
        # keep the tail that fits the draft model's own generation limit
        ctx = ctx[-max(1, int(self.engine._gen_limit) - k):]
        out = self.engine.generate(ctx[None, :], max_new_tokens=k,
                                   temperature=0.0)
        return np.asarray(out)[0, len(ctx):].astype(np.int32)


# ---------------------------------------------------------------------------
# Config + resolve
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpecDecodeConfig:
    k: int = 4                 # max draft tokens per slot per verify tick
    drafter: Any = "ngram"     # "ngram" | drafter instance | draft engine
    max_ngram: int = 3         # NGramDrafter suffix length to match
    min_accept: float = 0.25   # EWMA acceptance floor before cooldown
    window: int = 8            # verify ticks the EWMA must cover first
    cooldown: int = 32         # plain decode ticks before retrying


def _check_cache_contract(engine) -> Optional[str]:
    """The verify step rewinds write heads through
    :func:`~..models.common.cache_leaf_kind`; a cache tree with leaves
    outside the ``append_kv_cache`` contract would keep a stale head
    after a rewind and decode garbage.  Error string, or None if OK."""
    c1 = jax.eval_shape(lambda: engine.init_cache(1))
    has_index = False
    for path, _ in jax.tree_util.tree_flatten_with_path(c1)[0]:
        kind = model_common.cache_leaf_kind(path)
        if kind is None:
            return (f"cache leaf {jax.tree_util.keystr(path)} is outside "
                    f"the append_kv_cache layout")
        has_index = has_index or kind == "index"
    if not has_index:
        return "model cache has no cache_index leaf to rewind"
    return None


def resolve_specdec(engine, override=None) -> Optional["SpecDecoder"]:
    """Resolve the batcher's speculative-decoding setting.

    Precedence (the :func:`~.kvreuse.resolve_prefix_cache` discipline):
    ``DSTPU_SPECDEC=0`` is the operator kill switch — it disables over
    ANY config, including a ready instance.  An explicit ``False``
    (argument or engine config) stays off even under ``DSTPU_SPECDEC=1``;
    the env ``1`` only enables where nothing explicitly disabled.
    Otherwise the argument wins over the engine config.  Accepted
    values: ``None`` (defer), ``False`` (off), ``True`` (on, n-gram
    drafter with defaults), a dict / :class:`SpecDecodeConfig` with
    ``k`` / ``drafter`` / ``max_ngram`` / ``min_accept`` / ``window`` /
    ``cooldown``, or a ready :class:`SpecDecoder`.  Unsupported configs
    warn and return None (serving falls back to plain decode, never
    fatal)."""
    env = os.environ.get(SPECDEC_ENV, "").strip().lower()
    if env in ("0", "false", "off"):
        return None   # kill switch FIRST: a ready instance must not bypass it
    if isinstance(override, SpecDecoder):
        return override
    cfg = override if override is not None else \
        getattr(engine.config, "specdec", None)
    if isinstance(cfg, SpecDecoder):
        return cfg   # a ready instance via the engine config counts too
    if cfg is False:
        return None
    # ANY dict is an explicit enable ({} means defaults — bool({}) being
    # falsy must not silently no-op the request)
    if not (isinstance(cfg, (dict, SpecDecodeConfig)) or bool(cfg)
            or env in ("1", "true", "on")):
        return None
    if isinstance(cfg, SpecDecodeConfig):
        sc = cfg
    else:
        opts = dict(cfg) if isinstance(cfg, dict) else {}
        known = {f.name for f in dataclasses.fields(SpecDecodeConfig)}
        unknown = set(opts) - known
        if unknown:
            logger.warning(
                f"specdec: ignoring unknown keys {sorted(unknown)}")
        sc = SpecDecodeConfig(**{k: v for k, v in opts.items()
                                 if k in known})
    if sc.k < 1:
        logger.warning(
            f"speculative decoding disabled: k={sc.k} proposes nothing "
            f"(every tick would degenerate to plain decode)")
        return None
    err = _check_cache_contract(engine)
    if err is not None:
        logger.warning(f"speculative decoding disabled: {err}")
        return None
    drafter = sc.drafter
    if isinstance(drafter, str):
        if drafter == "ngram":
            drafter = NGramDrafter(max_ngram=sc.max_ngram)
        else:
            logger.warning(
                f"speculative decoding disabled: unknown drafter "
                f"{drafter!r} (supported: 'ngram', a drafter instance, "
                f"or a draft InferenceEngine)")
            return None
    elif isinstance(drafter, InferenceEngine):
        drafter = DraftModelDrafter(drafter)
    elif not callable(getattr(drafter, "propose", None)):
        logger.warning(
            "speculative decoding disabled: drafter has no "
            "propose(context, k) method")
        return None
    return SpecDecoder(sc, drafter)


# ---------------------------------------------------------------------------
# The decoder: verify executables + acceptance controller + telemetry
# ---------------------------------------------------------------------------

def _spec_sample(logits1, key, temp, top_k: int, top_p, rep, seen, d,
                 is_draft_row):
    """Sampled-mode verify for ONE logit row ``(1, V)``.

    Uses :func:`~.engine._penalized_logits` + ``_filtered_logits`` — the
    SAME transform ``_sample`` runs (penalty → temperature → static
    top-k → traced nucleus), shared rather than copied — to get the
    target distribution ``p``, then applies the rejection rule for a
    DETERMINISTIC proposal (q = point mass on the draft ``d``): accept
    with probability ``p[d]``; on rejection sample from the residual
    ``p`` with ``d`` removed (renormalized) — exactly the Chen et al.
    correction, so emitted tokens are distributed as the target.  The
    bonus/correction row (``is_draft_row=False``) is a plain sample
    from ``p``.  Per-slot ``temp <= 0`` inside a sampled pool falls
    back to the penalized argmax, mirroring ``_sample``'s final
    ``where``."""
    lg = _penalized_logits(logits1, rep, seen)
    greedy_tok = jnp.argmax(lg, axis=-1)[0]
    scaled = _filtered_logits(lg, temp, top_k, top_p)
    probs = jax.nn.softmax(scaled, axis=-1)
    k_acc, k_res = jax.random.split(key)
    accept = jax.random.uniform(k_acc) < probs[0, d]
    residual = scaled.at[0, d].set(-jnp.inf)
    res_tok = jax.random.categorical(k_res, residual, axis=-1)[0]
    bonus_tok = jax.random.categorical(k_res, scaled, axis=-1)[0]
    drafted = jnp.where(accept, d, res_tok)
    tok = jnp.where(is_draft_row, drafted, bonus_tok)
    return jnp.where(jnp.asarray(temp) <= 0.0, greedy_tok, tok)


class SpecDecoder:
    """One batcher's speculative-decoding plane.

    Host half: drafter dispatch + the acceptance-rate controller.
    Device half: jitted slot-vmapped verify executables, memoized per
    ``(pow2 draft width, greedy)`` after :meth:`attach` binds the
    batcher's decode model / sampler statics.
    """

    def __init__(self, cfg: SpecDecodeConfig, drafter):
        self.cfg = cfg
        self.drafter = drafter
        self._steps: Dict[tuple, Any] = {}
        self._decode_model = None
        self._top_k = 0
        self._seed = 0
        # controller state: EWMA of per-verify-tick acceptance, cooldown
        # in remaining plain ticks
        self.cooldown = 0
        self._ewma: Optional[float] = None
        self._ticks_in_window = 0
        # per-instance tallies for /statusz: the registry counters below
        # are PROCESS-wide (every decoder in the process shares the
        # cells), but a status section describes THIS decoder
        self.draft_tokens = 0
        self.accepted_tokens = 0
        self.verify_ticks = 0
        self.fallback_ticks = 0
        self._m_draft = telemetry_registry.counter(
            "specdec_draft_tokens_total", "draft tokens offered to verify")
        self._m_accepted = telemetry_registry.counter(
            "specdec_accepted_tokens_total",
            "draft tokens accepted by verify (the free tokens)")
        self._m_verify = telemetry_registry.counter(
            "specdec_verify_ticks_total", "batched verify ticks executed")
        self._m_fallback = telemetry_registry.counter(
            "specdec_fallback_ticks_total",
            "plain decode ticks taken while speculation was resolved but "
            "not engaged (controller cooldown, or the drafter proposed "
            "nothing)")
        self._m_alen = telemetry_registry.histogram(
            "specdec_accepted_len",
            "accepted drafts per active slot per verify tick",
            buckets=telemetry_registry.ACCEPT_LEN_BUCKETS)
        self._m_rate = telemetry_registry.gauge(
            "specdec_acceptance_rate",
            "EWMA of per-verify-tick draft acceptance")
        from ..telemetry import exporter as telemetry_exporter

        telemetry_exporter.register_status_owner(
            "specdec", self, "_telemetry_status")

    # -- binding -------------------------------------------------------
    def attach(self, batcher) -> "SpecDecoder":
        """Bind to a :class:`~.serving.ContinuousBatcher`'s decode model
        and sampler statics.  Re-attaching (a fresh batcher on the same
        engine) drops the executable memo — shapes/statics may differ."""
        self._decode_model = batcher.engine._decode_model
        self._top_k = int(batcher.top_k)
        self._seed = int(batcher.seed)
        self._steps.clear()
        return self

    # -- controller ----------------------------------------------------
    def active(self) -> bool:
        """True when the next tick should attempt speculation."""
        return self.cooldown <= 0

    def note_plain(self, ticks: int) -> None:
        """Record ``ticks`` plain decode ticks run while this decoder
        was resolved (cooldown drain + the fallback counter)."""
        self._m_fallback.inc(int(ticks))
        self.fallback_ticks += int(ticks)
        if self.cooldown > 0:
            self.cooldown = max(0, self.cooldown - int(ticks))

    def note_empty(self) -> None:
        """The drafter proposed nothing pool-wide: count a full miss so
        a persistently silent drafter drifts into cooldown instead of
        paying host-side proposal work every tick forever."""
        self._note_rate(0.0)

    def note_verify(self, drafted: int, accepted: int,
                    per_slot_accepts: List[int]) -> None:
        self._m_verify.inc()
        self.verify_ticks += 1
        if drafted:
            self._m_draft.inc(drafted)
            self.draft_tokens += drafted
        if accepted:
            self._m_accepted.inc(accepted)
            self.accepted_tokens += accepted
        for a in per_slot_accepts:
            self._m_alen.observe(float(a))
        self._note_rate(accepted / drafted if drafted else 0.0)

    def _note_rate(self, rate: float) -> None:
        alpha = 2.0 / (self.cfg.window + 1.0)
        self._ewma = rate if self._ewma is None else \
            (1 - alpha) * self._ewma + alpha * rate
        self._m_rate.set(self._ewma)
        self._ticks_in_window += 1
        if self._ticks_in_window >= self.cfg.window and \
                self._ewma < self.cfg.min_accept:
            # graceful degradation: drop to plain decode for a bounded
            # cooldown, then retry with a fresh measurement window
            self.cooldown = int(self.cfg.cooldown)
            self._ewma = None
            self._ticks_in_window = 0

    # -- verify executables --------------------------------------------
    def verify_step(self, w: int, greedy: bool):
        """The jitted slot-vmapped verify executable for draft width
        ``w`` (callers pass pow2 widths so the memo stays bounded at
        log2(k) entries per sampler variant — the decode-window
        discipline)."""
        key = (int(w), bool(greedy))
        if key not in self._steps:
            self._steps[key] = self._make_verify(*key)
        return self._steps[key]

    def _make_verify(self, w: int, greedy: bool):
        if self._decode_model is None:
            raise RuntimeError("SpecDecoder.attach(batcher) must run "
                               "before verify_step")
        decode_model = self._decode_model
        top_k = self._top_k
        base_seed = self._seed
        n_rows = w + 1

        def slot_verify(params, cache, token, pos, slot_id, temp, top_p,
                        rep, seen, done, drafts, tick, eos, pad):
            # token (1,1) = the last emitted token (next input); drafts
            # (w,); ONE chunked forward scores every draft position —
            # the same cached multi-token path chunked prefill uses, so
            # the KV layout contract (append_kv_cache) is shared, not
            # copied
            inputs = jnp.concatenate([token[0], drafts])[None, :]
            positions = (pos + jnp.arange(n_rows, dtype=jnp.int32))[None, :]
            out, vars_ = decode_model.apply(
                {"params": params, "cache": cache}, inputs,
                position_ids=positions, mutable=["cache"])
            logits = out["logits"][0].astype(jnp.float32)      # (w+1, V)
            key0 = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(base_seed), tick), slot_id)
            keys = jax.vmap(lambda j: jax.random.fold_in(key0, j))(
                jnp.arange(n_rows))
            # row j < w must reproduce drafts[j]; row w is the bonus/
            # correction row (sentinel draft never matches)
            d_next = jnp.concatenate(
                [drafts.astype(jnp.int32), jnp.full((1,), -1, jnp.int32)])
            is_draft = jnp.arange(n_rows) < w

            def body(carry, xs):
                alive, seen_c, last, n = carry
                lrow, d, key_j, draft_row = xs
                logits1 = lrow[None, :]
                if greedy:
                    # the batcher's EXACT greedy sampler (static temp=0):
                    # penalized argmax with the seen mask threaded token
                    # by token — argmax-exact vs plain decode ticks
                    tok = _sample(logits1, key_j, 0.0, top_k, 1.0, rep,
                                  seen_c)[0]
                else:
                    tok = _spec_sample(logits1, key_j, temp, top_k, top_p,
                                       rep, seen_c, d, draft_row)
                emit = alive
                # the chain survives only through an accepted non-EOS
                # draft; a correction/bonus token is always terminal
                alive = jnp.logical_and(
                    alive, jnp.logical_and(
                        jnp.logical_and(draft_row, tok == d), tok != eos))
                seen_c = jnp.where(emit, seen_c.at[0, tok].set(True),
                                   seen_c)
                last = jnp.where(emit, tok, last)
                n = n + emit.astype(jnp.int32)
                return (alive, seen_c, last, n), jnp.where(emit, tok, pad)

            alive0 = jnp.logical_not(done[0])   # done slots emit nothing
            (alive, seen, last, n), toks = jax.lax.scan(
                body, (alive0, seen, token[0, 0], jnp.int32(0)),
                (logits, d_next, keys, is_draft))
            new_pos = pos + n
            # rewind discipline: the forward advanced the write head by
            # w+1; pull it back to the accepted length so the next tick
            # overwrites the rejected drafts' K/V rows in place
            new_cache = model_common.set_cache_index(vars_["cache"],
                                                     new_pos)
            new_token = jnp.where(n > 0, last, token[0, 0])[None, None]
            new_done = jnp.logical_or(
                done, jnp.logical_and(n > 0, last == eos))
            return toks, n, new_cache, new_token, new_pos, seen, new_done

        vstep = jax.vmap(
            slot_verify,
            in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, None, None, None))
        # each (w, greedy) is its own executable BY DESIGN (pow2 widths);
        # intra-key drift is a real hot-loop recompile — warn
        return recompile.watch(
            jax.jit(vstep),
            name=f"serving.verify[{w}{'g' if greedy else 's'}]")

    # -- observability -------------------------------------------------
    def _telemetry_status(self) -> dict:
        """The ``/statusz`` ``specdec`` section."""
        return {
            "k": self.cfg.k,
            "drafter": getattr(self.drafter, "name",
                               type(self.drafter).__name__),
            "acceptance_ewma": None if self._ewma is None
            else round(self._ewma, 4),
            "cooldown": self.cooldown,
            "min_accept": self.cfg.min_accept,
            "draft_tokens": self.draft_tokens,
            "accepted_tokens": self.accepted_tokens,
            "verify_ticks": self.verify_ticks,
            "fallback_ticks": self.fallback_ticks,
        }
