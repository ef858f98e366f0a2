"""Inference engine: TP-sharded serving with compiled prefill/decode.

Analog of reference ``deepspeed.init_inference`` → ``InferenceEngine``
(``inference/engine.py:25``): there, injection policies rewrite torch
modules into fused CUDA kernels with a KV cache, CUDA graphs capture the
decode step (``engine.py:363,382``), and tensor slicing splits weights
across mp ranks (``module_inject/replace_module.py:41``).

TPU-native equivalences:

- CUDA-graph capture/replay ≡ a jitted decode step (XLA compiles once,
  replays forever — "free" graphs).
- kernel injection ≡ the model zoo already runs fused XLA/Pallas paths;
  for HF users, :mod:`..module_inject` converts HF checkpoints into zoo
  params (the policy-class analog).
- tensor slicing ≡ TP PartitionSpecs on a ``tp`` mesh axis; the per-layer
  partial-output allreduce the reference issues by hand
  (``transformer_inference.py`` mp allreduce) is inserted by XLA.
- KV cache ≡ a flax ``cache`` collection with static max length, updated
  by ``dynamic_update_slice`` inside the compiled step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm
from ..comm.mesh import MeshConfig, build_mesh, set_mesh
from ..models.common import TP_RULES
from ..parallel import zero as zero_lib
from ..telemetry import recompile, trace
from ..utils import log_dist
from ..utils.logging import logger


@dataclasses.dataclass
class InferenceConfig:
    """Subset-compatible with ``init_inference`` kwargs (reference
    ``deepspeed/__init__.py:222``)."""

    mp_size: int = 1
    ep_size: int = 1                   # expert-parallel serving degree (the
                                       # _create_ep_parallel_group analog)
    dtype: Any = None                  # default bf16
    max_tokens: Optional[int] = None   # generation/cache limit; resizes the
                                       # KV cache for rotary models, caps
                                       # generation for learned-position ones
    replace_with_kernel_inject: bool = True   # accepted; zoo is always "injected"
    checkpoint: Optional[str] = None
    quant: dict = dataclasses.field(default_factory=dict)
    # fused decode-tick megakernels (ops/pallas/decode_layer.py) for
    # families with a decode_fused config field; None keeps the model's
    # own flag.  DS_TPU_DECODE_FUSED env-overrides either way.
    decode_fused: Optional[bool] = None
    # shared-prefix KV reuse for the serving plane (inference/kvreuse.py):
    # True enables with default sizing, a dict may set page_tokens /
    # n_pages / budget_bytes; DSTPU_PREFIX_CACHE env-overrides either
    # way.  Consumed by ContinuousBatcher at construction — plain
    # generate() calls are unaffected.
    prefix_cache: Any = None
    # speculative decoding for the serving plane (inference/specdec.py):
    # True enables the host-side n-gram drafter with defaults, a dict
    # may set k / drafter / max_ngram / min_accept / window / cooldown;
    # DSTPU_SPECDEC env-overrides either way.  Consumed by
    # ContinuousBatcher at construction — plain generate() calls are
    # unaffected.
    specdec: Any = None
    # page-resident serving (paged decode attention over the prefix
    # cache's arena, ops/pallas/paged_attention.py): None = ON whenever
    # prefix_cache resolves; False opts out back to the gather path.
    # DSTPU_PAGED_DECODE env-overrides.  Consumed by ContinuousBatcher.
    paged_decode: Any = None

    @staticmethod
    def load(d) -> "InferenceConfig":
        if isinstance(d, InferenceConfig):
            return d
        d = dict(d or {})
        known = {f.name for f in dataclasses.fields(InferenceConfig)}
        extra = {k: v for k, v in d.items() if k not in known}
        cfg = InferenceConfig(**{k: v for k, v in d.items() if k in known})
        if extra:
            from ..utils.logging import logger

            logger.warning(f"init_inference: ignoring unsupported keys {sorted(extra)}")
        return cfg



def _params_depend_on(model, cfg, pos_field: str) -> bool:
    """True when any parameter SHAPE is a function of ``pos_field`` (i.e.
    the model has a learned position table sized by it)."""
    import dataclasses as _dc

    def shapes(c):
        m = type(model)(c)
        tree = jax.eval_shape(
            lambda r: m.init(r, jnp.zeros((1, 1), jnp.int32)),
            jax.random.PRNGKey(0))["params"]
        return [tuple(l.shape) for l in jax.tree_util.tree_leaves(tree)]

    cur = getattr(cfg, pos_field)
    alt = _dc.replace(cfg, **{pos_field: cur * 2})
    try:
        return shapes(cfg) != shapes(alt)
    except Exception:
        return True   # cannot prove independence: be conservative


class InferenceEngine:
    """Serving wrapper: ``engine(input_ids)`` forward + ``generate()``.

    ``model``: a zoo module (e.g. ``GPT2LMHeadModel``) — its config is
    cloned into decode mode for the cached step.  ``params``: optional
    ready param tree; otherwise pass ``checkpoint`` (a training checkpoint
    dir) or call ``load_params``.
    """

    def __init__(self, model=None, config=None, params=None, mesh=None, **kwargs):
        merged = dict(config or {})
        merged.update(kwargs)
        self.config = InferenceConfig.load(merged)
        self.model = model
        cfg = model.cfg
        if self.config.dtype is not None:
            cfg = dataclasses.replace(cfg, dtype=self.config.dtype)
        self.model_cfg = dataclasses.replace(cfg, remat=False)
        # real int8 weight-only serving (ops/w8.py; reference
        # pt_binding.cpp:622 int8 GEMMs): int8 storage + dequant-fused
        # matmul.  Families without a w8 config field (or quant.fake=true,
        # or bits != 8) keep the grouped fake-quant load path below.
        self._w8 = False
        q = self.config.quant
        if q.get("enabled") and hasattr(cfg, "w8"):
            bits = int(q.get("bits", q.get("qtype", 8)))
            if bits == 8 and not q.get("fake", False):
                self._w8 = True
                self.model_cfg = dataclasses.replace(
                    self.model_cfg, w8=True,
                    w8_group=int(q.get("group_size", 128)))
                # dense *_kernel AND MoE expert wi/wo leaves quantize;
                # only the tiny gate (wg) stays full width
        if self.config.decode_fused is not None and \
                hasattr(cfg, "decode_fused"):
            self.model_cfg = dataclasses.replace(
                self.model_cfg, decode_fused=bool(self.config.decode_fused))
        # models name their context-length field differently
        pos_field = "n_positions" if hasattr(cfg, "n_positions") \
            else "max_position_embeddings"
        self._pos_field = pos_field
        model_limit = getattr(cfg, pos_field)
        requested = self.config.max_tokens
        cache_kw = {}
        if requested and requested != model_limit and \
                _params_depend_on(model, self.model_cfg, pos_field):
            # learned position table (GPT-2 wpe, BERT, GPT-Neo): resizing
            # the field would reshape checkpoint params — the POSITION
            # table stays at the model's length; the KV cache shrinks via
            # ``cache_len`` (decode streams the whole static cache every
            # tick, so a 1024-slot cache for a 96-token generation costs
            # ~10× the serving bandwidth it needs)
            self._gen_limit = min(requested, model_limit)
            decode_len = model_limit
            if requested > model_limit:
                logger.warning(
                    f"max_tokens={requested} exceeds the learned position "
                    f"table ({pos_field}={model_limit}); generation is "
                    f"capped at {model_limit}")
            if self._gen_limit < model_limit and \
                    hasattr(self.model_cfg, "cache_len"):
                cache_kw["cache_len"] = self._gen_limit
        else:
            # rotary-style models: the field only sizes the KV cache, so
            # max_tokens may shrink it (less HBM) or extend it past the
            # trained context
            decode_len = requested or model_limit
            self._gen_limit = decode_len
        # a cache_len the CALLER set on the model config caps generation
        # too — a 256-slot cache must not admit 2048-token sequences
        # (clamped cache writes would silently corrupt decoding) — and
        # wins over a larger max_tokens-derived cache size
        user_cl = getattr(self.model_cfg, "cache_len", None)
        if user_cl:
            self._gen_limit = min(self._gen_limit, user_cl)
            cache_kw["cache_len"] = min(
                user_cl, cache_kw.get("cache_len", user_cl))
        self.decode_cfg = dataclasses.replace(
            self.model_cfg, decode=True, **{pos_field: decode_len},
            **cache_kw)
        self._fwd_model = type(model)(self.model_cfg)
        self._decode_model = type(model)(self.decode_cfg)

        if mesh is None:
            mesh = comm.get_mesh(required=False)
        if mesh is None:
            axes = {"tp": self.config.mp_size, "dp": -1}
            if self.config.ep_size > 1:
                axes["ep"] = self.config.ep_size
            # one server, one replica: with no model parallelism asked
            # for, the engine takes ONE device.  Spreading over every
            # visible device would replicate the weights and repeat each
            # request's work on all of them (and a >1-device mesh makes
            # kernel_mesh_plan refuse the flash kernel for any prefill
            # batch the device count does not divide); replicas on the
            # other chips are the router's business
            # (inference/router.py).
            devices = None
            if self.config.mp_size == 1 and self.config.ep_size == 1:
                devices = jax.devices()[:1]
            mesh = build_mesh(axes, devices=devices)
            set_mesh(mesh)
        else:
            for axis, want in (("tp", self.config.mp_size),
                               ("ep", self.config.ep_size)):
                have = mesh.shape.get(axis, 1)
                if want > 1 and have != want:
                    raise ValueError(
                        f"init_inference requested {axis}={want} but the "
                        f"active mesh has {axis}={have}; build the mesh with "
                        f"that degree or drop the argument")
        self.mesh = mesh

        self.params = None
        # /statusz section (weakly held — see telemetry/exporter.py)
        from ..telemetry import exporter as telemetry_exporter

        telemetry_exporter.register_status_owner(
            "inference", self, "_telemetry_status")
        if params is not None:
            self.load_params(params)
        elif self.config.checkpoint:
            self.load_checkpoint(self.config.checkpoint)

    def _telemetry_status(self) -> dict:
        # cached by load_params: a 1/s statusz scrape must not re-walk
        # a large param tree on the HTTP thread every request
        return {
            "model": type(self.model).__name__,
            "params_m": round(getattr(self, "_n_params", 0) / 1e6, 2),
            "loaded": self.params is not None,
            "gen_limit": int(self._gen_limit),
            "mp_size": int(self.mesh.shape.get("tp", 1)),
            "w8": self._w8,
            "dtype": str(self.model_cfg.dtype),
        }

    # ------------------------------------------------------------------
    def _param_shardings(self, abstract_boxed):
        specs = zero_lib.param_partition_specs(abstract_boxed, self.mesh,
                                               zero_stage=0, rules=TP_RULES)
        return zero_lib.named_shardings(self.mesh, specs)

    def load_params(self, params):
        """Place a host/abstract param tree with TP shardings (the tensor-
        slicing analog of ``ReplaceWithTensorSlicing``)."""
        dummy = self.model.dummy_inputs(1)
        boxed = jax.eval_shape(
            lambda r: self._fwd_model.init(r, dummy["input_ids"]),
            jax.random.PRNGKey(0))["params"]
        shardings = self._param_shardings(boxed)
        unboxed = jax.tree_util.tree_map(
            lambda x: getattr(x, "value", x), params,
            is_leaf=lambda x: hasattr(x, "names") and hasattr(x, "value"))
        if self._w8:
            from ..ops.w8 import quantize_dense_tree

            unboxed = quantize_dense_tree(
                unboxed, group=self.model_cfg.w8_group)
            log_dist("quantized dense kernels to int8 codes + grouped "
                     "scales (W8A16 serving)", ranks=[0])
        elif self.config.quant.get("enabled"):
            # inference weight quantization (the WeightQuantization / MoQ
            # checkpoint-quantize analog, reference weight_quantizer.py):
            # grouped fake-quant of >=2-D weights at load
            from ..ops.quantizer import fake_quantize

            bits = int(self.config.quant.get("bits",
                       self.config.quant.get("qtype", 8)))
            groups = int(self.config.quant.get("groups", 64))
            def _quant_leaf(path, x):
                if np.ndim(x) < 2:
                    return x
                g = groups
                if np.size(x) % groups != 0:
                    g = 1
                    logger.warning(
                        f"quantizing {jax.tree_util.keystr(path)} with ONE "
                        f"group (size {np.size(x)} not divisible by "
                        f"{groups}) — coarser than requested")
                return np.asarray(fake_quantize(
                    jnp.asarray(x, jnp.float32), bits, g))

            unboxed = jax.tree_util.tree_map_with_path(_quant_leaf, unboxed)
            log_dist(f"quantized inference weights to {bits} bits", ranks=[0])

        # store float params at the SERVING dtype (bf16 unless the caller
        # set dtype=): decode is weight-bandwidth-bound, and fp32 storage
        # + per-use casts read twice the bytes every tick (round-4 int8
        # review found this on the fp path).  W8 scales (``*_s``) stay
        # fp32 — the dequant combine needs them full width.
        store = self.model_cfg.dtype

        # cast + shard leaf-by-leaf: casting the whole tree eagerly first
        # would materialize a full unsharded copy on the default device
        # (OOM for models that only fit TP-sharded)
        def _put(path, x, s):
            dt = np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype
            cast = jnp.issubdtype(dt, jnp.floating) and \
                not getattr(path[-1], "key", "").endswith("_s")
            return jax.device_put(
                jnp.asarray(x, store) if cast else jnp.asarray(x), s)

        self.params = jax.tree_util.tree_map_with_path(
            _put, unboxed, shardings)
        n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))
        self._n_params = n
        log_dist(f"inference params loaded: {n/1e6:.1f}M, mp={self.mesh.shape['tp']}",
                 ranks=[0])
        try:
            # per-device resident bytes (TP splits the tree): the static
            # half of the serving OOM-headroom picture — KV caches and
            # activations come on top (live_hbm_bytes covers those)
            from ..telemetry import memory as telemetry_memory
            from ..telemetry import registry as telemetry_registry

            per_dev, _ = telemetry_memory.per_device_shard_bytes(
                jax.tree_util.tree_leaves(self.params))
            telemetry_registry.gauge(
                "hbm_params_bytes",
                "max per-device bytes resident for inference params"
            ).set(float(max(per_dev.values(), default=0)))
        except Exception:
            pass
        return self

    def load_checkpoint(self, ckpt_dir: str, tag: Optional[str] = None):
        """Load params from a TRAINING checkpoint dir (SDLoader analog —
        resharding to the serving mesh happens on restore)."""
        from ..runtime.checkpointing import get_fp32_state_dict_from_checkpoint

        params = get_fp32_state_dict_from_checkpoint(ckpt_dir, tag)
        return self.load_params(params)

    # ------------------------------------------------------------------
    @functools.cached_property
    def _compiled_forward(self):
        def fwd(params, input_ids):
            return self._fwd_model.apply({"params": params}, input_ids)["logits"]

        # caller-shaped inputs vary by design: count compiles, no warning
        return recompile.watch(jax.jit(fwd), name="inference.forward",
                               warn=False)

    def forward(self, input_ids, **kwargs):
        if self.params is None:
            raise RuntimeError("no parameters loaded; pass params=/checkpoint=")
        return self._compiled_forward(self.params, jnp.asarray(input_ids))

    __call__ = forward

    # ------------------------------------------------------------------
    def _prefill_impl(self, params, cache, input_ids, position_ids):
        """The ONE prefill body — jitted twice below (with and without
        cache donation) so the two paths can never diverge."""
        out, vars_ = self._decode_model.apply(
            {"params": params, "cache": cache}, input_ids,
            position_ids=position_ids, mutable=["cache"])
        return out["logits"], vars_["cache"]

    @functools.cached_property
    def _compiled_prefill(self):
        # chunked prefill compiles one executable per pow2 chunk length
        # and batch width BY DESIGN — counted, never warned
        return recompile.watch(jax.jit(self._prefill_impl),
                               name="inference.prefill", warn=False)

    @functools.cached_property
    def _compiled_prefill_donated(self):
        """Prefill with the CACHE DONATED — the page-resident serving
        path: its cache tree carries the shared page arena, and without
        donation every suffix-prefill chunk would copy the whole arena
        to apply an O(chunk) append.  Callers must rebind the arena from
        the returned cache (``PagedServingState.adopt``) — the donated
        input buffers are dead after the call."""
        return recompile.watch(
            jax.jit(self._prefill_impl, donate_argnums=(1,)),
            name="inference.prefill_paged", warn=False)

    @functools.lru_cache(maxsize=16)
    def _compiled_decode_step(self, top_k: int, top_p: float,
                              temperature: float):
        """One fused decode tick: cache-append forward + sampling + EOS
        bookkeeping; the CUDA-graph-replay analog.  ``top_k``/``top_p``/
        ``temperature`` are STATIC (constant per generate() call, lru-
        cached) so dead sampling branches — the nucleus sort, the
        categorical draw under greedy — drop out of the compiled step.

        Dynamic sampling state rides through the step so nothing leaves
        the device between ticks: ``seen_mask`` (B, V) powers the
        repetition penalty, ``done`` (B,) freezes finished sequences (they
        emit ``pad_id`` from then on), ``eos_id`` < 0 disables EOS.
        """
        tick = self._decode_tick(top_k, top_p, temperature)
        # batch width B legitimately varies across generate() calls (same
        # as generate_loop below) → counted, not warned; the continuously-
        # batched serving hot loop has its own fixed-width watchdog sites
        # (serving.decode[...]) that DO warn
        return recompile.watch(jax.jit(tick), name="inference.decode_step",
                               warn=False)

    def _decode_tick(self, top_k: int, top_p: float, temperature: float):
        """ONE decode tick as a pure function — the single source of truth
        shared by the stepwise jit and the scanned loop (their
        token-for-token equivalence is structural, not copy-kept)."""

        def step(params, cache, token, position, rng,
                 rep_penalty, seen_mask, done, eos_id, pad_id):
            out, vars_ = self._decode_model.apply(
                {"params": params, "cache": cache}, token,
                position_ids=position, mutable=["cache"])
            next_logits = out["logits"][:, -1, :].astype(jnp.float32)
            next_token = _sample(next_logits, rng, temperature, top_k,
                                 top_p, rep_penalty, seen_mask)
            next_token = jnp.where(done, pad_id, next_token)
            new_done = jnp.logical_or(done, next_token == eos_id)
            B = next_token.shape[0]
            seen_mask = seen_mask.at[jnp.arange(B), next_token].set(True)
            return next_token, vars_["cache"], seen_mask, new_done

        return step

    @functools.lru_cache(maxsize=16)
    def _compiled_generate_loop(self, top_k: int, top_p: float,
                                temperature: float):
        """The WHOLE decode loop as one ``lax.scan`` program: n tokens per
        host round-trip instead of one (the loop version pays an RTT per
        token on remote links).  Token-for-token identical to the stepwise
        path — same tick function, same RNG split order."""
        tick = self._decode_tick(top_k, top_p, temperature)

        def run(params, cache, token, pos0, rng, rep_penalty, seen_mask,
                done, eos_id, pad_id, steps):
            def body(carry, t):
                cache, token, seen, done, rng = carry
                rng, sub = jax.random.split(rng)
                nxt, cache, seen, done = tick(
                    params, cache, token, (pos0 + t)[:, None], sub,
                    rep_penalty, seen, done, eos_id, pad_id)
                return (cache, nxt[:, None], seen, done, rng), nxt

            (_, _, _, _, _), toks = jax.lax.scan(
                body, (cache, token, seen_mask, done, rng), steps)
            return toks   # (n, B)

        # (B, max_new_tokens) legitimately vary across generate() calls:
        # counted (watch the counter to spot an unbucketed caller), not
        # warned — the per-tick hot path is covered by decode_step
        return recompile.watch(jax.jit(run), name="inference.generate_loop",
                               warn=False)

    @staticmethod
    def _seen_mask_from(input_ids, vocab_size: int):
        B = input_ids.shape[0]
        # np.arange: a host index array — a jnp.arange here dispatches a
        # device computation per admission (the PR-4 positions contract)
        return jnp.zeros((B, vocab_size), bool).at[
            np.arange(B)[:, None], input_ids].set(True)

    def _zero_cache_fn(self, batch_size: int):
        """Memoized (per batch width) jitted zero-cache builder: the naive
        path re-traced the whole model (``eval_shape``) and dispatched one
        ``jnp.zeros`` per cache leaf on EVERY admission — pure host
        overhead per prefill batch, growing with the layer count.
        The memo is per-INSTANCE (not an lru_cache keyed by self, which
        would pin retired engines — and their HBM params — alive)."""
        memo = self.__dict__.setdefault("_zero_cache_memo", {})
        if batch_size in memo:
            return memo[batch_size]
        dummy = jnp.zeros((batch_size, 1), jnp.int32)
        vars_ = jax.eval_shape(
            lambda r: self._decode_model.init(r, dummy,
                                              position_ids=jnp.zeros((1, 1), jnp.int32)),
            jax.random.PRNGKey(0))
        leaves, treedef = jax.tree_util.tree_flatten(vars_["cache"])
        fn = jax.jit(lambda: tuple(jnp.zeros(l.shape, l.dtype)
                                   for l in leaves))
        memo[batch_size] = (fn, treedef)
        return fn, treedef

    def init_cache(self, batch_size: int):
        fn, treedef = self._zero_cache_fn(batch_size)
        return jax.tree_util.tree_unflatten(treedef, fn())

    def generate(self, input_ids, max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 repetition_penalty: float = 1.0, seed: int = 0,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: Optional[int] = None,
                 compiled_loop: bool = True):
        """Autoregressive generation: compiled prefill + compiled decode.

        Greedy when ``temperature == 0``; ``top_p`` nucleus and
        ``repetition_penalty`` follow the HF semantics.  Sequences that
        emit ``eos_token_id`` are frozen individually and padded with
        ``pad_token_id`` (default: the EOS id).

        ``compiled_loop=True`` (default) runs the whole decode loop as ONE
        compiled ``lax.scan`` — a single host round-trip for all tokens;
        output is always (B, S+max_new_tokens).  ``compiled_loop=False``
        steps tick-by-tick and stops early once every sequence is done
        (possibly returning fewer columns) — saves compute when EOS lands
        early, pays a round-trip per token.
        """
        if self.params is None:
            raise RuntimeError("no parameters loaded; pass params=/checkpoint=")
        input_ids = jnp.asarray(input_ids, jnp.int32)
        B, S = input_ids.shape
        limit = self._gen_limit
        if S + max_new_tokens > limit:
            raise ValueError(f"prompt({S}) + max_new_tokens({max_new_tokens}) "
                             f"exceeds the generation limit {limit} "
                             f"(max_tokens/model context)")
        with trace.span("serve/prefill", rows=int(B), len=int(S)):
            cache = self.init_cache(B)
            positions = jnp.asarray(np.arange(S)[None, :].repeat(B, 0))
            logits, cache = self._compiled_prefill(
                self.params, cache, input_ids, positions)
        rng = jax.random.PRNGKey(seed)
        rep_pen = jnp.float32(repetition_penalty)
        eos = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        pad = jnp.int32(eos_token_id if pad_token_id is None and
                        eos_token_id is not None else (pad_token_id or 0))
        vocab = logits.shape[-1]
        seen = self._seen_mask_from(input_ids, vocab)
        done = jnp.zeros((B,), bool)

        rng, sub = jax.random.split(rng)
        token = _sample(logits[:, -1, :].astype(jnp.float32), sub,
                        float(temperature), int(top_k), float(top_p),
                        rep_pen, seen)
        done = token == eos
        seen = seen.at[np.arange(B), token].set(True)
        if compiled_loop and max_new_tokens > 1:
            loop = self._compiled_generate_loop(
                int(top_k), float(top_p), float(temperature))
            with trace.span("serve/decode-tick", ticks=max_new_tokens - 1,
                            rows=int(B)):
                toks = loop(self.params, cache, token[:, None],
                            jnp.full((B,), S, jnp.int32), rng, rep_pen, seen,
                            done, eos, pad,
                            jnp.asarray(np.arange(max_new_tokens - 1)))
            return jnp.concatenate([input_ids, token[:, None], toks.T], axis=1)
        decode_step = self._compiled_decode_step(
            int(top_k), float(top_p), float(temperature))
        tokens = [token]
        pos = S
        for _ in range(max_new_tokens - 1):
            rng, sub = jax.random.split(rng)
            token, cache, seen, done = decode_step(
                self.params, cache, token[:, None],
                jnp.full((B, 1), pos, jnp.int32), sub,
                rep_pen, seen, done, eos, pad)
            tokens.append(token)
            pos += 1
            if eos_token_id is not None and bool(jax.device_get(done.all())):
                break
        return jnp.concatenate([input_ids] + [t[:, None] for t in tokens], axis=1)


def _penalized_logits(logits, repetition_penalty=1.0, seen_mask=None):
    """Repetition penalty on fp32 logits (B, V): ``seen_mask`` tokens'
    logits are divided (if positive) or multiplied (if negative) by the
    penalty — the standard CTRL-style rule HF implements.  Shared by
    :func:`_sample` and the speculative verify chain
    (``inference/specdec.py``) so the two cannot drift."""
    if seen_mask is not None:
        pen = jnp.where(logits > 0, logits / repetition_penalty,
                        logits * repetition_penalty)
        logits = jnp.where(seen_mask, pen, logits)
    return logits


def _filtered_logits(logits, temperature, top_k: int, top_p=1.0):
    """PENALIZED logits → the categorical's input: temperature scaling,
    static top-k mask, nucleus mask (live when ``top_p`` is traced or a
    non-trivial static).  ``softmax`` of the result is the target
    distribution speculative rejection sampling must preserve — one
    implementation, shared with ``inference/specdec.py``."""
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    static_full_p = isinstance(top_p, (int, float)) and \
        (top_p >= 1.0 or top_p <= 0.0)
    if not static_full_p:
        # nucleus: keep the smallest prefix of descending-prob tokens whose
        # mass reaches top_p (the top token always survives)
        p = jnp.where(jnp.asarray(top_p) <= 0.0, 1.0, jnp.asarray(top_p))
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        kept = mass_before < p
        thr = jnp.min(jnp.where(kept, sorted_desc, jnp.inf), axis=-1,
                      keepdims=True)
        scaled = jnp.where(scaled < thr, -jnp.inf, scaled)
    return scaled


def _sample(logits, rng, temperature, top_k: int, top_p=1.0,
            repetition_penalty=1.0, seen_mask=None):
    """Greedy / temperature / top-k / top-p sampling with repetition
    penalty on fp32 logits (B, V).  ``top_k`` is static.  ``top_p`` and
    ``temperature`` may be python floats (static — dead branches like the
    O(V log V) nucleus sort are dropped at trace time: a greedy decode
    step compiles to penalty+argmax only) or traced scalars (the
    per-request path in ``ContinuousBatcher``).
    """
    logits = _penalized_logits(logits, repetition_penalty, seen_mask)
    greedy = jnp.argmax(logits, axis=-1)
    static_greedy = isinstance(temperature, (int, float)) and temperature <= 0.0
    if static_greedy:
        return greedy
    scaled = _filtered_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(jnp.asarray(temperature) <= 0.0, greedy, sampled)
