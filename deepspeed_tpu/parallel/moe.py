"""Mixture-of-Experts with expert parallelism.

Analog of ``deepspeed/moe/`` (``MoE`` layer ``layer.py:18``, ``TopKGate``
``sharded_moe.py:352``, ``MOELayer`` ``sharded_moe.py:440``, all-to-all
autograd shim ``sharded_moe.py:90``, expert/data group math
``utils/groups.py:108``).  TPU-native design:

- The gating math (top-1/top-2, capacity, jitter, load-balancing aux loss)
  ports almost 1:1 — it was always einsum-shaped (GShard lineage).
- The explicit ``_AllToAll`` + expert process groups disappear: expert
  parameters carry a leading ``experts`` dim sharded on the ``ep`` mesh
  axis, the dispatched token tensor is sharding-constrained to the same
  axis, and XLA inserts the all-to-all pair (dispatch + combine) that the
  reference issues by hand (``sharded_moe.py:513,527``).
- Expert-vs-data group bookkeeping (``_create_expert_and_data_parallel``)
  is unnecessary: ``ep`` is one of the batch axes (see ``mesh.DATA_AXES``),
  so non-expert params are automatically replicated over it and expert
  grads are automatically reduced only across the right ranks.

Which dispatch serves which case (derived from ``MoEConfig.drop_tokens``
and the mesh, never from the environment):

- ``drop_tokens=True`` (default): GShard's capacity queues for top-1 /
  top-2 and the dense one-hot einsums ``sec,sm->ecm`` / ``sec,ecm->sm``.
  Tokens past an expert's capacity are dropped; with ``ep > 1`` the
  constrained (E, C, M) tensor is what XLA turns into the all-to-all.
- ``drop_tokens=False``: any ``top_k``, dropless.  :func:`topk_routing`
  (softmax in float32, top-k, optional renormalisation, load-balancing and
  z losses) over all tokens, then ONE sorted dispatch
  (:func:`sorted_dispatch`): stable argsort of the (token, choice) pairs
  by expert, group sizes by a compare-and-sum over the expert axis
  (:func:`_count_ids`: no scatter), a row gather, the grouped matmuls
  of ``ops/grouped_matmul.py`` over ragged groups, the inverse gather and
  the weighted sum over each token's choices.  Under data parallelism
  (dp / fsdp > 1) every rank sorts and multiplies its OWN tokens inside a
  ``shard_map`` over the batch axes, the expert leaves replicated after
  the ZeRO gather (``kernel_mesh_plan``, as the flash kernel).  ``ep > 1``
  raises (PERF.md section 7, row 8), it does not fall back to the
  capacity path.
- ``drop_tokens=False`` with a share (``routed_experts`` more than
  ``num_experts``): this instance holds experts ``first_expert`` to
  ``first_expert + num_experts - 1`` of the ``routed_experts`` that the
  router scores, as one chip of an expert-parallel group does.  Routing,
  renormalisation, the losses and the counts are over all of them; the
  same sorted dispatch sorts the pairs routed to a held expert to the
  front of its row buffer, multiplies them in ``num_experts`` groups and
  sums them back into their tokens; what the absent experts would have
  added is left out, and there is no exchange and nothing that stands in
  for one.

AFMoE's routing (Trinity, ``model_type: afmoe``; the DeepSeek-V3 lineage)
is the dropless path with fields: ``score_func="sigmoid"``, its
``route_norm`` is ``norm_topk_prob``, ``route_scale`` multiplies the chosen
scores, ``bias_update_rate`` (its ``load_balance_coeff``) a selection bias
that picks and does not weigh (the state leaf ``expert_bias``, moved by
:func:`bias_update` from each step's counts and by no gradient),
``num_shared_experts`` a dense SwiGLU beside the routed sum, computed
whole by every share.  ``shared_expert_gate`` (Qwen3-Next's) multiplies
that SwiGLU by ``sigmoid(x . w_g)``, one scalar a token.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..comm import mesh as mesh_lib
from ..ops.grouped_matmul import (combine_rows, grouped_matmul, repeat_gather,
                                  swiglu_plan, swiglu_rows)
from ..telemetry import registry, trace


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    # experts whose leaves this instance holds (the leading dim of every
    # expert leaf); all that there are, unless ``routed_experts`` says more
    num_experts: int = 8
    # drop_tokens=True: 1 or 2 (reference top1gating/top2gating);
    # drop_tokens=False: any 1 <= top_k <= num_experts
    top_k: int = 1
    capacity_factor: float = 1.0        # train capacity (sharded_moe.py:178)
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None   # None | 'Jitter' | 'RSample'
    aux_loss_weight: float = 0.01
    drop_tokens: bool = True            # False: dropless sorted dispatch
    use_residual: bool = False          # PR-MoE (layer.py:106)
    # dropless routing only: renormalise the k chosen probabilities to sum
    # to 1 (the capacity gates keep GShard's rule: top-2 does, top-1 not)
    norm_topk_prob: bool = False
    # added to the sum the k chosen scores are renormalised by (the LFM2
    # family's released routing has 1e-6 there: about 4 ulps of a float32
    # sum of four sigmoid scores, so it is computed and not argued away)
    norm_topk_eps: float = 0.0
    z_loss_weight: float = 0.0          # router z-loss (ST-MoE), dropless only
    expert_act: str = "gelu"            # 'gelu': wi/wo; 'swiglu': gate/up/down
    # a share of an expert-parallel layer (dropless only): the router
    # scores ``routed_experts`` (None: ``num_experts``, no share) and this
    # instance holds ``num_experts`` of them from ``first_expert`` on
    routed_experts: Optional[int] = None
    first_expert: int = 0
    # dropless routing only, under AFMoE's config.json names.  How a token
    # scores the experts: "softmax" over all of them, or each expert's own
    # "sigmoid"
    score_func: str = "softmax"
    # the k chosen scores (renormalised first under norm_topk_prob, which
    # is AFMoE's route_norm) times this
    route_scale: float = 1.0
    # not None: the top-k is taken of score + ``expert_bias``, a leaf that
    # picks and never weighs, has no gradient and that the optimizer skips
    # (STATE_LEAF); the step moves it by this rate against each expert's
    # load (:func:`bias_update`; AFMoE's load_balance_coeff).  The only
    # balancing mechanism it needs: set aux_loss_weight to 0 beside it
    bias_update_rate: Optional[float] = None
    # experts every token runs, one dense SwiGLU of num_shared_experts x
    # the experts' width added to the routed sum; a share computes it whole
    num_shared_experts: int = 0
    # the shared SwiGLU times sigmoid(x . w_g): a leaf of model_dim, one
    # scalar a token (the Qwen3-Next family's shared_expert_gate)
    shared_expert_gate: bool = False
    # group-limited routing (DeepSeek-V3's, under its config.json names):
    # the ROUTED experts lie in ``n_group`` groups of neighbours, a group
    # scores the sum of its two best selection scores, a token keeps its
    # ``topk_group`` best groups and takes its top_k among their experts
    # alone.  1 and 1: no limit, and nothing of it is traced
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"score_func must be 'softmax' or 'sigmoid', "
                             f"got {self.score_func!r}")
        if self.drop_tokens and self.afmoe_fields:
            raise NotImplementedError(
                f"{', '.join(self.afmoe_fields)}: written for the dropless "
                f"routing (drop_tokens=False) only")
        if self.num_shared_experts and self.expert_act != "swiglu":
            raise NotImplementedError("shared experts are SwiGLU")
        if self.shared_expert_gate and not self.num_shared_experts:
            raise ValueError("shared_expert_gate without a shared expert "
                             "(num_shared_experts)")
        if not 1 <= self.topk_group <= self.n_group \
                or self.routed % self.n_group:
            raise ValueError(
                f"n_group {self.n_group} and topk_group {self.topk_group}: "
                f"the {self.routed} routed experts in whole groups, of which "
                f"a token keeps 1 to n_group")
        if self.n_group > 1 and (
                self.top_k > self.routed // self.n_group * self.topk_group
                or self.routed // self.n_group < 2):
            raise ValueError(
                f"top_k {self.top_k} of {self.topk_group} groups of "
                f"{self.routed // self.n_group} experts: a group has at "
                f"least two, the kept groups at least top_k")
        if self.routed_experts is None:
            if self.first_expert:
                raise ValueError("first_expert without routed_experts")
            return
        if not 0 <= self.first_expert <= self.routed - self.num_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.num_experts - 1}"
                f" are not among the {self.routed} the router scores")
        if not self.holds_all and self.drop_tokens:
            raise NotImplementedError(
                "a share of the experts is written for the dropless sorted "
                "dispatch (drop_tokens=False) only")

    @property
    def routed(self) -> int:
        """Experts the router scores."""
        return self.routed_experts or self.num_experts

    @property
    def holds_all(self) -> bool:
        return self.routed == self.num_experts

    @property
    def afmoe_fields(self) -> Tuple[str, ...]:
        """The routing fields set away from their defaults, by name."""
        return tuple(f for f, off in (
            ("score_func", "softmax"), ("route_scale", 1.0),
            ("bias_update_rate", None), ("num_shared_experts", 0),
            ("norm_topk_eps", 0.0), ("shared_expert_gate", False),
            ("n_group", 1))
            if getattr(self, f) != off)


def _capacity(num_tokens: int, num_experts: int, factor: float, min_capacity: int,
              top_k: int = 1) -> int:
    cap = int(num_tokens * factor / num_experts)
    cap = max(cap, min_capacity)
    # an expert's queue can never exceed S*k entries, so any capacity
    # beyond that is pure padding — at S=1 decode the min_capacity floor
    # would otherwise 4x every expert matmul for no semantic difference
    return min(cap, num_tokens * top_k)


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def _count_ids(ids: jax.Array, n: int) -> jax.Array:
    """How many entries of the flat ``ids`` equal each of ``0..n-1``, int32
    (n,).  An id outside that range (a share's "held elsewhere" marker
    ``n``) is counted nowhere, as ``jnp.bincount(ids, length=n)`` has it.

    A compare against every bin and a sum along the ids, which XLA fuses
    into one pass over ``n * len(ids)`` lanes with no such array: the TPU
    runs ``bincount``'s scatter-add one index at a time (2.29 ms for
    262,144 ids into 64 bins on the v5e against 0.03, PERF.md section 6,
    PR 36).  The scatter would win again from some thousands of bins,
    which no configuration has."""
    return (ids == jnp.arange(n, dtype=ids.dtype)[:, None]).sum(
        1, dtype=jnp.int32)


def top1_gating(logits: jax.Array, capacity: int, rng=None,
                noise_policy: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-1 gating (reference ``sharded_moe.py:178`` lineage).

    Returns ``(l_aux, combine_weights [S,E,C], dispatch_mask [S,E,C])``.
    """
    S, E = logits.shape
    if noise_policy == "RSample" and rng is not None:
        logits_for_choice = logits + jax.random.gumbel(rng, logits.shape)
    else:
        logits_for_choice = logits
    gates = jax.nn.softmax(logits, axis=-1)                       # (S, E)
    expert_idx = jnp.argmax(logits_for_choice, axis=-1)           # (S,)
    mask1 = _one_hot(expert_idx, E)                               # (S, E)

    # position of each token within its expert's queue
    pos_in_expert = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1     # (S, E)
    keep = (pos_in_expert < capacity).astype(jnp.float32) * mask1

    # load-balancing aux loss: E * sum_e( fraction_tokens_e * mean_gate_e )
    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = jnp.sum(me * ce) * E

    gate_val = (gates * keep).sum(axis=-1, keepdims=True)         # (S, 1)
    pos = (pos_in_expert * keep).sum(axis=-1).astype(jnp.int32)   # (S,)
    pos_oh = _one_hot(pos, capacity)                              # (S, C)
    combine = (gate_val * keep)[:, :, None] * pos_oh[:, None, :]  # (S, E, C)
    dispatch = combine > 0.0
    return l_aux, combine, dispatch


def top2_gating(logits: jax.Array, capacity: int, rng=None,
                noise_policy: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-2 gating with 2nd-choice jitter (reference ``sharded_moe.py:279``)."""
    S, E = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, E)
    logits_wo_1 = jnp.where(mask1 > 0, -jnp.inf, logits)
    if noise_policy == "RSample" and rng is not None:
        logits_wo_1 = logits_wo_1 + jax.random.gumbel(rng, logits.shape)
    idx2 = jnp.argmax(logits_wo_1, axis=-1)
    mask2 = _one_hot(idx2, E)

    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1
    # second choices queue behind ALL first choices (reference :318)
    pos2 = (jnp.cumsum(mask2, axis=0) - 1.0) * mask2 + mask1.sum(axis=0, keepdims=True) * mask2
    keep1 = (pos1 < capacity).astype(jnp.float32) * mask1
    keep2 = (pos2 < capacity).astype(jnp.float32) * mask2

    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = jnp.sum(me * ce) * E

    g1 = (gates * keep1).sum(-1)
    g2 = (gates * keep2).sum(-1)
    denom = jnp.maximum(g1 + g2, jnp.finfo(gates.dtype).eps)
    g1, g2 = g1 / denom, g2 / denom

    p1 = (pos1 * keep1).sum(-1).astype(jnp.int32)
    p2 = (pos2 * keep2).sum(-1).astype(jnp.int32)
    combine = (g1[:, None] * keep1)[:, :, None] * _one_hot(p1, capacity)[:, None, :] \
        + (g2[:, None] * keep2)[:, :, None] * _one_hot(p2, capacity)[:, None, :]
    dispatch = combine > 0.0
    return l_aux, combine, dispatch


def group_limit(scores: jax.Array, top_k: int, n_group: int, topk_group: int
                ) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's group-limited selection over the selection ``scores``
    (S, E) (score + bias; no gradient passes): ``(allowed (S, E) bool,
    kept)``.  The E experts are ``n_group`` groups of E / n_group
    neighbours; a group's score is the sum of its two best; ``allowed``
    marks the experts of each token's ``topk_group`` best groups.  ``kept``
    is the share of the (token, choice) pairs of the UNRESTRICTED top-k that
    lie in an allowed group: 1 where the limit changed no choice.  Compares
    and sums alone: no gather by index (``_count_ids``' reason)."""
    S, E = scores.shape
    scores = jax.lax.stop_gradient(scores)
    best2, _ = jax.lax.top_k(scores.reshape(S, n_group, E // n_group), 2)
    group_score = best2.sum(-1)                                 # (S, G)
    cut = jax.lax.top_k(group_score, topk_group)[0][:, -1:]
    # ties at the cut keep every tied group, as no released weights have them
    allowed = jnp.repeat(group_score >= cut, E // n_group, axis=1)
    kth = jax.lax.top_k(scores, top_k)[0][:, -1:]
    kept = ((scores >= kth) & allowed).sum() / jnp.float32(S * top_k)
    return allowed, kept


def topk_routing(logits: jax.Array, top_k: int, norm_topk_prob: bool = False,
                 *, score_func: str = "softmax",
                 bias: Optional[jax.Array] = None, route_scale: float = 1.0,
                 norm_eps: float = 0.0, n_group: int = 1,
                 topk_group: int = 1, return_kept: bool = False
                 ) -> Tuple[jax.Array, ...]:
    """Dropless top-k routing over float32 ``logits`` (S, E).

    Returns ``(weights (S, k), experts (S, k) int32, counts (E,) int32,
    l_balance, l_z)``: the k largest scores of each token (softmax
    probabilities, renormalised to sum to 1 only with ``norm_topk_prob``:
    over ``sum + norm_eps``),
    how many of the S*k assignments each expert received, the
    load-balancing loss ``E * sum_e f_e * P_e`` (``f_e`` expert e's share
    of the assignments, ``P_e`` its mean score; 1 when balanced under
    softmax) and the router z-loss ``mean_s logsumexp(logits_s)^2``.

    ``score_func="sigmoid"`` scores each expert on its own.  With ``bias``
    (E,) the k experts are those of the largest ``score + bias`` and the
    weights stay their scores: the bias picks, it does not weigh, and no
    gradient reaches it.  ``route_scale`` multiplies the weights last.

    ``n_group`` > 1: the k experts are taken among those that
    :func:`group_limit` allows (of ``score + bias``); ``return_kept`` then
    adds its ``kept`` share as a sixth result (1.0 without groups)."""
    S, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1) if score_func == "softmax" \
        else jax.nn.sigmoid(logits)
    select = probs if bias is None else probs + jax.lax.stop_gradient(bias)
    kept = jnp.float32(1.0)
    if n_group > 1:
        allowed, kept = group_limit(select, top_k, n_group, topk_group)
        select = jnp.where(allowed, select, -jnp.inf)
    _, experts = jax.lax.top_k(select, top_k)
    # each chosen expert's own score, as top_k's values or
    # take_along_axis(probs, experts) give it bit for bit (one term of a
    # sum is not zero), with no gather and, in the backward, no scatter-add
    # into (S, E); the expert axis leads, so the sum runs over whole vector
    # registers of tokens and not across the lanes
    hit = experts.T[None] == jnp.arange(E)[:, None, None]        # (E, k, S)
    weights = jnp.where(hit, probs.T[:, None], 0.0).sum(0).T      # (S, k)
    if norm_topk_prob:
        total = weights.sum(axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    if route_scale != 1.0:
        weights = weights * route_scale
    counts = _count_ids(experts.reshape(-1), E)
    l_balance = E * jnp.sum(counts.astype(jnp.float32) / (S * top_k)
                            * probs.mean(axis=0))
    l_z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    out = weights, experts.astype(jnp.int32), counts, l_balance, l_z
    return out + (kept,) if return_kept else out


# the leaf of a gate that is state and no parameter: the selection bias
STATE_LEAF = "expert_bias"


def bias_update(counts: jax.Array, bias: jax.Array, rate: float) -> jax.Array:
    """The selection bias after a step that routed ``counts`` (E,) pairs
    to the experts of its layer: up by ``rate`` where an expert received
    fewer than the mean, down where more (the loss-free balancing of
    arXiv:2408.15664, as AFMoE's trainer applies it)."""
    c = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(c.mean() - c)


class TopKGate(nn.Module):
    """Gate module (reference ``sharded_moe.py:352``): fp32 linear + top-k."""

    cfg: MoEConfig
    model_dim: int

    @nn.compact
    def __call__(self, x: jax.Array, train: bool,
                 logits_only: bool = False):
        cfg = self.cfg
        wg = self.param("wg", nn.with_partitioning(
            nn.initializers.normal(0.02), ("embed", "experts_gate")),
            (self.model_dim, cfg.routed), jnp.float32)
        xf = x.astype(jnp.float32)
        if train and cfg.noisy_gate_policy == "Jitter":
            rng = self.make_rng("gating")
            xf = xf * jax.random.uniform(rng, xf.shape, minval=0.98, maxval=1.02)
        # a float32 router means float32 products: the TPU's default
        # precision would round both operands to bf16 first
        logits = jnp.dot(xf, wg, precision=jax.lax.Precision.HIGHEST)
        if logits_only:     # with the selection bias, where there is one
            bias = None if cfg.bias_update_rate is None else self.param(
                STATE_LEAF, nn.with_partitioning(
                    nn.initializers.zeros, ("experts_gate",)), (cfg.routed,),
                jnp.float32)
            return logits, bias
        S = logits.shape[0]
        factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
        capacity = _capacity(S, cfg.num_experts, factor, cfg.min_capacity,
                             cfg.top_k)
        rng = self.make_rng("gating") if (train and cfg.noisy_gate_policy == "RSample") else None
        if cfg.top_k == 1:
            return top1_gating(logits, capacity, rng, cfg.noisy_gate_policy)
        if cfg.top_k == 2:
            return top2_gating(logits, capacity, rng, cfg.noisy_gate_policy)
        raise ValueError(
            f"the capacity gate (drop_tokens=True) is GShard's top-1/top-2, "
            f"got top_k={cfg.top_k}; set drop_tokens=False for the dropless "
            f"top-k dispatch")


def _expert_ffn(act: str, ws, x, matmul, swiglu=None):
    """The expert FFN over ``matmul(x, w)``, which pairs every row of ``x``
    with its own expert's matrix of the (E, ., .) leaf ``w``.  ``swiglu``
    (the sorted dispatch of a share: ``ops/grouped_matmul.py swiglu_plan``)
    is ``silu(a) * b`` of ``[a | b]``, ONE product with ``[gate | up]``;
    without it gate and up are a product each."""
    if act == "swiglu":
        gate, up, down = ws
        if swiglu is None:
            return matmul(nn.silu(matmul(x, gate)) * matmul(x, up), down)
        return matmul(swiglu(matmul(x, jnp.concatenate([gate, up], axis=2))),
                      down)
    wi, wo = ws
    return matmul(nn.gelu(matmul(x, wi), approximate=True), wo)


def sorted_dispatch(x: jax.Array, weights: jax.Array, chosen: jax.Array,
                    ws: Tuple[jax.Array, ...], act: str,
                    first_expert: Optional[int] = None) -> jax.Array:
    """Dropless expert FFN of tokens ``x`` (S, M), token s going to experts
    ``chosen[s]`` (k of them) with ``weights[s]``; ``ws`` the (E, ., .)
    expert leaves.  Rows are sorted by expert, multiplied group by group and
    sorted back; no (token, choice) pair is left out and an expert nobody
    chose costs nothing.

    ``first_expert`` (a share): ``ws`` holds experts ``first_expert`` to
    ``first_expert + E - 1`` of those ``chosen`` names.  Pairs held
    elsewhere are sorted behind the last group, where no matmul tile
    covers them, and add nothing to their token: the buffer keeps a row
    for every pair, so no routing, however collapsed onto the held
    experts, drops one.  (A buffer of twice the share's even part of the
    pairs ran 13% faster on the v5e and drops pairs once a layer collapses
    onto the held experts: PERF.md section 6, PR 30.)

    The rows move through ``ops/grouped_matmul.py repeat_gather`` (into
    expert order) and ``combine_rows`` (back, weighted and summed a token):
    for a share on one TPU device, or a rank of the ``shard_map`` below,
    Pallas row kernels (``ops/pallas/moe_rows.py``: one DMA a row that
    holds a pair, none for the three in four that hold none, the weighted
    sum in VMEM with no ``(S, k, M)`` array); with every expert held, or
    anywhere else, XLA's gathers (``kernel_dispatch_total{site="moe_rows"}``
    says which and why).  Between the grouped matmuls of a share's SwiGLU
    experts stands one buffer ``[a | b]``: one product with
    ``[gate | up]`` and the row kernel ``swiglu_rows`` over the rows that
    hold a pair, where a full permutation has a product each and XLA's
    ``silu(a) * b`` (``kernel_dispatch_total{site="moe_swiglu"}``).

    Tokens do not interact, so under data parallelism each rank does this
    for its own tokens inside a ``shard_map`` over the batch axes (its own
    argsort, its own group sizes, the Pallas grouped matmul on its own
    rows), with the expert leaves replicated.  Where ``kernel_mesh_plan``
    refuses the mesh (tp, sp, pp, or rows the batch axes do not divide) the
    same code runs on the global arrays with XLA's ragged dot, which the
    partitioner can split."""
    from ..ops.pallas.spmd import kernel_mesh_plan

    E = ws[0].shape[0]
    share = first_expert is not None
    verdict, batch_axes = kernel_mesh_plan(x.shape[0])

    def one_rank(x, weights, chosen, *ws):
        S, k = chosen.shape
        with trace.device_span("moe/route"):
            flat = chosen.reshape(-1)
            if share:
                flat = flat - first_expert
                flat = jnp.where((flat >= 0) & (flat < E), flat, E)
            order = jnp.argsort(flat, stable=True)
            inv = jnp.argsort(order)
            sizes = _count_ids(flat, E)
            if share:
                # past the groups a row holds no pair, and a pair held
                # elsewhere has no row: the gathers read zeros there,
                # whatever the grouped matmul leaves in rows it skips
                order = jnp.where(jnp.arange(S * k) < sizes.sum(), order,
                                  S * k)
                inv = jnp.where(flat < E, inv, S * k)
        own = verdict is not None           # one device's own operands
        with trace.device_span("moe/dispatch"):
            rows = repeat_gather(x, order, inv, share,            # (S*k, M)
                                 per_device=own)
        with trace.device_span("moe/experts"):
            one_buffer = act == "swiglu" and swiglu_plan(
                rows, ws[0].shape[2], own, share)
            rows = _expert_ffn(
                act, ws, rows,
                lambda a, w: grouped_matmul(a, w, sizes, per_device=own),
                (lambda ab: swiglu_rows(ab, order)) if one_buffer else None)
        with trace.device_span("moe/combine"):
            return combine_rows(rows, weights, order, inv, share,
                                per_device=own)

    if verdict != "shard":
        return one_rank(x, weights, chosen, *ws)
    rows_spec = P(batch_axes)
    return jax.shard_map(
        one_rank, mesh=mesh_lib.get_mesh(),
        in_specs=(rows_spec,) * 3 + (P(),) * len(ws), out_specs=rows_spec,
        check_vma=False)(x, weights, chosen, *ws)


class ExpertsMLP(nn.Module):
    """E parallel FFNs with a leading expert dim sharded on ``ep``."""

    num_experts: int
    model_dim: int
    hidden_dim: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    w8: bool = False                   # int8 expert weights (ops/w8.py)
    w8_group: int = 128
    act: str = "gelu"                  # 'gelu' (wi, wo) | 'swiglu' (gate, up, down)
    # a share: the first of the routed experts that this instance holds
    first_expert: Optional[int] = None

    def _weight(self, name: str, down: bool = False):
        """One (experts, embed, mlp) leaf, or (experts, mlp, embed) for a
        ``down`` projection, cast to the compute dtype."""
        E, M, H = self.num_experts, self.model_dim, self.hidden_dim
        axes, shape = (("experts", "mlp", "embed"), (E, H, M)) if down \
            else (("experts", "embed", "mlp"), (E, M, H))
        return self.param(name, nn.with_partitioning(
            nn.initializers.normal(0.02), axes), shape,
            self.param_dtype).astype(self.dtype)

    def _weights(self) -> Tuple[jax.Array, ...]:
        """The expert leaves in the order :func:`_expert_ffn` takes them."""
        if self.act == "swiglu":
            return (self._weight("gate"), self._weight("up"),
                    self._weight("down", down=True))
        if self.act != "gelu":
            raise ValueError(f"expert_act must be 'gelu' or 'swiglu', got "
                             f"{self.act!r}")
        return self._weight("wi"), self._weight("wo", down=True)

    @nn.compact
    def __call__(self, x: jax.Array,
                 routing: Optional[Tuple[jax.Array, jax.Array]] = None
                 ) -> jax.Array:
        # (E, C, M) capacity-padded batch; or, with ``routing`` = (weights,
        # experts), both (S, k), x (S, M) tokens through the dropless
        # sorted dispatch.  Param declarations are IDENTICAL on both
        # paths, so one trained tree serves them.
        if routing is not None:
            if self.w8:
                raise NotImplementedError(
                    "int8 expert weights have no grouped-matmul path")
            return sorted_dispatch(x, *routing, self._weights(), self.act,
                                   self.first_expert)
        if self.w8:
            if self.act != "gelu":
                raise NotImplementedError(
                    f"{self.act} experts run the capacity einsum or the "
                    f"sorted dispatch; the int8 path is gelu-only")
            from ..ops.w8 import w8a16_expert_matmul

            def qparams(name, K, N, names):
                # codes keep the fp kernel's logical axes (TP sharding
                # intact); the grouped-scale K/g dim replicates
                g = self.w8_group if K % self.w8_group == 0 else K
                codes = self.param(name + "_q", nn.with_partitioning(
                    nn.initializers.zeros, names),
                    (self.num_experts, K, N), jnp.int8)
                scale = self.param(name + "_s", nn.with_partitioning(
                    nn.initializers.ones, (names[0], None, names[-1])),
                    (self.num_experts, K // g, N), jnp.float32)
                return codes, scale

            wi_q, wi_s = qparams("wi", self.model_dim, self.hidden_dim,
                                 ("experts", "embed", "mlp"))
            wo_q, wo_s = qparams("wo", self.hidden_dim, self.model_dim,
                                 ("experts", "mlp", "embed"))
            h = nn.gelu(w8a16_expert_matmul(x, wi_q, wi_s),
                        approximate=True)
            return w8a16_expert_matmul(h, wo_q, wo_s)
        return _expert_ffn(self.act, self._weights(), x,
                           lambda a, w: jnp.einsum("ecm,emh->ech", a, w))


class SharedExpert(nn.Module):
    """The SwiGLU every token runs beside its routed experts (AFMoE's
    ``shared_experts``): dense leaves, whole on every share.  ``gated``:
    times ``sigmoid(x . token_gate)``, a scalar a token."""

    model_dim: int
    hidden_dim: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    gated: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        def weight(name, axes, shape):
            return self.param(name, nn.with_partitioning(
                nn.initializers.normal(0.02), axes), shape,
                self.param_dtype).astype(self.dtype)

        M, H = self.model_dim, self.hidden_dim
        gate = weight("gate", ("embed", "mlp"), (M, H))
        up = weight("up", ("embed", "mlp"), (M, H))
        down = weight("down", ("mlp", "embed"), (H, M))
        out = jnp.dot(nn.silu(jnp.dot(x, gate)) * jnp.dot(x, up), down)
        if self.gated:
            token_gate = weight("token_gate", ("embed",), (M,))
            score = jnp.dot(x, token_gate, preferred_element_type=jnp.float32)
            out = (out * jax.nn.sigmoid(score)[:, None]).astype(out.dtype)
        return out


class MoELayer(nn.Module):
    """Drop-in MoE FFN (reference ``MOELayer`` ``sharded_moe.py:440`` +
    ``MoE`` wrapper ``layer.py:18``).

    Input ``(..., model_dim)`` → output ``(..., model_dim)``; also returns
    the aux loss.  The dispatched tensor is constrained to the ``ep`` axis,
    making XLA emit the all-to-all pair on ICI.
    """

    cfg: MoEConfig
    model_dim: int
    hidden_dim: int
    dtype: Any = jnp.bfloat16
    w8: bool = False                   # int8 expert weights for serving
    w8_group: int = 128

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False,
                 return_stats: bool = False):
        """``(out, weighted aux loss)``; with ``return_stats`` also the dict
        of small per-layer statistics that :func:`record_stats` books."""
        cfg = self.cfg
        orig_shape = x.shape
        x2 = x.reshape(-1, self.model_dim)                        # (S, M)
        experts = ExpertsMLP(cfg.num_experts, self.model_dim,
                             self.hidden_dim, dtype=self.dtype, w8=self.w8,
                             w8_group=self.w8_group, act=cfg.expert_act,
                             first_expert=None if cfg.holds_all
                             else cfg.first_expert,
                             name="experts")
        gate = TopKGate(cfg, self.model_dim, name="gate")
        mesh = mesh_lib.get_mesh(required=False)
        ep1 = mesh is None or mesh.shape.get("ep", 1) == 1
        S, E, k = x2.shape[0], cfg.routed, cfg.top_k
        l_z = jnp.float32(0.0)
        elsewhere = jnp.int32(0)
        bias = None
        if not cfg.drop_tokens:
            if not ep1:
                raise NotImplementedError(
                    "MoEConfig(drop_tokens=False) is the one-chip / data-"
                    "parallel sorted dispatch; with ep > 1 the sorted rows "
                    "need an all-to-all that is not written yet (PERF.md "
                    "section 7, row 8). It does not fall back to the "
                    "capacity path: use drop_tokens=True or ep=1")
            if not 1 <= k <= E:
                raise ValueError(f"top_k must be in 1..{E}, got {k}")
            with trace.device_span("moe/route"):
                logits, bias = gate(x2, train, logits_only=True)
                weights, chosen, counts, l_aux, l_z, kept = topk_routing(
                    logits, k, cfg.norm_topk_prob, score_func=cfg.score_func,
                    bias=bias, route_scale=cfg.route_scale,
                    norm_eps=cfg.norm_topk_eps, n_group=cfg.n_group,
                    topk_group=cfg.topk_group, return_kept=True)
            out = experts(x2, routing=(weights, chosen))
            if cfg.num_shared_experts:
                with trace.device_span("moe/shared"):
                    out = out + SharedExpert(
                        self.model_dim,
                        self.hidden_dim * cfg.num_shared_experts,
                        dtype=self.dtype, gated=cfg.shared_expert_gate,
                        name="shared")(x2)
            # pairs no expert's group holds (an id outside 0..E-1): the
            # grouped matmul multiplies exactly counts.sum() rows, or, of
            # a share, those of its own experts; the pairs routed to
            # experts held elsewhere are nobody's loss
            dropped = jnp.int32(S * k) - counts.sum()
            if not cfg.holds_all:
                here = counts[cfg.first_expert:
                              cfg.first_expert + cfg.num_experts]
                elsewhere = counts.sum() - here.sum()
        else:
            l_aux, combine, dispatch = gate(x2, train)
            dispatched = jnp.einsum("sec,sm->ecm",
                                    dispatch.astype(self.dtype), x2)
            dispatched = _constrain_ep(dispatched)            # all-to-all in
            expert_out = experts(dispatched)
            expert_out = _constrain_ep(expert_out)            # all-to-all out
            out = jnp.einsum("sec,ecm->sm", combine.astype(self.dtype),
                             expert_out)
            counts = dispatch.sum(axis=(0, 2)).astype(jnp.int32)
            dropped = jnp.int32(S * k) - counts.sum()

        if cfg.use_residual:
            # PR-MoE: dense MLP branch + learned 2-way mix (layer.py:106-125)
            from ..models.gpt2 import GPT2Config  # avoid cycle at module load

            dense = nn.Dense(self.hidden_dim, dtype=self.dtype, name="residual_fc1")(x2)
            dense = nn.gelu(dense, approximate=True)
            dense = nn.Dense(self.model_dim, dtype=self.dtype, name="residual_fc2")(dense)
            coef = nn.Dense(2, dtype=self.dtype, name="coefficient")(x2)
            coef = jax.nn.softmax(coef, axis=-1)
            out = out * coef[..., 0:1] + dense * coef[..., 1:2]

        aux = l_aux * cfg.aux_loss_weight + l_z * cfg.z_loss_weight
        out = out.reshape(orig_shape)
        if not return_stats:
            return out, aux
        stats = {"tokens_per_expert": counts, "dropped": dropped,
                 "balance_loss": l_aux, "router_z": l_z}
        if not cfg.holds_all:
            stats["elsewhere"] = elsewhere
        if bias is not None:
            stats[STATE_LEAF] = bias
        if cfg.n_group > 1:
            stats["group_kept_share"] = kept
        return out, aux, stats


def record_stats(stats: Dict[str, Any]) -> None:
    """Book one finished step's routing statistics in the registry.

    ``stats`` is the host copy of what :class:`MoELayer` returned with
    ``return_stats``, stacked over the model's MoE layers:
    ``tokens_per_expert`` (L, E), ``dropped`` (L,), ``balance_loss`` (L,),
    ``router_z`` (L,), and from a layer that holds a share of its experts
    ``elsewhere`` (L,), and from a layer routed under a selection bias
    ``expert_bias`` (L, E), the bias the step selected with.  Counters ``moe_tokens_per_expert{layer,expert}``
    (max / mean over a layer's experts is its load imbalance),
    ``moe_dropped_tokens_total`` (0 on the dropless path, always) and
    ``moe_pairs_elsewhere_total`` (pairs routed to experts that another
    instance holds: not dropped, not multiplied here); gauges
    ``moe_aux_loss`` / ``moe_router_z``, the layer means of the last step.
    Under a group limit: gauge ``moe_group_kept_share{layer}`` from
    ``group_kept_share`` (L,).
    With a bias: gauge ``moe_expert_bias{layer, stat=min|max}``, counter
    ``moe_bias_updates_total`` (one a layer a step: the engine applies
    :func:`bias_update` in the step that returned these statistics).
    """
    counts = np.asarray(stats["tokens_per_expert"])
    counts = counts.reshape(-1, counts.shape[-1])
    per_expert = registry.counter(
        "moe_tokens_per_expert",
        "(token, choice) pairs routed to each expert of each MoE layer",
        ("layer", "expert"))
    for layer, row in enumerate(counts):
        for expert, n in enumerate(row):
            per_expert.labels(layer, expert).inc(float(n))
    registry.counter(
        "moe_dropped_tokens_total",
        "(token, choice) pairs an expert's capacity turned away"
    ).inc(float(np.sum(stats["dropped"])))
    if "elsewhere" in stats:
        registry.counter(
            "moe_pairs_elsewhere_total",
            "(token, choice) pairs routed to an expert that another "
            "instance of the expert-parallel layer holds"
        ).inc(float(np.sum(stats["elsewhere"])))
    if STATE_LEAF in stats:
        bias = np.asarray(stats[STATE_LEAF]).reshape(counts.shape)
        spread = registry.gauge(
            "moe_expert_bias", "the selection bias over a layer's routed "
            "experts, last finished step", ("layer", "stat"))
        for layer, row in enumerate(bias):
            spread.labels(layer, "min").set(float(row.min()))
            spread.labels(layer, "max").set(float(row.max()))
        registry.counter(
            "moe_bias_updates_total", "selection-bias updates the compiled "
            "step made, one a biased layer a step").inc(float(len(bias)))
    if "group_kept_share" in stats:
        kept = registry.gauge(
            "moe_group_kept_share", "(token, choice) pairs of the "
            "unrestricted top-k that the group limit (n_group, topk_group) "
            "left in place, as a share of all pairs: 1 where it changed no "
            "choice; last finished step", ("layer",))
        for layer, share in enumerate(
                np.asarray(stats["group_kept_share"]).reshape(-1)):
            kept.labels(layer).set(float(share))
    registry.gauge("moe_aux_loss", "load-balancing loss, mean over layers, "
                   "last finished step").set(float(np.mean(stats["balance_loss"])))
    registry.gauge("moe_router_z", "router z-loss, mean over layers, last "
                   "finished step").set(float(np.mean(stats["router_z"])))


def _constrain_ep(x: jax.Array) -> jax.Array:
    """Pin the leading (expert) dim to the ``ep`` axis if a mesh is active."""
    mesh = mesh_lib.get_mesh(required=False)
    if mesh is None or mesh.shape.get("ep", 1) == 1:
        return x
    from jax.sharding import NamedSharding

    spec = P("ep", *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
