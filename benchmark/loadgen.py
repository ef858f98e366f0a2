"""Traffic from a data file and a seed, and the arithmetic on what came back.

One general generator per kind of traffic; a mix is a JSON file of
parameters under ``benchmark/traffic/``.  The mixture / jitter / shared
prefix / Zipf-output model is the one of
``deepspeed_tpu/telemetry/loadgen.py generate_trace`` (copied, not
imported: the yardstick must not move with the program).  The run's
``--seed`` draws everything: request sizes, arrival gaps and token ids, so
two seeds offer two traces of the same mix and no judged number hangs on
one frozen order (PERF.md section 6, PR 23).  A lead-in is the same mix
under another stream of the same seed.

The percentile is the repo's nearest-rank convention
(``telemetry/registry.pct``), copied for the same reason.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

_LEAD_IN_SALT = 0x5EED


def pct(sorted_xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over an ascending sequence; NaN on empty."""
    if not sorted_xs:
        return float("nan")
    return sorted_xs[min(len(sorted_xs) - 1, int(q * len(sorted_xs)))]


def _rng(*words: int) -> np.random.Generator:
    # --seed may exceed 2**31; SeedSequence takes any non-negative ints
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF for w in words])


# ----------------------------------------------------------------------
# serving: an open-loop request trace
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Request:
    idx: int
    arrival_s: float              # due time, seconds from the trace start
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int
    shared_prefix: bool


def serve_trace(mix: dict, seed: int, seconds: float, vocab_size: int,
                lead_in: bool = False) -> List[Request]:
    """Requests due in ``[0, seconds)``: Poisson arrivals at the mix's
    fixed ``rate_rps``, prompt lengths from the mixture with jitter (free
    lengths, no lattice), output lengths ``gen_len_min`` - 1 + Zipf capped at
    ``gen_len_max``, a share of prompts opening with one system prompt.
    ``lead_in=True`` gives the same mix under another stream of the same
    seed, for the set-up's lead-in."""
    rng = _rng(seed, _LEAD_IN_SALT if lead_in else 0)
    lens, weights = zip(*mix["prompt_len_mix"])
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    jitter = float(mix["prompt_len_jitter"])
    sp_len = int(mix.get("shared_prefix_len", 0))
    sp_ratio = float(mix.get("shared_prefix_ratio", 0.0))
    total = int(mix["max_total_len"])
    rate = float(mix["rate_rps"])
    # one system prompt per seed, the same in lead-in and window
    prefix = _rng(seed, 1).integers(0, vocab_size, size=sp_len).astype(np.int32)
    reqs: List[Request] = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / rate))
        if t >= seconds:
            return reqs
        mode = int(lens[int(rng.choice(len(lens), p=w))])
        plen = max(1, int(round(mode * (1.0 + rng.uniform(-jitter, jitter)))))
        gen = int(mix["gen_len_min"]) - 1 + int(rng.zipf(mix["gen_len_zipf_a"]))
        gen = max(int(mix["gen_len_min"]), min(int(mix["gen_len_max"]), gen))
        shared = bool(sp_len and rng.random() < sp_ratio)
        if shared:
            # the shared prefix plus at least one token of its own, so the
            # real last token still goes through prefill
            plen = max(plen, sp_len + 1)
        plen = min(plen, total - 1)
        gen = max(1, min(gen, total - plen))
        own = rng.integers(0, vocab_size, size=plen - (sp_len if shared else 0))
        own = own.astype(np.int32)
        prompt = np.concatenate([prefix, own]) if shared else own
        reqs.append(Request(len(reqs), t, prompt, gen, shared))


def trace_sha256(reqs: Sequence[Request]) -> str:
    h = hashlib.sha256()
    for r in reqs:
        h.update(float(r.arrival_s).hex().encode())
        h.update(r.prompt.tobytes())
        h.update(str((r.idx, r.max_new_tokens, r.shared_prefix)).encode())
    return h.hexdigest()


# ----------------------------------------------------------------------
# training: packed documents
# ----------------------------------------------------------------------
def packed_batches(mix: dict, seed: int, rows: int, vocab_size: int
                   ) -> Iterator[Dict[str, np.ndarray]]:
    """An endless stream of ``{"input_ids", "labels"}`` global batches of
    ``rows`` x ``seq_len`` tokens: documents of log-normal length (a fixed
    pool of lengths from ``mix_seed``, reordered by ``seed``) of
    Zipf-distributed token ids, each closed by ``eos``, packed end to end
    with no padding — a sequence may begin in the middle of a document."""
    seq = int(mix["seq_len"])
    pool_rng = _rng(mix["mix_seed"])
    dl = mix["doc_len_lognormal"]
    pool = np.exp(pool_rng.normal(np.log(dl["median"]), dl["sigma"],
                                  size=int(mix["doc_pool"])))
    pool = np.clip(np.round(pool), mix["doc_len_min"],
                   mix["doc_len_max"]).astype(np.int64)
    rng = _rng(seed, 2)
    pool = pool[rng.permutation(len(pool))]
    eos = int(mix.get("eos_token_id", vocab_size - 1))
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["token_zipf_a"]))
    cdf /= cdf[-1]
    need = rows * seq
    buf = np.empty(0, np.int32)
    d = 0
    while True:
        parts = [buf]
        have = len(buf)
        while have < need:
            n = int(pool[d % len(pool)])
            d += 1
            doc = np.searchsorted(cdf, rng.random(n)).astype(np.int32)
            doc[-1] = eos
            parts.append(doc)
            have += n
        flat = np.concatenate(parts)
        ids = flat[:need].reshape(rows, seq)
        buf = flat[need:]
        yield {"input_ids": ids, "labels": ids}


# ----------------------------------------------------------------------
# arithmetic on per-request records
# ----------------------------------------------------------------------
def tpot_ms(records: Sequence[dict]) -> List[float]:
    """Mean gap between output tokens after the first, one value for each
    request that has emitted at least two tokens, ascending."""
    out = []
    for r in records:
        n, t1, tl = r.get("n_out", 0), r.get("first_token"), r.get("last_emit")
        if n >= 2 and t1 is not None and tl is not None:
            out.append((tl - t1) * 1e3 / (n - 1))
    return sorted(out)


def tail(values: Sequence[float], q: float = 0.95) -> Optional[float]:
    """The q-quantile of ascending ``values``, or None when empty."""
    return None if not values else float(pct(values, q))
