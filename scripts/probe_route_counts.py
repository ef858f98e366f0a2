#!/usr/bin/env python3
"""The router's count and pick on the chip, each form alone at a cell's
size: ``jnp.bincount`` (a scatter-add), the compare-and-sum that
``parallel/moe.py _count_ids`` ships (the ids along the lanes), the same
with the bins last, and the count as a matmul of an exact 0 / 1 one-hot;
then ``take_along_axis`` and ``lax.top_k``'s values against the masked sum
of ``topk_routing`` (the expert axis leading) and the same with the
experts last, forward and forward + backward.  Device time of one call,
all its instructions, from a profiler trace
(``telemetry/device_scopes.capture``).  Alone, XLA picks the layouts that
suit each form; in the step its neighbours pick them, so
``probe_mellum2_scopes.py --top moe/route`` has the last word.

    chiprun -- python3 scripts/probe_route_counts.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (tokens a step, top-k, experts the router scores)
SIZES = {"mellum2": (32768, 8, 64), "trinity": (24576, 8, 128),
         "olmoe": (8192, 8, 64)}


def device_ms(fn, args, reps=10):
    import jax

    from deepspeed_tpu.telemetry import device_scopes

    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))

    def run():
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)

    events = device_scopes.capture(run)
    return sum(d for evs in events.values() for _, _, d in evs) / reps / 1e6


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel.moe import _count_ids

    def bins_last(ids, n):
        return (ids[:, None] == jnp.arange(n, dtype=ids.dtype)).sum(
            0, dtype=jnp.int32)

    def matmul(ids, n):
        hot = (ids[:, None] == jnp.arange(n, dtype=ids.dtype)).astype(
            jnp.bfloat16)
        return jnp.dot(jnp.ones((8, ids.shape[0]), jnp.bfloat16), hot,
                       preferred_element_type=jnp.float32)[0].astype(jnp.int32)

    def masked(probs, experts):             # as topk_routing has it
        hit = experts.T[None] == jnp.arange(probs.shape[-1])[:, None, None]
        return jnp.where(hit, probs.T[:, None], 0.0).sum(0).T

    def masked_experts_last(probs, experts):
        return jnp.where(experts[..., None] == jnp.arange(probs.shape[-1]),
                         probs[:, None, :], 0.0).sum(-1)

    def gathered(probs, experts):
        return jnp.take_along_axis(probs, experts, axis=-1)

    for cell, (S, k, E) in SIZES.items():
        key = jax.random.PRNGKey(0)
        probs = jax.nn.sigmoid(jax.random.normal(key, (S, E), jnp.float32))
        experts = jax.lax.top_k(probs, k)[1]
        counted = jnp.bincount(experts.reshape(-1), length=E)
        # from the (S, k) ids the router has, the reshape inside the call
        for name, count in (("bincount", lambda i, n: jnp.bincount(
                i, length=n).astype(jnp.int32)), ("compare_sum", _count_ids),
                ("compare_sum_bins_last", bins_last), ("matmul", matmul)):
            fn = lambda e: count(e.reshape(-1), E)
            assert (jax.jit(fn)(experts) == counted).all(), name
            print(json.dumps({"cell": cell, "count": name, "ids": S * k,
                              "bins": E,
                              "device_ms": device_ms(fn, (experts,))}),
                  flush=True)
        def squared(pick):
            return jax.grad(lambda p, e: (pick(p, e) ** 2).sum())

        want = gathered(probs, experts), squared(gathered)(probs, experts)
        # the last two find the ids inside the call, as a router does:
        # top_k's values (their backward a scatter-add) against its ids
        # and the masked sum
        for name, pick in (("take_along_axis", gathered),
                           ("masked_sum", masked),
                           ("masked_sum_experts_last", masked_experts_last),
                           ("top_k_values", lambda p, e: jax.lax.top_k(
                               p, k)[0]),
                           ("top_k_ids_masked_sum", lambda p, e: masked(
                               p, jax.lax.top_k(p, k)[1]))):
            grad = squared(pick)
            assert (jax.jit(pick)(probs, experts) == want[0]).all(), name
            assert (jax.jit(grad)(probs, experts) == want[1]).all(), name
            print(json.dumps({
                "cell": cell, "pick": name, "shape": [S, k, E],
                "forward_ms": device_ms(pick, (probs, experts)),
                "forward_backward_ms": device_ms(grad, (probs, experts))}),
                flush=True)


if __name__ == "__main__":
    main()
