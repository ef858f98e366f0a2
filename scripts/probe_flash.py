#!/usr/bin/env python
"""Isolated flash-attention fwd+bwd timing at given model dims across
tile configs — finds the per-shape tile recipe for the autotuner.

Usage: python scripts/probe_flash.py B=2 H=25 S=1024 D=64
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    kv = dict(a.split("=", 1) for a in sys.argv[1:])
    B = int(kv.get("B", 2)); H = int(kv.get("H", 25))
    S = int(kv.get("S", 1024)); D = int(kv.get("D", 64))
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)

    # causal useful flops (fwd 2 matmuls + bwd 3) ~ (2+3)*2*B*H*S^2*D/2
    flops = 5 * B * H * S * S * D

    def run(bq, bk):
        reps = 50   # one compiled scan: a single dispatch

        def f(q, k, v):
            def loss(q, k, v):
                return flash_attention(
                    q, k, v, causal=True, block_q=bq,
                    block_k=bk).astype(jnp.float32).sum()

            def body(carry, _):
                l, grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2))(q + carry.astype(q.dtype) * 0,
                                             k, v)
                # keep the backward LIVE: fold the grads into the carry
                # (discarding them would let XLA dead-code the dq/dkv
                # kernels and time forward-only)
                g_sum = sum(g.astype(jnp.float32).sum() for g in grads)
                return l + 0.0 * g_sum, None

            l, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=reps)
            return l

        jf = jax.jit(f)
        jax.device_get(jf(q, k, v))
        t0 = time.perf_counter()
        jax.device_get(jf(q, k, v))
        dt = (time.perf_counter() - t0) / reps
        return dt

    results = []
    for bq, bk in [(512, 512), (256, 512), (512, 256), (256, 256),
                   (1024, 512), (512, 1024), (1024, 1024), (128, 512),
                   (256, 1024)]:
        try:
            dt = run(bq, bk)
            results.append(((bq, bk), dt))
            print(json.dumps({
                "bq": bq, "bk": bk, "ms": round(dt * 1e3, 3),
                "tflops": round(flops / dt / 1e12, 1)}), flush=True)
        except Exception as e:
            print(json.dumps({"bq": bq, "bk": bk,
                              "error": repr(e)[:160]}), flush=True)
    best = min(results, key=lambda r: r[1])
    print(json.dumps({"best": best[0],
                      "ms": round(best[1] * 1e3, 3)}), flush=True)


if __name__ == "__main__":
    main()
