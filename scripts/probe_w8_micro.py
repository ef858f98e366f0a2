"""Round-5: which W8A16 impl wins per M-regime on the real chip?

Times (reps inside ONE compiled lax.scan, per the bench-measurement
rules) four impls at gpt2-760m serving shapes:
  pallas      — ops/pallas/w8_matmul.py panel kernel
  geinsum     — grouped einsum (current XLA fallback)
  dequant     — materialize bf16 weight, one big dot
  bf16        — dense bf16 baseline (the fp serving path reads this)
Run: python scripts/probe_w8_micro.py
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from deepspeed_tpu.ops.pallas.w8_matmul import w8a16_matmul_pallas  # noqa: E402
from deepspeed_tpu.ops.w8 import quantize_weight  # noqa: E402

REPS = 4000   # µs-scale kernels need thousands of in-scan reps before
              # compute dominates the blocking call


def timed(fn, *args):
    def body(c, _):
        y = fn(*args)
        return c + y.astype(jnp.float32).sum(), None

    run = jax.jit(lambda: jax.lax.scan(body, jnp.float32(0),
                                       None, length=REPS)[0])
    run().block_until_ready()
    t0 = time.perf_counter()
    run().block_until_ready()
    return (time.perf_counter() - t0) / REPS * 1e3   # ms/op


def geinsum(x, codes, scale, g):
    G = scale.shape[0]
    xg = x.reshape(*x.shape[:-1], G, g)
    cg = codes.reshape(G, g, -1)
    part = jnp.einsum("...ug,ugn->...un", xg.astype(jnp.bfloat16),
                      cg.astype(jnp.bfloat16))
    return jnp.einsum("...un,un->...n", part.astype(jnp.float32),
                      scale).astype(x.dtype)


def dequant_dot(x, codes, scale, g):
    G = scale.shape[0]
    w = (codes.reshape(G, g, -1).astype(jnp.float32)
         * scale[:, None, :]).reshape(codes.shape).astype(jnp.bfloat16)
    return jnp.dot(x, w)


def main():
    key = jax.random.PRNGKey(0)
    for K, N in [(1280, 3840), (1280, 5120), (5120, 1280)]:
        w = jax.random.normal(key, (K, N), jnp.float32)
        codes, scale = quantize_weight(w, 128)
        codes, scale = jax.device_put(codes), jax.device_put(scale)
        wb = jnp.asarray(w, jnp.bfloat16)
        for M in (8, 16, 64, 256):
            x = jax.random.normal(key, (M, K), jnp.bfloat16)
            r = {
                "pallas": timed(w8a16_matmul_pallas, x, codes, scale),
                "geinsum": timed(geinsum, x, codes, scale, 128),
                "dequant": timed(dequant_dot, x, codes, scale, 128),
                "bf16": timed(jnp.dot, x, wb),
            }
            best = min(r, key=r.get)
            print(f"K={K:5d} N={N:5d} M={M:3d}  "
                  + "  ".join(f"{k}={v:7.3f}ms" for k, v in r.items())
                  + f"  best={best}", flush=True)


if __name__ == "__main__":
    main()
