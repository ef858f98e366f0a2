#!/usr/bin/env python3
"""The chunked language-model head alone on the chip, at each cell's shape:
loss and gradient of ``models/common.py chunked_lm_loss`` with respect to
the hidden states and the table (bf16 operands), and the loss alone (what
``eval_batch`` runs); milliseconds a call over fenced calls, the
head-sized products of the compiled program, and what
``lm_head_products_total`` counted while it was traced.

    chiprun -- python3 scripts/probe_lm_head.py [--cell mellum2 ...]

One JSON line a cell.  It times the checkout it lies in, and runs in any
that has ``chunked_lm_loss`` (one without the counter reads zeros there):
a copy in a parent's ``scripts/`` makes the other side of a pair.
"""
import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell -> (tokens a step, hidden, vocabulary, padded vocabulary, loss_chunk)
SHAPES = {
    "xl": (2048, 1600, 50257, 50304, 8192),
    "olmoe": (8192, 2048, 50304, 50304, 8192),
    "mellum2": (32768, 2304, 24576, 24576, 8192),
    "trinity": (24576, 2048, 25024, 25088, 8192),
    "joyai": (16384, 2048, 16160, 16256, 8192),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="append", choices=sorted(SHAPES))
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true",
                    help="a sixteenth of every size, for the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.common import chunked_lm_loss
    from deepspeed_tpu.telemetry import registry

    counter = registry.counter("lm_head_products_total", labelnames=("pass",))

    def counted():
        return {p: counter.labels(p).value
                for p in ("primal", "forward", "backward")}

    for cell in args.cell or sorted(SHAPES):
        n, e, v, vp, chunk = SHAPES[cell]
        if args.rehearse:
            n, e, v, vp, chunk = n // 16, e // 16, v // 16, vp // 16, chunk // 16
            v = min(v, vp)
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.normal(size=(1, n, e)), jnp.bfloat16)
        wte = jnp.asarray(rng.normal(size=(vp, e)) * 0.02, jnp.bfloat16)
        labels = jnp.asarray(rng.integers(0, v, size=(1, n)), jnp.int32)

        def loss(h, wte):
            return chunked_lm_loss(h, wte, labels, vocab_size=v,
                                   padded_vocab_size=vp, chunk=chunk,
                                   dtype=jnp.bfloat16)

        row = {"cell": cell, "tokens": n, "chunks": -(-n // chunk)}
        for name, fn in (("loss_and_grad", jax.value_and_grad(loss, (0, 1))),
                         ("loss", loss)):
            before = counted()
            compiled = jax.jit(fn).lower(h, wte).compile()
            row[name + "_traced"] = {k: val - before[k]
                                     for k, val in counted().items()}
            row[name + "_products"] = len(re.findall(
                r" (?:convolution|dot)\(", compiled.as_text()))
            jax.block_until_ready(compiled(h, wte))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = compiled(h, wte)
            jax.block_until_ready(out)
            row[name + "_ms"] = round(
                (time.perf_counter() - t0) / args.calls * 1e3, 3)
        # one product at the chip's bf16 peak, for scale
        row["product_ms_at_197_tflops"] = round(
            2 * n * e * vp / 197e12 * 1e3, 3)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
