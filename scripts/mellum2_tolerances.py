#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/mellum2-12b-a2.5b-z3-8bit.json`` ``reference_check``
must refuse: the plain reference with both operands of every matrix
multiplication rounded to float8 e4m3 (loss, every MoE layer, one attention
layer of each type) and the attention layer computed wrong in five named
ways (``reference.FAULTS``), each against what the PROGRAM computes on the
same weights and inputs.  Prints one line a reading; the numbers go into
the configuration file's ``*_reason`` and PERF.md by hand.

    chiprun -- python3 scripts/mellum2_tolerances.py [--refuse] [seed ...]

``--refuse`` then holds the limits that were written to those readings:
each control stands in the reference's place through the cell's own
comparison (``train_mellum2.check_reference`` on a harness ``Context``)
and has to come out not ``correct``, and the sound reference ``correct``;
exit code 1 where one does not.  ``--rehearse`` runs the same control flow
at the configuration's CPU sizes (where the limits mean nothing: no exit
code).
"""
import json
import sys
import time
import types

from mellum2_cell import build

FP8 = (4, 3)


def control(reference, fault=None, operand_bits=None):
    """The reference with one thing wrong: an attention ``fault`` on the
    layer type it applies to, or every matmul's operands in ``operand_bits``."""
    def attention(kind, *a, **kw):
        sliding = kind == reference.SLIDING
        if fault in ("window+1", "no_window") and not sliding \
                or fault in ("default_rope", "no_attention_factor") and sliding:
            return reference.attention(kind, *a, **kw)
        return reference.attention(kind, *a, fault=fault,
                                   operand_bits=operand_bits, **kw)

    bits = {} if operand_bits is None else {"operand_bits": operand_bits}
    return types.SimpleNamespace(
        layers=reference.layers, attention=attention,
        loss_parts=lambda *a, **kw: reference.loss_parts(*a, **bits, **kw),
        expert_ffn=lambda *a, **kw: reference.expert_ffn(*a, **bits, **kw))


def refusals(seed, rehearse):
    """Every control through the cell's comparison; the names of those that
    came out ``correct`` and should not have (or the reverse)."""
    from benchmark.harness.runner import Context

    cell, driver, engine, cfg, conf, batches = build(seed, rehearse)
    reference = cell.reference()
    first = next(batches)       # the row the run compares
    wrong = []
    for name, stand_in in (
            [("sound", reference), ("fp8", control(reference,
                                                   operand_bits=FP8))]
            + [(f, control(reference, fault=f)) for f in reference.FAULTS]):
        ctx = Context(cell, seed, 0.0, False, rehearse, None,
                      time.perf_counter())
        driver.check_reference(ctx, engine, cfg, conf, stand_in,
                               iter([first]))
        print(json.dumps({"seed": seed, "what": "refusal", "control": name,
                          "correct": not ctx.notes, "notes": ctx.notes}),
              flush=True)
        if (not ctx.notes) != (name == "sound"):
            wrong.append(name)
    return wrong


def main(seeds, rehearse=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.llama import LlamaAttention
    from deepspeed_tpu.parallel.moe import MoELayer

    rel = lambda a, b: float(np.linalg.norm(np.asarray(a, np.float32) - np.asarray(b, np.float32))
                             / np.linalg.norm(np.asarray(b, np.float32)))
    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = build(seed, rehearse)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = engine.state.params
        kw = driver.reference_kwargs(conf)
        got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
        ffn_in, attn_in = [], []
        ce, aux = reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in,
                                       attn_inputs=attn_in)
        ce8, aux8 = reference.loss_parts(params, ids, **kw, operand_bits=FP8)
        print(json.dumps({"seed": seed, "what": "loss", "engine": got,
                          "sound": got - float(ce) - float(aux),
                          "fp8": got - float(ce8) - float(aux8)}), flush=True)
        leaves = list(reference.layers(params, len(ffn_in)))
        layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                         hidden_dim=cfg.expert_size, dtype=cfg.dtype)
        run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
        for i, (p, h) in enumerate(zip(leaves, ffn_in)):
            h = h.astype(cfg.dtype)
            out = run(p["moe"], h)
            ekw = dict(top_k=conf["num_experts_per_tok"], norm_topk_prob=True,
                       first_expert=cfg.moe.first_expert)
            print(json.dumps({
                "seed": seed, "what": "experts", "layer": i,
                "sound": rel(out, reference.expert_ffn(p["moe"], h, **ekw)),
                "fp8": rel(out, reference.expert_ffn(p["moe"], h, **ekw,
                                                     operand_bits=FP8))}),
                  flush=True)
        kinds = list(cfg.kinds)
        for kind in dict.fromkeys(kinds):
            i = kinds.index(kind)
            module = LlamaAttention(cfg, kind)
            h = attn_in[i].astype(cfg.dtype)
            pos = jnp.arange(h.shape[1])[None, :]
            out = jax.jit(lambda p, h: module.apply({"params": p}, h, pos,
                                                    None))(
                leaves[i]["self_attn"], h)
            akw = dict(n_head=cfg.num_attention_heads, n_kv_head=cfg.kv_heads,
                       head_dim=cfg.head_dim,
                       sliding_window=conf["sliding_window"],
                       rope_parameters=conf["rope_parameters"])
            want = lambda **extra: reference.attention(
                kind, leaves[i]["self_attn"], h, **akw, **extra)
            readings = {"sound": rel(out, want()),
                        "fp8": rel(out, want(operand_bits=FP8))}
            for fault in reference.FAULTS:
                if fault in ("window+1", "no_window") \
                        and kind != "sliding_attention":
                    continue
                if fault in ("default_rope", "no_attention_factor") \
                        and kind != "full_attention":
                    continue
                readings[fault] = rel(out, want(fault=fault))
            print(json.dumps(dict(seed=seed, what="attention", layer=i,
                                  kind=kind, **readings)), flush=True)
        del engine


if __name__ == "__main__":
    flags = {"--rehearse", "--refuse"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    if "--refuse" not in sys.argv:
        main(seeds, rehearse)
    else:
        wrong = {seed: refusals(seed, rehearse) for seed in seeds}
        print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
        if any(wrong.values()) and not rehearse:
            sys.exit(1)
