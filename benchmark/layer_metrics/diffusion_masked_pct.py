"""The share of the window's DATA tokens that block-diffusion training's
noise masked, in percent: masked / (masked + kept).  About 50 under
``t ~ U(t_min, 1]``; the loss, and the head's required operations, are
over the masked tokens alone, so a fall would say that a throughput was
bought by noising less.  A descriptor, as ``moe_held_pair_pct`` is.

Read from the program's counter ``diffusion_tokens_total{kind}``: the
driver's difference over the window (``observed["diffusion_tokens"]``)
where it took one, else the counter's totals since the first step.  A
program without the counter (no block-diffusion objective, or a commit
from before it) gives ``None``."""

COUNTER = "diffusion_tokens_total"


def totals():
    """``{"masked": n, "kept": n}`` booked so far, or ``None``."""
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    entry = get_registry().snapshot().get(COUNTER)
    if not entry or not entry["samples"]:
        return None
    out = {"masked": 0.0, "kept": 0.0}
    for s in entry["samples"]:
        kind = s["labels"].get("kind")
        if kind in out:
            out[kind] += s["value"]
    return out


def share(counts):
    """masked / all of a ``totals()`` dict, 0..1, or ``None``."""
    if not counts:
        return None
    n = counts["masked"] + counts["kept"]
    return counts["masked"] / n if n > 0 else None


def read(obs):
    found = share(obs.get("diffusion_tokens") or totals())
    return None if found is None else 100.0 * found
