"""Load imbalance of the experts: max / mean tokens an expert of one MoE
layer received, the median over the window's steps and layers (1.0 is a
perfect balance; the grouped matmul's longest group sets its tail).

Read from the program's counter ``moe_tokens_per_expert{layer,expert}``:
the driver takes :func:`snapshot` at every step boundary of the window
(the program books a step's counts once the step has finished, so a
difference of two snapshots may hold 0, 1 or 2 steps: empty ones are left
out, and max / mean does not depend on how many it holds).  A program
without the counter gives ``None``."""
import statistics

import numpy as np

COUNTER = "moe_tokens_per_expert"


def snapshot():
    """The counter as a (layers, experts) array, or ``None`` when the
    program has not booked it (yet)."""
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    entry = get_registry().snapshot().get(COUNTER)
    if not entry or not entry["samples"]:
        return None
    cells = {(int(s["labels"]["layer"]), int(s["labels"]["expert"])): s["value"]
             for s in entry["samples"]}
    out = np.zeros((1 + max(k[0] for k in cells), 1 + max(k[1] for k in cells)))
    for (layer, expert), v in cells.items():
        out[layer, expert] = v
    return out


def read(obs):
    snaps = [s for s in obs.get(COUNTER) or [] if s is not None]
    ratios = []
    for a, b in zip(snaps, snaps[1:]):
        if a.shape != b.shape:
            continue
        for row in b - a:
            if row.sum() > 0:
                ratios.append(row.max() / row.mean())
    return statistics.median(ratios) if ratios else None
