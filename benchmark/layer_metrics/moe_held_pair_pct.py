"""The (token, choice) pairs this chip's share of the experts multiplied,
over all the pairs that were routed, in percent: the median over the
window's steps and layers.  25 where 16 of 64 experts are held and the
routing is balanced; the expert load a cell's ``why`` promises rests on it.

Read from the program's counters: ``moe_tokens_per_expert{layer,expert}``
(the driver's snapshots at step boundaries, as ``moe_load_imbalance``
reads them; the columns of the held experts are the configuration's
``moe.first_expert`` and ``num_experts``) less what
``moe_dropped_tokens_total`` turned away.  A configuration that holds no
share, or a program without the counters, gives ``None``."""
import statistics

from benchmark.layer_metrics import moe_load_imbalance

DROPPED = "moe_dropped_tokens_total"


def _dropped():
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return 0.0
    entry = get_registry().snapshot().get(DROPPED)
    return sum(s["value"] for s in entry["samples"]) if entry else 0.0


def _held_columns(obs):
    """``(snapshots, first, count)`` of the held experts' columns, or
    ``None`` where the configuration holds no share."""
    conf = obs["cell"].config
    moe = conf.get("moe", {})
    if "routed_experts" not in moe or "num_experts" not in conf:
        return None
    snaps = [s for s in obs.get(moe_load_imbalance.COUNTER) or []
             if s is not None]
    return snaps, int(moe.get("first_expert", 0)), int(conf["num_experts"])


def read(obs):
    found = _held_columns(obs)
    if found is None:
        return None
    snaps, first, held = found
    shares = []
    for a, b in zip(snaps, snaps[1:]):
        if a.shape != b.shape or b.shape[1] < first + held:
            continue
        for row in b - a:
            if row.sum() > 0:
                shares.append(row[first:first + held].sum() / row.sum())
    if not shares:
        return None
    total = snaps[-1].sum()
    lost = _dropped() / total if total > 0 else 0.0
    return 100.0 * (statistics.median(shares) - lost)


def of_the_window(obs):
    """All the pairs the window routed to the held experts over all it
    routed, a share in 0..1: what the driver counts the window's required
    expert operations from (device time sums the steps and layers, so the
    work must too: a median of unequal layers is not their mean).  ``None``
    as :func:`read`."""
    found = _held_columns(obs)
    if found is None:
        return None
    snaps, first, held = found
    if len(snaps) < 2 or snaps[0].shape != snaps[-1].shape \
            or snaps[-1].shape[1] < first + held:
        return None
    routed = snaps[-1] - snaps[0]
    if routed.sum() <= 0:
        return None
    return float(routed[:, first:first + held].sum() / routed.sum())
