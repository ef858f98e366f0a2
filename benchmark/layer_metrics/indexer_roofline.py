"""The indexer's share of its roofline: the least time the chip could take
for the scores a step's FORWARD requires (one product of 16 heads x 64
channels a CAUSAL pair, and the bytes of qI, kI and the head weights:
``benchmark/flops_keye.py``) over the device time under the program's three
indexer scopes (``indexer_share_pct``'s numerator: the projections, the
selection kernel, the loss's reductions).  The scores' backward (dq_I, dk_I,
dw) is required too, but it runs inside the attention kernels, whose time
has no scopes: it is counted where its time is, in ``train_mfu_pct``'s
operations and ``flash_share_pct``'s time, and left out of both sides here
so that work and time are of the same things.  Low by design while the
selection is a bisection over a panel of scores: 32 counting passes a query
are no required work.

A driver or a program without the scopes or the counts gives ``None``."""
from benchmark import flops


def read(obs):
    ms = (obs.get("device_scope_ms") or {}).get("indexer")
    if not ms or obs.get("peak") is None \
            or "indexer_flops_per_step" not in obs:
        return None
    least, _bound = flops.roofline_seconds(
        obs["indexer_flops_per_step"], obs["indexer_bytes_per_step"],
        obs["peak"])
    return 100.0 * least * 1e3 / ms
