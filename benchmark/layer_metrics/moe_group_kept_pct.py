"""The (token, choice) pairs of the top-k WITHOUT groups that the group
limit (``n_group``, ``topk_group``) left in place, in percent of all pairs:
the median over the expert layers at the last finished step.  100 where the
limit changed no choice; what it reads below 100 is the share of the pairs
that the limit moved to another expert.

Read from the program's gauge ``moe_group_kept_share{layer}``
(``parallel/moe.py record_stats``).  A program without the gauge (routing
without groups, or a commit from before them) gives ``None``."""
import statistics

GAUGE = "moe_group_kept_share"


def read(obs):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    entry = get_registry().snapshot().get(GAUGE)
    if not entry or not entry["samples"]:
        return None
    return 100.0 * statistics.median(s["value"] for s in entry["samples"])
