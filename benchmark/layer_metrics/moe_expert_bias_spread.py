"""How far apart the selection bias holds a layer's routed experts, in the
units of their scores: ``max_e b - min_e b`` of each expert layer at the
last finished step, the median over the layers.  0 before the first
update; it grows by at most twice the update rate a step while the
balancing still works against the router, and stands still once every
expert's load crosses the mean as often from above as from below.

Read from the program's gauge ``moe_expert_bias{layer, stat=min|max}``
(``parallel/moe.py record_stats``).  A program without the gauge (routing
without a selection bias, or a commit from before it) gives ``None``."""
import statistics

GAUGE = "moe_expert_bias"


def read(obs):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    entry = get_registry().snapshot().get(GAUGE)
    if not entry or not entry["samples"]:
        return None
    layers = {}
    for s in entry["samples"]:
        layers.setdefault(s["labels"]["layer"], {})[s["labels"]["stat"]] = \
            s["value"]
    spreads = [v["max"] - v["min"] for v in layers.values()
               if "max" in v and "min" in v]
    return statistics.median(spreads) if spreads else None
