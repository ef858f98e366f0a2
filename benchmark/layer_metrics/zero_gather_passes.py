"""How many times a train step gathers its ZeRO-sharded weights: the bytes
one device receives through the step's all-gathers (the program's gauge
``step_collective_recv_bytes{site="engine.train_step", op="all-gather"}``,
from the executable's optimized HLO) over what ONE pass over the partition
has to move (``zero_required_recv_bytes{what="gather"}``, booked by
``parallel/zero.py`` when the engine places its state: every sharded leaf
in the compute type x (n-1)/n).  2 by ZeRO-3's design (forward and
backward), 3 if a remat's second forward gathers again; below a whole
number where the partitioner moved activations instead of a weight (a
vocabulary-sharded table).  ``None`` without the gauges."""
from benchmark.layer_metrics import _program, collective_recv_gib_step

REQUIRED = "zero_required_recv_bytes"


def read(obs):
    moved = collective_recv_gib_step.by_op(obs)
    if moved is None or "all-gather" not in moved:
        return None
    entry = _program.registry_snapshot().get(REQUIRED)
    for sample in (entry or {}).get("samples", ()):
        if sample["labels"].get("what") == "gather" and sample["value"] > 0:
            return moved["all-gather"] / sample["value"]
    return None
