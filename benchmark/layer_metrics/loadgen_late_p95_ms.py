"""95th percentile of (the time the generator submitted a request to the
batcher) - (its due time) over the requests due in the window, in ms: how
late the load generator ran, so that a starved generator cannot read as a
fast server.  The loop that submits also steps the batcher, so this is the
length of a ``step`` call as an arrival meets it."""
from benchmark import loadgen


def read(obs):
    if "records" not in obs:
        return None
    t1 = obs["window"][1]
    late = sorted((r.get("submit", t1) - r["due"]) * 1e3
                  for r in obs["records"] if r["tag"] == "win")
    return loadgen.tail(late, 0.95)
