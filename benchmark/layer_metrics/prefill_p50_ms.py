"""Median of prefill start -> first token on the host (ms), over the
requests whose prefill began inside the window."""
from benchmark import loadgen


def read(obs):
    if "records" not in obs:
        return None
    t0, t1 = obs["window"]
    d = sorted((r["first_token"] - r["prefill_start"]) * 1e3
               for r in obs["records"]
               if "first_token" in r and t0 <= r.get("prefill_start", -1) < t1)
    return loadgen.tail(d, 0.5)
