"""Median over requests of the mean gap between output tokens after the
first (ms), over the requests whose first token came inside the window and
that had two tokens or more by its end: the pace a user reads at while
every slot is busy."""
from benchmark import loadgen


def read(obs):
    if "records" not in obs:
        return None
    t0 = obs["window"][0]
    return loadgen.tail(loadgen.tpot_ms(
        [r for r in obs["records"] if r.get("first_token", -1) >= t0]), 0.5)
