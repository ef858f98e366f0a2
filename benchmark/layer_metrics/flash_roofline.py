"""The flash kernels' share of their roofline: the least time the chip
could take for the causal attention the traced steps required (FLOPs and
bytes from shapes, benchmark/flops.py) over the kernels' device time."""
from benchmark import flops


def read(obs):
    tr = obs.get("trace")
    names = obs["cell"].config.get("trace_names", {})
    if tr is None or "flash" not in names or obs.get("peak") is None:
        return None
    t = tr.ops_matching(names["flash"])
    if t <= 0:
        return None
    per_dev = obs["tokens"] / obs["n_devices"] * tr.window_s / obs["window_s"]
    least, _bound = flops.roofline_seconds(
        obs["attention_flops_per_token"] * per_dev,
        obs["attention_bytes_per_token"] * per_dev, obs["peak"])
    return 100.0 * least / t
