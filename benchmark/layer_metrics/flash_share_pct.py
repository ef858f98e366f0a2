"""Device time of the flash-attention kernels (forward and backward, by
kernel name in the trace) over device busy time, in percent."""


def read(obs):
    tr = obs.get("trace")
    names = obs["cell"].config.get("trace_names", {})
    if tr is None or "flash" not in names:
        return None
    t = tr.ops_matching(names["flash"])
    return 100.0 * t / tr.busy_s if t > 0 else None
