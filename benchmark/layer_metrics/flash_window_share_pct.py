"""Device time of the sliding-window layers' flash kernels (forward and
backward, the kernels named ``trace_names.flash_window``) over device busy
time, in percent.  A configuration without that name gives ``None``."""


def read(obs):
    tr = obs.get("trace")
    name = obs["cell"].config.get("trace_names", {}).get("flash_window")
    if tr is None or name is None:
        return None
    t = tr.ops_matching(name)
    return 100.0 * t / tr.busy_s if t > 0 else None
