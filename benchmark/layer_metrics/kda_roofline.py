"""The delta rule under a decay a key channel (Kimi Delta Attention), as a
share of its roofline: the least time the chip could take for the rules the
traced steps required (operations and bytes from shapes and by the
recurrence, ``benchmark/flops_ling3.py``: forward and backward; a remat's
second forward and the chunked form's extra products do not count) over the
device time the rule took (all of what the chip spent on it).

As ``gated_delta_roofline`` reads either: where the rule is a kernel of its
own, that time is the kernels' in the window's trace, by the name the
configuration's ``trace_names`` gives (``kda``); where it is XLA's fusions,
which carry no name of the program's on the v5e, it is the ms a step under
the ``linear_attn/delta_rule`` scope of ``engine.profile_device_scopes``
(``observed["device_scope_ms"]``).  A driver or a program with neither
gives ``None``."""
from benchmark import flops


def read(obs):
    if obs.get("peak") is None or "kda_bytes_per_step" not in obs:
        return None
    least, _bound = flops.roofline_seconds(
        obs["kda_flops_per_step"], obs["kda_bytes_per_step"], obs["peak"])
    tr = obs.get("trace")
    name = obs["cell"].config.get("trace_names", {}).get("kda")
    if tr is not None and name:
        t = tr.ops_matching(name)
        if t > 0:
            steps = obs["steps"] * tr.window_s / obs["window_s"]
            return 100.0 * least * steps / t
    ms = (obs.get("device_scope_ms") or {}).get("linear_attn/delta_rule")
    return 100.0 * least * 1e3 / ms if ms else None
