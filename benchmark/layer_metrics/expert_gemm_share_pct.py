"""Device time of the experts' grouped matrix multiplications (forward
and both backward passes, by instruction name in the trace: the
configuration's ``trace_names.expert_gemm``) over device busy time, in
percent."""


def read(obs):
    tr = obs.get("trace")
    names = obs["cell"].config.get("trace_names", {})
    if tr is None or "expert_gemm" not in names:
        return None
    t = tr.ops_matching(names["expert_gemm"])
    return 100.0 * t / tr.busy_s if t > 0 else None
