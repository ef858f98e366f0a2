"""Device time under the program's ``linear_attn/decay_gate`` scope (the
projection ``h W_f`` and the gate's activation into a float32 log-decay a
key channel; forward, the remat's second forward and backward) over the
step's device time, in percent: what a decay a key channel costs OUTSIDE
the delta rule, which a decay a head does not have.

The split is ``engine.profile_device_scopes``'s, from the driver's short
profiler session after the window (``observed["device_scope_ms"]``, as
``linear_attn_share_pct`` reads its own).  A driver or a program without
the scope gives ``None``."""
SCOPE = "linear_attn/decay_gate"


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or SCOPE not in ms:
        return None
    return 100.0 * ms[SCOPE] / ms["step"]
