"""Device time under the program's ``mlp_dense`` scope (the dense SwiGLU of
every block: gate, up and down; forward, the remat's second forward and
backward) over the step's device time, in percent: what the feed-forward
costs in a stack with no experts.

The v5e's device events carry an instruction's name and no scope, so the
split is ``engine.profile_device_scopes``'s: a short profiler session of
the driver's own after the window (``observed["device_scope_ms"]``: ms a
step under ``mlp_dense`` and of the whole step, as ``linear_attn_share_pct``
reads its own).  A driver or a program without it (a rehearsal, a commit
from before the scope was set on every dense block) gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or "mlp_dense" not in ms:
        return None
    return 100.0 * ms["mlp_dense"] / ms["step"]
