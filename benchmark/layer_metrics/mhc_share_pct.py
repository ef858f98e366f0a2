"""Device time under the program's five ``mhc/`` scopes (a sublayer's maps:
the row's statistics, ``x @ phi``, the sigmoids and the Sinkhorn sweeps; the
mix ``H_pre @ X``; the write-back ``H_res @ X + H_post^T y``; the widening
after the table and the lanes' sum before the norm; forward, the remat's
second forward and backward) over the step's device time, in percent: what
a residual stream of several lanes costs beside the sublayers it wraps.

The v5e's device events carry an instruction's name and no scope, so the
split is ``engine.profile_device_scopes``'s: a short profiler session of the
driver's own after the window (``observed["device_scope_ms"]``: ms a step
under ``mhc`` and of the whole step, as ``linear_attn_share_pct`` reads its
own).  A driver or a program without it (one lane, a rehearsal, a commit
from before the scopes) gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or "mhc" not in ms:
        return None
    return 100.0 * ms["mhc"] / ms["step"]
