"""Device time under the program's three ``short_conv/`` scopes (the
projection to three thirds, the gated causal filter, the projection back;
forward, the remat's second forward and backward) over the step's device
time, in percent: what the conv layers' token mixers cost.

The v5e's device events carry an instruction's name and no scope, so the
split is ``engine.profile_device_scopes``'s: a short profiler session of
the driver's own after the window, every instruction named through the
optimized HLO of the step that ran (``observed["device_scope_ms"]``: ms a
step under ``short_conv`` and of the whole step).  A driver or a program
without it (no conv layer, a rehearsal, a commit from before the scopes)
gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or "short_conv" not in ms:
        return None
    return 100.0 * ms["short_conv"] / ms["step"]
