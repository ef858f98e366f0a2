"""Device time under the program's five ``linear_attn/`` scopes (the two
projections in, the causal filter, the gated delta rule, the gated norm,
the projection back; forward, the remat's second forward and backward) over
the step's device time, in percent: what the Gated DeltaNet layers' token
mixers cost.

The v5e's device events carry an instruction's name and no scope, so the
split is ``engine.profile_device_scopes``'s: a short profiler session of
the driver's own after the window (``observed["device_scope_ms"]``: ms a
step under ``linear_attn`` and of the whole step, as
``short_conv_share_pct`` reads its own).  A driver or a program without it
(no such layer, a rehearsal, a commit from before the scopes) gives
``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or "linear_attn" not in ms:
        return None
    return 100.0 * ms["linear_attn"] / ms["step"]
