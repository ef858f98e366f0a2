"""Device time under the program's ``diffusion/`` scopes (drawing the
noise, building ``[x~ ; x]`` and the doubled position ids, slicing the
noisy half for the head and weighing its loss) over the step's device
time, in percent: what the objective costs outside the layers.

The v5e's device events carry an instruction's name and no scope, so the
split is ``engine.profile_device_scopes``'s: a short profiler session of
the driver's own after the window, every instruction named through the
optimized HLO of the step that ran (``observed["device_scope_ms"]``: ms a
step under ``diffusion`` and of the whole step).  A driver or a program
without it (no block-diffusion objective, a rehearsal, a commit from before
the scopes) gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step"):
        return None
    return 100.0 * ms.get("diffusion", 0.0) / ms["step"]
