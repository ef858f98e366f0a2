"""Device time under the program's ``loss_head`` scope (a looped stack's
``total_ut_steps`` exits through the ONE chunked head: the rows' logits, the
softmax, ``dh`` and ``dW``) and under ``ut/exit_gate`` (the gates, the exit
distribution, its entropy), forward and backward, over the step's device
time, in percent: what the exits cost beside the passes they read.

The v5e's device events carry an instruction's name and no scope, so the
split is a short profiler session of the driver's own after the window
(``observed["device_scope_ms"]``, ``drivers/train_ouro.py scope_split``).  A
driver or a program without it (one pass, a rehearsal, a commit from before
the loop) gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or "ut/exit_gate" not in ms:
        return None
    return 100.0 * (ms["loss_head"] + ms["ut/exit_gate"]) / ms["step"]
