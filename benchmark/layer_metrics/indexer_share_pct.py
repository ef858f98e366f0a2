"""Device time under the program's three indexer scopes (``attn/indexer``:
the indexer's projections, its key's LayerNorm and rotary;
``attn/select``: the exact top-k selection, a kernel of its own;
``attn/indexer_loss``: the reductions of the indexer's loss and counters
outside the kernels; forward, a remat's second forward and backward) over
the step's device time, in percent: what the learned selection costs
outside the attention kernels.  What those kernels spend on the indexer
inside themselves (the scores of a tile rebuilt in every kernel, the second
sweep that reads the heads' probabilities, the indexer's gradient) is part
of ``flash_share_pct``: a kernel's time has no scopes.

The v5e's device events carry an instruction's name and no scope, so the
split is ``engine.profile_device_scopes``'s: a short profiler session of
the driver's own after the window (``observed["device_scope_ms"]``: ms a
step under ``indexer`` and of the whole step).  A driver or a program
without it (no learned selection, a rehearsal, a commit from before the
scopes) gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    if not ms or not ms.get("step") or "indexer" not in ms:
        return None
    return 100.0 * ms["indexer"] / ms["step"]
