"""How far apart the passes of a looped stack run: (slowest - fastest) /
mean of the ``total_ut_steps`` passes' device time a step, forward, the
remat's second forward and backward together, by the program's
``ut/pass_<t>`` scopes, in percent.  The passes run the same shapes over the
same leaves, so anything but a few percent is a remat, layout or fusion
difference between them.

From the driver's own short profiler session after the window
(``observed["device_scope_ms"]["ut/pass"]``, one number a pass:
``drivers/train_ouro.py scope_split``).  Under a scanned loop the passes
would be one scope and the reading 0; the cell's loop is unrolled
(``trace_names`` of the configuration file).  A driver or a program without
the scopes gives ``None``."""


def read(obs):
    ms = obs.get("device_scope_ms")
    each = (ms or {}).get("ut/pass")
    if not each or not sum(each):
        return None
    return 100.0 * (max(each) - min(each)) * len(each) / sum(each)
