"""Device time of the collective instructions THE TRACE NAMES as such
(``trace_names.collective`` of the configuration) over device busy time,
in percent.  On the ``XLA Ops`` line an asynchronous collective is a short
start and a done that lasts as long as the device waited for it, and a
synchronous one holds the line for its whole length, so this is busy time
the device spent in those collectives that compute did NOT hide.  It is
not the layer's whole exposed share: the v5e's reduce-scatter is an
all-reduce and the slice of it in one ``fusion.<n>``, which
``trace_reduce`` folds into ``fusion`` with every other fusion, so no
pattern can take it (XL on four chips: 6.96 named of 14.8 in all).  The
program's ``engine.profile_device_scopes`` reads those by instruction,
through the executable's own ledger."""


def read(obs):
    tr = obs.get("trace")
    names = obs["cell"].config.get("trace_names", {})
    if tr is None or "collective" not in names:
        return None
    t = tr.ops_matching(names["collective"])
    return 100.0 * t / tr.busy_s if t > 0 else None
