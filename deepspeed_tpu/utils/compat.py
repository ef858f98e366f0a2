"""The manual-SPMD names the repo uses, under the spellings its call sites
were written against.  Code for the one installation there is (jax 0.9):
``jax.shard_map`` (``check_vma`` / ``axis_names`` keywords),
``lax.axis_size`` and ``lax.pcast`` all exist, so nothing here branches
on a version.
"""
from __future__ import annotations

from jax import lax, shard_map  # noqa: F401  (re-exported)
from jax.lax import axis_size  # noqa: F401  (re-exported)


def pcast_varying(x, axis):
    """Mark ``x`` as varying over the manual ``axis``."""
    return lax.pcast(x, (axis,), to="varying")
