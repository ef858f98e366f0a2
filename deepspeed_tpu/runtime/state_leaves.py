"""State leaves: leaves of a model's ``params`` that are state and no
parameter.

A model declares them with two methods: ``is_state_leaf(path) -> bool``
over a leaf's path of dict keys, and ``update_state_leaves(held, stats) ->
held`` from the step's statistics (its ``out["stats"]``, counts summed
over the step's micro-batches).  The engine then keeps them out of
everything the optimizer does (no gradient is taken for them, no moments
are allocated, neither weight decay nor the clipped norm sees them) and
applies the model's rule inside the compiled step, after the update: no
host round trip and no second executable.  They are saved and restored
with the parameters, and ``eval_batch`` only reads them.

``parallel/moe.py``'s selection bias (``expert_bias``) is the first.
"""
from __future__ import annotations

from typing import Callable, Tuple


def split(tree: dict, is_state: Callable[[tuple], bool],
          path: tuple = ()) -> Tuple[dict, dict]:
    """``(rest, held)``: the nested dict ``tree`` without its state
    leaves, and those alone; a dict left empty is left out."""
    rest, held = {}, {}
    for key, sub in tree.items():
        if isinstance(sub, dict):
            r, h = split(sub, is_state, path + (key,))
            if r:
                rest[key] = r
            if h:
                held[key] = h
        else:
            (held if is_state(path + (key,)) else rest)[key] = sub
    return rest, held


def merge(rest: dict, held: dict) -> dict:
    """The tree :func:`split` took apart."""
    out = dict(rest)
    for key, sub in held.items():
        out[key] = merge(out.get(key, {}), sub) if isinstance(sub, dict) \
            else sub
    return out
