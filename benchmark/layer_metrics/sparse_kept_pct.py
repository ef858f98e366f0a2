"""The (query, key) pairs the learned selection kept over all causal pairs
of the step's rows, in percent, mean of the layers, from the program's
gauge ``sparse_attention_kept_share`` as the last finished step set it
(``observed["sparse_kept_share"]``).  12.1 for top-2048 of 32,768-position
rows, by construction: a descriptor that says the selection ran at the
configuration's ``topk``, as ``moe_held_pair_pct`` describes the routing.
A program without the gauge gives ``None``."""


def read(obs):
    share = obs.get("sparse_kept_share")
    return None if share is None else 100.0 * share
