"""The causal 512 x 512 tiles of the score matrix that hold at least one
kept pair over all causal tiles, in percent, mean of the layers, from the
program's gauge ``sparse_attention_live_tile_share`` as the last finished
step set it (``observed["sparse_live_tile_share"]``): the rest is what a
kernel that skips empty tiles could ever skip.  Near 100 while the
indexer's choices are scattered; it falls if training concentrates them.
A program without the gauge gives ``None``."""


def read(obs):
    share = obs.get("sparse_live_tile_share")
    return None if share is None else 100.0 * share
