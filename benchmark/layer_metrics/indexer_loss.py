"""The indexer's loss of the last finished step: ``KL(attention's
head-mean probabilities || softmax of the indexer's scores)`` over the kept
keys, summed over the layers, from the program's gauge ``indexer_loss``
(``observed["indexer_loss"]``).  It falls as the indexer learns to rank
keys as attention weighs them.  A program without the gauge gives
``None``."""


def read(obs):
    return obs.get("indexer_loss")
