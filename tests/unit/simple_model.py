"""Tiny model/data fixtures — analog of reference ``tests/unit/simple_model.py``
(``SimpleModel`` :12, ``random_dataloader``, ``args_from_dict``)."""
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.core import meta


class SimpleModel(nn.Module):
    """Linear stack with MSE loss; returns scalar loss like the reference's
    SimpleModel returns CrossEntropy(x, y)."""

    hidden_dim: int = 16
    nlayers: int = 2

    @nn.compact
    def __call__(self, x, y, deterministic: bool = True):
        h = x
        for i in range(self.nlayers):
            h = nn.Dense(self.hidden_dim, name=f"linear_{i}")(h)
            h = nn.relu(h)
        out = nn.Dense(y.shape[-1], name="head")(h)
        return {"loss": jnp.mean((out - y) ** 2), "logits": out}

    def dummy_inputs(self, batch_size=2, seq_len=None):
        return {"x": jnp.zeros((batch_size, self.hidden_dim)),
                "y": jnp.zeros((batch_size, self.hidden_dim))}


class EmbedModel(nn.Module):
    """Untied-embedding LM head — the shape of model sparse_gradients
    targets (reference sparse grads come from nn.Embedding(sparse=True))."""

    vocab: int = 64
    dim: int = 16

    @nn.compact
    def __call__(self, input_ids, labels, deterministic: bool = True):
        h = nn.Embed(self.vocab, self.dim, name="tok_embed")(input_ids)
        h = nn.relu(nn.Dense(self.dim, name="proj")(h))
        logits = nn.Dense(self.vocab, name="head")(h)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return {"loss": jnp.mean(nll), "logits": logits}

    def dummy_inputs(self, batch_size=2, seq_len=8):
        ids = jnp.zeros((batch_size, seq_len), jnp.int32)
        return {"input_ids": ids, "labels": ids}


@functools.lru_cache(maxsize=None)
def _seeded(model):
    return meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])


def seeded_params(model):
    """Unboxed leaves of ``model.init`` at key 0 on a ``(1, 8)`` row of zeros,
    ONE compiled program a model (a flax module hashes by its fields) a
    process: thirty serving tests built them bare, sixty executables a call.
    The arrays are shared; the dicts that hold them are the caller's own."""
    return jax.tree_util.tree_map(lambda a: a, _seeded(model))


def tiny_gpt2_engine(cfg_over=None, mp_size=1, **kwargs):
    """``init_inference`` of gpt2-tiny in float32 on :func:`seeded_params`:
    the engine a dozen serving files built by a copy each."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", dtype=jnp.float32,
                                        **(cfg_over or {})))
    return deepspeed_tpu.init_inference(
        model=model, mp_size=mp_size, dtype=jnp.float32,
        params=seeded_params(model), **kwargs)


def random_dataset(total_samples: int, hidden_dim: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(total_samples, hidden_dim)).astype(np.float32)
    ys = (xs @ rng.normal(size=(hidden_dim, hidden_dim)).astype(np.float32)) * 0.1
    return [{"x": xs[i], "y": ys[i]} for i in range(total_samples)]


def random_token_dataset(total_samples: int, seq_len: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(total_samples, seq_len)).astype(np.int32)
    return [{"input_ids": ids[i], "labels": ids[i]} for i in range(total_samples)]


def token_batch(batch_size: int, seq_len: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(batch_size, seq_len)).astype(np.int32)
    return {"input_ids": ids, "labels": ids}
