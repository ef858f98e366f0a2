"""BERT family tests: training smoke, sparse-attention variant, HF parity."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.bert import BertForPreTraining, bert_config

from . import reference_compare as compare


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _mlm_batch(batch, seq, vocab, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100).astype(np.int32)
    nsp = rng.integers(0, 2, size=(batch,)).astype(np.int32)
    return {"input_ids": ids, "labels": labels, "next_sentence_label": nsp}


def test_bert_trains_zero2():
    model = BertForPreTraining(bert_config("bert-tiny"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 2}})
    engine.init_params()
    batch = _mlm_batch(engine.train_batch_size, 64, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_bert_sparse_attention_variant():
    cfg = bert_config("bert-tiny", max_position_embeddings=128,
                      sparse_attention={"mode": "bigbird", "block": 16,
                                        "num_random_blocks": 1,
                                        "num_sliding_window_blocks": 3,
                                        "num_global_blocks": 1},
                      dtype=jnp.float32)
    model = BertForPreTraining(cfg)
    ids = np.random.default_rng(0).integers(0, 512, size=(2, 128)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(ids))
    out = model.apply(params, jnp.asarray(ids))
    assert out["logits"].shape == (2, 128, 512)
    assert np.isfinite(np.asarray(out["logits"], np.float32)).all()


def test_hf_bert_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    hf_model = transformers.BertForPreTraining(hf_cfg).eval()

    from deepspeed_tpu.module_inject import convert_hf_model

    model, params = convert_hf_model(hf_model, dtype=jnp.float32)
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 12))
    with torch.no_grad():
        hf_out = hf_model(torch.tensor(ids))
    out = compare.apply(model, params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(out["logits"][:, :, :128], np.float32),
        hf_out.prediction_logits.numpy(), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(out["nsp_logits"], np.float32),
        hf_out.seq_relationship_logits.numpy(), rtol=2e-3, atol=2e-3)
