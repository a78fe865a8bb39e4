"""The plain reference against the program's own model at a tiny size:
they are written independently, so agreement checks both."""

import jax
import numpy as np

from perfbench import harness

TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 128,
        "vocab_size": 256, "sliding_window": 24, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5}


def _model():
    from deepspeed_tpu.models import build_model
    return build_model("tiny", num_kv_heads=2, sliding_window=24,
                       max_seq_len=1024, attn_impl="reference")


def test_logits_agree_with_the_programs_model():
    ref = harness.load_module("configs", "mistral_reference")
    model = _model()
    params = model.init(jax.random.PRNGKey(3))
    ids = np.random.default_rng(0).integers(0, 256, 700).astype(np.int32)
    rows = np.array([0, 23, 24, 25, 511, 512, 699])
    want = np.asarray(model.apply(params, ids[None]))[0, rows]
    got = ref.logits_rows(params, ids, rows, TINY)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_loss_agrees_with_the_programs_model():
    ref = harness.load_module("configs", "mistral_reference")
    model = _model()
    params = model.init(jax.random.PRNGKey(4))
    ids = np.random.default_rng(1).integers(0, 256, (2, 65)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    want = float(model.loss(params, batch))
    assert abs(ref.loss(params, batch, TINY, row_block=32) - want) < 1e-4
