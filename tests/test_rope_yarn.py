"""YaRN frequencies and RoPE by layer kind (Mellum2's ``rope_parameters``):
the program's ``models.layers.yarn_frequencies`` and the benchmark
reference's ``rope_table`` against numbers worked here from the formulas
(transformers' ``_compute_yarn_parameters``), against transformers itself
where it imports, and the model's dense forward (``CausalLM.apply``) against
the plain reference."""

import importlib.util
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import build_model, get_config
from deepspeed_tpu.models import layers as L

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 8192, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.2772588722239782}


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs", "mellum2_reference.py")
    spec = importlib.util.spec_from_file_location("mellum2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_hand(d=128, theta=5e5, s=16.0, l0=8192, fast=32.0, slow=1.0):
    """The issue's formulas, written out once more: (inv_freq, low, high)."""
    low = max(math.floor(d * math.log(l0 / (fast * 2 * math.pi))
                         / (2 * math.log(theta))), 0)
    high = min(math.ceil(d * math.log(l0 / (slow * 2 * math.pi))
                         / (2 * math.log(theta))), d - 1)
    inv = []
    for j in range(d // 2):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        plain = theta ** (-2 * j / d)
        inv.append((1 - ramp) * plain + ramp * plain / s)
    return np.asarray(inv), low, high


def test_published_yarn_numbers_by_hand(reference):
    """(D 128, theta 5e5, factor 16, original 8,192, beta 32 / 1): the
    ramp runs over bands 18 to 35; below it a band keeps its frequency,
    above it the band is divided by 16; the factor is 0.1 ln 16 + 1."""
    want, low, high = by_hand()
    assert (low, high) == (18, 35)
    cfg = get_config("mellum2-12b-a2.5b")
    got, factor = L.yarn_frequencies(cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    assert factor == pytest.approx(0.1 * math.log(16) + 1.0, abs=1e-15)
    assert factor == 1.2772588722239782
    plain = np.asarray(L.rope_frequencies(cfg))
    np.testing.assert_allclose(np.asarray(got)[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got)[35:], plain[35:] / 16,
                               rtol=1e-6)
    # band 26 sits 8/17 up the ramp
    assert np.asarray(got)[26] == pytest.approx(
        plain[26] * (1 - 8 / 17 + 8 / 17 / 16), rel=1e-5)
    ref, ref_factor = reference.rope_table(PUBLISHED, 128)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert ref_factor == factor
    # an attention_factor the config leaves out is computed
    cfg = cfg.replace(rope_yarn=(16.0, 8192, 32.0, 1.0, None))
    assert L.yarn_frequencies(cfg)[1] == pytest.approx(1.2772588722239782)


def test_rope_by_layer_follows_the_kind():
    """YaRN on the global layers of the pattern, plain frequencies and
    factor 1 on the windowed ones; on every layer of a stack that has no
    windowed ones; nothing for a model without it."""
    cfg = get_config("mellum2-12b-a2.5b", num_layers=8)
    inv, factor = L.rope_by_layer(cfg)
    plain = np.asarray(L.rope_frequencies(cfg))
    scaled = np.asarray(L.yarn_frequencies(cfg)[0])
    for layer in range(8):
        full = layer % 4 == 3
        np.testing.assert_array_equal(np.asarray(inv[layer]),
                                      scaled if full else plain)
        assert float(factor[layer]) == pytest.approx(
            1.2772588722239782 if full else 1.0)
    # a stack without windowed layers: every layer is one of full attention
    every, factors = L.rope_by_layer(cfg.replace(window_pattern=None,
                                                 sliding_window=None))
    assert np.all(np.asarray(every) == scaled[None])
    assert np.all(np.asarray(factors) > 1.27)
    assert L.rope_by_layer(get_config("mistral-7b")) is None
    assert build_model(get_config("tiny"))._rope_layers is None


@pytest.mark.parametrize("d,theta,factor,original", [
    (128, 5e5, 16.0, 8192), (64, 1e4, 4.0, 2048), (16, 1e4, 4.0, 32)])
def test_yarn_matches_transformers(reference, d, theta, factor, original):
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    scaling = {"rope_type": "yarn", "factor": factor, "beta_fast": 32.0,
               "beta_slow": 1.0,
               "original_max_position_embeddings": original}
    hf_cfg = types.SimpleNamespace(
        rope_theta=theta, head_dim=d, hidden_size=d * 4,
        num_attention_heads=4, max_position_embeddings=int(original * factor),
        partial_rotary_factor=1.0, rope_scaling=scaling)
    inv, attention_factor = rope_utils._compute_yarn_parameters(hf_cfg, "cpu")
    cfg = get_config("mellum2-12b-a2.5b", head_dim=d, rope_theta=theta,
                     rope_yarn=(factor, original, 32.0, 1.0, None))
    got, got_factor = L.yarn_frequencies(cfg)
    np.testing.assert_allclose(np.asarray(got), inv.numpy(), rtol=3e-6)
    assert got_factor == pytest.approx(attention_factor, rel=1e-12)
    ref, ref_factor = reference.rope_table(
        {**scaling, "rope_theta": theta, "attention_factor": None}, d)
    np.testing.assert_allclose(ref, inv.numpy(), rtol=3e-6)
    assert ref_factor == pytest.approx(attention_factor, rel=1e-12)


def test_dense_forward_matches_the_reference(reference):
    """``CausalLM.apply`` (no cache, no pages) walks the layers with their
    windows and their RoPE by kind: against the plain reference on seeded
    weights, past the window and past YaRN's original length."""
    yarn = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": None}
    config = {"num_experts": 8, "num_experts_per_tok": 2,
              "norm_topk_prob": True, "rms_norm_eps": 1e-6,
              "sliding_window": 16,
              "layer_types": (["sliding_attention"] * 3
                              + ["full_attention"]) * 2,
              "rope_parameters": {
                  "full_attention": yarn,
                  "sliding_attention": {"rope_type": "default",
                                        "rope_theta": 10000.0}}}
    model = build_model(get_config(
        "mellum2-12b-a2.5b", vocab_size=256, hidden_size=64, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        sliding_window=16, window_pattern=(16, 16, 16, 0), rope_theta=1e4,
        rope_yarn=(4.0, 32, 32.0, 1.0, None), max_seq_len=128,
        dtype="float32"))
    params = model.init(jax.random.PRNGKey(9))
    layers = params["layers"]
    layers["attn"] = {n: w * 4.0 for n, w in layers["attn"].items()}
    layers["mlp"] = {n: w * (10.0 if n == "router" else 8.0)
                     for n, w in layers["mlp"].items()}
    ids = np.random.default_rng(2).integers(0, 256, 90).astype(np.int32)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None]))[0]
    rows = np.arange(5, 90, 7)
    want = reference.logits_rows(params, ids, rows, config)
    assert np.abs(got[rows] - want).max() < 1e-4
    # and the caller that walks layers without their RoPE is refused
    with pytest.raises(NotImplementedError, match="differs by layer"):
        model._layer_fn(jax.tree.map(lambda a: a[0], layers),
                        jnp.zeros((1, 4, 64)), None, None)
