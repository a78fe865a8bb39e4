"""v2 module system + model implementation tests (reference pattern:
tests/unit/inference/v2/{modules,model_implementations})."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import torch

from deepspeed_tpu.inference.v2.modules import (ConfigBundle, DSLinearConfig,
                                                DSMoEConfig, DSNormConfig,
                                                DSUnembedConfig, available,
                                                instantiate, OP_LINEAR, OP_MOE,
                                                OP_PRE_NORM, OP_POST_NORM,
                                                OP_UNEMBED)
from deepspeed_tpu.inference.v2.model_implementations import (build_native,
                                                              resolve_container)


def test_registry_lists_defaults():
    avail = available()
    assert "paged_flash" in avail["attention"]
    assert "fused_norm" in avail["pre_norm"]
    assert "blas_fp" in avail["linear"]
    assert "ragged_moe" in avail["moe"]
    assert "logits_gather" in avail["unembed"]
    with pytest.raises(KeyError):
        instantiate(OP_LINEAR, ConfigBundle("nope", DSLinearConfig()))


def test_norm_and_linear_modules():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8)), jnp.float32)
    pre = instantiate(OP_PRE_NORM, ConfigBundle(
        "fused_norm", DSNormConfig(hidden_size=8, type="rmsnorm", eps=1e-6)))
    y = pre({"scale": jnp.ones((8,))}, x)
    np.testing.assert_allclose(np.mean(np.square(np.asarray(y)), -1), 1.0, rtol=1e-3)

    post = instantiate(OP_POST_NORM, ConfigBundle(
        "fused_norm", DSNormConfig(hidden_size=8, type="layernorm", eps=1e-6)))
    z = post({"scale": jnp.ones((8,)), "bias": jnp.zeros((8,))}, x, x)
    np.testing.assert_allclose(np.asarray(z).mean(-1), 0.0, atol=1e-5)

    lin = instantiate(OP_LINEAR, ConfigBundle(
        "blas_fp", DSLinearConfig(in_features=8, out_features=4, bias=True,
                                  activation="relu", dtype=jnp.float32)))
    w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    out = lin({"w": w, "b": jnp.zeros((4,))}, x)
    np.testing.assert_allclose(np.asarray(out), np.maximum(np.asarray(x) @ np.asarray(w), 0),
                               rtol=1e-5)

    gated = instantiate(OP_LINEAR, ConfigBundle(
        "blas_fp", DSLinearConfig(in_features=8, out_features=4,
                                  activation="swiglu", dtype=jnp.float32)))
    out = gated({"w_gate": w, "w_up": w}, x)
    assert out.shape == (2, 4)


def test_unembed_last_token_only():
    cfg = DSUnembedConfig(vocab_size=16, hidden_size=8,
                          norm=DSNormConfig(hidden_size=8, type="rmsnorm"),
                          tie_embeddings=True, dtype=jnp.float32)
    mod = instantiate(OP_UNEMBED, ConfigBundle("logits_gather", cfg))
    rng = np.random.default_rng(1)
    params = {"final_norm": {"scale": jnp.ones((8,))},
              "embed": {"tok": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)}}
    logits = mod(params, jnp.asarray(rng.normal(size=(3, 8)), jnp.float32))
    assert logits.shape == (3, 16) and logits.dtype == jnp.float32


def test_moe_module_matches_model_layer():
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    mcfg = TransformerConfig(vocab_size=1, hidden_size=16, num_layers=1, num_heads=1,
                             intermediate_size=32, max_seq_len=8, num_experts=4,
                             num_experts_per_tok=2, moe_impl="grouped", dtype="float32")
    pr, _ = L.init_moe_mlp(jax.random.PRNGKey(0), mcfg)
    mod = instantiate(OP_MOE, ConfigBundle("ragged_moe", DSMoEConfig(
        num_experts=4, top_k=2, hidden_size=16, intermediate_size=32,
        impl="grouped", dtype=jnp.float32)))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 16)), jnp.float32)
    y_mod, aux_mod = mod(pr, x)
    y_ref, aux_ref = L.apply_moe_grouped(pr, x, mcfg)
    np.testing.assert_allclose(np.asarray(y_mod), np.asarray(y_ref), rtol=1e-5)


# ---- arch containers: logits parity vs tiny random HF models -------------

def _parity(hf_model, tol=5e-3, vocab=128):
    hf_model.eval()
    ids = np.random.default_rng(0).integers(0, vocab, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.numpy()
    model, params = build_native(hf_model, dtype="float32")
    got = np.asarray(model.apply(jax.tree.map(jnp.asarray, params), jnp.asarray(ids)))
    np.testing.assert_allclose(got, ref, atol=tol, rtol=1e-2)


def test_container_llama():
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(0)
    _parity(LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64)))


def test_container_qwen2_biases():
    from transformers import Qwen2Config, Qwen2ForCausalLM
    torch.manual_seed(0)
    m = Qwen2ForCausalLM(Qwen2Config(
        vocab_size=128, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64))
    # qkv biases are real in qwen2 — randomize so a dropped bias would fail
    with torch.no_grad():
        for layer in m.model.layers:
            layer.self_attn.q_proj.bias.normal_()
            layer.self_attn.k_proj.bias.normal_()
            layer.self_attn.v_proj.bias.normal_()
    _parity(m)


def test_container_mixtral_moe():
    from transformers import MixtralConfig, MixtralForCausalLM
    torch.manual_seed(0)
    _parity(MixtralForCausalLM(MixtralConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
        num_local_experts=4, num_experts_per_tok=2)))


def test_container_opt():
    from transformers import OPTConfig, OPTForCausalLM
    torch.manual_seed(0)
    _parity(OPTForCausalLM(OPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        ffn_dim=64, max_position_embeddings=64, word_embed_proj_dim=32)))


def test_container_gpt2():
    from transformers import GPT2Config, GPT2LMHeadModel
    torch.manual_seed(0)
    _parity(GPT2LMHeadModel(GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)))


def test_container_phi3_fused_splits():
    try:
        from transformers import Phi3Config, Phi3ForCausalLM
    except ImportError:
        pytest.skip("transformers has no Phi3")
    torch.manual_seed(0)
    _parity(Phi3ForCausalLM(Phi3Config(
        vocab_size=128, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
        pad_token_id=0)))


def test_resolver_unknown_arch():
    class FakeCfg:
        architectures = ["SomethingElseForCausalLM"]

    with pytest.raises(NotImplementedError):
        resolve_container(FakeCfg())


def test_container_gptneox_partial_rotary_parallel_residual():
    """GPT-NeoX/Pythia: head-interleaved fused QKV split, partial rotary
    (rotary_pct), parallel attention+MLP residual, exact-erf gelu."""
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM
    torch.manual_seed(0)
    _parity(GPTNeoXForCausalLM(GPTNeoXConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64, max_position_embeddings=64,
        rotary_pct=0.25, use_parallel_residual=True)))


def test_container_falcon_multiquery_shared_norm():
    """Falcon-7B style: multi-query attention, parallel block with ONE
    shared layernorm (mapped into both norm slots), fused qkv split."""
    from transformers import FalconConfig, FalconForCausalLM
    torch.manual_seed(0)
    _parity(FalconForCausalLM(FalconConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, alibi=False)))


def test_container_gptj_shared_norm_biased_head():
    """GPT-J: interleaved partial rotary, parallel block sharing one
    layernorm, MLP-only biases, biased LM head."""
    from transformers import GPTJConfig, GPTJForCausalLM
    torch.manual_seed(0)
    m = GPTJForCausalLM(GPTJConfig(vocab_size=128, n_embd=32, n_layer=2,
                                   n_head=4, n_positions=64, rotary_dim=4))
    with torch.no_grad():
        m.lm_head.bias.normal_()
    _parity(m)


def test_container_bloom_alibi_embedding_norm():
    """BLOOM: ALiBi positions, embedding layernorm, head-interleaved fused
    QKV, tied head (reference ``module_inject/containers/bloom.py``)."""
    from transformers import BloomConfig, BloomForCausalLM
    torch.manual_seed(0)
    m = BloomForCausalLM(BloomConfig(vocab_size=128, hidden_size=32,
                                     n_layer=2, n_head=4))
    # HF inits all biases to zero; randomize so a dropped/mis-sliced bias
    # mapping would fail the parity check
    with torch.no_grad():
        for name, p in m.named_parameters():
            if name.endswith(".bias"):
                p.normal_(std=0.1)
    _parity(m)


def test_bloom_paged_engine_matches_dense():
    """BLOOM through InferenceEngineV2 (paged runner): the runner must apply
    the embedding layernorm and the ALiBi bias; greedy output == v1 dense."""
    import deepspeed_tpu as ds
    from transformers import BloomConfig, BloomForCausalLM
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceEngineConfig)
    torch.manual_seed(1)
    hf = BloomForCausalLM(BloomConfig(vocab_size=128, hidden_size=32,
                                      n_layer=2, n_head=4))
    hf.eval()
    model, params = build_native(hf, dtype="float32")
    params = jax.tree.map(jnp.asarray, params)

    v1 = ds.init_inference(model, dtype="float32")
    v1.module_params = jax.device_put(params, v1.param_shardings)

    cfg = RaggedInferenceEngineConfig(kv_block_size=16, dtype="float32")
    v2 = InferenceEngineV2(model, cfg, max_seq_len=64, params=jax.device_put(params))

    prompt = np.random.default_rng(0).integers(0, 128, (1, 12))
    dense = np.asarray(v1.generate(prompt, max_new_tokens=6))[0, 12:]
    ragged = v2.generate([prompt[0]], max_new_tokens=6)[0]
    np.testing.assert_array_equal(dense, ragged)


def test_container_phi_parallel_block_biased_head():
    """Phi-1.5/2: parallel attn+mlp sharing one layernorm, partial rotary,
    biases everywhere, untied biased LM head."""
    from transformers import PhiConfig, PhiForCausalLM
    torch.manual_seed(0)
    m = PhiForCausalLM(PhiConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, partial_rotary_factor=0.5))
    with torch.no_grad():
        m.lm_head.bias.normal_()
    _parity(m)


def test_container_gptneo_local_attention():
    """GPT-Neo: alternating global/local attention with a window SMALLER
    than the test sequence (so the sliding-window mask must bind), unscaled
    attention logits, qkv without biases."""
    from transformers import GPTNeoConfig, GPTNeoForCausalLM
    torch.manual_seed(0)
    m = GPTNeoForCausalLM(GPTNeoConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        attention_types=[[["global", "local"], 1]], window_size=5,
        max_position_embeddings=64))
    _parity(m)


def test_container_mistral_sliding_window_binds():
    """Mistral with sliding_window < sequence length: the windowed mask must
    match HF's (a model ignoring the window would diverge)."""
    from transformers import MistralConfig, MistralForCausalLM
    torch.manual_seed(0)
    m = MistralForCausalLM(MistralConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        max_position_embeddings=64, sliding_window=6))
    from deepspeed_tpu.inference.v2.model_implementations import resolve_container
    assert resolve_container(m.config).config(m.config).sliding_window == 6
    _parity(m)


def test_gptneo_paged_engine_matches_dense():
    """GPT-Neo through the v2 paged runner: out-proj bias (present without
    use_bias) and the per-layer local window must both be applied."""
    import deepspeed_tpu as ds
    from transformers import GPTNeoConfig, GPTNeoForCausalLM
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceEngineConfig)
    torch.manual_seed(2)
    hf = GPTNeoForCausalLM(GPTNeoConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        attention_types=[[["global", "local"], 1]], window_size=5,
        max_position_embeddings=64))
    hf.eval()
    model, params = build_native(hf, dtype="float32")
    params = jax.tree.map(jnp.asarray, params)

    v1 = ds.init_inference(model, dtype="float32")
    v1.module_params = jax.device_put(params, v1.param_shardings)

    cfg = RaggedInferenceEngineConfig(kv_block_size=16, dtype="float32")
    v2 = InferenceEngineV2(model, cfg, max_seq_len=64, params=jax.device_put(params))

    prompt = np.random.default_rng(0).integers(0, 128, (1, 12))
    dense = np.asarray(v1.generate(prompt, max_new_tokens=6))[0, 12:]
    ragged = v2.generate([prompt[0]], max_new_tokens=6)[0]
    np.testing.assert_array_equal(dense, ragged)


def test_container_bert_mlm_parity():
    """BERT: post-norm encoder, token-type embeddings, embedding layernorm,
    MLM head — logits parity vs HF BertForMaskedLM."""
    from transformers import BertConfig, BertForMaskedLM
    torch.manual_seed(0)
    m = BertForMaskedLM(BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, type_vocab_size=2))
    m.eval()
    ids = np.random.default_rng(0).integers(0, 128, (2, 16))
    tt = np.zeros_like(ids); tt[:, 8:] = 1
    with torch.no_grad():
        ref = m(torch.tensor(ids), token_type_ids=torch.tensor(tt)).logits.numpy()
    model, params = build_native(m, dtype="float32")
    from deepspeed_tpu.models.bert import EncoderLM
    assert isinstance(model, EncoderLM)
    got = np.asarray(model.apply(jax.tree.map(jnp.asarray, params), jnp.asarray(ids),
                                 token_type_ids=jnp.asarray(tt)))
    np.testing.assert_allclose(got, ref, atol=5e-3, rtol=1e-2)


def test_container_distilbert_mlm_parity():
    from transformers import DistilBertConfig, DistilBertForMaskedLM
    torch.manual_seed(0)
    m = DistilBertForMaskedLM(DistilBertConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
        max_position_embeddings=64))
    _parity(m)


def test_bert_mlm_loss_ignores_unmasked():
    """MLM loss averages only over labeled (-100-masked-out) positions."""
    from deepspeed_tpu.models import build_model
    model = build_model("bert-base", num_layers=2, hidden_size=64, num_heads=4,
                        intermediate_size=128, vocab_size=256, max_seq_len=32,
                        dtype="float32", param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 16))
    labels = np.full_like(ids, -100)
    labels[:, 3] = ids[:, 3]
    l1 = float(model.loss(params, {"input_ids": jnp.asarray(ids),
                                   "labels": jnp.asarray(labels)}))
    # flipping an ignored label must not change the loss
    labels2 = labels.copy(); labels2[:, 10] = -100
    l2 = float(model.loss(params, {"input_ids": jnp.asarray(ids),
                                   "labels": jnp.asarray(labels2)}))
    assert np.isfinite(l1) and abs(l1 - l2) < 1e-6


def test_bert_chunked_loss_matches_dense():
    """EncoderLM's vocab-chunked fused CE (decoder bias folded into an extra
    input column) must match the dense-logit loss."""
    from deepspeed_tpu.models import build_model
    model = build_model("bert-base", num_layers=2, hidden_size=64, num_heads=4,
                        intermediate_size=128, vocab_size=8192, max_seq_len=32,
                        dtype="float32", param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 8192, (2, 16))
    labels = np.full_like(ids, -100)
    pos = rng.random(ids.shape) < 0.3
    labels[pos] = ids[pos]
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}
    dense = float(model.loss(params, batch))        # under threshold: dense
    model_c = build_model(model.cfg.replace(loss_chunk_threshold_bytes=1))
    chunked = float(model_c.loss(params, batch))    # forced chunked path
    np.testing.assert_allclose(dense, chunked, rtol=1e-5)


def test_pipeline_encoder_support_boundaries():
    """Since round 5 the 1F1B engine accepts post-norm/MLM encoders (the
    old check_pipeline_model_support rejection is gone — reference
    pipelines arbitrary LayerSpec lists incl. BERT, pipe/module.py:86);
    the legacy GPipe autodiff path still rejects encoders and per-layer
    window patterns."""
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime.pipe.engine import build_pipeline_loss
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.models.config import TransformerConfig
    bert = build_model("bert-base", num_layers=2, hidden_size=32, num_heads=4,
                       intermediate_size=64, vocab_size=128)
    groups.reset_mesh()
    groups.set_mesh(groups.build_mesh(pipe=2, data=4))
    with pytest.raises(NotImplementedError):
        build_pipeline_loss(bert, num_stages=2)       # GPipe = legacy
    neo_like = TransformerConfig(sliding_window=8, local_attention_every=2)
    neo_model = build_model(neo_like.replace(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, dtype="float32"))
    with pytest.raises(NotImplementedError):
        build_pipeline_loss(neo_model, num_stages=2)


def test_container_gemma_geglu_scaled_embed():
    """Gemma: sqrt(E)-scaled embeddings, offset RMSNorm (+1 at load), GeGLU
    MLP, explicit head_dim, tied head."""
    from transformers import GemmaConfig, GemmaForCausalLM
    torch.manual_seed(0)
    m = GemmaForCausalLM(GemmaConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=64, max_position_embeddings=64))
    _parity(m)


def test_container_mpt_alibi_stacked_qkv():
    """MPT: stacked (non-interleaved) fused Wqkv, ALiBi, bias-free norms."""
    from transformers import MptConfig, MptForCausalLM
    torch.manual_seed(0)
    m = MptForCausalLM(MptConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4,
        expansion_ratio=2, max_seq_len=64))
    _parity(m)


def test_container_stablelm_partial_rotary_ln():
    from transformers import StableLmConfig, StableLmForCausalLM
    torch.manual_seed(0)
    m = StableLmForCausalLM(StableLmConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        max_position_embeddings=64, partial_rotary_factor=0.5))
    _parity(m)


def test_auto_container_fallback_unmapped_llama_like():
    """An unmapped arch with the Llama module layout converts through the
    AutoContainer fallback (reference AutoTP analog) with exact parity."""
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=64, max_position_embeddings=64)
    cfg.architectures = ["TotallyUnknownForCausalLM"]
    from deepspeed_tpu.inference.v2.model_implementations.archs import (
        AutoContainer, resolve_container)
    assert resolve_container(cfg) is AutoContainer
    m = LlamaForCausalLM(cfg)
    m.config.architectures = ["TotallyUnknownForCausalLM"]
    _parity(m)


def test_container_qwen2_moe_shared_expert():
    """Qwen2-MoE: un-renormalized top-k routing plus the sigmoid-gated
    always-on shared expert; logits parity vs HF."""
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM
    torch.manual_seed(0)
    m = Qwen2MoeForCausalLM(Qwen2MoeConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        moe_intermediate_size=48, shared_expert_intermediate_size=80,
        num_experts=4, num_experts_per_tok=2, max_position_embeddings=64,
        decoder_sparse_step=1, mlp_only_layers=[]))
    with torch.no_grad():
        for layer in m.model.layers:
            layer.self_attn.q_proj.bias.normal_()
            layer.self_attn.k_proj.bias.normal_()
            layer.self_attn.v_proj.bias.normal_()
    _parity(m, tol=1e-2)


def _tiny_hf_olmoe(norm_topk_prob=False):
    from transformers import OlmoeConfig, OlmoeForCausalLM
    torch.manual_seed(0)
    m = OlmoeForCausalLM(OlmoeConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=48,
        num_experts=8, num_experts_per_tok=3, norm_topk_prob=norm_topk_prob,
        max_position_embeddings=64))
    with torch.no_grad():      # weights that matter: norms off 1, a router
        for layer in m.model.layers:       # that prefers some experts
            layer.self_attn.q_norm.weight.uniform_(0.5, 1.5)
            layer.self_attn.k_norm.weight.uniform_(0.5, 1.5)
            layer.mlp.gate.weight.mul_(20.0)
            for expert in layer.mlp.experts:
                expert.down_proj.weight.mul_(20.0)
    return m


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_container_olmoe_qk_norm_routed_experts(norm_topk_prob):
    """OLMoE: the public checkpoint's names (``mlp.gate``,
    ``mlp.experts.{x}.{gate,up,down}_proj``, ``self_attn.{q,k}_norm``) land
    on the ``olmoe-1b-7b`` preset's pytree, and the native model (whole-
    projection q/k RMSNorm, softmax then top-k, dropless) agrees with HF's
    ``modeling_olmoe.py`` on a tiny random model's state dict."""
    m = _tiny_hf_olmoe(norm_topk_prob)
    model, params = build_native(m, dtype="float32")
    cfg = model.cfg
    assert (cfg.qk_norm, cfg.moe_impl, cfg.moe_norm_topk) == (
        "full", "grouped", norm_topk_prob)
    from deepspeed_tpu.models import build_model
    preset = build_model("olmoe-1b-7b").abstract_params()
    assert jax.tree.structure(params) == jax.tree.structure(preset)
    assert params["layers"]["attn"]["q_norm"]["scale"].shape == (2, 32)
    assert params["layers"]["mlp"]["wi_gate"].shape == (2, 8, 32, 48)
    _parity(m)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_olmoe_reference_follows_the_published_model(norm_topk_prob):
    """The benchmark's plain reference (``perfbench/configs/
    olmoe_reference.py``, which shares no code with the program) against
    transformers' own ``modeling_olmoe.py`` on the same tiny model, its
    state dict brought over by the container: the equations the reference
    was written from are the published ones, with and without the top-k
    renormalization. Float32 on both sides: 1e-4 on logits up to ~1."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "olmoe_reference", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "perfbench", "configs", "olmoe_reference.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    m = _tiny_hf_olmoe(norm_topk_prob).eval()
    ids = np.random.default_rng(0).integers(0, 128, (40,))
    with torch.no_grad():
        want = m(torch.tensor(ids[None])).logits[0].numpy()
    _, params = build_native(m, dtype="float32")
    config = {k: getattr(m.config, k) for k in (
        "num_experts", "num_experts_per_tok", "norm_topk_prob", "rope_theta",
        "rms_norm_eps")}
    got = reference.logits_rows(jax.tree.map(jnp.asarray, params), ids,
                                np.arange(len(ids)), config)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_auto_container_refuses_non_llama_layout():
    """AutoContainer must refuse checkpoints whose layer layout carries
    tensors outside the Llama mapping (silently dropping them would corrupt
    outputs)."""
    from deepspeed_tpu.inference.v2.model_implementations.archs import AutoContainer
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=16, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, intermediate_size=32))
    sd = m.state_dict()
    sd["model.layers.0.self_attn.q_norm.weight"] = torch.ones(8)
    cfg = AutoContainer.config(m.config)
    with pytest.raises(NotImplementedError, match="q_norm"):
        AutoContainer.build_params(sd, cfg)


def test_container_mellum2_config_mapping():
    """Mellum2's published ``config.json`` (the benchmark's file holds its
    keys) maps onto the ``mellum2-12b-a2.5b`` preset field for field: layer
    kinds -> window pattern, ``rope_parameters`` -> theta and YaRN on the
    global layers, the experts' own width, renormalised top-k, dropless."""
    import json
    import os
    import types
    from deepspeed_tpu.inference.v2.model_implementations import resolve_container
    from deepspeed_tpu.inference.v2.model_implementations.archs import MellumContainer
    from deepspeed_tpu.models import get_config
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "mellum2-12b-a2.5b-l8-serve.json")) as fh:
        published = json.load(fh)
    published["num_hidden_layers"] = 28          # the file's one cut
    hf = types.SimpleNamespace(architectures=["MellumForCausalLM"],
                               **published)
    container = resolve_container(hf)
    assert issubclass(container, MellumContainer)
    cfg = container.config(hf)
    preset = get_config("mellum2-12b-a2.5b")
    assert cfg.layer_windows() == preset.layer_windows() \
        == (1024, 1024, 1024, 0) * 7
    for field in ("vocab_size", "hidden_size", "num_layers", "num_heads",
                  "num_kv_heads", "head_dim", "intermediate_size",
                  "moe_intermediate_size", "max_seq_len", "rope_theta",
                  "rope_yarn", "sliding_window",
                  "norm_eps", "num_experts", "num_experts_per_tok",
                  "moe_norm_topk", "moe_impl", "qk_norm", "tie_embeddings"):
        assert getattr(cfg, field) == getattr(preset, field), field
    assert "attn.q_norm.scale" not in container.layer_mapping
    assert "mlp.router" in container.layer_mapping
    # a dense MLP layer in the stack, or scaled RoPE on the sliding layers,
    # is refused rather than mapped wrong
    hf.mlp_layer_types = ["dense"] + ["sparse"] * 27
    with pytest.raises(NotImplementedError, match="mlp_layer_types"):
        container.config(hf)
