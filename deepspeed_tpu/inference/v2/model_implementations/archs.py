"""Per-architecture model implementations (checkpoint containers).

Analog of ``inference/v2/model_implementations/{llama_v2,mistral,mixtral,
qwen_v2,phi3,opt,...}.py``: each class binds an HF architecture to (a) the
native ``TransformerConfig`` derived from its HF config and (b) the
declarative weight mapping (``LayerContainer``) that loads its checkpoint
into the scan-ready native layout. ``resolve_container`` dispatches on the
HF architecture string; ``build_native`` is the one-call path used by
``build_hf_engine`` and ``module_inject``.
"""

from typing import Dict, Tuple, Type

import numpy as np

from ....models.config import TransformerConfig
from ....models.transformer import CausalLM
from .layer_container import (LayerContainer, Param, t_identity, t_kv_bias,
                              t_kv_heads, t_linear, t_o_heads, t_q_bias,
                              t_q_heads)


def _get(hf_cfg, *names, default=None):
    for n in names:
        v = getattr(hf_cfg, n, None)
        if v is not None:
            return v
    return default


def _llama_family_config(hf_cfg, **overrides) -> TransformerConfig:
    kw = dict(
        vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
        num_layers=_get(hf_cfg, "num_hidden_layers", "n_layer"),
        num_heads=_get(hf_cfg, "num_attention_heads", "n_head"),
        num_kv_heads=_get(hf_cfg, "num_key_value_heads"),
        intermediate_size=_get(hf_cfg, "intermediate_size"),
        max_seq_len=_get(hf_cfg, "max_position_embeddings", default=4096),
        rope_theta=float(_get(hf_cfg, "rope_theta", default=10000.0)),
        norm_eps=float(_get(hf_cfg, "rms_norm_eps", "layer_norm_epsilon",
                            default=1e-5)),
        tie_embeddings=bool(_get(hf_cfg, "tie_word_embeddings", default=False)))
    kw.update(overrides)
    return TransformerConfig(**kw)


class LlamaContainer(LayerContainer):
    """Llama v2/v3 (reference ``model_implementations/llama_v2``)."""

    layer_mapping = {
        "attn.wq": Param("model.layers.{l}.self_attn.q_proj.weight", t_q_heads),
        "attn.wk": Param("model.layers.{l}.self_attn.k_proj.weight", t_kv_heads),
        "attn.wv": Param("model.layers.{l}.self_attn.v_proj.weight", t_kv_heads),
        "attn.wo": Param("model.layers.{l}.self_attn.o_proj.weight", t_o_heads),
        "norm1.scale": Param("model.layers.{l}.input_layernorm.weight"),
        "norm2.scale": Param("model.layers.{l}.post_attention_layernorm.weight"),
        "mlp.wi_gate": Param("model.layers.{l}.mlp.gate_proj.weight", t_linear),
        "mlp.wi_up": Param("model.layers.{l}.mlp.up_proj.weight", t_linear),
        "mlp.wo": Param("model.layers.{l}.mlp.down_proj.weight", t_linear),
    }
    non_layer_mapping = {
        "embed.tok": Param("model.embed_tokens.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear, optional=True),
        "final_norm.scale": Param("model.norm.weight"),
    }

    @classmethod
    def config(cls, hf_cfg):
        return _llama_family_config(hf_cfg)


class MistralContainer(LlamaContainer):
    """Mistral shares Llama's graph (reference ``mistral/container.py``)
    plus sliding-window attention."""

    @classmethod
    def config(cls, hf_cfg):
        # HF Mistral's sliding mask keeps q-k < W — same convention as
        # native sliding_window (verified vs eager HF at W < S).
        return _llama_family_config(
            hf_cfg, sliding_window=_get(hf_cfg, "sliding_window"))


class MixtralContainer(LlamaContainer):
    """Mixtral MoE (reference ``mixtral/container.py``)."""

    layer_mapping = {
        **{k: v for k, v in LlamaContainer.layer_mapping.items()
           if not k.startswith("mlp.")},
        "mlp.router": Param("model.layers.{l}.block_sparse_moe.gate.weight", t_linear),
        "mlp.wi_gate": Param(
            "model.layers.{l}.block_sparse_moe.experts.{x}.w1.weight", t_linear),
        "mlp.wi_up": Param(
            "model.layers.{l}.block_sparse_moe.experts.{x}.w3.weight", t_linear),
        "mlp.wo": Param(
            "model.layers.{l}.block_sparse_moe.experts.{x}.w2.weight", t_linear),
    }

    @classmethod
    def config(cls, hf_cfg):
        return _llama_family_config(
            hf_cfg,
            num_experts=int(_get(hf_cfg, "num_local_experts", "num_experts",
                                 default=8)),
            num_experts_per_tok=int(_get(hf_cfg, "num_experts_per_tok", default=2)))


class Qwen2Container(LlamaContainer):
    """Qwen2 = Llama graph + q/k/v biases (reference ``qwen_v2``)."""

    layer_mapping = {
        **LlamaContainer.layer_mapping,
        "attn.bq": Param("model.layers.{l}.self_attn.q_proj.bias", t_q_bias),
        "attn.bk": Param("model.layers.{l}.self_attn.k_proj.bias", t_kv_bias),
        "attn.bv": Param("model.layers.{l}.self_attn.v_proj.bias", t_kv_bias),
    }

    @classmethod
    def config(cls, hf_cfg):
        return _llama_family_config(hf_cfg, qkv_bias=True)


class Qwen2MoeContainer(Qwen2Container):
    """Qwen2-MoE (reference ``model_implementations/qwen_v2_moe``): dense
    Qwen2 attention (inherited q/k/v bias rows) + routed experts WITHOUT
    top-k renormalization + an always-on shared expert behind a sigmoid
    gate."""

    layer_mapping = {
        **{k: v for k, v in Qwen2Container.layer_mapping.items()
           if not k.startswith("mlp.")},
        "mlp.router": Param("model.layers.{l}.mlp.gate.weight", t_linear),
        "mlp.wi_gate": Param(
            "model.layers.{l}.mlp.experts.{x}.gate_proj.weight", t_linear),
        "mlp.wi_up": Param(
            "model.layers.{l}.mlp.experts.{x}.up_proj.weight", t_linear),
        "mlp.wo": Param(
            "model.layers.{l}.mlp.experts.{x}.down_proj.weight", t_linear),
        "mlp.shared_wi_gate": Param(
            "model.layers.{l}.mlp.shared_expert.gate_proj.weight", t_linear),
        "mlp.shared_wi_up": Param(
            "model.layers.{l}.mlp.shared_expert.up_proj.weight", t_linear),
        "mlp.shared_wo": Param(
            "model.layers.{l}.mlp.shared_expert.down_proj.weight", t_linear),
        "mlp.shared_gate": Param(
            "model.layers.{l}.mlp.shared_expert_gate.weight", t_linear),
    }
    # dense interleave layers (mlp_only_layers / decoder_sparse_step) use the
    # plain Qwen2 MLP names; routed layers use the expert mapping above
    layer_mapping_by_type = {"dense": Qwen2Container.layer_mapping}

    @classmethod
    def config(cls, hf_cfg):
        n = hf_cfg.num_hidden_layers
        step = int(_get(hf_cfg, "decoder_sparse_step", default=1))
        only = set(getattr(hf_cfg, "mlp_only_layers", None) or [])
        n_exp = int(_get(hf_cfg, "num_experts", default=8))
        # HF Qwen2MoeDecoderLayer: layer l is sparse iff l not in
        # mlp_only_layers and num_experts > 0 and (l+1) % decoder_sparse_step == 0
        tags = tuple(
            "moe" if (l not in only and n_exp > 0 and step > 0
                      and (l + 1) % step == 0) else "dense"
            for l in range(n))
        return _llama_family_config(
            hf_cfg, qkv_bias=True,
            intermediate_size=int(hf_cfg.intermediate_size),
            moe_intermediate_size=int(hf_cfg.moe_intermediate_size),
            layer_types=None if all(t == "moe" for t in tags) else tags,
            num_experts=n_exp,
            num_experts_per_tok=int(_get(hf_cfg, "num_experts_per_tok", default=2)),
            moe_norm_topk=bool(_get(hf_cfg, "norm_topk_prob", default=False)),
            moe_shared_expert_size=int(
                _get(hf_cfg, "shared_expert_intermediate_size", default=0)))


class OlmoeContainer(LlamaContainer):
    """OLMoE (``modeling_olmoe.py``): Llama's attention with one RMSNorm
    over the whole q and the whole k projection before the head split, and
    every MLP a routed one: softmax over all experts, top-k, the weights not
    renormalized unless ``norm_topk_prob``; no shared expert. Served
    dropless (``moe_impl="grouped"``), as the ``olmoe-1b-7b`` preset."""

    layer_mapping = {
        **{k: v for k, v in LlamaContainer.layer_mapping.items()
           if not k.startswith("mlp.")},
        "attn.q_norm.scale": Param("model.layers.{l}.self_attn.q_norm.weight"),
        "attn.k_norm.scale": Param("model.layers.{l}.self_attn.k_norm.weight"),
        "mlp.router": Param("model.layers.{l}.mlp.gate.weight", t_linear),
        "mlp.wi_gate": Param(
            "model.layers.{l}.mlp.experts.{x}.gate_proj.weight", t_linear),
        "mlp.wi_up": Param(
            "model.layers.{l}.mlp.experts.{x}.up_proj.weight", t_linear),
        "mlp.wo": Param(
            "model.layers.{l}.mlp.experts.{x}.down_proj.weight", t_linear),
    }

    @classmethod
    def config(cls, hf_cfg):
        return _llama_family_config(
            hf_cfg, qk_norm="full", qk_norm_bias=False, moe_impl="grouped",
            num_experts=int(hf_cfg.num_experts),
            num_experts_per_tok=int(hf_cfg.num_experts_per_tok),
            moe_norm_topk=bool(_get(hf_cfg, "norm_topk_prob", default=False)))


class SdarMoeContainer(LlamaContainer):
    """SDAR-MoE (JetLM/SDAR-30B-A3B-Chat ``config.json``, ``model_type``
    "sdar_moe"): Qwen3-MoE's layer in its published layout: GQA with an
    explicit ``head_dim``, one RMSNorm of ``head_dim`` lanes on every q and
    k head before RoPE (``self_attn.{q,k}_norm``), every MLP routed
    (``mlp.gate``, ``mlp.experts.{x}.{gate,up,down}_proj``) with experts of
    ``moe_intermediate_size``, no shared expert, untied head. Generated by
    diffusion over blocks; ``config.json`` names neither the block's length
    nor the schedule, so those are the family's released defaults unless
    the config object carries them (the ``sdar-30b-a3b`` preset's). Served
    dropless."""

    layer_mapping = dict(OlmoeContainer.layer_mapping)

    @classmethod
    def config(cls, hf_cfg):
        n = hf_cfg.num_hidden_layers
        step = int(_get(hf_cfg, "decoder_sparse_step", default=1))
        only = set(_get(hf_cfg, "mlp_only_layers", default=()) or ())
        if step != 1 or only:
            raise NotImplementedError(
                f"decoder_sparse_step={step}, mlp_only_layers={sorted(only)}"
                f" of {n} layers: only a stack whose every MLP is routed is "
                "mapped")
        if _get(hf_cfg, "use_sliding_window", default=False):
            raise NotImplementedError(
                "use_sliding_window: a window measures from the query's own "
                "position, a block's mask from its block's end")
        return _llama_family_config(
            hf_cfg, head_dim=int(hf_cfg.head_dim), qk_norm="head_dim",
            qk_norm_bias=False, qkv_bias=bool(
                _get(hf_cfg, "attention_bias", default=False)),
            moe_impl="grouped", num_experts=int(hf_cfg.num_experts),
            num_experts_per_tok=int(hf_cfg.num_experts_per_tok),
            moe_intermediate_size=int(hf_cfg.moe_intermediate_size),
            moe_norm_topk=bool(_get(hf_cfg, "norm_topk_prob", default=True)),
            block_length=int(_get(hf_cfg, "block_length", default=4)),
            denoising_steps=int(_get(hf_cfg, "denoising_steps", default=4)),
            remasking_strategy=_get(hf_cfg, "remasking_strategy",
                                    default="low_confidence_dynamic"),
            confidence_threshold=float(
                _get(hf_cfg, "confidence_threshold", default=0.9)),
            mask_token_id=int(_get(hf_cfg, "mask_token_id", default=151669)))


def _lfm2_layers():
    """``Lfm2MoeContainer``'s mappings by group tag (``layer_groups``: the
    mixer, and ".dense" behind it for a leading dense layer)."""
    norms = {"norm1.scale": Param("model.layers.{l}.operator_norm.weight"),
             "norm2.scale": Param("model.layers.{l}.ffn_norm.weight")}
    ff = "model.layers.{l}.feed_forward."
    mixers = {
        "conv": {
            "attn.w_in": Param("model.layers.{l}.conv.in_proj.weight",
                               t_linear),
            # Conv1d's (channels, 1, K): tap j meets the input K - 1 - j
            # positions back, as ``layers.causal_conv`` reads them
            "attn.conv": Param("model.layers.{l}.conv.conv.weight",
                               lambda w, cfg: w[:, 0, :].T),
            "attn.w_out": Param("model.layers.{l}.conv.out_proj.weight",
                                t_linear)},
        "full": {
            "attn.wq": Param("model.layers.{l}.self_attn.q_proj.weight",
                             t_q_heads),
            "attn.wk": Param("model.layers.{l}.self_attn.k_proj.weight",
                             t_kv_heads),
            "attn.wv": Param("model.layers.{l}.self_attn.v_proj.weight",
                             t_kv_heads),
            "attn.wo": Param("model.layers.{l}.self_attn.out_proj.weight",
                             t_o_heads),
            "attn.q_norm.scale": Param(
                "model.layers.{l}.self_attn.q_layernorm.weight"),
            "attn.k_norm.scale": Param(
                "model.layers.{l}.self_attn.k_layernorm.weight")}}
    mlps = {
        ".dense": {"mlp.wi_gate": Param(ff + "w1.weight", t_linear),
                   "mlp.wi_up": Param(ff + "w3.weight", t_linear),
                   "mlp.wo": Param(ff + "w2.weight", t_linear)},
        "": {"mlp.router": Param(ff + "gate.weight", t_linear),
             "mlp.router_bias": Param(ff + "expert_bias"),
             "mlp.wi_gate": Param(ff + "experts.{x}.w1.weight", t_linear),
             "mlp.wi_up": Param(ff + "experts.{x}.w3.weight", t_linear),
             "mlp.wo": Param(ff + "experts.{x}.w2.weight", t_linear)}}
    return {mixer + mlp: {**norms, **m, **f}
            for mixer, m in mixers.items() for mlp, f in mlps.items()}


class Lfm2MoeContainer(LayerContainer):
    """LFM2-MoE (LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type``
    "lfm2_moe"): ``layer_types`` lists each layer's mixer, a gated short
    convolution (``conv.{in_proj,conv,out_proj}``) or GQA with one RMSNorm
    of ``head_dim`` lanes on every q and k head before RoPE
    (``self_attn.{q,k,v,out}_proj``, ``{q,k}_layernorm``); the first
    ``num_dense_layers`` MLPs are dense (``feed_forward.w1/w3/w2``), the
    others routed (``feed_forward.gate``, ``expert_bias``,
    ``experts.{x}.w1/w3/w2``): sigmoid scores, the top k of score + bias,
    weights the scores over (their sum + 1e-6) x ``routed_scaling_factor``;
    norms ``operator_norm`` / ``ffn_norm``, the final one
    ``embedding_norm``; the head tied to the embedding. The native stack
    is ONE period as long as the stack (``cfg.mixer_pattern``). Served
    dropless, on the paged path only."""

    layer_mapping_by_type = _lfm2_layers()
    non_layer_mapping = {
        "embed.tok": Param("model.embed_tokens.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear, optional=True),
        "final_norm.scale": Param("model.embedding_norm.weight"),
    }

    @classmethod
    def config(cls, hf_cfg):
        if _get(hf_cfg, "conv_bias", default=False):
            raise NotImplementedError("conv_bias: the convolution and its "
                                      "projections are mapped without bias")
        kinds = {"conv": "conv", "full_attention": "full"}
        rope = _get(hf_cfg, "rope_parameters", default=None) or {}
        return _llama_family_config(
            hf_cfg, qk_norm="head_dim", qk_norm_bias=False,
            norm_eps=float(_get(hf_cfg, "norm_eps", default=1e-5)),
            rope_theta=float(_get(hf_cfg, "rope_theta", default=None)
                             or rope.get("rope_theta", 1e6)),
            mixer_pattern=tuple(kinds[t] for t in hf_cfg.layer_types),
            conv_kernel=int(hf_cfg.conv_L_cache),
            moe_first_dense=int(hf_cfg.num_dense_layers),
            moe_impl="grouped", num_experts=int(hf_cfg.num_experts),
            num_experts_per_tok=int(hf_cfg.num_experts_per_tok),
            moe_intermediate_size=int(hf_cfg.moe_intermediate_size),
            moe_norm_topk=bool(_get(hf_cfg, "norm_topk_prob", default=True)),
            moe_router_bias=bool(_get(hf_cfg, "use_expert_bias",
                                      default=True)),
            moe_routed_scale=float(_get(hf_cfg, "routed_scaling_factor",
                                        default=1.0)),
            moe_router_score="sigmoid", moe_norm_eps=1e-6,
            tie_embeddings=bool(_get(hf_cfg, "tie_word_embeddings",
                                     default=True)))


def _pair(transform=t_identity):
    """A leaf stacked over a shortcut-connected layer's pair: the two
    sources each through ``transform``."""
    return lambda ws, cfg: np.stack([transform(w, cfg) for w in ws])


def _both(template):
    return [template.format(l="{l}", j=j) for j in (0, 1)]


class LongcatFlashContainer(LayerContainer):
    """LongCat-Flash (``modeling_longcat_flash.py``): a layer holds two
    latent attentions (``self_attn.{0,1}``), two dense MLPs (``mlps.{0,1}``),
    their four norms and one routed block ``mlp`` (router ``classifier``
    over experts and zero experts, ``e_score_correction_bias``). The native
    tree stacks a pair's leaves behind the layer axis
    (``CausalLM._init_double_layer``). Every expert of the checkpoint is
    held; a chip's share is a cut of the expert axis afterwards
    (``num_experts`` / ``moe_router_experts``). Served path only."""

    layer_mapping = {
        "attn.wq_a": Param(_both("model.layers.{l}.self_attn.{j}.q_a_proj.weight"),
                           _pair(t_linear)),
        "attn.q_norm.scale": Param(
            _both("model.layers.{l}.self_attn.{j}.q_a_layernorm.weight"), _pair()),
        "attn.wq_b": Param(
            _both("model.layers.{l}.self_attn.{j}.q_b_proj.weight"),
            _pair(lambda w, cfg: w.T.reshape(
                cfg.q_lora_rank, cfg.num_heads, -1))),
        "attn.wkv_a": Param(
            _both("model.layers.{l}.self_attn.{j}.kv_a_proj_with_mqa.weight"),
            _pair(t_linear)),
        "attn.kv_norm.scale": Param(
            _both("model.layers.{l}.self_attn.{j}.kv_a_layernorm.weight"),
            _pair()),
        "attn.wkv_b": Param(
            _both("model.layers.{l}.self_attn.{j}.kv_b_proj.weight"),
            _pair(lambda w, cfg: w.T.reshape(
                cfg.kv_lora_rank, cfg.num_heads, -1))),
        "attn.wo": Param(
            _both("model.layers.{l}.self_attn.{j}.o_proj.weight"),
            _pair(lambda w, cfg: w.T.reshape(
                cfg.num_heads, cfg.v_head_dim, cfg.hidden_size))),
        "norm1.scale": Param(
            _both("model.layers.{l}.input_layernorm.{j}.weight"), _pair()),
        "norm2.scale": Param(
            _both("model.layers.{l}.post_attention_layernorm.{j}.weight"),
            _pair()),
        "mlp.wi_gate": Param(_both("model.layers.{l}.mlps.{j}.gate_proj.weight"),
                             _pair(t_linear)),
        "mlp.wi_up": Param(_both("model.layers.{l}.mlps.{j}.up_proj.weight"),
                           _pair(t_linear)),
        "mlp.wo": Param(_both("model.layers.{l}.mlps.{j}.down_proj.weight"),
                        _pair(t_linear)),
        "moe.router": Param("model.layers.{l}.mlp.router.classifier.weight",
                            t_linear),
        "moe.router_bias": Param(
            "model.layers.{l}.mlp.router.e_score_correction_bias"),
        "moe.wi_gate": Param(
            "model.layers.{l}.mlp.experts.{x}.gate_proj.weight", t_linear),
        "moe.wi_up": Param(
            "model.layers.{l}.mlp.experts.{x}.up_proj.weight", t_linear),
        "moe.wo": Param(
            "model.layers.{l}.mlp.experts.{x}.down_proj.weight", t_linear),
    }
    non_layer_mapping = {
        "embed.tok": Param("model.embed_tokens.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear),
        "final_norm.scale": Param("model.norm.weight"),
    }

    @classmethod
    def config(cls, hf_cfg):
        if _get(hf_cfg, "rope_scaling") is not None:
            raise NotImplementedError(
                "scaled RoPE on a latent attention's rope part is not mapped")
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=int(hf_cfg.num_layers),
            num_heads=hf_cfg.num_attention_heads,
            intermediate_size=int(hf_cfg.ffn_hidden_size),
            moe_intermediate_size=int(hf_cfg.expert_ffn_hidden_size),
            max_seq_len=hf_cfg.max_position_embeddings,
            rope_theta=float(hf_cfg.rope_theta), rope_interleaved=True,
            norm_eps=float(hf_cfg.rms_norm_eps),
            num_experts=int(hf_cfg.n_routed_experts),
            moe_zero_experts=int(_get(hf_cfg, "zero_expert_num", default=0)
                                 or 0),
            num_experts_per_tok=int(hf_cfg.moe_topk), moe_norm_topk=False,
            moe_router_bias=True,
            moe_routed_scale=float(hf_cfg.routed_scaling_factor),
            moe_impl="grouped", kv_lora_rank=int(hf_cfg.kv_lora_rank),
            q_lora_rank=int(hf_cfg.q_lora_rank),
            qk_nope_head_dim=int(hf_cfg.qk_nope_head_dim),
            qk_rope_head_dim=int(hf_cfg.qk_rope_head_dim),
            v_head_dim=int(hf_cfg.v_head_dim),
            mla_scale_q_lora=bool(_get(hf_cfg, "mla_scale_q_lora",
                                       default=True)),
            mla_scale_kv_lora=bool(_get(hf_cfg, "mla_scale_kv_lora",
                                        default=True)),
            shortcut_moe=True, tie_embeddings=False)


class MellumContainer(LlamaContainer):
    """Mellum2 (JetBrains/Mellum2-12B-A2.5B-Instruct ``config.json``,
    ``model_type`` "mellum"): GQA with an explicit ``head_dim``, layers of
    two kinds by ``layer_types`` (sliding window / full attention), RoPE by
    kind from ``rope_parameters`` (YaRN on the full layers), every MLP
    routed (``mlp_layer_types`` all "sparse") with experts of
    ``moe_intermediate_size``, top-k weights renormalised, no shared expert,
    no q/k norm (the config has no key for one). The published modelling
    code is not in the installed transformers: the weights' names are taken
    to be OLMoE's (``mlp.gate``, ``mlp.experts.{x}.*_proj``), which the
    Qwen-MoE family shares. Served dropless, as the ``mellum2-12b-a2.5b``
    preset."""

    layer_mapping = {k: v for k, v in OlmoeContainer.layer_mapping.items()
                     if "_norm" not in k}

    @classmethod
    def config(cls, hf_cfg):
        kinds = list(_get(hf_cfg, "layer_types", default=()))
        window = int(_get(hf_cfg, "sliding_window", default=0) or 0)
        sparse = _get(hf_cfg, "mlp_layer_types")
        if sparse and set(sparse) != {"sparse"}:
            raise NotImplementedError(
                f"mlp_layer_types {sorted(set(sparse))}: only a stack whose "
                "every MLP is routed is mapped")
        rope = _get(hf_cfg, "rope_parameters", default={}) or {}
        full = rope.get("full_attention", rope)
        local = rope.get("sliding_attention", full)
        if local.get("rope_type", "default") != "default" or float(
                local.get("rope_theta", 1e4)) != float(full.get("rope_theta",
                                                                 1e4)):
            raise NotImplementedError(
                "sliding-attention layers with scaled RoPE, or a theta of "
                "their own, are not mapped")
        yarn = None
        if full.get("rope_type", "default") == "yarn":
            yarn = (float(full["factor"]),
                    int(full["original_max_position_embeddings"]),
                    float(full.get("beta_fast", 32.0)),
                    float(full.get("beta_slow", 1.0)),
                    full.get("attention_factor"))
        elif full.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"rope_type {full['rope_type']!r} is not mapped")
        mixed = window and "sliding_attention" in kinds
        return _llama_family_config(
            hf_cfg, head_dim=int(hf_cfg.head_dim),
            rope_theta=float(full.get("rope_theta", 1e4)), rope_yarn=yarn,
            sliding_window=window or None,
            window_pattern=tuple(window if k == "sliding_attention" else 0
                                 for k in kinds) if mixed else None,
            moe_impl="grouped", num_experts=int(hf_cfg.num_experts),
            num_experts_per_tok=int(hf_cfg.num_experts_per_tok),
            moe_intermediate_size=int(hf_cfg.moe_intermediate_size),
            moe_norm_topk=bool(_get(hf_cfg, "norm_topk_prob", default=True)))


def _t_phi3_q(w, cfg):
    q = w[: cfg.num_heads * cfg.dims_per_head]
    return q.T.reshape(cfg.hidden_size, cfg.num_heads, cfg.dims_per_head)


def _t_phi3_k(w, cfg):
    h, kvh, d = cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
    k = w[h * d:(h + kvh) * d]
    return k.T.reshape(cfg.hidden_size, kvh, d)


def _t_phi3_v(w, cfg):
    h, kvh, d = cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
    v = w[(h + kvh) * d:]
    return v.T.reshape(cfg.hidden_size, kvh, d)


def _t_phi3_gate(w, cfg):
    return w[: cfg.ffn_size].T


def _t_phi3_up(w, cfg):
    return w[cfg.ffn_size:].T


class Phi3Container(LlamaContainer):
    """Phi-3: fused qkv_proj / gate_up_proj split on load (reference
    ``phi3/containers.py``)."""

    layer_mapping = {
        "attn.wq": Param("model.layers.{l}.self_attn.qkv_proj.weight", _t_phi3_q),
        "attn.wk": Param("model.layers.{l}.self_attn.qkv_proj.weight", _t_phi3_k),
        "attn.wv": Param("model.layers.{l}.self_attn.qkv_proj.weight", _t_phi3_v),
        "attn.wo": Param("model.layers.{l}.self_attn.o_proj.weight", t_o_heads),
        "norm1.scale": Param("model.layers.{l}.input_layernorm.weight"),
        "norm2.scale": Param("model.layers.{l}.post_attention_layernorm.weight"),
        "mlp.wi_gate": Param("model.layers.{l}.mlp.gate_up_proj.weight", _t_phi3_gate),
        "mlp.wi_up": Param("model.layers.{l}.mlp.gate_up_proj.weight", _t_phi3_up),
        "mlp.wo": Param("model.layers.{l}.mlp.down_proj.weight", t_linear),
    }


def _t_opt_pos(w, cfg):
    return w  # offset handled by cfg.position_offset at lookup time


class OPTContainer(LayerContainer):
    """OPT (reference ``opt/container.py``): learned positions offset by 2,
    pre-LN layernorm with biases, relu MLP, tied embeddings."""

    layer_mapping = {
        "attn.wq": Param("model.decoder.layers.{l}.self_attn.q_proj.weight", t_q_heads),
        "attn.wk": Param("model.decoder.layers.{l}.self_attn.k_proj.weight", t_kv_heads),
        "attn.wv": Param("model.decoder.layers.{l}.self_attn.v_proj.weight", t_kv_heads),
        "attn.wo": Param("model.decoder.layers.{l}.self_attn.out_proj.weight", t_o_heads),
        "attn.bq": Param("model.decoder.layers.{l}.self_attn.q_proj.bias", t_q_bias),
        "attn.bk": Param("model.decoder.layers.{l}.self_attn.k_proj.bias", t_kv_bias),
        "attn.bv": Param("model.decoder.layers.{l}.self_attn.v_proj.bias", t_kv_bias),
        "attn.bo": Param("model.decoder.layers.{l}.self_attn.out_proj.bias"),
        "norm1.scale": Param("model.decoder.layers.{l}.self_attn_layer_norm.weight"),
        "norm1.bias": Param("model.decoder.layers.{l}.self_attn_layer_norm.bias"),
        "norm2.scale": Param("model.decoder.layers.{l}.final_layer_norm.weight"),
        "norm2.bias": Param("model.decoder.layers.{l}.final_layer_norm.bias"),
        "mlp.wi": Param("model.decoder.layers.{l}.fc1.weight", t_linear),
        "mlp.bi": Param("model.decoder.layers.{l}.fc1.bias"),
        "mlp.wo": Param("model.decoder.layers.{l}.fc2.weight", t_linear),
        "mlp.bo": Param("model.decoder.layers.{l}.fc2.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("model.decoder.embed_tokens.weight"),
        "embed.pos": Param("model.decoder.embed_positions.weight", _t_opt_pos),
        "final_norm.scale": Param("model.decoder.final_layer_norm.weight"),
        "final_norm.bias": Param("model.decoder.final_layer_norm.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers, num_heads=hf_cfg.num_attention_heads,
            intermediate_size=hf_cfg.ffn_dim,
            max_seq_len=hf_cfg.max_position_embeddings,
            activation="relu", norm="layernorm", position="learned",
            position_offset=2, use_bias=True, tie_embeddings=True,
            norm_eps=1e-5)


def _t_gpt2_qkv(idx):
    def t(w, cfg):
        e = cfg.hidden_size
        part = w[:, idx * e:(idx + 1) * e]  # Conv1D weights are (in, out)
        return part.reshape(e, cfg.num_heads, cfg.dims_per_head)
    return t


def _t_gpt2_qkv_bias(idx):
    def t(b, cfg):
        e = cfg.hidden_size
        return b[idx * e:(idx + 1) * e].reshape(cfg.num_heads, cfg.dims_per_head)
    return t


def _t_gpt2_o(w, cfg):
    return w.reshape(cfg.num_heads, cfg.dims_per_head, cfg.hidden_size)


class GPT2Container(LayerContainer):
    """GPT-2 (Conv1D (in, out) weights; fused c_attn split on load)."""

    layer_mapping = {
        "attn.wq": Param("transformer.h.{l}.attn.c_attn.weight", _t_gpt2_qkv(0)),
        "attn.wk": Param("transformer.h.{l}.attn.c_attn.weight", _t_gpt2_qkv(1)),
        "attn.wv": Param("transformer.h.{l}.attn.c_attn.weight", _t_gpt2_qkv(2)),
        "attn.bq": Param("transformer.h.{l}.attn.c_attn.bias", _t_gpt2_qkv_bias(0)),
        "attn.bk": Param("transformer.h.{l}.attn.c_attn.bias", _t_gpt2_qkv_bias(1)),
        "attn.bv": Param("transformer.h.{l}.attn.c_attn.bias", _t_gpt2_qkv_bias(2)),
        "attn.wo": Param("transformer.h.{l}.attn.c_proj.weight", _t_gpt2_o),
        "attn.bo": Param("transformer.h.{l}.attn.c_proj.bias"),
        "norm1.scale": Param("transformer.h.{l}.ln_1.weight"),
        "norm1.bias": Param("transformer.h.{l}.ln_1.bias"),
        "norm2.scale": Param("transformer.h.{l}.ln_2.weight"),
        "norm2.bias": Param("transformer.h.{l}.ln_2.bias"),
        "mlp.wi": Param("transformer.h.{l}.mlp.c_fc.weight"),
        "mlp.bi": Param("transformer.h.{l}.mlp.c_fc.bias"),
        "mlp.wo": Param("transformer.h.{l}.mlp.c_proj.weight"),
        "mlp.bo": Param("transformer.h.{l}.mlp.c_proj.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("transformer.wte.weight"),
        "embed.pos": Param("transformer.wpe.weight"),
        "final_norm.scale": Param("transformer.ln_f.weight"),
        "final_norm.bias": Param("transformer.ln_f.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.n_embd,
            num_layers=hf_cfg.n_layer, num_heads=hf_cfg.n_head,
            intermediate_size=4 * hf_cfg.n_embd, max_seq_len=hf_cfg.n_positions,
            activation="gelu", norm="layernorm", position="learned",
            tie_embeddings=True, use_bias=True,
            norm_eps=hf_cfg.layer_norm_epsilon)


def _t_falcon_q(w, cfg):
    """Falcon (multi_query) fused query_key_value: rows are
    [q_head0..q_headH-1, k, v] each of head_dim."""
    h, d, e = cfg.num_heads, cfg.dims_per_head, cfg.hidden_size
    q = w.reshape(h + 2, d, e)[:h]             # (h, d, e)
    return q.transpose(2, 0, 1)


def _t_falcon_k(w, cfg):
    h, d, e = cfg.num_heads, cfg.dims_per_head, cfg.hidden_size
    k = w.reshape(h + 2, d, e)[h:h + 1]        # (1, d, e)
    return k.transpose(2, 0, 1)


def _t_falcon_v(w, cfg):
    h, d, e = cfg.num_heads, cfg.dims_per_head, cfg.hidden_size
    v = w.reshape(h + 2, d, e)[h + 1:]
    return v.transpose(2, 0, 1)


class FalconContainer(LayerContainer):
    """Falcon-7B style (reference ``falcon/container.py``): multi-query
    attention (one shared KV head), parallel attention+MLP sharing a SINGLE
    layernorm — mapped by binding norm1 and norm2 to the same source tensor.
    """

    layer_mapping = {
        "attn.wq": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_falcon_q),
        "attn.wk": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_falcon_k),
        "attn.wv": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_falcon_v),
        "attn.wo": Param("transformer.h.{l}.self_attention.dense.weight", t_o_heads),
        "norm1.scale": Param("transformer.h.{l}.input_layernorm.weight"),
        "norm1.bias": Param("transformer.h.{l}.input_layernorm.bias"),
        # parallel block with ONE shared norm: same tensor feeds both slots
        "norm2.scale": Param("transformer.h.{l}.input_layernorm.weight"),
        "norm2.bias": Param("transformer.h.{l}.input_layernorm.bias"),
        "mlp.wi": Param("transformer.h.{l}.mlp.dense_h_to_4h.weight", t_linear),
        "mlp.wo": Param("transformer.h.{l}.mlp.dense_4h_to_h.weight", t_linear),
    }
    non_layer_mapping = {
        "embed.tok": Param("transformer.word_embeddings.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear, optional=True),
        "final_norm.scale": Param("transformer.ln_f.weight"),
        "final_norm.bias": Param("transformer.ln_f.bias"),
    }

    @classmethod
    def specialize(cls, hf_cfg):
        if getattr(hf_cfg, "new_decoder_architecture", False):
            n_ln = getattr(hf_cfg, "num_ln_in_parallel_attn", None)
            if n_ln is None:
                n_ln = 2   # HF defaults to 2 under new_decoder_architecture
            return (FalconNewArchContainer if n_ln == 2
                    else FalconNewArchSharedLnContainer)
        return cls

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            num_kv_heads=1 if getattr(hf_cfg, "multi_query", True)
            else hf_cfg.num_attention_heads,
            intermediate_size=4 * hf_cfg.hidden_size,
            max_seq_len=_get(hf_cfg, "max_position_embeddings", default=2048),
            activation="gelu_exact", norm="layernorm", position="rope",
            rope_theta=float(_get(hf_cfg, "rope_theta", default=10000.0)),
            parallel_block=bool(_get(hf_cfg, "parallel_attn", default=True)),
            tie_embeddings=bool(_get(hf_cfg, "tie_word_embeddings", default=True)),
            norm_eps=float(_get(hf_cfg, "layer_norm_epsilon", default=1e-5)))


def _t_falcon_grouped(part):
    """Falcon new_decoder_architecture fused QKV: rows are grouped per KV
    head as [q_0..q_{hpg-1}, k, v] (HF ``FalconAttention._split_heads``)."""

    def t(w, cfg):
        kvh, h, d, e = cfg.kv_heads, cfg.num_heads, cfg.dims_per_head, cfg.hidden_size
        hpg = h // kvh
        w = w.reshape(kvh, hpg + 2, d, e)
        if part == "q":
            out = w[:, :hpg].reshape(h, d, e)
        elif part == "k":
            out = w[:, hpg]
        else:
            out = w[:, hpg + 1]
        return out.transpose(2, 0, 1)

    return t


class FalconNewArchContainer(FalconContainer):
    """Falcon-40B/180B (new_decoder_architecture): grouped-KV fused QKV and
    TWO parallel-block norms — ln_attn feeds attention, ln_mlp feeds the MLP
    (reference ``falcon/container.py`` maps the same split)."""

    layer_mapping = {
        "attn.wq": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_falcon_grouped("q")),
        "attn.wk": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_falcon_grouped("k")),
        "attn.wv": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_falcon_grouped("v")),
        "attn.wo": Param("transformer.h.{l}.self_attention.dense.weight", t_o_heads),
        "norm1.scale": Param("transformer.h.{l}.ln_attn.weight"),
        "norm1.bias": Param("transformer.h.{l}.ln_attn.bias"),
        "norm2.scale": Param("transformer.h.{l}.ln_mlp.weight"),
        "norm2.bias": Param("transformer.h.{l}.ln_mlp.bias"),
        "mlp.wi": Param("transformer.h.{l}.mlp.dense_h_to_4h.weight", t_linear),
        "mlp.wo": Param("transformer.h.{l}.mlp.dense_4h_to_h.weight", t_linear),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            num_kv_heads=int(_get(hf_cfg, "num_kv_heads",
                                  default=hf_cfg.num_attention_heads)),
            intermediate_size=int(_get(hf_cfg, "ffn_hidden_size",
                                       default=4 * hf_cfg.hidden_size)),
            max_seq_len=_get(hf_cfg, "max_position_embeddings", default=2048),
            activation="gelu_exact", norm="layernorm", position="rope",
            rope_theta=float(_get(hf_cfg, "rope_theta", default=10000.0)),
            parallel_block=bool(_get(hf_cfg, "parallel_attn", default=True)),
            tie_embeddings=bool(_get(hf_cfg, "tie_word_embeddings", default=True)),
            norm_eps=float(_get(hf_cfg, "layer_norm_epsilon", default=1e-5)))


class FalconNewArchSharedLnContainer(FalconNewArchContainer):
    """new_decoder_architecture with num_ln_in_parallel_attn == 1: one
    input_layernorm shared by both parallel branches."""

    layer_mapping = {
        **FalconNewArchContainer.layer_mapping,
        "norm1.scale": Param("transformer.h.{l}.input_layernorm.weight"),
        "norm1.bias": Param("transformer.h.{l}.input_layernorm.bias"),
        "norm2.scale": Param("transformer.h.{l}.input_layernorm.weight"),
        "norm2.bias": Param("transformer.h.{l}.input_layernorm.bias"),
    }


def _t_neox_qkv(idx):
    """NeoX fused query_key_value is HEAD-interleaved: (heads*3*d, e)."""

    def t(w, cfg):
        h, d, e = cfg.num_heads, cfg.dims_per_head, cfg.hidden_size
        part = w.reshape(h, 3, d, e)[:, idx]       # (heads, d, e)
        return part.transpose(2, 0, 1)             # (e, heads, d)

    return t


def _t_neox_qkv_bias(idx):
    def t(b, cfg):
        h, d = cfg.num_heads, cfg.dims_per_head
        return b.reshape(h, 3, d)[:, idx]

    return t


def _t_neox_o(w, cfg):
    return w.T.reshape(cfg.num_heads, cfg.dims_per_head, cfg.hidden_size)


class GPTNeoXContainer(LayerContainer):
    """GPT-NeoX / Pythia: head-interleaved fused QKV, partial rotary
    (``rotary_pct``), parallel attention+MLP residual, exact-erf gelu."""

    layer_mapping = {
        "attn.wq": Param("gpt_neox.layers.{l}.attention.query_key_value.weight",
                         _t_neox_qkv(0)),
        "attn.wk": Param("gpt_neox.layers.{l}.attention.query_key_value.weight",
                         _t_neox_qkv(1)),
        "attn.wv": Param("gpt_neox.layers.{l}.attention.query_key_value.weight",
                         _t_neox_qkv(2)),
        "attn.bq": Param("gpt_neox.layers.{l}.attention.query_key_value.bias",
                         _t_neox_qkv_bias(0)),
        "attn.bk": Param("gpt_neox.layers.{l}.attention.query_key_value.bias",
                         _t_neox_qkv_bias(1)),
        "attn.bv": Param("gpt_neox.layers.{l}.attention.query_key_value.bias",
                         _t_neox_qkv_bias(2)),
        "attn.wo": Param("gpt_neox.layers.{l}.attention.dense.weight", _t_neox_o),
        "attn.bo": Param("gpt_neox.layers.{l}.attention.dense.bias"),
        "norm1.scale": Param("gpt_neox.layers.{l}.input_layernorm.weight"),
        "norm1.bias": Param("gpt_neox.layers.{l}.input_layernorm.bias"),
        "norm2.scale": Param("gpt_neox.layers.{l}.post_attention_layernorm.weight"),
        "norm2.bias": Param("gpt_neox.layers.{l}.post_attention_layernorm.bias"),
        "mlp.wi": Param("gpt_neox.layers.{l}.mlp.dense_h_to_4h.weight", t_linear),
        "mlp.bi": Param("gpt_neox.layers.{l}.mlp.dense_h_to_4h.bias"),
        "mlp.wo": Param("gpt_neox.layers.{l}.mlp.dense_4h_to_h.weight", t_linear),
        "mlp.bo": Param("gpt_neox.layers.{l}.mlp.dense_4h_to_h.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("gpt_neox.embed_in.weight"),
        "embed.lm_head": Param("embed_out.weight", t_linear),
        "final_norm.scale": Param("gpt_neox.final_layer_norm.weight"),
        "final_norm.bias": Param("gpt_neox.final_layer_norm.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            intermediate_size=hf_cfg.intermediate_size,
            max_seq_len=hf_cfg.max_position_embeddings,
            activation="gelu_exact" if hf_cfg.hidden_act == "gelu" else "gelu",
            norm="layernorm", position="rope",
            rope_theta=float(_get(hf_cfg, "rotary_emb_base", "rope_theta",
                                  default=10000.0)),
            rotary_pct=float(_get(hf_cfg, "rotary_pct", default=0.25)),
            parallel_block=bool(_get(hf_cfg, "use_parallel_residual",
                                     default=True)),
            use_bias=True, tie_embeddings=False,
            norm_eps=float(_get(hf_cfg, "layer_norm_eps", default=1e-5)))


class GPTJContainer(LayerContainer):
    """GPT-J: interleaved partial rotary, parallel block with ONE shared
    layernorm, no attention biases but biased MLP (``mlp_bias``)."""

    layer_mapping = {
        "attn.wq": Param("transformer.h.{l}.attn.q_proj.weight", t_q_heads),
        "attn.wk": Param("transformer.h.{l}.attn.k_proj.weight", t_kv_heads),
        "attn.wv": Param("transformer.h.{l}.attn.v_proj.weight", t_kv_heads),
        "attn.wo": Param("transformer.h.{l}.attn.out_proj.weight", t_o_heads),
        "norm1.scale": Param("transformer.h.{l}.ln_1.weight"),
        "norm1.bias": Param("transformer.h.{l}.ln_1.bias"),
        "norm2.scale": Param("transformer.h.{l}.ln_1.weight"),   # shared norm
        "norm2.bias": Param("transformer.h.{l}.ln_1.bias"),
        "mlp.wi": Param("transformer.h.{l}.mlp.fc_in.weight", t_linear),
        "mlp.bi": Param("transformer.h.{l}.mlp.fc_in.bias"),
        "mlp.wo": Param("transformer.h.{l}.mlp.fc_out.weight", t_linear),
        "mlp.bo": Param("transformer.h.{l}.mlp.fc_out.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("transformer.wte.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear),
        "embed.lm_head_bias": Param("lm_head.bias", optional=True),
        "final_norm.scale": Param("transformer.ln_f.weight"),
        "final_norm.bias": Param("transformer.ln_f.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        d = hf_cfg.n_embd // hf_cfg.n_head
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.n_embd,
            num_layers=hf_cfg.n_layer, num_heads=hf_cfg.n_head,
            intermediate_size=_get(hf_cfg, "n_inner", default=4 * hf_cfg.n_embd),
            max_seq_len=hf_cfg.n_positions,
            activation="gelu", norm="layernorm", position="rope",
            rotary_pct=(_get(hf_cfg, "rotary_dim", default=d) or d) / d,
            rope_interleaved=True, parallel_block=True,
            use_bias=False, mlp_bias=True, tie_embeddings=False,
            lm_head_bias=True,
            norm_eps=float(_get(hf_cfg, "layer_norm_epsilon", default=1e-5)))


def _t_rms_offset(w, cfg):
    """Gemma stores RMSNorm weights as offsets (applied as x*(1+w)); adding
    1 at load maps them onto the standard x*w RMSNorm."""
    # fp32 add: HF computes 1 + w.float() per call; adding in a bf16
    # checkpoint's dtype would round the offset at load
    return w.astype(np.float32) + 1.0


class GemmaContainer(LlamaContainer):
    """Gemma (1): GeGLU MLP, sqrt(E)-scaled embeddings, offset RMSNorm
    weights, explicit head_dim, tied head."""

    layer_mapping = {
        **LlamaContainer.layer_mapping,
        "norm1.scale": Param("model.layers.{l}.input_layernorm.weight", _t_rms_offset),
        "norm2.scale": Param("model.layers.{l}.post_attention_layernorm.weight",
                             _t_rms_offset),
    }
    non_layer_mapping = {
        "embed.tok": Param("model.embed_tokens.weight"),
        "final_norm.scale": Param("model.norm.weight", _t_rms_offset),
    }

    @classmethod
    def config(cls, hf_cfg):
        return _llama_family_config(
            hf_cfg, activation="geglu",
            head_dim=_get(hf_cfg, "head_dim"),
            embed_scale=float(hf_cfg.hidden_size) ** 0.5,
            tie_embeddings=True)


class Gemma2Container(GemmaContainer):
    """Gemma-2 (HF ``modeling_gemma2``): sandwich norms (input / post-attn /
    pre-ffw / post-ffw, all offset-RMSNorm), attention-logit and final-logit
    tanh softcapping, query_pre_attn_scalar attention scale, and sliding
    window on the EVEN-indexed layers (HF layer_types alternation)."""

    layer_mapping = {
        **GemmaContainer.layer_mapping,
        "norm1.scale": Param("model.layers.{l}.input_layernorm.weight",
                             _t_rms_offset),
        "norm3.scale": Param("model.layers.{l}.post_attention_layernorm.weight",
                             _t_rms_offset),
        "norm2.scale": Param("model.layers.{l}.pre_feedforward_layernorm.weight",
                             _t_rms_offset),
        "norm4.scale": Param("model.layers.{l}.post_feedforward_layernorm.weight",
                             _t_rms_offset),
    }

    @classmethod
    def config(cls, hf_cfg):
        n = hf_cfg.num_hidden_layers
        sw = int(_get(hf_cfg, "sliding_window", default=4096) or 0)
        lt = list(getattr(hf_cfg, "layer_types", None) or
                  ["sliding_attention" if (i + 1) % 2 else "full_attention"
                   for i in range(n)])
        pattern = tuple(sw if t == "sliding_attention" else 0 for t in lt)
        if not sw or not any(pattern):
            pattern = None
        return _llama_family_config(
            hf_cfg, activation="geglu",
            head_dim=_get(hf_cfg, "head_dim"),
            embed_scale=float(hf_cfg.hidden_size) ** 0.5,
            tie_embeddings=True,
            sandwich_norm=True,
            window_pattern=pattern,
            attn_scale=float(_get(hf_cfg, "query_pre_attn_scalar",
                                  default=hf_cfg.head_dim)) ** -0.5,
            attn_softcap=float(_get(hf_cfg, "attn_logit_softcapping", default=0.0)
                               or 0.0),
            logit_softcap=float(_get(hf_cfg, "final_logit_softcapping", default=0.0)
                                or 0.0))


def _t_mpt_qkv(idx):
    """MPT fused Wqkv is stacked [q; k; v], each (E, E)."""

    def t(w, cfg):
        e = cfg.hidden_size
        part = w[idx * e:(idx + 1) * e]                # (E, E)
        return part.T.reshape(e, cfg.num_heads, cfg.dims_per_head)

    return t


class MptContainer(LayerContainer):
    """MPT (MosaicML): ALiBi positions, bias-free stacked-QKV blocks,
    layernorms without biases, exact gelu, tied head."""

    layer_mapping = {
        "attn.wq": Param("transformer.blocks.{l}.attn.Wqkv.weight", _t_mpt_qkv(0)),
        "attn.wk": Param("transformer.blocks.{l}.attn.Wqkv.weight", _t_mpt_qkv(1)),
        "attn.wv": Param("transformer.blocks.{l}.attn.Wqkv.weight", _t_mpt_qkv(2)),
        "attn.wo": Param("transformer.blocks.{l}.attn.out_proj.weight", t_o_heads),
        "norm1.scale": Param("transformer.blocks.{l}.norm_1.weight"),
        "norm1.bias": Param("transformer.blocks.{l}.norm_1.bias", optional=True),
        "norm2.scale": Param("transformer.blocks.{l}.norm_2.weight"),
        "norm2.bias": Param("transformer.blocks.{l}.norm_2.bias", optional=True),
        "mlp.wi": Param("transformer.blocks.{l}.ffn.up_proj.weight", t_linear),
        "mlp.wo": Param("transformer.blocks.{l}.ffn.down_proj.weight", t_linear),
        # qk_ln variant (full-width norms before the head split)
        "attn.q_norm.scale": Param("transformer.blocks.{l}.attn.q_ln.weight",
                                   optional=True),
        "attn.q_norm.bias": Param("transformer.blocks.{l}.attn.q_ln.bias",
                                  optional=True),
        "attn.k_norm.scale": Param("transformer.blocks.{l}.attn.k_ln.weight",
                                   optional=True),
        "attn.k_norm.bias": Param("transformer.blocks.{l}.attn.k_ln.bias",
                                  optional=True),
    }
    non_layer_mapping = {
        "embed.tok": Param("transformer.wte.weight"),
        "final_norm.scale": Param("transformer.norm_f.weight"),
        "final_norm.bias": Param("transformer.norm_f.bias", optional=True),
    }

    @classmethod
    def config(cls, hf_cfg):
        attn_cfg = getattr(hf_cfg, "attn_config", None)
        ac = lambda k, d: getattr(attn_cfg, k, d) if attn_cfg is not None else d
        alibi = ac("alibi", True)
        rope = ac("rope", False)
        if not alibi and not rope:
            raise NotImplementedError(
                "MPT with learned positions (alibi=False, rope=False) not mapped")
        if not getattr(hf_cfg, "no_bias", True):
            raise NotImplementedError(
                "MPT no_bias=False checkpoints (biased Wqkv/out_proj/ffn) "
                "not mapped — loading would silently drop the biases")
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.d_model,
            num_layers=hf_cfg.n_layers, num_heads=hf_cfg.n_heads,
            intermediate_size=int(hf_cfg.expansion_ratio * hf_cfg.d_model),
            max_seq_len=_get(hf_cfg, "max_seq_len", default=2048),
            activation="gelu_exact", norm="layernorm",
            position="alibi" if alibi else "rope",
            rope_theta=float(ac("rope_theta", 10000.0)),
            # MPT qk_ln: LayerNorm(d_model) on q / (kvh*d) on k BEFORE the
            # head split (modeling_mpt attn qk_ln) = our "full" layout
            qk_norm="full" if ac("qk_ln", False) else None,
            use_bias=False, tie_embeddings=True,
            norm_eps=float(_get(hf_cfg, "layer_norm_epsilon", default=1e-5)))

    @classmethod
    def build_params(cls, sd, cfg):
        params = super().build_params(sd, cfg)
        # layernorm applies a bias unconditionally; MPT's no_bias checkpoints
        # carry none — synthesize zeros
        for nm in ("norm1", "norm2"):
            grp = params["layers"][nm]
            if "bias" not in grp:
                grp["bias"] = np.zeros_like(grp["scale"])
        for nm in ("q_norm", "k_norm"):   # qk_ln under no_bias
            grp = params["layers"]["attn"].get(nm)
            if grp is not None and "bias" not in grp:
                grp["bias"] = np.zeros_like(grp["scale"])
        if "bias" not in params["final_norm"]:
            params["final_norm"]["bias"] = np.zeros_like(params["final_norm"]["scale"])
        return params


class StableLmContainer(LayerContainer):
    """StableLM: layernorm (with biases) around a Llama-style block, partial
    rotary, optional qkv biases, untied head."""

    layer_mapping = {
        "attn.wq": Param("model.layers.{l}.self_attn.q_proj.weight", t_q_heads),
        "attn.wk": Param("model.layers.{l}.self_attn.k_proj.weight", t_kv_heads),
        "attn.wv": Param("model.layers.{l}.self_attn.v_proj.weight", t_kv_heads),
        "attn.bq": Param("model.layers.{l}.self_attn.q_proj.bias", t_q_bias,
                         optional=True),
        "attn.bk": Param("model.layers.{l}.self_attn.k_proj.bias", t_kv_bias,
                         optional=True),
        "attn.bv": Param("model.layers.{l}.self_attn.v_proj.bias", t_kv_bias,
                         optional=True),
        "attn.wo": Param("model.layers.{l}.self_attn.o_proj.weight", t_o_heads),
        "norm1.scale": Param("model.layers.{l}.input_layernorm.weight"),
        "norm1.bias": Param("model.layers.{l}.input_layernorm.bias"),
        "norm2.scale": Param("model.layers.{l}.post_attention_layernorm.weight"),
        "norm2.bias": Param("model.layers.{l}.post_attention_layernorm.bias"),
        "mlp.wi_gate": Param("model.layers.{l}.mlp.gate_proj.weight", t_linear),
        "mlp.wi_up": Param("model.layers.{l}.mlp.up_proj.weight", t_linear),
        "mlp.wo": Param("model.layers.{l}.mlp.down_proj.weight", t_linear),
        # qk_layernorm variant: HF StableLmLayerNormPerHead is a ModuleList
        # of bias-free LayerNorm(head_dim) — {h}/{g} stack them to (H, D)
        "attn.q_norm.scale": Param(
            "model.layers.{l}.self_attn.q_layernorm.norms.{h}.weight",
            optional=True),
        "attn.k_norm.scale": Param(
            "model.layers.{l}.self_attn.k_layernorm.norms.{g}.weight",
            optional=True),
    }
    non_layer_mapping = {
        "embed.tok": Param("model.embed_tokens.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear),
        "final_norm.scale": Param("model.norm.weight"),
        "final_norm.bias": Param("model.norm.bias"),
    }

    @classmethod
    def specialize(cls, hf_cfg):
        if getattr(hf_cfg, "use_parallel_residual", False):
            return StableLmParallelContainer
        return cls

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            num_kv_heads=_get(hf_cfg, "num_key_value_heads"),
            intermediate_size=hf_cfg.intermediate_size,
            max_seq_len=hf_cfg.max_position_embeddings,
            activation="swiglu", norm="layernorm", position="rope",
            rope_theta=float(_get(hf_cfg, "rope_theta", default=10000.0)),
            rotary_pct=float(_get(hf_cfg, "partial_rotary_factor", default=0.25)),
            qkv_bias=bool(_get(hf_cfg, "use_qkv_bias", default=False)),
            qk_norm="per_head" if getattr(hf_cfg, "qk_layernorm", False) else None,
            qk_norm_bias=False,
            parallel_block=bool(getattr(hf_cfg, "use_parallel_residual", False)),
            tie_embeddings=False,
            norm_eps=float(_get(hf_cfg, "layer_norm_eps", default=1e-5)))


class StableLmParallelContainer(StableLmContainer):
    """StableLM with use_parallel_residual: ONE shared input_layernorm feeds
    both attention and MLP (HF StableLmDecoderLayer drops
    post_attention_layernorm in this mode) — norm2 binds to the same tensor."""

    layer_mapping = {
        **StableLmContainer.layer_mapping,
        "norm2.scale": Param("model.layers.{l}.input_layernorm.weight"),
        "norm2.bias": Param("model.layers.{l}.input_layernorm.bias"),
    }


class BertContainer(LayerContainer):
    """BERT (reference ``module_inject/containers/bert.py``): post-norm
    encoder blocks, token-type embeddings, embedding layernorm, MLM head
    (transform dense + LN + tied decoder with vocab bias)."""

    from ....models.bert import EncoderLM as model_class

    layer_mapping = {
        "attn.wq": Param("bert.encoder.layer.{l}.attention.self.query.weight", t_q_heads),
        "attn.wk": Param("bert.encoder.layer.{l}.attention.self.key.weight", t_kv_heads),
        "attn.wv": Param("bert.encoder.layer.{l}.attention.self.value.weight", t_kv_heads),
        "attn.bq": Param("bert.encoder.layer.{l}.attention.self.query.bias", t_q_bias),
        "attn.bk": Param("bert.encoder.layer.{l}.attention.self.key.bias", t_kv_bias),
        "attn.bv": Param("bert.encoder.layer.{l}.attention.self.value.bias", t_kv_bias),
        "attn.wo": Param("bert.encoder.layer.{l}.attention.output.dense.weight", t_o_heads),
        "attn.bo": Param("bert.encoder.layer.{l}.attention.output.dense.bias"),
        "norm1.scale": Param("bert.encoder.layer.{l}.attention.output.LayerNorm.weight"),
        "norm1.bias": Param("bert.encoder.layer.{l}.attention.output.LayerNorm.bias"),
        "norm2.scale": Param("bert.encoder.layer.{l}.output.LayerNorm.weight"),
        "norm2.bias": Param("bert.encoder.layer.{l}.output.LayerNorm.bias"),
        "mlp.wi": Param("bert.encoder.layer.{l}.intermediate.dense.weight", t_linear),
        "mlp.bi": Param("bert.encoder.layer.{l}.intermediate.dense.bias"),
        "mlp.wo": Param("bert.encoder.layer.{l}.output.dense.weight", t_linear),
        "mlp.bo": Param("bert.encoder.layer.{l}.output.dense.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("bert.embeddings.word_embeddings.weight"),
        "embed.pos": Param("bert.embeddings.position_embeddings.weight"),
        "embed.type": Param("bert.embeddings.token_type_embeddings.weight"),
        "embed.emb_norm.scale": Param("bert.embeddings.LayerNorm.weight"),
        "embed.emb_norm.bias": Param("bert.embeddings.LayerNorm.bias"),
        "mlm.dense": Param("cls.predictions.transform.dense.weight", t_linear,
                           optional=True),
        "mlm.bias": Param("cls.predictions.transform.dense.bias", optional=True),
        "mlm.norm.scale": Param("cls.predictions.transform.LayerNorm.weight",
                                optional=True),
        "mlm.norm.bias": Param("cls.predictions.transform.LayerNorm.bias",
                               optional=True),
        "mlm.decoder_bias": Param("cls.predictions.bias", optional=True),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            intermediate_size=hf_cfg.intermediate_size,
            max_seq_len=hf_cfg.max_position_embeddings,
            type_vocab_size=int(_get(hf_cfg, "type_vocab_size", default=2)),
            activation="gelu_exact", norm="layernorm", position="learned",
            post_norm=True, causal=False, embedding_norm=True, mlm_head=True,
            use_bias=True, tie_embeddings=True,
            norm_eps=float(_get(hf_cfg, "layer_norm_eps", default=1e-12)))


class DistilBertContainer(LayerContainer):
    """DistilBERT (reference ``module_inject/containers/distil_bert.py``):
    BERT graph without token types; MLM head named vocab_transform/
    vocab_layer_norm/vocab_projector."""

    from ....models.bert import EncoderLM as model_class

    layer_mapping = {
        "attn.wq": Param("distilbert.transformer.layer.{l}.attention.q_lin.weight", t_q_heads),
        "attn.wk": Param("distilbert.transformer.layer.{l}.attention.k_lin.weight", t_kv_heads),
        "attn.wv": Param("distilbert.transformer.layer.{l}.attention.v_lin.weight", t_kv_heads),
        "attn.bq": Param("distilbert.transformer.layer.{l}.attention.q_lin.bias", t_q_bias),
        "attn.bk": Param("distilbert.transformer.layer.{l}.attention.k_lin.bias", t_kv_bias),
        "attn.bv": Param("distilbert.transformer.layer.{l}.attention.v_lin.bias", t_kv_bias),
        "attn.wo": Param("distilbert.transformer.layer.{l}.attention.out_lin.weight", t_o_heads),
        "attn.bo": Param("distilbert.transformer.layer.{l}.attention.out_lin.bias"),
        "norm1.scale": Param("distilbert.transformer.layer.{l}.sa_layer_norm.weight"),
        "norm1.bias": Param("distilbert.transformer.layer.{l}.sa_layer_norm.bias"),
        "norm2.scale": Param("distilbert.transformer.layer.{l}.output_layer_norm.weight"),
        "norm2.bias": Param("distilbert.transformer.layer.{l}.output_layer_norm.bias"),
        "mlp.wi": Param("distilbert.transformer.layer.{l}.ffn.lin1.weight", t_linear),
        "mlp.bi": Param("distilbert.transformer.layer.{l}.ffn.lin1.bias"),
        "mlp.wo": Param("distilbert.transformer.layer.{l}.ffn.lin2.weight", t_linear),
        "mlp.bo": Param("distilbert.transformer.layer.{l}.ffn.lin2.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("distilbert.embeddings.word_embeddings.weight"),
        "embed.pos": Param("distilbert.embeddings.position_embeddings.weight"),
        "embed.emb_norm.scale": Param("distilbert.embeddings.LayerNorm.weight"),
        "embed.emb_norm.bias": Param("distilbert.embeddings.LayerNorm.bias"),
        "mlm.dense": Param("vocab_transform.weight", t_linear, optional=True),
        "mlm.bias": Param("vocab_transform.bias", optional=True),
        "mlm.norm.scale": Param("vocab_layer_norm.weight", optional=True),
        "mlm.norm.bias": Param("vocab_layer_norm.bias", optional=True),
        "mlm.decoder_bias": Param("vocab_projector.bias", optional=True),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.dim,
            num_layers=hf_cfg.n_layers, num_heads=hf_cfg.n_heads,
            intermediate_size=hf_cfg.hidden_dim,
            max_seq_len=hf_cfg.max_position_embeddings,
            activation="gelu_exact", norm="layernorm", position="learned",
            post_norm=True, causal=False, embedding_norm=True, mlm_head=True,
            use_bias=True, tie_embeddings=True, norm_eps=1e-12)


class PhiContainer(LayerContainer):
    """Phi-1.5/Phi-2 (reference ``model_implementations/phi``): parallel
    attention+MLP sharing ONE layernorm, partial rotary, biases everywhere,
    untied biased LM head."""

    layer_mapping = {
        "attn.wq": Param("model.layers.{l}.self_attn.q_proj.weight", t_q_heads),
        "attn.wk": Param("model.layers.{l}.self_attn.k_proj.weight", t_kv_heads),
        "attn.wv": Param("model.layers.{l}.self_attn.v_proj.weight", t_kv_heads),
        "attn.bq": Param("model.layers.{l}.self_attn.q_proj.bias", t_q_bias),
        "attn.bk": Param("model.layers.{l}.self_attn.k_proj.bias", t_kv_bias),
        "attn.bv": Param("model.layers.{l}.self_attn.v_proj.bias", t_kv_bias),
        "attn.wo": Param("model.layers.{l}.self_attn.dense.weight", t_o_heads),
        "attn.bo": Param("model.layers.{l}.self_attn.dense.bias"),
        "norm1.scale": Param("model.layers.{l}.input_layernorm.weight"),
        "norm1.bias": Param("model.layers.{l}.input_layernorm.bias"),
        # parallel block with ONE shared norm (like GPT-J)
        "norm2.scale": Param("model.layers.{l}.input_layernorm.weight"),
        "norm2.bias": Param("model.layers.{l}.input_layernorm.bias"),
        "mlp.wi": Param("model.layers.{l}.mlp.fc1.weight", t_linear),
        "mlp.bi": Param("model.layers.{l}.mlp.fc1.bias"),
        "mlp.wo": Param("model.layers.{l}.mlp.fc2.weight", t_linear),
        "mlp.bo": Param("model.layers.{l}.mlp.fc2.bias"),
        # qk_layernorm variant: one LayerNorm(head_dim) SHARED by all heads
        "attn.q_norm.scale": Param("model.layers.{l}.self_attn.q_layernorm.weight",
                                   optional=True),
        "attn.q_norm.bias": Param("model.layers.{l}.self_attn.q_layernorm.bias",
                                  optional=True),
        "attn.k_norm.scale": Param("model.layers.{l}.self_attn.k_layernorm.weight",
                                   optional=True),
        "attn.k_norm.bias": Param("model.layers.{l}.self_attn.k_layernorm.bias",
                                  optional=True),
    }
    non_layer_mapping = {
        "embed.tok": Param("model.embed_tokens.weight"),
        "embed.lm_head": Param("lm_head.weight", t_linear),
        "embed.lm_head_bias": Param("lm_head.bias", optional=True),
        "final_norm.scale": Param("model.final_layernorm.weight"),
        "final_norm.bias": Param("model.final_layernorm.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            num_kv_heads=_get(hf_cfg, "num_key_value_heads"),
            intermediate_size=hf_cfg.intermediate_size,
            max_seq_len=hf_cfg.max_position_embeddings,
            activation="gelu", norm="layernorm", position="rope",
            rope_theta=float(_get(hf_cfg, "rope_theta", default=10000.0)),
            rotary_pct=float(_get(hf_cfg, "partial_rotary_factor", default=0.5)),
            qk_norm="head_dim" if getattr(hf_cfg, "qk_layernorm", False) else None,
            parallel_block=True, use_bias=True, tie_embeddings=False,
            lm_head_bias=True,
            norm_eps=float(_get(hf_cfg, "layer_norm_eps", default=1e-5)))


class GPTNeoContainer(LayerContainer):
    """GPT-Neo (reference ``module_inject/containers/gptneo.py``): learned
    positions, alternating global/local (windowed) attention, un-biased
    q/k/v with biased out-proj and MLP, tied embeddings."""

    layer_mapping = {
        "attn.wq": Param("transformer.h.{l}.attn.attention.q_proj.weight", t_q_heads),
        "attn.wk": Param("transformer.h.{l}.attn.attention.k_proj.weight", t_kv_heads),
        "attn.wv": Param("transformer.h.{l}.attn.attention.v_proj.weight", t_kv_heads),
        "attn.wo": Param("transformer.h.{l}.attn.attention.out_proj.weight", t_o_heads),
        "attn.bo": Param("transformer.h.{l}.attn.attention.out_proj.bias"),
        "norm1.scale": Param("transformer.h.{l}.ln_1.weight"),
        "norm1.bias": Param("transformer.h.{l}.ln_1.bias"),
        "norm2.scale": Param("transformer.h.{l}.ln_2.weight"),
        "norm2.bias": Param("transformer.h.{l}.ln_2.bias"),
        "mlp.wi": Param("transformer.h.{l}.mlp.c_fc.weight", t_linear),
        "mlp.bi": Param("transformer.h.{l}.mlp.c_fc.bias"),
        "mlp.wo": Param("transformer.h.{l}.mlp.c_proj.weight", t_linear),
        "mlp.bo": Param("transformer.h.{l}.mlp.c_proj.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("transformer.wte.weight"),
        "embed.pos": Param("transformer.wpe.weight"),
        "final_norm.scale": Param("transformer.ln_f.weight"),
        "final_norm.bias": Param("transformer.ln_f.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        layers = list(getattr(hf_cfg, "attention_layers", []))
        sliding, every = None, None
        if "local" in layers:
            every = layers.index("local") + 1
            expected = (["global"] * (every - 1) + ["local"]) * \
                (len(layers) // every) + ["global"] * (len(layers) % every)
            if layers != expected[:len(layers)]:
                raise NotImplementedError(
                    f"irregular gpt-neo attention pattern {layers}")
            sliding = int(getattr(hf_cfg, "window_size", 256))
        # GPT-Neo applies NO attention scaling (HF never divides by
        # sqrt(d)); build_params cancels our 1/sqrt(d) by pre-scaling wq.
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size, hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_layers, num_heads=hf_cfg.num_heads,
            intermediate_size=_get(hf_cfg, "intermediate_size",
                                   default=4 * hf_cfg.hidden_size),
            max_seq_len=hf_cfg.max_position_embeddings,
            activation="gelu", norm="layernorm", position="learned",
            tie_embeddings=True, use_bias=False, out_bias=True, mlp_bias=True,
            sliding_window=sliding, local_attention_every=every,
            norm_eps=float(_get(hf_cfg, "layer_norm_epsilon", default=1e-5)))

    @classmethod
    def build_params(cls, sd, cfg):
        import numpy as np
        params = super().build_params(sd, cfg)
        # HF GPT-Neo uses unscaled q@k.T; our attention multiplies by
        # 1/sqrt(d), so pre-scale wq by sqrt(d) to cancel it. Same-dtype
        # scalar: a float64 python scalar would promote bf16/fp16
        # checkpoints to float64 under NumPy 2.
        wq = params["layers"]["attn"]["wq"]
        params["layers"]["attn"]["wq"] = wq * np.asarray(
            np.sqrt(cfg.dims_per_head), wq.dtype)
        return params


class BloomContainer(LayerContainer):
    """BLOOM (reference ``module_inject/containers/bloom.py``): ALiBi
    positions, a layernorm directly after the word embeddings
    (``embedding_norm``), NeoX-style head-interleaved fused QKV, tied head.
    """

    layer_mapping = {
        "attn.wq": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_neox_qkv(0)),
        "attn.wk": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_neox_qkv(1)),
        "attn.wv": Param("transformer.h.{l}.self_attention.query_key_value.weight",
                         _t_neox_qkv(2)),
        "attn.bq": Param("transformer.h.{l}.self_attention.query_key_value.bias",
                         _t_neox_qkv_bias(0)),
        "attn.bk": Param("transformer.h.{l}.self_attention.query_key_value.bias",
                         _t_neox_qkv_bias(1)),
        "attn.bv": Param("transformer.h.{l}.self_attention.query_key_value.bias",
                         _t_neox_qkv_bias(2)),
        "attn.wo": Param("transformer.h.{l}.self_attention.dense.weight", _t_neox_o),
        "attn.bo": Param("transformer.h.{l}.self_attention.dense.bias"),
        "norm1.scale": Param("transformer.h.{l}.input_layernorm.weight"),
        "norm1.bias": Param("transformer.h.{l}.input_layernorm.bias"),
        "norm2.scale": Param("transformer.h.{l}.post_attention_layernorm.weight"),
        "norm2.bias": Param("transformer.h.{l}.post_attention_layernorm.bias"),
        "mlp.wi": Param("transformer.h.{l}.mlp.dense_h_to_4h.weight", t_linear),
        "mlp.bi": Param("transformer.h.{l}.mlp.dense_h_to_4h.bias"),
        "mlp.wo": Param("transformer.h.{l}.mlp.dense_4h_to_h.weight", t_linear),
        "mlp.bo": Param("transformer.h.{l}.mlp.dense_4h_to_h.bias"),
    }
    non_layer_mapping = {
        "embed.tok": Param("transformer.word_embeddings.weight"),
        "embed.emb_norm.scale": Param("transformer.word_embeddings_layernorm.weight"),
        "embed.emb_norm.bias": Param("transformer.word_embeddings_layernorm.bias"),
        "final_norm.scale": Param("transformer.ln_f.weight"),
        "final_norm.bias": Param("transformer.ln_f.bias"),
    }

    @classmethod
    def config(cls, hf_cfg):
        return TransformerConfig(
            vocab_size=hf_cfg.vocab_size,
            hidden_size=_get(hf_cfg, "hidden_size", "n_embed"),
            num_layers=_get(hf_cfg, "num_hidden_layers", "n_layer"),
            num_heads=_get(hf_cfg, "num_attention_heads", "n_head"),
            max_seq_len=_get(hf_cfg, "max_position_embeddings", default=2048),
            activation="gelu", norm="layernorm", position="alibi",
            embedding_norm=True, use_bias=True, tie_embeddings=True,
            norm_eps=float(_get(hf_cfg, "layer_norm_epsilon", default=1e-5)))


ARCH_CONTAINERS: Dict[str, Type[LayerContainer]] = {
    "distilbert": DistilBertContainer,
    "bert": BertContainer,
    "bloom": BloomContainer,
    "gemma2": Gemma2Container,
    "gemma": GemmaContainer,
    "mpt": MptContainer,
    "stablelm": StableLmContainer,
    "llama": LlamaContainer,
    "mistral": MistralContainer,
    "mixtral": MixtralContainer,
    "qwen2moe": Qwen2MoeContainer,
    "olmoe": OlmoeContainer,
    "sdarmoe": SdarMoeContainer,
    "lfm2moe": Lfm2MoeContainer,
    "mellum": MellumContainer,
    "longcatflash": LongcatFlashContainer,
    "qwen2": Qwen2Container,
    "phi3": Phi3Container,
    "phi": PhiContainer,
    "opt": OPTContainer,
    "gptneox": GPTNeoXContainer,
    "gptneo": GPTNeoContainer,
    "falcon": FalconContainer,
    "gptj": GPTJContainer,
    "gpt2": GPT2Container,
}


class AutoContainer(LlamaContainer):
    """Best-effort fallback for unmapped decoder architectures with the
    Llama module layout — the analog of the reference's AutoTP
    (``module_inject/auto_tp.py:189``), which shards unrecognized models by
    pattern-matching their linear layers rather than per-arch policy."""

    @classmethod
    def config(cls, hf_cfg):
        return _llama_family_config(
            hf_cfg, sliding_window=_get(hf_cfg, "sliding_window"))

    # non-parameter buffers it is safe to leave unread
    _IGNORABLE = ("rotary_emb", "masked_bias", ".attn.bias", "inv_freq")

    @classmethod
    def build_params(cls, sd, cfg):
        # A config can be Llama-shaped while the layout is not (e.g. extra
        # q/k norms): any layer-0 tensor the mapping never reads means the
        # fallback would silently drop load-bearing weights — refuse loudly
        # instead (the explicit-container path's behavior for unknown archs).
        consumed = set()
        for param in cls.layer_mapping.values():
            for src in param.srcs:
                for x in range(max(1, cfg.num_experts)):
                    consumed.add(src.format(l=0, x=x))
        for param in cls.non_layer_mapping.values():
            consumed.update(param.srcs)
        unread = [k for k in sd
                  if (".0." in k or ".0.weight" in k) and "layers.0." in k
                  and k not in consumed
                  and not any(t in k for t in cls._IGNORABLE)]
        if unread:
            raise NotImplementedError(
                "AutoContainer fallback refuses this checkpoint: layer-0 "
                f"tensors outside the Llama layout would be dropped: {unread}")
        return super().build_params(sd, cfg)


def _looks_llama_shaped(hf_cfg) -> bool:
    return all(getattr(hf_cfg, f, None) is not None
               for f in ("hidden_size", "num_hidden_layers",
                         "num_attention_heads", "intermediate_size",
                         "rms_norm_eps"))


def resolve_container(hf_cfg) -> Type[LayerContainer]:
    arch = (getattr(hf_cfg, "architectures", None) or [type(hf_cfg).__name__])[0].lower()
    # prefix-match (HF arch strings start with the model type), longest key
    # first so "qwen2moe" wins over "qwen2"; substring matching would
    # capture e.g. RoBERTa under "bert"
    for key in sorted(ARCH_CONTAINERS, key=len, reverse=True):
        if arch.replace("_", "").startswith(key):
            return ARCH_CONTAINERS[key].specialize(hf_cfg)
    if _looks_llama_shaped(hf_cfg):
        from ....utils.logging import logger
        logger.warning(
            "no explicit container for architecture %r; attempting the "
            "AutoContainer Llama-layout fallback (reference AutoTP analog). "
            "Verify output parity before trusting it.", arch)
        return AutoContainer
    raise NotImplementedError(
        f"no v2 model implementation for architecture {arch!r}; "
        f"known: {sorted(ARCH_CONTAINERS)}")


def build_native(hf_model, dtype: str = None) -> Tuple[CausalLM, Dict]:
    """HF model instance → (native model, scan-ready param pytree).

    The container's ``model_class`` picks the native family (CausalLM for
    decoders, EncoderLM for BERT-style encoders)."""
    container = resolve_container(hf_model.config)
    cfg = container.config(hf_model.config)
    if dtype:
        cfg = cfg.replace(dtype=dtype)
    sd = hf_model.state_dict()
    params = container.build_params(sd, cfg)
    return container.model_class(cfg), params


def validate_layered_serving(engine_config, draft: bool = False) -> None:
    """Fail LOUDLY at engine build for what a model of mixed cache kinds
    (``kv_cache.cache_kinds``: windowed layers on rings of pages, global
    layers on whole tables) cannot be served with yet. Each moves or reads a
    sequence's pages by ONE block list, or rolls a step back across pages a
    ring has already reused (ROADMAP M2's remainder)."""
    c = engine_config
    probs = []
    if c.tp > 1:
        probs.append(f"tp={c.tp} (the pools of two kinds are not sharded)")
    if c.prefix_cache:
        probs.append("prefix_cache (a shared prefix's pages behind a "
                     "window are gone from the ring)")
    if c.kv_swap_dir or c.role != "unified":
        probs.append("the swap tier / prefill-decode handoff (kv_swap_dir, "
                     "role): a record holds one block list")
    if c.kv_dtype == "int8":
        probs.append("kv_dtype='int8' (the gather path has no ring of "
                     "packed rows)")
    if draft:
        probs.append("a draft model (a speculative rollback across a ring "
                     "is not defined)")
    if probs:
        raise NotImplementedError(
            "a model that mixes windowed and global layers keeps a cache a "
            "kind and cannot be served with: " + "; ".join(probs))


def validate_recurrent_serving(engine_config, cfg: TransformerConfig,
                               draft: bool = False) -> None:
    """Fail LOUDLY at engine build for what a model that keeps a state a
    slot cannot be served with yet, whatever its mixer kind
    (``cfg.recurrent_kinds``): a linear (Gated DeltaNet) layer keeps a
    recurrent state and a convolution tail, a conv (gated short
    convolution) layer a tail alone, and neither is a page: whatever
    moves, shares or rewinds a sequence by its block list alone would leave
    them behind (ROADMAP M4's remainder). An evicted or preempted sequence
    is not refused: it gives its pages back, is queued again with its
    prompt and the tokens it had emitted, and recomputes its state from
    them in a slot that admission zeroed."""
    c = engine_config
    kinds = " and ".join(cfg.recurrent_kinds)
    probs = []
    if c.tp > 1:
        probs.append(f"tp={c.tp} (the states are not sharded by head; no "
                     "exchange for a share of the experts)")
    if c.prefix_cache:
        probs.append("prefix_cache (a shared prefix's pages come without the "
                     f"{kinds} layers' state at its end)")
    if c.kv_swap_dir or c.role != "unified":
        probs.append("the swap tier / prefill-decode handoff (kv_swap_dir, "
                     "role): a record holds pages, no state")
    if c.kv_dtype == "int8":
        probs.append("kv_dtype='int8' (no packed form of the state; the "
                     "gather path is not walked by mixer kind)")
    if draft or cfg.num_nextn_predict_layers:
        probs.append("a draft, a second model's or the model's own "
                     "prediction module's (a rollback of a recurrent state "
                     "to the accepted prefix is not defined)")
    if c.nonfinite_policy == "repair":
        probs.append("nonfinite_policy='repair' (it rolls a row's step back "
                     "by its watermark; the state has moved)")
    if cfg.moe_is_share and cfg.moe_impl != "grouped":
        probs.append(f"moe_impl={cfg.moe_impl!r} with a router wider than "
                     "the experts held (only the dropless path knows which "
                     "experts it holds)")
    if probs:
        raise NotImplementedError(
            f"a model with {kinds} layers keeps a state a slot and "
            "cannot be served with: " + "; ".join(probs))


def validate_block_diffusion_serving(engine_config, cfg: TransformerConfig,
                                     draft: bool = False) -> None:
    """Fail LOUDLY at engine build for what a model that generates by
    diffusion over blocks (``cfg.block_length``) cannot be served with yet.
    A row past its prompt holds a half-denoised block (tokens and masked
    flags) on the frame programs' carry, its watermark moves a block at a
    time (by the step that commits the block and, in the same forward,
    begins to denoise the next), and what a denoising step writes past it
    is not a sequence's final K, V: whatever moves, shares or rewinds a
    sequence by pages and tokens alone would leave the block behind or
    read those rows. An evicted or preempted sequence is not refused: its
    committed blocks' tokens join its prompt, which then ends on a block's
    edge."""
    c = engine_config
    blk = cfg.block_length
    probs = []
    if c.tp > 1:
        probs.append(f"tp={c.tp} (the block step's logits at {blk} positions "
                     "a row are not exchanged; the carry's block is not "
                     "placed on a mesh)")
    if c.prefix_cache:
        probs.append("prefix_cache (a shared prefix must end on a block's "
                     "edge under the block mask, and its publisher's pages "
                     "past the watermark hold a denoising step's rows)")
    if c.kv_swap_dir or c.role != "unified":
        probs.append("the swap tier / prefill-decode handoff (kv_swap_dir, "
                     "role): a record holds pages, no block")
    if c.kv_dtype == "int8":
        probs.append("kv_dtype='int8' (the block's own keys ride beside the "
                     "pool unpacked; the packed path is not walked under the "
                     "block mask)")
    if draft or cfg.num_nextn_predict_layers:
        probs.append("a draft, a second model's or a prediction module's (a "
                     "step yields a block or nothing: there is no next token "
                     "to verify)")
    if c.nonfinite_policy == "repair":
        probs.append("nonfinite_policy='repair' (it rolls a row's step back "
                     "by its watermark; the block's flags have moved)")
    if c.prefill_chunk_size % blk:
        probs.append(f"prefill_chunk_size={c.prefill_chunk_size}, no multiple "
                     f"of block_length={blk} (a chunk must end on a block's "
                     "edge: a position sees its whole block)")
    elif c.prefill_chunk_size < 2 * blk:
        probs.append(f"prefill_chunk_size={c.prefill_chunk_size}, under two "
                     f"blocks of block_length={blk} (a row riding a chunk "
                     "forwards the block it commits and the next in one "
                     "step)")
    if blk & (blk - 1):
        probs.append(f"block_length={blk} (a power of two is what the pages, "
                     "the chunks and the narrow step's tiles divide by)")
    if cfg.sliding_window or cfg.position != "rope" \
            or cfg.recurrent_kinds or cfg.latent_lanes:
        probs.append("a window, ALiBi or learned positions, linear layers or "
                     "a latent cache (the block mask is walked for full "
                     "attention by head under RoPE alone)")
    if probs:
        raise NotImplementedError(
            "a model that generates by diffusion over blocks holds a "
            "half-denoised block a slot and cannot be served with: "
            + "; ".join(probs))


def validate_latent_serving(engine_config, cfg: TransformerConfig,
                            draft: bool = False) -> None:
    """Fail LOUDLY at engine build for what a model with a latent (MLA)
    cache, or with a share of its layer's experts, cannot be served with
    yet. The first group moves, shares or packs pages as a K pool and a V
    pool of equal shape; the second would need the experts' exchange
    (ROADMAP M1 / M3's remainder)."""
    c = engine_config
    probs = []
    if c.tp > 1:
        probs.append(f"tp={c.tp} (one latent head does not split by head; "
                     "no exchange for a share of the experts)")
    if c.kv_dtype == "int8":
        probs.append("kv_dtype='int8' (no packed latent row)")
    if c.prefix_cache:
        probs.append("prefix_cache (its page copies move two pools)")
    if c.kv_swap_dir or c.role != "unified":
        probs.append("the swap tier / prefill-decode handoff (kv_swap_dir, "
                     "role): a record holds a K and a V payload")
    if draft:
        probs.append("a draft model (the speculative loops carry four pools; "
                     "a model's own prediction module drafts into its own "
                     "pool: validate_self_draft)")
    if cfg.moe_is_share and cfg.moe_impl != "grouped":
        probs.append(f"moe_impl={cfg.moe_impl!r} with a router wider than "
                     "the experts held (only the dropless path knows which "
                     "experts it holds)")
    if probs:
        raise NotImplementedError(
            "a model with a latent cache cannot be served with: "
            + "; ".join(probs))


def validate_self_draft(engine_config, cfg: TransformerConfig) -> None:
    """Fail LOUDLY at engine build for a model whose prediction modules
    (``cfg.num_nextn_predict_layers``) the serving loops cannot draft
    with: they run ONE module (gamma 1), whose layer keeps its rows in one
    more layer of a latent pool."""
    probs = []
    if cfg.num_nextn_predict_layers != 1:
        probs.append(f"num_nextn_predict_layers="
                     f"{cfg.num_nextn_predict_layers} (one module drafts "
                     "one token a step; a chain of them is not written)")
    if not cfg.latent_lanes or cfg.shortcut_moe:
        probs.append("a cache other than one pool of latent rows a plain "
                     "layer (the module's layer is the stack's last kind "
                     "and shares its pool)")
    if engine_config.prefill_chunk_size < 2:
        probs.append("prefill_chunk_size < 2 (width-1 frames are the "
                     "draft / verify frames)")
    if probs:
        raise NotImplementedError(
            "this model's prediction module cannot draft for it with: "
            + "; ".join(probs))


def validate_tp_serving(cfg: TransformerConfig, tp: int,
                        role: str = "target") -> None:
    """Fail LOUDLY if this architecture cannot run tensor-parallel serving
    at degree ``tp`` (the shard_map frame loops, ``model_runner.py``).

    The manual TP layout shards attention heads, KV heads (and their paged
    KV pools), and the MLP intermediate dim; every one of those must divide
    by ``tp`` — a silent per-tensor replication fallback would break the
    per-layer psum arithmetic, so unlike training FSDP this is all-or-
    nothing. Vocab is the one axis allowed to fall back (replicated embed +
    LM head when ``vocab_size % tp != 0``): that costs memory, not
    correctness. Checked at engine construction AND draft attach — the
    draft rides the same mesh, so it must satisfy the same divisibility
    (``role`` names the offender in the error)."""
    if tp <= 1:
        return
    probs = []
    if cfg.is_moe:
        probs.append("MoE layers (expert parallelism is a different axis; "
                     "serve MoE models single-chip or add expert sharding)")
    if cfg.num_heads % tp:
        probs.append(f"num_heads={cfg.num_heads} not divisible by tp={tp}")
    if cfg.kv_heads % tp:
        probs.append(f"kv_heads={cfg.kv_heads} not divisible by tp={tp} "
                     "(the paged KV pools shard head-wise)")
    if cfg.ffn_size % tp:
        probs.append(f"ffn_size={cfg.ffn_size} not divisible by tp={tp}")
    if cfg.qk_norm in ("full", "per_head"):
        probs.append(f"qk_norm={cfg.qk_norm!r} norm weights span the head "
                     "dim that TP shards (use 'head_dim'-shared QK norms, "
                     "or serve single-chip)")
    if probs:
        raise NotImplementedError(
            f"tensor-parallel serving (tp={tp}) unsupported for the {role} "
            "model: " + "; ".join(probs))
