"""Model configurations and preset registry.

The reference frameworks ships no model zoo for training (users bring
torch modules) but its benchmark configs name concrete architectures
(BASELINE.md acceptance configs: GPT-2-small, BERT-large, Llama-2-7B,
Mixtral-8x7B, Llama-2-70B). deepspeed_tpu ships a native functional
transformer covering those families; HF models are adapted via
``module_inject`` at inference time.
"""

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None → MHA
    head_dim: Optional[int] = None      # None → hidden_size // num_heads
    intermediate_size: Optional[int] = None  # None → 4x (gelu) / 8/3x rounded (swiglu)
    max_seq_len: int = 4096
    # "swiglu"/"geglu" are gated (silu / tanh-gelu gate); rest are plain MLPs
    activation: str = "swiglu"          # "swiglu" | "geglu" | "gelu" | "gelu_exact" | "relu"
    norm: str = "rmsnorm"               # "rmsnorm" | "layernorm"
    position: str = "rope"              # "rope" | "learned" | "alibi"
    position_offset: int = 0            # learned-position index offset (OPT: 2)
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0             # fraction of head_dim rotated (GPT-NeoX)
    rope_interleaved: bool = False      # GPT-NeoX/GPT-J (cos,sin per pair) layout
    # YaRN frequency scaling (arXiv 2309.00071; transformers'
    # ``_compute_yarn_parameters``): (factor, original_max_position,
    # beta_fast, beta_slow, attention_factor); attention_factor None ->
    # 0.1 ln(factor) + 1. It scales the layers of full attention: in a stack
    # that mixes them with windowed ones (``layer_windows()``; Mellum2's
    # ``rope_parameters`` by layer type) a windowed layer never sees a
    # distance past its window and keeps the plain frequencies
    rope_yarn: Optional[tuple] = None
    parallel_block: bool = False        # h + attn(ln1 h) + mlp(ln2 h) (NeoX/Falcon)
    norm_eps: float = 1e-5
    embedding_norm: bool = False        # layernorm right after token embed (BLOOM/BERT)
    embed_scale: float = 1.0            # token-embedding multiplier (Gemma: sqrt(E))
    post_norm: bool = False             # norm AFTER residual add (BERT) vs pre-LN
    type_vocab_size: int = 0            # token-type (segment) embeddings (BERT)
    mlm_head: bool = False              # BERT MLM head: dense+gelu+LN+decoder bias
    tie_embeddings: bool = False
    lm_head_bias: bool = False          # biased untied LM head (GPT-J, Phi)
    use_bias: bool = False
    qkv_bias: bool = False              # bias on q/k/v only (Qwen2)
    mlp_bias: Optional[bool] = None     # None → use_bias (GPT-J: mlp-only biases)
    out_bias: Optional[bool] = None     # attention out-proj bias override (GPT-Neo)
    causal: bool = True
    # sliding-window attention: query attends keys in (q-window, q] (Mistral).
    # local_attention_every=N makes every Nth layer (1-indexed remainder 0...
    # i.e. layers with index % N == N-1) windowed and the rest global
    # (GPT-Neo alternates global/local); None with sliding_window set means
    # ALL layers are windowed.
    sliding_window: Optional[int] = None
    local_attention_every: Optional[int] = None
    # explicit per-layer window sizes (len == num_layers, 0 = global) for
    # patterns local_attention_every can't express (Gemma-2 windows the
    # EVEN-indexed layers). Takes precedence over local_attention_every. A
    # pattern shorter than the stack is one period of it (Mellum2: three
    # windowed layers, then a global one), so a cut in depth keeps it.
    window_pattern: Optional[tuple] = None
    # q/k normalization before rope (HF refs: MPT attn_config.qk_ln,
    # StableLM qk_layernorm, Phi qk_layernorm):
    #   "full":     one norm over the flattened (H*D) q / (KVH*D) k vectors
    #   "head_dim": one (D,) norm shared by all heads
    #   "per_head": separate (H, D) weights per head
    # The norm family follows cfg.norm (all current variants: layernorm).
    qk_norm: Optional[str] = None
    qk_norm_bias: bool = True           # StableLM's per-head LNs are bias-free
    # Gemma-2 block structure: extra norms on each sublayer OUTPUT before
    # the residual add (norm1=input, norm3=post-attn, norm2=pre-ffw,
    # norm4=post-ffw)
    sandwich_norm: bool = False
    attn_softcap: float = 0.0           # tanh softcap on attention logits (Gemma-2)
    logit_softcap: float = 0.0          # tanh softcap on final LM logits (Gemma-2)
    attn_scale: Optional[float] = None  # override 1/sqrt(head_dim) (Gemma-2
                                        # query_pre_attn_scalar ** -0.5)
    # per-layer structure tags for heterogeneous stacks ("dense" | "moe";
    # len == num_layers). None = homogeneous (every layer is MoE iff
    # num_experts > 0). Qwen2-MoE's mlp_only_layers / decoder_sparse_step
    # interleave dense-MLP layers into a routed-expert stack.
    layer_types: Optional[tuple] = None
    # leading dense layers of a routed stack (DeepSeek-V3's
    # first_k_dense_replace): layers [0, k) are "dense", the rest "moe".
    # A RULE where ``layer_types`` is a list: it holds at any depth, so a
    # preset survives a cut of num_layers alone. The two exclude each other
    # (``layer_tags`` refuses a config that sets both)
    moe_first_dense: int = 0
    # MoE (Mixtral-style; 0 experts → dense)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    moe_norm_topk: bool = True          # renormalize top-k gates (Mixtral yes, Qwen2-MoE no)
    moe_shared_expert_size: int = 0     # always-on shared expert width (Qwen2-MoE)
    moe_shared_expert_gate: bool = True  # its sigmoid gate (Qwen2-MoE yes, DeepSeek-V3's family no)
    # what the router's logits become before the top-k: "softmax" over all
    # experts, or "sigmoid" each on its own (DeepSeek-V3's family)
    moe_router_score: str = "softmax"
    # "einsum": capacity-bounded one-hot dispatch (GShard/EP all-to-all);
    # "grouped": dropless sort-by-expert + ragged_dot (megablox pattern,
    # expert axis unsharded only)
    moe_impl: str = "einsum"
    # routed-expert FFN width when it differs from the dense-MLP width
    # (Qwen2-MoE: moe_intermediate_size vs intermediate_size); None → ffn_size
    moe_intermediate_size: Optional[int] = None
    # routed experts beyond the ones held (LongCat-Flash): the router scores
    # ``moe_router_experts`` experts with weights (None: ``num_experts``,
    # every expert held) and ``moe_zero_experts`` without (identity: the
    # token itself, times its weight). ``num_experts`` stays the experts
    # whose weights THIS model holds, ``moe_expert_first`` and on of the
    # published ones: one chip's share of an expert-parallel layer. What the absent experts
    # would have added is left out, no code stands in for the exchange.
    moe_router_experts: Optional[int] = None
    moe_expert_first: int = 0
    moe_zero_experts: int = 0
    moe_router_bias: bool = False       # choose by score + bias, weigh by score
    # added to the sum the chosen sigmoid scores are renormalised by
    # (DeepSeek-V3's family: 1e-20; LFM2: 1e-6)
    moe_norm_eps: float = 1e-20
    moe_routed_scale: float = 1.0       # routed_scaling_factor on the weights
    # latent attention (MLA, DeepSeek-V2; LongCat-Flash's layout): a
    # low-rank query (q_lora_rank), ONE cached row a token and layer of
    # kv_lora_rank values and a shared RoPE key of qk_rope_head_dim; heads
    # of qk_nope_head_dim + qk_rope_head_dim for scores and v_head_dim for
    # values. 0: the attention every other model has
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False      # q x sqrt(hidden / q_lora_rank)
    mla_scale_kv_lora: bool = False     # latent x sqrt(hidden / kv_lora_rank)
    # shortcut-connected double layer (LongCat-Flash): a layer is two
    # attentions and two dense MLPs, and ONE routed block that reads the
    # stream after the first attention and joins at the layer's end
    shortcut_moe: bool = False
    # multi-token prediction (DeepSeek-V3's MTP; GLM-4.7-Flash has one):
    # modules beside the stack, each two norms, a projection 2E -> E, one
    # layer of the stack's last kind with its own attention and a norm
    # before the model's own head; module k predicts the token k + 2 ahead
    # from the stack's hidden state and the next token's embedding. Served
    # as the model's own draft (inference/v2); 0: none
    num_nextn_predict_layers: int = 0
    # token mixers by layer, ONE period of the pattern ("linear": a Gated
    # DeltaNet layer, arXiv:2412.06464; "conv": a gated short convolution,
    # LFM2's; "full": softmax attention), as ``window_pattern``: a cut of
    # num_layers alone keeps it (Qwen3-Next: three linear layers, then a
    # full one). A pattern as long as the stack is one period (LFM2's
    # published ``layer_types`` has none that tiles a cut), and only such a
    # stack may begin with ``moe_first_dense`` dense layers. None: every
    # layer attends. Neither a linear nor a conv layer keeps keys or
    # values. A linear layer keeps per sequence a state of
    # linear_num_value_heads x linear_key_head_dim x linear_value_head_dim
    # and the last linear_conv_kernel - 1 inputs of its causal depthwise
    # convolution; a conv layer the last conv_kernel - 1 rows of its
    # convolution's input (hidden_size channels) and nothing else. Served
    # on the paged path (inference/v2) only
    mixer_pattern: Optional[tuple] = None
    conv_kernel: int = 3                # taps of a conv layer (conv_L_cache)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    # q_proj is doubled: a head's query and, beside it, the gate whose
    # sigmoid multiplies the attention's output elementwise before o_proj
    attn_output_gate: bool = False
    # RMSNorm multiplies by 1 + w (w stored, drawn around 0), q/k norms too
    norm_unit_offset: bool = False
    # generation by diffusion over blocks (SDAR, arXiv:2510.06303): the
    # sequence is cut into blocks of ``block_length`` positions; a position
    # sees every key up to the END of its own block, the logits at a
    # position score the token AT it (no shift), and a block is generated
    # by ``denoising_steps`` forwards that each unmask block_length /
    # denoising_steps of its masked positions (those of largest confidence;
    # under "low_confidence_dynamic" every one whose confidence passes
    # ``confidence_threshold`` where those are more); the mask-free
    # block's K, V are kept from one forward more of it, which the paged
    # path fuses with the next block's first denoising step (2 x
    # block_length positions in one forward: ``denoising_steps`` forwards a
    # block, and one more for a request's last). 0: left to right, a token
    # a forward. Served on the paged path (inference/v2) only
    block_length: int = 0
    denoising_steps: int = 0            # 0 -> block_length (one a step)
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 0
    # numerics
    dtype: str = "bfloat16"             # activation dtype
    param_dtype: str = "float32"        # stored parameter dtype
    # attention implementation: "auto" | "reference" | "flash" | "ring"
    attn_impl: str = "auto"
    # remat policy for scan-over-layers ("none"|"full"|"dots")
    remat: str = "none"
    # partition saved activations: checkpoint-boundary residuals stored with
    # their SEQUENCE dim sharded over the tensor axis, gathered on use
    # (reference partition_activations, checkpointing.py:486)
    partition_activations: bool = False
    # QAT activation fake-quant bits (compression QuantAct analog): each
    # layer's attention/MLP inputs round-trip an int grid with an STE
    # backward; 0 disables
    act_quant_bits: int = 0
    # vocab-chunked fused cross-entropy (ops/cross_entropy.py): number of
    # lm-head chunks; 0 disables. Engaged when the (B, S, V) logits would
    # exceed loss_chunk_threshold_bytes — the fused path trades one extra
    # lm-head matmul for never materializing the logits.
    loss_chunks: int = 8
    loss_chunk_threshold_bytes: int = 1 << 30

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dims_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        if self.activation in ("swiglu", "geglu"):
            return ((int(self.hidden_size * 8 / 3) + 255) // 256) * 256
        return 4 * self.hidden_size

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def p_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def moe_ffn_size(self) -> int:
        return self.moe_intermediate_size or self.ffn_size

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def moe_router_width(self) -> int:
        """Outputs of the router: the published experts with weights, held
        here or not, and the zero experts behind them."""
        return (self.moe_router_experts or self.num_experts) \
            + self.moe_zero_experts

    @property
    def moe_is_share(self) -> bool:
        """The router scores more than the experts held: some selections
        land on zero experts or on experts of other chips."""
        return self.moe_router_width != self.num_experts

    @property
    def latent_lanes(self) -> int:
        """Lanes of a latent cache row: the latent and the RoPE key, padded
        so both parts sit on lane tiles (512 + 64 -> 640); 0 for a model
        that caches K and V by head."""
        if not self.kv_lora_rank:
            return 0
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def attn_layers(self) -> int:
        """Attention layers, each with its cache layer: two a layer in a
        shortcut-connected stack."""
        return self.num_layers * (2 if self.shortcut_moe else 1)

    @property
    def cache_layers(self) -> int:
        """Layers of the serving cache: the stack's attention layers (a
        linear or a conv layer caches no keys) and, behind them, one for each
        prediction module's."""
        return (self.attn_layers - self.linear_layers - self.conv_layers
                + self.num_nextn_predict_layers)

    @property
    def layer_tags(self) -> Optional[tuple]:
        """Per-layer structure tags, or None for a stack whose layers are
        alike: ``layer_types``, or ``moe_first_dense`` dense layers ahead
        of the routed ones; never both."""
        if self.layer_types is not None:
            assert not self.moe_first_dense, \
                "layer_types and moe_first_dense both state the layout"
            return self.layer_types
        if self.is_moe and 0 < self.moe_first_dense < self.num_layers:
            return ("dense",) * self.moe_first_dense + ("moe",) * (
                self.num_layers - self.moe_first_dense)
        return None

    def layer_windows(self) -> Optional[tuple]:
        """Per-layer window sizes (0 = global) of a stack that mixes
        windowed and global layers, or None where the layers are alike (a
        uniform window is ``sliding_window`` alone)."""
        if self.window_pattern is not None:
            p = tuple(int(w) for w in self.window_pattern)
            if self.num_layers % len(p):
                raise ValueError(
                    f"window_pattern of {len(p)} layers does not tile "
                    f"num_layers={self.num_layers}")
            return p * (self.num_layers // len(p))
        if self.sliding_window is None or not self.local_attention_every:
            return None
        n = self.local_attention_every
        return tuple(self.sliding_window if i % n == n - 1 else 0
                     for i in range(self.num_layers))

    def layer_mixers(self) -> Optional[tuple]:
        """Per-layer mixer kinds of a stack that mixes linear or conv
        layers with full attention layers, or None where every layer
        attends."""
        if self.mixer_pattern is None:
            return None
        p = tuple(self.mixer_pattern)
        assert set(p) <= {"linear", "conv", "full"}, p
        if self.num_layers % len(p):
            raise ValueError(
                f"mixer_pattern of {len(p)} layers does not tile "
                f"num_layers={self.num_layers}")
        return p * (self.num_layers // len(p))

    @property
    def linear_layers(self) -> int:
        """Layers whose mixer is linear: each holds a recurrent state and
        a convolution tail a sequence, and no cache layer."""
        return (self.layer_mixers() or ()).count("linear")

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is a gated short convolution: each holds a
        convolution tail a sequence and nothing else, and no cache layer."""
        return (self.layer_mixers() or ()).count("conv")

    @property
    def recurrent_kinds(self) -> tuple:
        """The mixer kinds of this stack that keep a state a sequence, in
        the order their arrays ride the serving carry
        (``PagedModelRunner.recurrent_shapes``); () where every layer
        attends."""
        return tuple(kind for kind in ("linear", "conv")
                     if kind in (self.layer_mixers() or ()))

    @property
    def linear_channels(self) -> int:
        """Channels of a linear layer's convolution: q, k and v side by
        side (Qwen3-Next: 2,048 + 2,048 + 4,096)."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def unmask_per_step(self) -> int:
        """Positions a denoising step of a block unmasks at least: the
        block's length over the steps (0 for a left-to-right model)."""
        if not self.block_length:
            return 0
        steps = self.denoising_steps or self.block_length
        if self.block_length % steps:
            raise ValueError(
                f"denoising_steps={steps} does not divide "
                f"block_length={self.block_length}")
        return self.block_length // steps

    def layer_type(self, i: int) -> str:
        tags = self.layer_tags
        if tags is not None:
            return tags[i]
        return "moe" if self.is_moe else "dense"

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


# ---- preset registry (sizes from the public model cards) ----

PRESETS = {
    # GPT-2 family (learned positions, gelu, layernorm, tied embeddings, biases)
    "gpt2-small": TransformerConfig(vocab_size=50257, hidden_size=768, num_layers=12, num_heads=12,
                                    max_seq_len=1024, activation="gelu", norm="layernorm", position="learned",
                                    tie_embeddings=True, use_bias=True),
    "gpt2-medium": TransformerConfig(vocab_size=50257, hidden_size=1024, num_layers=24, num_heads=16,
                                     max_seq_len=1024, activation="gelu", norm="layernorm", position="learned",
                                     tie_embeddings=True, use_bias=True),
    "gpt2-xl": TransformerConfig(vocab_size=50257, hidden_size=1600, num_layers=48, num_heads=25,
                                 max_seq_len=1024, activation="gelu", norm="layernorm", position="learned",
                                 tie_embeddings=True, use_bias=True),
    # Llama-2 family
    "llama2-7b": TransformerConfig(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
                                   intermediate_size=11008, max_seq_len=4096),
    "llama2-13b": TransformerConfig(vocab_size=32000, hidden_size=5120, num_layers=40, num_heads=40,
                                    intermediate_size=13824, max_seq_len=4096),
    "llama2-70b": TransformerConfig(vocab_size=32000, hidden_size=8192, num_layers=80, num_heads=64,
                                    num_kv_heads=8, intermediate_size=28672, max_seq_len=4096),
    "llama3-8b": TransformerConfig(vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32,
                                   num_kv_heads=8, intermediate_size=14336, max_seq_len=8192,
                                   rope_theta=500000.0),
    # Mixtral MoE
    "mixtral-8x7b": TransformerConfig(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
                                      num_kv_heads=8, intermediate_size=14336, max_seq_len=32768,
                                      rope_theta=1e6, num_experts=8, num_experts_per_tok=2),
    # BLOOM family (ALiBi positions, embedding layernorm, gelu, biases)
    "bloom-560m": TransformerConfig(vocab_size=250880, hidden_size=1024, num_layers=24, num_heads=16,
                                    max_seq_len=2048, activation="gelu", norm="layernorm",
                                    position="alibi", embedding_norm=True, tie_embeddings=True,
                                    use_bias=True),
    "bloom-7b1": TransformerConfig(vocab_size=250880, hidden_size=4096, num_layers=30, num_heads=32,
                                   max_seq_len=2048, activation="gelu", norm="layernorm",
                                   position="alibi", embedding_norm=True, tie_embeddings=True,
                                   use_bias=True),
    # Falcon-7B (multi-query attention, parallel block, one shared norm)
    "falcon-7b": TransformerConfig(vocab_size=65024, hidden_size=4544, num_layers=32, num_heads=71,
                                   num_kv_heads=1, intermediate_size=18176, max_seq_len=2048,
                                   activation="gelu_exact", norm="layernorm", parallel_block=True,
                                   tie_embeddings=True),
    # GPT-J-6B (interleaved partial rotary, parallel block, MLP-only biases)
    "gptj-6b": TransformerConfig(vocab_size=50400, hidden_size=4096, num_layers=28, num_heads=16,
                                 intermediate_size=16384, max_seq_len=2048, activation="gelu",
                                 norm="layernorm", rotary_pct=64 / 256, rope_interleaved=True,
                                 parallel_block=True, mlp_bias=True),
    # GPT-NeoX-20B / Pythia family (partial rotary, parallel residual)
    "gpt-neox-20b": TransformerConfig(vocab_size=50432, hidden_size=6144, num_layers=44, num_heads=64,
                                      intermediate_size=24576, max_seq_len=2048,
                                      activation="gelu_exact", norm="layernorm", rotary_pct=0.25,
                                      parallel_block=True, use_bias=True),
    # MPT-7B (ALiBi, bias-free, exact gelu)
    "mpt-7b": TransformerConfig(vocab_size=50368, hidden_size=4096, num_layers=32, num_heads=32,
                                intermediate_size=16384, max_seq_len=2048, activation="gelu_exact",
                                norm="layernorm", position="alibi", tie_embeddings=True),
    # Gemma-7B (GeGLU, sqrt(E)-scaled embeddings, wide head_dim)
    "gemma-7b": TransformerConfig(vocab_size=256000, hidden_size=3072, num_layers=28, num_heads=16,
                                  head_dim=256, intermediate_size=24576, max_seq_len=8192,
                                  activation="geglu", embed_scale=3072.0 ** 0.5,
                                  tie_embeddings=True, norm_eps=1e-6),
    # Qwen2-7B (GQA + qkv biases)
    "qwen2-7b": TransformerConfig(vocab_size=152064, hidden_size=3584, num_layers=28, num_heads=28,
                                  num_kv_heads=4, intermediate_size=18944, max_seq_len=32768,
                                  rope_theta=1e6, qkv_bias=True, norm_eps=1e-6),
    # Phi-2 (parallel block sharing one layernorm, partial rotary, biases)
    "phi-2": TransformerConfig(vocab_size=51200, hidden_size=2560, num_layers=32, num_heads=32,
                               intermediate_size=10240, max_seq_len=2048, activation="gelu",
                               norm="layernorm", position="rope", rotary_pct=0.4,
                               parallel_block=True, use_bias=True),
    # Mistral-7B (GQA + sliding-window attention)
    "mistral-7b": TransformerConfig(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
                                    num_kv_heads=8, intermediate_size=14336, max_seq_len=32768,
                                    sliding_window=4096),
    # OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): 64 routed
    # experts of width 1024, 8 a token, softmax over all 64 then top-8 with
    # the weights NOT renormalized, no shared expert; one RMSNorm over the
    # whole q / k projection before the head split and RoPE. Dropless
    # routing: a served token that lost its expert is a wrong answer
    "olmoe-1b-7b": TransformerConfig(vocab_size=50304, hidden_size=2048, num_layers=16, num_heads=16,
                                     num_kv_heads=16, intermediate_size=1024, max_seq_len=4096,
                                     num_experts=64, num_experts_per_tok=8, moe_norm_topk=False,
                                     moe_impl="grouped", qk_norm="full", qk_norm_bias=False),
    # Mellum2-12B-A2.5B (JetBrains/Mellum2-12B-A2.5B-Instruct config.json):
    # GQA 32/4 with head_dim 128 beside hidden 2304 (H x D = 4096); layers
    # in periods of three sliding-window (1,024) and one full-attention
    # layer; RoPE theta 5e5 on both kinds, YaRN (16 x 8,192) on the full
    # layers only; every layer routes to 8 of 64 experts of width 896,
    # softmax over all 64, the top-8 weights renormalised, no shared expert
    # (the published dense intermediate_size 7168 is used by no layer);
    # untied head. Dropless routing, as OLMoE
    "mellum2-12b-a2.5b": TransformerConfig(
        vocab_size=98304, hidden_size=2304, num_layers=28, num_heads=32, num_kv_heads=4,
        head_dim=128, intermediate_size=7168, moe_intermediate_size=896, max_seq_len=131072,
        rope_theta=500000.0, rope_yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
        sliding_window=1024, window_pattern=(1024, 1024, 1024, 0),
        norm_eps=1e-6, num_experts=64, num_experts_per_tok=8, moe_norm_topk=True,
        moe_impl="grouped"),
    # LongCat-Flash-Omni's language model (meituan-longcat/LongCat-Flash-Omni
    # config.json): 28 shortcut-connected double layers; MLA with ranks
    # 1536 / 512, 64 heads of 128 + 64 (scores) and 128 (values), both
    # low-rank paths rescaled; dense FFNs 12288 wide; a router over 512
    # experts of width 2048 and 256 identity experts, top 12 chosen by score
    # + bias, weighed by the score x 6, not renormalised; untied head.
    # ``num_experts`` is what a chip holds: the whole 512 here, 16 of them
    # (``--set num_experts=16 moe_router_experts=512``) on one chip of 32
    "longcat-flash-omni": TransformerConfig(
        vocab_size=131072, hidden_size=6144, num_layers=28, num_heads=64,
        intermediate_size=12288, moe_intermediate_size=2048, max_seq_len=131072,
        rope_theta=1e7, rope_interleaved=True, norm_eps=1e-5,
        num_experts=512, moe_zero_experts=256, num_experts_per_tok=12,
        moe_norm_topk=False, moe_router_bias=True, moe_routed_scale=6.0,
        moe_impl="grouped", kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, shortcut_moe=True),
    # GLM-4.7-Flash (zai-org/GLM-4.7-Flash config.json, glm4_moe_lite:
    # DeepSeek-V3's layer): MLA with ranks 768 / 512, 20 heads of 192 + 64
    # (scores) and 256 (values), no rescaling; one leading dense layer
    # 10240 wide, then 46 layers of 64 experts of width 1536 beside one
    # ungated shared expert: sigmoid scores, the top 4 of score + bias,
    # weights the scores renormalised x 1.8; one prediction module, served
    # as the model's own draft; untied head
    "glm-4.7-flash": TransformerConfig(
        vocab_size=154880, hidden_size=2048, num_layers=47, num_heads=20,
        intermediate_size=10240, moe_intermediate_size=1536, max_seq_len=202752,
        rope_theta=1e6, rope_interleaved=True, norm_eps=1e-5,
        num_experts=64, num_experts_per_tok=4, moe_first_dense=1,
        moe_norm_topk=True, moe_router_bias=True, moe_routed_scale=1.8,
        moe_router_score="sigmoid", moe_shared_expert_size=1536,
        moe_shared_expert_gate=False, moe_impl="grouped",
        kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, num_nextn_predict_layers=1),
    # Qwen3-Next-80B-A3B-Instruct (Qwen/Qwen3-Next-80B-A3B-Instruct
    # config.json, qwen3_next): periods of three Gated DeltaNet layers (16
    # key / 32 value heads of 128, convolution of 4 taps) and one of gated
    # softmax attention (16 / 2 heads of 256, a quarter rotary, one RMSNorm
    # a head on q and k); every layer routes to 10 of 512 experts of width
    # 512, softmax over all, the ten renormalised, beside a shared expert
    # of 512 under its sigmoid gate; norms 1 + w; untied head. The
    # checkpoint's prediction module is not among the config's keys and is
    # not served. ``--set num_experts=128 moe_router_experts=512`` is one
    # chip of 4 that share each layer
    "qwen3-next-80b-a3b": TransformerConfig(
        vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=16, num_kv_heads=2,
        head_dim=256, intermediate_size=5120, moe_intermediate_size=512, max_seq_len=262144,
        rope_theta=1e7, rotary_pct=0.25, norm_eps=1e-6, qk_norm="head_dim",
        norm_unit_offset=True, attn_output_gate=True,
        mixer_pattern=("linear", "linear", "linear", "full"),
        linear_num_key_heads=16, linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel=4,
        num_experts=512, num_experts_per_tok=10, moe_norm_topk=True,
        moe_shared_expert_size=512, moe_shared_expert_gate=True, moe_impl="grouped"),
    # SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat config.json, sdar_moe):
    # Qwen3-MoE's layer (GQA 32/4 x 128, one RMSNorm of 128 lanes a q and k
    # head before RoPE; every layer routes to 8 of 128 experts of width
    # 768, softmax over all, the eight renormalised, no shared expert; the
    # published dense intermediate_size 6144 is used by no layer), untied
    # head; generated by diffusion over blocks: config.json names neither
    # the block length nor the schedule, these are the family's released
    # defaults (blocks of 4, 4 steps, low_confidence_dynamic at 0.9, mask
    # token 151669)
    "sdar-30b-a3b": TransformerConfig(
        vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, intermediate_size=6144, moe_intermediate_size=768, max_seq_len=32768,
        rope_theta=1e6, norm_eps=1e-6, qk_norm="head_dim", qk_norm_bias=False,
        num_experts=128, num_experts_per_tok=8, moe_norm_topk=True, moe_impl="grouped",
        block_length=4, denoising_steps=4, remasking_strategy="low_confidence_dynamic",
        confidence_threshold=0.9, mask_token_id=151669),
    # LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B config.json, lfm2_moe): three
    # gated short convolutions (3 taps, no bias, no activation) to one layer
    # of softmax attention (GQA 32 / 8 heads of 64, one RMSNorm of 64 lanes
    # a q and k head before RoPE); two leading dense layers 11,776 wide,
    # then 64 experts of width 1,536: sigmoid scores, the top 4 of score +
    # expert_bias, weights the scores over (their sum + 1e-6), scale 1, no
    # shared expert; plain RMSNorm weights; the head tied to the embedding
    # (the family's checkpoints; the key is absent). ``layer_types`` as
    # published: a cut names its own (a pattern as long as the stack)
    "lfm2-24b-a2b": TransformerConfig(
        vocab_size=65536, hidden_size=2048, num_layers=40, num_heads=32, num_kv_heads=8,
        intermediate_size=11776, moe_intermediate_size=1536, max_seq_len=128000,
        rope_theta=1e6, norm_eps=1e-5, qk_norm="head_dim", qk_norm_bias=False,
        mixer_pattern=("conv", "conv", "full", "conv") * 10, conv_kernel=3,
        num_experts=64, num_experts_per_tok=4, moe_first_dense=2, moe_norm_topk=True,
        moe_router_bias=True, moe_routed_scale=1.0, moe_router_score="sigmoid",
        moe_norm_eps=1e-6, moe_impl="grouped", tie_embeddings=True),
    # BERT family (post-norm encoder, MLM head; acceptance config 2 trains
    # bert-large under ZeRO-1/2)
    "bert-base": TransformerConfig(vocab_size=30522, hidden_size=768, num_layers=12, num_heads=12,
                                   intermediate_size=3072, max_seq_len=512, type_vocab_size=2,
                                   activation="gelu_exact", norm="layernorm", position="learned",
                                   post_norm=True, causal=False, embedding_norm=True,
                                   mlm_head=True, use_bias=True, tie_embeddings=True,
                                   norm_eps=1e-12),
    "bert-large": TransformerConfig(vocab_size=30522, hidden_size=1024, num_layers=24, num_heads=16,
                                    intermediate_size=4096, max_seq_len=512, type_vocab_size=2,
                                    activation="gelu_exact", norm="layernorm", position="learned",
                                    post_norm=True, causal=False, embedding_norm=True,
                                    mlm_head=True, use_bias=True, tie_embeddings=True,
                                    norm_eps=1e-12),
    # tiny variants for tests / CI
    "tiny": TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                              intermediate_size=128, max_seq_len=128, param_dtype="float32",
                              dtype="float32"),
    "tiny-gpt2": TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                                   intermediate_size=256, max_seq_len=128, activation="gelu",
                                   norm="layernorm", position="learned", tie_embeddings=True,
                                   use_bias=True, dtype="float32"),
    "tiny-moe": TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                                  intermediate_size=128, max_seq_len=128, num_experts=4,
                                  num_experts_per_tok=2, dtype="float32"),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    if name not in PRESETS:
        raise KeyError(f"Unknown model preset {name!r}; available: {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return cfg.replace(**overrides) if overrides else cfg
