"""Grouped (expert) matmul for MoE.

Analog of ``inference/v2/kernels/cutlass_ops/moe_gemm`` (grouped GEMM over
per-expert token groups). On TPU the idiomatic primitive is
``jax.lax.ragged_dot`` (Megablox-style: rows grouped by expert, group sizes
ragged) which XLA lowers to MXU-tiled grouped matmul.
"""

import jax
import jax.numpy as jnp


def grouped_gemm(tokens, expert_weights, group_sizes, layer=None):
    """tokens: (T, E) rows sorted by expert; expert_weights: (X, E, F);
    group_sizes: (X,) rows per expert. Returns (T, F); rows past the groups
    are not written (zero on the CPU, whatever the buffer held on the
    chip). The weights are cast to the tokens' dtype.

    ``layer``: ``expert_weights`` is stacked over layers, (L, X, E, F), and
    this is the layer to multiply by. The stack goes to the product whole,
    as L * X experts of which only this layer's have rows. On the chip
    ``ragged_dot`` is a kernel, and a layer's slice handed to a kernel is a
    copy of that layer's experts (805 MB for OLMoE-1B-7B, every layer and
    step); an expert without rows costs the kernel nothing."""
    if layer is not None and expert_weights.dtype != tokens.dtype:
        # the cast is a copy of the layer's experts as it is
        expert_weights, layer = expert_weights[layer], None
    expert_weights = expert_weights.astype(tokens.dtype)
    if layer is not None:
        n_layers, n_exp = expert_weights.shape[:2]
        expert_weights = expert_weights.reshape(
            (n_layers * n_exp,) + expert_weights.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * n_exp,), group_sizes.dtype), group_sizes,
            (layer * n_exp,))
    return jax.lax.ragged_dot(tokens, expert_weights, group_sizes)


def moe_expert_ffn(tokens, wi_gate, wi_up, wo, group_sizes, layer=None):
    """SwiGLU expert FFN over grouped rows: (T, E) → (T, E). ``layer`` as
    in ``grouped_gemm``."""
    g = grouped_gemm(tokens, wi_gate, group_sizes, layer)
    u = grouped_gemm(tokens, wi_up, group_sizes, layer)
    h = jax.nn.silu(g) * u
    return grouped_gemm(h, wo, group_sizes, layer)
