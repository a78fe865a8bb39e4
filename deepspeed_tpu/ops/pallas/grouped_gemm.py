"""Grouped (expert) matmul for MoE.

Analog of ``inference/v2/kernels/cutlass_ops/moe_gemm`` (grouped GEMM over
per-expert token groups). On TPU the idiomatic primitive is
``jax.lax.ragged_dot`` (Megablox-style: rows grouped by expert, group sizes
ragged) which XLA lowers to MXU-tiled grouped matmul.
"""

import jax


def grouped_gemm(tokens, expert_weights, group_sizes):
    """tokens: (T, E) rows sorted by expert; expert_weights: (X, E, F);
    group_sizes: (X,) rows per expert. Returns (T, F)."""
    return jax.lax.ragged_dot(tokens, expert_weights, group_sizes)


def moe_expert_ffn(tokens, wi_gate, wi_up, wo, group_sizes):
    """SwiGLU expert FFN over grouped rows: (T, E) → (T, E)."""
    g = grouped_gemm(tokens, wi_gate, group_sizes)
    u = grouped_gemm(tokens, wi_up, group_sizes)
    h = jax.nn.silu(g) * u
    return grouped_gemm(h, wo, group_sizes)
