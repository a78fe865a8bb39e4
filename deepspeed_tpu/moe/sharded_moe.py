"""MoE gating and dispatch math.

TPU-native analog of ``deepspeed/moe/sharded_moe.py`` (top1gating ``:183``,
top2gating ``:290``, topkgating ``:374``, einsum dispatch/combine in
``MOELayer:96``). The reference dispatches tokens to expert-parallel ranks
with an explicit ``_AllToAll`` autograd op; here dispatch/combine are one-hot
einsums whose expert dim is sharded over the ``expert`` mesh axis, so XLA
lowers the same data movement to all-to-all over ICI.

All functions are capacity-based with static shapes (XLA requirement): each
expert processes exactly C = ceil(k*T/X * capacity_factor) token slots;
overflow tokens are dropped (their combine weight is 0), matching the
reference's ``drop_tokens=True`` default.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def _capacity(num_tokens: int, num_experts: int, k: int, capacity_factor: float,
              min_capacity: int = 4) -> int:
    cap = int(num_tokens * k / num_experts * capacity_factor)
    cap = max(cap, min_capacity)
    # round to MXU-friendly multiple
    return ((cap + 7) // 8) * 8


def load_balancing_loss(gates, mask):
    """GShard aux loss: num_experts * Σ_e (fraction_tokens_e * mean_gate_e).

    gates: (T, X) softmax router probs; mask: (T, X) 0/1 top-k assignment.
    """
    num_experts = gates.shape[1]
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask.astype(jnp.float32), axis=0)
    return num_experts * jnp.sum(me * ce)


def topk_gating_einsum(logits, k: int = 2, capacity_factor: float = 1.25,
                       min_capacity: int = 4, normalize: bool = True
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k gating producing einsum dispatch/combine tensors.

    logits: (T, X) raw router outputs (fp32).
    ``normalize``: renormalize the k chosen gates to sum to 1 (Mixtral/top2
    convention); False keeps raw softmax mass (Qwen2-MoE norm_topk_prob=False).
    Returns (combine (T, X, C) fp32, dispatch (T, X, C) bool, aux_loss scalar).
    """
    t, x = logits.shape
    c = _capacity(t, x, k, capacity_factor, min_capacity)
    gates = jax.nn.softmax(logits, axis=-1)  # (T, X)

    # top-k expert choice per token
    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # (T, k)
    if normalize:
        denom = jnp.sum(topk_vals, axis=-1, keepdims=True)
        topk_w = topk_vals / jnp.maximum(denom, 1e-9)
    else:
        topk_w = topk_vals

    # full assignment mask for aux loss
    mask_tx = jnp.sum(jax.nn.one_hot(topk_idx, x, dtype=jnp.float32), axis=1)  # (T, X)
    aux = load_balancing_loss(gates, mask_tx)

    # position of each (token, choice) within its expert's capacity buffer:
    # cumulative count over the flattened (choice-major, token) order, so
    # earlier tokens win slots — same priority rule as reference top2gating.
    onehot_kx = jax.nn.one_hot(topk_idx, x, dtype=jnp.int32)         # (T, k, X)
    flat = onehot_kx.transpose(1, 0, 2).reshape(k * t, x)            # (k*T, X)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat                  # (k*T, X)
    pos = jnp.sum(flat * pos_in_expert, axis=1).reshape(k, t).T      # (T, k)
    keep = pos < c                                                   # (T, k)

    w = topk_w * keep.astype(topk_w.dtype)                           # (T, k)
    # combine[t, x, c] = Σ_choice w[t,i] * [idx==x] * [pos==c]
    pos_oh = jax.nn.one_hot(pos, c, dtype=jnp.float32)               # (T, k, C)
    expert_oh = jax.nn.one_hot(topk_idx, x, dtype=jnp.float32)       # (T, k, X)
    combine = jnp.einsum("tk,tkx,tkc->txc", w.astype(jnp.float32), expert_oh, pos_oh)
    dispatch = combine > 0
    return combine, dispatch, aux


def topk_gating_grouped(logits, k: int = 2, normalize: bool = True,
                        bias=None, scale: float = 1.0,
                        score: str = "softmax", eps: float = 1e-20):
    """Top-k gating for the grouped (megablox-style) dropless path.
    ``bias`` (X,): the k experts are the largest of score + bias, their
    weights the scores alone (DeepSeek-V3's / LongCat's correction bias);
    ``scale`` multiplies the weights. ``score``: "softmax" over all the
    experts, or "sigmoid" each on its own (DeepSeek-V3's family, whose
    renormalised weights are s / (sum s + ``eps``): 1e-20 there, LFM2's
    1e-6).

    Returns (topk_idx (T, k) int32, weights (T, k) fp32 normalized over the
    k choices, aux_loss). No capacity buffers: every token reaches its
    experts (the reference's grouped MoE GEMM semantics,
    ``inference/v2/kernels/cutlass_ops/moe_gemm``).
    """
    x = logits.shape[1]
    sigmoid = score == "sigmoid"
    assert sigmoid or score == "softmax", score
    gates = jax.nn.sigmoid(logits) if sigmoid \
        else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        topk_vals, topk_idx = jax.lax.top_k(gates, k)
    else:
        _, topk_idx = jax.lax.top_k(gates + bias.astype(gates.dtype)[None], k)
        topk_vals = jnp.take_along_axis(gates, topk_idx, axis=-1)
    if normalize:
        denom = jnp.sum(topk_vals, axis=-1, keepdims=True)
        w = topk_vals / (denom + eps if sigmoid
                         else jnp.maximum(denom, 1e-9))
    else:
        w = topk_vals
    if scale != 1.0:
        w = w * scale
    mask_tx = jnp.sum(jax.nn.one_hot(topk_idx, x, dtype=jnp.float32), axis=1)
    aux = load_balancing_loss(gates, mask_tx)
    return topk_idx.astype(jnp.int32), w.astype(jnp.float32), aux


def top1_gating_einsum(logits, capacity_factor: float = 1.0, min_capacity: int = 4):
    """Switch-style top-1 gating (reference ``top1gating:183``)."""
    return topk_gating_einsum(logits, k=1, capacity_factor=capacity_factor,
                              min_capacity=min_capacity)
