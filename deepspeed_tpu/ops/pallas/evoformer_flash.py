"""Pallas Evoformer (DS4Science) bias-flash attention.

Analog of the reference CUTLASS kernel
(``csrc/deepspeed4science/evoformer_attn/attention.cu``): AlphaFold-style
attention over (B, N, S, H, D) MSA activations with a per-row mask bias
(B, N, 1, 1, S) and a pairwise triangle bias (B, 1, H, S, S) folded into the
logits IN-KERNEL — the (B, N, H, S, S) logits tensor never exists in HBM,
which is the entire point at MSA scale.

Design split (the sparse-flash precedent in this repo): the FORWARD is the
fused Pallas kernel (the serving-critical path and the memory headline);
the BACKWARD recomputes through the query-chunked XLA formulation
(``ops/evoformer.py``), whose peak is O(chunk · S) per (row, head) — same
numerics, bounded memory, no hand-written 5-tensor kernel backward. The
reference kernel's dB1/dB2 outputs fall out of the recompute's autodiff.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _evo_fwd_kernel(q_ref, k_ref, v_ref, b1_ref, b2_ref, o_ref, *,
                    has_b1, has_b2, block_k):
    q = q_ref[0, 0]                                     # (Bq, D), pre-scaled
    sk = k_ref.shape[2]
    num_kv = sk // block_k
    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_b1:
            b1 = b1_ref[0, 0, 0, pl.ds(j * block_k, block_k)]      # (Bk,)
            s = s + b1[None, :].astype(jnp.float32)
        if has_b2:
            # this q-block's (Bq, Bk) tile of the pair bias
            b2 = b2_ref[0, 0, :, pl.ds(j * block_k, block_k)]
            s = s + b2.astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kv, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)


def evoformer_flash_fwd(q, k, v, bias1, bias2, *, scale,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Fused forward. q/k/v: (B, N, H, S, D) head-major; bias1:
    (B, N, 1, 1, S) or None; bias2: (B, 1, H, S, S) or None.
    Returns (B, N, H, S, D) in q's dtype."""
    b, n, h, s, d = q.shape
    bn = b * n
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(bn, h, s, d)
    kf = k.reshape(bn, h, s, d)
    vf = v.reshape(bn, h, s, d)
    has_b1 = bias1 is not None
    has_b2 = bias2 is not None
    b1 = (bias1.reshape(bn, 1, 1, s) if has_b1
          else jnp.zeros((1, 1, 1, s), q.dtype))
    b2 = (bias2.reshape(b, h, s, s) if has_b2
          else jnp.zeros((1, 1, block_q, s), q.dtype))

    grid = (bn, h, s // block_q)
    out = pl.pallas_call(
        functools.partial(_evo_fwd_kernel, has_b1=has_b1, has_b2=has_b2,
                          block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, s),
                         (lambda bi, hi, qi: (bi, 0, 0, 0)) if has_b1
                         else (lambda bi, hi, qi: (0, 0, 0, 0))),
            pl.BlockSpec((1, 1, block_q, s),
                         (lambda bi, hi, qi: (bi // n, hi, qi, 0)) if has_b2
                         else (lambda bi, hi, qi: (0, 0, 0, 0))),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bn, h, s, d), q.dtype),
        name="evoformer_flash_fwd",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf, b1, b2)
    return out.reshape(b, n, h, s, d)


def evoformer_flash_supported(s, d, block_q=DEFAULT_BLOCK_Q,
                              block_k=DEFAULT_BLOCK_K) -> bool:
    """Mosaic alignment, not just divisibility: S must be lane-aligned (the
    bias blocks' last dim and the kv rows) — s % min(block, s) alone is
    vacuously true for any s <= block and would admit 70-row blocks."""
    if s % 128 != 0 or d not in (64, 128, 256):
        return False
    bq, bk = min(block_q, s), min(block_k, s)
    return s % bq == 0 and s % bk == 0
