"""Pallas fused decode attention over a contiguous KV cache.

Analog of the reference's v1 fused decode kernel (``softmax_context`` in
``csrc/transformer/inference/csrc/`` — KV-cache attention for the
kernel-injection engine): one query token per sequence attends over its
(B, S_max, KVH, D) cache slice with online softmax in VMEM — the
(B, H, S_max) logits tensor the XLA path materializes never exists.

Structure matches ``paged_attention.py`` with the block table replaced by
contiguous block indexing; GQA runs each kv head's query group as rows of
one (G, D) tile. Grid = (batch, kv_head, cache_block); m/l/acc scratch
carried across the block dimension.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK = 512


def _decode_kernel(len_ref,                    # scalar prefetch
                   q_ref, k_ref, v_ref,        # blocks
                   o_ref,
                   m_ref, l_ref, acc_ref,
                   *, block, n_blocks, scale):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = len_ref[b]

    @pl.when(j * block < seq_len)
    def _block():
        q = q_ref[0, 0]                                   # (G, D)
        k = k_ref[0, 0]                                   # (block, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        slot = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def fused_decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                           block=DEFAULT_BLOCK):
    """q: (B, H, D) single decode token per sequence; k_cache/v_cache:
    (B, S_max, KVH, D); cache_len: (B,) valid entries (including the one
    just written). Returns (B, H, D)."""
    b, h, d = q.shape
    s_max, kvh = k_cache.shape[1], k_cache.shape[2]
    block = min(block, s_max)
    if s_max % block:
        raise ValueError(f"S_max={s_max} not divisible by block={block}")
    n_blocks = s_max // block
    group = h // kvh
    scale = float(scale if scale is not None else d ** -0.5)

    qg = q.reshape(b, kvh, group, d)
    # (B, S, KVH, D) → (B, KVH, S, D) so the kernel reads (block, D) tiles
    km = k_cache.swapaxes(1, 2)
    vm = v_cache.swapaxes(1, 2)

    def q_map(bi, hi, ji, lens):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, ji, lens):
        return (bi, hi, ji, 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, n_blocks=n_blocks,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, group, d), q_map),
                pl.BlockSpec((1, 1, block, d), kv_map),
                pl.BlockSpec((1, 1, block, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
        name="decode_attn",
        interpret=jax.default_backend() != "tpu",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(cache_len.astype(jnp.int32), qg, km, vm)
    return out.reshape(b, h, d)
