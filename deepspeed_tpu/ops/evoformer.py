"""Evoformer (DS4Science) attention.

Analog of ``deepspeed/ops/deepspeed4science/evoformer_attn.py``
(``DS4Sci_EvoformerAttention:88``, CUTLASS kernels under
``csrc/deepspeed4science/evoformer_attn``): attention over AlphaFold-style
5-D activations (batch, rows, seq, heads, dim) with up to two additive
biases — a per-row mask bias (B, N, 1, 1, S) and a pairwise triangle bias
(B, 1, H, S, S).

TPU mapping: the reference needs a custom kernel because a materialized
(B, N, H, S, S) logits tensor blows past HBM at MSA scale; here the query
dimension is processed in ``lax.scan`` chunks so peak memory is
O(chunk · S) per (row, head) while XLA fuses the bias adds and softmax into
the chunk matmuls. Fully differentiable (scan autodiff); numerics are fp32
softmax like the reference kernel.
"""

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .attention import _use_pallas


def _bias_shapes(q):
    b, n, s = q.shape[0], q.shape[1], q.shape[2]
    h = q.shape[3]
    return (b, n, 1, 1, s), (b, 1, h, s, s)


def DS4Sci_EvoformerAttention(q, k, v, biases: Sequence = (), chunk: int = 256):
    """q/k/v: (B, N, S, H, D); biases: up to two of
    [(B, N, 1, 1, S) mask bias, (B, 1, H, S, S) pair bias].
    Returns (B, N, S, H, D) in q's dtype.

    Dispatch: MXU-friendly shapes run the fused Pallas bias-flash forward
    (``pallas/evoformer_flash.py`` — logits never hit HBM) with a
    query-chunked recompute backward; other shapes take the chunked XLA
    path end-to-end. The choice is by shape: a kernel that was chosen and
    fails, raises. The env kill switch is read at Python call time
    (OUTSIDE the jitted internals) so toggling it mid-process works, like
    every other Pallas dispatcher in this repo.
    """
    biases = [b for b in biases if b is not None]
    assert len(biases) <= 2, "at most two biases (mask, pair)"
    bias1 = bias2 = None
    s1, s2 = _bias_shapes(q)
    for b in biases:
        if b.shape == s1:
            bias1 = b
        elif b.shape == s2:
            bias2 = b
        else:
            raise ValueError(f"bias shape {b.shape} matches neither mask "
                             f"{s1} nor pair {s2}")
    from .pallas.evoformer_flash import evoformer_flash_supported
    if _use_pallas() and evoformer_flash_supported(q.shape[2], q.shape[4]):
        return _evo_attn_jit(q, k, v, bias1, bias2, chunk)
    return _chunked_jit(q, k, v, bias1, bias2, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _evo_attn(q, k, v, bias1, bias2, chunk):
    from .pallas.evoformer_flash import evoformer_flash_fwd
    d = q.shape[-1]
    out = evoformer_flash_fwd(
        jnp.moveaxis(q, 3, 2), jnp.moveaxis(k, 3, 2), jnp.moveaxis(v, 3, 2),
        bias1, bias2, scale=d ** -0.5)
    return jnp.moveaxis(out, 2, 3)


def _evo_attn_fwd_rule(q, k, v, bias1, bias2, chunk):
    return _evo_attn(q, k, v, bias1, bias2, chunk), (q, k, v, bias1, bias2)


def _evo_attn_bwd_rule(chunk, residuals, g):
    # recompute through the chunked XLA formulation: identical math, peak
    # memory O(chunk * S) per (row, head); dBias1/dBias2 fall out of
    # autodiff (the reference kernel's dB outputs)
    q, k, v, bias1, bias2 = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_, b1, b2: _chunked(q_, k_, v_, b1, b2, chunk),
        q, k, v, bias1, bias2)
    return vjp(g)


_evo_attn.defvjp(_evo_attn_fwd_rule, _evo_attn_bwd_rule)

_evo_attn_jit = jax.jit(_evo_attn, static_argnums=(5,))


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunked_jit(q, k, v, bias1, bias2, chunk):
    return _chunked(q, k, v, bias1, bias2, chunk)


def _chunked(q, k, v, bias1, bias2, chunk: int = 256):
    bdim, n, s, h, d = q.shape
    scale = d ** -0.5
    # (B, N, S, H, D) → (B, N, H, S, D)
    qt = jnp.moveaxis(q, 3, 2) * scale
    kt = jnp.moveaxis(k, 3, 2)
    vt = jnp.moveaxis(v, 3, 2)

    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    n_chunks = qt.shape[3] // chunk
    q_chunks = qt.reshape(bdim, n, h, n_chunks, chunk, d)
    q_chunks = jnp.moveaxis(q_chunks, 3, 0)          # (C, B, N, H, chunk, D)
    b2_chunks = None
    if bias2 is not None:
        b2 = bias2
        if pad:
            b2 = jnp.pad(b2, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        b2_chunks = jnp.moveaxis(
            b2.reshape(bdim, 1, h, n_chunks, chunk, s), 3, 0)

    def one_chunk(qc, b2c):
        logits = jnp.einsum("bnhqd,bnhkd->bnhqk", qc.astype(jnp.float32),
                            kt.astype(jnp.float32))
        if bias1 is not None:
            logits = logits + bias1.astype(jnp.float32)   # (B,N,1,1,S) broadcast
        if b2c is not None:
            logits = logits + b2c.astype(jnp.float32)     # (B,1,H,chunk,S)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bnhqk,bnhkd->bnhqd", probs.astype(vt.dtype), vt)

    if n_chunks == 1:
        out = one_chunk(q_chunks[0], None if b2_chunks is None else b2_chunks[0])
    else:
        def body(_, xs):
            if b2_chunks is None:
                qc = xs
                return None, one_chunk(qc, None)
            qc, b2c = xs
            return None, one_chunk(qc, b2c)

        xs = q_chunks if b2_chunks is None else (q_chunks, b2_chunks)
        _, outs = jax.lax.scan(body, None, xs)   # (C, B, N, H, chunk, D)
        out = jnp.moveaxis(outs, 0, 3).reshape(bdim, n, h, n_chunks * chunk, d)
    if pad:
        out = out[:, :, :, :s]
    return jnp.moveaxis(out, 2, 3).astype(q.dtype)     # back to (B, N, S, H, D)
