"""Pallas flash attention (training) for TPU.

Replaces the reference's CUDA fused-attention kernels
(``csrc/transformer/inference/csrc/softmax_context`` and the training
transformer kernel, SURVEY.md §2.2): FlashAttention-2-style online-softmax
tiling sized for the MXU, fp32 accumulation, causal block skipping, GQA via
block index maps (kv heads are never materialized per-q-head in HBM).

Layout inside the kernel: (B, H, S, D). The public wrapper takes the model's
(B, S, H, D) and transposes (free under XLA fusion).
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _interpret() -> bool:
    # Mosaic compiles only on TPU; anywhere else run the kernel interpreted
    # (slow but exact) so tests exercise the same code path.
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _q_block_ranges(qi, block_q, block_k, num_kv, causal, window):
    """KV-block loop bounds for q block qi: (kv_lo, full_lo, full_hi, kv_hi).

    [kv_lo, full_lo) and [full_hi, kv_hi) run with masking; [full_lo,
    full_hi) is mask-free. A sliding window both LOWERS kv_hi's
    counterpart kv_lo (blocks left of every row's window are skipped —
    the flash win for long-context Mistral) and shrinks the mask-free
    middle from below.
    """
    if causal:
        kv_hi = jax.lax.min((((qi + 1) * block_q + block_k - 1) // block_k), num_kv)
        n_full = (qi * block_q) // block_k
    else:
        kv_hi = num_kv
        n_full = num_kv
    if window is None:
        return 0, 0, n_full, kv_hi
    # first block holding any col visible to the block's first row
    kv_lo = jax.lax.max(0, (qi * block_q - window + 1) // block_k)
    # first block whose cols are inside the window of even the LAST row
    lo_full = jax.lax.max(0, ((qi + 1) * block_q - window + block_k - 1) // block_k)
    full_lo = jax.lax.clamp(kv_lo, lo_full, kv_hi)
    full_hi = jax.lax.clamp(full_lo, n_full, kv_hi)
    return kv_lo, full_lo, full_hi, kv_hi


def _fwd_kernel(q_ref, k_ref, v_ref, slopes_ref, seg_ref, o_ref, lse_ref, *, causal,
                alibi, segmented, window, block_q, block_k):
    qi = pl.program_id(2)
    q = q_ref[0, 0]                                      # (Bq, D) input dtype
    seq_k = k_ref.shape[2]
    num_kv = seq_k // block_k
    slope = slopes_ref[pl.program_id(1), 0] if alibi else None
    qseg = seg_ref[0, 0, pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)] \
        if segmented else None
    kv_lo, full_lo, full_hi, kv_hi = _q_block_ranges(
        qi, block_q, block_k, num_kv, causal, window)
    if segmented:
        full_lo, full_hi = kv_lo, kv_lo   # every block needs the seg mask

    def make_body(masked):
        def body(j, carry):
            m, l, acc = carry
            k = k_ref[0, 0, pl.ds(pl.multiple_of(j * block_k, block_k), block_k), :]                   # (Bk, D)
            v = v_ref[0, 0, pl.ds(pl.multiple_of(j * block_k, block_k), block_k), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)           # (Bq, Bk)
            if alibi or masked:
                rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if alibi:   # in-kernel ALiBi: no (H, S, S) bias ever touches HBM
                s = s + slope * (cols - rows).astype(jnp.float32)
            if masked:
                keep = rows >= cols if causal else \
                    jnp.ones(s.shape, jnp.bool_)
                if window is not None:
                    keep = keep & (rows - cols < window)
                if segmented:   # packed sequences: attend within segment only
                    kseg = seg_ref[0, 0, pl.ds(pl.multiple_of(j * block_k, block_k),
                                               block_k)]
                    keep = keep & (qseg[:, None] == kseg[None, :])
                s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_new = l * alpha + jnp.sum(p, axis=1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    carry = jax.lax.fori_loop(kv_lo, full_lo, make_body(True),
                              (m0, l0, acc0))
    carry = jax.lax.fori_loop(full_lo, full_hi, make_body(False), carry)
    m, l, acc = jax.lax.fori_loop(full_hi, kv_hi, make_body(True), carry)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = m + jnp.log(l_safe)


def _fwd(q, k, v, slopes, seg, causal, alibi, segmented, window, block_q, block_k):
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    grid = (b, h, sq // block_q)
    group = h // kvh

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, alibi=alibi,
                          segmented=segmented, window=window,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, k.shape[2], d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, k.shape[2], d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((q.shape[1], 128), lambda bi, hi, qi: (0, 0)),
            pl.BlockSpec((1, 1, seg.shape[2]), lambda bi, hi, qi: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        name="flash_fwd",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, slopes, seg)
    return out, lse


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, seg_ref,
               dq_ref, *, causal, alibi, segmented, window, block_q, block_k):
    qi = pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    slope = slopes_ref[pl.program_id(1), 0] if alibi else None
    qseg = seg_ref[0, 0, pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)] \
        if segmented else None
    seq_k = k_ref.shape[2]
    num_kv = seq_k // block_k
    kv_lo, full_lo, full_hi, kv_hi = _q_block_ranges(
        qi, block_q, block_k, num_kv, causal, window)
    if segmented:
        full_lo, full_hi = kv_lo, kv_lo

    def make_body(masked):
        def body(j, dq):
            k = k_ref[0, 0, pl.ds(pl.multiple_of(j * block_k, block_k), block_k), :]
            v = v_ref[0, 0, pl.ds(pl.multiple_of(j * block_k, block_k), block_k), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if alibi or masked:
                rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if alibi:
                s = s + slope * (cols - rows).astype(jnp.float32)
            if masked:
                keep = rows >= cols if causal else jnp.ones(s.shape, jnp.bool_)
                if window is not None:
                    keep = keep & (rows - cols < window)
                if segmented:
                    kseg = seg_ref[0, 0, pl.ds(pl.multiple_of(j * block_k, block_k),
                                               block_k)]
                    keep = keep & (qseg[:, None] == kseg[None, :])
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])                                   # (Bq, Bk)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(k.dtype)
            return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        return body

    dq = jax.lax.fori_loop(kv_lo, full_lo, make_body(True),
                           jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq = jax.lax.fori_loop(full_lo, full_hi, make_body(False), dq)
    dq = jax.lax.fori_loop(full_hi, kv_hi, make_body(True), dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, seg_ref,
                dk_ref, dv_ref, *, causal, alibi, segmented, window, block_q, block_k):
    ki = pl.program_id(2)
    k = k_ref[0, 0]                                       # (Bk, D)
    v = v_ref[0, 0]
    slope = slopes_ref[pl.program_id(1), 0] if alibi else None
    kseg = seg_ref[0, 0, pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)] \
        if segmented else None
    seq_q = q_ref.shape[2]
    num_q = seq_q // block_q
    if causal:
        q_lo = (ki * block_k) // block_q
        # q blocks at/above i_um sit fully below the diagonal: no masking
        i_um = ((ki + 1) * block_k - 1 + block_q - 1) // block_q
    else:
        q_lo = 0
        i_um = 0
    if window is not None:
        # dual of _q_block_ranges: rows past the window of the block's last
        # col contribute nothing (r < c + window); the mask-free middle ends
        # once the block's LAST row leaves the window of the first col
        q_hi_w = jax.lax.min(num_q,
                             ((ki + 1) * block_k - 1 + window + block_q - 1) // block_q)
        i_full_end = jax.lax.max(q_lo, (ki * block_k + window) // block_q)
    else:
        q_hi_w = num_q
        i_full_end = num_q

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q = q_ref[0, 0, pl.ds(pl.multiple_of(i * block_q, block_q), block_q), :]
            do = do_ref[0, 0, pl.ds(pl.multiple_of(i * block_q, block_q), block_q), :]
            lse = lse_ref[0, 0, 0, pl.ds(pl.multiple_of(i * block_q, block_q), block_q)]
            delta = delta_ref[0, 0, 0, pl.ds(pl.multiple_of(i * block_q, block_q), block_q)]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)          # (Bq, Bk)
            if alibi or masked:
                rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if alibi:
                s = s + slope * (cols - rows).astype(jnp.float32)
            if masked:
                keep = rows >= cols if causal else jnp.ones(s.shape, jnp.bool_)
                if window is not None:
                    keep = keep & (rows - cols < window)
                if segmented:
                    qseg = seg_ref[0, 0, pl.ds(pl.multiple_of(i * block_q, block_q),
                                               block_q)]
                    keep = keep & (qseg[:, None] == kseg[None, :])
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv_new = dv + jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
            return dk_new, dv_new
        return body

    zeros = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    if segmented:   # every q block needs the segment mask
        m1_end = q_hi_w
        full_end = q_hi_w
    else:
        m1_end = jax.lax.clamp(q_lo, jax.lax.min(i_um, num_q) if causal else 0, q_hi_w)
        full_end = jax.lax.clamp(m1_end, i_full_end, q_hi_w)
    dk, dv = jax.lax.fori_loop(q_lo, m1_end, make_body(True), (zeros, zeros))
    dk, dv = jax.lax.fori_loop(m1_end, full_end, make_body(False), (dk, dv))
    dk, dv = jax.lax.fori_loop(full_end, q_hi_w, make_body(True), (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(causal, alibi, segmented, window, block_q, block_k, residuals, g):
    q, k, v, slopes, seg, out, lse = residuals
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]  # (B,H,1,Sq)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, alibi=alibi,
                          segmented=segmented, window=window,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, k.shape[2], d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, k.shape[2], d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
            pl.BlockSpec((q.shape[1], 128), lambda bi, hi, qi: (0, 0)),
            pl.BlockSpec((1, 1, seg.shape[2]), lambda bi, hi, qi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="flash_bwd_dq",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, do, lse, delta, slopes, seg)

    sk = k.shape[2]
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, alibi=alibi,
                          segmented=segmented, window=window,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, sq, d), lambda bi, hi, ki_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki_: (bi, hi // group, ki_, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki_: (bi, hi // group, ki_, 0)),
            pl.BlockSpec((1, 1, sq, d), lambda bi, hi, ki_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, sq), lambda bi, hi, ki_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, sq), lambda bi, hi, ki_: (bi, hi, 0, 0)),
            pl.BlockSpec((q.shape[1], 128), lambda bi, hi, ki_: (0, 0)),
            pl.BlockSpec((1, 1, seg.shape[2]), lambda bi, hi, ki_: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki_: (bi, hi, ki_, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki_: (bi, hi, ki_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), q.dtype),
        ],
        name="flash_bwd_dkv",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, do, lse, delta, slopes, seg)

    if group > 1:
        dk = dk_h.reshape(b, kvh, group, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(b, kvh, group, sk, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h.astype(k.dtype), dv_h.astype(v.dtype)
    return dq, dk, dv, jnp.zeros_like(slopes), \
        np.zeros(seg.shape, jax.dtypes.float0)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bhsd(q, k, v, slopes, seg, causal, alibi, segmented, window, block_q, block_k):
    """Scale-free core: callers fold the softmax scale into q.

    ``slopes``: (H, 128) fp32 per-head ALiBi slopes (lane-broadcast; a
    zeros placeholder when ``alibi`` is False)."""
    out, _ = _fwd(q, k, v, slopes, seg, causal, alibi, segmented, window,
                  block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, slopes, seg, causal, alibi, segmented, window,
                    block_q, block_k):
    out, lse = _fwd(q, k, v, slopes, seg, causal, alibi, segmented, window,
                    block_q, block_k)
    return out, (q, k, v, slopes, seg, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, *, causal=True, segment_ids=None, scale=None,
                    alibi_slopes=None, window=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """q: (B, S, H, D); k/v: (B, S, KVH, D) → (B, S, H, D).

    Requires S % block == 0 and D in {64, 128, 256}; callers
    (``ops/attention.py``) fall back to the XLA path otherwise.
    ``alibi_slopes``: (H,) per-head slopes — the bias slope*(k-q) is
    computed inside the kernel from block coordinates (no O(S^2) bias in
    HBM), fwd and bwd. Slopes are NON-DIFFERENTIABLE here (the vjp
    returns zero for them): ALiBi slopes are fixed constants, not
    trainable parameters.
    """
    if window is not None:
        if not causal:
            raise NotImplementedError("flash sliding window is causal-only")
        if not isinstance(window, int) or window <= 0:
            raise ValueError("flash window must be a static positive int")
    b, s, h, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q != 0 or s % block_k != 0:
        raise ValueError(f"seq len {s} not divisible by blocks ({block_q},{block_k})")
    scale = scale if scale is not None else d ** -0.5
    segmented = segment_ids is not None
    if segmented:
        seg = jnp.asarray(segment_ids, jnp.int32)[:, None, :]   # (B, 1, S)
    else:
        seg = jnp.zeros((b, 1, 128), jnp.int32)
    alibi = alibi_slopes is not None
    if alibi:
        slopes = jnp.broadcast_to(
            jnp.asarray(alibi_slopes, jnp.float32)[:, None], (h, 128))
    else:
        slopes = jnp.zeros((h, 128), jnp.float32)
    # Fold the softmax scale into q outside the custom_vjp: the kernels run
    # scale-free (one fewer VPU pass over every (Bq, Bk) score tile, fwd and
    # bwd) and autodiff chains d(q*scale)/dq for free.
    qt = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_bhsd(qt, kt, vt, slopes, seg, bool(causal), alibi, segmented,
                      window, int(block_q), int(block_k))
    return out.transpose(0, 2, 1, 3)
