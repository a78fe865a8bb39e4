"""Ring attention with a fused Pallas flash inner kernel.

SURVEY §7's planned design ("Pallas flash kernel with ppermute KV rotation"):
the einsum ring (``ring_attention.py``) materializes fp32 (B, H, Cq, S/n)
score chunks in HBM per ring step; here each ring step runs a flash
CONTINUATION kernel — the online-softmax carry (m, l, acc) threads through
``n`` kernel invocations while K/V blocks rotate around the ``seq`` axis via
``ppermute`` — so scores only ever exist as (block_q, block_k) VMEM tiles.

Masking is computed from GLOBAL positions (q_offset/k_offset ride in as
scalar-prefetch operands, traced per ring step), so causal, sliding-window,
ALiBi, and packed-segment masking compose exactly as in the einsum ring and
the local flash kernel (``ops/pallas/flash_attention.py``) — parity tests
assert all four against the einsum reference.

The backward is a second ring: dK/dV accumulators rotate WITH their K/V
blocks (each returns home after n steps having collected every rank's
contribution), dQ accumulates locally; both are computed by per-step Pallas
kernels using the saved forward lse — the FlashAttention-2 recomputation
scheme stretched around the ring. The reference has no CP at all
(``deepspeed/sequence/layer.py:145`` — Ulysses is its only long-sequence
mechanism); this kernel is the TPU-native extension.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cdiv(a, b):
    return (a + b - 1) // b


def _global_q_ranges(rows_base, k_off, block_q, block_k, num_kv, window):
    """KV-block loop bounds for the q block starting at GLOBAL row
    ``rows_base`` against a kv shard starting at GLOBAL col ``k_off``
    (both traced): (kv_lo, full_lo, full_hi, kv_hi); [full_lo, full_hi) is
    mask-free. The global generalization of flash's ``_q_block_ranges`` —
    with rows_base/k_off of the local shard it reduces to the same bounds.
    """
    zero = jnp.int32(0)
    nkv = jnp.int32(num_kv)
    # causal: block j visible iff its first col <= the block's last row
    kv_hi = jnp.clip(_cdiv(rows_base + block_q - k_off, block_k), zero, nkv)
    # mask-free (causal) iff the block's last col < the block's first row
    n_full = jnp.clip((rows_base - k_off) // block_k, zero, nkv)
    if window is None:
        return zero, zero, n_full, kv_hi
    kv_lo = jnp.clip((rows_base - window + 1 - k_off) // block_k, zero, nkv)
    lo_full = _cdiv(rows_base + block_q - window - k_off, block_k)
    full_lo = jnp.clip(lo_full, kv_lo, kv_hi)
    full_hi = jnp.clip(n_full, full_lo, kv_hi)
    return kv_lo, full_lo, full_hi, kv_hi


def _ring_fwd_kernel(off_ref,                      # scalar prefetch (2,)
                     q_ref, k_ref, v_ref, slopes_ref, qseg_ref, kseg_ref,
                     m_in_ref, l_in_ref, acc_in_ref,
                     m_ref, l_ref, acc_ref, *,
                     alibi, segmented, window, block_q, block_k):
    qi = pl.program_id(2)
    q_off = off_ref[0]
    k_off = off_ref[1]
    q = q_ref[0, 0]                                     # (Bq, D)
    rows_base = q_off + qi * block_q
    num_kv = k_ref.shape[2] // block_k
    slope = slopes_ref[pl.program_id(1), 0] if alibi else None
    qseg = qseg_ref[0, 0, pl.ds(pl.multiple_of(qi * block_q, block_q),
                                block_q)] if segmented else None
    kv_lo, full_lo, full_hi, kv_hi = _global_q_ranges(
        rows_base, k_off, block_q, block_k, num_kv, window)
    if segmented:
        full_lo, full_hi = kv_lo, kv_lo      # every block needs the seg mask

    def make_body(masked):
        def body(j, carry):
            m, l, acc = carry
            k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if alibi or masked:
                rows = rows_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = k_off + j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
            if alibi:
                s = s + slope * (cols - rows).astype(jnp.float32)
            if masked:
                keep = rows >= cols
                if window is not None:
                    keep = keep & (rows - cols < window)
                if segmented:
                    kseg = kseg_ref[0, 0, pl.ds(j * block_k, block_k)]
                    keep = keep & (qseg[:, None] == kseg[None, :])
                s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, None])
            if masked:
                p = jnp.where(keep, p, 0.0)   # kill exp(NEG_INF - NEG_INF)
            l_new = l * alpha + jnp.sum(p, axis=1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    carry = (m_in_ref[0, 0, 0], l_in_ref[0, 0, 0], acc_in_ref[0, 0])
    carry = jax.lax.fori_loop(kv_lo, full_lo, make_body(True), carry)
    carry = jax.lax.fori_loop(full_lo, full_hi, make_body(False), carry)
    m, l, acc = jax.lax.fori_loop(full_hi, kv_hi, make_body(True), carry)
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l
    acc_ref[0, 0] = acc


def _fwd_step(off, q, k, v, slopes, qseg, kseg, m, l, acc, *,
              alibi, segmented, window, block_q, block_k, vma):
    """One ring step: fold one rotating KV block into the carry.
    q: (B, H, Sq, D) pre-scaled; k/v: (B, KVH, Sk, D); m/l: (B, H, Sq) f32;
    acc: (B, H, Sq, D) f32; off: int32 (2,) = (q_offset, k_offset)."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    grid = (b, h, sq // block_q)
    qmap = lambda bi, hi, qi, off_: (bi, hi, qi, 0)
    kvmap = lambda bi, hi, qi, off_: (bi, hi // group, 0, 0)
    mlmap = lambda bi, hi, qi, off_: (bi, hi, 0, qi)
    return pl.pallas_call(
        functools.partial(_ring_fwd_kernel, alibi=alibi, segmented=segmented,
                          window=window, block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qmap),
                pl.BlockSpec((1, 1, sk, d), kvmap),
                pl.BlockSpec((1, 1, sk, d), kvmap),
                pl.BlockSpec((h, 128), lambda bi, hi, qi, off_: (0, 0)),
                pl.BlockSpec((1, 1, qseg.shape[2]),
                             lambda bi, hi, qi, off_: (bi, 0, 0)),
                pl.BlockSpec((1, 1, kseg.shape[2]),
                             lambda bi, hi, qi, off_: (bi, 0, 0)),
                pl.BlockSpec((1, 1, 1, block_q), mlmap),
                pl.BlockSpec((1, 1, 1, block_q), mlmap),
                pl.BlockSpec((1, 1, block_q, d), qmap),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, block_q), mlmap),
                pl.BlockSpec((1, 1, 1, block_q), mlmap),
                pl.BlockSpec((1, 1, block_q, d), qmap),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32, vma=vma),
        ],
        input_output_aliases={7: 0, 8: 1, 9: 2},   # carry updated in place
        name="ring_flash_fwd",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(off, q, k, v, slopes, qseg, kseg, m, l, acc)


def _ring_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    slopes_ref, qseg_ref, kseg_ref, dq_ref, *,
                    alibi, segmented, window, block_q, block_k):
    qi = pl.program_id(2)
    q_off = off_ref[0]
    k_off = off_ref[1]
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    rows_base = q_off + qi * block_q
    num_kv = k_ref.shape[2] // block_k
    slope = slopes_ref[pl.program_id(1), 0] if alibi else None
    qseg = qseg_ref[0, 0, pl.ds(pl.multiple_of(qi * block_q, block_q),
                                block_q)] if segmented else None
    kv_lo, full_lo, full_hi, kv_hi = _global_q_ranges(
        rows_base, k_off, block_q, block_k, num_kv, window)
    if segmented:
        full_lo, full_hi = kv_lo, kv_lo

    def make_body(masked):
        def body(j, dq):
            k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if alibi or masked:
                rows = rows_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = k_off + j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
            if alibi:
                s = s + slope * (cols - rows).astype(jnp.float32)
            if masked:
                keep = rows >= cols
                if window is not None:
                    keep = keep & (rows - cols < window)
                if segmented:
                    kseg = kseg_ref[0, 0, pl.ds(j * block_k, block_k)]
                    keep = keep & (qseg[:, None] == kseg[None, :])
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            if masked:
                p = jnp.where(keep, p, 0.0)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(k.dtype)
            return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        return body

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(kv_lo, full_lo, make_body(True), dq)
    dq = jax.lax.fori_loop(full_lo, full_hi, make_body(False), dq)
    dq = jax.lax.fori_loop(full_hi, kv_hi, make_body(True), dq)
    dq_ref[0, 0] = dq


def _ring_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     slopes_ref, qseg_ref, kseg_ref, dk_ref, dv_ref, *,
                     alibi, segmented, window, block_q, block_k):
    ki = pl.program_id(2)
    q_off = off_ref[0]
    k_off = off_ref[1]
    k = k_ref[0, 0]                                      # (Bk, D)
    v = v_ref[0, 0]
    cols_base = k_off + ki * block_k
    num_q = q_ref.shape[2] // block_q
    slope = slopes_ref[pl.program_id(1), 0] if alibi else None
    kseg = kseg_ref[0, 0, pl.ds(pl.multiple_of(ki * block_k, block_k),
                                block_k)] if segmented else None
    # dual bounds in global coords: q blocks with last row >= first col
    zero = jnp.int32(0)
    nq = jnp.int32(num_q)
    q_lo = jnp.clip((cols_base - q_off) // block_q, zero, nq)
    # mask-free once the block's first row > the block's last col
    i_um = jnp.clip(_cdiv(cols_base + block_k - q_off, block_q), zero, nq)
    if window is not None:
        q_hi = jnp.clip(_cdiv(cols_base + block_k + window - q_off, block_q),
                        zero, nq)
        i_full_end = jnp.clip((cols_base + window - q_off) // block_q,
                              zero, nq)
    else:
        q_hi = nq
        i_full_end = nq

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
            do = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[0, 0, 0, pl.ds(i * block_q, block_q)]
            delta = delta_ref[0, 0, 0, pl.ds(i * block_q, block_q)]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if alibi or masked:
                rows = q_off + i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                cols = cols_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if alibi:
                s = s + slope * (cols - rows).astype(jnp.float32)
            if masked:
                keep = rows >= cols
                if window is not None:
                    keep = keep & (rows - cols < window)
                if segmented:
                    qseg = qseg_ref[0, 0, pl.ds(i * block_q, block_q)]
                    keep = keep & (qseg[:, None] == kseg[None, :])
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            if masked:
                p = jnp.where(keep, p, 0.0)
            dv_new = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
            return dk_new, dv_new
        return body

    zeros = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    if segmented:
        m1_end = q_hi
        full_end = q_hi
    else:
        m1_end = jnp.clip(i_um, q_lo, q_hi)
        full_end = jnp.clip(i_full_end, m1_end, q_hi)
    dk, dv = jax.lax.fori_loop(q_lo, m1_end, make_body(True), (zeros, zeros))
    dk, dv = jax.lax.fori_loop(m1_end, full_end, make_body(False), (dk, dv))
    dk, dv = jax.lax.fori_loop(full_end, q_hi, make_body(True), (dk, dv))
    dk_ref[0, 0] = dk
    dv_ref[0, 0] = dv


def _bwd_step(off, q, k, v, do, lse, delta, slopes, qseg, kseg, *,
              alibi, segmented, window, block_q, block_k, vma):
    """Per-ring-step gradients: dq (B, H, Sq, D) f32, and this KV block's
    dk/dv (B, KVH, Sk, D) f32 (summed over the GQA group in-step so the
    rotating accumulator stays KVH-sized)."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    common = dict(alibi=alibi, segmented=segmented, window=window,
                  block_q=block_q, block_k=block_k)
    kvmap = lambda bi, hi, qi, off_: (bi, hi // group, 0, 0)
    qmap = lambda bi, hi, qi, off_: (bi, hi, qi, 0)
    smap = lambda bi, hi, qi, off_: (0, 0)
    qsegmap = lambda bi, hi, qi, off_: (bi, 0, 0)
    lsemap = lambda bi, hi, qi, off_: (bi, hi, 0, qi)
    dq = pl.pallas_call(
        functools.partial(_ring_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qmap),
                pl.BlockSpec((1, 1, sk, d), kvmap),
                pl.BlockSpec((1, 1, sk, d), kvmap),
                pl.BlockSpec((1, 1, block_q, d), qmap),
                pl.BlockSpec((1, 1, 1, block_q), lsemap),
                pl.BlockSpec((1, 1, 1, block_q), lsemap),
                pl.BlockSpec((h, 128), smap),
                pl.BlockSpec((1, 1, qseg.shape[2]), qsegmap),
                pl.BlockSpec((1, 1, kseg.shape[2]), qsegmap),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), qmap),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32, vma=vma),
        name="ring_flash_bwd_dq",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(off, q, k, v, do, lse, delta, slopes, qseg, kseg)

    fullq = lambda bi, hi, ki_, off_: (bi, hi, 0, 0)
    kmap = lambda bi, hi, ki_, off_: (bi, hi // group, ki_, 0)
    lmap = lambda bi, hi, ki_, off_: (bi, hi, 0, 0)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_ring_dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, sq, d), fullq),
                pl.BlockSpec((1, 1, block_k, d), kmap),
                pl.BlockSpec((1, 1, block_k, d), kmap),
                pl.BlockSpec((1, 1, sq, d), fullq),
                pl.BlockSpec((1, 1, 1, sq), lmap),
                pl.BlockSpec((1, 1, 1, sq), lmap),
                pl.BlockSpec((h, 128), lambda bi, hi, ki_, off_: (0, 0)),
                pl.BlockSpec((1, 1, qseg.shape[2]),
                             lambda bi, hi, ki_, off_: (bi, 0, 0)),
                pl.BlockSpec((1, 1, kseg.shape[2]),
                             lambda bi, hi, ki_, off_: (bi, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, d),
                             lambda bi, hi, ki_, off_: (bi, hi, ki_, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda bi, hi, ki_, off_: (bi, hi, ki_, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32, vma=vma),
        ],
        name="ring_flash_bwd_dkv",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(off, q, k, v, do, lse, delta, slopes, qseg, kseg)
    if group > 1:
        dk = dk_h.reshape(b, kvh, group, sk, d).sum(axis=2)
        dv = dv_h.reshape(b, kvh, group, sk, d).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


def _rotate(axis_name, *xs):
    n = jax.lax.axis_size(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    return tuple(None if x is None else jax.lax.ppermute(x, axis_name, perm)
                 for x in xs)


def _vary(x, axes):
    """Mark device-constant arrays as axis-varying so loop carries and
    kernel operands type-check under shard_map's check_vma."""
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(axes), to="varying")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _ring_flash_local(q, k, v, seg, slopes, axis_name, window, use_alibi,
                      block_q, block_k, vary_axes):
    out, _ = _ring_fwd_local(q, k, v, seg, slopes, axis_name, window,
                             use_alibi, block_q, block_k, vary_axes)
    return out


def _ring_fwd_local(q, k, v, seg, slopes, axis_name, window, use_alibi,
                    block_q, block_k, vary_axes):
    """Runs inside shard_map. q: (B, Sq, H, D) PRE-SCALED local shard;
    k/v: (B, Sk, KVH, D); seg: (B, Sq) int32 or None (static flag);
    slopes: (H, 128) f32 (zeros when ``use_alibi`` is False — slopes are
    non-differentiable constants, as in the local flash kernel).
    Returns (o (B, Sq, H, D), lse (B, H, Sq))."""
    n = jax.lax.axis_size(axis_name)
    p_idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    segmented = seg is not None
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qseg = (seg[:, None, :] if segmented
            else _vary(jnp.zeros((b, 1, 128), jnp.int32), vary_axes))
    slopes = _vary(slopes, vary_axes)
    m0 = _vary(jnp.full((b, h, 1, sq), NEG_INF, jnp.float32), vary_axes)
    l0 = _vary(jnp.zeros((b, h, 1, sq), jnp.float32), vary_axes)
    acc0 = _vary(jnp.zeros((b, h, sq, d), jnp.float32), vary_axes)

    def step(i, carry):
        m, l, acc, kv = carry
        k_blk, v_blk, kseg_blk = kv
        src = (p_idx - i) % n
        off = jnp.stack([p_idx * sq, src * sk]).astype(jnp.int32)
        m, l, acc = _fwd_step(
            off, qt, k_blk, v_blk, slopes, qseg,
            kseg_blk if segmented else qseg,
            m, l, acc, alibi=use_alibi, segmented=segmented,
            window=window, block_q=block_q, block_k=block_k,
            vma=frozenset(vary_axes))
        kv_next = _rotate(axis_name, k_blk, v_blk, kseg_blk)
        return m, l, acc, kv_next

    kseg0 = seg[:, None, :] if segmented else None
    m, l, acc, _ = jax.lax.fori_loop(
        0, n, step, (m0, l0, acc0, (kt, vt, kseg0)))
    m, l = m[:, :, 0, :], l[:, :, 0, :]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype).transpose(0, 2, 1, 3)
    lse = m + jnp.log(l_safe)
    return o, lse


def _ring_flash_fwd_rule(q, k, v, seg, slopes, axis_name, window, use_alibi,
                         block_q, block_k, vary_axes):
    out, lse = _ring_fwd_local(q, k, v, seg, slopes, axis_name, window,
                               use_alibi, block_q, block_k, vary_axes)
    return out, (q, k, v, seg, slopes, out, lse)


def _ring_flash_bwd_rule(axis_name, window, use_alibi, block_q, block_k,
                         vary_axes, residuals, g):
    q, k, v, seg, slopes, out, lse = residuals
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    segmented = seg is not None
    n = jax.lax.axis_size(axis_name)
    p_idx = jax.lax.axis_index(axis_name)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)
    qseg = (seg[:, None, :] if segmented
            else _vary(jnp.zeros((b, 1, 128), jnp.int32), vary_axes))
    slopes = _vary(slopes, vary_axes)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)[:, :, None, :]              # (B, H, 1, Sq)
    lse4 = lse[:, :, None, :]

    def step(i, carry):
        dq, kvg = carry
        k_blk, v_blk, kseg_blk, dk_acc, dv_acc = kvg
        src = (p_idx - i) % n
        off = jnp.stack([p_idx * sq, src * sk]).astype(jnp.int32)
        dq_s, dk_s, dv_s = _bwd_step(
            off, qt, k_blk, v_blk, dot, lse4, delta, slopes, qseg,
            kseg_blk if segmented else qseg,
            alibi=use_alibi, segmented=segmented, window=window,
            block_q=block_q, block_k=block_k, vma=frozenset(vary_axes))
        # accumulate BEFORE rotating: this block's grad accumulator collects
        # each rank's contribution as it travels, arriving home after n steps
        kvg_next = _rotate(axis_name, k_blk, v_blk, kseg_blk,
                           dk_acc + dk_s, dv_acc + dv_s)
        return dq + dq_s, kvg_next

    dk0 = _vary(jnp.zeros((b, kvh, sk, d), jnp.float32), vary_axes)
    dq0 = _vary(jnp.zeros((b, h, sq, d), jnp.float32), vary_axes)
    dq, (_, _, _, dk, dv) = jax.lax.fori_loop(
        0, n, step, (dq0, (kt, vt, seg[:, None, :] if segmented else None,
                           dk0, dk0)))
    dq = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    dk = dk.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv.transpose(0, 2, 1, 3).astype(v.dtype)
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg, jnp.zeros_like(slopes)


_ring_flash_local.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_flash_body(q, k, v, seg=None, *, axis_name, scale, window,
                    slopes, vary_axes=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """shard_map body: ring attention with the Pallas flash inner kernel.
    Same contract as ``ring_attention._ring_body`` (local (B, S/n, H|KVH, D)
    shards in, (B, S/n, H, D) out)."""
    b, sq, h, d = q.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, k.shape[1])
    use_alibi = slopes is not None
    slopes_arr = (jnp.broadcast_to(
        jnp.asarray(slopes, jnp.float32)[:, None], (h, 128))
        if use_alibi else jnp.zeros((h, 128), jnp.float32))
    qs = q * jnp.asarray(scale, q.dtype)
    axes = tuple(vary_axes) if vary_axes else (axis_name,)
    return _ring_flash_local(qs, k, v, seg, slopes_arr, axis_name, window,
                             use_alibi, int(block_q), int(block_k), axes)


def ring_flash_supported(sq_local, sk_local, d, window, block_q=DEFAULT_BLOCK_Q,
                         block_k=DEFAULT_BLOCK_K) -> bool:
    """Static eligibility: shard sizes must tile, head dim must be MXU-
    friendly, and the window must be a static int (traced windows fall back
    to the einsum ring)."""
    bq = min(block_q, sq_local)
    bk = min(block_k, sk_local)
    if sq_local % bq or sk_local % bk:
        return False
    if d not in (64, 128, 256):
        return False
    if window is not None and not isinstance(window, int):
        return False
    return True
