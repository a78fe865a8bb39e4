"""Fused weight-only-quantized matmul (mixed-input GEMM).

Analog of the reference's FP6/INT4 fused GEMMs
(``inference/v2/kernels/core_ops/cuda_linear/linear_kernels_cuda.cu``,
``cutlass_ops/mixed_gemm/``): the quantized weight streams from HBM in its
packed form and dequantizes TILE BY TILE in VMEM inside the matmul — the
full-size bf16 weight never exists, so decode-time linears keep the 4-8x
HBM-bandwidth win that is the point of weight-only quantization (the
previous ``QuantizedLinear`` dequantized the whole weight into HBM first:
``inference/quantization/layers.py:135`` in round-2's review).

Layouts (chosen so the kernel NEVER relayouts in VMEM — an in-kernel
unpack interleave is a sublane shuffle per tile, where a plane is one
contiguous tile read):
- scales are per (K-group, column): ``(K/g, N)`` f32 with g == the kernel's
  K-tile, so each k-step reads one ``(1, nt)`` scale row;
- int8: q ``(K, N)`` int8, used directly;
- int4: two nibble PLANES — byte row i holds w[i] (low nibble) and
  w[i + K/2] (high nibble): a k-tile reads a contiguous byte tile and picks
  its plane by grid index, no unpack interleave;
- fp6 (e3m2): codes distributed over FOUR planes — byte triple
  (B0, B1, B2)[i] packs codes for rows i, i+K/4, i+K/2, i+3K/4 — decoded
  arithmetically (sign/exp/mantissa), no codebook gather.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    return jax.default_backend() != "tpu"


# ---- quantization (load time, plain XLA) ---------------------------------

def _group_scales(w, group, qmax):
    k, n = w.shape
    wg = w.reshape(k // group, group, n).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wg), axis=1)                  # (K/g, N)
    return jnp.maximum(absmax, 1e-10) / qmax


_FP6_MAX = 28.0


def quantize_woq(w, bits: int = 8, group_size: int = 128):
    """w: (K, N) → dict(q, scales, bits, group_size, shape).

    K must be divisible by group_size (and by 2*group_size for int4,
    4*group_size for fp6 — the plane layouts need aligned halves/quarters).
    """
    k, n = w.shape
    planes = {8: 1, 4: 2, 6: 4}[bits]
    if k % (group_size * planes):
        raise ValueError(f"K={k} must be divisible by {group_size * planes} "
                         f"for bits={bits}")
    if bits == 8:
        scales = _group_scales(w, group_size, 127.0)
        s_full = jnp.repeat(scales, group_size, axis=0)
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / s_full), -127, 127
                     ).astype(jnp.int8)
    elif bits == 4:
        scales = _group_scales(w, group_size, 7.0)
        s_full = jnp.repeat(scales, group_size, axis=0)
        qi = jnp.clip(jnp.round(w.astype(jnp.float32) / s_full), -7, 7
                      ).astype(jnp.int32)
        lo = qi[: k // 2] & 0xF
        hi = qi[k // 2:] & 0xF
        q = (lo | (hi << 4)).astype(jnp.int8)              # (K/2, N)
    elif bits == 6:
        scales = _group_scales(w, group_size, _FP6_MAX)
        s_full = jnp.repeat(scales, group_size, axis=0)
        x = (w.astype(jnp.float32) / s_full)
        codes = _fp6_encode(x)                             # (K, N) int32 6-bit
        kq = k // 4
        c0, c1, c2, c3 = (codes[i * kq:(i + 1) * kq] for i in range(4))
        word = c0 | (c1 << 6) | (c2 << 12) | (c3 << 18)
        q = jnp.stack([word & 0xFF, (word >> 8) & 0xFF, (word >> 16) & 0xFF]
                      ).astype(jnp.uint8)                  # (3, K/4, N)
    else:
        raise ValueError(f"bits must be 4, 6 or 8, got {bits}")
    return {"q": q, "scales": scales, "bits": bits,
            "group_size": group_size, "shape": (k, n)}


def _fp6_encode(x):
    """Nearest e3m2 code (sign + 3-bit exp, bias 3 + 2-bit mantissa) for
    |x| <= 28; arithmetic round-to-nearest (monotone codebook)."""
    ax = jnp.abs(x)
    # exponent of the nearest representable: normals span [0.25, 28]
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(ax, 1e-12))) + 3, 0, 7
                 ).astype(jnp.int32)
    step = jnp.where(e == 0, 1.0 / 16.0, jnp.exp2(e.astype(jnp.float32) - 3) / 4)
    base = jnp.where(e == 0, 0.0, jnp.exp2(e.astype(jnp.float32) - 3))
    m = jnp.clip(jnp.round((ax - base) / step), 0, 3).astype(jnp.int32)
    # rounding up past m=3 bumps the exponent; re-derive via value compare
    v = base + m.astype(jnp.float32) * step
    nxt_e = jnp.minimum(e + 1, 7)
    nxt_v = jnp.exp2(nxt_e.astype(jnp.float32) - 3)
    bump = (jnp.abs(ax - nxt_v) < jnp.abs(ax - v)) & (e < 7)
    e = jnp.where(bump, nxt_e, e)
    m = jnp.where(bump, 0, m)
    code = (e << 2) | m
    return jnp.where(x < 0, code | 0x20, code)


def _fp6_decode_f32(code):
    """code int32 in [0, 63] → f32 value (vector arithmetic, no gather)."""
    sign = jnp.where((code & 0x20) != 0, -1.0, 1.0)
    e = ((code >> 2) & 0x7).astype(jnp.float32)
    m = (code & 0x3).astype(jnp.float32)
    mag = jnp.where(e == 0, m / 16.0, (1.0 + 0.25 * m) * jnp.exp2(e - 3.0))
    return sign * mag


# ---- the fused kernel ----------------------------------------------------

def _woq_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, nk, out_dtype):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                       # (M, kt)
    s = s_ref[0]                                       # (1, nt) f32
    if bits == 8:
        w = q_ref[0].astype(jnp.float32)               # (kt, nt)
    elif bits == 4:
        u = q_ref[0].astype(jnp.int32) & 0xFF
        half = nk // 2
        nib = jnp.where(ki < half, u & 0xF, u >> 4)
        w = jnp.where(nib >= 8, nib - 16, nib).astype(jnp.float32)
    else:   # fp6: three byte planes → 6-bit code of this quarter
        b = q_ref[...].astype(jnp.int32) & 0xFF        # (3, kt, nt)
        word = b[0] | (b[1] << 8) | (b[2] << 16)
        quarter = nk // 4
        shift = 6 * (ki // quarter)
        code = (word >> shift) & 0x3F
        w = _fp6_decode_f32(code)
    w = (w * s).astype(x.dtype)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(out_dtype)


def woq_matmul(x, qstate, *, block_n: int = 256):
    """y = x @ dequant(Wq): x (M, K) bf16/f32; returns (M, N) in x.dtype.

    The K-tile equals the quantization group size, so each k-step consumes
    exactly one scale row. M rides whole (decode batches are small); N is
    tiled by ``block_n``.
    """
    k, n = qstate["shape"]
    bits, g = qstate["bits"], qstate["group_size"]
    q, scales = qstate["q"], qstate["scales"]
    m = x.shape[0]
    assert x.shape[1] == k, (x.shape, qstate["shape"])
    nt = min(block_n, n)
    if n % nt:
        nt = n  # fall back to one tile when block_n doesn't divide N
    nk = k // g
    grid = (n // nt, nk)
    planes = {8: 1, 4: 2, 6: 4}[bits]
    kq = k // planes                                    # byte rows per plane

    def s_map(ni, ki):
        return (ki, 0, ni)

    if bits == 6:
        q3 = q.reshape(3, kq, n)
        q_spec = pl.BlockSpec((3, g, nt), lambda ni, ki: (0, ki % (kq // g), ni))
        q_in = q3
    else:
        q_spec = pl.BlockSpec((1, g, nt),
                              lambda ni, ki: (0, ki % (kq // g), ni))
        q_in = q.reshape(1, *q.shape)

    out = pl.pallas_call(
        functools.partial(_woq_kernel, bits=bits, nk=nk, out_dtype=x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, m, g), lambda ni, ki: (0, 0, ki)),
            q_spec,
            pl.BlockSpec((1, 1, nt), s_map),   # scales as (nk, 1, N): the
            # (1, nt) tail matches the array dims (TPU block tiling rule)
        ],
        out_specs=pl.BlockSpec((1, m, nt), lambda ni, ki: (0, 0, ni)),
        scratch_shapes=[pltpu.VMEM((m, nt), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((1, m, n), x.dtype),
        name="woq_matmul",
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x.reshape(1, m, k), q_in, scales.reshape(nk, 1, n))
    return out[0]


def woq_dequantize(qstate, dtype=jnp.bfloat16):
    """Full dequantization (reference/verification path)."""
    k, n = qstate["shape"]
    bits, g = qstate["bits"], qstate["group_size"]
    q, scales = qstate["q"], qstate["scales"]
    s_full = jnp.repeat(scales, g, axis=0)
    if bits == 8:
        w = q.astype(jnp.float32)
    elif bits == 4:
        u = q.astype(jnp.int32) & 0xFF
        lo = u & 0xF
        hi = u >> 4
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        w = jnp.concatenate([lo, hi]).astype(jnp.float32)
    else:
        b = q.astype(jnp.int32) & 0xFF
        word = b[0] | (b[1] << 8) | (b[2] << 16)
        codes = [(word >> (6 * i)) & 0x3F for i in range(4)]
        w = jnp.concatenate([_fp6_decode_f32(c) for c in codes])
    return (w * s_full).astype(dtype)
