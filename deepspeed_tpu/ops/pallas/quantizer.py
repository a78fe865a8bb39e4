"""Block quantization kernels (int8/int4).

Analog of the reference's ``csrc/quantization/`` (quantize.cu /
dequantize.cu / swizzled_quantize.cu): symmetric per-group quantization used
by ZeRO++ quantized-weight allgather (qwZ) and quantized-gradient reduction
(qgZ), and by ZeRO-Inference weight-only quantization.

The Pallas kernel fuses max-reduction, scale computation and rounding per
group; groups are rows of a (num_groups, group_size) view, matching the
reference's group layout. int4 packs two nibbles per int8 byte.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    return jax.default_backend() != "tpu"


def _quant_kernel(x_ref, q_ref, scale_ref, *, qmax):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-10) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[:] = q.astype(jnp.int8)
    scale_ref[:] = scale


def quantize_int8(x, group_size: int = 256):
    """x: any shape with total % group_size == 0 →
    (q int8 same-shape, scales (groups, 1) fp32)."""
    orig_shape = x.shape
    flat = x.reshape(-1, group_size)
    g = flat.shape[0]
    block_g = min(g, 256)
    if g % block_g != 0:
        block_g = 1
    q, scale = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=127.0),
        grid=(g // block_g,),
        in_specs=[pl.BlockSpec((block_g, group_size), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_g, group_size), lambda i: (i, 0)),
                   pl.BlockSpec((block_g, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(flat.shape, jnp.int8),
                   jax.ShapeDtypeStruct((g, 1), jnp.float32)],
        name="quantize_int8",
        interpret=_interpret(),
    )(flat)
    return q.reshape(orig_shape), scale


def dequantize_int8(q, scales, orig_dtype=jnp.float32, group_size: int = 256):
    flat = q.reshape(-1, group_size)
    out = flat.astype(jnp.float32) * scales
    return out.reshape(q.shape).astype(orig_dtype)


def quantize_int4(x, group_size: int = 256):
    """Symmetric int4: values in [-7, 7], packed two per byte."""
    orig_shape = x.shape
    flat = x.reshape(-1, group_size)
    g = flat.shape[0]
    block_g = min(g, 256)
    if g % block_g != 0:
        block_g = 1
    q, scale = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=7.0),
        grid=(g // block_g,),
        in_specs=[pl.BlockSpec((block_g, group_size), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_g, group_size), lambda i: (i, 0)),
                   pl.BlockSpec((block_g, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(flat.shape, jnp.int8),
                   jax.ShapeDtypeStruct((g, 1), jnp.float32)],
        name="quantize_int4",
        interpret=_interpret(),
    )(flat)
    # pack pairs of nibbles: (..., 2k) | (..., 2k+1) << 4
    lo = (q[:, 0::2].astype(jnp.int32) & 0xF)
    hi = (q[:, 1::2].astype(jnp.int32) & 0xF) << 4
    packed = (lo | hi).astype(jnp.int8)
    return packed, scale, orig_shape


def dequantize_int4(packed, scales, orig_shape, orig_dtype=jnp.float32,
                    group_size: int = 256):
    p = packed.astype(jnp.int32)
    lo = (p & 0xF)
    hi = (p >> 4) & 0xF
    # sign-extend 4-bit two's complement
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    g = packed.shape[0]
    out = jnp.zeros((g, group_size), jnp.int32)
    out = out.at[:, 0::2].set(lo)
    out = out.at[:, 1::2].set(hi)
    return (out.astype(jnp.float32) * scales).reshape(orig_shape).astype(orig_dtype)


# Reference-named convenience wrappers (csrc/quantization/pt_binding.cpp
# exposes quantize/dequantize pairs per bit width)

def ds_quantize(x, groups: int, bits: int = 8):
    group_size = x.size // groups
    if bits == 8:
        return quantize_int8(x, group_size)
    if bits == 4:
        return quantize_int4(x, group_size)
    raise ValueError(f"unsupported bits={bits}")
