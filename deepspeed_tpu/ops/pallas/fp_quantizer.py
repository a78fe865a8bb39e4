"""FP8 quantization with stochastic rounding.

Analog of ``csrc/fp_quantizer/fp_quantize.cu`` (FP8/FP6/FP12 quantize /
dequantize with stochastic rounding). TPU v5+ has native fp8 support
(e4m3/e5m2); the kernel computes per-group scales to use the fp8 dynamic
range and stochastically rounds with the on-core PRNG — gradient/weight
compression without bias.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _interpret():
    return jax.default_backend() != "tpu"


def _fp8_quant_kernel(x_ref, seed_ref, q_ref, scale_ref, *, fmax, stochastic):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / fmax
    scaled = x / scale
    if stochastic:
        pltpu.prng_seed(seed_ref[0])
        bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
        q = pltpu.stochastic_round(scaled, bits, target_dtype=q_ref.dtype)
    else:
        q = scaled.astype(q_ref.dtype)
    q_ref[:] = q
    scale_ref[:] = scale


def quantize_fp8(x, group_size: int = 256, fmt: str = "e4m3", stochastic: bool = True,
                 seed: int = 0):
    """x → (q fp8, scales (groups, 1) fp32)."""
    dtype = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    fmax = E4M3_MAX if fmt == "e4m3" else E5M2_MAX
    orig_shape = x.shape
    flat = x.reshape(-1, group_size)
    g = flat.shape[0]
    if _interpret():
        # interpreter path: deterministic rounding (prng/stochastic_round are
        # TPU-core features); numerics identical up to rounding mode.
        absmax = jnp.max(jnp.abs(flat.astype(jnp.float32)), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-12) / fmax
        q = (flat / scale).astype(dtype)
        return q.reshape(orig_shape), scale
    block_g = min(g, 256)
    if g % block_g != 0:
        block_g = 1
    q, scale = pl.pallas_call(
        functools.partial(_fp8_quant_kernel, fmax=fmax, stochastic=stochastic),
        grid=(g // block_g,),
        in_specs=[pl.BlockSpec((block_g, group_size), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((block_g, group_size), lambda i: (i, 0)),
                   pl.BlockSpec((block_g, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(flat.shape, dtype),
                   jax.ShapeDtypeStruct((g, 1), jnp.float32)],
        name="fp_quantize",
        interpret=False,
    )(flat, jnp.asarray([seed], jnp.int32))
    return q.reshape(orig_shape), scale


def dequantize_fp8(q, scales, orig_dtype=jnp.float32, group_size: int = 256):
    flat = q.reshape(-1, group_size).astype(jnp.float32)
    return (flat * scales).reshape(q.shape).astype(orig_dtype)
