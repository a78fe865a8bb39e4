"""Fused Adam update kernel over flat parameter buffers.

Analog of ``csrc/adam/multi_tensor_adam.cu`` (FusedAdam): one kernel updates
params + both moments in place. Under jit the tree_map optimizer already
fuses per-tensor; this kernel exists for the flat-buffer path (contiguous
ZeRO shards) where one launch covers the whole partition, and as the
Pallas-native counterpart the op-builder table points at.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    return jax.default_backend() != "tpu"


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, hyper_ref,
                 p_out, m_out, v_out):
    lr = hyper_ref[0]
    b1 = hyper_ref[1]
    b2 = hyper_ref[2]
    eps = hyper_ref[3]
    wd = hyper_ref[4]
    bc1 = hyper_ref[5]
    bc2 = hyper_ref[6]
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
    p_out[:] = (p - lr * update).astype(p_out.dtype)
    m_out[:] = m
    v_out[:] = v


def fused_adam_flat(params, grads, exp_avg, exp_avg_sq, *, step, lr,
                    betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                    block: int = 1 << 16):
    """Flat fp32 buffers (N,) → (new_params, new_m, new_v). N % 128 == 0 for
    the TPU path; other sizes fall back to plain XLA."""
    n = params.size
    # bias corrections are computed out here: Mosaic has no scalar powf
    hyper = jnp.asarray([lr, betas[0], betas[1], eps, weight_decay,
                         1 - betas[0] ** step, 1 - betas[1] ** step],
                        jnp.float32)
    if n % 128 != 0:
        # XLA fallback — identical math
        g = grads.astype(jnp.float32)
        m = betas[0] * exp_avg + (1 - betas[0]) * g
        v = betas[1] * exp_avg_sq + (1 - betas[1]) * g * g
        bc1 = 1 - betas[0] ** step
        bc2 = 1 - betas[1] ** step
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * params
        return (params - lr * upd).astype(params.dtype), m, v
    blk = min(block, n)
    while n % blk != 0:
        blk //= 2
    grid = (n // blk,)
    spec = pl.BlockSpec((blk,), lambda i: (i,))
    return pl.pallas_call(
        _adam_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(params.shape, params.dtype),
                   jax.ShapeDtypeStruct(params.shape, jnp.float32),
                   jax.ShapeDtypeStruct(params.shape, jnp.float32)],
        name="fused_adam",
        interpret=_interpret(),
    )(params, grads, exp_avg, exp_avg_sq, hyper)
