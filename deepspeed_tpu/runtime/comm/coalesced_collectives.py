"""Quantized / coalesced collectives (ZeRO++ analog).

Analog of ``deepspeed/runtime/comm/coalesced_collectives.py``
(``reduce_scatter_coalesced:81``, ``all_to_all_quant_reduce:31`` = qgZ) and
the qwZ quantized-weight allgather (``partition_parameters.py:753
CUDAQuantizer``). Collectives run inside ``shard_map`` over the ``data``
axis; quantization uses the Pallas block kernels (``ops/pallas/quantizer``),
so the wire format is int8 + fp32 group scales — 4x less ICI/DCN traffic
than fp32, 2x less than bf16.
"""

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...utils import groups


def quantize_int8(x, group_size: int = 256):
    """jnp block quantizer — same math as ``ops/pallas/quantizer`` but usable
    inside shard_map manual regions (pallas_call needs vma annotations there;
    XLA fuses this to the same kernel shape anyway)."""
    flat = x.reshape(-1, group_size).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(flat), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-10) / 127.0
    q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize_int8(q, scales, orig_dtype=jnp.float32, group_size: int = 256):
    flat = q.reshape(-1, group_size).astype(jnp.float32) * scales
    return flat.reshape(q.shape).astype(orig_dtype)


def _flatten_concat(tensors: Sequence[jnp.ndarray]):
    flats = [t.reshape(-1) for t in tensors]
    sizes = [f.size for f in flats]
    return jnp.concatenate(flats), sizes


def _unflatten(flat, sizes, shapes):
    out, off = [], 0
    for n, s in zip(sizes, shapes):
        out.append(flat[off:off + n].reshape(s))
        off += n
    return out


def reduce_scatter_coalesced(tensors: List[jnp.ndarray], axis_name: str = "data"):
    """Flatten a tensor list and reduce-scatter once over the axis
    (reference ``:81``). Inside shard_map: returns this rank's reduced shard."""
    flat, sizes = _flatten_concat(tensors)
    n = jax.lax.axis_size(axis_name)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0, tiled=True), sizes


def quantized_reduce_scatter(x, axis_name: str = "data", group_size: int = 256):
    """qgZ-style gradient reduction (inside shard_map): each rank quantizes
    its n chunks to int8, all-to-alls them, dequantizes and reduces locally.
    Comm volume: int8 + scales instead of fp32. Returns the reduced shard."""
    n = jax.lax.axis_size(axis_name)
    pad = (-x.size) % (n * group_size)
    flat = jnp.concatenate([x.reshape(-1), jnp.zeros((pad,), x.dtype)]) if pad else x.reshape(-1)
    chunks = flat.reshape(n, -1)                     # chunk i → rank i
    q, scales = quantize_int8(chunks, group_size)    # (n, C) int8, (n*C/gs, 1)
    scales = scales.reshape(n, -1)
    # exchange: rank r receives chunk r from every peer
    q_x = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=True)
    s_x = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0, tiled=True)
    deq = dequantize_int8(q_x.reshape(n, -1, group_size).reshape(n, -1),
                          s_x.reshape(-1, 1), jnp.float32, group_size).reshape(n, -1)
    return jnp.sum(deq, axis=0)                      # reduced shard of this rank


def quantized_all_gather(shard, axis_name: str = "data", group_size: int = 256,
                         out_dtype=jnp.float32):
    """qwZ-style weight allgather (inside shard_map): quantize the local
    shard, all-gather int8 + scales, dequantize — 4x less gather traffic
    (reference zero_quantized_weights, engine.py:901)."""
    pad = (-shard.size) % group_size
    flat = jnp.concatenate([shard.reshape(-1), jnp.zeros((pad,), shard.dtype)]) \
        if pad else shard.reshape(-1)
    q, scales = quantize_int8(flat, group_size)
    q_all = jax.lax.all_gather(q, axis_name, axis=0, tiled=True)
    s_all = jax.lax.all_gather(scales, axis_name, axis=0, tiled=True)
    full = dequantize_int8(q_all, s_all, out_dtype, group_size)
    if pad:
        n = jax.lax.axis_size(axis_name)
        full = full.reshape(n, -1)[:, :shard.size].reshape(-1)
    return full


def all_to_all_quant_reduce(tensors: List[jnp.ndarray], groups_=None,
                            axis_name: str = "data", group_size: int = 256):
    """Reference-named entry (``:31``): hierarchical quantized gradient
    reduction over a tensor list. Returns per-tensor reduced shards."""
    flat, sizes = _flatten_concat(tensors)
    reduced = quantized_reduce_scatter(flat, axis_name, group_size)
    return reduced, sizes


# ----------------------------------------------------------------------
# In-step ZeRO++ (qwZ weight gather / qgZ grad reduce-scatter), used by the
# engine's shard_map training path. All functions run INSIDE a shard_map
# manual region over `axis_name`.
# ----------------------------------------------------------------------

@jax.named_scope("zero_reduce_scatter")
def quantized_reduce_scatter_along_dim(g, dim: int, axis_name: str = "data",
                                       group_size: int = 256):
    """Reduce-scatter a full-shape cotangent along ``dim`` with an int8 wire
    format (qgZ). Returns this rank's reduced shard (f32)."""
    n = jax.lax.axis_size(axis_name)
    gm = jnp.moveaxis(g, dim, 0)
    lead = gm.shape[0]
    chunks = gm.reshape(n, -1)                       # row i → rank i's shard
    c = chunks.shape[1]
    pad = (-c) % group_size
    if pad:
        chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
    q, scales = quantize_int8(chunks, group_size)    # rows don't cross: C' % gs == 0
    scales = scales.reshape(n, -1)
    q_x = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=True)
    s_x = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0, tiled=True)
    deq = dequantize_int8(q_x, s_x.reshape(-1, 1), jnp.float32, group_size)
    red = jnp.sum(deq, axis=0)
    if pad:
        red = red[:c]
    shard = red.reshape((lead // n,) + gm.shape[1:])
    return jnp.moveaxis(shard, 0, dim)


@jax.named_scope("zero_reduce_scatter")
def reduce_scatter_along_dim(g, dim: int, axis_name: str = "data"):
    """Full-precision reduce-scatter along ``dim`` (psum_scatter)."""
    gm = jnp.moveaxis(g, dim, 0)
    red = jax.lax.psum_scatter(gm, axis_name, scatter_dimension=0, tiled=True)
    return jnp.moveaxis(red, 0, dim)


@jax.named_scope("zero_gather")
def _gather_along_dim(shard, dim: int, axis_name: str, quantized: bool,
                      group_size: int):
    xm = jnp.moveaxis(shard, dim, 0)
    if quantized:
        flat = xm.reshape(-1)
        full_flat = quantized_all_gather(flat, axis_name, group_size, xm.dtype)
        n = jax.lax.axis_size(axis_name)
        full = full_flat.reshape((n * xm.shape[0],) + xm.shape[1:])
    else:
        full = jax.lax.all_gather(xm, axis_name, axis=0, tiled=True)
    return jnp.moveaxis(full, 0, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def zeropp_param_gather(shard, dim: int, axis_name: str = "data",
                        qw: bool = True, qg: bool = True, group_size: int = 256):
    """ZeRO++ parameter gather with gradient reduce-scatter as its VJP.

    Forward (qwZ, reference ``engine.py:901`` zero_quantized_weights): the
    ZeRO-3 param shard is all-gathered along ``dim`` over ``axis_name`` with
    int8 + per-group scales on the wire (4x less gather traffic than fp32).
    Backward (qgZ, reference ``coalesced_collectives.py:31``
    all_to_all_quant_reduce): the full-shape cotangent is reduce-scattered
    back to shards, again int8 on the wire when ``qg``.

    Runs inside a shard_map manual region; straight-through estimator — the
    quantization error is treated as noise, exactly like the reference.
    """
    return _gather_along_dim(shard, dim, axis_name, qw, group_size)


def _zeropp_gather_fwd(shard, dim, axis_name, qw, qg, group_size):
    return _gather_along_dim(shard, dim, axis_name, qw, group_size), None


def _zeropp_gather_bwd(dim, axis_name, qw, qg, group_size, _res, g):
    if qg:
        shard = quantized_reduce_scatter_along_dim(g, dim, axis_name, group_size)
    else:
        shard = reduce_scatter_along_dim(g.astype(jnp.float32), dim, axis_name)
    return (shard.astype(g.dtype),)


zeropp_param_gather.defvjp(_zeropp_gather_fwd, _zeropp_gather_bwd)
