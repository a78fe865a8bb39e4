"""Attention op dispatch.

Single call site for all models: picks the best implementation for the
platform (Pallas flash attention on TPU, fused-einsum reference path on CPU),
the way the reference routes attention through op builders
(``deepspeed/ops/transformer/inference/ds_attention.py``).

Ulysses sequence parallelism (reference ``deepspeed/sequence/layer.py:145``)
is expressed here as sharding constraints: activations arrive sequence-sharded
``P(batch, 'seq', ...)``; constraining q/k/v to head-sharded
``P(batch, None, 'seq', None)`` makes XLA emit exactly the all-to-all that
``_SeqAllToAll`` hand-codes, riding ICI.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils import groups


def _use_pallas() -> bool:
    import os
    if os.environ.get("DS_TPU_DISABLE_PALLAS", "0") == "1":
        return False
    return jax.default_backend() == "tpu"


def _flash_shape_ok(s: int, d: int) -> bool:
    """Shapes the flash kernel tiles: whole 128-row tiles, and a sequence
    its (at most 512-wide) blocks divide."""
    from .pallas.flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
    return (s >= 128 and s % 128 == 0 and d in (64, 128, 256)
            and s % min(DEFAULT_BLOCK_Q, s) == 0
            and s % min(DEFAULT_BLOCK_K, s) == 0)


def _flash(mesh, q, k, v, *, causal, segment_ids, scale, alibi_slopes, window):
    """The flash kernel, on one device or many. XLA's SPMD pass cannot
    partition a Mosaic call ("wrap the call in a shard_map"), so on a mesh
    of more than one device the kernel runs under ``shard_map``: batch over
    the data-like axes, heads over the axes that shard them (Ulysses' head
    exchange, tensor-parallel weights) where those divide the head counts —
    a dim an axis does not divide is gathered and computed whole. Inside a
    manual region (ZeRO++ step, pipeline stages) the caller's ``shard_map``
    already made the operands local."""
    from ..parallel.sharding import current_manual_axes
    from .pallas.flash_attention import flash_attention

    def local(q, k, v, seg, slopes):
        return flash_attention(q, k, v, causal=causal, segment_ids=seg,
                               scale=scale, alibi_slopes=slopes, window=window)

    if mesh is None or mesh.devices.size == 1 or current_manual_axes():
        return local(q, k, v, segment_ids, alibi_slopes)

    def axes_dividing(names, *dims):
        names = tuple(a for a in names if mesh.shape[a] > 1)
        n = math.prod(mesh.shape[a] for a in names)
        return names if names and not any(d % n for d in dims) else None

    batch = axes_dividing(groups.BATCH_AXES, q.shape[0])
    heads = axes_dividing(("seq", "tensor"), q.shape[2], k.shape[2])
    qkv = P(batch, None, heads, None)
    # absent operands are empty pytrees: their spec matches no leaf
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(batch, None), P(heads)),
        out_specs=qkv, check_vma=False)(q, k, v, segment_ids, alibi_slopes)


def window_mask(q_pos, k_pos, window):
    """Sliding-window visibility: key k is visible to query q iff
    q - k < window; window may be traced, and window <= 0 means global
    (the sentinel per-layer local/global patterns scan over). Single source
    of the convention for all three attention engines."""
    w = jnp.asarray(window, jnp.int32)
    return (q_pos - k_pos < w) | (w <= 0)


def reference_attention(q, k, v, *, causal=True, bias=None, segment_ids=None, scale=None,
                        window=None, softcap=0.0):
    """Plain XLA attention: (B, S, H, D) x (B, S, KVH, D) -> (B, S, H, D).

    Handles GQA by repeating kv heads. fp32 softmax for stability.
    ``window``: sliding-window width — query q sees keys in (q-window, q].
    May be a traced scalar (per-layer local/global patterns under scan);
    window <= 0 means global.
    ``softcap``: Gemma-2 attention-logit softcapping, applied to the scaled
    logits (+ bias) BEFORE masking, matching HF's order.
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    sk = k.shape[1]
    if causal or window is not None:
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        k_pos = jnp.arange(sk)[None, :]
        mask = q_pos >= k_pos if causal else jnp.ones((sq, sk), bool)
        if window is not None:
            mask = mask & window_mask(q_pos, k_pos, window)
        logits = jnp.where(mask[None, None, :, :], logits, jnp.finfo(jnp.float32).min)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]  # (B, Sq, Sk)
        logits = jnp.where(seg_mask[:, None, :, :], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _alibi_bias_from_slopes(slopes, sq, sk):
    """(H,) slopes → (1, H, Sq, Sk) additive bias (XLA fallback paths)."""
    q_pos = jnp.arange(sq) + (sk - sq)
    k_pos = jnp.arange(sk)
    rel = (k_pos[None, :] - q_pos[:, None]).astype(jnp.float32)
    return (jnp.asarray(slopes, jnp.float32)[:, None, None] * rel)[None]


def _reference_with_slopes(q, k, v, causal, bias, alibi_slopes, segment_ids,
                           scale, window, softcap=0.0):
    """Single fallback entry: expand ALiBi slopes to a bias and run the XLA
    reference path (keeps the expansion in exactly one place)."""
    if alibi_slopes is not None and bias is None:
        bias = _alibi_bias_from_slopes(alibi_slopes, q.shape[1], k.shape[1])
    return reference_attention(q, k, v, causal=causal, bias=bias,
                               segment_ids=segment_ids, scale=scale,
                               window=window, softcap=softcap)


def _ulysses_exchange(mesh, q, k, v, local_attn):
    """The Ulysses head/seq exchange around a local attention computation.

    Under plain SPMD jit, ``with_sharding_constraint`` pins q/k/v to
    head-sharded and the output back to seq-sharded; XLA derives the two
    all-to-alls from the spec flip (reference ``sequence/layer.py:145``
    hand-codes them as ``_SeqAllToAll``).

    Inside a partial-manual shard_map region (the ZeRO++ quantized-collective
    step is manual over the data-like axes) the ``seq`` axis is Auto-typed
    and sharding constraints may not mention it — there the exchange is
    expressed with sharding-in-types: ``explicit_axes`` locally retypes
    ``seq`` Explicit, ``reshard`` forces the seq->head all-to-all, the local
    attention runs back under ``auto_axes`` (so attention impls need no
    explicit-mode sharding rules), and a second ``reshard`` forces the
    head->seq all-to-all out.
    """
    head_spec = P(groups.BATCH_AXES, None, "seq", None)
    out_spec = P(groups.BATCH_AXES, "seq", None, None)

    from ..parallel.sharding import current_manual_axes
    if not current_manual_axes():
        def pin(x, spec):
            return jax.lax.with_sharding_constraint(x, jax.NamedSharding(mesh, spec))
        out = local_attn(pin(q, head_spec), pin(k, head_spec), pin(v, head_spec))
        return pin(out, out_spec)

    seq_in = P(None, "seq", None, None)
    head = P(None, None, "seq", None)

    def inner(q, k, v):
        q, k, v = (jax.sharding.reshard(x, head) for x in (q, k, v))
        out = jax.sharding.auto_axes(local_attn, axes=("seq",),
                                     out_sharding=head)(q, k, v)
        return jax.sharding.reshard(out, seq_in)

    return jax.sharding.explicit_axes(
        inner, axes=("seq",), in_sharding=(seq_in, seq_in, seq_in))(q, k, v)


def multihead_attention(q, k, v, *, causal=True, bias=None, segment_ids=None, scale=None,
                        window=None, alibi_slopes=None, impl: Optional[str] = None,
                        softcap=0.0):
    """Dispatching attention entry point.

    q: (B, S, H, D); k/v: (B, S, KVH, D). Returns (B, S, H, D).
    impl: None (auto) | "reference" | "flash" | "ulysses"
    window: sliding-window width (Mistral/GPT-Neo local attention). A
    static int >= S is a no-op (dropped so flash stays eligible); a traced
    scalar or a binding window routes to the reference path.
    alibi_slopes: (H,) per-head ALiBi slopes — handled IN-KERNEL by the
    flash path (no O(S^2) bias tensor); expanded to a bias only for the
    XLA fallback. Treated as non-differentiable constants. Mutually
    exclusive with an explicit ``bias``.
    """
    if bias is not None and alibi_slopes is not None:
        raise ValueError(
            "pass either an explicit additive bias or alibi_slopes, not "
            "both (the slopes would be silently dropped)")
    if isinstance(window, int) and (window >= q.shape[1] or window <= 0):
        window = None   # cannot bind (or the <=0 "global" sentinel)
    mesh = groups.get_mesh() if groups.mesh_is_initialized() else None
    seq_sharded = mesh is not None and mesh.shape.get("seq", 1) > 1

    if impl == "ring":
        from ..sequence.ring_attention import ring_attention
        if not causal:
            raise NotImplementedError("ring attention is causal-only")
        if seq_sharded:
            if bias is not None or softcap:
                raise NotImplementedError(
                    "ring attention takes ALiBi as slopes (not an explicit "
                    "bias tensor) and has no logit softcapping; use Ulysses "
                    "SP or attn_impl='reference'")
            return ring_attention(q, k, v, scale=scale, window=window,
                                  alibi_slopes=alibi_slopes,
                                  segment_ids=segment_ids)
        # no seq axis: plain local attention
        return _reference_with_slopes(q, k, v, causal, bias, alibi_slopes,
                                      segment_ids, scale, window, softcap)

    # flash handles static-int causal windows in-kernel (block skipping);
    # traced per-layer windows (scan over local/global patterns) cannot be
    # static and stay on the reference path
    flash_window_ok = window is None or (isinstance(window, int) and causal)
    if impl == "flash" and (bias is not None or not flash_window_ok or softcap):
        raise NotImplementedError(
            "the Pallas flash kernel does not take an additive attention "
            "bias tensor, a traced/non-causal sliding window, or logit "
            "softcapping; use attn_impl='reference' (auto dispatch already "
            "routes these there)")

    def dispatch(q, k, v):
        # shape-based choice only: a kernel that was chosen and then fails
        # to compile or run raises — it never drops to the O(S^2) path
        if impl == "flash" or (impl is None and _use_pallas()
                               and _flash_shape_ok(q.shape[1], q.shape[3])
                               and bias is None and not softcap
                               and flash_window_ok):
            return _flash(mesh, q, k, v, causal=causal, segment_ids=segment_ids,
                          scale=scale, alibi_slopes=alibi_slopes, window=window)
        return _reference_with_slopes(q, k, v, causal, bias, alibi_slopes,
                                      segment_ids, scale, window, softcap)

    if seq_sharded:
        # Ulysses: swap sequence-sharding for head-sharding around the local
        # attention; the exchange lowers to all-to-all over the seq axis.
        return _ulysses_exchange(mesh, q, k, v, dispatch)
    return dispatch(q, k, v)


def decode_attention(q, k_cache, v_cache, cache_len, *, bias=None, scale=None,
                     window=None, softcap=0.0):
    """Decode/prefill attention against a (B, S_max, KVH, D) KV cache.

    q: (B, S_new, H, D) — the S_new query tokens occupy cache slots
    [cache_len - S_new, cache_len); each query attends causally: key slot k
    is visible to query i iff k < cache_len - S_new + i + 1.
    bias: optional additive (B, H, S_new, S_max) attention bias (ALiBi);
    bias routes around the fused Pallas kernel.
    window: sliding-window width (query at slot p sees slots (p-window, p]);
    may be traced, <= 0 means global.

    Single-token decode (S_new == 1) over a LONG cache routes through the
    fused Pallas kernel (``ops/pallas/decode_attention.py`` — the v1
    fused-decode analog of the reference's ``softmax_context``), which never
    materializes the (B, H, S_max) logits. Both forms are HBM-bound
    streaming the cache, so the crossover is late (measured ≥8k on v5e);
    shorter caches and prefill chunks use the batched XLA einsum below.
    """
    b, s_new, h, d = q.shape
    if isinstance(window, int) and window >= k_cache.shape[1]:
        window = None   # cannot bind within this cache
    if (s_new == 1 and bias is None and window is None and not softcap
            and _use_pallas()
            and k_cache.shape[1] >= 8192
            and k_cache.shape[1] % 128 == 0 and d % 64 == 0
            and h % k_cache.shape[2] == 0):
        from .pallas.decode_attention import fused_decode_attention
        block = min(512, k_cache.shape[1])
        if k_cache.shape[1] % block:
            block = 128
        out = fused_decode_attention(q[:, 0], k_cache, v_cache, cache_len,
                                     scale=scale, block=block)
        return out[:, None]
    kvh = k_cache.shape[2]
    if kvh != h:
        rep = h // kvh
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    q_pos = (cache_len[:, None] - s_new) + jnp.arange(s_new)[None, :]      # (B, S_new)
    k_pos = jnp.arange(k_cache.shape[1])[None, None, :]                    # (1, 1, S_max)
    mask = k_pos <= q_pos[:, :, None]                                      # (B, S_new, S_max)
    if window is not None:
        mask = mask & window_mask(q_pos[:, :, None], k_pos, window)
    logits = jnp.where(mask[:, None, :, :], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)
