"""Block-sparse flash attention — the splash-kernel analog.

Analog of the reference's block-sparse attention kernels
(``deepspeed/ops/sparse_attention/`` Triton matmul/softmax over a block
layout; ``csrc/sparse_attention/utils.cpp``): attention cost scales with
the number of ACTIVE blocks, not S². The sparsity layout (a boolean
(S/block, S/block) grid from ``SparsityConfig.make_layout``) is compiled,
per kernel query tile, into

- a scalar-prefetched table of active key tiles + counts, so the Pallas
  grid only DMAs and computes live tiles (``pl.when`` retires padding
  slots), and
- precomputed per-tile token masks (causality folded in), applied inside
  the kernel for exact parity with the dense masked form.

Forward kernel only: the custom_vjp backward recomputes the dense masked
attention (correct, O(S²) — the reference trains BERT-era models where
that is acceptable; the fwd kernel is the inference/latency win).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TILE_Q = 128
TILE_K = 128


def compile_layout_tables(layout: np.ndarray, layout_block: int,
                          causal: bool):
    """Coarsen the fine (n, n) layout to kernel tiles.

    Returns (table (QT, MA) int32 — active key tiles per query tile, padded;
    counts (QT,) int32; masks (QT, MA, TILE_Q, TILE_K) f32 0/1 — exact token
    mask per live tile with causality folded in)."""
    n = layout.shape[0]
    s = n * layout_block
    if s % TILE_Q or s % TILE_K:
        raise ValueError(f"seq {s} not divisible by kernel tiles")
    token = np.repeat(np.repeat(layout.astype(bool), layout_block, 0),
                      layout_block, 1)
    if causal:
        token &= np.tril(np.ones((s, s), bool))
    qt, kt = s // TILE_Q, s // TILE_K
    tiled = token.reshape(qt, TILE_Q, kt, TILE_K).transpose(0, 2, 1, 3)
    coarse = tiled.any(axis=(2, 3))                 # (QT, KT)
    counts = coarse.sum(axis=1).astype(np.int32)
    ma = max(1, int(counts.max()))
    table = np.zeros((qt, ma), np.int32)
    masks = np.zeros((qt, ma, TILE_Q, TILE_K), np.float32)
    for i in range(qt):
        active = np.nonzero(coarse[i])[0]
        table[i, :len(active)] = active
        for j, ki in enumerate(active):
            masks[i, j] = tiled[i, ki]
    return table, counts, masks


def _kernel(table_ref, counts_ref,                  # scalar prefetch
            q_ref, k_ref, v_ref, mask_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, max_active, scale):
    qi = pl.program_id(2)
    ji = pl.program_id(3)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ji < counts_ref[qi])
    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_ref[0, 0] > 0, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ji == max_active - 1)
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _dense_reference(q, k, v, token_mask, scale):
    """Dense masked attention over (B, H, S, D) — the backward-pass form."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(token_mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


class _LayoutCache:
    """layout bytes → compiled (table, counts, masks, token_mask)."""

    def __init__(self):
        self._store = {}

    def get(self, layout: np.ndarray, layout_block: int, causal: bool):
        key = (layout.tobytes(), layout.shape, layout_block, causal)
        if key not in self._store:
            table, counts, masks = compile_layout_tables(layout, layout_block,
                                                         causal)
            token = np.repeat(np.repeat(layout.astype(bool), layout_block, 0),
                              layout_block, 1)
            if causal:
                token &= np.tril(np.ones(token.shape, bool))
            self._store[key] = (table, counts, masks, token)
        return self._store[key]


_LAYOUTS = _LayoutCache()


def _fwd_kernel_call(qb, kb, vb, table, counts, masks, *, ma, scale):
    """Tables/masks are RUNTIME arguments (device arrays), not closure
    constants — baked constants blow past compile-payload limits at long S."""
    b, h, s, d = qb.shape
    qt = masks.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, max_active=ma, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, qt, ma),
            in_specs=[
                pl.BlockSpec((1, 1, TILE_Q, d),
                             lambda bi, hi, qi, ji, t, c: (bi, hi, qi, 0)),
                pl.BlockSpec((1, 1, TILE_K, d),
                             lambda bi, hi, qi, ji, t, c: (bi, hi, t[qi, ji], 0)),
                pl.BlockSpec((1, 1, TILE_K, d),
                             lambda bi, hi, qi, ji, t, c: (bi, hi, t[qi, ji], 0)),
                pl.BlockSpec((1, 1, TILE_Q, TILE_K),
                             lambda bi, hi, qi, ji, t, c: (qi, ji, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, TILE_Q, d),
                                   lambda bi, hi, qi, ji, t, c: (bi, hi, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((TILE_Q, 1), jnp.float32),
                pltpu.VMEM((TILE_Q, 1), jnp.float32),
                pltpu.VMEM((TILE_Q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, qt * TILE_Q, d), qb.dtype),
        name="sparse_flash_fwd",
        interpret=jax.default_backend() != "tpu",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
    )(table, counts, qb, kb, vb, masks)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _sparse_attn(qb, kb, vb, table, counts, masks, ma, scale, layout_block):
    return _fwd_kernel_call(qb, kb, vb, table, counts, masks, ma=ma, scale=scale)


def _sparse_attn_fwd(qb, kb, vb, table, counts, masks, ma, scale, layout_block):
    out = _sparse_attn(qb, kb, vb, table, counts, masks, ma, scale, layout_block)
    return out, (qb, kb, vb, masks, table, counts)


def _sparse_attn_bwd(ma, scale, layout_block, res, g):
    qb, kb, vb, masks, table, counts = res
    qt = masks.shape[0]
    s = qt * TILE_Q
    # reassemble the (S, S) token mask from the per-tile masks (in-graph, so
    # no giant constant rides the executable)
    full = jnp.zeros((qt, s // TILE_K, TILE_Q, TILE_K), jnp.float32)
    ji = jnp.arange(ma)
    valid = ji[None, :] < counts[:, None]                      # (QT, MA)
    qidx = jnp.broadcast_to(jnp.arange(qt)[:, None], (qt, ma)).reshape(-1)
    kidx = table.reshape(-1)
    contrib = jnp.where(valid.reshape(-1)[:, None, None], masks.reshape(-1, TILE_Q, TILE_K), 0.0)
    full = full.at[qidx, kidx].add(contrib)
    token_mask = full.transpose(0, 2, 1, 3).reshape(s, s) > 0

    def f(q_, k_, v_):
        return _dense_reference(q_, k_, v_, token_mask, scale)

    _, vjp = jax.vjp(f, qb, kb, vb)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None, None


_sparse_attn.defvjp(_sparse_attn_fwd, _sparse_attn_bwd)


def precompile_layout(layout, layout_block: int, causal: bool = False):
    """Host-side layout compilation: returns (table, counts, masks) device
    arrays to pass to ``sparse_flash_attention(..., tables=...)`` when the
    call sits inside an outer jit — passing them as runtime arguments keeps
    multi-MB mask tensors out of the compile payload."""
    table, counts, masks, _ = _LAYOUTS.get(np.asarray(layout, bool),
                                           layout_block, causal)
    return (jnp.asarray(table), jnp.asarray(counts),
            jnp.asarray(masks))


def sparse_flash_attention(q, k, v, layout=None, *, layout_block: int,
                           scale=None, causal: bool = False, tables=None):
    """Block-sparse attention with a block-skipping fwd kernel.

    q/k/v: (B, S, H, D); layout: (S/layout_block,)² bool numpy array — or
    pass ``tables=precompile_layout(...)`` (required under an outer jit).
    GQA repeats KV heads. Sequences shorter than one kernel tile fall back
    to the dense masked form.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    scale = float(scale if scale is not None else d ** -0.5)
    qb = jnp.swapaxes(q, 1, 2)
    kb = jnp.swapaxes(k, 1, 2)
    vb = jnp.swapaxes(v, 1, 2)
    if tables is None:
        layout = np.asarray(layout, bool)
        if s % TILE_Q or s < TILE_Q:
            token = np.repeat(np.repeat(layout, layout_block, 0),
                              layout_block, 1)
            if causal:
                token &= np.tril(np.ones((s, s), bool))
            out = _dense_reference(qb, kb, vb, jnp.asarray(token), scale)
            return jnp.swapaxes(out, 1, 2)
        tables = precompile_layout(layout, layout_block, causal)
    table, counts, masks = tables
    ma = table.shape[1]
    out = _sparse_attn(qb, kb, vb, table, counts, masks, ma, scale,
                       layout_block)
    return jnp.swapaxes(out, 1, 2)
