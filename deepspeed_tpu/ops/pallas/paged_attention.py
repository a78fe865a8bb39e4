"""Pallas paged attention: flash attention over in-place KV pages.

Analog of the reference's blocked-flash ragged kernel
(``inference/v2/kernels/ragged_ops/blocked_flash/flash.h``): each sequence's
KV lives scattered across fixed-size pages of a global pool; attention reads
the pages IN PLACE via the block table — the (B, S_max, KVH, D) gathered
cache the XLA fallback materializes never exists.

One algorithm covers BOTH decode (C == 1) and chunked prefill (C > 1) — the
Dynamic-SplitFuse unification: queries are rows of a (C*G, D) tile whose
per-row absolute positions ride in as an int32 block, so per-row causal
masking, sliding windows, and ALiBi (reference blocked-flash handles these
in-kernel too) need no gathered bias tensors; online-softmax state
(m, l, acc) lives in VMEM scratch carried across a sequence's pages; the
block table and per-sequence page bounds are scalar-prefetched
(``pltpu.PrefetchScalarGridSpec``). GQA runs the q-head group of each kv
head as rows of one tile.

It wants different tiles at different widths, and ``_tiling`` picks them
from static shapes against one VMEM budget, but the iteration space is the
same at every width: a slot's LIVE PAGES ``[lo, cs)``, never the table's
width (``_live_pages_kernel``, the one page walk). A grid step walks its
slot's pages in groups, a group's pages copied by hand into one half of a
double buffer while the group before it computes; a slot with no context
copies nothing and goes straight to the chunk's own keys, a window moves
``lo`` and the walk starts there, a ring is the same walk through
``page mod ring``. So a step's cost follows the context it reads:

- few query rows (a decode or speculation step): the work is the bytes of
  the context, so a step takes ALL LOCAL KV HEADS. Grid = (batch,); a page
  is copied once across heads — the whole ``(KVH, page, D)`` block
  ``kv_commit.py`` also moves — 4 pages a group.
- from 128 rows a KV head (a prefill chunk): a head's rows fill the MXU, so
  they are cut into ROW TILES of 128 (``row_tile``) and a step computes,
  against each group of pages and against the chunk's own keys, only the
  tiles that hold a live row: a decoding row riding the wide step pays for
  the one tile its G rows sit in, a prompt's last partial chunk for the
  tiles it reaches, and a slot with no live row copies no page, reads no
  block and writes zeros. A tile's (128, K*bs) f32 scores are what a step
  holds beside its pages, so the group is 8 pages and a step takes as many
  KV heads as fit the budget beside it, on grid (batch, KV heads / heads a
  step). A head's page is one contiguous ``(page, D)`` block.

The latent format (``vpool=None``; ``kv_cache.py``): ONE pool of one "head"
whose row is the key of every query head and whose first ``value_lanes``
lanes are the value. The same walk, with one buffer and one copy a page;
a prefill chunk's query rows (64 heads x 128 positions) are far more than
a step's tiles hold, so the chunk's POSITIONS are tiled over the grid's
second axis, every query head of a few positions a tile
(``_latent_tiling``): the (C, H) order of the queries is the tiles' own, so
nothing is transposed, and a tile none of whose positions is live (all but
the first of a decoding slot riding a wide step, a frozen slot's every one)
walks nothing and writes zeros (``_latent_kernel``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# What one step's tiles may take of a core's VMEM (the compiler's scoped
# default is 16 MiB on v5e; the rest is its own temporaries).
_VMEM_BUDGET = 12 << 20
_MXU_ROWS = 128         # from here on one head's product fills the MXU
_HEAD_PAGES = 8         # most pages a many-rows step groups: 1,024 keys
_FOLD_PAGES = 4         # pages a folded step groups: 8 timed 2-15% slower at
# mistral's 8 KV heads (a short context computes the group's dead keys) and
# does not fit OLMoE's 16 (benchmarks/paged_decode_sweep.py, PERF.md PR 29)


def _step_bytes(heads, rows, keys, d, itemsize, value_lanes=None, tile=None):
    """VMEM a step's tiles take with ``heads`` kv heads of ``rows`` query
    rows against groups of ``keys`` keys, the scores computed ``tile`` rows
    a head at a time (every row at once where it is None). ``value_lanes``:
    the latent format, whose values are lanes of the one buffered row."""
    dv, bufs = (d, 2 * d) if value_lanes is None else (value_lanes, d)
    return (heads * (tile or rows) * keys * (4 + 4 + 2)  # s, exp(s - m), its cast
            + 2 * heads * keys * bufs * itemsize     # K and V, double-buffered
            + heads * rows * (4 * dv + 2 * itemsize * (d + dv)))  # acc; q, out x 2


def row_tile(rows):
    """Rows of the tiles a step cuts a kv head's ``rows`` query rows into,
    to compute only the tiles that hold a live row: ``_MXU_ROWS``, all of
    them where that does not divide them, and None (not cut) while they
    leave the MXU mostly empty."""
    if rows < _MXU_ROWS:
        return None
    return _MXU_ROWS if rows % _MXU_ROWS == 0 else rows


def _tiling(rows, kvh, mb, page_size, d, itemsize):
    """(kv heads a step, pages a group, rows a row tile). A step takes every
    local kv head while a head's rows leave the MXU mostly empty — its cost
    is then the context's bytes and the count of steps — and its tiles fit
    the budget; else the widest group of pages whose score tile (a row
    tile's, ``row_tile``) fits for one head, and as many heads (a divisor
    of the local ones) as fit beside it."""
    def fits(heads, pages, tile=None):
        return _step_bytes(heads, rows, pages * page_size, d, itemsize,
                           tile=tile) <= _VMEM_BUDGET

    if rows < _MXU_ROWS and fits(kvh, _FOLD_PAGES):
        return kvh, min(_FOLD_PAGES, mb), None
    tile = row_tile(rows)
    pages = _HEAD_PAGES
    while pages > 1 and not fits(1, pages, tile):
        pages //= 2
    heads = max(h for h in range(1, kvh + 1)
                if kvh % h == 0 and (h == 1 or fits(h, pages, tile)))
    return heads, min(pages, mb), tile


def _latent_tiling(c, h, mb, page_size, d, value_lanes, itemsize):
    """(tiles of chunk positions, pages a group) of the latent format:
    every query head reads the one pool head, so a step takes every head of
    as many positions as fit the budget beside a group of ``_FOLD_PAGES``
    pages (the one position of a decode step; 8 of a chunk of 128 at 64
    heads: 512 rows), then the widest group that still fits."""
    def fits(rows, pages):
        return _step_bytes(1, rows, pages * page_size, d, itemsize,
                           value_lanes) <= _VMEM_BUDGET

    tiles = next(t for t in range(1, c + 1)
                 if c % t == 0 and (t == c or fits(c * h // t, _FOLD_PAGES)))
    pages = _HEAD_PAGES if c * h // tiles >= _MXU_ROWS else _FOLD_PAGES
    while pages > _FOLD_PAGES and not fits(c * h // tiles, pages):
        pages //= 2
    return tiles, min(pages, mb)


def _scores(q, k, key_pos, pos, win, slope, *, scale, softcap):
    """Masked-score inputs of one key block: ``s`` f32 ([KVH,] R, T) and the
    (R, T) mask of causality and the window. q ([KVH,] R, D), k ([KVH,] T,
    D); key_pos (R, T) or (1, T); pos (R, 1); slope ([KVH,] R, 1) or None."""
    lead = tuple(range(q.ndim - 2))
    if k.shape[-2] == 1:
        # a decode step's chunk holds ONE key. Mosaic lowers q @ k.T
        # with a one-row k through a vector.broadcast that carries the
        # f32 result type on the bf16 q tile, which its own verifier
        # refuses (R = GQA group > 1 rows, bf16; v5e, jax 0.9.0). The
        # same products in f32 on the VPU are exact and cost R*D
        s = jnp.sum(q.astype(jnp.float32) * k.astype(jnp.float32),
                    axis=q.ndim - 1, keepdims=True)
    else:
        s = jax.lax.dot_general(
            q, k, (((q.ndim - 1,), (k.ndim - 1,)), (lead, lead)),
            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if slope is not None:
        rel = (key_pos - pos).astype(jnp.float32)
        s = s + slope * rel.reshape((1,) * len(lead) + rel.shape)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = key_pos <= pos
    mask = jnp.logical_and(mask,
                           jnp.logical_or(win <= 0, key_pos > pos - win))
    return s, mask


def _online_update(m_ref, l_ref, acc_ref, s, mask, v):
    """Fold one key block into the running softmax: s ([KVH,] R, T), mask
    (R, T), v ([KVH,] T, D)."""
    lead = tuple(range(s.ndim - 2))
    s = jnp.where(mask.reshape((1,) * len(lead) + mask.shape), s, NEG_INF)
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=s.ndim - 1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=s.ndim - 1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((s.ndim - 1,), (v.ndim - 2,)), (lead, lead)),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _chunk_and_finalize(m_ref, l_ref, acc_ref, q, ck, cv, kpos, pos, win,
                        slope, *, scale, softcap):
    """The chunk's own keys as a last virtual page, then acc / l."""
    s, mask = _scores(q, ck, kpos, pos, win, slope, scale=scale,
                      softcap=softcap)
    mask = jnp.logical_and(mask, kpos >= 0)               # pad keys dead
    _online_update(m_ref, l_ref, acc_ref, s, mask, cv)
    l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
    return acc_ref[...] / l_safe


def _live_pages_kernel(lyr_ref, bt_ref, cs_ref, lo_ref, win_ref,  # scalar prefetch
                       q_ref, k_hbm, v_hbm, pos_ref, slope_ref,
                       ck_ref, cv_ref, cpos_ref,
                       o_ref,
                       kbuf, vbuf, sems, m_ref, l_ref, acc_ref,
                       *, page_size, pages_per_step, scale, softcap,
                       use_alibi, ring=None, value_lanes=None, slot=None,
                       live_ref=None, row_tile=None):
    """One slot a grid step, with every local kv head at once or, where the
    grid has a second axis, the kv heads that axis names: walk the slot's
    live pages [lo, cs) in groups of K, group g+1's pages on their way into
    the other half of (kbuf, vbuf) while group g computes. The latent format
    (``_latent_kernel``) has no ``v_hbm``, ``cv_ref`` or ``vbuf``: a value
    is the first ``value_lanes`` lanes of its key's row, and the grid's
    slot index comes as ``slot`` (read outside the branch this runs in).

    A many-rows step (``_row_tiles_kernel``: ``live_ref`` (2, B), the
    slot's live query rows [first, end), and ``row_tile``) computes, against
    each group and against the chunk's own keys, only the row tiles that
    hold a live row; the other rows of its output are zeros, and a slot
    with no live row copies no page at all."""
    pools = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))
    if v_hbm is None:
        pools, vbuf = pools[:1], kbuf
    K = pages_per_step
    span = K * page_size
    b = pl.program_id(0) if slot is None else slot
    hs = q_ref.shape[1]                    # kv heads this step takes
    heads = slice(None) if hs == k_hbm.shape[1] \
        else pl.ds(pl.program_id(1) * hs, hs)
    cs = cs_ref[b]
    lo = lo_ref[b]
    first = lo // span
    end = (cs + span - 1) // span          # cs = 0 (a frozen slot): no group
    if live_ref is not None:
        tiles = (live_ref[0, b] // row_tile,
                 (live_ref[1, b] + row_tile - 1) // row_tile)
        end = jnp.where(tiles[0] < tiles[1], end, first)

    def page_copies(g, half, start):
        # a page is copied iff it holds a slot of [lo, cs): whole
        # (heads, page, D) blocks, never part of a page. A group's dead tail
        # keeps what the buffer held before — finite pool data or the zeros
        # below — under keys the staleness mask kills.
        for t in range(K):
            page = g * K + t

            @pl.when(jnp.logical_and(page * page_size < cs,
                                     (page + 1) * page_size > lo))
            def _():
                if not start:
                    src = 0
                elif ring is None:
                    src = bt_ref[b, page]
                else:
                    src = bt_ref[b, page % ring]
                rows = pl.ds(t * page_size, page_size)
                for hbm, buf, which in pools:
                    dma = pltpu.make_async_copy(
                        hbm.at[lyr_ref[0], heads, src], buf.at[half, :, rows],
                        sems.at[which, half])
                    if start:
                        dma.start()
                    else:
                        dma.wait()

    if slot is None:            # the latent kernel cleans outside its branch
        @pl.when(b == 0)
        def _clean():
            # 0 x NaN is NaN: a dead key's probability is exactly 0, so
            # what sits under it in V must be finite
            vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(first < end)
    def _first():
        page_copies(first, 0, True)

    if live_ref is None:
        def on_live_rows(fn):
            fn(q, pos, slope, m_ref, l_ref, acc_ref, None)

        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
    else:
        def on_live_rows(fn):
            """``fn`` on the operands of each row tile that holds a live
            row, the running softmax's as views of the scratch."""
            def tile(t, carry):
                rows = pl.ds(pl.multiple_of(t * row_tile, row_tile), row_tile)
                fn(q_ref[0, :, rows], pos_ref[0, rows],
                   slope_ref[:, rows] if use_alibi else None,
                   m_ref.at[:, rows], l_ref.at[:, rows], acc_ref.at[:, rows],
                   rows)
                return carry

            jax.lax.fori_loop(*tiles, tile, 0)

        def fresh(q, pos, slope, m, l, acc, rows):
            m[...] = jnp.full(m.shape, NEG_INF, m.dtype)
            l[...] = jnp.zeros(l.shape, l.dtype)
            acc[...] = jnp.zeros(acc.shape, acc.dtype)

        o_ref[...] = jnp.zeros_like(o_ref)
        on_live_rows(fresh)

    win = win_ref[0]
    if live_ref is None:
        q = q_ref[0]                                      # (heads, R, D)
        pos = pos_ref[0]                                  # (R, 1) int32
        slope = slope_ref[...] if use_alibi else None     # (heads, R, 1)

    def group(g, carry):
        half = (g - first) % 2

        @pl.when(g + 1 < end)
        def _next():
            page_copies(g + 1, 1 - half, True)

        page_copies(g, half, False)

        def scored(q, pos, slope, m, l, acc, rows):
            slot = g * span + jax.lax.broadcasted_iota(
                jnp.int32, (q.shape[1], span), 1)
            s, mask = _scores(q, kbuf[half], slot, pos, win, slope,
                              scale=scale, softcap=softcap)
            mask = jnp.logical_and(mask, slot < cs)       # stale pool slots
            v = vbuf[half]
            _online_update(m, l, acc, s, mask,
                           v if value_lanes is None else v[..., :value_lanes])

        on_live_rows(scored)
        return carry

    jax.lax.fori_loop(first, end, group, 0)

    def finished(q, pos, slope, m, l, acc, rows):
        out = _chunk_and_finalize(
            m, l, acc, q, ck_ref[0],
            cv_ref[0] if value_lanes is None else ck_ref[0][..., :value_lanes],
            cpos_ref[0, 0].reshape(1, -1), pos, win, slope, scale=scale,
            softcap=softcap)
        if rows is None:
            o_ref[0] = out.astype(o_ref.dtype)
        else:
            o_ref[0, :, rows] = out.astype(o_ref.dtype)

    on_live_rows(finished)


def _row_tiles_kernel(lyr_ref, bt_ref, cs_ref, lo_ref, win_ref, live_ref,
                      *refs, **kw):
    """``_live_pages_kernel`` for a many-rows step by head, whose sixth
    prefetched scalar is each slot's live query rows."""
    _live_pages_kernel(lyr_ref, bt_ref, cs_ref, lo_ref, win_ref, *refs,
                       live_ref=live_ref, **kw)


def _latent_kernel(lyr_ref, bt_ref, cs_ref, lo_ref, win_ref, live_ref,
                   q_ref, k_hbm, pos_ref, slope_ref, ck_ref, cpos_ref, o_ref,
                   kbuf, sems, m_ref, l_ref, acc_ref, *, tile_positions,
                   tiles, **kw):
    """``_live_pages_kernel`` over the latent format's operands, for a tile
    of ``tile_positions`` chunk positions some of which are live
    (``live_ref`` (2, B): the slot's first live chunk index and the one
    past its last); any other tile walks nothing and writes zeros."""
    b = pl.program_id(0)
    first = pl.program_id(1) * tile_positions if tiles > 1 else 0
    live = jnp.logical_and(first < live_ref[1, b],
                           first + tile_positions > live_ref[0, b])

    @pl.when(jnp.logical_and(b == 0, first == 0))
    def _clean():
        # the walk's own cleaning would sit behind ``live``: a value is a
        # lane of the key's buffer, which must be finite under a dead key
        kbuf[...] = jnp.zeros_like(kbuf)

    @pl.when(live)
    def _walk():
        _live_pages_kernel(lyr_ref, bt_ref, cs_ref, lo_ref, win_ref, q_ref,
                           k_hbm, None, pos_ref, slope_ref, ck_ref, None,
                           cpos_ref, o_ref, kbuf, None, sems, m_ref, l_ref,
                           acc_ref, slot=b, **kw)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def paged_ragged_attention(q, kpool, vpool, block_tables, positions,
                           chunk_k=None, chunk_v=None, *, layer=None,
                           scale=None, window=0, alibi_slopes=None,
                           softcap=0.0, ring=None, value_lanes=None,
                           visible_to=None):
    """Unified paged attention for decode AND chunked prefill.

    q: (B, C, H, D) — C query tokens per sequence (1 = decode);
    kpool/vpool: the FULL (L, KVH, NB, bs, D) kv-head-major page pools with
    ``layer`` the (traced) layer index — the kernel chases
    (layer, head, page) directly, so no per-layer pool slice is ever
    materialized. A 4-D (KVH, NB, bs, D) single-layer pool with
    ``layer=None`` is also accepted. The pools are READ-ONLY here and must
    NOT yet contain the current chunk: ``chunk_k``/``chunk_v`` (B, C, KVH,
    D) carry the chunk's own KV, processed as a final virtual page with
    per-key positions = ``positions`` (pool slots >= the chunk's first
    position are treated as stale and masked). This keeps the pool
    loop-invariant across the layer scan — the caller commits all layers'
    chunk KV at once afterwards (``kv_commit.py``). With ``chunk_k=None``
    the pool is taken as ALREADY containing every slot up to each query's
    position (the pre-round-4 contract, kept for the v1 fused-decode path).

    block_tables: (B, MB) int32 page ids; positions: (B, C)
    int32 absolute slot of each query, -1 for padding rows (their outputs
    are garbage the caller discards). Query at slot p attends slots <= p,
    within (p - window, p] when ``window`` > 0; ``alibi_slopes``: (H,)
    per-head slopes applied in-kernel; ``softcap``: Gemma-2 attention-logit
    tanh cap. Returns (B, C, H, D).

    ``ring`` (static): the pages are a ring behind a static ``window`` —
    ``block_tables`` is (B, ring) and position ``p`` lives in page
    ``table[slot, (p // bs) mod ring]`` (``kv_cache.CacheKind``: the ring is
    long enough that every key the window admits is still in it). The walk
    is the same at every width: the window's pages ``[lo, cs)``, each
    through ``mod ring``, whatever the context's length. The kernel is
    named ``paged_attn_ring_c<C>``.

    The latent format: ``vpool`` and ``chunk_v`` None, ``kpool`` (L, 1, NB,
    bs, lanes) and ``chunk_k`` (B, C, 1, lanes) the rows, q (B, C, H, lanes)
    the absorbed queries, ``value_lanes`` (static) the row's leading lanes
    that are its value. Returns (B, C, H, value_lanes); the kernel is named
    ``paged_attn_mla_c<C>``.

    ``visible_to`` (B, C) int32, given: the last key position each query
    sees, in place of its own (a model that attends causally BY BLOCK hands
    in the last position of the query's block; -1 at a pad). The keys keep
    their true positions and the chunk its true start: the kernel's one
    comparison ``key <= pos`` reads another operand. Neither a window nor
    ALiBi, which measure from the query's own position, goes with it.
    """
    latent = vpool is None
    assert latent == (value_lanes is not None) and not (latent and ring)
    if ring is not None:
        assert block_tables.shape[1] == ring and isinstance(window, int) \
            and 0 < window <= (ring - 1) * kpool.shape[-2], (ring, window)
    if kpool.ndim == 4:
        kpool = kpool[None]
        vpool = None if latent else vpool[None]
        layer = 0
    b, c, h, d = q.shape
    _, kvh, nb, page_size, _ = kpool.shape
    see = positions
    if visible_to is not None:
        assert not window and alibi_slopes is None and ring is None
        see = visible_to
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    mb = block_tables.shape[1]
    pool_heads, dv = kvh, d
    if latent:
        # tiles of chunk positions stand where the kv heads stand below,
        # each reading the pool's one head
        kvh, K = _latent_tiling(c, h, mb, page_size, d, value_lanes,
                                kpool.dtype.itemsize)
        hs, dv, rows = 1, value_lanes, c * h // kvh
        assert alibi_slopes is None
    else:
        group = h // kvh
        rows = c * group
    scale = float(scale if scale is not None else d ** -0.5)
    if window is None:
        window = 0
    softcap = float(softcap or 0.0)

    tile = None
    if not latent:
        hs, K, tile = _tiling(rows, kvh, mb, page_size, d,
                              kpool.dtype.itemsize)
    # a step takes ``hs`` kv heads: all of them on grid (slots,), or a
    # share on grid (slots, kv heads / hs)
    split = hs < kvh

    if latent:
        # (B, C, H, D) as it lies: tile t holds positions [t C/T, (t+1) C/T)
        # with every head, row r = c*H + h of the tile
        qg = q.reshape(b, kvh, rows, d)
        pos_rep = jnp.repeat(see, h, axis=1).reshape(b, kvh * rows, 1)
    else:
        # (B, C, H, D) → (B, KVH, C*G, D): row r = c*G + g
        qg = q.reshape(b, c, kvh, group, d).transpose(0, 2, 1, 3, 4).reshape(
            b, kvh, rows, d)
        # per-row positions: row r = c*G + g sits at positions[c]
        pos_rep = jnp.repeat(see, group, axis=1).reshape(b, rows, 1)
    valid = positions >= 0
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    minpos = jnp.min(jnp.where(valid, positions, 1 << 30), axis=1)
    if chunk_k is not None:
        # chunk KV → (B, KVH, C, D) blocks + (B, 1, C) key positions;
        # pool is valid only BELOW the chunk's first position
        ckg = chunk_k.astype(q.dtype).transpose(0, 2, 1, 3)
        cvg = None if latent else chunk_v.astype(q.dtype).transpose(0, 2, 1, 3)
        cpos = positions.reshape(b, 1, c)
        # fully-padded rows have no valid positions: zero pages, not 2^30
        chunk_start = jnp.where(minpos == 1 << 30, 0, minpos).astype(jnp.int32)
    else:
        # pool already holds every slot <= pos; dead chunk blocks
        ckg = jnp.zeros((b, pool_heads, c, d), q.dtype)
        cvg = None if latent else ckg
        cpos = jnp.full((b, 1, c), -1, jnp.int32)
        chunk_start = (jnp.max(jnp.where(valid, positions, -1), axis=1)
                       + 1).astype(jnp.int32)
    lo = jnp.where(win_arr[0] > 0,
                   jnp.maximum(minpos - win_arr[0] + 1, 0),
                   0).astype(jnp.int32)

    use_alibi = alibi_slopes is not None
    if use_alibi:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(kvh, group)
        slopes = jnp.tile(sl, (1, c)).reshape(kvh, rows, 1)
    else:
        slopes = jnp.zeros((kvh, rows, 1), jnp.float32)

    scalars = (lyr, block_tables, chunk_start, lo, win_arr)
    if latent or tile:
        # the live chunk indices [first, end) of each slot (consecutive:
        # ``kv_commit``'s contract too); by head, as query rows
        n_live = jnp.sum(valid, axis=1, dtype=jnp.int32)
        first = jnp.argmax(valid, axis=1).astype(jnp.int32)
        live = jnp.stack([first, first + n_live])
        scalars += (live if latent else live * group,)

    def head_of(idx):
        return idx[0] if split else 0

    def read_at(bi, idx):
        """(slot, head) of the blocks a step reads: its own, but for a step
        by row tiles of a slot with no live row, which reads nothing and
        names block (0, 0) so that a run of such steps fetches nothing."""
        if not tile:
            return bi, head_of(idx)
        on = idx[-1][1, bi] > idx[-1][0, bi]
        return jnp.where(on, bi, 0), jnp.where(on, head_of(idx), 0)

    def slot_map(bi, *idx):
        return (*read_at(bi, idx), 0, 0)

    def out_map(bi, *idx):
        return (bi, head_of(idx), 0, 0)

    def row_map(bi, *idx):
        return (read_at(bi, idx)[0], 0, 0)

    def row_map_4(bi, *_):
        return (bi, 0, 0, 0)

    def head_map(bi, *idx):
        # the slopes' block never changes where no step reads it
        return (read_at(bi, idx)[1] if use_alibi or not tile else 0, 0, 0)

    def tile_map(bi, *idx):
        return (bi, head_of(idx), 0)

    # the latent format's every tile reads the chunk's one head of rows and
    # has its own positions
    chunk_map = row_map_4 if latent else slot_map
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    chunk_spec = pl.BlockSpec((1, hs, c, d), chunk_map)
    buffer = pltpu.VMEM((2, hs, K * page_size, d), kpool.dtype)
    kernel, name, both = _live_pages_kernel, "paged_attn", [True, True]
    if tile:
        kernel = functools.partial(_row_tiles_kernel, row_tile=tile)
    if latent:
        kernel, name, both = functools.partial(
            _latent_kernel, tile_positions=c // kvh, tiles=kvh), \
            "paged_attn_mla", [True, False]
    elif ring is not None:
        name = "paged_attn_ring"

    def pools(k, v):
        """``k`` and, but for the latent format, ``v`` behind it."""
        return [x for x, keep in zip((k, v), both) if keep]

    out = pl.pallas_call(
        functools.partial(
            kernel, page_size=page_size, pages_per_step=K,
            scale=scale, softcap=softcap, use_alibi=use_alibi, ring=ring,
            **({"value_lanes": value_lanes} if latent else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, kvh // hs) if split else (b,),
            in_specs=[
                pl.BlockSpec((1, hs, rows, d), slot_map),
                *pools(pool_spec, pool_spec),
                pl.BlockSpec((1, rows, 1), tile_map if latent else row_map),
                pl.BlockSpec((hs, rows, 1), head_map),
                *pools(chunk_spec, chunk_spec),
                pl.BlockSpec((1, 1, c), row_map),
            ],
            out_specs=pl.BlockSpec((1, hs, rows, dv), out_map),
            scratch_shapes=[
                *pools(buffer, buffer),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hs, rows, 1), jnp.float32),        # m
                pltpu.VMEM((hs, rows, 1), jnp.float32),        # l
                pltpu.VMEM((hs, rows, dv), jnp.float32),       # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, dv), q.dtype),
        name=f"{name}_c{c}",
        interpret=jax.default_backend() != "tpu",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * (1 + split)),
    )(*scalars,
      qg, *pools(kpool, vpool), pos_rep, slopes, *pools(ckg, cvg), cpos)
    if latent:
        return out.reshape(b, c, h, dv)
    # (B, KVH, C*G, D) → (B, C, H, D)
    return out.reshape(b, kvh, c, group, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, dv)


def paged_decode_attention(q, kpool, vpool, block_tables, seq_lens, *,
                           scale=None, window=0, alibi_slopes=None,
                           softcap=0.0):
    """Single-token decode wrapper: q (B, H, D), seq_lens (B,) tokens in
    each sequence INCLUDING the one being decoded. Returns (B, H, D)."""
    positions = (seq_lens - 1).astype(jnp.int32)[:, None]      # (B, 1)
    out = paged_ragged_attention(q[:, None], kpool, vpool, block_tables,
                                 positions, scale=scale, window=window,
                                 alibi_slopes=alibi_slopes, softcap=softcap)
    return out[:, 0]
