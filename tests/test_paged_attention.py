"""Pallas paged decode attention vs the XLA gather reference."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention


def _reference(q, kpool, vpool, tables, lens):
    """Gather pages → masked softmax attention. q: (B,H,D);
    kpool: (KVH,NB,bs,D)."""
    kvh, nb, bs, d = kpool.shape
    b, h, _ = q.shape
    kp = kpool[:, tables]                    # (KVH, B, MB, bs, D)
    kp = kp.reshape(kvh, b, -1, d).transpose(1, 0, 2, 3)   # (B, KVH, S, D)
    vp = vpool[:, tables].reshape(kvh, b, -1, d).transpose(1, 0, 2, 3)
    group = h // kvh
    kp = jnp.repeat(kp, group, axis=1)
    vp = jnp.repeat(vp, group, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q, kp, preferred_element_type=jnp.float32)
    s = s * (d ** -0.5)
    slot = jnp.arange(kp.shape[2])[None, None, :]
    s = jnp.where(slot < lens[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bhkd->bhd", p, vp)


@pytest.mark.parametrize("h,kvh,d", [(4, 4, 64), (8, 2, 64), (4, 1, 128)])
def test_paged_decode_matches_gather(h, kvh, d):
    b, bs, nb, mb = 3, 16, 12, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32) * 0.1
    kpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    # distinct physical pages per sequence; lengths not page-aligned
    tables = jnp.asarray(rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32)
    lens = jnp.asarray([5, 16 * 2 + 3, 16 * 4], jnp.int32)

    out = paged_decode_attention(q, kpool, vpool, tables, lens)
    ref = _reference(q, kpool, vpool, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_decode_under_jit_and_donation():
    b, h, kvh, d, bs, nb, mb = 2, 4, 2, 64, 8, 6, 3
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32) * 0.1
    kpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    lens = jnp.asarray([20, 9], jnp.int32)
    f = jax.jit(paged_decode_attention)
    out = f(q, kpool, vpool, tables, lens)
    ref = _reference(q, kpool, vpool, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_fused_contiguous_decode_matches_xla():
    """Fused single-token decode over a contiguous cache (the v1
    softmax_context analog) matches the masked XLA form."""
    from deepspeed_tpu.ops.pallas.decode_attention import fused_decode_attention
    import deepspeed_tpu.ops.attention as att
    rng = np.random.default_rng(3)
    B, S, H, KVH, D = 4, 256, 8, 4, 64
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    cl = jnp.asarray(rng.integers(10, S, (B,)), jnp.int32)
    orig = att._use_pallas
    att._use_pallas = lambda: False
    try:
        ref = att.decode_attention(q, k, v, cl)
    finally:
        att._use_pallas = orig
    out = fused_decode_attention(q[:, 0], k, v, cl, block=128)[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


# ---- unified ragged kernel: prefill chunks, windows, ALiBi, softcap ------

def _ragged_reference(q, kpool, vpool, tables, positions, *, window=0,
                      alibi_slopes=None, softcap=0.0, scale=None):
    """Gather-pages reference for the unified kernel: q (B,C,H,D),
    positions (B,C) absolute slots (-1 pad)."""
    kvh, nb, bs, d = kpool.shape
    b, c, h, _ = q.shape
    kp = kpool[:, tables].reshape(kvh, b, -1, d).transpose(1, 0, 2, 3)
    vp = vpool[:, tables].reshape(kvh, b, -1, d).transpose(1, 0, 2, 3)
    group = h // kvh
    kp = jnp.repeat(kp, group, axis=1)
    vp = jnp.repeat(vp, group, axis=1)
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bchd,bhkd->bhck", q, kp,
                   preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(kp.shape[2])[None, None, None, :]        # (1,1,1,S)
    pos = positions[:, None, :, None].astype(jnp.float32)      # (B,1,C,1)
    if alibi_slopes is not None:
        s = s + jnp.asarray(alibi_slopes, jnp.float32)[None, :, None, None] \
            * (slot - pos)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = slot <= pos
    if window:
        mask = mask & (slot > pos - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhck,bhkd->bchd", p, vp)


def _ragged_case(c=4, h=4, kvh=2, d=64, **kw):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    b, bs, nb, mb = 2, 16, 10, 4
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, c, h, d)), jnp.float32) * 0.1
    kpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32)
    # chunk positions: seq 0 prefilling slots 17..17+c-1; seq 1 decode-ish
    # near its end with padding rows
    pos0 = 17 + np.arange(c)
    pos1 = np.concatenate([[40, 41], -np.ones(max(0, c - 2))])[:c]
    positions = jnp.asarray(np.stack([pos0, pos1]), jnp.int32)
    out = paged_ragged_attention(q, kpool, vpool, tables, positions, **kw)
    ref = _ragged_reference(q, kpool, vpool, tables, positions, **kw)
    valid = np.asarray(positions) >= 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               rtol=3e-5, atol=3e-5)


def test_paged_ragged_prefill_causal():
    _ragged_case()


def test_paged_ragged_prefill_window():
    _ragged_case(window=8)


def test_paged_ragged_traced_window():
    """Per-layer window patterns reach the kernel as traced scalars."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention

    def run(win):
        b, c, h, kvh, d, bs, nb, mb = 2, 2, 4, 2, 64, 16, 10, 4
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((b, c, h, d)), jnp.float32) * 0.1
        kpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
        vpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
        tables = jnp.asarray(rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32)
        positions = jnp.asarray([[30, 31], [12, 13]], jnp.int32)
        out = paged_ragged_attention(q, kpool, vpool, tables, positions,
                                     window=win)
        ref = _ragged_reference(q, kpool, vpool, tables, positions,
                                window=int(win))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    for w in (jnp.asarray(6, jnp.int32), jnp.asarray(0, jnp.int32)):
        run(w)


def test_paged_ragged_alibi():
    from deepspeed_tpu.models.layers import alibi_slopes
    _ragged_case(h=4, kvh=4, alibi_slopes=alibi_slopes(4))


def test_paged_ragged_softcap_and_scale():
    _ragged_case(softcap=30.0, scale=0.2)


def test_paged_decode_window_alibi_wrapper():
    """Decode wrapper with window+ALiBi vs reference at C=1."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
    from deepspeed_tpu.models.layers import alibi_slopes
    b, h, kvh, d, bs, nb, mb = 2, 4, 4, 64, 16, 8, 3
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32) * 0.1
    kpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((kvh, nb, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32)
    lens = jnp.asarray([30, 14], jnp.int32)
    sl = alibi_slopes(h)
    out = paged_decode_attention(q, kpool, vpool, tables, lens, window=9,
                                 alibi_slopes=sl)
    ref = _ragged_reference(q[:, None], kpool, vpool, tables,
                            (lens - 1)[:, None], window=9, alibi_slopes=sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, 0]),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("c,h,kvh,dtype", [
    (1, 8, 2, jnp.bfloat16),    # GQA decode step: the one-key chunk branch
    (1, 4, 4, jnp.float32),     # MHA decode step
    (3, 8, 2, jnp.bfloat16),    # speculative verify width
    (4, 4, 2, jnp.float32),     # prefill chunk
])
def test_paged_ragged_chunk_beside_pool(c, h, kvh, dtype):
    """The serving runner's contract: the chunk's own KV rides beside a
    pool that does not hold it yet. Must equal attention over a pool with
    the chunk already scattered in."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    b, d, bs, nb, mb = 2, 64, 16, 10, 4
    rng = np.random.default_rng(5)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    q = rand(b, c, h, d, scale=0.1)
    kpool, vpool = rand(kvh, nb, bs, d), rand(kvh, nb, bs, d)
    ck, cv = rand(b, c, kvh, d), rand(b, c, kvh, d)
    tables = jnp.asarray(rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32)
    positions = jnp.asarray(np.stack([17 + np.arange(c), 40 + np.arange(c)]),
                            jnp.int32)
    out = paged_ragged_attention(q, kpool, vpool, tables, positions, ck, cv,
                                 window=20)
    blk = jnp.take_along_axis(tables, positions // bs, axis=1)   # (B, C)
    off = positions % bs
    kfull = kpool.at[:, blk, off].set(ck.transpose(2, 0, 1, 3))
    vfull = vpool.at[:, blk, off].set(cv.transpose(2, 0, 1, 3))
    ref = _ragged_reference(q, kfull, vfull, tables, positions, window=20)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


# ---- the narrow path: live pages x all KV heads a step --------------------

_NARROW_HEADS = [(32, 8), (16, 16), (4, 1)]
# the latent format, marked by 0 kv heads: G = 64 query heads and a small G
# on the one pool's one head, values the row's first lanes
_LATENT_HEADS = [(64, 0), (4, 0)]
_NARROW_CASES = [
    (heads, c, chunk, feature)
    for heads in _NARROW_HEADS for c in (1, 3) for chunk in (True, False)
    for feature in ("plain", "window")
] + [
    (heads, c, True, feature)
    for heads in _NARROW_HEADS for c in (1, 3)
    for feature in ("alibi", "softcap", "layer")
] + [
    (heads, c, chunk, "plain")
    for heads in _LATENT_HEADS for c in (1, 3) for chunk in (True, False)
] + [(heads, 1, True, "layer") for heads in _LATENT_HEADS]


@pytest.mark.parametrize(
    "heads,c,chunk,feature", _NARROW_CASES,
    ids=[f"h{h}kv{k}-c{c}-{'chunk' if ch else 'pool'}-{f}"
         for (h, k), c, ch, f in _NARROW_CASES])
def test_narrow_steps_walk_live_pages(heads, c, chunk, feature):
    """A decode or speculation step folds every KV head into one grid step
    a slot and copies the slot's live pages by hand: against the gather
    reference over slots that are frozen (no context, every row a pad), end
    exactly on a page, end mid-page (one row a pad at C = 3), span more
    than one group of pages, and, under a window, start past their first
    group (``lo > 0``)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.models.layers import alibi_slopes
    h, kvh = heads
    d, bs, mb, layers = 32, 16, 8, 3
    latent, dv = kvh == 0, 24
    if latent:
        kvh = 1
        assert pa._latent_tiling(c, h, mb, bs, d, dv, 4)[0] == 1
    else:
        assert pa._tiling(c * h // kvh, kvh, mb, bs, d, 4) == (kvh, 4, None)
    ctx = [0, 2 * bs, bs + 5, 5 * bs + 3, 7 * bs - c]
    b, nb = len(ctx), 1 + sum(-(-(x + c) // bs) for x in ctx)
    rng = np.random.default_rng(7)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    q = rand(b, c, h, d, scale=0.3)
    kpool, vpool = rand(layers, kvh, nb, bs, d), rand(layers, kvh, nb, bs, d)
    ck, cv = rand(b, c, kvh, d), rand(b, c, kvh, d)
    tables = np.zeros((b, mb), np.int32)
    perm, at = 1 + rng.permutation(nb - 1), 0
    positions = np.full((b, c), -1, np.int32)
    for s, x in enumerate(ctx):
        if not x:
            continue                                  # frozen: page 0, pads
        need = -(-(x + c) // bs)
        tables[s, :need] = perm[at:at + need]
        at += need
        positions[s] = x + np.arange(c)
    if c > 1:
        positions[2, -1] = -1                         # a decoding row's pad
    tables, positions = jnp.asarray(tables), jnp.asarray(positions)
    kw = {"window": 2 * bs + 3} if feature == "window" else {}
    if feature == "alibi":
        kw["alibi_slopes"] = alibi_slopes(h)
    if feature == "softcap":
        kw.update(softcap=20.0, scale=0.3)
    if latent:
        kw.update(value_lanes=dv, scale=0.2)
    lyr = 2 if feature == "layer" else 0

    if feature == "layer":
        run = jax.jit(lambda i, *a: pa.paged_ragged_attention(
            *a, layer=i, **kw))
        call = functools.partial(run, jnp.asarray(lyr, jnp.int32))
    else:
        call = functools.partial(pa.paged_ragged_attention, layer=lyr, **kw)
    if latent:
        out = call(q, kpool, None, tables, positions,
                   *((ck, None) if chunk else ()))
        assert out.shape == (b, c, h, dv)
        vpool, cv = kpool, ck       # the reference's values: the row whole
    else:
        out = call(q, kpool, vpool, tables, positions,
                   *((ck, cv) if chunk else ()))

    kfull, vfull = kpool[lyr], vpool[lyr]
    if chunk:                       # the reference reads the chunk from a pool
        safe = jnp.maximum(positions, 0)
        blk = jnp.take_along_axis(tables, safe // bs, axis=1)
        blk = jnp.where(positions >= 0, blk, 0)       # pads land in trash page 0
        kfull = kfull.at[:, blk, safe % bs].set(ck.transpose(2, 0, 1, 3))
        vfull = vfull.at[:, blk, safe % bs].set(cv.transpose(2, 0, 1, 3))
    kw.pop("value_lanes", None)
    ref = _ragged_reference(q, kfull, vfull, tables, positions, **kw)
    if latent:
        ref = ref[..., :dv]
    valid = np.asarray(positions) >= 0
    assert valid[0].sum() == 0 and valid[1:].all(axis=1).sum() >= 3
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               rtol=3e-5, atol=3e-5)


def test_tiles_follow_from_shapes():
    """(kv heads a step, pages a group, rows a row tile) from static shapes
    against the one VMEM budget: a decode or speculation step takes every
    local kv head, 4 pages a group, its rows not cut; a prefill chunk's rows
    fill the MXU, so they are cut into tiles of 128 (one at OLMoE's 128
    rows, four at mistral's 512, eight at Mellum2's 1,024) and a step takes
    the widest group whose f32 score tile, a ROW TILE's, fits for one head
    and as many heads as fit beside it: four heads and 8 pages at 128 and
    at 512 rows, two heads and 8 pages at 1,024."""
    from deepspeed_tpu.ops.pallas.paged_attention import _tiling, row_tile
    assert [row_tile(r) for r in (4, 24, 128, 512, 1024)] == [
        None, None, 128, 128, 128]
    assert row_tile(192) == 192            # 128 does not divide it: one tile
    for kvh, group, heads in ((8, 4, 4), (16, 1, 4), (2, 4, 2)):
        # mistral, OLMoE, mistral at tp=4
        assert _tiling(128 * group, kvh, 64, 128, 128, 2) == (heads, 8, 128)
        for c in (1, 3, 8):
            assert _tiling(c * group, kvh, 64, 128, 128, 2) == (kvh, 4, None)
    assert _tiling(128 * 8, 4, 256, 128, 128, 2) == (2, 8, 128)  # Mellum2, full
    assert _tiling(128 * 8, 4, 10, 128, 128, 2) == (2, 8, 128)   # ... its ring
    assert _tiling(4, 8, 2, 128, 128, 2) == (8, 2, None)  # a table of 2 pages
    assert _tiling(512, 8, 2, 128, 128, 2) == (4, 2, 128)
    assert _tiling(512, 1, 64, 128, 128, 2) == (1, 8, 128)  # MQA: grid (slots,)
    assert _tiling(128, 3, 64, 128, 128, 2) == (3, 8, 128)  # heads divide evenly
    assert _tiling(128, 6, 64, 128, 128, 2) == (3, 8, 128)


# ---- the wide path: live pages x the KV heads that fit a step -------------

def _linear_reference(q, lin_k, lin_v, positions, *, window=0, scale=None,
                      alibi_slopes=None, softcap=0.0):
    """Causal (windowed) attention over a LINEAR context, which knows no
    pages: q (B, C, H, D), lin_k / lin_v (B, T, KVH, D), positions (B, C)."""
    group = q.shape[2] // lin_k.shape[2]
    kk = jnp.repeat(lin_k, group, axis=2)
    vv = jnp.repeat(lin_v, group, axis=2)
    s = jnp.einsum("bchd,bkhd->bhck", q, kk) * (
        scale if scale is not None else q.shape[-1] ** -0.5)
    key = jnp.arange(lin_k.shape[1])[None, None, None, :]
    pos = jnp.asarray(positions)[:, None, :, None]
    if alibi_slopes is not None:
        s = s + jnp.asarray(alibi_slopes, jnp.float32)[
            None, :, None, None] * (key - pos)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = key <= pos
    if window:
        mask &= key > pos - window
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhck,bkhd->bchd", p, vv)


# (query heads, kv heads, chunk, kv heads a step): GQA 4, MHA, G = 8 (MQA: the
# one head on grid (slots,)), and two heads a step of four
_WIDE_HEADS = [(8, 2, 32, 1), (2, 2, 128, 1), (8, 1, 16, 1), (16, 4, 32, 2)]
_WIDE_FEATURES = ("plain", "window", "ring", "alibi", "softcap", "pool",
                  "layer")
# the latent format (0 kv heads; the last number is the TILES of chunk
# positions on the grid's second axis): 8 heads x 32 positions in 2 tiles,
# G = 64 x 16 positions in 8 (slot 2's one live position: 7 dead tiles)
_WIDE_LATENT = [(8, 0, 32, 2), (64, 0, 16, 8)]
_WIDE_LATENT_FEATURES = ("plain", "window", "pool", "layer")


def _write_pages(pools, lins, table, held, lyr=0):
    """What a run wrote of one slot into numpy ``pools`` (layers, KVH, NB,
    bs, D): the first ``held`` positions of its linear keys and values
    ``lins`` (T, KVH, D), page after page through ``table`` (a ring wraps),
    the last page's tail left stale."""
    bs = pools[0].shape[3]
    for page in range(-(-held // bs)):
        rows = slice(page * bs, min((page + 1) * bs, held))
        for pool, lin in zip(pools, lins):
            pool[lyr, :, table[page % len(table)], :rows.stop - rows.start] = \
                np.asarray(lin[rows]).transpose(1, 0, 2)


def _wide_case(monkeypatch, h, kvh, c, heads, feature):
    """A prefill chunk of 128 rows a KV head, ``heads`` KV heads a grid step
    (the budget is set so that no more fit: at these sizes the real one
    holds them all), over a pool filled the way a run fills it (page after
    page through the table or the ring, the last page's tail stale), against
    attention over the LINEAR context, which knows no pages. Slots: frozen (every row a pad); a context of whole
    pages; a decoding row riding the chunk (one live row); a context ending
    mid-page in the walk's second group; one three or more groups long
    whose window starts inside a page of a later group."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.models.layers import alibi_slopes
    d, bs, layers, lyr = 32, 16, 3, 2 if feature == "layer" else 0
    latent, dv = kvh == 0, 24
    if latent:
        kvh, tiles = 1, heads
    group = h // kvh
    window = {"window": 8 * bs + 2 * bs + 3, "ring": 40}.get(feature, 0)
    ring = -(-(window + c) // bs) + 1 if feature == "ring" else None
    span = 8 * bs
    ctx = [0, 2 * bs, 3 * bs + 5, span + 3 * bs + 5, 3 * span + 7]
    if ring:
        ctx[3:] = [ring * bs, 5 * ring * bs + 37]         # wrapped five times
    mb = ring or -(-(max(ctx) + c) // bs)
    if latent:
        monkeypatch.setattr(pa, "_VMEM_BUDGET", pa._step_bytes(
            1, c * h // tiles, span, d, 4, dv))
        assert pa._latent_tiling(c, h, mb, bs, d, dv, 4) == (tiles, min(8, mb))
    else:
        monkeypatch.setattr(pa, "_VMEM_BUDGET",
                            pa._step_bytes(heads, c * group, span, d, 4))
        assert pa._tiling(c * group, kvh, mb, bs, d, 4) == (
            heads, min(8, mb), c * group)
    b, nb = len(ctx), 1 + len(ctx) * mb
    rng = np.random.default_rng(13)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    total = max(ctx) + c
    lin_k, lin_v = rand(b, total, kvh, d), rand(b, total, kvh, d)
    if latent:
        lin_v = lin_k               # a value is its key's first lanes
    q = rand(b, c, h, d, scale=0.3)
    kpool = np.array(rand(layers, kvh, nb, bs, d))        # stale everywhere
    vpool = np.array(rand(layers, kvh, nb, bs, d))
    tables = np.zeros((b, mb), np.int32)
    tables[1:] = (1 + rng.permutation(nb - 1)[:(b - 1) * mb]).reshape(b - 1, mb)
    positions = np.full((b, c), -1, np.int32)
    for s, cs in enumerate(ctx):
        if not cs:
            continue                                      # frozen: pads
        live = 1 if s == 2 else c                         # slot 2 decodes
        positions[s, :live] = cs + np.arange(live)
        held = cs + live if feature == "pool" else cs     # what a run wrote
        _write_pages((kpool, vpool), (lin_k[s], lin_v[s]), tables[s], held,
                     lyr)
    chunk = ()
    if feature != "pool":
        chunk = tuple(jnp.stack([lin[s, cs:cs + c] for s, cs in enumerate(ctx)])
                      for lin in (lin_k, lin_v))
    kw = {"window": window} if window else {}
    if ring:
        kw["ring"] = ring
    if feature == "alibi":
        kw["alibi_slopes"] = alibi_slopes(h)
    if feature == "softcap":
        kw.update(softcap=20.0, scale=0.3)
    args = (q, jnp.asarray(kpool), jnp.asarray(vpool), jnp.asarray(tables),
            jnp.asarray(positions), *chunk)
    if latent:
        kw.update(value_lanes=dv, scale=0.2)
        args = (q, args[1], None, *args[3:5], *((chunk[0], None) if chunk
                                                else ()))
    if feature == "layer":
        out = jax.jit(lambda i, *a: pa.paged_ragged_attention(
            *a, layer=i, **kw))(jnp.asarray(lyr, jnp.int32), *args)
    else:
        out = pa.paged_ragged_attention(*args, layer=lyr, **kw)

    ref = _linear_reference(q, lin_k, lin_v, positions,
                            **{k: v for k, v in kw.items()
                               if k not in ("ring", "value_lanes")})
    if latent:
        assert out.shape == ref.shape[:3] + (dv,)
        ref = ref[..., :dv]
    valid = positions >= 0
    assert valid[0].sum() == 0 and valid[2].sum() == 1 and valid[3:].all()
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize(
    "heads,feature", [(hd, f) for hd in _WIDE_HEADS for f in _WIDE_FEATURES]
    + [(hd, f) for hd in _WIDE_LATENT for f in _WIDE_LATENT_FEATURES],
    ids=[f"h{h}kv{k}-c{c}-{n}-a-step-{f}" for h, k, c, n in _WIDE_HEADS
         for f in _WIDE_FEATURES]
    + [f"h{h}latent-c{c}-{n}-tiles-{f}" for h, _, c, n in _WIDE_LATENT
       for f in _WIDE_LATENT_FEATURES])
def test_wide_steps_walk_live_pages(monkeypatch, heads, feature):
    """A prefill chunk takes one KV head a grid step, or the few that fit,
    and walks the slot's live pages as the narrow step does: see
    ``_wide_case``."""
    _wide_case(monkeypatch, *heads, feature)


# ---- row tiles: a many-rows step computes the tiles that hold a live row ---

# the serve cells' heads at C = 128: (query heads, kv heads, kv heads a
# step, feature): mistral's 512 rows a kv head under its window, Mellum2's
# 1,024 over whole tables and over its ring, OLMoE's 128 (one tile), four
# heads a step
_TILE_SHAPES = {"mistral": (32, 8, 1, "window"),
                "mellum2-full": (32, 4, 1, "plain"),
                "mellum2-ring": (32, 4, 1, "ring"),
                "olmoe": (16, 16, 4, "plain")}
# live positions of a slot's chunk: a frozen slot, a decoding row riding the
# wide step, a prompt's last partial chunk, a full chunk
_TILE_HEIGHTS = {"frozen": 0, "rider": 1, "partial": 37, "full": 128}


def _tile_case(monkeypatch, shape, height):
    """A wide step at a cell's heads (C = 128; head_dim 32, pages of 16 so
    that interpret mode gets through it) whose slots hold ``height`` live
    positions each, or every height in one batch (``mixed``), behind
    contexts of 0, under a page, and several groups of pages: every live
    row against attention over the LINEAR context, and every row of a tile
    with no live row exactly zero."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    h, kvh, heads, feature = _TILE_SHAPES[shape]
    c, d, bs, group = 128, 32, 16, h // kvh
    rows, span = c * group, 8 * bs
    tile = pa.row_tile(rows)
    window = {"window": 2 * span + 2 * bs + 3, "ring": span + 2 * bs + 3}.get(
        feature, 0)
    ring = -(-(window + c) // bs) + 1 if feature == "ring" else None
    far = 5 * ring * bs + 37 if ring else 3 * span + 7
    if height == "mixed":
        slots = [(0, 0), (1, bs - 5), (1, far), (37, 0), (37, far),
                 (128, bs - 5), (128, far)]
    else:
        slots = [(_TILE_HEIGHTS[height], ctx) for ctx in (0, bs - 5, far)]
    mb = ring or -(-(far + c) // bs)
    monkeypatch.setattr(pa, "_VMEM_BUDGET",
                        pa._step_bytes(heads, rows, span, d, 4, tile=tile))
    assert pa._tiling(rows, kvh, mb, bs, d, 4) == (heads, 8, 128)
    b, nb = len(slots), 1 + len(slots) * mb
    rng = np.random.default_rng(17)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    total = far + c
    lin_k, lin_v = rand(b, total, kvh, d), rand(b, total, kvh, d)
    q = rand(b, c, h, d, scale=0.3)
    kpool = np.array(rand(1, kvh, nb, bs, d))             # stale everywhere
    vpool = np.array(rand(1, kvh, nb, bs, d))
    tables = (1 + rng.permutation(nb - 1)[:b * mb]).reshape(b, mb)
    positions = np.full((b, c), -1, np.int32)
    for s, (w, cs) in enumerate(slots):
        positions[s, :w] = cs + np.arange(w)
        _write_pages((kpool, vpool), (lin_k[s], lin_v[s]), tables[s],
                     cs if w else 0)                      # frozen: no context
    ck, cv = (jnp.stack([lin[s, cs:cs + c] for s, (_, cs) in enumerate(slots)])
              for lin in (lin_k, lin_v))
    kw = {"window": window} if window else {}
    out = np.asarray(pa.paged_ragged_attention(
        q, jnp.asarray(kpool), jnp.asarray(vpool),
        jnp.asarray(tables, jnp.int32), jnp.asarray(positions), ck, cv,
        layer=0, **kw, **({"ring": ring} if ring else {})))
    ref = np.asarray(_linear_reference(q, lin_k, lin_v, positions, **kw))
    valid = positions >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=3e-5, atol=3e-5)
    for s, (w, _) in enumerate(slots):
        computed = -(-w * group // tile) * tile // group  # chunk positions
        assert not out[s, computed:].any(), (s, w)


@pytest.mark.parametrize("height", [*_TILE_HEIGHTS, "mixed"])
@pytest.mark.parametrize("shape", list(_TILE_SHAPES))
def test_wide_steps_compute_their_live_row_tiles(monkeypatch, shape, height):
    """A wide step's query rows are cut into tiles of 128 by chunk position
    and a tile is computed only if it holds a live row (``_tile_case``):
    none of a frozen slot's, the first of a decoding row's, two of four or
    eight of a partial chunk's at G = 4 and 8, all of a full chunk's."""
    _tile_case(monkeypatch, shape, height)


class _Off:
    """A scalar-prefetch ref that reads ``by`` off the truth, or off its
    row ``row`` alone."""

    def __init__(self, ref, by, row=None):
        self.ref, self.by, self.row = ref, by, row

    def __getitem__(self, i):
        if self.row is not None and i[0] != self.row:
            return self.ref[i]
        return jnp.maximum(self.ref[i] + self.by, 0)


@pytest.mark.parametrize("fault", ["last-page-skipped", "lo-a-page-late",
                                   "a-live-tile-skipped"])
def test_wide_walk_comparison_sees_a_planted_fault(monkeypatch, fault):
    """The comparisons above are sharp enough: a walk that stops a page
    short of ``cs``, or starts a page past ``lo``, fails the first, and a
    step that leaves out the last tile that holds a live row the second."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    sound = pa._live_pages_kernel

    def faulty(lyr, bt, cs, lo, win, *rest, page_size, **kw):
        if fault == "last-page-skipped":
            cs = _Off(cs, -page_size)
        elif fault == "lo-a-page-late":
            lo = _Off(lo, page_size)
        else:
            kw["live_ref"] = _Off(kw["live_ref"], -kw["row_tile"], row=1)
        return sound(lyr, bt, cs, lo, win, *rest, page_size=page_size, **kw)

    monkeypatch.setattr(pa, "_live_pages_kernel", faulty)
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        if fault == "a-live-tile-skipped":
            _tile_case(monkeypatch, "mistral", "partial")
        else:
            _wide_case(monkeypatch, 8, 2, 32, 1, "window")


# ---- a ring of pages behind the window (caches by layer kind) -------------

_RING_CASES = [(c, ctx) for c in (1, 3, 16) for ctx in (
    "shorter-than-the-ring", "exactly-the-ring", "chunk-crosses-the-wrap",
    "wrapped-many-times")]


@pytest.mark.parametrize("c,ctx", _RING_CASES,
                         ids=[f"c{c}-{ctx}" for c, ctx in _RING_CASES])
def test_ring_of_pages_matches_the_linear_context(c, ctx):
    """``ring=R``: position p lives in ``table[slot, (p // bs) mod R]``. The
    pool is filled the way a run fills it (page after page through the
    ring, later pages over earlier ones) and the kernel — the narrow tiling at
    C = 1 and 3, the wide one at C = 16 (128 rows a KV head) — must equal
    windowed attention over the LINEAR context, which knows no pages:
    beside a frozen slot, contexts shorter than the ring, ending exactly on
    it, with the chunk across the wrap, and wrapped many times with the
    window's first page far from 0 (``lo > 0``)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    h, kvh, d, bs, ring, window, layers = 16, 2, 32, 16, 5, 40, 2
    assert ring * bs >= window + c + bs              # the ring's invariant
    assert pa._tiling(c * h // kvh, kvh, ring, bs, d, 4) == (
        (kvh, 4, None) if c < 16 else (kvh, 5, 128))  # both heads fit a step
    start = {"shorter-than-the-ring": 23, "exactly-the-ring": ring * bs,
             "chunk-crosses-the-wrap": 2 * ring * bs - min(c, 5) + 1
             if c > 1 else 2 * ring * bs - 1,
             "wrapped-many-times": 333}[ctx]
    starts = [0, start, start + 7]                   # slot 0 is frozen
    b, nb = len(starts), 1 + len(starts) * ring
    rng = np.random.default_rng(11)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    total = max(starts) + c
    lin_k, lin_v = rand(b, total, kvh, d), rand(b, total, kvh, d)
    q = rand(b, c, h, d, scale=0.3)
    kpool = np.array(rand(layers, kvh, nb, bs, d))
    vpool = np.array(rand(layers, kvh, nb, bs, d))
    tables = (1 + rng.permutation(nb - 1)[:b * ring]).reshape(b, ring)
    positions = np.full((b, c), -1, np.int32)
    lyr = 1
    for s, cs in enumerate(starts):
        if s == 0:
            continue
        positions[s] = cs + np.arange(c)
        for page in range(-(-cs // bs)):             # what a run has written
            rows = slice(page * bs, min((page + 1) * bs, total))
            n = rows.stop - rows.start
            for pool, lin in ((kpool, lin_k), (vpool, lin_v)):
                pool[lyr, :, tables[s, page % ring], :n] = \
                    np.asarray(lin[s, rows]).transpose(1, 0, 2)
    ck = jnp.stack([lin_k[s, cs:cs + c] for s, cs in enumerate(starts)])
    cv = jnp.stack([lin_v[s, cs:cs + c] for s, cs in enumerate(starts)])
    out = pa.paged_ragged_attention(
        q, jnp.asarray(kpool), jnp.asarray(vpool),
        jnp.asarray(tables, jnp.int32), jnp.asarray(positions), ck, cv,
        layer=lyr, window=window, ring=ring)
    ref = _linear_reference(q, lin_k, lin_v, positions, window=window)
    np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                               rtol=3e-5, atol=3e-5)


def test_ring_kernels_carry_their_own_names():
    """The trace tells the kinds apart: a kernel that reads a ring is
    ``paged_attn_ring_c<C>``, and the accepted readers' ``^paged_attn_c\\d+$``
    keeps meaning the kernel over whole tables."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    b, c, h, kvh, d, bs, ring = 2, 1, 4, 2, 32, 16, 4
    args = (jnp.zeros((b, c, h, d)), jnp.zeros((1, kvh, 9, bs, d)),
            jnp.zeros((1, kvh, 9, bs, d)), jnp.zeros((b, ring), jnp.int32),
            jnp.zeros((b, c), jnp.int32), jnp.zeros((b, c, kvh, d)),
            jnp.zeros((b, c, kvh, d)))
    ringed = str(jax.make_jaxpr(lambda *a: paged_ragged_attention(
        *a, layer=0, window=20, ring=ring))(*args))
    plain = str(jax.make_jaxpr(lambda *a: paged_ragged_attention(
        *a, layer=0, window=20))(*args))
    assert "paged_attn_ring_c1" in ringed and "paged_attn_c1" not in ringed
    assert "paged_attn_c1" in plain and "ring" not in plain
