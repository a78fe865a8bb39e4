"""Pallas paged decode attention vs the XLA gather reference."""

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
