"""Pallas page commit (interpret mode) vs the XLA scatter it replaces on
the chip: a commit is a copy, so every page but trash page 0 must come out
equal bit for bit."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2.model_runner import commit_scatter
from deepspeed_tpu.ops.pallas.kv_commit import kv_commit

# each row of a case: (first live position, live count, pads ahead of them)
CASES = {
    # C, KVH, page, D, dtype, rows
    "decode-gqa8": (1, 8, 128, 128, jnp.bfloat16,
                    [(5, 1, 0), (127, 1, 0), (128, 1, 0), (300, 1, 0)]),
    "decode-mha16-dead-row": (1, 16, 128, 128, jnp.bfloat16,
                              [(77, 1, 0), (0, 0, 0), (255, 1, 0)]),
    "spec-verify-straddles-page": (3, 8, 128, 128, jnp.bfloat16,
                                   [(126, 3, 0), (127, 2, 0), (0, 3, 0)]),
    "spec-verify-pads-ahead-and-behind": (3, 8, 128, 128, jnp.bfloat16,
                                          [(13, 2, 1), (140, 1, 1),
                                           (15, 1, 0), (0, 0, 0)]),
    "prefill-straddles-page-gqa8": (128, 8, 128, 128, jnp.bfloat16,
                                    [(70, 128, 0), (0, 128, 0), (256, 128, 0)]),
    "prefill-straddles-page-mha16": (128, 16, 128, 128, jnp.bfloat16,
                                     [(70, 128, 0), (129, 128, 0)]),
    "prefill-pads-behind": (128, 8, 128, 128, jnp.bfloat16,
                            [(384, 37, 0), (100, 29, 0), (0, 1, 0)]),
    "prefill-dead-row-between": (128, 8, 128, 128, jnp.bfloat16,
                                 [(10, 128, 0), (0, 0, 0), (250, 9, 0)]),
    "prefill-start-not-on-a-sublane-tile": (128, 8, 128, 128, jnp.bfloat16,
                                            [(5, 128, 0), (13, 20, 0),
                                             (131, 3, 0)]),
    "narrow-chunk-straddles-page": (8, 8, 128, 128, jnp.bfloat16,
                                         [(12, 8, 0), (124, 8, 0), (3, 5, 2)]),
    "chunk-wider-than-page-f32": (40, 2, 16, 16, jnp.float32,
                                  [(7, 40, 0), (0, 33, 0), (64, 1, 0),
                                   (0, 0, 0)]),
    "every-row-dead": (3, 8, 128, 128, jnp.bfloat16, [(0, 0, 0), (0, 0, 0)]),
    # the latent format (a name that starts so): ONE pool of one head of
    # 640-lane rows, no second pool and no second chunk
    "latent-decode-dead-row": (1, 1, 128, 640, jnp.bfloat16,
                               [(5, 1, 0), (0, 0, 0), (128, 1, 0)]),
    "latent-prefill-straddles-page": (128, 1, 128, 640, jnp.bfloat16,
                                      [(70, 128, 0), (0, 0, 0), (384, 37, 0)]),
    "latent-chunk-wider-than-page-f32": (40, 1, 16, 48, jnp.float32,
                                         [(7, 40, 0), (64, 1, 0)]),
}


def _case(name, layers=2, seed=0):
    c, kvh, page, d, dtype, rows = CASES[name]
    b = len(rows)
    mb = max((p0 + n - 1) // page for p0, n, _ in rows) + 1
    nb = 1 + b * mb
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    kpool, vpool = rand(layers, kvh, nb, page, d), rand(layers, kvh, nb, page, d)
    ck, cv = rand(layers, b, c, kvh, d), rand(layers, b, c, kvh, d)
    # distinct physical pages per row, never trash page 0
    tables = jnp.asarray(1 + rng.permutation(nb - 1)[: b * mb].reshape(b, mb),
                         jnp.int32)
    positions = np.full((b, c), -1, np.int32)
    for i, (p0, n, ahead) in enumerate(rows):
        positions[i, ahead:ahead + n] = p0 + np.arange(n)
    if name.startswith("latent"):
        vpool = cv = None
    return kpool, vpool, ck, cv, tables, jnp.asarray(positions)


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("name", list(CASES))
def test_commit_kernel_equals_the_scatter(name):
    args = _case(name)
    got = jax.jit(kv_commit)(*args)
    want = commit_scatter(*args)
    if name.startswith("latent"):
        assert got[1] is None and want[1] is None
        got, want, args = got[:1], want[:1], args[:1]
    for g, w, pool in zip(got, want, args[:2]):
        assert g.dtype == pool.dtype and g.shape == pool.shape
        np.testing.assert_array_equal(_bits(g)[:, :, 1:], _bits(w)[:, :, 1:])
    if name == "every-row-dead":
        for g, pool in zip(got, args[:2]):
            np.testing.assert_array_equal(_bits(g)[:, :, 1:],
                                          _bits(pool)[:, :, 1:])


# a ring of pages (caches by layer kind): position p lands in page
# ``table[row, (p // page) mod ring]``; rows as in CASES
RING_CASES = {
    # C, KVH, page, D, dtype, ring, rows
    "decode-shorter-than-the-ring": (1, 4, 128, 128, jnp.bfloat16, 10,
                                     [(5, 1, 0), (1279, 1, 0), (0, 0, 0)]),
    "decode-exactly-the-ring-and-wrapped": (1, 4, 128, 128, jnp.bfloat16, 10,
                                            [(1280, 1, 0), (12800, 1, 0),
                                             (31000, 1, 0)]),
    "prefill-crosses-the-wrap": (128, 4, 128, 128, jnp.bfloat16, 10,
                                 [(1200, 128, 0), (10 * 128 * 7 - 1, 128, 0),
                                  (2560, 128, 0)]),
    "prefill-wrapped-pads-behind": (128, 4, 128, 128, jnp.bfloat16, 10,
                                    [(20000, 37, 0), (1270, 29, 0),
                                     (0, 0, 0)]),
    "spec-verify-crosses-the-wrap-f32": (3, 2, 16, 16, jnp.float32, 4,
                                         [(62, 3, 0), (63, 2, 1),
                                          (127, 1, 0)]),
}


@pytest.mark.parametrize("name", list(RING_CASES))
def test_ring_commit_kernel_equals_the_scatter(name):
    """``ring=R``: the kernel's page map goes through ``mod R`` as the
    scatter's does, bit for bit, and it carries a name of its own."""
    c, kvh, page, d, dtype, ring, rows = RING_CASES[name]
    b, layers = len(rows), 2
    nb = 1 + b * ring
    rng = np.random.default_rng(1)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    kpool, vpool = rand(layers, kvh, nb, page, d), rand(layers, kvh, nb, page, d)
    ck, cv = rand(layers, b, c, kvh, d), rand(layers, b, c, kvh, d)
    tables = jnp.asarray(1 + rng.permutation(nb - 1)[: b * ring].reshape(
        b, ring), jnp.int32)
    positions = np.full((b, c), -1, np.int32)
    for i, (p0, n, ahead) in enumerate(rows):
        positions[i, ahead:ahead + n] = p0 + np.arange(n)
    args = (kpool, vpool, ck, cv, tables, jnp.asarray(positions))
    got = jax.jit(kv_commit, static_argnames="ring")(*args, ring=ring)
    want = commit_scatter(*args, ring=ring)
    changed = False
    for g, w, pool in zip(got, want, args[:2]):
        np.testing.assert_array_equal(_bits(g)[:, :, 1:], _bits(w)[:, :, 1:])
        changed |= bool(np.any(_bits(g)[:, :, 1:] != _bits(pool)[:, :, 1:]))
    assert changed
    # the first live position of row 0 sits where the ring says
    p0 = rows[0][0]
    np.testing.assert_array_equal(
        _bits(got[0])[:, :, int(tables[0, p0 // page % ring]), p0 % page],
        _bits(ck)[:, 0, rows[0][2]])
    text = str(jax.make_jaxpr(lambda *a: kv_commit(*a, ring=ring))(*args))
    assert f"kv_commit_ring_c{c}" in text and f"kv_commit_c{c}" not in text


def test_commit_kernel_in_the_serving_forward(monkeypatch, mesh_8dp):
    """The runner with the chip's predicate on (both paged kernels, in
    interpret mode here) serves the tokens of the scatter path through
    wide and narrow frames, and its pools hold the same pages: the first
    layer's bit for bit (what it commits has met no attention yet), the
    others' to the rounding the two attentions differ by."""
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model
    model = build_model("tiny")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = {u: rng.integers(0, 200, (n,)).astype(np.int32)
               for u, n in enumerate((5, 23, 40))}

    def serve():
        eng = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            kv_block_size=16, prefill_chunk_size=8, max_tokens_per_step=256,
            dtype="float32", max_ragged_batch_size=4, frame_steps=4),
            max_seq_len=128)
        eng.params = jax.device_put(params)
        got = dict(eng.serve(iter([[(u, p) for u, p in prompts.items()]]),
                             max_new_tokens=6))
        return got, np.asarray(eng.kv.k), np.asarray(eng.kv.v)

    want, wk, wv = serve()
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    got, gk, gv = serve()
    assert set(got) == set(want)
    for u in want:
        np.testing.assert_array_equal(got[u], want[u])
    for g, w in ((gk, wk), (gv, wv)):
        assert np.any(w[:, :, 1:] != 0)
        np.testing.assert_array_equal(g[0, :, 1:], w[0, :, 1:])
        np.testing.assert_allclose(g[:, :, 1:], w[:, :, 1:], rtol=1e-3,
                                   atol=1e-5)
