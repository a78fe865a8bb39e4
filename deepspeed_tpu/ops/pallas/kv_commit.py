"""Pallas page commit: a step's new KV written into the pools IN PLACE.

The serving forward keeps the (L, KVH, NB, bs, D) pools read-only through
the layer walk (``paged_attention.py``) and commits every layer's chunk KV
afterwards. As an XLA scatter that commit asks for KV heads minor while the
paged kernel reads them major, so XLA relaid both whole pools every step
(two copies of a pool's bytes, and a second pool of temporaries). This
kernel takes the pools in the layout the paged kernel reads, aliased to its
results, and touches only the pages the step's live positions land on: per
(layer, page) it fetches the page's ``(KVH, bs, D)`` rows, puts the chunk's
rows over the live ones and stores the page back.

TPU mapping: the grid is (layer, slot); a slot is one of the ``J`` pages
that a sequence's chunk can reach. Which page a slot holds and which of its
rows are live is scalar-prefetched (``_plan``) so the BlockSpec index maps
chase it. A dead slot (a pad, a wholly dead row, a block the chunk
does not reach) maps to the page of a live neighbour and does nothing: the
pipeline neither fetches nor stores a block whose index did not change, so
dead slots move no bytes. The chunk's rows are aligned to the block's by a
sublane rotation in f32 (the packed bf16 tile takes no unaligned row
offset); bf16 -> f32 -> bf16 is exact, so a commit stays a copy.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of the scalar-prefetched plan, one column a slot
_PAGE, _LIVE, _LO, _HI, _ROLL = range(5)


def _commit_kernel(plan_ref, *refs):
    """``refs``: the chunks, the pools and the results, one each a pool
    (K and V, or the latent format's one)."""
    n = len(refs) // 3
    kout_ref = refs[2 * n]
    s = pl.program_id(1)

    @pl.when(plan_ref[_LIVE, s] == 1)
    def _block():
        _, kvh, _, rows, d = kout_ref.shape
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0)
        mask = jnp.logical_and(r >= plan_ref[_LO, s], r < plan_ref[_HI, s])
        roll = plan_ref[_ROLL, s]
        for src, old, new in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
            for h in range(kvh):
                x = pltpu.roll(src[0, 0, h].astype(jnp.float32), roll, 0)
                new[0, h, 0] = jnp.where(
                    mask, x[:rows], old[0, h, 0].astype(jnp.float32)
                ).astype(new.dtype)


def _plan(positions, block_tables, rows, slots, chunk_rows, ring=None):
    """(5, B * slots) int32: for each slot its page, whether any live
    position lands in it, the live rows' [lo, hi) in page coordinates, and
    the rotation that brings the chunk's rows under them. Dead slots take
    the page of the live slot before them (the first live one, for those
    ahead of it; trash page 0 if the step has none)."""
    b, c = positions.shape
    live = positions >= 0
    n = jnp.sum(live, axis=1, dtype=jnp.int32)                    # (B,)
    c0 = jnp.argmax(live, axis=1).astype(jnp.int32)
    p0 = jnp.where(n > 0, jnp.take_along_axis(
        positions, c0[:, None], axis=1)[:, 0], 0)
    start = (p0[:, None] // rows + jnp.arange(slots, dtype=jnp.int32)) * rows
    lo = p0[:, None] - start                                      # (B, J)
    hi = lo + n[:, None]
    slot_live = jnp.logical_and(n[:, None] > 0, hi > 0).reshape(-1)
    if ring is None:
        at = jnp.clip(start // rows, 0, block_tables.shape[1] - 1)
    else:
        at = (start // rows) % ring
    page = jnp.take_along_axis(block_tables, at, axis=1)
    # block row r takes chunk row r - lo + c0: rotate the chunk up by that
    roll = (lo - c0[:, None]) % chunk_rows
    order = jnp.arange(b * slots, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(slot_live, order, -1))
    owner = jnp.where(before < 0, jnp.argmax(slot_live), before)
    some = jnp.any(slot_live)
    return jnp.stack([
        jnp.where(some, page.reshape(-1)[owner], 0),
        slot_live.astype(jnp.int32), lo.reshape(-1), hi.reshape(-1),
        roll.reshape(-1)]).astype(jnp.int32)


def kv_commit(kpool, vpool, chunk_k, chunk_v, block_tables, positions,
              ring=None, layer0=0):
    """Write a step's chunk KV into the page pools in place.

    kpool/vpool: (L, KVH, NB, bs, D), donated to the results;
    chunk_k/chunk_v: (L, B, C, KVH, D) in the pools' dtype; block_tables:
    (B, MB) int32 page ids; positions: (B, C) int32 absolute slot of each
    chunk position, -1 for a pad. A row's live positions are consecutive
    and ascending at consecutive chunk indices (every serving loop plans
    them so); pads may lie ahead of them or behind. Pads and wholly dead
    rows write nothing. ``ring`` (static): the (B, ring) table is a ring,
    position ``p`` lands in page ``table[slot, (p // bs) mod ring]``; the
    kernel is then named ``kv_commit_ring_c<C>``. The latent format
    (``kv_cache.py``): ``vpool`` and ``chunk_v`` are None, the one pool's
    rows are committed alone and the kernel is named
    ``kv_commit_mla_c<C>``. The chunk may hold fewer layers than the pools:
    it is then written to the pools' layers from ``layer0`` (static) on.
    Returns (kpool, vpool)."""
    _, kvh, _, page_size, d = kpool.shape
    layers = chunk_k.shape[0]
    b, c = positions.shape
    # a slot is a whole page: blocks of 16 rows in a decode step moved an
    # eighth of the bytes (0.06 against ~0.1 ms a step) but once in ~3,500
    # programs one took 3 s on the chip (PERF.md, PR 27)
    rows = page_size
    slots = (c + rows - 2) // rows + 1      # pages c rows can straddle
    chunk_rows = -(-max(c, rows) // 8) * 8

    def chunk_blocks(x):                    # (L, B, C, KVH, D) -> head-major
        x = x.transpose(0, 1, 3, 2, 4)
        return jnp.pad(x, ((0, 0),) * 3 + ((0, chunk_rows - c), (0, 0)))

    def chunk_map(li, si, plan):
        return (li, si // slots, 0, 0, 0)

    def pool_map(li, si, plan):
        return (li + layer0 if layer0 else li, 0, plan[_PAGE, si], 0, 0)

    chunk_spec = pl.BlockSpec((1, 1, kvh, chunk_rows, d), chunk_map)
    pool_spec = pl.BlockSpec((1, kvh, 1, rows, d), pool_map)
    pools = [kpool] if vpool is None else [kpool, vpool]
    chunks = [chunk_k] if vpool is None else [chunk_k, chunk_v]
    n = len(pools)
    name = "kv_commit_mla" if vpool is None else \
        "kv_commit" if ring is None else "kv_commit_ring"
    out = pl.pallas_call(
        _commit_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(layers, b * slots),
            in_specs=[chunk_spec] * n + [pool_spec] * n,
            out_specs=[pool_spec] * n,
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # the pools, written in place
        input_output_aliases={1 + n + i: i for i in range(n)},
        name=f"{name}_c{c}",
        interpret=jax.default_backend() != "tpu",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(_plan(positions, block_tables, rows, slots, chunk_rows, ring),
      *map(chunk_blocks, chunks), *pools)
    return (out[0], None) if vpool is None else tuple(out)
