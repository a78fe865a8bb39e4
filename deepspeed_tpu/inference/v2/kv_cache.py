"""Blocked (paged) KV cache.

Analog of ``inference/v2/ragged/kv_cache.py:40`` (BlockedKVCache): KV lives
in fixed-size blocks in a device pool; sequences hold block lists, so memory
scales with tokens actually generated instead of max_seq_len per slot.

Layout: k/v pools are (L, KVH, num_blocks, block_size, D) — kv-head-major so
the Pallas paged-decode kernel (``ops/pallas/paged_attention.py``) reads each
(page, head) slab contiguously in place. A sequence's logical cache is the
concatenation of its blocks; prefill chunks gather pages by block table (XLA
gather), decode attends in place.

Caches by layer kind (``cache_kinds`` / ``LayeredKVCache``): a stack that
mixes windowed and global layers (``cfg.layer_windows()``) keeps a cache a
KIND. The global layers' is the object above, over those layers alone: a
sequence holds ``blocks_for(tokens)`` pages of it. A windowed kind holds a
RING of ``R`` pages a sequence whatever its length: position ``p`` lives in
``table[slot, (p // bs) mod R]``. THE RING'S INVARIANT, stated here once:
with ``R * bs >= window + chunk + bs``, a step that commits positions
``[cs, cs + C)``, ``C <= chunk``, overwrites positions ``<= cs + C - 1 -
R * bs < cs - window``, which no query at ``cs`` or later can see, so the
ring always holds every key a live query's window admits, and a step that is
rolled back (``cs`` unchanged) has destroyed nothing it will read again.
A model of one kind (no pattern: every benchmark model before Mellum2) is
not grouped and builds ``BlockedKVCache`` as it always did.

The latent format (``BlockedKVCache(latent=True)``; a model with latent
attention, ``cfg.latent_lanes``): ONE pool ``(attention layers, 1, pages,
block_size, lanes)`` and no second: a token's row is its latent and its
shared RoPE key, the key of every query head, and its first
``kv_lora_rank`` lanes are the value. ``v`` is None; allocator, tables,
``blocks_for``, admission and the slot table are as they are.

Quantized pages (``kv_dtype="int8"``): the pools become int8 with the last
dim widened to D + 4 *scale lanes* — each (token, head) row stores its D
quantized values followed by its f32 absmax scale bitcast into 4 int8 lanes
(``quantize_kv_lanes``/``dequantize_kv_lanes``). Packing the scale INTO the
page row (ZeRO-Inference-style row quantization, arXiv 2207.00032) keeps
every page a single int8 array, so block tables, the page movers, the swap
tier, and the tensor-parallel head sharding all move the quantized
representation unchanged — spill/restore ships the already-int8 bytes with
zero conversion, and per-token pool bytes drop from 4D (f32) to D + 4.
"""

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocked_allocator import BlockedAllocator

# process-wide compiled page-movement helpers (see BlockedKVCache._fn)
_PAGE_FNS = {}

# int8 lanes appended to each quantized page row: one f32 per-(token, head)
# absmax scale, bitcast so the page stays a single int8 array
KV_SCALE_LANES = 4


def quantize_kv_lanes(x):
    """Quantize ``(..., D)`` float rows to packed ``(..., D + 4)`` int8 page
    rows: symmetric absmax int8 values plus the f32 scale bitcast into the
    trailing ``KV_SCALE_LANES`` lanes. All-zero rows get scale 0, so they
    dequantize to exactly 0."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = amax / 127.0
    q = jnp.where(scale > 0, jnp.round(x.astype(jnp.float32)
                                       / jnp.where(scale > 0, scale, 1.0)), 0)
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    lanes = jax.lax.bitcast_convert_type(scale, jnp.int8)  # (..., 1, 4)
    return jnp.concatenate(
        [q, lanes.reshape(q.shape[:-1] + (KV_SCALE_LANES,))], axis=-1)


def dequantize_kv_lanes(packed, dtype):
    """Unpack ``(..., D + 4)`` int8 page rows to ``(..., D)`` in ``dtype``.
    The scale is sanitized: never-written pool rows (and anything routed
    through the trash block) hold arbitrary bytes whose bitcast can be
    NaN/inf — those rows read as 0 instead of poisoning the attention."""
    q = packed[..., :-KV_SCALE_LANES]
    scale = jax.lax.bitcast_convert_type(
        packed[..., -KV_SCALE_LANES:], jnp.float32)       # lanes collapse
    scale = jnp.where(jnp.isfinite(scale), scale, 0.0)
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """Layers that share one cache: their indices in the model (ascending),
    their window (0: global) and the pages of their ring (None: whole
    tables)."""
    name: str
    layers: Tuple[int, ...]
    window: int
    ring: Optional[int]


def ring_pages(window: int, chunk: int, block_size: int) -> int:
    """Pages of a ring behind ``window`` under steps of ``chunk`` positions:
    the fewest with ``R * bs >= window + chunk + bs`` (the module's
    invariant), so that 1,024 + 128 over pages of 128 is 10."""
    return -(-(window + chunk) // block_size) + 1


def cache_kinds(windows, block_size: int, max_blocks: int, chunk: int):
    """The cache kinds of a model whose layers have ``windows`` (per-layer
    sizes, 0 = global; None = layers alike): the global layers' whole
    tables, then the windowed layers' ring. None, and one cache as ever,
    for a model of one kind, for windows that differ among the windowed
    layers (no model here has them) and where the ring would not be shorter
    than the table: those mask by the layer's window inside one pool."""
    sizes = {w for w in windows or () if w}
    if len(sizes) != 1 or 0 not in windows:
        return None
    window, = sizes
    ring = ring_pages(window, chunk, block_size)
    if ring >= max_blocks:
        return None
    return (CacheKind("full", tuple(i for i, w in enumerate(windows) if not w),
                      0, None),
            CacheKind(f"window{window}",
                      tuple(i for i, w in enumerate(windows) if w),
                      window, ring))


#: lanes of a vector register row on the chip: the minor extent a Mosaic
#: copy of a page moves whole
ROW_LANES = 128


def heads_per_row(kv_heads: int, head_dim: int) -> int:
    """KV heads that share one ``ROW_LANES``-lane row of a page where the
    pages are read and written in place by the chip's kernels: 1 for heads
    of 128 lanes or more, 2 for heads of 64 (``kv_heads`` even). Mosaic
    refuses a copy whose minor extent is half a row (``tests/
    test_chip_compile_lfm2.py``), so such a pool is laid out (layers,
    kv_heads / p, pages, page, p x head_dim): head j p + r of a token in
    lanes [r head_dim, (r + 1) head_dim) of row j. The same bytes a token;
    ``model_runner._forward`` reads the packing off the pool's shape."""
    p = ROW_LANES // head_dim \
        if head_dim < ROW_LANES and ROW_LANES % head_dim == 0 else 1
    return p if kv_heads % p == 0 else 1


class BlockedKVCache:
    def __init__(self, num_layers: int, kv_heads: int, head_dim: int,
                 num_blocks: int, block_size: int = 64, dtype=jnp.bfloat16,
                 kv_dtype: Optional[str] = None, latent: bool = False):
        self.num_layers = num_layers
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.block_size = block_size
        self.num_blocks = num_blocks
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', "
                             f"got {kv_dtype!r}")
        self.quantized = kv_dtype == "int8"
        # pool row width: head_dim floats, or head_dim int8 + scale lanes
        self.lanes = head_dim + KV_SCALE_LANES if self.quantized else head_dim
        pool_dtype = jnp.int8 if self.quantized else dtype
        shape = (num_layers, kv_heads, num_blocks, block_size, self.lanes)
        self.latent = latent
        self.k = jnp.zeros(shape, pool_dtype)
        self.v = None if latent else jnp.zeros(shape, pool_dtype)
        self.allocator = BlockedAllocator(num_blocks)
        self._sharding = None       # set by shard(); places swap-in updates

    @property
    def block_bytes(self) -> int:
        """Resident HBM bytes per block across BOTH pools (the latent
        format's one) — the unit the byte-accounting telemetry multiplies
        block counts by."""
        per_row = self.lanes * self.k.dtype.itemsize
        pools = 1 if self.latent else 2
        return (pools * self.num_layers * self.kv_heads * self.block_size
                * per_row)

    def in_use(self):
        """``LayeredKVCache.in_use`` of a cache of one kind: the latent
        format's, or the full-attention layers' of a model whose other
        layers are linear."""
        pages = self.num_blocks - self.free_blocks - 1
        return ([("latent" if self.latent else "full", pages,
                  self.block_bytes)], pages * self.block_size)

    def blocks_for(self, num_tokens: int) -> int:
        return (num_tokens + self.block_size - 1) // self.block_size

    def shard(self, sharding) -> None:
        """Re-place the pools under an explicit sharding (tensor-parallel
        serving: ``P(None, tp)`` — head-wise, axis 1 of
        (L, KVH, NB, bs, D)). The block layout, allocator, and block tables
        are untouched: a KV page is (layer, head, block) addressed, so
        splitting the head dim leaves every page id meaning the same thing
        on every shard — admission control stays topology-blind."""
        self.k = jax.device_put(self.k, sharding)
        self.v = jax.device_put(self.v, sharding)
        self._sharding = sharding

    def reserve_trash_block(self) -> None:
        """Pin block 0 as the trash block: padded/frozen rows' writes (and
        pad-position reads) are routed there, so it must never be handed to
        a sequence. Call once, right after construction."""
        got = self.allocator.allocate(1)
        assert got == [0], "trash block must be block 0 (allocate first)"

    @staticmethod
    def bucket_width(need: int, cap: int) -> int:
        """Next power of two >= ``need``, clamped to ``cap``. Shape buckets
        for block-table width and batch size: attention cost and jit-cache
        population both scale with the padded width, so bucketing keeps the
        compile count O(log) while padding waste stays < 2x."""
        w = 1
        while w < min(need, cap):
            w *= 2
        return min(w, cap)

    @staticmethod
    def floor_pow2(n: float) -> int:
        """Largest power of two <= ``n`` (min 1) — the frame-steps bucket
        floor shared by the adaptive frame sizer and the scheduler's
        pressure cap, so both draw from the SAME pow2 bucket set and the
        frame jit cache stays O(log) in the steps argument."""
        p = 1
        while p * 2 <= n:
            p *= 2
        return p

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def write(self, block_ids: jnp.ndarray, start_pos: int, new_k, new_v):
        """Scatter S new tokens into the paged pools.

        block_ids: (max_blocks,) int32 block table of the sequence;
        start_pos: int, first logical slot to write; new_k/new_v: (L, S, KVH, D).
        """
        if self.quantized:
            raise NotImplementedError(
                "write() takes raw float rows; quantized pools are written "
                "by the compiled loops via quantize_kv_lanes")
        s = new_k.shape[1]
        pos = start_pos + jnp.arange(s)
        blk = block_ids[pos // self.block_size]       # (S,) physical block
        off = pos % self.block_size                    # (S,) offset in block
        self.k = self.k.at[:, :, blk, off].set(new_k.transpose(0, 2, 1, 3))
        self.v = self.v.at[:, :, blk, off].set(new_v.transpose(0, 2, 1, 3))

    # ------------------------------------------------------------------
    # page movement (KV memory hierarchy: COW copies + host-RAM swap tier)
    #
    # All three helpers are frame-BOUNDARY device ops: the prefix cache's
    # copy-on-write block copy, and the swap tier's page read/restore. They
    # are jitted (the pool-donating ones in-place) and registered in
    # ``analysis/programs.py`` so graft-lint GL001/GL002/GL004 cover them
    # like the frame loops; block-id operands are padded to power-of-two
    # buckets (pad id 0 = the trash block) so the jit cache stays O(log).
    # Call sites must REBIND the donated pools from the result tuple —
    # ``kv.k, kv.v = kv.copy_blocks(kv.k, kv.v, src, dst)`` — the GL002
    # AST cross-check enforces it (ast_checks.DISPATCH_DONATIONS).
    # ------------------------------------------------------------------

    @staticmethod
    def _build_copy_blocks():
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def copy_blocks(kpool, vpool, src, dst):
            """Copy whole pages src[i] -> dst[i] inside the (donated)
            pools — the COW block copy. Pad pairs are (0, 0): the trash
            block copied onto itself."""
            return (kpool.at[:, :, dst].set(kpool[:, :, src]),
                    vpool.at[:, :, dst].set(vpool[:, :, src]))
        return copy_blocks

    @staticmethod
    def _build_gather_pages():
        @jax.jit
        def gather_pages(kpool, vpool, ids):
            """Read pages ``ids`` out of the pools as one
            (L, KVH, n, bs, D) pair (swap-out staging; the caller's
            ``np.asarray`` is the boundary D2H transfer)."""
            return jnp.take(kpool, ids, axis=2), jnp.take(vpool, ids, axis=2)
        return gather_pages

    @staticmethod
    def _build_scatter_pages():
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def scatter_pages(kpool, vpool, ids, kp, vp):
            """Write page payloads back into the (donated) pools at
            ``ids`` (swap-in restore). Pad ids are 0: garbage lands in the
            trash block, which is never read as live content. Payload
            dtype must already match the pool — the host wrapper rejects
            mixed-dtype moves loudly (a blind astype here would turn an
            f32-era tier record restored into an int8 pool into silently
            corrupted scale lanes)."""
            return (kpool.at[:, :, ids].set(kp),
                    vpool.at[:, :, ids].set(vp))
        return scatter_pages

    def _pad_ids(self, ids: List[int], pad: int = 0) -> jnp.ndarray:
        w = self.bucket_width(max(len(ids), 1), self.num_blocks)
        out = np.full((w,), pad, np.int32)
        out[:len(ids)] = ids
        return jnp.asarray(out)

    def _fn(self, name: str):
        # the page movers are pure functions of their operands (no
        # closed-over state), so every cache instance shares ONE jit per
        # helper — a fresh engine reuses the compiled program instead of
        # paying a recompile inside some request's TTFT
        if name not in _PAGE_FNS:
            _PAGE_FNS[name] = getattr(BlockedKVCache, f"_build_{name}")()
        return _PAGE_FNS[name]

    def copy_blocks(self, kpool, vpool, src_ids: List[int],
                    dst_ids: List[int]):
        """COW page copy at a frame boundary; returns the updated (donated)
        pools — rebind them."""
        assert len(src_ids) == len(dst_ids)
        return self._fn("copy_blocks")(kpool, vpool, self._pad_ids(src_ids),
                                       self._pad_ids(dst_ids))

    def read_pages(self, block_ids: List[int]):
        """Swap-out read: pages as HOST numpy (L, KVH, n, bs, D) k/v pair.
        One boundary D2H transfer per pool; under tensor parallelism the
        pools are head-sharded, so the transfer assembles per-shard slices
        along axis 1."""
        kp, vp = self._fn("gather_pages")(self.k, self.v,
                                          self._pad_ids(block_ids))
        n = len(block_ids)
        return np.asarray(kp)[:, :, :n], np.asarray(vp)[:, :, :n]

    def scatter_pages(self, kpool, vpool, block_ids: List[int],
                      k_pages: np.ndarray, v_pages: np.ndarray):
        """Swap-in restore: scatter host page payloads into the (donated)
        pools at ``block_ids``; returns the updated pools — rebind them.
        Under tensor parallelism the update is placed with the pools'
        sharding first, so the scatter stays shard-local.

        Mixed-dtype moves fail loudly: restoring a record written by a
        differently-typed pool (e.g. an f32-era tier record into an int8
        pool) would either corrupt packed scale lanes or reinterpret int8
        bytes as floats. Tier records carry a versioned layout field
        (``kv_hierarchy``) precisely so this surfaces as an error at the
        boundary, never as silent coercion."""
        for nm, pages, pool in (("k", k_pages, kpool), ("v", v_pages, vpool)):
            if np.dtype(pages.dtype) != np.dtype(pool.dtype):
                raise ValueError(
                    f"scatter_pages: {nm}-page payload dtype {pages.dtype} "
                    f"!= pool dtype {pool.dtype} — refusing the mixed-dtype "
                    "move (stale tier record from a differently-quantized "
                    "pool?); re-ingest the sequence instead")
        ids = self._pad_ids(block_ids)
        w = int(ids.shape[0])
        n = len(block_ids)
        if w > n:   # pad payload rows to the id bucket (land in trash)
            reps = [(0, 0)] * 5
            reps[2] = (0, w - n)
            k_pages = np.pad(k_pages, reps)
            v_pages = np.pad(v_pages, reps)
        if self._sharding is not None:
            k_pages = jax.device_put(jnp.asarray(k_pages), self._sharding)
            v_pages = jax.device_put(jnp.asarray(v_pages), self._sharding)
        return self._fn("scatter_pages")(kpool, vpool, ids, k_pages, v_pages)

    def gather(self, block_table: jnp.ndarray):
        """block_table: (B, max_blocks) → (L, B, max_blocks*block_size, KVH, D)
        contiguous logical view (padding blocks read block 0 — callers mask
        by sequence length). Quantized pools return PACKED rows (D + scale
        lanes) — dequantize with ``dequantize_kv_lanes``."""
        k = jnp.take(self.k, block_table, axis=2)      # (L, KVH, B, max_blocks, bs, D)
        v = jnp.take(self.v, block_table, axis=2)
        l, kvh, b, nb, bs, d = k.shape
        k = k.reshape(l, kvh, b, nb * bs, d).transpose(0, 2, 3, 1, 4)
        v = v.reshape(l, kvh, b, nb * bs, d).transpose(0, 2, 3, 1, 4)
        return (k, v)


class LayeredKVCache:
    """One ``BlockedKVCache`` a cache kind (``cache_kinds``), behind the
    attributes the serve loop reads of a cache. ``k`` / ``v`` are TUPLES of
    the kinds' pools, in the kinds' order: the frame programs take and give
    them back as they take one pool of a model of one kind. Every count
    without a kind's name (``num_blocks``, ``free_blocks``, ``blocks_for``,
    ``allocator``) is the table kind's, whose pool is what grows with the
    context and what ``num_kv_blocks`` sizes; a ring kind's pool follows
    from the slots: ``slots * ring`` pages and the trash page."""

    latent = False      # every kind's pools are K and V by head

    def __init__(self, kinds, kv_heads: int, head_dim: int, num_blocks: int,
                 slots: int, block_size: int = 64, dtype=jnp.bfloat16):
        self.kinds = tuple(kinds)
        self.groups = tuple(
            BlockedKVCache(len(kind.layers), kv_heads, head_dim,
                           num_blocks if kind.ring is None
                           else slots * kind.ring + 1,
                           block_size=block_size, dtype=dtype)
            for kind in self.kinds)
        self.block_size = block_size

    @property
    def k(self):
        return tuple(g.k for g in self.groups)

    @k.setter
    def k(self, pools):
        for g, pool in zip(self.groups, pools):
            g.k = pool

    @property
    def v(self):
        return tuple(g.v for g in self.groups)

    @v.setter
    def v(self, pools):
        for g, pool in zip(self.groups, pools):
            g.v = pool

    @property
    def rings(self):
        """(cache, ring) of every ring kind, in the kinds' order."""
        return tuple((g, kind.ring)
                     for g, kind in zip(self.groups[1:], self.kinds[1:]))

    # ---- the table kind's, under the names a cache of one kind gives ----

    @property
    def allocator(self):
        return self.groups[0].allocator

    @property
    def num_blocks(self) -> int:
        return self.groups[0].num_blocks

    @property
    def free_blocks(self) -> int:
        return self.groups[0].free_blocks

    @property
    def block_bytes(self) -> int:
        return self.groups[0].block_bytes

    def blocks_for(self, num_tokens: int) -> int:
        return self.groups[0].blocks_for(num_tokens)

    def reserve_trash_block(self) -> None:
        for g in self.groups:
            g.reserve_trash_block()

    def in_use(self):
        """([(kind's name, pages sequences hold, bytes a page)], tokens the
        table kind's pages hold: what admission reserved for the live
        sequences). The trash page is no sequence's."""
        rows = [(kind.name, g.num_blocks - g.free_blocks - 1, g.block_bytes)
                for kind, g in zip(self.kinds, self.groups)]
        return rows, rows[0][1] * self.block_size
