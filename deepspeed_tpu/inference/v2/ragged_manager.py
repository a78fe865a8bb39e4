"""Sequence state tracking for continuous batching.

Analog of ``inference/v2/ragged/ragged_manager.py:19`` (DSStateManager) and
``sequence_descriptor.py`` (DSSequenceDescriptor), plus the device-side slot
table backing the frame-based serving loop: per-slot state (last token,
cached-token counts, per-row limits/EOS/temperature, padded block tables)
lives on DEVICE between frames; the host keeps numpy mirrors purely for
admission control and never reads slot state back mid-frame.
"""

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .telemetry import N_STATS, zero_stats


@dataclasses.dataclass
class DSSequenceDescriptor:
    uid: int
    blocks: List[int] = dataclasses.field(default_factory=list)
    seen_tokens: int = 0            # tokens whose KV is in cache
    pending: List[int] = dataclasses.field(default_factory=list)   # not yet prefetched
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    slot: int = -1                  # decode-slot index, -1 = not resident
    # a model of mixed cache kinds (kv_cache.LayeredKVCache): the pages of
    # each ring kind's ring, one list a kind; ``blocks`` is the table kind's
    ring_blocks: List[List[int]] = dataclasses.field(default_factory=list)
    # KV memory hierarchy (kv_hierarchy.py): tokens whose pages are already
    # valid at admission (mapped prefix-cache blocks, or swapped-in pages) —
    # prefill starts here instead of token zero. Reset when the blocks are
    # released (preemption eviction).
    resume_cached: int = 0
    # the prefix cache is probed ONCE per enqueue (a capacity-deferred miss
    # stays a miss across retries — re-probing every boundary would let a
    # 50-boundary deferral record 50 lookups and skew the hit rate)
    hier_probed: bool = False
    # committed-stream position this sequence has published prefix blocks
    # up to, and the chain entry id at that position (monotonic; the
    # publish walk resumes there instead of re-hashing from token zero)
    published_upto: int = 0
    publish_parent: int = -1        # kv_hierarchy.CHAIN_ROOT
    # disaggregated serving (engine role="prefill"): FULL blocks already
    # published to the shared swap tier as request-record segments — the
    # boundary-incremental publish cursor (kv_hierarchy
    # ``publish_request_segment``)
    tier_blocks: int = 0
    # handoff pipelining (engine ``handoff_pipeline``): the FINAL record
    # segment was already published at the boundary BEFORE the first-token
    # frame (its write I/O overlaps that frame) — the handoff boundary
    # does no page I/O. ``tier_partial`` marks a final publish whose tail
    # block was only partially committed (its snapshot is stale above the
    # record watermark, so a mispredicted handoff must republish from
    # block zero rather than append past it).
    tier_final: bool = False
    tier_partial: bool = False

    @property
    def in_prefill(self) -> bool:
        return len(self.pending) > 0

    @property
    def cur_len(self) -> int:
        return self.seen_tokens


class DSStateManager:
    """Owns sequence descriptors + their KV block lists."""

    def __init__(self, kv_cache, max_tracked_sequences: int = 2048):
        self.kv_cache = kv_cache
        # (cache, ring) of every ring kind of a cache by layer kind
        # (``LayeredKVCache.rings``); a cache of one kind has none
        self.rings = getattr(kv_cache, "rings", ())
        self.max_tracked = max_tracked_sequences
        self.seqs: Dict[int, DSSequenceDescriptor] = {}

    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        if uid in self.seqs:
            return self.seqs[uid]
        if len(self.seqs) >= self.max_tracked:
            raise RuntimeError(f"tracking limit reached ({self.max_tracked} sequences)")
        seq = DSSequenceDescriptor(uid=uid)
        self.seqs[uid] = seq
        return seq

    def ensure_capacity(self, seq: DSSequenceDescriptor, new_total_tokens: int) -> bool:
        """Grow the sequence's block list to hold ``new_total_tokens``;
        returns False if the pool can't satisfy it. Under caches by layer
        kind the sequence also takes its ring of every ring kind
        (``min(blocks_for(tokens), ring)`` pages), all or nothing."""
        need = self.kv_cache.blocks_for(new_total_tokens) - len(seq.blocks)
        ring_need = []
        if self.rings:
            if not seq.ring_blocks:
                seq.ring_blocks = [[] for _ in self.rings]
            ring_need = [min(kv.blocks_for(new_total_tokens), ring) - len(held)
                         for (kv, ring), held in zip(self.rings,
                                                     seq.ring_blocks)]
            if any(n > kv.free_blocks
                   for (kv, _), n in zip(self.rings, ring_need)):
                return False
        if need > self.kv_cache.allocator.free_blocks:
            return False
        if need > 0:
            seq.blocks.extend(self.kv_cache.allocator.allocate(need))
        for (kv, _), n, held in zip(self.rings, ring_need, seq.ring_blocks):
            if n > 0:
                held.extend(kv.allocator.allocate(n))
        return True

    def release_blocks(self, seq: DSSequenceDescriptor) -> None:
        """Give the sequence's pages back, of every kind (an eviction: the
        descriptor lives on and is re-admitted cold)."""
        if seq.blocks:
            self.kv_cache.allocator.free(seq.blocks)
            seq.blocks = []
        for (kv, _), held in zip(self.rings, seq.ring_blocks):
            if held:
                kv.allocator.free(held)
        seq.ring_blocks = []

    def flush_sequence(self, uid: int):
        seq = self.seqs.pop(uid, None)
        if seq is not None:
            self.release_blocks(seq)

    @staticmethod
    def block_table(seq: DSSequenceDescriptor, max_blocks: int,
                    blocks=None) -> np.ndarray:
        """Padded block-table ROW as host numpy. Callers stack rows and ship
        ONE device transfer per step — returning a jnp array here cost a
        host->device round trip per sequence per call. ``blocks``: another
        of the sequence's lists (a ring kind's pages, ``max_blocks`` the
        ring) in place of ``seq.blocks``."""
        blocks = seq.blocks if blocks is None else blocks
        if len(blocks) > max_blocks:
            # never truncate: positions past a truncated table would gather
            # a wrong page and silently overwrite live KV
            raise ValueError(
                f"uid={seq.uid}: {len(blocks)} blocks exceed the "
                f"{max_blocks}-wide table (sequence past max_seq_len?)")
        tbl = np.zeros((max_blocks,), np.int32)
        tbl[:len(blocks)] = blocks
        return tbl

    @property
    def tracked_sequences(self):
        return dict(self.seqs)


#: the slot arrays an admission rewrites a row of
_ADMIT_STATE = ("prompts", "tables", "ring_tables", "prompt_lens", "limits",
                "eos_ids", "temps", "cached", "produced", "last_tok",
                "penult", "done", "poison", "nonfinite", "recurrent",
                "block")


#: the slots' axis of what a model's mixers keep a slot
#: (``PagedModelRunner.recurrent_shapes``), by the array's rank: a linear
#: layer's state (layers, slots, Hv, dk, dv), and a convolution tail, a
#: linear layer's or a conv layer's (layers, K - 1, slots, channels). A
#: stack of conv layers carries tails alone
RECURRENT_SLOT_AXES = {5: 1, 4: 2}


@functools.partial(jax.jit, donate_argnums=(0,))
def _admit_rows(state, idx, prompts, tables, rings, ints, temps):
    """``DeviceSlotTable.admit``'s device writes as one program: row i of
    each staged array into row ``idx[i]`` of the slot array; ``idx`` past the
    table drops the row. ``ints``: (slots, 4) prompt length, limit, EOS id,
    admission watermark. A slot freed by quarantine must not hand its
    poison / latch state to the next tenant of the row, so both clear; nor
    may a model with linear or conv layers hand on the row's recurrent
    state and convolution tails (``recurrent``, empty for every other
    model): a new tenant's are zeros, what a sequence has before its first
    token. Nor
    may a model that generates by diffusion over blocks hand on a
    half-denoised block (``block`` = (tokens, masked), empty for every
    other model): a new tenant's is all masked."""
    def put(a, v):
        return a.at[idx].set(v, mode="drop")

    out = dict(state, prompts=put(state["prompts"], prompts),
               tables=put(state["tables"], tables),
               ring_tables=tuple(map(put, state["ring_tables"], rings)),
               temps=put(state["temps"], temps))
    for j, name in enumerate(("prompt_lens", "limits", "eos_ids", "cached")):
        out[name] = put(state[name], ints[:, j])
    for name in ("produced", "last_tok", "penult"):
        out[name] = put(state[name], 0)
    for name in ("done", "poison", "nonfinite"):
        out[name] = put(state[name], False)
    # (no operation at all where the tuple is empty)
    def fresh(a):
        axis = RECURRENT_SLOT_AXES[a.ndim]
        return jnp.where(
            put(jnp.zeros((a.shape[axis],), bool), True).reshape(
                (1,) * axis + (-1,) + (1,) * (a.ndim - axis - 1)),
            jnp.zeros((), a.dtype), a)

    out["recurrent"] = tuple(map(fresh, state["recurrent"]))
    # (tokens, masked), or empty: no operation at all
    out["block"] = tuple(put(a, fill)
                         for a, fill in zip(state["block"], (0, True)))
    return out


class DeviceSlotTable:
    """Fixed set of serving slots whose state is device-resident.

    The frame loop (``PagedModelRunner.frame_loop``) reads and writes these
    arrays as a donated carry; between frames they simply stay on device.
    The host mirrors (``*_h`` numpy arrays, ``uid_of_slot``/``slot_of_uid``)
    exist only so admission control and retirement can be decided without a
    device read-back: ``absorb`` replays the frame's emit mask against the
    mirrors using the exact arithmetic of the in-graph body, so mirror and
    device state never diverge.

    A free slot is a frozen row: ``done=True, limits=0`` — the frame body
    gives it width 0, its positions go to -1, and the pager routes its
    (masked) writes to the trash block.

    Under speculative serving, ``cached`` doubles as the per-row COMMITTED
    watermark: a speculative step writes target KV for all gamma+1 verified
    positions, but the in-graph rollback selects ``cached`` back to the
    accepted prefix — pool slots at or beyond the watermark may hold
    rejected speculation and are simply overwritten by the next step's
    writes (no host-side block surgery). ``penult`` carries the token at
    position ``cached - 1``, which the draft re-feeds each step to keep its
    own KV pools on the committed prefix without a catch-up pass.
    """

    def __init__(self, n_slots: int, prompt_width: int, table_width: int, rng,
                 tp=None, debug_replicas: bool = False,
                 n_stats: int = N_STATS, rings=(), hidden=None,
                 recurrent=(), block=None):
        self.n_slots = n_slots
        self.n_stats = n_stats     # lanes of the runner's stat vector
        # tensor-parallel serving (tp.TPContext): every slot array is
        # REPLICATED over the tp mesh — the frame loop's shard_map treats
        # them as unmapped carries, and every frame-boundary mutation
        # (admit/evict/set_poison) goes through ``_dev``, which places the
        # update replicated so it lands as ONE logical mesh-wide write
        # (XLA SPMD broadcasts it), never a per-shard host loop.
        self.tp = tp
        self.debug_replicas = debug_replicas
        if tp is not None:
            self._rep = tp.rep()
        zi = lambda *shape: self._dev(jnp.zeros(shape, jnp.int32))  # noqa: E731
        # device state (frame-loop inputs; carry arrays are donated)
        self.prompts = zi(n_slots, max(1, prompt_width))
        self.prompt_lens = zi(n_slots)
        self.limits = zi(n_slots)
        self.eos_ids = self._dev(jnp.full((n_slots,), -1, jnp.int32))
        self.temps = self._dev(jnp.zeros((n_slots,), jnp.float32))
        self.tables = zi(n_slots, max(1, table_width))
        # caches by layer kind: one (n_slots, ring) table a ring kind, of
        # the ring's own width from the start; the frame program then takes
        # (tables, *ring_tables) where it takes ``tables`` of one kind
        self.ring_tables = tuple(zi(n_slots, ring) for ring in rings)
        self.cached = zi(n_slots)
        self.produced = zi(n_slots)
        self.last_tok = zi(n_slots)
        self.penult = zi(n_slots)          # speculative carry: token at cached-1
        # self-speculative carry (``hidden`` = (width, dtype), a model that
        # drafts with its own prediction module): the stack's hidden state
        # at cached-1. A new tenant's first chunk never reads it
        self.hidden = None if hidden is None else self._dev(
            jnp.zeros((n_slots, hidden[0]), hidden[1]))
        # a model with linear or conv layers (``recurrent`` = the (shape,
        # dtype) of each array, ``PagedModelRunner.recurrent_shapes``):
        # every slot's recurrent states and convolution tails (a conv
        # stack's: tails alone), not a table of pages but a row a slot;
        # ``admit`` zeroes a new tenant's. () otherwise
        self.recurrent = tuple(self._dev(jnp.zeros(shape, dtype))
                               for shape, dtype in recurrent)
        # a model that generates by diffusion over blocks (``block`` = (L
        # positions a block, the positions a denoising step unmasks at
        # least)): the block a
        # row past its prompt holds, tokens and masked flags, L a slot, on
        # the carry as ``recurrent`` is; ``admit`` masks a new tenant's
        # whole. () otherwise. The replay then mirrors
        # ``model_runner._block_plan`` (``_absorb_block``), two blocks wide
        # where another model's narrow frame is one position
        self.block_length, self.unmask_per_step = block or (0, 0)
        self.block = () if block is None else (
            zi(n_slots, block[0]),
            self._dev(jnp.ones((n_slots, block[0]), bool)))
        # denoising steps a row has run on the block it holds (host mirror)
        self.denoised_h = np.zeros((n_slots,), np.int64)
        self.done = self._dev(jnp.ones((n_slots,), bool))
        # fault-injection flag (frame NaNs the row's logits while set) and
        # the in-graph finite-check latch — both ride the donated carry
        # like stats, so arming a fault or catching a NaN never retraces
        self.poison = self._dev(jnp.zeros((n_slots,), bool))
        self.nonfinite = self._dev(jnp.zeros((n_slots,), bool))
        self.rng = self._dev(rng)
        # in-graph telemetry counters (telemetry.n_stats): accumulate on the
        # donated carry; the host reads AND rebases them only at frame
        # boundaries (stats_delta), so the int32 lanes can never wrap
        # within one read window. Under tp it is replicated like the rest.
        self.stats = self._fresh_stats()
        # a frame's trip count by value, staged once each (``_trips``)
        self._trip_scalars: Dict[int, jax.Array] = {}
        # host mirrors — admission control only
        self.uid_of_slot = np.full((n_slots,), -1, np.int64)
        self.slot_of_uid: Dict[int, int] = {}
        self.cached_h = np.zeros((n_slots,), np.int64)
        self.start_h = np.zeros((n_slots,), np.int64)   # cached_h at admit
        self.plen_h = np.zeros((n_slots,), np.int64)
        self.produced_h = np.zeros((n_slots,), np.int64)
        self.limit_h = np.zeros((n_slots,), np.int64)
        self.eos_h = np.full((n_slots,), -1, np.int64)
        self.temps_h = np.zeros((n_slots,), np.float64)
        self.done_h = np.ones((n_slots,), bool)

    def _dev(self, x):
        """Stage a (small) host value onto the device — replicated over the
        tp mesh when tensor-parallel, plain ``jnp.asarray`` otherwise. Every
        frame-boundary H2D write funnels through here so sharded and
        single-chip engines have the same one-write-per-mutation shape."""
        if self.tp is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), self._rep)

    def _fresh_stats(self):
        return self._dev(zero_stats(self.n_stats))

    def _trips(self, n_steps):
        """A frame's ``n_steps`` as the device scalar the program takes,
        staged at a value's first use: a Python int would be one host to
        device write a dispatch, 0.45 ms a frame on the chip (``PERF.md``,
        PR 41)."""
        if n_steps is None:
            return None
        n = self._trip_scalars.get(n_steps)
        if n is None:
            n = self._trip_scalars[n_steps] = self._dev(np.int32(n_steps))
        return n

    @property
    def committed_h(self) -> np.ndarray:
        """Host mirror of the per-row committed watermark: tokens whose
        target KV is final (``cached`` — pool slots at or beyond it may hold
        rejected speculation awaiting overwrite)."""
        return self.cached_h

    # ---------------- host-mirror queries (no device sync) ----------------

    def free_slots(self) -> int:
        return int((self.uid_of_slot < 0).sum())

    def live_count(self) -> int:
        return self.n_slots - self.free_slots()

    @property
    def prefill_end_h(self) -> np.ndarray:
        """Where each row's prefill ends: its prompt's length, and for a
        model that generates by diffusion over blocks the prompt's whole
        blocks (the remainder joins the first generated block)."""
        if not self.block_length:
            return self.plen_h
        return self.plen_h // self.block_length * self.block_length

    def prefill_steps_left(self, width: int) -> int:
        """Steps of a ``width``-wide frame until no live row prefills: a
        prefilling row takes ``min(width, what is left)`` prompt tokens a
        step whatever the other rows do, so the count is exact (0: nothing
        prefills, the next frame is narrow)."""
        live = self.uid_of_slot >= 0
        left = np.where(live, self.prefill_end_h - self.cached_h,
                        0).max(initial=0)
        return int(-(-left // width))

    def prefill_carried(self) -> bool:
        """Whether a live row is partway through its prompt: an earlier
        frame took chunks of it and left some (a row admitted at this
        boundary, behind a prefix hit too, has begun nothing yet)."""
        live = self.uid_of_slot >= 0
        return bool((live & (self.start_h < self.cached_h)
                     & (self.cached_h < self.prefill_end_h)).any())

    def steps_to_first_finish(self) -> int:
        """Steps of a narrow frame until the first live row emits the LAST
        token of its budget, at a token a step: exact without a draft and
        without an EOS, with either the latest that it can be."""
        live = self.uid_of_slot >= 0
        if self.block_length:
            return int(max(1, min(self._block_steps_left(i)
                                  for i in np.flatnonzero(live))))
        return int(max(1, (self.limit_h - self.produced_h)[live].min()))

    def _block_cost(self, start: int, plen: int) -> int:
        """Forwards the block at ``start`` costs a row whose prompt is
        ``plen`` long, at the fewest positions a denoising step unmasks
        (L / S): the steps that unmask its positions past the prompt. The
        first of them is also the commit of the block before; a row's last
        block takes one forward more, its commit alone
        (``model_runner._block_scan_body``)."""
        masked = start + self.block_length - max(start, plen)
        return -(-masked // self.unmask_per_step)

    def _block_steps_left(self, i: int) -> int:
        """Steps until row ``i`` emits the last token of its budget: its
        blocks still to commit at ``_block_cost`` each, less the denoising
        steps it has run on the one it holds, and the last one's commit.
        Exact where a step unmasks L / S positions
        (``low_confidence_static``, or no confidence past the threshold)
        and without an EOS; with either the latest that it can be."""
        blk = self.block_length
        start, plen = int(self.cached_h[i]), int(self.plen_h[i])
        start = max(start, plen // blk * blk)
        want = int(self.limit_h[i] - self.produced_h[i])
        steps = -int(self.denoised_h[i])
        if want <= 0:
            return steps
        # the block it holds may begin inside the prompt; every block
        # behind it is past the prompt and costs alike
        first = start + blk - max(start, plen)
        further = -(-max(0, want - first) // blk)
        return (steps + self._block_cost(start, plen)
                + further * self._block_cost(start + blk, plen) + 1)

    def all_greedy(self) -> bool:
        live = self.uid_of_slot >= 0
        return bool(np.all(self.temps_h[live] <= 0.0))

    # ---------------- frame-boundary mutations ----------------

    def ensure_widths(self, prompt_need: int, table_need: int,
                      prompt_cap: int, table_cap: int) -> None:
        """Grow the padded prompt buffer / block-table width to the next
        power-of-two bucket (keeps the jit cache O(log) in table width).
        Admission control guarantees ``need <= cap`` (over-context requests
        are clamped or rejected before they reach the slot table)."""
        from .kv_cache import BlockedKVCache
        assert prompt_need <= prompt_cap and table_need <= table_cap, \
            "admission let an over-context request through"
        p = self.prompts.shape[1]
        if prompt_need > p:
            new_p = BlockedKVCache.bucket_width(prompt_need, prompt_cap)
            self.prompts = self._dev(
                jnp.pad(self.prompts, ((0, 0), (0, new_p - p))))
        t = self.tables.shape[1]
        if table_need > t:
            new_t = BlockedKVCache.bucket_width(table_need, table_cap)
            self.tables = self._dev(
                jnp.pad(self.tables, ((0, 0), (0, new_t - t))))

    def admit(self, items: List[Tuple]) -> None:
        """Admit arrivals into free slots: ``items`` is a list of
        (uid, seq, prompt_tokens, limit, temperature, eos_id[, cached0]).
        ``cached0`` (default 0) is the KV-hierarchy admission watermark:
        tokens whose pages are already valid in the row's block table
        (mapped prefix-cache blocks or swapped-in pages) — the frame body
        starts prefill there, exactly like resuming a mid-prefill row.
        All device writes are ONE program (``_admit_rows``), whatever the
        number of sequences that arrive at this frame boundary."""
        free = [i for i in range(self.n_slots) if self.uid_of_slot[i] < 0]
        assert len(items) <= len(free), "admit() beyond free slots"
        p_w = int(self.prompts.shape[1])
        t_w = int(self.tables.shape[1])
        rows, p_rows, t_rows = [], [], []
        plens, lims, eoss, temps, cacheds = [], [], [], [], []
        for item, slot in zip(items, free):
            (uid, seq, toks, limit, temp, eos), rest = item[:6], item[6:]
            cached0 = int(rest[0]) if rest else 0
            toks = np.asarray(toks, np.int32).reshape(-1)
            assert 0 <= cached0 < max(len(toks), 1), \
                "admission watermark must leave >= 1 token to prefill"
            self.uid_of_slot[slot] = uid
            self.slot_of_uid[uid] = slot
            seq.slot = slot
            self.cached_h[slot] = self.start_h[slot] = cached0
            self.plen_h[slot] = len(toks)
            self.produced_h[slot] = 0
            self.denoised_h[slot] = 0
            self.limit_h[slot] = limit
            self.eos_h[slot] = -1 if eos is None else eos
            self.temps_h[slot] = temp
            self.done_h[slot] = False
            p_row = np.zeros((p_w,), np.int32)
            p_row[:len(toks)] = toks
            # shared helper keeps the no-truncate guard in one place
            t_row = DSStateManager.block_table(seq, t_w)
            rows.append(slot)
            p_rows.append(p_row)
            t_rows.append(t_row)
            plens.append(len(toks))
            lims.append(limit)
            eoss.append(-1 if eos is None else eos)
            temps.append(temp)
            cacheds.append(cached0)
        # ONE program whatever the batch: the batch's values ride padded to
        # a row a slot, the index of a row past the batch is out of range
        # and its write dropped. (One ``.at[rows].set`` an array, eagerly,
        # was ~14 programs and ~28 transfers with the device idle: 53 ms a
        # wide frame in PR 39's traces, and programs by batch size.) _dev
        # places every staged operand replicated under tp, so this stays one
        # logical mesh-wide update, not a per-shard host loop
        k, n = len(rows), self.n_slots

        def padded(values, dtype):
            values = np.asarray(values, dtype)
            out = np.zeros((n,) + values.shape[1:], dtype)
            out[:k] = values
            return self._dev(out)

        state = {name: getattr(self, name) for name in _ADMIT_STATE}
        idx = np.full((n,), n, np.int32)     # n: past the table, dropped
        idx[:k] = rows
        state = _admit_rows(
            state, self._dev(idx),
            padded(np.stack(p_rows), np.int32),
            padded(np.stack(t_rows), np.int32),
            tuple(padded(np.stack([
                DSStateManager.block_table(item[1], table.shape[1],
                                           item[1].ring_blocks[i])
                for item in items]), np.int32)
                for i, table in enumerate(self.ring_tables)),
            padded(np.stack([plens, lims, eoss, cacheds], axis=1), np.int32),
            padded(temps, np.float32))
        for name, value in state.items():
            setattr(self, name, value)

    def retire(self, uid: int) -> None:
        """Free the slot on the host side; the device row is already frozen
        (EOS set ``done`` in-graph, a limit-finisher sits at
        ``produced == limits`` — either way the frame body gives it width 0
        until ``admit`` rewrites the row)."""
        slot = self.slot_of_uid.pop(uid)
        self.uid_of_slot[slot] = -1
        self.done_h[slot] = True

    def evict(self, uid: int) -> None:
        """Evict a LIVE row back to the host at a frame boundary (scheduler
        preemption). Unlike ``retire``, the device row is NOT already
        frozen, so this writes ``done=True, limits=0`` — the frozen-row
        invariant — before freeing the slot: the next frame gives the row
        width 0 and ``admit`` can rewrite it for a new request. One tiny
        host→device write at the boundary; nothing is read back (the host
        mirrors already hold the committed watermark and emitted tokens,
        so the caller re-queues prompt + emitted for re-prefill). Under
        tensor parallelism the carry is replicated, so this stays ONE
        logical write — ``_dev`` places the index replicated and XLA SPMD
        applies the update mesh-wide, never a per-shard loop."""
        slot = self.slot_of_uid.pop(uid)
        self.uid_of_slot[slot] = -1
        self.done_h[slot] = True
        idx = self._dev(jnp.asarray([slot], jnp.int32))
        self.done = self.done.at[idx].set(True)
        self.limits = self.limits.at[idx].set(0)
        # quarantine evicts through here too: clear the fault flags so the
        # freed slot's latch cannot re-report at later boundaries
        self.poison = self.poison.at[idx].set(False)
        self.nonfinite = self.nonfinite.at[idx].set(False)

    # ---------------- frame execution + host replay ----------------

    def dispatch_frame(self, runner, params, kv, width: int, steps: int,
                       greedy: bool, draft=None, repair=False, n_steps=None):
        """Dispatch one frame of ``n_steps`` steps (an operand of the frame
        program, at most its static capacity ``steps`` and that by default)
        and swap the donated carry in place, returning the (tokens, emit)
        DEVICE arrays of ``steps`` rows — no host transfer
        happens here (the telemetry transfer-guard test wraps exactly this
        method). ``draft="self"`` runs the frame in which the model's own
        prediction module drafts (``hidden`` rides the carry, no pool more);
        ``draft=(draft_runner, draft_params, draft_kv, gamma)``
        runs the speculative frame: the draft's paged KV pools ride the same
        donated carry and share this table's block tables. The in-graph
        telemetry counters (``self.stats``) ride the carry too and come back
        as a device array."""
        if draft is None or draft == "self":
            tables = self.tables
            if self.ring_tables:
                tables = (tables,) + self.ring_tables
            # a self-draft's ``hidden`` goes in last and comes back behind
            # ``last_tok``, where the carry has it
            hidden = [] if draft is None else [self.hidden]
            # what the mixers keep a slot (``recurrent``) goes in by name
            # and comes back last; a half-denoised block likewise (a model
            # has one or the other)
            last = "block" if self.block else \
                "recurrent" if self.recurrent else None
            extra = {last: getattr(self, last)} if last else {}
            out = runner.frame_loop(
                params, self.prompts, self.prompt_lens, self.limits,
                self.eos_ids, self.temps, tables, self.cached,
                self.produced, self.last_tok, self.done, self.poison,
                self.nonfinite, self.stats, self.rng, kv.k, kv.v, *hidden,
                width=width, steps=steps, greedy=greedy, repair=repair,
                n_steps=self._trips(n_steps), **extra)
            if last:
                *out, carried = out
                setattr(self, last, carried)
            (toks, emit, self.cached, self.produced, self.last_tok, *hidden,
             self.done, self.poison, self.nonfinite, self.stats, self.rng,
             kv.k, kv.v) = out
            if hidden:
                self.hidden, = hidden
            return toks, emit
        draft_runner, draft_params, draft_kv, gamma = draft
        (toks, emit, self.cached, self.produced, self.last_tok, self.penult,
         self.done, self.poison, self.nonfinite, self.stats, self.rng, kv.k,
         kv.v, draft_kv.k, draft_kv.v) = runner.frame_loop_spec(
            draft_runner, params, draft_params, self.prompts,
            self.prompt_lens, self.limits, self.eos_ids, self.temps,
            self.tables, self.cached, self.produced, self.last_tok,
            self.penult, self.done, self.poison, self.nonfinite, self.stats,
            self.rng, kv.k, kv.v, draft_kv.k, draft_kv.v, width=width,
            steps=steps, greedy=greedy, gamma=gamma, repair=repair,
            n_steps=self._trips(n_steps))
        return toks, emit

    def set_poison(self, uids: List[int]) -> None:
        """Arm the device poison flag for live rows (fault injection): the
        next frame NaNs their logits in-graph, exercising the REAL
        finite-check → quarantine path. One tiny host→device write at the
        boundary; unknown/retired uids are ignored (the fault raced a
        normal retirement — nothing to poison)."""
        rows = [self.slot_of_uid[u] for u in uids if u in self.slot_of_uid]
        if not rows:
            return
        idx = self._dev(jnp.asarray(rows, jnp.int32))
        self.poison = self.poison.at[idx].set(True)

    def clear_nonfinite(self, uids: List[int]) -> None:
        """Repair-policy boundary hook: the host decided these latched rows
        get another chance — clear the finite-check latch AND the poison
        flag (an injected fault is treated as a one-frame blip under
        repair), one batched host→device write at the boundary. Unknown /
        already-retired uids are ignored."""
        rows = [self.slot_of_uid[u] for u in uids if u in self.slot_of_uid]
        if not rows:
            return
        idx = self._dev(jnp.asarray(rows, jnp.int32))
        self.poison = self.poison.at[idx].set(False)
        self.nonfinite = self.nonfinite.at[idx].set(False)

    def resync_committed(self, uids: List[int]) -> None:
        """Re-read the device committed watermark for repaired rows. The
        host replay (``absorb``) cannot see WHICH steps a repaired row
        rolled back (the emit mask marks only that nothing was emitted), so
        after a repair boundary its ``cached_h`` mirror may run ahead of the
        device ``cached``; one tiny (B,) frame-boundary read — same budget
        class as ``nonfinite_uids`` — truths it up. produced/done/emissions
        are emit-mask-driven in the replay and never drift."""
        rows = [self.slot_of_uid[u] for u in uids if u in self.slot_of_uid]
        if not rows:
            return
        cached = np.asarray(self.cached)   # replicated under tp: full (B,)
        for r in rows:
            self.cached_h[r] = int(cached[r])

    def nonfinite_uids(self) -> List[int]:
        """Frame-boundary read of the in-graph finite-check latch: live
        uids whose logits went non-finite during the last frame (candidates
        for quarantine). One tiny (B,) device→host transfer per boundary —
        outside the frame, like ``stats_delta`` — and the ONLY read the
        poison-quarantine machinery performs."""
        flags = np.asarray(self.nonfinite)
        return [int(self.uid_of_slot[i]) for i in range(self.n_slots)
                if flags[i] and self.uid_of_slot[i] >= 0]

    def stats_delta(self) -> np.ndarray:
        """Frame-boundary read of the in-graph counters: returns the
        increment since the previous call and REBASES the device vector to
        zero, so the int32 lanes would need 2^31 events between reads to
        overflow. The caller owns the read cadence: the engine reads every
        frame while telemetry is enabled, and after a disabled stretch it
        discards the first (backlog, possibly wrapped) delta. Both the
        read and the fresh zero vector are frame-boundary transfers.

        Tensor-parallel: the vector is replicated like the rest of the
        carry, and every device keeps the copy its own shard accumulated.
        The copies agree by construction — each shard's counters derive
        exclusively from replicated carry values (emit masks, active
        masks, post-collective logits) — so the steady-state read fetches
        ONE copy (one small host read, preserving the zero-in-frame-D2H
        budget per boundary). With ``debug_replicas`` the read widens to
        every device's copy and ASSERTS they agree, turning a hypothetical
        replication bug (a collective missed somewhere in the forward)
        into a loud boundary failure instead of silently skewed
        telemetry."""
        if self.tp is not None and self.debug_replicas:
            rows = np.stack([np.asarray(s.data) for s in
                             self.stats.addressable_shards])  # (tp, N_STATS)
            if not (rows == rows[0]).all():
                raise AssertionError(
                    "frame stats diverged across tp shards — a shard-"
                    f"varying value leaked into the counters:\n{rows}")
        delta = np.asarray(self.stats).astype(np.int64)
        self.stats = self._fresh_stats()
        # the lanes are int32 counts rebased at every read; read as
        # unsigned they hold 2^32 before they wrap, which
        # ``check_stat_range`` holds the largest per-frame delta under
        return delta & 0xFFFFFFFF

    def absorb(self, toks: np.ndarray, emit: np.ndarray, width: int,
               n_steps=None):
        """Replay the frame against the host mirrors (same arithmetic as the
        in-graph body) → ({uid: [tokens emitted this frame]}, [finished uids]).
        A row finishes when it emits its EOS or reaches its token limit.
        ``n_steps``: the steps the frame ran, where fewer than its rows (the
        rows behind them are empty and did not happen: not replayed).
        Speculative frames hand in (steps, B, gamma+1) token/emit arrays —
        the mirrors replay the variable tokens-per-step emit mask exactly,
        so the committed watermark never needs a device read-back."""
        if n_steps is not None:
            toks, emit = toks[:n_steps], emit[:n_steps]
        if self.block_length:
            return self._absorb_block(toks, emit, width)
        if emit.ndim == 3:
            return self._absorb_spec(toks, emit, width)
        emissions: Dict[int, List[int]] = {}
        finished: List[int] = []
        live = [i for i in range(self.n_slots) if self.uid_of_slot[i] >= 0]
        for s in range(toks.shape[0]):
            for i in live:
                if self.done_h[i]:
                    continue
                if self.cached_h[i] < self.plen_h[i]:
                    self.cached_h[i] += min(width,
                                            self.plen_h[i] - self.cached_h[i])
                elif self.produced_h[i] < self.limit_h[i]:
                    self.cached_h[i] += 1
                else:
                    continue
                if emit[s, i]:
                    t = int(toks[s, i])
                    uid = int(self.uid_of_slot[i])
                    emissions.setdefault(uid, []).append(t)
                    self.produced_h[i] += 1
                    if t == self.eos_h[i] or self.produced_h[i] >= self.limit_h[i]:
                        self.done_h[i] = True
        for i in live:
            if self.done_h[i]:
                finished.append(int(self.uid_of_slot[i]))
        return emissions, finished

    def _absorb_spec(self, toks: np.ndarray, emit: np.ndarray, width: int):
        """Speculative replay: a decode row advances its committed watermark
        by however many tokens its emit row carries (accepted drafts + the
        bonus/correction token); prefill rows advance by the chunk and emit
        at most their first token in column 0 — the exact arithmetic of
        ``_spec_scan_body``."""
        emissions: Dict[int, List[int]] = {}
        finished: List[int] = []
        live = [i for i in range(self.n_slots) if self.uid_of_slot[i] >= 0]
        for s in range(toks.shape[0]):
            for i in live:
                if self.done_h[i]:
                    continue
                uid = int(self.uid_of_slot[i])
                if self.cached_h[i] < self.plen_h[i]:
                    self.cached_h[i] += min(width,
                                            self.plen_h[i] - self.cached_h[i])
                    if emit[s, i, 0]:
                        t = int(toks[s, i, 0])
                        emissions.setdefault(uid, []).append(t)
                        self.produced_h[i] += 1
                        if (t == self.eos_h[i]
                                or self.produced_h[i] >= self.limit_h[i]):
                            self.done_h[i] = True
                elif self.produced_h[i] < self.limit_h[i]:
                    m = 0
                    for k in range(emit.shape[2]):
                        if not emit[s, i, k]:
                            continue   # (the mask is a prefix; stay defensive)
                        t = int(toks[s, i, k])
                        emissions.setdefault(uid, []).append(t)
                        m += 1
                        self.produced_h[i] += 1
                        if (t == self.eos_h[i]
                                or self.produced_h[i] >= self.limit_h[i]):
                            self.done_h[i] = True
                    self.cached_h[i] += m
        for i in live:
            if self.done_h[i]:
                finished.append(int(self.uid_of_slot[i]))
        return emissions, finished

    def _absorb_block(self, toks: np.ndarray, emit: np.ndarray, width: int):
        """Replay of a model that generates by diffusion over blocks, the
        arithmetic of ``model_runner._block_plan`` / ``_block_scan_body``: a
        row prefills its prompt's whole blocks by the chunk; past them each
        step of a row with budget left either denoises the block it holds
        (nothing out, the watermark stands) or commits it: the watermark
        moves a block and the row's emit columns carry the block's tokens
        past the prompt, cut at the budget and behind the first EOS. A
        commit always emits (a block past the prompt's whole ones reaches
        past the prompt, and a row with no budget left takes no step), so
        the emit mask tells the two apart with no device read-back; and a
        commit that does not end the row (no EOS out, budget left) was a
        FUSED step, the first denoising step of the block the row holds
        next."""
        emissions: Dict[int, List[int]] = {}
        finished: List[int] = []
        blk = self.block_length
        whole = self.prefill_end_h
        live = [i for i in range(self.n_slots) if self.uid_of_slot[i] >= 0]
        commits = emit.any(axis=-1).tolist()     # one pass, no array a row
        for s in range(toks.shape[0]):
            for i in live:
                if self.done_h[i]:
                    continue
                if self.cached_h[i] < whole[i]:
                    self.cached_h[i] += min(width, whole[i] - self.cached_h[i])
                    continue
                if self.produced_h[i] >= self.limit_h[i]:
                    continue
                if not commits[s][i]:
                    self.denoised_h[i] += 1
                    continue
                self.cached_h[i] += blk
                uid = int(self.uid_of_slot[i])
                for k in np.flatnonzero(emit[s, i]):
                    t = int(toks[s, i, k])
                    emissions.setdefault(uid, []).append(t)
                    self.produced_h[i] += 1
                    if (t == self.eos_h[i]
                            or self.produced_h[i] >= self.limit_h[i]):
                        self.done_h[i] = True
                self.denoised_h[i] = 0 if self.done_h[i] else 1
        for i in live:
            if self.done_h[i]:
                finished.append(int(self.uid_of_slot[i]))
        return emissions, finished
