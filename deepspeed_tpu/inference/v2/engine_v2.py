"""Continuous-batching inference engine (FastGen analog).

Analog of ``inference/v2/engine_v2.py:30`` (InferenceEngineV2): paged KV
(``kv_cache.py``), sequence tracking (``ragged_manager.py``), and Dynamic
SplitFuse scheduling — long prompts are split into fixed chunks, short
prompts and decode steps are fused into one forward pass, keeping every step
near the token budget so latency stays flat while the MXU stays fed
(reference ``can_schedule:184`` admission logic).

Serving surface (MII-compatible): ``put(batch_uids, batch_tokens)``,
``scheduled step()``, ``query``, ``can_schedule``, ``flush``; plus the
frame-based ``serve(arrivals)`` loop for continuous batching with dynamic
arrivals at compiled-loop speed (host touches the device only at K-step
frame boundaries) and ``generate``, a closed batch through that loop.
"""

import collections
import dataclasses
import math
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import CausalLM
from ...utils.logging import log_dist, logger
from ..config import DeepSpeedInferenceConfig
from ..sampling import sample_logits
from .faults import (FaultReason, FrameDispatchError, LedgerEntry,
                     snapshot_ledger)
from .kv_cache import (BlockedKVCache, LayeredKVCache, cache_kinds,
                       heads_per_row)
from .model_runner import PagedModelRunner, _use_pallas_paged
from .ragged_manager import DeviceSlotTable, DSStateManager
from .scheduler import FifoPolicy
from .telemetry import ServingTelemetry, check_stat_range


@dataclasses.dataclass
class RaggedInferenceEngineConfig:
    """Analog of ``inference/v2/config_v2.py`` (RaggedInferenceEngineConfig)."""
    max_ragged_batch_size: int = 64          # decode slots + prefill seqs per step
    max_ragged_sequence_count: int = 2048
    # 128 measured best on v5e decode (page-DMA bound: fewer, larger page
    # fetches beat 64; 256 over-fetches for short tails)
    kv_block_size: int = 128
    num_kv_blocks: Optional[int] = None      # explicit override wins
    # workload-driven pool sizing (r4 review: memory-fraction defaults left
    # decode rows at 25% utilization and the decode-collapse probe showed a
    # 1.4x throughput cost to oversizing): provision for the EXPECTED live
    # context/concurrency, not the theoretical max. Sequences beyond the
    # estimate still run while free blocks last (admission control gates
    # the rest); None falls back to the worst case (max_seq_len / batch).
    expected_context: Optional[int] = None   # avg live tokens per sequence
    expected_concurrency: Optional[int] = None   # avg live sequences
    prefill_chunk_size: int = 128            # Dynamic SplitFuse chunk
    max_tokens_per_step: int = 512           # token budget per step
    max_tracked_sequences: int = 2048
    # serve(): the MOST steps a device-resident frame runs. Larger frames
    # amortize the host boundary further but delay admission of new arrivals
    # by up to frame_steps decode steps (see README "frame loop" tradeoff).
    # How many a frame does run is an operand of the frame program, planned
    # at each boundary: a wide frame whose prompts begin in it ends with its
    # last prefilling row, so a first token is not held back by steps no
    # prompt needs.
    frame_steps: int = 8
    # adaptive frame sizing (ROADMAP item (c)): re-pick the frame length
    # each frame from the pow2 bucket set {1, 2, ..., frame_steps} using an
    # EWMA arrival-rate estimate — small frames under bursty TTFT-sensitive
    # traffic, frame_steps when saturated or drained. The buckets are the
    # policy's granularity only: every length runs the same program.
    adaptive_frame_steps: bool = False
    frame_steps_ewma_alpha: float = 0.25
    # speculative decoding (draft/verify on the frame carry): tokens the
    # draft proposes per target verify. Emitted tokens per target forward is
    # 1 + acceptance * gamma, so larger gammas only pay off with a strong
    # draft (see README "Speculative decoding on the frame carry").
    speculate_gamma: int = 2
    # serving telemetry (README "Serving telemetry"): False switches off the
    # HOST side only (per-frame counter sync, latency histograms, monitor
    # fan-out) — the in-graph counters are always compiled in, so toggling
    # never retraces a frame program, and the rate-limited overload-deferral
    # warning stays on (losing the overload signal is the failure mode
    # telemetry exists to fix). serving_bench.py pins the host path at
    # < 2% throughput overhead.
    telemetry: bool = True
    # put the serve loop on the profiler's clock: every frame a
    # serve_frame/w<width>/s<steps> TraceAnnotation, every boundary phase a
    # serve/<phase> one, each frame's counters the stats of a
    # serve/frame_work one. Measured with no profiler attached (v5e,
    # chat-steady, three 51 s runs each, PR 23): tokens_per_s 354.79 on
    # against 354.74 off, ttft_mean_ms 1,237 against 1,242 — inside the
    # runs' own spread (a TraceMe with no profiler is a flag test). Off by
    # default because nothing reads the spans without a profiler.
    telemetry_trace: bool = False
    # fault tolerance (faults.py / README "Fault tolerance & chaos
    # testing"): a frame dispatch that raises is retried up to
    # max_frame_retries times with exponential backoff (backoff * 2^attempt
    # seconds) — injected faults and pre-dispatch host errors retry
    # token-identically because the donated carry was never consumed; an
    # error from inside the compiled frame invalidates the donated buffers,
    # so the retry fails fast into the crash path (ledger snapshot +
    # FrameDispatchError) instead of silently corrupting state
    max_frame_retries: int = 2
    frame_retry_backoff_s: float = 0.02
    # wall-clock watchdog: warn + count (ds_serving_slow_frames_total) when
    # one frame exceeds this many milliseconds. None disables. The watchdog
    # never kills a frame — a jit cannot be safely interrupted — it makes
    # stuck-behind-a-slow-frame time visible so per-request deadlines (the
    # actual recovery mechanism) can act at the next boundary.
    watchdog_frame_ms: Optional[float] = None
    fault_log_max: int = 256
    # what the frame boundary does with a row whose in-graph finite-check
    # latch tripped (README "Fault tolerance & chaos testing"):
    #   "quarantine" (default) — evict + retire with a poison_row fault
    #     (the batch never dies for one request);
    #   "repair"     — the compiled frame rolls the row back to its
    #     pre-fault carry instead of freezing it (a transient blip — an
    #     ECC hiccup, a one-off numeric spike — costs the row one frame,
    #     not its life), and the host escalates to quarantine only after
    #     nonfinite_repair_limit CONSECUTIVE latched boundaries. Repair
    #     compiles a distinct frame program (static flag), so the default
    #     path stays byte-identical.
    nonfinite_policy: str = "quarantine"
    nonfinite_repair_limit: int = 2
    # tensor-parallel serving (README "Multi-chip serving"): shard the model
    # weights (Megatron column/row via parallel/sharding.py rules) and the
    # paged KV pools (head-wise) across a 1-D tp mesh of the first `tp`
    # local devices; the frame loops compile under shard_map with the whole
    # slot-table carry REPLICATED, so admission, scheduling, deadlines,
    # quarantine, and crash snapshots stay single-host and frame-boundary-
    # only. tp=1 never touches shard_map — byte-identical to the unsharded
    # engine (serving_bench.py --tp asserts this inline).
    tp: int = 1
    # quantized all-reduce/all-gather for the per-step activation, masked-
    # embedding, and logit exchanges (EQuARX, arXiv 2506.17615): opt-in,
    # parity-at-tolerance (tests/test_serving_tp.py pins the contract)
    tp_quantized_collectives: bool = False
    # wire format of the quantized exchanges: "int8" (symmetric absmax) or
    # "fp8" (e4m3 scaled casts, Big-Send-off-style) — both one byte per
    # element on the wire, proven <=0.5x exact traffic by graft-cost GL202
    tp_collective_payload: str = "int8"
    # decompose the MLP all-reduce into ppermute ring chunks XLA can
    # schedule around neighboring compute (T3, arXiv 2401.16677): opt-in;
    # ring summation order differs from psum, so parity is at-tolerance
    tp_overlap_collectives: bool = False
    # debug mode: read every device's copy of the frame counters at every
    # boundary and assert they agree (replica-consistency proof); steady
    # state reads one copy
    tp_debug_replica_check: bool = False
    # ---- KV memory hierarchy (kv_hierarchy.py; README "KV memory
    # hierarchy") ----
    # prefix cache with copy-on-write block sharing: admission maps a new
    # prompt's published prefix blocks read-only into its block table and
    # starts prefill at the first uncached position (greedy outputs stay
    # token-identical cache-on vs cache-off; all device touches are frame-
    # boundary-only). Off by default: cache-held blocks outlive requests,
    # which changes the pool-drain invariant callers may rely on.
    prefix_cache: bool = False
    # cap on device blocks the prefix cache may pin (LRU-evicts — spilling
    # to the swap tier when one is configured — beyond it); None = bounded
    # only by pool pressure (admission reclaims cold entries on demand)
    prefix_cache_max_blocks: Optional[int] = None
    # host-RAM swap tier on the swap_tensor machinery: a directory for
    # swapped KV pages (tmpfs/ramdisk for a true RAM tier). When set,
    # scheduler preemption swaps the victim's committed pages out and
    # re-admission swaps them back in (replacing re-prefill), cold prefix
    # blocks spill instead of dropping, and crash recovery restores pages
    # (the tier's index persists beside the pages, so a fresh engine
    # sharing the directory resumes without recomputing). None disables.
    kv_swap_dir: Optional[str] = None
    # preemption swaps committed KV instead of re-prefilling (needs
    # kv_swap_dir; False keeps the PR-4 re-prefill path)
    kv_swap_preempt: bool = True
    # boundary swap-out writes ride the aio queue and COMMIT at the NEXT
    # frame boundary (overlapped with the frame in between) instead of
    # blocking the boundary on the wait; any read path that needs a queued
    # record drains it first, so semantics are unchanged. False restores
    # the synchronous commits.
    kv_swap_async: bool = True
    # ---- disaggregated prefill/decode serving (router.py roles; README
    # "Disaggregated prefill/decode") ----
    # "unified" serves requests end to end (the default — nothing below
    # changes). "prefill" runs wide chunked-prefill frames only: the
    # moment a request's committed watermark covers its prompt, its KV
    # pages are PUBLISHED into the shared swap tier (requires a tier) and
    # the request is handed back to the router as a HandoffEvent for
    # decode placement. "decode" is a placement label — the engine behaves
    # like "unified", restoring handed-off pages through the ordinary
    # swap-in admission path (PR 8) and streaming tokens.
    role: str = "unified"
    # admission probes the shared tier's content-addressed prefix records
    # (fleet-wide prefix share) when the local prefix cache misses; only
    # active when a swap tier is attached and records exist
    tier_prefix_share: bool = True
    # handoff pipelining (README "Disaggregated prefill/decode"): a
    # prefill-role row whose remaining prompt fits the next frame will
    # hand off at the NEXT boundary — publish its final record segment
    # (including the partial tail block at the current chunk-aligned
    # watermark) NOW, so the write I/O overlaps the first-token frame
    # instead of landing on the handoff critical path (the decode
    # replica's restore blocks on the commit). The record's watermark
    # stays at the publish point; the decode side replays the sub-frame
    # tail cold (chunk-aligned, so greedy outputs are token-identical).
    # False restores the publish-at-handoff behavior.
    handoff_pipeline: bool = True
    dtype: str = "bfloat16"
    # ---- low-precision serving (README "Quantization") ----
    # resident weight storage for the big matmuls (qkv/out/mlp/lm_head):
    # None serves the checkpoint dtype; "int8" quantizes per output channel
    # at engine build (model_implementations/quantize.py) and dequantizes
    # in-graph at use — ~4x smaller resident weights vs f32, logit error
    # bounded <=5% by the parity contract (tests/test_quantized_serving.py)
    weight_dtype: Optional[str] = None
    # paged KV pool storage: None keeps `dtype`; "int8" stores every page
    # as packed absmax-quantized rows with per-(token, head) f32 scales in
    # trailing int8 lanes (kv_cache.quantize_kv_lanes) — quantize at
    # append, dequantize at attention read, and the page movers, swap
    # tier, prefix publishes, and disagg handoffs all move the int8
    # representation unchanged (records shrink with the pool)
    kv_dtype: Optional[str] = None


@dataclasses.dataclass
class ServeBoundary:
    """One frame-boundary progress event, yielded by
    ``serve(..., yield_boundaries=True)`` between request completions.

    This is the cooperative-scheduling hook the multi-engine router
    (``router.py``) is built on: every ``next()`` on the serve generator
    advances the engine by AT MOST one frame (or one idle arrival poll)
    before control returns to the caller, and the event doubles as the
    engine's progress HEARTBEAT — ``t`` is the engine clock at the
    boundary, so a front-end can detect a replica whose frames have
    stopped making wall-clock progress. Plain consumers that never pass
    ``yield_boundaries`` see the historical ``(uid, tokens)``-only
    stream, byte-identical."""
    index: int          # frame-boundary index (the fault-schedule clock)
    dispatched: bool    # False for an idle poll (nothing live, no frame)
    live: int           # live slots after this boundary's retirements
    queued: int         # engine-side queue depth (FIFO deque / scheduler)
    free_slots: int
    t: float            # engine clock (time.monotonic unless injected)
    # prompt tokens waiting in the engine-side queue (FIFO deque /
    # scheduler queues) — the router's prefill-replica placement signal:
    # a prefill replica's real backlog is prompt TOKENS, not request count
    queued_tokens: int = 0
    # tokens committed by the frame this boundary closed, per live uid
    # (the host emit-mask replay the loop already computed) — the service
    # edge's streaming surface: an SSE front-end forwards these at every
    # boundary instead of waiting for the final (uid, tokens) yield. None
    # for an idle (undispatched) boundary; {} when the frame emitted
    # nothing new.
    emissions: Optional[Dict[int, List[int]]] = None


@dataclasses.dataclass
class HandoffEvent:
    """A prefill-role engine finished ``uid``'s prefill: its committed KV
    pages are published in the shared swap tier and the request leaves
    this engine. ``arrival`` is the ready-to-place RESUME arrival dict
    (the ``snapshot_split`` shape — original prompt, committed tokens,
    original budget, scheduling metadata) the router forwards to a decode
    replica, whose ordinary swap-in admission restores the pages at the
    watermark. Yielded from ``serve()`` between retirements and the
    boundary event; ``published=False`` marks a handoff whose page
    publish failed (the decode replica re-prefills instead — correctness
    preserved, work recomputed)."""
    uid: int
    arrival: Dict
    published: bool = True


class InferenceEngineV2:
    def __init__(self, model, config: Optional[RaggedInferenceEngineConfig] = None,
                 params=None, max_seq_len: Optional[int] = None,
                 draft_model=None, draft_params=None):
        self._config = config or RaggedInferenceEngineConfig()
        from ...module_inject import as_inference_model
        self.model, converted = as_inference_model(model, None)
        if params is not None:
            converted = params
        if self.model.cfg.dtype != self._config.dtype:
            self.model.cfg = self.model.cfg.replace(dtype=self._config.dtype)
        cfg = self.model.cfg
        self.max_seq_len = max_seq_len or cfg.max_seq_len

        if converted is None:
            self.params = self.model.init(jax.random.PRNGKey(0))
        else:
            self.params = jax.device_put(converted)

        c = self._config
        if c.weight_dtype not in (None, "int8"):
            raise ValueError(f"weight_dtype={c.weight_dtype!r}: expected "
                             "None or 'int8'")
        if c.kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype={c.kv_dtype!r}: expected None or "
                             "'int8'")
        if c.tp_collective_payload not in ("int8", "fp8"):
            raise ValueError(
                f"tp_collective_payload={c.tp_collective_payload!r}: "
                "expected 'int8' or 'fp8'")
        if c.weight_dtype and c.tp <= 1:
            # tp>1 quantizes inside _init_tensor_parallel, jointly with the
            # partition-spec tree (scales must shard with their weight)
            from .model_implementations.quantize import quantize_params
            self.params, _ = quantize_params(
                self.params, self.model.logical_axes(),
                weight_dtype=c.weight_dtype)
        bs = c.kv_block_size
        max_blocks_per_seq = (self.max_seq_len + bs - 1) // bs
        exp_ctx = min(c.expected_context or self.max_seq_len, self.max_seq_len)
        per_seq = (exp_ctx + 1 + bs - 1) // bs      # +1 lookahead slot
        conc = min(c.expected_concurrency or c.max_ragged_batch_size,
                   c.max_ragged_batch_size)
        num_blocks = c.num_kv_blocks or (conc * per_seq + 1)
        # a stack that mixes windowed and global layers keeps a cache a
        # kind (kv_cache.cache_kinds); None, and everything below as it
        # always was, for a model whose layers are alike
        kinds = cache_kinds(cfg.layer_windows(), bs, max_blocks_per_seq,
                            c.prefill_chunk_size)
        if cfg.latent_lanes:
            # latent attention: one row a token and attention layer
            from .model_implementations.archs import validate_latent_serving
            validate_latent_serving(c, cfg, draft=draft_model is not None)
            # the prediction module's layer keeps its rows behind the stack's
            self.kv = BlockedKVCache(cfg.cache_layers, 1, cfg.latent_lanes,
                                     num_blocks=num_blocks, block_size=bs,
                                     dtype=cfg.act_dtype, latent=True)
        elif kinds is None:
            if cfg.recurrent_kinds:
                # linear and conv layers cache no keys: the pool is the
                # full layers'
                from .model_implementations.archs import \
                    validate_recurrent_serving
                validate_recurrent_serving(c, cfg,
                                           draft=draft_model is not None)
            if cfg.block_length:
                # generation by diffusion over blocks: a row past its
                # prompt holds a half-denoised block on the carry
                from .model_implementations.archs import \
                    validate_block_diffusion_serving
                validate_block_diffusion_serving(
                    c, cfg, draft=draft_model is not None)
            # where the chip's kernels read and write the pages in place,
            # heads narrower than a 128-lane row share one (kv_cache.
            # heads_per_row); the forward reads that off the pool's shape
            in_row = heads_per_row(cfg.kv_heads, cfg.dims_per_head) \
                if _use_pallas_paged() and c.kv_dtype != "int8" \
                and c.tp == 1 and cfg.position != "alibi" else 1
            self.kv = BlockedKVCache(cfg.cache_layers if cfg.recurrent_kinds
                                     else cfg.num_layers,
                                     cfg.kv_heads // in_row,
                                     cfg.dims_per_head * in_row,
                                     num_blocks=num_blocks, block_size=bs,
                                     dtype=cfg.act_dtype, kv_dtype=c.kv_dtype)
        else:
            from .model_implementations.archs import validate_layered_serving
            validate_layered_serving(c, draft=draft_model is not None)
            self.kv = LayeredKVCache(kinds, cfg.kv_heads, cfg.dims_per_head,
                                     num_blocks=num_blocks,
                                     slots=c.max_ragged_batch_size,
                                     block_size=bs, dtype=cfg.act_dtype)
        # block 0 is the trash block for padded writes — never allocate it
        self.kv.reserve_trash_block()
        self.state = DSStateManager(self.kv, c.max_tracked_sequences)
        self.runner = PagedModelRunner(self.model, bs, max_blocks_per_seq,
                                       kinds=kinds)
        self.max_blocks_per_seq = max_blocks_per_seq
        self._rng = jax.random.PRNGKey(0)
        self.draft_model = None
        self.draft_params = None
        self.draft_runner = None
        self.draft_kv = None
        # a model with a prediction module drafts for itself in serve()
        # unless a draft model is attached or the caller says otherwise
        self.self_draft = self.runner.has_mtp
        if self.self_draft:
            from .model_implementations.archs import validate_self_draft
            validate_self_draft(c, cfg)
        self.telemetry = ServingTelemetry(enabled=c.telemetry,
                                          trace=c.telemetry_trace)
        # fault tolerance (faults.py): structured abnormal-retirement log,
        # the host-side request ledger serve() maintains for crash
        # recovery, and the snapshot taken automatically when a frame
        # dispatch fails fatally (serve(resume_from=...) consumes it)
        self.fault_log: collections.deque = collections.deque(
            maxlen=c.fault_log_max)
        self.last_crash_snapshot: Optional[Dict] = None
        self._ledger: Dict[int, LedgerEntry] = {}
        self._resume_pending: set = set()
        self._clock = time.monotonic
        # nonfinite handling (faults.py): "repair" compiles the rollback
        # variant of the frame programs; the host tracks consecutive
        # latched boundaries per row to escalate persistent faults
        if c.nonfinite_policy not in ("quarantine", "repair"):
            raise ValueError(
                f"nonfinite_policy={c.nonfinite_policy!r}: expected "
                "'quarantine' or 'repair'")
        if c.role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role={c.role!r}: expected 'unified', "
                             "'prefill' or 'decode'")
        if c.nonfinite_repair_limit < 1:
            raise ValueError("nonfinite_repair_limit must be >= 1")
        self._nonfinite_repair = c.nonfinite_policy == "repair"
        self._repair_counts: Dict[int, int] = {}
        # graceful drain (router.py): while set, serve() boundaries stop
        # ADMITTING queued work — live rows run to completion, the queue
        # holds, and the router migrates it via snapshot_serving_state()
        self._draining = False
        # KV memory hierarchy (kv_hierarchy.py): host-RAM swap tier +
        # prefix cache with copy-on-write block sharing. Both default off;
        # the cache rides the refcounted allocator, so cache-off paths are
        # untouched (every allocate is ref 1, every free releases).
        self.kv_swap = None
        self.prefix_cache = None
        if c.kv_swap_dir:
            from .kv_hierarchy import KVSwapTier
            self.kv_swap = KVSwapTier(c.kv_swap_dir)
        if c.prefix_cache:
            from .kv_hierarchy import PrefixCache
            self.prefix_cache = PrefixCache(
                self.kv, max_blocks=c.prefix_cache_max_blocks,
                swap=self.kv_swap)
        self._pc_stats_base: Optional[Dict] = None
        self._tier_stats_base: Optional[Dict] = None
        # disaggregated serving: set per serve() run (role == "prefill"
        # with a tier attached)
        self._handoff_mode = False
        # tensor-parallel serving context (tp.TPContext): set up BEFORE any
        # draft attach so the draft shards onto the same mesh
        self.tp_ctx = None
        if c.tp > 1:
            self._init_tensor_parallel()
        if draft_model is not None:
            self.attach_draft(draft_model, draft_params)
        log_dist(f"InferenceEngineV2: blocks={num_blocks}x{bs} "
                 f"budget={c.max_tokens_per_step} chunk={c.prefill_chunk_size}", ranks=[0])

    def _init_tensor_parallel(self) -> None:
        """Shard the engine across the 1-D tp mesh: validate the arch
        (``archs.validate_tp_serving``), column/row-shard the weights per
        the ``parallel/sharding.py`` logical-axis rules, shard the paged KV
        pools head-wise, and bind the context to the runner so every
        serving loop compiles under shard_map. Slot tables created by
        ``serve()`` pick the context up per-run."""
        from jax.sharding import NamedSharding
        from .tp import build_tp_context
        c = self._config
        ctx = build_tp_context(self.model, c.tp,
                               quantized=c.tp_quantized_collectives,
                               overlap=c.tp_overlap_collectives,
                               payload=c.tp_collective_payload)
        if c.weight_dtype:
            # transform params and specs JOINTLY: int8 q keeps the weight's
            # spec, the keepdims scale gets the contracted entries nulled —
            # shard_params tree-maps the two trees against each other, so
            # they must stay mirrors
            from .model_implementations.quantize import quantize_params
            self.params, qspecs = quantize_params(
                self.params, self.model.logical_axes(), ctx.param_specs,
                weight_dtype=c.weight_dtype)
            ctx = dataclasses.replace(ctx, param_specs=qspecs)
        self.tp_ctx = ctx
        self.params = ctx.shard_params(self.params)
        self.kv.shard(NamedSharding(ctx.mesh, ctx.kv_spec))
        self.runner.set_tp(ctx)
        log_dist(
            f"InferenceEngineV2: tensor-parallel serving tp={c.tp} "
            f"(vocab_sharded={ctx.vocab_sharded} "
            f"quantized={c.tp_quantized_collectives} "
            f"overlap={c.tp_overlap_collectives})", ranks=[0])

    def attach_draft(self, draft_model, draft_params=None) -> None:
        """Attach a small draft ``CausalLM`` for speculative decoding.

        The draft gets its OWN paged KV pools sized like the target's
        (same block count and block size) and indexed by the SAME per-slot
        block tables — admission reserves blocks once and both models
        address them, so speculation changes nothing about admission,
        retirement, or bucket growth. ``draft_params=None`` initializes
        fresh draft weights; pass the target's params for a self-draft
        (useful as the 100%-acceptance upper bound in benchmarks)."""
        self._one_kind_only("a draft model")
        from ...module_inject import as_inference_model
        self.draft_model, converted = as_inference_model(draft_model, None)
        if draft_params is not None:
            converted = draft_params
        if self.draft_model.cfg.dtype != self._config.dtype:
            self.draft_model.cfg = self.draft_model.cfg.replace(
                dtype=self._config.dtype)
        dcfg = self.draft_model.cfg
        if dcfg.vocab_size != self.model.cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size={dcfg.vocab_size} must match the target's "
                f"{self.model.cfg.vocab_size} — verification compares token "
                "ids and distributions position-wise")
        if self._config.prefill_chunk_size < 2:
            raise ValueError(
                "speculative serving needs prefill_chunk_size >= 2: width-1 "
                "frames are reinterpreted as draft/verify steps")
        if dcfg.max_seq_len < self.max_seq_len:
            if dcfg.position == "learned":
                # out-of-table positions would clamp in the embedding gather:
                # proposals turn to garbage at long contexts with no error,
                # just collapsed acceptance — fail loudly instead
                raise ValueError(
                    f"draft max_seq_len={dcfg.max_seq_len} < engine serving "
                    f"length {self.max_seq_len}: the draft's learned position "
                    "table cannot cover the contexts it must draft for")
            logger.warning(
                f"draft max_seq_len={dcfg.max_seq_len} < engine serving "
                f"length {self.max_seq_len}; proposals beyond the draft's "
                "trained context will likely be rejected (throughput, not "
                "correctness, degrades)")
        if converted is None:
            self.draft_params = self.draft_model.init(jax.random.PRNGKey(1))
        else:
            self.draft_params = jax.device_put(converted)
        c = self._config
        if c.weight_dtype and self.tp_ctx is None:
            # the draft serves under the same storage contract as the
            # target (tp>1 quantizes jointly with its specs below)
            from .model_implementations.quantize import quantize_params
            self.draft_params, _ = quantize_params(
                self.draft_params, self.draft_model.logical_axes(),
                weight_dtype=c.weight_dtype)
        self.draft_kv = BlockedKVCache(
            dcfg.num_layers, dcfg.kv_heads, dcfg.dims_per_head,
            num_blocks=self.kv.num_blocks, block_size=c.kv_block_size,
            dtype=dcfg.act_dtype, kv_dtype=c.kv_dtype)
        self.draft_runner = PagedModelRunner(self.draft_model, c.kv_block_size,
                                             self.max_blocks_per_seq)
        if self.tp_ctx is not None:
            # the draft rides the target's mesh: same divisibility contract
            # (validated with role="draft" so the error names the culprit),
            # its params sharded by its own logical axes, its paged KV
            # pools head-wise like the target's
            from jax.sharding import NamedSharding
            from .tp import build_tp_context
            dctx = build_tp_context(self.draft_model, c.tp,
                                    quantized=c.tp_quantized_collectives,
                                    overlap=c.tp_overlap_collectives,
                                    payload=c.tp_collective_payload,
                                    role="draft", mesh=self.tp_ctx.mesh)
            if c.weight_dtype:
                from .model_implementations.quantize import quantize_params
                self.draft_params, dqs = quantize_params(
                    self.draft_params, self.draft_model.logical_axes(),
                    dctx.param_specs, weight_dtype=c.weight_dtype)
                dctx = dataclasses.replace(dctx, param_specs=dqs)
            self.draft_params = dctx.shard_params(self.draft_params)
            self.draft_kv.shard(NamedSharding(dctx.mesh, dctx.kv_spec))
            self.draft_runner.set_tp(dctx)
        # the speculative loops close over the draft runner's _forward: a
        # re-attach must evict them or the old draft would keep running
        # (evict() folds their programs into the monotonic compile total)
        self.runner.evict("spec_frame")
        if self.prefix_cache is not None:
            # spilled prefix pages now carry the draft pool's page too,
            # so a restored block keeps draft acceptance
            self.prefix_cache.draft_kv = self.draft_kv
        log_dist(f"InferenceEngineV2: draft attached "
                 f"(layers={dcfg.num_layers} gamma={c.speculate_gamma})",
                 ranks=[0])

    @property
    def _lookahead(self) -> int:
        """Positions past prompt + budget a row's pages must hold: the one
        slot a step writes ahead, and for a model that generates by
        diffusion over blocks the whole of a last block that the budget
        cuts (its every position is denoised and written)."""
        return max(1, self.model.cfg.block_length)

    def _one_kind_only(self, what: str) -> None:
        """A model of mixed cache kinds is served without what moves a
        sequence's pages by one block list or rolls a step back across a
        ring (``archs.validate_layered_serving``)."""
        if isinstance(self.kv, LayeredKVCache):
            raise NotImplementedError(
                f"{what}: this model mixes windowed and global layers and "
                "keeps a cache a kind (kv_cache.LayeredKVCache); it is "
                "served without a draft, a swap tier or a prefix cache")
        if self.kv.latent:
            raise NotImplementedError(
                f"{what}: this model keeps one pool of latent rows "
                "(kv_cache.BlockedKVCache(latent=True)); it is served "
                "without a draft, a swap tier or a prefix cache")
        if self.model.cfg.recurrent_kinds:
            raise NotImplementedError(
                f"{what}: this model's "
                f"{' and '.join(self.model.cfg.recurrent_kinds)} layers keep "
                "a state a slot, which is no page; it is served without a "
                "draft, a swap tier or a prefix cache")
        if self.model.cfg.block_length:
            raise NotImplementedError(
                f"{what}: this model generates by diffusion over blocks and "
                "a row holds a half-denoised block, which is no page and no "
                "token a draft could verify; it is served without a draft, "
                "a swap tier or a prefix cache")

    def attach_kv_tier(self, tier, tag: Optional[str] = None) -> None:
        """Attach an EXTERNAL (typically shared) ``KVSwapTier`` — the
        disaggregated fleet's transport: every replica points at ONE tier
        instance, so pages a prefill replica publishes are the pages a
        decode replica restores, and content-addressed prefix records are
        matchable fleet-wide. Replaces any tier built from
        ``kv_swap_dir``. ``tag`` namespaces this engine's prefix-cache
        spill keys inside the shared tier (defaults to the engine's id —
        unique per process, which is all the per-instance ``kvblk_``
        records need)."""
        self._one_kind_only("a swap tier")
        self.kv_swap = tier
        if self.prefix_cache is not None:
            self.prefix_cache.swap = tier
            self.prefix_cache.tag = (f"{id(self):x}_" if tag is None
                                     else f"{tag}_")
        self._tier_stats_base = None

    @property
    def serve_stats(self) -> Dict:
        """Thin read-through view over the telemetry subsystem — the dict
        shape the pre-telemetry serve() exposed (frames, frame_steps_hist,
        arrival_ewma, spec acceptance counters), now fed from the in-graph
        frame counters. Full detail: ``engine.telemetry.snapshot()`` /
        ``engine.telemetry.render_prometheus()``."""
        return self.telemetry.serve_view

    def attach_monitor(self, monitor, every_frames: int = 1) -> None:
        """Fan serving telemetry out through a ``MonitorMaster`` (or any
        object with ``write_events([(tag, value, step)])``) at frame
        boundaries — the serving twin of the training engine's monitor."""
        self.telemetry.attach_monitor(monitor, every_frames=every_frames)

    def begin_drain(self) -> None:
        """Graceful-drain hook (router replica removal): from the next
        frame boundary on, ``serve()`` stops admitting queued work — live
        rows keep decoding to completion while the queue holds. Once the
        live count hits zero the queue is exactly the engine's ledger, so
        ``snapshot_serving_state()`` + ``faults.snapshot_split()`` migrate
        it to a healthy peer without losing an accepted request."""
        self._draining = True

    def end_drain(self) -> None:
        """Cancel a drain (replica kept after all): admission resumes at
        the next frame boundary."""
        self._draining = False

    def set_role(self, role: str) -> None:
        """Re-label this engine's serving role (the autoscaler's elastic
        prefill<->decode rebalancing surface). The role is latched at
        ``serve()`` entry, so a flip takes effect at the replica's NEXT
        serve generator — the fleet driver restarts the generator after an
        idle drain, migrating anything queued, exactly like a failover
        resume (token-identical by the same argument)."""
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role={role!r}: expected 'unified', "
                             "'prefill' or 'decode'")
        if role == "prefill" and self.kv_swap is None:
            raise ValueError(
                "set_role('prefill') needs a KV swap tier (kv_swap_dir= "
                "or attach_kv_tier()) — the prefill->decode handoff "
                "publishes committed pages through it")
        self._config.role = role

    def cancel_request(self, uid: int) -> bool:
        """Cancel an accepted, in-flight request (the service edge's
        client-disconnect path): marks the ledger entry cancelled and
        expires its deadline, so the NEXT frame boundary's existing
        deadline machinery cancels it wherever it sits — popped from the
        queue, or evicted from its live slot with its KV blocks freed —
        and retires it with a ``cancelled`` FaultReason instead of
        ``deadline_expired``. Safe to call from another thread while a
        serve generator runs (it only writes two fields of an existing
        ledger entry; the boundary does the actual teardown). Returns
        False when ``uid`` is not in flight (already retired)."""
        ent = self._ledger.get(uid)
        if ent is None:
            return False
        ent.cancelled = True
        ent.deadline_at = self._clock()
        return True

    # ------------------------------------------------------------------
    # admission control (reference engine_v2.py:184)
    # ------------------------------------------------------------------

    def can_schedule(self, uids: List[int], lengths: List[int]) -> bool:
        """Would these new sequences fit (blocks + tracking)?"""
        blocks_needed = sum(self.kv.blocks_for(l + 1) for l in lengths)
        if blocks_needed > self.kv.free_blocks:
            return False
        if len(self.state.seqs) + len(uids) > self._config.max_tracked_sequences:
            return False
        return True

    def query(self, uid: int) -> Tuple[int, List[int]]:
        """(#tokens still pending prefill, generated tokens so far)."""
        seq = self.state.seqs.get(uid)
        if seq is None:
            return (0, [])
        return (len(seq.pending), list(seq.generated))

    # ------------------------------------------------------------------
    # ingest
    # ------------------------------------------------------------------

    def put(self, batch_uids: List[int], batch_tokens: List[np.ndarray]) -> None:
        """Register prompt tokens for the given sequence uids."""
        for uid, toks in zip(batch_uids, batch_tokens):
            toks = np.asarray(toks).reshape(-1).tolist()
            seq = self.state.get_or_create_sequence(uid)
            if not self.state.ensure_capacity(seq, seq.seen_tokens + len(toks) + 1):
                raise RuntimeError(f"uid={uid}: KV pool exhausted "
                                   f"({self.kv.free_blocks} blocks free)")
            seq.pending.extend(toks)
            seq.done = False

    def flush(self, uids: List[int]) -> None:
        for uid in uids:
            self.state.flush_sequence(uid)

    # ------------------------------------------------------------------
    # Dynamic SplitFuse step
    # ------------------------------------------------------------------

    def _schedule(self) -> Tuple[List, List]:
        """Pick (prefill_seqs, decode_seqs) under the token budget.

        SplitFuse policy: decode tokens first (latency-critical, 1 token
        each), remaining budget split into prefill chunks.
        """
        c = self._config
        budget = c.max_tokens_per_step
        decode = [s for s in self.state.seqs.values()
                  if not s.in_prefill and not s.done and s.seen_tokens > 0]
        decode = decode[:min(len(decode), c.max_ragged_batch_size, budget)]
        budget -= len(decode)
        prefill = []
        for s in self.state.seqs.values():
            if s.in_prefill and budget >= min(len(s.pending), c.prefill_chunk_size):
                prefill.append(s)
                budget -= min(len(s.pending), c.prefill_chunk_size)
                if len(prefill) + len(decode) >= c.max_ragged_batch_size or budget <= 0:
                    break
        return prefill, decode

    def _run_batch(self, seqs, chunk: int, take: Dict[int, int],
                   greedy=True, temperature=0.0):
        """Run one padded (B, chunk) forward over paged KV for ``seqs``.

        The batch dimension is padded to the next power of two: the
        per-chunk jit cache keys only on chunk width, so without padding
        every distinct live batch size B compiles a fresh program. Pad rows
        carry positions -1 — the pager routes their writes to the trash
        block and the attention mask kills their reads — and their sampled
        tokens are never consumed."""
        b = len(seqs)
        bp = BlockedKVCache.bucket_width(
            b, max(b, self._config.max_ragged_batch_size))
        ids = np.zeros((bp, chunk), np.int32)
        positions = np.full((bp, chunk), -1, np.int32)
        valid = np.zeros((bp,), np.int32)
        for i, s in enumerate(seqs):
            n = take[s.uid]
            toks = s.pending[:n] if s.in_prefill else s.generated[-1:]
            ids[i, :n] = toks
            positions[i, :n] = s.seen_tokens + np.arange(n)
            valid[i] = n

        logits, self.kv.k, self.kv.v = self.runner.run(
            chunk, self.params, jnp.asarray(ids), jnp.asarray(positions),
            self._kind_tables(seqs, self.max_blocks_per_seq, rows=bp),
            jnp.asarray(valid), self.kv.k, self.kv.v)
        self._rng, sub = jax.random.split(self._rng)
        toks = np.asarray(sample_logits(logits, sub, greedy=greedy,
                                        temperature=temperature))
        out = {}
        for i, s in enumerate(seqs):
            n = take[s.uid]
            if s.in_prefill:
                s.pending = s.pending[n:]
                s.seen_tokens += n
                if not s.pending:          # prompt fully consumed → first token
                    s.generated.append(int(toks[i]))
                    out[s.uid] = int(toks[i])
            else:
                s.seen_tokens += n
                s.generated.append(int(toks[i]))
                out[s.uid] = int(toks[i])
        return out

    def step(self, temperature: float = 0.0) -> Dict[int, int]:
        """One SplitFuse iteration → {uid: newly generated token}."""
        prefill, decode = self._schedule()
        produced: Dict[int, int] = {}
        c = self._config
        if prefill:
            take = {s.uid: min(len(s.pending), c.prefill_chunk_size) for s in prefill}
            for s in prefill:   # capacity for the chunk + next token
                self.state.ensure_capacity(s, s.seen_tokens + take[s.uid] + 1)
            produced.update(self._run_batch(prefill, c.prefill_chunk_size, take,
                                            greedy=temperature == 0.0,
                                            temperature=temperature))
        if decode:
            ok = [s for s in decode
                  if self.state.ensure_capacity(s, s.seen_tokens + 2)]
            take = {s.uid: 1 for s in ok}
            if ok:
                produced.update(self._run_batch(ok, 1, take,
                                                greedy=temperature == 0.0,
                                                temperature=temperature))
        return produced

    # ------------------------------------------------------------------
    # convenience: a closed batch through serve()
    # ------------------------------------------------------------------

    def generate(self, prompts: List[np.ndarray], max_new_tokens: int = 32,
                 temperature: float = 0.0, eos_token_id: Optional[int] = None,
                 speculate: Optional[bool] = None,
                 gamma: Optional[int] = None):
        """Batch generation: ``prompts`` as ONE closed batch through
        ``serve()``, every request arrived at t = 0, run to its end. Returns
        each prompt's generated tokens, in prompt order (an EOS ends a row
        and is its last token).

        A call IS a serve run, of the same frame program: it resets and
        fills ``engine.telemetry`` / ``serve_stats``, speculates by
        ``serve()``'s default where a draft model or a prediction module is
        attached (``speculate`` / ``gamma`` pass straight through; the same
        tokens under greedy), admits more prompts than there are slots or
        pages in turns, raises where a prompt + budget can never fit the KV
        pool, and leaves the engine drained. Sampled tokens follow the frame
        carry's key, split from the engine's stream: the same engine and the
        same calls give the same tokens."""
        done = dict(self.serve(
            iter([list(enumerate(prompts))]), max_new_tokens=max_new_tokens,
            temperature=temperature, eos_token_id=eos_token_id,
            speculate=speculate, gamma=gamma))
        uids = range(len(prompts))
        lost = [u for u in uids if u not in done]
        if lost:
            raise RuntimeError(
                f"generate(): requests {lost} were retired without an "
                "answer (quarantined rows: engine.fault_log says why)")
        return [done[u] for u in uids]

    def _kind_tables(self, seqs, width: int, rows: Optional[int] = None):
        """``seqs``' block tables as the runner's programs take them:
        (rows, width) of page ids, rows past the sequences all trash page
        0; under caches by layer kind a tuple of that, the table kind's,
        and every ring kind's (rows, ring)."""
        def stack(width, blocks_of):
            tables = np.zeros((rows or len(seqs), width), np.int32)
            for i, s in enumerate(seqs):
                tables[i] = self.state.block_table(s, width, blocks_of(s))
            return jnp.asarray(tables)

        tables = stack(width, lambda s: s.blocks)
        if not self.state.rings:
            return tables
        return (tables,) + tuple(
            stack(ring, lambda s, i=i: s.ring_blocks[i])
            for i, (_, ring) in enumerate(self.state.rings))

    # ------------------------------------------------------------------
    # frame-based persistent serving loop (dynamic arrivals)
    # ------------------------------------------------------------------

    @staticmethod
    def _norm_arrival(item, max_new_tokens, temperature, eos_token_id):
        """Normalize one arrival to ``(uid, tokens, limit, temp, eos,
        tenant, priority, slo_ms, deadline_ms, generated, trace)``.

        ``trace`` (dict arrivals only) is the distributed-trace context
        ``{"id", "parent"}`` minted at the edge/router (``tracing.py``);
        it rides the ledger so snapshots, failovers, and handoffs
        continue the SAME trace on the next replica.

        ``generated`` (dict arrivals only; normally None) marks a RESUME
        arrival — the router's cross-engine failover/migration surface
        (``faults.snapshot_split``): ``tokens`` is the ORIGINAL prompt,
        ``generated`` the tokens another engine already committed, and
        ``max_new_tokens`` the ORIGINAL budget. Ingestion folds
        prompt+generated for re-prefill (the crash-resume machinery), the
        ledger keeps the original prompt/limit, and the submit bypasses a
        ``RequestScheduler``'s tenant queue quota — the request was
        already accepted once. An empty list is still a resume (a queued,
        never-admitted request migrating off a drained replica).

        Tuple form: ``(uid, tokens[, max_new_tokens[, temperature[,
        eos_id]]])`` with serve()-level defaults filled in; None in any
        optional field means "use the default" (pass eos_id=-1 to disable
        EOS for one row when a serve()-level eos_token_id is set). Tuples
        carry no scheduling metadata (tenant/priority/slo_ms/deadline_ms
        are None).

        Dict form (the scheduler-aware surface): ``{"uid", "tokens"}`` plus
        optional ``max_new_tokens``/``temperature``/``eos_token_id`` and the
        scheduling fields ``tenant`` (str), ``priority`` ("interactive" |
        "batch" | "best_effort" or 0..2), ``slo_ms`` (per-request TTFT
        target that tightens the scheduler's pressure loop), ``deadline_ms``
        (wall-clock budget from ENQUEUE: past it, the request is cancelled
        at the next frame boundary — queued or live — its KV blocks freed
        and a ``deadline_expired`` FaultReason recorded, whatever the
        admission policy). tenant/priority/slo_ms are inert without a
        ``scheduler=``."""
        if isinstance(item, dict):
            uid, toks = item["uid"], item["tokens"]
            limit = item.get("max_new_tokens")
            limit = max_new_tokens if limit is None else limit
            temp = item.get("temperature")
            temp = temperature if temp is None else temp
            eos = item.get("eos_token_id")
            eos = eos_token_id if eos is None else eos
            tenant, prio = item.get("tenant"), item.get("priority")
            slo_ms = item.get("slo_ms")
            deadline_ms = item.get("deadline_ms")
            trace = item.get("trace")
            if deadline_ms is not None and deadline_ms <= 0:
                raise ValueError(f"uid={uid}: deadline_ms must be > 0")
            generated = item.get("generated")
            if generated is not None:
                generated = [int(t) for t in generated]
                if len(generated) > int(limit):
                    raise ValueError(
                        f"uid={uid}: resume arrival carries "
                        f"{len(generated)} committed tokens beyond its "
                        f"budget of {limit}")
        else:
            uid, toks = item[0], item[1]
            limit = item[2] if len(item) > 2 and item[2] is not None \
                else max_new_tokens
            temp = item[3] if len(item) > 3 and item[3] is not None \
                else temperature
            eos = item[4] if len(item) > 4 and item[4] is not None \
                else eos_token_id
            tenant = prio = slo_ms = deadline_ms = generated = trace = None
        return uid, np.asarray(toks, np.int32).reshape(-1), int(limit), \
            float(temp), eos, tenant, prio, slo_ms, deadline_ms, generated, \
            trace

    def serve(self, arrivals: Iterable, *, max_new_tokens: int = 32,
              temperature: float = 0.0, eos_token_id: Optional[int] = None,
              frame_steps: Optional[int] = None,
              frame_slots: Optional[int] = None,
              speculate: Optional[bool] = None, gamma: Optional[int] = None,
              rng=None, scheduler=None, faults=None, resume_from=None,
              yield_boundaries: bool = False):
        """Continuous batching with dynamic arrivals at compiled-loop speed.

        Generator: yields ``(uid, generated_tokens)`` as sequences finish.

        ``arrivals`` is an iterator polled once per frame boundary; each
        ``next()`` returns the sequences that arrived since the last poll
        (possibly an empty list) as ``(uid, prompt_tokens[, max_new_tokens
        [, temperature[, eos_id]]])`` tuples, and raises StopIteration when
        no more will ever come. The iterator is the serving clock: a
        Poisson front-end yields whatever its queue holds. When NO slots
        are live, serve() re-polls immediately — a front-end should block
        briefly (e.g. ``queue.get(timeout=...)``) on an empty queue, or the
        idle loop busy-spins a host core.

        Execution model: decoding runs as K-step FRAMES — one
        ``lax.scan``-based jit over a fixed set of slots — with all per-slot
        state (last token, cached counts, per-row limits/EOS/temperature,
        RNG, padded block tables) device-resident between frames. The host touches the loop only at frame boundaries:
        admit arrivals into free slots (KV capacity reserved up front —
        admission control defers arrivals the pool can't hold), retire
        finished rows (EOS detection is in-graph; the host replays the emit
        mask against its mirrors), and grow the shape buckets. Frames are
        shape-bucketed (width ∈ {prefill_chunk, 1}; power-of-two table and
        prompt widths) so the jit cache stays O(log).

        Speculative decoding (``speculate``; defaults to on when a draft is
        attached, or when the model has a prediction module of its own,
        ``cfg.num_nextn_predict_layers``, which then drafts one token a
        step into one more layer of the model's own cache: no draft model,
        no second set of pools): pure-decode frames run ``gamma`` draft proposals plus one
        gamma+1-wide target verify per step, emitting 1 + accepted tokens
        per target forward. Acceptance, EOS, and rollback are in-graph; the
        host replay just reads the wider emit mask, so the frame-boundary
        contract is unchanged. Per-frame acceptance statistics accumulate in
        ``self.serve_stats``.

        ``rng`` (key or int seed) makes sampled runs reproducible: it seeds
        the frame carry's device RNG directly instead of splitting from the
        engine's stream. A frame runs AT MOST ``frame_steps`` steps: a wide
        frame whose prompts begin in it ends with its last prefilling row
        (``_plan_frame_steps``; ``_serve_loop``, phase ``plan``). ``adaptive_frame_steps`` in the config re-picks that most
        per frame (pow2 buckets up to ``frame_steps``) from an EWMA
        arrival-rate estimate; an explicit ``frame_steps=`` argument pins it.

        ``scheduler`` is the admission policy: how waiting requests queue
        and who is admitted next. There is one serve loop and it knows
        the policy only through the methods ``scheduler.py`` lists; None
        (the default) is ``scheduler.FifoPolicy``, arrival order. A
        ``scheduler.RequestScheduler`` is the SLO-aware policy object:
        priority classes with aging, per-tenant weighted fair-share and
        quotas, TTFT-SLO load shedding/deferral, and frame-boundary
        preemption (see ``scheduler.py`` and README "Scheduling & SLOs").
        Arrivals may then be dicts carrying ``tenant``/``priority``/
        ``slo_ms``. All policy runs host-side at frame boundaries — zero
        new in-frame transfers — and a default ``RequestScheduler()``
        gives FIFO's outputs in FIFO's order
        (``test_no_scheduler_path_is_fifo_identical``).

        Fault tolerance (``faults.py``, README "Fault tolerance & chaos
        testing"): frame dispatch runs under bounded retry with exponential
        backoff; a row whose logits go non-finite is quarantined at the
        frame boundary (evicted, retired with a ``poison_row``
        ``FaultReason`` in ``engine.fault_log``) while its batch siblings
        keep decoding; arrivals may carry ``deadline_ms`` (enforced at
        frame boundaries for queued AND live rows, freeing KV blocks on
        expiry); and the host-side request ledger makes the loop
        crash-recoverable: ``engine.snapshot_serving_state()`` (or the
        automatic ``engine.last_crash_snapshot`` on a fatal dispatch
        failure) feeds ``serve(..., resume_from=snapshot)``, which
        re-admits every in-flight request by re-prefilling prompt +
        committed tokens — greedy outputs are token-identical across the
        restart. ``faults=`` takes a ``faults.FaultInjector`` whose
        scripted schedule exercises these paths deterministically (chaos
        tests, ``serving_bench.py --chaos``).

        ``yield_boundaries=True`` additionally yields a ``ServeBoundary``
        event at every frame boundary (after that boundary's retirements),
        turning the generator into a cooperatively-steppable loop: one
        ``next()`` advances the engine by at most one frame. This is the
        router's scheduling and heartbeat surface (``router.py``); plain
        consumers keep the ``(uid, tokens)``-only stream.

        While a ``serve`` generator is live it owns the engine's scheduler
        state — don't interleave ``step()``/``generate()`` calls: ``step()``
        allocates pages and tracks sequences behind the loop's mirrors, and
        ``generate()`` is a serve run of its own, which resets the ledger
        and the telemetry the live generator reads.
        """
        # argument validation is EAGER (serve() itself is not a generator):
        # a misconfigured call raises here, at the call site, not at the
        # first next() deep inside some consumer
        c = self._config
        steps = frame_steps or c.frame_steps
        adaptive = c.adaptive_frame_steps and frame_steps is None
        self_draft = self.self_draft and self.draft_model is None
        if speculate is None:
            speculate = self.draft_model is not None or self_draft
        if speculate and self.draft_model is None and not self_draft:
            raise ValueError("speculate=True but no draft model is attached "
                             "(pass draft_model= at construction or call "
                             "attach_draft()) and the model has no "
                             "prediction module to draft with")
        if speculate and self_draft:
            # one draft a module, and the modules are what the model has
            modules = self.model.cfg.num_nextn_predict_layers
            if gamma not in (None, modules):
                raise ValueError(
                    f"gamma={gamma}: a model that drafts with its own "
                    f"prediction modules drafts one token a module "
                    f"({modules})")
            gamma = modules
        gamma = int(gamma if gamma is not None else c.speculate_gamma)
        if speculate and gamma < 1:
            raise ValueError(f"speculate needs gamma >= 1, got {gamma}")
        n_slots = frame_slots or c.max_ragged_batch_size
        check_stat_range(n_slots, c.prefill_chunk_size, steps,
                         self.runner.stat_context(self.max_seq_len,
                                                  c.prefill_chunk_size))
        arrivals = iter(arrivals)
        if rng is None:
            self._rng, frame_rng = jax.random.split(self._rng)
        elif isinstance(rng, (int, np.integer)):
            frame_rng = jax.random.PRNGKey(int(rng))
        else:
            frame_rng = rng
        slots = DeviceSlotTable(
            n_slots, prompt_width=c.prefill_chunk_size,
            table_width=1, rng=frame_rng, tp=self.tp_ctx,
            debug_replicas=c.tp_debug_replica_check,
            n_stats=self.runner.n_stats,
            rings=[ring for _, ring in self.state.rings],
            hidden=(self.model.cfg.hidden_size, self.model.cfg.act_dtype)
            if speculate and self_draft else None,
            recurrent=self.runner.recurrent_shapes(n_slots),
            block=(self.model.cfg.block_length,
                   self.model.cfg.unmask_per_step)
            if self.runner.block_length else None)
        if faults is not None:
            faults.begin_serve()     # rearm the scripted schedule
        if self.prefix_cache is not None:
            # telemetry counters reset per serve run; rebase the cache's
            # cumulative bookkeeping so the first boundary's delta doesn't
            # absorb a previous run's history
            self._pc_stats_base = dict(self.prefix_cache.stats)
        self._handoff_mode = c.role == "prefill"
        if self._handoff_mode and self.kv_swap is None:
            raise ValueError(
                "role='prefill' needs a KV swap tier (kv_swap_dir= or "
                "attach_kv_tier()) — the prefill→decode handoff publishes "
                "committed pages through it")
        resume = self._resume_entries(resume_from)
        if self.kv_swap is not None:
            # swap records exist solely for re-admission: a run that will
            # not resume a uid has abandoned its pages — release them so
            # a crash/restart cycle can't accumulate dead pages in the
            # tier (records created by THIS run's preemptions come later).
            # A SHARED tier (the fleet) never prunes — the router owns
            # record lifecycle there (prune_requests is a no-op).
            self.kv_swap.prune_requests({r[0] for r in resume})
            self._tier_stats_base = dict(self.kv_swap.stats)
        self._ledger = {}
        self._resume_pending = {r[0] for r in resume}
        self._repair_counts = {}
        self._draining = False
        self.telemetry.begin_serve(speculate=speculate, gamma=gamma,
                                   adaptive=adaptive, n_slots=n_slots,
                                   kv_blocks_total=self.kv.num_blocks,
                                   tp_degree=self._config.tp,
                                   kv_block_bytes=self.kv.block_bytes,
                                   layered=self.runner.layer_work is not None,
                                   latent=bool(self.model.cfg.latent_lanes),
                                   share=self.model.cfg.moe_is_share,
                                   mtp=self.runner.has_mtp,
                                   recurrent_slot_bytes=sum(
                                       math.prod(shape)
                                       * jnp.dtype(dtype).itemsize
                                       for shape, dtype in
                                       self.runner.recurrent_shapes(1)),
                                   recurrent_stats=self.runner
                                   .recurrent_stat_names,
                                   block=self.runner.block_length)
        sched = FifoPolicy() if scheduler is None else scheduler
        sched.begin_serve(self)
        return self._serve_guarded(slots, arrivals, sched, steps,
                                   max_new_tokens, temperature, eos_token_id,
                                   speculate, gamma, adaptive, faults, resume,
                                   yield_boundaries)

    def _serve_guarded(self, slots, arrivals, sched, steps, max_new_tokens,
                       temperature, eos_token_id, speculate, gamma, adaptive,
                       faults, resume, boundaries=False):
        try:
            yield from self._serve_loop(
                slots, arrivals, sched, steps, max_new_tokens, temperature,
                eos_token_id, speculate=speculate, gamma=gamma,
                adaptive=adaptive, faults=faults, resume=resume,
                boundaries=boundaries)
        finally:
            # generator abandonment (break / close() / mid-stream error)
            # must not strand in-flight state: release every slot-held
            # sequence and every queued one that already has a descriptor
            # (deferred arrivals, preempted rows holding their emitted
            # tokens), or their KV blocks leak and a later call reusing a
            # uid would inherit stale generated tokens. The ledger is the
            # authoritative accepted-not-retired set — it also covers rows
            # caught mid-transit by a fault between eviction and
            # re-admission, which neither the slot table nor the policy's
            # queue sees.
            for uid in list(slots.slot_of_uid):
                self.state.flush_sequence(uid)
            for uid in sched.queued_uids():
                self.state.flush_sequence(uid)
            for uid in list(self._ledger):
                self.state.flush_sequence(uid)
            self._ledger.clear()

    @staticmethod
    def _pick_frame_steps(ewma: float, max_steps: int, saturated: bool) -> int:
        """Adaptive frame length (ROADMAP item (c)): the pow2 bucket whose
        size roughly admits one expected arrival per frame — bursty traffic
        gets small frames (arrivals wait at most frame_steps decode steps
        for admission), while a saturated table (no free slots: admission
        can't act anyway) or a drained arrival stream gets the full
        ``max_steps`` to amortize the host boundary. Buckets are
        {pow2 <= max_steps} ∪ {max_steps}; the pick is an operand of the
        frame program (``n_steps``), not a program of its own."""
        if saturated or ewma < 0.125:
            return max_steps
        target = max(1.0, max_steps / (1.0 + ewma))
        return min(BlockedKVCache.floor_pow2(target), max_steps)

    @staticmethod
    def _plan_frame_steps(cur_steps: int, max_steps: int, live: int,
                          prefill_steps: int, finish_steps: int,
                          carried: bool = False) -> int:
        """How many of the ``cur_steps`` steps the policy allows a frame
        runs: those in which its rows have something to do.

        A WIDE frame (``prefill_steps`` > 0: what its neediest prefilling row
        has left, ``DeviceSlotTable.prefill_steps_left``) whose prompts all
        begin in it ends with its last prefilling row, whose first token
        then leaves at the step that computed it; the decode steps behind it
        run narrow in the next frame. It runs half of ``max_steps`` at
        least: every step given up is a token of the new request that leaves
        behind its first one and not with it (the first is out sooner, the
        last no sooner), and under half a frame the boundary weighs on every
        row that rides it. A frame that takes a prompt over from an earlier
        one (``carried``, ``DeviceSlotTable.prefill_carried``) runs whole: a
        first token that has waited a frame or more gains a small share of
        its wait, and a full table of long prompts loses more to where its
        tail frames end than the few steps weigh (``PERF.md``, PR 41).

        A NARROW frame ends with the first row to emit the last token of its
        budget (``finish_steps``, ``DeviceSlotTable.steps_to_first_finish``)
        where that row would wait at least as many steps for the frame's end
        as there are ``live`` rows to pay for one more boundary, about a
        step each: a lone request's answer is complete at the step that
        computed its last token, a full table keeps its frames."""
        if prefill_steps:
            if carried:
                return cur_steps
            return min(cur_steps, max(prefill_steps, max_steps // 2))
        wait = cur_steps - finish_steps
        return finish_steps if wait >= live else cur_steps

    def _validate_arrival(self, uid, toks, limit, in_flight: bool) -> int:
        """serve()'s enqueue-time validation; returns the (possibly
        clamped) generation budget."""
        if uid < 0:
            raise ValueError(
                f"uid={uid}: serve() uids must be >= 0 (-1 is "
                "the free-slot sentinel)")
        if in_flight:
            raise ValueError(
                f"uid={uid} is already live in the slot table — "
                "serve() uids must be unique among in-flight "
                "requests")
        if uid in self.state.seqs:
            raise ValueError(
                f"uid={uid} is already tracked by the engine "
                "(stale from an earlier put()?) — "
                "flush it before serving, or it would inherit "
                "the old descriptor's tokens")
        ahead = self._lookahead
        if len(toks) + 1 + ahead > self.max_seq_len:
            raise ValueError(
                f"uid={uid}: prompt of {len(toks)} tokens can "
                f"never fit max_seq_len={self.max_seq_len}")
        if len(toks) + limit + ahead > self.max_seq_len:
            clamped = self.max_seq_len - len(toks) - ahead
            logger.warning(
                f"uid={uid}: prompt ({len(toks)}) + budget "
                f"({limit}) + {ahead} exceeds max_seq_len="
                f"{self.max_seq_len}; clamping budget to "
                f"{clamped}")
            limit = clamped
        return limit

    def _sync_frame_stats(self, slots, width, cur_steps, ewma, queue_depth,
                          stats_synced):
        """Frame-boundary counter absorption.

        The in-graph counters replay the old host arithmetic exactly
        (verify forwards = emit column 0; accepted drafts = the rest;
        accepted-but-not-emitted drafts at budget/EOS truncation are
        NOT counted, so acceptance_rate is the rate of draft slots
        that became useful tokens). One tiny frame-BOUNDARY read.
        The disabled path must stay the true zero-stats baseline, so
        even the argument gathering (counter sync, compile totals,
        mirror scans) is gated, not just the absorption."""
        tel = self.telemetry
        if tel.enabled and stats_synced:
            tel.on_frame(
                delta=slots.stats_delta(),
                width=width, steps=cur_steps,
                live_slots=slots.live_count(),
                kv_blocks_in_use=self.kv.num_blocks - self.kv.free_blocks,
                arrival_ewma=ewma, queue_depth=queue_depth,
                kv_kinds=self.kv.in_use() if self.runner.layer_work
                or self.kv.latent else None)
            return True
        if tel.enabled:
            # telemetry re-enabled mid-serve: the device vector holds
            # the whole disabled-period backlog (possibly int32-wrapped,
            # and this frame's events are mixed into it) — rebase and
            # discard; counters only count frames measured while enabled
            slots.stats_delta()
            tel.frame_view_update(width, cur_steps, ewma)
            return True
        tel.frame_view_update(width, cur_steps, ewma)
        return False

    # ------------------------------------------------------------------
    # fault tolerance: ledger, deadlines, quarantine, resilient dispatch
    # (faults.py; README "Fault tolerance & chaos testing")
    # ------------------------------------------------------------------

    def snapshot_serving_state(self) -> Dict:
        """Serialize the host-side request ledger of the current (or last)
        serve run — every accepted, not-yet-retired request's original
        prompt, committed tokens, remaining budget/deadline, and scheduling
        metadata — as a plain-python dict. Zero device reads (the ledger
        and the ``generated`` mirrors are host state the frame boundaries
        already maintain). Feed it to ``serve(..., resume_from=)`` on a
        restarted engine: resumed requests re-prefill prompt + committed
        tokens via the preemption machinery, so greedy outputs are
        token-identical across the restart (tokens from a frame that never
        returned are simply re-generated). Sampled (temperature > 0) rows
        resume correctly but not bit-identically — the frame RNG restarts.
        """
        return snapshot_ledger(self._ledger, self.state.seqs, self._clock,
                               swap_tier=self.kv_swap)

    def _ledger_add(self, uid, toks, limit, temp, eos, deadline_ms,
                    tenant=None, priority=None, slo_ms=None,
                    resumed_from=0, trace=None) -> None:
        self._ledger[uid] = LedgerEntry(
            uid=uid, prompt=[int(t) for t in toks], limit=int(limit),
            temp=float(temp), eos=eos,
            deadline_at=(None if deadline_ms is None
                         else self._clock() + deadline_ms * 1e-3),
            tenant=tenant, priority=priority, slo_ms=slo_ms,
            resumed_from=resumed_from, trace=trace)

    def _enqueue_traced(self, uid, **kw) -> None:
        """``telemetry.on_enqueue`` + write the effective trace context
        back into the ledger entry: a trace minted BY the engine (tuple
        arrivals carry none) must still ride snapshots, failovers, and
        handoffs, or the continuation would start a second tree."""
        trace = self.telemetry.on_enqueue(uid, **kw)
        ent = self._ledger.get(uid)
        if ent is not None and trace is not None:
            ent.trace = trace

    def _enqueue(self, sched, uid, toks, limit, temp, eos, dl_ms, gen,
                 tenant, prio, slo_ms, trace):
        """Accept one request: ledger, lifecycle span, the policy's queue.
        Every way in comes through here: a fresh arrival (``gen`` None),
        and a RESUME (a mid-run arrival from router failover / drain
        migration / prefill→decode handoff, or a ``resume_from`` snapshot
        entry), ``gen`` the tokens another run already committed.

        A resume rebuilds the host sequence and queues prompt + committed
        for re-prefill (the preemption fold), so greedy outputs are
        token-identical across the restart; the ledger keeps the original
        prompt and budget. Its submit bypasses the tenant queue quota, its
        only difference from a fresh one: the request was already ACCEPTED,
        and ``tenant_max_queued`` must not drop its committed tokens (the
        quota is submit()'s only shed, so a bypassed submit never sheds).
        Returns the output of a resume that had already spent its budget
        (retired here, for the caller to yield), else None."""
        tel = self.telemetry
        req = sched.new_request(uid, toks, limit, temp, eos, tenant, prio,
                                slo_ms)
        n_gen = len(gen or ())
        self._ledger_add(uid, toks, limit, temp, eos, dl_ms,
                         tenant=req.tenant, priority=req.pclass,
                         slo_ms=req.slo_ms, resumed_from=n_gen, trace=trace)
        self._enqueue_traced(uid, tenant=req.tenant, pclass=req.pclass,
                             resumed=n_gen > 0, trace=trace,
                             prompt_tokens=len(toks))
        if gen is None:
            shed = sched.submit(req)
            if shed is not None:
                tel.on_shed(uid, shed.tenant, shed.priority, shed.reason)
                self._ledger.pop(uid, None)
            return None
        seq = self.state.get_or_create_sequence(uid)
        seq.generated = list(gen)
        seq.done = False
        if limit <= n_gen:
            # finished before the other run could yield it
            out = np.asarray(seq.generated, np.int64)
            self.state.flush_sequence(uid)
            self._ledger.pop(uid, None)
            tel.on_retire(uid)
            return out
        if gen:
            req.tokens = np.concatenate([toks, np.asarray(gen, np.int32)])
        req.limit = limit - n_gen
        req.resumed_from, req.resumed = n_gen, True
        sched.submit(req, bypass_quota=True)
        return None

    def _resume_entries(self, resume_from) -> List[Tuple]:
        """Normalize a ``snapshot_serving_state()`` dict into resume
        ingestion tuples (validated eagerly, at the serve() call site)."""
        if resume_from is None:
            return []
        if resume_from.get("version") != 1:
            raise ValueError("resume_from: unrecognized snapshot "
                             f"version {resume_from.get('version')!r}")
        out = []
        for r in resume_from.get("requests", []):
            uid = int(r["uid"])
            if uid in self.state.seqs:
                raise ValueError(
                    f"resume_from: uid={uid} is already tracked by the "
                    "engine — flush it before resuming")
            generated = [int(t) for t in r.get("generated", [])]
            out.append((uid, np.asarray(r["prompt"], np.int32),
                        int(r["limit"]), float(r["temp"]), r["eos"],
                        r.get("deadline_remaining_ms"), generated,
                        r.get("tenant"), r.get("priority"), r.get("slo_ms"),
                        r.get("trace")))
        return out

    def _fault_retire(self, uid: int, kind: str, frame: int, detail: str,
                      partial=None, tenant=None, priority=None) -> None:
        """Abnormal request retirement: drop the ledger entry, record a
        structured ``FaultReason`` (with the committed partial output), and
        count it — the request is NOT yielded and NOT counted as a normal
        retirement."""
        ent = self._ledger.pop(uid, None)
        self._drop_swap(uid)
        if ent is not None:
            tenant = tenant or ent.tenant
            priority = priority if priority is not None else ent.priority
        self.fault_log.append(FaultReason(
            uid=uid, kind=kind, frame=frame, detail=detail,
            tokens_emitted=len(partial or ()),
            partial=list(partial) if partial else None,
            tenant=tenant,
            priority=str(priority) if priority is not None else None))
        self.telemetry.on_fault(kind, uid=uid)
        logger.warning(f"serve(): uid={uid} retired with fault "
                       f"kind={kind} at frame {frame}: {detail}")

    def _note_resume_truncated(self, uid, want, limit, frame: int) -> None:
        """Heterogeneous failover/migration landed on a peer whose
        ``max_seq_len`` cannot hold the request's original budget: the
        clamp makes token-identity with the no-failure run impossible, so
        record a structured fault (log + ``ds_serving_faults_total{kind=
        "resume_truncated"}``) instead of letting the shortened output
        pass as a normal completion. The request still serves what fits —
        capacity is a physical limit; dropping committed work would be
        strictly worse."""
        self.fault_log.append(FaultReason(
            uid=uid, kind="resume_truncated", frame=frame,
            detail=f"resume budget clamped {want}->{limit} by "
                   f"max_seq_len={self.max_seq_len}; output will be "
                   "shorter than the no-failure run"))
        self.telemetry.on_fault("resume_truncated", uid=uid)

    def _fault_event(self, kind: str, frame: int, detail: str) -> None:
        """Frame-level fault event (no single victim request): retries,
        slow frames, injected allocation failures, fatal crashes."""
        self.fault_log.append(FaultReason(uid=-1, kind=kind, frame=frame,
                                          detail=detail))
        self.telemetry.on_fault(kind)
        logger.warning(f"serve(): {kind} at frame {frame}: {detail}")

    def _expire_deadlines(self, slots, frame: int, sched) -> None:
        """Frame-boundary deadline enforcement for queued AND live rows:
        an expired request is cancelled wherever it sits — popped from the
        policy's queue (BEFORE it can be admitted, aged, or preempted
        for), or evicted from its live slot — its KV blocks are freed and
        a ``deadline_expired`` timeout retirement is recorded."""
        now = self._clock()
        expired = [uid for uid, ent in self._ledger.items()
                   if ent.deadline_at is not None and now >= ent.deadline_at]
        for uid in expired:
            seq = self.state.seqs.get(uid)
            partial = list(seq.generated) if seq is not None else []
            if uid in slots.slot_of_uid:
                slots.evict(uid)
                sched.on_retire(uid)
                where = f"live row ({len(partial)} tokens committed)"
            else:
                sched.cancel(uid)
                where = "queued (never admitted)"
            self.state.flush_sequence(uid)       # frees any KV blocks
            ent = self._ledger.get(uid)
            if ent is not None and ent.cancelled:
                self._fault_retire(uid, "cancelled", frame,
                                   detail=f"cancel_request() while {where}",
                                   partial=partial)
            else:
                self._fault_retire(uid, "deadline_expired", frame,
                                   detail=f"deadline_ms elapsed while "
                                          f"{where}",
                                   partial=partial)

    def _quarantine_rows(self, uids, slots, frame: int, sched,
                         escalated: bool = False) -> None:
        """Poison-row quarantine: latched rows are evicted (the preemption
        path: freeze + free slot + free KV blocks) and retired with a
        ``poison_row`` FaultReason — the batch never dies for one request.
        One tiny boundary read (``nonfinite_uids``), nothing in-frame."""
        detail = ("non-finite logits persisted past nonfinite_repair_limit="
                  f"{self._config.nonfinite_repair_limit} boundaries; row "
                  "quarantined, siblings unaffected") if escalated else \
            ("non-finite logits (in-graph finite-check); row quarantined, "
             "siblings unaffected")
        for uid in uids:
            seq = self.state.seqs.get(uid)
            partial = list(seq.generated) if seq is not None else []
            slots.evict(uid)
            sched.on_retire(uid)
            if self.prefix_cache is not None:
                # pages published by a row whose logits went non-finite
                # may themselves hold non-finite KV — never hand them to
                # a healthy request
                self.prefix_cache.invalidate_uid(uid)
            self.state.flush_sequence(uid)
            self._repair_counts.pop(uid, None)
            self._fault_retire(uid, "poison_row", frame, detail=detail,
                               partial=partial)

    def _handle_nonfinite(self, slots, frame: int, sched) -> List[int]:
        """Frame-boundary dispatch for latched finite-check rows. Under the
        default ``quarantine`` policy every latched row is evicted/retired.
        Under ``repair`` the compiled frame already rolled each latched row
        back to its pre-fault carry — a row is given another chance (latch
        and poison flag cleared; one batched boundary write) until it has
        latched ``nonfinite_repair_limit`` CONSECUTIVE boundaries, at which
        point the blip is a persistent fault and the row escalates to the
        quarantine path. Returns the repaired uids so the caller can
        resync their committed-watermark mirrors after the host replay
        (``DeviceSlotTable.resync_committed``).

        Repaired rows keep their published prefix blocks: the per-step
        finite check gates the watermark, so every page at or below it was
        verified finite before it could be published."""
        flagged = slots.nonfinite_uids()
        if not self._nonfinite_repair:
            if flagged:
                self._quarantine_rows(flagged, slots, frame, sched)
            return []
        # a clean boundary resets a row's consecutive-blip count
        for uid in [u for u in self._repair_counts if u not in flagged]:
            self._repair_counts.pop(uid)
        repaired, doomed = [], []
        for uid in flagged:
            n = self._repair_counts.get(uid, 0) + 1
            if n > self._config.nonfinite_repair_limit:
                doomed.append(uid)
            else:
                self._repair_counts[uid] = n
                repaired.append(uid)
        if doomed:
            self._quarantine_rows(doomed, slots, frame, sched,
                                  escalated=True)
        if repaired:
            slots.clear_nonfinite(repaired)
            for uid in repaired:
                seq = self.state.seqs.get(uid)
                self.fault_log.append(FaultReason(
                    uid=uid, kind="nonfinite_repaired", frame=frame,
                    detail=f"non-finite logits; row rolled back to its "
                           f"pre-fault carry (blip "
                           f"{self._repair_counts[uid]}/"
                           f"{self._config.nonfinite_repair_limit})",
                    tokens_emitted=len(seq.generated) if seq else 0))
                # no uid passed: the request is still in flight, its
                # lifecycle span must survive the blip
                self.telemetry.on_fault("nonfinite_repaired")
        return repaired

    def _run_frame_resilient(self, slots, width, steps, n_steps, greedy,
                             draft, faults, frame: int):
        """Dispatch one frame under the resilience policy: injected-fault
        hooks, bounded retry with exponential backoff for transient
        dispatch failures (the donated carry is untouched by a
        pre-dispatch failure, so a retried frame is token-identical), a
        wall-clock watchdog, and — when the retry budget is exhausted — an
        automatic ledger snapshot (``last_crash_snapshot``) before the
        crash surfaces as ``FrameDispatchError``."""
        c = self._config
        attempt = 0
        while True:
            try:
                # the watchdog window opens before the injection hook: an
                # injected stall simulates a slow DISPATCH, so it must be
                # inside the measured span
                t0 = self._clock()
                if faults is not None:
                    faults.before_dispatch(frame, attempt)
                # one frame of ``n_steps`` steps: dispatch, then fetch the
                # (steps, B[, gamma+1]) token/emit pair: the host waiting
                # for the chip, and the only device->host transfer a frame
                # performs
                with self.telemetry.phase("dispatch"):
                    toks, emit = slots.dispatch_frame(
                        self.runner, self.params, self.kv, width, steps,
                        greedy, draft=draft, repair=self._nonfinite_repair,
                        n_steps=n_steps)
                with self.telemetry.phase("fetch"):
                    toks, emit = np.asarray(toks), np.asarray(emit)
                dt_ms = (self._clock() - t0) * 1e3
                if c.watchdog_frame_ms is not None \
                        and dt_ms > c.watchdog_frame_ms:
                    self._fault_event(
                        "slow_frame", frame,
                        f"frame took {dt_ms:.1f} ms > watchdog "
                        f"{c.watchdog_frame_ms} ms (width={width} "
                        f"steps={n_steps})")
                return toks, emit
            except Exception as e:        # noqa: BLE001 — bounded + re-raised
                attempt += 1
                if attempt > c.max_frame_retries:
                    self.last_crash_snapshot = self.snapshot_serving_state()
                    self._fault_event(
                        "dispatch_failed", frame,
                        f"{type(e).__name__}: {e} (after {attempt} attempts)")
                    raise FrameDispatchError(
                        f"frame {frame} dispatch failed after {attempt} "
                        f"attempts ({type(e).__name__}: {e}); "
                        "engine.last_crash_snapshot holds the request "
                        "ledger — serve(resume_from=...) resumes the "
                        "in-flight requests") from e
                self._fault_event(
                    "dispatch_retry", frame,
                    f"{type(e).__name__}: {e} (attempt {attempt}/"
                    f"{c.max_frame_retries}, retrying)")
                backoff = c.frame_retry_backoff_s * (2 ** (attempt - 1))
                if backoff > 0:
                    time.sleep(backoff)

    def _note_recovery_progress(self, slots, resume_t0: float,
                                n_resumed: int) -> None:
        """Once every resumed request has cleared the queue (re-admitted
        into a slot, or already terminally handled — immediate-complete,
        expired, faulted), stamp ``ds_serving_recoveries_total`` and the
        ``last_recovery_ms`` gauge: the window clients of the crashed run
        waited on the restarted engine before decoding resumed."""
        if not self._resume_pending:
            return
        self._resume_pending = {u for u in self._resume_pending
                                if u in self._ledger
                                and u not in slots.slot_of_uid}
        if not self._resume_pending:
            self.telemetry.on_recover(
                n_resumed, (self._clock() - resume_t0) * 1e3)

    # ------------------------------------------------------------------
    # KV memory hierarchy (kv_hierarchy.py): prefix-cache admission,
    # copy-on-write, boundary publishing, swap-tier restore
    # ------------------------------------------------------------------

    def _drop_swap(self, uid: int) -> None:
        """Drop a request's swap-tier record at terminal retirement (the
        record was either consumed by a swap-in or is now stale). NOT
        called on generator abandonment after a crash — the tier must
        outlive the engine so ``serve(resume_from=)`` can restore pages."""
        if self.kv_swap is not None:
            self.kv_swap.drop_request(uid)

    def _admit_capacity(self, uid: int, seq, toks, limit: int,
                        boundary: int) -> Optional[int]:
        """Reserve KV capacity for one admission. Returns the admission
        watermark ``cached0`` (tokens whose pages are already valid — 0 on
        the cold path) or None when the pool cannot hold the request yet.

        With the hierarchy off this is exactly the old
        ``ensure_capacity`` probe. With it on, in order of preference:
        (1) a preempted/crashed victim whose committed pages sit in the
        host swap tier restores them into fresh blocks (replacing
        re-prefill); (2) a prompt matching published prefix blocks maps
        them read-only (copy-on-write for a mid-block divergence); (3)
        cold. Capacity failures first try reclaiming cold unreferenced
        cache blocks. A deferred request KEEPS its mapped shared blocks
        (refcount bumps, zero pool cost) and its ``resume_cached`` mark,
        so the retry at the next boundary resumes where it left off."""
        total = len(toks) + limit + self._lookahead
        if self.prefix_cache is None and self.kv_swap is None:
            return 0 if self.state.ensure_capacity(seq, total) else None
        chunk = self._config.prefill_chunk_size
        # --- (1) swap-in re-admission ---
        if self.kv_swap is not None and not seq.blocks:
            from .kv_hierarchy import token_fingerprint
            rec = self.kv_swap.request_record(uid)
            # the record's pages cover the first rec["tokens"] tokens of
            # the folded stream at eviction — a prefix of ``toks`` by
            # construction (a queued victim emits nothing). The CONTENT
            # fingerprint is re-validated too: a reused uid with a fresh
            # prompt must never restore another request's pages
            if rec is not None and not (
                    0 < rec["tokens"] <= len(toks)
                    and rec.get("fingerprint") ==
                    token_fingerprint(toks[:rec["tokens"]])):
                self.kv_swap.drop_request(uid)     # stale: uid was reused
                rec = None
            if rec is not None:
                if not self._ensure_capacity_reclaim(seq, total):
                    return None      # record kept: retry next boundary
                try:
                    self.kv_swap.restore_request(
                        uid, self.kv, seq.blocks[:rec["blocks"]],
                        draft_kv=self.draft_kv)
                except Exception as e:   # noqa: BLE001 — fall back
                    self.kv_swap.drop_request(uid)
                    self._fault_event(
                        "swap_failed", boundary,
                        f"uid={uid}: page restore failed "
                        f"({type(e).__name__}: {e}); re-prefilling")
                else:
                    self.kv_swap.drop_request(uid)
                    cached0 = (min(rec["tokens"], len(toks) - 1)
                               // chunk * chunk)
                    seq.resume_cached = cached0
                    self.telemetry.on_kv_swap_in(
                        rec["blocks"], resume=uid in self._resume_pending,
                        uid=uid)
                    return cached0
        # --- (2) prefix hit: the LOCAL cache first (device blocks shared
        # read-only — zero pool cost), then the SHARED tier's content-
        # addressed prefix records (the fleet-wide share: pages another
        # replica prefilled restore into private blocks at the
        # watermark). One probe per enqueue (a deferred HIT retry already
        # holds its mapped blocks, and a deferred miss must not count a
        # fresh lookup per boundary) ---
        cached0 = seq.resume_cached
        if not seq.blocks and not seq.hier_probed and \
                (self.prefix_cache is not None or
                 (self.kv_swap is not None and
                  self._config.tier_prefix_share)):
            seq.hier_probed = True
            if self.prefix_cache is not None:
                cached0 = self._prefix_map(seq, toks)
            if cached0 == 0 and self.kv_swap is not None \
                    and self._config.tier_prefix_share:
                cached0 = self._tier_prefix_map(seq, toks, boundary)
        # --- (3) fresh blocks for everything past the mapped prefix ---
        if not self._ensure_capacity_reclaim(seq, total):
            return None
        seq.resume_cached = cached0
        return cached0

    def _ensure_capacity_reclaim(self, seq, total: int) -> bool:
        """``ensure_capacity`` with one retry after evicting cold
        unreferenced prefix-cache blocks (spilled to the swap tier when
        one is configured — KV pressure spills instead of shedding)."""
        if self.state.ensure_capacity(seq, total):
            return True
        if self.prefix_cache is not None:
            need = self.kv.blocks_for(total) - len(seq.blocks) \
                - self.kv.free_blocks
            if need > 0 and self.prefix_cache.reclaim(need) > 0 \
                    and self.state.ensure_capacity(seq, total):
                return True
        return False

    def _prefix_map(self, seq, toks) -> int:
        """Map the longest usable published prefix into ``seq.blocks``:
        full blocks below the (chunk-aligned) admission watermark are
        shared read-only; a hit ending mid-block copies that page
        (copy-on-write) so the divergent continuation writes a private
        copy. Returns the watermark (0 = miss). Chunk alignment makes a
        hit admission replay the exact prefill chunk boundaries of a cold
        one, keeping greedy outputs token-identical cache-on vs -off."""
        pc = self.prefix_cache
        tel = self.telemetry
        alloc = self.kv.allocator
        bs = self.kv.block_size
        chunk = self._config.prefill_chunk_size
        full, partial = pc.match(toks)
        # every matched entry is still refcount-1 until mapped below —
        # protect the whole chain so one entry's swap-restore cannot
        # reclaim a chain-mate this same admission is about to share
        protect = {e.eid for e in full} | \
            ({partial[0].eid} if partial else set())
        usable = []
        for e in full:
            if not pc.ensure_resident(e, protect=protect):
                break
            usable.append(e)
        partial_ok = partial if (
            partial is not None and len(usable) == len(full)
            and pc.ensure_resident(partial[0], protect=protect)) else None
        matched = len(usable) * bs + (partial_ok[1] if partial_ok else 0)
        cached0 = min(matched, len(toks) - 1) // chunk * chunk
        n_full, mid = cached0 // bs, cached0 % bs
        chain = usable + ([partial_ok[0]] if partial_ok else [])
        if mid and alloc.free_blocks < 1 and \
                not pc.reclaim(1, protect={e.eid for e in chain}):
            # no page for the COW copy: shrink the hit to whole blocks,
            # aligned to BOTH the block and the chunk (chunk need not
            # divide the block size) so mid comes out 0 — anything else
            # would re-derive a COW against a pool known to be empty
            align = bs * chunk // math.gcd(bs, chunk)
            cached0 = n_full * bs // align * align
            n_full, mid = cached0 // bs, 0
        if cached0 <= 0:
            tel.on_prefix_lookup(0, 0, False)
            return 0
        shared = [e.block for e in chain[:n_full]]
        alloc.share(shared)
        seq.blocks.extend(shared)
        if mid:
            src = chain[n_full].block
            dst = alloc.allocate(1)[0]
            self.kv.k, self.kv.v = self.kv.copy_blocks(
                self.kv.k, self.kv.v, [src], [dst])
            if self.draft_kv is not None:
                self.draft_kv.k, self.draft_kv.v = self.draft_kv.copy_blocks(
                    self.draft_kv.k, self.draft_kv.v, [src], [dst])
            seq.blocks.append(dst)
            pc.stats["cow_copies"] += 1
        pc.touch(chain[:n_full + (1 if mid else 0)], cached0)
        tel.on_prefix_lookup(cached0, n_full + (1 if mid else 0), mid > 0)
        # record the watermark ON THE DESCRIPTOR the moment blocks are
        # mapped: if the remainder reservation defers this admission, the
        # retry must resume at cached0 — prefilling from 0 would WRITE
        # into the shared (published, read-only) pages
        seq.resume_cached = cached0
        # the mapped full blocks ARE published entries: seed the publish
        # cursor so this row's first boundary publish resumes after them
        # instead of re-hashing the whole shared prefix
        seq.published_upto = n_full * bs
        seq.publish_parent = chain[n_full - 1].eid if n_full else -1
        return cached0

    def _publish_prefixes(self, slots) -> None:
        """Frame-boundary publish: every live row's full blocks below its
        committed watermark enter the prefix index (content below the
        watermark is final — sharing is read-only by construction). Also
        syncs the cache's bookkeeping deltas into the telemetry counters."""
        pc = self.prefix_cache
        if pc is None:
            return
        bs = self.kv.block_size
        for uid, slot in list(slots.slot_of_uid.items()):
            seq = self.state.seqs.get(uid)
            ent = self._ledger.get(uid)
            if seq is None or ent is None or not seq.blocks:
                continue
            w = int(slots.cached_h[slot])
            lo = seq.published_upto // bs * bs
            if w // bs * bs <= lo:
                continue                     # no newly committed full block
            # hand publish only the UNPUBLISHED suffix of the stream — a
            # long-context row's boundary publish must not re-copy its
            # whole prompt+generated history every block
            pl = len(ent.prompt)
            seg = seq.generated[lo - pl:] if lo >= pl \
                else ent.prompt[lo:] + seq.generated
            _, seq.publish_parent, d_done = pc.publish(
                uid, seg, seq.blocks, w, start_depth=lo // bs,
                parent=seq.publish_parent)
            # advance only as far as the walk actually got: an early stop
            # (cache at capacity, or a reclaimed chain position) must
            # retry those depths, never skip them
            seq.published_upto = d_done * bs
        s = dict(pc.stats)
        base = self._pc_stats_base or {k: 0 for k in s}
        self.telemetry.on_prefix_update(
            s["published"] - base["published"],
            s["evicted"] - base["evicted"],
            s["swapped_out"] - base["swapped_out"],
            s["swapped_in"] - base["swapped_in"],
            pc.resident_blocks())
        self._pc_stats_base = s

    # ------------------------------------------------------------------
    # disaggregated serving (role="prefill"): boundary drain of async
    # swap-out commits, incremental tier publish, prefill→decode handoff
    # ------------------------------------------------------------------

    def _drain_swap_boundary(self, boundary: int) -> None:
        """Frame-boundary drain of async swap-out commits: the writes
        queued at the previous boundary rode the aio queue through the
        frame in between (overlapped); a drain failure drops the queued
        records — their victims fall back to re-prefill — and surfaces as
        a ``swap_failed`` fault, never a crashed serve. For a non-shared
        tier the commit-mode counters sync into this engine's telemetry
        (a SHARED tier's counters are fleet-level — the router exports
        them instead, since any replica's boundary may drain a peer's
        queued writes)."""
        tier = self.kv_swap
        if tier is None:
            return
        try:
            tier.drain(blocking=False)
        except Exception as e:       # noqa: BLE001 — degrade loudly
            self._fault_event(
                "swap_failed", boundary,
                f"async swap-out commit failed ({type(e).__name__}: {e}); "
                "queued records dropped, victims will re-prefill")
        if not tier.shared and self.telemetry.enabled:
            s, base = tier.stats, self._tier_stats_base or {}
            self.telemetry.on_kv_swap_commits(
                s["commits_overlapped"] - base.get("commits_overlapped", 0),
                s["commits_blocking"] - base.get("commits_blocking", 0))
            self._tier_stats_base = dict(s)

    def _full_stream(self, ent, seq) -> List[int]:
        """The folded token stream the row's KV pages cover: original
        prompt + every committed token (for a resume, ``seq.generated``
        already starts with the carried-in tokens, so this is exactly the
        admitted prompt + this engine's emissions)."""
        return [int(t) for t in ent.prompt] + [int(t) for t in seq.generated]

    def _tier_prefix_map(self, seq, toks, boundary: int) -> int:
        """Fleet-wide prefix share, the admission side: match the prompt
        against the shared tier's content-addressed prefix records and
        restore the hit pages into freshly-allocated PRIVATE blocks (the
        tier is host RAM — nothing is shared on device, so no COW is
        needed). Returns the chunk-aligned admission watermark (0 =
        miss). Chunk alignment keeps the cold chunk-boundary replay, so
        greedy outputs stay token-identical tier-hit vs cold."""
        chunk = self._config.prefill_chunk_size
        hit = self.kv_swap.match_prefix(toks, chunk)
        if hit is None:
            return 0
        key, rec = hit
        cached0 = min(rec["tokens"], len(toks) - 1) // chunk * chunk
        if cached0 <= 0:
            return 0
        n = self.kv.blocks_for(cached0)
        if self.kv.allocator.free_blocks < n and self.prefix_cache is not None:
            self.prefix_cache.reclaim(n - self.kv.allocator.free_blocks)
        if self.kv.allocator.free_blocks < n:
            return 0
        blocks = self.kv.allocator.allocate(n)
        try:
            self.kv_swap.restore_prefix(key, self.kv, blocks,
                                        draft_kv=self.draft_kv)
        except Exception as e:   # noqa: BLE001 — degrade to a cold miss
            self.kv.allocator.free(blocks)
            self._fault_event(
                "swap_failed", boundary,
                f"tier prefix restore failed ({type(e).__name__}: {e}); "
                "admitting cold")
            return 0
        seq.blocks.extend(blocks)
        seq.resume_cached = cached0
        self.telemetry.on_tier_prefix_hit(cached0, n)
        return cached0

    def _publish_segments(self, uid: int, seq, stream, w: int, nb: int,
                          handoff=None) -> int:
        """Publish blocks ``[seq.tier_blocks, nb)`` of ``seq`` (covering
        ``stream[:w]``) into the uid's tier record, passing the publish
        cursor so a record desynced by a dropped commit — a failed drain
        on this engine OR a peer sharing the tier — is detected and
        healed by republishing the whole prefix from block zero (the
        restore invariant ``blocks == blocks_for(tokens)`` survives every
        failure path). Returns the blocks written and advances the
        cursor; raises on I/O errors (the caller maps them to
        ``swap_failed``)."""
        from .kv_hierarchy import token_fingerprint
        fp = token_fingerprint(stream[:w])
        start = seq.tier_blocks
        if not self.kv_swap.publish_request_segment(
                uid, w, fp, self.kv, seq.blocks[start:nb],
                draft_kv=self.draft_kv,
                async_commit=self._config.kv_swap_async,
                handoff=handoff, start_block=start):
            seq.tier_blocks = start = 0
            self.kv_swap.publish_request_segment(
                uid, w, fp, self.kv, seq.blocks[:nb],
                draft_kv=self.draft_kv,
                async_commit=self._config.kv_swap_async,
                handoff=handoff, start_block=0)
        seq.tier_blocks = nb
        return nb - start

    def _tier_publish_progress(self, slots, boundary: int,
                               next_steps: int = 1) -> None:
        """Prefill-role boundary publish: every live MID-PREFILL row's
        newly-committed full blocks enter its tier record as one more
        segment (async — the writes overlap with the next frame). A
        replica killed mid-prompt therefore leaves a restorable
        partial-watermark record: the failover peer restores the pages
        and resumes prefill at the watermark instead of from token
        zero.

        Handoff PIPELINING (``handoff_pipeline``, README "Disaggregated
        prefill/decode"): a row whose remaining prompt fits the next
        frame (``remaining <= chunk * next_steps``) will hand off at the
        NEXT boundary — so this boundary publishes its FINAL segment
        (everything below the current chunk-aligned watermark, including
        a partially-filled tail block) and stamps the handoff metadata.
        The final segment's write I/O then overlaps the first-token frame
        instead of landing between the handoff and the decode replica's
        blocking restore; the handoff boundary itself does zero page I/O.
        The record's watermark stays at the publish point — the decode
        side replays the (sub-frame, chunk-aligned) tail cold, exactly
        the proven partial-watermark failover path, so greedy outputs
        stay token-identical. A mispredicted handoff (the next frame ran
        shorter than planned — adaptive sizing or a scheduler pressure
        cap) is healed here one boundary later: a partial tail block's
        snapshot is stale above its watermark, so the record is dropped
        and republished from block zero before any further append."""
        bs = self.kv.block_size
        chunk = self._config.prefill_chunk_size
        pipeline = self._config.handoff_pipeline
        for uid, slot in list(slots.slot_of_uid.items()):
            if slots.cached_h[slot] >= slots.plen_h[slot]:
                continue                       # prefill done: handoff path
            seq = self.state.seqs.get(uid)
            ent = self._ledger.get(uid)
            if seq is None or ent is None or not seq.blocks:
                continue
            w_cur = int(slots.cached_h[slot])
            remaining = int(slots.plen_h[slot]) - w_cur
            if seq.tier_final:
                # the pipelined final publish predicted a handoff that
                # did not come: fall back to incremental publishing. A
                # full-block record is still appendable (just clear the
                # flags); a partial tail block must be republished from
                # zero (its snapshot is garbage above the watermark, and
                # segments are append-only).
                if seq.tier_partial:
                    try:
                        self.kv_swap.drop_request(uid)
                    except Exception as e:   # noqa: BLE001 — best-effort
                        self._fault_event(
                            "swap_failed", boundary,
                            f"uid={uid}: stale pipelined record drop "
                            f"failed ({type(e).__name__}: {e})")
                    seq.tier_blocks = 0
                seq.tier_final = seq.tier_partial = False
            final = pipeline and remaining <= chunk * max(1, next_steps)
            if final:
                nb, w = self.kv.blocks_for(w_cur), w_cur
            else:
                nb = w_cur // bs
                w = nb * bs
            if nb > len(seq.blocks):
                continue
            meta = {"prompt_tokens": len(ent.prompt),
                    "generated": len(seq.generated),
                    "role": "prefill", "pipelined": True} if final else None
            if nb <= seq.tier_blocks:
                if final and seq.tier_blocks == nb and nb > 0:
                    # no new pages, but the record is now the COMPLETE
                    # handoff record — stamp the metadata (no page I/O).
                    # A False return means the record is GONE (a failed
                    # async drain dropped it): leave tier_final unset so
                    # the handoff republishes honestly instead of
                    # claiming a record that does not exist
                    try:
                        if self.kv_swap.stamp_request_handoff(uid, meta):
                            seq.tier_final = True
                        else:
                            seq.tier_blocks = 0
                    except Exception as e:   # noqa: BLE001 — best-effort
                        self._fault_event(
                            "swap_failed", boundary,
                            f"uid={uid}: pipelined handoff stamp failed "
                            f"({type(e).__name__}: {e})")
                continue
            stream = self._full_stream(ent, seq)
            try:
                n_new = self._publish_segments(uid, seq, stream, w, nb,
                                               handoff=meta)
                seq.tier_final = final
                seq.tier_partial = final and w < nb * bs
                if n_new:
                    self.telemetry.on_kv_swap_out(n_new, uid=uid,
                                                  publish=True)
            except Exception as e:   # noqa: BLE001 — publish is best-effort
                self._fault_event(
                    "swap_failed", boundary,
                    f"uid={uid}: incremental prefill publish failed "
                    f"({type(e).__name__}: {e}); continuing unpublished")

    def _handoff_arrival(self, uid: int, ent, seq) -> Dict:
        """The resume-arrival dict a handoff forwards to the router —
        exactly the ``faults.snapshot_split`` shape (original prompt +
        committed tokens + ORIGINAL budget + scheduling metadata), so the
        decode replica's ingestion is the proven failover path."""
        item = {
            "uid": int(uid),
            "tokens": [int(t) for t in ent.prompt],
            "generated": [int(t) for t in seq.generated],
            "max_new_tokens": int(ent.limit),
            "temperature": float(ent.temp),
            "eos_token_id": -1 if ent.eos is None else int(ent.eos),
        }
        for k, v in (("tenant", ent.tenant), ("priority", ent.priority),
                     ("slo_ms", ent.slo_ms), ("trace", ent.trace)):
            if v is not None:
                item[k] = v
        if ent.deadline_at is not None:
            item["deadline_ms"] = max(
                (ent.deadline_at - self._clock()) * 1e3, 1e-3)
        return item

    def _collect_handoffs(self, slots, boundary: int, chunk: int,
                          sched) -> List[HandoffEvent]:
        """Prefill-role frame boundary: every live row whose committed
        watermark covers its prompt is DONE here — publish its remaining
        pages (final segment, with the handoff metadata) plus a
        content-addressed PREFIX record for the prompt itself (the
        fleet-wide prefix share: later identical prompts on ANY replica
        admit at the watermark), then evict the row and hand the request
        back as a ``HandoffEvent``. Rows that already finished outright
        (EOS / budget) were retired by the caller and never reach here."""
        out: List[HandoffEvent] = []
        for uid, slot in list(slots.slot_of_uid.items()):
            if slots.cached_h[slot] < slots.plen_h[slot]:
                continue                       # still prefilling
            seq = self.state.seqs.get(uid)
            ent = self._ledger.get(uid)
            if seq is None or ent is None or not seq.generated:
                continue
            stream = self._full_stream(ent, seq)
            w = int(slots.cached_h[slot])
            n = self.kv.blocks_for(w)
            published = False
            if seq.tier_final:
                # pipelined handoff: the final segment (and the handoff
                # metadata) was published at the boundary BEFORE the
                # first-token frame — the record is complete and
                # restorable at its own (lower, chunk-aligned) watermark,
                # and this boundary does zero page I/O. The decode
                # replica replays the sub-frame tail cold. Refresh only
                # the generated-token count in the metadata — a False
                # return means a failed async drain DROPPED the record
                # after the early publish: report published=False so the
                # router counts it (handoffs_unpublished) and the decode
                # side's re-prefill is an accounted fallback, not a
                # silent one.
                try:
                    published = self.kv_swap.stamp_request_handoff(
                        uid, {"prompt_tokens": len(ent.prompt),
                              "generated": len(seq.generated),
                              "role": "prefill", "pipelined": True})
                except Exception as e:   # noqa: BLE001 — metadata only
                    self._fault_event(
                        "swap_failed", boundary,
                        f"uid={uid}: pipelined handoff stamp failed "
                        f"({type(e).__name__}: {e})")
            elif 0 < w < len(stream) + 1 and seq.tier_blocks < n <= \
                    len(seq.blocks):
                try:
                    n_new = self._publish_segments(
                        uid, seq, stream, w, n,
                        handoff={"prompt_tokens": len(ent.prompt),
                                 "generated": len(seq.generated),
                                 "role": "prefill"})
                    published = True
                    if n_new:
                        self.telemetry.on_kv_swap_out(n_new, uid=uid,
                                                      publish=True)
                except Exception as e:   # noqa: BLE001 — decode re-prefills
                    self._fault_event(
                        "swap_failed", boundary,
                        f"uid={uid}: handoff page publish failed "
                        f"({type(e).__name__}: {e}); the decode replica "
                        "will re-prefill")
            elif seq.tier_blocks >= n:
                published = True               # already covered by segments
            if published and self._config.tier_prefix_share:
                w_pfx = len(ent.prompt) // chunk * chunk
                n_pfx = self.kv.blocks_for(w_pfx)
                if w_pfx >= chunk and n_pfx <= len(seq.blocks):
                    try:
                        self.kv_swap.put_prefix(
                            stream[:w_pfx], self.kv, seq.blocks[:n_pfx],
                            draft_kv=self.draft_kv,
                            async_commit=self._config.kv_swap_async)
                    except Exception as e:   # noqa: BLE001 — best-effort
                        self._fault_event(
                            "swap_failed", boundary,
                            f"uid={uid}: tier prefix publish failed "
                            f"({type(e).__name__}: {e})")
            item = self._handoff_arrival(uid, ent, seq)
            pipelined = seq.tier_final
            slots.evict(uid)
            sched.on_retire(uid)
            self.state.flush_sequence(uid)
            self._ledger.pop(uid, None)
            self.telemetry.on_handoff_out(uid, pipelined=pipelined)
            logger.info(f"serve(): uid={uid} handed off at boundary "
                        f"{boundary} (watermark={w}, published={published}, "
                        f"pipelined={pipelined})")
            out.append(HandoffEvent(uid=uid, arrival=item,
                                    published=published))
        return out

    # ------------------------------------------------------------------
    # the serve loop: one, whatever the admission policy (scheduler.py)
    # ------------------------------------------------------------------

    def _evict_to_queue(self, uid, slots, sched, boundary: int = -1):
        """Preempt a live row at a frame boundary: freeze its device slot,
        release its KV blocks, fold its emitted tokens into the request's
        prompt, and re-queue it at the front of its class/tenant queue.
        Re-admission re-prefills the committed prefix — token-identical
        under greedy decoding — unless the host-RAM swap tier is on, in
        which case the victim's committed pages are swapped OUT here (one
        boundary D2H read per pool) and swapped back IN at re-admission,
        replacing the re-prefill with a page restore."""
        seq = self.state.seqs[uid]
        req = sched.on_evict(uid)
        emitted = seq.generated[req.gen_base:]
        if emitted:
            req.tokens = np.concatenate(
                [np.asarray(req.tokens, np.int32),
                 np.asarray(emitted, np.int32)])
            req.limit -= len(emitted)
        if self.kv_swap is not None and self._config.kv_swap_preempt \
                and seq.blocks:
            # committed watermark: pages cover the first w tokens of the
            # folded stream (the newest emitted token rides ``last_tok``
            # and is NOT in KV yet, so w == len(req.tokens) - 1 for a
            # decode-phase victim; mid-prefill victims sit lower)
            w = int(slots.committed_h[slots.slot_of_uid[uid]])
            n = self.kv.blocks_for(w)
            if 0 < w <= len(req.tokens) and n <= len(seq.blocks):
                from .kv_hierarchy import token_fingerprint
                try:
                    # async: the page writes ride the aio queue and commit
                    # at the NEXT boundary's drain, overlapped with the
                    # frame in between (the device gather already
                    # happened, so freeing the blocks below stays safe); a
                    # commit failure drops the record and the victim
                    # re-prefills
                    self.kv_swap.put_request(
                        uid, w, self.kv, seq.blocks[:n],
                        draft_kv=self.draft_kv,
                        fingerprint=token_fingerprint(req.tokens[:w]),
                        async_commit=self._config.kv_swap_async)
                    self.telemetry.on_kv_swap_out(n, uid=uid)
                except Exception as e:   # noqa: BLE001 — re-prefill instead
                    self._fault_event(
                        "swap_failed", boundary,
                        f"uid={uid}: page swap-out failed "
                        f"({type(e).__name__}: {e}); victim will re-prefill")
        slots.evict(uid)
        seq.resume_cached = 0           # the mapped pages are going away
        seq.hier_probed = False         # re-admission probes the cache anew
        # the put_request above REPLACED any incremental segment record
        # (prefill-role engines), and re-admission's restore will consume
        # it — the publish cursor must restart at zero or the next
        # progress publish would write a record whose segments start at a
        # stale block offset while claiming the full watermark (silently
        # corrupt pages on the decode side's restore)
        seq.tier_blocks = 0
        seq.tier_final = seq.tier_partial = False
        self.state.release_blocks(seq)
        sched.requeue_front(req)
        self.telemetry.on_preempt(uid, req.tenant, req.pclass)

    def _serve_loop(self, slots, arrivals, sched, steps, max_new_tokens,
                    temperature, eos_token_id, speculate=False, gamma=0,
                    adaptive=False, faults=None, resume=(),
                    boundaries=False):
        """The frame loop. Enqueue and admission flow through the policy
        object ``sched`` (``scheduler.FifoPolicy`` or a
        ``RequestScheduler``), with its control pass, its preemptions and
        its cap on the frame length at each boundary. All of it is
        host-side boundary work — the frames themselves are untouched.
        Deadline expiry runs BEFORE the control pass, so expired work is
        cancelled before it can be aged, preempted for, or admitted."""
        c = self._config
        tel = self.telemetry
        alpha = c.frame_steps_ewma_alpha
        ewma = 0.0
        exhausted = False
        stats_synced = True     # device stat vector starts at zero
        boundary = -1           # frame-boundary index (fault schedules key
        #                         on it; == dispatched-frame index while
        #                         rows are live)
        resume_t0 = self._clock()
        n_resumed = len(resume)
        # ---- crash-recovery ingestion: the snapshot's requests re-enter
        # ahead of any new arrival, with their original class/tenant/slo
        for (uid, prompt, limit, temp, eos, dl_ms, generated, tenant, prio,
             slo_ms, trace) in resume:
            done_out = self._enqueue(sched, uid, prompt, limit, temp, eos,
                                     dl_ms, generated, tenant, prio, slo_ms,
                                     trace)
            if done_out is not None:
                with tel.phase("yield"):
                    yield uid, done_out

        def try_reserve(req):
            # the policy's probe: blocks for ``req`` at this boundary, or None
            seq = self.state.get_or_create_sequence(req.uid)
            cached0 = self._admit_capacity(req.uid, seq, req.tokens,
                                           req.limit, boundary)
            return None if cached0 is None else (seq, cached0)

        while True:
            boundary += 1
            # a poll on an empty server is the wait for the next arrival,
            # not work between two frames: it is the phase ``idle``
            with tel.phase("poll" if slots.live_count()
                           or sched.queued_count() else "idle"):
                # commit the async swap-out writes queued at the previous
                # boundary (they overlapped with the frame in between)
                self._drain_swap_boundary(boundary)
                # ---- poll the arrival clock ----
                if exhausted:
                    batch = None
                    ewma = (1.0 - alpha) * ewma
                else:
                    try:
                        batch = next(arrivals)
                    except StopIteration:
                        exhausted = True
                        batch = None
                    ewma = alpha * len(batch or []) + (1.0 - alpha) * ewma
                    # validate at ENQUEUE — before any KV reservation is made
                    # for this round, so a bad request can't strand blocks
                    # already reserved for earlier items in the same batch
                    for item in (batch or []):
                        uid, toks, limit, temp, eos, tenant, prio, slo_ms, \
                            dl_ms, gen, trace = self._norm_arrival(
                                item, max_new_tokens, temperature, eos_token_id)
                        want = limit
                        limit = self._validate_arrival(
                            uid, toks, limit,
                            in_flight=uid in slots.slot_of_uid or
                            sched.is_queued(uid))
                        if gen is not None and limit < want:
                            self._note_resume_truncated(uid, want, limit,
                                                        boundary)
                        done_out = self._enqueue(
                            sched, uid, toks, limit, temp, eos, dl_ms, gen,
                            tenant, prio, slo_ms, trace)
                        if done_out is not None:
                            with tel.phase("yield"):
                                yield uid, done_out
            with tel.phase("admit"):
                # ---- deadlines: cancel expired work (queued or live) BEFORE
                # it can be aged, preempted for, or admitted ----
                self._expire_deadlines(slots, boundary, sched)
                # ---- the policy's control pass: age queues, refill fair-share
                # credit, recompute pressure, shed best-effort work under
                # critical pressure (structured reasons land in
                # sched.shed_log). It takes the SLO view itself, if it reads
                # one ----
                for shed in sched.on_boundary(tel.slo_view,
                                              live_count=slots.live_count()):
                    tel.on_shed(shed.uid, shed.tenant, shed.priority,
                                shed.reason)
                    # a shed request may have a blockless descriptor left by a
                    # failed capacity probe — drop it, or the uid could never
                    # be reused (ditto a stale swap-tier record)
                    self.state.flush_sequence(shed.uid)
                    self._ledger.pop(shed.uid, None)
                    self._drop_swap(shed.uid)
                # ---- frame-boundary preemption: make room for a queued
                # interactive arrival by evicting a lower-priority live row
                # (pointless while draining: nothing will be admitted) ----
                if not self._draining and sched.preempt_wanted(slots.free_slots()):
                    committed = {u: int(slots.committed_h[s])
                                 for u, s in slots.slot_of_uid.items()}
                    for uid in sched.pick_victims(
                            committed, free_blocks=self.kv.free_blocks):
                        self._evict_to_queue(uid, slots, sched, boundary)
                # ---- admission, in the policy's order (blocks reserved for
                # the whole prompt + generation budget up front, so block
                # tables never grow mid-flight) ----
                blocks_before = self.kv.free_blocks
                alloc_blocked = faults is not None \
                    and faults.kv_alloc_blocked(boundary)
                if alloc_blocked and sched.queued_count():
                    self._fault_event(
                        "kv_alloc_failed", boundary,
                        "injected KV-block allocation failure; admission "
                        "deferred this boundary")
                admits = []
                if not alloc_blocked and not self._draining:
                    for req, res in sched.pick(slots.free_slots(), try_reserve,
                                               live_count=slots.live_count()):
                        seq, cached0 = res
                        seq.done = False
                        req.gen_base = len(seq.generated)
                        admits.append((req.uid, seq, req.tokens, req.limit,
                                       req.temp, req.eos, cached0))
                        tel.on_admit(req.uid)
                if sched.queued_count() and not self._draining:
                    # overload is otherwise invisible: the deferred arrivals
                    # just wait — count it and warn (rate-limited). admit()
                    # hasn't executed yet, so subtract this round's admits or
                    # a full table would be misreported as KV pressure;
                    # likewise free_blocks already reflects this round's
                    # reservations, so thread the reserved count through to
                    # keep standing pressure distinguishable from a busy
                    # admission round
                    tel.on_defer(
                        queue_depth=sched.queued_count(),
                        frame_steps=tel.serve_view["frame_steps_last"] or steps,
                        free_slots=slots.free_slots() - len(admits),
                        free_blocks=self.kv.free_blocks,
                        reserved_blocks=blocks_before - self.kv.free_blocks)
                if admits:
                    slots.ensure_widths(
                        max(len(a[2]) for a in admits),
                        max(len(a[1].blocks) for a in admits),
                        self.max_seq_len, self.max_blocks_per_seq)
                    slots.admit(admits)
                self._note_recovery_progress(slots, resume_t0, n_resumed)
            if slots.live_count() == 0:
                tel.on_idle_boundary()
                if exhausted and not sched.queued_count():
                    return
                if boundaries:
                    with tel.phase("yield"):
                        yield ServeBoundary(
                            index=boundary, dispatched=False, live=0,
                            queued=sched.queued_count(),
                            free_slots=slots.free_slots(), t=self._clock(),
                            queued_tokens=sched.queued_prompt_tokens())
                continue         # arrival gap: poll the clock again
            with tel.phase("plan"):
                # ---- frame plan: wide while any slot prefills, else pure
                # decode at width 1 (two programs in all, the frame's length
                # their operand; width-1 frames are the speculative
                # draft/verify frames when a draft rides). The policy's
                # pressure signal caps the frame length so admission
                # boundaries come around sooner while interactive latency is
                # at risk, and a frame ends where its rows have nothing left
                # to do in it (``_plan_frame_steps``: from the host mirrors,
                # no device read) ----
                need = slots.prefill_steps_left(c.prefill_chunk_size)
                # (two blocks wide where the model generates by diffusion
                # over blocks)
                width = c.prefill_chunk_size if need \
                    else self.runner.narrow_width
                cur_steps = steps
                saturated = slots.free_slots() == 0
                if adaptive:
                    cur_steps = self._pick_frame_steps(ewma, steps, saturated)
                cur_steps = self._plan_frame_steps(
                    min(cur_steps, sched.frame_steps_cap(steps)), steps,
                    slots.live_count(), need, slots.steps_to_first_finish(),
                    slots.prefill_carried())
                tel.on_frame_plan(ewma, saturated, cur_steps)
                draft = None
                if speculate and self.draft_model is None:
                    draft = "self"      # the model's own prediction module
                elif speculate:
                    draft = (self.draft_runner, self.draft_params, self.draft_kv,
                             gamma)
                if faults is not None:
                    slots.set_poison(faults.poison_uids(boundary))
            with tel.frame_trace(width, cur_steps):
                toks, emit = self._run_frame_resilient(
                    slots, width, steps, cur_steps, slots.all_greedy(),
                    draft, faults, boundary)
            with tel.phase("absorb"):
                stats_synced = self._sync_frame_stats(
                    slots, width, cur_steps, ewma, sched.queued_count(),
                    stats_synced)
                # quarantine BEFORE the host replay: a poisoned row's slot is
                # freed here, so absorb neither emits its garbage tail nor
                # retires it as finished (repair-policy rows survive instead
                # and get their mirrors resynced after the replay)
                repaired = self._handle_nonfinite(slots, boundary, sched)
                emissions, finished = slots.absorb(toks, emit, width,
                                                   cur_steps)
                if repaired:
                    slots.resync_committed(repaired)
                for uid, new_toks in emissions.items():
                    seq = self.state.seqs[uid]
                    seq.generated.extend(new_toks)
                    # the committed watermark, NOT the speculative write cursor:
                    # rejected draft positions never count as seen
                    seq.seen_tokens = int(
                        slots.committed_h[slots.slot_of_uid[uid]])
                    tel.on_emit(uid, len(new_toks))
            with tel.phase("publish"):
                if self._handoff_mode:
                    self._tier_publish_progress(slots, boundary, cur_steps)
                self._publish_prefixes(slots)
            with tel.phase("retire"):
                for uid in finished:
                    seq = self.state.seqs[uid]
                    seq.done = True
                    out = np.asarray(seq.generated, np.int64)
                    slots.retire(uid)
                    self.state.flush_sequence(uid)
                    sched.on_retire(uid)
                    self._ledger.pop(uid, None)
                    self._drop_swap(uid)
                    tel.on_retire(uid)
                    with tel.phase("yield"):
                        yield uid, out
            if self._handoff_mode:
                # prefill complete (and not finished outright): publish
                # the final pages + prefix record and hand the request
                # back to the router for decode placement
                with tel.phase("publish"):
                    handoffs = self._collect_handoffs(
                        slots, boundary, c.prefill_chunk_size, sched)
                for event in handoffs:
                    with tel.phase("yield"):
                        yield event
            if boundaries:
                with tel.phase("yield"):
                    yield ServeBoundary(
                        index=boundary, dispatched=True,
                        live=slots.live_count(), queued=sched.queued_count(),
                        free_slots=slots.free_slots(), t=self._clock(),
                        queued_tokens=sched.queued_prompt_tokens(),
                        emissions=emissions)

    def serialize(self, path: str):
        """Analog of ``engine_v2.py:251`` — snapshot params for fast reload."""
        from ...runtime.checkpoint_engine.orbax_engine import NumpyCheckpointEngine
        NumpyCheckpointEngine().save({"module": self.params, "meta": {}}, path)


def build_hf_engine(model_or_path, engine_config: Optional[RaggedInferenceEngineConfig] = None,
                    **kwargs) -> InferenceEngineV2:
    """Analog of ``engine_factory.py:69``: build from an HF model instance or
    a checkpoint DIRECTORY (HF layout: config.json + [sharded] weights) —
    the directory path never materializes a torch module."""
    import os
    if isinstance(model_or_path, str) and os.path.isdir(model_or_path):
        from ...module_inject import native_from_checkpoint
        model, params = native_from_checkpoint(model_or_path)
        return InferenceEngineV2(model, engine_config, params=params, **kwargs)
    return InferenceEngineV2(model_or_path, engine_config, **kwargs)
