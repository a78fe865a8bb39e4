"""Serving telemetry: in-graph frame counters, request tracing, export.

The frame loop (``engine_v2.serve``) exists to keep the host out of the
decode path, which also removes every place a profiler hook or counter used
to live. This module restores the telemetry surface WITHOUT reintroducing
host round-trips, in three layers:

1. **In-graph frame counters** — the serving scan bodies
   (``model_runner._serving_scan_body`` / ``_spec_scan_body``) accumulate a
   small ``(N_STATS,)`` int32 vector on the scan carry: tokens emitted,
   active row-steps (the live-slot occupancy integral), prompt tokens
   consumed, in-graph EOS events, and draft/verify counts under speculative
   decoding. The vector rides the donated frame carry like every other slot
   array, so it costs a handful of in-graph reductions and surfaces ONLY at
   frame boundaries — zero extra device→host transfers inside a frame
   (``tests/test_serving_telemetry.py`` pins this with a transfer guard).

2. **Host request-lifecycle tracing** — ``serve()`` stamps
   enqueue → admit → first-token → retire transitions per request into
   fixed-memory log-bucketed histograms (``LogBucketHistogram``): TTFT,
   inter-token latency, queue wait, and end-to-end latency, each with
   p50/p90/p99 summaries. Inter-token latency is measured at frame
   granularity: a row emitting ``n`` tokens in a frame records ``n`` samples
   of ``gap / n`` where ``gap`` is the time since the row's previous
   emission — intra-frame spacing is not host-observable by design.

3. **Export** — ``render_prometheus()`` (text exposition format, scrapeable
   behind any HTTP handler), frame-boundary event fan-out through a monitor
   (anything with ``write_events([(tag, value, step)])`` — e.g.
   ``monitor.MonitorMaster``), and an opt-in ``jax.profiler``
   ``TraceAnnotation`` wrapper so device profiles line up with frames.

4. **Boundary phases** — ``phase(name)`` wraps each stretch of host work
   between two frames (``PHASES``): its exclusive time accumulates into
   ``counters["host_<name>_ns"]``, and with ``trace=True`` it is a
   ``serve/<name>`` span on the profiler's clock, so an idle gap of the
   device reads as poll, admit, plan, fetch, absorb, publish, retire, the
   consumer's ``yield`` or an empty server's ``idle`` instead of a JAX
   internal.

``engine.serve_stats`` is a thin read-through view over this subsystem
(``ServingTelemetry.serve_view``): the dict the pre-telemetry tests and
``serving_bench.py`` already consume, now fed from the device counters.

``enabled=False`` disables the HOST side only (no per-frame device counter
sync, no histograms, no fan-out); the in-graph counters are always part of
the compiled frame — they are a few scalar reductions, and keeping one
program variant means toggling telemetry never recompiles anything.
"""

import math
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import Dict, List, Optional

import jax
import jax.monitoring
import numpy as np

from ...utils.logging import logger

# ---------------------------------------------------------------------------
# in-graph stat vector layout (accumulated on the frame carry)
# ---------------------------------------------------------------------------
# Indices into the (N_STATS,) int32 vector the serving scan bodies carry.
# Semantics per accumulation step:
#   EMITTED        tokens emitted (sum of the emit mask)
#   ACTIVE_STEPS   rows that did any work this step — the occupancy integral
#   PREFILL_TOKS   prompt tokens consumed this step
#   EOS            emitted tokens that hit their row's EOS id
#   TARGET_FWD     decode-mode target forwards: plain decode row-steps, or
#                  width-1 speculative VERIFY forwards (matching the
#                  pre-telemetry serve_stats arithmetic exactly — decode
#                  rows coasting inside wide speculative frames are not
#                  verify forwards and are not counted here)
#   DRAFTED        draft tokens proposed (gamma per verify forward)
#   ACCEPTED       accepted-and-emitted draft tokens (emit columns >= 1)
#   KV_READ        KV positions the step's queries must read, over active
#                  rows: min(cached + w, sliding_window + w), or cached + w
#                  without a (uniform) window — w is the row's chunk width
#   ATTN_PAIRS     query x key pairs that attention must score: w * KV_READ
#   ROW_TILES      row tiles of a WIDE step's query rows, one layer's: over
#                  slots and kv heads, a head's width x G rows a slot cut as
#                  the paged kernel cuts them (``paged_attention.row_tile``)
#   ROW_TILES_LIVE those of them that hold a live row, ceil(w x G / tile):
#                  the tiles the kernel computes (0 for a frozen slot, 1 for
#                  a decoding row riding the step)
#   POSITIONS      token positions the step's per-token layers (embedding,
#                  projections, MLP) ran: the rung of ``pack_ladder`` the
#                  step chose in the graph, or slots x width where the
#                  shape has one rung only
#   RUNG0..        steps run at rung i of the step's ``pack_ladder``
#                  (``MAX_RUNGS`` lanes; a one-rung shape counts in none)
# A model with routed experts carries MOE_STAT_NAMES' and MOVED_STAT_NAMES'
# lanes more, behind these (``n_stats``); a dense model's vector, and so its
# frame programs, have no trace of them:
#   EXPERT_ROWS    rows the step sent through routed experts (token x
#                  chosen expert), summed over the MoE layers: the group
#                  sizes the grouped product was handed
#   EXPERTS_TOUCHED  experts that got at least one row, summed over the MoE
#                  layers: each is a read of that expert's three matrices
#   EXPERT_ROWS_MAX  the largest group of each MoE layer, summed over them
#   EXPERT_ROWS_MOVED  rows the layers' dispatch gathered and their combine
#                  added (``layers.moe_rows_moved``): the whole blocks that
#                  hold the groups' rows where the chip holds a share of the
#                  experts, every selection row (tokens x k, dead positions
#                  too) elsewhere
# KV_READ and ATTN_PAIRS are work, not events: the host splits each frame's
# delta by the frame's width into ``<name>_narrow`` / ``<name>_wide``
# counters (SPLIT_STAT_NAMES), the operands of the paged kernels' roofline
# shares.
STAT_EMITTED = 0
STAT_ACTIVE_STEPS = 1
STAT_PREFILL_TOKS = 2
STAT_EOS = 3
STAT_TARGET_FWD = 4
STAT_DRAFTED = 5
STAT_ACCEPTED = 6
STAT_KV_READ = 7
STAT_ATTN_PAIRS = 8
STAT_ROW_TILES = 9
STAT_ROW_TILES_LIVE = 10
STAT_POSITIONS = 11
STAT_RUNG0 = 12
#: rungs a ladder may have (``pack_ladder``), one lane each
MAX_RUNGS = 6
N_STATS = STAT_RUNG0 + MAX_RUNGS
#: first of the lanes a model with routed experts appends (MOE_STAT_NAMES)
STAT_EXPERT_ROWS = N_STATS
#: a smaller token buffer costs what this one does: the MXU's rows are not
#: filled and the weights are read all the same
MIN_RUNG = 128

STAT_NAMES = ("tokens_emitted", "active_row_steps", "prefill_tokens",
              "eos_events", "target_forwards", "drafted_tokens",
              "accepted_draft_tokens")
#: lanes after STAT_NAMES, split by frame width at absorption
SPLIT_STAT_NAMES = ("kv_positions_read", "attn_pairs")
#: lanes after those: the wide steps' row tiles and the ones computed
TILE_STAT_NAMES = ("attn_row_tiles", "attn_row_tiles_live")
#: the routed experts' work, lanes STAT_EXPERT_ROWS..: counters of their own
MOE_STAT_NAMES = ("expert_rows", "experts_touched", "expert_rows_max")
#: one lane right behind them (EXPERT_ROWS_MOVED above): what the routed
#: layers' bookkeeping moved for the rows MOE_STAT_NAMES count
MOVED_STAT_NAMES = ("expert_rows_moved",)


#: the attention's work SUMMED OVER LAYERS with each layer's own window,
#: lanes of a model of mixed cache kinds alone (``kv_cache.cache_kinds``),
#: behind the routed experts' where it has those too: KV positions read,
#: query x key pairs scored, and the ring kinds' part of the first. Work,
#: so split by frame width like SPLIT_STAT_NAMES. (KV_READ and ATTN_PAIRS
#: count such a model ONE layer at full context, an upper bound.)
LAYER_STAT_NAMES = ("kv_positions_read_layers", "attn_pairs_layers",
                    "kv_positions_read_window")


#: a router wider than the experts held (``cfg.moe_is_share``: zero
#: experts, or one chip's share of a layer's experts), lanes behind
#: MOE_STAT_NAMES, which then count the HELD experts' work: the live
#: tokens' selections (tokens x k, summed over the layers), those that
#: chose a zero expert, and those that chose an expert held elsewhere
SHARE_STAT_NAMES = ("expert_selections", "zero_expert_selections",
                    "absent_expert_selections")
#: a model with latent attention, last lanes of its vector: latent rows its
#: attention layers read and query x row pairs they score, summed over the
#: attention layers (two a shortcut-connected layer). Work, so split by
#: frame width like SPLIT_STAT_NAMES
LATENT_STAT_NAMES = ("latent_positions_read", "latent_pairs")


#: a model with a prediction module (``cfg.num_nextn_predict_layers``),
#: the very last lanes of its vector: the module's work when it drafts, kept
#: apart from the stack's (EXPERT_ROWS and the lanes above count the stack
#: alone): cached rows its attention layer read, rows it sent through its
#: routed experts, and experts those touched. 0 while it does not draft
MTP_STAT_NAMES = ("mtp_latent_positions_read", "mtp_expert_rows",
                  "mtp_experts_touched")


#: a model with linear (Gated DeltaNet) layers (``cfg.mixer_pattern``), the
#: very last lanes of its vector (it has no prediction module's): positions
#: its linear layers moved their states by (live positions x linear layers)
#: and states they read and wrote (live rows x linear layers, a step). Its
#: full-attention layers count their reads in LAYER_STAT_NAMES. Last, the
#: positions the delta rule COMPUTED for them (``model_runner
#: ._rule_positions``). On the chip a wide step's rule is one kernel over
#: the rows that hold anything (``ops/pallas/gated_delta_rule.py``): one
#: position for a row that holds one, a prefilling row's live blocks of 64,
#: none for a row that sits out. Elsewhere (``model_runner._rule_by_rows``)
#: a wide step runs the chunked form on the rows that hold more than one
#: live position, two a trip of a loop (trips x 2 x width), and the
#: one-position recurrence on every row. A narrow step is the recurrence on
#: every row, on every backend
RECURRENT_STAT_NAMES = ("gdn_positions", "gdn_state_rw",
                        "gdn_positions_computed")
#: a model with conv (gated short convolution) layers, the very last lane
#: of its vector, behind RECURRENT_STAT_NAMES where the stack has linear
#: layers too: positions its conv layers moved their tails by (live
#: positions x conv layers). A conv layer keeps no matrix state, so there
#: is no lane of states read and written, and its convolution computes the
#: chunk it is handed: none of positions computed either
CONV_STAT_NAMES = ("conv_positions",)


#: a model that generates by diffusion over blocks (``cfg.block_length``),
#: the very last lanes of its vector (it has neither a prediction module
#: nor linear layers): of the row-forwards ``target_forwards`` counts for
#: it (a row past its prompt forwarding the block of L positions it holds,
#: or 2 L in a fused step: ONE row-forward), those that denoise (read
#: logits at L positions and unmask some; a fused one among them) and those
#: that ONLY commit (the mask-free block of a row it ends: K, V kept, L
#: tokens or fewer out, logits unused); positions the denoising steps
#: unmasked; blocks whose watermark moved (by a fused step or a commit);
#: masked positions those forwards computed (the rest of their positions
#: held a token already); and ``bd_fused_forwards``, the denoising forwards
#: that also committed the block before: its K, V kept and its tokens out
#: from the first L positions, the next block's first denoising step at
#: the second L (``model_runner._block_scan_body``). A row's every block
#: but its last is committed so
BLOCK_STAT_NAMES = ("bd_denoise_forwards", "bd_commit_forwards",
                    "bd_positions_unmasked", "bd_blocks_committed",
                    "bd_masked_positions_computed", "bd_fused_forwards")


def n_stats(routed: bool, layered: bool = False, share: bool = False,
            latent: bool = False, mtp: bool = False,
            recurrent: int = 0, block: bool = False) -> int:
    """Lanes of the stat vector of a model with (or without) routed
    experts (all of them held, or a share), of mixed cache kinds or of
    one, with latent attention or without, with a prediction module or
    without, with ``recurrent`` lanes for the mixers that keep a state a
    slot (the length of ``PagedModelRunner.recurrent_stat_names``),
    generating by diffusion over blocks or left to right."""
    return (N_STATS
            + (len(MOE_STAT_NAMES) + len(MOVED_STAT_NAMES) if routed else 0)
            + (len(SHARE_STAT_NAMES) if share else 0)
            + (len(LAYER_STAT_NAMES) if layered else 0)
            + (len(LATENT_STAT_NAMES) if latent else 0)
            + (len(MTP_STAT_NAMES) if mtp else 0)
            + recurrent
            + (len(BLOCK_STAT_NAMES) if block else 0))


#: host work between two frames, in loop order; ``dispatch`` and ``fetch``
#: lie inside the ``serve_frame/...`` span (fetch is the host waiting for
#: the chip), ``yield`` is the consumer's time. ``idle`` is ``poll`` on an
#: empty server (nothing live, nothing queued): the wait for the next
#: arrival, which is no work of the boundary's
PHASES = ("idle", "poll", "admit", "plan", "dispatch", "fetch", "absorb",
          "publish", "retire", "yield")


#: a request's time from the edge to its first token on the wire, cut where
#: it is handed over (README "Time to first token, stage by stage"): each
#: stage ends at the stamp of the thread that takes the request on
TTFT_STAGES = ("ingress", "feed", "queue", "prefill", "egress")
#: what ``tracing.TraceCollector`` folds a first token into, for the
#: replica that emitted it; that replica's serve loop mirrors them into
#: ``counters`` at its frame boundaries (``_sync_ttft``)
TTFT_COUNTERS = ("ttft_requests", "ttft_total_ns") + tuple(
    f"ttft_{s}_ns" for s in TTFT_STAGES) + ("ttft_prefill_frames",)


class _CompileLog:
    """Every program this process asks XLA for, by the thread that asked:
    JAX's backend-compile event (it wraps a load from the persistent cache
    too — either way the caller waited). One process-wide listener; a
    serve loop reads its own thread's totals, so replicas driven by
    threads of one process do not count each other's programs."""

    def __init__(self):
        try:
            from jax._src.dispatch import BACKEND_COMPILE_EVENT as event
        except ImportError:
            event = "/jax/core/compile/backend_compile_duration"
        self.event = event
        self.by_thread: Dict[int, List[int]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_built)

    def _on_built(self, event, duration, **kwargs):
        del kwargs
        if event == self.event:
            rec = self.by_thread.setdefault(threading.get_ident(), [0, 0])
            rec[0] += 1
            rec[1] += int(duration * 1e9)

    def totals(self):
        """(programs, nanoseconds waited) of the calling thread so far."""
        return tuple(self.by_thread.get(threading.get_ident(), (0, 0)))


_COMPILE_LOG: Optional[_CompileLog] = None


def compile_log() -> _CompileLog:
    """The process's one compile listener, registered at first use."""
    global _COMPILE_LOG
    if _COMPILE_LOG is None:
        _COMPILE_LOG = _CompileLog()
    return _COMPILE_LOG


class _Phase:
    """One ``ServingTelemetry.phase`` span. Phases nest (a ``yield`` inside
    ``retire``): the outer one pauses while the inner runs, so the
    ``host_*_ns`` counters are exclusive times and tile the loop."""

    __slots__ = ("tel", "name", "key", "ann", "timed")

    def __init__(self, tel, name):
        self.tel, self.name, self.key = tel, name, f"host_{name}_ns"
        self.ann, self.timed = None, False

    def __enter__(self):
        tel = self.tel
        if tel.trace:
            self.ann = jax.profiler.TraceAnnotation("serve/" + self.name)
            self.ann.__enter__()
        if tel.enabled:
            if tel._compile_base is None:
                tel._sync_compiles()     # programs count from the first phase
            now = tel.phase_clock()
            stack, c = tel._phase_stack, tel.counters
            if stack:
                outer = stack[-1]
                c[outer[0]] = c.get(outer[0], 0) + now - outer[1]
            stack.append([self.key, now])
            self.timed = True
        return self

    def __exit__(self, *exc):
        tel = self.tel
        stack, c = tel._phase_stack, tel.counters
        if self.timed and stack and stack[-1][0] == self.key:
            now = tel.phase_clock()
            c[self.key] = c.get(self.key, 0) + now - stack.pop()[1]
            if stack:
                stack[-1][1] = now
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


def check_stat_range(slots: int, width: int, steps: int,
                     context: int) -> None:
    """The work lanes are int32 sums that wrap modulo 2^32 and that the
    host reads as unsigned at every boundary, so a frame's true delta must
    stay under 2^32. The largest is ATTN_PAIRS with every slot consuming
    ``width`` positions over ``context`` readable ones (the sliding window,
    or the longest sequence) at each of ``steps`` steps; a configuration
    past that is refused here instead of reading wrapped counters."""
    worst = slots * steps * width * (context + width)
    if worst >= 1 << 32:
        raise ValueError(
            f"a frame of {slots} slots x {steps} steps x {width} positions "
            f"over {context} of context can score {worst} query x key "
            "pairs, which the int32 frame counters cannot hold (2^32): "
            "lower frame_steps or prefill_chunk_size")


def pack_ladder(slots: int, width: int) -> tuple:
    """The token-buffer sizes ("rungs") a step of ``slots`` rows x ``width``
    positions may run its per-token layers at, ascending; the last is the
    chunk whole (``slots * width``: nothing packed). A rung below it holds
    ``k`` rows consuming a whole chunk with every other row decoding, for
    ``k`` = 1, 2, 4, ... under ``slots``, rounded up to 16 rows (a packed
    bfloat16 tile). A rung under ``MIN_RUNG`` tokens buys nothing over the
    next, so a decode step, a speculative verify and any small chunk have
    one rung. Where a shape has such rungs it also has the one for ``k`` =
    0, every live row decoding: most steps of a wide frame once its prompt
    tokens are consumed. At most ``MAX_RUNGS`` are kept (the largest, and
    ``k`` = 0). The frame programs choose a step's rung in the graph from
    its live count; the host names the per-rung step counters off the same
    list."""
    top = slots * width

    def rung(k):
        return -(-(k * width + slots - k) // 16) * 16

    rungs, k = [], 1
    while k < slots:
        if MIN_RUNG <= rung(k) < top and rung(k) not in rungs:
            rungs.append(rung(k))
        k *= 2
    if not rungs:
        return (top,)
    return (rung(0),) + tuple(rungs[2 - MAX_RUNGS:]) + (top,)


def zero_stats(lanes: int = N_STATS):
    """Fresh ``(lanes,)`` device stat vector for a frame carry (a
    tensor-parallel frame loop carries it replicated, like every other slot
    array; see ``DeviceSlotTable.stats_delta``)."""
    import jax.numpy as jnp
    return jnp.zeros((lanes,), jnp.int32)


# ---------------------------------------------------------------------------
# fixed-memory log-bucketed histogram
# ---------------------------------------------------------------------------


class LogBucketHistogram:
    """Log-bucketed latency histogram with O(1) memory and record cost.

    ``n_buckets`` geometric buckets spanning ``[lo, lo * growth**n_buckets)``
    plus one overflow bucket; values below ``lo`` land in bucket 0. With the
    defaults (100 µs first bound, ×2 growth, 22 buckets) the span is
    100 µs … ~7 min, which covers TTFT through E2E on one scale.

    ``percentile(p)`` returns the geometric midpoint of the bucket holding
    the p-quantile sample — the standard fixed-memory estimator; the error
    is bounded by the bucket's growth factor. Deterministic given the same
    recorded values, which is what the golden tests rely on.
    """

    def __init__(self, lo: float = 1e-4, growth: float = 2.0,
                 n_buckets: int = 22):
        assert lo > 0 and growth > 1 and n_buckets >= 1
        self.lo = lo
        self.growth = growth
        self.n_buckets = n_buckets
        self._log_g = math.log(growth)
        # bucket i covers (bounds[i-1], bounds[i]]; bucket n_buckets = +Inf
        self.bounds = [lo * growth ** i for i in range(n_buckets)]
        self.counts = np.zeros(n_buckets + 1, np.int64)
        self.total = 0
        self.sum = 0.0

    def record(self, value: float, count: int = 1) -> None:
        if count <= 0:
            return
        if value <= self.lo:
            idx = 0
        else:
            idx = min(int(math.ceil(math.log(value / self.lo) / self._log_g
                                    - 1e-12)), self.n_buckets)
        self.counts[idx] += count
        self.total += count
        self.sum += value * count

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 100]; None when empty."""
        if self.total == 0:
            return None
        rank = p / 100.0 * self.total
        cum = 0
        for i, c in enumerate(self.counts):
            cum += int(c)
            if cum >= rank and c > 0:
                if i >= self.n_buckets:          # overflow bucket
                    return self.bounds[-1] * self.growth
                upper = self.bounds[i]
                if i == 0:
                    return upper / 2.0
                return math.sqrt(upper / self.growth * upper)
        return self.bounds[-1] * self.growth

    def summary(self) -> Dict:
        return {
            "count": int(self.total),
            "sum": round(self.sum, 6),
            "p50": self.percentile(50), "p90": self.percentile(90),
            "p99": self.percentile(99),
        }

    def reset(self) -> None:
        self.counts[:] = 0
        self.total = 0
        self.sum = 0.0


# ---------------------------------------------------------------------------
# per-request lifecycle span
# ---------------------------------------------------------------------------


class _Span:
    __slots__ = ("uid", "enqueue_t", "admit_t", "first_token_t",
                 "last_emit_t", "tokens", "emit_spans", "tenant", "pclass",
                 "resumed", "trace", "parent", "prompt_tokens", "work0")

    def __init__(self, uid: int, enqueue_t: float,
                 tenant: Optional[str] = None, pclass: Optional[str] = None,
                 resumed: bool = False, prompt_tokens: int = 0):
        self.uid = uid
        self.enqueue_t = enqueue_t
        self.prompt_tokens = prompt_tokens
        # (frames, steps) the loop had dispatched when this was admitted
        self.work0 = (0, 0)
        self.admit_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.last_emit_t: Optional[float] = None
        self.tokens = 0
        self.emit_spans = 0         # per-frame emit instants recorded
        self.tenant = tenant        # scheduler metadata (None without one)
        self.pclass = pclass
        # a resume arrival (router failover / drain migration / prefill→
        # decode handoff) already emitted its true first token on another
        # engine: this engine's first emission is a CONTINUATION, not a
        # TTFT sample — recording it would pollute the per-replica TTFT
        # histograms the disaggregation bench compares. The fleet-merged
        # ``ds_fleet_ttft_ms`` attribution lives in tracing.TraceCollector
        # (one sample per TRACE id, spanning handoff/failover).
        self.resumed = resumed
        # distributed-trace context (tracing.py): the fleet-wide trace id
        # this request rides, and the span id engine spans parent to (the
        # trace's root) — both carried in from the arrival dict, or minted
        # locally when a tracer is attached and the arrival had none
        self.trace: Optional[str] = None
        self.parent: Optional[str] = None


class ServingTelemetry:
    """The serving telemetry subsystem (see module docstring).

    ``clock`` is injectable (defaults to ``time.monotonic``) so lifecycle
    tests can script deterministic timestamps. ``record_spans`` keeps the
    last ``max_spans`` retired request records (bounded memory) for
    per-request debugging; aggregation never needs them.
    """

    HIST_NAMES = ("ttft", "itl", "queue_wait", "e2e")
    #: per-request ceiling on per-frame "emit" instant spans (tracing):
    #: keeps a long generation from exhausting the collector's per-trace
    #: span budget before its terminal spans are recorded
    MAX_EMIT_SPANS = 64

    def __init__(self, enabled: bool = True, trace: bool = False,
                 clock=time.monotonic, record_spans: bool = False,
                 max_spans: int = 1024,
                 defer_warn_interval_s: float = 5.0,
                 slo_window: int = 64, steps_trace_len: int = 128,
                 phase_clock=time.perf_counter_ns):
        self.enabled = enabled
        self.trace = trace
        self.clock = clock
        # nanosecond clock of the boundary phases (injectable like ``clock``)
        self.phase_clock = phase_clock
        self.record_spans = record_spans
        self.spans: deque = deque(maxlen=max_spans)
        self.defer_warn_interval_s = defer_warn_interval_s
        # sliding-window sample counts for the LIVE SLO signal (slo_view):
        # the cumulative histograms never forget a good warm-up, so the
        # admission control loop reads a recent-window p90 instead
        self.slo_window = slo_window
        self.steps_trace_len = steps_trace_len
        self.monitor = None
        self.monitor_every = 1
        # constant identity labels (engine=..., model=...) merged into
        # EVERY exported ds_serving_* series — the router's per-replica
        # metric identity. Lives OUTSIDE reset(): identity outlives serve
        # runs. Empty (the default) keeps the exposition byte-identical.
        self.base_labels: Dict[str, str] = {}
        # distributed tracing (tracing.TraceCollector): like base_labels,
        # identity/wiring that outlives serve runs. None (the default)
        # keeps every hook's fast path unchanged.
        self.tracer = None
        self.trace_replica: Optional[str] = None
        # monitor step: monotonic across serve() runs (reset() zeroes the
        # per-serve frame counter, but an attached TensorBoard/CSV writer
        # must never see its step axis jump back to zero)
        self.lifetime_frames = 0
        self.reset()

    # ------------------------------------------------------------------
    # lifecycle of the subsystem itself
    # ------------------------------------------------------------------

    def reset(self) -> None:
        """Zero every counter, histogram, and open span (new serve() run)."""
        self._gamma = 0
        self._kv_block_bytes = 0
        self._phase_stack: List[list] = []
        # the serve loop's thread totals in the compile log when its first
        # phase began (None until then): programs_requested counts from it
        self._compile_base = None
        self.counters: Dict[str, int] = {n: 0 for n in STAT_NAMES}
        # work the frames did and computed: the positions their per-token
        # layers ran (a device lane), the steps that packed their live
        # tokens (labeled by the rung's size), and the attention's reads
        # and pairs by frame width
        self.counters["positions_computed"] = 0
        self.counters["rung_steps"] = 0
        for n in SPLIT_STAT_NAMES:
            self.counters[f"{n}_narrow"] = 0
            self.counters[f"{n}_wide"] = 0
        for n in TILE_STAT_NAMES + MOE_STAT_NAMES:
            self.counters[n] = 0
        # exclusive host time of each boundary phase (phase())
        for n in PHASES:
            self.counters[f"host_{n}_ns"] = 0
        # every program the serve loop's thread asked XLA for since its
        # first phase (frame programs AND the small ones admission
        # dispatches), and the time it waited for them
        self.counters.update(programs_requested=0, compile_wait_ns=0)
        # time to first token by stage, of the first tokens this replica
        # emitted: mirrored from the tracer (_sync_ttft), which counts for
        # its own lifetime, so from what it held when this run began
        self.counters.update(dict.fromkeys(TTFT_COUNTERS, 0))
        self._ttft_base = self._tracer_ttft()
        # (width, steps) of the frame that ended before this boundary's
        # poll; (0, 0) after a boundary that dispatched none
        self.last_frame = (0, 0)
        self.counters.update(requests_enqueued=0, requests_admitted=0,
                             requests_retired=0, admission_deferrals=0,
                             requests_shed=0, requests_preempted=0,
                             frames=0, slot_steps_capacity=0,
                             # steps RUN, by the host's plan (a frame's
                             # ``n_steps``): in all frames, and in the wide
                             # ones, which end with their last prefilling row
                             frame_steps=0, wide_steps=0,
                             # fault-tolerance surface (faults.py): total
                             # faults (kind-labeled), plus the per-kind
                             # headline counters the SLO dashboard plots
                             faults=0, quarantined=0, deadline_expired=0,
                             cancelled=0, nonfinite_repaired=0,
                             recoveries=0, frame_retries=0, slow_frames=0,
                             # KV memory hierarchy (kv_hierarchy.py):
                             # prefix-cache hit/publish/COW traffic and
                             # swap-tier page movement, exported as the
                             # ds_serving_prefix_* / ds_serving_kv_swap_*
                             # metric families
                             prefix_lookups=0, prefix_hits=0,
                             prefix_hit_tokens=0, prefix_blocks_published=0,
                             prefix_cow_copies=0, prefix_blocks_evicted=0,
                             prefix_blocks_swapped_out=0,
                             prefix_blocks_swapped_in=0,
                             kv_swap_out_requests=0, kv_swap_out_blocks=0,
                             kv_swap_in_requests=0, kv_swap_in_blocks=0,
                             # bytes moved over the swap tier in EITHER
                             # direction, at the pool's resident
                             # representation (quantized pools move their
                             # int8+scale pages, so an int8 engine's swap
                             # traffic reads ~2.7x smaller than f32 for
                             # the same block counts)
                             kv_swap_bytes=0,
                             kv_swap_resume_restores=0,
                             # disaggregated prefill/decode fleet
                             # (router.py roles): requests handed off to a
                             # decode replica after this engine finished
                             # their prefill, admissions served from the
                             # shared tier's content-addressed prefix
                             # records, and async swap-out commit modes
                             # (overlapped with the next frame vs forced
                             # blocking at a lookup)
                             handoffs_out=0, handoffs_pipelined=0,
                             tier_prefix_hits=0,
                             tier_prefix_hit_tokens=0,
                             kv_swap_commits_overlapped=0,
                             kv_swap_commits_blocking=0)
        self.gauges: Dict[str, float] = {
            "live_slots": 0, "slot_count": 0, "queue_depth": 0,
            "kv_blocks_in_use": 0, "kv_blocks_in_use_peak": 0,
            "kv_blocks_total": 0, "kv_resident_bytes": 0,
            "occupancy": 0.0, "recompiled_programs": 0,
            "slo_risk": 0.0, "frame_steps_chosen": 0,
            "last_recovery_ms": 0.0, "tp_degree": 1,
            "prefix_blocks_resident": 0, "prefix_hit_rate": 0.0,
        }
        self.hists: Dict[str, LogBucketHistogram] = {
            n: LogBucketHistogram() for n in self.HIST_NAMES}
        # scheduler label surfaces: {metric: {((label, value), ...): count}}
        # — cardinality is classes x tenants, bounded by the tenant set
        self.labeled: Dict[str, Dict[tuple, int]] = {}
        # gauges by cache kind, of a model of mixed kinds alone:
        # {gauge: {kind: value}}, beside the gauge of the same name (which
        # stays the table kind's pool with its trash page)
        self.kind_gauges: Dict[str, Dict[str, int]] = {}
        self._tail_names, self._share, self._mtp = (), False, False
        self._recurrent_bytes = 0       # a live slot's, a model with any
        self._recurrent_stats = ()      # and the names of its last lanes
        self._block = 0                 # a block's positions, such a model
        # per-class TTFT (the bench/SLO acceptance surface)
        self.class_ttft: Dict[str, LogBucketHistogram] = {}
        # live SLO signal windows (recent samples, seconds)
        self._win: Dict[str, deque] = {
            "ttft": deque(maxlen=self.slo_window),
            "queue_wait": deque(maxlen=self.slo_window)}
        # adaptive-frame-steps decision trace (ROADMAP follow-up (d)): a
        # bounded ring of {frame, ewma, saturated, steps} records so
        # frame-size oscillation is debuggable from serve_stats or a scrape
        self.steps_trace: deque = deque(maxlen=self.steps_trace_len)
        self._open_spans: Dict[int, _Span] = {}
        self._last_defer_warn: Optional[float] = None
        self._defers_since_warn = 0
        # serve_stats read-through view (engine.serve_stats returns this)
        self.serve_view: Dict = {
            "frames": 0, "frame_steps_last": None, "frame_steps_hist": {},
            "frame_steps_trace": self.steps_trace,
            "arrival_ewma": 0.0, "adaptive_frame_steps": False,
            "slo": {"ttft_p90_ms": None, "queue_wait_p90_ms": None},
            "spec": {"gamma": 0, "target_forwards": 0, "emitted_tokens": 0,
                     "accepted_drafts": 0, "acceptance_rate": None,
                     "tokens_per_target_forward": None},
            "telemetry_enabled": self.enabled,
        }

    def begin_serve(self, *, speculate: bool, gamma: int, adaptive: bool,
                    n_slots: int, kv_blocks_total: int,
                    tp_degree: int = 1, kv_block_bytes: int = 0,
                    layered: bool = False, latent: bool = False,
                    share: bool = False, mtp: bool = False,
                    recurrent_slot_bytes: int = 0,
                    recurrent_stats=RECURRENT_STAT_NAMES,
                    block: int = 0) -> None:
        """Called by ``serve()`` at generator construction.
        ``kv_block_bytes`` is the pool-resident footprint of one KV block
        across all layers (``BlockedKVCache.block_bytes``) — the
        multiplier that turns block counts into the byte-denominated
        swap/residency series (``ds_serving_kv_swap_bytes_total``,
        ``ds_serving_kv_resident_bytes``). ``layered``: the model keeps a
        cache a layer kind (``kv_cache.LayeredKVCache``) and its stat vector
        ends with LAYER_STAT_NAMES; only then do their counters, and the
        gauges and sums ``on_frame`` keeps by kind, exist. ``latent``: the
        model's attention is latent, its cache one pool of rows and its
        vector's last lanes LATENT_STAT_NAMES, with the same gauges and
        sums. ``share``: its router is wider than the experts it holds
        (SHARE_STAT_NAMES behind the experts' lanes). ``mtp``: the model
        has a prediction module, and its vector's very last lanes are
        MTP_STAT_NAMES. ``recurrent_slot_bytes`` (nonzero: the model has
        linear or conv layers, its very last lanes are ``recurrent_stats``
        (RECURRENT_STAT_NAMES for linear layers, CONV_STAT_NAMES for conv
        layers, both in that order for a stack with both) and ``layered``
        says how its full layers count): what a live slot holds of
        recurrent state and convolution tails (a conv layer's: the tail
        alone), for the gauge ``recurrent_bytes_in_use`` and its sum over
        frames. ``block``
        (nonzero: the model generates by diffusion over blocks of that many
        positions): its very last lanes are BLOCK_STAT_NAMES, and its
        narrow frames are two blocks wide."""
        self.reset()
        self._share, self._mtp = share, mtp
        self._recurrent_bytes = recurrent_slot_bytes
        self._recurrent_stats = tuple(recurrent_stats)
        self._block = block
        if block:
            assert not mtp and not recurrent_slot_bytes, \
                "each claims the vector's last lanes"
            self.counters.update(dict.fromkeys(BLOCK_STAT_NAMES, 0))
        for n in MTP_STAT_NAMES if mtp else ():
            self.counters[n] = 0
        if recurrent_slot_bytes:
            assert not mtp, "both claim the vector's last lanes"
            self.counters.update(dict.fromkeys(self._recurrent_stats, 0),
                                 recurrent_bytes_in_use_sum=0)
            self.gauges["recurrent_bytes_in_use"] = 0
        self._tail_names = (LAYER_STAT_NAMES if layered else
                            LATENT_STAT_NAMES if latent else ())
        for n in SHARE_STAT_NAMES if share else ():
            self.counters[n] = 0
        if self._tail_names:
            for n in self._tail_names:
                self.counters[f"{n}_narrow"] = 0
                self.counters[f"{n}_wide"] = 0
            # the gauges below, summed over frames: a window's mean is the
            # ratio of two deltas (kv_bytes_per_context_token)
            self.counters.update(kv_bytes_in_use_sum=0,
                                 context_tokens_reserved_sum=0)
            self.gauges.update(kv_bytes_in_use=0, context_tokens_reserved=0)
        self._gamma = gamma if speculate else 0
        self._kv_block_bytes = kv_block_bytes
        self.serve_view["adaptive_frame_steps"] = adaptive
        self.serve_view["spec"]["gamma"] = self._gamma
        self.gauges["slot_count"] = n_slots
        self.gauges["kv_blocks_total"] = kv_blocks_total
        self.gauges["tp_degree"] = tp_degree

    def attach_monitor(self, monitor, every_frames: int = 1) -> None:
        """Fan out frame-boundary events through ``monitor.write_events``
        (e.g. a ``MonitorMaster`` → TensorBoard/CSV/W&B) every
        ``every_frames`` frames. CSV writers open one file per tag per
        flush — raise ``every_frames`` for high-frame-rate serving."""
        self.monitor = monitor
        self.monitor_every = max(1, every_frames)

    def set_base_labels(self, **labels) -> None:
        """Attach constant identity labels (``engine=``, ``model=``) to
        every exported series — the per-replica identity a multi-engine
        router stamps on each engine's telemetry so one scrape
        distinguishes replicas. ``None`` values are dropped; calling with
        no arguments clears nothing (pass ``engine=None`` explicitly to
        unset a label)."""
        for k, v in labels.items():
            if v is None:
                self.base_labels.pop(k, None)
            else:
                self.base_labels[k] = str(v)

    def set_tracer(self, tracer, replica: Optional[str] = None) -> None:
        """Attach a ``tracing.TraceCollector`` (or None to detach):
        lifecycle hooks then emit frame-boundary-stamped spans into the
        fleet-wide trace each request carries (minting a trace locally
        when an arrival has none). ``replica`` labels this engine's spans
        — the router stamps its replica name, mirroring
        ``set_base_labels``. Requires ``enabled=True`` (the hooks that
        stamp spans are the host lifecycle hooks)."""
        self.tracer = tracer
        if replica is not None:
            self.trace_replica = replica
        self._ttft_base = self._tracer_ttft()

    def _tracer_ttft(self) -> Dict[str, int]:
        if self.tracer is None:
            return dict.fromkeys(TTFT_COUNTERS, 0)
        return self.tracer.ttft_totals(self.trace_replica)

    def _sync_ttft(self) -> None:
        """Mirror the tracer's TTFT_COUNTERS for this replica into
        ``counters``, as ``_sync_compiles`` mirrors the compile log: the
        last stamp of a request (the edge's first write) comes from
        another thread than the serve loop's, which owns ``counters``, so
        the tracer folds under its lock and the loop copies at a frame
        boundary. What an edge wrote during a frame shows after it."""
        if self.tracer is not None:
            for name, total in self._tracer_ttft().items():
                self.counters[name] = total - self._ttft_base[name]

    def on_idle_boundary(self) -> None:
        """A boundary with nothing live: no frame follows it."""
        if self.enabled:
            self.last_frame = (0, 0)
            self._sync_ttft()

    def _work_done(self) -> tuple:
        """(frames, steps) the loop has dispatched this run."""
        v = self.serve_view
        return v["frames"], sum(
            steps * n for steps, n in v["frame_steps_hist"].items())

    def _trace_span(self, span, name: str, t0: float, t1=None,
                    status: Optional[str] = None,
                    attrs: Optional[Dict] = None) -> None:
        """Emit one span for an open request into the attached tracer
        (no-op without one); parents to the trace root carried in the
        arrival so the cross-replica tree stays connected."""
        if self.tracer is None or span is None or span.trace is None:
            return
        a = {"uid": span.uid}
        if attrs:
            a.update(attrs)
        self.tracer.span(span.trace, name, t0, t1, parent=span.parent,
                         replica=self.trace_replica, status=status, attrs=a)

    def _labelstr(self, extra: str = "") -> str:
        """Render ``{...}`` merging the base identity labels with
        ``extra`` (a pre-rendered ``k="v",...`` fragment); empty when
        neither exists, so label-free telemetry keeps the historical
        exposition byte-for-byte."""
        base = ",".join(f'{k}="{v}"'
                        for k, v in sorted(self.base_labels.items()))
        both = ",".join(s for s in (base, extra) if s)
        return f"{{{both}}}" if both else ""

    # ------------------------------------------------------------------
    # request lifecycle (host side, called from serve())
    # ------------------------------------------------------------------

    def _labels(self, span: Optional[_Span]) -> Optional[tuple]:
        if span is None or (span.tenant is None and span.pclass is None):
            return None
        return (("class", span.pclass or "unknown"),
                ("tenant", span.tenant or "unknown"))

    def _inc_labeled(self, name: str, labels: Optional[tuple],
                     n: int = 1) -> None:
        if labels is None:
            return
        series = self.labeled.setdefault(name, {})
        series[labels] = series.get(labels, 0) + n

    def on_enqueue(self, uid: int, tenant: Optional[str] = None,
                   pclass: Optional[str] = None,
                   resumed: bool = False,
                   trace: Optional[Dict] = None,
                   prompt_tokens: int = 0) -> Optional[Dict]:
        """The serve loop's poll took ``uid`` from its arrivals. Where a
        router fed them, the wait since it placed the request there (the
        rest of the frame that was in flight) is the span ``engine.feed``,
        which says what frame that was; ``engine.queue`` begins here.

        ``trace`` is the distributed-trace context the arrival carried
        (``{"id", "parent"}``, minted at the edge/router); with a tracer
        attached and no context, a trace is minted HERE — a bare engine
        (tuple arrivals) still yields one connected tree per request.
        Returns the EFFECTIVE context so the engine can write a locally
        minted one back into its ledger — without that, a failover/
        handoff resume of a tuple arrival would start a second tree."""
        if not self.enabled:
            return trace
        self.counters["requests_enqueued"] += 1
        span = _Span(uid, self.clock(), tenant, pclass, resumed=resumed,
                     prompt_tokens=prompt_tokens)
        if self.tracer is not None:
            if not trace:
                tid, root = self.tracer.mint(
                    "engine.recv", replica=self.trace_replica,
                    t=span.enqueue_t, attrs={"uid": uid})
                trace = {"id": tid, "parent": root}
            span.trace = trace.get("id")
            span.parent = trace.get("parent")
            placed = self.tracer.placed_at(span.trace)
            if placed is not None:
                width, steps = self.last_frame
                self._trace_span(
                    span, "engine.feed", placed,
                    max(placed, span.enqueue_t),
                    attrs={"after_width": width, "after_steps": steps})
        self._open_spans[uid] = span
        return trace

    def on_admit(self, uid: int) -> None:
        if not self.enabled:
            return
        span = self._open_spans.get(uid)
        if span is None:
            return
        if span.admit_t is not None:
            # RE-admission after a preemption: the request was already
            # counted, and (now - enqueue_t) would log the row's live
            # generation time as queue wait — poisoning the windowed SLO
            # signal the scheduler sheds on. A request admits once.
            return
        span.admit_t = self.clock()
        span.work0 = self._work_done()
        self.counters["requests_admitted"] += 1
        wait = span.admit_t - span.enqueue_t
        self.hists["queue_wait"].record(wait)
        self._win["queue_wait"].append(wait)
        self._inc_labeled("requests_admitted", self._labels(span))
        self._trace_span(span, "engine.queue", span.enqueue_t,
                         span.admit_t)

    def on_emit(self, uid: int, n_tokens: int) -> None:
        """``n_tokens`` emitted to ``uid`` at this frame boundary."""
        if not self.enabled or n_tokens <= 0:
            return
        span = self._open_spans.get(uid)
        if span is None:
            return
        now = self.clock()
        if span.first_token_t is None:
            span.first_token_t = now
            if not span.resumed:
                ttft = now - span.enqueue_t
                self.hists["ttft"].record(ttft)
                self._win["ttft"].append(ttft)
                if span.pclass is not None:
                    self.class_ttft.setdefault(
                        span.pclass, LogBucketHistogram()).record(ttft)
            # first emission on THIS engine: the prefill (or, for a
            # resumed request, the restore + re-prefill) phase ends here.
            # The collector keys fleet TTFT by TRACE id — only the first
            # replica to emit records a sample, so a handed-off/failed-
            # over request gets exactly one true first-token time.
            frames, steps = (a - b for a, b in
                             zip(self._work_done(), span.work0))
            self._trace_span(
                span, "engine.restore" if span.resumed else
                "engine.prefill", span.admit_t or span.enqueue_t, now,
                attrs={"frames": frames, "steps": steps})
            stages = None
            if self.tracer is not None and span.trace is not None:
                stages = self.tracer.note_first_token(
                    span.trace, now, replica=self.trace_replica,
                    poll_t=span.enqueue_t, admit_t=span.admit_t,
                    frames=frames)
            if self.trace and not span.resumed:
                # the request's stages on the profiler's clock, beside
                # the frame that ended them; ``mono_ns`` is this instant
                # on the spans' clock, the offset between the two
                with jax.profiler.TraceAnnotation(
                        "serve/first_token", uid=span.uid,
                        prompt_tokens=span.prompt_tokens, frames=frames,
                        steps=steps, mono_ns=time.monotonic_ns(),
                        **{f"{k}_ns": v for k, v in (stages or {}).items()}):
                    pass
        else:
            gap = max(0.0, now - span.last_emit_t)
            self.hists["itl"].record(gap / n_tokens, count=n_tokens)
        # cap the per-frame emit instants per REQUEST: a long generation
        # would otherwise spend the trace's whole span budget on emit
        # markers and truncate the terminal spans (decode/handoff/
        # restore) that tracing exists to show — the decode span's
        # ``tokens`` attr carries the total anyway
        if span.emit_spans < self.MAX_EMIT_SPANS:
            span.emit_spans += 1
            self._trace_span(span, "emit", now, attrs={"n": n_tokens})
        span.last_emit_t = now
        span.tokens += n_tokens
        self._inc_labeled("tokens_emitted", self._labels(span), n_tokens)

    def on_retire(self, uid: int) -> None:
        if not self.enabled:
            return
        span = self._open_spans.pop(uid, None)
        if span is None:
            return
        now = self.clock()
        self.counters["requests_retired"] += 1
        self.hists["e2e"].record(now - span.enqueue_t)
        self._inc_labeled("requests_retired", self._labels(span))
        if span.first_token_t is not None:
            self._trace_span(span, "engine.decode", span.first_token_t,
                             now, attrs={"tokens": span.tokens})
        if self.tracer is not None and span.trace is not None:
            # the retiring replica ends the fleet-level request: one E2E
            # sample per trace id, and the root span closes "ok" (the
            # edge may still extend the root to cover its last SSE write)
            self.tracer.note_done(span.trace, now)
            self.tracer.finish(span.trace, now, status="ok")
        if self.record_spans:
            rec = {
                "uid": span.uid, "enqueue_t": span.enqueue_t,
                "admit_t": span.admit_t, "first_token_t": span.first_token_t,
                "retire_t": now, "tokens": span.tokens,
            }
            if span.tenant is not None or span.pclass is not None:
                rec["tenant"] = span.tenant     # scheduler runs only — the
                rec["pclass"] = span.pclass     # FIFO span shape is a golden
            self.spans.append(rec)

    def on_shed(self, uid: int, tenant: Optional[str] = None,
                pclass: Optional[str] = None,
                reason: Optional[str] = None) -> None:
        """The scheduler rejected ``uid`` (SLO pressure or tenant quota).

        Like ``on_defer``, deliberately NOT gated on ``enabled``: shedding
        is a client-visible overload action — losing its count is the
        failure mode telemetry exists to prevent."""
        self.counters["requests_shed"] += 1
        span = self._open_spans.pop(uid, None)
        if span is not None:
            self._inc_labeled("requests_shed", self._labels(span))
            if self.tracer is not None and span.trace is not None:
                # shed traces are ALWAYS sampled — overload rejections
                # are exactly what a uniform sampler would lose
                self.tracer.mark(span.trace, "shed")
                self.tracer.finish(span.trace, self.clock(),
                                   status=f"shed:{reason or 'unknown'}")
        elif tenant is not None or pclass is not None:
            self._inc_labeled("requests_shed",
                              (("class", pclass or "unknown"),
                               ("tenant", tenant or "unknown")))

    def on_preempt(self, uid: int, tenant: Optional[str] = None,
                   pclass: Optional[str] = None) -> None:
        """A live row was evicted back to the queue at a frame boundary to
        make room for an interactive arrival (span stays open — the
        request is still in flight and will re-admit)."""
        self.counters["requests_preempted"] += 1
        span = self._open_spans.get(uid)
        if span is not None:
            self._inc_labeled("requests_preempted", self._labels(span))
            self._trace_span(span, "preempt", self.clock())
        elif tenant is not None or pclass is not None:
            self._inc_labeled("requests_preempted",
                              (("class", pclass or "unknown"),
                               ("tenant", tenant or "unknown")))

    def on_fault(self, kind: str, uid: Optional[int] = None) -> None:
        """One fault event (``faults.FAULT_KINDS``). Like ``on_shed``/
        ``on_defer``, deliberately NOT gated on ``enabled``: a fault is a
        client-visible failure action, and losing its count is the failure
        mode telemetry exists to prevent. ``uid`` (for request-terminal
        kinds) closes the request's open span WITHOUT recording latency
        samples — a quarantined or timed-out request must not poison the
        TTFT/E2E histograms the SLO control loop reads."""
        self.counters["faults"] += 1
        self._inc_labeled("faults", (("kind", kind),))
        if kind == "poison_row":
            self.counters["quarantined"] += 1
        elif kind == "nonfinite_repaired":
            self.counters["nonfinite_repaired"] += 1
        elif kind == "deadline_expired":
            self.counters["deadline_expired"] += 1
        elif kind == "cancelled":
            self.counters["cancelled"] += 1
        elif kind == "dispatch_retry":
            self.counters["frame_retries"] += 1
        elif kind == "slow_frame":
            self.counters["slow_frames"] += 1
        if uid is not None:
            span = self._open_spans.pop(uid, None)
            if span is not None and self.tracer is not None \
                    and span.trace is not None:
                # faulted traces are ALWAYS sampled; a request-terminal
                # fault ends the fleet-level request (status = the kind)
                self.tracer.mark(span.trace,
                                 "cancelled" if kind == "cancelled"
                                 else "fault")
                # no note_done: faulted requests stay out of the fleet
                # E2E histogram, mirroring the per-replica semantics
                self.tracer.finish(span.trace, self.clock(), status=kind)

    def on_recover(self, n_requests: int, recovery_ms: float) -> None:
        """A ``serve(..., resume_from=)`` run re-admitted ``n_requests``
        snapshot requests; ``recovery_ms`` is resume-start → last
        re-admission (the window clients waited on the restarted engine)."""
        self.counters["recoveries"] += n_requests
        self.gauges["last_recovery_ms"] = round(recovery_ms, 3)

    # ------------------------------------------------------------------
    # KV memory hierarchy (prefix cache + swap tier) — perf counters,
    # gated on ``enabled`` like the frame counters (unlike shed/fault
    # events, a missed hit count is not a client-visible failure)
    # ------------------------------------------------------------------

    def on_prefix_lookup(self, hit_tokens: int, hit_blocks: int,
                         cow: bool) -> None:
        """One admission-time prefix-cache lookup; ``hit_tokens == 0`` is
        a miss. ``cow`` marks a mid-block hit that took a copy-on-write
        page copy."""
        if not self.enabled:
            return
        self.counters["prefix_lookups"] += 1
        if hit_tokens > 0:
            self.counters["prefix_hits"] += 1
            self.counters["prefix_hit_tokens"] += hit_tokens
        if cow:
            self.counters["prefix_cow_copies"] += 1
        self.gauges["prefix_hit_rate"] = round(
            self.counters["prefix_hits"]
            / max(1, self.counters["prefix_lookups"]), 4)

    def on_prefix_update(self, published: int, evicted: int,
                         swapped_out: int, swapped_in: int,
                         resident: int) -> None:
        """Frame-boundary prefix-cache bookkeeping delta."""
        if not self.enabled:
            return
        self.counters["prefix_blocks_published"] += published
        self.counters["prefix_blocks_evicted"] += evicted
        self.counters["prefix_blocks_swapped_out"] += swapped_out
        self.counters["prefix_blocks_swapped_in"] += swapped_in
        self.gauges["prefix_blocks_resident"] = resident

    def on_kv_swap_out(self, n_blocks: int, uid: Optional[int] = None,
                       publish: bool = False) -> None:
        """A request's committed pages left for the host tier — a
        preemption victim's swap-out, or (``publish=True``) a prefill
        replica's tier publish on the handoff path; ``uid`` stamps the
        tier I/O into the request's distributed trace."""
        if not self.enabled:
            return
        self.counters["kv_swap_out_requests"] += 1
        self.counters["kv_swap_out_blocks"] += n_blocks
        self.counters["kv_swap_bytes"] += n_blocks * self._kv_block_bytes
        if uid is not None:
            self._trace_span(self._open_spans.get(uid),
                             "tier.publish" if publish else "kv.swap_out",
                             self.clock(), attrs={"blocks": n_blocks})

    def on_kv_swap_in(self, n_blocks: int, resume: bool = False,
                      uid: Optional[int] = None) -> None:
        """A request re-admitted by restoring its swapped pages (instead
        of re-prefilling); ``resume`` marks the crash-recovery path.
        ``uid`` stamps the restore into the request's distributed trace —
        the decode-side restore span of a prefill→decode handoff."""
        if not self.enabled:
            return
        self.counters["kv_swap_in_requests"] += 1
        self.counters["kv_swap_in_blocks"] += n_blocks
        self.counters["kv_swap_bytes"] += n_blocks * self._kv_block_bytes
        if resume:
            self.counters["kv_swap_resume_restores"] += 1
        if uid is not None:
            self._trace_span(self._open_spans.get(uid), "kv.restore",
                             self.clock(),
                             attrs={"blocks": n_blocks, "resume": resume})

    def on_handoff_out(self, uid: int, pipelined: bool = False) -> None:
        """A prefill-role engine finished ``uid``'s prefill, published its
        pages to the shared tier, and handed the request to the router for
        decode placement. The span closes WITHOUT latency samples (the
        request is still in flight — its decode replica owns the rest of
        its lifecycle; the TTFT recorded at this engine's first emission
        already stands). ``pipelined`` marks a handoff whose final record
        segment was published during the first-token frame (engine
        ``handoff_pipeline``), so the handoff boundary did no page I/O."""
        if not self.enabled:
            return
        self.counters["handoffs_out"] += 1
        if pipelined:
            self.counters["handoffs_pipelined"] += 1
        span = self._open_spans.pop(uid, None)
        if span is not None and self.tracer is not None \
                and span.trace is not None:
            now = self.clock()
            self._trace_span(span, "engine.handoff",
                             span.first_token_t or span.admit_t
                             or span.enqueue_t, now, status="handoff",
                             attrs={"pipelined": pipelined,
                                    "tokens": span.tokens})
            # handed-off traces are ALWAYS sampled; the trace stays OPEN
            # — the decode replica owns the rest of its lifecycle and
            # finishes it at retire
            self.tracer.mark(span.trace, "handoff")

    def on_tier_prefix_hit(self, hit_tokens: int, n_blocks: int) -> None:
        """An admission restored a content-addressed prefix record from
        the shared tier (the fleet-wide prefix share)."""
        if not self.enabled:
            return
        self.counters["tier_prefix_hits"] += 1
        self.counters["tier_prefix_hit_tokens"] += hit_tokens
        self.counters["kv_swap_in_blocks"] += n_blocks

    def on_kv_swap_commits(self, overlapped: int = 0,
                           blocking: int = 0) -> None:
        """Swap-tier record commits since the last boundary, split by mode
        (overlapped = drained at a frame boundary after riding the aio
        queue through the previous frame; blocking = forced synchronous)."""
        if not self.enabled:
            return
        self.counters["kv_swap_commits_overlapped"] += overlapped
        self.counters["kv_swap_commits_blocking"] += blocking

    def slo_view(self) -> Dict[str, Optional[float]]:
        """LIVE SLO signal: p90 (ms) over the recent sample windows — the
        input the scheduler's control loop reads each frame boundary (the
        cumulative histograms would let a good warm-up mask a bad now).
        Mirrored into ``serve_view['slo']`` for observability.

        Thread-tolerant by retry: the threaded fleet driver's router
        thread scores replicas through here while each replica's worker
        thread appends samples — a snapshot that races an append raises
        RuntimeError ("deque mutated during iteration") and is simply
        retaken; after a few collisions the stale answer (None) degrades
        scoring gracefully instead of killing the caller."""
        out: Dict[str, Optional[float]] = {}
        for name in ("ttft", "queue_wait"):
            w = self._win[name]
            vals = None
            for _ in range(4):
                try:
                    vals = list(w)
                    break
                except RuntimeError:     # mutated mid-snapshot: retake
                    continue
            out[f"{name}_p90_ms"] = round(
                float(np.percentile(np.asarray(vals), 90)) * 1e3, 3) \
                if vals else None
        self.serve_view["slo"] = out
        return out

    def on_defer(self, queue_depth: int, frame_steps: Optional[int],
                 free_slots: int, free_blocks: int,
                 reserved_blocks: int = 0) -> None:
        """Admission deferred at least one arrival this frame boundary.

        Overload used to be invisible; this logs a structured warning,
        rate-limited to one per ``defer_warn_interval_s`` (with a count of
        suppressed events), and counts every occurrence. Deliberately NOT
        gated on ``enabled``: it fires at most once per overloaded frame
        boundary, and losing the overload signal is the exact failure mode
        this hook exists to fix — telemetry=False must not bring it back.

        ``free_blocks`` is the pool AFTER this round's admissions reserved
        their blocks; ``reserved_blocks`` is that round's reservation, so
        the warning can distinguish a pool that was already exhausted from
        one this very boundary just consumed (without it, a busy admission
        round reads as standing KV pressure)."""
        self.counters["admission_deferrals"] += 1
        self.gauges["queue_depth"] = queue_depth
        now = self.clock()
        self._defers_since_warn += 1
        if (self._last_defer_warn is not None
                and now - self._last_defer_warn < self.defer_warn_interval_s):
            return
        reason = "no free slots" if free_slots == 0 else \
            f"KV pool pressure ({free_blocks} blocks free)"
        logger.warning(
            f"serve(): admission deferred ({reason}); queue_depth="
            f"{queue_depth} frame_steps_bucket={frame_steps} "
            f"free_slots={free_slots} free_kv_blocks={free_blocks} "
            f"kv_blocks_reserved_this_round={reserved_blocks} "
            f"deferral_events_since_last_warning={self._defers_since_warn}")
        self._last_defer_warn = now
        self._defers_since_warn = 0

    def on_frame_plan(self, ewma: float, saturated: bool,
                      chosen: int) -> None:
        """Record one frame-size decision (EWMA input, saturated flag,
        chosen pow2 bucket) into the bounded ring surfaced as
        ``serve_stats['frame_steps_trace']`` and the
        ``ds_serving_frame_steps_chosen`` gauge. Always on (one dict append
        per frame): frame-size oscillation is exactly the thing that needs
        debugging when telemetry is otherwise being kept cheap."""
        self.steps_trace.append({
            "frame": self.serve_view["frames"], "ewma": round(ewma, 4),
            "saturated": bool(saturated), "steps": int(chosen)})
        self.gauges["frame_steps_chosen"] = int(chosen)

    # ------------------------------------------------------------------
    # frame boundary (device counter absorption + fan-out)
    # ------------------------------------------------------------------

    def phase(self, name: str):
        """Context manager around one boundary phase of the serve loop
        (``PHASES``): adds its exclusive elapsed ``phase_clock`` time to
        ``counters["host_<name>_ns"]`` whenever ``enabled``, and is a
        ``serve/<name>`` ``TraceAnnotation`` when ``trace`` is set — two
        clock reads and a dict add otherwise."""
        return _Phase(self, name)

    def _sync_compiles(self) -> None:
        """Programs the calling (serve-loop) thread asked XLA for since the
        loop's first phase — the ``recompiled_programs`` gauge used to see
        frame programs only, while admission asks for ~18 small ones per
        new batch size and every stream waits for them."""
        n, ns = compile_log().totals()
        if self._compile_base is None:
            self._compile_base = (n, ns)
        self.counters["programs_requested"] = n - self._compile_base[0]
        self.counters["compile_wait_ns"] = ns - self._compile_base[1]
        self.gauges["recompiled_programs"] = \
            self.counters["programs_requested"]

    def on_frame(self, *, delta: np.ndarray, width: int, steps: int,
                 live_slots: int, kv_blocks_in_use: int,
                 arrival_ewma: float, queue_depth: int,
                 kv_kinds=None) -> None:
        """Absorb one frame's device counter DELTA (``(n_stats,)`` int64)
        plus the host-known frame facts, update the serve_stats view, and
        fan out to the attached monitor. When telemetry is disabled the
        engine calls ``frame_view_update`` instead (so even the argument
        gathering is skipped); the guard here is defensive for other
        callers. ``kv_kinds`` (a model of mixed cache kinds):
        ``LayeredKVCache.in_use()`` at this boundary."""
        if not self.enabled:
            self.frame_view_update(width, steps, arrival_ewma)
            return
        for i, name in enumerate(STAT_NAMES):
            self.counters[name] += int(delta[i])
        # the vector's very last lanes: the prediction module's, or the
        # linear layers' (a model has one or the other)
        last = {}
        last_names = (MTP_STAT_NAMES if self._mtp else
                      self._recurrent_stats if self._recurrent_bytes else
                      BLOCK_STAT_NAMES if self._block else ())
        if last_names:
            delta, tail = np.split(delta, [len(delta) - len(last_names)])
            last = dict(zip(last_names, map(int, tail)))
            for name, value in last.items():
                self.counters[name] += value
        if self._recurrent_bytes:
            # a live slot holds its states whatever its context
            nbytes = live_slots * self._recurrent_bytes
            self.gauges["recurrent_bytes_in_use"] = nbytes
            self.counters["recurrent_bytes_in_use_sum"] += nbytes
        # a model of mixed cache kinds ends its vector with the layered
        # work, one with latent attention with the latent rows' work
        layers = {}
        if self._tail_names:
            delta, tail = np.split(delta, [len(delta) - len(self._tail_names)])
            layers = dict(zip(self._tail_names, map(int, tail)))
        # a dense model's vector ends with the rung lanes
        moe_names = MOE_STAT_NAMES + MOVED_STAT_NAMES \
            + (SHARE_STAT_NAMES if self._share else ())
        # (its counters read 0; MOVED_STAT_NAMES' exists once a model with
        # routed experts has run a frame)
        moe = dict.fromkeys(MOE_STAT_NAMES, 0)
        moe.update(zip(moe_names, map(int, delta[STAT_EXPERT_ROWS:])))
        for name, value in moe.items():
            self.counters[name] = self.counters.get(name, 0) + value
        if self.trace:
            # this frame's work on the profiler's clock, right after its
            # serve_frame span: a reduction of the trace matches work to
            # device time frame by frame, with no second clock
            with jax.profiler.TraceAnnotation(
                    "serve/frame_work", width=width, steps=steps,
                    **{n: int(delta[i]) for i, n in
                       enumerate(STAT_NAMES + SPLIT_STAT_NAMES
                                 + TILE_STAT_NAMES)}, **moe,
                    **layers, **last):
                pass
        # (a block-diffusion model's narrow frames are two blocks wide)
        wide = width > max(1, 2 * self._block)
        split = "wide" if wide else "narrow"
        for i, name in enumerate(SPLIT_STAT_NAMES, len(STAT_NAMES)):
            self.counters[f"{name}_{split}"] += int(delta[i])
        for name, value in layers.items():
            self.counters[f"{name}_{split}"] += value
        for i, name in enumerate(TILE_STAT_NAMES, STAT_ROW_TILES):
            self.counters[name] += int(delta[i])
        if kv_kinds is not None:
            # pages by kind, their bytes over the kinds, and the tokens the
            # table kind's pages hold
            rows, reserved = kv_kinds
            peaks = self.kind_gauges.setdefault("kv_blocks_in_use_peak", {})
            self.kind_gauges["kv_blocks_in_use"] = {
                kind: pages for kind, pages, _ in rows}
            for kind, pages, _ in rows:
                peaks[kind] = max(peaks.get(kind, 0), pages)
            nbytes = sum(pages * page_bytes for _, pages, page_bytes in rows)
            self.gauges["kv_bytes_in_use"] = nbytes
            self.gauges["context_tokens_reserved"] = reserved
            self.counters["kv_bytes_in_use_sum"] += nbytes
            self.counters["context_tokens_reserved_sum"] += reserved
        # what the frame's per-token layers ran, as the chip counted it:
        # the rung each step chose for its live tokens, and how many steps
        # ran at each rung of the frame's ladder (a speculative decode step
        # verifies gamma + 1 positions a row)
        self.counters["positions_computed"] += int(delta[STAT_POSITIONS])
        ladder = pack_ladder(
            int(self.gauges["slot_count"]),
            self._gamma + 1 if width == 1 and self._gamma else width)
        for tokens, n in zip(ladder, delta[STAT_RUNG0:N_STATS]):
            if n:
                self.counters["rung_steps"] += int(n)
                self._inc_labeled("rung_steps", (("tokens", str(tokens)),),
                                  int(n))
        self.counters["frames"] += 1
        self.counters["frame_steps"] += steps
        if wide:
            self.counters["wide_steps"] += steps
        self.lifetime_frames += 1
        # run-average occupancy = active_row_steps / slot_steps_capacity
        # (the gauge below is the LAST frame's figure — drain frames sit
        # near zero, so averages must come from the counters)
        self.counters["slot_steps_capacity"] += \
            int(self.gauges["slot_count"]) * steps
        self.gauges["live_slots"] = live_slots
        self.gauges["kv_blocks_in_use"] = kv_blocks_in_use
        # byte-denominated residency: block counts x the pool-resident
        # block footprint, so an int8-KV engine's HBM pressure reads
        # directly against an f32 engine's on the same dashboard panel
        self.gauges["kv_resident_bytes"] = \
            kv_blocks_in_use * self._kv_block_bytes
        # instantaneous gauges go stale on the drain frames at the end of a
        # run — the peak is the run-level KV-pressure figure
        self.gauges["kv_blocks_in_use_peak"] = max(
            self.gauges["kv_blocks_in_use_peak"], kv_blocks_in_use)
        self.gauges["queue_depth"] = queue_depth
        self._sync_compiles()
        self._sync_ttft()
        if self.gauges["slot_count"]:
            self.gauges["occupancy"] = round(
                int(delta[STAT_ACTIVE_STEPS])
                / (self.gauges["slot_count"] * steps), 4)
        self.frame_view_update(width, steps, arrival_ewma)
        sp = self.serve_view["spec"]
        if self._gamma:
            sp["target_forwards"] = self.counters["target_forwards"]
            # tokens emitted BY SPECULATIVE STEPS (the historical
            # serve_stats semantics): every verify forward emits its column
            # 0, plus the accepted drafts — prefill-completion tokens from
            # wide frames are counted in tokens_emitted but not here
            sp["emitted_tokens"] = (self.counters["target_forwards"]
                                    + self.counters["accepted_draft_tokens"])
            sp["accepted_drafts"] = self.counters["accepted_draft_tokens"]
            if sp["target_forwards"]:
                sp["acceptance_rate"] = round(
                    sp["accepted_drafts"]
                    / (self._gamma * sp["target_forwards"]), 4)
                sp["tokens_per_target_forward"] = round(
                    sp["emitted_tokens"] / sp["target_forwards"], 4)
        if (self.monitor is not None
                and self.counters["frames"] % self.monitor_every == 0):
            self.monitor.write_events(self.monitor_events())

    def frame_view_update(self, width: int, steps: int,
                          arrival_ewma: float) -> None:
        """The cheap host bookkeeping the pre-telemetry serve_stats always
        had (frame count, frame-steps histogram, arrival EWMA) — the only
        per-frame work that runs when telemetry is disabled."""
        v = self.serve_view
        v["telemetry_enabled"] = self.enabled   # stays live across toggles
        self.last_frame = (width, steps)
        v["frames"] += 1
        v["frame_steps_last"] = steps
        v["frame_steps_hist"][steps] = v["frame_steps_hist"].get(steps, 0) + 1
        v["arrival_ewma"] = round(arrival_ewma, 4)

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------

    def snapshot(self) -> Dict:
        """Everything, as plain python (JSON-serializable)."""
        out = {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "kind_gauges": {n: dict(v) for n, v in self.kind_gauges.items()},
            "histograms": {n: h.summary() for n, h in self.hists.items()},
            "spec": dict(self.serve_view["spec"]),
            "labeled": {
                name: {",".join(f"{k}={v}" for k, v in key): val
                       for key, val in series.items()}
                for name, series in self.labeled.items()},
            "class_ttft_p90_ms": {
                cls: (round(h.percentile(90) * 1e3, 3)
                      if h.percentile(90) is not None else None)
                for cls, h in self.class_ttft.items()},
            "slo": dict(self.serve_view["slo"]),
            "frame_steps_trace": list(self.steps_trace),
        }
        # tokens_per_target_forward lives ONLY in out["spec"] (computed from
        # verify forwards + accepted drafts) — dividing total tokens_emitted
        # by target_forwards would silently mix in prefill-completion
        # emissions that no decode/verify forward produced
        cap = self.counters["slot_steps_capacity"]
        out["derived"] = {
            "spec_acceptance_rate": self.serve_view["spec"]["acceptance_rate"],
            "occupancy_avg": round(
                self.counters["active_row_steps"] / cap, 4) if cap else None,
        }
        return out

    def latency_ms(self) -> Dict[str, Dict]:
        """p50/p90/p99 per histogram in milliseconds (None when empty) —
        the shape serving_bench.py embeds in its JSON rows."""
        out = {}
        for n, h in self.hists.items():
            s = h.summary()
            out[n] = {
                "count": s["count"],
                **{p: (round(s[p] * 1e3, 3) if s[p] is not None else None)
                   for p in ("p50", "p90", "p99")},
            }
        return out

    def monitor_events(self) -> List:
        """Frame-boundary event batch for ``Monitor.write_events``; the
        step axis is ``lifetime_frames``, monotonic across serve() runs."""
        step = self.lifetime_frames
        ev = [(f"serving/{n}", float(v), step)
              for n, v in self.counters.items()]
        ev += [(f"serving/{n}", float(v), step)
               for n, v in self.gauges.items()]
        for n, h in self.hists.items():
            for p in ("p50", "p90", "p99"):
                q = h.percentile(float(p[1:]))
                if q is not None:
                    ev.append((f"serving/{n}_{p}_ms", q * 1e3, step))
        return ev

    def render_prometheus(self) -> str:
        """Prometheus text exposition snapshot (version 0.0.4).

        Counters render as ``counter``, gauges as ``gauge``, and each
        latency histogram as a full ``histogram`` (cumulative ``le``
        buckets + ``_sum``/``_count``) with p50/p90/p99 beside it as a
        ``summary``-style quantile series. Serve behind any HTTP handler::

            from http.server import BaseHTTPRequestHandler, HTTPServer
            class H(BaseHTTPRequestHandler):
                def do_GET(self):
                    body = engine.telemetry.render_prometheus().encode()
                    self.send_response(200); self.end_headers()
                    self.wfile.write(body)
        """
        lines: List[str] = []

        def fmt(v: float) -> str:
            f = float(v)
            return str(int(f)) if f == int(f) else repr(f)

        lb = self._labelstr
        for name, val in self.counters.items():
            full = f"ds_serving_{name}_total"
            lines.append(f"# TYPE {full} counter")
            lines.append(f"{full}{lb()} {fmt(val)}")
            # per-class/per-tenant scheduler labels share the family: one
            # TYPE line, unlabeled total first, labeled samples after
            for key, lval in sorted(self.labeled.get(name, {}).items()):
                labels = ",".join(f'{k}="{v}"' for k, v in key)
                lines.append(f"{full}{lb(labels)} {fmt(lval)}")
        for name, val in self.gauges.items():
            full = f"ds_serving_{name}"
            lines.append(f"# TYPE {full} gauge")
            lines.append(f"{full}{lb()} {fmt(val)}")
            for kind, kval in sorted(self.kind_gauges.get(name, {}).items()):
                extra = f'kind="{kind}"'
                lines.append(f"{full}{lb(extra)} {fmt(kval)}")
        if self.class_ttft:
            full = "ds_serving_class_ttft_p90_seconds"
            lines.append(f"# TYPE {full} gauge")
            for cls in sorted(self.class_ttft):
                q = self.class_ttft[cls].percentile(90)
                if q is not None:
                    extra = f'class="{cls}"'
                    lines.append(f"{full}{lb(extra)} {q:g}")
        ar = self.serve_view["spec"]["acceptance_rate"]
        lines.append("# TYPE ds_serving_spec_acceptance_rate gauge")
        lines.append(f"ds_serving_spec_acceptance_rate{lb()} "
                     f"{fmt(ar) if ar is not None else 'NaN'}")
        for name, h in self.hists.items():
            full = f"ds_serving_{name}_seconds"
            lines.append(f"# TYPE {full} histogram")
            cum = 0
            for bound, cnt in zip(h.bounds, h.counts[:-1]):
                cum += int(cnt)
                extra = f'le="{bound:g}"'
                lines.append(f"{full}_bucket{lb(extra)} {cum}")
            extra = 'le="+Inf"'
            lines.append(f"{full}_bucket{lb(extra)} {h.total}")
            lines.append(f"{full}_sum{lb()} {h.sum:g}")
            lines.append(f"{full}_count{lb()} {h.total}")
            for p in (50, 90, 99):
                q = h.percentile(p)
                if q is not None:
                    extra = f'quantile="0.{p}"'
                    lines.append(f"{full}_quantile{lb(extra)} {q:g}")
        return "\n".join(lines) + "\n"

    def serve_metrics_http(self, port: int = 0, host: str = "127.0.0.1"):
        """Serve ``render_prometheus()`` at ``/metrics`` from a stdlib
        ``http.server`` daemon thread — the zero-dependency scrape endpoint
        (ROADMAP telemetry follow-up (c))::

            srv = engine.telemetry.serve_metrics_http(9100)
            print(srv.metrics_port)      # bound port (pass 0 for ephemeral)
            ...
            srv.shutdown(); srv.server_close()

        Returns the ``ThreadingHTTPServer``; each GET renders a fresh
        snapshot, so a Prometheus scrape always sees the latest frame
        boundary. Anything but ``/metrics`` (or ``/``) answers 404."""
        import http.server
        import threading

        tel = self

        class _MetricsHandler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path.split("?")[0].rstrip("/") in ("", "/metrics"):
                    body = tel.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_error(404)

            def log_message(self, fmt, *args):   # scrapes are not log spam
                pass

        srv = http.server.ThreadingHTTPServer((host, port), _MetricsHandler)
        srv.daemon_threads = True
        srv.metrics_port = srv.server_address[1]
        thread = threading.Thread(target=srv.serve_forever,
                                  name="ds-serving-metrics", daemon=True)
        thread.start()
        return srv

    # ------------------------------------------------------------------
    # jax.profiler alignment
    # ------------------------------------------------------------------

    def frame_trace(self, width: int, steps: int):
        """Context manager wrapping one frame in a named
        ``jax.profiler.TraceAnnotation`` (opt-in via ``trace=True``), so a
        captured device profile (``jax.profiler.trace(logdir)`` around a
        serving run) shows frames as named spans that line up with the
        request lifecycle timestamps recorded here."""
        if not self.trace:
            return nullcontext()
        return jax.profiler.TraceAnnotation(f"serve_frame/w{width}/s{steps}")
