"""SimEngine: the virtual-time twin of ``InferenceEngineV2.serve()``.

Presents the exact engine surface the fleet layer consumes — ``serve()``
as a cooperatively-steppable generator yielding ``(uid, tokens)`` /
``HandoffEvent`` / ``ServeBoundary``, plus ``_config`` / ``telemetry`` /
``kv`` / ``_ledger`` / ``snapshot_serving_state`` / drain-and-role hooks
— while executing NO frames: a "frame" advances per-row token counters
deterministically and charges virtual seconds from the committed cost
baseline (``sim.cost.FrameCostModel``).

Everything that IS policy stays the production object: the
``RequestScheduler`` passed by the router's ``scheduler_factory`` runs
verbatim (submit quotas, SLO sheds, aging, fair share, preemption,
admission, frame-steps caps), the ``ServingTelemetry`` is the real class
on the virtual clock (so TTFT/ITL percentiles come out of the same
histograms the live fleet exports), and the per-boundary sequence below
mirrors ``engine_v2._serve_loop`` (the one serve loop, here always under
a ``RequestScheduler``) stage for stage — arrival poll,
deadline expiry, ``on_boundary`` control pass, preemption, admission,
idle/exhausted handling, frame plan, emissions, retirement, handoffs,
boundary event. Arrival normalization reuses the real
``InferenceEngineV2._norm_arrival`` staticmethod.

Time: the engine keeps a replica-LOCAL timeline ``local_t`` and seeks
the shared :class:`~.clock.VirtualClock` to it whenever it runs, so
every timestamp the real policy objects read (ledger deadlines,
ShedReason.t, telemetry spans, ``ServeBoundary.t``) is replica-local
virtual time. The fleet driver (``sim.sim``) gates arrival delivery on
``min(local_t)`` across replicas and fast-forwards idle engines.
"""

import dataclasses
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from ..engine_v2 import (HandoffEvent, InferenceEngineV2,
                         RaggedInferenceEngineConfig, ServeBoundary)
from ..faults import FaultReason, LedgerEntry, snapshot_ledger
from ..telemetry import (N_STATS, STAT_ACCEPTED, STAT_ACTIVE_STEPS,
                         STAT_DRAFTED, STAT_EMITTED, STAT_EOS,
                         STAT_POSITIONS, STAT_PREFILL_TOKS, STAT_TARGET_FWD,
                         ServingTelemetry)
from .clock import VirtualClock
from .cost import FrameCostModel

_VOCAB = 32000


def synth_token(uid: int, k: int) -> int:
    """Deterministic synthetic token value for generated token ``k`` of
    request ``uid`` (never 0/1 — those are common pad/eos ids)."""
    return ((uid * 1009 + k * 31 + 7) % (_VOCAB - 2)) + 2


class _SimSeq:
    """Host-side descriptor mirror (``state.seqs`` entry): just enough
    for ``faults.snapshot_ledger`` and the serve-loop bookkeeping."""
    __slots__ = ("uid", "generated", "done", "blocks", "seen_tokens")

    def __init__(self, uid: int):
        self.uid = uid
        self.generated: List[int] = []
        self.done = False
        self.blocks = 0          # reserved KV blocks (count, not ids)
        self.seen_tokens = 0

    def get(self, key, default=None):   # snapshot_ledger duck-typing aid
        return getattr(self, key, default)


class _SimState:
    """``engine.state`` twin: descriptor map + KV release on flush."""

    def __init__(self, kv: "_SimKV"):
        self.seqs: Dict[int, _SimSeq] = {}
        self._kv = kv

    def get_or_create_sequence(self, uid: int) -> _SimSeq:
        seq = self.seqs.get(uid)
        if seq is None:
            seq = self.seqs[uid] = _SimSeq(uid)
        return seq

    def flush_sequence(self, uid: int) -> None:
        seq = self.seqs.pop(uid, None)
        if seq is not None and seq.blocks:
            self._kv.release(seq.blocks)
            seq.blocks = 0


class _SimKV:
    """Paged-pool accounting twin (``engine.kv``): block arithmetic and
    a free-block counter — the numbers admission control runs on."""

    def __init__(self, num_blocks: int, block_size: int,
                 block_bytes: int = 0):
        self.num_blocks = int(num_blocks)
        self.free_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.block_bytes = int(block_bytes)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)

    def reserve(self, n: int) -> bool:
        if n > self.free_blocks:
            return False
        self.free_blocks -= n
        return True

    def release(self, n: int) -> None:
        self.free_blocks = min(self.num_blocks, self.free_blocks + n)


class SimSwapTier:
    """Shared KV swap-tier twin for disaggregated sim fleets.

    Stores WATERMARKS, not pages: a handoff/preemption record maps uid ->
    committed token count, and re-admission turns it into a ``cached0``
    prefill skip. Satisfies the ``EngineRouter`` ctor's shared-tier
    validation (one instance, ``shared=True``) and the autoscaler's
    tier-identity checks."""

    shared = True

    def __init__(self):
        self.records: Dict[int, Dict] = {}
        self.stats: Dict[str, int] = {"requests": 0, "handoffs": 0}
        self.flight = None          # router.attach_tracing assigns this

    # -- engine-side surface -----------------------------------------
    def put_request(self, uid: int, watermark: int, kv=None, blocks=None,
                    **kw) -> None:
        self.records[uid] = {"watermark": int(watermark)}
        self.stats["requests"] += 1

    def stamp_request_handoff(self, uid: int, meta: Dict) -> bool:
        rec = self.records.setdefault(uid, {"watermark": 0})
        rec.update(meta)
        self.stats["handoffs"] += 1
        return True

    def request_record(self, uid: int) -> Optional[Dict]:
        return self.records.get(uid)

    def drop_request(self, uid: int) -> None:
        self.records.pop(uid, None)

    def prune_requests(self, keep) -> None:
        pass                        # shared tier: router owns lifecycle


@dataclasses.dataclass
class _SimRow:
    """One live slot: the per-row counters a virtual frame advances."""
    uid: int
    plen: int                  # folded prompt length (tokens to commit)
    limit: int                 # REMAINING generation budget
    temp: float
    eos: Optional[int]
    cached: int                # committed tokens (prefill watermark)
    start: int                 # ``cached`` at admission
    gen_base: int              # seq.generated entries predating admission


class SimEngine:
    """See module docstring. One instance per simulated replica."""

    def __init__(self, *, config: Optional[RaggedInferenceEngineConfig]
                 = None, clock: Optional[VirtualClock] = None,
                 cost_model: Optional[FrameCostModel] = None,
                 max_seq_len: int = 4096, num_layers: int = 16,
                 sink: Optional[Callable] = None,
                 spec_acceptance: float = 0.7,
                 idle_poll_s: float = 0.002,
                 kv_swap=None, name: str = ""):
        self._config = config or RaggedInferenceEngineConfig()
        self._clock = clock or VirtualClock()
        self.cost = cost_model or FrameCostModel()
        self.max_seq_len = int(max_seq_len)
        self.model = SimpleNamespace(
            cfg=SimpleNamespace(num_layers=num_layers))
        self.name = name
        self.local_t = float(self._clock())
        self.sink = sink
        self.spec_acceptance = float(spec_acceptance)
        self.idle_poll_s = float(idle_poll_s)
        c = self._config
        n_blocks = c.num_kv_blocks
        if n_blocks is None and c.expected_context and \
                c.expected_concurrency:
            per = -(-(c.expected_context) // c.kv_block_size)
            n_blocks = per * c.expected_concurrency
        if n_blocks is None:
            n_blocks = c.max_ragged_batch_size * \
                (-(-self.max_seq_len // c.kv_block_size))
        self.kv = _SimKV(n_blocks, c.kv_block_size)
        self.state = _SimState(self.kv)
        self.telemetry = ServingTelemetry(enabled=c.telemetry,
                                          clock=self._clock)
        self.kv_swap = kv_swap
        self.last_crash_snapshot = None
        self.fault_log: List[FaultReason] = []
        self._ledger: Dict[int, LedgerEntry] = {}
        self._draining = False
        self._rows: Dict[int, _SimRow] = {}
        # per-engine prefix-cache model: recently published prompt token
        # tuples; admission skips the longest block-aligned common prefix
        self._prefix_store: List[tuple] = []
        self._prefix_blocks = 0
        # frames_executed x steps — the sim's work ledger (and the proof
        # surface that NO real frames ran: serving code asserts on this)
        self.virtual_frames = 0
        self.virtual_steps = 0

    # ------------------------------------------------------------------
    # engine surface the fleet layer calls outside serve()
    # ------------------------------------------------------------------

    def attach_kv_tier(self, tier, tag: Optional[str] = None) -> None:
        self.kv_swap = tier

    def begin_drain(self) -> None:
        self._draining = True

    def end_drain(self) -> None:
        self._draining = False

    def set_role(self, role: str) -> None:
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role={role!r}: expected 'unified', "
                             "'prefill' or 'decode'")
        if role == "prefill" and self.kv_swap is None:
            raise ValueError("set_role('prefill') needs a KV swap tier")
        self._config.role = role

    def cancel_request(self, uid: int) -> bool:
        ent = self._ledger.get(uid)
        if ent is None:
            return False
        ent.cancelled = True
        ent.deadline_at = self._clock()
        return True

    def snapshot_serving_state(self) -> Dict:
        return snapshot_ledger(self._ledger, self.state.seqs, self._clock,
                               swap_tier=self.kv_swap)

    def serve_stats(self) -> Dict:
        return self.telemetry.serve_view

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _emit_event(self, kind: str, uid=None, **kw) -> None:
        if self.sink is not None:
            self.sink(kind, uid=uid, t=self.local_t, engine=self.name,
                      **kw)

    def _validate_arrival(self, uid, toks, limit, in_flight: bool) -> int:
        if uid < 0:
            raise ValueError(f"uid={uid}: serve() uids must be >= 0")
        if in_flight or uid in self.state.seqs:
            raise ValueError(f"uid={uid} is already in flight")
        if len(toks) + 2 > self.max_seq_len:
            raise ValueError(
                f"uid={uid}: prompt of {len(toks)} tokens can never fit "
                f"max_seq_len={self.max_seq_len}")
        if len(toks) + limit + 1 > self.max_seq_len:
            limit = self.max_seq_len - len(toks) - 1
        return limit

    def _prefix_hit(self, toks) -> int:
        """Longest block-aligned published-prefix match (the local
        prefix-cache model; 0 when the cache is off)."""
        if not self._config.prefix_cache or not self._prefix_store:
            return 0
        best = 0
        t = tuple(int(x) for x in toks)
        for stored in self._prefix_store:
            n = 0
            for a, b in zip(stored, t):
                if a != b:
                    break
                n += 1
            best = max(best, n)
        bs = self.kv.block_size
        best = (best // bs) * bs
        return min(best, len(t) - 1)

    def _publish_prefix(self, toks) -> None:
        if not self._config.prefix_cache:
            return
        cap = self._config.prefix_cache_max_blocks
        t = tuple(int(x) for x in toks)
        if not t or t in self._prefix_store:
            return
        self._prefix_store.append(t)
        self._prefix_blocks += self.kv.blocks_for(len(t))
        if cap is not None:
            while self._prefix_blocks > cap and len(self._prefix_store) > 1:
                old = self._prefix_store.pop(0)
                self._prefix_blocks -= self.kv.blocks_for(len(old))
        self.telemetry.gauges["prefix_blocks_resident"] = \
            self._prefix_blocks

    def _admit_capacity(self, uid: int, seq: _SimSeq, toks, limit: int,
                        resumed: bool) -> Optional[int]:
        """KV reservation + cached-prefix discovery (the ``try_reserve``
        the real admission passes the scheduler). Returns ``cached0`` or
        None when the pool can't hold the request."""
        need = self.kv.blocks_for(len(toks) + limit + 1)
        if not self.kv.reserve(need):
            return None
        seq.blocks += need
        cached0 = 0
        if resumed and self.kv_swap is not None:
            rec = self.kv_swap.request_record(uid)
            if rec:
                cached0 = min(int(rec.get("watermark", 0)), len(toks) - 1)
                if cached0:
                    self.telemetry.on_kv_swap_in(
                        self.kv.blocks_for(cached0), resume=True)
        if cached0 == 0:
            cached0 = self._prefix_hit(toks)
            if self._config.prefix_cache:
                self.telemetry.on_prefix_lookup(
                    cached0, self.kv.blocks_for(cached0) if cached0
                    else 0, cow=False)
        return cached0

    def _fault_retire(self, uid: int, kind: str, frame: int, detail: str,
                      partial=None) -> None:
        ent = self._ledger.pop(uid, None)
        if self.kv_swap is not None:
            self.kv_swap.drop_request(uid)
        self.fault_log.append(FaultReason(
            uid=uid, kind=kind, frame=frame, detail=detail,
            tokens_emitted=len(partial or ()),
            partial=list(partial) if partial else None,
            tenant=ent.tenant if ent else None,
            priority=str(ent.priority) if ent and ent.priority is not None
            else None))
        self.telemetry.on_fault(kind, uid=uid)
        self._emit_event("fault", uid, kind=kind)

    def _expire_deadlines(self, sched, boundary: int) -> None:
        now = self._clock()
        expired = [uid for uid, ent in self._ledger.items()
                   if ent.deadline_at is not None
                   and now >= ent.deadline_at]
        for uid in expired:
            seq = self.state.seqs.get(uid)
            partial = list(seq.generated) if seq is not None else []
            if uid in self._rows:
                del self._rows[uid]
                sched.on_retire(uid)
            else:
                sched.cancel(uid)
            self.state.flush_sequence(uid)
            ent = self._ledger.get(uid)
            kind = "cancelled" if ent is not None and ent.cancelled \
                else "deadline_expired"
            self._fault_retire(uid, kind, boundary, detail=kind,
                               partial=partial)

    def _evict_to_queue(self, uid: int, sched) -> None:
        """Mirror of ``engine_v2._evict_to_queue``: fold emitted tokens,
        free blocks, requeue front; swap tier keeps the watermark so
        re-admission restores instead of re-prefilling."""
        from ..scheduler import PRIORITY_NAMES
        seq = self.state.seqs[uid]
        row = self._rows.pop(uid)
        req = sched.on_evict(uid)
        emitted = seq.generated[req.gen_base:]
        if emitted:
            req.tokens = np.concatenate(
                [np.asarray(req.tokens, np.int32),
                 np.asarray(emitted, np.int32)])
            req.limit -= len(emitted)
        if self.kv_swap is not None and self._config.kv_swap_preempt \
                and 0 < row.cached <= len(req.tokens):
            self.kv_swap.put_request(uid, row.cached)
            self.telemetry.on_kv_swap_out(
                self.kv.blocks_for(row.cached), uid=uid)
        if seq.blocks:
            self.kv.release(seq.blocks)
            seq.blocks = 0
        sched.requeue_front(req)
        self.telemetry.on_preempt(uid, req.tenant,
                                  PRIORITY_NAMES[req.priority])
        self._emit_event("preempt", uid)

    # ------------------------------------------------------------------
    # serve
    # ------------------------------------------------------------------

    def serve(self, arrivals, *, max_new_tokens: int = 32,
              temperature: float = 0.0, eos_token_id: Optional[int] = None,
              frame_steps: Optional[int] = None,
              frame_slots: Optional[int] = None,
              speculate: Optional[bool] = None, gamma: Optional[int] = None,
              rng=None, scheduler=None, faults=None, resume_from=None,
              yield_boundaries: bool = False):
        """Virtual-time ``serve()`` — same contract as the real engine's
        (see module docstring). ``scheduler`` is REQUIRED: the simulator
        exists to exercise the production policy object."""
        if scheduler is None:
            raise ValueError(
                "SimEngine.serve needs scheduler= (pass a "
                "scheduler_factory to the router): the simulator runs "
                "the real RequestScheduler, there is no FIFO twin")
        c = self._config
        steps = frame_steps or c.frame_steps
        adaptive = c.adaptive_frame_steps and frame_steps is None
        if speculate is None:
            speculate = False       # sim has no draft model attached
        gamma = int(gamma if gamma is not None else c.speculate_gamma)
        n_slots = frame_slots or c.max_ragged_batch_size
        arrivals = iter(arrivals)
        self._handoff_mode = c.role == "prefill"
        if self._handoff_mode and self.kv_swap is None:
            raise ValueError("role='prefill' needs a KV swap tier")
        # a closed-mid-flight predecessor generator (role flip / drain
        # abandonment) may have left reserved descriptors behind: release
        # them so the KV accounting starts clean
        for uid in list(self.state.seqs):
            self.state.flush_sequence(uid)
        self._ledger = {}
        self._rows = {}
        self._draining = False
        self.telemetry.begin_serve(
            speculate=bool(speculate), gamma=gamma, adaptive=adaptive,
            n_slots=n_slots, kv_blocks_total=self.kv.num_blocks,
            tp_degree=c.tp, kv_block_bytes=self.kv.block_bytes)
        scheduler.begin_serve(self)
        resume = InferenceEngineV2._resume_entries(self, resume_from)
        return self._serve_loop(arrivals, scheduler, steps,
                                max_new_tokens, temperature, eos_token_id,
                                bool(speculate), gamma, adaptive, resume,
                                yield_boundaries)

    def _serve_loop(self, arrivals, sched, steps, max_new_tokens,
                    temperature, eos_token_id, speculate, gamma, adaptive,
                    resume, boundaries):
        from ..scheduler import (PRIORITY_NAMES, Request,
                                 normalize_priority)
        c = self._config
        tel = self.telemetry
        alpha = c.frame_steps_ewma_alpha
        ewma = 0.0
        exhausted = False
        boundary = -1
        self._clock.seek(self.local_t)
        # ---- crash-recovery ingestion (mirrors engine_v2._enqueue) ----
        for (uid, prompt, limit, temp, eos, dl_ms, generated, tenant, prio,
             slo_ms, trace) in resume:
            seq = self.state.get_or_create_sequence(uid)
            seq.generated = list(generated)
            prio = normalize_priority(prio)
            tenant = tenant or "default"
            self._ledger_add(uid, prompt, limit, temp, eos, dl_ms,
                             tenant=tenant, priority=PRIORITY_NAMES[prio],
                             slo_ms=slo_ms, resumed_from=len(generated),
                             trace=trace)
            trace = tel.on_enqueue(uid, tenant=tenant,
                                   pclass=PRIORITY_NAMES[prio],
                                   resumed=len(generated) > 0, trace=trace)
            self._trace_back(uid, trace)
            remaining = limit - len(generated)
            if remaining <= 0:
                out = np.asarray(seq.generated, np.int64)
                self.state.flush_sequence(uid)
                self._ledger.pop(uid, None)
                tel.on_retire(uid)
                yield uid, out
                continue
            folded = list(prompt) + list(generated)
            sched.submit(Request(
                uid=uid, tokens=np.asarray(folded, np.int32),
                limit=remaining, temp=temp, eos=eos, tenant=tenant,
                priority=prio, slo_ms=slo_ms,
                resumed_from=len(generated), resumed=True),
                bypass_quota=True)
        while True:
            boundary += 1
            self._clock.seek(self.local_t)
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
                for item in (batch or []):
                    uid, toks, limit, temp, eos, tenant, prio, slo_ms, \
                        dl_ms, gen, trace = \
                        InferenceEngineV2._norm_arrival(
                            item, max_new_tokens, temperature,
                            eos_token_id)
                    limit = self._validate_arrival(
                        uid, toks, limit,
                        in_flight=uid in self._rows
                        or sched.is_queued(uid))
                    prio = normalize_priority(prio)
                    tenant = tenant or "default"
                    self._ledger_add(uid, toks, limit, temp, eos, dl_ms,
                                     tenant=tenant,
                                     priority=PRIORITY_NAMES[prio],
                                     slo_ms=slo_ms,
                                     resumed_from=len(gen) if gen else 0,
                                     trace=trace)
                    trace = tel.on_enqueue(uid, tenant=tenant,
                                           pclass=PRIORITY_NAMES[prio],
                                           resumed=bool(gen), trace=trace)
                    self._trace_back(uid, trace)
                    if gen is not None:
                        seq = self.state.get_or_create_sequence(uid)
                        seq.generated = list(gen)
                        remaining = limit - len(gen)
                        if remaining <= 0:
                            out = np.asarray(seq.generated, np.int64)
                            self.state.flush_sequence(uid)
                            self._ledger.pop(uid, None)
                            tel.on_retire(uid)
                            yield uid, out
                            continue
                        folded = np.concatenate(
                            [toks, np.asarray(gen, np.int32)]) \
                            if gen else toks
                        sched.submit(Request(
                            uid=uid, tokens=folded, limit=remaining,
                            temp=temp, eos=eos, tenant=tenant,
                            priority=prio, slo_ms=slo_ms,
                            resumed_from=len(gen), resumed=True),
                            bypass_quota=True)
                        continue
                    shed = sched.submit(Request(
                        uid=uid, tokens=toks, limit=limit, temp=temp,
                        eos=eos, tenant=tenant, priority=prio,
                        slo_ms=slo_ms))
                    if shed is not None:
                        tel.on_shed(uid, shed.tenant, shed.priority,
                                    shed.reason)
                        self._ledger.pop(uid, None)
                        self._emit_event("shed", uid, reason=shed.reason)
            # ---- deadlines, control pass, preemption, admission: the
            # exact _serve_loop stage order ----
            self._expire_deadlines(sched, boundary)
            for shed in sched.on_boundary(tel.slo_view(),
                                          live_count=len(self._rows)):
                tel.on_shed(shed.uid, shed.tenant, shed.priority,
                            shed.reason)
                self.state.flush_sequence(shed.uid)
                self._ledger.pop(shed.uid, None)
                if self.kv_swap is not None:
                    self.kv_swap.drop_request(shed.uid)
                self._emit_event("shed", shed.uid, reason=shed.reason)
            tel.gauges["slo_risk"] = round(sched.risk, 4)
            n_slots = tel.gauges["slot_count"] or c.max_ragged_batch_size
            free_slots = int(n_slots) - len(self._rows)
            if not self._draining and sched.preempt_wanted(free_slots):
                committed = {u: r.cached for u, r in self._rows.items()}
                for uid in sched.pick_victims(
                        committed, free_blocks=self.kv.free_blocks):
                    self._evict_to_queue(uid, sched)
                free_slots = int(n_slots) - len(self._rows)

            def try_reserve(req):
                seq = self.state.get_or_create_sequence(req.uid)
                cached0 = self._admit_capacity(req.uid, seq, req.tokens,
                                               req.limit, req.resumed)
                if cached0 is None:
                    return None
                return (seq, cached0)

            admits = []
            if not self._draining:
                for req, res in sched.pick(free_slots, try_reserve,
                                           live_count=len(self._rows)):
                    seq, cached0 = res
                    seq.done = False
                    req.gen_base = len(seq.generated)
                    self._rows[req.uid] = _SimRow(
                        uid=req.uid, plen=len(req.tokens),
                        limit=req.limit, temp=req.temp, eos=req.eos,
                        cached=int(cached0), start=int(cached0),
                        gen_base=req.gen_base)
                    admits.append(req.uid)
                    tel.on_admit(req.uid)
                    self._emit_event("admit", req.uid, cached0=cached0)
            if sched.queued_count() and not self._draining:
                tel.on_defer(
                    queue_depth=sched.queued_count(),
                    frame_steps=tel.serve_view["frame_steps_last"]
                    or steps,
                    free_slots=int(n_slots) - len(self._rows),
                    free_blocks=self.kv.free_blocks)
            if not self._rows:
                if exhausted and not sched.queued_count():
                    return
                self.local_t += self.idle_poll_s
                self._clock.seek(self.local_t)
                if boundaries:
                    yield ServeBoundary(
                        index=boundary, dispatched=False, live=0,
                        queued=sched.queued_count(),
                        free_slots=int(n_slots), t=self._clock(),
                        queued_tokens=sched.queued_prompt_tokens())
                continue
            # ---- frame plan (real arithmetic, virtual execution) ----
            rows = self._rows
            need = -(-max(0, *(r.plen - r.cached for r in rows.values()))
                     // c.prefill_chunk_size)
            width = c.prefill_chunk_size if need else 1
            cur_steps = steps
            saturated = int(n_slots) == len(rows)
            if adaptive:
                cur_steps = InferenceEngineV2._pick_frame_steps(
                    ewma, steps, saturated)
            # the engine's own plan of how many of them the frame runs
            cur_steps = InferenceEngineV2._plan_frame_steps(
                min(cur_steps, sched.frame_steps_cap(steps)), steps,
                len(rows), need, max(1, min(
                    r.limit - (len(self.state.seqs[u].generated) - r.gen_base)
                    for u, r in rows.items())),
                any(r.start < r.cached < r.plen for r in rows.values()))
            tel.on_frame_plan(ewma, saturated, cur_steps)
            emissions, finished, first_uids, delta = \
                self._run_virtual_frame(width, cur_steps, speculate, gamma)
            dt = self.cost.frame_seconds(
                steps=cur_steps, live=len(self._rows),
                n_slots=int(n_slots), width=width,
                spec=speculate and width == 1, tp=c.tp,
                quant=c.weight_dtype == "int8"
                or c.tp_quantized_collectives)
            # the simulator prices a frame as the chunk whole (no rungs):
            # slots x steps x positions, a speculative step gamma + 1 wide
            delta[STAT_POSITIONS] = int(n_slots) * cur_steps * (
                gamma + 1 if speculate and width == 1 else width)
            self.local_t += dt
            self._clock.seek(self.local_t)
            self.virtual_frames += 1
            self.virtual_steps += cur_steps
            tel.on_frame(delta=delta, width=width, steps=cur_steps,
                         live_slots=len(self._rows),
                         kv_blocks_in_use=self.kv.num_blocks
                         - self.kv.free_blocks,
                         arrival_ewma=ewma,
                         queue_depth=sched.queued_count())
            for uid in first_uids:
                # stamped POST-advance: the first token exists when the
                # frame that computed it completes, not when it starts
                self._emit_event("first_token", uid)
            for uid, new_toks in emissions.items():
                tel.on_emit(uid, len(new_toks))
                self._emit_event("emit", uid, n=len(new_toks))
            for uid in finished:
                seq = self.state.seqs[uid]
                seq.done = True
                out = np.asarray(seq.generated, np.int64)
                row = self._rows.pop(uid)
                self._publish_prefix(self._ledger[uid].prompt
                                     if uid in self._ledger else [])
                self.state.flush_sequence(uid)
                sched.on_retire(uid)
                self._ledger.pop(uid, None)
                if self.kv_swap is not None:
                    self.kv_swap.drop_request(uid)
                tel.on_retire(uid)
                self._emit_event("retire", uid, n=len(out))
                yield uid, out
            if self._handoff_mode:
                yield from self._collect_handoffs(sched, boundary)
            if boundaries:
                yield ServeBoundary(
                    index=boundary, dispatched=True,
                    live=len(self._rows), queued=sched.queued_count(),
                    free_slots=int(n_slots) - len(self._rows),
                    t=self._clock(),
                    queued_tokens=sched.queued_prompt_tokens(),
                    emissions=emissions)

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

    def _trace_back(self, uid, trace) -> None:
        ent = self._ledger.get(uid)
        if ent is not None and trace is not None:
            ent.trace = trace

    def _run_virtual_frame(self, width, cur_steps, speculate, gamma):
        """Advance every live row ``cur_steps`` virtual steps: prefill
        rows commit ``width`` prompt tokens per step (emitting their
        first token at prompt completion), decode rows emit one token
        per step — or ``1 + round(acceptance * gamma)`` per verify
        forward under speculation (width-1 frames only, matching the
        real frame programs). Deterministic synthetic token values."""
        emissions: Dict[int, List[int]] = {}
        finished: List[int] = []
        first_uids: List[int] = []
        delta = np.zeros(N_STATS, np.int64)
        spec_k = int(round(self.spec_acceptance * gamma)) \
            if speculate and gamma > 0 else 0
        for uid, row in self._rows.items():
            seq = self.state.seqs[uid]
            new: List[int] = []
            done = False
            for _ in range(cur_steps):
                if done:
                    break
                delta[STAT_ACTIVE_STEPS] += 1
                if row.cached < row.plen:
                    take = min(width, row.plen - row.cached)
                    row.cached += take
                    delta[STAT_PREFILL_TOKS] += take
                    if row.cached < row.plen:
                        continue
                    emit_n = 1          # prompt-completion token
                elif width == 1 and spec_k:
                    delta[STAT_TARGET_FWD] += 1
                    delta[STAT_DRAFTED] += gamma
                    remaining = row.limit - (len(seq.generated)
                                             - row.gen_base)
                    emit_n = max(1, min(1 + spec_k, remaining))
                    delta[STAT_ACCEPTED] += emit_n - 1
                else:
                    if width == 1:
                        delta[STAT_TARGET_FWD] += 1
                    emit_n = 1
                for _k in range(emit_n):
                    k = len(seq.generated)
                    tok = synth_token(uid, k)
                    seq.generated.append(tok)
                    new.append(tok)
                    row.cached += 1
                    delta[STAT_EMITTED] += 1
                    if row.eos is not None and tok == row.eos:
                        delta[STAT_EOS] += 1
                        done = True
                        break
                    if len(seq.generated) - row.gen_base >= row.limit:
                        done = True
                        break
                seq.seen_tokens = row.cached
            if new:
                emissions[uid] = new
                if len(seq.generated) - row.gen_base == len(new):
                    first_uids.append(uid)
            if done or len(seq.generated) - row.gen_base >= row.limit:
                if not self._handoff_mode:
                    finished.append(uid)
        return emissions, finished, first_uids, delta

    def _collect_handoffs(self, sched, boundary: int):
        """Prefill-role boundary: rows whose watermark covers their
        prompt hand off (mirrors ``engine_v2._collect_handoffs``)."""
        for uid in [u for u, r in self._rows.items()
                    if r.cached >= r.plen]:
            seq = self.state.seqs.get(uid)
            ent = self._ledger.get(uid)
            if seq is None or ent is None or not seq.generated:
                continue
            row = self._rows[uid]
            self.kv_swap.put_request(uid, row.cached)
            self.kv_swap.stamp_request_handoff(
                uid, {"prompt_tokens": len(ent.prompt),
                      "generated": len(seq.generated), "role": "prefill"})
            item = {
                "uid": int(uid),
                "tokens": [int(t) for t in ent.prompt],
                "generated": [int(t) for t in seq.generated],
                "max_new_tokens": int(ent.limit),
                "temperature": float(ent.temp),
                "eos_token_id": -1 if ent.eos is None else int(ent.eos),
            }
            for k, v in (("tenant", ent.tenant),
                         ("priority", ent.priority),
                         ("slo_ms", ent.slo_ms), ("trace", ent.trace)):
                if v is not None:
                    item[k] = v
            if ent.deadline_at is not None:
                item["deadline_ms"] = max(
                    (ent.deadline_at - self._clock()) * 1e3, 1e-3)
            del self._rows[uid]
            sched.on_retire(uid)
            self.state.flush_sequence(uid)
            self._ledger.pop(uid, None)
            self.telemetry.on_handoff_out(uid, pipelined=False)
            self._emit_event("handoff_out", uid)
            yield HandoffEvent(uid=uid, arrival=item, published=True)
