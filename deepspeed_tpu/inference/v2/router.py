"""Multi-engine serving front-end: health-checked placement + failover.

The stack below this module is an observable, schedulable, crash-safe
SINGLE engine. The ROADMAP's "millions of users" tier needs a front-end
that owns N engine replicas — possibly heterogeneous (different TP degree,
draft config, pool sizing) — and survives any one of them dying. That is
``EngineRouter``, in four pieces (README "Multi-engine routing &
failover"; the multi-replica lineage of DeepSpeed Inference, arXiv
2207.00032, with the replica-vs-shard tradeoff framed per Placement
Semantics, arXiv 2601.02311 — replicas here are the AVAILABILITY axis,
``tp=`` inside each engine the latency axis):

1. **Placement** — tenant/session AFFINITY via consistent hashing (a
   stable hash ring with virtual nodes, so adding/removing a replica only
   remaps ~1/N of the keyspace and a session's KV-prefix locality — the
   prefix cache is per-engine — survives membership churn), with a
   LEAST-LOADED fallback scored from each engine's existing telemetry
   (queue depth + live slots, free KV blocks, windowed TTFT p90). Scoring
   is a pure function (``placement_score``) and ties break by name, so
   placement is deterministic given the same snapshots.

2. **Cooperative stepping** — each replica's ``serve(...,
   yield_boundaries=True)`` generator advances AT MOST one frame per
   ``next()``; the router round-robins the replicas, so one host thread
   drives the whole fleet deterministically (no thread interleave in the
   chaos tests) while every engine keeps its own compiled frame loop.

3. **Health** — every ``ServeBoundary`` is a progress heartbeat. A replica
   whose OWN dispatched frames stop making wall-clock progress — boundary
   time minus the instant the router stepped it exceeds
   ``heartbeat_timeout_s``, so one slow replica never inflates its peers'
   gaps in the serial stepping loop — accumulates missed heartbeats and is
   treated as failed at ``max_missed_heartbeats``, on top of the engine's
   own fault signals: retry exhaustion surfaces ``FrameDispatchError``
   (with ``last_crash_snapshot`` already taken), and the scripted
   ``RouterFaultInjector`` kills replicas deterministically for chaos
   tests.

4. **Failover** — a failed replica is QUARANTINED (rejoin after an
   exponential tick backoff; ``max_engine_failures`` strikes and it is
   DEAD), its snapshot is split per-request (``faults.snapshot_split``)
   and every in-flight request re-admitted on a healthy peer as a RESUME
   arrival — the peer re-prefills prompt + committed tokens, so greedy
   outputs are token-identical to the no-failure run, across heterogeneous
   TP degrees (the snapshot is engine-shape-agnostic). Re-routes are
   bounded per request (``max_reroute_retries``) with exponential tick
   backoff, so a flapping replica degrades CAPACITY (fewer healthy peers,
   some queueing) instead of AVAILABILITY (requests still complete
   elsewhere). Planned removal is ``drain()``: placement stops, live rows
   finish (``engine.begin_drain`` holds the queue), then the queue is
   snapshot-migrated to the peers.

5. **Disaggregated prefill/decode** (README "Disaggregated prefill/
   decode"; the DeepSpeed-Inference/FastGen split taken past the paper,
   since here the handoff is token-identical by construction) — replicas
   whose engines carry ``role="prefill"`` run wide chunked-prefill frames
   and, at the committed watermark, publish the request's KV pages into
   the fleet's SHARED ``KVSwapTier`` and yield a ``HandoffEvent``; the
   router re-places the request on a decode/unified replica, whose
   ordinary swap-in admission restores the pages and streams tokens.
   Arrivals are classified prefill-heavy vs decode-heavy (prompt length
   vs ``max_new_tokens``); prefill replicas are scored by queued prompt
   TOKENS, decode replicas by ``placement_score``. The tier also carries
   content-addressed prefix records, so a hot shared prompt is prefilled
   once fleet-wide and every later arrival on any replica admits at the
   watermark.

Everything here is host-side policy over frame boundaries: the router adds
zero device work and never touches an engine's compiled loops.
"""

import bisect
import collections
import dataclasses
import hashlib
import time
from typing import Dict, Iterable, List, Optional, Tuple

from ...utils.logging import logger
from .engine_v2 import HandoffEvent, ServeBoundary
from .faults import FrameDispatchError, snapshot_split

# replica lifecycle states
HEALTHY = "healthy"          # accepting placements, being stepped
DRAINING = "draining"        # finishing live rows, queue held for migration
DRAINED = "drained"          # drain complete, generator closed
QUARANTINED = "quarantined"  # failed; rejoin pending (tick backoff)
CLOSED = "closed"            # serve generator ended normally
DEAD = "dead"                # failed past max_engine_failures — never rejoins


@dataclasses.dataclass
class RouterConfig:
    """Policy knobs for ``EngineRouter`` (see module docstring)."""
    # which driver advances the fleet (README "Service edge"):
    #   "serial"   — the cooperative single-thread stepping loop below
    #                (deterministic; the chaos-test driver);
    #   "threaded" — service.fleet.FleetDriver: one worker thread per
    #                replica advances its serve generator concurrently,
    #                boundary events flow back to a router thread that
    #                keeps placement/failover/heartbeat semantics
    #                identical. serve() dispatches on this flag.
    driver: str = "serial"
    # consistent-hash ring: virtual nodes per replica (more = smoother
    # keyspace split, slightly larger ring)
    ring_replicas: int = 64
    # least-loaded score weights (placement_score): queue+live occupancy,
    # KV pool pressure, windowed TTFT p90 against slo_ref_ms
    w_queue: float = 1.0
    w_kv: float = 0.5
    w_ttft: float = 0.25
    slo_ref_ms: float = 1000.0
    # an affinity target whose load score exceeds this falls back to the
    # least-loaded replica for the request (None = affinity always sticks;
    # sessions trade prefix-cache locality for load spreading past it)
    affinity_overload_score: Optional[float] = None
    # progress-heartbeat health check: a DISPATCHED frame taking more than
    # this many seconds of the replica's OWN time (boundary timestamp minus
    # the instant the router stepped it — NOT boundary-to-boundary, which
    # in the serial stepping loop would include every peer's frame time)
    # counts one missed heartbeat; at max_missed_heartbeats the replica is
    # treated as failed.
    # None disables (the deterministic chaos suites drive failure through
    # the injector and FrameDispatchError instead of wall clocks). Like the
    # engine watchdog, this cannot preempt a truly hung jit — it catches
    # the replica whose frames still return but have stopped keeping up.
    heartbeat_timeout_s: Optional[float] = None
    max_missed_heartbeats: int = 3
    # per-request failover bound: how many times one request may be
    # re-routed after engine failures before it is failed outright
    max_reroute_retries: int = 2
    # re-route backoff, in ROUTER TICKS (deterministic): the first
    # failover is immediate, repeat failovers of the same request wait
    # reroute_backoff_ticks * 2^(hop-1) ticks
    reroute_backoff_ticks: int = 1
    # failed-replica rejoin backoff, in ticks, doubling per failure;
    # rejoin=False keeps failed replicas quarantined forever
    rejoin: bool = True
    quarantine_backoff_ticks: int = 8
    max_engine_failures: int = 3
    fault_log_max: int = 256
    # ---- disaggregated prefill/decode placement (engine roles; README
    # "Disaggregated prefill/decode") ----
    # an arrival is PREFILL-HEAVY when its prompt is at least this many
    # times its generation budget (prompt length vs max_new_tokens — the
    # classification heuristic); prefill-heavy arrivals go to a prefill
    # replica (scored by queued prompt TOKENS, the signal that predicts
    # its wide-frame backlog), everything else — including every handoff
    # and failover resume that already has committed tokens — goes to
    # decode/unified replicas by placement_score. Inert without prefill
    # replicas in the fleet.
    prefill_route_ratio: float = 4.0
    # absolute floor: prompts shorter than this are never prefill-routed
    # even when the ratio says so (a 12-token prompt with budget 2 is not
    # worth a handoff round-trip)
    prefill_route_min_prompt: int = 32


@dataclasses.dataclass
class RouterFault:
    """One router-level fault event (``EngineRouter.fault_log``)."""
    kind: str            # engine_crash | engine_kill | missed_heartbeat |
    #                      request_failed | engine_dead
    tick: int
    engine: Optional[str] = None
    uid: Optional[int] = None
    detail: str = ""


def placement_score(queued: int, live: int, slots: int,
                    kv_free_frac: float, ttft_p90_ms: Optional[float],
                    slo_ref_ms: float, w_queue: float = 1.0,
                    w_kv: float = 0.5, w_ttft: float = 0.25) -> float:
    """Least-loaded placement score for one replica — LOWER is better.
    Pure function of a telemetry snapshot (queue depth + live slots
    normalized by capacity, KV pool pressure, windowed TTFT p90 against a
    reference SLO), so the least-loaded choice is a deterministic function
    of the snapshots and unit-testable without engines."""
    occ = (queued + live) / max(1, slots)
    kv = 1.0 - min(max(kv_free_frac, 0.0), 1.0)
    lat = (ttft_p90_ms / slo_ref_ms) if ttft_p90_ms else 0.0
    return w_queue * occ + w_kv * kv + w_ttft * lat


class _Replica:
    """Internal per-engine state: the serve generator, its feed queue (the
    arrival iterator the engine polls each boundary), and health/heartbeat
    bookkeeping."""

    def __init__(self, name: str, engine):
        self.name = name
        self.engine = engine
        self.status = HEALTHY
        self.gen = None
        self.feed: collections.deque = collections.deque()
        self.closing = False
        self.last_boundary: Optional[ServeBoundary] = None
        self.missed_heartbeats = 0
        self.failures = 0
        self.rejoin_tick: Optional[int] = None

    def feed_iter(self):
        """The engine-side arrival iterator: each frame boundary drains
        whatever the router placed since the last poll; StopIteration only
        when the router is shutting this replica down. Drains by popleft
        (atomic per item) rather than snapshot-then-clear, so an item
        appended mid-drain is never silently dropped — the contract the
        threaded fleet driver's mailbox relies on (identical behavior
        under the serial driver, which never appends mid-drain)."""
        while True:
            if self.closing and not self.feed:
                return
            batch = []
            while True:
                try:
                    batch.append(self.feed.popleft())
                except IndexError:
                    break
            yield batch

    def accepting(self) -> bool:
        return self.status == HEALTHY


class EngineRouter:
    """Front-end owning N ``InferenceEngineV2`` replicas (see module
    docstring). ``engines`` is a ``{name: engine}`` mapping or a list
    (auto-named ``engine0..``); each engine's telemetry is stamped with
    ``engine=<name>, model=<label>`` base labels so one scrape
    distinguishes replicas (``model_labels`` overrides the default
    ``<layers>L-tp<degree>`` label)."""

    def __init__(self, engines, config: Optional[RouterConfig] = None,
                 model_labels: Optional[Dict[str, str]] = None,
                 clock=None):
        self.cfg = config or RouterConfig()
        if not isinstance(engines, dict):
            engines = {f"engine{i}": e for i, e in enumerate(engines)}
        if not engines:
            raise ValueError("EngineRouter needs at least one engine")
        self._replicas: Dict[str, _Replica] = {
            name: _Replica(name, eng) for name, eng in engines.items()}
        # replica roles come from the engine configs (engine_v2
        # ``role=``): "prefill" replicas run chunked prefill and hand off
        # at the watermark, "decode"/"unified" replicas stream tokens.
        # The role rides every replica's telemetry as a base label so the
        # fleet's ds_serving_*/ds_router_* series are separable per role.
        self._roles: Dict[str, str] = {
            name: getattr(r.engine._config, "role", "unified")
            for name, r in self._replicas.items()}
        self._has_prefill = any(v == "prefill" for v in self._roles.values())
        for name, r in self._replicas.items():
            cfg = r.engine.model.cfg
            label = (model_labels or {}).get(
                name, f"{cfg.num_layers}L-tp{r.engine._config.tp}")
            r.engine.telemetry.set_base_labels(engine=name, model=label,
                                               role=self._roles[name])
        # the disaggregated fleet's shared KV tier: every prefill
        # replica's handoff pages must be restorable by some decode/
        # unified replica, which requires ONE shared KVSwapTier instance
        # across them (validated loudly — a per-engine tier would make
        # every handoff silently re-prefill)
        self._tier = None
        if self._has_prefill:
            tiers = {name: r.engine.kv_swap
                     for name, r in self._replicas.items()}
            for name, tier in tiers.items():
                if tier is None:
                    # a tier-less decode/unified replica would silently
                    # RE-PREFILL every handoff placed on it (its swap-in
                    # admission path never runs) — reject it as loudly as
                    # a tier-less prefill replica
                    raise ValueError(
                        f"replica {name!r} (role="
                        f"{self._roles[name]!r}) has no KV swap tier — "
                        "attach ONE shared KVSwapTier (attach_kv_tier) "
                        "to every replica in a disaggregated fleet")
            shared = {id(t) for t in tiers.values()}
            if len(shared) != 1 or not any(
                    self._roles[n] != "prefill" for n in tiers):
                raise ValueError(
                    "disaggregated fleet: every replica must share ONE "
                    "KVSwapTier instance (shared=True) spanning prefill "
                    "AND decode/unified roles — pages published at "
                    "handoff must be restorable by the decode side")
            self._tier = next(t for t in tiers.values() if t is not None)
            if not self._tier.shared:
                raise ValueError(
                    "disaggregated fleet: the shared KVSwapTier must be "
                    "constructed with shared=True (per-engine pruning "
                    "would drop peers' in-flight handoff records)")
        # consistent-hash ring over ALL replicas; membership is filtered at
        # lookup so the keyspace split is stable across failures/rejoins
        ring: List[Tuple[int, str]] = []
        for name in self._replicas:
            for i in range(self.cfg.ring_replicas):
                h = hashlib.sha1(f"{name}#{i}".encode()).digest()
                ring.append((int.from_bytes(h[:8], "big"), name))
        self._ring = sorted(ring)
        self._ring_hashes = [h for h, _ in self._ring]
        # routing state
        self._assignment: Dict[int, str] = {}       # uid -> replica name
        # uid -> affinity key at first placement: snapshot-resumed items
        # are rebuilt from the engine LEDGER, which never stored the
        # session key — re-stamping it keeps a failed-over session's
        # requests together on ONE healthy peer (prefix locality), instead
        # of scattering by-uid
        self._affinity: Dict[int, str] = {}
        self._reroute_hops: Dict[int, int] = {}
        self._deferred: List[Tuple[int, object, frozenset]] = []
        self._unplaced: collections.deque = collections.deque()
        self._pending_drains: set = set()
        self.fault_log: collections.deque = collections.deque(
            maxlen=self.cfg.fault_log_max)
        self.counters: Dict[str, int] = dict(
            placements=0, failovers=0, reroutes=0, drains=0,
            drain_migrated=0, engine_kills=0, rejoins=0,
            heartbeat_misses=0, requests_failed=0, completions=0,
            engine_retired=0, handoffs=0, handoffs_unpublished=0,
            # autoscaling controller (service/autoscale.py): exported as
            # the ds_router_scale_* series
            scale_up=0, scale_down=0, scale_role_flips=0)
        self._serve_limit = 32       # serve()'s max_new_tokens default
        #                              (the classification denominator)
        # fleet-wide observability (tracing.py; attach_tracing): the
        # distributed-trace collector every replica's telemetry feeds,
        # and the crash flight recorder. Both None by default — zero new
        # work on the placement/failover paths until attached.
        self.tracer = None
        self.flight = None
        self.placements_by_engine: Dict[str, int] = {
            name: 0 for name in self._replicas}
        self.last_recovery_ms: float = 0.0
        self._tick = 0               # current serve-loop tick (fault_log)
        # injectable clock (ctor clock=): feeds the heartbeat gap
        # measurement (step_t0 in _step vs the boundary's engine-clock t)
        # and every trace/flight timestamp — the virtual-time seam the
        # trace-driven simulator (sim/) steps the fleet on
        self._clock = clock or time.monotonic

    # ------------------------------------------------------------------
    # fleet-wide observability (tracing.py)
    # ------------------------------------------------------------------

    def attach_tracing(self, collector=None, recorder=None):
        """Wire distributed tracing + the crash flight recorder through
        the fleet (README "Distributed tracing & flight recorder"):
        every replica's telemetry stamps boundary spans into ONE shared
        ``TraceCollector`` (labeled with its replica name), the router
        mints a trace at ingestion for arrivals the edge didn't stamp,
        and fleet events (placements, failovers, heartbeat misses,
        handoffs, kills, drains, tier commits) land in the
        ``FlightRecorder`` ring — which dumps a postmortem bundle on
        replica death or an engine crash snapshot. Defaults are built
        when not passed; returns ``(collector, recorder)``."""
        from .tracing import FlightRecorder, TraceCollector
        self.tracer = collector if collector is not None else \
            TraceCollector()
        self.flight = recorder if recorder is not None else \
            FlightRecorder(collector=self.tracer)
        if self.flight.collector is None:
            self.flight.collector = self.tracer
        for name, r in self._replicas.items():
            r.engine.telemetry.set_tracer(self.tracer, replica=name)
        if self._tier is not None:
            self._tier.flight = self.flight
        return self.tracer, self.flight

    @staticmethod
    def _trace_of(item) -> Optional[Dict]:
        return item.get("trace") if isinstance(item, dict) else None

    def _flight_note(self, kind: str, **kw) -> None:
        if self.flight is not None:
            self.flight.record(kind, **kw)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def replica_status(self) -> Dict[str, str]:
        return {name: r.status for name, r in self._replicas.items()}

    def stats(self) -> Dict:
        out = {
            "counters": dict(self.counters),
            "placements_by_engine": dict(self.placements_by_engine),
            "replicas": self.replica_status(),
            "roles": dict(self._roles),
            "in_flight": len(self._assignment),
            "last_recovery_ms": self.last_recovery_ms,
        }
        if self._tier is not None:
            out["tier"] = dict(self._tier.stats)
        return out

    def render_prometheus(self) -> str:
        """``ds_router_*`` counters plus every replica's ``ds_serving_*``
        exposition (each stamped with its ``engine=``/``model=`` base
        labels at construction) — one scrape for the whole fleet. The
        exposition format allows ONE ``# TYPE`` line per metric family,
        so the per-replica outputs are merged with repeated TYPE headers
        dropped (every replica exports the same families; a duplicate
        header would make a real scraper reject the whole payload)."""
        lines: List[str] = []
        for name, val in self.counters.items():
            full = f"ds_router_{name}_total"
            lines.append(f"# TYPE {full} counter")
            lines.append(f"{full} {val}")
            if name == "placements":
                # engine samples carry the replica's role base label so a
                # heterogeneous fleet's legs are separable per role
                for en in sorted(self.placements_by_engine):
                    lines.append(
                        f'{full}{{engine="{en}",role='
                        f'"{self._roles.get(en, "unified")}"}} '
                        f"{self.placements_by_engine[en]}")
        if self._tier is not None:
            # fleet-level shared-tier traffic (any replica's boundary may
            # drain a peer's queued writes, so these counters live on the
            # tier, not on one engine's telemetry)
            for stat, val in sorted(self._tier.stats.items()):
                full = f"ds_router_tier_{stat}_total"
                lines.append(f"# TYPE {full} counter")
                lines.append(f"{full} {val}")
        lines.append("# TYPE ds_router_last_recovery_ms gauge")
        lines.append(f"ds_router_last_recovery_ms {self.last_recovery_ms}")
        lines.append("# TYPE ds_router_replica_up gauge")
        for name, r in sorted(self._replicas.items()):
            up = 1 if r.status in (HEALTHY, DRAINING) else 0
            lines.append(f'ds_router_replica_up{{engine="{name}",role='
                         f'"{self._roles[name]}"}} {up}')
        lines.append("# TYPE ds_router_prefill_queue_tokens gauge")
        for name, r in sorted(self._replicas.items()):
            if self._roles[name] == "prefill":
                lines.append(
                    f'ds_router_prefill_queue_tokens{{engine="{name}",'
                    f'role="prefill"}} {self._prefill_score(r)}')
        # merge by FAMILY, not by concatenation: the text format requires
        # all lines of one metric to form a single group, so every
        # replica's samples for a family are emitted together under one
        # TYPE header (each telemetry exposition leads every family with
        # its TYPE line, which is the block key here)
        order: List[str] = []
        fams: Dict[str, List[str]] = {}
        for r in self._replicas.values():
            cur = None
            for line in r.engine.telemetry.render_prometheus().splitlines():
                if line.startswith("# TYPE "):
                    cur = line
                    if cur not in fams:
                        fams[cur] = []
                        order.append(cur)
                elif cur is not None and line:
                    fams[cur].append(line)
        for t in order:
            lines.append(t)
            lines.extend(fams[t])
        # fleet-level tracing + flight-recorder series (unique families —
        # no per-replica merge needed): the fleet-merged ds_fleet_ttft_ms
        # / ds_fleet_e2e_ms true-attribution summaries live here
        if self.tracer is not None:
            lines.extend(self.tracer.render_prometheus().splitlines())
        if self.flight is not None:
            lines.extend(self.flight.render_prometheus().splitlines())
        return "\n".join(lines) + "\n"

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------

    @staticmethod
    def _uid_of(item) -> int:
        return int(item["uid"] if isinstance(item, dict) else item[0])

    @staticmethod
    def _affinity_key(item) -> str:
        """Session affinity key: an explicit ``session``, else the tenant,
        else the uid (no affinity beyond the single request)."""
        if isinstance(item, dict):
            return str(item.get("session") or item.get("tenant")
                       or item["uid"])
        return str(item[0])

    def _ring_pick(self, key: str, cands: Dict[str, "_Replica"]
                   ) -> Optional[str]:
        if not cands:
            return None
        h = int.from_bytes(hashlib.sha1(key.encode()).digest()[:8], "big")
        i = bisect.bisect_right(self._ring_hashes, h)
        for j in range(len(self._ring)):
            name = self._ring[(i + j) % len(self._ring)][1]
            if name in cands:
                return name
        return None

    def _score(self, r: _Replica) -> float:
        cfg = self.cfg
        b = r.last_boundary
        queued = (b.queued if b else 0) + len(r.feed)
        live = b.live if b else 0
        eng = r.engine
        slo = eng.telemetry.slo_view()
        # slot capacity from the replica's own boundary (live + free is the
        # frame's REAL slot count — serve(frame_slots=) can run under the
        # config max, which would understate occupancy here)
        slots = (b.live + b.free_slots) if b else \
            eng._config.max_ragged_batch_size
        return placement_score(
            queued, live, slots,
            eng.kv.free_blocks / max(1, eng.kv.num_blocks),
            slo.get("ttft_p90_ms"), cfg.slo_ref_ms,
            cfg.w_queue, cfg.w_kv, cfg.w_ttft)

    def _least_loaded(self, cands: Dict[str, "_Replica"]) -> str:
        # ties break by name: deterministic placement given the snapshots
        return min(cands, key=lambda n: (self._score(cands[n]), n))

    @staticmethod
    def _can_serve(r: _Replica, item) -> bool:
        """Prompt-size feasibility on a (possibly heterogeneous) replica:
        an arrival whose prompt — plus already-committed tokens for a
        failover resume, which the peer re-prefills — can never fit the
        replica's ``max_seq_len`` would hard-raise inside its serve
        generator (``_validate_arrival``) and tear the whole fleet serve
        down; screen it out of placement instead."""
        if isinstance(item, dict):
            need = len(item["tokens"]) + len(item.get("generated") or ())
        else:
            need = len(item[1])
        return need + 2 <= r.engine.max_seq_len

    def _classify(self, item) -> str:
        """Prefill-heavy vs decode-heavy arrival classification (the
        disaggregated fleet's placement heuristic). An arrival carrying
        committed tokens (a handoff or failover resume with
        ``generated``) is ALWAYS decode-heavy — a token can only exist
        after full prefill, so its remaining work is streaming. Fresh
        arrivals classify by prompt length vs generation budget:
        ``len(prompt) >= prefill_route_ratio * max_new_tokens`` (and at
        least ``prefill_route_min_prompt``) routes to a prefill replica.
        Returns "any" for role-blind fleets (no prefill replicas)."""
        if not self._has_prefill:
            return "any"
        if isinstance(item, dict):
            if item.get("generated"):
                return "decode"
            toks = item["tokens"]
            limit = item.get("max_new_tokens")
        else:
            toks = item[1]
            limit = item[2] if len(item) > 2 and item[2] is not None \
                else None
        limit = self._serve_limit if limit is None else limit
        plen = len(toks)
        if plen >= self.cfg.prefill_route_min_prompt and \
                plen >= self.cfg.prefill_route_ratio * max(1, limit):
            return "prefill"
        return "decode"

    @staticmethod
    def _feed_prompt_tokens(r: "_Replica") -> int:
        t = 0
        for item in r.feed:
            if isinstance(item, dict):
                t += len(item["tokens"]) + len(item.get("generated") or ())
            else:
                t += len(item[1])
        return t

    def _prefill_score(self, r: "_Replica") -> int:
        """Prefill-replica placement score: queued prompt TOKENS (router
        feed + the replica's own queue, from its last boundary) — lower
        is better. Token count, not request count: one 8k prompt is more
        wide-frame backlog than twenty 64-token ones."""
        b = r.last_boundary
        return (b.queued_tokens if b else 0) + self._feed_prompt_tokens(r)

    def _pick(self, key: str, exclude: frozenset = frozenset(),
              item=None) -> Optional[str]:
        fits = (lambda r: True) if item is None else \
            (lambda r: self._can_serve(r, item))
        cands = {n: r for n, r in self._replicas.items()
                 if r.accepting() and n not in exclude and fits(r)}
        if not cands:
            # nothing excluded left either? fall back to any accepting
            # replica rather than stranding the request
            cands = {n: r for n, r in self._replicas.items()
                     if r.accepting() and fits(r)}
        if not cands:
            return None
        # role-aware split (disaggregated fleet): prefill-heavy arrivals
        # prefer a prefill replica by queued-prompt-token score;
        # decode-heavy ones prefer decode/unified replicas. Either side
        # falls back to the other rather than stranding the request —
        # unified replicas serve anything, and a prefill replica serving
        # a decode request still makes progress (it hands off one token
        # further each round trip).
        role_need = "any" if item is None else self._classify(item)
        if role_need == "prefill":
            pcands = {n: r for n, r in cands.items()
                      if self._roles[n] == "prefill"}
            if pcands:
                return min(pcands,
                           key=lambda n: (self._prefill_score(pcands[n]), n))
        if role_need in ("prefill", "decode"):
            dcands = {n: r for n, r in cands.items()
                      if self._roles[n] != "prefill"}
            if dcands:
                cands = dcands
        name = self._ring_pick(key, cands)
        if self.cfg.affinity_overload_score is not None and \
                self._score(self._replicas[name]) > \
                self.cfg.affinity_overload_score:
            name = self._least_loaded(cands)
        return name

    def _place(self, item, exclude: frozenset = frozenset()) -> bool:
        uid = self._uid_of(item)
        key = self._affinity_key(item)
        self._affinity.setdefault(uid, key)
        if self.tracer is not None and isinstance(item, dict) \
                and not item.get("trace"):
            # arrival reached the router without a trace (no edge in
            # front): mint it HERE — router ingestion is the fleet's
            # earliest common observation point
            tid, root = self.tracer.mint(
                "router.ingest", replica="router", t=self._clock(),
                attrs={"uid": uid})
            item["trace"] = {"id": tid, "parent": root}
        name = self._pick(key, exclude, item)
        if name is None:
            # DEAD/DRAINED/CLOSED are all terminal — none of them ever
            # accepts again, so cycling _unplaced would spin forever
            if all(r.status in (DEAD, DRAINED, CLOSED)
                   for r in self._replicas.values()):
                raise RuntimeError(
                    "EngineRouter: every replica is dead, drained, or "
                    "closed — no capacity left to place requests on")
            # no NON-TERMINAL replica (healthy or one that may rejoin)
            # can ever hold this prompt: fail the request loudly instead
            # of parking it in _unplaced forever
            if not any(self._can_serve(r, item)
                       for r in self._replicas.values()
                       if r.status not in (DEAD, DRAINED, CLOSED)):
                self._assignment.pop(uid, None)
                self._affinity.pop(uid, None)
                self._reroute_hops.pop(uid, None)
                self._drop_tier_record(uid)
                self.counters["requests_failed"] += 1
                self.fault_log.append(RouterFault(
                    kind="request_failed", uid=uid, tick=self._tick,
                    detail="prompt can never fit any live replica's "
                           "max_seq_len"))
                self._request_failed_trace(item, "unservable prompt")
                logger.warning(f"router: uid={uid} failed — prompt fits "
                               "no live replica's max_seq_len")
                return False
            self._unplaced.append((item, exclude))
            return False
        r = self._replicas[name]
        tr = self._trace_of(item)
        if self.tracer is not None and tr:
            # stamped BEFORE the append: the replica's serve loop may take
            # the item from its feed at once, and its wait there (the
            # span ``engine.feed``) begins at this instant
            placed = self._clock()
            self.tracer.note_placed(tr["id"], placed)
            self.tracer.instant(
                tr["id"], "router.place", placed,
                parent=tr.get("parent"), replica="router",
                attrs={"uid": uid, "replica": name,
                       "resumed": bool(isinstance(item, dict)
                                       and item.get("generated"))})
        r.feed.append(item)
        self._assignment[uid] = name
        self.counters["placements"] += 1
        self.placements_by_engine[name] = \
            self.placements_by_engine.get(name, 0) + 1
        self._flight_note("placement", replica=name, uid=uid,
                          tick=self._tick,
                          trace=tr.get("id") if tr else None)
        return True

    def _request_failed_trace(self, item, detail: str) -> None:
        """A request died AT THE ROUTER (unservable / re-route budget):
        close its trace with a failed status — always sampled."""
        tr = self._trace_of(item)
        if self.tracer is not None and tr:
            self.tracer.mark(tr["id"], "fault")
            self.tracer.finish(tr["id"], self._clock(),
                               status=f"failed:{detail}")
        self._flight_note("request_failed", uid=self._uid_of(item),
                          tick=self._tick, detail=detail,
                          trace=tr.get("id") if tr else None)

    # ------------------------------------------------------------------
    # failure handling
    # ------------------------------------------------------------------

    def _close_gen(self, r: _Replica) -> None:
        if r.gen is None:
            return
        try:
            r.gen.close()
        except Exception as e:       # noqa: BLE001 — cleanup best-effort
            logger.warning(f"router: closing {r.name} serve generator "
                           f"raised {type(e).__name__}: {e}")
        r.gen = None

    def _route_failover(self, item, tick: int, exclude: frozenset) -> None:
        """Queue one orphaned request for re-placement on a healthy peer,
        bounded per request with exponential tick backoff."""
        uid = self._uid_of(item)
        hops = self._reroute_hops.get(uid, 0) + 1
        self._reroute_hops[uid] = hops
        if hops > self.cfg.max_reroute_retries:
            self._assignment.pop(uid, None)
            self._affinity.pop(uid, None)
            # a resubmission of this uid gets a FRESH budget, not the
            # exhausted one
            self._reroute_hops.pop(uid, None)
            self._drop_tier_record(uid)
            self.counters["requests_failed"] += 1
            self.fault_log.append(RouterFault(
                kind="request_failed", tick=tick, uid=uid,
                detail=f"re-route budget exhausted after {hops - 1} "
                       f"failovers (max_reroute_retries="
                       f"{self.cfg.max_reroute_retries})"))
            self._request_failed_trace(item, "re-route budget exhausted")
            logger.warning(f"router: uid={uid} failed — re-route budget "
                           "exhausted")
            return
        self.counters["reroutes"] += 1
        delay = 0 if hops == 1 else \
            self.cfg.reroute_backoff_ticks * (2 ** (hops - 2))
        self._deferred.append((tick + delay, item, exclude))

    def _fail_replica(self, r: _Replica, tick: int, kind: str,
                      detail: str, snapshot: Optional[Dict]) -> None:
        """Common failure path (crash, injected kill, missed heartbeats):
        quarantine the replica (or mark it dead past the strike budget),
        split its snapshot per-request, and re-route every orphaned
        request — feed leftovers the engine never polled ride along
        unchanged."""
        cfg = self.cfg
        if r.status == DRAINING:
            # planned removal in progress: the failure must not erase the
            # operator's drain intent — re-arm it so a rejoining replica
            # drains (empty, immediately) instead of accepting placements
            self._pending_drains.add(r.name)
        self._close_gen(r)
        r.failures += 1
        r.missed_heartbeats = 0
        r.last_boundary = None
        self.counters["failovers"] += 1
        self.fault_log.append(RouterFault(kind=kind, tick=tick,
                                          engine=r.name, detail=detail))
        if not cfg.rejoin or r.failures > cfg.max_engine_failures:
            r.status = DEAD
            if r.failures > cfg.max_engine_failures:
                self.fault_log.append(RouterFault(
                    kind="engine_dead", tick=tick, engine=r.name,
                    detail=f"{r.failures} failures > max_engine_failures="
                           f"{cfg.max_engine_failures}"))
        else:
            r.status = QUARANTINED
            r.rejoin_tick = tick + cfg.quarantine_backoff_ticks * \
                (2 ** (r.failures - 1))
        exclude = frozenset((r.name,))
        orphans = list(r.feed)
        r.feed.clear()
        resumed = self._restamp_affinity(
            snapshot_split(snapshot or {"version": 1, "requests": []}))
        # flight recorder: the failure event itself (engine_crash carries
        # a crash snapshot — an auto-dump kind), then replica death
        self._flight_note(kind, replica=r.name, tick=tick, detail=detail,
                          orphans=len(orphans), resumed=len(resumed))
        if r.status == DEAD:
            self._flight_note("replica_dead", replica=r.name, tick=tick,
                              detail=f"{kind}: {detail}")
        for item in orphans + resumed:
            # failed-over traces are ALWAYS sampled, and the failover hop
            # is visible in the span tree
            tr = self._trace_of(item)
            if self.tracer is not None and tr:
                self.tracer.mark(tr["id"], "failover")
                self.tracer.instant(
                    tr["id"], "router.failover", self._clock(),
                    parent=tr.get("parent"), replica="router",
                    attrs={"uid": self._uid_of(item), "from": r.name,
                           "kind": kind})
        for item in orphans:
            self._route_failover(item, tick, exclude)
        for item in resumed:
            self._route_failover(item, tick, exclude)
        logger.warning(f"router: replica {r.name} {kind} at tick {tick} "
                       f"({detail}); {len(orphans)} queued + {len(resumed)} "
                       f"in-flight requests re-routing, status={r.status}")

    def _kill(self, name: str, tick: int, detail: str) -> bool:
        """Hard-kill a replica (scripted engine_kill): snapshot the live
        ledger while the generator is suspended at a boundary, then fail
        it over exactly like a crash. Returns whether a replica was
        actually killed — a no-op (already quarantined/dead) must not
        start a new recovery-window measurement."""
        r = self._replicas.get(name)
        if r is None or r.status not in (HEALTHY, DRAINING):
            return False      # can't kill what isn't running
        snap = r.engine.snapshot_serving_state() if r.gen is not None \
            else {"version": 1, "requests": []}
        self.counters["engine_kills"] += 1
        self._fail_replica(r, tick, "engine_kill", detail, snap)
        return True

    def _maybe_rejoin(self, tick: int) -> None:
        for r in self._replicas.values():
            if r.status == QUARANTINED and r.rejoin_tick is not None \
                    and tick >= r.rejoin_tick:
                r.status = HEALTHY
                r.rejoin_tick = None
                self.counters["rejoins"] += 1
                self._flight_note("rejoin", replica=r.name, tick=tick)
                logger.warning(f"router: replica {r.name} rejoining at "
                               f"tick {tick} (failure {r.failures}/"
                               f"{self.cfg.max_engine_failures})")

    def _note_heartbeat(self, r: _Replica, b: ServeBoundary, tick: int,
                        step_t0: Optional[float] = None) -> Optional[str]:
        """Record a boundary heartbeat; returns a failure detail string
        when the replica crossed the missed-heartbeat threshold. The gap
        is the replica's OWN frame time — boundary timestamp minus
        ``step_t0`` (when the router handed it control this tick) — so a
        slow peer in the serial stepping loop cannot charge its frame
        time to this replica's heartbeat."""
        cfg = self.cfg
        out = None
        if (cfg.heartbeat_timeout_s is not None and b.dispatched
                and step_t0 is not None):
            if b.t - step_t0 > cfg.heartbeat_timeout_s:
                r.missed_heartbeats += 1
                self.counters["heartbeat_misses"] += 1
                self._flight_note(
                    "heartbeat_miss", replica=r.name, tick=tick,
                    detail=f"frame {b.t - step_t0:.3f}s > "
                           f"{cfg.heartbeat_timeout_s}s "
                           f"({r.missed_heartbeats}/"
                           f"{cfg.max_missed_heartbeats})")
                if r.missed_heartbeats >= cfg.max_missed_heartbeats:
                    out = (f"{r.missed_heartbeats} consecutive frames "
                           f"slower than heartbeat_timeout_s="
                           f"{cfg.heartbeat_timeout_s}")
            else:
                r.missed_heartbeats = 0
        r.last_boundary = b
        return out

    # ------------------------------------------------------------------
    # drain (planned replica removal)
    # ------------------------------------------------------------------

    def drain(self, name: str) -> None:
        """Begin a graceful drain: stop placing on ``name``, let its live
        rows finish, then snapshot-and-migrate its queue to the peers.
        Callable mid-serve (the router notices at its next tick) or
        scripted via a ``RouterFaultSpec(kind="engine_drain")``."""
        if name not in self._replicas:
            raise KeyError(f"unknown replica {name!r}")
        self._pending_drains.add(name)

    def rejoin_replica(self, name: str) -> bool:
        """Return a DRAINED (or CLOSED) replica to service — the
        autoscaler's scale-UP surface (``service/autoscale.py``): a
        drained replica parks warm (weights resident, generator closed)
        and rejoins here with a fresh serve generator at the driver's
        next tick. DEAD replicas never rejoin (the strike budget is a
        health verdict, not a capacity knob). Returns whether the status
        changed."""
        r = self._replicas.get(name)
        if r is None:
            raise KeyError(f"unknown replica {name!r}")
        if r.status not in (DRAINED, CLOSED):
            return False
        self._pending_drains.discard(name)
        r.status = HEALTHY
        return True

    def validate_replica_role(self, name: str, role: str) -> None:
        """Raise if re-labeling ``name`` to ``role`` would violate the
        disaggregated-fleet invariants the constructor enforces: a
        prefill replica needs the fleet's one shared tier, and flipping
        the last non-prefill replica away would strand every handoff.
        Pure check — the fleet driver pre-validates a flip HERE before
        halting the replica's worker (a post-halt rejection would have
        paid the generator restart for nothing)."""
        r = self._replicas.get(name)
        if r is None:
            raise KeyError(f"unknown replica {name!r}")
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role={role!r}")
        if role == "prefill":
            tier = r.engine.kv_swap
            if tier is None or not getattr(tier, "shared", False):
                raise ValueError(
                    f"replica {name!r}: role='prefill' needs the fleet's "
                    "shared KVSwapTier attached (attach_kv_tier)")
            if self._tier is not None and tier is not self._tier:
                raise ValueError(
                    f"replica {name!r}: prefill role must share the "
                    "fleet's one KVSwapTier instance")
            if all(self._roles[n] == "prefill" or n == name
                   or self._replicas[n].status == DEAD
                   for n in self._roles):
                # DEAD replicas never rejoin, so they are not decode
                # capacity — a fleet whose only non-prefill peers are
                # dead would ping-pong every decode request one token
                # per handoff round
                raise ValueError(
                    f"replica {name!r}: flipping the last live "
                    "non-prefill replica would strand every handoff")

    def set_replica_role(self, name: str, role: str) -> None:
        """Re-label a replica's role in the router's placement tables
        AFTER its engine's ``set_role`` (the autoscaler's prefill<->decode
        flip); validates first (``validate_replica_role``)."""
        self.validate_replica_role(name, role)
        r = self._replicas[name]
        if role == "prefill":
            self._tier = r.engine.kv_swap
        self._roles[name] = role
        self._has_prefill = any(v == "prefill" for v in self._roles.values())
        r.engine.telemetry.set_base_labels(role=role)

    def _begin_drain(self, name: str, tick: int) -> None:
        r = self._replicas[name]
        if r.status != HEALTHY:
            return
        r.status = DRAINING
        r.engine.begin_drain()
        self.counters["drains"] += 1
        self._flight_note("drain_begin", replica=name, tick=tick)
        logger.warning(f"router: draining replica {name} at tick {tick}")

    def _finish_drain(self, r: _Replica, tick: int) -> None:
        """Live rows are done: migrate the held queue (engine ledger ==
        queued requests now) plus any unpolled feed items, close the
        generator, and retire the replica from the ring."""
        snap = r.engine.snapshot_serving_state()
        self._close_gen(r)
        r.engine.end_drain()
        r.status = DRAINED
        exclude = frozenset((r.name,))
        migrated = 0
        for item in list(r.feed):
            self._place(item, exclude)
            migrated += 1
        r.feed.clear()
        for item in self._restamp_affinity(snapshot_split(snap)):
            self._place(item, exclude)
            migrated += 1
        self.counters["drain_migrated"] += migrated
        logger.warning(f"router: replica {r.name} drained at tick {tick}; "
                       f"{migrated} queued requests migrated")

    # ------------------------------------------------------------------
    # the serve loop
    # ------------------------------------------------------------------

    def _step(self, r: _Replica, tick: int, serve_kwargs: Dict,
              scheduler_factory=None):
        """Advance one replica by one frame boundary, collecting any
        retirements it yielded on the way. Crash handling lives here:
        ``FrameDispatchError`` escaping the generator IS the engine's
        retry-exhaustion signal, and ``last_crash_snapshot`` was taken
        before it propagated."""
        done: List[Tuple[int, object]] = []
        if r.gen is None:
            if r.status not in (HEALTHY, DRAINING):
                return done
            kwargs = dict(serve_kwargs)
            if scheduler_factory is not None:
                # one policy object per serve run per replica — scheduler
                # state is engine-local (a rejoining replica gets a fresh
                # one; its queues were migrated away at failure)
                kwargs["scheduler"] = scheduler_factory()
            r.gen = r.engine.serve(r.feed_iter(), yield_boundaries=True,
                                   **kwargs)
        step_t0 = self._clock()
        try:
            while True:
                item = next(r.gen)
                if isinstance(item, ServeBoundary):
                    hb_fail = self._note_heartbeat(r, item, tick, step_t0)
                    if hb_fail is not None:
                        snap = r.engine.snapshot_serving_state()
                        self._fail_replica(r, tick, "missed_heartbeat",
                                           hb_fail, snap)
                    break
                if isinstance(item, HandoffEvent):
                    self._handle_handoff(r, item, tick)
                    continue
                uid, toks = item
                self._finish(uid)
                done.append((uid, toks))
        except StopIteration:
            r.gen = None
            if r.status == HEALTHY:
                r.status = CLOSED
        except FrameDispatchError as e:
            snap = r.engine.last_crash_snapshot
            r.gen = None
            self._fail_replica(r, tick, "engine_crash", str(e), snap)
        return done

    def _handle_handoff(self, r: "_Replica", ev: HandoffEvent,
                        tick: int) -> None:
        """A prefill replica finished ``ev.uid``'s prefill: its pages sit
        in the shared tier and ``ev.arrival`` is the resume arrival — re-
        place it on the decode side (the classification sees its
        committed tokens and never routes it back to a prefill replica;
        session affinity is re-stamped so a session's decode lands with
        its siblings). Placement failure parks it in ``_unplaced`` like
        any other arrival — it retries every tick and the in-flight
        accounting (``_assignment``) keeps serve() from shutting down
        under it."""
        self.counters["handoffs"] += 1
        if not ev.published:
            self.counters["handoffs_unpublished"] += 1
        self._assignment.pop(ev.uid, None)
        tr = self._trace_of(ev.arrival)
        self._flight_note("handoff", replica=r.name, uid=ev.uid, tick=tick,
                          published=ev.published,
                          trace=tr.get("id") if tr else None)
        self._restamp_affinity([ev.arrival])
        self._place(ev.arrival)

    def _drop_tier_record(self, uid: int) -> None:
        """A request failed terminally at the ROUTER (re-route budget /
        unservable prompt): its handoff pages in the shared tier are now
        orphaned — release them (engines drop records only for requests
        they retire themselves)."""
        if self._tier is not None:
            self._tier.drop_request(uid)

    def _finish(self, uid: int) -> None:
        self._assignment.pop(uid, None)
        self._affinity.pop(uid, None)
        self._reroute_hops.pop(uid, None)
        self.counters["completions"] += 1

    def _reap_engine_retired(self) -> None:
        """Clear assignments for requests an engine retired WITHOUT
        yielding them — deadline expiry, poison-row quarantine, and
        scheduler sheds all end a request at a boundary with only a fault
        /shed record. Without this, the shutdown condition (`nothing in
        _assignment`) would never hold and serve() would spin forever.
        A uid assigned to a LIVE replica that is in neither its feed nor
        its engine ledger is gone (feed items enter the ledger the
        boundary they are polled); failed-over uids are skipped — they
        ride _deferred/_unplaced until re-placed."""
        pending = {self._uid_of(i) for _, i, _ in self._deferred}
        pending |= {self._uid_of(i) for i, _ in self._unplaced}
        for uid, name in list(self._assignment.items()):
            r = self._replicas[name]
            if r.status in (QUARANTINED, DEAD) or uid in pending:
                continue
            if uid in r.engine._ledger or \
                    any(self._uid_of(i) == uid for i in r.feed):
                continue
            self._assignment.pop(uid, None)
            self._affinity.pop(uid, None)
            self._reroute_hops.pop(uid, None)
            self.counters["engine_retired"] += 1

    def _restamp_affinity(self, items: List[Dict]) -> List[Dict]:
        """Re-attach each snapshot-resumed request's original affinity key
        (the ledger never stored it) so the session re-places as a unit."""
        for item in items:
            key = self._affinity.get(self._uid_of(item))
            if key is not None:
                item.setdefault("session", key)
        return items

    def serve(self, arrivals: Iterable, *, max_new_tokens: int = 32,
              temperature: float = 0.0, eos_token_id: Optional[int] = None,
              scheduler_factory=None, faults=None,
              engine_kwargs: Optional[Dict] = None):
        """Serve one arrival stream across the replica fleet.

        Generator yielding ``(uid, generated_tokens)`` as requests finish
        on ANY replica. ``arrivals`` has the same iterator contract as
        ``InferenceEngineV2.serve`` — polled once per router tick; dict
        arrivals may additionally carry ``session`` (the affinity key;
        falls back to ``tenant``, then uid). ``scheduler_factory`` (a
        zero-arg callable) builds one ``RequestScheduler`` PER replica —
        policy objects are engine-local. ``faults`` takes a
        ``RouterFaultInjector`` whose scripted engine_kill/engine_drain
        events drive the chaos tests deterministically. ``engine_kwargs``
        passes extra serve() options (frame_steps, speculate, ...) to
        every replica.

        One router tick = poll arrivals → place → step every live replica
        one frame boundary → handle drains/rejoins. All failover
        re-admission flows through resume arrivals
        (``faults.snapshot_split``), so greedy outputs are token-identical
        to a no-failure run.

        With ``RouterConfig(driver="threaded")`` this delegates to the
        thread-per-replica ``service.fleet.FleetDriver`` — same arrival
        contract, same policy state, same (uid, tokens) stream, with
        every replica's frames overlapping on its own worker thread.
        The serial loop below stays the deterministic chaos driver."""
        if self.cfg.driver == "threaded":
            from .service.fleet import FleetDriver
            return FleetDriver(self).serve(
                arrivals, max_new_tokens=max_new_tokens,
                temperature=temperature, eos_token_id=eos_token_id,
                scheduler_factory=scheduler_factory, faults=faults,
                engine_kwargs=engine_kwargs)
        if self.cfg.driver != "serial":
            raise ValueError(f"RouterConfig.driver={self.cfg.driver!r}: "
                             "expected 'serial' or 'threaded'")
        return self._serve_serial(
            arrivals, max_new_tokens=max_new_tokens, temperature=temperature,
            eos_token_id=eos_token_id, scheduler_factory=scheduler_factory,
            faults=faults, engine_kwargs=engine_kwargs)

    def _serve_serial(self, arrivals, *, max_new_tokens=32, temperature=0.0,
                      eos_token_id=None, scheduler_factory=None, faults=None,
                      engine_kwargs=None):
        """The cooperative single-thread stepping loop (see ``serve``)."""
        cfg = self.cfg
        self._serve_limit = max_new_tokens   # classification denominator
        serve_kwargs = dict(max_new_tokens=max_new_tokens,
                            temperature=temperature,
                            eos_token_id=eos_token_id,
                            **(engine_kwargs or {}))
        arrivals = iter(arrivals)
        exhausted = False
        tick = -1
        recovery_t0: Optional[float] = None
        # fresh run: per-request routing state from an earlier (possibly
        # abandoned) serve must not leak into this one — an orphaned
        # resume still parked in _deferred/_unplaced would otherwise be
        # served under a NEW tick clock and yield a uid this call's
        # consumer never submitted (the engines reset their own serve
        # state the same way at entry). Health survives across calls;
        # rejoin_tick was relative to the previous clock, so re-arm it.
        self._assignment.clear()
        self._affinity.clear()
        self._reroute_hops.clear()
        self._deferred = []
        self._unplaced.clear()
        for r in self._replicas.values():
            r.feed.clear()
            if r.status == CLOSED:
                r.status = HEALTHY   # the old generator is gone anyway
            if r.status == QUARANTINED and r.rejoin_tick is not None:
                r.rejoin_tick = cfg.quarantine_backoff_ticks * \
                    (2 ** (r.failures - 1))
        if faults is not None:
            faults.begin()
        # abandonment safety: a consumer that breaks out of (or
        # closes) this generator mid-serve must still run every
        # replica engine's own serve-generator cleanup (slot/KV/
        # ledger teardown) — and a later serve() call must start
        # fresh generators, not keep stepping stale ones with the
        # previous call's parameters
        try:
            while True:
                tick += 1
                self._tick = tick
                # scripted router faults (deterministic chaos clock)
                if faults is not None:
                    for name in faults.drains(tick):
                        self.drain(name)
                    for name in faults.kills(tick):
                        if self._kill(name, tick, "scripted engine_kill"):
                            recovery_t0 = self._clock()
                self._maybe_rejoin(tick)
                for name in sorted(self._pending_drains):
                    self._begin_drain(name, tick)
                # keep the intent for replicas that cannot drain YET (e.g.
                # quarantined after failing mid-drain — they must drain on
                # rejoin, not resume accepting placements)
                self._pending_drains = {
                    n for n in self._pending_drains
                    if self._replicas[n].status == QUARANTINED}
                # global arrival poll (once per tick)
                if not exhausted:
                    try:
                        batch = next(arrivals)
                    except StopIteration:
                        exhausted = True
                        batch = None
                    for item in (batch or []):
                        self._place(item)
                # deferred failover re-placements whose backoff expired, then
                # anything that could not be placed earlier (capacity returns
                # when a replica rejoins)
                due = [d for d in self._deferred if d[0] <= tick]
                self._deferred = [d for d in self._deferred if d[0] > tick]
                for _, item, exclude in due:
                    self._place(item, exclude)
                for _ in range(len(self._unplaced)):
                    item, exclude = self._unplaced.popleft()
                    self._place(item, exclude)
                # recovery window: last kill → every orphaned request back on
                # a healthy peer's feed (the engines' own recovery gauges
                # cover re-admission from there)
                if recovery_t0 is not None and not self._deferred \
                        and not self._unplaced:
                    self.last_recovery_ms = round(
                        (self._clock() - recovery_t0) * 1e3, 3)
                    recovery_t0 = None
                # step the fleet — one frame boundary per replica per tick
                for r in list(self._replicas.values()):
                    for uid, toks in self._step(r, tick, serve_kwargs,
                                                scheduler_factory):
                        yield uid, toks
                    if r.status == DRAINING and r.last_boundary is not None \
                            and r.last_boundary.live == 0:
                        self._finish_drain(r, tick)
                # engines retire some requests WITHOUT yielding (deadline
                # expiry, quarantine, scheduler shed) — reconcile so those
                # don't strand the shutdown condition below
                self._reap_engine_retired()
                # shutdown: nothing in flight, nothing queued anywhere
                if exhausted and not self._assignment and not self._deferred \
                        and not self._unplaced:
                    break
            # close every live generator cleanly (feeds drain to StopIteration)
            for r in self._replicas.values():
                r.closing = True
            for r in self._replicas.values():
                while r.gen is not None:
                    try:
                        item = next(r.gen)
                    except StopIteration:
                        r.gen = None
                        break
                    except FrameDispatchError:
                        r.gen = None
                        break
                    if isinstance(item, HandoffEvent):
                        # unreachable in practice (the main loop only
                        # exits with zero in-flight requests), but a
                        # handoff must never be dropped on the floor
                        self._handle_handoff(r, item, tick)
                    elif not isinstance(item, ServeBoundary):
                        self._finish(item[0])
                        yield item
                r.closing = False
        finally:
            for r in self._replicas.values():
                self._close_gen(r)
                r.closing = False
