"""Fleet-wide distributed request tracing + crash flight recorder.

Every telemetry span before this module lived and died inside ONE engine:
a handed-off or failed-over request had no end-to-end timeline, and PR 11
had to drop TTFT on resumed spans because attribution was per-replica.
This module is the fleet-level observability layer (README "Distributed
tracing & flight recorder"; the serving-system observability tier of
DeepSpeed Inference, arXiv 2207.00032), in three pieces:

1. **Trace context** — a trace id minted ONCE per request at the edge (or
   at router ingestion, or by a bare engine) rides the arrival dict as
   ``item["trace"] = {"id": ..., "parent": <root span id>}`` and is
   propagated through ``LedgerEntry`` -> ``snapshot_serving_state`` ->
   ``snapshot_split`` resume arrivals and ``HandoffEvent`` arrivals, so
   one request is ONE connected span tree across replicas, handoffs, and
   failovers. Every span's ``parent`` is either ``None`` (the root) or a
   span id present in the same trace — ``validate_trace`` checks exactly
   that, and ``bin/dstpu_trace`` turns it into a CI gate.

2. **TraceCollector** — a thread-safe bounded store of those spans.
   Producers stamp spans ONLY at frame boundaries (host timestamps the
   serve loops already take — zero in-frame device reads; the transfer
   guard stays green by construction), and the fleet driver's worker
   threads feed it exactly where they already report boundaries. Exports:
   Chrome-trace-event JSON (``chrome://tracing`` / Perfetto "Open trace
   file"), JSONL, and per-request lookup (``ServiceEdge`` serves all
   three at ``GET /debug/trace``). ``sample_rate`` bounds retention —
   but faulted / shed / handed-off / failed-over / cancelled requests are
   ALWAYS kept (``mark()``): the traces worth debugging are precisely the
   ones a uniform sampler would lose.

   The collector also owns the fleet-level *true* end-to-end histograms:
   ``ds_fleet_ttft_ms`` / ``ds_fleet_e2e_ms`` record exactly ONE sample
   per trace id — whichever replica emits the trace's first token records
   TTFT against the trace's mint time, spanning handoff and failover.
   This restores the attribution PR 11 had to give up (per-replica
   ``ds_serving_ttft_seconds`` series are unchanged: resumed spans still
   record nothing locally). Histogram recording is independent of span
   sampling — an unsampled trace still counts.

   With that one sample goes the request's TIME TO FIRST TOKEN BY STAGE
   (``telemetry.TTFT_STAGES``; README "Time to first token, stage by
   stage"): ``note_first_token`` cuts mint -> ``note_placed`` -> the
   serve loop's poll -> admit -> first token -> ``note_first_write`` into
   nanoseconds that tile the total, and sums them per replica
   (``ttft_totals``); each replica's serve loop mirrors its own into
   ``telemetry.counters``, so they ride ``/metrics``. The serve loop's
   own histograms start at its poll and cannot see a request wait in
   the replica's feed for the frame in flight.

3. **FlightRecorder** — a bounded ring of structured fleet events
   (placements, heartbeats, faults, kills, tier commits, autoscale
   actions) plus a postmortem dump: on replica DEAD, on an engine crash
   snapshot, or on SIGINT (``install_signal_handler``), the recorder
   writes a bundle — the last-N events, every in-flight request's trace,
   and the fleet latency summaries — to ``dump_dir``. The bundle is what
   you read AFTER the process is gone, so it is plain JSON on disk, not
   an endpoint.

Everything here is host-side bookkeeping behind one lock, touched at
frame boundaries and service-edge events only; no compiled program
changes (``.graft-cost-baseline.json`` stays byte-identical).
"""

import collections
import hashlib
import json
import os
import signal
import threading
import time
from typing import Dict, List, Optional, Tuple

from ...utils.logging import logger
from .telemetry import TTFT_COUNTERS, TTFT_STAGES, LogBucketHistogram

#: marks that force retention regardless of ``sample_rate`` — the
#: always-sample set the ISSUE pins (plus cancel/preempt, which are the
#: disconnect-debugging traces)
IMPORTANT_MARKS = ("fault", "shed", "handoff", "failover", "cancelled",
                  "disconnect")

#: flight-recorder event kinds that trigger an automatic postmortem dump
AUTO_DUMP_KINDS = ("replica_dead", "engine_crash")


def _ns(t: float) -> int:
    return int(round(t * 1e9))


def _frac_of(trace_id: str) -> float:
    """Deterministic uniform fraction of a trace id (sha1-based), so the
    sampling decision is reproducible given the id — no RNG state."""
    h = hashlib.sha1(trace_id.encode()).digest()
    return int.from_bytes(h[:8], "big") / float(1 << 64)


def validate_trace(spans: List[Dict]) -> List[str]:
    """Connectivity check for one trace's span list: exactly one trace
    id, exactly one root (``parent is None``), and every non-root span's
    parent present in the trace (an intact parent chain). Returns a list
    of problems — empty means the trace is one connected tree. Used by
    the continuity tests and the ``dstpu_trace`` CI gate."""
    problems: List[str] = []
    if not spans:
        return ["trace has no spans"]
    tids = {s.get("trace") for s in spans}
    if len(tids) != 1:
        problems.append(f"spans carry {len(tids)} distinct trace ids: "
                        f"{sorted(str(t) for t in tids)}")
    sids = {s["sid"] for s in spans}
    roots = [s for s in spans if s.get("parent") is None]
    if len(roots) != 1:
        problems.append(f"expected exactly 1 root span, found "
                        f"{len(roots)}: {[s['name'] for s in roots]}")
    for s in spans:
        p = s.get("parent")
        if p is not None and p not in sids:
            problems.append(f"orphan span {s['name']!r} (sid={s['sid']}): "
                            f"parent {p!r} not in trace")
    return problems


class TraceCollector:
    """Thread-safe bounded distributed-trace store (see module
    docstring). ``clock`` is injectable for deterministic tests; all ids
    are sequential (sampling hashes them, so retention is still uniform).
    """

    def __init__(self, sample_rate: float = 1.0, max_traces: int = 512,
                 max_spans_per_trace: int = 512, clock=time.monotonic):
        if not 0.0 <= sample_rate <= 1.0:
            raise ValueError(f"sample_rate={sample_rate} not in [0, 1]")
        self.sample_rate = sample_rate
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self.clock = clock
        self._lock = threading.RLock()
        self._seq = 0
        # open (in-flight) traces + finished retained ones, both bounded
        self._open: "collections.OrderedDict[str, Dict]" = \
            collections.OrderedDict()
        self._done: "collections.OrderedDict[str, Dict]" = \
            collections.OrderedDict()
        # one-TTFT/E2E-per-trace-id bookkeeping (independent of sampling)
        self._ttft_done: set = set()
        self._e2e_done: set = set()
        self.fleet_ttft = LogBucketHistogram()
        self.fleet_e2e = LogBucketHistogram()
        self.counters: Dict[str, int] = dict(
            traces_minted=0, traces_retained=0, traces_dropped=0,
            spans_recorded=0, spans_truncated=0, ttft_samples=0,
            e2e_samples=0)
        # TTFT by stage (TTFT_COUNTERS), summed per replica that emitted
        # the first token; like the histograms, independent of sampling.
        # A trace whose edge streams waits here, from its first token to
        # its first write, outside the trace's own record: an unsampled
        # trace is dropped when its engine retires it, which a short
        # request does before the edge has written anything
        self._ttft_totals: Dict[Optional[str], Dict[str, int]] = {}
        self._ttft_pending: "collections.OrderedDict[str, Dict]" = \
            collections.OrderedDict()

    # ------------------------------------------------------------------
    # span production
    # ------------------------------------------------------------------

    def mint(self, name: str = "request", replica: str = "edge",
             t: Optional[float] = None, attrs: Optional[Dict] = None,
             awaits_write: bool = False) -> Tuple[str, str]:
        """Create a new trace with its root span open; returns
        ``(trace_id, root_span_id)`` — the ``{"id", "parent"}`` context
        the arrival dict carries from here on. The root span id is
        always ``"s0"`` (per-trace span ids are sequential).
        ``awaits_write``: whoever mints it will report the request's
        first write to the client (``note_first_write``), so its TTFT
        sample waits for that; otherwise the sample ends at the first
        token's emission."""
        with self._lock:
            self._seq += 1
            tid = f"t{self._seq:08x}"
            t = self.clock() if t is None else t
            root = {"trace": tid, "sid": "s0", "parent": None, "name": name,
                    "replica": replica, "t0": t, "t1": None, "status": None,
                    "attrs": dict(attrs or {})}
            self._open[tid] = {
                "id": tid, "t0": t, "t_last": t, "nspans": 1, "seq": 1,
                "spans": [root], "marks": [], "status": None,
                "uid": (attrs or {}).get("uid"),
                "awaits_write": awaits_write, "place_t": None,
            }
            self.counters["traces_minted"] += 1
            # bound the open set: a leaked/abandoned trace must not grow
            # memory forever — evict the oldest open trace past 4x budget
            while len(self._open) > 4 * self.max_traces:
                old_tid, old = self._open.popitem(last=False)
                self._finalize(old_tid, old)
            return tid, "s0"

    def _trace(self, trace_id) -> Optional[Dict]:
        tr = self._open.get(trace_id)
        if tr is None:
            tr = self._done.get(trace_id)
        return tr

    def span(self, trace_id: str, name: str, t0: float,
             t1: Optional[float] = None, parent: Optional[str] = None,
             replica: Optional[str] = None, status: Optional[str] = None,
             attrs: Optional[Dict] = None) -> Optional[str]:
        """Append one completed span (``t1=None`` records an instant).
        Returns the span id, or None when the trace is unknown (already
        evicted) or its span budget is exhausted."""
        with self._lock:
            tr = self._trace(trace_id)
            if tr is None:
                return None
            if tr["nspans"] >= self.max_spans_per_trace:
                self.counters["spans_truncated"] += 1
                return None
            sid = f"s{tr['seq']}"
            tr["seq"] += 1
            tr["nspans"] += 1
            tr["spans"].append({
                "trace": trace_id, "sid": sid, "parent": parent,
                "name": name, "replica": replica, "t0": t0,
                "t1": t0 if t1 is None else t1, "status": status,
                "attrs": dict(attrs or {})})
            tr["t_last"] = max(tr["t_last"], t0 if t1 is None else t1)
            self.counters["spans_recorded"] += 1
            return sid

    def instant(self, trace_id: str, name: str, t: Optional[float] = None,
                parent: Optional[str] = None, replica: Optional[str] = None,
                attrs: Optional[Dict] = None) -> Optional[str]:
        """Zero-duration span (placement decisions, emissions, SSE
        writes, tier publishes)."""
        return self.span(trace_id, name, self.clock() if t is None else t,
                         parent=parent, replica=replica, attrs=attrs)

    def mark(self, trace_id: str, mark: str) -> None:
        """Flag a trace as always-sampled (fault/shed/handoff/failover/
        cancelled — see ``IMPORTANT_MARKS``; unknown marks still force
        retention, the taxonomy is advisory)."""
        with self._lock:
            tr = self._trace(trace_id)
            if tr is not None and mark not in tr["marks"]:
                tr["marks"].append(mark)

    def note_placed(self, trace_id: str, t: float) -> None:
        """The router is about to append the request to a replica's feed
        (the ``router.place`` instant): where ``ingress`` ends and
        ``feed`` begins. A re-placement (failover, handoff, drain)
        overwrites it: the time lost to the first try is ``ingress``."""
        with self._lock:
            tr = self._trace(trace_id)
            if tr is not None:
                tr["place_t"] = t

    def placed_at(self, trace_id: str) -> Optional[float]:
        with self._lock:
            tr = self._trace(trace_id)
            return None if tr is None else tr["place_t"]

    def note_first_token(self, trace_id: str, t: float, *,
                         replica: Optional[str] = None,
                         poll_t: Optional[float] = None,
                         admit_t: Optional[float] = None,
                         frames: int = 0) -> Optional[Dict[str, int]]:
        """Record the trace's FIRST first-token time — exactly one
        fleet-TTFT sample per trace id, whichever replica got there
        first (handoff: the prefill replica; failover: the original
        unless it died before emitting). Independent of span sampling.

        This is also where the request's time is cut into TTFT_STAGES,
        the one place that computes them: mint -> ``note_placed`` ->
        ``poll_t`` (the serve loop took it from its feed) -> ``admit_t``
        -> ``t`` -> ``note_first_write``, in whole nanoseconds so that
        the stages tile the total exactly. A stamp nobody gave (a bare
        engine has no router, a unit test no serve loop) falls on the
        one before it: that stage is 0. Returns the stages known now
        (all but ``egress``), or None if the trace had its sample."""
        with self._lock:
            tr = self._trace(trace_id)
            if tr is None or trace_id in self._ttft_done:
                return None
            self._ttft_done.add(trace_id)
            self.fleet_ttft.record(max(0.0, t - tr["t0"]))
            self.counters["ttft_samples"] += 1
            at = _ns(tr["t0"])
            stages = {}
            # four stamps, so four stages: ``egress`` ends at the write
            for stage, stamp in zip(TTFT_STAGES, (tr["place_t"], poll_t,
                                                  admit_t, t)):
                end = at if stamp is None else max(at, _ns(stamp))
                stages[stage], at = end - at, end
            rec = {"replica": replica, "first_ns": at, "frames": frames,
                   "stages": stages}
            if tr["awaits_write"]:
                self._ttft_pending[trace_id] = rec
                # a stream that never wrote (a client gone before its
                # first token) leaves its record here: bound them
                while len(self._ttft_pending) > 4 * self.max_traces:
                    self._ttft_pending.popitem(last=False)
            else:
                self._fold_ttft(rec, at)
            return dict(stages)

    def note_first_write(self, trace_id: str, t: float) -> None:
        """The edge has written and flushed the request's first ``token``
        event: ``egress`` ends, and the request's stages reach the
        totals of the replica that emitted the token."""
        with self._lock:
            rec = self._ttft_pending.pop(trace_id, None)
            if rec is not None:
                self._fold_ttft(rec, max(rec["first_ns"], _ns(t)))

    def _fold_ttft(self, rec: Dict, end_ns: int) -> None:
        tot = self._ttft_totals.setdefault(
            rec["replica"], dict.fromkeys(TTFT_COUNTERS, 0))
        stages = dict(rec["stages"], egress=end_ns - rec["first_ns"])
        tot["ttft_requests"] += 1
        tot["ttft_total_ns"] += sum(stages.values())
        for stage, ns in stages.items():
            tot[f"ttft_{stage}_ns"] += ns
        tot["ttft_prefill_frames"] += rec["frames"]

    def ttft_totals(self, replica: Optional[str] = None) -> Dict[str, int]:
        """TTFT_COUNTERS of the first tokens ``replica`` emitted, since
        this collector was made (a copy: the replica's serve loop mirrors
        it into its own counters at a frame boundary)."""
        with self._lock:
            return dict(self._ttft_totals.get(replica)
                        or dict.fromkeys(TTFT_COUNTERS, 0))

    def note_done(self, trace_id: str, t: float) -> None:
        """One fleet end-to-end sample per trace id (mint -> retire)."""
        with self._lock:
            tr = self._trace(trace_id)
            if tr is None or trace_id in self._e2e_done:
                return
            self._e2e_done.add(trace_id)
            self.fleet_e2e.record(max(0.0, t - tr["t0"]))
            self.counters["e2e_samples"] += 1

    def finish(self, trace_id: str, t: Optional[float] = None,
               status: Optional[str] = None) -> None:
        """Close the trace's root span and apply the sampling decision.
        Idempotent: the first call sets the status and samples; later
        calls (the edge closing its stream after the engine retired) only
        extend the root span's end time."""
        with self._lock:
            t = self.clock() if t is None else t
            tr = self._open.pop(trace_id, None)
            if tr is None:
                tr = self._done.get(trace_id)
                if tr is not None:
                    root = tr["spans"][0]
                    root["t1"] = max(root["t1"] or t, t)
                    tr["t_last"] = max(tr["t_last"], t)
                return
            root = tr["spans"][0]
            root["t1"] = max(root["t0"], t)
            if root["status"] is None:
                root["status"] = status
            tr["status"] = status
            tr["t_last"] = max(tr["t_last"], t)
            self._finalize(trace_id, tr)

    def _finalize(self, trace_id: str, tr: Dict) -> None:
        keep = bool(tr["marks"]) or \
            _frac_of(trace_id) < self.sample_rate
        if not keep:
            self.counters["traces_dropped"] += 1
            self._ttft_done.discard(trace_id)
            self._e2e_done.discard(trace_id)
            return
        self._done[trace_id] = tr
        self.counters["traces_retained"] += 1
        while len(self._done) > self.max_traces:
            old_tid, _ = self._done.popitem(last=False)
            self._ttft_done.discard(old_tid)
            self._e2e_done.discard(old_tid)

    # ------------------------------------------------------------------
    # lookup / export
    # ------------------------------------------------------------------

    def traces(self, include_open: bool = True) -> List[Dict]:
        """Snapshot of retained (and optionally in-flight) traces, oldest
        first; each entry is ``{"id", "t0", "status", "marks", "uid",
        "open", "spans": [...]}`` with spans copied (safe to serialize
        while serving continues)."""
        with self._lock:
            out = []
            for store, is_open in ((self._done, False),
                                   (self._open, True)):
                if is_open and not include_open:
                    continue
                for tid, tr in store.items():
                    out.append({
                        "id": tid, "t0": tr["t0"], "status": tr["status"],
                        "marks": list(tr["marks"]), "uid": tr["uid"],
                        "open": is_open,
                        "spans": [dict(s) for s in tr["spans"]]})
            out.sort(key=lambda t: t["t0"])
            return out

    def get(self, trace_id: Optional[str] = None,
            uid: Optional[int] = None) -> Optional[Dict]:
        """Per-request lookup by trace id or by uid (the LAST trace
        minted for that uid wins — uids may be reused across serve
        runs)."""
        with self._lock:
            if trace_id is None and uid is not None:
                # metadata scan only (newest mint wins — uids may be
                # reused across serve runs; ids are zero-padded, so max()
                # is mint order); copying every retained trace's spans to
                # find one uid would stall the span producers blocked on
                # this lock
                hits = [tid for store in (self._open, self._done)
                        for tid, tr in store.items() if tr["uid"] == uid]
                trace_id = max(hits) if hits else None
            if trace_id is None:
                return None
            tr = self._trace(trace_id)
            if tr is None:
                return None
            return {"id": tr["id"], "t0": tr["t0"],
                    "status": tr["status"], "marks": list(tr["marks"]),
                    "uid": tr["uid"], "open": trace_id in self._open,
                    "spans": [dict(s) for s in tr["spans"]]}

    def in_flight_traces(self) -> List[Dict]:
        """The open traces only — the flight recorder's postmortem set."""
        return [t for t in self.traces() if t["open"]]

    def export_jsonl(self, traces: Optional[List[Dict]] = None) -> str:
        """One span per line (the ``dstpu_trace`` input format)."""
        traces = self.traces() if traces is None else traces
        lines = []
        for tr in traces:
            for s in tr["spans"]:
                lines.append(json.dumps(s, sort_keys=True))
        return "\n".join(lines) + ("\n" if lines else "")

    def export_chrome(self, traces: Optional[List[Dict]] = None) -> Dict:
        """Chrome-trace-event JSON (``chrome://tracing`` / Perfetto "Open
        trace file"): one *process* lane per replica, one *thread* lane
        per trace inside it, span times in µs relative to the earliest
        root. Completed spans are ``ph="X"``, instants ``ph="i"``."""
        traces = self.traces() if traces is None else traces
        events: List[Dict] = []
        if not traces:
            return {"traceEvents": [], "displayTimeUnit": "ms"}
        epoch = min(t["t0"] for t in traces)
        replicas: Dict[str, int] = {}
        for ti, tr in enumerate(traces, start=1):
            for s in tr["spans"]:
                rep = s.get("replica") or "fleet"
                if rep not in replicas:
                    pid = len(replicas) + 1
                    replicas[rep] = pid
                    events.append({"ph": "M", "name": "process_name",
                                   "pid": pid, "tid": 0,
                                   "args": {"name": rep}})
                pid = replicas[rep]
                ts = (s["t0"] - epoch) * 1e6
                args = {"trace": s["trace"], "sid": s["sid"],
                        "parent": s["parent"], "status": s["status"],
                        **(s.get("attrs") or {})}
                base = {"name": s["name"], "cat": "serving", "pid": pid,
                        "tid": ti, "ts": round(ts, 3), "args": args}
                t1 = s["t1"] if s["t1"] is not None else s["t0"]
                if t1 > s["t0"]:
                    events.append({**base, "ph": "X",
                                   "dur": round((t1 - s["t0"]) * 1e6, 3)})
                else:
                    events.append({**base, "ph": "i", "s": "t"})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def snapshot(self) -> Dict:
        """Counters + fleet latency summaries, plain python."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "open": len(self._open), "retained": len(self._done),
                "sample_rate": self.sample_rate,
                "fleet_ttft_ms": _ms_summary(self.fleet_ttft),
                "fleet_e2e_ms": _ms_summary(self.fleet_e2e),
            }

    def render_prometheus(self) -> str:
        """``ds_trace_*`` counters + the fleet-merged ``ds_fleet_ttft_ms``
        / ``ds_fleet_e2e_ms`` summaries (exactly one sample per trace id —
        the true cross-replica attribution)."""
        with self._lock:
            lines: List[str] = []
            for name, val in self.counters.items():
                full = f"ds_trace_{name}_total"
                lines.append(f"# TYPE {full} counter")
                lines.append(f"{full} {val}")
            lines.append("# TYPE ds_trace_open_traces gauge")
            lines.append(f"ds_trace_open_traces {len(self._open)}")
            lines.append("# TYPE ds_trace_retained_traces gauge")
            lines.append(f"ds_trace_retained_traces {len(self._done)}")
            for metric, hist in (("ds_fleet_ttft_ms", self.fleet_ttft),
                                 ("ds_fleet_e2e_ms", self.fleet_e2e)):
                lines.append(f"# TYPE {metric} summary")
                for p in (50, 90, 99):
                    q = hist.percentile(p)
                    if q is not None:
                        lines.append(f'{metric}{{quantile="0.{p}"}} '
                                     f"{q * 1e3:g}")
                lines.append(f"{metric}_sum {hist.sum * 1e3:g}")
                lines.append(f"{metric}_count {hist.total}")
            return "\n".join(lines) + "\n"


def _ms_summary(hist: LogBucketHistogram) -> Dict:
    s = hist.summary()
    return {"count": s["count"],
            **{p: (round(s[p] * 1e3, 3) if s[p] is not None else None)
               for p in ("p50", "p90", "p99")}}


class FlightRecorder:
    """Bounded ring of structured fleet events + postmortem bundle dump
    (see module docstring). ``collector`` (a ``TraceCollector``) supplies
    the in-flight traces the bundle snapshots; ``dump_dir=None`` keeps
    the bundle in memory only (``last_bundle``) — tests and embedded
    users read it there, services point it at a real directory."""

    def __init__(self, collector: Optional[TraceCollector] = None,
                 max_events: int = 1024, dump_dir: Optional[str] = None,
                 auto_dump: bool = True, clock=time.monotonic):
        self.collector = collector
        self.dump_dir = dump_dir
        self.auto_dump = auto_dump
        self.clock = clock
        self._lock = threading.RLock()
        self.events: collections.deque = collections.deque(maxlen=max_events)
        self.counters: Dict[str, int] = dict(events=0, dumps=0)
        self.dumps: List[str] = []          # paths written (in order)
        self.last_bundle: Optional[Dict] = None
        self._prev_sigint = None

    def record(self, kind: str, replica: Optional[str] = None,
               uid: Optional[int] = None, trace: Optional[str] = None,
               detail: str = "", tick: Optional[int] = None,
               **attrs) -> None:
        """Append one fleet event; ``AUTO_DUMP_KINDS`` (replica death,
        crash snapshot) trigger the postmortem dump inline — the events
        that precede a death must be on disk before anyone asks."""
        ev = {"t": round(self.clock(), 6), "kind": kind}
        for k, v in (("replica", replica), ("uid", uid), ("trace", trace),
                     ("tick", tick)):
            if v is not None:
                ev[k] = v
        if detail:
            ev["detail"] = detail
        if attrs:
            ev.update(attrs)
        with self._lock:
            self.events.append(ev)
            self.counters["events"] += 1
        if self.auto_dump and kind in AUTO_DUMP_KINDS:
            self.dump(reason=f"{kind}:{replica or ''}")

    def bundle(self, reason: str) -> Dict:
        """Assemble the postmortem bundle: ring + in-flight traces +
        fleet latency summaries. Pure read — safe while serving runs."""
        with self._lock:
            events = list(self.events)
        out = {
            "format": "dstpu-flight-bundle/1",
            "reason": reason,
            "created_unix": time.time(),
            "n_events": len(events),
            "events": events,
        }
        if self.collector is not None:
            out["in_flight_traces"] = self.collector.in_flight_traces()
            out["fleet_latency"] = self.collector.snapshot()
        return out

    def dump(self, reason: str = "manual",
             path: Optional[str] = None) -> Optional[str]:
        """Write the bundle to disk (``dump_dir`` or an explicit
        ``path``); returns the path, or None when memory-only. The bundle
        is always kept as ``last_bundle`` either way."""
        b = self.bundle(reason)
        with self._lock:
            self.last_bundle = b
            self.counters["dumps"] += 1
            n = self.counters["dumps"]
        if path is None:
            if self.dump_dir is None:
                return None
            os.makedirs(self.dump_dir, exist_ok=True)
            tag = "".join(c if c.isalnum() or c in "-_" else "_"
                          for c in reason)[:48]
            path = os.path.join(self.dump_dir,
                                f"flight_{n:04d}_{tag}.json")
        try:
            with open(path, "w") as f:
                json.dump(b, f, indent=1)
        except OSError as e:
            logger.warning(f"FlightRecorder: dump to {path} failed: {e}")
            return None
        self.dumps.append(path)
        logger.warning(f"FlightRecorder: postmortem bundle "
                       f"({b['n_events']} events, reason={reason!r}) "
                       f"written to {path}")
        return path

    def install_signal_handler(self, signum: int = signal.SIGINT) -> None:
        """Dump a postmortem bundle on SIGINT (or ``signum``) before
        chaining to whatever handler was installed — a Ctrl-C'd serve run
        leaves its last-N events and in-flight traces behind. Main-thread
        only (the ``signal`` module's contract)."""
        prev = signal.getsignal(signum)
        self._prev_sigint = prev

        def _handler(sig, frame):
            try:
                self.dump(reason=f"signal:{sig}")
            finally:
                if callable(prev):
                    prev(sig, frame)
                elif prev == signal.SIG_DFL:
                    signal.signal(sig, signal.SIG_DFL)
                    signal.raise_signal(sig)

        signal.signal(signum, _handler)

    def render_prometheus(self) -> str:
        with self._lock:
            lines = []
            for name, val in self.counters.items():
                full = f"ds_flight_{name}_total"
                lines.append(f"# TYPE {full} counter")
                lines.append(f"{full} {val}")
            lines.append("# TYPE ds_flight_ring_size gauge")
            lines.append(f"ds_flight_ring_size {len(self.events)}")
            return "\n".join(lines) + "\n"


# ----------------------------------------------------------------------
# workload extraction (the trace -> simulator replay surface)
# ----------------------------------------------------------------------

# root-span attrs that ARE the replayable workload identity of a request
# (stamped at the edge's mint; see service/edge.py). Everything else on
# the span tree is execution history, not workload.
WORKLOAD_ATTRS = ("prompt_tokens", "max_new_tokens", "tenant", "priority",
                  "slo_ms", "session", "deadline_ms")


def extract_workload(spans_by_trace: Dict[str, List[Dict]]) -> List[Dict]:
    """Extract a replayable ARRIVAL TRACE from exported spans.

    ``spans_by_trace`` maps trace id -> span dicts (the ``export_jsonl``
    / ``export_chrome`` record shape; ``bin/dstpu_trace``'s
    ``load_spans`` parses both back to exactly this). Each trace's ROOT
    span (sid ``s0``) was minted the instant the edge/router accepted
    the request, and its attrs carry the workload identity
    (``WORKLOAD_ATTRS``): the result is one arrival event per trace —

        {"t": <seconds from the first arrival>, "uid", "prompt_tokens",
         "max_new_tokens"?, "tenant"?, "priority"?, "slo_ms"?,
         "session"?, "deadline_ms"?}

    sorted by (t, uid) — the ``sim.traffic`` trace format the fleet
    simulator replays (and ``save_trace``/``load_trace`` round-trip).
    Traces without a root span or a uid are skipped (a trailing partial
    export), as are roots predating the metadata stamp with no
    ``prompt_tokens`` — those cannot be replayed faithfully and a
    silently guessed prompt length would be fiction, not observability.
    Returns [] for an empty export."""
    events: List[Dict] = []
    skipped = 0
    for tid, spans in spans_by_trace.items():
        root = next((s for s in spans
                     if s.get("sid") == "s0" or s.get("parent") is None),
                    None)
        if root is None:
            skipped += 1
            continue
        attrs = root.get("attrs") or {}
        uid = attrs.get("uid")
        if uid is None or attrs.get("prompt_tokens") is None:
            skipped += 1
            continue
        ev = {"t": float(root["t0"]), "uid": int(uid), "trace_id": tid}
        for k in WORKLOAD_ATTRS:
            if attrs.get(k) is not None:
                ev[k] = attrs[k]
        events.append(ev)
    if skipped:
        logger.warning(f"extract_workload: skipped {skipped} trace(s) "
                       "without a root span / uid / prompt_tokens "
                       "(pre-metadata exports are not replayable)")
    events.sort(key=lambda e: (e["t"], e["uid"]))
    if events:
        t0 = events[0]["t"]
        for ev in events:
            ev["t"] = round(ev["t"] - t0, 9)
    return events
