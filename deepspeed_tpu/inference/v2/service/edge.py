"""HTTP/SSE streaming front-end + fleet-edge admission control.

The MII layer of the reference stack (arXiv 2207.00032): a network
endpoint in front of the ``FleetDriver``, stdlib-only
(``http.server.ThreadingHTTPServer`` — one handler thread per connection,
which matches the driver's thread-per-replica model and adds no
dependencies):

* ``POST /v1/generate`` — JSON body with ``prompt`` (token ids) plus the
  scheduling surface (``max_new_tokens``/``temperature``/``tenant``/
  ``priority``/``slo_ms``/``deadline_ms``/``session``/``eos_token_id``).
  The response streams Server-Sent Events: ``accepted`` (uid), ``token``
  events as frames commit (the ``ServeBoundary.emissions`` feed), and a
  final ``done`` carrying the full output — byte-identical to a direct
  ``serve()`` of the same request. ``"stream": false`` returns one JSON
  body at completion instead.
* **Fleet-edge admission control** — BEFORE a request ever reaches a
  replica's scheduler, the edge sheds from two aggregate signals: the
  best healthy replica's ``placement_score`` (if even the least-loaded
  destination is past ``shed_score``, the whole fleet is saturated) and
  fleet-wide queued-token pressure (``max_queued_tokens``). A shed is a
  ``429`` with ``Retry-After`` derived from the fleet's measured token
  drain rate — back-pressure with an honest ETA, so closed-loop clients
  retry when capacity actually exists instead of hammering. Edge sheds
  fire before any replica's scheduler sheds locally (the bench's
  edge-admission leg pins the ordering).
* **Client-disconnect cancellation** — a dropped connection (detected at
  the next event write, or at the keep-alive ping when the stream is
  quiet) cancels the request through ``FleetDriver.cancel`` -> the
  engine's existing deadline/cancel path, freeing its slot and KV blocks
  at the next frame boundary.
* ``GET /metrics`` — ``ds_edge_*`` series + the whole fleet's
  ``ds_router_*``/``ds_serving_*`` exposition (including the fleet-merged
  ``ds_fleet_ttft_ms``/``ds_fleet_e2e_ms`` trace attribution and the
  ``ds_trace_*``/``ds_flight_*`` series) in one scrape;
  ``GET /healthz`` — replica status + driver stats as JSON.
* **Distributed tracing + flight recorder** (``..tracing``) — the edge
  mints a trace id per request (the root span is the client's view:
  bytes in → last SSE write) and wires the fleet's ``TraceCollector``/
  ``FlightRecorder`` through the router; ``GET /debug/trace`` serves
  Chrome-trace/Perfetto JSON (``?uid=``/``?trace=`` per-request,
  ``&format=jsonl`` raw spans), ``GET /debug/flight`` the live
  postmortem bundle.
"""

import http.server
import itertools
import json
import queue
import threading
from typing import Dict, Optional

import dataclasses

from ....utils.logging import logger


@dataclasses.dataclass
class EdgeConfig:
    """Service-edge knobs (admission thresholds + HTTP plumbing)."""
    host: str = "127.0.0.1"
    port: int = 0                     # 0 = ephemeral (read srv.edge_port)
    # ---- fleet-edge admission control ----
    # shed when even the LEAST-loaded accepting replica's placement_score
    # exceeds this (None disables the score gate). The serial router's
    # affinity_overload_score spreads load at this scale; the edge gate
    # is the harder stop above it.
    shed_score: Optional[float] = None
    # shed when fleet-wide queued prompt tokens (engine queues + feeds +
    # parked arrivals) exceed this (None disables)
    max_queued_tokens: Optional[int] = None
    # Retry-After = queued_tokens / drain_rate, clamped to this range
    retry_after_min_s: float = 1.0
    retry_after_max_s: float = 30.0
    # ---- request validation ----
    max_prompt_tokens: int = 65536
    max_new_tokens_cap: int = 4096
    max_body_bytes: int = 8 << 20
    # quiet-stream keep-alive: an SSE comment every this many seconds —
    # doubles as the disconnect probe while no tokens flow
    keepalive_s: float = 5.0
    # non-streaming requests give up after this long (the engine-side
    # deadline_ms is the real mechanism; this is the HTTP backstop)
    sync_timeout_s: float = 600.0
    # ---- distributed tracing + crash flight recorder (tracing.py;
    # README "Distributed tracing & flight recorder") ----
    # mint a trace id per request at the edge and wire the fleet's
    # TraceCollector/FlightRecorder through the router (False leaves the
    # fleet untraced unless the caller attached its own)
    trace: bool = True
    # fraction of COMPLETED traces retained (faulted/shed/handed-off/
    # failed-over/cancelled requests are ALWAYS retained)
    trace_sample_rate: float = 1.0
    trace_max_traces: int = 512
    # flight-recorder ring length + postmortem dump directory (None =
    # bundles kept in memory only; services should point this at disk)
    flight_events: int = 1024
    flight_dir: Optional[str] = None


class ServiceEdge:
    """HTTP/SSE front-end over a started ``FleetDriver`` (see module
    docstring). ``start()`` binds the server (``edge_port`` holds the
    bound port); ``shutdown()`` stops accepting and closes."""

    def __init__(self, driver, config: Optional[EdgeConfig] = None,
                 tracer=None, recorder=None):
        self.driver = driver
        self.cfg = config or EdgeConfig()
        self._uids = itertools.count(1)
        self._lock = threading.Lock()    # guards counters/gauges: handler
        #                                  threads mutate them concurrently
        #                                  (a bare dict += loses updates)
        self.counters: Dict[str, int] = dict(
            requests=0, sheds=0, disconnects=0, completed=0, errors=0,
            cancelled=0)
        self.gauges: Dict[str, float] = dict(
            streams_active=0, queued_tokens=0, retry_after_s=0.0)
        self._srv = None
        self._thread = None
        # distributed tracing + flight recorder, wired fleet-wide through
        # the router (every replica's telemetry + the placement/failover
        # paths); pass tracer=/recorder= to share externally-built ones
        self.tracer = None
        self.flight = None
        self._traces: Dict[int, str] = {}   # live uid -> trace id
        self._sse_spans: Dict[int, int] = {}   # uid -> sse.write instants
        if self.cfg.trace or tracer is not None:
            from ..tracing import FlightRecorder, TraceCollector
            tracer = tracer if tracer is not None else TraceCollector(
                sample_rate=self.cfg.trace_sample_rate,
                max_traces=self.cfg.trace_max_traces)
            recorder = recorder if recorder is not None else FlightRecorder(
                collector=tracer, max_events=self.cfg.flight_events,
                dump_dir=self.cfg.flight_dir)
            self.tracer, self.flight = \
                driver.router.attach_tracing(tracer, recorder)

    def _inc(self, name: str, delta: int = 1) -> None:
        with self._lock:
            self.counters[name] += delta

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def admission_check(self) -> Optional[Dict]:
        """None = admit; else a shed verdict dict (reason + retry_after_s)
        — computed from aggregate fleet signals only, so an overloaded
        fleet rejects at the edge in microseconds instead of queueing work
        a replica's scheduler would shed seconds later."""
        cfg = self.cfg
        queued = self.driver.queued_tokens_estimate()
        self.gauges["queued_tokens"] = queued
        reason = None
        if cfg.max_queued_tokens is not None and \
                queued > cfg.max_queued_tokens:
            reason = (f"queued_tokens {queued} > "
                      f"max_queued_tokens {cfg.max_queued_tokens}")
        elif cfg.shed_score is not None:
            score = self.driver.best_placement_score()
            if score is None:
                reason = "no replica accepting placements"
            elif score > cfg.shed_score:
                reason = (f"best placement_score {score:.3f} > "
                          f"shed_score {cfg.shed_score}")
        if reason is None:
            return None
        rate = self.driver.tokens_per_second()
        retry = queued / rate if rate > 0 else cfg.retry_after_max_s
        retry = min(max(retry, cfg.retry_after_min_s),
                    cfg.retry_after_max_s)
        self.gauges["retry_after_s"] = round(retry, 3)
        return {"reason": reason, "retry_after_s": round(retry, 3)}

    # ------------------------------------------------------------------
    # metrics
    # ------------------------------------------------------------------

    def render_prometheus(self) -> str:
        lines = []
        for name, val in self.counters.items():
            full = f"ds_edge_{name}_total"
            lines.append(f"# TYPE {full} counter")
            lines.append(f"{full} {val}")
        for name, val in self.gauges.items():
            full = f"ds_edge_{name}"
            lines.append(f"# TYPE {full} gauge")
            lines.append(f"{full} {val}")
        try:
            fleet = self.driver.router.render_prometheus()
        except Exception as e:        # noqa: BLE001 — engines render
            # concurrently with serving; a torn read degrades one scrape,
            # never the service
            logger.warning(f"ServiceEdge: fleet exposition failed "
                           f"({type(e).__name__}: {e})")
            fleet = ""
        return "\n".join(lines) + "\n" + fleet

    # ------------------------------------------------------------------
    # request plumbing
    # ------------------------------------------------------------------

    def _parse_request(self, body: Dict) -> Dict:
        cfg = self.cfg
        prompt = body.get("prompt", body.get("tokens"))
        if not isinstance(prompt, list) or not prompt or \
                not all(isinstance(t, int) for t in prompt):
            raise ValueError("'prompt' must be a non-empty list of "
                             "token ids")
        if len(prompt) > cfg.max_prompt_tokens:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"max_prompt_tokens={cfg.max_prompt_tokens}")
        item = {"uid": next(self._uids), "tokens": prompt}
        limit = body.get("max_new_tokens")
        if limit is not None:
            limit = int(limit)
            if not 0 < limit <= cfg.max_new_tokens_cap:
                raise ValueError(f"max_new_tokens must be in "
                                 f"1..{cfg.max_new_tokens_cap}")
            item["max_new_tokens"] = limit
        for key, cast in (("temperature", float), ("slo_ms", float),
                          ("deadline_ms", float), ("eos_token_id", int)):
            if body.get(key) is not None:
                item[key] = cast(body[key])
        for key in ("tenant", "priority", "session"):
            if body.get(key) is not None:
                item[key] = body[key]
        return item

    # ------------------------------------------------------------------
    # distributed-trace plumbing (no-ops when tracing is off)
    # ------------------------------------------------------------------

    def _note_sse_write(self, uid: int, n_tokens: int) -> None:
        """A ``token`` event was written and flushed. The request's first
        ends its time to first token (the stage ``egress``); every one is
        an ``sse.write`` instant of its trace."""
        tid = self._traces.get(uid) if self.tracer is not None else None
        if tid is None:
            return
        # cap per request, like the engine's emit instants: a long stream
        # must not spend the trace's span budget on write markers before
        # its terminal spans land
        seen = self._sse_spans.get(uid, 0)
        if seen >= 64:
            return
        self._sse_spans[uid] = seen + 1
        now = self.tracer.clock()
        if not seen:
            self.tracer.note_first_write(tid, now)
        # the root span id is "s0" by mint() construction
        self.tracer.instant(tid, "sse.write", now, parent="s0",
                            replica="edge",
                            attrs={"uid": uid, "n": n_tokens})

    def _trace_close(self, uid: int, outcome: str,
                     mark: Optional[str] = None) -> None:
        """End the edge's view of the request: extend/close the root span
        (idempotent with the engine's retire-side finish)."""
        if self.tracer is None:
            return
        with self._lock:
            tid = self._traces.pop(uid, None)
            self._sse_spans.pop(uid, None)
        if tid is None:
            return
        if mark is not None:
            self.tracer.mark(tid, mark)
        self.tracer.finish(tid, status=f"edge:{outcome}")
        if self.flight is not None and outcome in ("disconnect", "timeout",
                                                   "error"):
            self.flight.record("edge_" + outcome, uid=uid, trace=tid)

    def handle_generate(self, body: Dict, recv_t: Optional[float] = None):
        """Shared core of the POST handler (unit-testable without
        sockets): returns ``("shed", verdict)`` or
        ``("stream", uid, events_queue)``. The caller owns consuming the
        queue and cancelling on disconnect. ``recv_t``: when the handler
        had the request's bytes, on the tracer's clock (now, if the
        caller took no stamp before it parsed them)."""
        if recv_t is None and self.tracer is not None:
            recv_t = self.tracer.clock()
        item = self._parse_request(body)
        uid = item["uid"]
        tid = None
        if self.tracer is not None:
            # the trace starts the moment the edge had the bytes (before
            # they were parsed and validated: a long prompt's parse is
            # part of its ``ingress``) — fleet TTFT/E2E are measured from
            # THERE, the client's view. A stream's TTFT ends at its first
            # write; a response sent whole at the end has none to wait for.
            # The root span carries the request's WORKLOAD identity
            # (prompt length, budget, scheduling metadata) so a trace
            # export is a replayable arrival trace — the
            # ``dstpu_trace --workload`` / sim-replay surface
            attrs = {"uid": uid, "prompt_tokens": len(item["tokens"])}
            for k in ("max_new_tokens", "tenant", "priority", "slo_ms",
                      "session", "deadline_ms"):
                if item.get(k) is not None:
                    attrs[k] = item[k]
            tid, root = self.tracer.mint(
                "edge.recv", replica="edge", t=recv_t, attrs=attrs,
                awaits_write=bool(body.get("stream", True)))
            item["trace"] = {"id": tid, "parent": root}
            with self._lock:
                self._traces[uid] = tid
        verdict = self.admission_check()
        if self.tracer is not None:
            self.tracer.instant(
                tid, "edge.admit", parent=root, replica="edge",
                attrs={"uid": uid,
                       "verdict": "shed" if verdict else "admitted"})
        if verdict is not None:
            self._inc("sheds")
            if self.flight is not None:
                self.flight.record("edge_shed", uid=uid, trace=tid,
                                   detail=verdict["reason"][:160])
            self._trace_close(uid, "shed", mark="shed")
            return ("shed", verdict)
        events: queue.Queue = queue.Queue()
        self._inc("requests")
        self.driver.submit(item, subscriber=events.put)
        return ("stream", uid, events)

    def start(self):
        """Bind + serve on a daemon thread; returns self (``edge_port``
        has the bound port)."""
        edge = self

        class _Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):   # not log spam
                pass

            # -- helpers -------------------------------------------------
            def _json(self, code: int, payload: Dict,
                      headers: Optional[Dict] = None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _sse_event(self, event: str, payload: Dict):
                chunk = (f"event: {event}\n"
                         f"data: {json.dumps(payload)}\n\n").encode()
                self.wfile.write(chunk)
                self.wfile.flush()

            # -- endpoints -----------------------------------------------
            def do_GET(self):
                raw_path, _, query = self.path.partition("?")
                path = raw_path.rstrip("/")
                if path in ("", "/metrics"):
                    body = edge.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif path == "/healthz":
                    self._json(200, {
                        "replicas": edge.driver.router.replica_status(),
                        "stats": edge.driver.stats(),
                        "edge": {"counters": dict(edge.counters),
                                 "gauges": dict(edge.gauges)}})
                elif path == "/debug/trace":
                    self._debug_trace(query)
                elif path == "/debug/flight":
                    if edge.flight is None:
                        self.send_error(404, "flight recorder disabled")
                    else:
                        self._json(200, edge.flight.bundle("http"))
                else:
                    self.send_error(404)

            def _debug_trace(self, query: str):
                """``GET /debug/trace`` — the fleet's retained traces as
                Chrome-trace-event JSON (load in chrome://tracing or
                Perfetto). ``?trace=<id>`` / ``?uid=<n>`` narrow to one
                request; ``&format=jsonl`` returns raw span lines (the
                ``dstpu_trace`` CLI input)."""
                if edge.tracer is None:
                    self.send_error(404, "tracing disabled")
                    return
                import urllib.parse
                q = urllib.parse.parse_qs(query)
                if q.get("trace") or q.get("uid"):
                    try:
                        uid = int(q["uid"][0]) if q.get("uid") else None
                    except ValueError:
                        self._json(400, {"error": "uid must be an int"})
                        return
                    tr = edge.tracer.get(
                        trace_id=(q.get("trace") or [None])[0], uid=uid)
                    if tr is None:
                        self.send_error(404, "no such trace")
                        return
                    traces = [tr]
                else:
                    traces = edge.tracer.traces()
                if (q.get("format") or [""])[0] == "jsonl":
                    body = edge.tracer.export_jsonl(traces).encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._json(200, edge.tracer.export_chrome(traces))

            def do_POST(self):
                if self.path.split("?")[0].rstrip("/") != "/v1/generate":
                    self.send_error(404)
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    if n <= 0 or n > edge.cfg.max_body_bytes:
                        raise ValueError(f"body size {n} out of range")
                    raw = self.rfile.read(n)
                    recv_t = edge.tracer.clock() if edge.tracer else None
                    body = json.loads(raw)
                    stream = bool(body.get("stream", True))
                    out = edge.handle_generate(body, recv_t)
                except (ValueError, KeyError, TypeError,
                        json.JSONDecodeError) as e:
                    edge._inc("errors")
                    self._json(400, {"error": str(e)})
                    return
                if out[0] == "shed":
                    verdict = out[1]
                    self._json(429, {"error": "overloaded", **verdict},
                               headers={"Retry-After": str(max(
                                   1, int(round(verdict["retry_after_s"])))
                               )})
                    return
                _, uid, events = out
                if stream:
                    self._stream_sse(uid, events)
                else:
                    self._respond_sync(uid, events)

            def _consume(self, events, on_event,
                         deadline_s: Optional[float] = None) -> str:
                """Pump subscriber events until terminal; returns the
                outcome ("done" | "error" | "disconnect" | "timeout").
                ``on_event(None)`` is the quiet-stream keep-alive probe
                (streaming responses write a comment there; sync
                responses ignore it). One loop serves both response
                modes so terminal-event semantics can never diverge."""
                import time as _t
                t0 = _t.monotonic()
                while True:
                    wait = edge.cfg.keepalive_s
                    if deadline_s is not None:
                        left = deadline_s - (_t.monotonic() - t0)
                        if left <= 0:
                            return "timeout"
                        wait = min(wait, left)
                    try:
                        ev = events.get(timeout=wait)
                    except queue.Empty:
                        try:
                            on_event(None)       # keep-alive / probe
                        except (BrokenPipeError, ConnectionResetError,
                                OSError):
                            return "disconnect"
                        continue
                    try:
                        on_event(ev)
                    except (BrokenPipeError, ConnectionResetError,
                            OSError):
                        return "disconnect"
                    if ev["type"] == "done":
                        return "done"
                    if ev["type"] == "error":
                        return "error"

            def _stream_sse(self, uid, events):
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                with edge._lock:
                    edge.gauges["streams_active"] += 1
                n_sent = 0

                def on_event(ev):
                    nonlocal n_sent
                    if ev is None:
                        self.wfile.write(b": keep-alive\n\n")
                        self.wfile.flush()
                        return
                    if ev["type"] == "tokens":
                        self._sse_event("token", {
                            "uid": uid, "tokens": ev["tokens"],
                            "index": n_sent})
                        n_sent += len(ev["tokens"])
                        edge._note_sse_write(uid, len(ev["tokens"]))
                    elif ev["type"] == "done":
                        self._sse_event("done", {
                            "uid": uid, "tokens": ev["tokens"],
                            "n": len(ev["tokens"])})
                    else:
                        self._sse_event("error", {
                            k: v for k, v in ev.items() if k != "type"})

                try:
                    self._sse_event("accepted", {"uid": uid})
                    outcome = self._consume(events, on_event)
                except (BrokenPipeError, ConnectionResetError, OSError):
                    outcome = "disconnect"
                finally:
                    with edge._lock:
                        edge.gauges["streams_active"] -= 1
                if outcome == "disconnect":
                    edge._inc("disconnects")
                    edge._inc("cancelled")
                    edge.driver.cancel(uid)
                    edge._trace_close(uid, "disconnect",
                                      mark="disconnect")
                    self.close_connection = True
                elif outcome == "done":
                    edge._inc("completed")
                    edge._trace_close(uid, "done")
                else:
                    edge._inc("errors")
                    edge._trace_close(uid, "error")

            def _respond_sync(self, uid, events):
                final = {}

                def on_event(ev):
                    if ev is not None and ev["type"] in ("done", "error"):
                        final.update(ev)

                outcome = self._consume(events, on_event,
                                        deadline_s=edge.cfg.sync_timeout_s)
                if outcome == "done":
                    edge._inc("completed")
                    edge._trace_close(uid, "done")
                    self._json(200, {"uid": uid, "tokens": final["tokens"],
                                     "n": len(final["tokens"])})
                elif outcome == "error":
                    edge._inc("errors")
                    edge._trace_close(uid, "error")
                    self._json(500, {"uid": uid, "error":
                                     final.get("reason", "failed"),
                                     "detail": final.get("detail", "")})
                else:
                    edge._inc("errors")
                    edge.driver.cancel(uid)
                    edge._trace_close(uid, "timeout", mark="cancelled")
                    self._json(504, {"uid": uid, "error": "timeout"})

        class _Server(http.server.ThreadingHTTPServer):
            # stdlib default backlog is 5 — hundreds of closed-loop
            # sessions connect in one burst
            request_queue_size = 256
            daemon_threads = True

        srv = _Server((self.cfg.host, self.cfg.port), _Handler)
        self._srv = srv
        self.edge_port = srv.server_address[1]
        self._thread = threading.Thread(target=srv.serve_forever,
                                        name="ds-service-edge", daemon=True)
        self._thread.start()
        logger.info(f"ServiceEdge: listening on "
                    f"http://{self.cfg.host}:{self.edge_port}")
        return self

    def shutdown(self):
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._srv = None
