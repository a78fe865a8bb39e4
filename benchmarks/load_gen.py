"""Closed-loop load generator for the HTTP/SSE service edge.

The first benchmark that measures the system as TRAFFIC experiences it:
N concurrent closed-loop sessions (each a thread holding a persistent
conversation: submit -> stream tokens -> think -> submit the next turn)
against a real network endpoint (``service.edge.ServiceEdge``), not
against an in-process arrival iterator. Closed-loop means each session
waits for its own completion before its next turn — the offered load
self-regulates like real users, and a 429 (edge shed) is honored by
sleeping the server's ``Retry-After`` before retrying, so the measured
latency includes honest back-pressure.

Determinism: every session's prompts, budgets, and think times derive
from ``--seed``; the TOKEN-PARITY check replays every request through a
direct single-engine ``serve()`` (the repo's greedy token-identity
invariant makes batching/placement irrelevant) and asserts the STREAMED
bytes match exactly. Zero parity violations across >= 200 concurrent
sessions is the acceptance bar (ISSUE 14).

Run self-hosted (builds a tiny fleet + edge in-process, CPU smoke):

    python benchmarks/load_gen.py --self-host --sessions 200 --turns 2

or against an external endpoint (no parity check unless --reference):

    python benchmarks/load_gen.py --url http://127.0.0.1:8100
"""

import argparse
import http.client
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB = 200          # tiny-model-safe token id range


# ----------------------------------------------------------------------
# deterministic workload
# ----------------------------------------------------------------------

def build_schedule(sessions: int, turns: int, prompt_len: int,
                   max_new: int, think_ms: float, seed: int
                   ) -> Dict[Tuple[int, int], Dict]:
    """(session, turn) -> {prompt, max_new_tokens, think_s, tenant,
    priority}. Pure function of the arguments — the parity reference
    replays exactly this."""
    rng = np.random.default_rng(seed)
    sched = {}
    for s in range(sessions):
        for t in range(turns):
            plen = int(rng.integers(max(4, prompt_len // 2),
                                    prompt_len + 1))
            sched[(s, t)] = {
                "prompt": [int(x) for x in rng.integers(0, VOCAB, (plen,))],
                "max_new_tokens": int(rng.integers(max(1, max_new // 2),
                                                   max_new + 1)),
                "think_s": float(rng.uniform(0.2, 1.0)) * think_ms * 1e-3,
                "tenant": f"t{s % 4}",
                "priority": "interactive" if s % 3 else "batch",
            }
    return sched


# ----------------------------------------------------------------------
# SSE client (stdlib only)
# ----------------------------------------------------------------------

def sse_generate(host: str, port: int, body: Dict, timeout: float = 120.0):
    """POST /v1/generate and consume the SSE stream. Returns
    ``(status, result)``: status 200 -> result = {"streamed": [...],
    "done": [...], "ttft_s": ...}; status 429 -> result = retry-after
    seconds; else result = error text."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        t0 = time.monotonic()
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status == 429:
            retry = float(resp.getheader("Retry-After") or 1.0)
            resp.read()
            return 429, retry
        if resp.status != 200:
            return resp.status, resp.read().decode(errors="replace")
        streamed: List[int] = []
        done: Optional[List[int]] = None
        error = None
        ttft = None
        buf = b""
        while True:
            line = resp.readline()
            if not line:
                break
            buf += line
            if line != b"\n":
                continue
            ev, data = None, None
            for ln in buf.decode().strip().splitlines():
                if ln.startswith("event: "):
                    ev = ln[7:]
                elif ln.startswith("data: "):
                    data = json.loads(ln[6:])
            buf = b""
            if ev == "token":
                if ttft is None:
                    ttft = time.monotonic() - t0
                streamed.extend(data["tokens"])
            elif ev == "done":
                done = data["tokens"]
                break
            elif ev == "error":
                error = data
                break
        if error is not None:
            return -1, error
        return 200, {"streamed": streamed, "done": done,
                     "ttft_s": ttft if ttft is not None
                     else time.monotonic() - t0,
                     "e2e_s": time.monotonic() - t0}
    finally:
        conn.close()


# ----------------------------------------------------------------------
# closed-loop sessions
# ----------------------------------------------------------------------

def _aggregate(results: Dict, failures: List[str], sheds: Dict,
               elapsed: float, **mode_fields) -> Dict:
    """Shared report tail for ``run_load``/``run_open_loop`` — one
    definition of the mismatch check, percentile summaries, and report
    keys, so closed- and open-loop runs can never drift apart. Callers
    pass SNAPSHOTS (taken under their lock — a straggler thread past the
    join timeout may still be writing)."""
    stream_mismatch = [
        k for k, v in results.items()
        if v["done"] is None or v["streamed"] != v["done"]]
    ttfts = sorted(v["ttft_s"] for v in results.values())
    e2es = sorted(v["e2e_s"] for v in results.values())
    toks = sum(len(v["done"] or ()) for v in results.values())

    def pct(xs, p):
        return round(float(np.percentile(xs, p)) * 1e3, 2) if xs else None

    return {
        **mode_fields,
        "completed": len(results),
        "failures": failures[:20], "n_failures": len(failures),
        "edge_sheds_seen": sheds["count"],
        "retry_wait_s": round(sheds["retry_wait_s"], 2),
        "stream_vs_done_mismatches": len(stream_mismatch),
        "elapsed_s": round(elapsed, 3),
        "tokens": toks,
        "tok_per_sec": round(toks / max(elapsed, 1e-9), 1),
        "ttft_ms": {"p50": pct(ttfts, 50), "p90": pct(ttfts, 90),
                    "p99": pct(ttfts, 99)},
        "e2e_ms": {"p50": pct(e2es, 50), "p90": pct(e2es, 90)},
        "_results": results,       # stripped before JSON dump
    }


def run_load(host: str, port: int, sched: Dict, sessions: int, turns: int,
             max_shed_retries: int = 20) -> Dict:
    """Drive the schedule with one thread per session; returns the
    aggregate report (latencies, sheds, failures, and every request's
    streamed/done tokens for the parity check)."""
    results: Dict[Tuple[int, int], Dict] = {}
    lock = threading.Lock()
    failures: List[str] = []
    sheds = {"count": 0, "retry_wait_s": 0.0}

    def session(s: int) -> None:
        for t in range(turns):
            req = sched[(s, t)]
            time.sleep(req["think_s"])
            body = {k: req[k] for k in ("prompt", "max_new_tokens",
                                        "tenant", "priority")}
            body["session"] = f"s{s}"
            tries = 0
            while True:
                status, out = sse_generate(host, port, body)
                if status == 200:
                    with lock:
                        results[(s, t)] = out
                    break
                if status == 429 and tries < max_shed_retries:
                    tries += 1
                    with lock:
                        sheds["count"] += 1
                        sheds["retry_wait_s"] += out
                    time.sleep(min(float(out), 5.0))
                    continue
                with lock:
                    failures.append(f"({s},{t}): status={status} {out}")
                return

    threads = [threading.Thread(target=session, args=(s,), daemon=True)
               for s in range(sessions)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    elapsed = time.monotonic() - t0
    with lock:
        snap, fails, shed_snap = dict(results), list(failures), dict(sheds)
    return _aggregate(snap, fails, shed_snap, elapsed,
                      sessions=sessions, turns=turns,
                      requests=sessions * turns)


# ----------------------------------------------------------------------
# open-loop (arrival-rate) sessions — the PR-12 ROADMAP follow-up
# ----------------------------------------------------------------------

def run_open_loop(host: str, port: int, sched: Dict, rate: float) -> Dict:
    """OPEN-loop load: requests fire at a fixed arrival RATE on their own
    threads — nobody waits for a previous completion, so offered load
    does NOT self-regulate and overload actually lands on the edge
    (closed-loop sessions slow down with the system and can never
    overdrive it). Each scheduled request (session, turn) launches at a
    deterministic offset ``i / rate`` seconds; an edge shed (429) is
    counted and DROPPED — in an open-loop world the arrival is lost, not
    retried, which is exactly the regime tracing overhead must be
    measured under. Returns the same report shape as ``run_load`` (shed
    requests are not failures; ``completed + edge_sheds_seen`` accounts
    for every arrival)."""
    order = sorted(sched)
    results: Dict[Tuple[int, int], Dict] = {}
    lock = threading.Lock()
    failures: List[str] = []
    sheds = {"count": 0, "retry_wait_s": 0.0}
    start = time.monotonic() + 0.05        # common launch epoch

    def fire(i: int, key) -> None:
        req = sched[key]
        sched_t = start + i / max(rate, 1e-6)   # INTENDED arrival
        delay = sched_t - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        body = {k: req[k] for k in ("prompt", "max_new_tokens",
                                    "tenant", "priority")}
        body["session"] = f"s{key[0]}"
        send_t = time.monotonic()               # ACTUAL send
        status, out = sse_generate(host, port, body)
        with lock:
            if status == 200:
                # stamp both times: schedule-relative latency charges the
                # request from when it was SUPPOSED to arrive, so a lagging
                # generator (thread wakeup under load) can't flatter the
                # system by silently closing the loop
                out["sched_t"] = sched_t
                out["send_t"] = send_t
                results[key] = out
            elif status == 429:
                sheds["count"] += 1
                sheds["retry_wait_s"] += out
            else:
                failures.append(f"{key}: status={status} {out}")

    threads = [threading.Thread(target=fire, args=(i, key), daemon=True)
               for i, key in enumerate(order)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    elapsed = time.monotonic() - t0
    with lock:
        snap, fails, shed_snap = dict(results), list(failures), dict(sheds)
    report = _aggregate(snap, fails, shed_snap, elapsed,
                        mode="open-loop", arrival_rate_per_s=rate,
                        requests=len(order))
    # schedule-relative view: TTFT measured from the INTENDED arrival
    # (sched_t), plus the generator's own lag (send_t - sched_t). If lag
    # is material relative to the latencies reported, the run was
    # generator-bound, not system-bound — sched_ttft_ms is the honest
    # number either way, and the one the fleet simulator predicts.
    lags = sorted(v["send_t"] - v["sched_t"] for v in snap.values())
    sched_ttfts = sorted(v["send_t"] - v["sched_t"] + v["ttft_s"]
                         for v in snap.values())

    def pct(xs, p):
        return round(float(np.percentile(xs, p)) * 1e3, 2) if xs else None

    report["gen_lag_ms"] = {"p50": pct(lags, 50), "p90": pct(lags, 90),
                            "max": pct(lags, 100)}
    report["sched_ttft_ms"] = {"p50": pct(sched_ttfts, 50),
                               "p90": pct(sched_ttfts, 90),
                               "p99": pct(sched_ttfts, 99)}
    return report


# ----------------------------------------------------------------------
# parity reference: direct serve() of the same schedule
# ----------------------------------------------------------------------

def direct_reference(mk_engine, sched: Dict) -> Dict[Tuple[int, int], List]:
    """Every scheduled request through ONE fresh engine's serve() —
    greedy outputs are placement/batching-independent, so this is THE
    token-identity reference for whatever the fleet streamed."""
    eng = mk_engine()
    uids = {}
    items = []
    for i, (key, req) in enumerate(sorted(sched.items())):
        uids[i] = key
        items.append({"uid": i, "tokens": req["prompt"],
                      "max_new_tokens": req["max_new_tokens"]})
    out = {}
    CHUNK = 16      # keep the queue bounded; admission defers overflow
    def arrivals():
        for i in range(0, len(items), CHUNK):
            yield items[i:i + CHUNK]
    for uid, toks in eng.serve(arrivals(), max_new_tokens=8):
        out[uids[uid]] = [int(t) for t in toks]
    return out


def check_parity(report: Dict, ref: Dict) -> int:
    """Count parity violations: streamed tokens must be byte-identical
    to the direct reference for every completed request."""
    bad = report["stream_vs_done_mismatches"]
    for key, v in report["_results"].items():
        if v["done"] != ref.get(key):
            bad += 1
    return bad


# ----------------------------------------------------------------------
# self-hosted harness (CPU smoke fleet)
# ----------------------------------------------------------------------

def build_fleet(replicas: int, batch: int, max_seq_len: int,
                scheduler: bool, edge_cfg=None, autoscale: bool = False):
    """Tiny fleet + threaded driver + edge, for self-hosted runs and the
    serving bench. Returns (router, driver, edge, mk_engine)."""
    import jax
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.router import EngineRouter
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    from deepspeed_tpu.inference.v2.service import (AutoscaleController,
                                                    EdgeConfig, FleetDriver,
                                                    ServiceEdge)
    from deepspeed_tpu.models import build_model

    model = build_model("tiny", num_heads=8)
    params = model.init(jax.random.PRNGKey(0))

    def mk_engine():
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(
            kv_block_size=16, prefill_chunk_size=8,
            max_tokens_per_step=1024, dtype="float32",
            max_ragged_batch_size=batch, frame_steps=2,
            frame_retry_backoff_s=0.0), params=params,
            max_seq_len=max_seq_len)

    router = EngineRouter({f"replica{i}": mk_engine()
                           for i in range(replicas)})
    sched_factory = None
    if scheduler:
        sched_factory = lambda: RequestScheduler(SchedulerConfig(  # noqa
            lookahead_reserve=True))
    driver = FleetDriver(
        router,
        autoscaler=AutoscaleController() if autoscale else None)
    driver.start(max_new_tokens=8, scheduler_factory=sched_factory)
    edge = ServiceEdge(driver, edge_cfg or EdgeConfig()).start()
    return router, driver, edge, mk_engine


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", default=None,
                    help="existing endpoint (http://host:port); default "
                         "is --self-host")
    ap.add_argument("--self-host", action="store_true",
                    help="build a tiny in-process fleet + edge and drive "
                         "it (CPU smoke; enables the parity check)")
    ap.add_argument("--sessions", type=int, default=200)
    ap.add_argument("--turns", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--think-ms", type=float, default=200.0)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--open-loop", action="store_true",
                    help="arrival-RATE mode: requests fire at --rate/s "
                         "regardless of completions (offered load does "
                         "not self-regulate; 429s are dropped, not "
                         "retried)")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="open-loop arrival rate, requests/s (default 20)")
    ap.add_argument("--scheduler", action="store_true",
                    help="self-host with the SLO-aware RequestScheduler "
                         "(+ admission lookahead) per replica")
    ap.add_argument("--out", default=None, help="write the JSON report "
                                                "here as well as stdout")
    args = ap.parse_args()

    sched = build_schedule(args.sessions, args.turns, args.prompt_len,
                           args.max_new, args.think_ms, args.seed)
    ref = None
    if args.url and not args.self_host:
        host, port = args.url.split("//")[-1].split(":")
        port = int(port)
        edge = driver = None
    else:
        router, driver, edge, mk_engine = build_fleet(
            args.replicas, args.batch,
            max_seq_len=2 * (args.prompt_len + args.max_new) + 32,
            scheduler=args.scheduler)
        host, port = "127.0.0.1", edge.edge_port
        ref = direct_reference(mk_engine, sched)

    if args.open_loop:
        report = run_open_loop(host, port, sched, args.rate)
    else:
        report = run_load(host, port, sched, args.sessions, args.turns)
    if ref is not None:
        report["parity_violations"] = check_parity(report, ref)
    report.pop("_results")
    if edge is not None:
        report["edge_counters"] = dict(edge.counters)
        report["driver"] = driver.stats()["driver"]
        edge.shutdown()
        driver.stop()
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    # open-loop: a shed arrival is lost by design, not a failure — every
    # arrival must still be ACCOUNTED for (completed or shed)
    accounted = report["completed"] + (report["edge_sheds_seen"]
                                       if args.open_loop else 0)
    ok = (accounted == report["requests"]
          and report["n_failures"] == 0
          and report["stream_vs_done_mismatches"] == 0
          and report.get("parity_violations", 0) == 0)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
