"""The load generator: a JAX-free child process of its own, so that it
shares neither the chip nor the server's interpreter lock.

    python3 perfbench/client.py --plan P.json --out LOG.jsonl --port N \
        --epoch T --seed S --vocab V

``--epoch`` is a reading of ``time.monotonic()`` (one clock for every
process of the machine): all times in the plan and in the log are seconds
after it. The SSE client and the rule that a request is timed from when it
was DUE, not from when it was sent, are copied from the repo's
``benchmarks/load_gen.py``; its schedule (evenly spaced, uniform lengths)
is not.

One line of JSON per request goes to ``--out`` when all are done:
``key, cls, prompt_len, max_new, sched_t, send_t, first_t, last_t, events
[[t, n], ...], tokens, status`` (``ok``, or what went wrong).
"""

import argparse
import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import draws  # noqa: E402


def sse_generate(port, prompt, max_new, clock, timeout_s):
    """POST /v1/generate and read the stream. Returns ``(status, first_t,
    last_t, events, tokens)``; times by ``clock()`` at the arrival of each
    ``token`` event. ``status`` is ``ok`` only if the ``done`` event came,
    carried what was streamed, and that is exactly ``max_new`` tokens."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    events, streamed, done, status = [], [], None, None
    try:
        conn.request("POST", "/v1/generate", json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new, "stream": True}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            return f"http_{resp.status}", None, None, [], []
        event, data = None, None
        while True:
            line = resp.readline()
            if not line:
                status = "stream ended without done"
                break
            if line.startswith(b"event: "):
                event = line[7:].strip().decode()
            elif line.startswith(b"data: "):
                data = line[6:]
            elif line == b"\n" and event is not None:
                now = clock()
                if event == "token":
                    toks = json.loads(data)["tokens"]
                    streamed.extend(toks)
                    events.append([now, len(toks)])
                elif event == "done":
                    done = json.loads(data)["tokens"]
                    break
                elif event == "error":
                    status = f"error event: {data[:200]!r}"
                    break
                event, data = None, None
    except (OSError, http.client.HTTPException) as e:
        status = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    if status is None:
        if done != streamed:
            status = "streamed tokens differ from the done event"
        elif len(streamed) != max_new:
            status = f"{len(streamed)} tokens for max_new_tokens {max_new}"
        else:
            status = "ok"
    first = events[0][0] if events else None
    last = events[-1][0] if events else None
    return status, first, last, events, streamed


class Recorder:
    def __init__(self, port, seed, vocab, epoch, timeout_s):
        self.port, self.seed, self.vocab = port, seed, vocab
        self.epoch, self.timeout_s = epoch, timeout_s
        self.lock = threading.Lock()
        self.records = []

    def clock(self):
        return time.monotonic() - self.epoch

    def send(self, request, prompt, sched_t):
        send_t = self.clock()
        status, first, last, events, tokens = sse_generate(
            self.port, prompt, request["max_new"], self.clock, self.timeout_s)
        record = {"key": request["key"], "cls": request["cls"],
                  "prompt_len": request["prompt_len"],
                  "max_new": request["max_new"], "sched_t": sched_t,
                  "send_t": send_t, "first_t": first, "last_t": last,
                  "events": events, "tokens": tokens, "status": status}
        with self.lock:
            self.records.append(record)


def run_open(plan, rec):
    """One thread per request, made beforehand and started when the request
    is due; the prompt is made before that, off the timed path."""
    threads = []
    for request in plan["requests"]:
        prompt = draws.tokens_for(rec.seed, request, rec.vocab)
        threads.append((request["t"], threading.Thread(
            target=rec.send, args=(request, prompt, request["t"]),
            daemon=True)))
    for due, th in threads:
        wait = due - rec.clock()
        if wait > 0:
            time.sleep(wait)
        th.start()
    return [th for _, th in threads]


def run_closed(plan, rec, stop_t):
    """``clients`` threads, each walking its own requests from its start
    until ``stop_t``. A request is due the moment its client is free."""
    def client(c):
        mine = [r for r in plan["requests"] if r["client"] == c]
        time.sleep(max(0.0, plan["starts"][c] - rec.clock()))
        for request in mine:
            prompt = draws.tokens_for(rec.seed, request, rec.vocab)
            if plan["think_s"]:
                time.sleep(plan["think_s"])
            now = rec.clock()
            if now >= stop_t:
                return
            rec.send(request, prompt, now)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(plan["clients"])]
    for th in threads:
        th.start()
    return threads


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--epoch", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    args = ap.parse_args()
    with open(args.plan) as fh:
        plan = json.load(fh)
    stop_t = plan["stop_t"]           # no request is sent after this
    deadline = plan["deadline_t"]     # nor waited for after this
    rec = Recorder(args.port, args.seed, args.vocab, args.epoch,
                   timeout_s=max(1.0, deadline))
    if plan["mode"] == "open":
        threads = run_open(plan, rec)
    else:
        threads = run_closed(plan, rec, stop_t)
    for th in threads:
        th.join(timeout=max(0.0, deadline - rec.clock()))
    with rec.lock:
        records = list(rec.records)
    unfinished = sum(th.is_alive() for th in threads)
    with open(args.out, "w") as fh:
        for record in sorted(records, key=lambda r: r["key"]):
            fh.write(json.dumps(record) + "\n")
    print(json.dumps({"records": len(records), "unfinished": unfinished}),
          flush=True)
    # daemon threads still in a request die with the process; the server
    # sees the closed socket and cancels
    sys.exit(0)


if __name__ == "__main__":
    main()
