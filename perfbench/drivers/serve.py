"""Serving cells: the server a user starts, loaded from where a user finds
it (``bin/dstpu_serve``'s ``build_service(parse_args(argv))``, unchanged),
under traffic from a client process of its own over HTTP/SSE.

Timeline of a run (seconds after the epoch, which is set once the server
is warm):

    set-up    build the service (weights on the device from --seed), send
              the warm-up requests that reach the widest shape buckets the
              cell's own lengths reach, start the client
    0..pre    the same traffic, before the window, so it opens in steady
              state (still set-up)
    window    pre .. pre + seconds: what is measured
    drain     requests due in the window are followed to their end
    check     counts, clean pool, no compile in the window, and sampled
              requests against the configuration's plain reference
"""

import json
import os
import subprocess
import sys
import time

from perfbench import clientlog, draws, harness, peaks, reference_check
from perfbench import trace_reduce, work

WINDOW_SPAN = "perfbench/trace_window"
#: the client's first request is due this long after it is started
CLIENT_START_S = 1.5
#: a traced run profiles TRACE_S seconds, from TRACE_LEAD_S into the window
TRACE_LEAD_S, TRACE_S = 1.0, 10.0
#: warm-up sends the next batch this long after the last one's replies: past
#: the boundary that admitted the batch before it, inside that batch's frame
ADMIT_GAP_S = 0.02


def load_dstpu_serve():
    """``bin/dstpu_serve`` has no ``.py``: load it by path (as the repo's
    ``chip_smoke.py`` does)."""
    from importlib.machinery import SourceFileLoader
    from importlib.util import module_from_spec, spec_from_loader
    loader = SourceFileLoader("dstpu_serve", os.path.join(
        harness.ROOT, "bin", "dstpu_serve"))
    mod = module_from_spec(spec_from_loader("dstpu_serve", loader))
    loader.exec_module(mod)
    return mod


def serve_argv(config, seed, run_dir):
    s = config["serve"]
    argv = ["--model", config["preset"], "--replicas", "1", "--port", "0",
            "--batch", str(s["batch"]), "--max-seq-len", str(s["max_seq_len"]),
            "--max-new-tokens", "32", "--seed", str(seed),
            "--flight-dir", os.path.join(run_dir, "flight")]
    if s.get("kv_blocks"):
        argv += ["--kv-blocks", str(s["kv_blocks"])]
    for field, value in harness.preset_overrides(config).items():
        argv += ["--set", f"{field}={value}"]
    return argv


def pow2(n):
    return 1 << max(0, n - 1).bit_length()


def warm_requests(requests, page, max_seq_len, frame_steps):
    """The fewest requests that bring the engine's two shape buckets
    (padded prompt width, block-table width: each the next power of two,
    and they only grow) to where the cell's own lengths take them. One
    request with the longest prompt does it; its output is as short as the
    table's bucket allows."""
    p_max = max(r["prompt_len"] for r in requests)
    pages = max(-(-(r["prompt_len"] + r["max_new"] + 1) // page)
                for r in requests)
    # long enough to outlast the wide frame that ends its prefill, so a
    # narrow (decode) frame runs too
    new = 3 * frame_steps
    while (pow2(-(-(p_max + new + 1) // page)) < pow2(pages)
           and p_max + new + 1 < max_seq_len):
        new += page
    return [{"key": 10 ** 9, "cls": "warm", "prompt_len": p_max,
             "max_new": new}]


def warm_admit_batches(send, upto):
    """The program writes a newly admitted batch of k rows into its slot
    table with small programs whose shapes hold k: the first time k
    requests are admitted at one frame boundary, each compiles (or is
    loaded), and every stream waits. Admit 1, 2 .. ``upto`` at once here,
    so that the window meets no new k whatever its timing. A chain: while
    the frame of batch k runs, batch k + 1 arrives and waits; it is
    admitted whole at that frame's end, when batch k's replies come back.
    ``send(key, prompt_len, max_new)`` returns a status."""
    import threading
    failures = []

    def one(key):
        status = send(key, 8, 2)
        if status != "ok":
            failures.append(status)

    def start(k, first_key):
        threads = [threading.Thread(target=one, args=(first_key + i,))
                   for i in range(k)]
        for th in threads:
            th.start()
        return threads

    key = 10 ** 9 + 1
    in_frame = start(1, key)
    for k in range(2, upto + 1):
        time.sleep(ADMIT_GAP_S)
        key += k
        waiting = start(k, key)
        for th in in_frame:
            th.join()
        in_frame = waiting
    for th in in_frame:
        th.join()
    if failures:
        raise SystemExit(f"perfbench: warm-up batch failed: {failures}")


def admit_batches(tracer, t0, t1):
    """Sizes of the batches the program admitted in [t0, t1), from its own
    ``engine.queue`` spans: requests admitted at one frame boundary end
    their wait within microseconds of each other."""
    ends = sorted(s["t1"] for tr in tracer.traces() for s in tr["spans"]
                  if s["name"] == "engine.queue" and t0 <= s["t1"] < t1)
    sizes, last = [], None
    for t in ends:
        if last is not None and t - last < 1e-3:
            sizes[-1] += 1
        else:
            sizes.append(1)
        last = t
    return sizes


class FrameLog:
    """A monitor for ``ServingTelemetry.attach_monitor``: the program calls
    it at every frame boundary; it notes the time and the counters. Only
    attached in a traced run."""

    def __init__(self, telemetry):
        self.telemetry = telemetry
        self.rows = []

    def write_events(self, events):
        del events
        c = self.telemetry.counters
        self.rows.append((time.monotonic(), c["tokens_emitted"],
                          c["prefill_tokens"], c["frames"],
                          self.telemetry.serve_view["frame_steps_last"]))

    def frames(self, t0, t1):
        """Per frame that ENDED in [t0, t1): (t, emitted, prefill, steps)."""
        out = []
        for prev, row in zip(self.rows, self.rows[1:]):
            if t0 <= row[0] < t1:
                out.append((row[0], row[1] - prev[1], row[2] - prev[2],
                            row[4]))
        return out


def snapshot(eng):
    return {"t": time.monotonic(), "counters": dict(eng.telemetry.counters),
            "gauges": dict(eng.telemetry.gauges),
            "compiles": eng.runner.compile_count_total()}


def sleep_until(t):
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


def traced_span(run, seconds):
    """Profile ``seconds`` of the window. Returns the trace's directory and
    the host times of the span."""
    import jax
    trace_dir = harness.start_profile(run.run_dir)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        time.sleep(seconds)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    return trace_dir, t0, t1


def warm_up(svc, config, seed, plan):
    """Every shape the traffic will use: the widest buckets, then every
    admit batch the slots (or a closed loop's callers) allow."""
    import perfbench.client as client
    eng, port = svc.engines["replica0"], svc.edge.edge_port
    vocab, slots = config["vocab_size"], int(config["serve"]["batch"])

    def send(key, prompt_len, max_new):
        prompt = draws.tokens_for(seed, {"key": key, "prompt_len": prompt_len},
                                  vocab)
        return client.sse_generate(port, prompt, max_new, time.monotonic,
                                   1100.0)[0]

    t0 = time.monotonic()
    for r in warm_requests(plan["requests"], eng.kv.block_size,
                           eng.max_seq_len, eng._config.frame_steps):
        status = send(r["key"], r["prompt_len"], r["max_new"])
        if status != "ok":
            raise SystemExit(f"perfbench: warm-up request failed: {status}")
    t1 = time.monotonic()
    upto = min(slots, plan.get("clients", slots))
    for _ in range(2):      # once more if a batch was split at a boundary
        warm_admit_batches(send, upto)
        met = set(admit_batches(svc.edge.tracer, t1, time.monotonic()))
        if met >= set(range(1, upto + 1)):
            break
    harness.log(f"warm-up: widest buckets {t1 - t0:.1f} s, admit batches "
                f"{time.monotonic() - t1:.1f} s, sizes met {sorted(met)}")


def start_client(run_dir, plan, port, seed, vocab):
    """Write the plan and start the client process. Returns (process,
    epoch, path of its log); the plan's times count from the epoch."""
    plan_path = os.path.join(run_dir, "plan.json")
    log_path = os.path.join(run_dir, "client.jsonl")
    with open(plan_path, "w") as fh:
        json.dump(plan, fh)
    epoch = time.monotonic() + CLIENT_START_S
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.HERE, "client.py"),
         "--plan", plan_path, "--out", log_path, "--port", str(port),
         "--epoch", repr(epoch), "--seed", str(seed), "--vocab", str(vocab)],
        stdout=subprocess.PIPE, text=True)
    return child, epoch, log_path


def finish_client(child, timeout_s):
    """Wait for the client; returns its last line (``records``,
    ``unfinished``). The process is ended whatever happens."""
    try:
        out, _ = child.communicate(timeout=timeout_s)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    return json.loads(out.strip().splitlines()[-1])


def run(run):
    import jax
    cell, config, traffic = run.cell, run.cell.config, run.cell.traffic
    serve = load_dstpu_serve()
    harness.log(f"building the service: {config['preset']} "
                f"{config['num_hidden_layers']} layers")
    svc = serve.build_service(serve.parse_args(
        serve_argv(config, run.seed, run.run_dir)))
    try:
        return measure(run, svc, jax)
    finally:
        svc.edge.shutdown()
        svc.driver.stop()


def measure(run, svc, jax):
    cell, config, traffic = run.cell, run.cell.config, run.cell.traffic
    eng = svc.engines["replica0"]
    port = svc.edge.edge_port
    vocab = config["vocab_size"]
    pre_s, drain_s = float(traffic["pre_window_s"]), float(traffic["drain_s"])
    generator = cell.module("generators", traffic["generator"])
    plan = generator.plan(traffic, traffic.get("schedule_seed", run.seed),
                          pre_s + run.seconds)
    plan.update(stop_t=pre_s + run.seconds,
                deadline_t=pre_s + run.seconds + drain_s)

    # ---- warm-up: every shape the window will use ----
    warm_up(svc, config, run.seed, plan)
    harness.log(f"frame programs: {eng.runner.compile_count()}; programs so "
                f"far: {len(run.compiles.built)} of which "
                f"{len(run.compiles.hits)} from the cache")

    frame_log = None
    if run.trace:
        eng.telemetry.trace = True       # serve_frame/w<width>/s<steps> spans
        frame_log = FrameLog(eng.telemetry)
        eng.telemetry.attach_monitor(frame_log, every_frames=1)

    # ---- the client, a process of its own ----
    child, epoch, log_path = start_client(run.run_dir, plan, port, run.seed,
                                          vocab)
    try:
        w0, w1 = epoch + pre_s, epoch + pre_s + run.seconds
        sleep_until(w0)
        setup_s = time.monotonic() - run.t_start
        snap0 = snapshot(eng)
        span = None
        if run.trace:
            sleep_until(w0 + TRACE_LEAD_S)
            span = traced_span(run, min(
                TRACE_S, max(0.5, run.seconds - TRACE_LEAD_S - 0.5)))
        sleep_until(w1)
        snap1 = snapshot(eng)
        tail = finish_client(child, drain_s + 60)
    except BaseException:
        child.kill()
        child.wait()
        raise
    records = clientlog.read_log(log_path)
    harness.log(f"client: {tail}")

    # ---- drained? ----
    deadline = time.monotonic() + 30
    while svc.driver.in_flight() and time.monotonic() < deadline:
        time.sleep(0.05)
    clean = (eng.kv.free_blocks == eng.kv.num_blocks - 1
             and not eng.state.seqs)
    if not clean:
        harness.log(f"unclean drain: {eng.kv.free_blocks} of "
                    f"{eng.kv.num_blocks} pages free, "
                    f"{len(eng.state.seqs)} sequences live")

    t0, t1 = pre_s, pre_s + run.seconds
    due = clientlog.due_in(records, t0, t1)
    unfinished = int(tail["unfinished"])
    failed = [r for r in due if r["status"] != "ok"]
    for r in failed[:5]:
        harness.log(f"failed request {r['key']}: {r['status']}")
    # every program asked of XLA in the window, compiled or loaded from the
    # cache: either way the serve loop waited for it
    asked = run.compiles.between(snap0["t"], snap1["t"])
    steady = clientlog.steadiness(records, t0, t1)
    harness.log(
        f"in the window: {asked} programs asked for "
        f"({snap1['compiles'] - snap0['compiles']} frame programs); admit "
        f"batches up to {max(admit_batches(svc.edge.tracer, w0, w1), default=0)}"
        f"; completed/due {steady['completed']}/{steady['due']}, in flight "
        f"{steady['in_flight']} and awaiting a first token "
        f"{steady['waiting']} at the start, middle and end")

    # ---- sampled requests against the plain reference ----
    reference = cell.module("configs", config["reference"])
    samples = pick_samples(clientlog.ok(due), traffic["check"])
    t_ref = time.monotonic()
    by_key = {r["key"]: r for r in plan["requests"]}
    ref_ok, worst = reference_check.check(
        reference, eng.params, config,
        [(f"{r['cls']}-{r['key']}",
          draws.tokens_for(run.seed, by_key[r["key"]], vocab), r["tokens"])
         for r in samples])
    harness.log(f"reference gaps (tolerance {reference_check.LOGIT_TOL}) in "
                f"{time.monotonic() - t_ref:.1f} s: {worst}")

    correct = (not failed and not unfinished and clean and ref_ok
               and asked == 0 and bool(samples)
               and all(r["status"] == "ok" for r in records))
    ctx = build_ctx(run, svc, records, unfinished, snap0, snap1, setup_s,
                    span, frame_log, epoch, asked)
    return {"correct": correct, "attempted": len(due) + unfinished,
            "failed": len(failed) + unfinished, "ctx": ctx}


def pick_samples(done, check):
    """Seeded by nothing: the first ``short`` and ``long`` completed
    requests of the window by prompt length, so the same requests are
    checked in every run of a seed."""
    by_len = sorted(done, key=lambda r: (r["prompt_len"], r["key"]))
    n_short, n_long = int(check["short"]), int(check["long"])
    picked = by_len[:n_short]
    for r in reversed(by_len):
        if len(picked) >= n_short + n_long:
            break
        if r not in picked:
            picked.append(r)
    return picked


def build_ctx(run, svc, records, unfinished, snap0, snap1, setup_s, span,
              frame_log, epoch, asked):
    """What the metric readers read: the client's records, the program's
    counters over the window, the queue spans, and the trace's reduction."""
    config, traffic = run.cell.config, run.cell.traffic
    eng = svc.engines["replica0"]
    t0 = float(traffic["pre_window_s"])
    t1 = t0 + run.seconds
    delta = {k: snap1["counters"][k] - snap0["counters"].get(k, 0)
             for k in snap1["counters"]}
    queue_waits = [(s["t1"] - s["t0"]) * 1e3
                   for tr in svc.edge.tracer.traces() for s in tr["spans"]
                   if s["name"] == "engine.queue"
                   and epoch + t0 <= s["t0"] < epoch + t1]
    d = work.dims(config)
    ctx = {"kind": "serve", "records": records, "t0": t0, "t1": t1,
           "window_s": run.seconds, "unfinished": unfinished,
           "setup_s": setup_s, "traffic": traffic, "config": config,
           "counters": delta, "gauges": snap1["gauges"],
           "kv_blocks": eng.kv.num_blocks, "window_compiles": asked,
           "queue_waits_ms": queue_waits, "dims": d,
           "memory_peak_bytes": harness.device_block(run.devices)[
               "memory_peak_bytes"],
           "trace": None, "span": None, "frames": None}
    if frame_log is not None:
        ctx["frames"] = frame_log.frames(epoch + t0, epoch + t1)
    if span is not None:
        ctx.update(reduce_span(run, span, frame_log, records, d))
    return ctx


def reduce_span(run, span, frame_log, records, d):
    """The traced span's device time, set against the work the program
    counted for the frames that ran in it."""
    trace_dir, h0, h1 = span
    trace = trace_reduce.load_newest(trace_dir)
    if trace is None:
        harness.log("the profiler wrote no trace")
        return {}
    red = trace_reduce.reduce_trace(trace, WINDOW_SPAN)
    if red is None:
        harness.log("no operation ran on a device in the traced span")
        return {}
    # work is counted per frame, so device time is taken over whole frames:
    # from the first to the last serve_frame span that lies in the trace
    p0, p1 = trace_reduce.find_span(trace, WINDOW_SPAN)
    whole = [s for s in trace_reduce.find_spans(trace, "serve_frame/")
             if s[0] >= p0 and s[1] <= p1]
    if not whole:
        harness.log("no whole serve_frame span in the trace")
        return {"trace": red}

    def host_time(p):
        return h0 + (p - p0) / 1e9

    aligned = trace_reduce.reduce_trace(
        trace, window=[whole[0][0], whole[-1][1]])
    # the program logs a frame just after its span ends
    frames = frame_log.frames(host_time(whole[0][1]) - 0.002,
                              host_time(whole[-1][1]) + 0.05)
    if len(frames) != len(whole):
        harness.log(f"{len(whole)} frame spans but {len(frames)} logged "
                    "frames in the traced span")
    emitted = sum(f[1] for f in frames)
    prefill = sum(f[2] for f in frames)
    steps = sum(f[3] for f in frames)
    wide = sum(1 for f in frames if f[2] > 0)
    done = [(r["prompt_len"], len(r["tokens"])) for r in clientlog.ok(records)]
    pk = peaks.peaks_for(run.devices[0].device_kind)
    floor_s, bound, flops, nbytes = work.serve_span_floor(
        d, pk, prefill_tokens=prefill, decode_tokens=emitted, steps=steps,
        requests=done or [(1, 1)])
    harness.log(
        f"traced frames {aligned['window_s']:.2f} s: busy "
        f"{aligned['busy_s']:.3f} s, "
        f"{len(frames)} frames ({wide} wide), {steps} steps, {prefill} "
        f"prompt + {emitted} generated tokens; floor {floor_s * 1e3:.1f} ms "
        f"({bound}-bound: {flops / 1e12:.2f} TFLOP, {nbytes / 1e9:.2f} GB)")
    return {"trace": red,
            "span": {"busy_s": aligned["busy_s"],
                     "window_s": aligned["window_s"],
                     "custom_call_s": aligned["custom_call_s"],
                     "frames": len(frames), "wide_frames": wide,
                     "steps": steps, "prefill_tokens": prefill,
                     "emitted_tokens": emitted, "floor_s": floor_s,
                     "bound": bound}}
