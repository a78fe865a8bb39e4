#!/usr/bin/env python
"""Serving benchmark: FastGen-analog measured end to end.

Produces the recorded artifact the round-2 review demanded (SERVING_rNN.json
via `python benchmarks/serving_bench.py > SERVING_rNN.json`): one JSON object
with a row per workload — decode-heavy, prefill-heavy, and mixed Dynamic-
SplitFuse — each carrying tokens/sec, per-step latency p50/p95, KV-pool
utilization, and host-scheduler overhead, plus the paged-Pallas vs XLA-gather
decode delta. Reference bar shape: ``blogs/deepspeed-fastgen/README.md:28,139``
(FastGen reports effective throughput and p50/p95 latency trade-offs; the
absolute rows here are gpt2-small-class on one v5e chip).

Methodology:
- the default row set measures the chip and exits nonzero without one; the
  focused contract modes (``--tp``, ``--router``, ``--chaos``, ...) assert
  token parity and also run where the CPU was asked for explicitly
  (``JAX_PLATFORMS=cpu``), on the ``tiny`` preset. Any failed row exits
  nonzero;
- decode throughput uses the COMPILED multi-token loop (one dispatch for N
  tokens), so host dispatch does not set the rate;
- the mixed workload intentionally uses host-driven ``step()`` so the number
  includes the real SplitFuse scheduler cost, which is reported separately
  as ``sched_overhead_pct`` (host wall-time share of the step loop);
- timings end in a device→host read of a value that depends on the step.
"""

import json
import logging
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mk_engine(model_name, batch, max_seq_len=None, expected_context=None):
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model
    cfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=max(batch, 16),
        max_tokens_per_step=max(batch * 2, 768),
        # the bench knows its workload; a server would pass its SLA numbers
        expected_context=expected_context,
        expected_concurrency=batch if expected_context else None,
    )
    model = build_model(model_name)
    return InferenceEngineV2(model, cfg, max_seq_len=max_seq_len)


def bench_platform_floor():
    """Cost of one streamed 32 MB pass over HBM on this chip, and the
    bandwidth it implies (v5e spec: 819 GB/s) — the context for the
    absolute decode numbers, which stream weights and KV the same way."""
    import time
    import jax
    import jax.numpy as jnp
    from jax import lax
    n = 32 * 1024 * 1024 // 2
    xs = jnp.ones((8, n), jnp.bfloat16)

    @jax.jit
    def run(xs, c):
        def body(c, x):
            return c + jnp.sum(x.astype(jnp.float32)), ()
        def rep(c, _):
            c, _n = lax.scan(body, c, xs)
            return c, ()
        c, _ = lax.scan(rep, c, None, length=6)
        return c

    c0 = jnp.zeros((), jnp.float32)
    run(xs, c0)
    jax.device_get(run(xs, c0))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(run(xs, c0))
        best = min(best, time.perf_counter() - t0)
    per = best / 48
    return {"workload": "platform-floor",
            "stream_32mb_op_ms": round(per * 1e3, 3),
            "effective_hbm_gbps": round(32 / 1024 / per, 1)}


def _kv_util(eng):
    total = eng.kv.num_blocks
    return round(1.0 - eng.kv.free_blocks / total, 4)


def bench_decode(model_name, batch, prompt_len, new_tokens):
    """Decode-heavy: steady-state generation throughput (compiled loop).
    The pool is workload-auto-sized (expected_context = prompt + generation
    budget) — r4's decode rows sat at 25% utilization on the memory-fraction
    default."""
    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + new_tokens)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, eng.model.cfg.vocab_size, (prompt_len,)).astype(np.int32)
               for _ in range(batch)]
    eng.generate(prompts, max_new_tokens=4)          # compile both step counts
    eng.generate(prompts, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=4)
    t1 = time.perf_counter()
    # KV utilization at the deepest point of the long run
    eng.put(list(range(batch)), prompts)
    while any(eng.state.seqs[u].in_prefill for u in range(batch)):
        eng.step()
    util = _kv_util(eng)
    eng.flush(list(range(batch)))
    t1b = time.perf_counter()
    eng.generate(prompts, max_new_tokens=new_tokens)
    t2 = time.perf_counter()
    decode_dt = (t2 - t1b) - (t1 - t0)               # marginal decode cost
    toks = batch * (new_tokens - 4)
    return {
        "workload": "decode-heavy", "model": model_name,
        "batch": batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "decode_tok_per_sec": round(toks / decode_dt, 1),
        "decode_ms_per_token_per_seq": round(decode_dt / (new_tokens - 4) * 1e3, 2),
        "e2e_tok_per_sec": round(batch * new_tokens / (t2 - t1b), 1),
        "kv_util_after_prefill": util,
    }


def bench_prefill(model_name, batch, prompt_len):
    """Prefill-heavy: prompt-token ingestion throughput via SplitFuse chunks."""
    eng = _mk_engine(model_name, batch, expected_context=prompt_len + 1)
    rng = np.random.default_rng(1)

    def run():
        prompts = [rng.integers(0, eng.model.cfg.vocab_size,
                                (prompt_len,)).astype(np.int32)
                   for _ in range(batch)]
        uids = list(range(batch))
        eng.put(uids, prompts)
        lat = []
        t0 = time.perf_counter()
        while any(eng.state.seqs[u].in_prefill for u in uids):
            s = time.perf_counter()
            eng.step()
            lat.append(time.perf_counter() - s)
        dt = time.perf_counter() - t0
        util = _kv_util(eng)
        eng.flush(uids)
        return dt, lat, util

    run()                                             # compile
    dt, lat, util = run()
    total = batch * prompt_len
    return {
        "workload": "prefill-heavy", "batch": batch, "prompt_len": prompt_len,
        "prefill_tok_per_sec": round(total / dt, 1),
        "step_ms_p50": round(statistics.median(lat) * 1e3, 2),
        "step_ms_p95": round(float(np.percentile(lat, 95)) * 1e3, 2),
        "kv_util_peak": util,
    }


def bench_mixed(model_name, batch, prompt_len, new_tokens):
    """Mixed SplitFuse: half the fleet decodes while half prefills — the
    host-driven step() loop, so the scheduler cost is IN the number."""
    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + new_tokens)
    rng = np.random.default_rng(2)
    vocab = eng.model.cfg.vocab_size

    def run():
        uids_a = list(range(0, batch // 2))
        uids_b = list(range(batch // 2, batch))
        eng.put(uids_a, [rng.integers(0, vocab, (prompt_len,)).astype(np.int32)
                         for _ in uids_a])
        # drive group A into decode
        while any(eng.state.seqs[u].in_prefill for u in uids_a):
            eng.step()
        # group B arrives: steps now fuse B's prefill chunks with A's decodes
        eng.put(uids_b, [rng.integers(0, vocab, (prompt_len,)).astype(np.int32)
                         for _ in uids_b])
        lat, produced = [], 0
        # time the scheduler from INSIDE step() (wrapping the bound method)
        # so each iteration schedules exactly once
        sched_box = [0.0]
        orig_schedule = eng._schedule

        def timed_schedule():
            s = time.perf_counter()
            out = orig_schedule()
            sched_box[0] += time.perf_counter() - s
            return out

        eng._schedule = timed_schedule
        t0 = time.perf_counter()
        while (any(eng.state.seqs[u].in_prefill for u in uids_b)
               or min(len(eng.state.seqs[u].generated) for u in uids_a + uids_b)
               < new_tokens):
            s = time.perf_counter()
            out = eng.step()
            produced += len(out)
            lat.append(time.perf_counter() - s)
        dt = time.perf_counter() - t0
        eng._schedule = orig_schedule
        sched_t = sched_box[0]
        util = _kv_util(eng)
        eng.flush(uids_a + uids_b)
        return dt, lat, sched_t, produced, util

    run()                                             # compile
    dt, lat, sched_t, produced, util = run()
    return {
        "workload": "mixed-splitfuse", "batch": batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "generated_tok_per_sec": round(produced / dt, 1),
        "step_ms_p50": round(statistics.median(lat) * 1e3, 2),
        "step_ms_p95": round(float(np.percentile(lat, 95)) * 1e3, 2),
        "sched_overhead_pct": round(100 * sched_t / dt, 2),
        "steps": len(lat), "kv_util_peak": util,
    }


def _poisson_schedule(vocab, prompt_len, n_arrivals, rate_hz, seed=3):
    """The shared Poisson arrival schedule (fixed seed): every dynamic
    serving contender — frame loop, speculative frame loop, host step loop —
    must measure against the SAME (prompts, offsets), or the side-by-side
    columns stop being comparable."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]
    gaps = rng.exponential(1.0 / rate_hz, n_arrivals)
    gaps[0] = 0.0
    return prompts, np.cumsum(gaps)


def _wallclock_arrivals(prompts, offsets, t_start):
    """serve() arrivals clock: each poll yields whatever the schedule says
    is due by now (possibly nothing)."""
    nxt = 0
    while nxt < len(prompts):
        now = time.perf_counter() - t_start
        due = []
        while nxt < len(prompts) and offsets[nxt] <= now:
            due.append((nxt, prompts[nxt]))
            nxt += 1
        yield due


def bench_mixed_dynamic(model_name, batch, prompt_len, new_tokens,
                        n_arrivals=32, rate_hz=40.0, frame_steps=8):
    """Dynamic arrivals (Poisson, fixed seed): the frame-based serve() loop
    vs the host-driven step() loop on the SAME arrival schedule. This is the
    workload the frame loop exists for — mixed-splitfuse showed the host
    step loop at ~1/9.5 of the statically-compiled path; here both
    contenders ingest mid-stream arrivals, so the gap this tracks is pure
    host-scheduling overhead, not admission capability."""
    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + new_tokens)
    prompts, offsets = _poisson_schedule(eng.model.cfg.vocab_size, prompt_len,
                                         n_arrivals, rate_hz)

    def run_frames():
        """serve() with wall-clock Poisson arrivals; returns (produced, dt,
        device_time) — dt - device_time is the host boundary cost."""
        arrivals = _wallclock_arrivals(prompts, offsets, time.perf_counter())
        produced = 0
        t0 = time.perf_counter()
        for _uid, toks in eng.serve(arrivals, max_new_tokens=new_tokens,
                                    frame_steps=frame_steps):
            produced += len(toks)
        dt = time.perf_counter() - t0
        c = eng.telemetry.counters      # this serve()'s dispatch + fetch
        return produced, dt, (c["host_dispatch_ns"]
                              + c["host_fetch_ns"]) / 1e9

    def run_host_steps():
        """The pre-frame-loop contender: put()+step() per token, same
        schedule, same admission control as serve() (full prompt+budget
        block reservation, FIFO deferral when the pool can't hold it —
        step() grows KV lazily, so without the reservation an over-admitted
        batch dies mid-decode)."""
        live, counts, produced = set(), {}, 0
        queue, nxt = [], 0
        final = prompt_len + new_tokens + 1

        def can_admit():
            growth = sum(eng.kv.blocks_for(final) -
                         len(eng.state.seqs[u].blocks) for u in live)
            return (len(live) < batch and
                    eng.kv.free_blocks - growth >= eng.kv.blocks_for(final))

        t0 = time.perf_counter()
        while nxt < n_arrivals or queue or live:
            now = time.perf_counter() - t0
            while nxt < n_arrivals and offsets[nxt] <= now:
                queue.append(nxt)
                nxt += 1
            while queue and can_admit():
                u = queue.pop(0)
                eng.put([u], [prompts[u]])
                counts[u] = 0
                live.add(u)
            if not live:
                continue
            out = eng.step()
            for u, _t in out.items():
                counts[u] += 1
                if counts[u] >= new_tokens:
                    eng.state.seqs[u].done = True
                    produced += counts[u]
                    eng.flush([u])
                    live.discard(u)
        return produced, time.perf_counter() - t0

    run_frames()                                      # compile both widths
    f_produced, f_dt, f_dev = run_frames()
    # telemetry state of the measured run: TTFT/ITL/E2E/queue-wait
    # percentile summaries ride in the bench JSON
    telemetry = {
        "latency_ms": eng.telemetry.latency_ms(),
        # run-AVERAGE occupancy and run-PEAK KV pressure (the live gauges
        # hold the near-empty final drain frame's figures, useless for
        # comparing configurations)
        "occupancy_avg": eng.telemetry.snapshot()["derived"]["occupancy_avg"],
        "kv_blocks_in_use_peak":
            eng.telemetry.gauges["kv_blocks_in_use_peak"],
        "admission_deferrals": eng.telemetry.counters["admission_deferrals"],
        "recompiled_programs": eng.runner.compile_count_total(),
    }
    run_host_steps()                                  # compile
    h_produced, h_dt = run_host_steps()
    return {
        "workload": "mixed-splitfuse-dynamic", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals, "arrival_rate_hz": rate_hz,
        "frame_steps": frame_steps,
        "frame_tok_per_sec": round(f_produced / f_dt, 1),
        "sched_overhead_pct": round(100 * (f_dt - f_dev) / f_dt, 2),
        "telemetry": telemetry,
        "host_step_tok_per_sec": round(h_produced / h_dt, 1),
        "frame_speedup": round((f_produced / f_dt) / (h_produced / h_dt), 2),
        "note": "same Poisson schedule for both loops; frame_tok_per_sec is "
                "the device-resident frame loop (host touches the loop only "
                "at frame boundaries), host_step_tok_per_sec the per-step "
                "host scheduler this PR retires for dynamic traffic",
    }


def bench_mixed_dynamic_spec(model_name, batch, prompt_len, new_tokens,
                             n_arrivals=32, rate_hz=40.0, frame_steps=8,
                             gamma=2):
    """Speculative decoding on the frame carry, measured on the SAME
    mixed-splitfuse-dynamic Poisson schedule as the non-speculative frame
    loop and the host step loop (same seed => identical arrival offsets).

    The draft is a SELF-draft (draft == target params): the high-acceptance
    upper bound, so ``tokens_per_target_forward`` approaches gamma+1 and the
    row isolates the architecture win (fewer target forwards per emitted
    token, zero extra host<->device transfers inside a frame) from draft
    quality. Wall-clock speedup additionally depends on the draft/target
    cost ratio — a self-draft pays the full target cost per proposal, so on
    real deployments expect a small draft and read acceptance_rate +
    tokens_per_target_forward to size the win."""
    base = bench_mixed_dynamic(model_name, batch, prompt_len, new_tokens,
                               n_arrivals=n_arrivals, rate_hz=rate_hz,
                               frame_steps=frame_steps)
    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + new_tokens)
    eng.attach_draft(eng.model, eng.params)
    prompts, offsets = _poisson_schedule(eng.model.cfg.vocab_size, prompt_len,
                                         n_arrivals, rate_hz)

    def run_spec():
        arrivals = _wallclock_arrivals(prompts, offsets, time.perf_counter())
        produced = 0
        t0 = time.perf_counter()
        for _uid, toks in eng.serve(arrivals, max_new_tokens=new_tokens,
                                    frame_steps=frame_steps, gamma=gamma):
            produced += len(toks)
        return produced, time.perf_counter() - t0

    run_spec()                                     # compile both widths
    produced, dt = run_spec()
    sp = eng.serve_stats["spec"]
    spec_tps = round(produced / dt, 1)
    return {
        "workload": "mixed-splitfuse-dynamic-spec", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals, "arrival_rate_hz": rate_hz,
        "frame_steps": frame_steps, "gamma": gamma, "draft": "self",
        "acceptance_rate": sp["acceptance_rate"],
        "tokens_per_target_forward": sp["tokens_per_target_forward"],
        "spec_frame_tok_per_sec": spec_tps,
        "frame_tok_per_sec": base.get("frame_tok_per_sec"),
        "host_step_tok_per_sec": base.get("host_step_tok_per_sec"),
        "spec_vs_frame_speedup": round(
            spec_tps / base["frame_tok_per_sec"], 2)
            if base.get("frame_tok_per_sec") else None,
        "spec_vs_host_step_speedup": round(
            spec_tps / base["host_step_tok_per_sec"], 2)
            if base.get("host_step_tok_per_sec") else None,
        "note": "same Poisson schedule for all three loops; the self-draft "
                "row bounds acceptance from above — wall-clock speedup on "
                "real serving scales with (1 + acceptance*gamma) / "
                "(1 + gamma*draft_cost_ratio)",
    }


def bench_telemetry_overhead(model_name, batch, prompt_len, new_tokens,
                             n_arrivals=16, repeats=5, assert_budget=False):
    """Telemetry-on vs telemetry-off serving throughput on an IDENTICAL
    deterministic arrival schedule (one arrival per frame-boundary poll — no
    wall clock, so both modes see byte-identical admission timing).

    The in-graph counters are always compiled into the frame, so the delta
    isolates exactly the host stats path this PR adds: the per-frame counter
    sync, lifecycle histograms, and view updates. ``repeats`` paired rounds
    in balanced order; the reported overhead is the geometric mean of the
    per-order median on/off ratios (see the inline measurement notes). In
    the smoke configuration (``assert_budget=True``) the run FAILS if that
    estimate exceeds 2% — the telemetry budget is a tested contract, not an
    aspiration."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 1000, (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]

    def run_once(eng):
        def arrivals():
            for u, p in enumerate(prompts):
                yield [(u, p)]
        produced = 0
        t0 = time.perf_counter()
        for _uid, toks in eng.serve(arrivals(), max_new_tokens=new_tokens):
            produced += len(toks)
        return produced, time.perf_counter() - t0

    # both modes on ONE engine (identical compiled programs — the in-graph
    # counters are always part of the frame), measured as PAIRED rounds:
    # each round times on and off back to back and contributes one on/off
    # ratio, so box-wide slowdowns (shared-CPU noise dwarfs the µs-scale
    # host stats path at smoke size) hit both halves alike and cancel.
    # Rounds run in BALANCED order (half on-first, half off-first) because
    # the first serve after a mode switch pays a measurable cache penalty
    # on a contended box; the geometric mean of the two per-order medians
    # cancels that bias, which a single median over alternating rounds
    # does not (odd counts leave one order over-represented).
    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + new_tokens)
    run_once(eng)                                     # compile
    ratios = {("on", "off"): [], ("off", "on"): []}
    best = {"on": 1e9, "off": 1e9}
    produced = 0

    def measure_rounds(n):
        nonlocal produced
        for r in range(n):
            dts = {}
            order = ("on", "off") if r % 2 == 0 else ("off", "on")
            for mode in order:
                eng.telemetry.enabled = mode == "on"
                produced, dts[mode] = run_once(eng)
                best[mode] = min(best[mode], dts[mode])
            ratios[order].append(dts["on"] / dts["off"])

    def estimate():
        meds = [statistics.median(v) for v in ratios.values() if v]
        g = 1.0
        for m in meds:
            g *= m
        return 100 * (g ** (1.0 / len(meds)) - 1.0)

    rounds = 2 * ((repeats + 1) // 2)                 # round UP to balanced
    measure_rounds(rounds)
    # one retry pass absorbs a fully contended measurement window before
    # the smoke assert fires (fresh rounds fold into the medians)
    if assert_budget and estimate() >= 2.0:
        measure_rounds(rounds)
    eng.telemetry.enabled = True
    run_once(eng)                                     # telemetry for the row
    tel_summary = eng.telemetry.latency_ms()
    results = {m: {"tok_per_sec": round(produced / b, 1),
                   "best_s": round(b, 4)} for m, b in best.items()}
    all_ratios = [r for v in ratios.values() for r in v]
    overhead_pct = round(estimate(), 2)
    overhead_pct_min = round(100 * (min(all_ratios) - 1.0), 2)
    row = {
        "workload": "telemetry-overhead", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals, "repeats": repeats,
        "paired_rounds_run": len(all_ratios),   # may exceed repeats (retry)
        "telemetry_on_tok_per_sec": results["on"]["tok_per_sec"],
        "telemetry_off_tok_per_sec": results["off"]["tok_per_sec"],
        "overhead_pct": overhead_pct,
        "overhead_pct_min": overhead_pct_min,
        "within_2pct_budget": overhead_pct < 2.0,
        "latency_ms": tel_summary,
        "note": "same deterministic schedule both modes; in-graph counters "
                "are compiled in regardless, so this is the host stats "
                "path alone. overhead_pct = geometric mean of the "
                "per-order median paired on/off ratios (cancels both "
                "box-wide noise and first-runner bias); overhead_pct_min "
                "is the single cleanest round",
    }
    if assert_budget:
        assert overhead_pct < 2.0, \
            f"telemetry overhead {overhead_pct}% exceeds the 2% budget: {row}"
    return row


def bench_tracing_overhead(model_name, batch, prompt_len, new_tokens,
                           n_arrivals=16, repeats=5, assert_budget=False):
    """Tracing-on vs tracing-off serving throughput on an IDENTICAL
    deterministic arrival schedule — the distributed-tracing twin of
    ``bench_telemetry_overhead`` (same paired-round/balanced-order
    measurement; see its inline notes). Telemetry is ENABLED in both
    modes, so the delta isolates exactly what the tracing PR adds: span
    minting, boundary span appends into the ``TraceCollector``, and the
    one-sample-per-trace fleet histograms. Spans are stamped at frame
    boundaries only — the compiled frames are byte-identical either way —
    so the budget is the same <2% contract the telemetry row pins
    (asserted in the smoke configuration, reported on TPU)."""
    from deepspeed_tpu.inference.v2.tracing import TraceCollector
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 1000, (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]

    def run_once(eng):
        def arrivals():
            for u, p in enumerate(prompts):
                yield [(u, p)]
        produced = 0
        t0 = time.perf_counter()
        for _uid, toks in eng.serve(arrivals(), max_new_tokens=new_tokens):
            produced += len(toks)
        return produced, time.perf_counter() - t0

    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + new_tokens)
    collector = TraceCollector(max_traces=64)   # steady-state bounded ring
    run_once(eng)                               # compile
    ratios = {("on", "off"): [], ("off", "on"): []}
    best = {"on": 1e9, "off": 1e9}
    produced = 0

    def measure_rounds(n):
        nonlocal produced
        for r in range(n):
            dts = {}
            order = ("on", "off") if r % 2 == 0 else ("off", "on")
            for mode in order:
                eng.telemetry.set_tracer(
                    collector if mode == "on" else None, replica="bench")
                produced, dts[mode] = run_once(eng)
                best[mode] = min(best[mode], dts[mode])
            ratios[order].append(dts["on"] / dts["off"])

    def estimate():
        meds = [statistics.median(v) for v in ratios.values() if v]
        g = 1.0
        for m in meds:
            g *= m
        return 100 * (g ** (1.0 / len(meds)) - 1.0)

    rounds = 2 * ((repeats + 1) // 2)
    measure_rounds(rounds)
    if assert_budget and estimate() >= 2.0:
        measure_rounds(rounds)                  # retry absorbs a noisy window
    eng.telemetry.set_tracer(None)
    all_ratios = [r for v in ratios.values() for r in v]
    overhead_pct = round(estimate(), 2)
    results = {m: {"tok_per_sec": round(produced / b, 1),
                   "best_s": round(b, 4)} for m, b in best.items()}
    snap = collector.snapshot()
    row = {
        "workload": "tracing-overhead", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals, "repeats": repeats,
        "paired_rounds_run": len(all_ratios),
        "tracing_on_tok_per_sec": results["on"]["tok_per_sec"],
        "tracing_off_tok_per_sec": results["off"]["tok_per_sec"],
        "overhead_pct": overhead_pct,
        "overhead_pct_min": round(100 * (min(all_ratios) - 1.0), 2),
        "within_2pct_budget": overhead_pct < 2.0,
        "traces_minted": snap["counters"]["traces_minted"],
        "spans_recorded": snap["counters"]["spans_recorded"],
        "fleet_ttft_ms": snap["fleet_ttft_ms"],
        "note": "same deterministic schedule both modes, telemetry ON in "
                "both — the delta is span production + collection alone "
                "(frame-boundary stamps, no compiled-program change). "
                "Measurement = geometric mean of per-order median paired "
                "on/off ratios, the telemetry row's estimator",
    }
    if assert_budget:
        assert overhead_pct < 2.0, \
            f"tracing overhead {overhead_pct}% exceeds the 2% budget: {row}"
    return row


def bench_scheduler(model_name, batch, prompt_len, new_tokens,
                    slo_ttft_ms=None):
    """FIFO vs SLO-aware scheduling under a DETERMINISTIC 2-tenant overload
    schedule (arrivals keyed to frame-boundary polls, no wall clock, so
    both modes see identical admission opportunities):

    * tenant "bulk" front-loads a burst of 2x-slot-count best-effort long
      jobs that saturates the table and queues deep (its queue quota sheds
      the deepest arrivals deterministically);
    * tenant "chat" then streams short interactive requests with a TTFT
      SLO.

    FIFO serves the burst in arrival order, so every chat request waits
    behind bulk; the scheduler jumps chat over the queue and preempts live
    bulk rows (plus SLO shedding/deferral and frame shrinking when the
    measured TTFT p90 actually breaches the target — wall-clock-dependent,
    so the deterministic shed in this row comes from the bulk queue
    quota). Per-class TTFT p90 comes from recorded spans, computed
    identically for both modes; goodput counts retired tokens only (shed
    work produces nothing)."""
    import jax
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    # the SLO target is meant to be breachable-but-sane for the platform;
    # CPU smoke frames are ~ms-scale, so a TPU-grade 50 ms target would
    # just pin the control loop at critical and measure compile noise
    if slo_ttft_ms is None:
        slo_ttft_ms = 50.0 if jax.default_backend() == "tpu" else 1000.0
    n_slots = batch
    n_bulk, n_chat = 2 * batch, batch
    # bulk jobs must OUTLIVE many frames (that is what makes the burst an
    # overload instead of a blip): several frames' worth of decode budget
    bulk_new = 6 * new_tokens
    chat_new = max(4, new_tokens // 2)
    eng = _mk_engine(model_name, batch,
                     expected_context=prompt_len + bulk_new)
    eng.telemetry.record_spans = True
    rng = np.random.default_rng(11)
    vocab = eng.model.cfg.vocab_size
    bulk_p = [rng.integers(0, vocab, (prompt_len,)).astype(np.int32)
              for _ in range(n_bulk)]
    chat_p = [rng.integers(0, vocab, (prompt_len // 4,)).astype(np.int32)
              for _ in range(n_chat)]
    classes = {u: "best_effort" for u in range(n_bulk)}
    classes.update({n_bulk + i: "interactive" for i in range(n_chat)})

    def arrivals():
        yield [{"uid": u, "tokens": bulk_p[u], "max_new_tokens": bulk_new,
                "tenant": "bulk", "priority": "best_effort"}
               for u in range(n_bulk)]
        for i in range(n_chat):
            yield []
            yield [{"uid": n_bulk + i, "tokens": chat_p[i],
                    "max_new_tokens": chat_new, "tenant": "chat",
                    "priority": "interactive", "slo_ms": slo_ttft_ms}]

    def mk_sched():
        return RequestScheduler(SchedulerConfig(
            slo_ttft_ms=slo_ttft_ms,
            tenant_weights={"chat": 2.0, "bulk": 1.0},
            # bulk may queue at most one table's worth beyond its live
            # rows; the burst's tail sheds with a structured reason
            tenant_max_queued=n_slots, aging_frames=16))

    def run(scheduler):
        produced = 0
        t0 = time.perf_counter()
        for _uid, toks in eng.serve(arrivals(), max_new_tokens=new_tokens,
                                    frame_slots=n_slots,
                                    scheduler=scheduler):
            produced += len(toks)
        dt = time.perf_counter() - t0
        spans = {s["uid"]: s for s in eng.telemetry.spans}
        ttft = {"interactive": [], "best_effort": []}
        for u, cls in classes.items():
            s = spans.get(u)
            if s is not None and s.get("first_token_t") is not None:
                ttft[cls].append((s["first_token_t"] - s["enqueue_t"]) * 1e3)
        eng.telemetry.spans.clear()
        out = {
            "goodput_tok_per_sec": round(produced / dt, 1),
            "completed_requests": len(spans),
        }
        for cls, vals in ttft.items():
            out[f"{cls}_ttft_p90_ms"] = round(
                float(np.percentile(vals, 90)), 2) if vals else None
            out[f"{cls}_completed"] = len(vals)
        return out

    # warm BOTH paths (the scheduler run compiles extra programs: the
    # re-prefill prompt bucket after a preemption, pressure-capped frame
    # steps) so neither timed run pays compile
    run(None)
    run(mk_sched())
    eng.telemetry.spans.clear()
    fifo = run(None)
    sched = mk_sched()
    slo = run(sched)
    submitted = n_bulk + n_chat
    slo.update({
        "shed_requests": sched.stats()["shed_total"],
        "shed_rate": round(sched.stats()["shed_total"] / submitted, 4),
        "preempted": sched.stats()["preempted"],
        "admitted_by_class": sched.stats()["admitted_by_class"],
        "slo_risk_final": sched.stats()["risk"],
    })
    fi, si = fifo["interactive_ttft_p90_ms"], slo["interactive_ttft_p90_ms"]
    return {
        "workload": "scheduler-slo", "batch": batch, "slots": n_slots,
        "prompt_len": prompt_len, "bulk_new_tokens": bulk_new,
        "chat_new_tokens": chat_new,
        "bulk_requests": n_bulk, "chat_requests": n_chat,
        "slo_ttft_ms": slo_ttft_ms,
        "fifo": fifo, "slo_aware": slo,
        "interactive_ttft_p90_speedup": round(fi / si, 2)
        if fi and si else None,
        "note": "deterministic 2-tenant overload, identical arrival "
                "schedule both modes; goodput counts retired tokens only "
                "(shed best-effort work produces none). The SLO row should "
                "show interactive TTFT p90 well under FIFO's — chat "
                "arrivals jump the bulk queue and preempt live bulk rows "
                "— at the cost of shed/deferred bulk work",
    }


def bench_chaos(model_name, batch, prompt_len, new_tokens, n_arrivals=12):
    """Fault-tolerant serving under a FIXED fault schedule vs the
    fault-free baseline, on one deterministic arrival schedule (one
    arrival per frame-boundary poll — no wall clock, so both runs see
    identical admission timing).

    Three measured legs:

    * **baseline** — fault-free serve (goodput reference);
    * **chaos** — same schedule under transient dispatch failures
      (absorbed by bounded retry), one poisoned row (quarantined
      mid-flight), and a KV-alloc failure window (admission deferral);
      overhead = the goodput cost of surviving all of it;
    * **kill+resume** — same schedule again, crashed by a fatal dispatch
      fault mid-run, then resumed from the automatic ledger snapshot;
      reports the recovery-time gauge and end-to-end goodput including
      the crash.

    Correctness is asserted inline (surviving outputs token-identical to
    the baseline, KV pool drained) — the chaos row doubles as a smoke
    check, mirroring the telemetry-overhead row's tested-contract style."""
    from deepspeed_tpu.inference.v2.faults import (FaultInjector,
                                                   FrameDispatchError)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 1000, (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]

    def arrivals():
        for u, p in enumerate(prompts):
            yield [(u, p)]

    def mk():
        eng = _mk_engine(model_name, batch,
                         expected_context=prompt_len + new_tokens)
        eng._config.frame_retry_backoff_s = 0.0   # measure work, not sleep
        return eng

    def run(eng, faults=None, resume_from=None, arr=None):
        outs, produced = {}, 0
        t0 = time.perf_counter()
        for uid, toks in eng.serve(arr if arr is not None else arrivals(),
                                   max_new_tokens=new_tokens, faults=faults,
                                   resume_from=resume_from):
            outs[uid] = toks
            produced += len(toks)
        return outs, produced, time.perf_counter() - t0

    eng = mk()
    run(eng)                                         # compile
    base_outs, base_produced, base_dt = run(eng)

    poison_uid = n_arrivals // 2
    chaos_schedule = [
        {"kind": "dispatch_exception", "frame": 2, "times": 2},
        {"kind": "poison_row", "frame": n_arrivals // 2, "uid": poison_uid},
        {"kind": "kv_alloc_fail", "frame": 4, "times": 2},
    ]
    inj = FaultInjector(chaos_schedule)
    chaos_outs, chaos_produced, chaos_dt = run(eng, faults=inj)
    assert poison_uid not in chaos_outs, "poisoned row must not be yielded"
    for u, toks in chaos_outs.items():
        np.testing.assert_array_equal(base_outs[u], toks,
                                      err_msg=f"uid={u} diverged under chaos")
    assert eng.kv.free_blocks == eng.kv.num_blocks - 1
    chaos_counters = {k: eng.telemetry.counters[k]
                      for k in ("faults", "quarantined", "frame_retries",
                                "deadline_expired")}

    # ---- kill + resume: fatal fault mid-run, resume from the snapshot ----
    fatal = FaultInjector([{"kind": "dispatch_exception",
                            "frame": n_arrivals // 2, "times": 100}])
    resumed_outs, produced_crash = {}, 0
    t0 = time.perf_counter()
    try:
        for uid, toks in eng.serve(arrivals(), max_new_tokens=new_tokens,
                                   faults=fatal):
            resumed_outs[uid] = toks
            produced_crash += len(toks)
        raise AssertionError("fatal fault schedule did not crash the serve")
    except FrameDispatchError:
        pass
    snap = eng.last_crash_snapshot
    in_flight = len(snap["requests"])
    rest, produced_rest, _ = run(eng, resume_from=snap, arr=iter([[]]))
    resume_dt = time.perf_counter() - t0
    resumed_outs.update(rest)
    for u, toks in resumed_outs.items():
        np.testing.assert_array_equal(
            base_outs[u], toks, err_msg=f"uid={u} diverged across restart")
    # arrivals the crashed run never polled are the front-end's to replay;
    # completeness here covers everything the engine had accepted
    recovery_ms = eng.telemetry.gauges["last_recovery_ms"]

    base_tps = base_produced / base_dt
    chaos_tps = chaos_produced / chaos_dt
    resume_tps = (produced_crash + produced_rest) / resume_dt
    return {
        "workload": "chaos-serving", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals,
        "fault_schedule": chaos_schedule,
        "baseline_tok_per_sec": round(base_tps, 1),
        "chaos_tok_per_sec": round(chaos_tps, 1),
        # per-token time under chaos vs baseline (goodput-normalized, so
        # the quarantined row's missing tokens don't read as overhead)
        "chaos_overhead_pct": round(
            100 * ((chaos_dt / chaos_produced)
                   / (base_dt / base_produced) - 1), 2)
        if chaos_produced else None,
        "chaos_goodput_ratio": round(chaos_tps / base_tps, 4),
        "chaos_counters": chaos_counters,
        "kill_resume": {
            "in_flight_at_crash": in_flight,
            "recovery_ms": recovery_ms,
            "goodput_tok_per_sec": round(resume_tps, 1),
            "goodput_ratio_vs_baseline": round(resume_tps / base_tps, 4),
            "recoveries": eng.telemetry.counters["recoveries"],
        },
        "note": "same deterministic schedule all three legs; chaos leg "
                "survives 2 transient dispatch failures + 1 poisoned row "
                "+ a 2-boundary KV-alloc outage (survivor outputs asserted "
                "token-identical, pool drain asserted); kill+resume leg "
                "crashes mid-run and resumes from the automatic ledger "
                "snapshot (outputs asserted token-identical across the "
                "restart)",
    }


def bench_router(model_name, batch, prompt_len, new_tokens, n_arrivals=12):
    """Multi-engine router: fleet goodput under a deterministic engine-kill
    schedule vs the no-failure fleet baseline, on one deterministic arrival
    schedule (one arrival per router tick, every request pinned to ONE
    replica by session affinity so the kill actually orphans work).

    Three measured legs:

    * **single** — one engine, no router (the pre-PR reference; its greedy
      outputs are THE parity target for both fleet legs);
    * **fleet** — two replicas behind ``EngineRouter``, fault-free
      (placement + cooperative stepping overhead);
    * **kill+failover** — same schedule, the affinity-pinned replica
      hard-killed mid-stream by the scripted ``RouterFaultInjector``; the
      router splits its snapshot per-request and re-admits everything on
      the survivor. Reports the kill/baseline goodput ratio and the
      router's failover ``recovery_ms`` (last kill -> every orphaned
      request re-placed on a healthy peer's feed).

    Correctness is asserted inline (every accepted request completes on
    every leg, token-identical to the single-engine run; zero
    requests_failed; the victim ends quarantined) — the row doubles as a
    smoke check, mirroring bench_chaos's tested-contract style."""
    import jax
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.faults import RouterFaultInjector
    from deepspeed_tpu.inference.v2.router import (EngineRouter,
                                                   RouterConfig, QUARANTINED)
    from deepspeed_tpu.models import build_model

    # one model + params shared by every replica: heterogeneous DEGREES are
    # the tests' business (tp=1<->tp=8 under the multichip marker); the
    # bench measures routing overhead and failover, which need identical
    # weights for the token-identity asserts to mean anything
    model = build_model(model_name)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, model.cfg.vocab_size - 5,
                            (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]

    def arrivals():
        # dict arrivals, ALL up front, one session: affinity pins the
        # whole stream to a single replica and the front-loaded queue
        # (slots < arrivals) guarantees the tick-3 kill orphans live rows
        # AND queued work — the failover path under real load, not a kill
        # of an already-idle replica
        yield [{"uid": u, "tokens": p, "session": "pinned"}
               for u, p in enumerate(prompts)]

    def mk():
        # slots below the arrival count build a real queue; small frames
        # keep requests in flight across several router ticks
        cfg = RaggedInferenceEngineConfig(
            max_ragged_batch_size=batch,
            max_tokens_per_step=max(batch * 2, 768),
            frame_steps=2,
            expected_context=prompt_len + new_tokens,
            expected_concurrency=batch)
        eng = InferenceEngineV2(model, cfg, params=params,
                                max_seq_len=prompt_len + new_tokens + 2)
        eng._config.frame_retry_backoff_s = 0.0   # measure work, not sleep
        return eng

    engines = {"a": mk(), "b": mk()}

    def run(router=None, faults=None):
        src = engines["a"].serve(arrivals(), max_new_tokens=new_tokens) \
            if router is None else \
            router.serve(arrivals(), max_new_tokens=new_tokens,
                         faults=faults)
        outs, produced = {}, 0
        t0 = time.perf_counter()
        for uid, toks in src:
            outs[uid] = toks
            produced += len(toks)
        return outs, produced, time.perf_counter() - t0

    run()                                            # compile engine a
    # compile engine b too (the failover leg lands everything on it; a
    # cold survivor would bill its frame compiles to recovery)
    outs_b, _, _ = run(EngineRouter({"b": engines["b"]}))
    base_outs, base_produced, base_dt = run()
    for u, toks in outs_b.items():
        np.testing.assert_array_equal(
            base_outs[u], toks, err_msg=f"uid={u}: replicas diverged")

    # backoff must exceed the WORST-CASE run length in ticks (the big TPU
    # workload runs for hundreds of decode ticks): if the victim rejoins
    # mid-run, the final QUARANTINED assert below fails even though
    # failover itself worked
    mk_router = lambda: EngineRouter(    # noqa: E731 — two identical legs
        engines, RouterConfig(quarantine_backoff_ticks=1 << 20))
    fleet_outs, fleet_produced, fleet_dt = run(mk_router())
    for u, toks in fleet_outs.items():
        np.testing.assert_array_equal(
            base_outs[u], toks, err_msg=f"uid={u} diverged behind router")

    router = mk_router()
    victim = router._pick("pinned")
    inj = RouterFaultInjector(
        [{"kind": "engine_kill", "tick": 3, "engine": victim}])
    kill_outs, kill_produced, kill_dt = run(router, faults=inj)
    for u, toks in kill_outs.items():
        np.testing.assert_array_equal(
            base_outs[u], toks,
            err_msg=f"uid={u} diverged across kill+failover")
    assert set(kill_outs) == set(base_outs), \
        "every accepted request must complete across the failover"
    st = router.stats()
    assert st["counters"]["requests_failed"] == 0
    assert st["counters"]["engine_kills"] == 1
    assert st["counters"]["reroutes"] >= 1, \
        "the kill must orphan in-flight work (else the leg measured nothing)"
    assert st["replicas"][victim] == QUARANTINED
    for eng in engines.values():
        assert eng.kv.free_blocks == eng.kv.num_blocks - 1, \
            "KV pool must drain on every replica"

    base_tps = base_produced / base_dt
    fleet_tps = fleet_produced / fleet_dt
    kill_tps = kill_produced / kill_dt
    return {
        "workload": "router-failover", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals, "replicas": 2,
        "kill_schedule": [{"kind": "engine_kill", "tick": 3,
                           "engine": victim}],
        "single_engine_tok_per_sec": round(base_tps, 1),
        "fleet_tok_per_sec": round(fleet_tps, 1),
        "fleet_goodput_ratio": round(fleet_tps / base_tps, 4),
        "kill_tok_per_sec": round(kill_tps, 1),
        "kill_goodput_ratio": round(kill_tps / base_tps, 4),
        "recovery_ms": router.last_recovery_ms,
        "router_counters": {k: st["counters"][k]
                            for k in ("placements", "failovers", "reroutes",
                                      "completions", "requests_failed")},
        "note": "same deterministic pinned-session schedule all three "
                "legs; fleet leg measures routing overhead (one engine "
                "does the work — affinity pins the session), kill leg "
                "hard-kills the pinned replica at tick 3 and fails every "
                "in-flight request over to the survivor via per-request "
                "snapshot split (outputs asserted token-identical to the "
                "single-engine run, zero requests_failed); recovery_ms is "
                "kill -> all orphans re-placed, excluding the survivor's "
                "own re-prefill (its recovery gauges cover that)",
    }


def bench_disagg(model_name, batch, long_prompt, short_prompt,
                 long_new, short_new, n_long=5, n_short=8,
                 assert_contract=True, model_overrides=None, chunk=None):
    """Disaggregated prefill/decode fleet vs the monolithic fleet at
    EQUAL replica count, on one deterministic long-prompt/short-decode
    mix (the workload disaggregation exists for: long prefills stall a
    monolithic replica's frame boundary — every decode row coasting in
    its wide frames pays chunk-sized steps — while a decode replica that
    never sees a wide frame streams at width-1 cost).

    Three measured legs, same arrival schedule:

    * **single** — one unified engine (greedy outputs are THE parity
      target for both fleets);
    * **mono fleet** — two unified replicas behind ``EngineRouter``
      (every replica does both jobs);
    * **disagg fleet** — one prefill + one decode replica over a SHARED
      ``KVSwapTier``: prefill-heavy arrivals route to the prefill
      replica, which publishes committed pages at the watermark and
      hands off; the decode replica restores the pages and streams.

    Reports fleet-merged TTFT p90 and decode ITL p90 per leg — EXACT
    percentiles over raw samples, measured on per-replica BUSY-TIME
    clocks (each engine's clock advances only while its own frames run:
    the latency a thread-per-replica driver delivers, since the serial
    cooperative router would sum every replica's frame into every
    wall-clock gap and mask exactly the contention disaggregation
    removes; resumed continuations record no TTFT, so a handoff
    request's TTFT is its true first token on the prefill side). Each
    fleet leg is the MEDIAN of 5 interleaved rounds. ASSERTS (CPU smoke)
    the tentpole contract: the disagg fleet improves BOTH percentiles vs
    the mono fleet — operationalized as winning the strict MAJORITY of
    PAIRED rounds per metric (round i's legs run back-to-back, so the
    pairing cancels the slow shared-box drift that leaks into aggregate
    medians) — with all outputs token-identical to the single engine. The CPU-smoke margins are modest (a few percent on latency,
    ~1.4x throughput): the stock tiny model's frames are
    dispatch-overhead-bound, so the wide-frame FLOP tax the architecture
    removes is mostly invisible here — the real-chip economics (a chunk-
    wide frame costs chunk x a decode frame) are where the split pays."""
    import jax
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.kv_hierarchy import KVSwapTier
    from deepspeed_tpu.inference.v2.router import EngineRouter, RouterConfig
    from deepspeed_tpu.inference.v2.telemetry import LogBucketHistogram
    from deepspeed_tpu.models import build_model
    import tempfile

    # finer latency buckets for THIS bench: the telemetry default (x2
    # geometric growth) quantizes p90 to within a factor of 2 — a real
    # 1.5-2x fleet-level gap can land both legs in one bucket and read
    # as a tie. 1.15x growth resolves ~15% differences; restored in the
    # finally below so no other row inherits it.
    growth_defaults = LogBucketHistogram.__init__.__defaults__
    LogBucketHistogram.__init__.__defaults__ = (1e-4, 1.15, 120)
    # ...and keep RAW samples beside the buckets: the percentile CONTRACT
    # below compares two fleets whose true gap can sit inside one bucket —
    # exact sample percentiles make a tie mean "actually equal", not
    # "same bucket". Restored in the finally.
    _orig_record = LogBucketHistogram.record

    def _recording(self, value, count=1):
        _orig_record(self, value, count)
        if count > 0:
            self._raw = getattr(self, "_raw", [])
            self._raw.extend([value] * count)

    LogBucketHistogram.record = _recording

    try:
        model = build_model(model_name, **(model_overrides or {}))
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(31)
        chunk = chunk or max(16, long_prompt // 8)
        longs = {u: rng.integers(0, model.cfg.vocab_size - 5,
                                 (long_prompt,)).astype(np.int32)
                 for u in range(n_long)}
        shorts = {100 + u: rng.integers(0, model.cfg.vocab_size - 5,
                                        (short_prompt,)).astype(np.int32)
                  for u in range(n_short)}

        def arrivals():
            # a realistic interactive mix: BURSTS of short requests (>90%
            # of arrivals — the population whose p90 the SLO story is
            # about; bursty admission matters because the frame width is
            # global, so one boundary admits a whole burst with a single
            # chunk-wide frame instead of going wide every tick) with
            # long prompts dripped in between bursts. On the mono fleet
            # each long stretches its replica's frames to chunk width for
            # the whole prefill, taxing every short decoding beside it;
            # concurrency stays under the slot count so queueing never
            # masks the frame-latency effect.
            items = list(shorts.items())
            long_items = list(longs.items())
            burst = max(4, n_short // max(1, n_long + 1))
            burst_every = max(6, short_new // 2)
            long_every = max(2, (n_long + 1 and
                                 (burst_every * (n_long + 2)) //
                                 max(1, n_long + 1)))
            tick = 0
            while items or long_items:
                b = []
                if items and tick % burst_every == 0:
                    for _ in range(burst):
                        if items:
                            u, t = items.pop(0)
                            b.append({"uid": u, "tokens": t,
                                      "max_new_tokens": short_new})
                if long_items and tick % long_every == long_every // 2:
                    u, t = long_items.pop(0)
                    b.append({"uid": u, "tokens": t,
                              "max_new_tokens": long_new})
                yield b
                tick += 1

        def mk(**over):
            kw = dict(max_ragged_batch_size=batch,
                      max_tokens_per_step=max(batch * 2, 768),
                      prefill_chunk_size=chunk, frame_steps=2,
                      expected_context=long_prompt + short_new,
                      expected_concurrency=batch)
            kw.update(over)
            eng = InferenceEngineV2(
                model, RaggedInferenceEngineConfig(**kw), params=params,
                max_seq_len=long_prompt + max(long_new, short_new) + 2)
            eng._config.frame_retry_backoff_s = 0.0
            return eng

        def merged_p90_ms(engines, name):
            raw = [v for e in engines
                   for v in getattr(e.telemetry.hists[name], "_raw", [])]
            if not raw:
                return None
            return round(float(np.percentile(np.asarray(raw), 90)) * 1e3, 3)

        class _BusyClock:
            """Per-replica BUSY-TIME clock: advances only while THIS
            engine's frames execute. The serial cooperative router sums
            every replica's frame into every wall-clock gap — both legs
            would measure the same tick time, masking exactly the
            contention disaggregation removes. Busy time is the latency a
            thread-per-replica driver (ROADMAP item 2a) delivers: a
            decode row's inter-token gap is ITS replica's frame time, so
            a monolithic replica's wide prefill frames tax its decode
            stream and a disaggregated decode replica's never do."""

            def __init__(self):
                self.t = 0.0

            def __call__(self):
                return self.t

        def attach_busy_clock(eng):
            clk = _BusyClock()
            orig = eng._run_frame_resilient

            def timed(*frame_args):
                t0 = time.perf_counter()
                try:
                    return orig(*frame_args)
                finally:
                    clk.t += time.perf_counter() - t0

            eng._run_frame_resilient = timed
            eng._clock = clk
            eng.telemetry.clock = clk

        def run(src):
            outs, produced = {}, 0
            t0 = time.perf_counter()
            for uid, toks in src:
                outs[uid] = toks
                produced += len(toks)
            return outs, produced, time.perf_counter() - t0

        # --- single engine: compile + parity base ---
        single = mk()
        run(single.serve(arrivals(), max_new_tokens=short_new))  # compile pass
        base_outs, base_produced, base_dt = run(
            single.serve(arrivals(), max_new_tokens=short_new))

        def mk_timed(**over):
            eng = mk(**over)
            attach_busy_clock(eng)
            return eng

        def leg(engines, router_cfg=None):
            router = EngineRouter(engines, router_cfg or RouterConfig())
            outs, produced, dt = run(
                router.serve(arrivals(), max_new_tokens=short_new))
            for u, toks in outs.items():
                np.testing.assert_array_equal(
                    base_outs[u], toks, err_msg=f"uid={u} diverged")
            assert set(outs) == set(base_outs), \
                "every accepted request must complete"
            engs = [r.engine for r in router._replicas.values()]
            row = {
                "tok_per_sec": round(produced / dt, 1),
                "ttft_p90_ms": merged_p90_ms(engs, "ttft"),
                "itl_p90_ms": merged_p90_ms(engs, "itl"),
                "counters": {k: router.counters[k]
                             for k in ("placements", "handoffs",
                                       "requests_failed")},
            }
            if router._tier is not None:
                row["tier"] = dict(router._tier.stats)
            for e in engs:
                e.telemetry.set_base_labels(engine=None, model=None, role=None)
            return row

        # --- mono fleet: two unified replicas (compile both) ---
        mono_engines = {"u0": mk_timed(), "u1": mk_timed()}
        leg(dict(mono_engines))                                  # compile pass

        # --- disagg fleet: prefill + decode over one shared tier ---
        pe = mk_timed(role="prefill")
        de = mk_timed(role="decode")
        disagg_engines = {"prefill": pe, "decode": de}
        cfg = RouterConfig(prefill_route_min_prompt=min(64, long_prompt))

        def fresh_tier():
            # a FRESH tier per pass: an earlier pass's prefix records would
            # otherwise let the next pass admit its prompts at the
            # watermark (warm-tier advantage the mono leg doesn't get)
            t = KVSwapTier(tempfile.mkdtemp(prefix="dstpu_disagg_tier_"),
                           shared=True)
            pe.attach_kv_tier(t, tag="p")
            de.attach_kv_tier(t, tag="d")
            return t

        fresh_tier()
        leg(dict(disagg_engines), cfg)                           # compile pass

        # measured rounds, INTERLEAVED (mono, disagg, mono, disagg, ...)
        # with per-leg MEDIANS: single wall-clock rounds on a shared box
        # swing several-fold (the telemetry-overhead bench's lesson), and
        # the percentile contract below must reflect the workload, not
        # which leg drew the noisy round. Parity is asserted EVERY round.
        mono_rounds, disagg_rounds = [], []
        for _ in range(5):
            mono_rounds.append(leg(mono_engines))
            fresh_tier()
            disagg_rounds.append(leg(disagg_engines, cfg))

        def median_leg(rounds):
            out = dict(rounds[-1])     # counters/tier from the last round
            for k in ("tok_per_sec", "ttft_p90_ms", "itl_p90_ms"):
                out[k] = round(float(np.median([r[k] for r in rounds])), 3)
            return out

        mono = median_leg(mono_rounds)
        disagg = median_leg(disagg_rounds)
        for r in disagg_rounds:
            assert r["counters"]["handoffs"] >= n_long, \
                "every long prompt must hand off (else the leg measured " \
                "nothing)"
        for eng in (single, *mono_engines.values(), pe, de):
            assert eng.kv.free_blocks == eng.kv.num_blocks - 1, \
                "KV pool must drain on every replica"
        # the contract is a PAIRED per-round sign test: round i's mono and
        # disagg passes run back-to-back, so comparing within the pair
        # cancels the slow box drift that still leaks into aggregate
        # medians (sequential rounds on a shared box degrade severalfold
        # over a run). "Improves" = disagg wins the strict majority of
        # paired rounds on BOTH percentiles.
        pair_wins = {
            m: sum(1 for r_m, r_d in zip(mono_rounds, disagg_rounds)
                   if r_d[m] < r_m[m])
            for m in ("ttft_p90_ms", "itl_p90_ms")}
        if assert_contract:
            need = len(mono_rounds) // 2 + 1
            assert pair_wins["ttft_p90_ms"] >= need, \
                (f"disagg TTFT p90 must beat the monolithic fleet in a "
                 f"majority of paired rounds: won "
                 f"{pair_wins['ttft_p90_ms']}/{len(mono_rounds)} "
                 f"(medians {disagg['ttft_p90_ms']} vs "
                 f"{mono['ttft_p90_ms']} ms)")
            assert pair_wins["itl_p90_ms"] >= need, \
                (f"disagg decode ITL p90 must beat the monolithic fleet in "
                 f"a majority of paired rounds: won "
                 f"{pair_wins['itl_p90_ms']}/{len(mono_rounds)} "
                 f"(medians {disagg['itl_p90_ms']} vs "
                 f"{mono['itl_p90_ms']} ms)")

        return {
            "workload": "disagg-serving", "batch": batch,
            "long_prompt": long_prompt, "short_prompt": short_prompt,
            "long_new_tokens": long_new, "short_new_tokens": short_new,
            "n_long": n_long, "n_short": n_short, "chunk": chunk,
            "replicas": 2,
            "single_tok_per_sec": round(base_produced / base_dt, 1),
            "mono_fleet": mono,
            "disagg_fleet": disagg,
            "paired_round_wins": {k: f"{v}/{len(mono_rounds)}"
                                  for k, v in pair_wins.items()},
            "rounds": {
                "mono": [{k: r[k] for k in ("ttft_p90_ms", "itl_p90_ms",
                                            "tok_per_sec")}
                         for r in mono_rounds],
                "disagg": [{k: r[k] for k in ("ttft_p90_ms", "itl_p90_ms",
                                              "tok_per_sec")}
                           for r in disagg_rounds],
            },
            "ttft_p90_speedup": round(mono["ttft_p90_ms"]
                                      / disagg["ttft_p90_ms"], 3),
            "itl_p90_speedup": round(mono["itl_p90_ms"]
                                     / disagg["itl_p90_ms"], 3),
            "note": "same deterministic bursty long-prompt/short-decode "
                    "schedule on all three legs; TTFT/ITL are EXACT p90s "
                    "over raw samples on per-replica BUSY-TIME clocks "
                    "(thread-per-replica latency semantics — the serial "
                    "cooperative driver would charge every replica's frame "
                    "to every wall-clock gap), fleet-merged (handoff "
                    "continuations record no TTFT), median of 5 "
                    "interleaved rounds per fleet leg. The disagg leg "
                    "routes prefill-heavy arrivals to the prefill replica "
                    "(queued-prompt-token scoring), hands off committed "
                    "pages through the shared tier at the watermark, and "
                    "keeps long-prefill wide frames off the decode "
                    "replica's stream — outputs asserted token-identical "
                    "to the single engine on every leg; smoke margins are "
                    "modest because stock-tiny frames are overhead-bound "
                    "(see docstring)",
        }
    finally:
        LogBucketHistogram.__init__.__defaults__ = growth_defaults
        LogBucketHistogram.record = _orig_record


def bench_prefix_cache(model_name, batch, prompt_len, new_tokens,
                       n_arrivals=12, tail_len=8,
                       assert_contract=True):
    """KV memory hierarchy: prefix-cache hit-rate sweep on a deterministic
    shared-prefix arrival schedule (one arrival per frame-boundary poll —
    no wall clock in the schedule, so every leg sees identical admission
    timing).

    For each share fraction f, ``f * n_arrivals`` requests carry one long
    shared prefix plus a short unique tail (the multi-turn / system-prompt
    shape) and the rest are fully unique. Each point runs a cache-OFF
    baseline and a fresh cache-ON engine on the same schedule, asserting
    greedy outputs token-identical, and reports measured hit rate, TTFT
    p50/p90, and goodput. The ISSUE-8 acceptance contract — >= 2x TTFT p90
    at >= 50% hit rate — is asserted inline at the full-share point (like
    the telemetry-overhead budget, a swallowed assert is not an assert)."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model
    rng = np.random.default_rng(21)
    shared = rng.integers(0, 1000, (prompt_len,)).astype(np.int32)
    # two passes per leg (warm + measured): tails and unique prompts are
    # PER-PASS, so the measured pass can only hit via the shared prefix —
    # the thing the sweep is measuring — never via a replayed full prompt
    tails = [[rng.integers(0, 1000, (tail_len,)).astype(np.int32)
              for _ in range(n_arrivals)] for _ in range(2)]
    uniques = [[rng.integers(0, 1000,
                             (prompt_len + tail_len,)).astype(np.int32)
                for _ in range(n_arrivals)] for _ in range(2)]

    def arrivals(share_frac, pass_no):
        n_shared = int(round(share_frac * n_arrivals))
        for u in range(n_arrivals):
            p = np.concatenate([shared, tails[pass_no][u]]) \
                if u < n_shared else uniques[pass_no][u]
            yield [(pass_no * 100 + u, p)]

    def mk(prefix):
        model = build_model(model_name)
        # hit granularity is a full KV block rounded to the prefill chunk:
        # size both so the shared prefix spans several chunks (the v5e-
        # tuned 128 block would leave a 128-token prefix as ONE chunk and
        # measure nothing but the boundary)
        # frame_steps=1: every scan step is an admission boundary, the
        # regime a TTFT-sensitive deployment runs in (the adaptive sizer
        # picks small frames under bursty interactive traffic). An 8-step
        # frame would complete the whole 5-chunk prefill INSIDE one frame
        # and quantize TTFT to the frame boundary on both legs.
        # slots sized to the in-flight population so TTFT measures SERVICE
        # time (the prefill the cache removes), not slot-queueing — a
        # saturated table hides any admission-side win behind queue wait
        slots = max(batch, 8)
        cfg = RaggedInferenceEngineConfig(
            max_ragged_batch_size=slots,
            kv_block_size=32, prefill_chunk_size=32, frame_steps=1,
            expected_context=prompt_len + tail_len + new_tokens,
            expected_concurrency=slots,
            prefix_cache=prefix)
        return InferenceEngineV2(
            model, cfg,
            max_seq_len=prompt_len + tail_len + new_tokens + 2)

    def run(eng, share_frac, pass_no):
        outs, produced = {}, 0
        t0 = time.perf_counter()
        for uid, toks in eng.serve(arrivals(share_frac, pass_no),
                                   max_new_tokens=new_tokens):
            outs[uid] = toks
            produced += len(toks)
        dt = time.perf_counter() - t0
        lat = eng.telemetry.latency_ms()
        c = eng.telemetry.counters
        return outs, {
            "tok_per_sec": round(produced / dt, 1),
            "ttft_p50_ms": lat["ttft"]["p50"],
            "ttft_p90_ms": lat["ttft"]["p90"],
            "prefill_tokens": c["prefill_tokens"],
            "hit_rate": round(c["prefix_hits"] / c["prefix_lookups"], 4)
            if c["prefix_lookups"] else None,
            "hit_tokens": c["prefix_hit_tokens"],
        }

    def leg(prefix, frac):
        # frame programs are per-engine jits: one full warm pass compiles
        # BOTH frame widths (and, cache-on, the shared COW copy program)
        # so no measured request's TTFT absorbs a compile — the
        # bench_chaos warm-then-measure discipline. The warm pass also
        # pre-populates the cache-on leg's prefix index, so the measured
        # pass reports the steady-state hit rate.
        eng = mk(prefix)
        run(eng, frac, 0)
        return (eng,) + run(eng, frac, 1)

    sweep = []
    for frac in (0.0, 0.5, 1.0):
        _, base_outs, base = leg(False, frac)
        # the cached leg runs cache-ON at every point — share 0.0 is the
        # overhead row (all lookups miss, publishes still happen)
        eng, outs, cached = leg(True, frac)
        for u, toks in base_outs.items():
            np.testing.assert_array_equal(
                toks, outs[u],
                err_msg=f"uid={u} diverged cache-on at share={frac}")
        speed = (round(base["ttft_p90_ms"] / cached["ttft_p90_ms"], 3)
                 if cached["ttft_p90_ms"] else None)
        sweep.append({
            "share_frac": frac,
            "hit_rate": cached["hit_rate"],
            "hit_tokens": cached["hit_tokens"],
            "cold": {k: base[k] for k in
                     ("tok_per_sec", "ttft_p50_ms", "ttft_p90_ms",
                      "prefill_tokens")},
            "cached": {k: cached[k] for k in
                       ("tok_per_sec", "ttft_p50_ms", "ttft_p90_ms",
                        "prefill_tokens")},
            "ttft_p90_speedup": speed,
            "goodput_ratio": round(cached["tok_per_sec"]
                                   / base["tok_per_sec"], 4),
        })
    full = sweep[-1]
    if assert_contract:
        assert full["hit_rate"] >= 0.5, \
            f"hit rate {full['hit_rate']} < 0.5 on the full-share schedule"
        assert full["ttft_p90_speedup"] >= 2.0, \
            f"TTFT p90 speedup {full['ttft_p90_speedup']} < 2x at " \
            f"hit rate {full['hit_rate']}"
    return {
        "workload": "prefix-cache", "batch": batch,
        "shared_prefix_len": prompt_len, "tail_len": tail_len,
        "new_tokens": new_tokens, "arrivals": n_arrivals,
        "sweep": sweep,
        "note": "deterministic shared-prefix schedule (one arrival per "
                "boundary); every point asserts greedy outputs "
                "token-identical cache-on vs cache-off; full-share point "
                "asserts >= 2x TTFT p90 at >= 50% hit rate (ISSUE-8 "
                "acceptance). TTFT percentiles come from x2-growth "
                "log-bucket histograms, so ratios are quantized to powers "
                "of two — a 2.0 at hit_rate 0 is one bucket of scheduling "
                "noise, not a cache effect (prefill_tokens is the "
                "noise-free column)",
    }


def bench_tp(model_name, batch, prompt_len, new_tokens, tp, n_arrivals=8):
    """Tensor-parallel frame serving: tokens/s/chip scaling vs the
    single-chip baseline on one deterministic arrival schedule.

    Three engines run the IDENTICAL schedule:

    * **pre-PR baseline** — a default-config engine (the exact pre-TP code
      path: ``tp=1`` never touches shard_map);
    * **tp=1** — an engine constructed with ``tp=1`` explicitly; its
      outputs are asserted BYTE-IDENTICAL to the baseline (the tp knob at
      degree 1 must be a no-op, not a slightly different program);
    * **tp=N** — the shard_map engine; greedy outputs asserted
      token-identical, throughput reported absolute and per chip.

    A fourth leg re-runs tp=N with the int8-quantized collectives for the
    traffic-vs-exactness tradeoff row (completion asserted, tokens not —
    that's the tolerance contract, see tests/test_serving_tp.py).

    On this single-chip container the mesh is the virtual-8-CPU-device one
    (``--tp`` forces it before jax initializes), so per-chip numbers model
    PARALLELIZATION OVERHEAD only — 8 simulated devices share one host's
    cores and real ICI wins don't exist here. The honest headline is
    tokens/s/chip RATIO vs tp=1, not absolute throughput."""
    import jax
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model

    # the stock "tiny" has 4 heads; the TP row needs every sharded axis
    # divisible by the mesh degree
    model = (build_model(model_name, num_heads=8) if model_name == "tiny"
             else build_model(model_name))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, model.cfg.vocab_size - 5,
                            (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]

    def arrivals():
        for i in range(0, n_arrivals, 2):
            yield [(i + j, prompts[i + j])
                   for j in range(2) if i + j < n_arrivals]

    def mk(**over):
        kw = dict(max_ragged_batch_size=batch, kv_block_size=16,
                  prefill_chunk_size=16, max_tokens_per_step=256,
                  dtype="float32", frame_steps=8,
                  expected_context=prompt_len + new_tokens,
                  expected_concurrency=batch)
        kw.update(over)
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                                 params=params,
                                 max_seq_len=prompt_len + new_tokens + 2)

    def run(eng):
        outs, produced = {}, 0
        t0 = time.perf_counter()
        for uid, toks in eng.serve(arrivals(), max_new_tokens=new_tokens):
            outs[uid] = toks
            produced += len(toks)
        return outs, produced, time.perf_counter() - t0

    legs = {}
    base_outs = None
    eng_pre = mk()                       # default config == pre-PR engine
    run(eng_pre)                         # compile
    base_outs, base_produced, base_dt = run(eng_pre)

    eng1 = mk(tp=1)
    run(eng1)
    tp1_outs, _p, tp1_dt = run(eng1)
    for u, toks in base_outs.items():
        # byte-identical, not merely token-identical: same dtype, same values
        assert toks.dtype == tp1_outs[u].dtype
        np.testing.assert_array_equal(
            toks, tp1_outs[u],
            err_msg=f"uid={u}: tp=1 engine diverged from the pre-PR path")
    legs["tp1_tok_per_sec"] = round(base_produced / tp1_dt, 1)

    engN = mk(tp=tp)
    run(engN)
    tpN_outs, tpN_produced, tpN_dt = run(engN)
    for u, toks in base_outs.items():
        np.testing.assert_array_equal(
            toks, tpN_outs[u],
            err_msg=f"uid={u}: tp={tp} diverged from single-chip greedy")
    legs[f"tp{tp}_tok_per_sec"] = round(tpN_produced / tpN_dt, 1)
    legs[f"tp{tp}_tok_per_sec_per_chip"] = round(tpN_produced / tpN_dt / tp, 2)

    engQ = mk(tp=tp, tp_quantized_collectives=True)
    run(engQ)
    q_outs, q_produced, q_dt = run(engQ)
    assert len(q_outs) == n_arrivals and q_produced == tpN_produced, \
        "quantized-collective serve must still complete every budget"
    legs[f"tp{tp}_quantized_tok_per_sec"] = round(q_produced / q_dt, 1)

    per_chip_ratio = (tpN_produced / tpN_dt / tp) / (base_produced / base_dt)
    return {
        "workload": "tp-serving", "tp": tp, "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals,
        "baseline_tok_per_sec": round(base_produced / base_dt, 1),
        **legs,
        "scaling_tok_per_sec_per_chip_vs_tp1": round(per_chip_ratio, 4),
        "platform_devices": jax.device_count(),
        "note": "virtual CPU mesh on this container: per-chip ratio "
                "measures sharding overhead, not real multi-chip speedup "
                "(8 simulated devices share one host); tp=1 asserted "
                "byte-identical to the pre-PR engine, tp=N asserted "
                "token-identical, quantized leg asserted complete",
    }


def bench_quant(model_name, batch, prompt_len, new_tokens, n_arrivals=8):
    """Quantized serving at a FIXED KV HBM byte budget: f32 pages vs int8
    pages vs int8 pages + int8 weights.

    All three legs get the SAME byte budget for their KV pools; each
    converts it to however many blocks its resident page representation
    affords (int8 pages pack the row as D int8 + 4 scale-lane bytes, so
    they fit ~2.7x the blocks at f32 D=64). The capacity claim is then
    measured, not computed: every leg serves the identical arrival burst
    and reports how many slots were concurrently live before the first
    KV-pressure admission deferral — the int8 legs should carry the whole
    burst where the f32 leg defers.

    Tolerance contracts ride inline, exactly as the tests pin them
    (tests/test_quantized_serving.py): int8-KV greedy outputs are asserted
    TOKEN-IDENTICAL to the f32 leg (write-once pages), while the
    weight-quantized leg is asserted to complete every budget (argmax may
    legitimately flip near-ties)."""
    import jax
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model

    model = build_model(model_name)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, model.cfg.vocab_size - 5,
                            (prompt_len,)).astype(np.int32)
               for _ in range(n_arrivals)]

    def mk(num_kv_blocks=None, **over):
        kw = dict(max_ragged_batch_size=batch, kv_block_size=16,
                  prefill_chunk_size=16, max_tokens_per_step=256,
                  dtype="float32", frame_steps=4, frame_retry_backoff_s=0.0,
                  num_kv_blocks=num_kv_blocks)
        kw.update(over)
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                                 params=params,
                                 max_seq_len=prompt_len + new_tokens + 2)

    # probe each representation's resident block footprint, then hand every
    # leg the same byte budget: enough f32 blocks for ~3 of the 8 arrivals
    # (so the f32 leg measurably defers), which the int8 page format turns
    # into headroom for the full burst
    f32_block_bytes = mk().kv.block_bytes
    int8_block_bytes = mk(kv_dtype="int8").kv.block_bytes
    blocks_per_seq = -(-(prompt_len + new_tokens + 1) // 16)
    hbm_budget = (3 * blocks_per_seq + 2) * f32_block_bytes

    def run(eng):
        """Serve the burst, sampling the live-slot gauge at every emission
        (frame-grained). With the slot table sized past the burst, the
        high-water mark IS the slots-until-first-deferral figure: a
        KV-bound engine admits up to pool capacity and defers the rest at
        that same boundary, so the peak reads the stall point."""
        outs, produced, peak = {}, 0, 0
        t0 = time.perf_counter()
        for uid, toks in eng.serve(iter([[(u, p) for u, p in
                                          enumerate(prompts)]]),
                                   max_new_tokens=new_tokens):
            peak = max(peak, int(eng.telemetry.gauges["live_slots"]))
            outs[uid] = toks
            produced += len(toks)
        dt = time.perf_counter() - t0
        if not eng.telemetry.counters["admission_deferrals"]:
            peak = n_arrivals            # the whole burst fit at once
        return outs, produced, dt, peak

    def leg(name, **over):
        eng = mk(num_kv_blocks=max(2, hbm_budget
                                   // eng_block_bytes[name]), **over)
        run(eng)                         # compile
        outs, produced, dt, slots = run(eng)
        return eng, outs, {
            f"{name}_tok_per_sec": round(produced / dt, 1),
            f"{name}_kv_blocks": eng.kv.num_blocks,
            f"{name}_kv_block_bytes": eng.kv.block_bytes,
            f"{name}_slots_until_first_deferral": slots,
            f"{name}_admission_deferrals":
                eng.telemetry.counters["admission_deferrals"],
        }

    eng_block_bytes = {"f32": f32_block_bytes,
                       "int8_kv": int8_block_bytes,
                       "int8_kv_w8": int8_block_bytes}
    _, base_outs, row_f32 = leg("f32")
    _, kv_outs, row_kv = leg("int8_kv", kv_dtype="int8")
    for u, toks in base_outs.items():
        np.testing.assert_array_equal(
            toks, kv_outs[u],
            err_msg=f"uid={u}: int8-KV diverged from f32 greedy")
    _, w_outs, row_w = leg("int8_kv_w8", kv_dtype="int8",
                           weight_dtype="int8")
    assert len(w_outs) == n_arrivals and \
        all(len(t) == new_tokens for t in w_outs.values()), \
        "weight-quantized serve must still complete every budget"

    return {
        "workload": "quant-serving", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "arrivals": n_arrivals,
        "kv_hbm_budget_bytes": hbm_budget,
        **row_f32, **row_kv, **row_w,
        "kv_block_bytes_ratio_f32_over_int8": round(
            f32_block_bytes / int8_block_bytes, 2),
        "slots_ratio_int8_over_f32": round(
            row_kv["int8_kv_slots_until_first_deferral"]
            / max(1, row_f32["f32_slots_until_first_deferral"]), 2),
        "note": "identical arrival burst per leg at one KV byte budget; "
                "int8-KV outputs asserted token-identical to f32, "
                "weight-quantized leg asserted complete; tiny-model CPU "
                "tok/s measures dequant overhead at toy shapes, not the "
                "HBM-bandwidth win the page format buys on real chips",
    }


def bench_decode_collapse_probe(model_name, prompt_len, new_tokens):
    """Round-3 left the batch-64 decode collapse (3.2x the batch-32 step
    time) unexplained. Probe the two candidate causes directly: KV-pool
    size (bigger pool -> more HBM touched per page scatter?) and batch
    scaling of the paged kernel grid."""
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model

    def decode_rate(batch, num_blocks):
        cfg = RaggedInferenceEngineConfig(
            max_ragged_batch_size=max(batch, 16),
            max_tokens_per_step=max(batch * 2, 768),
            num_kv_blocks=num_blocks)
        eng = InferenceEngineV2(build_model(model_name), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, eng.model.cfg.vocab_size,
                                (prompt_len,)).astype(np.int32)
                   for _ in range(batch)]
        eng.generate(prompts, max_new_tokens=4)
        eng.generate(prompts, max_new_tokens=new_tokens)
        t0 = time.perf_counter()
        eng.generate(prompts, max_new_tokens=4)
        t1 = time.perf_counter()
        eng.generate(prompts, max_new_tokens=new_tokens)
        t2 = time.perf_counter()
        return batch * (new_tokens - 4) / ((t2 - t1) - (t1 - t0))

    bs = 128
    blocks_for = lambda b: b * ((prompt_len + new_tokens) // bs + 2) + 1
    r64_small = decode_rate(64, blocks_for(64))       # tight pool
    r64_big = decode_rate(64, blocks_for(64) * 2)
    r32 = decode_rate(32, blocks_for(64))             # same pool, half batch
    pool_sensitive = r64_big < 0.8 * r64_small
    return {
        "workload": "decode-collapse-probe", "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "b64_tight_pool_tok_per_sec": round(r64_small, 1),
        "b64_2x_pool_tok_per_sec": round(r64_big, 1),
        "b32_same_pool_tok_per_sec": round(r32, 1),
        "verdict": ("pool-size-bound (page scatter touches the whole pool)"
                    if pool_sensitive else
                    "batch-scaling-bound (per-step cost superlinear in B "
                    "with pool size ruled out)"),
    }


def bench_woq_delta():
    """Fused WOQ matmul vs bf16 dense at serving shapes: the measured
    ratio, beside the platform-floor row's streamed-HBM bandwidth (the
    kernel's win is the 4x-8x smaller weight read)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.woq_matmul import quantize_woq, woq_matmul

    rng = np.random.default_rng(0)
    rows = []
    for m, k, n, bits in ((1, 4096, 4096, 4), (16, 4096, 4096, 4),
                          (16, 4096, 4096, 8)):
        w = jnp.asarray(rng.normal(size=(k, n)) * 0.02, jnp.bfloat16)
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        fused = quantize_woq(w, bits, 128)
        # metadata ints stay static via closure; the packed arrays ride as
        # jit args (closing over them would bake multi-MB constants into
        # the program)
        meta = {f: fused[f] for f in ("bits", "group_size", "shape")}

        @jax.jit
        def dense(x, w):
            (y,), _ = jax.lax.scan(lambda c, _: ((jnp.tanh(c[0] @ w),), ()),
                                   (x,), None, length=32)
            return y

        @jax.jit
        def quant(x, q, scales):
            qs = {**meta, "q": q, "scales": scales}
            (y,), _ = jax.lax.scan(
                lambda c, _: ((jnp.tanh(woq_matmul(c[0], qs)),), ()),
                (x,), None, length=32)
            return y

        q_arr, s_arr = fused["q"], fused["scales"]
        jax.device_get(dense(x, w)); jax.device_get(quant(x, q_arr, s_arr))
        td = tq = 1e9
        for _ in range(3):
            t0 = time.perf_counter(); jax.device_get(dense(x, w))
            td = min(td, time.perf_counter() - t0)
            t0 = time.perf_counter(); jax.device_get(quant(x, q_arr, s_arr))
            tq = min(tq, time.perf_counter() - t0)
        rows.append({"m": m, "k": k, "n": n, "bits": bits,
                     "dense_ms_per_op": round(td / 32 * 1e3, 3),
                     "woq_ms_per_op": round(tq / 32 * 1e3, 3),
                     "woq_speedup": round(td / tq, 3)})
    return {"workload": "woq-kernel-delta", "rows": rows}


def bench_kernel_delta(model_name, batch, prompt_len, new_tokens, repeats=2):
    """Paged-Pallas vs XLA-gather decode delta (same workload, kernel off).

    Measured ``repeats`` times per mode, every run recorded beside the
    best one."""
    rows = {}
    for mode, env in (("paged_pallas", "0"), ("xla_gather", "1")):
        os.environ["DS_TPU_DISABLE_PALLAS"] = env
        try:
            vals = [bench_decode(model_name, batch, prompt_len,
                                 new_tokens)["decode_tok_per_sec"]
                    for _ in range(repeats)]
            rows[mode] = max(vals)
            rows[mode + "_runs"] = vals
        finally:
            os.environ.pop("DS_TPU_DISABLE_PALLAS", None)
    if rows.get("xla_gather"):
        rows["pallas_speedup"] = round(rows["paged_pallas"] / rows["xla_gather"], 3)
    return {"workload": "kernel-delta", "batch": batch, "prompt_len": prompt_len,
            "new_tokens": new_tokens, **rows}


def bench_service(model_name, batch, prompt_len, new_tokens,
                  n_arrivals=12, sessions=200, turns=2,
                  assert_contract=True):
    """The service edge measured as traffic experiences it (ISSUE 14).

    Four legs:

    * **routing-overhead** — the SAME front-loaded burst through the
      serial cooperative router and the thread-per-replica
      ``FleetDriver`` (identical policy state), outputs asserted
      token-identical; the tok/s ratio is what true concurrency buys
      over one host thread stepping replicas in turn (paired rounds,
      median).
    * **closed-loop load** — ``load_gen`` drives ``sessions`` concurrent
      closed-loop SSE sessions with think-time against a real HTTP
      endpoint; every streamed byte is compared against a direct
      single-engine ``serve()`` of the same schedule. ZERO parity
      violations is a hard contract.
    * **edge-admission** — a no-think burst against a deliberately tiny
      edge queue budget: the fleet must shed at the EDGE (429 +
      Retry-After) while every replica's local scheduler sheds NOTHING
      (the ordering contract: back-pressure belongs at the front door),
      and the closed-loop clients must still complete by honoring
      Retry-After.
    * **autoscale** — a load swing (burst -> idle -> long-prompt burst)
      against a 3-replica shared-tier fleet under the
      ``AutoscaleController``: expects >=1 scale_down (idle drain),
      >=1 scale_up (rejoin under backlog), and >=1 prefill role flip,
      with all outputs token-identical.

    All asserts are CPU-smoke contracts (``assert_contract``); on TPU
    they are reported, not asserted."""
    import jax
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import load_gen
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.kv_hierarchy import KVSwapTier
    from deepspeed_tpu.inference.v2.router import EngineRouter, RouterConfig
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    from deepspeed_tpu.inference.v2.service import (AutoscaleConfig,
                                                    AutoscaleController,
                                                    EdgeConfig, FleetDriver,
                                                    ServiceEdge)
    from deepspeed_tpu.models import build_model
    import tempfile

    model = build_model(model_name, num_heads=8)
    params = model.init(jax.random.PRNGKey(0))
    max_seq = 4 * (prompt_len + new_tokens) + 32

    def mk(**over):
        kw = dict(kv_block_size=16, prefill_chunk_size=8,
                  max_tokens_per_step=1024, dtype="float32",
                  max_ragged_batch_size=batch, frame_steps=2,
                  frame_retry_backoff_s=0.0)
        kw.update(over)
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                                 params=params, max_seq_len=max_seq)

    rng = np.random.default_rng(12)
    prompts = {u: rng.integers(0, 200, (prompt_len,)).astype(np.int32)
               for u in range(n_arrivals)}

    def burst():
        yield [(u, prompts[u]) for u in sorted(prompts)]

    # ---- leg 1: routing overhead, serial vs threaded, paired rounds ----
    def run_driver(threaded):
        router = EngineRouter(
            {"a": mk(), "b": mk()},
            RouterConfig(driver="threaded" if threaded else "serial"))
        t0 = time.perf_counter()
        outs = dict(router.serve(burst(), max_new_tokens=new_tokens))
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in outs.values())
        return outs, toks / dt, dt

    ref_outs, _, _ = run_driver(False)      # warm trace round (discarded)
    rounds = []
    for _ in range(3):
        s_outs, s_rate, s_dt = run_driver(False)
        t_outs, t_rate, t_dt = run_driver(True)
        for u in ref_outs:
            assert np.array_equal(s_outs[u], ref_outs[u]), f"serial uid={u}"
            assert np.array_equal(t_outs[u], ref_outs[u]), \
                f"threaded driver outputs diverge at uid={u}"
        rounds.append({"serial_tok_per_sec": round(s_rate, 1),
                       "threaded_tok_per_sec": round(t_rate, 1),
                       "speedup": round(t_rate / s_rate, 3)})
    speedup = statistics.median(r["speedup"] for r in rounds)
    routing = {"rounds": rounds,
               "threaded_over_serial_tok_per_sec": round(speedup, 3),
               "note": "same front-loaded burst, token-identical asserted "
                       "each round; CPU smoke shares one physical device "
                       "across replicas, so the overlap win is bounded by "
                       "host-side scheduling, not compute parallelism"}

    # ---- leg 2: closed-loop load against the real endpoint ----
    sched = load_gen.build_schedule(sessions, turns, prompt_len,
                                    new_tokens, think_ms=200.0, seed=3)
    router, driver, edge, mk_ref = load_gen.build_fleet(
        2, batch, max_seq_len=max_seq, scheduler=False)
    try:
        # the reference MUST be the fleet's own engine family (mk_ref):
        # on TPU the bench model differs from build_fleet's tiny smoke
        # fleet, and a cross-model "parity" count would be noise
        ref = load_gen.direct_reference(mk_ref, sched)
        report = load_gen.run_load("127.0.0.1", edge.edge_port, sched,
                                   sessions, turns)
        violations = load_gen.check_parity(report, ref)
        report.pop("_results")
        report["parity_violations"] = violations
        report["edge_counters"] = dict(edge.counters)
        if assert_contract:
            assert report["completed"] == report["requests"], \
                f"{report['n_failures']} sessions failed: " \
                f"{report['failures'][:3]}"
            assert violations == 0, \
                f"{violations} token-parity violations between the SSE " \
                "stream and direct serve()"
    finally:
        edge.shutdown()
        driver.stop()

    # ---- leg 3: edge admission sheds BEFORE any local scheduler shed ----
    shed_sessions = 40
    shed_sched = load_gen.build_schedule(shed_sessions, 1, prompt_len,
                                         new_tokens, think_ms=0.0, seed=5)
    mk2_ref = mk                 # leg 3's fleet IS built from mk()
    router2 = EngineRouter({"replica0": mk()})
    driver2 = FleetDriver(router2)
    driver2.start(max_new_tokens=new_tokens,
                  scheduler_factory=lambda: RequestScheduler(SchedulerConfig(
                      tenant_max_queued=16, lookahead_reserve=True)))
    edge2 = ServiceEdge(driver2, EdgeConfig(
        max_queued_tokens=4 * prompt_len,
        retry_after_min_s=0.2, retry_after_max_s=2.0)).start()
    try:
        ref2 = load_gen.direct_reference(mk2_ref, shed_sched)
        rep2 = load_gen.run_load("127.0.0.1", edge2.edge_port, shed_sched,
                                 shed_sessions, 1, max_shed_retries=200)
        v2 = load_gen.check_parity(rep2, ref2)
        rep2.pop("_results")
        local_sheds = sum(
            r.engine.telemetry.counters["requests_shed"]
            for r in router2._replicas.values())
        edge_leg = {
            "sessions": shed_sessions,
            "edge_sheds": edge2.counters["sheds"],
            "local_scheduler_sheds": local_sheds,
            "completed": rep2["completed"],
            "requests": rep2["requests"],
            "parity_violations": v2,
            "sheds_retried": rep2["edge_sheds_seen"],
            "retry_wait_total_s": rep2["retry_wait_s"],
            "note": "tiny edge queue budget (max_queued_tokens="
                    f"{4 * prompt_len}): the 429/Retry-After path must "
                    "engage at the edge while every replica's scheduler "
                    "sheds nothing, and closed-loop retries must still "
                    "complete every request",
        }
        if assert_contract:
            assert edge2.counters["sheds"] > 0, \
                "overload burst never tripped edge admission"
            assert local_sheds == 0, \
                f"{local_sheds} local scheduler sheds — the edge must " \
                "shed first"
            assert rep2["completed"] == rep2["requests"], \
                f"edge-shed leg lost requests: {rep2['failures'][:3]}"
            assert v2 == 0, f"{v2} parity violations in the shed leg"
    finally:
        edge2.shutdown()
        driver2.stop()

    # ---- leg 4: autoscale (drain/rejoin + prefill role flip) ----
    td = tempfile.mkdtemp()
    tier = KVSwapTier(os.path.join(td, "tier"), shared=True)
    engines = {}
    for n in ("replica0", "replica1", "replica2"):
        e = mk(max_tokens_per_step=2048)
        e.attach_kv_tier(tier, tag=n)
        engines[n] = e
    router3 = EngineRouter(engines)
    ctl = AutoscaleController(AutoscaleConfig(
        evaluate_every_s=0.15, sustain=2, min_live_replicas=1,
        flip_prefill_high=100, flip_dwell_s=2.0))
    driver3 = FleetDriver(router3, autoscaler=ctl)
    driver3.start(max_new_tokens=new_tokens)
    results = {}
    lock = __import__("threading").Lock()

    def sub_for(uid):
        def sub(ev):
            if ev["type"] == "done":
                with lock:
                    results[uid] = ev["tokens"]
        return sub

    try:
        shorts = {u: [int(t) for t in prompts[u]] for u in range(4)}
        for u, p in shorts.items():
            driver3.submit({"uid": u, "tokens": p,
                            "max_new_tokens": new_tokens}, sub_for(u))
        t0 = time.monotonic()
        while len(results) < len(shorts) and time.monotonic() - t0 < 120:
            time.sleep(0.05)
        time.sleep(2.0)                      # idle window -> scale_down
        # oversubscribe the surviving replica's slot table (and KV pool)
        # so queued-token pressure SUSTAINS — a burst the frame absorbs
        # into free slots in one boundary never registers as pressure
        plen = (max_seq - new_tokens - 2) // 8 * 8
        longs = {100 + i: [int(t) for t in rng.integers(0, 200, (plen,))]
                 for i in range(3 * batch)}
        for u, p in longs.items():           # burst -> scale_up + flip
            driver3.submit({"uid": u, "tokens": p, "max_new_tokens": 4},
                           sub_for(u))
        t0 = time.monotonic()
        while len(results) < len(shorts) + len(longs) and \
                time.monotonic() - t0 < 180:
            time.sleep(0.05)
        time.sleep(2.5)                      # drain window -> flip back
        scale = {k: v for k, v in router3.counters.items()
                 if k.startswith("scale")}
        auto_leg = {
            "completed": len(results),
            "requests": len(shorts) + len(longs),
            "events": [{k: e[k] for k in ("tick", "action", "replica")}
                       for e in ctl.events],
            "counters": scale,
            "final_status": router3.replica_status(),
            "final_roles": dict(router3._roles),
        }
        if assert_contract:
            assert len(results) == len(shorts) + len(longs), \
                "autoscale leg lost requests"
            assert scale["scale_down"] >= 1, "idle fleet never scaled down"
            assert scale["scale_up"] >= 1, \
                "backlogged fleet never rejoined parked capacity"
            assert scale["scale_role_flips"] >= 1, \
                "prefill pressure never flipped a replica"
    finally:
        driver3.stop()

    return {
        "workload": "service-edge",
        "batch": batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "replicas": 2,
        "routing_overhead": routing,
        "loadgen": report,
        "edge_admission": edge_leg,
        "autoscale": auto_leg,
        "note": "load_gen drives real HTTP/SSE sessions against the "
                "threaded fleet driver; parity checks compare every "
                "streamed token against a direct single-engine serve() "
                "of the same schedule. CPU smoke: absolute rates are "
                "dispatch-bound, the contracts (parity, shed ordering, "
                "autoscale round-trip) are the measurement",
    }


def bench_sim_check(timeout_s=300):
    """Run ``bin/dstpu_sim --check`` as a subprocess and surface its JSON
    verdict as a bench row. The check is the simulator's own CI smoke
    (deterministic twin runs, snapshot/resume digest, full completion,
    virtual frames only, answers-in-seconds); a breach is an
    AssertionError here so the default row set's exit-code contract
    catches it like the telemetry/tracing budgets."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the child is pinned to the CPU (the sim never dispatches a frame), so
    # it never asks for the chip this process holds
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bin", "dstpu_sim"), "--check"],
        capture_output=True, text=True, timeout=timeout_s,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        verdict = json.loads(proc.stdout)
    except ValueError:
        verdict = {"ok": False, "failures": [
            {"check": "json_output",
             "detail": (proc.stdout or proc.stderr)[:300]}]}
    row = {"workload": "sim-check", "exit_code": proc.returncode, **verdict}
    assert proc.returncode == 0 and verdict.get("ok"), \
        f"dstpu_sim --check failed: {verdict.get('failures')}"
    return row


def bench_sim_fidelity(model_name, batch=8, tolerance=0.6,
                       rate=4.0, duration_s=8.0, assert_contract=True):
    """Sim-vs-real fidelity gate (ISSUE 18): replay ONE recorded arrival
    schedule through the live engine (wall clock, real frames) and
    through the fleet simulator (virtual clock, priced frames), and
    assert the sim's predicted TTFT/ITL p50/p90 land within a stated
    RELATIVE tolerance of the measured run.

    Method:

    * the schedule is a seeded Poisson trace (``sim.traffic.synth_trace``
      — the exact input ``bin/dstpu_sim`` replays); prompts are the
      trace's deterministic token fillers, vocab-clamped for the live
      model (the sim never runs the model, so only LENGTHS must match);
    * the cost model is calibrated from a DIFFERENT-seed schedule's live
      PER-FRAME wall timings, each stamped with the frame's real
      (width, steps, live) plan — prefill frames run
      width=prefill_chunk_size and price from the ledger's wide bucket,
      so the fit sees two distinct work clusters (fitting and scoring
      on the same run would grade the fit, not the sim);
    * live legs repeat until a replay pays no XLA compile stall: frame
      composition shifts with wall timing, so novel (width, steps)
      shapes can keep compiling for a few passes — the virtual fleet
      never compiles, so the measured legs must not either;
    * both sides run the same single-replica deployment (same engine
      config, same ``RequestScheduler``) and both measure
      schedule-relative latency: TTFT = first emission boundary minus
      the arrival's SCHEDULED time, ITL = (retire - first)/(n-1).

    The tolerance is deliberately coarse (default 60% relative): the sim
    prices frames with a two-parameter affine model over static ledger
    counts, so it predicts capacity-planning magnitudes, not
    microseconds. The gate pins that the prediction stays the right
    SIZE — a regression that doubles live TTFT or halves sim cost
    breaches it."""
    import jax
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig, ServeBoundary)
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    from deepspeed_tpu.inference.v2.sim import (FleetSimulator, SimConfig,
                                                synth_trace)
    from deepspeed_tpu.inference.v2.sim.cost import (
        FrameCostModel, calibrate_from_boundaries)
    from deepspeed_tpu.inference.v2.sim.traffic import (prompt_for,
                                                        session_prefix_for)
    from deepspeed_tpu.models import build_model

    # generations long enough that ITL spans many frames: a short
    # generation retires in the boundary that emitted its first token,
    # so (retire - first)/(n - 1) quantizes to zero and the comparison
    # grades boundary-stamp granularity, not the cost model
    frame_steps, chunk, max_new = 4, 8, 48
    shape = dict(rate=rate, duration_s=duration_s, prompt_mean=12,
                 prompt_max=24, new_tokens_mean=24, new_tokens_max=max_new,
                 sessions=2)
    trace = synth_trace("poisson", seed=9, **shape)       # measured
    cal_trace = synth_trace("poisson", seed=11, **shape)  # calibration

    model = build_model(model_name, num_heads=8)
    params = model.init(jax.random.PRNGKey(0))
    vocab = model.cfg.vocab_size
    max_seq = 2 * (24 + max_new) + 32
    # ONE engine config for both legs: the sim derives its KV block
    # pool and admission limits from the same fields, so any drift here
    # would grade config skew, not fidelity
    eng_cfg = RaggedInferenceEngineConfig(
        kv_block_size=16, prefill_chunk_size=chunk,
        max_tokens_per_step=1024, dtype="float32",
        max_ragged_batch_size=batch, frame_steps=frame_steps,
        frame_retry_backoff_s=0.0)
    eng = InferenceEngineV2(model, eng_cfg, params=params,
                            max_seq_len=max_seq)

    def items_for(tr):
        out = []
        for ev in tr:
            prefix = (session_prefix_for(ev["session"], vocab=vocab)
                      if ev.get("session") else None)
            item = {"uid": int(ev["uid"]),
                    "tokens": np.asarray(
                        prompt_for(int(ev["uid"]), int(ev["prompt_tokens"]),
                                   vocab=vocab, session_prefix=prefix),
                        np.int32)}
            if ev.get("max_new_tokens") is not None:
                item["max_new_tokens"] = int(ev["max_new_tokens"])
            for k in ("tenant", "priority", "slo_ms", "session"):
                if ev.get(k) is not None:
                    item[k] = ev[k]
            out.append((float(ev["t"]), item))
        return out

    frames = []               # per-boundary (dt, width, steps, live)
    prev_mark = [None, 0.0]   # (boundary index, wall stamp) last frame
    orig_rfr = eng._run_frame_resilient

    def timed_rfr(slots, width, steps, cur_steps, greedy, draft, faults,
                  frame):
        out = orig_rfr(slots, width, steps, cur_steps, greedy, draft,
                       faults, frame)
        t1 = time.monotonic()
        if prev_mark[0] == frame - 1:
            # consecutive dispatched boundaries: the delta prices one
            # FULL boundary — dispatch plus the host work around it
            # (admission, absorb, retirement) that the sim's virtual
            # advance must also represent — stamped with this frame's
            # real plan so prefill and decode boundaries land in their
            # own ledger programs
            frames.append({"dt": t1 - prev_mark[1],
                           "width": int(width), "steps": int(cur_steps),
                           "live": slots.live_count(), "n_slots": batch})
        prev_mark[0], prev_mark[1] = frame, t1
        return out

    eng._run_frame_resilient = timed_rfr

    def live_replay(tr):
        """Wall-clock replay; returns (ttfts, itls, boundaries,
        completed) with schedule-relative latencies in seconds."""
        sched_items = items_for(tr)
        prev_mark[0] = None          # boundary counter restarts
        t0 = time.monotonic()

        def arrivals():
            nxt = 0
            while nxt < len(sched_items):
                now = time.monotonic() - t0
                due = []
                while nxt < len(sched_items) and sched_items[nxt][0] <= now:
                    due.append(sched_items[nxt][1])
                    nxt += 1
                yield due

        sched_t = {it["uid"]: t0 + t for t, it in sched_items}
        first_t, last_t, emitted, retired = {}, {}, {}, 0
        for ev in eng.serve(arrivals(), max_new_tokens=max_new,
                            scheduler=RequestScheduler(SchedulerConfig()),
                            yield_boundaries=True):
            if isinstance(ev, ServeBoundary):
                # ITL spans first..LAST observed emission: the retire
                # tuple can arrive boundaries before the device's
                # trailing emit flags drain, so stamping retirement
                # would understate the span
                for uid, toks in (ev.emissions or {}).items():
                    if toks:
                        if uid not in first_t:
                            first_t[uid] = ev.t
                        last_t[uid] = ev.t
                        emitted[uid] = emitted.get(uid, 0) + len(toks)
            elif isinstance(ev, tuple):
                retired += 1
        ttfts = sorted(first_t[u] - sched_t[u] for u in first_t)
        itls = sorted((last_t[u] - first_t[u]) / (emitted[u] - 1)
                      for u in first_t if emitted.get(u, 0) > 1)
        return ttfts, itls, None, retired

    def quiet_replay(tr, attempts=5, stall_s=0.30):
        """Replay until no frame pays an XLA compile stall: the frame
        mix shifts with wall timing, so novel (width, steps) shapes can
        keep compiling for a few passes."""
        out = None
        for _ in range(attempts):
            frames.clear()
            out = live_replay(tr)
            if max((f["dt"] for f in frames), default=0.0) < stall_s:
                break
        return out

    quiet_replay(cal_trace)                           # calibration run
    # ``frames`` holds the quiet calibration replay's real per-frame
    # timings. warmup_factor is wide open: quiet_replay already removed
    # compile stalls, and a wide prefill frame legitimately costs ~7x a
    # decode frame — the default 5x-median cutoff would drop exactly
    # the samples the TTFT prediction needs.
    cal = calibrate_from_boundaries(FrameCostModel(), list(frames),
                                    warmup_factor=50.0)

    def pcts(xs):
        return {p: round(float(np.percentile(xs, p)) * 1e3, 3)
                if xs else None for p in (50, 90)}

    # measured leg: median percentile over three quiet replays — a
    # single replay's tail is at the mercy of one host hiccup, and the
    # gate must grade the cost model, not the benchmark machine
    reps = [quiet_replay(trace) for _ in range(3)]
    live_completed = min(r[3] for r in reps)
    live = {m: {p: round(float(np.median(
                [pcts(r[idx])[p] for r in reps
                 if pcts(r[idx])[p] is not None] or [np.nan])), 3)
                for p in (50, 90)}
            for idx, m in ((0, "ttft"), (1, "itl"))}
    for m in live:
        for p in (50, 90):
            if np.isnan(live[m][p]):
                live[m][p] = None

    sim_cfg = SimConfig(
        replicas=1, engine=eng_cfg, max_seq_len=max_seq,
        scheduler=SchedulerConfig(), max_new_tokens=max_new,
        calibration=cal)
    res = FleetSimulator(sim_cfg).run(trace)
    comparisons = []
    for metric in ("ttft", "itl"):
        for p in (50, 90):
            lv = live[metric][p]
            sv = res.latency[metric][f"p{p}"]
            if lv is None or sv is None or lv <= 0:
                continue
            err = abs(sv - lv) / lv
            comparisons.append({
                "metric": f"{metric}_p{p}", "live_ms": lv,
                "sim_ms": round(sv, 3), "rel_err": round(err, 3),
                "within": err <= tolerance})
    row = {
        "workload": "sim-fidelity", "batch": batch,
        "frame_steps": frame_steps, "prefill_chunk": chunk,
        "requests": len(trace), "live_completed": live_completed,
        "sim_completed": res.completed,
        "tolerance_rel": tolerance,
        "calibration": cal.to_json(),
        "comparisons": comparisons,
        "live_ms": live,
        "sim_ms": {"ttft": res.latency["ttft"],
                   "itl": res.latency["itl"]},
        "sim_virtual_frames": res.virtual_frames,
        "note": "one recorded Poisson schedule replayed through the live "
                "engine (wall clock) and the fleet simulator (virtual "
                "clock, cost model calibrated on a different-seed "
                "schedule's boundary deltas); schedule-relative TTFT/ITL "
                "p50/p90 must agree within the stated relative tolerance",
    }
    if assert_contract:
        assert live_completed == len(trace), \
            f"live replay lost requests: {live_completed}/{len(trace)}"
        assert res.completed == len(trace), \
            f"sim lost requests: {res.completed}/{len(trace)}"
        assert comparisons, "no comparable percentiles measured"
        bad = [c for c in comparisons if not c["within"]]
        assert not bad, \
            f"sim-vs-real fidelity breach (tolerance {tolerance}): {bad}"
    return row


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--speculate", action="store_true",
                    help="run the speculative-decoding serving rows "
                         "(mixed-splitfuse-dynamic Poisson schedule: "
                         "acceptance rate, tokens/target-forward, and the "
                         "frame-vs-host-step speedup side by side)")
    ap.add_argument("--gamma", type=int, default=2,
                    help="draft tokens per target verify (default 2)")
    ap.add_argument("--scheduler", action="store_true",
                    help="run only the scheduler-slo row (FIFO vs SLO-aware "
                         "admission under a deterministic 2-tenant overload "
                         "schedule: per-class TTFT p90, shed rate, goodput)")
    ap.add_argument("--tp", type=int, default=0,
                    help="run only the tensor-parallel serving row at this "
                         "degree (tokens/s/chip scaling vs the single-chip "
                         "baseline, with inline byte-identity and token-"
                         "parity asserts). With JAX_PLATFORMS=cpu set "
                         "explicitly, widens the CPU platform to a virtual "
                         "N-device mesh (parity/overhead run); otherwise "
                         "benches the real devices and errors loudly if "
                         "fewer than N exist.")
    ap.add_argument("--quant", action="store_true",
                    help="run only the quantized-serving row (f32 vs int8 "
                         "KV pages vs int8 KV + int8 weights at one fixed "
                         "KV HBM byte budget: tokens/s, blocks afforded, "
                         "and slots-until-first-deferral per leg, with "
                         "inline int8-KV token-identity asserts)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="run only the prefix-cache row (hit-rate sweep on "
                         "a deterministic shared-prefix arrival schedule: "
                         "TTFT p50/p90 and goodput vs the cold baseline, "
                         "with inline token-identity asserts and the >=2x "
                         "TTFT-p90-at->=50%%-hit-rate acceptance contract)")
    ap.add_argument("--disagg", action="store_true",
                    help="run only the disaggregated prefill/decode row "
                         "(1 prefill + 1 decode replica over the shared "
                         "KV tier vs a 2-replica monolithic fleet on a "
                         "long-prompt/short-decode mix: TTFT p90 + decode "
                         "ITL p90 per leg, with inline token-identity and "
                         "both-percentiles-improve asserts)")
    ap.add_argument("--service", action="store_true",
                    help="run only the service-edge row (serial vs "
                         "threaded fleet-driver routing overhead, "
                         "closed-loop HTTP/SSE load with inline "
                         "token-parity asserts, edge-admission-sheds-"
                         "before-local-sheds leg, and the autoscale "
                         "drain/rejoin/role-flip round trip)")
    ap.add_argument("--sessions", type=int, default=200,
                    help="closed-loop sessions for the --service load "
                         "leg (default 200, the acceptance bar)")
    ap.add_argument("--tracing", action="store_true",
                    help="run only the tracing-overhead row (distributed-"
                         "tracing on vs off on an identical deterministic "
                         "schedule, paired rounds, <2%% budget asserted "
                         "like the telemetry row)")
    ap.add_argument("--router", action="store_true",
                    help="run only the router-failover row (single engine "
                         "vs a 2-replica EngineRouter fleet, fault-free "
                         "and under a deterministic engine-kill schedule: "
                         "goodput ratios + failover recovery_ms, with "
                         "inline token-identity asserts)")
    ap.add_argument("--sim-fidelity", action="store_true",
                    help="run only the sim-vs-real fidelity gate (one "
                         "recorded Poisson schedule replayed through the "
                         "live engine and the trace-driven fleet "
                         "simulator; predicted TTFT/ITL p50/p90 must land "
                         "within the committed relative tolerance — "
                         "SERVING_r15.json is this mode's output)")
    ap.add_argument("--chaos", action="store_true",
                    help="run only the chaos-serving row (fault-free "
                         "baseline vs a fixed fault schedule — transient "
                         "dispatch failures, a poisoned row, a KV-alloc "
                         "outage — plus a kill-and-resume leg reporting "
                         "recovery time and goodput; survivor outputs are "
                         "asserted token-identical)")
    args = ap.parse_args()
    if args.tp and args.tp > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        # CPU was EXPLICITLY requested (as tests/conftest.py does): widen
        # it to the virtual args.tp-device mesh.
        # The flag must land before the first jax.devices() call — once a
        # backend is initialized, platform updates no longer re-select it.
        # With JAX_PLATFORMS unset or an accelerator named, nothing is
        # forced: a real slice benches its real devices, and too few
        # devices is a loud error below, never a silent CPU hijack.
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={args.tp}")
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.tp and args.tp > 1:
        if len(jax.devices()) < args.tp:
            raise SystemExit(
                f"--tp {args.tp}: only {len(jax.devices())} devices visible "
                f"on platform {jax.default_backend()!r}; for a virtual CPU "
                "parity run set JAX_PLATFORMS=cpu explicitly")
    # stdout stays pure JSON so `> SERVING_rNN.json` works as documented
    from deepspeed_tpu.utils.logging import logs_to_stderr
    logs_to_stderr()
    platform = jax.default_backend()
    focused = any((args.tp, args.quant, args.prefix_cache, args.disagg,
                   args.service, args.tracing, args.router, args.sim_fidelity,
                   args.chaos, args.scheduler, args.speculate))
    if platform != "tpu" and not (
            focused and os.environ.get("JAX_PLATFORMS") == "cpu"):
        raise SystemExit(
            f"serving_bench: no TPU found (platform {platform!r}). The "
            "default row set measures the chip; only the focused contract "
            "modes run on a CPU that was asked for with JAX_PLATFORMS=cpu")
    if platform == "tpu":
        model, long_prompt = "gpt2-small", 768
        decode_cfgs = [(8, 128, 128), (32, 128, 128), (64, 128, 128)]
        prefill_cfgs = [(8, long_prompt)]
        mixed = (16, 256, 64)
        mixed_dynamic = (16, 256, 64, 32)      # last field: n_arrivals
        delta = (32, 512, 128)
        # near-full contexts (832 + 128 + 1 lookahead slot = 961 <= 1024,
        # exactly 8 pages/seq; 896 would need a 9th page past max_seq_len)
        delta_long = (16, 832, 128)
        medium_decode = ("gpt2-medium", 8, 128, 128)
        collapse = (128, 64)
    else:   # contract check on the CPU that was asked for
        model, long_prompt = "tiny", 64
        decode_cfgs = [(4, 16, 16)]
        prefill_cfgs = [(4, long_prompt)]
        mixed = (4, 32, 8)
        mixed_dynamic = (4, 32, 8, 8)
        delta = (4, 32, 16)
        delta_long = None
        medium_decode = None
        collapse = None

    rows = []

    def add(row):
        rows.append(row)
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    def guarded(tag, fn, *a, **kw):
        # a failed config is a structured row, never a raw traceback —
        # and finish() turns any such row into a nonzero exit
        try:
            add(fn(*a, **kw))
        except Exception as e:
            add({"workload": tag, "status": "failed",
                 "error_type": type(e).__name__, "error": str(e)[:300]})

    def finish(summary):
        print(json.dumps(summary))
        if any(r.get("status") == "failed" for r in rows):
            sys.exit(1)

    if args.tp:
        # focused mode: the tensor-parallel scaling row only
        b, p, n, arr = mixed_dynamic
        guarded("tp-serving", bench_tp, model, b, p, n, tp=args.tp,
                n_arrivals=arr)
        row = next((r for r in rows if r.get("workload") == "tp-serving"),
                   {})
        finish({
            "metric": "fastgen_serving_tp",
            "model": model, "platform": jax.default_backend(),
            "value": row.get("scaling_tok_per_sec_per_chip_vs_tp1"),
            "unit": f"tp={args.tp} tokens/s/chip vs single-chip baseline",
            "rows": rows,
        })
        return

    if args.quant:
        # focused mode: the quantized-serving capacity/tolerance row only
        b, p, n, arr = mixed_dynamic
        # the slot table must outsize the burst so the ONLY admission
        # constraint is KV-pool pressure — the quantity under test
        guarded("quant-serving", bench_quant, model, max(b, 8), max(p, 32),
                n, n_arrivals=8)
        row = next((r for r in rows if r.get("workload") == "quant-serving"),
                   {})
        finish({
            "metric": "fastgen_serving_quant",
            "model": model, "platform": platform,
            "value": row.get("slots_ratio_int8_over_f32"),
            "unit": "slots-until-first-deferral ratio int8-KV/f32 at one "
                    "KV HBM byte budget (block-bytes ratio "
                    f"{row.get('kv_block_bytes_ratio_f32_over_int8')})",
            "rows": rows,
        })
        return

    if args.prefix_cache:
        # focused mode: the KV-memory-hierarchy row only
        b, p, n, arr = mixed_dynamic
        guarded("prefix-cache", bench_prefix_cache, model, b,
                max(p, 2 * long_prompt), n, n_arrivals=max(arr, 12),
                assert_contract=(platform != "tpu"))
        row = next((r for r in rows if r.get("workload") == "prefix-cache"),
                   {})
        full = (row.get("sweep") or [{}])[-1]
        finish({
            "metric": "fastgen_serving_prefix_cache",
            "model": model, "platform": platform,
            "value": full.get("ttft_p90_speedup"),
            "unit": "TTFT p90 speedup vs cold at full-share "
                    f"(hit rate {full.get('hit_rate')})",
            "rows": rows,
        })
        return

    if args.disagg:
        # focused mode: the disaggregated prefill/decode fleet row only
        if platform == "tpu":
            b = 32
            cfgs = dict(long_prompt=1024, short_prompt=64,
                        long_new=8, short_new=64, n_long=4, n_short=48)
        else:
            # chunk=8: a long prompt spans 32 chunk steps (16 two-step
            # frames), so a monolithic replica's stream is chunk-wide for
            # most of a long's prefill while a burst of 8-token shorts
            # admits in ONE cheap wide frame — the widest differential
            # wide-frame count the overhead-bound tiny model can show
            b = 16
            cfgs = dict(long_prompt=256, short_prompt=8,
                        long_new=4, short_new=24, n_long=4, n_short=45,
                        chunk=8)
        guarded("disagg-serving", bench_disagg, model, b,
                assert_contract=(platform != "tpu"), **cfgs)
        row = next((r for r in rows
                    if r.get("workload") == "disagg-serving"), {})
        finish({
            "metric": "fastgen_serving_disagg",
            "model": model, "platform": platform,
            "value": row.get("ttft_p90_speedup"),
            "unit": "disagg/monolithic fleet TTFT p90 speedup "
                    f"(ITL p90 speedup {row.get('itl_p90_speedup')}) on a "
                    "long-prompt/short-decode mix at equal replica count",
            "rows": rows,
        })
        return

    if args.service:
        # focused mode: the service-edge row only
        b, p, n, arr = mixed_dynamic
        guarded("service-edge", bench_service, model, max(b, 8), p, n,
                n_arrivals=max(arr, 12), sessions=args.sessions,
                assert_contract=(platform != "tpu"))
        row = next((r for r in rows
                    if r.get("workload") == "service-edge"), {})
        finish({
            "metric": "fastgen_serving_service",
            "model": model, "platform": platform,
            "value": (row.get("routing_overhead") or {}).get(
                "threaded_over_serial_tok_per_sec"),
            "unit": "threaded/serial fleet-driver tok/s ratio "
                    f"({(row.get('loadgen') or {}).get('sessions')} "
                    "closed-loop SSE sessions, zero parity violations "
                    "asserted)",
            "rows": rows,
        })
        return

    if args.tracing:
        # focused mode: the distributed-tracing overhead row only
        b, p, n, arr = mixed_dynamic
        guarded("tracing-overhead", bench_tracing_overhead, model, b, p, n,
                n_arrivals=arr, assert_budget=(platform != "tpu"))
        row = next((r for r in rows
                    if r.get("workload") == "tracing-overhead"), {})
        finish({
            "metric": "fastgen_serving_tracing",
            "model": model, "platform": platform,
            "value": row.get("overhead_pct"),
            "unit": "distributed-tracing overhead % (paired on/off "
                    "rounds, <2% budget asserted in smoke)",
            "rows": rows,
        })
        return

    if args.router:
        # focused mode: the multi-engine failover row only
        b, p, n, arr = mixed_dynamic
        guarded("router-failover", bench_router, model, b, p, n,
                n_arrivals=max(arr, 8))
        row = next((r for r in rows
                    if r.get("workload") == "router-failover"), {})
        finish({
            "metric": "fastgen_serving_router",
            "model": model, "platform": platform,
            "value": row.get("kill_goodput_ratio"),
            "unit": "kill+failover/single-engine goodput ratio "
                    "(deterministic engine-kill schedule)",
            "rows": rows,
        })
        return

    if args.sim_fidelity:
        # focused mode: the sim-vs-real fidelity gate only
        b = mixed_dynamic[0]
        guarded("sim-fidelity", bench_sim_fidelity, model, batch=max(b, 8),
                assert_contract=(platform != "tpu"))
        guarded("sim-check", bench_sim_check)
        row = next((r for r in rows
                    if r.get("workload") == "sim-fidelity"), {})
        worst = max((c["rel_err"] for c in row.get("comparisons", [])),
                    default=None)
        finish({
            "metric": "fastgen_serving_sim_fidelity",
            "model": model, "platform": platform,
            "value": worst,
            "unit": "worst sim-vs-live relative error over TTFT/ITL "
                    f"p50/p90 (tolerance {row.get('tolerance_rel')})",
            "rows": rows,
        })
        return

    if args.chaos:
        # focused mode: fault tolerance vs the fault-free baseline only
        b, p, n, arr = mixed_dynamic
        guarded("chaos-serving", bench_chaos, model, b, p, n,
                n_arrivals=max(arr, 12))
        row = next((r for r in rows if r.get("workload") == "chaos-serving"),
                   {})
        finish({
            "metric": "fastgen_serving_chaos",
            "model": model, "platform": platform,
            "value": row.get("chaos_goodput_ratio"),
            "unit": "chaos/baseline goodput ratio (fixed fault schedule)",
            "rows": rows,
        })
        return

    if args.scheduler:
        # focused mode: the FIFO-vs-SLO-aware overload row only
        b, p, n, _arr = mixed_dynamic
        guarded("scheduler-slo", bench_scheduler, model, b, p, n)
        row = next((r for r in rows if r.get("workload") == "scheduler-slo"),
                   {})
        finish({
            "metric": "fastgen_serving_scheduler",
            "model": model, "platform": platform,
            "value": (row.get("slo_aware") or {}).get("interactive_ttft_p90_ms"),
            "unit": "SLO-aware interactive TTFT p90 (ms)",
            "rows": rows,
        })
        return

    if args.speculate:
        # focused mode: the speculative serving rows only (the spec bench
        # internally re-runs the non-spec frame + host-step contenders on
        # the same Poisson schedule for the side-by-side columns)
        b, p, n, arr = mixed_dynamic
        # speculation only engages on pure-decode (width-1) frames: give the
        # schedule enough decode budget that rows outlive the prefill frames
        spec_frame_steps = 8
        n = max(n, 3 * spec_frame_steps)
        guarded("mixed-splitfuse-dynamic-spec", bench_mixed_dynamic_spec,
                model, b, p, n, n_arrivals=arr, gamma=args.gamma,
                frame_steps=spec_frame_steps)
        spec_rows = [r for r in rows
                     if r.get("workload") == "mixed-splitfuse-dynamic-spec"]
        best = max((r.get("spec_frame_tok_per_sec", 0) or 0
                    for r in spec_rows), default=0)
        finish({
            "metric": "fastgen_serving_speculative",
            "model": model, "platform": platform,
            "value": best, "unit": "speculative serve tokens/s",
            "rows": rows,
        })
        return

    for b, p, n in decode_cfgs:
        guarded("decode-heavy", bench_decode, model, b, p, n)
    for b, p in prefill_cfgs:
        guarded("prefill-heavy", bench_prefill, model, b, p)
    guarded("mixed-splitfuse", bench_mixed, model, *mixed)
    b, p, n, arr = mixed_dynamic
    guarded("mixed-splitfuse-dynamic", bench_mixed_dynamic, model, b, p, n,
            n_arrivals=arr)
    # telemetry budget: the <2% overhead contract is ASSERTED in the smoke
    # configuration (deterministic schedule, CPU) and reported on TPU
    guarded("telemetry-overhead", bench_telemetry_overhead, model, b, p, n,
            n_arrivals=arr, assert_budget=(platform != "tpu"))
    # distributed-tracing budget: same <2% contract, spans-on vs spans-off
    guarded("tracing-overhead", bench_tracing_overhead, model, b, p, n,
            n_arrivals=arr, assert_budget=(platform != "tpu"))
    # SLO-aware scheduling vs FIFO on a deterministic 2-tenant overload
    guarded("scheduler-slo", bench_scheduler, model, b, p, n)
    # the fleet simulator's own CI smoke (determinism, snapshot/resume,
    # real-policy execution) rides in the default row set: a sim that
    # stops being deterministic must fail THIS artifact, not wait for
    # someone to run the focused mode
    guarded("sim-check", bench_sim_check)
    guarded("kernel-delta", bench_kernel_delta, model, *delta)
    if delta_long is not None:
        guarded("kernel-delta", bench_kernel_delta, model, *delta_long)
    if medium_decode is not None:
        guarded("decode-heavy", bench_decode, *medium_decode)
    if collapse is not None:
        guarded("decode-collapse-probe", bench_decode_collapse_probe, model,
                *collapse)
    if platform == "tpu":
        guarded("woq-kernel-delta", bench_woq_delta)
        guarded("platform-floor", bench_platform_floor)

    best_decode = max((r.get("decode_tok_per_sec", 0) for r in rows), default=0)
    finish({
        "metric": "fastgen_serving",
        "model": model, "platform": platform,
        "value": best_decode, "unit": "decode tokens/s",
        "rows": rows,
    })


if __name__ == "__main__":
    main()
