#!/usr/bin/env python
"""chip_smoke.py: does the system still start on the chip?

Drives the main path once through the entry points a user calls, at the
full width of models the repo supports, with random weights from
``--seed`` and depth cut only as far as one chip's 16 GB forces:

* ``serve`` — the server a user starts: the wiring of ``bin/dstpu_serve``
  with one replica (engine → EngineRouter → FleetDriver → ServiceEdge) on
  ``mistral-7b`` at its published widths, bf16, engine-default page and
  chunk size, answering ``POST /v1/generate`` over HTTP: several-hundred-
  token prompts, some concurrent, one streamed and one ``"stream": false``
  of the same prompt (identical tokens), then a clean drain.
* ``train`` — ``deepspeed_tpu.initialize`` + ``engine.train_batch`` on
  ``gpt2-medium`` whole (seq 1024, bf16, ZeRO-1, micro-batch 8): loss
  finite and falling on a fixed batch, timed after ``block_until_ready``.

Each phase is its own process — a chip belongs to one process at a time,
so this parent never imports JAX — and proves from inside that process
that the Mosaic kernel ran: the compiled program's text holds the
``tpu_custom_call`` (flash attention in the train step; paged attention
in the prefill-width and the width-1 serving frame), so interpret mode,
``DS_TPU_DISABLE_PALLAS`` or a swallowed exception cannot pass. Each
phase also checks its result against a plain-XLA reference.

``--chips 4`` runs ONLY what exists across chips, in one process that
drives all four: ZeRO-3 ``train_batch`` on ``mesh {"data": 4}`` against
the same global batch on one device, and the serve model at ``tp=4``
against ``tp=1`` on the same schedule.

stdout: one JSON line per phase, then — only if every phase passed on a
TPU — ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}``. No chip, a failed phase, or a missing repo is a nonzero exit and
no such line. ``--rehearse`` walks the same code at toy sizes on whatever
backend JAX has (the CPU rehearsal of guide ``on-chip-measurement`` §2);
it skips the kernel proof and never prints the success line.
"""

import argparse
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
PHASE_TIMEOUT_S = 1000       # the whole script must end inside 1200 s

# what each phase runs: the real thing, and the toy the rehearsal walks
SERVE = {
    "real": dict(model="mistral-7b", overrides={"num_layers": 16}, batch=16,
                 max_seq_len=4096, kv_blocks=416),
    "toy": dict(model="tiny", overrides={}, batch=4, max_seq_len=1024,
                kv_blocks=None),
}
PROMPT_LENS = (300, 384, 270, 330, 412)   # >= 2 chunks of 128, one bucket
NEW_TOKENS = 32
TRAIN = {
    "real": dict(model="gpt2-medium", seq=1024, micro=8, steps=6),
    "toy": dict(model="tiny-gpt2", seq=128, micro=8, steps=6),
}
# bf16 keeps 8 bits of mantissa: logits of magnitude ~4-8 resolve to ~0.03,
# and the paged and reference paths round differently through every layer
LOGIT_TOL = 0.25
LOSS_TOL = 0.05


# ----------------------------------------------------------------------
# parent: starts each phase as a child in turn, never touches JAX
# ----------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; no kernel proof, no "
                         "success line")
    ap.add_argument("--phase", help=argparse.SUPPRESS)   # child entry
    args = ap.parse_args()
    if args.phase:
        return run_phase(args)

    device = None
    for phase in (("serve", "train") if args.chips == 1 else ("multichip",)):
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--chips", str(args.chips), "--seed", str(args.seed)]
        if args.rehearse:
            cmd.append("--rehearse")
        try:
            # run() kills the child when the limit cuts it
            proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                                  timeout=PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            sys.exit(f"chip_smoke: phase {phase} cut at {PHASE_TIMEOUT_S} s")
        report = None
        for line in proc.stdout.splitlines():
            print(line, flush=True)
            if line.startswith("{"):
                report = json.loads(line)
        if proc.returncode != 0 or not report or not report.get("passed"):
            sys.exit(f"chip_smoke: phase {phase} failed "
                     f"(exit code {proc.returncode})")
        if device not in (None, report["device"]):
            sys.exit(f"chip_smoke: phases saw different devices: {device} "
                     f"vs {report['device']}")
        device = report["device"]
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return
    print(json.dumps({"ok": True, "device": device}))


# ----------------------------------------------------------------------
# children: one process, one phase, the chip to itself
# ----------------------------------------------------------------------

def run_phase(args):
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    # stdout carries the phase's JSON line and nothing else
    from deepspeed_tpu.utils.logging import logs_to_stderr
    logs_to_stderr()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no accelerator: {device}")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found {device}")
    size = "toy" if args.rehearse else "real"
    phase = {"serve": phase_serve, "train": phase_train,
             "multichip": phase_multichip}[args.phase]
    report = phase(size, args.seed, on_chip=not args.rehearse)
    stats = devs[0].memory_stats() or {}
    report.update(phase=args.phase, device=device, passed=True,
                  compile_cache=cache_dir,
                  peak_hbm_gb=round(stats.get("peak_bytes_in_use", 0) / 1e9, 2))
    print(json.dumps(report), flush=True)


def has_mosaic_call(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def abstract(tree):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


# ---- serve ------------------------------------------------------------

def load_dstpu_serve():
    """``bin/dstpu_serve`` has no ``.py``: load it by path."""
    from importlib.machinery import SourceFileLoader
    from importlib.util import module_from_spec, spec_from_loader
    loader = SourceFileLoader("dstpu_serve",
                              os.path.join(ROOT, "bin", "dstpu_serve"))
    mod = module_from_spec(spec_from_loader("dstpu_serve", loader))
    loader.exec_module(mod)
    return mod


def record_frame_programs():
    """Note the arguments of every distinct frame program the runner
    dispatches, so the programs that RAN — not lookalikes — can be lowered
    again afterwards and their text inspected. Returns the live dict
    ``(width, prompt width, table width) -> (runner, abstract args,
    static kwargs)``."""
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    seen = {}
    dispatch = PagedModelRunner.frame_loop

    def frame_loop(self, *a, **kw):
        key = (kw["width"], a[1].shape[1], a[6].shape[1])
        if key not in seen:
            seen[key] = (self, abstract(a), dict(kw))
        return dispatch(self, *a, **kw)

    PagedModelRunner.frame_loop = frame_loop
    return seen


def prove_paged_kernel(programs, chunk, on_chip):
    """Both frame widths must have run, each with the Mosaic call in it
    (a rehearsal has none to find)."""
    widths = {}
    for (width, _, _), (runner, a, kw) in programs.items():
        compiled = runner._fns["frame"].lower(*a, **kw).compile()
        widths[width] = widths.get(width, True) and has_mosaic_call(compiled)
    assert set(widths) == {chunk, 1}, f"frame widths that ran: {set(widths)}"
    if not on_chip:
        return "not checked (rehearsal)"
    assert all(widths.values()), \
        f"paged-attention Mosaic call missing from frame programs: {widths}"
    return {f"width{w}": ok for w, ok in sorted(widths.items())}


def post_generate(port, prompt, stream):
    """POST /v1/generate; returns the generated tokens (for a streamed
    call: the concatenated ``token`` events, checked against ``done``)."""
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/v1/generate", json.dumps(
            {"prompt": prompt, "max_new_tokens": NEW_TOKENS,
             "stream": stream}), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200, f"HTTP {resp.status}: {body[:300]}"
    finally:
        conn.close()
    if not stream:
        return json.loads(body)["tokens"]
    streamed, done = [], None
    for event in body.strip().split("\n\n"):
        fields = dict(ln.split(": ", 1) for ln in event.splitlines()
                      if ": " in ln)
        data = json.loads(fields.get("data", "null"))
        if fields.get("event") == "token":
            streamed.extend(data["tokens"])
        elif fields.get("event") == "done":
            done = data["tokens"]
        elif fields.get("event") == "error":
            raise AssertionError(f"server sent an error event: {data}")
    assert done is not None and streamed == done, \
        f"streamed tokens {streamed} != done event {done}"
    return streamed


def reference_apply(model):
    """``model.apply`` with plain-XLA attention, jitted once."""
    import jax
    from deepspeed_tpu.models import build_model
    return jax.jit(build_model(model.cfg.replace(attn_impl="reference")).apply)


def reference_gaps(ref_apply, params, prompt, generated):
    """Teacher-forced check against plain-XLA attention: for each generated
    token, how far its reference logit sits below the reference maximum at
    that position (0 = the reference's own greedy choice). Covers the first
    token (prefill frames) and every later one (width-1 decode frames)."""
    import numpy as np
    ids = np.asarray(list(prompt) + list(generated[:-1]), np.int32)
    padded = np.zeros((1, -(-len(ids) // 128) * 128), np.int32)
    padded[0, :len(ids)] = ids          # causal: the pad tail changes nothing
    logits = np.asarray(ref_apply(params, padded)[0], np.float32)
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(generated)]
    return rows.max(-1) - rows[np.arange(len(generated)), generated]


def phase_serve(size, seed, on_chip):
    import numpy as np
    s = SERVE[size]
    programs = record_frame_programs()
    serve = load_dstpu_serve()
    argv = ["--model", s["model"], "--replicas", "1", "--port", "0",
            "--batch", str(s["batch"]), "--max-seq-len", str(s["max_seq_len"]),
            "--max-new-tokens", str(NEW_TOKENS), "--seed", str(seed)]
    for field, value in s["overrides"].items():
        argv += ["--set", f"{field}={value}"]
    if s["kv_blocks"]:
        argv += ["--kv-blocks", str(s["kv_blocks"])]
    t0 = time.perf_counter()
    svc = serve.build_service(serve.parse_args(argv))
    build_s = time.perf_counter() - t0
    try:
        eng = svc.engines["replica0"]
        cfg = eng.model.cfg
        port = svc.edge.edge_port
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in PROMPT_LENS]

        # the first request compiles both frame programs; the same prompt
        # again, unstreamed, walks the same frames: identical tokens
        t0 = time.perf_counter()
        first = post_generate(port, prompts[0], stream=True)
        first_request_s = time.perf_counter() - t0
        unstreamed = post_generate(port, prompts[0], stream=False)
        assert first == unstreamed and len(first) == NEW_TOKENS, \
            f"streamed {first} != unstreamed {unstreamed} of one prompt"

        # steady: every prompt at once. Under load a row decodes inside
        # wide (prefill-width) frames while its neighbours still prefill,
        # so in bf16 its near-tied logits may order differently than they
        # did alone: each answer is held to the reference below, not to
        # `first` (whether prompt 0 repeated itself is reported)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(prompts)) as pool:   # a failure re-raises here
            results = list(pool.map(
                lambda p: post_generate(port, p, stream=True), prompts))
        steady_s = time.perf_counter() - t0
        assert all(len(t) == NEW_TOKENS for t in results), \
            [len(t) for t in results]

        deadline = time.monotonic() + 30
        while svc.driver.in_flight() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.kv.free_blocks == eng.kv.num_blocks - 1 \
            and not eng.state.seqs, \
            f"unclean drain: {eng.kv.free_blocks} of {eng.kv.num_blocks} free"

        ref_apply = reference_apply(eng.model)
        gaps = {"alone": reference_gaps(ref_apply, eng.params, prompts[0],
                                        first)}
        for i, (prompt, tokens) in enumerate(zip(prompts, results)):
            gaps[i] = reference_gaps(ref_apply, eng.params, prompt, tokens)
        worst = {k: round(float(g.max()), 4) for k, g in gaps.items()}
        assert all(np.isfinite(g).all() and g.max() <= LOGIT_TOL
                   for g in gaps.values()), \
            f"paged path disagrees with model.apply: max gaps {worst}"
        kernel = prove_paged_kernel(programs, eng._config.prefill_chunk_size,
                                    on_chip)
        n_tokens = NEW_TOKENS * len(results)
        return {
            "model": s["model"], "layers": cfg.num_layers, "dtype": cfg.dtype,
            "kv_page": eng.kv.block_size, "kv_blocks": eng.kv.num_blocks,
            "kv_pool_gb": round(eng.kv.block_bytes * eng.kv.num_blocks / 1e9, 2),
            "prefill_chunk": eng._config.prefill_chunk_size,
            "build_s": round(build_s, 2),
            "compile_s": round(first_request_s, 2),
            "steady_s": round(steady_s, 3),
            "requests": len(results), "tokens": n_tokens,
            "tokens_per_s": round(n_tokens / steady_s, 1),
            "frame_programs": eng.runner.compile_count(),
            "mosaic_paged_attention": kernel,
            "ref_logit_gap_max": worst, "ref_logit_tol": LOGIT_TOL,
            "same_tokens_under_load": results[0] == first,
            "first_tokens": first[:4],
        }
    finally:
        svc.edge.shutdown()
        svc.driver.stop()


# ---- train ------------------------------------------------------------

def make_batch(vocab, rows, seq, seed):
    import numpy as np
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, seq + 1),
                                               dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def train_engine(model_name, seq, micro, gas, zero_stage):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, get_config
    # remat="dots" (keep matmul outputs, recompute the rest): without it
    # gpt2-medium at micro-batch 8 saves ~20 GB of activations
    model = build_model(get_config(model_name, max_seq_len=seq,
                                   dtype="bfloat16", remat="dots"))
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": True},
        "steps_per_print": 10 ** 9,
    })
    return engine


def timed_steps(engine, batch, steps):
    """``steps`` train_batch calls on one fixed batch, each timed to
    ``block_until_ready``. Returns (losses, seconds per step)."""
    import jax
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(batch))
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, secs


def prove_flash_kernel(engine, batch, on_chip):
    """Lower the step that ran again, from its arguments' shapes, and look
    for the Mosaic call in it (a rehearsal has none to find)."""
    import jax
    import jax.numpy as jnp
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    compiled = engine._train_step_fn.lower(
        *abstract((engine.module_params, engine.opt_state,
                   engine.scaler_state, batch)), lr,
        gas=engine.gradient_accumulation_steps()).compile()
    if not on_chip:
        return "not checked (rehearsal)"
    assert has_mosaic_call(compiled), \
        "flash-attention Mosaic call missing from the train step"
    return True


def phase_train(size, seed, on_chip):
    import statistics
    import jax
    import numpy as np
    from deepspeed_tpu.models import build_model
    t = TRAIN[size]
    t0 = time.perf_counter()
    engine = train_engine(t["model"], t["seq"], t["micro"], gas=1,
                          zero_stage=1)
    build_s = time.perf_counter() - t0
    cfg = engine.model.cfg
    batch = engine.stage_batch(
        make_batch(cfg.vocab_size, t["micro"], t["seq"], seed))

    # the step donates the parameters: take the reference loss first
    ref = build_model(cfg.replace(attn_impl="reference"))
    ref_loss = float(jax.jit(ref.loss)(
        engine.module_params, jax.tree.map(lambda x: x[0], batch)))

    losses, secs = timed_steps(engine, batch, t["steps"])
    # did block_until_ready really wait? then the value is already here
    # and fetching it costs no step time
    loss = engine.train_batch(batch)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    losses.append(float(jax.device_get(loss)))
    fetch_after_ready_ms = (time.perf_counter() - t0) * 1e3

    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert abs(losses[0] - ref_loss) <= LOSS_TOL, \
        f"first loss {losses[0]} vs reference attention {ref_loss}"
    steady = statistics.median(secs[1:])
    return {
        "model": t["model"], "layers": cfg.num_layers, "seq": t["seq"],
        "micro_batch": t["micro"], "zero_stage": 1, "dtype": cfg.dtype,
        "params_m": round(engine.model.param_count() / 1e6, 1),
        "build_s": round(build_s, 2),
        "compile_s": round(secs[0], 2),
        "steady_s": round(steady, 4),
        "step_s": [round(x, 4) for x in secs[1:]],
        "tokens_per_s": round(t["micro"] * t["seq"] / steady, 1),
        "fetch_after_ready_ms": round(fetch_after_ready_ms, 3),
        "losses": [round(x, 4) for x in losses],
        "ref_loss": round(ref_loss, 4), "ref_loss_tol": LOSS_TOL,
        "mosaic_flash_attention": prove_flash_kernel(engine, batch, on_chip),
    }


# ---- four chips -------------------------------------------------------

def shard_devices(tree):
    """(distinct devices holding shards, largest leaf's global shape, its
    shard shape) over a pytree of arrays."""
    import jax
    leaves = jax.tree.leaves(tree)
    big = max(leaves, key=lambda x: x.size)
    devices = {s.device.id for x in leaves for s in x.addressable_shards}
    return sorted(devices), big.shape, big.addressable_shards[0].data.shape


def zero3_dp4(size, seed, on_chip):
    """ZeRO-3 on mesh {"data": 4} against the same global batch on ONE of
    the four devices (micro 4 x gas 8 there — one chip holds the whole
    model state, so less is left for activations — micro 8 x dp 4 here)."""
    import gc
    import jax
    import numpy as np
    from deepspeed_tpu.utils import groups
    t = TRAIN[size]
    runs = {}
    for name, devices, micro, gas in (
            ("one_device", jax.devices()[:1], t["micro"] // 2, 8),
            ("dp4", jax.devices()[:4], t["micro"], 1)):
        groups.reset_mesh()
        groups.set_mesh(groups.build_mesh(devices=devices))
        engine = train_engine(t["model"], t["seq"], micro, gas=gas,
                              zero_stage=3)
        cfg = engine.model.cfg
        rows = make_batch(cfg.vocab_size, 4 * t["micro"], t["seq"], seed)
        batch = engine.stage_batch(rows)
        losses, secs = timed_steps(engine, batch, 4)
        runs[name] = {"losses": [round(x, 4) for x in losses],
                      "compile_s": round(secs[0], 2),
                      "steady_s": round(float(np.median(secs[1:])), 4)}
        if name == "dp4":
            p_dev, p_shape, p_shard = shard_devices(engine.module_params)
            o_dev, o_shape, o_shard = shard_devices(engine.opt_state)
            assert len(p_dev) == 4 and len(o_dev) == 4, (p_dev, o_dev)
            assert np.prod(p_shard) * 4 == np.prod(p_shape), (p_shape, p_shard)
            assert np.prod(o_shard) * 4 == np.prod(o_shape), (o_shape, o_shard)
            runs[name].update(
                param_shard_devices=p_dev, param_shard=[p_shape, p_shard],
                opt_shard_devices=o_dev, opt_shard=[o_shape, o_shard])
            runs[name]["mosaic_flash_attention"] = \
                prove_flash_kernel(engine, batch, on_chip)
        del engine, batch
        gc.collect()
    groups.reset_mesh()
    a, b = runs["one_device"]["losses"], runs["dp4"]["losses"]
    assert np.isfinite(a + b).all() and b[-1] < b[0], (a, b)
    gap = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
    assert gap <= LOSS_TOL, f"dp=4 and one-device losses differ: {a} vs {b}"
    return {"model": t["model"], "zero_stage": 3, "global_batch": 4 * t["micro"],
            "loss_gap_max": round(gap, 4), "loss_tol": LOSS_TOL, **runs}


def serve_tp4(size, seed, on_chip):
    """The serve phase's model at tp=4 against tp=1 on one schedule, driven
    through ``engine.serve`` (the HTTP edge adds nothing across chips)."""
    import gc
    import jax
    import numpy as np
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model, get_config
    s = SERVE[size]
    cfg = get_config(s["model"], **s["overrides"])
    if size == "toy":
        cfg = cfg.replace(num_heads=8)      # every sharded axis must divide 4
    model = build_model(cfg.replace(param_dtype=cfg.dtype))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS[:4]]
    # two arrive at once, the others one frame boundary apart
    schedule = [[(0, prompts[0]), (1, prompts[1])], [(2, prompts[2])],
                [(3, prompts[3])]]
    programs = record_frame_programs()
    ref_apply = reference_apply(model)
    runs, tokens = {}, {}
    for tp in (1, 4):
        programs.clear()
        eng = InferenceEngineV2(
            model, RaggedInferenceEngineConfig(
                tp=tp, dtype=cfg.dtype, max_ragged_batch_size=s["batch"],
                num_kv_blocks=256 if s["kv_blocks"] else None),
            params=params, max_seq_len=s["max_seq_len"])
        t0 = time.perf_counter()
        tokens[tp] = {uid: [int(x) for x in toks] for uid, toks in
                      eng.serve(iter(schedule), max_new_tokens=NEW_TOKENS)}
        wall_s = time.perf_counter() - t0
        assert sorted(tokens[tp]) == [0, 1, 2, 3] and all(
            len(t) == NEW_TOKENS for t in tokens[tp].values())
        assert eng.kv.free_blocks == eng.kv.num_blocks - 1
        gaps = np.concatenate([
            reference_gaps(ref_apply, params, prompts[u], tokens[tp][u])
            for u in (0, 3)])
        assert gaps.max() <= LOGIT_TOL, f"tp={tp}: max gap {gaps.max()}"
        runs[f"tp{tp}"] = {"serve_s_with_compile": round(wall_s, 2),
                           "ref_logit_gap_max": round(float(gaps.max()), 4)}
        if tp == 4:
            w_dev, w_shape, w_shard = shard_devices(eng.params)
            k_dev, k_shape, k_shard = shard_devices(eng.kv.k)
            assert len(w_dev) == 4 and len(k_dev) == 4, (w_dev, k_dev)
            runs["tp4"].update(weight_shard_devices=w_dev,
                               weight_shard=[w_shape, w_shard],
                               kv_shard_devices=k_dev,
                               kv_shard=[k_shape, k_shard])
        runs[f"tp{tp}"]["mosaic_paged_attention"] = prove_paged_kernel(
            programs, eng._config.prefill_chunk_size, on_chip)
        del eng
        gc.collect()
    same = tokens[1] == tokens[4]
    return {"model": s["model"], "layers": cfg.num_layers,
            "greedy_tokens_identical": same,
            "note": ("identical" if same else
                     "tp=4 sums each row-parallel product as four bf16 "
                     "partial sums, so near-tied logits can order "
                     "differently; every token of both runs is within "
                     f"{LOGIT_TOL} of the reference maximum"),
            "ref_logit_tol": LOGIT_TOL, **runs}


def phase_multichip(size, seed, on_chip):
    return {"zero3_dp4": zero3_dp4(size, seed, on_chip),
            "serve_tp4": serve_tp4(size, seed, on_chip)}


if __name__ == "__main__":
    main()
