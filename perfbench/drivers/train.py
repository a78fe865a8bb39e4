"""Training cells: ``deepspeed_tpu.initialize`` + ``engine.train_batch``,
the calls a user makes, on a mesh over the cell's chips. One process
drives all of them.

Set-up builds the engine (weights on the devices from --seed, sharded as
the ZeRO stage says) and runs the warm-up steps, which compile. The window
then runs whole steps, each on a fresh batch made on the host, each timed
to ``block_until_ready``. After the window one more step is held to the
configuration's plain reference: ``train_batch`` returns the loss of the
weights it was given, so the reference loss of those weights on that batch
is taken just before it.
"""

import math
import time

from perfbench import harness, peaks, trace_reduce, work

WINDOW_SPAN = "perfbench/trace_window"
STEP_SPAN = "perfbench/train_batch"
# bf16 compute against a float32 reference on a loss near ln(vocab) ~ 10:
# PR 21's value; a wrong mask, shard or gradient layout moves it by more
LOSS_TOL = 0.05


def build_engine(run, plan):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, get_config
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    config = run.cell.config
    groups.reset_mesh()
    groups.set_mesh(groups.build_mesh(devices=run.devices))
    model = build_model(get_config(
        config["preset"], **{"max_seq_len": plan["seq_len"],
                             **harness.preset_overrides(config)}))
    ds_config = dict(config["ds_config"])
    ds_config.update(
        train_micro_batch_size_per_gpu=plan["micro_batch_per_chip"],
        gradient_accumulation_steps=plan["gradient_accumulation_steps"],
        seed=run.seed, steps_per_print=10 ** 9)
    engine, _, _, _ = ds.initialize(model=model, config=ds_config)
    return engine


def run(run):
    import jax
    cell, config, traffic = run.cell, run.cell.config, run.cell.traffic
    generator = cell.module("generators", traffic["generator"])
    plan = generator.plan(traffic, run.seed, run.seconds)
    chips = len(run.devices)
    rows = (plan["micro_batch_per_chip"] * chips
            * plan["gradient_accumulation_steps"])
    seq, vocab = plan["seq_len"], config["vocab_size"]

    def batch(step):
        return generator.batch_for(run.seed, step, rows, seq, vocab)

    def step(i):
        b = batch(i)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            loss = jax.block_until_ready(engine.train_batch(b))
        return loss, t0, time.monotonic()

    harness.log(f"building the engine on {chips} chips")
    engine = build_engine(run, plan)
    for i in range(int(traffic.get("warmup_steps", 2))):
        _, a, b = step(2 * 10 ** 6 + i)
        harness.log(f"warm-up step {i}: {b - a:.2f} s")

    # ---- the window ----
    w0 = time.monotonic()
    setup_s = w0 - run.t_start
    losses, starts, ends = [], [], []
    span, trace_at = None, int(traffic.get("trace_from_step", 2))
    i = 0
    while True:
        if run.trace and i == trace_at:
            span = traced_steps(run, jax, step, i, plan["trace_steps"],
                                losses, starts, ends)
            i += plan["trace_steps"]
            continue
        loss, a, b = step(i)
        if b - w0 > run.seconds:
            break                      # ended after the window: not counted
        losses.append(loss)
        starts.append(a)
        ends.append(b)
        i += 1
    losses = [float(x) for x in jax.device_get(losses)]
    finite = all(math.isfinite(x) for x in losses)

    # ---- one more step, against the plain reference ----
    reference = cell.module("configs", config["reference"])
    check = batch(10 ** 6)
    t0 = time.monotonic()
    ref_loss = reference.loss(
        engine.module_params,
        jax.tree.map(lambda x: x[0], engine.stage_batch(check)), config)
    got = float(jax.block_until_ready(engine.train_batch(check)))
    harness.log(f"loss {got:.4f} against the plain reference's "
                f"{ref_loss:.4f} (tolerance {LOSS_TOL}) in "
                f"{time.monotonic() - t0:.1f} s")
    correct = (finite and math.isfinite(got)
               and abs(got - ref_loss) <= LOSS_TOL and len(ends) > 0)

    d = work.dims(config)
    ctx = {"kind": "train", "setup_s": setup_s, "chips": chips,
           "step_ends": [e - w0 for e in ends],
           "step_seconds": [b - a for a, b in zip(starts, ends)],
           # whole steps only, so the rate is over the time they took: from
           # the window's start to the end of the last step that ended in it
           "window_s": (ends[-1] - w0) if ends else run.seconds,
           "tokens_per_step": rows * seq,
           "flops_per_step": work.train_step_flops(d, rows, seq),
           "peaks": None if run.rehearse else peaks.peaks_for(
               run.devices[0].device_kind),
           "memory_peak_bytes": harness.device_block(run.devices)[
               "memory_peak_bytes"],
           "losses": losses, "trace": None, "traffic": traffic,
           "config": config, "dims": d}
    if span is not None:
        trace = trace_reduce.load_newest(span)
        ctx["trace"] = trace and trace_reduce.reduce_trace(trace, WINDOW_SPAN)
    return {"correct": correct, "attempted": len(ends) + 1,
            "failed": 0 if correct else 1, "ctx": ctx}


def traced_steps(run, jax, step, first, n, losses, starts, ends):
    """Profile ``n`` steps of the window (python tracer off)."""
    trace_dir = harness.start_profile(run.run_dir)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for i in range(first, first + n):
            loss, a, b = step(i)
            losses.append(loss)
            starts.append(a)
            ends.append(b)
    jax.profiler.stop_trace()
    return trace_dir
