#!/usr/bin/env python
"""Headline benchmark: ZeRO training throughput on the available chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: model-FLOPs utilization (MFU)-derived tokens/sec/chip for a
GPT-2-style causal LM trained with deepspeed_tpu (ZeRO + fused step),
scaled against the reference's A100 per-device baseline.

vs_baseline: measured MFU / 0.40 — DeepSpeed's published large-model
training runs sustain roughly 40% MFU on A100 (e.g. Ulysses blog: >54% of
peak on its best config, typical ZeRO-3 runs lower); beating 1.0 means the
TPU step loop is better at feeding its matrix units than the reference's.

The `extra` payload carries the evidence for the MFU story the headline
number rests on:
  - `matmul_ceiling_mfu`: raw bf16 matmul efficiency at the model's own
    matrix widths (the practical chip ceiling for this workload — if model
    MFU ~= this, the step loop is compute-bound, not framework-bound).
  - `matmul_peak_mfu`: the same measurement at large square shapes (what
    the chip can do when shapes are ideal).
  - `rows`: the gpt2-small batch sweep (8/16/32) and a gpt2-medium row,
    including failed configs recorded with their error instead of hidden.

Methodology notes:
- Needs the chip: no TPU, an unknown device kind, or a failed headline
  config prints a `bench-error` line and exits nonzero. Nothing here runs
  at a CPU size.
- Every timed window ends in `jax.block_until_ready` on a value that
  depends on the last step; warmup steps (compile included) come first.
- Batches are staged on device before the timed loop (input pipeline is
  benchmarked by the data-pipeline suite, not here).
- Matmul timing loops live inside one `lax.scan` dispatch, never chained
  small jit calls, so host dispatch does not enter the rate.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# bf16 peak by `device_kind` (Google Cloud documentation, "TPU v5e": 197
# TFLOP/s). A device that is not listed is an error, never a default.
PEAK_TFLOPS = {"TPU v5 lite": 197.0}


def peak_tflops():
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_TFLOPS:
        raise RuntimeError(f"no bf16 peak recorded for device kind {kind!r} "
                           f"(known: {sorted(PEAK_TFLOPS)})")
    return PEAK_TFLOPS[kind]


def _timed_matmul_chain(m, widths, iters=10, unroll=10):
    """Sustained bf16 TFLOP/s for a DEPENDENT matmul chain, one dispatch.

    ``widths`` is a cycle of inner dims (first == last): each step runs
    x @ W_0 @ W_1 ... with x genuinely carried between steps, so XLA can
    neither hoist the matmuls out of the loop nor overlap iterations —
    this measures back-to-back dependent GEMM throughput. ``unroll`` chains
    repeat inside the scan body so per-iteration loop overhead stays small
    next to sub-ms matmuls. A down-scale between steps keeps values finite
    (elementwise, fused, negligible next to the GEMMs).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    ws = [jnp.full((widths[i], widths[i + 1]), 0.01, jnp.bfloat16)
          for i in range(len(widths) - 1)]
    x0 = jnp.ones((m, widths[0]), jnp.bfloat16)

    @jax.jit
    def run(x, ws):
        def body(x, _):
            for _ in range(unroll):
                for w in ws:
                    x = x @ w
                x = (x * 1e-2).astype(jnp.bfloat16)
            return x, ()

        x, _ = lax.scan(body, x, None, length=iters)
        return jnp.sum(x.astype(jnp.float32))

    jax.block_until_ready(run(x0, ws))  # compile+warm
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x0, ws))
        windows.append(time.perf_counter() - t0)
    dt = statistics.median(windows)
    flops = 2 * m * sum(widths[i] * widths[i + 1]
                        for i in range(len(widths) - 1)) * iters * unroll
    return flops / dt / 1e12


def measure_matmul_ceiling():
    """Raw bf16 matmul efficiency: at model-relevant widths and at ideal shapes.

    gpt2-small's biggest GEMMs are 768-wide (QKV/proj: 768x768; MLP:
    768x3072x768); gpt2-medium's are 1024/4096. The ceiling that bounds the
    model is dependent-GEMM efficiency at THOSE widths, not at 8192^2.
    """
    peak = peak_tflops()
    # 8192 rows = the bench's batch*seq token count. The MLP chain
    # (768x3072x768) is the model's dominant GEMM pattern: its efficiency
    # is the practical per-matmul ceiling at gpt2-small's widths. (The
    # model itself can exceed it via intra-layer independent matmuls —
    # q/k/v — overlapping; model MFU >= this chain means the step loop
    # adds no framework overhead on top of the chip's shape limits.)
    mlp_tf = _timed_matmul_chain(8192, (768, 3072, 768))
    proj_tf = _timed_matmul_chain(8192, (768, 768))
    ideal_tf = _timed_matmul_chain(8192, (8192, 8192), iters=2, unroll=5)
    return {
        "matmul_ceiling_mfu": round(mlp_tf / peak, 4),
        "matmul_proj_mfu": round(proj_tf / peak, 4),
        "matmul_peak_mfu": round(ideal_tf / peak, 4),
    }


def run_train_config(name, batch, seq, dtype, zero_stage, warmup, steps, gas=1):
    """Train one config; return a result row. Failures become rows too.
    ``batch`` is the GLOBAL per-chip batch; ``gas`` splits it into
    microbatches (batch must divide by gas)."""
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, get_config

    n_chips = len(jax.devices())
    row = {"model": name, "batch": batch, "seq": seq}
    if gas > 1:
        row["gas"] = gas
    try:
        cfg = get_config(name, max_seq_len=seq)
        # remat="dots": save matmul outputs, recompute elementwise
        model = build_model(cfg.replace(dtype=dtype, remat="dots"))
        config = {
            "train_batch_size": batch * max(1, n_chips),
            "train_micro_batch_size_per_gpu": batch // gas,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "zero_optimization": {"stage": zero_stage},
            "bf16": {"enabled": dtype == "bfloat16"},
            "steps_per_print": 10 ** 9,
        }
        engine, _, _, _ = ds.initialize(model=model, config=config)
        rng = np.random.default_rng(0)

        def make_batch():
            ids = rng.integers(0, cfg.vocab_size,
                               (config["train_batch_size"], seq), dtype=np.int32)
            return {"input_ids": ids, "labels": ids}

        batches = [engine.stage_batch(make_batch()) for _ in range(4)]
        for i in range(warmup):
            loss = engine.train_batch(batches[i % len(batches)])
        jax.block_until_ready(loss)

        t0 = time.perf_counter()
        for i in range(steps):
            loss = engine.train_batch(batches[i % len(batches)])
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        final_loss = float(loss)

        tokens = steps * config["train_batch_size"] * seq
        tps_chip = tokens / dt / max(1, n_chips)
        n_params = model.param_count()
        achieved_tflops = tps_chip * 6 * n_params / 1e12
        peak = peak_tflops()
        row.update({
            "tokens_per_sec_chip": round(tps_chip, 1),
            "params_m": round(n_params / 1e6, 1),
            "achieved_tflops_per_chip": round(achieved_tflops, 2),
            "mfu": round(achieved_tflops / peak, 4),
            "step_ms": round(dt / steps * 1e3, 1),
            "final_loss": round(final_loss, 4),
            "zero_stage": zero_stage,
        })
    except Exception as e:  # OOM / compile failure is a result, not a crash
        msg = str(e)
        row["status"] = "failed"
        row["error_type"] = type(e).__name__
        if "RESOURCE_EXHAUSTED" in msg or "OOM" in msg.upper():
            row["skip_reason"] = "out of device memory at this batch"
        else:
            row["error"] = msg[:200]
    return row


def _bench_error(**extra):
    """One parsable line, then a nonzero exit: a run without its headline
    number is a failed run."""
    print(json.dumps({"metric": "bench-error", "value": 0, "unit": "",
                      "vs_baseline": 0, "extra": extra}))
    sys.exit(1)


def main():
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logs_to_stderr
    enable_compile_cache()
    logs_to_stderr()     # stdout is the ONE JSON line

    try:
        n_chips = len(jax.devices())
        platform = jax.default_backend()
        peak_tflops()
    except RuntimeError as e:   # no backend, or a device with no recorded peak
        _bench_error(error_type=type(e).__name__, error=str(e)[:300])
    if platform != "tpu":
        _bench_error(error=f"bench.py measures the chip; JAX found "
                           f"platform {platform!r}")

    # micro-batch 8 with remat="dots" rides gas to a 128 global batch; the
    # sweep keeps the single-step batch 8/16/32 rows and a gpt2-medium row
    # beside it (the choice rests on pre-round sweeps, to be re-measured)
    headline_cfg = ("gpt2-small", 128, 1024, "bfloat16", 1, 3, 10, 16)
    sweep = [("gpt2-small", 8, 1024, "bfloat16", 1, 3, 10),
             ("gpt2-small", 16, 1024, "bfloat16", 1, 3, 10),
             ("gpt2-small", 16, 1024, "bfloat16", 1, 3, 10, 2),
             ("gpt2-small", 32, 1024, "bfloat16", 1, 3, 10),
             ("gpt2-small", 32, 1024, "bfloat16", 1, 3, 10, 4),
             ("gpt2-medium", 4, 1024, "bfloat16", 1, 3, 10)]

    try:
        ceiling = measure_matmul_ceiling()
    except Exception as e:  # a ceiling failure must not kill the bench
        ceiling = {"matmul_ceiling_error": f"{type(e).__name__}: {str(e)[:200]}"}
    headline = run_train_config(*headline_cfg)

    if headline.get("status") == "failed":
        # don't burn chip time on the sweep when the headline config failed
        _bench_error(**headline, **ceiling)
    rows = [run_train_config(*s) for s in sweep]

    mfu = headline["mfu"]
    extra = {
        "platform": platform,
        "chips": n_chips,
        **{k: headline[k] for k in ("params_m", "achieved_tflops_per_chip",
                                    "mfu", "step_ms", "final_loss")},
    }
    extra.update(ceiling)
    if ceiling.get("matmul_ceiling_mfu"):
        # How much of the chip's practical (model-width) matmul ceiling
        # the full training step achieves — framework efficiency.
        extra["mfu_vs_matmul_ceiling"] = round(
            mfu / ceiling["matmul_ceiling_mfu"], 3)
    if rows:
        extra["rows"] = rows

    result = {
        "metric": "gpt2s-zero1-train-tokens-per-sec-per-chip",
        "value": headline["tokens_per_sec_chip"],
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
        "extra": extra,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
