#!/usr/bin/env python
"""MoE dispatch-path throughput: capacity einsum vs dropless grouped.

The round-4 review asked for a recorded throughput row next to the
dropless-under-EP equivalence tests (``tests/test_models.py::
test_moe_grouped_ep_*``). On this 1-chip platform the expert axis cannot be
really sharded, so the measured rows compare the two dispatch paths at
ep=1 (where "grouped" is the sort+ragged_dot megablox path the EP ring
reuses per shard); the EP ring itself is validated for equivalence on the
virtual 8-device mesh and its throughput character is the local ragged_dot
plus two all-to-alls over ICI.

Prints one JSON line; run with the repo root on sys.path.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_path(moe_impl, tokens, hidden, ffn, experts, k, iters=20):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.utils import groups

    groups.reset_mesh()
    cfg = TransformerConfig(
        vocab_size=256, hidden_size=hidden, num_layers=1, num_heads=8,
        intermediate_size=ffn, moe_intermediate_size=ffn, num_experts=experts,
        num_experts_per_tok=k, moe_impl=moe_impl, moe_capacity_factor=1.25,
        max_seq_len=4096, dtype="bfloat16")
    params, _ = L.init_moe_mlp(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, tokens, hidden)),
                    jnp.bfloat16)

    @jax.jit
    def run(params, x):
        def body(c, _):
            y, aux = L.apply_moe_mlp(params, c, cfg)
            return (y * 0.5 + c * 0.5).astype(c.dtype), aux
        y, _ = jax.lax.scan(body, x, None, length=iters)
        return jnp.sum(y.astype(jnp.float32))

    jax.device_get(run(params, x))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(run(params, x))
        best = min(best, time.perf_counter() - t0)
    return tokens * iters / best


def bench_ep_virtual(tokens, hidden, ffn, experts, k, iters=5):
    """EP-ring comm-pattern row on the virtual 8-device CPU mesh (r4 review:
    the sharded-EP variant had equivalence tests only, no recorded perf
    character). CPU wall time is NOT a TPU number — the row records the
    RELATIVE cost of the a2a ring vs the local grouped path on the same
    mesh, i.e. the dispatch/comm overhead structure."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.utils import groups

    out = {}
    for ep in (1, 4):
        groups.reset_mesh()
        groups.set_mesh(groups.build_mesh(expert=ep, data=8 // ep))
        cfg = TransformerConfig(
            vocab_size=256, hidden_size=hidden, num_layers=1, num_heads=8,
            intermediate_size=ffn, moe_intermediate_size=ffn,
            num_experts=experts, num_experts_per_tok=k, moe_impl="grouped",
            max_seq_len=4096, dtype="float32")
        params, _ = L.init_moe_mlp(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, tokens // 8, hidden)), jnp.float32)

        @jax.jit
        def run(params, x):
            def body(c, _):
                y, aux = L.apply_moe_mlp(params, c, cfg)
                return (y * 0.5 + c * 0.5).astype(c.dtype), aux
            y, _ = jax.lax.scan(body, x, None, length=iters)
            return jnp.sum(y.astype(jnp.float32))

        jax.device_get(run(params, x))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            jax.device_get(run(params, x))
            best = min(best, time.perf_counter() - t0)
        out[f"ep{ep}_tok_per_sec"] = round(tokens * iters / best, 1)
    out["ep_ring_relative"] = round(out["ep4_tok_per_sec"] /
                                    out["ep1_tok_per_sec"], 3)
    return out


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--ep-virtual", action="store_true",
                    help="run the EP-ring row on a forced CPU mesh")
    args = ap.parse_args()
    if args.ep_virtual:
        print(json.dumps(bench_ep_virtual(tokens=2048, hidden=256, ffn=512,
                                          experts=8, k=2)))
        return

    import jax
    platform = jax.default_backend()
    if platform == "tpu":
        shape = dict(tokens=4096, hidden=1024, ffn=2816, experts=8, k=2)
    else:
        shape = dict(tokens=256, hidden=64, ffn=128, experts=4, k=2,
                     iters=3)
    rows = {}
    for impl in ("einsum", "grouped"):
        rows[impl] = round(bench_path(impl, **shape), 1)
    # EP ring on the virtual mesh: a child PINNED TO THE CPU (the backend
    # must be forced before jax initializes) — it never asks for the chip
    # this process holds, so one process still owns each chip
    import subprocess
    ep_row = None
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=8").strip())
        res = subprocess.run([sys.executable, os.path.abspath(__file__),
                              "--ep-virtual"], env=env, capture_output=True,
                             text=True, timeout=900)
        for ln in reversed(res.stdout.splitlines()):
            if ln.startswith("{"):
                ep_row = json.loads(ln)
                break
        if ep_row is None:
            # a null row is indistinguishable from "not run": record the
            # child's failure instead
            ep_row = {"error": f"rc={res.returncode}: "
                               f"{res.stderr.strip()[-200:]}"}
    except Exception as e:
        ep_row = {"error": f"{type(e).__name__}: {str(e)[:120]}"}
    out = {
        "metric": "moe_dispatch_tokens_per_sec", "platform": platform,
        "shape": shape, "einsum_tok_per_sec": rows["einsum"],
        "grouped_tok_per_sec": rows["grouped"],
        "grouped_speedup": round(rows["grouped"] / rows["einsum"], 3),
        "ep_virtual_mesh": ep_row,
        "note": "dropless grouped (sort + ragged_dot) vs capacity einsum "
                "dispatch at ep=1 on the real chip; ep_virtual_mesh records "
                "the EP a2a-ring's relative cost on the virtual 8-device "
                "CPU mesh (comm-pattern sanity — 1 real chip cannot shard "
                "the expert axis)",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
