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

``--grouped-sweep`` times the routed experts' grouped product ALONE, on the
chip: ``jax.lax.ragged_dot`` (over the stack as L x X groups, what every cell
ran before the kernel) against ``ops/pallas/grouped_gemm.py``'s kernel, us a
product, at the rows the cells' rungs give (128 / 1,152 / 2,176 / 4,224 /
8,320 / 16,384 selection rows) and the three served models' (K, N, groups),
over even groups and groups drawn at the cell's ``expert_load_max_over_mean``;
one JSON line a row, the bytes and FLOPs floors beside the times.

``--bookkeeping-sweep`` times what a routed block does AROUND its products,
on the chip: ``apply_moe_grouped``'s route, dispatch and combine with the
products stubbed out, us a layer, at the three wide cells' shapes (tokens x k
x hidden = 1,040 x 12 x 6,144; 1,040 x 10 x 2,048; 528 and 1,040 x 8 x 2,304)
and the narrow steps' (16 x 8 and 128 x 8 x 2,048, 32 x 4 and 16 x 4 x 2,048)
over the share of the router's experts the chip holds (2 / 25 / 100%) and the
live share of the positions: the unbounded form (every selection row moved
once each way: a gather by sorted row, a gather by token and a sum of k rows
since PR 52) beside the bounded one at each ``--blocks`` rows a trip that
leaves more than two blocks, then what the rule ships at that shape
(``layers._move_block``) and the same with the gather's buffer zeroed first
instead of unwritten; one JSON line a row, the rows in groups and the
buffer's MB beside the times.
"""

import functools
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


# (hidden, expert width, experts held, the cell's expert_load_max_over_mean,
# the share of a step's selection rows that land on a held expert, the
# layers the cell stacks): the benchmark's three routed configurations
# (ledger, PR 34)
SWEEP_MODELS = {
    "olmoe-1b-7b": (2048, 1024, 64, 5.0, 1.0, 8),
    "mellum2-12b-a2.5b": (2304, 896, 64, 4.0, 1.0, 8),
    # top 12 of 768 outputs, 16 of them held: 0.25 rows a token of 12
    "longcat-flash-omni": (6144, 2048, 16, 1.76, 0.25 / 12, 4),
}
SWEEP_ROWS = (128, 1152, 2176, 4224, 8320, 16384)
HBM_BYTES_PER_S, BF16_FLOPS_PER_S = 819e9, 197e12   # TPU v5e, Google Cloud documentation


def draw_groups(rng, rows, groups, max_over_mean):
    """Group sizes that sum to ``rows``: even, or (``max_over_mean`` > 1)
    shares that fall off geometrically so that the largest is that many
    times the mean, in a random order."""
    if max_over_mean <= 1:
        share = np.ones(groups)
    else:
        lo, hi = 0.0, 50.0
        for _ in range(60):
            a = (lo + hi) / 2
            share = np.exp(-a * np.arange(groups) / groups)
            lo, hi = (a, hi) if share[0] * groups / share.sum() \
                < max_over_mean else (lo, a)
        share = rng.permutation(share)
    sizes = np.floor(share / share.sum() * rows).astype(np.int64)
    sizes[np.argsort(-share)[:rows - sizes.sum()]] += 1
    return sizes.astype(np.int32)


def grouped_sweep(args):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import grouped_gemm as G
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"--grouped-sweep needs the chip, found {dev.platform}")

    def ragged(tokens, stack, sizes, layer):
        n_layers, n_exp = stack.shape[:2]
        padded = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * n_exp,), sizes.dtype), sizes,
            (layer * n_exp,))
        return jax.lax.ragged_dot(
            tokens, stack.reshape((n_layers * n_exp,) + stack.shape[2:]),
            padded)

    def timed(product, tokens, stack, sizes):
        """us a product: ``iters`` of them in one program, each on the next
        layer of the stack, each result folded into the carry."""
        @jax.jit
        def loop(tokens, stack, sizes):
            def body(i, acc):
                out = product(tokens, stack, sizes, i % stack.shape[0])
                return acc + out[:8].astype(jnp.float32)
            return jax.lax.fori_loop(
                0, args.iters, body,
                jnp.zeros((8, stack.shape[-1]), jnp.float32))
        loop(tokens, stack, sizes).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            loop(tokens, stack, sizes).block_until_ready()
            best = min(best, time.perf_counter() - t)
        return best / args.iters * 1e6

    rng = np.random.default_rng(args.seed)
    # "256": that row tile with the rule's tk, tn; "128x6144x1024": all three
    tilings = [None] + [tuple(map(int, t.split("x")))
                        for t in args.tilings.split(",") if t]
    for name in args.models.split(",") if args.models else SWEEP_MODELS:
        hidden, width, groups, skew, held, layers = SWEEP_MODELS[name]
        for k, n in ((hidden, width), (width, hidden)):
            # made on the device: a cell's stack is 1-2 GB
            stack = jax.random.normal(
                jax.random.PRNGKey(args.seed), (layers, groups, k, n),
                jnp.bfloat16) * k ** -0.5
            for rows in map(int, args.rows.split(",")):
                # LongCat's 12 selections a token where the others have 8
                rows = rows * 3 // 2 if held < 1 else rows
                tokens = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
                for draw, ratio in (("even", 1.0), ("skewed", skew)):
                    sizes_np = draw_groups(rng, max(1, round(rows * held)),
                                           groups, ratio)
                    sizes = jnp.asarray(sizes_np)
                    in_groups = int(sizes_np.sum())
                    row = {
                        "model": name, "k": k, "n": n, "groups": groups,
                        "rows": rows, "rows_in_groups": in_groups,
                        "draw": draw,
                        "max_over_mean": round(
                            float(sizes_np.max() * groups / in_groups), 2),
                        "bytes_floor_us": round(
                            int((sizes_np > 0).sum()) * k * n * 2
                            / HBM_BYTES_PER_S * 1e6, 1),
                        "flops_floor_us": round(
                            in_groups * k * n * 2 / BF16_FLOPS_PER_S * 1e6, 1),
                        "ragged_dot_us": round(
                            timed(ragged, tokens, stack, sizes), 1),
                    }
                    want = np.asarray(ragged(tokens, stack, sizes, 1)
                                      [:in_groups], np.float32)
                    rule = G.tiles(rows, k, n)
                    for tiling in tilings:
                        if tiling is not None:
                            tiling = tiling + rule[len(tiling):]
                            if k % tiling[1] or n % tiling[2]:
                                continue
                        product = functools.partial(G.grouped_mm,
                                                    tiling=tiling)
                        label = "x".join(map(str, tiling or rule))
                        if tiling is None:
                            row["tiles"] = label
                        tag = "" if tiling is None else f"_{label}"
                        got = np.asarray(product(tokens, stack, sizes, 1)
                                         [:in_groups], np.float32)
                        row[f"kernel_us{tag}"] = round(
                            timed(product, tokens, stack, sizes), 1)
                        row[f"gap{tag}"] = float(np.abs(got - want).max())
                    row["device"] = dev.device_kind
                    print(json.dumps(row), flush=True)


# (tokens a step holds, selections a token, hidden size, the router's
# outputs): the rungs of the three cells whose routed layers run wide (ledger,
# PR 44; Mellum2's two), then the narrow steps of the cells that decode (16
# slots; SDAR's 32 rows of 4 positions; GLM's verify of 2 positions a slot)
BOOKKEEPING_SHAPES = {
    "longcat-flash-omni": (1040, 12, 6144, 768),
    "qwen3-next-80b-a3b": (1040, 10, 2048, 512),
    "mellum2-12b-a2.5b": (528, 8, 2304, 64),
    "mellum2-12b-a2.5b.t1040": (1040, 8, 2304, 64),
    "olmoe-1b-7b.narrow": (16, 8, 2048, 64),
    "sdar-30b-a3b.narrow": (128, 8, 2048, 128),
    "glm-4.7-flash.verify2": (32, 4, 2048, 64),
    "lfm2-24b-a2b.narrow": (16, 4, 2048, 64),
}
BOOKKEEPING_SHARES = (0.02, 0.25, 1.0)


def bookkeeping_sweep(args):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.ops.pallas import grouped_gemm as G
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.anywhere:
        sys.exit(f"--bookkeeping-sweep needs the chip, found {dev.platform}")
    # no products: the rows come back as they went in, through a barrier so
    # that the sorted buffer is made as a kernel's operand is
    G.moe_expert_ffn = lambda tokens, *_: jax.lax.optimization_barrier(tokens)
    rule, unwritten = L._move_block, G.unwritten

    def timed(cfg, params, x, live, block, zeroed=False):
        """(us a layer, rows in groups, rows moved) of ``iters`` routed
        blocks in one program, each over the last one's output, at ``block``
        rows a trip (None: unbounded; "rule": what ships). ``zeroed``: the
        gather's buffer starts as zeros, not unwritten."""
        L._move_block = rule if block == "rule" else lambda cfg, rows: block
        G.unwritten = (lambda rows, like: jnp.zeros(
            (rows, like.shape[1]), like.dtype)) if zeroed else unwritten
        rows = x.shape[1] * cfg.num_experts_per_tok

        @jax.jit
        def loop(params, x, live):
            def body(_, carry):
                c, held, moved = carry
                out, _, sizes, *_ = L.apply_moe_grouped(params, c, cfg,
                                                        live=live)
                c = (0.5 * c + 0.5 * out).astype(c.dtype)
                return (c, held + jnp.sum(sizes),
                        moved + L.moe_rows_moved(cfg, sizes, rows))
            zero = jnp.zeros((), jnp.int32)
            return jax.lax.fori_loop(0, args.iters, body, (x, zero, zero))
        try:
            jax.block_until_ready(loop(params, x, live))
            best = float("inf")
            for _ in range(3):
                t = time.perf_counter()
                _, held, moved = jax.block_until_ready(loop(params, x, live))
                best = min(best, time.perf_counter() - t)
        finally:
            L._move_block, G.unwritten = rule, unwritten
        return (best / args.iters * 1e6, int(held) / args.iters,
                int(moved) / args.iters)

    rng = np.random.default_rng(args.seed)
    blocks = [int(b) for b in args.blocks.split(",") if b]
    for name in args.models.split(",") if args.models else BOOKKEEPING_SHAPES:
        tokens, k, hidden, width = BOOKKEEPING_SHAPES[name]
        if args.anywhere and dev.platform != "tpu":
            tokens, hidden = max(16, tokens // 8), hidden // 16   # a rehearsal
        for share in BOOKKEEPING_SHARES:
            held = max(1, round(width * share))
            cfg = TransformerConfig(
                vocab_size=256, hidden_size=hidden, num_layers=1, num_heads=8,
                intermediate_size=128, moe_intermediate_size=128,
                num_experts=held, num_experts_per_tok=k, moe_impl="grouped",
                max_seq_len=4096, dtype="bfloat16",
                **({} if held == width else {"moe_router_experts": width}))
            params = {"router": jnp.asarray(
                          rng.normal(size=(hidden, width)), jnp.float32),
                      **{n: jnp.zeros((1,), jnp.bfloat16)
                         for n in L.EXPERT_MATRICES}}
            x = jnp.asarray(rng.normal(size=(1, tokens, hidden)), jnp.bfloat16)
            for live_share in map(float, args.live.split(",")):
                live = jnp.asarray(
                    rng.random((1, tokens)) < live_share)
                row = {"model": name, "tokens": tokens, "k": k,
                       "hidden": hidden, "router_width": width, "held": held,
                       "live_share": live_share,
                       "buffer_mb": round(tokens * k * hidden * 2 / 1e6, 1)}
                us, in_groups, _ = timed(cfg, params, x, live, None)
                row.update(rows=tokens * k, rows_in_groups=round(in_groups),
                           unbounded_us=round(us, 1))
                for block in blocks:
                    if tokens * k <= 2 * block:     # as the rule: no bound
                        continue
                    us, _, moved = timed(cfg, params, x, live, block)
                    row[f"bounded_us_b{block}"] = round(us, 1)
                    row[f"rows_moved_b{block}"] = round(moved)
                # what ships: the rule's block, or the unbounded lines
                block = rule(cfg, tokens * k)
                us, _, moved = timed(cfg, params, x, live, "rule")
                row.update(rule_block=block, rule_us=round(us, 1),
                           rule_rows_moved=round(moved))
                if block:
                    row["rule_zeroed_us"] = round(timed(
                        cfg, params, x, live, block, zeroed=True)[0], 1)
                row["device"] = dev.device_kind
                print(json.dumps(row), flush=True)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--ep-virtual", action="store_true",
                    help="run the EP-ring row on a forced CPU mesh")
    ap.add_argument("--grouped-sweep", action="store_true",
                    help="the grouped product alone: ragged_dot against the "
                         "kernel, us a product (needs the chip)")
    ap.add_argument("--models", default="",
                    help="a sweep's models (default: all of its own)")
    ap.add_argument("--rows", default=",".join(map(str, SWEEP_ROWS)))
    ap.add_argument("--tilings", default="",
                    help="tilings to time beside the rule's: TM or TMxTKxTN, "
                         "comma-separated")
    ap.add_argument("--bookkeeping-sweep", action="store_true",
                    help="a routed block's route, dispatch and combine "
                         "without its products: every row moved against "
                         "the bounded form, us a layer (needs the chip)")
    ap.add_argument("--blocks", default="128,256,512,1024",
                    help="--bookkeeping-sweep: rows a trip to time")
    ap.add_argument("--live", default="0.8",
                    help="--bookkeeping-sweep: live shares of the positions")
    ap.add_argument("--anywhere", action="store_true",
                    help="--bookkeeping-sweep at an eighth of the size on "
                         "whatever device there is: a rehearsal, no timing")
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.grouped_sweep:
        return grouped_sweep(args)
    if args.bookkeeping_sweep:
        return bookkeeping_sweep(args)
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
