"""One linear layer's gated delta rule alone, on the chip.

    python benchmarks/gdn_scan_bench.py [--prefilling 0,2,4,8] [--frozen N]
                                        [--steps N] [--iters N] [--out FILE]

The scan of a WIDE step of Qwen3-Next-80B-A3B's linear layers as the frame
program runs it (``model_runner.linear_layer``), at the benchmark cell's
shapes: 16 rows x 128 positions, 16 key / 32 value heads of 128 x 128
float32 states, the states of all 6 linear layers of the cut in one stack,
activations bfloat16. ``--prefilling`` of the 16 rows hold a prompt's chunk
(half of them a full one, half 100 positions: a second block part dead),
``--frozen`` hold nothing, the others are decoding rows riding the step
with one live position. Two paths over the same operands:

- ``xla``: what every other backend runs and the chip ran before the
  kernel: the layer's states sliced out of the stack, ``_rule_by_rows``
  over ``layers.gdn_rule`` (the recurrence on all 16 rows, the chunked form
  two gathered rows a trip), the select that keeps a frozen row's state,
  the layer put back;
- ``kernel``: ``ops/pallas/gated_delta_rule.py``, one call a layer.

A timed call is ``--steps`` steps of the 6 layers (a scan whose xs are each
layer's own operands, arguments of the program: nothing is loop-invariant
and nothing a constant of the executable), the states carried and
donated, and the rule's output CONSUMED as the program consumes it: the
gated norm's float32 pass over it, written out in bfloat16 (a harness that
only sums ``out`` lets XLA skip writing it). Prints microseconds a
layer-step, the bytes the rule cannot avoid (a listed row's states once in
and once out, the live blocks of u, the output) over the chip's 819 GB/s,
and the kernel's largest gap to the XLA path. Needs the chip: interpret
mode times nothing (``--rehearse``: tiny shapes, interpreted, counts only).
"""

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

HBM_BYTES_PER_S = 819e9     # TPU v5e, Google Cloud documentation
LAYERS = 6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prefilling", default="0,2,4,8")
    ap.add_argument("--frozen", type=int, default=1)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import model_runner as M
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.pallas import gated_delta_rule as K

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit("gdn_scan_bench needs the chip (or --rehearse)")
    b, c, hk, hv, d = (16, 128, 16, 32, 128) if not args.rehearse \
        else (4, 128, 1, 2, 128)
    layers = LAYERS if not args.rehearse else 2
    act = jnp.bfloat16
    cfg = types.SimpleNamespace(
        linear_num_key_heads=hk, linear_key_head_dim=d,
        linear_num_value_heads=hv, linear_value_head_dim=d, norm_eps=1e-6)
    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    small = {"A_log": jnp.log(jnp.asarray(rng.uniform(1e-3, 16.0, (hv,)),
                                          jnp.float32)),
             "dt_bias": jnp.asarray(rng.uniform(-6.0, -2.0, (hv,)),
                                    jnp.float32)}
    xs = (normal(layers, b, c, 2 * hk * d + hv * d).astype(act),
          normal(layers, b, c, hv), normal(layers, b, c, hv),
          normal(layers, b, c, hv, d).astype(act),
          jnp.arange(layers, dtype=jnp.int32))

    def consume(out, z):
        """The gated norm's pass over the rule's output
        (``layers.gdn_output`` before its projection)."""
        o = out.astype(jnp.float32)
        y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.norm_eps)
        return (y * jax.nn.silu(z.astype(jnp.float32))).astype(act)

    def program(kernel, steps):
        def run(state, n_live, xs):
            at = jnp.arange(c)[None]
            positions = jnp.where(at < n_live[:, None], at, -1)
            pad = positions < 0
            moved = (n_live > 0)[:, None, None, None]

            def layer(carry, x):
                state, acc = carry
                u, b_in, a_in, z, li = x
                if kernel:
                    beta, g = L.gdn_gates(small, b_in, a_in, ~pad)
                    out, state = K.gdn_rule_rows(u, beta, g, state, li,
                                                 plan[4])
                else:
                    def rule(u, b_in, a_in, pad, state):
                        q, k, v = L.gdn_split(u, cfg)
                        beta, g = L.gdn_gates(small, b_in, a_in, ~pad)
                        return L.gdn_rule(q, k, v, beta, g, state)
                    state_l = jax.lax.dynamic_index_in_dim(state, li, 0,
                                                           False)
                    out, new = M._rule_by_rows(plan, rule, u, b_in, a_in,
                                               pad, state_l)
                    state = jax.lax.dynamic_update_index_in_dim(
                        state, jnp.where(moved, new, state_l), li, 0)
                return (state, acc + consume(out, z)), None

            acc = jnp.zeros((b, c, hv, d), act)
            for _ in range(steps):
                plan = M._row_plan(positions)
                (state, acc), _ = jax.lax.scan(layer, (state, acc), xs)
            return state, acc
        return jax.jit(run, donate_argnums=(0,))

    def mix(prefilling):
        n = [1] * b
        for row in range(prefilling):
            n[row * (b // max(prefilling, 1))] = c if row % 2 == 0 else 100
        frozen = [r for r in range(b - 1, -1, -1) if n[r] == 1][:args.frozen]
        for r in frozen:
            n[r] = 0
        return n

    steps, iters = (args.steps, args.iters) if on_chip else (1, 1)
    state0 = normal(layers, b, hv, d, d)
    programs = {"xla": program(False, steps), "kernel": program(True, steps)}
    results = []
    for prefilling in map(int, args.prefilling.split(",")):
        n = mix(prefilling)
        n_live = jnp.asarray(n, jnp.int32)
        blocks = sum(-(-x // L.GDN_CHUNK) for x in n if x > 1)
        riders, listed = n.count(1), sum(x > 0 for x in n)
        # a listed row's states in and out, the live blocks of u (a rider's
        # one tile of 16 positions), the float32 output of every row
        floor_bytes = (listed * 2 * hv * d * d * 4
                       + (blocks * L.GDN_CHUNK + riders * 16)
                       * (2 * hk * d + hv * d) * 2
                       + b * c * hv * d * 4)
        line = {"prefilling": prefilling, "riders": riders,
                "frozen": n.count(0), "live_blocks": blocks,
                "floor_us": round(floor_bytes / HBM_BYTES_PER_S * 1e6, 1)}
        ends = {}
        for name, fn in programs.items():
            state, acc = fn(state0 + 0.0, n_live, xs)   # compiles, once
            jax.block_until_ready((state, acc))
            ends[name] = (state, acc)
            if on_chip:
                state = state0 + 0.0
                jax.block_until_ready(state)
                t0 = time.perf_counter()
                for _ in range(iters):
                    state, acc = fn(state, n_live, xs)
                jax.block_until_ready((state, acc))
                line[f"{name}_us"] = round(
                    (time.perf_counter() - t0) / (iters * steps * layers)
                    * 1e6, 1)
        line["max_gap_state"] = float(jnp.abs(
            ends["xla"][0] - ends["kernel"][0]).max())
        # (behind a prefilling row's live positions the XLA path leaves
        # what it computed of the dead ones, the kernel zeros)
        live = (jnp.arange(c)[None] < n_live[:, None])[:, :, None, None]
        line["max_gap_consumed"] = float(jnp.abs(jnp.where(
            live, ends["xla"][1].astype(jnp.float32)
            - ends["kernel"][1].astype(jnp.float32), 0.0)).max())
        if on_chip:
            line["floor_share_kernel"] = round(
                line["floor_us"] / line["kernel_us"], 3)
        line["device"] = jax.devices()[0].device_kind
        print(json.dumps(line), flush=True)
        results.append(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
