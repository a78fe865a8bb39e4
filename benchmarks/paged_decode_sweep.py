"""The paged-attention kernels alone, on the chip.

    python benchmarks/paged_decode_sweep.py [--root DIR] [--label NAME]
                                            [--chunk C] [--configs A,B]
                                            [--live N,M] [--contexts N,M]
                                            [--riders N,M] [--tiling H,P,T]

One step's attention (``--chunk`` 1: the narrow kernel of a decode step;
128: the wide one of a prefill chunk) at the serve configurations' shapes
(mistral-7b: 32 query / 8 KV heads, window 4096, table width 64;
OLMoE-1B-7B: 16 / 16, no window, table width 32; Mellum2-12B-A2.5B: 32 / 4,
its two global layers over a table of 256 and its six windowed ones over a
ring of 10 pages behind a window of 1,024; LongCat-Flash-Omni: 64 query
heads on the ONE latent head of a pool of 640-lane rows, values the first
512 lanes, scale 1 / sqrt(192), table width 128; LFM2-24B-A2B: 32 / 8 heads
of 64 lanes as 4 rows of two heads, table width 32, beside the same bytes
as 4 heads of 128 lanes; 16 slots, pages of 128,
head_dim 128, bf16) over live slots 1 / 3 / 16 and contexts 256 / 1,024 /
4,096 / 7,168 (Mellum2: to 30,000): microseconds a layer, the pages a layer had to move
(``live x ceil((cs - lo) / page)``, K and V of every KV head), their
bytes over the time as a share of the chip's 819 GB/s, and the largest gap
of a live row's output to a float32 gather reference (at either width: a
chunk's rows attend the pool below the chunk and the chunk's own keys
causally). ``live = 0`` is what sixteen frozen slots cost. ``--riders N``
(with ``--chunk`` > 1): N of the live slots are DECODING rows riding the
wide step, ONE live position at the context, the other live slots a full
chunk there. ``--tiling H,P,T`` times another tiling than ``_tiling``'s own
(kv heads a step, pages a group, rows a row tile) before the rule is
changed. ``--root`` imports ``deepspeed_tpu`` from another
checkout (a ``git archive`` copy of the parent), so one script times both
sides. Needs the chip: the kernel's interpret mode times nothing.
"""

import argparse
import functools
import json
import os
import sys
import time

HBM_BYTES_PER_S = 819e9     # TPU v5e, Google Cloud documentation
SLOTS, PAGE, D, LAYERS, POOL_PAGES = 16, 128, 128, 4, 416
CONFIGS = {
    # name: (query heads, kv heads, window, table width, ring, pool pages)
    "mistral-7b": (32, 8, 4096, 64, None, POOL_PAGES),
    "olmoe-1b-7b": (16, 16, 0, 32, None, POOL_PAGES),
    "mellum2-full": (32, 4, 0, 256, None, 4097),
    "mellum2-window": (32, 4, 1024, 256, 10, 161),
    "longcat-latent": (64, 1, 0, 128, None, 2049),
    "lfm2-pairs": (32, 4, 0, 32, None, 513),
    "lfm2-x4": (32, 4, 0, 32, None, 513),
}
# heads of 64 lanes, two a 128-lane row of a page (``kv_cache.heads_per_row``;
# Mosaic refuses a page copy 64 lanes wide): the kernel sees 4 heads of 128
# lanes and queries whose other 64 lanes are zeros, as ``_forward`` hands
# them. "lfm2-x4" is the same bytes under dense queries, what 4 KV heads of
# 128 lanes cost
PAIRED = {"lfm2-pairs": 64}
# the latent format: (a row's lanes, its leading lanes that are the value,
# the scores' scale); one pool, no second
LATENT = {"longcat-latent": (640, 512, 192 ** -0.5)}
LIVE = (0, 1, 3, 16)
CONTEXTS = (256, 1024, 4096, 7168, 16384, 30000)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--label", default="")
    ap.add_argument("--chunk", type=int, default=1)
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--live", default=",".join(map(str, LIVE)))
    ap.add_argument("--contexts", default=",".join(map(str, CONTEXTS)))
    ap.add_argument("--riders", default="0")
    ap.add_argument("--tiling", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import numpy as np
    import jax
    import jax.numpy as jnp
    import inspect
    from deepspeed_tpu.ops.pallas import paged_attention
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    if args.tiling:
        forced = tuple(map(int, args.tiling.split(",")))
        paged_attention._tiling = lambda rows, kvh, mb, *_: (
            forced[0], min(forced[1], mb), *forced[2:])
    has_ring = "ring" in inspect.signature(paged_ragged_attention).parameters
    chunk = args.chunk

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"paged_decode_sweep needs the chip, found {dev.platform}")

    @functools.partial(jax.jit, static_argnames=("dv", "scale"))
    def reference(q, kpool, vpool, table, pos, ck, cv, window, dv=None,
                  scale=None):
        """ONE slot of layer 0 by gather, in float32: q (C, H, D), table
        (MB,), pos (C,), ck / cv (C, KVH, D) -> (C, H, D). The latent
        format (``dv``): a value is its key's first ``dv`` lanes."""
        h, kvh = q.shape[1], kpool.shape[1]
        D = q.shape[-1]
        f32 = jnp.float32
        k = kpool[0][:, table].reshape(kvh, -1, D).astype(f32)
        k = jnp.concatenate([k, ck.astype(f32).transpose(1, 0, 2)], axis=1)
        held = k.shape[1] - ck.shape[0]
        if dv:
            v = k[..., :dv]
        else:
            v = vpool[0][:, table].reshape(kvh, -1, D).astype(f32)
            v = jnp.concatenate([v, cv.astype(f32).transpose(1, 0, 2)],
                                axis=1)
        # the pool is good below the chunk's first position; the chunk's
        # own keys sit at the chunk's positions
        key = jnp.concatenate([jnp.arange(held), pos])[None, :]
        live = key <= pos[:, None]
        live &= jnp.concatenate([jnp.arange(held) < pos[0], pos >= 0])[None, :]
        live &= (key > pos[:, None] - window) | (window <= 0)
        qg = q.astype(f32).reshape(-1, kvh, h // kvh, D)
        s = jnp.einsum("chgd,hkd->hgck", qg, k, precision="highest") * (
            scale or D ** -0.5)
        s = jnp.where(live[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgck,hkd->chgd", p, v,
                          precision="highest").reshape(-1, h, v.shape[-1])

    def bench(pool, h, window, mb, live, ctx, ring, dv=None, scale=None,
              riders=0, paired=0):
        kvh, pool_pages, D = pool.shape[1], pool.shape[2], pool.shape[-1]
        vpool = None if dv else pool
        rng = np.random.default_rng(live * 10007 + ctx)
        q = rng.standard_normal((SLOTS, chunk, h, D)) * 0.1
        if paired:
            # query head g of a row's 2 x (h / 2 kvh): its own head's lanes
            own = (np.arange(h) // (h // (2 * kvh))) % 2
            q = q * (np.arange(D)[None, :] // paired == own[:, None])
        q = jnp.asarray(q, jnp.bfloat16)
        ck = jnp.asarray(rng.standard_normal((SLOTS, chunk, kvh, D)),
                         jnp.bfloat16)
        # a ring holds min(pages, ring) pages whatever the context
        pages = min(-(-(ctx + chunk) // PAGE), ring or mb)
        tables = np.zeros((SLOTS, ring or mb), np.int32)
        pos = np.full((SLOTS, chunk), -1, np.int32)
        for s in range(live):
            # a slot's pages lie scattered through the pool, as in a server
            tables[s, :pages] = 1 + rng.permutation(pool_pages - 1)[:pages]
            rows = 1 if s < riders else chunk
            pos[s, :rows] = ctx + np.arange(rows)
        kw = {"ring": ring} if ring else {}
        if dv:
            kw.update(value_lanes=dv, scale=scale)

        @jax.jit
        def step(q, kpool, vpool, tables, pos, ck):
            def layer(i, x):
                out = paged_ragged_attention(
                    x, kpool, vpool, tables, pos, ck, None if dv else ck,
                    layer=i % LAYERS, window=window, **kw)
                # a latent step's output is its value lanes wide
                return x.at[..., :out.shape[-1]].set(out) if dv else out
            return jax.lax.fori_loop(0, args.iters * LAYERS, layer, q)

        a = (q, pool, vpool, jnp.asarray(tables), jnp.asarray(pos), ck)
        step(*a).block_until_ready()
        gap = None
        if live:
            # jitted: the pool is ONE argument however many operands of the
            # kernel it becomes
            one = jax.jit(lambda *a: paged_ragged_attention(
                *a, None if dv else a[-1], layer=0, window=window,
                **kw))(*a)
            ref_tables = a[3]
            if ring:
                # the ring as the table it stands for: page p in slot p mod R
                ref_tables = ref_tables[:, jnp.arange(mb) % ring]
            # over the live rows: a dead row's output is nobody's
            gap = max(float(jnp.max(jnp.abs(
                one[s].astype(jnp.float32) - reference(
                    q[s], pool, pool, ref_tables[s], a[4][s], ck[s], ck[s],
                    window, dv=dv, scale=scale))[pos[s] >= 0]))
                for s in range(live))
        times = []
        for _ in range(5):
            t = time.perf_counter()
            step(*a).block_until_ready()
            times.append(time.perf_counter() - t)
        us = min(times) / (args.iters * LAYERS) * 1e6
        lo = max(ctx - window + 1, 0) if window else 0
        moved = live * (-(-ctx // PAGE) - lo // PAGE)
        nbytes = moved * (1 if dv else 2) * kvh * PAGE * D * 2
        return {"us_a_layer": round(us, 2), "pages_moved": moved,
                "max_gap_to_gather": gap,
                "share_of_hbm_peak_pct": round(
                    100 * nbytes / (us * 1e-6) / HBM_BYTES_PER_S, 2)}

    for name in args.configs.split(","):
        h, kvh, window, mb, ring, pool_pages = CONFIGS[name]
        if ring and not has_ring:
            continue                  # a checkout older than the rings
        lanes, dv, scale = LATENT.get(name, (D, None, None))
        if dv and "value_lanes" not in inspect.signature(
                paged_ragged_attention).parameters:
            continue                  # a checkout older than the format
        pool = jax.random.normal(jax.random.PRNGKey(kvh),
                                 (LAYERS, kvh, pool_pages, PAGE, lanes),
                                 jnp.bfloat16)
        for live in map(int, args.live.split(",")):
            for ctx in map(int, args.contexts.split(",")) if live else (0,):
                if ctx > mb * PAGE:
                    continue
                ctx = min(ctx, mb * PAGE - chunk)  # the chunk needs its slots
                for riders in map(int, args.riders.split(",")):
                    if riders > live or riders and (chunk == 1 or not live):
                        continue
                    row = {"label": args.label, "config": name,
                           "chunk": chunk, "live": live, "riders": riders,
                           "context": ctx, "device": dev.device_kind,
                           **bench(pool, h, window, mb, live, ctx, ring, dv,
                                   scale, riders, PAIRED.get(name, 0))}
                    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
