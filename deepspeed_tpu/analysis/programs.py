"""The serving-program registry: trace the REAL frame loops on tiny
abstract shapes so Family A checks the programs production actually runs.

``build_serving_programs()`` constructs a tiny f32 engine (and, when the
process has >= 8 devices — the conftest/CLI force a virtual CPU mesh — a
tp=8 twin plus self-draft speculative variants) and returns one
``TracedProgram`` per serving entry point x shape bucket:

- ``frame_loop`` at width=chunk (prefill frames) and width=1 (decode),
- ``frame_loop_spec`` (speculative decode frames, gamma=2),
- the per-chunk ``run`` program of the host-step API and the page movers.

Tracing never compiles or executes — ``jit.trace`` stops at the jaxpr — so
the whole registry costs seconds on CPU. Donation indices come from the
live ``Traced.donate_argnums``, which is also what keeps
``ast_checks.DISPATCH_DONATIONS`` honest (the test suite cross-checks the
two).

The tp programs are traced with the default EXACT collectives: the
T3-style ring lowering (``tp_overlap_collectives``) is replica-invariant
by ring algebra, not by local dataflow, so the GL003 taint pass cannot
prove it — that lowering stays covered by the dynamic parity suites and
``tp_debug_replica_check`` instead of a static false positive.
"""

import functools
from typing import List, Optional

from .jaxpr_checks import TracedProgram

_GAMMA = 2


def _tiny_engine(tp: int = 1, quantized: bool = False, overlap: bool = False,
                 payload: str = "int8", kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None):
    import jax
    from ..models import build_model
    from ..inference.v2.engine_v2 import (InferenceEngineV2,
                                          RaggedInferenceEngineConfig)
    model = build_model("tiny", num_heads=8)
    params = model.init(jax.random.PRNGKey(0))
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=16, prefill_chunk_size=8, max_tokens_per_step=64,
        max_ragged_batch_size=4, frame_steps=2, dtype="float32", tp=tp,
        tp_quantized_collectives=quantized, tp_overlap_collectives=overlap,
        tp_collective_payload=payload, kv_dtype=kv_dtype,
        weight_dtype=weight_dtype)
    eng = InferenceEngineV2(model, cfg, params=params, max_seq_len=64)
    eng.attach_draft(model, params)    # self-draft: spec loops traceable
    return eng


def _slot_table(eng):
    import jax
    from ..inference.v2.ragged_manager import DeviceSlotTable
    return DeviceSlotTable(4, prompt_width=8, table_width=4,
                           rng=jax.random.PRNGKey(0), tp=eng.tp_ctx,
                           n_stats=eng.runner.n_stats)


def _frame_args(eng, slots):
    kv = eng.kv
    return (eng.params, slots.prompts, slots.prompt_lens, slots.limits,
            slots.eos_ids, slots.temps, slots.tables, slots.cached,
            slots.produced, slots.last_tok, slots.done, slots.poison,
            slots.nonfinite, slots.stats, slots.rng, kv.k, kv.v)


def _spec_args(eng, slots):
    kv, dkv = eng.kv, eng.draft_kv
    return (eng.params, eng.draft_params, slots.prompts, slots.prompt_lens,
            slots.limits, slots.eos_ids, slots.temps, slots.tables,
            slots.cached, slots.produced, slots.last_tok, slots.penult,
            slots.done, slots.poison, slots.nonfinite, slots.stats,
            slots.rng, kv.k, kv.v, dkv.k, dkv.v)


def _program(name, builder, args, statics) -> TracedProgram:
    """Wrap one jitted entry point. ``builder()`` must return a FRESH jit
    every call (fresh trace, no jit-cache hit) — check_retrace depends on
    it."""
    loop_trips = None
    if name.startswith("frame_loop"):
        # a frame's trip count is an operand (``n_steps``), handed in as
        # the serve loop hands it; the cost pass charges the frame's loop
        # at its capacity
        loop_trips = statics["steps"]
        statics = dict(statics, n_steps=loop_trips)

    def trace():
        return builder().trace(*args, **statics)
    prog = TracedProgram(name=name, trace=trace, retrace=trace,
                         loop_trips=loop_trips)
    try:
        import bisect
        import jax
        tr = prog.traced()
        # Traced.donate_argnums index the FLAT arg leaves (a param pytree
        # expands to one index per leaf); keep those for the aval-matching
        # check and ALSO map them back to user positional args for the
        # DISPATCH_DONATIONS cross-check in tests/test_static_analysis.py
        prog.donate_argnums = tuple(tr.donate_argnums)
        bounds, total = [], 0
        for a in args:
            total += len(jax.tree_util.tree_leaves(a))
            bounds.append(total)
        prog.donate_user_args = tuple(sorted(
            {bisect.bisect_right(bounds, i) for i in prog.donate_argnums}))
    except Exception:          # noqa: BLE001 — checks surface it as findings
        pass
    return prog


def _engine_programs(eng, tag: str) -> List[TracedProgram]:
    import jax.numpy as jnp
    runner, draft_runner = eng.runner, eng.draft_runner
    slots = _slot_table(eng)
    frame = functools.partial(_frame_args, eng, slots)
    spec = functools.partial(_spec_args, eng, slots)
    kv = eng.kv
    progs = [
        _program(f"frame_loop[w=8]{tag}", runner._build_frame_loop, frame(),
                 dict(width=8, steps=2, greedy=True)),
        _program(f"frame_loop[w=1]{tag}", runner._build_frame_loop, frame(),
                 dict(width=1, steps=2, greedy=True)),
        # nonfinite_policy="repair" compiles DISTINCT programs (the
        # pre-fault-carry rollback selects are static-gated) — a repair
        # engine runs the repair variant of EVERY frame program it
        # dispatches (wide prefill frames and the speculative loop
        # included), so each needs its own GL001-GL004 coverage
        _program(f"frame_loop[w=1,repair]{tag}", runner._build_frame_loop,
                 frame(), dict(width=1, steps=2, greedy=True, repair=True)),
        _program(f"frame_loop[w=8,repair]{tag}", runner._build_frame_loop,
                 frame(), dict(width=8, steps=2, greedy=True, repair=True)),
        _program(f"frame_loop_spec[w=1]{tag}",
                 lambda: runner._build_frame_loop_spec(draft_runner), spec(),
                 dict(width=1, steps=2, greedy=True, gamma=_GAMMA)),
        _program(f"frame_loop_spec[w=1,repair]{tag}",
                 lambda: runner._build_frame_loop_spec(draft_runner), spec(),
                 dict(width=1, steps=2, greedy=True, gamma=_GAMMA,
                      repair=True)),
        # a draft-carrying engine dispatches its WIDE (prefill) frames
        # through frame_loop_spec too — width=chunk is a distinct compiled
        # program (the draft ingests the same chunk), so it needs its own
        # coverage; the registry-completeness test pins this variant matrix
        _program(f"frame_loop_spec[w=8]{tag}",
                 lambda: runner._build_frame_loop_spec(draft_runner), spec(),
                 dict(width=8, steps=2, greedy=True, gamma=_GAMMA)),
        _program(f"frame_loop_spec[w=8,repair]{tag}",
                 lambda: runner._build_frame_loop_spec(draft_runner), spec(),
                 dict(width=8, steps=2, greedy=True, gamma=_GAMMA,
                      repair=True)),
    ]
    if eng.tp_ctx is None:
        # the host-step path never compiles under shard_map; trace it once
        tables = jnp.zeros((2, 4), jnp.int32)
        ids = jnp.zeros((2, 8), jnp.int32)
        pos = jnp.zeros((2, 8), jnp.int32)
        valid = jnp.full((2,), 8, jnp.int32)
        progs.append(_program(
            f"run[chunk=8]{tag}", lambda: runner._build(8),
            (eng.params, ids, pos, tables, valid, kv.k, kv.v), {}))
        # KV memory-hierarchy page movers (kv_cache.py / kv_hierarchy.py):
        # the frame-BOUNDARY device programs behind copy-on-write block
        # copies and host-RAM swap restores — donation- and transfer-
        # checked exactly like the frame loops (they run between frames,
        # so a host-sync primitive inside one would still be a boundary
        # stall worth catching; identical program under tp via GSPMD)
        from ..inference.v2.kv_cache import BlockedKVCache
        bids = jnp.zeros((2,), jnp.int32)
        # pool row width comes from kv.lanes: head_dim for float pools,
        # head_dim + packed scale lanes for int8 pools — the movers ship
        # whatever representation the pool holds
        pages = jnp.zeros((kv.num_layers, kv.kv_heads, 2, kv.block_size,
                           kv.lanes), kv.k.dtype)
        progs.append(_program(
            f"copy_blocks{tag}", BlockedKVCache._build_copy_blocks,
            (kv.k, kv.v, bids, bids), {}))
        progs.append(_program(
            f"scatter_pages{tag}", BlockedKVCache._build_scatter_pages,
            (kv.k, kv.v, bids, pages, pages), {}))
        progs.append(_program(
            f"gather_pages{tag}", BlockedKVCache._build_gather_pages,
            (kv.k, kv.v, bids), {}))
    return progs


def build_serving_programs(include_tp: Optional[bool] = None
                           ) -> List[TracedProgram]:
    """Trace every serving entry point; ``include_tp=None`` auto-detects
    (>= 8 devices). Returns the registry the lint CLI and the repo
    regression test both walk.

    Role coverage (ISSUE 12): the disaggregated prefill/decode fleet
    introduces NO new compiled programs — a ``role="prefill"`` engine
    dispatches the already-registered wide ``frame_loop[w=8]`` (and spec)
    variants, a decode replica the width-1 ones, and every tier transfer
    (handoff publish/restore, prefix-record restore) goes through the
    registered ``gather_pages``/``scatter_pages``/``copy_blocks`` movers
    at frame boundaries. Handoff/classification/commit logic is host-side
    policy, so GL001–GL004 and the Family C cost ledger cover the
    disaggregated fleet through this same registry — the completeness
    test cross-checks that no serve() dispatch site exists outside it."""
    import jax
    progs = _engine_programs(_tiny_engine(tp=1), "")
    # the quantized serving stack (kv_dtype/weight_dtype int8) compiles
    # DISTINCT programs — int8 pools with packed scale lanes, dequant at
    # the attention read, quantize at append, int8 weight dequant in every
    # matmul — so each gets its own GL001-GL004 + Family C coverage; the
    # page movers re-trace over int8 pools (the swap tier moves the
    # quantized representation, which is the 2-4x tier-I/O claim)
    progs += _engine_programs(
        _tiny_engine(kv_dtype="int8", weight_dtype="int8"), "[quant]")
    if include_tp is None:
        include_tp = len(jax.devices()) >= 8
    if include_tp:
        progs += _engine_programs(_tiny_engine(tp=8), "[tp=8]")
    return progs


#: base entry points re-traced under each non-default collective lowering
#: for the Family C payload contracts (GL202): the frame loops issue
#: the per-layer psums + the logit gather, which is everything the
#: quantized/overlap flags touch. Repair twins are skipped — the repair
#: selects change no collective, so their payloads are the non-repair ones.
_COST_VARIANT_BASES = ("frame_loop[w=8]", "frame_loop[w=1]",
                       "frame_loop_spec[w=1]", "frame_loop_spec[w=8]")


def _variant_programs(eng, tag: str, variant: str) -> List[TracedProgram]:
    progs = [p for p in _engine_programs(eng, tag)
             if p.name.replace(tag, "") in _COST_VARIANT_BASES]
    for p in progs:
        p.variant = variant
        p.counterpart = p.name.replace(tag, "[tp=8]")
    return progs


def build_cost_programs(include_tp: Optional[bool] = None
                        ) -> List[TracedProgram]:
    """The Family C (graft-cost) registry: every serving program the
    GL001-GL004 registry traces — same engines, same shapes, so the two
    families describe the same compiled artifacts — PLUS tp=8 twins traced
    under the non-default collective lowerings:

    - ``variant="quantized"`` (``tp_quantized_collectives``): the EQuARX
      int8 programs GL202 payload-compares against their exact
      counterparts;
    - ``variant="overlap"`` (``tp_overlap_collectives``): the T3 ring
      programs whose total wire bytes must EQUAL the exact psum's
      (2(N-1) ppermute chunks x chunk bytes = the ring all-reduce cost).

    The variant twins get GL001/GL002 coverage from the cost gate but NOT
    GL003 (the ring is replica-invariant by ring algebra, which the taint
    pass cannot prove — same reason the main registry traces exact
    collectives only) and not GL004 (one trace each; the exact twins
    already pin retrace determinism of the shared entry points)."""
    import jax
    if include_tp is None:
        include_tp = len(jax.devices()) >= 8
    progs = build_serving_programs(include_tp=include_tp)
    if include_tp:
        progs += _variant_programs(_tiny_engine(tp=8, quantized=True),
                                   "[tp=8,quant]", "quantized")
        # fp8 (e4m3) wire variant: same one-byte payload contract as int8,
        # proven by the same GL202 comparison against the exact twins
        # (CostReport.int8_payload counts float8_* collective operands too)
        progs += _variant_programs(
            _tiny_engine(tp=8, quantized=True, payload="fp8"),
            "[tp=8,fp8]", "quantized")
        progs += _variant_programs(_tiny_engine(tp=8, overlap=True),
                                   "[tp=8,ring]", "overlap")
    return progs
