"""Tensor-parallel frame serving (shard_map on the 8-device mesh).

`serve()` with ``tp=8`` compiles the frame loops under ``jax.shard_map``
over a 1-D tp mesh: weights column/row-sharded, paged KV pools (target AND
draft) sharded head-wise, and the whole slot-table carry replicated so every
frame-boundary policy (admission, quarantine, deadlines, snapshots) stays
single-host. The contract these tests pin, on the same virtual 8-device CPU
mesh the MULTICHIP dryruns use:

- greedy outputs token-identical to ``tp=1`` — plain, speculative, and
  mid-stream-arrival serving alike;
- the zero-in-frame-device-to-host transfer guard still holds;
- the opt-in collective lowerings (T3-style overlap ring, EQuARX-style int8
  quantized exchanges) meet their parity contracts;
- fault tolerance is topology-blind: poison-row quarantine keeps survivor
  parity on a sharded engine, and a crash snapshot taken at one TP degree
  resumes token-identically at another (the carry/snapshot plumbing is
  engine-shape-agnostic — the prerequisite for the multi-engine router).

Engines are f32 and module-scoped where possible: shard_map programs over 8
virtual devices compile slowly enough that every fresh engine costs seconds.
"""

import numpy as np
import jax
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.faults import (FaultInjector, FaultSpec,
                                               FrameDispatchError)
from deepspeed_tpu.models import build_model

pytestmark = pytest.mark.multichip

MAX_NEW = 8


@pytest.fixture(scope="module")
def tp_model_params():
    """tiny with 8 heads: every TP-sharded axis (heads=kv_heads=8, ffn=128,
    vocab=256) divides the 8-way mesh."""
    model = build_model("tiny", num_heads=8)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **over):
    kw = dict(kv_block_size=16, prefill_chunk_size=16, max_tokens_per_step=256,
              dtype="float32", max_ragged_batch_size=8, frame_steps=4,
              frame_retry_backoff_s=0.0)
    kw.update(over)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                             params=params, max_seq_len=128)


PROMPTS = {u: np.random.default_rng(5).integers(0, 200, (200,))
           .astype(np.int32)[o:o + n]
           for u, (o, n) in enumerate(((0, 7), (10, 24), (40, 33), (80, 5)))}
SCHEDULE = {0: [0, 1], 2: [2], 3: [3]}


def _mid_stream_arrivals():
    for k in range(max(SCHEDULE) + 2):
        yield [(u, PROMPTS[u]) for u in SCHEDULE.get(k, [])]


@pytest.fixture(scope="module")
def greedy_base(tp_model_params):
    """tp=1 greedy serve() outputs — THE reference every sharded variant
    must reproduce token-for-token."""
    model, params = tp_model_params
    return dict(_engine(model, params).serve(_mid_stream_arrivals(),
                                             max_new_tokens=MAX_NEW))


@pytest.fixture(scope="module")
def tp8_engine(tp_model_params):
    model, params = tp_model_params
    return _engine(model, params, tp=8)


def test_tp8_greedy_token_parity(tp8_engine, greedy_base):
    """tp=8 serve() is token-identical to tp=1 under greedy decoding,
    including sequences admitted mid-decode, and drains clean."""
    e = tp8_engine
    got = dict(e.serve(_mid_stream_arrivals(), max_new_tokens=MAX_NEW))
    for u in PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"uid={u} diverged")
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    assert not e.state.seqs
    assert e.telemetry.gauges["tp_degree"] == 8


def test_tp8_device_counters_match_tp1(tp8_engine, tp_model_params,
                                       greedy_base):
    """The in-graph frame counters (read from one device's copy) replay the
    same totals as the single-chip engine — the telemetry surface is
    topology-blind."""
    model, params = tp_model_params
    e1 = _engine(model, params)
    dict(e1.serve(_mid_stream_arrivals(), max_new_tokens=MAX_NEW))
    dict(tp8_engine.serve(_mid_stream_arrivals(), max_new_tokens=MAX_NEW))
    for name in ("tokens_emitted", "prefill_tokens", "eos_events",
                 "target_forwards"):
        assert (e1.telemetry.counters[name]
                == tp8_engine.telemetry.counters[name]), name


def test_tp8_spec_greedy_parity(tp_model_params, greedy_base):
    """Speculative serving on the sharded engine (self-draft, its own
    head-sharded KV pools riding the same mesh) stays token-identical to
    the tp=1 non-speculative baseline."""
    model, params = tp_model_params
    e = _engine(model, params, tp=8)
    e.attach_draft(model, params)
    got = dict(e.serve(_mid_stream_arrivals(), max_new_tokens=MAX_NEW,
                       gamma=2))
    for u in PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"uid={u} diverged")
    sp = e.serve_stats["spec"]
    assert sp["tokens_per_target_forward"] > 2.0, sp
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_tp8_zero_in_frame_transfers(tp_model_params, greedy_base,
                                     frame_transfer_guard):
    """Sharding must not smuggle device reads into the frame: dispatch
    under a device-to-host transfer guard (conftest's shared definition of
    "in-frame"), with the frame counters and the rest of the replicated
    carry all surfacing at boundaries only."""
    model, params = tp_model_params
    e = _engine(model, params, tp=8)
    got = dict(e.serve(iter([[(0, PROMPTS[0]), (1, PROMPTS[1])]]),
                       max_new_tokens=MAX_NEW))
    for u in (0, 1):
        np.testing.assert_array_equal(greedy_base[u], got[u])


def test_tp8_replica_consistency_debug_mode(tp_model_params, greedy_base):
    """tp_debug_replica_check reads EVERY device's copy of the frame
    counters at every boundary and asserts they agree — the
    replica-consistency proof of the one-copy steady-state read. A full
    serve under the check passing is the assertion (any shard-varying leak
    into the counters raises)."""
    model, params = tp_model_params
    e = _engine(model, params, tp=8, tp_debug_replica_check=True)
    got = dict(e.serve(iter([[(0, PROMPTS[0]), (1, PROMPTS[1])]]),
                       max_new_tokens=MAX_NEW))
    for u in (0, 1):
        np.testing.assert_array_equal(greedy_base[u], got[u])
    assert e.telemetry.counters["tokens_emitted"] == 2 * MAX_NEW


def test_tp8_quantized_collectives_parity_at_tolerance(tp8_engine,
                                                       tp_model_params,
                                                       greedy_base):
    """The opt-in int8 all-reduce/all-gather path (EQuARX-style): per-row
    symmetric quantization bounds the logit error, so single-step logits
    must track the exact path within tolerance and generation must still
    complete every budget. Token-for-token equality is NOT the contract —
    quantization may legitimately flip near-ties."""
    model, params = tp_model_params
    eq = _engine(model, params, tp=8, tp_quantized_collectives=True)
    got = dict(eq.serve(_mid_stream_arrivals(), max_new_tokens=MAX_NEW))
    assert set(got) == set(PROMPTS)
    assert all(len(v) == MAX_NEW for v in got.values())
    assert eq.kv.free_blocks == eq.kv.num_blocks - 1

    # logit-level tolerance on one exact forward vs one quantized forward:
    # run the SAME single-token decode through both engines' runners
    ids = np.asarray([[5]], np.int32)
    pos = np.asarray([[0]], np.int32)
    tbl = np.asarray([[1]], np.int32)
    ones = np.asarray([1], np.int32)

    def one_logits(e):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        tp = e.tp_ctx
        import functools
        fwd = functools.partial(e.runner._forward, tp=tp)

        def core(params, kpool, vpool):
            logits, _, _ = fwd(params, jnp.asarray(ids), jnp.asarray(pos),
                               jnp.asarray(tbl), jnp.asarray(ones),
                               kpool, vpool)
            return logits

        f = jax.shard_map(core, mesh=tp.mesh,
                          in_specs=(tp.param_specs, tp.kv_spec, tp.kv_spec),
                          out_specs=P(), check_vma=False)
        return np.asarray(jax.jit(f)(e.params, e.kv.k, e.kv.v))

    exact = one_logits(tp8_engine)
    quant = one_logits(eq)
    scale = np.abs(exact).max()
    assert np.abs(exact - quant).max() <= 0.05 * scale, \
        (np.abs(exact - quant).max(), scale)


def test_tp8_overlap_ring_collectives_parity(tp_model_params, greedy_base):
    """The T3-style overlap path (MLP all-reduce as ppermute ring chunks)
    reorders the reduction but changes no operand values: greedy tokens on
    this model match the exact path."""
    model, params = tp_model_params
    eo = _engine(model, params, tp=8, tp_overlap_collectives=True)
    got = dict(eo.serve(_mid_stream_arrivals(), max_new_tokens=MAX_NEW))
    for u in PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"uid={u} diverged")


@pytest.mark.chaos
def test_tp8_poison_quarantine_survivor_parity(tp_model_params, greedy_base):
    """Chaos on the sharded engine: a poisoned row is quarantined via the
    mesh-aware evict (one replicated boundary write) while its batch
    siblings stay token-identical to the fault-free tp=1 baseline — the
    quarantine/evict machinery is topology-blind."""
    model, params = tp_model_params
    e = _engine(model, params, tp=8)
    fi = FaultInjector([FaultSpec(kind="poison_row", frame=1, uid=1)])
    got = dict(e.serve(iter([[(u, PROMPTS[u]) for u in (0, 1, 2)]]),
                       max_new_tokens=MAX_NEW, faults=fi))
    assert 1 not in got
    for u in (0, 2):
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"survivor uid={u}")
    fl = [f for f in e.fault_log if f.kind == "poison_row"]
    assert len(fl) == 1 and fl[0].uid == 1
    assert e.kv.free_blocks == e.kv.num_blocks - 1   # evicted blocks freed
    assert not e.state.seqs


@pytest.mark.chaos
def test_snapshot_resumes_across_tp_degrees(tp_model_params, greedy_base):
    """Kill-and-resume with a DIFFERENT tensor-parallel degree on each side:
    the ledger snapshot is host-only and engine-shape-agnostic, so a tp=8
    crash resumes on tp=1 (and tp=1 on tp=8) token-identically — the
    contract ROADMAP item 2's multi-engine failover router builds on."""
    model, params = tp_model_params

    def crash(e):
        fi = FaultInjector(
            [FaultSpec(kind="dispatch_exception", frame=2, times=99)])
        out = {}
        with pytest.raises(FrameDispatchError):
            for u, t in e.serve(iter([[(u, PROMPTS[u]) for u in (0, 1, 2)]]),
                                max_new_tokens=MAX_NEW, faults=fi):
                out[u] = t
        assert e.last_crash_snapshot is not None
        return out, e.last_crash_snapshot

    # tp=8 crash -> tp=1 resume
    done, snap = crash(_engine(model, params, tp=8))
    merged = dict(done)
    merged.update(dict(_engine(model, params).serve(iter([[]]),
                                                    resume_from=snap)))
    for u in (0, 1, 2):
        np.testing.assert_array_equal(greedy_base[u], merged[u],
                                      err_msg=f"tp8->tp1 uid={u}")

    # tp=1 crash -> tp=8 resume
    done, snap = crash(_engine(model, params))
    e8 = _engine(model, params, tp=8)
    merged = dict(done)
    merged.update(dict(e8.serve(iter([[]]), resume_from=snap)))
    for u in (0, 1, 2):
        np.testing.assert_array_equal(greedy_base[u], merged[u],
                                      err_msg=f"tp1->tp8 uid={u}")
    assert e8.telemetry.counters["recoveries"] == len(snap["requests"])


def test_tp_validation_rejects_indivisible_arch():
    """Loud construction-time failure when a sharded axis doesn't divide:
    a silently replicated head tensor would corrupt the psum arithmetic."""
    model = build_model("tiny")          # 4 heads: 4 % 8 != 0
    with pytest.raises(NotImplementedError, match="num_heads=4"):
        InferenceEngineV2(model,
                          RaggedInferenceEngineConfig(tp=8, dtype="float32"),
                          max_seq_len=128)


def test_tp_vocab_fallback_replicates(tp_model_params):
    """A vocab the tp degree doesn't divide falls back to a replicated
    embedding/LM head (memory cost, not a correctness cliff) while heads
    and MLP stay sharded."""
    model = build_model("tiny", num_heads=8, vocab_size=252)  # 252 % 8 != 0
    params = model.init(jax.random.PRNGKey(0))
    e1 = _engine(model, params)
    e8 = _engine(model, params, tp=8)
    assert not e8.tp_ctx.vocab_sharded
    p = np.random.default_rng(7).integers(0, 250, (9,)).astype(np.int32)
    base = dict(e1.serve(iter([[(0, p)]]), max_new_tokens=MAX_NEW))
    got = dict(e8.serve(iter([[(0, p)]]), max_new_tokens=MAX_NEW))
    np.testing.assert_array_equal(base[0], got[0])


def test_tp8_planned_frames_emit_the_tokens_of_whole_frames(
        tp8_engine, greedy_base, planned_against_whole):
    """The frame's step count is a replicated operand of the ``shard_map``
    program: the sharded engine's planned frames give the tokens of whole
    frames and of the tp=1 run, through the programs it had."""
    planned, hist = planned_against_whole(
        tp8_engine, _mid_stream_arrivals, max_new_tokens=MAX_NEW)
    for u in PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], planned[u],
                                      err_msg=f"uid={u} diverged")
    assert min(hist) < 4
    assert tp8_engine.kv.free_blocks == tp8_engine.kv.num_blocks - 1
