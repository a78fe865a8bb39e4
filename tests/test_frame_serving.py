"""Frame-based persistent serving loop tests.

The frame loop (``engine_v2.serve``) must match host-driven ``step()``
serving token-for-token under greedy decoding — including sequences admitted
while others are mid-decode — and must keep the compiled-program count
O(log) in batch size (the recompile budget that makes continuous batching
run at compiled-loop speed)."""

import numpy as np
import jax
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.scheduler import RequestScheduler
from deepspeed_tpu.models import build_model


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def tiny_model_params():
    model = build_model("tiny")
    return model, model.init(jax.random.PRNGKey(0))


# One serve loop runs whatever the admission policy: what a de-forked
# helper does is checked under both (None = scheduler.FifoPolicy). A
# factory, not an instance: a scheduler is bound to one serve at a time.
POLICIES = pytest.mark.parametrize(
    "policy", [lambda: None, RequestScheduler], ids=["fifo", "scheduler"])


def _engine(model, params, **over):
    kw = dict(kv_block_size=16, prefill_chunk_size=16, max_tokens_per_step=256,
              dtype="float32", max_ragged_batch_size=8, frame_steps=4)
    kw.update(over)
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                          max_seq_len=128)
    e.params = jax.device_put(params)
    return e


def _step_serve(eng, admissions, max_new_tokens):
    """Host-driven baseline: put() batches at arbitrary points mid-decode,
    step() until every uid has its budget. Per-uid greedy outputs are
    schedule-independent (rows are independent in the forward and chunk
    boundaries depend only on the chunk size), so this is THE reference for
    any admission timing."""
    admissions = list(admissions)
    counts = {}
    outs = {}
    while admissions or counts:
        if admissions:
            uids, prompts = admissions.pop(0)
            eng.put(uids, prompts)
            counts.update({u: 0 for u in uids})
        for _ in range(3):   # a few steps between admissions
            produced = eng.step()
            for u, _t in produced.items():
                counts[u] += 1
            for u in list(counts):
                if counts[u] >= max_new_tokens:
                    seq = eng.state.seqs[u]
                    seq.done = True
                    outs[u] = np.asarray(seq.generated[:max_new_tokens])
                    eng.flush([u])
                    del counts[u]
            if not counts:
                break
    return outs


def test_frame_serving_parity_mid_stream_arrivals(tiny_model_params):
    """serve() greedy outputs == step() greedy outputs per uid, with
    sequences admitted while others are mid-decode on both sides."""
    model, params = tiny_model_params
    rng = np.random.default_rng(5)
    prompts = {u: rng.integers(0, 200, (n,)).astype(np.int32)
               for u, n in zip(range(4), (7, 24, 33, 5))}

    # frame loop: uids 0/1 arrive up front; 2 and 3 arrive at later frame
    # boundaries, while 0/1 are already decoding
    schedule = {0: [0, 1], 2: [2], 3: [3]}

    def arrivals():
        for k in range(5):
            yield [(u, prompts[u]) for u in schedule.get(k, [])]

    e1 = _engine(model, params)
    got = dict(e1.serve(arrivals(), max_new_tokens=8))
    assert set(got) == set(prompts)
    assert e1.kv.free_blocks == e1.kv.num_blocks - 1   # all retired+flushed

    # host-driven baseline with its own (different) mid-stream admissions
    e2 = _engine(model, params)
    ref = _step_serve(e2, [([0, 1], [prompts[0], prompts[1]]),
                           ([2], [prompts[2]]), ([3], [prompts[3]])], 8)

    for u in prompts:
        np.testing.assert_array_equal(ref[u], got[u],
                                      err_msg=f"uid={u} diverged")


def test_frame_serving_in_graph_eos(tiny_model_params):
    """A row whose sampled token hits its per-row EOS freezes IN-GRAPH and
    retires with the EOS included; other rows are unaffected."""
    model, params = tiny_model_params
    rng = np.random.default_rng(6)
    prompts = {0: rng.integers(0, 200, (9,)).astype(np.int32),
               1: rng.integers(0, 200, (21,)).astype(np.int32)}

    base = dict(_engine(model, params).serve(
        iter([[(u, prompts[u]) for u in prompts]]), max_new_tokens=8))
    eos = int(base[0][2])          # uid 0's third token becomes its EOS
    stop = base[0].tolist().index(eos)   # freezes at the FIRST occurrence

    got = dict(_engine(model, params).serve(
        iter([[(0, prompts[0], None, None, eos), (1, prompts[1])]]),
        max_new_tokens=8))
    np.testing.assert_array_equal(got[0], base[0][:stop + 1])
    if eos not in base[1].tolist():
        np.testing.assert_array_equal(got[1], base[1])   # neighbor untouched


@pytest.mark.parametrize("api", ["serve", "generate"])
def test_frame_serving_admission_control_overload(tiny_model_params, api):
    """More arrivals than slots: admission defers (FIFO) until retirements
    free slots; everything still finishes and the pool drains clean.
    ``generate()`` is that closed batch, its uids the prompts' order."""
    model, params = tiny_model_params
    rng = np.random.default_rng(7)
    prompts = {u: rng.integers(0, 200, (6 + u,)).astype(np.int32)
               for u in range(6)}
    e = _engine(model, params, max_ragged_batch_size=2)

    if api == "generate":
        got = dict(enumerate(e.generate(list(prompts.values()),
                                        max_new_tokens=5)))
        assert e.telemetry.counters["admission_deferrals"] > 0
    else:
        got = dict(e.serve(iter([[(u, prompts[u]) for u in prompts]]),
                           max_new_tokens=5, frame_slots=2))
    assert set(got) == set(prompts)
    assert all(len(v) == 5 for v in got.values())
    assert e.kv.free_blocks == e.kv.num_blocks - 1

    ref = _step_serve(_engine(model, params),
                      [(list(prompts), list(prompts.values()))], 5)
    for u in prompts:
        np.testing.assert_array_equal(ref[u], got[u])


def test_frame_serving_sampled_rows(tiny_model_params):
    """Per-row temperatures ride the device carry: a sampled row and greedy
    rows share one frame; the greedy rows still match the greedy baseline."""
    model, params = tiny_model_params
    rng = np.random.default_rng(8)
    prompts = {0: rng.integers(0, 200, (11,)).astype(np.int32),
               1: rng.integers(0, 200, (17,)).astype(np.int32)}

    base = dict(_engine(model, params).serve(
        iter([[(u, prompts[u]) for u in prompts]]), max_new_tokens=6))
    got = dict(_engine(model, params).serve(
        iter([[(0, prompts[0], None, 0.8), (1, prompts[1])]]),
        max_new_tokens=6))
    assert len(got[0]) == 6                      # sampled row completed
    np.testing.assert_array_equal(got[1], base[1])   # greedy row bit-exact


def test_run_batch_recompile_count_bounded(tiny_model_params):
    """Ragged batch-size sweep: the per-chunk jit cache must stay O(log) in
    live batch size (power-of-two padding), not O(B)."""
    model, params = tiny_model_params
    e = _engine(model, params)
    rng = np.random.default_rng(9)
    # admit one sequence per step: decode batch ramps 1,2,3,...,7 while each
    # step also runs a batch-1 prefill chunk
    for u in range(7):
        e.put([u], [rng.integers(0, 200, (5,)).astype(np.int32)])
        e.step()
    for _ in range(4):
        e.step()
    # programs: prefill chunk=16 at padded B=1, decode chunk=1 at padded
    # B in {1, 2, 4, 8} -> 5. Unpadded, the decode sweep alone compiles 7.
    # compile_count() is per-function, so the test can pin WHICH entry
    # point recompiled, not just the aggregate.
    cc = e.runner.compile_count()
    assert sum(cc.values()) <= 5, cc
    assert cc.get("chunk16", 0) <= 1 and cc.get("chunk1", 0) <= 4, cc
    # block tables come back as host numpy — one device transfer per step,
    # not one per sequence
    seq = e.state.seqs[0]
    assert isinstance(e.state.block_table(seq, 4), np.ndarray)


def test_frame_loop_recompile_count_bounded(tiny_model_params):
    """The frame jit retraces only per shape bucket: width in {chunk, 1} x
    power-of-two table/prompt widths — a long dynamic-arrival run stays at a
    handful of programs."""
    model, params = tiny_model_params
    e = _engine(model, params)
    rng = np.random.default_rng(10)

    def arrivals():
        for k in range(8):
            # staggered lengths force prompt-width regrowth + mixed frames
            yield [(k, rng.integers(0, 200, (4 + 7 * k,)).astype(np.int32))]

    got = dict(e.serve(arrivals(), max_new_tokens=6))
    assert len(got) == 8
    frame_fn = e.runner._fns["frame"]
    assert frame_fn._cache_size() <= 6


@POLICIES
def test_frame_serving_admission_guards(tiny_model_params, policy):
    """A duplicate in-flight uid is a client error (loud, before it can
    corrupt the uid<->slot mapping); an over-context budget is clamped so
    the slot table never outgrows max_seq_len; a request that an EMPTY
    pool cannot hold can never be admitted, and says so."""
    model, params = tiny_model_params
    rng = np.random.default_rng(12)
    p = rng.integers(0, 200, (8,)).astype(np.int32)

    with pytest.raises(ValueError, match="already live"):
        list(_engine(model, params).serve(
            iter([[(0, p)], [(0, p)]]), max_new_tokens=64,
            scheduler=policy()))

    # a duplicate of a QUEUED uid (one slot: uid 1 waits behind uid 0)
    with pytest.raises(ValueError, match="already live"):
        list(_engine(model, params).serve(
            iter([[(0, p), (1, p)], [(1, p)]]), max_new_tokens=8,
            frame_slots=1, scheduler=policy()))

    # 100-token prompt in a 128-token context: budget 64 -> clamped to 27
    long_p = rng.integers(0, 200, (100,)).astype(np.int32)
    e = _engine(model, params)
    got = dict(e.serve(iter([[(0, long_p)]]), max_new_tokens=64,
                       scheduler=policy()))
    assert len(got[0]) == 128 - 100 - 1
    assert e.kv.free_blocks == e.kv.num_blocks - 1

    # 3 usable blocks of 16 positions against 8 + 64 + 1: the table is
    # empty and nothing was admitted, so waiting cannot help. The uid the
    # error names was never admitted and leaves nothing behind
    small = _engine(model, params, num_kv_blocks=4)
    with pytest.raises(RuntimeError, match="uid=7: prompt.*can never fit"):
        list(small.serve(iter([[(7, p)]]), max_new_tokens=64,
                         scheduler=policy()))
    assert not small.state.seqs and not small._ledger
    assert small.kv.free_blocks == small.kv.num_blocks - 1


@POLICIES
def test_frame_serving_abandonment_releases_state(tiny_model_params, policy):
    """Breaking out of serve() mid-stream (server shutdown, client error)
    must release every in-flight sequence, live or still queued (two
    slots: one waits with a descriptor from its capacity probe): no leaked
    KV blocks, no stale descriptors that would feed old tokens to a later
    call reusing a uid."""
    model, params = tiny_model_params
    rng = np.random.default_rng(13)
    prompts = {u: rng.integers(0, 200, (10 + u,)).astype(np.int32)
               for u in range(4)}
    e = _engine(model, params)
    for _uid, _toks in e.serve(iter([[(u, prompts[u]) for u in prompts]]),
                               max_new_tokens=16, scheduler=policy()):
        break                                   # abandon with 3 in flight
    assert not e.state.seqs
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    for _uid, _toks in e.serve(iter([[(u, prompts[u]) for u in prompts]]),
                               max_new_tokens=16, frame_slots=2,
                               scheduler=policy()):
        break                                   # 1 live, 2 queued
    assert not e.state.seqs and not e._ledger
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    # the engine is reusable afterwards, uids included
    got = dict(e.serve(iter([[(0, prompts[0])]]), max_new_tokens=4,
                       scheduler=policy()))
    assert len(got[0]) == 4


# ---------------------------------------------------------------------------
# speculative decoding on the frame carry
# ---------------------------------------------------------------------------
# The speculative tests share module-scope engines and one greedy baseline:
# every fresh engine recompiles its serving programs from scratch on CPU, so
# reusing engines (their jit caches persist across serve() calls — serve
# leaves the engine clean) keeps the suite inside the tier-1 time budget.


SPEC_PROMPTS = {u: np.random.default_rng(5).integers(0, 200, (200,))
                .astype(np.int32)[o:o + n]
                for u, (o, n) in enumerate(((0, 7), (10, 24), (40, 33),
                                            (80, 5)))}
SPEC_SCHEDULE = {0: [0, 1], 2: [2], 3: [3]}


def _spec_engine(model, params, draft_model=None, draft_params=None, **over):
    """Engine with a draft attached; draft defaults to a self-draft (same
    model, same params — the 100%-acceptance upper bound)."""
    e = _engine(model, params, **over)
    e.attach_draft(draft_model if draft_model is not None else model,
                   draft_params if draft_params is not None else params)
    return e


def _mid_stream_arrivals(prompts=None, schedule=None):
    prompts = SPEC_PROMPTS if prompts is None else prompts
    schedule = SPEC_SCHEDULE if schedule is None else schedule
    for k in range(max(schedule) + 2):
        yield [(u, prompts[u]) for u in schedule.get(k, [])]


@pytest.fixture(scope="module")
def greedy_base(tiny_model_params):
    """Non-speculative greedy serve() outputs for SPEC_PROMPTS — THE
    reference every speculative variant must reproduce bit-exactly."""
    model, params = tiny_model_params
    return dict(_engine(model, params).serve(_mid_stream_arrivals(),
                                             max_new_tokens=8))


@pytest.fixture(scope="module")
def self_draft_engine(tiny_model_params):
    model, params = tiny_model_params
    return _spec_engine(model, params)


@pytest.fixture(scope="module")
def distinct_draft_engine(tiny_model_params):
    """Draft with a different arch (1 layer) and a fresh init: proposals are
    effectively random, so essentially every speculative step rejects."""
    from deepspeed_tpu.models import build_model as _bm
    model, params = tiny_model_params
    draft = _bm("tiny", num_layers=1)
    return _spec_engine(model, params, draft_model=draft,
                        draft_params=draft.init(jax.random.PRNGKey(42)))


def test_spec_greedy_parity_self_draft(self_draft_engine, greedy_base):
    """Speculative serve() with draft == target is token-identical to the
    non-speculative frame loop under greedy decoding — including sequences
    admitted mid-decode — and emits > 2 tokens per target forward at
    gamma=2 (full acceptance, minus end-of-budget truncation)."""
    e = self_draft_engine
    got = dict(e.serve(_mid_stream_arrivals(), max_new_tokens=8, gamma=2))
    for u in SPEC_PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"uid={u} diverged")
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    sp = e.serve_stats["spec"]
    assert sp["tokens_per_target_forward"] > 2.0, sp
    # acceptance never synced the host: the frame only hands back the
    # (steps, B, gamma+1) token/emit pair
    assert sp["accepted_drafts"] > 0


def test_spec_greedy_parity_distinct_draft(distinct_draft_engine, greedy_base):
    """A DIFFERENT draft (1 layer, fresh init — near-zero acceptance) must
    still produce bit-identical greedy output: verification + in-graph
    rollback make draft quality a throughput knob, never a correctness one."""
    e = distinct_draft_engine
    got = dict(e.serve(_mid_stream_arrivals(), max_new_tokens=8, gamma=2))
    for u in SPEC_PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"uid={u} diverged")
    assert e.serve_stats["spec"]["acceptance_rate"] < 1.0


def test_spec_rollback_forced_rejection(distinct_draft_engine, greedy_base):
    """The garbage draft forces a rejection + rollback on essentially every
    step; the committed watermark and host mirrors must stay consistent:
    emitted tokens match non-speculative serving, every row retires at
    exactly its budget, and the pool drains clean (rejected KV entries are
    overwritten in place, never freed)."""
    e = distinct_draft_engine
    got = dict(e.serve(iter([[(u, SPEC_PROMPTS[u]) for u in SPEC_PROMPTS]]),
                       max_new_tokens=8, gamma=2))
    assert set(got) == set(SPEC_PROMPTS)
    for u in SPEC_PROMPTS:
        assert len(got[u]) == 8            # full budget despite rollbacks
        np.testing.assert_array_equal(greedy_base[u], got[u],
                                      err_msg=f"uid={u}")
    sp = e.serve_stats["spec"]
    assert sp["acceptance_rate"] < 0.5, sp   # rejections actually happened
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    assert not e.state.seqs                  # mirrors fully retired
    # the engine (and its draft pools) stay reusable after heavy rollback
    again = dict(e.serve(iter([[(0, SPEC_PROMPTS[0])]]), max_new_tokens=4))
    np.testing.assert_array_equal(again[0], greedy_base[0][:4])


def test_spec_in_graph_eos(self_draft_engine, greedy_base):
    """EOS inside an accepted draft run truncates the emit mask in-graph:
    the row keeps the EOS, drops the speculated tail, and retires."""
    e = self_draft_engine
    eos = int(greedy_base[0][2])       # uid 0's third token becomes its EOS
    stop = greedy_base[0].tolist().index(eos)
    got = dict(e.serve(
        iter([[(0, SPEC_PROMPTS[0], None, None, eos),
               (1, SPEC_PROMPTS[1])]]), max_new_tokens=8, gamma=2))
    np.testing.assert_array_equal(got[0], greedy_base[0][:stop + 1])
    if eos not in greedy_base[1].tolist():
        np.testing.assert_array_equal(got[1], greedy_base[1])


def test_spec_recompile_count_bounded(tiny_model_params):
    """Speculation adds ONE new entry point (spec_frame) with the same
    shape-bucket discipline: width in {chunk, 1} x pow2 table/prompt widths.
    The per-function compile_count pins exactly where programs come from."""
    model, params = tiny_model_params
    e = _spec_engine(model, params)     # fresh engine: counting programs
    rng = np.random.default_rng(10)

    def arrivals():
        for k in range(6):   # staggered lengths: prompt buckets 16 -> 32 -> 64
            yield [(k, rng.integers(0, 200, (4 + 7 * k,)).astype(np.int32))]

    got = dict(e.serve(arrivals(), max_new_tokens=4, gamma=2))
    assert len(got) == 6
    cc = e.runner.compile_count()
    assert cc.get("spec_frame", 0) <= 6, cc
    assert "frame" not in cc          # the non-spec frame never compiled


def test_spec_sampled_rows_complete(self_draft_engine, greedy_base):
    """temperature > 0 rides the speculative frame via rejection sampling:
    sampled rows complete their budget; greedy rows in the same frame stay
    bit-exact vs the non-speculative greedy baseline."""
    e = self_draft_engine
    got = dict(e.serve(
        iter([[(0, SPEC_PROMPTS[0], None, 0.8), (1, SPEC_PROMPTS[1])]]),
        max_new_tokens=8, gamma=2))
    assert len(got[0]) == 8
    np.testing.assert_array_equal(got[1], greedy_base[1])


def test_serve_rng_reproducible(self_draft_engine, tiny_model_params):
    """An explicit rng/seed threads into the frame carry: two sampled serves
    with the same seed are identical (speculative or not); the default path
    still draws from the engine's stream."""
    model, params = tiny_model_params

    def one(e, seed, **kw):
        return dict(e.serve(
            iter([[(0, SPEC_PROMPTS[0], None, 0.8),
                   (1, SPEC_PROMPTS[1], None, 0.8)]]),
            max_new_tokens=8, rng=seed, **kw))

    es = self_draft_engine
    a, b = one(es, 7, gamma=2), one(es, 7, gamma=2)
    for u in a:
        np.testing.assert_array_equal(a[u], b[u])
    en = _engine(model, params)
    c, d = one(en, 7, speculate=False), one(en, 7, speculate=False)
    for u in c:
        np.testing.assert_array_equal(c[u], d[u])
    # generate() hands serve() no rng: its key is split from the engine's
    # stream, so engines of one seed give one answer, call after call (the
    # serves above took an explicit rng and split nothing of `en`'s stream:
    # both engines start level)
    prompts = [SPEC_PROMPTS[0][:20], SPEC_PROMPTS[1][:9]]
    twin = _engine(model, params)
    twin.runner = en.runner         # one model, one geometry: one compile
    runs = [[e.generate(prompts, max_new_tokens=8, temperature=0.8)
             for _ in range(2)] for e in (en, twin)]
    for x, y in zip(*runs):
        for t, u in zip(x, y):
            np.testing.assert_array_equal(t, u)


def test_adaptive_frame_steps_buckets(tiny_model_params):
    """Adaptive frame sizing: bursty arrivals shrink the frame to a small
    pow2 bucket (TTFT), a drained arrival stream recovers the full
    frame_steps (throughput); the chosen sizes surface in serve_stats."""
    model, params = tiny_model_params
    e = _engine(model, params, frame_steps=8, adaptive_frame_steps=True)
    rng = np.random.default_rng(3)

    def arrivals():
        for k in range(4):        # one arrival per poll: ewma ~ 1
            yield [(k, rng.integers(0, 200, (4,)).astype(np.int32))]

    got = dict(e.serve(arrivals(), max_new_tokens=48))
    assert len(got) == 4 and all(len(v) == 48 for v in got.values())
    hist = e.serve_stats["frame_steps_hist"]
    assert any(k < 8 for k in hist), hist      # shrank under arrivals
    assert 8 in hist, hist                     # recovered when drained
    # the run's last frame ends with the last request's last token
    assert e.serve_stats["frame_steps_last"] < 8
    # explicit frame_steps= pins the size even with the config flag on: the
    # wide frame ends with its one-chunk prompt at half of the 4, a narrow
    # one runs 4 steps, the last ends with the request's eighth token
    dict(e.serve(iter([[(9, rng.integers(0, 200, (4,)).astype(np.int32))]]),
                 max_new_tokens=8, frame_steps=4))
    assert e.serve_stats["frame_steps_hist"] == {2: 2, 4: 1}


def test_generate_refuses_what_the_pool_can_never_hold(tiny_model_params):
    """generate() is a closed batch through serve() and takes its answer: a
    request whose prompt + budget an EMPTY pool cannot hold is refused
    loudly (no partial prefix by host steps), the pool is whole afterwards,
    and the same engine then serves a budget that fits."""
    model, params = tiny_model_params
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 200, (24,)).astype(np.int32)

    full = _engine(model, params).generate([prompt], max_new_tokens=16)[0]

    # trash + 3 blocks = 48 tokens: holds the 24-token prompt and 16 new
    # tokens, never the 24 + 32 + 1 that admission reserves up front
    small = _engine(model, params, num_kv_blocks=4)
    with pytest.raises(RuntimeError, match="can never fit the KV pool"):
        small.generate([prompt], max_new_tokens=32)
    assert not small.state.seqs
    assert small.kv.free_blocks == small.kv.num_blocks - 1
    np.testing.assert_array_equal(
        small.generate([prompt], max_new_tokens=16)[0], full)
    assert small.kv.free_blocks == small.kv.num_blocks - 1


# ---------------------------------------------------------------------------
# token packing: a wide step's per-token layers run on its live tokens
# ---------------------------------------------------------------------------

PACK = dict(max_ragged_batch_size=8, prefill_chunk_size=64, kv_block_size=16,
            max_tokens_per_step=512, frame_steps=2)
LADDER = (16, 144, 272, 512)    # pack_ladder(8, 64): 0, 2, 4 whole chunks, all


def _ragged_positions(ws, width):
    offs = np.arange(width)[None, :]
    return np.where(offs < np.asarray(ws)[:, None], 3 + offs, -1).astype(
        np.int32)


@pytest.mark.parametrize("ws,rung", [
    ([1, 1, 0, 1, 1, 0, 1, 1], 0),       # every live row decodes
    ([64, 1, 0, 1, 1, 0, 7, 1], 1),      # one whole chunk, decodes, w = 0
    ([64, 64, 64, 0, 1, 1, 9, 0], 2),
    ([64] * 8, 3),                       # every row a whole chunk: unpacked
    ([0] * 8, 0),                        # nothing live
])
def test_pack_unpack_round_trip(ws, rung):
    """``_on_live`` hands a per-token function the live positions alone, in
    order, on the rung that holds them, and puts its outputs back where they
    came from with zeros at the dead positions."""
    from deepspeed_tpu.inference.v2 import model_runner as mr
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    assert pack_ladder(8, 64) == LADDER
    assert pack_ladder(8, 1) == (8,) and pack_ladder(8, 16) == (128,)
    assert pack_ladder(16, 128) == (16, 144, 272, 528, 1040, 2048)
    positions = _ragged_positions(ws, 64)
    x = np.arange(8 * 64 * 3, dtype=np.float32).reshape(8, 64, 3) + 1.0
    seen = []

    def fn(x, pos):
        seen.append(x.shape[:2])
        return x * 2.0, pos[..., None]

    pack = mr._pack_plan(positions, LADDER)
    assert int(pack[0]) == rung
    y, p = jax.jit(lambda x, pos: mr._on_live(
        mr._pack_plan(pos, LADDER), fn, x, pos))(x, positions)
    live = positions >= 0
    np.testing.assert_array_equal(np.asarray(y)[live], 2.0 * x[live])
    np.testing.assert_array_equal(np.asarray(p)[live][:, 0], positions[live])
    assert not np.asarray(y)[~live].any()
    assert seen == [(1, 16), (1, 144), (1, 272), (1, 512)]    # a trace a rung
    # the packed view holds the live positions first, in chunk order
    n = int(live.sum())
    np.testing.assert_array_equal(np.asarray(pack[1])[:n],
                                  np.flatnonzero(live.reshape(-1))[:n])


@pytest.fixture(scope="module")
def pack_engines(tiny_model_params):
    """Two engines over one model at a shape that packs (8 x 64): as
    shipped, and with the ladder cut to its top rung (the unpacked
    program)."""
    model, params = tiny_model_params
    packed = _engine(model, params, **PACK)
    whole = _engine(model, params, **PACK)
    whole.runner.pack_ladder = lambda b, c: (b * c,)
    return packed, whole


@pytest.mark.parametrize("n_prefill,rung", [(1, 144), (3, 272), (8, 512)])
def test_packed_wide_frame_matches_unpacked(pack_engines, n_prefill, rung):
    """A wide frame with 1, 3 and all 8 rows prefilling a whole chunk (the
    rest decoding) emits the tokens of the unpacked program, takes the rung
    its live count calls for, and says so in the per-rung counters."""
    packed, whole = pack_engines
    rng = np.random.default_rng(11 + n_prefill)
    late = {u: rng.integers(0, 200, (70,)).astype(np.int32)
            for u in range(n_prefill)}
    early = {u: rng.integers(0, 200, (5,)).astype(np.int32)
             for u in range(n_prefill, 8)}

    def arrivals():
        yield list(early.items())        # short prompts: decoding by frame 1
        yield list(late.items())         # these prefill while the rest decode

    outs = {}
    for name, e in (("packed", packed), ("whole", whole)):
        before = e.runner.compile_count_total()
        outs[name] = dict(e.serve(arrivals(), max_new_tokens=12))
        assert e.kv.free_blocks == e.kv.num_blocks - 1
        outs[name + "_programs"] = e.runner.compile_count_total() - before
    assert set(outs["packed"]) == set(late) | set(early)
    for u in outs["whole"]:
        np.testing.assert_array_equal(outs["packed"][u], outs["whole"][u],
                                      err_msg=f"uid={u} diverged")
    steps = {int(dict(k)["tokens"]): v for k, v in
             packed.telemetry.labeled["rung_steps"].items()}
    assert steps.get(rung, 0) >= 1, steps
    # a late prompt's last chunk is short (6 tokens), so the same wide
    # frame also holds a step on a lower rung
    assert len(steps) > 1, steps
    c = packed.telemetry.counters
    assert c["rung_steps"] == sum(steps.values())
    assert c["positions_computed"] < whole.telemetry.counters[
        "positions_computed"] or n_prefill == 8
    assert "ds_serving_rung_steps_total{" in packed.telemetry.render_prometheus()
    # no program beyond the unpacked engine's set: the rung is chosen in
    # the graph, never by a new program
    assert outs["packed_programs"] == outs["whole_programs"]


@pytest.mark.parametrize("ws", [[64, 1, 0, 1, 1, 0, 7, 1],
                                [64, 64, 40, 1, 1, 1, 0, 0],
                                [64] * 8])
def test_packed_forward_logits_match_unpacked(pack_engines, ws):
    """One forward over a ragged chunk: last-token logits of the live rows
    and the KV the step commits agree with the unpacked program's."""
    packed, whole = pack_engines
    rng = np.random.default_rng(3)
    positions = _ragged_positions(ws, 64)
    ids = rng.integers(0, 200, (8, 64)).astype(np.int32)
    tables = np.arange(1, 8 * 8 + 1, dtype=np.int32).reshape(8, 8)
    got = {}
    for name, e in (("packed", packed), ("whole", whole)):
        logits, k, v = jax.jit(e.runner._forward)(
            e.params, ids, positions, tables, np.asarray(ws, np.int32),
            jax.numpy.zeros_like(e.kv.k), jax.numpy.zeros_like(e.kv.v))
        got[name] = (np.asarray(logits), np.asarray(k), np.asarray(v))
    rows = np.asarray(ws) > 0
    np.testing.assert_allclose(got["packed"][0][rows], got["whole"][0][rows],
                               rtol=1e-5, atol=1e-5)
    for i in (1, 2):     # pages 1.. hold the live positions' KV (0 is trash)
        np.testing.assert_allclose(got["packed"][i][:, :, 1:],
                                   got["whole"][i][:, :, 1:],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant,packs", [
    ("int8-kv", True),          # gather path: the dense halves pack all the same
    ("int8-weights", True),     # L.dq dequantizes inside the rung
    ("moe-grouped", True),      # dropless routing treats every token alike
    ("moe-einsum", False),      # capacity routing: the chunk stays whole
])
def test_packing_adapts_to_the_model(variant, packs):
    """Who else runs ``_forward``: quantized KV, quantized weights and
    dropless experts pack like the dense model and emit the tokens of the
    unpacked program; capacity-routed experts let pad positions compete for
    capacity, so that model has one rung and its counters say so."""
    over, preset = {}, "tiny"
    if variant == "int8-kv":
        over["kv_dtype"] = "int8"
    elif variant == "int8-weights":
        over["weight_dtype"] = "int8"
    else:
        preset = "tiny-moe"
    model = build_model(preset, **({"moe_impl": "grouped"}
                                   if variant == "moe-grouped" else {}))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    prompts = {u: rng.integers(0, 200, (n,)).astype(np.int32)
               for u, n in enumerate((100, 5, 70, 9))}
    outs = {}
    for name in ("packed", "whole"):
        e = InferenceEngineV2(
            model, RaggedInferenceEngineConfig(dtype="float32", **PACK, **over),
            params=params, max_seq_len=128)
        if name == "whole":
            e.runner.pack_ladder = lambda b, c: (b * c,)
        outs[name] = dict(e.serve(iter([list(prompts.items())]),
                                  max_new_tokens=6))
        assert e.kv.free_blocks == e.kv.num_blocks - 1
        if name == "packed":
            assert (e.telemetry.counters["rung_steps"] > 0) == packs
            assert (len(e.runner.pack_ladder(8, 64)) > 1) == packs
    for u in prompts:
        np.testing.assert_array_equal(outs["packed"][u], outs["whole"][u],
                                      err_msg=f"{variant} uid={u}")


@pytest.mark.parametrize("batch", [1, 3, 4])
def test_admission_is_one_program_whatever_the_batch(batch):
    """``DeviceSlotTable.admit`` writes a batch's rows with ONE program of
    fixed shapes (``_admit_rows``: the batch rides padded to a row a slot,
    a row past the batch is dropped): the rows admitted read what they were
    given, a row freed by quarantine hands on neither its poison flag nor
    its latch, every other row stands, and no batch size compiles anew."""
    from deepspeed_tpu.inference.v2 import ragged_manager as rm
    slots = rm.DeviceSlotTable(4, 8, 2, jax.random.PRNGKey(0), rings=(3,))
    live = rm.DSSequenceDescriptor(uid=9, blocks=[7], ring_blocks=[[5]])
    slots.admit([(9, live, [3, 1, 4], 5, 0.0, None)])
    if batch == 4:
        slots.retire(9)
    slots.poison = slots.poison.at[1:].set(True)
    slots.nonfinite = slots.nonfinite.at[1:].set(True)
    compiled = rm._admit_rows._cache_size()
    items = [(20 + i, rm.DSSequenceDescriptor(
                  uid=20 + i, blocks=[10 + i, 30 + i], ring_blocks=[[40 + i]]),
              np.arange(2 + i) + 50, 6 + i, 0.5 * i, 2 if i else None, i % 2)
             for i in range(batch)]
    slots.admit(items)
    assert rm._admit_rows._cache_size() == compiled
    rows = [slots.slot_of_uid[20 + i] for i in range(batch)]
    assert rows == list(range(1, 1 + batch) if batch < 4 else range(4))
    for i, r in enumerate(rows):
        assert list(np.asarray(slots.prompts[r])[:2 + i]) == \
            list(range(50, 52 + i))
        assert not np.asarray(slots.prompts[r])[2 + i:].any()
        assert list(np.asarray(slots.tables[r])) == [10 + i, 30 + i]
        assert np.asarray(slots.ring_tables[0][r])[0] == 40 + i
        got = [int(np.asarray(getattr(slots, n)[r])) for n in
               ("prompt_lens", "limits", "eos_ids", "cached", "produced",
                "last_tok", "penult")]
        assert got == [2 + i, 6 + i, 2 if i else -1, i % 2, 0, 0, 0]
        assert float(slots.temps[r]) == 0.5 * i
        assert not (bool(slots.done[r]) or bool(slots.poison[r])
                    or bool(slots.nonfinite[r]))
    if batch < 4:       # the tenant of row 0 stands
        assert list(np.asarray(slots.prompts[0])[:3]) == [3, 1, 4]
        assert int(slots.limits[0]) == 5 and int(slots.tables[0][0]) == 7
        for r in set(range(1, 4)) - set(rows):
            assert bool(slots.done[r]) and bool(slots.poison[r])


# ---------------------------------------------------------------------------
# a frame runs the steps its rows have work for (``n_steps``, an operand)
# ---------------------------------------------------------------------------


def _spy_frames(monkeypatch):
    """Every frame the serve loop dispatches: (width, capacity, n_steps,
    remaining prompt tokens and remaining budget of the live rows by the
    host mirrors, the program's (tokens, emit) as it returned them, the
    prompt tokens earlier frames took of each live row)."""
    from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
    seen = []
    dispatch = DeviceSlotTable.dispatch_frame

    def spy(self, runner, params, kv, width, steps, *a, n_steps=None, **kw):
        live = self.uid_of_slot >= 0
        left = np.maximum(self.plen_h - self.cached_h, 0)[live]
        budget = (self.limit_h - self.produced_h)[live]
        toks, emit = dispatch(self, runner, params, kv, width, steps, *a,
                              n_steps=n_steps, **kw)
        seen.append((width, steps, n_steps, left, budget, toks, emit,
                     (self.cached_h - self.start_h)[live]))
        return toks, emit

    monkeypatch.setattr(DeviceSlotTable, "dispatch_frame", spy)
    return seen


def _planned(width, steps, left, budget, began, cap=None):
    """The plan's two rules, from the text: a wide frame ends with its last
    prefilling row, at half of ``frame_steps`` at least, unless it takes a
    prompt over from an earlier frame; a narrow one with the first row to
    emit its last token, if that row would wait at least as many steps as
    there are live rows; neither runs more than ``cap``."""
    cap = steps if cap is None else cap
    if width > 1 and ((began > 0) & (left > 0)).any():
        return cap
    if width > 1:
        return min(cap, max(steps // 2, -(-int(left.max()) // width)))
    first = max(1, int(budget.min()))
    return first if cap - first >= len(budget) else cap


@pytest.mark.parametrize("cur,live,prefill,finish,carried,want", [
    (8, 3, 1, 50, False, 4),    # a one-chunk prompt: half of the frame
    (8, 3, 3, 50, False, 4),
    (8, 3, 5, 50, False, 5),    # ends with the last prefilling row
    (8, 3, 8, 50, False, 8),
    (8, 3, 16, 50, False, 8),   # a long prompt keeps every step
    (8, 3, 1, 50, True, 8),     # and its last frame does: a prompt carried
    (8, 16, 5, 50, True, 8),    # over from an earlier frame runs whole
    (2, 3, 5, 50, True, 2),
    (2, 3, 5, 50, False, 2),    # the policy's cap (pressure, adaptive) holds
    (4, 3, 1, 50, False, 4),
    (1, 1, 1, 1, False, 1),
    (8, 1, 0, 3, False, 3),     # narrow: a lone row's last token ends it
    (8, 5, 0, 3, False, 3),     # 5 steps to wait, 5 rows live
    (8, 6, 0, 3, False, 8),     # more rows than steps to wait: whole
    (8, 16, 0, 1, False, 8),    # a full table keeps its frames
    (8, 1, 0, 8, False, 8),
    (8, 1, 0, 50, False, 8),
    (2, 1, 0, 1, False, 1),
    (2, 2, 0, 1, False, 2),
])
def test_plan_frame_steps(cur, live, prefill, finish, carried, want):
    assert InferenceEngineV2._plan_frame_steps(
        cur, 8, live, prefill, finish, carried) == want


@pytest.fixture(scope="module")
def chunk128_engine():
    """The benchmark's frame shape at a small size: chunks of 128, frames
    of 8 steps, sequences to 2,048."""
    model = build_model("tiny", max_seq_len=2048)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(
        kv_block_size=64, prefill_chunk_size=128, max_tokens_per_step=1024,
        dtype="float32", max_ragged_batch_size=4, frame_steps=8,
        num_kv_blocks=129), params=model.init(jax.random.PRNGKey(0)),
        max_seq_len=2048)


LENGTHS = (1500, 1, 127, 128, 129, 300, 1024)


@POLICIES
def test_planned_frames_emit_the_tokens_of_whole_frames(
        chunk128_engine, planned_against_whole, monkeypatch, policy):
    """Prompts of 1 to 1,500 tokens arrive one a boundary while other rows
    are mid-decode (the longest with them: a serve's slot table grows to
    its buckets once): every request's greedy tokens are those of a run whose
    frames all run their 8 steps, every frame ran what the plan says for
    what the host mirrors showed, one wide and one narrow program served
    both runs, and what a program returns behind its ``n_steps`` rows is
    -1 / not emitted."""
    e = chunk128_engine
    rng = np.random.default_rng(41)
    early = [(100 + i, rng.integers(0, 200, (9,)).astype(np.int32), 150)
             for i in range(2)]
    late = [(n, rng.integers(0, 200, (n,)).astype(np.int32), 6)
            for n in LENGTHS]

    def arrivals():
        yield early + late[:1]
        for req in late[1:]:
            yield []
            yield [req]

    seen = _spy_frames(monkeypatch)
    planned, hist = planned_against_whole(
        e, arrivals, max_new_tokens=8, scheduler=policy())
    assert e.runner.compile_count() == {"frame": 2}
    assert {len(planned[n]) for n in LENGTHS} == {6}
    n_planned = sum(hist.values())
    for width, steps, n, left, budget, toks, emit, began in seen[:n_planned]:
        assert steps == 8 and (width == 1) == (not left.any())
        assert n == _planned(width, steps, left, budget, began), (
            width, left, budget, began)
        toks, emit = np.asarray(toks), np.asarray(emit)
        assert toks.shape == emit.shape == (8, 4)
        assert (toks[n:] == -1).all() and not emit[n:].any()
        assert ((toks[:n] >= 0) == emit[:n]).all()
    assert len(seen) > n_planned and all(f[2] == 8
                                         for f in seen[n_planned:])
    wide = {f[2] for f in seen[:n_planned] if f[0] > 1}
    # one to three chunks: half a frame; 1,024 tokens: all 8; 1,500: 8 and,
    # carried over, 8 for the 4 chunks left
    assert wide == {4, 8}
    assert any(f[2] == 8 and 0 < f[3].max() <= 4 * 128 and f[7].any()
               for f in seen[:n_planned] if f[0] > 1)
    assert any(f[2] < 8 for f in seen[:n_planned] if f[0] == 1)
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_absorb_ignores_rows_past_n_steps(chunk128_engine, monkeypatch):
    """Whatever sits in the emission buffers behind a frame's ``n_steps``
    rows did not happen: planted emissions there change no request's
    tokens and no mirror."""
    from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
    e = chunk128_engine
    rng = np.random.default_rng(43)
    reqs = [(u, rng.integers(0, 200, (n,)).astype(np.int32))
            for u, n in enumerate((5, 200, 70))]
    want = dict(e.serve(iter([reqs]), max_new_tokens=11))
    absorb = DeviceSlotTable.absorb
    planted = []

    def plant(self, toks, emit, width, n_steps=None):
        toks, emit = toks.copy(), emit.copy()
        toks[n_steps:], emit[n_steps:] = 7, True
        planted.append(len(toks) - n_steps)
        return absorb(self, toks, emit, width, n_steps)

    monkeypatch.setattr(DeviceSlotTable, "absorb", plant)
    got = dict(e.serve(iter([reqs]), max_new_tokens=11))
    assert any(planted)
    for u in want:
        np.testing.assert_array_equal(got[u], want[u])
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_the_operand_is_staged_once_a_value():
    """``dispatch_frame`` hands the program its trip count as an int32
    device scalar staged at the value's first use, not as a Python int (one
    host to device write a dispatch); ``None`` (run the capacity) stays so."""
    from deepspeed_tpu.inference.v2 import ragged_manager as rm
    slots = rm.DeviceSlotTable(4, 8, 2, jax.random.PRNGKey(0))
    three = slots._trips(3)
    assert isinstance(three, jax.Array) and three.dtype == np.int32
    assert three.shape == () and int(three) == 3
    assert slots._trips(3) is three and slots._trips(8) is not three
    assert slots._trips(None) is None and set(slots._trip_scalars) == {3, 8}


def test_a_prefix_hit_plans_from_what_is_left(tiny_model_params, monkeypatch):
    """A prompt of seven chunks whose first five are in the prefix cache is
    admitted at ``cached0`` = 80: its wide frame runs the half frame its two
    chunks left ask for, not the seven steps the whole prompt would."""
    model, params = tiny_model_params
    e = _engine(model, params, prefix_cache=True, frame_steps=8)
    rng = np.random.default_rng(44)
    head = rng.integers(0, 200, (80,)).astype(np.int32)
    first = np.concatenate([head, rng.integers(0, 200, (32,)).astype(np.int32)])
    again = np.concatenate([head, rng.integers(0, 200, (32,)).astype(np.int32)])
    seen = _spy_frames(monkeypatch)
    got = dict(e.serve(iter([[(0, first)], [], [], [(1, again)]]),
                       max_new_tokens=4))
    assert e.telemetry.counters["prefix_hits"] == 1
    wide = [(f[2], int(f[3].max())) for f in seen if f[0] > 1]
    assert wide == [(7, 112), (4, 32)]
    alone = _engine(model, params, frame_steps=8)
    want = dict(alone.serve(iter([[(1, again)]]), max_new_tokens=4))
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("how", ["adaptive", "pressure"])
def test_the_plan_runs_under_the_policys_cap(tiny_model_params, monkeypatch,
                                             how):
    """What ``_pick_frame_steps`` (adaptive sizing) and
    ``frame_steps_cap`` (a scheduler under SLO pressure) return is the most
    a frame runs, and an operand like the plan's own count: every frame
    runs ``min(cap, what its rows need)``, through the two programs of an
    engine that does neither."""
    model, params = tiny_model_params
    e = _engine(model, params, frame_steps=8,
                adaptive_frame_steps=how == "adaptive")
    caps = []
    if how == "adaptive":
        pick = InferenceEngineV2._pick_frame_steps
        monkeypatch.setattr(
            InferenceEngineV2, "_pick_frame_steps", staticmethod(
                lambda *a: caps.append(pick(*a)) or caps[-1]))
        sched = None
    else:
        sched = RequestScheduler()
        monkeypatch.setattr(
            RequestScheduler, "frame_steps_cap",
            lambda self, most: caps.append((2, most, 1)[len(caps) % 3])
            or caps[-1])
    rng = np.random.default_rng(45)

    def arrivals():
        for k, n in enumerate((60, 40, 4, 20, 50, 9)):   # one a poll
            yield [(k, rng.integers(0, 200, (n,)).astype(np.int32))]

    seen = _spy_frames(monkeypatch)
    got = dict(e.serve(arrivals(), max_new_tokens=64, scheduler=sched))
    assert len(got) == 6 and all(len(v) == 64 for v in got.values())
    assert len(caps) == len(seen) and len(set(caps)) > 1
    for cap, (width, steps, n, left, budget, *_, began) in zip(caps, seen):
        assert n == _planned(width, steps, left, budget, began, cap), (
            cap, width)
    assert any(n < cap for cap, (_, _, n, *_) in zip(caps, seen))
    assert e.runner.compile_count() == {"frame": 2}


@pytest.mark.parametrize("which", ["self_draft_engine",
                                   "distinct_draft_engine"])
def test_planned_frames_under_an_external_draft(request, which,
                                                planned_against_whole,
                                                greedy_base):
    """``frame_loop_spec`` runs its steps under the same operand: with a
    draft that is always accepted (rows reach their budget sooner than a
    token a step, so a narrow frame's planned end is the latest it can be)
    and one that never is, the tokens are those of whole frames and of the
    run without a draft."""
    e = request.getfixturevalue(which)
    planned, hist = planned_against_whole(
        e, _mid_stream_arrivals, max_new_tokens=8, gamma=2)
    for u in SPEC_PROMPTS:
        np.testing.assert_array_equal(greedy_base[u], planned[u])
    assert min(hist) < 4 and set(e.runner.compile_count()) == {"spec_frame"}
    assert e.kv.free_blocks == e.kv.num_blocks - 1
