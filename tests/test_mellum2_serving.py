"""Mellum2-12B-A2.5B on the paged serving path, against its plain reference.

The preset (``models/config.py`` ``mellum2-12b-a2.5b``) mixes windowed and
global layers, so the engine keeps a cache a KIND (``kv_cache.cache_kinds``):
whole block tables for the global layers, a ring of pages behind the window
for the windowed ones; RoPE differs by kind (YaRN on the global layers) and
every layer routes to experts. The reference is the benchmark's
(``perfbench/configs/mellum2_reference.py``: float32, no cache, the masks
and the frequencies written out), which shares no code with the program.
Sizes here are small; the shape is Mellum2's: 8 layers ``S, S, S, F`` twice,
GQA, a window of 16 over pages of 8 (a ring of 4 pages under chunks of 8),
8 experts top 2 renormalised, YaRN x4 over an original length of 32, and
contexts several times the ring and past the original length.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                 LayeredKVCache, cache_kinds,
                                                 ring_pages)
from deepspeed_tpu.models import build_model, get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 on both sides, summed in another order (pages and a ring against
#: one softmax, rows grouped by expert against every expert dense and
#: masked): measured 2.3e-5 at most over every compared row. Every fault of
#: ``test_tolerance_catches`` reads over 0.01, a hundred tolerances.
LOGIT_TOL = 1e-4

WINDOW, PAGE, CHUNK, SLOTS, MAX_LEN = 16, 8, 8, 4, 128
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 32, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": None}
#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
          "moe_intermediate_size": 32, "vocab_size": 256, "num_experts": 8,
          "num_experts_per_tok": 2, "norm_topk_prob": True,
          "rms_norm_eps": 1e-6, "sliding_window": WINDOW,
          "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
          + ["sliding_attention"] * 3 + ["full_attention"],
          "rope_parameters": {
              "full_attention": YARN,
              "sliding_attention": {"rope_type": "default",
                                    "rope_theta": 10000.0}}}
SHAPE = dict(max_ragged_batch_size=SLOTS, prefill_chunk_size=CHUNK,
             kv_block_size=PAGE, max_tokens_per_step=64, frame_steps=2)


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs", "mellum2_reference.py")
    spec = importlib.util.spec_from_file_location("mellum2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_mellum2(**overrides):
    kw = dict(
        vocab_size=CONFIG["vocab_size"], hidden_size=CONFIG["hidden_size"],
        num_layers=CONFIG["num_hidden_layers"],
        num_heads=CONFIG["num_attention_heads"],
        num_kv_heads=CONFIG["num_key_value_heads"],
        head_dim=CONFIG["head_dim"],
        intermediate_size=CONFIG["intermediate_size"],
        moe_intermediate_size=CONFIG["moe_intermediate_size"],
        num_experts=CONFIG["num_experts"],
        num_experts_per_tok=CONFIG["num_experts_per_tok"],
        sliding_window=WINDOW, window_pattern=(WINDOW,) * 3 + (0,),
        rope_theta=10000.0, rope_yarn=(4.0, 32, 32.0, 1.0, None),
        max_seq_len=MAX_LEN, dtype="float32")
    kw.update(overrides)
    return build_model(get_config("mellum2-12b-a2.5b", **kw))


def scaled_init(model, seed=31):
    """Seeded float32 weights, the layers' matrices scaled up from their
    initial 0.02 so that attention, routing and the experts all move the
    logits (at the initial scale a layer adds a thousandth to the residual
    and any mask, frequency or routing would pass)."""
    params = model.init(jax.random.PRNGKey(seed))
    layers = params["layers"]
    layers["attn"] = {n: w * 4.0 for n, w in layers["attn"].items()}
    layers["mlp"] = {n: w * (10.0 if n == "router" else 8.0)
                     for n, w in layers["mlp"].items()}
    return params


@pytest.fixture(scope="module")
def model_params():
    model = tiny_mellum2()
    return model, scaled_init(model)


def engine(model, params, max_seq_len=MAX_LEN, **config):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(dtype="float32",
                                           **{**SHAPE, **config}),
        params=params, max_seq_len=max_seq_len)


@pytest.fixture(scope="module")
def eng(model_params):
    return engine(*model_params)


def sequences():
    """Two requests (prompt + forced continuation): one several times the
    ring (4 pages = 32 positions) and past YaRN's original 32, one shorter
    than the window."""
    rng = np.random.default_rng(131)
    return {0: (rng.integers(0, 256, 93 + 12).astype(np.int32), 93),
            2: (rng.integers(0, 256, 11 + 9).astype(np.int32), 11)}


def hand_tables(e, seqs, max_len=MAX_LEN, roll=0):
    """Block tables a kind for ``seqs``' slots, pages handed out by hand:
    whole tables for the table kind, ``ring`` pages for a ring kind (rolled
    by ``roll`` slots)."""
    out = []
    for kind in e.runner.kinds or [None]:
        width = max_len // PAGE if kind is None or kind.ring is None \
            else kind.ring
        table = np.zeros((SLOTS, width), np.int32)
        for i, slot in enumerate(seqs):
            table[slot] = 1 + i * width + np.arange(width)
            if kind is not None and kind.ring is not None:
                table[slot] = np.roll(table[slot], roll)
        out.append(table)
    return tuple(out) if e.runner.kinds else out[0]


def paged_steps(e, params, seqs, garbage_seed=7, max_len=MAX_LEN, roll=0):
    """Walk ``seqs`` {slot: (ids, prompt_len)} through the runner's forward
    the way a frame does: prompts in chunks of ``CHUNK`` beside each other,
    then one position a step through the paged caches of both kinds, the
    other slots idle with ``garbage_seed``'s ids under position -1. Yields
    per step (logits (slots, V), {slot: position of its last token}).
    ``roll``: the decode steps find the rings' pages ``roll`` slots from
    where the prefill wrote them."""
    rng = np.random.default_rng(garbage_seed)
    tables = hand_tables(e, seqs, max_len)
    rolled = hand_tables(e, seqs, max_len, roll)
    pools = jax.tree.map(jnp.zeros_like, (e.kv.k, e.kv.v))
    fwd = jax.jit(e.runner._forward)
    done = {slot: 0 for slot in seqs}
    while any(done[s] < len(ids) for s, (ids, _) in seqs.items()):
        prefilling = any(done[s] < plen for s, (_, plen) in seqs.items())
        width = CHUNK if prefilling else 1
        ids = rng.integers(0, 256, (SLOTS, width)).astype(np.int32)
        positions = np.full((SLOTS, width), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        for slot, (seq, plen) in seqs.items():
            at = done[slot]
            n = min(width, plen - at) if at < plen else min(1, len(seq) - at)
            ids[slot, :n] = seq[at:at + n]
            positions[slot, :n] = at + np.arange(n)
            valid[slot], done[slot] = n, at + n
        logits, k, v = fwd(params, ids, positions,
                           tables if prefilling else rolled, valid, *pools)
        pools = (k, v)
        yield np.asarray(logits), {s: done[s] - 1 for s in seqs if valid[s]}


def worst_gap(e, params, reference, config, seqs=None, **walk):
    """Largest |program logit - reference logit| over every row the paged
    walk ends a step on (each chunk's last position, every decode step)."""
    seqs = seqs or sequences()
    rows = {slot: [] for slot in seqs}
    got = {slot: [] for slot in seqs}
    for logits, last in paged_steps(e, params, seqs, **walk):
        for slot, pos in last.items():
            rows[slot].append(pos)
            got[slot].append(logits[slot])
    worst = 0.0
    for slot, (ids, _) in seqs.items():
        want = reference.logits_rows(params, ids, rows[slot], config)
        worst = max(worst, float(np.abs(np.stack(got[slot]) - want).max()))
    return worst


def test_engine_keeps_a_cache_a_kind(eng):
    """Two kinds from the model's config alone: the two global layers on
    whole tables, the six windowed ones on a ring of 4 pages a slot."""
    kinds = eng.runner.kinds
    assert [(k.name, k.layers, k.window, k.ring) for k in kinds] == [
        ("full", (3, 7), 0, None),
        ("window16", (0, 1, 2, 4, 5, 6), WINDOW, 4)]
    assert isinstance(eng.kv, LayeredKVCache)
    assert ring_pages(WINDOW, CHUNK, PAGE) == 4
    assert ring_pages(1024, 128, 128) == 10      # the benchmark's
    full, ring = eng.kv.groups
    assert full.k.shape[0] == 2 and ring.k.shape[0] == 6
    assert ring.num_blocks == SLOTS * 4 + 1
    assert [t.shape for t in eng.kv.k] == [full.k.shape, ring.k.shape]


def test_paged_path_matches_the_reference(eng, model_params, reference):
    """Chunked prefill, then decode through the full kind's tables and the
    window kind's ring after it has wrapped (105 positions over a ring of
    32), YaRN's scaled bands past position 32: every compared row within
    ``LOGIT_TOL`` of the plain reference."""
    _, params = model_params
    gap = worst_gap(eng, params, reference, CONFIG)
    assert gap < LOGIT_TOL, gap


FAULTS = {
    "no-window-mask": {"sliding_window": 4096},
    "unscaled-yarn-band": {"rope_parameters": {
        **CONFIG["rope_parameters"],
        "full_attention": {**YARN, "factor": 1.0, "attention_factor": 1.0}}},
    "no-attention-factor": {"rope_parameters": {
        **CONFIG["rope_parameters"],
        "full_attention": {**YARN, "attention_factor": 1.0}}},
    "yarn-on-the-window-layers": {"rope_parameters": {
        **CONFIG["rope_parameters"], "sliding_attention": YARN}},
    "gate-not-renormalised": {"norm_topk_prob": False},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_tolerance_catches(eng, model_params, reference, fault):
    """The tolerance is tight enough: against a reference with one thing
    wrong (which stands for the program with that thing wrong) the gap is
    hundreds of tolerances."""
    _, params = model_params
    gap = worst_gap(eng, params, reference, {**CONFIG, **FAULTS[fault]})
    assert gap > 100 * LOGIT_TOL, (fault, gap)


def test_tolerance_catches_a_page_from_the_wrong_ring_slot(
        eng, model_params, reference):
    """The decode steps read the ring one slot off: every position behind
    the window comes from the wrong page."""
    _, params = model_params
    seqs = {0: sequences()[0]}
    assert worst_gap(eng, params, reference, CONFIG, seqs=seqs) < LOGIT_TOL
    gap = worst_gap(eng, params, reference, CONFIG, seqs=seqs, roll=1)
    assert gap > 100 * LOGIT_TOL, gap


def test_window_longer_than_every_context_is_the_one_kind_program():
    """With a window no context reaches, the program of two kinds (rings of
    10 pages under tables of 32) computes what the program of one kind
    computes for the same weights without any window: same logits at every
    step of the same walk."""
    plain = dict(rope_yarn=None, max_seq_len=256)
    mixed = tiny_mellum2(sliding_window=64, window_pattern=(64,) * 3 + (0,),
                         **plain)
    alike = tiny_mellum2(sliding_window=None, window_pattern=None, **plain)
    params = scaled_init(mixed)
    e_mixed = engine(mixed, params, max_seq_len=256)
    e_alike = engine(alike, params, max_seq_len=256)
    assert [k.ring for k in e_mixed.runner.kinds] == [None, 10]
    assert e_alike.runner.kinds is None
    assert isinstance(e_alike.kv, BlockedKVCache)
    rng = np.random.default_rng(5)
    seqs = {1: (rng.integers(0, 256, 49 + 8).astype(np.int32), 49),
            3: (rng.integers(0, 256, 20 + 5).astype(np.int32), 20)}
    steps = 0
    for (a, last), (b, _) in zip(
            paged_steps(e_mixed, params, seqs, max_len=256),
            paged_steps(e_alike, params, seqs, max_len=256)):
        for slot in last:
            np.testing.assert_allclose(a[slot], b[slot], atol=2e-5, rtol=0)
        steps += 1
    assert steps > 8


def test_a_ring_no_shorter_than_the_table_is_no_kind():
    """A pattern whose ring would hold the whole context anyway (GPT-Neo
    sizes: window 5, pages of 16, sequences to 64) is one kind, the object
    and the programs it always was."""
    assert cache_kinds((0, 5, 0, 5), 16, 4, 128) is None
    assert cache_kinds(None, 128, 64, 128) is None
    kinds = cache_kinds((1024, 1024, 1024, 0) * 2, 128, 256, 128)
    assert [(k.name, k.window, k.ring, k.layers) for k in kinds] == [
        ("full", 0, None, (3, 7)),
        ("window1024", 1024, 10, (0, 1, 2, 4, 5, 6))]
    # Gemma-2's sizes: window 4,096 under sequences to 8,192 is a ring of 34
    # pages beside a table of 64; under sequences to 4,096 it is one kind
    assert cache_kinds((4096, 0) * 2, 128, 64, 128)[1].ring == 34
    assert cache_kinds((4096, 0) * 2, 128, 32, 128) is None
    # windows that differ among the windowed layers, or no global layer:
    # one pool, each layer under its own (traced) window, as ever
    assert cache_kinds((0, 1024, 4096, 0), 128, 64, 128) is None
    assert cache_kinds((1024, 2048), 128, 64, 128) is None


# ---------------------------------------------------------------------------
# the serve loop: reservation by kind, the drain, the counters
# ---------------------------------------------------------------------------


def serve_all(e, requests, **kw):
    """{uid: tokens} of ``requests`` [(uid, prompt, limit)] served
    together, and each boundary's pages in use by kind."""
    out, seen = {}, []
    arrivals = iter([[(uid, p, limit) for uid, p, limit in requests]])
    for item in e.serve(arrivals, temperature=0.0, yield_boundaries=True,
                        **kw):
        if isinstance(item, tuple):
            out[item[0]] = item[1]
        else:
            seen.append(dict((k, n) for k, n, _ in e.kv.in_use()[0]))
    return out, seen


def test_serve_reserves_by_kind_and_drains_clean(eng, model_params,
                                                 reference):
    """Admission reserves ``blocks_for(prompt + limit + 1)`` pages of the
    table kind and ``min(that, ring)`` of the ring kind; retirement frees
    both; the served tokens are the reference's greedy choices (teacher
    forced, as the benchmark checks them)."""
    _, params = model_params
    rng = np.random.default_rng(77)
    requests = [(11, rng.integers(0, 256, 70).astype(np.int32), 9),
                (12, rng.integers(0, 256, 9).astype(np.int32), 4),
                (13, rng.integers(0, 256, 33).astype(np.int32), 6)]
    out, seen = serve_all(eng, requests)
    pages = [-(-(len(p) + limit + 1) // PAGE) for _, p, limit in requests]
    assert seen[0] == {"full": sum(pages),
                       "window16": sum(min(n, 4) for n in pages)}
    assert seen[-1] == {"full": 0, "window16": 0}
    for g in eng.kv.groups:
        assert g.free_blocks == g.num_blocks - 1       # the trash page
    assert not eng.state.seqs
    for uid, prompt, limit in requests:
        toks = [int(t) for t in out[uid]]
        assert len(toks) == limit
        ids = list(prompt) + toks[:-1]
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + limit)
        want = reference.logits_rows(params, ids, rows, CONFIG)
        gaps = want.max(-1) - want[np.arange(limit), toks]
        assert gaps.max() < LOGIT_TOL, (uid, gaps)


def test_layered_counters_and_gauges(eng):
    """The stat vector of a model of mixed kinds ends with the attention's
    work summed over the layers under each layer's own window; the host
    mirror below replays the frames' steps exactly."""
    rng = np.random.default_rng(78)
    plen, limit = 50, 7
    serve_all(eng, [(21, rng.integers(0, 256, plen).astype(np.int32), limit)])
    c = eng.telemetry.counters
    read = pairs = ring = 0
    steps = [(at, min(CHUNK, plen - at)) for at in range(0, plen, CHUNK)] \
        + [(plen + i, 1) for i in range(limit - 1)]
    for cached, w in steps:
        for win, n in ((0, 2), (WINDOW, 6)):
            kv = min(cached + w, win + w) if win else cached + w
            read += n * kv
            pairs += n * w * kv
            ring += n * kv if win else 0
    assert c["kv_positions_read_layers_narrow"] \
        + c["kv_positions_read_layers_wide"] == read
    assert c["attn_pairs_layers_narrow"] + c["attn_pairs_layers_wide"] == pairs
    assert c["kv_positions_read_window_narrow"] \
        + c["kv_positions_read_window_wide"] == ring
    assert c["kv_positions_read_layers_narrow"] > 0
    assert c["kv_positions_read_layers_wide"] > 0
    # experts: every live token, top 2, in each of the 8 layers
    assert c["expert_rows"] == (plen + limit - 1) * 2 * 8
    frames = c["frames"]
    assert c["context_tokens_reserved_sum"] > 0
    assert c["kv_bytes_in_use_sum"] > 0
    # while the request lived it held 8 pages of the table kind (58 tokens)
    # and the ring's 4; a page is 2 or 6 layers x 2 x 2 heads x 8 x 16 x 4 B
    held = -(-(plen + limit + 1) // PAGE)
    page = 2 * 2 * PAGE * 16 * 4
    peak = eng.telemetry.kind_gauges["kv_blocks_in_use_peak"]
    assert peak == {"full": held, "window16": 4}
    assert c["kv_bytes_in_use_sum"] <= frames * (held * 2 + 4 * 6) * page
    assert eng.telemetry.gauges["kv_blocks_in_use_peak"] == held + 1


def test_eviction_frees_every_kind(eng):
    """``release_blocks`` (the eviction path) gives back the ring's pages
    with the table's and leaves the descriptor re-admittable."""
    seq = eng.state.get_or_create_sequence(901)
    assert eng.state.ensure_capacity(seq, 75)
    assert (len(seq.blocks), [len(r) for r in seq.ring_blocks]) == (10, [4])
    assert eng.state.ensure_capacity(seq, 20)          # nothing to add
    eng.state.release_blocks(seq)
    assert seq.blocks == [] and seq.ring_blocks == []
    assert eng.state.ensure_capacity(seq, 9)
    assert (len(seq.blocks), [len(r) for r in seq.ring_blocks]) == (2, [2])
    eng.state.flush_sequence(901)
    for g in eng.kv.groups:
        assert g.free_blocks == g.num_blocks - 1


def test_ring_pool_exhaustion_defers_whole(eng):
    """All or nothing: a sequence the ring kind cannot hold takes no page
    of the table kind either."""
    ring = eng.kv.groups[1]
    hog = ring.allocator.allocate(ring.free_blocks - 1)
    seq = eng.state.get_or_create_sequence(902)
    free = eng.kv.free_blocks
    assert not eng.state.ensure_capacity(seq, 40)
    assert eng.kv.free_blocks == free and not seq.blocks
    ring.allocator.free(hog)
    eng.state.flush_sequence(902)


@pytest.mark.parametrize("what,config", [
    ("tp=2", dict(tp=2)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("swap tier", dict(kv_swap_dir="/nonexistent/tier")),
    ("handoff", dict(role="prefill")),
    ("int8", dict(kv_dtype="int8")),
])
def test_refused_at_build(model_params, what, config):
    """What moves a sequence's pages by one block list, or shards or packs
    the pools, is refused for a model of mixed kinds, loudly and with the
    reason."""
    model, params = model_params
    with pytest.raises(NotImplementedError, match="keeps a cache a kind"):
        engine(model, params, **config)


def test_refused_draft_and_swap_tier(eng, model_params):
    model, params = model_params
    with pytest.raises(NotImplementedError, match="a draft model"):
        InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype="float32", **SHAPE), params=params, max_seq_len=MAX_LEN,
            draft_model=model, draft_params=params)
    with pytest.raises(NotImplementedError, match="a draft model"):
        eng.attach_draft(model, params)
    with pytest.raises(NotImplementedError, match="a swap tier"):
        eng.attach_kv_tier(object())
    assert not eng.state.seqs


# ---------------------------------------------------------------------------
# the stepwise API (put / step) hands the runner's chunk programs a table a
# kind, and generate() is a closed batch through serve(), so the patterned
# models the engine always served that way (Gemma-2's window_pattern,
# GPT-Neo's local_attention_every) keep running when their ring is shorter
# than the table
# ---------------------------------------------------------------------------

PATTERNS = {"gemma2": dict(window_pattern=(WINDOW, 0)),
            "gpt-neo": dict(local_attention_every=2)}


def patterned(pattern):
    model = build_model(get_config(
        "tiny", num_layers=4, sliding_window=WINDOW, max_seq_len=MAX_LEN,
        dtype="float32", **PATTERNS[pattern]))
    params = model.init(jax.random.PRNGKey(7))
    # scaled up from the initial 0.02 so that the greedy tokens wander
    for part, scale in (("attn", 4.0), ("mlp", 8.0)):
        params["layers"][part] = {
            n: w * scale for n, w in params["layers"][part].items()}
    return model, params


@pytest.mark.parametrize("api", ["generate", "generate-queued", "step"])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_stepwise_api_serves_a_patterned_model_by_kind(pattern, api):
    """Greedy tokens through caches by kind (ring of 4 pages beside a table
    of 16) equal those of the one pool under traced windows (a chunk so
    wide that the ring would be no shorter than the table), prompts several
    times the ring and shorter than the window; every page comes back.
    ``generate-queued``: two slots for the three prompts, so the third is
    admitted when a row retires, into a ring another request has used."""
    model, params = patterned(pattern)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.cfg.vocab_size, n).astype(np.int32)
               for n in (70, 11, 33)]

    def run(e):
        if api != "step":
            return [t.tolist() for t in e.generate(prompts,
                                                   max_new_tokens=20)]
        e.put([0, 1, 2], prompts)
        out = {0: [], 1: [], 2: []}
        while min(len(t) for t in out.values()) < 20:
            for uid, tok in e.step().items():
                out[uid].append(tok)
        e.flush([0, 1, 2])
        return [out[u][:20] for u in (0, 1, 2)]

    slots = dict(max_ragged_batch_size=2) if api == "generate-queued" else {}
    by_kind = engine(model, params, **slots)
    assert isinstance(by_kind.kv, LayeredKVCache)
    assert [k.ring for k in by_kind.runner.kinds] == [None, 4]
    one_pool = engine(model, params, prefill_chunk_size=128,
                      max_tokens_per_step=512, **slots)
    assert one_pool.runner.kinds is None
    assert run(by_kind) == run(one_pool)
    if slots:
        assert by_kind.telemetry.counters["admission_deferrals"] > 0
    assert not by_kind.state.seqs
    assert [g.free_blocks for g in by_kind.kv.groups] == [
        g.num_blocks - 1 for g in by_kind.kv.groups]


def test_planned_frames_emit_the_tokens_of_whole_frames(
        model_params, planned_against_whole):
    """Tables by kind and rings in the carry of a frame whose step count is
    an operand: prompts of one to nine chunks arriving while others decode
    get the tokens of a run whose frames all run their 8 steps, through the
    same two programs, and both kinds drain."""
    e = engine(*model_params, frame_steps=8)
    rng = np.random.default_rng(78)
    reqs = [(uid, rng.integers(0, 256, n).astype(np.int32), limit)
            for uid, n, limit in ((21, 70, 9), (22, 9, 14), (23, 33, 6),
                                  (24, 8, 5))]
    planned, hist = planned_against_whole(
        e, lambda: iter([batch for r in reqs for batch in ([r], [])]))
    assert {u: len(t) for u, t in planned.items()} \
        == {21: 9, 22: 14, 23: 6, 24: 5}
    assert 4 in hist and max(hist) == 8      # 9 chunks: 8 steps; 1-5: half
    for g in e.kv.groups:
        assert g.free_blocks == g.num_blocks - 1
