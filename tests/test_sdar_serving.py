"""SDAR-30B-A3B-Chat on the paged serving path, against its plain reference.

The preset (``models/config.py`` ``sdar-30b-a3b``) generates by diffusion
over blocks: a position sees its whole block, the logits at a position score
the token AT it, a row past its prompt holds a block of L positions on the
frame program's carry and each of its steps either denoises it (nothing out,
no K, V kept) or commits it (L tokens or fewer out), and a commit that does
not end the row is the next block's first denoising step too: one forward of
2 L positions. The reference is the benchmark's
(``perfbench/configs/sdar_moe_reference.py``: float32, one sequence, no
paging, S + 1 forwards a block), which shares no code with the program.
Sizes here are small and keep every ratio that matters: GQA, a norm a head,
top 2 of 8 experts renormalised, L = 4.

One engine a schedule (module fixtures) and ONE jitted forward a width are
shared by every case.
"""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
from deepspeed_tpu.models import build_model, get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 on both sides, summed in another order (rows grouped by expert
#: against experts gathered by choice, pages and the block's own keys
#: against one softmax): measured under 2e-5 over every compared row, on
#: logits up to 6. Every planted fault below (a causal mask, a shifted
#: logit, a denoising step's K, V read as committed) reads over 0.05: more
#: than three orders outside.
LOGIT_TOL = 2e-4
FAULT_FLOOR = 0.05

MASK_ID, BLK = 255, 4
#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1e6,
          "rms_norm_eps": 1e-6, "vocab_size": 256, "num_experts": 8,
          "num_experts_per_tok": 2, "norm_topk_prob": True}
SHAPE = dict(max_ragged_batch_size=4, prefill_chunk_size=16, kv_block_size=8,
             max_tokens_per_step=1024, frame_steps=4, dtype="float32")
SLOTS, WIDTH, PAGE, SEQ = 4, 16, 8, 256


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


def diffusion(steps=4, strategy="low_confidence_static", threshold=0.9):
    return {"block_length": BLK, "denoising_steps": steps,
            "remasking_strategy": strategy, "confidence_threshold": threshold,
            "mask_token_id": MASK_ID}


def config_of(**kw):
    return dict(CONFIG, diffusion=diffusion(**kw))


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs", "sdar_moe_reference.py")
    spec = importlib.util.spec_from_file_location("sdar_moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the benchmark's blocks are sized for 3k tokens
    mod.Q_BLOCK, mod.KV_BUCKET = 16, 64
    return mod


def tiny_sdar(steps=4, strategy="low_confidence_static", threshold=0.9):
    cfg = get_config(
        "sdar-30b-a3b", vocab_size=CONFIG["vocab_size"],
        hidden_size=CONFIG["hidden_size"], num_layers=2,
        num_heads=CONFIG["num_attention_heads"],
        num_kv_heads=CONFIG["num_key_value_heads"],
        head_dim=CONFIG["head_dim"], moe_intermediate_size=32,
        num_experts=CONFIG["num_experts"],
        num_experts_per_tok=CONFIG["num_experts_per_tok"], max_seq_len=SEQ,
        dtype="float32", block_length=BLK, denoising_steps=steps,
        remasking_strategy=strategy, confidence_threshold=threshold,
        mask_token_id=MASK_ID)
    return build_model(cfg)


@pytest.fixture(scope="module")
def params():
    """Seeded float32 weights, the layers' matrices scaled up from their
    initial 0.02 so that attention, routing and the experts all move the
    logits, the router wider still so that the top 2 of 8 carry most of the
    mass, the q / k norms' weights drawn around 1."""
    raw = tiny_sdar().init(jax.random.PRNGKey(47))

    def widen(path, w):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        names = [p.key for p in path if hasattr(p, "key")]
        if name == "router":
            return w * 20.0
        if "q_norm" in names or "k_norm" in names:
            return w + 0.3 * jax.random.normal(jax.random.PRNGKey(len(names)),
                                               w.shape, w.dtype)
        if w.ndim >= 3 or name == "lm_head":
            return w * 6.0
        return w
    return jax.tree_util.tree_map_with_path(widen, raw)


def engine_of(params, **kw):
    return InferenceEngineV2(tiny_sdar(**kw),
                             RaggedInferenceEngineConfig(**SHAPE),
                             params=params, max_seq_len=SEQ)


@pytest.fixture(scope="module")
def engine(params):
    """Blocks of 4 in 4 steps, static: the cell's schedule."""
    return engine_of(params)


def prompts_of(lengths, seed=0, high=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(8, high, size=n).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# the forward: prefill chunks and block steps through the pool against the
# reference's one-shot forward under the block mask
# ---------------------------------------------------------------------------


class Served:
    """The runner's ``_forward`` over pools of its own, ONE jitted program a
    width: what a frame step calls, without the frame around it."""

    def __init__(self, params):
        self.params = params
        self.runner = PagedModelRunner(tiny_sdar(), PAGE, SEQ // PAGE)
        cfg = self.runner.cfg
        shape = (cfg.num_layers, cfg.kv_heads, 1 + SEQ // PAGE, PAGE,
                 cfg.dims_per_head)
        self.k = jnp.zeros(shape, jnp.float32)
        self.v = jnp.zeros(shape, jnp.float32)
        self.tables = jnp.arange(1, 1 + SEQ // PAGE, dtype=jnp.int32)[None]
        self.fwd = jax.jit(self.runner._forward)

    def step(self, ids, start):
        """Logits (L, V) at the first L of ``ids`` placed at ``start`` ..;
        the step's K, V are written into the pages."""
        ids = np.asarray(ids, np.int32)[None]
        pos = (start + np.arange(ids.shape[1], dtype=np.int32))[None]
        logits, k, v = self.fwd(self.params, jnp.asarray(ids),
                                jnp.asarray(pos), self.tables,
                                jnp.asarray([ids.shape[1]], jnp.int32),
                                self.k, self.v)
        self.k, self.v = k, v
        return np.asarray(logits[0])


@pytest.fixture(scope="module")
def walked(params, reference):
    """A sequence of 40 tokens, to be served as a prefill chunk of 16 and
    six block steps, and the reference's ONE forward of the whole under the
    block mask."""
    ids = prompts_of([40], seed=5)[0]
    want = np.asarray(reference.forward(params, ids, config_of())[0])
    return ids, want


def test_prefill_chunks_and_block_steps_equal_the_one_shot_forward(
        params, reference, walked):
    """Chunks through the pool under the block mask, then mask-free blocks
    a block at a time over the pages and their own keys: the logits AT each
    position (no shift) are the reference's one-shot forward's."""
    ids, want = walked
    served = Served(params)
    worst = 0.0
    got = served.step(ids[:16], 0)
    worst = max(worst, np.abs(got - want[:BLK]).max())
    for s in range(16, 40, BLK):
        got = served.step(ids[s:s + BLK], s)
        worst = max(worst, np.abs(got - want[s:s + BLK]).max())
    assert worst < LOGIT_TOL, worst


@pytest.mark.parametrize("fault", ["causal_mask", "shifted_logit",
                                   "kept_denoising_kv"])
def test_the_tolerance_catches_a_planted_fault(params, reference, walked,
                                               fault, monkeypatch):
    """What the comparison above is tight enough to refuse: a mask that is
    causal by position, logits read one position back, and a denoising
    step's K, V read as if committed."""
    ids, want = walked
    served = Served(params)
    if fault == "causal_mask":
        # the served path with the block's last position not handed in
        real = model_runner._paged_attention
        monkeypatch.setattr(
            model_runner, "_paged_attention",
            lambda *a, visible_to=None, **kw: real(*a, **kw))
        served.fwd = jax.jit(served.runner._forward)
        served.step(ids[:16], 0)
        got = served.step(ids[16:20], 16)
        gap = np.abs(got - want[16:20]).max()
    elif fault == "shifted_logit":
        served.step(ids[:16], 0)
        got = served.step(ids[16:20], 16)
        gap = np.abs(got[:-1] - want[17:20]).max()
    else:
        # a denoising step writes rows of a half-masked block at [16, 20);
        # were they read as committed by the next block's step (the
        # watermark moved without the commit's forward), its logits move
        served.step(ids[:16], 0)
        masked = np.where([False, True, False, True], MASK_ID, ids[16:20])
        served.step(masked, 16)
        got = served.step(ids[20:24], 20)
        gap = np.abs(got - want[20:24]).max()
        # and with the commit's forward in between they do not
        served.step(ids[16:20], 16)
        again = served.step(ids[20:24], 20)
        assert np.abs(again - want[20:24]).max() < LOGIT_TOL
    assert gap > FAULT_FLOOR, gap


def test_reference_walk_equals_its_one_shot_forward(params, reference):
    """The reference keeps the K, V it computed for committed blocks; the
    block mask makes them final: its block step over kept K, V gives the
    logits its ONE forward of the final sequence gives."""
    config = config_of()
    prompt = prompts_of([10], seed=2)[0]
    trace = []
    out = reference.generate(params, prompt, 12, config, trace=trace)
    final = list(prompt) + out
    whole = np.asarray(reference.forward(
        params, np.asarray(final[:len(final) // BLK * BLK], np.int32),
        config)[0])
    walk = reference._Walk(params, final[:8], len(final), config)
    for s in range(8, len(final) - BLK + 1, BLK):
        got = np.asarray(walk.step(final[s:s + BLK])[0])
        assert np.abs(got - whole[s:s + BLK]).max() < LOGIT_TOL
        walk.commit(final[s:s + BLK])
    # a denoising step costs a forward a masked position at S = L, and the
    # prompt's remainder (10 = 8 + 2) is held unmasked in the first block
    assert [len(t[2]) for t in trace[:2]] == [2, 1]
    assert len(trace) == 2 + 4 + 4 + 4      # the last block is cut to 2


# ---------------------------------------------------------------------------
# through serve(): tokens against the reference's walk
# ---------------------------------------------------------------------------


@pytest.fixture()
def mirrored(monkeypatch):
    """``DeviceSlotTable.absorb`` checked against the graph at every frame
    boundary: the host mirrors of the watermark and of the tokens produced
    equal the device's carry, row by live row."""
    real, seen = DeviceSlotTable.absorb, []

    def absorb(self, toks, emit, width, n_steps=None):
        out = real(self, toks, emit, width, n_steps)
        live = self.uid_of_slot >= 0
        cached, produced = np.asarray(self.cached), np.asarray(self.produced)
        assert (cached[live] == self.cached_h[live]).all(), \
            (cached, self.cached_h)
        assert (produced[live] == self.produced_h[live]).all()
        assert (self.cached_h[live] % BLK == 0).all()
        seen.append(int(live.sum()))
        return out

    monkeypatch.setattr(DeviceSlotTable, "absorb", absorb)
    return seen


def test_served_tokens_equal_the_reference(engine, params, reference,
                                           mirrored):
    """Prompt remainders 0..3 (and a prompt shorter than a block), more
    requests than slots (slots reused: admission masks a new tenant's block
    whole), a budget that cuts the last block, rows past their prompt
    riding wide steps beside prefilling rows; ``absorb``'s mirror equals the
    graph at every boundary."""
    lengths = [16, 17, 18, 19, 3, 40, 21, 8, 5]
    prompts = prompts_of(lengths, seed=1)
    outs = engine.generate(prompts, max_new_tokens=10)
    config = config_of()
    for prompt, out in zip(prompts, outs):
        assert list(out) == reference.generate(params, prompt, 10, config)
    c = engine.telemetry.counters
    assert mirrored and max(mirrored) == SLOTS
    assert c["tokens_emitted"] == 10 * len(prompts)
    # S forwards a block (fewer for a first block's remainder), the first
    # of them fused with the commit of the block before; one forward more
    # a request, its last block's commit alone
    assert c["target_forwards"] == \
        c["bd_denoise_forwards"] + c["bd_commit_forwards"]
    assert c["bd_blocks_committed"] == \
        c["bd_commit_forwards"] + c["bd_fused_forwards"]
    assert c["bd_positions_unmasked"] == c["bd_denoise_forwards"]
    blocks = sum(-(-(n % BLK + 10) // BLK) for n in lengths)
    assert c["bd_blocks_committed"] == blocks
    assert c["bd_commit_forwards"] == len(lengths)
    assert c["bd_positions_unmasked"] == \
        blocks * BLK - sum(n % BLK for n in lengths)
    assert c["bd_masked_positions_computed"] >= c["bd_positions_unmasked"]
    # some block row rode a wide step: a frame that prefilled also forwarded
    # rows past their prompt (9 requests over 4 slots)
    assert c["wide_steps"] > 0 and c["prefill_tokens"] == sum(
        n // BLK * BLK for n in lengths)
    # every expert row is a live position's: 2 a position and layer, and a
    # fused forward is two blocks of positions
    assert c["expert_rows"] == 2 * 2 * (
        c["prefill_tokens"]
        + BLK * (c["target_forwards"] + c["bd_fused_forwards"]))


def test_a_prompt_may_hold_the_mask_token(engine, params, reference):
    """Whether a position is masked is state: a prompt's ``mask_token_id``
    in its whole blocks and in its remainder is a token like any other."""
    prompt = prompts_of([22], seed=3)[0]
    prompt[[2, 13, 20, 21]] = MASK_ID
    out, = engine.generate([prompt], max_new_tokens=6)
    assert list(out) == reference.generate(params, prompt, 6, config_of())
    assert len(out) == 6


def test_eos_inside_a_block_ends_the_row_there(engine, params, reference):
    """The block's positions up to and including the first EOS go out, the
    rest of it does not; the row is done."""
    prompt = prompts_of([13], seed=4)[0]
    free = reference.generate(params, prompt, 16, config_of())
    # an EOS the model emits mid-block (13 = 12 + 1: its first block gives
    # three tokens, so its fifth is the second position of its second block)
    eos = free[4]
    cut = free.index(eos) + 1
    assert cut < 16
    out, = engine.generate([prompt], max_new_tokens=16, eos_token_id=eos)
    assert list(out) == free[:cut]
    assert list(out) == reference.generate(params, prompt, 16, config_of(),
                                           eos=eos)


@pytest.mark.parametrize("steps", [1, 2])
def test_fewer_denoising_steps(params, reference, steps):
    """S = 1 unmasks a whole block in one forward, S = 2 two positions a
    step: 1 and 2 forwards a block, and one more a request."""
    eng = engine_of(params, steps=steps)
    prompts = prompts_of([16, 18, 7], seed=6)
    outs = eng.generate(prompts, max_new_tokens=8)
    config = config_of(steps=steps)
    for prompt, out in zip(prompts, outs):
        assert list(out) == reference.generate(params, prompt, 8, config)
    c = eng.telemetry.counters
    assert c["bd_blocks_committed"] == 2 + 3 + 3
    assert c["bd_commit_forwards"] == 3
    assert c["bd_fused_forwards"] == 1 + 2 + 2
    # a whole block: S forwards; a remainder of 2 or 3: 1 at either S
    assert c["bd_denoise_forwards"] == (2 * steps) + (1 + 2 * steps) \
        + (1 + 2 * steps)


@pytest.mark.parametrize("threshold,passes", [(0.02, True), (0.999, False)])
def test_low_confidence_dynamic(params, reference, engine, threshold, passes):
    """A threshold that positions pass unmasks them all at once (fewer
    forwards than static's); one that none passes falls to the static rule:
    the same tokens and the same count of forwards."""
    eng = engine_of(params, strategy="low_confidence_dynamic",
                    threshold=threshold)
    prompts = prompts_of([16, 9], seed=7)
    outs = eng.generate(prompts, max_new_tokens=8)
    config = config_of(strategy="low_confidence_dynamic", threshold=threshold)
    for prompt, out in zip(prompts, outs):
        assert list(out) == reference.generate(params, prompt, 8, config)
    static = engine.generate(prompts, max_new_tokens=8)
    n_static = engine.telemetry.counters["bd_denoise_forwards"]
    n = eng.telemetry.counters["bd_denoise_forwards"]
    if passes:
        assert n < n_static
        assert eng.telemetry.counters["bd_positions_unmasked"] == \
            engine.telemetry.counters["bd_positions_unmasked"]
    else:
        assert n == n_static
        assert all(list(a) == list(b) for a, b in zip(outs, static))


def test_temperature_draws_by_its_rng(engine):
    """At a temperature a masked position's token is a draw, a function of
    the rng handed to ``serve()``: the same rng gives the same tokens,
    another other tokens, and neither the greedy ones."""
    prompts = prompts_of([12, 17], seed=8)

    def run(rng):
        done = dict(engine.serve(
            iter([[(i, p, 12) for i, p in enumerate(prompts)]]),
            temperature=1.5, rng=rng))
        return [list(done[i]) for i in range(len(prompts))]

    a, b, c = run(11), run(11), run(12)
    greedy = [list(o) for o in engine.generate(prompts, max_new_tokens=12)]
    assert a == b and a != c and a != greedy
    assert all(len(o) == 12 and max(o) < CONFIG["vocab_size"] for o in a)


# ---------------------------------------------------------------------------
# the fused step: a block's commit rides the next block's first denoising
# step, against a plain walk of S + 1 forwards a block through the same
# ``_forward``
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def walker(params):
    return Served(params)


def plain_walk(served, prompt, budget, *, eos=None, per_step=1,
               threshold=None):
    """The block-diffusion walk with nothing fused, a forward a step
    through the runner's ``_forward`` (chunks of 16 and of L): a block's
    denoising steps, each unmasking the ``per_step`` masked positions of
    largest confidence (every one past ``threshold`` where those are at
    least as many), then ONE forward of the mask-free block, whose K, V
    stay. Returns (tokens, forwards of block rows, blocks committed)."""
    prompt = np.asarray(prompt, np.int32)
    whole, at = len(prompt) // BLK * BLK, 0
    while at < whole:
        n = 16 if whole - at >= 16 else BLK
        served.step(prompt[at:at + n], at)
        at += n
    out, forwards, blocks, ended = [], 0, 0, False
    while len(out) < budget and not ended:
        rest = prompt[at:at + BLK]
        tok = np.concatenate([rest, np.zeros(BLK - len(rest), np.int32)])
        masked = np.arange(BLK) >= len(rest)
        while masked.any():
            logits = served.step(np.where(masked, MASK_ID, tok), at)
            forwards += 1
            x0 = logits.argmax(-1)
            z = logits.astype(np.float64)
            logc = z[np.arange(BLK), x0] - np.log(np.exp(
                z - z.max(-1, keepdims=True)).sum(-1)) - z.max(-1)
            cand = sorted(np.flatnonzero(masked), key=lambda j: (-logc[j], j))
            pick = cand[:per_step]
            if threshold is not None:
                passing = [j for j in cand if logc[j] > np.log(threshold)]
                if len(passing) >= per_step:
                    pick = passing
            tok[pick], masked[pick] = x0[pick], False
        served.step(tok, at)                     # the commit's own forward
        forwards += 1
        blocks += 1
        for j in range(BLK):
            if at + j < len(prompt) or len(out) >= budget or ended:
                continue
            out.append(int(tok[j]))
            ended = tok[j] == eos
        at += BLK
    return out, forwards, blocks


#: (prompt lengths, budget, schedule): what the fused step must not move
FUSED_CASES = {
    "prompt_ends_inside_a_block": ([18, 7], 12, {}),
    "prompt_ends_on_an_edge": ([16, 8], 12, {}),
    "budget_ends_inside_a_block": ([16, 21], 10, {}),
    "two_positions_a_step": ([17, 12], 11, dict(steps=2)),
    "dynamic_unmasks_more_than_a_step_must": (
        [16, 9], 12, dict(strategy="low_confidence_dynamic", threshold=0.02)),
}


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_fused_tokens_equal_a_plain_walk(params, engine, walker, reference,
                                         mirrored, case):
    """Token for token, and the forwards counted: ceil(m / per step) a block
    of m masked positions and ONE more a request, where the walk takes one
    more a block."""
    lengths, budget, kw = FUSED_CASES[case]
    eng = engine_of(params, **kw) if kw else engine
    cfg = eng.model.cfg
    prompts = prompts_of(lengths, seed=len(case))
    outs = eng.generate(prompts, max_new_tokens=budget)
    forwards = blocks = 0
    for prompt, out in zip(prompts, outs):
        want, f, b = plain_walk(
            walker, prompt, budget, per_step=cfg.unmask_per_step,
            threshold=cfg.confidence_threshold
            if cfg.remasking_strategy == "low_confidence_dynamic" else None)
        assert list(out) == want
        assert want == reference.generate(params, prompt, budget,
                                          config_of(**kw))
        forwards, blocks = forwards + f, blocks + b
    c = eng.telemetry.counters
    assert c["bd_blocks_committed"] == blocks
    assert c["bd_commit_forwards"] == len(prompts)
    assert c["bd_fused_forwards"] == blocks - len(prompts)
    # the walk's forwards less the commits that rode a denoising step
    assert c["target_forwards"] == forwards - c["bd_fused_forwards"]
    if not kw:
        assert c["target_forwards"] == len(prompts) + sum(
            BLK * -(-(n % BLK + budget) // BLK) - n % BLK for n in lengths)
    if case.startswith("dynamic"):
        # some step unmasked more than L / S
        assert c["bd_positions_unmasked"] > c["bd_denoise_forwards"]
    assert mirrored


def test_an_eos_in_a_committed_block_ends_the_row_unfused(
        params, engine, walker, mirrored):
    """The block that holds the EOS is the row's last: it commits alone,
    no block behind it is denoised, and the masked positions the forwards
    computed are the walk's own."""
    prompt = prompts_of([13], seed=4)[0]
    free, *_ = plain_walk(walker, prompt, 16)
    eos = free[4]           # the second position of the second block
    cut = free.index(eos) + 1
    assert cut < 16
    out, = engine.generate([prompt], max_new_tokens=16, eos_token_id=eos)
    want, forwards, blocks = plain_walk(walker, prompt, 16, eos=eos)
    assert list(out) == want == free[:cut]
    c = engine.telemetry.counters
    assert c["bd_blocks_committed"] == blocks == -(-(1 + cut) // BLK)
    assert c["bd_commit_forwards"] == 1
    assert c["bd_fused_forwards"] == blocks - 1
    assert c["target_forwards"] == forwards - (blocks - 1)
    # a block's m masked positions are computed m, m - 1 .. 1 times at one
    # a step: nothing behind the last block
    assert c["bd_masked_positions_computed"] == 3 * 4 // 2 + (blocks - 1) * 10
    assert c["eos_events"] == 1


def test_a_fused_step_rides_a_wide_frame(params, engine, walker, mirrored,
                                         monkeypatch):
    """A row past its prompt beside a row that prefills: its fused step
    takes 2 L positions of the chunk's width, and both rows' tokens are the
    plain walk's."""
    from deepspeed_tpu.inference.v2.telemetry import ServingTelemetry
    frames, real = [], ServingTelemetry.on_frame

    def on_frame(self, *, delta, width, **kw):
        frames.append((width, int(delta[-1])))
        return real(self, delta=delta, width=width, **kw)

    monkeypatch.setattr(ServingTelemetry, "on_frame", on_frame)
    first, second = prompts_of([18, 40], seed=12)
    done = dict(engine.serve(iter([[(0, first, 14)], [], [(1, second, 6)]])))
    assert list(done[0]) == plain_walk(walker, first, 14)[0]
    assert list(done[1]) == plain_walk(walker, second, 6)[0]
    assert any(width == WIDTH and fused for width, fused in frames), frames
    assert any(width == 2 * BLK and fused for width, fused in frames), frames
    assert {width for width, _ in frames} == {WIDTH, 2 * BLK}


def test_rows_past_the_watermark_are_never_read(params, reference):
    """What a denoising step and a fused step's second half write lies at
    and past the watermark: with every such row of both pools overwritten
    between steps (frames of one step), the tokens stand."""
    eng = InferenceEngineV2(
        tiny_sdar(), RaggedInferenceEngineConfig(**dict(SHAPE, frame_steps=1)),
        params=params, max_seq_len=SEQ)
    prompts = prompts_of([18, 16, 5], seed=13)
    want = [list(o) for o in eng.generate(prompts, max_new_tokens=10)]
    assert want == [reference.generate(params, p, 10, config_of())
                    for p in prompts]
    real, spoiled = DeviceSlotTable.absorb, []

    def absorb(self, toks, emit, width, n_steps=None):
        out = real(self, toks, emit, width, n_steps)
        tables = np.asarray(self.tables)
        at = np.arange(tables.shape[1] * PAGE)
        rows = [i for i in range(self.n_slots) if self.uid_of_slot[i] >= 0]
        for i in rows:
            past = at[at >= self.cached_h[i]]
            page, slot = tables[i, past // PAGE], past % PAGE
            # (a page past the row's last is page 0, which nobody reads)
            eng.kv.k = eng.kv.k.at[:, :, page, slot].set(1e4)
            eng.kv.v = eng.kv.v.at[:, :, page, slot].set(-1e4)
            spoiled.append(int((page > 0).sum()))
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(DeviceSlotTable, "absorb", absorb)
        got = [list(o) for o in eng.generate(prompts, max_new_tokens=10)]
    assert got == want
    assert sum(spoiled) > 100 and eng.telemetry.counters["bd_fused_forwards"]


@pytest.mark.parametrize("steps", [4, 2, 1])
def test_host_mirror_equals_a_walk_of_the_devices_plan(steps):
    """``_block_scan_body`` itself, a step at a time over logits drawn at
    random (a stub for the forward), against ``DeviceSlotTable``'s replay
    of its emissions: watermark, tokens produced and done flags equal the
    carry's after every step, at both widths; ``_block_steps_left`` names
    the step at which a row past its prompt emits its last token (the
    latest it can be where an EOS may cut it short); forwards a row:
    ceil(m / per step) a block and one more."""
    from deepspeed_tpu.inference.v2.telemetry import (BLOCK_STAT_NAMES,
                                                      STAT_NAMES, n_stats)
    cfg = tiny_sdar(steps=steps).cfg
    per, n, vocab = cfg.unmask_per_step, 8, 12
    rng = np.random.default_rng(steps)
    plens = rng.integers(1, 40, n)
    limits = rng.integers(1, 26, n)
    eos = np.where(np.arange(n) % 2, -1, 3)      # every other row has none
    prompts = rng.integers(4, vocab, (n, 64)).astype(np.int32)
    slots = DeviceSlotTable(n, 64, 4, jax.random.PRNGKey(0),
                            block=(BLK, per))
    slots.uid_of_slot[:] = np.arange(n)
    slots.plen_h[:], slots.limit_h[:], slots.eos_h[:] = plens, limits, eos
    slots.done_h[:] = False

    def stub(key):
        def fwd(params, ids, positions, tables, w, kpool, vpool, **kw):
            del params, ids, tables, w
            assert kw["head_at"].shape == (n,)
            # a fused row's logits are read behind its 2 L live positions'
            # first half, any other row's at its chunk's start
            live = jnp.sum(positions >= 0, axis=1)
            assert positions.shape[1] >= 2 * BLK
            ok = jnp.where(kw["head_at"] > 0, live == 2 * BLK, True)
            logits = jax.random.normal(key, (n, BLK, vocab))
            return jnp.where(ok[:, None, None], logits, jnp.nan), kpool, \
                vpool, None
        return fwd

    def step(width):
        def run(key, carry):
            body = model_runner._block_scan_body(
                stub(key), None, jnp.asarray(prompts),
                jnp.asarray(plens, jnp.int32), jnp.asarray(limits, jnp.int32),
                jnp.asarray(eos, jnp.int32), jnp.zeros((n,)), None, width,
                True, cfg)
            return body(carry, None)
        return jax.jit(run)

    steppers = {w: step(w) for w in (16, 2 * BLK)}
    zeros, pool = jnp.zeros((n,), jnp.int32), jnp.zeros((1,))
    carry = (zeros, zeros, zeros, jnp.zeros((n,), bool),
             jnp.zeros((n,), bool), jnp.zeros((n,), bool),
             jnp.zeros((n_stats(False, block=True),), jnp.int32),
             jax.random.PRNGKey(1), pool, pool,
             (jnp.zeros((n, BLK), jnp.int32), jnp.ones((n, BLK), bool)))
    due, forwards, widths = {}, np.zeros(n, int), set()
    for t in range(400):
        if slots.done_h.all():
            break
        width = 16 if slots.prefill_steps_left(16) else 2 * BLK
        widths.add(width)
        past = ~slots.done_h & (slots.cached_h >= slots.prefill_end_h)
        for i in np.flatnonzero(past):
            due.setdefault(i, t + slots._block_steps_left(i))
            # the closed form counts down a step at a time
            assert eos[i] >= 0 or due[i] == t + slots._block_steps_left(i)
        forwards += past
        carry, (toks, emit) = steppers[width](jax.random.PRNGKey(100 + t),
                                              carry)
        _, finished = slots.absorb(np.asarray(toks)[None],
                                   np.asarray(emit)[None], width)
        assert (np.asarray(carry[0]) == slots.cached_h).all()
        assert (np.asarray(carry[1]) == slots.produced_h).all()
        # (the carry's flag latches an EOS; a spent budget freezes by count)
        assert ((np.asarray(carry[3]) | (slots.produced_h >= limits))
                == slots.done_h).all()
        assert not np.asarray(carry[5]).any()       # no NaN was read
        for i in finished:
            if i in due:
                # the step that emitted the last token is t: ``due`` counted
                # the steps up to and including it
                assert due[i] == t + 1 if eos[i] < 0 else due[i] >= t + 1
                del due[i]
    assert slots.done_h.all() and widths == {16, 2 * BLK}
    lanes = np.asarray(carry[6])
    stats = dict(zip(STAT_NAMES, lanes),
                 **dict(zip(BLOCK_STAT_NAMES, lanes[-len(BLOCK_STAT_NAMES):])))
    assert stats["target_forwards"] == forwards.sum() \
        == stats["bd_denoise_forwards"] + stats["bd_commit_forwards"]
    assert stats["bd_blocks_committed"] == \
        stats["bd_fused_forwards"] + stats["bd_commit_forwards"]
    assert stats["bd_commit_forwards"] == n
    for i in np.flatnonzero(eos < 0):
        first = BLK - plens[i] % BLK
        further = -(-max(0, limits[i] - first) // BLK)
        assert forwards[i] == -(-first // per) + further * (BLK // per) + 1
    assert (slots.produced_h[eos < 0] == limits[eos < 0]).all()


# ---------------------------------------------------------------------------
# the host mirror's planning, the mask in the kernel, the refusals
# ---------------------------------------------------------------------------


def test_steps_to_first_finish_counts_blocks():
    """The narrow frame's plan: blocks still to commit at S forwards (a
    first block's remainder fewer) and the last one's commit, less the
    denoising steps already run."""
    slots = DeviceSlotTable(2, 16, 4, jax.random.PRNGKey(0), block=(BLK, 1))
    slots.uid_of_slot[:] = [5, 6]
    slots.plen_h[:] = [18, 16]
    slots.cached_h[:] = [16, 16]
    slots.limit_h[:] = [10, 4]
    assert list(slots.prefill_end_h) == [16, 16]
    # row 0: 2 of its first block, then two whole ones: 2 + 4 + 4, and the
    # last one's commit
    assert slots._block_steps_left(0) == 11
    # row 1: one whole block and its commit
    assert slots._block_steps_left(1) == 5
    slots.denoised_h[1] = 3
    assert slots.steps_to_first_finish() == 2
    assert slots.prefill_steps_left(16) == 0
    slots.cached_h[0] = 0
    assert slots.prefill_steps_left(16) == 1


@pytest.mark.parametrize("unmask", [1, 2, BLK])
def test_block_steps_left_equals_a_walk_of_the_blocks(unmask):
    """The plan's count is closed (a boundary pays for it at every live
    row): the same as walking the blocks one at a time, whatever the
    prompt's remainder, the budget and the denoising steps already run."""
    slots = DeviceSlotTable(1, 16, 4, jax.random.PRNGKey(0),
                            block=(BLK, unmask))
    slots.uid_of_slot[:] = [7]
    rng = np.random.default_rng(unmask)
    for _ in range(300):
        plen = int(rng.integers(1, 40))
        whole = plen // BLK * BLK
        cached = whole + BLK * int(rng.integers(0, 6))
        want, run = int(rng.integers(0, 50)), int(rng.integers(0, 3))
        slots.plen_h[0], slots.cached_h[0] = plen, cached
        slots.produced_h[0], slots.limit_h[0] = 3, 3 + want
        slots.denoised_h[0] = run
        steps, start = -run, cached
        while want > 0:
            steps += slots._block_cost(start, plen)
            want -= start + BLK - max(start, plen)
            start += BLK
            steps += want <= 0          # the last block's commit alone
        assert slots._block_steps_left(0) == steps, (plen, cached, run)


@pytest.mark.parametrize("chunk", [BLK, 16], ids=["block", "chunk"])
def test_pallas_mask_equals_the_gather_path(chunk):
    """The kernel (interpreted here) with the block's last position handed
    in as ``visible_to`` against the gather path under the same mask: a
    block step (C = L) and a prefill chunk, rows at different contexts, a
    frozen row."""
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention
    b, h, kvh, d, page, pages = 3, 4, 2, 16, 8, 13
    rng = np.random.default_rng(chunk)
    q = jnp.asarray(rng.normal(size=(b, chunk, h, d)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, chunk, kvh, d)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(b, chunk, kvh, d)), jnp.float32)
    kpool = jnp.asarray(rng.normal(size=(1, kvh, pages, page, d)), jnp.float32)
    vpool = jnp.asarray(rng.normal(size=(1, kvh, pages, page, d)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(12).reshape(b, 4), jnp.int32)
    start = np.array([12, 0, 4])
    live = np.array([chunk, min(chunk, 8), 0])      # the last row is frozen
    offs = np.arange(chunk)
    positions = jnp.asarray(np.where(offs[None] < live[:, None],
                                     start[:, None] + offs[None], -1),
                            jnp.int32)
    see = jnp.where(positions >= 0, positions // BLK * BLK + BLK - 1, -1)
    got = paged_ragged_attention(q, kpool, vpool, tables, positions, ck, cv,
                                 layer=0, visible_to=see)
    cfg = get_config("tiny", num_kv_heads=kvh)
    gathered = [jnp.take(p, 0, axis=0)[:, tables].reshape(
        kvh, b, -1, d).transpose(1, 2, 0, 3) for p in (kpool, vpool)]
    chunk_start = jnp.asarray(np.where(live > 0, start, 1 << 30), jnp.int32)
    want = model_runner._paged_attention(
        q, *gathered, positions, cfg, chunk_k=ck, chunk_v=cv,
        chunk_start=chunk_start, visible_to=see)
    mask = np.asarray(positions >= 0)
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask],
                               atol=2e-5)
    # and it is the block mask that was compared: causal by position differs
    causal = model_runner._paged_attention(
        q, *gathered, positions, cfg, chunk_k=ck, chunk_v=cv,
        chunk_start=chunk_start)
    assert np.abs(np.asarray(causal)[mask] - np.asarray(want)[mask]).max() \
        > 1e-2


REFUSED = {
    "tp": (dict(tp=2), "tp=2"),
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache"),
    "swap": (dict(kv_swap_dir="/tmp/x"), "swap tier"),
    "handoff": (dict(role="prefill"), "handoff"),
    "int8": (dict(kv_dtype="int8"), "int8"),
    "repair": (dict(nonfinite_policy="repair"), "repair"),
    "chunk": (dict(prefill_chunk_size=18), "no multiple of block_length"),
}


@pytest.mark.parametrize("what", list(REFUSED) + ["draft", "block_of_6"])
def test_what_a_half_denoised_block_is_refused_with(what):
    """Each with its reason, at engine build."""
    from deepspeed_tpu.inference.v2.model_implementations.archs import \
        validate_block_diffusion_serving
    cfg = tiny_sdar().cfg
    base = dict(SHAPE)
    if what == "draft":
        with pytest.raises(NotImplementedError, match="a draft"):
            validate_block_diffusion_serving(
                RaggedInferenceEngineConfig(**base), cfg, draft=True)
        return
    if what == "block_of_6":
        with pytest.raises(NotImplementedError, match="power of two"):
            validate_block_diffusion_serving(
                RaggedInferenceEngineConfig(**dict(base,
                                                   prefill_chunk_size=18)),
                cfg.replace(block_length=6, denoising_steps=3))
        return
    kw, reason = REFUSED[what]
    with pytest.raises(NotImplementedError, match=reason):
        validate_block_diffusion_serving(
            RaggedInferenceEngineConfig(**dict(base, **kw)), cfg)
    # ... and nothing is refused of the shape the tests serve
    validate_block_diffusion_serving(RaggedInferenceEngineConfig(**base), cfg)


def test_the_engine_refuses_at_build_and_in_put_step(engine, params):
    with pytest.raises(NotImplementedError, match="half-denoised block"):
        InferenceEngineV2(
            tiny_sdar(), RaggedInferenceEngineConfig(
                **dict(SHAPE, prefill_chunk_size=18)), params=params,
            max_seq_len=SEQ)
    with pytest.raises(NotImplementedError, match="half-denoised block"):
        engine.attach_draft(tiny_sdar())
    engine.put([900], [np.arange(8, 14)])
    try:
        with pytest.raises(NotImplementedError, match=r"put\(\) / step\(\)"):
            engine.step()
    finally:
        engine.flush([900])


# ---------------------------------------------------------------------------
# the reference's replay, as the benchmark's check calls it
# ---------------------------------------------------------------------------


def gaps_of(reference, params, prompt, generated, config):
    ids = list(prompt) + list(generated[:-1])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(generated))
    logits = reference.logits_rows(params, ids, rows, config)
    return logits.max(-1) - logits[np.arange(len(generated)),
                                   np.asarray(generated)]


def test_replay_finds_the_served_order_and_refuses_a_wrong_one(
        engine, params, reference, monkeypatch):
    """One row a generated token, in order, from the step that unmasked it:
    the served tokens read a gap of ~0 (float32), the sample's last block is
    not compared; a block denoised in the WRONG order (lowest confidence
    first, by the reference's own walk) is explained by no order and reads
    an infinite gap, as does a block with a token swapped."""
    config = config_of()
    prompt = prompts_of([18], seed=9)[0]
    out, = engine.generate([prompt], max_new_tokens=14)
    out = [int(t) for t in out]
    gaps = gaps_of(reference, params, prompt, out, config)
    assert gaps.shape == (14,) and gaps.max() < LOGIT_TOL
    # the replay's rows are the denoising steps' own: the first generated
    # token's row equals the walk's logits at its position
    trace = []
    assert reference.generate(params, prompt, 14, config, trace=trace) == out
    # forced wrong: the least confident position first
    real = reference._choose

    def least_first(logits, masked, per_step, threshold):
        x0, _ = real(logits, masked, per_step, threshold)
        z = np.asarray(logits, np.float64)
        conf = np.exp(z.max(-1) - np.log(np.exp(z).sum(-1)))
        order = sorted(masked, key=lambda j: (conf[j], j))
        return x0, sorted(order[:per_step])

    monkeypatch.setattr(reference, "_choose", least_first)
    wrong = reference.generate(params, prompt, 14, config)
    monkeypatch.setattr(reference, "_choose", real)
    assert wrong != out
    assert np.isinf(gaps_of(reference, params, prompt, wrong, config)).any()
    swapped = list(out)
    swapped[3], swapped[4] = (swapped[4] + 1) % 250 + 1, swapped[3]
    assert np.isinf(gaps_of(reference, params, prompt, swapped, config)).any()


# ---------------------------------------------------------------------------
# the published layout, and the other families' programs
# ---------------------------------------------------------------------------


def test_container_round_trips_a_checkpoint_in_the_published_layout(params):
    """``sdar_moe``'s names (``self_attn.{q,k,v,o}_proj``,
    ``self_attn.{q,k}_norm``, ``mlp.gate``, ``mlp.experts.{e}.*_proj``): a
    seeded checkpoint written in that layout comes back as the program's
    own tree, leaf for leaf, under the preset's configuration."""
    import types
    from deepspeed_tpu.inference.v2.model_implementations import \
        resolve_container
    from deepspeed_tpu.inference.v2.model_implementations.archs import \
        SdarMoeContainer
    cfg = tiny_sdar().cfg
    hf = types.SimpleNamespace(
        architectures=["SDARMoeForCausalLM"], model_type="sdar_moe",
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.kv_heads, head_dim=cfg.dims_per_head,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok, norm_topk_prob=True,
        max_position_embeddings=cfg.max_seq_len, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps, tie_word_embeddings=False,
        attention_bias=False, decoder_sparse_step=1, mlp_only_layers=[],
        use_sliding_window=False, mask_token_id=MASK_ID)
    container = resolve_container(hf)
    assert issubclass(container, SdarMoeContainer)
    got = container.config(hf)
    for field in ("num_heads", "num_kv_heads", "head_dim", "qk_norm",
                  "qk_norm_bias", "num_experts", "num_experts_per_tok",
                  "moe_intermediate_size", "moe_norm_topk", "moe_impl",
                  "block_length", "denoising_steps", "mask_token_id",
                  "norm_eps", "rope_theta", "tie_embeddings"):
        assert getattr(got, field) == getattr(cfg, field), field
    # the family's released default where the config object names none
    assert got.remasking_strategy == "low_confidence_dynamic" \
        == get_config("sdar-30b-a3b").remasking_strategy
    e, h, kvh, d = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                    cfg.dims_per_head)
    lay = jax.tree.map(np.asarray, params["layers"])
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]["tok"]),
          "lm_head.weight": np.asarray(params["embed"]["lm_head"]).T,
          "model.norm.weight": np.asarray(params["final_norm"]["scale"])}
    for l in range(cfg.num_layers):
        pre = f"model.layers.{l}."
        a, m = lay["attn"], lay["mlp"]
        sd.update({
            pre + "self_attn.q_proj.weight": a["wq"][l].reshape(e, h * d).T,
            pre + "self_attn.k_proj.weight": a["wk"][l].reshape(e, kvh * d).T,
            pre + "self_attn.v_proj.weight": a["wv"][l].reshape(e, kvh * d).T,
            pre + "self_attn.o_proj.weight": a["wo"][l].reshape(h * d, e).T,
            pre + "self_attn.q_norm.weight": a["q_norm"]["scale"][l],
            pre + "self_attn.k_norm.weight": a["k_norm"]["scale"][l],
            pre + "input_layernorm.weight": lay["norm1"]["scale"][l],
            pre + "post_attention_layernorm.weight": lay["norm2"]["scale"][l],
            pre + "mlp.gate.weight": m["router"][l].T})
        for x in range(cfg.num_experts):
            ex = pre + f"mlp.experts.{x}."
            sd.update({ex + "gate_proj.weight": m["wi_gate"][l, x].T,
                       ex + "up_proj.weight": m["wi_up"][l, x].T,
                       ex + "down_proj.weight": m["wo"][l, x].T})
    back = container.build_params(sd, got)
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(back)[0]:
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat[path]),
                                      err_msg=jax.tree_util.keystr(path))
    assert len(flat) == len(jax.tree.leaves(back))
    hf.decoder_sparse_step = 2
    with pytest.raises(NotImplementedError, match="decoder_sparse_step"):
        container.config(hf)


#: sha256 of the lowered text of the frame program at 4 slots x 2 steps,
#: pages of 8, at the PARENT commit (54d5e4d, PR 46): no frame program of
#: another model changes with the block mask, the block carry or the
#: vector's new lanes. The ``olmoe`` and ``glm`` entries are PR 52's own
#: tree (their routed blocks' combine became a gather by token; at 14b980e
#: they read 985c4f63... / b27895b9... and 1db826b1... / 93642342...); the
#: two ``mistral`` ones are still the commit's above
SMALL = dict(vocab_size=256, hidden_size=64, max_seq_len=256, dtype="float32")
FAMILIES = {
    "mistral": ("mistral-7b", dict(
        num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=128,
        sliding_window=64, **SMALL)),
    "olmoe": ("olmoe-1b-7b", dict(
        num_layers=2, num_heads=4, num_kv_heads=4, intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, **SMALL)),
    "glm": ("glm-4.7-flash", dict(
        num_layers=3, num_heads=4, intermediate_size=96,
        moe_intermediate_size=32, moe_shared_expert_size=32, num_experts=8,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, **SMALL)),
}
PARENTS = {
    ("mistral", 1):
        "2f0b973a36baaff3143e22f9f943b2f71b97de766bcae02ed2acf23f38f79804",
    ("mistral", 16):
        "d0d1a945be5a107ec9671f2088842e1b73a39ffec7419f015b24e7085bf4bb91",
    ("olmoe", 1):
        "086813a69a469c631d004aa7b80841bedd45d3bdb5933cad2502b4f308979851",
    ("olmoe", 16):
        "8c7491bf84b5957f17262cd1a9c90b2db905b63dc135476997b970c7459cab3d",
    ("glm", 1):
        "07bada29e5a50e1dbf0cd8b6bd55dc9265091cfe7fc8c7330dc67851c7d92774",
    ("glm", 16):
        "84c80e2c99708882f3b467fe69c7a0fc7db511151ccb3cef082ea958f8307522",
}


@pytest.mark.parametrize("family,width", list(PARENTS),
                         ids=[f"{f}-w{w}" for f, w in PARENTS])
def test_other_families_frame_programs_are_the_parents(family, width):
    preset, kw = FAMILIES[family]
    cfg = get_config(preset, **kw)
    model = build_model(cfg)
    runner = PagedModelRunner(model, 8, 32)
    slots, steps, i32, sds = 4, 2, jnp.int32, jax.ShapeDtypeStruct
    row, flag = sds((slots,), i32), sds((slots,), jnp.bool_)
    key = jax.random.PRNGKey(0)
    if cfg.latent_lanes:
        pools = (sds((cfg.cache_layers, 1, 33, 8, cfg.latent_lanes),
                     jnp.float32), None)
    else:
        pool = sds((cfg.num_layers, cfg.kv_heads, 33, 8, cfg.dims_per_head),
                   jnp.float32)
        pools = (pool, pool)
    hidden = (sds((slots, cfg.hidden_size), jnp.float32),) \
        if runner.has_mtp else ()
    text = runner._build_frame_loop().lower(
        model.abstract_params(), sds((slots, 256), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, 32), i32), row, row, row,
        flag, flag, flag, sds((runner.n_stats,), i32),
        sds(key.shape, key.dtype), *pools, *hidden, width=width, steps=steps,
        greedy=True, n_steps=sds((), i32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENTS[family, width]
