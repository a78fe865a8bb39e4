"""The scoped reduction on an excerpt recorded on a TPU v5e (chip run of
PR 23, kept as JSON beside this file): ops found by their layer scope and
kernels by their name, not by shape; XLA's layout copies of the pool under
``kv_commit``; recomputed ops by ``jax.checkpoint``'s mark; idle time by
the program's own spans; each frame's work from its ``serve/frame_work``
span. Also: every new reader leaves its metric out (None, no exception)
where the program has no such span or counter, as the parent commit has
not; and the bursty generator's gaps hold their mean and their cv."""

import json
import os

import numpy as np
import pytest

from perfbench import draws, harness, scope_reduce, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
POOL = {"num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 128, "hidden_size": 256}
NEW_READERS = (
    "useful_position_share", "boundary_host_ms_per_frame",
    "idle_attributed_share", "train_idle_attributed_share",
    "scope_coverage", "train_scope_coverage", "kv_commit_share",
    "paged_decode_roofline", "paged_prefill_roofline", "train_remat_share")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "trace_scopes_v5e_excerpt.json")) as fh:
        return json.load(fh)


def test_scope_of_takes_the_innermost_scope_and_unwraps_autodiff():
    path = ("jit(loop)/while/body/closed_call/mlp/paged_attn/"
            "paged_attn_c1/pallas_call:")
    assert scope_reduce.scope_of("x fusion bf16[1]", path) == "paged_attn"
    assert scope_reduce.scope_of(
        "x fusion bf16[1]", "jit(f)/transpose(jvp(lm_head_loss))/"
        "checkpoint/rematted_computation/mlp/dot_general:") == "mlp"
    assert scope_reduce.scope_of(
        "x fusion bf16[1]", "jit(f)/transpose(jvp(lm_head_loss))/mul:") \
        == "lm_head_loss"
    assert scope_reduce.scope_of("x fusion bf16[1]", "jit(f)/while:") \
        == scope_reduce.UNSCOPED
    pool = scope_reduce.pool_shape_pattern(
        dict(POOL, num_hidden_layers=16, num_key_value_heads=8), 416)
    assert scope_reduce.scope_of("copy.104 copy bf16[16,8,416,128,128]",
                                 "", pool) == "kv_commit"
    assert scope_reduce.scope_of("copy.77 copy bf16[16,4096,32,128]", "",
                                 pool) == scope_reduce.UNSCOPED
    assert scope_reduce.scope_of("fusion.1 fusion bf16[16,8,416,128,128]",
                                 "", pool) == scope_reduce.UNSCOPED


def test_kernels_are_found_by_their_names():
    assert scope_reduce.kernel_of(
        "paged_attn_c128.17 custom-call(tpu_custom_call) bf16[16,8,512,128]"
    ) == "paged_attn_c128"
    assert scope_reduce.kernel_of(
        "flash_bwd_dkv custom-call(tpu_custom_call) bf16[1,8,8192,128]") \
        == "flash_bwd_dkv"
    assert scope_reduce.kernel_of(
        "custom-call.2 custom-call(AllocateBuffer) bf16[4,1024]") is None
    assert scope_reduce.kernel_of("fusion.181 fusion bf16[16,128]") is None


def test_serve_reduction_on_the_recorded_frames(trace):
    red = scope_reduce.serve_reduction(trace, POOL, 64)
    assert red["frames"] == 3 and red["frames_narrow"] == 3
    assert red["frames_wide"] == 0
    # the work the program wrote for those frames, summed
    assert red["kv_positions_read_narrow"] == 6000
    assert red["attn_pairs_narrow"] == 6000
    # scopes tile the busy time
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"],
                                                         rel=0.01)
    assert set(red["scope_s"]) == {"kv_commit", "attn", "mlp", "paged_attn",
                                   scope_reduce.UNSCOPED}
    # the kernel by its name: three frames of four steps
    assert list(red["kernel_s"]) == ["paged_attn_c1"]
    assert red["kernel_s"]["paged_attn_c1"] == pytest.approx(
        red["scope_s"]["paged_attn"])
    # the pool's two layout copies are most of kv_commit, and carry no
    # scope of their own: without the pool's shape they are unscoped
    bare = scope_reduce.reduce_scoped(
        trace, *trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN))
    assert bare["scope_s"]["kv_commit"] < 0.1 * red["scope_s"]["kv_commit"]
    assert bare["unscoped_ops"][0][0].startswith("copy")
    assert "[2,2,64,128,128]" in bare["unscoped_ops"][0][0]
    assert red["remat_s"] == 0.0


def test_idle_gaps_are_named_by_the_programs_spans(trace):
    red = scope_reduce.serve_reduction(trace, POOL, 64)
    assert sum(red["idle_by_span"].values()) == pytest.approx(red["idle_s"])
    assert red["idle_s"] + red["busy_s"] == pytest.approx(red["window_s"],
                                                          rel=1e-3)
    named = {k for k in red["idle_by_span"]
             if k != scope_reduce.UNATTRIBUTED}
    assert named and all(k.startswith(("serve/", "serve_frame/"))
                         for k in named)
    assert "serve/absorb" in named


def test_an_empty_servers_wait_is_idle_of_its_own(trace, monkeypatch):
    """``serve/idle`` (the loop's poll with nothing live or queued) is no
    gap of the boundary's: its seconds are reported apart and count on
    neither side of ``idle_attributed_share``."""
    assert scope_reduce.serve_reduction(trace, POOL, 64)["empty_s"] == 0.0
    renamed = {"frame_work": trace["frame_work"], "planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                [scope_reduce.EMPTY_SERVER if e[0] == "serve/absorb"
                 else e[0]] + e[1:] for e in ln["events"]]}
            for ln in p["lines"]]} for p in trace["planes"]]}
    red = scope_reduce.serve_reduction(renamed, POOL, 64)
    assert 0.0 < red["empty_s"] < red["idle_s"]
    assert red["empty_s"] == pytest.approx(sum(
        v for k, v in red["idle_by_span"].items()
        if k.startswith(scope_reduce.EMPTY_SERVER)))
    monkeypatch.setattr(scope_reduce, "for_ctx", lambda ctx: red)
    got = harness.load_module("layer_metrics",
                              "idle_attributed_share").read({})
    live = red["idle_s"] - red["empty_s"]
    assert got == pytest.approx(100.0 * (live - red["idle_by_span"].get(
        scope_reduce.UNATTRIBUTED, 0.0)) / live)


def test_frames_without_their_work_end_the_window(trace):
    cut = dict(trace, frame_work=trace["frame_work"][:2])
    assert scope_reduce.serve_reduction(cut, POOL, 64)["frames"] == 2
    assert scope_reduce.serve_reduction(dict(trace, frame_work=[]),
                                        POOL, 64) is None


def test_train_reduction_finds_the_recomputed_ops(trace):
    red = scope_reduce.train_reduction(trace)
    assert red["steps"] == 2
    assert 0 < red["remat_s"] < red["scope_s"]["mlp"]
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"],
                                                         rel=0.01)
    assert all(k.startswith("train_batch") for k in red["idle_by_span"])
    # a program without the step annotation gives nothing to reduce
    bare = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                e for e in ln["events"] if not e[0].startswith("train")]}
            for ln in p["lines"]]} for p in trace["planes"]],
        "frame_work": []}
    assert scope_reduce.train_reduction(bare) is None


def test_event_paths_reads_the_metadata_of_a_wire_format_file(tmp_path):
    """A two-event plane written by hand in protobuf wire format: the op
    path is the ``tf_op`` stat of the event's metadata record."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_meta = field(1, 26) + field(2, b"tf_op")
    events = [
        field(1, 1) + field(2, b"%fusion.1 = bf16[8]{0} fusion()")
        + field(5, field(1, 26) + field(5, b"jit(loop)/mlp/dot_general:")),
        field(1, 2) + field(2, b"%copy.2 = bf16[8]{0} copy()"),
    ]
    plane = (field(1, 7) + field(2, b"/device:TPU:0")
             + field(3, field(2, b"XLA Ops"))
             + b"".join(field(4, entry(i + 1, e))
                        for i, e in enumerate(events))
             + field(5, entry(26, stat_meta)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane)
                     + field(1, field(2, b"/host:CPU")))
    assert scope_reduce.event_paths(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[8]{0} fusion()": "jit(loop)/mlp/dot_general:"}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_leaves_its_metric_out_of_an_empty_ctx(name):
    """On the parent commit the program has no such span or counter: the
    reader returns None and does not raise (an empty ctx, a ctx with
    counters that lack the new ones, a ctx whose trace has no program
    span)."""
    read = harness.load_module("layer_metrics", name).read
    assert read({}) is None
    assert read({"kind": "serve", "counters": {"frames": 0}, "trace": None,
                 "span": None, "frames": None}) is None
    old = {"kind": "serve", "trace": None, "counters": {
        "frames": 12, "prefill_tokens": 5, "target_forwards": 7}}
    assert read(old) is None


def test_counter_readers_read_the_window_delta():
    ctx = {"kind": "serve", "counters": {
        "frames": 4, "prefill_tokens": 300, "target_forwards": 100,
        "positions_computed": 16 * 128 * 8 + 3 * 16 * 8,
        "host_poll_ns": 4_000_000, "host_admit_ns": 8_000_000,
        "host_fetch_ns": 900_000_000, "host_yield_ns": 2_000_000,
        "host_idle_ns": 700_000_000}}
    share = harness.load_module("layer_metrics",
                                "useful_position_share").read(ctx)
    assert share == pytest.approx(100.0 * 400 / (16384 + 384))
    host = harness.load_module("layer_metrics",
                               "boundary_host_ms_per_frame").read(ctx)
    assert host == pytest.approx((4 + 8 + 2) / 4)


def test_gamma_gaps_hold_their_mean_and_cv():
    gen = harness.load_module("generators", "open_loop_gamma")
    rng = draws.stream(1, 2)
    gaps = [gen.draw_gap(rng, {"process": "gamma", "rate": 0.64, "cv": 2.0})
            for _ in range(10000)]
    assert abs(np.mean(gaps) - 1 / 0.64) < 0.06 / 0.64
    assert 1.85 < np.std(gaps) / np.mean(gaps) < 2.15
    with pytest.raises(ValueError):
        gen.draw_gap(rng, {"process": "poisson", "rate": 1.0})


def test_chat_bursty_is_chat_steady_in_clumps():
    read = lambda n: harness.read_json(os.path.join(  # noqa: E731
        harness.HERE, "traffic", f"{n}.json"))
    steady, bursty = read("chat-steady"), read("chat-bursty")
    same = ("classes", "pre_window_s", "drain_s", "slo", "check")
    assert all(steady[k] == bursty[k] for k in same)
    assert bursty["arrivals"] == {"process": "gamma", "rate": 0.64,
                                  "cv": 2.0}
    assert bursty["schedule_seed"] != steady["schedule_seed"]
    gen = harness.load_module("generators", bursty["generator"])
    plan = gen.plan(bursty, bursty["schedule_seed"], 66.0)["requests"]
    again = gen.plan(bursty, bursty["schedule_seed"], 30.0)["requests"]
    assert plan[:len(again)] == again           # prefix-stable
    assert len([r for r in plan if 15.0 <= r["t"] < 66.0]) == 35
    # the clump the cell exists for: more arrivals inside ten seconds than
    # the server has slots (whether they queue is read off the chip runs:
    # the draw is chosen by nothing else)
    ts = [r["t"] for r in plan]
    assert max(sum(1 for u in ts if t - 10.0 < u <= t) for t in ts) > 16
