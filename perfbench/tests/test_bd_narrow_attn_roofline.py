"""The reader of ``bd_narrow_attn_roofline``: the floor of the work that the
frames two blocks wide counted over the device time of ``paged_attn_c<2L>``,
on a small trace excerpt made here; it leaves the metric out (None, no
exception) where the narrow frames are a block wide and the kernel is
``paged_attn_c<L>``, as the parent commit's are, where the run has no trace
and where the model does not generate by diffusion over blocks; its entry in
BENCHMARK.json follows every older one."""

import os

import pytest

from perfbench import harness, peaks, scope_reduce, trace_reduce, work_bd

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "bd_narrow_attn_roofline"
CELL = "sdar-decode-closed"


def sdar():
    return harness.read_json(os.path.join(
        harness.HERE, "configs", "sdar-30b-a3b-l6-serve.json"))


def module():
    return harness.load_module("layer_metrics", NAME)


def excerpt(width):
    """Two narrow frames ``width`` wide and a wide one with their work, and
    a third narrow frame whose work the trace lacks."""
    host = [[scope_reduce.WINDOW_SPAN, 0, 1000],
            [f"serve_frame/w{width}/s8", 100, 200],
            ["serve/frame_work", 310, 1],
            ["serve_frame/w128/s8", 400, 100], ["serve/frame_work", 510, 1],
            [f"serve_frame/w{width}/s8", 600, 100],
            ["serve/frame_work", 710, 1],
            [f"serve_frame/w{width}/s8", 800, 100]]
    narrow = {"width": width, "steps": 8, "target_forwards": 128,
              "kv_positions_read": 50_000, "attn_pairs": 300_000,
              "bd_denoise_forwards": 126, "bd_commit_forwards": 2,
              "bd_fused_forwards": 30}
    wide = dict(narrow, width=128, kv_positions_read=9_000,
                attn_pairs=700_000)
    return {"planes": [{"name": trace_reduce.HOST_PLANE,
                        "lines": [{"name": "python", "events": host}]}],
            "frame_work": [(310, dict(narrow)), (510, dict(wide)),
                           (710, dict(narrow))]}


def ctx_over(monkeypatch, trace, kernel_s):
    """A traced serving run's context whose trace is ``trace`` and whose
    device time by kernel is ``kernel_s``."""
    monkeypatch.setattr(work_bd, "for_ctx", lambda ctx: {"frames": 3})
    monkeypatch.setattr(work_bd, "device_peaks",
                        lambda: peaks.peaks_for("TPU v5 lite"))
    monkeypatch.setattr(scope_reduce, "for_ctx",
                        lambda ctx: {"busy_s": 1e-6, "kernel_s": kernel_s})
    monkeypatch.setattr(scope_reduce, "newest_trace", lambda: "here")
    monkeypatch.setattr(scope_reduce, "load_scoped", lambda path: trace)
    return {"config": sdar(), "trace": True, "kind": "serve"}


def test_the_floor_of_the_frames_two_blocks_wide_over_the_kernels_time(
        monkeypatch):
    assert module().narrow_work(excerpt(8), 8) == (100_000, 600_000)
    assert module().narrow_work(excerpt(8), 4) is None
    ctx = ctx_over(monkeypatch, excerpt(8), {
        "paged_attn_c8": 5e-3, "paged_attn_c8.1": 1e-3,
        "paged_attn_c128": 9e-3, "kv_commit_c8": 7e-3})
    # 12,288 B a position against 98,304 FLOPs a pair: the bytes bind
    floor = max(100_000 * 12_288 / 819e9, 600_000 * 98_304 / 197e12)
    assert floor == pytest.approx(100_000 * 12_288 / 819e9)
    got = module().read(ctx)
    assert got == pytest.approx(100 * floor / 6e-3) and 0 < got < 100


@pytest.mark.parametrize("width,kernel_s", [
    # the parent's program: narrow frames a block wide, the kernel at L
    (4, {"paged_attn_c4": 5e-3, "paged_attn_c128": 9e-3}),
    # the kernel without such frames, the frames without the kernel
    (4, {"paged_attn_c8": 5e-3}), (8, {"paged_attn_c128": 9e-3}), (8, {})])
def test_a_narrow_frame_a_block_wide_leaves_the_metric_out(
        monkeypatch, width, kernel_s):
    assert module().read(ctx_over(monkeypatch, excerpt(width),
                                  kernel_s)) is None


def test_nothing_to_read_leaves_the_metric_out():
    olmoe = harness.read_json(os.path.join(
        harness.HERE, "configs", "olmoe-1b-7b-l8-serve.json"))
    for ctx in ({}, {"counters": {}}, {"kind": "serve", "trace": None},
                {"kind": "serve", "trace": None, "config": sdar()},
                {"kind": "serve", "trace": True, "config": olmoe}):
        assert module().read(ctx) is None


def test_its_entry_follows_the_older_ones():
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": [CELL]}
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert "tokens_per_s" in e2e
