"""The trace reduction on a small trace kept beside this file: busy union,
idle share, self times under a ``while``, collectives exposed, custom
calls, and idle gaps by what the host was doing. Expected values worked by
hand from ``trace_small.json`` (nanoseconds; MIN_GAP_NS lowered so that
its microsecond-scale gaps count)."""

import json
import os

import pytest

from perfbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "trace_small.json")) as fh:
        return json.load(fh)


@pytest.fixture(autouse=True)
def small_gaps(monkeypatch):
    monkeypatch.setattr(trace_reduce, "MIN_GAP_NS", 100)


def test_union_and_clip():
    assert trace_reduce.union([[5, 7], [1, 3], [2, 4], [7, 7]]) == \
        [[1, 4], [5, 7]]
    assert trace_reduce.clip([[1, 4], [5, 7]], 2, 6) == [[2, 4], [5, 6]]


def test_self_times_subtract_children():
    got = dict((n, s) for n, _, s in trace_reduce.self_times(
        [["while.1", 0, 100], ["a", 0, 30], ["b", 40, 50], ["c", 200, 10]]))
    assert got == {"while.1": 20, "a": 30, "b": 50, "c": 10}


def test_busy_idle_and_window(trace):
    red = trace_reduce.reduce_trace(trace, "perfbench/trace_window")
    # window 0..10000 ns. TPU:0 busy [1000,7000] + [8000,10000] = 8000;
    # TPU:1 busy [2000,8000] = 6000; mean 7000
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["busy_s"] == pytest.approx(7000e-9)
    # the event at 12000 lies outside the window
    assert "fusion.9" not in dict(red["device_ops"])


def test_ops_collectives_and_custom_calls(trace):
    red = trace_reduce.reduce_trace(trace, "perfbench/trace_window")
    ops = dict(red["device_ops"])
    # while.1 covers 6000 of which its body covers all: self time 0
    assert ops["while.1"] == 0
    # fusion.1: 2000 on TPU:0 + 4000 on TPU:1, mean over 2 devices
    assert ops["fusion.1"] == pytest.approx(3000e-9)
    # collectives: start 500 + done 1000 on TPU:0, all-reduce 2000 on TPU:1
    assert red["collective_exposed_s"] == pytest.approx(1750e-9)
    assert red["custom_call_s"] == pytest.approx(500e-9)


def test_idle_gaps_are_named_by_the_python_threads_spans(trace):
    red = trace_reduce.reduce_trace(trace, "perfbench/trace_window")
    gaps = dict(red["idle_gaps"])
    # TPU:0: [0,1000] middle 500 -> serve_frame/w128 alone (PjitFunction
    # starts at 600); [7000,8000] middle 7500 -> no python span (the
    # compiler thread's "algsimp" does not count)
    # TPU:1: [0,2000] middle 1000 -> serve_frame/w128 > PjitFunction(loop);
    # [8000,10000] middle 9000 -> serve_frame/w1
    assert gaps == {
        "serve_frame/w128/s8": pytest.approx(500e-9),
        "serve_frame/w128/s8>PjitFunction(loop)": pytest.approx(1000e-9),
        "unattributed-host": pytest.approx(500e-9),
        "serve_frame/w1/s8": pytest.approx(1000e-9)}


def test_display_names_of_hlo_text():
    d = trace_reduce.display_name
    assert d("%fusion.181 = bf16[16,128,14336]{2,1,0:T(8,128)(2,1)} fusion("
             "bf16[16,4096,14336]{2,1,0} %get-tuple-element.1622), kind=kOut"
             ) == "fusion.181 fusion bf16[16,128,14336]"
    kernel = d("%closed_call.14 = bf16[16,8,512,128]{3,2,1,0:T(8,128)(2,1)S(1)}"
               " custom-call(s32[1]{0:T(128)} %dynamic_slice.143), "
               "custom_call_target=\"tpu_custom_call\", operand_layout={}")
    assert kernel == ("closed_call.14 custom-call(tpu_custom_call) "
                      "bf16[16,8,512,128]")
    assert trace_reduce.is_custom_call(kernel)
    gather = d("%all-gather-start.3 = (f32[4]{0}, f32[16]{0}) "
               "all-gather-start(f32[4]{0} %p), replica_groups={}")
    assert gather == "all-gather-start.3 all-gather-start f32[4]"
    assert trace_reduce.is_collective(gather)
    assert not trace_reduce.is_collective(kernel)
    assert d("serve_frame/w1/s8") == "serve_frame/w1/s8"


def test_explicit_window_and_frame_spans(trace):
    spans = trace_reduce.find_spans(trace, "serve_frame/")
    assert [s[2] for s in spans] == ["serve_frame/w128/s8",
                                     "serve_frame/w1/s8"]
    red = trace_reduce.reduce_trace(trace, window=[spans[0][0], spans[-1][1]])
    assert red["window_s"] == pytest.approx(9400e-9)


def test_no_device_plane_is_none():
    assert trace_reduce.reduce_trace({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events":
                                         [["x", 0, 5]]}]}]}) is None


def test_load_xplane_reads_what_the_profiler_writes(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("perfbench/trace_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.newest_xplane(str(tmp_path)))
    assert trace_reduce.find_span(trace, "perfbench/trace_window")
    assert not any(e[0].startswith("$") for p in trace["planes"]
                   for ln in p["lines"] for e in ln["events"])
    # a CPU run has no device plane: nothing is reported, never a CPU time
    assert trace_reduce.reduce_trace(trace, "perfbench/trace_window") is None


def test_recorded_v5e_excerpt():
    """30 ms of a real trace (TPU v5 lite, mixed-queue, PR 22): the start
    of a narrow serving frame, cut to the events inside 31 ms. Names are
    already display names; the ``while`` around the steps straddles the
    cut and is not in it."""
    with open(os.path.join(HERE, "trace_v5e_excerpt.json")) as fh:
        trace = json.load(fh)
    red = trace_reduce.reduce_trace(trace)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.030043017)
    assert red["busy_s"] == pytest.approx(0.03004278)
    assert red["busy_s"] <= red["window_s"]
    assert red["collective_exposed_s"] == 0.0
    # the decode paged-attention kernel: 16 layers of one step
    assert red["custom_call_s"] == pytest.approx(0.004653438)
    top = dict(red["device_ops"])
    # the frame opens with the relayout of both KV pools (ROADMAP S2)
    assert top["copy.74 copy bf16[16,8,416,128,128]"] == \
        pytest.approx(0.005308542)
    assert top["closed_call.14 custom-call(tpu_custom_call) "
               "bf16[16,8,4,128]"] == pytest.approx(0.004653436)
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"]
