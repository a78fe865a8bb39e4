"""The reader of ``moved_rows_per_expert_row``: rows the dispatch gathered
over rows the experts computed, from the window's two counters; it leaves
the metric out (None, no exception) where the program does not count the
first, as the parent commit does not, or routed nothing; its entry in
BENCHMARK.json follows every older one and lists the four cells that report
``expert_rows_per_token``."""

import os

import pytest

from perfbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "moved_rows_per_expert_row"


def read(ctx):
    return harness.load_module("layer_metrics", NAME).read(ctx)


@pytest.mark.parametrize("ctx", [
    {}, {"counters": None}, {"counters": {}},
    # the parent's counters: the experts' work, not what was moved
    {"counters": {"expert_rows": 4000, "experts_touched": 64,
                  "expert_rows_max": 400}},
    # a dense model beside the lane's name, and a window in which no step ran
    {"counters": {"expert_rows": 0, "expert_rows_moved": 0}},
    {"counters": {"expert_rows": 0, "expert_rows_moved": 512}}])
def test_nothing_to_read_leaves_the_metric_out(ctx):
    assert read(ctx) is None


@pytest.mark.parametrize("moved,computed,ratio", [
    # a LongCat step of 1,040 x 12 selections: 200 rows held and live, one
    # block of 256 moved; every row moved reads 62.4
    (256, 200, 1.28), (12480, 200, 62.4),
    # every pick held, a fifth of the rung dead: 14 blocks of 256 for 3,380
    (3584, 3380, 3584 / 3380),
    (128, 128, 1.0)])
def test_the_ratio_is_moved_over_computed(moved, computed, ratio):
    got = read({"counters": {"expert_rows": computed, "experts_touched": 8,
                             "expert_rows_moved": moved}})
    assert got == pytest.approx(ratio) and got >= 1


def test_its_entry_follows_the_older_ones():
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = by_name[NAME]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "routed experts",
        "moves": "tokens_per_s",
        "workloads": by_name["expert_rows_per_token"]["workloads"]}
    assert len(entry["workloads"]) == 4
    assert list(by_name)[-1] == NAME
    e2e = {m["name"]: m for m in bench["end_to_end"]}["tokens_per_s"]
    assert all(c in e2e.get("workloads", entry["workloads"])
               for c in entry["workloads"])


#: the stat vector's optional groups of lanes by served family: routed
#: (OLMoE), mixed cache kinds (Mellum2), a share over latent pages (LongCat),
#: latent pages and a prediction module (GLM), a share beside recurrent
#: states (Qwen3-Next)
FAMILIES = {
    "routed": {},
    "layered": {"layered": True},
    "share-latent": {"share": True, "latent": True},
    "latent-mtp": {"latent": True, "mtp": True},
    "share-recurrent": {"share": True, "layered": True, "recurrent": True},
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_lane_rides_behind_the_experts_three(family):
    """``expert_rows_moved`` is the routed experts' fourth lane in every
    family's vector: the share's, the attention's and the last lanes follow
    it, and the host reads each frame's delta from there."""
    import numpy as np
    from deepspeed_tpu.inference.v2 import telemetry as T
    flags = FAMILIES[family]
    assert T.MOE_STAT_NAMES + T.MOVED_STAT_NAMES == (
        "expert_rows", "experts_touched", "expert_rows_max",
        "expert_rows_moved")
    lanes = T.n_stats(True, **flags)
    assert lanes - T.n_stats(False, **{k: v for k, v in flags.items()
                                        if k != "share"}) \
        == 4 + (len(T.SHARE_STAT_NAMES) if flags.get("share") else 0)
    tel = T.ServingTelemetry()
    recurrent = flags.pop("recurrent", False)
    tel.begin_serve(speculate=False, gamma=0, adaptive=False, n_slots=4,
                    kv_blocks_total=8, recurrent_slot_bytes=64 * recurrent,
                    **flags)
    delta = np.zeros(lanes, np.int64)
    delta[T.STAT_EXPERT_ROWS:T.STAT_EXPERT_ROWS + 4] = (200, 9, 40, 256)
    if flags.get("share"):
        delta[T.STAT_EXPERT_ROWS + 4:T.STAT_EXPERT_ROWS + 7] = (9600, 3000,
                                                                6400)
    tel.on_frame(delta=delta, width=128, steps=1, live_slots=4,
                 kv_blocks_in_use=2, arrival_ewma=0.0, queue_depth=0,
                 kv_kinds=None)
    c = tel.counters
    assert (c["expert_rows"], c["expert_rows_max"],
            c["expert_rows_moved"]) == (200, 40, 256)
    if flags.get("share"):
        assert c["expert_selections"] == 9600
    assert read({"counters": c}) == pytest.approx(1.28)
