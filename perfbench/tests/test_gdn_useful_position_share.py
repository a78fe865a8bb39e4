"""The reader of ``gdn_useful_position_share``: 100 x the positions the
linear layers needed over those the delta rule computed, from the window's
counters; it leaves the metric out (None, no exception) where the program
does not count the second, as the parent commit and the other models do not;
its entry in BENCHMARK.json follows every older one."""

import os

import pytest

from perfbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "gdn_useful_position_share"
CELL = "qwen3next-longdoc-closed"


def read(ctx):
    return harness.load_module("layer_metrics", NAME).read(ctx)


@pytest.mark.parametrize("ctx", [
    {}, {"counters": None}, {"counters": {}},
    # the parent's counters: the rule's work, not what was computed
    {"counters": {"gdn_positions": 3600, "gdn_state_rw": 96,
                  "positions_computed": 9000}},
    # a window in which no step ran
    {"counters": {"gdn_positions": 0, "gdn_positions_computed": 0}}])
def test_nothing_to_read_leaves_the_metric_out(ctx):
    assert read(ctx) is None


@pytest.mark.parametrize("needed,computed,share", [
    (3600, 12288, 100.0 * 3600 / 12288),
    # a step of 16 rows x 128: 4 rows prefill in two trips, 12 ride, in 6
    # layers: 6 x (4 x 128 + 12) of 6 x (4 x 128 + 16)
    (6 * 524, 6 * 528, 100.0 * 524 / 528),
    (96, 96, 100.0)])
def test_the_share_is_needed_over_computed(needed, computed, share):
    got = read({"counters": {"gdn_positions": needed, "gdn_state_rw": 6,
                             "gdn_positions_computed": computed}})
    assert got == pytest.approx(share) and 0 < got <= 100


def test_its_entry_follows_the_older_ones():
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "frame program",
        "moves": "tokens_per_s", "workloads": [CELL]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("recurrent_state_share")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert entry["moves"] in e2e and cell["chips"] == 1
