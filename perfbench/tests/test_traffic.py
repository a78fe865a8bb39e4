"""The general generator: the same file and seed give the same schedule, a
longer horizon continues it, and the files the cells name say what the
issue says."""

import os

import numpy as np

from perfbench import draws, harness


def traffic(name):
    return harness.read_json(os.path.join(harness.HERE, "traffic",
                                          f"{name}.json"))


def plan(name, seed, horizon):
    params = traffic(name)
    gen = harness.load_module("generators", params["generator"])
    return gen.plan(params, seed, horizon)


def test_open_loop_is_seeded_and_prefix_stable():
    a = plan("chat-steady", 5, 45.0)["requests"]
    b = plan("chat-steady", 5, 45.0)["requests"]
    c = plan("chat-steady", 5, 20.0)["requests"]
    d = plan("chat-steady", 6, 45.0)["requests"]
    assert a == b and a[:len(c)] == c and a != d
    assert all(0 < r["t"] < 45.0 for r in a)
    assert [r["t"] for r in a] == sorted(r["t"] for r in a)
    assert [r["key"] for r in a] == list(range(len(a)))


def test_lengths_stay_inside_their_clips():
    big = dict(traffic("mixed-queue"))
    big["arrivals"] = {"process": "poisson", "rate": 200.0}
    gen = harness.load_module("generators", "open_loop")
    reqs = gen.plan(big, 1, 20.0)["requests"]
    chat = [r for r in reqs if r["cls"] == "chat"]
    long_ = [r for r in reqs if r["cls"] == "long"]
    assert 0.07 < len(long_) / len(reqs) < 0.13
    assert all(32 <= r["prompt_len"] <= 2048 and 16 <= r["max_new"] <= 512
               for r in chat)
    assert all(1024 <= r["prompt_len"] <= 7168 and 16 <= r["max_new"] <= 64
               for r in long_)
    assert 230 < np.median([r["prompt_len"] for r in chat]) < 285
    assert 2700 < np.median([r["prompt_len"] for r in long_]) < 3500
    # long prompts are about 45% of the tokens
    tok = lambda rs: sum(r["prompt_len"] + r["max_new"] for r in rs)  # noqa
    assert 0.35 < tok(long_) / tok(reqs) < 0.55
    # no request outgrows the served context
    assert max(r["prompt_len"] + r["max_new"] for r in reqs) + 1 <= 8192


def test_poisson_arrivals_hold_their_rate():
    rng = draws.stream(1, 2)
    gaps = [draws.draw_gap(rng, {"process": "poisson", "rate": 4.0})
            for _ in range(20000)]
    assert abs(np.mean(gaps) - 0.25) < 0.02
    assert 0.9 < np.std(gaps) / np.mean(gaps) < 1.1


def test_a_second_draw_is_the_same_mix_on_another_schedule():
    a, b = traffic("chat-steady"), traffic("chat-steady-draw2")
    assert a["schedule_seed"] != b["schedule_seed"]
    same = lambda t: {k: v for k, v in t.items()  # noqa: E731
                      if k not in ("schedule_seed", "what")}
    assert same(a) == same(b)


def test_closed_loop_gives_each_client_its_own_sequence():
    p = plan("longprompt-closed", 9, 45.0)
    assert p["mode"] == "closed" and p["clients"] == 8
    mine = [[r for r in p["requests"] if r["client"] == c] for c in range(8)]
    assert all(len(m) >= 100 for m in mine)
    assert [r["prompt_len"] for r in mine[0][:5]] != \
        [r["prompt_len"] for r in mine[1][:5]]
    assert len({r["key"] for r in p["requests"]}) == len(p["requests"])
    # the callers start one after another over ramp_s, not in lock-step
    assert p["starts"] == [float(c) for c in range(8)]


def test_tokens_come_from_the_seed():
    r = {"key": 3, "prompt_len": 40}
    a = draws.tokens_for(7, r, 32000)
    assert a == draws.tokens_for(7, r, 32000) != draws.tokens_for(8, r, 32000)
    assert a != draws.tokens_for(7, dict(r, key=4), 32000)
    assert len(a) == 40 and all(0 <= t < 32000 for t in a)


def test_train_batches_are_fresh_each_step():
    gen = harness.load_module("generators", "train_steps")
    a, b = gen.batch_for(1, 0, 4, 64, 32000), gen.batch_for(1, 1, 4, 64, 32000)
    assert a["input_ids"].shape == (4, 64)
    assert (a["input_ids"][:, 1:] == a["labels"][:, :-1]).all()
    assert not (a["input_ids"] == b["input_ids"]).all()
    assert (gen.batch_for(1, 0, 4, 64, 32000)["labels"] == a["labels"]).all()
