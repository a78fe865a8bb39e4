"""Aux subsystem tests: MoE facade, launcher, elasticity, flops profiler,
curriculum/data pipeline, compression, universal checkpoint, zero_to_fp32,
hybrid engine (reference: tests/unit/{moe,launcher,elasticity,profiling,
data_efficiency,compression,checkpoint})."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model
from deepspeed_tpu.utils import groups


# ---- MoE facade ----

def test_moe_facade(mesh_8dp, rng):
    from deepspeed_tpu.moe.layer import MoE
    moe = MoE(hidden_size=32, num_experts=4, k=2, capacity_factor=2.0, ffn_dim=64)
    params = moe.init(rng)
    x = jax.random.normal(rng, (2, 8, 32))
    out, aux, counts = moe(params, x)
    assert out.shape == x.shape
    assert jnp.isfinite(aux)
    assert int(jnp.sum(counts)) > 0


def test_top1_gate(mesh_8dp, rng):
    from deepspeed_tpu.moe.layer import TopKGate
    gate = TopKGate(model_dim=16, num_experts=4, k=1, capacity_factor=2.0)
    params = gate.init(rng)
    tokens = jax.random.normal(rng, (32, 16))
    combine, dispatch, aux = gate(params, tokens)
    # each token dispatched at most once (top-1)
    per_token = jnp.sum(dispatch, axis=(1, 2))
    assert int(jnp.max(per_token)) <= 1


# ---- launcher ----

def test_hostfile_parse(tmp_path):
    from deepspeed_tpu.launcher.runner import parse_hostfile, parse_inclusion_exclusion
    hf = tmp_path / "hosts"
    hf.write_text("worker-0 slots=4\nworker-1 slots=4\n# comment\n")
    pool = parse_hostfile(str(hf))
    assert pool == {"worker-0": 4, "worker-1": 4}
    active = parse_inclusion_exclusion(pool, include_str="worker-1:0,2")
    assert active == {"worker-1": [0, 2]}
    active = parse_inclusion_exclusion(pool, exclude_str="worker-0")
    assert list(active) == ["worker-1"]
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(pool, include_str="a", exclude_str="b")


def test_launcher_dry_run(tmp_path, capsys):
    from deepspeed_tpu.launcher.runner import main
    hf = tmp_path / "hosts"
    hf.write_text("h1 slots=2\nh2 slots=2\n")
    rc = main(["--hostfile", str(hf), "--dry_run", "train.py", "--lr", "1e-4"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[h1]" in out and "[h2]" in out
    assert "WORLD_SIZE=4" in out and "NODE_RANK=1" in out


# ---- env report ----

def test_env_report():
    from deepspeed_tpu.env_report import env_info, op_report
    r = op_report()
    assert "cpu_adam" in r and "flash_attn" in r
    e = env_info()
    assert "jax version" in e


# ---- elasticity ----

def test_elastic_config_math():
    from deepspeed_tpu.elasticity.elasticity import (compute_elastic_config,
                                                     get_candidate_batch_sizes,
                                                     get_valid_gpus)
    # reference HCN semantics: each base scaled by the largest highly
    # composite number keeping it under the cap (8*6=48, 12*4=48)
    assert get_candidate_batch_sizes([8, 12], 50) == [48]
    assert get_candidate_batch_sizes([7], 50) == [42]
    assert get_valid_gpus(16, [2, 4], 1, 100) == [1, 2, 4, 8]
    cfg = {"elasticity": {"enabled": True, "micro_batch_sizes": [2, 4],
                          "max_train_batch_size": 64, "min_gpus": 1, "max_gpus": 16}}
    batch, gpus = compute_elastic_config(cfg)
    assert batch % 2 == 0 and len(gpus) > 0
    final, valid, mb = compute_elastic_config(cfg, world_size=8, return_microbatch=True)
    assert 8 in valid and final % (8 * mb) == 0


def test_elastic_incompatible_world_size():
    from deepspeed_tpu.elasticity.elasticity import (ElasticityIncompatibleWorldSize,
                                                     compute_elastic_config)
    cfg = {"elasticity": {"enabled": True, "micro_batch_sizes": [4],
                          "max_train_batch_size": 16, "min_gpus": 1, "max_gpus": 4}}
    with pytest.raises(ElasticityIncompatibleWorldSize):
        compute_elastic_config(cfg, world_size=1000)


# ---- flops profiler ----

def test_flops_profiler(mesh_8dp, rng):
    from deepspeed_tpu.profiling.flops_profiler.profiler import (FlopsProfiler,
                                                                 transformer_flops)
    model = build_model("tiny")
    params = model.init(rng)
    ids = jnp.zeros((2, 16), jnp.int32)
    prof = FlopsProfiler()
    cost = prof.profile_fn(model.apply, params, ids, run=True)
    assert prof.get_total_flops() > 0
    assert prof.get_total_duration() > 0
    report = prof.print_model_profile()
    assert "flops" in report

    est = transformer_flops(model.cfg, batch=2, seq=16)
    assert est["total_flops"] > 0 and est["params"] > 0


def test_analytic_param_count_matches_model():
    from deepspeed_tpu.profiling.flops_profiler.profiler import _param_count
    for preset in ("tiny", "gpt2-small", "llama2-7b"):
        model = build_model(preset)
        analytic = _param_count(model.cfg)
        actual = model.param_count()
        assert abs(analytic - actual) / actual < 0.02, (preset, analytic, actual)


# ---- curriculum / data pipeline ----

def test_curriculum_linear():
    from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
    sched = CurriculumScheduler({
        "curriculum_type": "fixed_linear", "min_difficulty": 8, "max_difficulty": 64,
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8}})
    assert sched.update_difficulty(0) == 8
    mid = sched.update_difficulty(50)
    assert 8 < mid < 64 and mid % 8 == 0
    assert sched.update_difficulty(100) == 64
    assert sched.update_difficulty(1000) == 64


def test_curriculum_discrete():
    from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
    sched = CurriculumScheduler({
        "curriculum_type": "fixed_discrete", "min_difficulty": 2, "max_difficulty": 10,
        "schedule_config": {"difficulty": [2, 5, 10], "max_step": [10, 20]}})
    assert sched.update_difficulty(5) == 2
    assert sched.update_difficulty(15) == 5
    assert sched.update_difficulty(25) == 10


def test_data_sampler_partition():
    from deepspeed_tpu.runtime.data_pipeline.data_sampler import DeepSpeedDataSampler
    seen = []
    for rank in range(2):
        s = DeepSpeedDataSampler(total_samples=32, micro_batch_size=2,
                                 data_parallel_rank=rank, data_parallel_size=2,
                                 gradient_accumulation_steps=2, shuffle=False)
        batches = list(s)
        assert all(len(b) == 2 for b in batches)
        seen.extend(np.concatenate(batches).tolist())
    assert sorted(seen) == list(range(32))  # full coverage, no overlap


def test_random_ltd(rng):
    from deepspeed_tpu.runtime.data_pipeline.basic_layer import RandomLayerTokenDrop
    layer = RandomLayerTokenDrop(lambda p, x: x * 2.0, keep_ratio=0.5)
    x = jnp.ones((2, 16, 4))
    out = layer(None, x, rng, train=True)
    doubled = int(jnp.sum(out == 2.0))
    kept = int(jnp.sum(out == 1.0))
    assert doubled == 2 * 8 * 4 and kept == 2 * 8 * 4


# ---- compression ----

def test_fake_quant_and_prune(rng):
    from deepspeed_tpu.compression.compress import fake_quantize, magnitude_prune
    w = jax.random.normal(rng, (64, 64))
    q = fake_quantize(w, bits=8)
    assert float(jnp.max(jnp.abs(q - w))) < float(jnp.max(jnp.abs(w))) / 127
    # straight-through gradient
    g = jax.grad(lambda w: jnp.sum(fake_quantize(w) ** 2))(w)
    assert jnp.all(jnp.isfinite(g))
    p = magnitude_prune(w, 0.5)
    assert 0.45 < float(jnp.mean(p == 0)) < 0.55


def test_layer_reduction(mesh_8dp, rng):
    from deepspeed_tpu.compression.compress import redundancy_clean
    model = build_model("tiny", num_layers=4)
    params = model.init(rng)
    cfg = {"compression_training": {"layer_reduction": {
        "enabled": True, "keep_layers": [0, 2]}}}
    reduced = redundancy_clean(params, cfg)
    assert jax.tree.leaves(reduced["layers"])[0].shape[0] == 2


# ---- universal checkpoint + zero_to_fp32 ----

def test_universal_checkpoint_reshard(tmp_path):
    """Save on dp8, resume on dp4+tp2 — the topology-free format reshards."""
    from deepspeed_tpu.checkpoint.universal import ds_to_universal, load_universal_checkpoint
    cfg = {"train_batch_size": 16, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 9, "seed": 3}
    groups.reset_mesh()
    model = build_model("tiny")
    e1, _, _, _ = ds.initialize(model=model, config=cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (16, 32))
    e1.train_batch({"input_ids": ids, "labels": ids})
    ds_to_universal(e1, str(tmp_path / "uni"))
    ref = np.asarray(e1.module_params["embed"]["tok"])

    groups.reset_mesh()
    groups.set_mesh(groups.build_mesh(data=4, tensor=2))
    model2 = build_model("tiny")
    e2, _, _, _ = ds.initialize(model=model2, config=dict(cfg))
    load_universal_checkpoint(e2, str(tmp_path / "uni"))
    np.testing.assert_allclose(ref, np.asarray(e2.module_params["embed"]["tok"]),
                               atol=1e-6)
    assert e2.global_steps == e1.global_steps
    # training continues on the new topology
    loss = e2.train_batch({"input_ids": ids, "labels": ids})
    assert np.isfinite(float(loss))


def test_zero_to_fp32(tmp_path):
    from deepspeed_tpu.utils.zero_to_fp32 import get_fp32_state_dict_from_zero_checkpoint
    cfg = {"train_batch_size": 16, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 9}
    groups.reset_mesh()
    model = build_model("tiny")
    engine, _, _, _ = ds.initialize(model=model, config=cfg)
    engine.save_checkpoint(str(tmp_path), tag="t0")
    sd = get_fp32_state_dict_from_zero_checkpoint(str(tmp_path), tag="t0")
    assert "embed.tok" in sd
    assert sd["embed.tok"].dtype == np.float32
    np.testing.assert_allclose(sd["embed.tok"],
                               np.asarray(engine.module_params["embed"]["tok"]))


# ---- hybrid engine ----

def test_hybrid_engine_generate(mesh_8dp):
    from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
    cfg = {"train_batch_size": 16, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 0}, "steps_per_print": 10 ** 9}
    engine = DeepSpeedHybridEngine(model=build_model("tiny"), config=cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 200, (2, 8))
    out = engine.generate(prompt, max_new_tokens=4, temperature=0.0)
    assert out.shape == (2, 12)
    # train a step, generate again (params updated in place)
    ids = rng.integers(0, 256, (16, 32))
    engine.train_batch({"input_ids": ids, "labels": ids})
    out2 = engine.generate(prompt, max_new_tokens=4, temperature=0.0)
    assert out2.shape == (2, 12)


def test_engine_emits_monitor_events(tmp_path):
    """The engine writes loss/lr/loss-scale/grad-norm/throughput samples to
    the monitor every steps_per_print (reference engine.py:2001,2222), not
    just lr."""
    import csv as csv_mod
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    groups.set_mesh(groups.build_mesh(data=8))
    cfg = {
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 2,
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "t"},
    }
    engine, _, _, _ = ds.initialize(model=build_model("tiny"), config=cfg)
    rng = np.random.default_rng(0)
    for _ in range(4):
        ids = rng.integers(0, 256, (16, 32))
        engine.train_batch({"input_ids": ids, "labels": ids})
    files = list((tmp_path).rglob("*.csv"))
    names = {f.stem.split("-")[-1] if "-" in f.stem else f.stem for f in files}
    joined = " ".join(str(f) for f in files)
    for key in ("loss", "lr", "loss_scale"):
        assert any(key in str(f) for f in files), (key, files)


# ---- autotuner strategies ----

def test_tuner_strategies():
    """Grid covers everything in order; random covers everything; model-based
    fits the saturating throughput curve and converges on the best candidate
    without exhausting the grid (reference autotuning/tuner/)."""
    from deepspeed_tpu.autotuning.tuner import (GridSearchTuner, ModelBasedTuner,
                                                RandomTuner, build_tuner)
    exps = [{"zero_stage": s, "micro_batch": mb}
            for s in (0, 1) for mb in (1, 2, 4, 8)]

    def true_tput(e):       # saturating in mb, stage 1 slightly slower
        base = e["micro_batch"] / (0.5 + 0.05 * e["micro_batch"])
        return base * (0.9 if e["zero_stage"] == 1 else 1.0)

    g = GridSearchTuner(exps)
    order = []
    while g.has_next():
        e = g.next_trial()
        order.append(e)
        g.update(e, true_tput(e))
    assert order == exps
    assert g.best()[0] == {"zero_stage": 0, "micro_batch": 8}

    r = RandomTuner(exps, seed=3)
    while r.has_next():
        e = r.next_trial()
        r.update(e, true_tput(e))
    assert r.best()[0] == {"zero_stage": 0, "micro_batch": 8}

    m = ModelBasedTuner(exps)
    for _ in range(6):      # under-budget: 6 of 8 trials
        e = m.next_trial()
        m.update(e, true_tput(e))
    assert m.best()[0]["micro_batch"] == 8   # model extrapolates to the top

    import pytest as _pytest
    with _pytest.raises(ValueError):
        build_tuner("nope", exps)


def test_autotuner_strategy_integration(monkeypatch):
    """Autotuner routes trials through the selected strategy."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner

    class FakeModel:
        class cfg:
            vocab_size = 16
        def param_count(self):
            return 1000

    at = Autotuner(FakeModel(), {}, micro_batch_candidates=(1, 2, 4),
                   zero_stage_candidates=(0, 1), strategy="model_based",
                   max_trials=4, remat_candidates=("none",))
    monkeypatch.setattr(
        at, "_trial",
        lambda s, mb, remat="none": mb / (0.5 + 0.1 * mb) * (0.8 if s else 1.0))
    patch = at.tune()
    assert patch["train_micro_batch_size_per_gpu"] == 4
    assert patch["zero_optimization"]["stage"] == 0
    assert len(at.results) <= 4


def test_autotuner_remat_dimension(monkeypatch):
    """remat joins the search space (round-5: "dots" is a measured
    THROUGHPUT win on HBM-bound parts, not only a memory knob): the
    heuristic runs a remat post-pass at the winning (stage, mb) and the
    returned patch carries the activation_checkpointing policy."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner

    class FakeModel:
        class cfg:
            vocab_size = 16
        def param_count(self):
            return 1000

    at = Autotuner(FakeModel(), {}, micro_batch_candidates=(1, 2),
                   zero_stage_candidates=(0,),
                   remat_candidates=("none", "dots"))
    monkeypatch.setattr(
        at, "_trial",
        lambda s, mb, remat="none": mb * (1.1 if remat == "dots" else 1.0))
    patch = at.tune()
    assert patch["train_micro_batch_size_per_gpu"] == 2
    assert patch["activation_checkpointing"]["policy"] == "dots"
    # the strategy path searches the full product including remat
    at2 = Autotuner(FakeModel(), {}, micro_batch_candidates=(1, 2),
                    zero_stage_candidates=(0,), strategy="gridsearch",
                    remat_candidates=("none", "dots"))
    monkeypatch.setattr(
        at2, "_trial",
        lambda s, mb, remat="none": mb * (1.1 if remat == "dots" else 1.0))
    patch2 = at2.tune()
    assert patch2["activation_checkpointing"]["policy"] == "dots"


def test_multinode_runners_build_commands():
    """Runner family (reference multinode_runner.py): each transport builds
    the right fan-out invocation from the per-node commands."""
    from collections import OrderedDict
    from deepspeed_tpu.launcher.multinode_runner import build_runner
    import pytest as _pytest

    world = OrderedDict([("h1", [0, 1]), ("h2", [0, 1])])
    per_node = [("h1", "ENV=1 python -m x"), ("h2", "ENV=1 python -m x")]

    pdsh = build_runner("pdsh", None, world).get_cmd(per_node)
    assert len(pdsh) == 2 and pdsh[0].startswith("pdsh -S -w h1 ")

    mpi = build_runner("openmpi", None, world).get_cmd(per_node)
    assert len(mpi) == 1 and "-H h1:2,h2:2" in mpi[0] and "-np 2" in mpi[0]

    slurm = build_runner("slurm", None, world).get_cmd(per_node)
    assert "--nodes=2" in slurm[0] and "--nodelist=h1,h2" in slurm[0]

    mpich = build_runner("mpich", None, world).get_cmd(per_node)
    assert "-hosts h1,h2" in mpich[0]

    with _pytest.raises(ValueError):
        build_runner("nope", None, world)


def test_compression_scheduler_offsets(rng):
    """Techniques activate at their schedule_offset and apply() transforms
    only the live ones (reference compression/scheduler.py)."""
    from deepspeed_tpu.compression.scheduler import CompressionScheduler
    cfg = {"compression_training": {
        "weight_quantization": {
            "shared_parameters": {"enabled": True, "schedule_offset": 2},
            "different_groups": {"g": {"params": {"start_bits": 8},
                                       "modules": ["mlp"]}}},
        "sparse_pruning": {
            "shared_parameters": {"enabled": True, "schedule_offset": 5},
            "different_groups": {"g": {"params": {"dense_ratio": 0.5},
                                       "modules": ["mlp"]}}},
    }}
    sched = CompressionScheduler(cfg)
    params = {"mlp": {"w": jax.random.normal(rng, (32, 32))}}
    assert sched.step() == []                       # step 1: nothing yet
    assert sched.step() == ["weight_quantization"]  # step 2
    p1 = sched.apply(params)
    assert float(jnp.sum(p1["mlp"]["w"] == 0.0)) < 32 * 32 * 0.4  # no pruning yet
    sched.step(3)
    assert sched.active_techniques() == ["weight_quantization", "sparse_pruning"]
    p2 = sched.apply(params)
    zeros = float(jnp.sum(p2["mlp"]["w"] == 0.0))
    assert zeros >= 32 * 32 * 0.5                   # pruned to dense_ratio


def test_comet_monitor_config_and_degradation():
    """Comet joins the monitor fan-out (reference monitor/comet.py); absent
    SDK degrades to disabled without erroring, and events still flow."""
    from deepspeed_tpu.runtime.config import DeepSpeedMonitorConfig
    from deepspeed_tpu.monitor.monitor import CometMonitor, MonitorMaster
    cfg = DeepSpeedMonitorConfig(comet={"enabled": True, "project": "p",
                                        "workspace": "w"})
    assert cfg.enabled
    m = MonitorMaster(cfg)
    assert any(isinstance(x, CometMonitor) for x in m.monitors)
    m.write_events([("loss", 1.0, 1)])   # no-op when SDK missing, no raise


def test_elastic_in_process_rejoin(tmp_path):
    """In-process elastic recovery (reference elastic_agent.py:32, minus the
    process restart): two OS processes train ZeRO-2; a universal snapshot is
    taken; rank 1 is killed; rank 0 — SAME PID — tears down the distributed
    runtime, rebuilds the mesh at world 1, reshards from the universal
    checkpoint, and keeps training."""
    import json
    import socket
    import subprocess
    import sys
    import textwrap

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    worker = tmp_path / "worker.py"
    worker.write_text(textwrap.dedent("""
        import json, os, sys, time
        sys.path.insert(0, %r)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import deepspeed_tpu as ds
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.elasticity.rejoin import InProcessElasticWorker
        from deepspeed_tpu.models import build_model
        from deepspeed_tpu.utils import groups

        RUN = os.environ["DS_TEST_RUN_DIR"]
        rank = int(os.environ["RANK"])
        pid0 = os.getpid()

        dist.init_distributed(verbose=False, elastic=True,
                              distributed_port=int(os.environ["DS_TEST_PORT"]))

        def make_engine(world):
            groups.reset_mesh()
            model = build_model("tiny")
            dp = len(jax.devices())
            engine, _, _, _ = ds.initialize(model=model, config={
                "train_batch_size": 2 * dp,
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "steps_per_print": 10 ** 9, "seed": 7})
            return engine

        w = InProcessElasticWorker(make_engine, os.path.join(RUN, "uckpt"),
                                   RUN, heartbeat_timeout=3.0)
        w.start(rank, 2)
        engine = make_engine(2)
        rng = np.random.default_rng(0)

        def step(engine):
            bs = engine.train_batch_size()
            ids = rng.integers(0, 256, (bs, 16))
            return float(engine.train_batch({"input_ids": ids, "labels": ids}))

        losses = [step(engine) for _ in range(3)]
        w.heartbeat()
        w.save_universal(engine)
        snap = np.asarray(jax.tree.leaves(engine.module_params)[0],
                          np.float32).copy()
        if rank == 1:
            os._exit(1)                      # hard death, no cleanup

        # rank 0: wait for the peer's heartbeat to go stale, then rejoin
        deadline = time.time() + 30
        while not w.membership_changed():
            if time.time() > deadline:
                raise RuntimeError("peer death never detected")
            time.sleep(0.5)
        engine = w.rejoin()
        assert os.getpid() == pid0            # same process, no restart
        assert jax.process_count() == 1
        assert engine.global_steps == 3       # resumed from the snapshot
        restore_err = float(np.max(np.abs(np.asarray(
            jax.tree.leaves(engine.module_params)[0], np.float32) - snap)))
        after = [step(engine) for _ in range(2)]
        assert all(np.isfinite(after))
        print("RESULT " + json.dumps({"losses": losses, "after": after,
                                      "restore_err": restore_err,
                                      "world_end": len(jax.devices())}))
    """) % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(MASTER_ADDR="127.0.0.1", WORLD_SIZE="2", JAX_PLATFORMS="cpu",
               DS_TEST_PORT=str(port), DS_TEST_RUN_DIR=str(tmp_path))
    procs = []
    try:
        for r in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, str(worker)], env=dict(env, RANK=str(r)),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        out0, _ = procs[0].communicate(timeout=300)
        procs[1].wait(timeout=30)
        assert procs[0].returncode == 0, out0.decode()[-2000:]
        line = [ln for ln in out0.decode().splitlines()
                if ln.startswith("RESULT ")][0]
        res = json.loads(line[len("RESULT "):])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    assert res["world_end"] == 2              # rank 0's two local devices
    assert len(res["after"]) == 2
    # state restoration is the property under test: the rebuilt engine's
    # params equal the pre-kill snapshot (the universal checkpoint was taken
    # at the same step), and post-rejoin training stays finite — a strict
    # loss-decrease over 2 random-batch steps would be stochastic
    assert res["restore_err"] <= 1e-5
    assert all(np.isfinite(res["after"]))


def test_xtc_binarize_ternarize():
    """XTC 1-/2-bit weight grids (reference Binary/TernaryQuantizer): value
    sets, scales, and straight-through gradients."""
    from deepspeed_tpu.compression.compress import (binarize, fake_quantize,
                                                    ternarize)
    w = jnp.asarray(np.random.default_rng(0).normal(size=(32, 16)), jnp.float32)
    b = binarize(w)
    # per-output-channel two-point grid
    for col in range(4):
        vals = np.unique(np.round(np.abs(np.asarray(b[:, col])), 6))
        assert len(vals) == 1
    np.testing.assert_allclose(np.asarray(jnp.abs(b).mean(0)),
                               np.asarray(jnp.abs(w).mean(0)), rtol=1e-5)
    t = ternarize(w)
    for col in range(4):
        vals = np.unique(np.round(np.asarray(t[:, col]), 6))
        assert len(vals) <= 3 and 0.0 in vals
    # STE: identity gradients through both
    g = jax.grad(lambda w: jnp.sum(binarize(w) * 3.0))(w)
    np.testing.assert_allclose(np.asarray(g), 3.0)
    # fake_quantize routes the XTC bit-widths
    np.testing.assert_allclose(np.asarray(fake_quantize(w, bits=1)),
                               np.asarray(b))


def test_activation_quant_model_trains():
    """act_quant_bits (QuantAct analog): quantized activations change the
    forward, training still converges, grads flow (STE)."""
    from deepspeed_tpu.models import build_model, get_config
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    cfg = get_config("tiny")
    m_ref = build_model(cfg)
    m_q = build_model(cfg.replace(act_quant_bits=8))
    params = jax.jit(m_ref.init)(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    ids = jnp.asarray(r.integers(0, 256, (2, 16)))
    la = float(m_ref.loss(params, {"input_ids": ids, "labels": ids}))
    lq = float(m_q.loss(params, {"input_ids": ids, "labels": ids}))
    assert abs(la - lq) > 1e-7            # quantization actually bites
    assert abs(la - lq) < 0.5             # ...but int8 stays close
    g = jax.grad(m_q.loss)(params, {"input_ids": ids, "labels": ids})
    assert all(np.all(np.isfinite(x)) for x in jax.tree.leaves(g))


def test_knowledge_distillation_loss():
    """DistilledModel: alpha mixes CE and KD; pure-KD training pulls the
    student toward the teacher's distribution on a fixed batch."""
    from deepspeed_tpu.compression.distillation import (DistilledModel,
                                                        kd_loss,
                                                        make_teacher_provider)
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    student = build_model("tiny")
    teacher = build_model("tiny")
    sp = jax.jit(student.init)(jax.random.PRNGKey(1))
    tp = jax.jit(teacher.init)(jax.random.PRNGKey(2))
    r = np.random.default_rng(0)
    ids = jnp.asarray(r.integers(0, 256, (2, 16)))
    batch = {"input_ids": ids, "labels": ids}

    provider = make_teacher_provider(teacher, tp)
    kbatch = provider(batch)
    assert kbatch["teacher_logits"].shape == (2, 16, 256)

    dm = DistilledModel(student, alpha=0.5, temperature=2.0)
    ce = float(student.loss(sp, batch))
    mixed = float(dm.loss(sp, kbatch))
    kd = float(kd_loss(student.apply(sp, ids), kbatch["teacher_logits"], 2.0))
    np.testing.assert_allclose(mixed, 0.5 * ce + 0.5 * kd, rtol=1e-5)
    # a batch without teacher logits degrades to the plain student loss
    np.testing.assert_allclose(float(dm.loss(sp, batch)), ce, rtol=1e-6)

    # pure KD descends toward the teacher on the fixed batch
    dm1 = DistilledModel(student, alpha=1.0, temperature=1.0)
    loss_g = jax.jit(jax.value_and_grad(dm1.loss))
    p = sp
    k0 = float(dm1.loss(p, kbatch))
    for _ in range(10):
        l, g = loss_g(p, kbatch)
        p = jax.tree.map(lambda a, b: a - 0.5 * b, p, g)
    assert float(dm1.loss(p, kbatch)) < k0


def test_distilled_model_trains_under_engine():
    """The XTC recipe config wraps the student via from_config and trains
    through deepspeed_tpu.initialize with teacher logits in the batch."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.compression.compress import xtc_recipe
    from deepspeed_tpu.compression.distillation import (DistilledModel,
                                                        make_teacher_provider)
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    teacher = build_model("tiny")
    tp = jax.jit(teacher.init)(jax.random.PRNGKey(2))
    recipe = xtc_recipe(keep_number_layer=1, schedule_offset=0)
    student = DistilledModel.from_config(build_model("tiny"), recipe)
    assert isinstance(student, DistilledModel)
    engine, _, _, _ = ds.initialize(model=student, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 9})
    provider = make_teacher_provider(teacher, tp)
    r = np.random.default_rng(0)
    ids = r.integers(0, 256, (8, 16))
    batch = provider({"input_ids": ids, "labels": ids})
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_distilled_model_gets_engine_dtype_override():
    """Engine precision overrides must reach the WRAPPED student (setting
    cfg on the wrapper would shadow-attribute and silently change nothing)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.compression.distillation import DistilledModel
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    student = DistilledModel(build_model("tiny"), alpha=0.5)
    engine, _, _, _ = ds.initialize(model=student, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True}, "steps_per_print": 10 ** 9})
    assert student.student.cfg.dtype == "bfloat16"
    assert "cfg" not in vars(student)   # no shadow attribute on the wrapper


def test_op_builder_prebuild_all():
    """AOT prebuild path (reference DS_BUILD_OPS analog): every registered
    op builds or reports a reasoned skip; nothing raises."""
    from deepspeed_tpu.ops.op_builder import ALL_OPS, build_all
    results = build_all(verbose=False)
    assert set(results) == {cls().name for cls in ALL_OPS.values()}
    assert all(s.startswith(("ok", "skipped")) for s in results.values()), results


def test_row_pruning_masks_trains_and_shrinks(mesh_8dp, rng):
    """Structured row/channel pruning (reference basic_layer.py:166/212):
    init_compression MASKS the low-norm intermediate channels (train stage);
    redundancy_clean physically SLICES them (dim_reduction) — the shrunk
    model's forward equals the masked model's, and the pruned model trains."""
    from deepspeed_tpu.compression.compress import (init_compression,
                                                    redundancy_clean)
    from deepspeed_tpu.models import build_model
    cfg_kw = dict(vocab_size=256, hidden_size=32, num_layers=2, num_heads=4,
                  intermediate_size=64, max_seq_len=64, dtype="float32",
                  activation="gelu", tie_embeddings=True)
    from deepspeed_tpu.models.config import TransformerConfig
    model = build_model(TransformerConfig(**cfg_kw))
    params = model.init(rng)
    comp = {"compression_training": {"row_pruning": {
        "shared_parameters": {"enabled": True},
        "different_groups": {"rp1": {"params": {"dense_ratio": 0.5}}}}}}

    masked = init_compression(params, comp)
    wi = np.asarray(masked["layers"]["mlp"]["wi"])
    assert wi.shape == (2, 32, 64)                       # shapes unchanged
    zero_channels = (np.abs(wi).sum(axis=1) == 0).sum(axis=1)
    np.testing.assert_array_equal(zero_channels, [32, 32])   # half masked

    # physical dim reduction picks the SAME channels: forwards agree exactly
    shrunk = redundancy_clean(masked, comp)
    assert shrunk["layers"]["mlp"]["wi"].shape == (2, 32, 32)
    assert shrunk["layers"]["mlp"]["wo"].shape == (2, 32, 32)
    small = build_model(TransformerConfig(**{**cfg_kw, "intermediate_size": 32}))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)))
    out_masked = model.apply(masked, ids)
    out_small = small.apply(shrunk, ids)
    np.testing.assert_allclose(np.asarray(out_masked), np.asarray(out_small),
                               rtol=1e-5, atol=1e-5)

    # the pruned model trains
    import deepspeed_tpu as ds
    engine, _, _, _ = ds.initialize(model=small, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10 ** 9})
    engine.module_params = jax.device_put(shrunk, engine.param_shardings)
    engine._resync_masters_from_params()
    rng2 = np.random.default_rng(1)
    bids = rng2.integers(0, 256, (8, 16))
    losses = [float(engine.train_batch({"input_ids": bids, "labels": bids}))
              for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_rejoin_membership_consensus_skewed_detection(tmp_path):
    """The failure mode the consensus exists for: two survivors detect the
    failure at DIFFERENT times. The early one publishes; the late one must
    adopt the PUBLISHED epoch (not wait on a self-computed future epoch and
    fall back to a divergent local view). Pure-filesystem test, no jax."""
    import threading
    import time as _t
    from deepspeed_tpu.elasticity.rejoin import InProcessElasticWorker

    run_dir = str(tmp_path)
    w0 = InProcessElasticWorker(lambda w: None, "/unused", run_dir,
                                heartbeat_timeout=2.0)
    w1 = InProcessElasticWorker(lambda w: None, "/unused", run_dir,
                                heartbeat_timeout=2.0)
    w0.start(0, 3)
    w1.start(1, 3)           # rank 2 never heartbeats → dead

    res = {}
    t0 = threading.Thread(target=lambda: res.setdefault("w0",
                                                        w0._agree_alive()))
    t0.start()               # rank 0 detects first, publishes membership.1
    _t.sleep(1.5)            # rank 1 detects LATE, after the publish
    res["w1"] = w1._agree_alive()
    t0.join(10)
    assert res["w0"] == res["w1"] == [0, 1]
    assert w0._epoch == w1._epoch == 1       # both consumed the same epoch

    # a second failure event later: epochs advance by scan, not blind count
    with open(os.path.join(run_dir, "heartbeat.1"), "w") as f:
        f.write("0")         # rank 1's heartbeat goes stale epoch-wise
    os.utime(os.path.join(run_dir, "heartbeat.1"), (0, 0))
    w0.rank, w0.world = 0, 2
    alive2 = w0._agree_alive()
    assert alive2 == [0]
    assert w0._epoch == 2


def test_launcher_local_end_to_end(tmp_path):
    """REAL execution of the localhost launch path (not a command-string
    test): dstpu main() → launch.py spawner → 2 worker OS processes, each
    seeing its RANK/LOCAL_RANK/WORLD_SIZE/MASTER_* env (reference
    launcher/launch.py:133 semantics). Also: a failing worker propagates a
    non-zero exit through the whole chain."""
    import textwrap
    from deepspeed_tpu.launcher.runner import main

    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        out = os.path.join(os.environ["OUT_DIR"],
                           f"rank{os.environ['RANK']}.json")
        with open(out, "w") as f:
            json.dump({k: os.environ.get(k) for k in
                       ("RANK", "LOCAL_RANK", "WORLD_SIZE", "NODE_RANK",
                        "MASTER_ADDR", "MASTER_PORT")}, f)
        sys.exit(int(sys.argv[1]) if len(sys.argv) > 1 else 0)
    """))
    os.environ["OUT_DIR"] = str(tmp_path)
    try:
        # EXPORT_ENVS must carry OUT_DIR through the shell hop
        from deepspeed_tpu.launcher import runner as rmod
        rmod.EXPORT_ENVS.append("OUT_DIR")
        rc = main(["--num_gpus", "2", str(script)])
        assert rc == 0
        import json
        got = {}
        for r in (0, 1):
            with open(tmp_path / f"rank{r}.json") as f:
                got[r] = json.load(f)
        assert got[0]["RANK"] == "0" and got[1]["RANK"] == "1"
        assert got[0]["LOCAL_RANK"] == "0" and got[1]["LOCAL_RANK"] == "1"
        assert got[0]["WORLD_SIZE"] == got[1]["WORLD_SIZE"] == "2"
        assert got[0]["MASTER_ADDR"] and got[0]["MASTER_PORT"]
        # failure propagation: worker exit 3 → launcher returns non-zero
        rc_bad = main(["--num_gpus", "2", str(script), "3"])
        assert rc_bad != 0
    finally:
        rmod.EXPORT_ENVS.remove("OUT_DIR")
        os.environ.pop("OUT_DIR", None)


@pytest.mark.parametrize("nproc,env,expect_chip", [
    (1, {}, None),                                  # one worker drives every chip
    (4, {}, "2"),                                   # else: its own chip each
    (4, {"JAX_PLATFORMS": "cpu"}, None),            # CPU workers need no chip
    (4, {"TPU_VISIBLE_CHIPS": "0,1"}, None),        # the caller partitioned
], ids=["nproc1", "nproc4", "cpu-pinned", "caller-set"])
def test_launcher_gives_each_worker_its_own_chip(monkeypatch, nproc, env,
                                                 expect_chip):
    """A chip belongs to one process at a time: ``--nproc`` > 1 workers
    must not all inherit every local chip."""
    from deepspeed_tpu.launcher.launch import _chip_env
    for var in ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS"):
        monkeypatch.delenv(var, raising=False)
    for var, value in env.items():
        monkeypatch.setenv(var, value)
    got = _chip_env(2, nproc)
    assert got.get("TPU_VISIBLE_CHIPS") == expect_chip
    if expect_chip is not None:
        assert got["TPU_PROCESS_BOUNDS"] == "1,1,1"
