#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile the benchmark's
programs at FULL size with the chip's compiler, for a described
``v5e:2x2`` topology, with no chip attached. What the compiler refuses
here costs no chip time; what it accepts gives ``memory_analysis()``, the
ground for slots, pages and remat (recorded in PERF.md).

    JAX_PLATFORMS=cpu python3 perfbench/compile_full.py serve  <config> [table_width ...]
    JAX_PLATFORMS=cpu python3 perfbench/compile_full.py train  <config> <traffic> [remat]

Nothing runs, so this gives no time and no result; it is never reported as
a chip run. The serving engine is built on the CPU with zero weights (its
constructor wants arrays), then both frame widths are lowered from shapes
placed on the described device.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402

from perfbench import harness  # noqa: E402

GB = 1e9


def describe():
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def report(name, compiled):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: args {m.argument_size_in_bytes / GB:.2f} GB, temp "
          f"{m.temp_size_in_bytes / GB:.2f} GB, out "
          f"{m.output_size_in_bytes / GB:.2f} GB, alias "
          f"{m.alias_size_in_bytes / GB:.2f} GB -> live "
          f"{(m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes) / GB:.2f}"
          f" GB; tpu_custom_call x{text.count('tpu_custom_call')}, "
          f"all-gather x{text.count('all-gather')}, reduce-scatter "
          f"x{text.count('reduce-scatter')}, all-reduce "
          f"x{text.count('all-reduce')}", flush=True)


def serve(config_name, table_widths):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_model, get_config
    config = harness.read_json(os.path.join(
        harness.HERE, "configs", f"{config_name}.json"))
    s = config["serve"]
    cfg = get_config(config["preset"], **harness.preset_overrides(config))
    model = build_model(cfg.replace(param_dtype=cfg.dtype))
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                          model.abstract_params())
    eng = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            dtype=cfg.dtype, max_ragged_batch_size=s["batch"],
            num_kv_blocks=s["kv_blocks"]),
        params=params, max_seq_len=s["max_seq_len"])
    chip = SingleDeviceSharding(describe().devices[0])
    jax.default_backend = lambda: "tpu"      # the kernels ask; steer them

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def like(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    b = s["batch"]
    i32, f32 = jnp.int32, jnp.float32
    key = jax.random.PRNGKey(0)
    frame = eng.runner._build_frame_loop()
    prompt_width = s["max_seq_len"]
    for table_width in table_widths or [eng.max_blocks_per_seq]:
        for width in (eng._config.prefill_chunk_size, 1):
            args = (like(eng.params), sds((b, prompt_width), i32),
                    sds((b,), i32), sds((b,), i32), sds((b,), i32),
                    sds((b,), f32), sds((b, table_width), i32),
                    sds((b,), i32), sds((b,), i32), sds((b,), i32),
                    sds((b,), bool), sds((b,), bool), sds((b,), bool),
                    sds((7,), i32), sds(key.shape, key.dtype),
                    like(eng.kv.k), like(eng.kv.v))
            compiled = frame.lower(*args, width=width,
                                   steps=eng._config.frame_steps,
                                   greedy=True).compile()
            report(f"frame width {width}, table {table_width} pages, "
                   f"prompt buffer {prompt_width}", compiled)


def train(config_name, traffic_name, remat=None):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, get_config
    from deepspeed_tpu.parallel import sharding as shd
    from deepspeed_tpu.utils import groups
    config = harness.read_json(os.path.join(
        harness.HERE, "configs", f"{config_name}.json"))
    traffic = harness.read_json(os.path.join(
        harness.HERE, "traffic", f"{traffic_name}.json"))
    topo = describe()
    jax.default_backend = lambda: "tpu"
    overrides = harness.preset_overrides(config)
    overrides["max_seq_len"] = traffic["seq_len"]
    if remat:
        overrides["remat"] = remat
    model = build_model(get_config(config["preset"], **overrides))
    # the engine builds its state on a mesh of real devices: give it the
    # CPU's four, then lower its own step for the described chips
    cpu_mesh = groups.build_mesh(devices=jax.devices()[:4])
    groups.reset_mesh()
    groups.set_mesh(cpu_mesh)
    ds_config = dict(config["ds_config"])
    ds_config.update(
        train_micro_batch_size_per_gpu=traffic["micro_batch_per_chip"],
        gradient_accumulation_steps=traffic["gradient_accumulation_steps"],
        steps_per_print=10 ** 9)
    engine, _, _, _ = ds.initialize(model=model, config=ds_config)
    tpu_mesh = Mesh(
        __import__("numpy").asarray(topo.devices).reshape(
            cpu_mesh.devices.shape), cpu_mesh.axis_names)

    def moved(x):
        if isinstance(x, NamedSharding) and x.mesh == cpu_mesh:
            return NamedSharding(tpu_mesh, x.spec)
        return x

    def on_tpu(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=moved(a.sharding)), tree)

    # every sharding the engine keeps names the CPU mesh: move them to the
    # described chips and have the engine build its step again
    for name, value in list(vars(engine).items()):
        if name.endswith("shardings") or name == "_replicated":
            setattr(engine, name, jax.tree.map(
                moved, value,
                is_leaf=lambda x: isinstance(x, NamedSharding)))
    engine.mesh = tpu_mesh
    groups.reset_mesh()
    groups.set_mesh(tpu_mesh)
    engine._compile_step_fns()

    rows = traffic["micro_batch_per_chip"] * 4
    gas = traffic["gradient_accumulation_steps"]
    ids = jax.ShapeDtypeStruct(
        (gas, rows, traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(tpu_mesh, jax.sharding.PartitionSpec(
            None, *list(shd.batch_spec(tpu_mesh))[:2])))
    batch = {"input_ids": ids, "labels": ids}
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    with tpu_mesh:
        compiled = engine._train_step_fn.lower(
            *on_tpu((engine.module_params, engine.opt_state,
                     engine.scaler_state)), batch, lr, gas=gas).compile()
    report(f"ZeRO-{engine.zero_stage} step, {config['num_hidden_layers']} "
           f"layers, seq {traffic['seq_len']}, remat "
           f"{overrides.get('remat')}", compiled)


if __name__ == "__main__":
    if sys.argv[1] == "serve":
        serve(sys.argv[2], [int(w) for w in sys.argv[3:]])
    else:
        train(*sys.argv[2:])
