"""Rehearsal compiles of SDAR-30B-A3B's frame programs for the chip, without
the chip (``tests/test_chip_compile.py``'s method and fixtures, in a file of
its own so that the compile sits on another worker than that file's)."""

import re

import jax
import jax.numpy as jnp
import pytest

from test_chip_compile import (PAGE, _assert_commits_in_place,  # noqa: F401
                               as_tpu, four_chips, one_chip)


@pytest.mark.parametrize("width", [8, 128], ids=["narrow", "wide"])
def test_sdar_frame_programs_fit_the_chip(one_chip, as_tpu, width):
    """The benchmark's SDAR-30B-A3B configuration (published widths, 6 of 48
    layers, every one of 128 experts, the whole vocabulary, bf16; 16 slots,
    8 steps, 513 pages of 128, sequences to 4,096; blocks of 4): both frame
    programs compile with the chip's compiler from shapes alone. The narrow
    one is TWO BLOCKS wide (a fused step forwards the block it commits and
    the next): its paged kernel is the by-head one at 8 query positions a
    row (``paged_attn_c8``: 64 query rows a KV head, every KV head a step),
    under the mask that lets a position see its block. Both commit in
    place, hold the grouped-product kernel three times a rung and
    ``ragged_dot`` nowhere, NO buffer shaped like one layer's stack of
    experts, the head's logits for 4 positions a row, never for the narrow
    step's 8 or a chunk's 128, and arguments and temporaries under the
    chip's 15.75 GB."""
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 513, 4096
    cfg = get_config("sdar-30b-a3b", num_layers=6,
                     remasking_strategy="low_confidence_static")
    assert cfg.dtype == "bfloat16" and cfg.block_length == 4
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pool = sds((cfg.num_layers, cfg.kv_heads, pages, PAGE, cfg.dims_per_head),
               jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    runner = PagedModelRunner(model, PAGE, seq // PAGE)
    blk = cfg.block_length
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pool, pool,
        block=(sds((slots, blk), i32), sds((slots, blk), flag)),
        width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    rungs = len(pack_ladder(slots, width))
    assert len(re.findall(rf"%paged_attn_c{width}\S* = ", text)) == 1
    _assert_commits_in_place(compiled, text, pool, 1e3)
    assert len(re.findall(r"%grouped_mm_m128\S* = ", text)) == 3 * rungs
    assert "ragged-dot" not in text
    one_layer = re.findall(
        r"= bf16\[(?:1,)?128,(?:2048,768|768,2048)\]\S* (\w[\w-]*)\(", text)
    assert not one_layer, one_layer
    # the head runs on a block's positions a row
    assert re.search(rf"f32\[{slots},{blk},{cfg.vocab_size}\]", text)
    assert not re.search(rf"\[{slots},{width},{cfg.vocab_size}\]", text)
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"sdar frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert total < 15.75e9, total
