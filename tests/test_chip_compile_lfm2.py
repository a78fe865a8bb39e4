"""Rehearsal compiles of LFM2-24B-A2B's frame programs for the chip, without
the chip (``tests/test_chip_compile.py``'s method and fixtures, in a file of
its own so that the compile, 45 s a program, sits on another worker than
that file's, as ``tests/test_chip_compile_sdar.py`` does)."""

import jax
import jax.numpy as jnp
import pytest

from test_chip_compile import (PAGE, as_tpu, four_chips,  # noqa: F401
                               one_chip)

LFM2_CUT = ("conv", "full", "conv", "conv", "conv", "full", "conv", "conv",
            "conv")


@pytest.mark.parametrize("width, mixers", [(1, LFM2_CUT), (128, LFM2_CUT[:3])],
                         ids=["narrow", "wide"])
def test_lfm2_frame_programs_fit_the_chip(one_chip, as_tpu, width, mixers):
    """The benchmark's LFM2-24B-A2B configuration (published widths; layers
    1..9 of 40: one leading dense layer and two periods ``full conv conv
    conv`` of routed layers, all 64 experts, the whole vocabulary, bf16; 16
    slots, 8 steps, sequences to 4,096: tables of 32 pages over pools of
    513 pages for the TWO full layers, 8 KV heads of 64 lanes; beside them
    every slot's convolution tails (7, 2, 16, 2048) bf16 and nothing else):
    the frame programs compile with the chip's compiler from shapes alone.
    The narrow program is the cell's whole stack; the wide one (three rungs
    of everything: 88 s of compile at nine layers) its first three layers,
    ``conv | full conv``: the dense conv layer, a routed attention layer
    and a routed conv layer, each kind of layer the stack has (the whole
    wide program compiled with 0.147 GB of temporaries, PR 51, and runs in
    the cell).
    Mosaic refuses a page copy whose minor extent is 64 lanes ("Slice shape
    along dimension 4 must be aligned to tiling (128), but is 64": this
    test, with the pool (2, 8, 513, 128, 64)), so on the chip two heads
    share a 128-lane row (``kv_cache.heads_per_row``): the pool is (2, 4,
    513, 128, 128), 4,096 B a token as published, and the kernels run at a
    shape they had (4 heads of 128 lanes, 8 query rows a head).
    The stack is one scan step, its layers unrolled: the paged kernel once
    a full layer and the commit once after the walk, the grouped-product
    kernel three times a routed layer and rung, NO value shaped like a pool
    that XLA copied, no buffer shaped like a layer's experts, and arguments
    and temporaries under 15.75 GB."""
    import re
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 513, 4096
    cfg = get_config("lfm2-24b-a2b", num_layers=len(mixers),
                     moe_first_dense=1, mixer_pattern=mixers)
    full, conv = mixers.count("full"), mixers.count("conv")
    routed = len(mixers) - 1
    assert cfg.dtype == "bfloat16" and cfg.conv_layers == conv
    assert cfg.cache_layers == full and not cfg.moe_is_share
    assert (cfg.kv_heads, cfg.dims_per_head) == (8, 64)
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    from deepspeed_tpu.inference.v2.kv_cache import heads_per_row
    assert heads_per_row(cfg.kv_heads, cfg.dims_per_head) == 2
    pool = sds((full, 4, pages, PAGE, 128), jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    runner = PagedModelRunner(model, PAGE, seq // PAGE)
    assert runner.n_stats == 18 + 4 + 3 + 1
    tail, = (sds(*shape) for shape in runner.recurrent_shapes(slots))
    assert tail.shape == (conv, 2, 16, 2048) and tail.dtype == jnp.bfloat16
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pool, pool,
        recurrent=(tail,), width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    rungs = len(pack_ladder(slots, width))
    assert len(re.findall(rf"%paged_attn_\S*c{width}\S* = ", text)) == full
    assert len(re.findall(r"%paged_attn_\S* = ", text)) == full
    assert len(re.findall(r"%kv_commit_\S* = ", text)) == 1
    # 16 rows x 4 picks are 64 rows: the narrow step's product, and a wide
    # step's smallest rung's, is the kernel at tiles of 64 (as GLM's draft)
    assert len(re.findall(r"%grouped_mm_m\d+\S* = ", text)) \
        == routed * 3 * rungs
    assert len(re.findall(r"%grouped_mm_m64\S* = ", text)) == routed * 3
    assert "ragged-dot" not in text and "gdn_rule" not in text
    # (the tails are 0.9 MB in all: their seven updates a step are small
    # fusions, whatever XLA makes of them)
    shape = ",".join(map(str, pool.shape))
    made = re.findall(rf"%(\S+) = bf16\[{shape}\]\S* (copy|copy-start|fusion|"
                      rf"scatter|transpose)\(", text)
    assert not made, (shape, made)
    stacks = re.findall(
        r"= bf16\[(?:1,)?64,(?:2048,1536|1536,2048)\]\S* "
        r"(?!parameter|bitcast|get-tuple-element)(\w[\w-]*)\(", text)
    assert not stacks, stacks
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"lfm2 frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    if mixers == LFM2_CUT:
        assert 10.5e9 < m.argument_size_in_bytes < 10.8e9
    assert m.temp_size_in_bytes < 0.5e9
    assert total < 15.75e9, total
