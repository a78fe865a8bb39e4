"""Rehearsal compiles for the chip, without the chip.

The TPU compiler is installed with jaxlib and compiles for a DESCRIBED
``v5e:2x2`` topology (no device attached): each case lowers one kernel of
the main path at the shapes ``chip_smoke.py`` runs and asserts the Mosaic
custom call is in the compiled program. Interpret-mode tests cannot see
what these see — a slice the tiling refuses, too much fast memory, an op
the Mosaic verifier rejects (the C=1, GQA, bf16 decode step did exactly
that). A compile that passes here is not a chip run.

The kernels pick ``interpret`` from ``jax.default_backend()``; the CPU
suite sees "cpu" there, so the ``as_tpu`` fixture steers it — the program
itself gets no option for this.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu here: nothing to rehearse
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip — keep the cache off here
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# the serve phase's model (mistral-7b): 32 query / 8 KV heads, head_dim 128,
# engine-default page 128, sliding window 4096, bf16
H, KVH, D, PAGE, WINDOW = 32, 8, 128, 128, 4096


def _paged_step_shapes(sharding, h, kvh, chunk, table, b=16, layers=4,
                       pages=416, lanes=D):
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = sds((layers, kvh, pages, PAGE, lanes))
    return (sds((b, chunk, h, lanes)), pool, pool, sds((b, table), jnp.int32),
            sds((b, chunk), jnp.int32), sds((b, chunk, kvh, lanes)),
            sds((b, chunk, kvh, lanes)), sds((), jnp.int32))


def _paged_step(q, kpool, vpool, tables, positions, ck, cv, layer):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    return paged_ragged_attention(q, kpool, vpool, tables, positions, ck, cv,
                                  layer=layer, window=WINDOW)


@pytest.mark.parametrize("chunk", [1, 3, 128],
                         ids=["decode", "spec-verify", "prefill"])
def test_paged_attention_compiles_at_serve_widths(one_chip, as_tpu, chunk):
    text = _compile_text(_paged_step, *_paged_step_shapes(
        one_chip, H, KVH, chunk, table=8, b=8, pages=64))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,kvh,table", [(32, 8, 32), (32, 8, 64),
                                         (16, 16, 32)],
                         ids=["mistral-mb32", "mistral-mb64", "olmoe-mb32"])
def test_narrow_paged_attention_compiles_at_the_cells_shapes(one_chip, as_tpu,
                                                             h, kvh, table):
    """The decode step's kernel at the benchmark's shapes (16 slots, 416
    pages, the table widths the cells' contexts bucket to): every local KV
    head folded into one grid step a slot — the batched products over heads
    with 4 (GQA) or 1 (MHA) query rows, the hand-made page copies and the
    double buffer (4.2 / 8.4 MB) pass Mosaic's verifier and its VMEM."""
    text = _compile_text(_paged_step, *_paged_step_shapes(
        one_chip, h, kvh, 1, table))
    assert len(re.findall(r"%paged_attn_c1\S* = ", text)) == 1
    assert "tpu_custom_call" in text


# the serve cells' attention shapes: (query heads, kv heads, table width,
# window, ring, pool pages); 16 slots, pages of 128, head_dim 128, bf16
CELL_SHAPES = {
    "mistral": (32, 8, 64, WINDOW, None, 416),
    "olmoe": (16, 16, 32, 0, None, 416),
    "mellum2-full": (32, 4, 256, 0, None, 4097),
    "mellum2-ring": (32, 4, 10, 1024, 10, 161),
}


def _cell_step(name, chunk, sharding):
    """(the attention of one layer at a cell's shapes, its argument shapes)."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    h, kvh, table, window, ring, pages = CELL_SHAPES[name]
    kw = {"ring": ring} if ring else {}

    def step(q, kpool, vpool, tables, positions, ck, cv, layer):
        return paged_ragged_attention(q, kpool, vpool, tables, positions, ck,
                                      cv, layer=layer, window=window, **kw)

    return step, _paged_step_shapes(sharding, h, kvh, chunk, table, pages=pages)


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_wide_paged_attention_compiles_at_the_cells_shapes(one_chip, as_tpu,
                                                           cell):
    """A prefill chunk's kernel at the benchmark's shapes (C = 128: 512 rows
    a KV head in mistral, 128 in OLMoE, 1,024 in Mellum2 over its table of
    256 and its ring of 10): ONE Mosaic call a layer, named for the readers
    of the trace; the hand-made page copies of a step's heads, the double
    buffer, the loop over the row tiles that hold a live row (dynamic
    slices of 128 rows of the query, the positions and the running
    softmax) and a tile's (128, K x 128) f32 scores pass Mosaic's verifier
    inside the VMEM budget ``_tiling`` reckons with, under the compiler's
    scoped default."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    h, kvh, table, _, ring, _ = CELL_SHAPES[cell]
    rows = 128 * h // kvh
    heads, pages, tile = pa._tiling(rows, kvh, table, PAGE, D, 2)
    assert (heads, pages, tile) == {128: (4, 8, 128), 512: (4, 8, 128),
                                    1024: (2, 8, 128)}[rows]
    assert pa._step_bytes(heads, rows, pages * PAGE, D, 2, tile=tile) \
        <= pa._VMEM_BUDGET
    assert pa._VMEM_BUDGET < 16 << 20          # v5e's scoped default
    step, shapes = _cell_step(cell, 128, one_chip)
    text = _compile_text(step, *shapes)
    name = "paged_attn_ring_c128" if ring else "paged_attn_c128"
    assert len(re.findall(rf"%{name}\S* = ", text)) == 1
    assert len(re.findall(r"%paged_attn_\S* = ", text)) == 1
    assert "tpu_custom_call" in text


# sha256 of the traced programs (wrapper and kernel, no source locations:
# the Mosaic module's own text carries file lines and function names, the
# jaxpr does not) of the narrow step's and the verify step's attention at
# the cells' shapes, as PR 31 left them (`git archive ce6a163`)
NARROW_PAGED_JAXPRS = {
    ("mistral", 1):
        "482457c4dc95d38d744dd6c1db36af410f589fab3bdb850673d409370d348506",
    ("mistral", 3):
        "de2fe1ccec61abac1fbacd119bfc3473707820d07d6086414ecccdfc915c1bc4",
    ("olmoe", 1):
        "07318bfce0969fccf9fc0a1b9b4c3ad12dc5640f60514f1a98a1884570536dc2",
    ("olmoe", 3):
        "76a3d1d997934f040a901e4327aca6e7e8f86198662d9374bdc90319ddeb71f6",
    ("mellum2-full", 1):
        "566b1d7bde3318cbc8493e370ec0466f0fd81312318afed223803223f2d735d9",
    ("mellum2-ring", 1):
        "2e9ed8400371d87ed877cf5a53737eaa1c245fdd1bd62c92c035e094045ab124",
    ("mellum2-ring", 3):
        "87eb0914d0f80b03926ea53bbdf4eccdc52f2651445b20f4d1a6713bb2f2238f",
}


@pytest.mark.parametrize("cell,chunk", list(NARROW_PAGED_JAXPRS),
                         ids=[f"{n}-c{c}" for n, c in NARROW_PAGED_JAXPRS])
def test_narrow_paged_attention_is_the_program_it_was(cell, chunk):
    """PR 33 gave the wide tiling the narrow step's walk and must not move
    the narrow step: at C = 1 and C = 3 (speculation's verify step) what is
    traced — index maps, kernel body, operands — is PR 31's to the
    character. A change that means to move it re-pins."""
    import hashlib
    step, shapes = _cell_step(cell, chunk, None)
    jaxpr = jax.make_jaxpr(step)(*shapes)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == \
        NARROW_PAGED_JAXPRS[cell, chunk]


# the same of the latent format's two programs at LongCat-Flash-Omni's
# shape (64 query heads on the one head of a pool of 640-lane rows, values
# the first 512, 8 attention layers, tables of 128), as PR 34 left them
# (`git archive 162720a`)
LATENT_PAGED_JAXPRS = {
    1: "41488787d8ac541e277e73d9a3d37420d3725509ebb79b1adf41deabf3f4d742",
    128: "f734a903f55f5e602792d03119b99d4c870fadad44eb0ce8abf77ca0d7ac1329",
}


@pytest.mark.parametrize("chunk", list(LATENT_PAGED_JAXPRS),
                         ids=["narrow", "wide"])
def test_latent_paged_attention_is_the_program_it_was(chunk):
    """PR 38 cut the by-head wide step into row tiles inside the one page
    walk, which the latent kernel calls with position tiles of its own: its
    traced programs, narrow and wide, are PR 34's to the character."""
    import hashlib
    from deepspeed_tpu.ops.pallas.paged_attention import paged_ragged_attention
    q, pool, _, tables, positions, ck, _, layer = _paged_step_shapes(
        None, 64, 1, chunk, 128, layers=8, pages=2049, lanes=640)

    def step(q, pool, tables, positions, ck, layer):
        return paged_ragged_attention(q, pool, None, tables, positions, ck,
                                      None, layer=layer, value_lanes=512,
                                      scale=192 ** -0.5)

    jaxpr = jax.make_jaxpr(step)(q, pool, tables, positions, ck, layer)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == \
        LATENT_PAGED_JAXPRS[chunk]


@pytest.mark.parametrize("chunk", [1, 3, 128],
                         ids=["decode", "spec-verify", "prefill"])
def test_kv_commit_compiles_at_serve_widths(one_chip, as_tpu, chunk):
    """The page commit at the serve phase's widths: a Mosaic call whose
    pools are its results (donated: no copy of one beside it)."""
    from deepspeed_tpu.ops.pallas.kv_commit import kv_commit
    b, layers, pages, table = 8, 4, 64, 8
    dt = jnp.bfloat16

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers, KVH, pages, PAGE, D))
    compiled = jax.jit(kv_commit, donate_argnums=(0, 1)).lower(
        pool, pool, sds((layers, b, chunk, KVH, D)),
        sds((layers, b, chunk, KVH, D)), sds((b, table), jnp.int32),
        sds((b, chunk), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = layers * KVH * pages * PAGE * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2


@pytest.mark.parametrize("b,s,h,kvh,d,window", [
    (8, 1024, 16, 16, 64, None),          # train phase: gpt2-medium, micro 8
    (1, 1024, H, KVH, D, None),           # serve model's widths
    (1, 8192, H, KVH, D, WINDOW),         # ... with its window binding
], ids=["gpt2-medium", "mistral-7b", "mistral-7b-window"])
def test_flash_attention_fwd_bwd_compiles(one_chip, as_tpu, b, s, h, kvh, d,
                                          window):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    dt = jnp.bfloat16
    q = jax.ShapeDtypeStruct((b, s, h, d), dt, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kvh, d), dt, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward + the dq and dkv backward kernels
    assert text.count("tpu_custom_call") >= 3


def test_fused_adam_compiles(one_chip, as_tpu):
    """Mosaic has no scalar powf: the bias corrections stay outside."""
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_flat
    flat = jax.ShapeDtypeStruct((1 << 22,), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda p, g, m, v: fused_adam_flat(p, g, m, v, step=jnp.int32(3),
                                           lr=jnp.float32(1e-3)),
        flat, flat, flat, flat)
    assert "tpu_custom_call" in text


# the routed cells' experts: (hidden, expert width, experts held, layers
# stacked, selections a token); 16 slots, so 16 tokens the narrowest step
# and 2,048 the widest rung
EXPERT_SHAPES = {"olmoe": (2048, 1024, 64, 8, 8),
                 "mellum2": (2304, 896, 64, 8, 8),
                 "longcat": (6144, 2048, 16, 4, 12)}


@pytest.mark.parametrize("tokens", [16, 2048], ids=["narrowest", "widest"])
@pytest.mark.parametrize("cell", list(EXPERT_SHAPES))
def test_grouped_product_compiles_at_the_cells_shapes(one_chip, as_tpu, cell,
                                                      tokens):
    """The routed experts' SwiGLU at the benchmark's shapes, the stacked
    weights whole and the layer an index: three Mosaic calls named for the
    readers of the trace (a row tile of 128 against a WHOLE expert matrix,
    LongCat's two buffers of 25 MB inside the limit the call asks for), no
    ``ragged_dot``, and no buffer shaped like one layer's experts."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import moe_expert_ffn
    hidden, width, experts, layers, top = EXPERT_SHAPES[cell]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up, down = (layers, experts, hidden, width), (layers, experts, width,
                                                  hidden)
    text = _compile_text(
        moe_expert_ffn, sds((tokens * top, hidden)), sds(up), sds(up), sds(down),
        sds((experts,), jnp.int32), sds((), jnp.int32))
    assert len(re.findall(r"%grouped_mm_m128\S* = ", text)) == 3
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text
    one_layer = re.findall(
        rf"= bf16\[(?:1,)?{experts},(?:{hidden},{width}|{width},{hidden})\]"
        rf"\S* (\w[\w-]*)\(", text)
    assert not one_layer, one_layer


def test_grouped_product_gradient_slices_no_layer(one_chip, as_tpu):
    """``jax.grad`` through the routed experts with the stacked weights and a
    layer index, at OLMoE's shapes: the kernel forward, ``ragged_dot``'s own
    transposes backward over the stack's L * X groups, and, as in the
    forward, no buffer shaped like one layer's experts (the derivative of
    the stack is the stack's shape, and an argument's)."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import moe_expert_ffn
    hidden, width, experts, layers, top = EXPERT_SHAPES["olmoe"]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(tokens, gate, up, down, sizes, layer):
        out = moe_expert_ffn(tokens, gate, up, down, sizes, layer)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    up, down = (layers, experts, hidden, width), (layers, experts, width,
                                                  hidden)
    text = _compile_text(
        jax.grad(loss, argnums=(0, 1, 2, 3)), sds((144 * top, hidden)),
        sds(up), sds(up), sds(down), sds((experts,), jnp.int32),
        sds((), jnp.int32))
    assert len(re.findall(r"%(?:jvp_)?grouped_mm_m128\S* = ", text)) == 3
    assert len(re.findall(r"%ragged-dot-none\S* = ", text)) == 6
    one_layer = re.findall(
        rf"= (?:bf16|f32)\[(?:1,)?{experts},(?:{hidden},{width}|{width},{hidden})\]"
        rf"\S* (\w[\w-]*)\(", text)
    assert not one_layer, one_layer


@pytest.mark.parametrize("axes,kernels", [
    (dict(expert=2, data=2), 3), (dict(data=4), 0)],
    ids=["expert-parallel", "data-parallel"])
def test_routed_block_compiles_for_four_chips(four_chips, as_tpu, axes,
                                              kernels):
    """The dropless routed block and its gradient, compiled for the 2 x 2
    mesh. Under a sharded expert axis the local product inside
    ``apply_moe_grouped_ep``'s ``shard_map`` is the kernel (the region is
    manual over every mesh axis and checks how values vary, so the call
    states it: Mosaic lowers nothing less) with ``ragged_dot``'s transposes
    behind it; under data parallelism alone XLA's SPMD pass partitions the
    block, cannot partition a Mosaic call, and the product stays
    ``ragged_dot``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    mesh = groups.set_mesh(groups.build_mesh(devices=four_chips, **axes))
    try:
        cfg = TransformerConfig(
            vocab_size=256, hidden_size=256, num_layers=1, num_heads=4,
            intermediate_size=512, moe_intermediate_size=512, num_experts=8,
            num_experts_per_tok=2, moe_impl="grouped", max_seq_len=128,
            dtype="bfloat16")
        params, _ = L.init_moe_mlp(jax.random.PRNGKey(0), cfg)

        def loss(params, x):
            out, aux = L.apply_moe_mlp(params, x, cfg)
            return jnp.sum(out.astype(jnp.float32) ** 2) + aux

        def sds(shape, dtype, spec):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=NamedSharding(mesh, spec))

        held = P("expert") if "expert" in axes else P()
        shapes = jax.tree.map(
            lambda a: sds(a.shape, a.dtype, held if a.ndim == 3 else P()),
            params)
        x = sds((8, 128, 256), jnp.bfloat16, P(tuple(axes)))
        text = _compile_text(jax.value_and_grad(loss), shapes, x)
    finally:
        groups.reset_mesh()
    assert len(re.findall(r"%(?:jvp_)?grouped_mm_m128\S* = ", text)) == kernels
    assert len(re.findall(r"%ragged-dot\S* = ", text)) == (6 if kernels else 9)


def _assert_commits_in_place(compiled, text, pool, scatter_temp_gb):
    """A frame program writes the step's KV into the pools in place: the
    commit kernel once, no pool-shaped value that XLA made (a relaid or
    defensive copy, a scatter), and temporaries at least 3 GB under what
    the program held while the commit was a scatter (``scatter_temp_gb``,
    PERF.md section 4: the second copy of both pools)."""
    import re
    assert len(re.findall(r"%kv_commit_c\d+\S* = ", text)) == 1
    shape = ",".join(map(str, pool.shape))
    made = re.findall(
        rf"= bf16\[{shape}\]\S* (copy|copy-start|fusion|scatter|transpose|"
        rf"dynamic-update-slice)\(", text)
    assert not made, made
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (scatter_temp_gb - 3.0) * 1e9, temp


@pytest.mark.parametrize("chunk,scatter_temp_gb", [(1, 4.165), (128, 4.735)],
                         ids=["narrow", "wide"])
def test_mistral_frame_programs_fit_the_chip(one_chip, as_tpu, chunk,
                                             scatter_temp_gb):
    """The benchmark's frame programs (mistral-7b widths, 16 layers, bf16;
    16 slots x 1 or 128 positions, 8 steps, 416 pages of 128, sequences to
    8,192) compile with the chip's compiler from shapes alone, keep their
    paged kernel, commit in place, hold (the wide one) one conditional per
    packed stage (embedding, q/k/v, output projection + MLP) and no copy of
    a whole weight stack inside the layer loop, and their arguments and
    temporaries stay under the chip's 15.75 GB (PERF.md section 4 records
    the sizes)."""
    import re
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import N_STATS
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 416, 8192
    cfg = get_config("mistral-7b", num_layers=16, dtype="bfloat16")
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pool = sds((cfg.num_layers, cfg.kv_heads, pages, PAGE, D), jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    frame = PagedModelRunner(model, PAGE, seq // PAGE)._build_frame_loop()
    compiled = frame.lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((N_STATS,), i32), sds(key.shape, key.dtype), pool, pool,
        width=chunk, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%paged_attn_c\d+\S* = ", text)) == 1
    _assert_commits_in_place(compiled, text, pool, scatter_temp_gb)
    assert len(re.findall(r" conditional\(", text)) == (3 if chunk > 1 else 0)
    # a layout copy of a stacked weight belongs to the entry computation
    # (once a frame, as before), never to the body of the layer loop
    stacked = re.findall(
        r"= bf16\[16,(?:4096,32,128|4096,8,128|32,128,4096|4096,14336|"
        r"14336,4096)\]\S* copy\((\S+?)[,)]", text)
    assert all(src.startswith("%params") for src in stacked), stacked
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"mistral frame program, width {chunk}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert total < 15.75e9, total


@pytest.mark.parametrize("chunk", [1, 128], ids=["narrow", "wide"])
def test_a_frames_step_operand_costs_no_memory_over_the_scan(
        one_chip, as_tpu, monkeypatch, chunk):
    """The benchmark's mistral frame programs with their step count an
    operand (``n_steps``: a ``while`` whose trips the host plans) against
    the same body under ``lax.scan`` over a static length, what the frame
    programs ran before PR 41: compiled for the chip from shapes alone, the
    operand's program takes the arguments of the scan's and the one scalar,
    aliases as many of them to its results (the donated carry, both pools),
    and its temporaries are the scan's to within a megabyte (no second copy
    of a pool: PR 27 took 3.49 GB of them out)."""
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.inference.v2.telemetry import N_STATS
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 416, 8192
    cfg = get_config("mistral-7b", num_layers=16, dtype="bfloat16")
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pool = sds((cfg.num_layers, cfg.kv_heads, pages, PAGE, D), jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    args = (params, sds((slots, seq), i32), row, row, row,
            sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row,
            row, row, sds((slots,), flag), sds((slots,), flag),
            sds((slots,), flag), sds((N_STATS,), i32),
            sds(key.shape, key.dtype), pool, pool)

    def memory(**operand):
        frame = model_runner.PagedModelRunner(
            model, PAGE, seq // PAGE)._build_frame_loop()
        compiled = frame.lower(*args, width=chunk, steps=steps, greedy=True,
                               **operand).compile()
        return compiled.memory_analysis(), compiled.as_text()

    loop, text = memory(n_steps=sds((), i32))
    assert len(re.findall(r" while\(", text)) >= 1
    assert "n_steps" in text or "s32[]" in text     # the scalar is taken

    def as_a_scan(body, carry, steps, n_steps, shape):
        carry, (toks, emit) = jax.lax.scan(body, carry, None, length=steps)
        return (toks, emit) + carry

    monkeypatch.setattr(model_runner, "_run_steps", as_a_scan)
    scan, _ = memory()
    print(f"mistral frame program, width {chunk}: temp "
          f"{loop.temp_size_in_bytes / 1e9:.4f} GB with the operand, "
          f"{scan.temp_size_in_bytes / 1e9:.4f} GB as a scan")
    assert loop.argument_size_in_bytes - scan.argument_size_in_bytes <= 512
    assert loop.alias_size_in_bytes >= scan.alias_size_in_bytes
    # (32 and 97 KB over the scan's 0.74 and 1.08 GB: the emission buffers
    # are made up front; a pool is 0.87 GB)
    assert loop.temp_size_in_bytes <= scan.temp_size_in_bytes + (1 << 20), (
        loop.temp_size_in_bytes, scan.temp_size_in_bytes)


@pytest.mark.parametrize("width,scatter_temp_gb", [(1, 3.633), (128, 4.209)],
                         ids=["narrow", "wide"])
def test_olmoe_frame_programs_fit_the_chip(one_chip, as_tpu, width,
                                           scatter_temp_gb):
    """The benchmark's OLMoE-1B-7B configuration (published widths, 8 of 16
    layers, bf16; 16 slots, 8 steps, 416 pages of 128, sequences to 4,096):
    both frame programs compile with the chip's compiler from shapes alone,
    keep the paged kernel, commit in place (MHA: 16 KV heads a block), hold
    the grouped-product kernel (``grouped_mm_m128``: three products a routed
    layer, at every rung, and ``ragged_dot`` nowhere), hold NO buffer shaped like one layer's
    stack of experts (the products read the stacked weights whole: a
    layer's slice handed to a kernel is 805 MB copied a layer a step), and
    their arguments and temporaries stay under the chip's 15.75 GB."""
    import re
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 416, 4096
    cfg = get_config("olmoe-1b-7b", num_layers=8)
    assert cfg.dtype == "bfloat16"
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
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pool, pool,
        width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    rungs = len(pack_ladder(slots, width))
    assert len(re.findall(r" conditional\(", text)) == (3 if rungs > 1 else 0)
    assert len(re.findall(r"%paged_attn_c\d+\S* = ", text)) == 1
    _assert_commits_in_place(compiled, text, pool, scatter_temp_gb)
    assert len(re.findall(r"%grouped_mm_m128\S* = ", text)) == 3 * rungs
    assert "ragged-dot" not in text
    # one layer's experts: defined nowhere, in the loop, a conditional or
    # the entry (the stack itself is a parameter, [8,64,...])
    one_layer = re.findall(
        r"= bf16\[(?:1,)?64,(?:2048,1024|1024,2048)\]\S* (\w[\w-]*)\(", text)
    assert not one_layer, one_layer
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"olmoe frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert total < 15.75e9, total


@pytest.mark.parametrize("width", [1, 128], ids=["narrow", "wide"])
def test_mellum2_frame_programs_fit_the_chip(one_chip, as_tpu, width):
    """The benchmark's Mellum2-12B-A2.5B configuration (published widths, 8
    of 28 layers ``S, S, S, F`` twice, bf16; 16 slots, 8 steps, sequences to
    32,768: tables of 256 pages over a pool of 4,097 for the two global
    layers, rings of 10 pages over a pool of 161 for the six windowed ones):
    both frame programs compile with the chip's compiler from shapes alone.
    The walk is a scan over the two periods with a period's four layers
    unrolled: three ring kernels and one over whole tables, one commit a
    kind, in place (no value shaped like either kind's pool that XLA made),
    the grouped-product kernel three times a layer and rung, NO buffer
    shaped like one layer's stack of experts, and arguments and temporaries
    under the chip's 15.75 GB."""
    import re
    from deepspeed_tpu.inference.v2.kv_cache import cache_kinds
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq, chunk = 16, 8, 4097, 32768, 128
    cfg = get_config("mellum2-12b-a2.5b", num_layers=8)
    assert cfg.dtype == "bfloat16"
    model = build_model(cfg.replace(param_dtype=cfg.dtype))
    kinds = cache_kinds(cfg.layer_windows(), PAGE, seq // PAGE, chunk)
    assert [(k.layers, k.ring) for k in kinds] == [
        ((3, 7), None), ((0, 1, 2, 4, 5, 6), 10)]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pools = tuple(
        sds((len(k.layers), cfg.kv_heads,
             pages if k.ring is None else slots * k.ring + 1, PAGE,
             cfg.dims_per_head), jnp.bfloat16) for k in kinds)
    tables = tuple(sds((slots, k.ring or seq // PAGE), i32) for k in kinds)
    key = jax.random.PRNGKey(0)
    runner = PagedModelRunner(model, PAGE, seq // PAGE, kinds=kinds)
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), tables, row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pools, pools,
        width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    rungs = len(pack_ladder(slots, width))
    assert len(re.findall(r"%paged_attn_c\d+\S* = ", text)) == 1
    assert len(re.findall(r"%paged_attn_ring_c\d+\S* = ", text)) == 3
    assert len(re.findall(r"%kv_commit_c\d+\S* = ", text)) == 1
    assert len(re.findall(r"%kv_commit_ring_c\d+\S* = ", text)) == 1
    # one conditional for the embedding, two a layer (q/k/v; output
    # projection + experts), a period's four layers unrolled
    assert len(re.findall(r" conditional\(", text)) == \
        (1 + 2 * 4 if rungs > 1 else 0)
    assert len(re.findall(r"%grouped_mm_m128\S* = ", text)) == 3 * rungs * 4
    assert "ragged-dot" not in text
    for pool in pools:
        shape = ",".join(map(str, pool.shape))
        made = re.findall(
            rf"= bf16\[{shape}\]\S* (copy|copy-start|fusion|scatter|"
            rf"transpose|dynamic-update-slice)\(", text)
        assert not made, made
    one_layer = re.findall(
        r"= bf16\[(?:1,)?64,(?:2304,896|896,2304)\]\S* (\w[\w-]*)\(", text)
    assert not one_layer, one_layer
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"mellum2 frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert 9.9e9 < m.argument_size_in_bytes < 10.1e9
    assert total < 15.75e9, total


LONGCAT_CUT = dict(num_layers=4, num_experts=16, moe_router_experts=512,
                   vocab_size=16384)


@pytest.mark.parametrize("width", [1, 128], ids=["narrow", "wide"])
def test_longcat_frame_programs_fit_the_chip(one_chip, as_tpu, width):
    """The benchmark's LongCat-Flash-Omni configuration (published widths; 4
    of 28 double layers, experts 0..15 of 512 beside the whole router, an
    eighth of the vocabulary, bf16; 16 slots, 8 steps, sequences to 16,384:
    tables of 128 pages over ONE pool of 2,049 pages of 640-lane rows for
    the 8 attention layers): both frame programs compile with the chip's
    compiler from shapes alone. A scan step is one double layer: the latent
    kernel twice, one commit of the one pool in place (no value shaped like
    it that XLA made), the grouped-product kernel three times a layer and
    rung, NO buffer shaped like one layer's held experts or a layer's pair
    of dense matrices, and arguments and temporaries under 15.75 GB."""
    import re
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 2049, 16384
    cfg = get_config("longcat-flash-omni", **LONGCAT_CUT)
    assert cfg.dtype == "bfloat16" and cfg.latent_lanes == 640
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pool = sds((cfg.attn_layers, 1, pages, PAGE, cfg.latent_lanes),
               jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    runner = PagedModelRunner(model, PAGE, seq // PAGE)
    assert runner.n_stats == 18 + 4 + 3 + 2
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pool, None,
        width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    rungs = len(pack_ladder(slots, width))
    assert len(re.findall(rf"%paged_attn_mla_c{width}\S* = ", text)) == 2
    assert len(re.findall(r"%paged_attn_\S* = ", text)) == 2
    assert len(re.findall(rf"%kv_commit_mla_c{width}\S* = ", text)) == 1
    assert len(re.findall(r"%kv_commit_\S* = ", text)) == 1
    assert len(re.findall(r"%grouped_mm_m128\S* = ", text)) == 3 * rungs
    assert "ragged-dot" not in text
    shape = ",".join(map(str, pool.shape))
    made = re.findall(
        rf"= bf16\[{shape}\]\S* (copy|copy-start|fusion|scatter|transpose|"
        rf"dynamic-update-slice)\(", text)
    assert not made, made
    stacks = re.findall(
        r"= bf16\[(?:1,)?(?:16,(?:6144,2048|2048,6144)|2,(?:6144,12288|"
        r"12288,6144))\]\S* (?!parameter|bitcast)(\w[\w-]*)\(", text)
    assert not stacks, stacks
    # the chip holds a share: a wide rung's dispatch and combine move blocks
    # of rows in two loops over a buffer nothing zeroes, and XLA makes no
    # value of a rung's (tokens x 12, 6144) selection rows (it selected,
    # weighted and scattered every one of them before; the scheduler may
    # still move the smallest rung's buffer into fast memory for the product)
    ladder = [rung for rung in pack_ladder(slots, width) if 12 * rung > 256]
    assert len(ladder) == (5 if width > 1 else 0)
    assert len(re.findall(r"%unwritten_rows\S* = ", text)) == len(ladder)
    sorted_rows = "|".join(str(12 * rung) for rung in ladder) or "none"
    made = re.findall(
        rf"= bf16\[(?:{sorted_rows}),6144\]\S* (?!parameter|bitcast|"
        rf"get-tuple-element|custom-call|dynamic-update-slice|copy-start|"
        rf"copy-done)(\w[\w-]*)\(",
        text)
    assert not made, made
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"longcat frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert 12.9e9 < m.argument_size_in_bytes < 13.1e9
    assert total < 15.75e9, total


def test_latent_tilings_are_pinned():
    """(tiles of chunk positions, pages a group) of the latent kernel at the
    cells' shapes: LongCat's 64 heads come out as PR 34 left them (a decode
    step whole, a chunk of 128 in 16 tiles of 8 positions = 512 rows against
    groups of 8 pages); GLM-4.7-Flash's 20 heads give a decode step of 20
    query rows, a verify of 40, and a chunk in 4 tiles of 32 positions = 640
    rows against groups of 4 pages."""
    from deepspeed_tpu.ops.pallas.paged_attention import _latent_tiling
    got = {(c, h): _latent_tiling(c, h, mb, PAGE, 640, 512, 2)
           for c, h, mb in ((1, 64, 128), (128, 64, 128), (1, 20, 64),
                            (2, 20, 64), (128, 20, 64))}
    assert got == {(1, 64): (1, 4), (128, 64): (16, 8), (1, 20): (1, 4),
                   (2, 20): (1, 4), (128, 20): (4, 4)}


def test_glm_reference_walks_candidates_in_programs_that_fit(one_chip):
    """The plain reference of GLM-4.7-Flash runs BESIDE the server, which
    fills the chip, and holds a row at a near-tie of the router to about ten
    candidate routings: the two programs that walk and score them take a
    block of 1,024 candidates whatever their number, lower with the chip's
    compiler at the cell's sizes (4,096 of context, 20 heads, the whole
    vocabulary a block of 16,384 at a time) and hold a few hundred MB."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "glm_reference", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "perfbench", "configs", "glm4_moe_lite_reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf, i32 = jnp.bfloat16, jnp.int32
    e, h, t, v, n = 2048, 20, 4096, 154880, ref.TOKEN_BLOCK
    lp = {"norm1": {"scale": sds((e,), bf)}, "norm2": {"scale": sds((e,), bf)},
          "attn": {"wq_a": sds((e, 768), bf), "wq_b": sds((768, h, 256), bf),
                   "q_norm": {"scale": sds((768,), bf)},
                   "wkv_a": sds((e, 576), bf), "wkv_b": sds((512, h, 448), bf),
                   "kv_norm": {"scale": sds((512,), bf)},
                   "wo": sds((h, 256, e), bf)}}
    sizes = dict(eps=1e-5, theta=1e6, d_n=192, d_r=64)
    walk = ref.attend.lower(lp, sds((n, e)), sds((n,), i32),
                            sds((t, h, 256)), sds((t, h, 256)),
                            **sizes).compile().memory_analysis()
    score = ref._envelope_at.lower(
        sds((n, e)), sds((n,)), sds((n,), i32), sds((e,), bf),
        sds((e, v), bf), sds((), i32), eps=1e-5,
        size=ref.VOCAB_BLOCK).compile().memory_analysis()
    for held in (walk, score):
        assert held.temp_size_in_bytes + held.output_size_in_bytes < 400e6


@pytest.mark.parametrize("width", [1, 128], ids=["narrow", "wide"])
def test_glm_frame_programs_fit_the_chip(one_chip, as_tpu, width):
    """The benchmark's GLM-4.7-Flash configuration (published widths; the
    dense layer and 6 of 46 routed layers, all 64 experts, the whole
    vocabulary, the prediction module, bf16; 16 slots, 8 steps, sequences to
    8,192: tables of 64 pages over ONE pool of 577 pages of 640-lane rows
    for the 7 + 1 cache layers): both SELF-SPECULATIVE frame programs
    compile with the chip's compiler from shapes alone. The narrow one
    drafts with the module (``paged_attn_mla_c1``, its grouped product at 64
    rows) and verifies two wide (``paged_attn_mla_c2`` in the dense and in
    the routed segment); the wide one computes the module's rows alone (no
    attention, no experts of the module's). Each commits twice, the stack's
    layers and the module's, in place: no value shaped like the pool that
    XLA made, no buffer shaped like a layer's experts, neither an operand
    of a conditional, and arguments and temporaries under 15.75 GB."""
    import re
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 577, 8192
    cfg = get_config("glm-4.7-flash", num_layers=7)
    assert cfg.dtype == "bfloat16" and cfg.latent_lanes == 640
    assert cfg.layer_tags == ("dense",) + ("moe",) * 6
    assert (cfg.attn_layers, cfg.cache_layers) == (7, 8)
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pool = sds((cfg.cache_layers, 1, pages, PAGE, cfg.latent_lanes),
               jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    runner = PagedModelRunner(model, PAGE, seq // PAGE)
    assert runner.has_mtp and runner.n_stats == 18 + 4 + 2 + 3
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, 2048), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pool, None,
        sds((slots, cfg.hidden_size), jnp.bfloat16),
        width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()

    def count(pattern):
        return len(re.findall(rf"%{pattern}\S* = ", text))

    if width == 1:
        assert count("paged_attn_mla_c1") == 1      # the module's draft
        assert count("paged_attn_mla_c2") == 2      # dense and routed scans
        assert count("paged_attn_") == 3
        assert count("kv_commit_mla_c2") == count("kv_commit_") == 2
        assert count("grouped_mm_m128") == 3        # the verify's 128 rows
        assert count("grouped_mm_m64") == 3         # the draft's 64
    else:
        rungs = len(pack_ladder(slots, width))
        assert count("paged_attn_mla_c128") == count("paged_attn_") == 2
        assert count("kv_commit_mla_c128") == count("kv_commit_") == 2
        assert count("grouped_mm_") == 3 * rungs    # the stack's alone
    assert "ragged-dot" not in text
    pool_shape = ",".join(map(str, pool.shape))
    experts = r"(?:1,|6,)?64,(?:2048,1536|1536,2048)"
    made = re.findall(
        rf"= bf16\[(?:{pool_shape}|{experts})\]\S* (copy|copy-start|fusion|"
        rf"scatter|transpose|dynamic-update-slice|dynamic-slice)\(", text)
    assert not made, made
    for line in re.findall(r"^.* conditional\(.*$", text, re.M):
        assert not re.search(rf"bf16\[(?:{pool_shape}|{experts})\]", line), \
            line[:300]
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"glm self-speculative frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    # the wide program never reads the module's experts and attention
    # (1.27 GB) and takes them all the same: its steps are a ``while``
    # (the count is an operand), through which JAX prunes no unused
    # argument. They are resident for the narrow program either way
    assert abs(m.argument_size_in_bytes - 11.106e9) < 0.02e9
    assert m.temp_size_in_bytes < 0.6e9
    assert total < 15.75e9, total


def test_gated_delta_rule_compiles_at_the_cells_shapes(one_chip, as_tpu):
    """The delta rule's kernel of a wide step (``gated_delta_rule.py``) at
    Qwen3-Next's shape in the benchmark's cell: 16 rows x 128 positions, 16
    key / 32 value heads of 128 x 128 float32 states, the 6 linear layers'
    states in one stack, bf16 activations. One Mosaic call named for the
    chunk (its products' precision: ``test_gated_delta_rule_kernel.py``) and
    NOTHING beside its operands: the states are moved in place (aliased, no
    temporary), the row list and the gates' relayout are kilobytes."""
    import re
    from deepspeed_tpu.ops.pallas import gated_delta_rule as gdr
    b, c, hk, hv, d, layers = 16, 128, 16, 32, 128, 6
    assert gdr.supported(c, hk, hv, d, d)
    assert gdr.pairs_a_step(hk, hv, d, d) == 4      # 64 grid steps a layer

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(u, beta, g, state, layer, n_live):
        return gdr.gdn_rule_rows(u, beta, g, state, layer,
                                 gdr.row_list(n_live))

    state = sds((layers, b, hv, d, d), jnp.float32)
    gate = sds((b, c, hv), jnp.float32)
    lowered = jax.jit(step, donate_argnums=(3,)).lower(
        sds((b, c, 2 * hk * d + hv * d), jnp.bfloat16), gate, gate, state,
        sds((), jnp.int32), sds((b,), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gdn_rule_c128\S* = ", text)) == 1
    assert "tpu_custom_call" in text
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == layers * b * hv * d * d * 4
    assert m.temp_size_in_bytes < 1 << 20, m.temp_size_in_bytes


# sha256 of the traced frame programs (``.trace(...).jaxpr``: the chip's
# kernels are in, no source locations) of two configurations WITHOUT linear
# layers at small presets, 4 slots x 2 steps, pages of 8, as the parent of
# PR 49 traced them (`git archive 897d6f5`): the delta rule's kernel and
# its row list are traced for a model with linear layers alone. The two
# ``olmoe`` entries are PR 52's own tree (the routed block's combine became a
# gather by token there, so they moved with it; the parent, 14b980e, traced
# 7be83967... and 6856f45e...); the two ``mistral`` ones held through it
NO_LINEAR_LAYERS_JAXPRS = {
    ("mistral", 1):
        "43857c5ebb989a535af62e12c7e65cbcf339846e75a65125498dc496b52cd23f",
    ("mistral", 16):
        "6b658872dfc0a68520d289bce6e9f471790565eb7974162be5567b7265ec9d68",
    ("olmoe", 1):
        "638a66309912d43661eb2c340198d04b8eea1cc42dc7e76a954102cca7aadd8f",
    ("olmoe", 16):
        "3004f47a62c75e0313fc330f6a86fdc5b2bf28752d015524054ff819215f5ad2",
}
NO_LINEAR_LAYERS = {
    "mistral": ("mistral-7b", dict(num_kv_heads=2, intermediate_size=128,
                                   sliding_window=64)),
    "olmoe": ("olmoe-1b-7b", dict(num_kv_heads=4, intermediate_size=32,
                                  num_experts=8, num_experts_per_tok=2)),
}


@pytest.mark.parametrize("family,width", list(NO_LINEAR_LAYERS_JAXPRS),
                         ids=[f"{f}-w{w}" for f, w in NO_LINEAR_LAYERS_JAXPRS])
def test_frame_programs_without_linear_layers_are_the_parents(as_tpu, family,
                                                              width):
    import hashlib
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.models import build_model, get_config
    preset, kw = NO_LINEAR_LAYERS[family]
    cfg = get_config(preset, vocab_size=256, hidden_size=64, max_seq_len=256,
                     dtype="float32", num_layers=2, num_heads=4, **kw)
    model = build_model(cfg)
    runner = PagedModelRunner(model, 8, 32)
    slots, steps, i32, sds = 4, 2, jnp.int32, jax.ShapeDtypeStruct
    row, flag = sds((slots,), i32), sds((slots,), jnp.bool_)
    key = jax.random.PRNGKey(0)
    pool = sds((cfg.num_layers, cfg.kv_heads, 33, 8, cfg.dims_per_head),
               jnp.float32)
    jaxpr = runner._build_frame_loop().trace(
        model.abstract_params(), sds((slots, 256), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, 32), i32), row, row, row,
        flag, flag, flag, sds((runner.n_stats,), i32),
        sds(key.shape, key.dtype), pool, pool, width=width, steps=steps,
        greedy=True, n_steps=sds((), i32)).jaxpr
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == \
        NO_LINEAR_LAYERS_JAXPRS[family, width]


QWEN3_NEXT_CUT = dict(num_layers=8, num_experts=128, moe_router_experts=512)


@pytest.mark.parametrize("width", [1, 128], ids=["narrow", "wide"])
def test_qwen3_next_frame_programs_fit_the_chip(one_chip, as_tpu, width):
    """The benchmark's Qwen3-Next-80B-A3B configuration (published widths; 8
    of 48 layers = two periods of three Gated DeltaNet layers and one of
    gated attention, experts 0..127 of 512 beside the whole router, the
    whole vocabulary, bf16; 16 slots, 8 steps, sequences to 16,384: tables
    of 128 pages over pools of 2,049 pages for the TWO full layers, 2 KV
    heads of 256; beside them every slot's recurrent state (16, 6, 32, 128,
    128) float32 and convolution tail (16, 6, 3, 8192)): both frame programs
    compile with the chip's compiler from shapes alone. A scan step is one
    period: the paged kernel once (head_dim 256, 8 query rows a KV head: a
    shape no other cell has), one commit in place after the walk, the
    grouped-product kernel three times a layer and rung, NO value shaped
    like a pool or like the states that XLA copied (the state rides the
    scan's carry and is updated in place, a layer's part at a time: 201 MB
    that a copy a layer would move 48 times a frame; in the wide program by
    the delta rule's kernel, once a linear layer, which names the layer and
    the rows it moves and slices nothing out), no buffer shaped like a layer's held experts, and arguments and
    temporaries under 15.75 GB."""
    import re
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    from deepspeed_tpu.models import build_model, get_config
    slots, steps, pages, seq = 16, 8, 2049, 16384
    cfg = get_config("qwen3-next-80b-a3b", **QWEN3_NEXT_CUT)
    assert cfg.dtype == "bfloat16" and cfg.linear_layers == 6
    assert cfg.cache_layers == 2 and cfg.moe_is_share
    model = build_model(cfg.replace(param_dtype=cfg.dtype))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    i32, flag = jnp.int32, jnp.bool_
    row = sds((slots,), i32)
    pool = sds((2, 2, pages, PAGE, 256), jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    runner = PagedModelRunner(model, PAGE, seq // PAGE)
    assert runner.n_stats == 18 + 4 + 3 + 3 + 3
    state, tail = (sds(*shape) for shape in runner.recurrent_shapes(slots))
    assert state.shape == (6, 16, 32, 128, 128) and state.dtype == jnp.float32
    assert tail.shape == (6, 3, 16, 8192) and tail.dtype == jnp.bfloat16
    compiled = runner._build_frame_loop().lower(
        params, sds((slots, seq), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, seq // PAGE), i32), row, row,
        row, sds((slots,), flag), sds((slots,), flag), sds((slots,), flag),
        sds((runner.n_stats,), i32), sds(key.shape, key.dtype), pool, pool,
        recurrent=(state, tail), width=width, steps=steps, greedy=True,
        n_steps=sds((), i32)).compile()
    text = compiled.as_text()
    rungs = len(pack_ladder(slots, width))
    assert len(re.findall(rf"%paged_attn_\S*c{width}\S* = ", text)) == 1
    assert len(re.findall(r"%paged_attn_\S* = ", text)) == 1
    assert len(re.findall(r"%kv_commit_\S* = ", text)) == 1
    assert len(re.findall(r"%grouped_mm_m128\S* = ", text)) == 4 * 3 * rungs
    # a wide step's delta rule: the kernel, once a linear layer of a period
    assert len(re.findall(r"%gdn_rule_c128\S* = ", text)) == \
        (3 if width > 1 else 0)
    assert "ragged-dot" not in text
    for kind, value in (("bf16", pool), ("f32", state)):
        # the state's update in place is a fusion XLA names for it
        shape = ",".join(map(str, value.shape))
        made = [(name, op) for name, op in re.findall(
            rf"%(\S+) = {kind}\[{shape}\]\S* (copy|copy-start|fusion|"
            rf"scatter|transpose)\(", text)
            if "dynamic-update-slice" not in name]
        assert not made, (shape, made)
    stacks = re.findall(
        r"= bf16\[(?:1,)?128,(?:2048,512|512,2048)\]\S* "
        r"(?!parameter|bitcast)(\w[\w-]*)\(", text)
    assert not stacks, stacks
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"qwen3-next frame program, width {width}: args "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert 9.4e9 < m.argument_size_in_bytes < 9.8e9
    assert total < 15.75e9, total
    # the narrow program holds what it held before a wide step's rows were
    # told apart (0.082 GB) and ~4 MB for the float32 sum of a token's 10
    # gathered rows (PR 52: 0.086); the wide one, whose delta rule is the kernel
    # (the states in place, its output and the gates by block its only
    # temporaries), 0.366 GB where two gathered rows a trip of XLA's
    # chunked form held 0.389 and every row through it at once 0.536
    assert m.temp_size_in_bytes < (0.087e9 if width == 1 else 0.40e9)


def test_chip_smoke_fails_without_a_chip():
    """The suite runs on the CPU: ``chip_smoke.py`` must exit nonzero there
    and never print its success line (the children stop before any phase)."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stderr
