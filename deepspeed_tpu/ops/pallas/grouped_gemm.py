"""Grouped (expert) matmul for MoE.

Analog of ``inference/v2/kernels/cutlass_ops/moe_gemm`` (grouped GEMM over
per-expert token groups): rows sorted by expert, group sizes ragged. On the
chip, where the operands are local (``_kernel_runs``), the product is a
Pallas kernel of this file (the Megablox pattern, ``grouped_mm_m<tm>``),
tiled by the static shapes it is called with; elsewhere it is
``jax.lax.ragged_dot``, which is also the kernel's oracle and its
derivative.

The kernel walks (row tile, group) VISITS in row order: a tile of ``tm``
rows that spans several groups is visited once for each, every visit a whole
tile's product stored under the group's row mask. Consecutive visits of one
group name the same weight block, so the pipeline fetches it once: with a
block that is a whole expert matrix an expert is read ONCE a product, however
many rows it has. A tile none of whose rows is in a group is never visited
and stays unwritten.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...parallel.sharding import current_manual_axes
from ...utils import groups

# what the blocks of one call may take of a v5e core's 128 MiB of VMEM, and
# what Mosaic grants a kernel that asks for nothing
_VMEM_BUDGET = 64 << 20
_VMEM_DEFAULT = 16 << 20
_ROW_TILE = 128             # the MXU's height: fewer rows a visit fill it no faster


def _on_chip():
    return jax.default_backend() == "tpu"


def _kernel_runs():
    """Whether the product is this file's kernel: on the chip, where the
    operands are local. Mosaic lowers a call on one device, or inside a
    ``shard_map`` that is manual over EVERY mesh axis
    (``apply_moe_grouped_ep`` names them all where it can); XLA's SPMD pass
    cannot partition the call and has rules for ``ragged_dot``, so on a mesh
    that it partitions the product stays ``ragged_dot``."""
    if not _on_chip():
        return False
    if not groups.mesh_is_initialized():
        return True
    mesh, manual = groups.get_mesh(), current_manual_axes()
    return manual == set(mesh.axis_names) if manual else mesh.devices.size == 1


def unwritten(rows, like):
    """A (rows, E) buffer that nothing has written, of the dtype of ``like``
    (T, E) and varying as it does, for rows that are filled as far as their
    reader goes and no further: where the kernel runs, the result of a call
    no step of which touches it (whatever the memory held, as the product
    leaves its rows past the groups); zeros elsewhere."""
    shape = (rows, like.shape[1])
    if not _kernel_runs():
        return jnp.zeros(shape, like.dtype)
    return pl.pallas_call(
        lambda out: None, name="unwritten_rows",
        out_shape=jax.ShapeDtypeStruct(shape, like.dtype,
                                       vma=jax.typeof(like).vma),
        out_specs=pl.BlockSpec(memory_space=pl.ANY))()


def _cuts(x):
    """``x`` and the multiples of 128 that divide it, largest first: the
    sizes a block may have along a lane or contraction axis."""
    return [x] + [c for c in range(x - x % 128, 0, -128)
                  if c != x and x % c == 0]


def _vmem_bytes(tm, tk, tn, k, itemsize):
    """Two buffers each of the row tile, the weight block and the result
    tile, the float32 product and, where K is tiled, its accumulator."""
    return (2 * itemsize * (tm * tk + tk * tn + tm * tn)
            + 4 * tm * tn * (2 if tk < k else 1))


def tiles(rows, k, n, itemsize=2):
    """(tm, tk, tn) for a ``(rows, k) x (groups, k, n)`` product, from its
    static shapes alone. ``tm``: a tile of the MXU's 128 rows whatever the
    rows an expert, so the number of groups does not enter: every visit is
    a whole tile's product, and a tile spans ``1 + tm / (rows an expert)``
    groups on average, so a taller tile multiplies more masked rows than it
    saves steps (the sweep of ``benchmarks/moe_bench.py --grouped-sweep``),
    while at 2 rows an expert one tile holds every row and a visit is one
    expert's matrix read against 128 rows. ``tk, tn``: the whole expert
    matrix where its two buffers fit the budget (the weights are then read
    once a product); else N is cut first (a pass over the rows for each
    cut, the group's block still fetched once a pass), K last (its blocks
    change every step, so every visit reads them again)."""
    tm = min(_ROW_TILE, rows)
    fits = lambda tk, tn: _vmem_bytes(tm, tk, tn, k, itemsize) <= _VMEM_BUDGET
    for tn in _cuts(n):
        if fits(k, tn):
            return tm, k, tn
    for tk in _cuts(k):
        if fits(tk, tn):
            return tm, tk, tn
    raise ValueError(f"no block of a ({k}, {n}) matrix fits {_VMEM_BUDGET} B "
                     f"beside {tm} rows")


def _visits(group_sizes, rows, tm):
    """The walk, for scalar prefetch: ``offsets`` (X + 1,) the groups' first
    rows; ``gids`` / ``tids`` (V,) the group and the row tile of each visit,
    V = row tiles + X - 1 the most there can be; and how many there are,
    which is the grid's extent (entries past it repeat the last visit)."""
    n_groups = group_sizes.shape[0]
    n_tiles = pl.cdiv(rows, tm)
    ends = jnp.cumsum(group_sizes)
    first = (ends - group_sizes) // tm
    n_vis = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    vis_end = jnp.cumsum(n_vis)
    count = vis_end[-1]
    # -1 throughout where no group has rows: group 0, tile 0, never visited
    v = jnp.minimum(jnp.arange(n_tiles + n_groups - 1), count - 1)
    gids = jnp.searchsorted(vis_end, v, side="right")
    tids = jnp.maximum(first[gids] + v - (vis_end - n_vis)[gids], 0)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    return tuple(a.astype(jnp.int32) for a in (offsets, gids, tids, count))


def _kernel(offsets, gids, tids, layer, lhs, rhs, out, *acc, tm, tiles_k):
    del layer               # the weight's index map reads it
    v, kk = pl.program_id(1), pl.program_id(2)

    def store(val):
        g = gids[v]
        row = tids[v] * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out[...] = jnp.where(mine, val.astype(out.dtype), out[...])

    part = jnp.dot(lhs[...], rhs[...], preferred_element_type=jnp.float32)
    if tiles_k == 1:
        return store(part)
    acc_ref, = acc

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = part

    @pl.when(kk > 0)
    def _():
        acc_ref[...] += part

    @pl.when(kk == tiles_k - 1)
    def _():
        store(acc_ref[...])


def _vary_alike(*operands):
    """Inside a ``shard_map`` that checks it, a ``pallas_call`` wants its
    operands and its result to vary over the same mesh axes: their union
    (the rows vary over the token axes too, an expert's weights over the
    expert axis alone). Outside one the union is empty and nothing moves."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return vma, [jax.lax.pcast(a, tuple(vma - jax.typeof(a).vma), to="varying")
                 if vma - jax.typeof(a).vma else a for a in operands]


def grouped_mm(tokens, weights, group_sizes, layer=None, tiling=None,
               interpret=False):
    """The kernel: ``tokens`` (T, K) rows sorted by group, ``weights``
    (X, K, N), or (L, X, K, N) with ``layer`` the one to multiply by (a
    prefetched scalar in the weight's index map: no layer is sliced out),
    ``group_sizes`` (X,). Returns (T, N) in the tokens' dtype, accumulated
    in float32; rows in no group are not written. ``tiling`` overrides
    ``tiles`` (the sweep and the tests)."""
    rows, k = tokens.shape
    n = weights.shape[-1]
    tm, tk, tn = tiling or tiles(rows, k, n, tokens.dtype.itemsize)
    tiles_k, tiles_n = k // tk, n // tn
    assert tk * tiles_k == k and tn * tiles_n == n, (k, tk, n, tn)
    walk = _visits(group_sizes, rows, tm)
    stacked = weights.ndim == 4
    layer = jnp.asarray(layer if stacked else 0, jnp.int32).reshape(1)
    vma, operands = _vary_alike(*walk, layer, tokens, weights)
    count = operands.pop(3)

    def w_index(ni, v, ki, offsets, gids, tids, layer):
        at = (gids[v], ki, ni)
        return (layer[0],) + at if stacked else at

    # the blocks and room for Mosaic's own temporaries, never under its default
    need = _vmem_bytes(tm, tk, tn, k, tokens.dtype.itemsize)
    call = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((rows, n), tokens.dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_n, count, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, o, g, t, l:
                             (t[v], ki)),
                pl.BlockSpec((None,) * (weights.ndim - 2) + (tk, tn), w_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, v, ki, o, g, t, l:
                                   (t[v], ni)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else []),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(_VMEM_DEFAULT, need + (8 << 20))),
        name=f"grouped_mm_m{tm}",
        interpret=interpret,
    )
    return call(*operands)


def _ragged(tokens, weights, group_sizes, layer):
    """``jax.lax.ragged_dot``. A stack of layers goes in whole, as L * X
    experts of which only this layer's have rows: an expert without rows
    costs ``ragged_dot`` nothing, and a layer's slice would be a copy of
    that layer's experts wherever the product is a kernel."""
    if layer is not None:
        n_layers, n_exp = weights.shape[:2]
        weights = weights.reshape((n_layers * n_exp,) + weights.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * n_exp,), group_sizes.dtype), group_sizes,
            (layer * n_exp,))
    return jax.lax.ragged_dot(tokens, weights, group_sizes)


@jax.custom_vjp
def _product(tokens, weights, group_sizes, layer):
    return grouped_mm(tokens, weights, group_sizes, layer)


def _product_fwd(tokens, weights, group_sizes, layer):
    return (_product(tokens, weights, group_sizes, layer),
            (tokens, weights, group_sizes, layer))


def _product_bwd(res, g):
    # ragged_dot's own transposes
    tokens, weights, group_sizes, layer = res
    _, vjp = jax.vjp(lambda t, w: _ragged(t, w, group_sizes, layer),
                     tokens, weights)
    return vjp(g) + (None, None)


_product.defvjp(_product_fwd, _product_bwd)


def grouped_gemm(tokens, expert_weights, group_sizes, layer=None):
    """tokens: (T, E) rows sorted by expert; expert_weights: (X, E, F);
    group_sizes: (X,) rows per expert. Returns (T, F); rows past the groups
    are not written (zero on the CPU, whatever the buffer held on the
    chip). The weights are cast to the tokens' dtype.

    ``layer``: ``expert_weights`` is stacked over layers, (L, X, E, F), and
    this is the layer to multiply by. The stack goes to the product whole
    and the layer picks the block (the kernel) or the groups with rows
    (``ragged_dot``): a layer's slice handed to a kernel is a copy of that
    layer's experts (805 MB for OLMoE-1B-7B, every layer and step)."""
    if layer is not None and expert_weights.dtype != tokens.dtype:
        # the cast is a copy of the layer's experts as it is
        expert_weights, layer = expert_weights[layer], None
    expert_weights = expert_weights.astype(tokens.dtype)
    product = _product if _kernel_runs() else _ragged
    return product(tokens, expert_weights, group_sizes, layer)


def moe_expert_ffn(tokens, wi_gate, wi_up, wo, group_sizes, layer=None):
    """SwiGLU expert FFN over grouped rows: (T, E) → (T, E). ``layer`` as
    in ``grouped_gemm``."""
    g = grouped_gemm(tokens, wi_gate, group_sizes, layer)
    u = grouped_gemm(tokens, wi_up, group_sizes, layer)
    h = jax.nn.silu(g) * u
    return grouped_gemm(h, wo, group_sizes, layer)
