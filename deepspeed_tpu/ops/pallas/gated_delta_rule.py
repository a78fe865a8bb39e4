"""Pallas gated delta rule: a wide step's linear layer over a row list.

The rule of ``models/layers.py::gdn_rule`` (Gated DeltaNet,
arXiv:2412.06464) for ONE linear layer's step of a (B, C) chunk whose rows
hold different things: a decoding row riding the step holds one live
position, a prefilling row up to C, a frozen row none. As XLA ops the
chunked form is ~50 small float32 launches a block of 64 positions, each
writing a ``(..., 64, 64)`` or ``(..., dk, dv)`` intermediate to HBM
(``model_runner._rule_by_rows``, which stays the CPU's path and this
kernel's oracle). Here a row's state ``(dk, dv)`` a head is read into VMEM
once, carried through the row's LIVE blocks of 64, and written once, in
place; nothing of a block's algebra leaves VMEM.

TPU mapping: the grid is (list position, head group). The rows with a live
position are listed by scalar prefetch (``rows``, their live counts ``n``
in list order, and how many there are); the rows without one follow them
in the list, so that their outputs are zeroed, but their states are never
named: a step past the count keeps the state's block index of the last
listed step (the pipeline moves no block whose index did not change) and
does nothing to it. A step reads its row's ``n``:

- 1 (a rider): the recurrence on position 0, on the VPU: S' = exp(g) S;
  delta = beta (v - S'^T k); S = S' + k (x) delta; o = S^T q.
- more: the chunked form over ``ceil(n / 64)`` blocks. The value heads of
  one key head (Hv / Hk = 2 of them) are stacked into one block-diagonal
  system of 2 x 64 = 128 positions, so that k k^T and q k^T (once a key
  head, side by side), the inverse ``(I - A)^-1`` (``_inverse``) and the
  products against the deltas are 128 x 128 and fill the MXU's tile; the
  deltas are solved as T (beta v - beta e^gc k S), the oracle's u - w S in
  one product.
- 0: zeros out, the state untouched.

A step's head pairs are a loop (``lax.fori_loop``), not unrolled: the
unrolled body ran ~5% faster and took the chip's host seconds to trace.

The key head of a value head is picked by the index map (q | k | v are
lane blocks of the convolution's output ``u`` as it is: nothing is
repeated, split or transposed in HBM), the L2 norms of q and k and the
cumulative decay are computed in the kernel. Every product is float32 at
``Precision.HIGHEST`` (Mosaic's ``contract_precision<fp32>``), as the
configuration states.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...models.layers import _RULE_PRECISION, GDN_CHUNK

# a step's state block, double-buffered in and out: 4 x this of VMEM
_STATE_BLOCK_BYTES = 512 << 10


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=_RULE_PRECISION,
                               preferred_element_type=jnp.float32)


def _pick(row, mask):
    """(m, 1): for each sublane the one lane of ``row`` (1, m) its ``mask``
    (m, m) row names: a row vector turned into a column, exactly (a select
    and a sum along lanes; a transpose of the row's tile timed slower)."""
    return jnp.sum(jnp.where(mask, row, 0.0), axis=1, keepdims=True)


def _along_lanes(x, width):
    """A (1, 1) value along ``width`` lanes, as a value of its own: Mosaic
    broadcasts along sublanes or lanes, not both at once, and what then
    multiplies a (rows, width) matrix broadcasts along sublanes alone."""
    return jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) >= 0, x, 0.0)


def _l2norm(x, scale=1.0):
    y = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    return y if scale == 1.0 else y * scale


_BASE = 8       # positions of the diagonal blocks the merges start from


def _odd(x, s):
    """The rows of ``x`` that lie in the SECOND of each pair of blocks of
    ``s`` rows, packed (``s`` a multiple of 8: whole sublane tiles)."""
    return jnp.concatenate([x[k + s:k + 2 * s]
                            for k in range(0, x.shape[0], 2 * s)], axis=0)


def _to_odd(y, s):
    """``_odd``'s inverse, zeros in the first of each pair of blocks."""
    zero = jnp.zeros((s, y.shape[1]), y.dtype)
    return jnp.concatenate(
        [piece for k in range(0, y.shape[0], s)
         for piece in (zero, y[k:k + s])], axis=0)


def _inverse(a_mat, ri, ci, cs):
    """(I - A)^-1 of ``a_mat`` (m, m): strictly lower triangular within
    each diagonal block of ``cs`` positions, zero elsewhere.

    The diagonal blocks of ``_BASE`` = 8 positions by forward substitution
    on the VPU (row i of a block = e_i + sum_{j < i} A[i, j] row j; a block
    is one sublane tile, so a step is one multiply-add of the whole matrix
    for every block at once: seven steps), then pairs of blocks merged
    level by level, [[Ta, 0], [Tb C Ta, Tb]]: T + T L T with L the blocks
    C, whose rows are HALF the rows, so two half products a level, three
    products' worth for 64. The oracle's doubling, (I + A)(I + A^2)...(I +
    A^32), is ten whole products of which half the MACs are the stacked
    heads' zeros; on the chip a pair of heads' block timed 3.1 us with it
    against 2.5 (PERF.md, PR 49: the microbenchmark)."""
    m = a_mat.shape[0]
    s = min(_BASE, cs)
    inv = (ri == ci).astype(jnp.float32)
    for j in range(s - 1):
        # column j of every diagonal block, times its row j
        step = jnp.sum(jnp.where(ci == ri // s * s + j, a_mat, 0.0), axis=1,
                       keepdims=True)
        row_j = jnp.broadcast_to(inv.reshape(m // s, s, m)[:, j:j + 1],
                                 (m // s, s, m))
        inv = inv + step * row_j.reshape(m, m)
    while s < cs:
        below = ((ri // (2 * s)) == (ci // (2 * s))) & ((ri // s) % 2 == 1) \
            & ((ci // s) % 2 == 0)
        carried = _to_odd(_dot(_odd(jnp.where(below, a_mat, 0.0), s), inv), s)
        inv = inv + _to_odd(_dot(_odd(inv, s), carried), s)
        s *= 2
    return inv


def _kernel(rows_ref, n_ref, chunk_ref, meta_ref,       # scalar prefetch
            q_ref, k_ref, v_ref, q0_ref, k0_ref, v0_ref, beta_ref, g_ref,
            s_in, o_ref, s_out, *, cs, r, dk, dv):
    """One (list position, head group) step; ``cs`` positions a block,
    ``r`` value heads a key head. q_ref / k_ref (1, C, pairs * dk) and
    v_ref (1, C, pairs * r * dv): lanes of ``u``, of the row where it
    prefills (else of a row that does: not moved again); q0_ref / k0_ref /
    v0_ref: the same lanes of the row's first positions, which are all a
    rider needs; beta_ref / g_ref (1, pairs, C / cs, r * cs): a block's
    gates, the r heads' positions side by side; s_in / s_out (1, 1, pairs *
    r, dk, dv); o_ref (1, C, 1, pairs * r, dv): a position's heads are the
    sublanes of one tile, the layout (B, C, Hv, dv) has in HBM, so what
    reads the output reads it as it lies (a (C, heads x dv) block would be
    another tiling: 33.5 MB relaid a layer)."""
    del rows_ref, chunk_ref                         # the index maps read them
    n = n_ref[pl.program_id(0)]
    c = q_ref.shape[1]
    pairs, nblk, m = q_ref.shape[2] // dk, c // cs, r * cs
    f32 = jnp.float32

    def lanes(ref, rows, at, width):
        # (``at`` is traced: the pairs of a step are a loop, not unrolled,
        # so that a kernel is ~700 traced operations and not ~3,000)
        return ref[0, rows, pl.ds(pl.multiple_of(at * width, 128),
                                  width)].astype(f32)

    @pl.when(meta_ref[1] == 0)
    def _nothing_listed():
        # every step keeps ONE state block, which none computes: as it came
        s_out[...] = s_in[...]

    for t in range(nblk):
        @pl.when(n <= t * cs)
        def _dead_block(t=t):
            o_ref[0, t * cs:(t + 1) * cs] = jnp.zeros(
                (cs,) + o_ref.shape[2:], o_ref.dtype)

    @pl.when(n == 1)
    def _rider():
        # position 0 alone, of the row's first tile of positions
        head = slice(0, q0_ref.shape[1])
        first = jax.lax.broadcasted_iota(jnp.int32, (cs, 1), 0) == 0
        ri = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
        eye = ri == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)

        def pair(p, carry):
            q_col = _pick(_l2norm(lanes(q0_ref, head, p, dk)[:1], dk ** -0.5),
                          eye)
            k_col = _pick(_l2norm(lanes(k0_ref, head, p, dk)[:1]), eye)
            for a in range(r):
                h = p * r + a
                gate = slice(a * cs, a * cs + 1)
                beta = beta_ref[0, p, 0:1, gate]                 # (1, 1)
                s = s_in[0, 0, h] * _along_lanes(
                    jnp.exp(g_ref[0, p, 0:1, gate]), dv)
                sk = jnp.sum(s * k_col, axis=0, keepdims=True)   # (1, dv)
                delta = beta * (lanes(v0_ref, head, h, dv)[:1] - sk)
                s = s + k_col * delta
                s_out[0, 0, h] = s
                o = jnp.sum(s * q_col, axis=0, keepdims=True)
                o_ref[0, 0:cs, 0, h, :] = jnp.where(
                    first, o, 0.0).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, pairs, pair, 0)

    def block(t):
        rows = slice(t * cs, (t + 1) * cs)
        ri = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
        same = (ri // cs) == (ci // cs)         # a value head's own block
        eye = ri == ci
        lower = same & (ci <= ri)
        strict = same & (ci < ri)
        last = ci == ri // cs * cs + cs - 1
        live = t * cs + jax.lax.broadcasted_iota(jnp.int32, (cs, 1), 0) < n
        src = s_in if t == 0 else s_out

        def pair(p, carry):
            q = _l2norm(lanes(q_ref, rows, p, dk), dk ** -0.5)   # (cs, dk)
            k = _l2norm(lanes(k_ref, rows, p, dk))
            q2 = jnp.concatenate([q] * r, axis=0)                # (m, dk)
            k2 = jnp.concatenate([k] * r, axis=0)
            v2 = jnp.concatenate([lanes(v_ref, rows, p * r + a, dv)
                                  for a in range(r)], axis=0)    # (m, dv)
            # the cumulative log decay of each head's block, as a row and
            # as the same column
            gc_row = jnp.sum(
                jnp.where(same & (ri <= ci),
                          _pick(g_ref[0, p, t:t + 1, :], eye), 0.0),
                axis=0, keepdims=True)                           # (1, m)
            beta = _pick(beta_ref[0, p, t:t + 1, :], eye)        # (m, 1)
            gc = _pick(gc_row, eye)
            gl = _pick(gc_row, last)            # at its block's last position
            decay = jnp.where(lower, jnp.exp(
                jnp.where(lower, gc - gc_row, 0.0)), 0.0)
            # k k^T and q k^T once a key head, side by side for its heads
            kq = _dot(jnp.concatenate([k, q], axis=0), k2, ((1,), (1,)))
            kk = jnp.concatenate([kq[:cs]] * r, axis=0)          # (m, m)
            qk = jnp.concatenate([kq[cs:]] * r, axis=0)
            inv = _inverse(jnp.where(strict, -(beta * kk) * decay, 0.0),
                           ri, ci, cs)
            ke = k2 * (beta * jnp.exp(gc))
            qe = q2 * jnp.exp(gc)
            states, k_s, q_s = [], [], []
            for a in range(r):
                mine = slice(a * cs, (a + 1) * cs)
                states.append(src[0, 0, p * r + a])
                both = _dot(jnp.concatenate([ke[mine], qe[mine]], axis=0),
                            states[a])
                k_s.append(both[:cs])
                q_s.append(both[cs:])
            # the deltas: T (beta v - beta e^gc k S), u - w S in one solve
            v_new = _dot(inv, v2 * beta - jnp.concatenate(k_s, axis=0))
            o = jnp.concatenate(q_s, axis=0) + _dot(
                jnp.where(lower, qk * decay, 0.0), v_new)
            kd = k2 * jnp.exp(gl - gc)
            for a in range(r):
                mine = slice(a * cs, (a + 1) * cs)
                h = p * r + a
                s_out[0, 0, h] = states[a] * _along_lanes(jnp.exp(
                    gc_row[:, (a + 1) * cs - 1:(a + 1) * cs]), dv) \
                    + _dot(kd[mine], v_new[mine], ((0,), (0,)))
                o_ref[0, rows, 0, h, :] = jnp.where(
                    live, o[mine], 0.0).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, pairs, pair, 0)

    for t in range(nblk):
        pl.when(jnp.logical_and(n > 1, t * cs < n))(
            functools.partial(block, t))


def pairs_a_step(hk, hv, dk, dv):
    """Key heads (each with its value heads) a grid step takes: as many as
    keep a step's state block within ``_STATE_BLOCK_BYTES``."""
    r = hv // hk
    return max(p for p in range(1, hk + 1)
               if hk % p == 0 and (p == 1 or p * r * dk * dv * 4
                                   <= _STATE_BLOCK_BYTES))


def supported(c, hk, hv, dk, dv):
    """Whether the kernel takes a chunk of ``c`` positions of these heads
    (static shapes): whole blocks of ``GDN_CHUNK``, square states whose
    heads are whole lane tiles, and q | k | v cut on a step's blocks."""
    if hv % hk or dk != dv or dk % 128 or c % GDN_CHUNK:
        return False
    pairs = pairs_a_step(hk, hv, dk, dv)
    return (2 * hk * dk) % (pairs * (hv // hk) * dv) == 0


def row_list(n_live):
    """The kernel's list of a step whose rows hold ``n_live`` (B,) live
    positions: (rows (B,) the rows that hold one or more, in slot order,
    then the others; n (B,) the live positions of each listed row, 0 past
    them; chunk (2, B) whose whole chunk a list position's step holds: its
    own where it prefills, else (not to move another) the prefilling row's
    before it, at that row's last head group (1), or where none is before
    it the first one's, at its first head group (0); how many are listed).
    Cumulative sums and a scatter, no sort."""
    listed = n_live > 0
    count = jnp.sum(listed.astype(jnp.int32))
    at = jnp.where(listed, jnp.cumsum(listed.astype(jnp.int32)) - 1,
                   count + jnp.cumsum((~listed).astype(jnp.int32)) - 1)
    order = jnp.arange(at.size, dtype=jnp.int32)
    rows = jnp.zeros_like(order).at[at].set(order)
    n = n_live[rows].astype(jnp.int32)
    before = jax.lax.cummax(jnp.where(n > 1, order, -1))
    chunk = jnp.stack([
        rows[jnp.where(before < 0, jnp.argmax(n > 1), before)],
        (before >= 0).astype(jnp.int32)])
    return rows, n, chunk.astype(jnp.int32), count


def gdn_rule_rows(u, beta, g, state, layer, plan, interpret=None):
    """``_rule_rows`` under ``jax.jit``: a period's linear layers call it
    with the same shapes and are traced once (the chip's host traces the
    kernel in seconds: set-up time). Off the chip the kernel runs
    interpreted (``interpret`` None), as ``kv_commit`` does."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hv, dk, dv = state.shape[2:]
    if not supported(u.shape[1], (u.shape[2] - hv * dv) // (2 * dk), hv, dk,
                     dv):
        raise ValueError(f"no kernel for a chunk {u.shape} of states "
                         f"{state.shape}: ask ``supported`` first")
    return _rule_rows(u, beta, g, state, jnp.asarray(layer, jnp.int32), plan,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rule_rows(u, beta, g, state, layer, plan, interpret):
    """The rule of one linear layer's wide step. u (B, C, 2 Hk dk + Hv dv):
    the convolution's output q | k | v (any float dtype; q and k are
    normalised here); beta, g (B, C, Hv) float32, 0 at a dead position
    (``layers.gdn_gates``); state (linear layers, B, Hv, dk, dv) float32,
    DONATED to the result, and ``layer`` (traced) the one this is;
    ``plan``: ``row_list(n_live)``, a row's live positions its first.

    Returns out (B, C, Hv, dv) float32, zeros at every position that is
    not live, and the states with this layer's listed rows moved on: no
    other block of it is read or written."""
    rows, n, chunk, count = plan
    b, c, channels = u.shape
    _, _, hv, dk, dv = state.shape
    hk = (channels - hv * dv) // (2 * dk)
    r, cs = hv // hk, GDN_CHUNK
    pairs = pairs_a_step(hk, hv, dk, dv)
    steps, nblk = hk // pairs, c // cs
    v_at = 2 * hk * dk // (pairs * r * dv)  # v's first block, in its own
    head = min(16, c)       # positions of a rider's block: a bf16 tile

    def by_block(x):        # (B, C, Hv) -> (B, Hk, blocks, r * cs)
        x = x.reshape(b, nblk, cs, hk, r).transpose(0, 3, 1, 4, 2)
        return x.reshape(b, hk, nblk, r * cs)

    def listed(i, j, rows, n, chunk, meta):
        """The (row, head group) whose blocks step (i, j) holds: its own,
        or the last listed step's where the list has ended."""
        live = i < meta[1]
        return (rows[jnp.minimum(i, jnp.maximum(meta[1] - 1, 0))],
                jnp.where(live, j, steps - 1))

    def chunk_map(first):
        def index(i, j, rows, n, chunk, meta):
            own = n[i] > 1
            return (chunk[0, i], 0, first + jnp.where(
                own, j, chunk[1, i] * (steps - 1)))
        return index

    def head_map(first):
        def index(i, j, *scalars):
            row, j = listed(i, j, *scalars)
            return row, 0, first + j
        return index

    def gate_map(i, j, *scalars):
        row, j = listed(i, j, *scalars)
        return row, j, 0, 0

    def state_map(i, j, *scalars):
        row, j = listed(i, j, *scalars)
        return scalars[3][0], row, j, 0, 0

    gate_spec = pl.BlockSpec((1, pairs, nblk, r * cs), gate_map)
    state_spec = pl.BlockSpec((1, 1, pairs * r, dk, dv), state_map)
    # q | k | v: (lanes a step, the first block) of each; k's lies one
    # round of the head groups behind q's
    parts = (pairs * dk, 0), (pairs * dk, steps), (pairs * r * dv, v_at)
    meta = jnp.stack([layer, count.astype(jnp.int32)])
    out, state = pl.pallas_call(
        functools.partial(_kernel, cs=cs, r=r, dk=dk, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((1, c, w), chunk_map(first))
                      for w, first in parts]
            + [pl.BlockSpec((1, head, w), head_map(first))
               for w, first in parts]
            + [gate_spec, gate_spec, state_spec],
            out_specs=[
                pl.BlockSpec((1, c, 1, pairs * r, dv),
                             lambda i, j, rows, *_: (rows[i], 0, j, 0, 0)),
                state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, c, steps, pairs * r, dv),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4 + 8: 1},        # the states, in place
        name=f"gdn_rule_c{c}",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(rows, n, chunk, meta, u, u, u, u, u, u, by_block(beta), by_block(g),
      state)
    return out.reshape(b, c, hv, dv), state
