"""The two backward products of a grouped matmul, as Pallas TPU kernels.

`y = lax.ragged_dot(x [M, K], w [E, K, N], counts [E])` multiplies the
rows of group g (rows offsets[g] .. offsets[g + 1] of x, in order) by
w[g].  Autodiff transposes it into two more `ragged_dot`s for which
XLA:TPU first copies the stacked weights into another layout
(PERF.md, PR 26: 4.74 ms for the pair against 2.05 forward at OLMoE's
shapes, and 0.85 ms a copy).  The kernels here read and write `w` where
it lies:

  ragged-dot-dlhs   dx [M, K] = dy [M, N] x w[g]^T, the contraction over N
                    done inside the kernel on the stored [K, N] tile;
  ragged-dot-drhs   dw [E, K, N] = x[g]^T dy[g], accumulated in a float32
                    VMEM scratch over the row tiles of group g, written
                    once a group in `w`'s dtype; zeros for an empty group.

Both walk the rows in tiles of `tm`.  A tile that two or more groups
share is visited once a group under a row mask, so the grid's visit axis
is a list of VISITS (group, row tile), made from `counts` alone
(`_visits`) and handed to the index maps as scalar-prefetch operands;
its length is a traced number (at most M / tm + E - 1).  A visit
multiplies one of three static row ranges of its tile: the whole tile, or
the half its group's rows lie in (`_visit`: with groups of two tiles'
rows half of all visits are shared tiles).  `counts` may be anything that
sums to M: skewed, empty groups, groups smaller than a tile, no boundary
on the tile grid.  Operands go to the MXU in their own dtype (bf16 in the
cells), every product accumulates in float32.

A block holds one group's whole matrix where that fits (`_dlhs_tile`,
`_drhs_tiles`): then x and dy are read once and a visit is one grid step.
That takes more than the 16 MiB a kernel gets by default, so both calls
ask for VMEM_LIMIT of the chip's 128 MiB and size their blocks to
BLOCK_BUDGET (my chip probes, PR 29, in PERF.md section 6).

The names begin `ragged-dot-` because that is what these kernels are:
benchmarks/reduce/moe_ops.py finds the expert layer's grouped matmul
kernels in a device trace by that head.
"""

from __future__ import annotations

import functools

ROW_TILE = 256
DLHS, DRHS = "ragged-dot-dlhs", "ragged-dot-drhs"
VMEM_LIMIT = 64 * 1024 * 1024
# the blocks of one call, double-buffered, with its float32 accumulator:
# half the limit, the rest is Mosaic's for the products' float32 results
BLOCK_BUDGET = VMEM_LIMIT // 2


def usable(rows: int, k: int, n: int, itemsize: int = 2) -> bool:
    """Shapes the kernels take: whole 128-lane tiles of both widths, whole
    row tiles, and blocks that fit the budget."""
    return (k % 128 == 0 and n % 128 == 0 and rows > 0
            and rows % ROW_TILE == 0
            and _dlhs_tile(ROW_TILE, k, n, itemsize) is not None
            and _drhs_tiles(ROW_TILE, k, n, itemsize) is not None)


def _halvings(dim: int):
    """dim, dim / 2, ... while a multiple of 128."""
    while dim % 128 == 0:
        yield dim
        if dim % 2:
            break
        dim //= 2


def _dlhs_tile(tm: int, k: int, n: int, itemsize: int):
    """The dlhs kernel's tile over K, the output's width: all of K if
    w[g] whole, a [tm, n] tile of dy and a [tm, K] tile of dx fit
    (double-buffered, and dx once more in float32), else the widest
    halving that does; None if none.  The contraction over N is never
    split: w[g]'s block stays in VMEM across the row tiles of g."""
    for tn in _halvings(k):
        if (2 * itemsize * (tm * n + tn * n + tm * tn)
                + 4 * tm * tn) <= BLOCK_BUDGET:
            return tn
    return None


def _drhs_tiles(tm: int, k: int, n: int, itemsize: int):
    """(tile over K, tile over N) of the drhs kernel: the [tk, tn] block of
    dw (a float32 accumulator and two output buffers of it, plus the
    double-buffered x and dy tiles) under the budget that moves the fewest
    bytes: x is read once for each tile of N, dy once for each of K.
    None if not even [128, 128] fits."""
    best = None
    for tk in _halvings(k):
        for tn in _halvings(n):
            if ((4 + 2 * itemsize) * tk * tn
                    + 2 * itemsize * tm * (tk + tn)) > BLOCK_BUDGET:
                continue
            moved = k * (n // tn) + n * (k // tk)
            if best is None or moved < best[0]:
                best = (moved, tk, tn)
    return best[1:] if best else None


def _visits(counts, rows: int, tm: int):
    """counts [E] int32 -> (offsets [E + 1], group_of [V], tile_of [V],
    n_visits) int32, V = rows / tm + E - 1.

    Group g is visited once for each row tile its rows touch, the tiles in
    order; an empty group once all the same (the drhs kernel has to write
    its zeros), at the tile where it would start.  Visits are listed by
    group, so the tiles never go backwards and a tile's visits are
    consecutive.  Entries from n_visits on repeat the last visit."""
    import jax.numpy as jnp

    n_groups = counts.shape[0]
    tiles = rows // tm
    ends = jnp.cumsum(counts, dtype=jnp.int32)
    starts = ends - counts
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = jnp.minimum(starts // tm, tiles - 1)
    n_tiles = jnp.where(counts > 0, (ends + tm - 1) // tm - first, 1)
    visit_ends = jnp.cumsum(n_tiles, dtype=jnp.int32)
    n_visits = visit_ends[-1]
    v = jnp.minimum(jnp.arange(tiles + n_groups - 1, dtype=jnp.int32),
                    n_visits - 1)
    group_of = jnp.searchsorted(visit_ends, v, side="right").astype(
        jnp.int32)
    tile_of = first[group_of] + v - (visit_ends - n_tiles)[group_of]
    return offsets, group_of, tile_of.astype(jnp.int32), n_visits


def _visit(offsets, group_of, tile_of, v, tm: int, body):
    """Run body(first row, rows, mask builder) for the one of three static
    row ranges (the tile, its first half, its second half) that covers the
    rows visit v's group has in its tile; nothing for an empty group.
    mask(width) -> [rows, width] bool."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    group, tile = group_of[v], tile_of[v]
    lo, hi = offsets[group] - tile * tm, offsets[group + 1] - tile * tm
    half = tm // 2
    first_half = hi <= half
    second_half = lo >= half
    whole = jnp.logical_not(first_half | second_half)
    for r0, rows, chosen in ((0, tm, whole), (0, half, first_half),
                             (half, half, second_half)):
        def mask(width, r0=r0, rows=rows):
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
            return (row >= lo) & (row < hi)

        pl.when(chosen & (hi > lo))(
            functools.partial(body, r0, rows, mask))


def _dlhs_kernel(offsets, group_of, tile_of, dy_ref, w_ref, dx_ref, *,
                 tm: int):
    """One visit: dx[tile, this K tile] (rows of the group) = dy x w^T."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v = pl.program_id(1)

    # the tile's first visit owns the rows no group has (none where counts
    # sum to the rows): zeros, as ragged_dot gives them
    @pl.when((v == 0) | (tile_of[jnp.maximum(v - 1, 0)] != tile_of[v]))
    def _fresh():
        dx_ref[...] = jnp.zeros(dx_ref.shape, dx_ref.dtype)

    def body(r0, rows, mask):
        at = pl.ds(r0, rows)
        part = jax.lax.dot_general(
            dy_ref[at, :], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dx_ref[at, :] = jnp.where(
            mask(part.shape[1]), part,
            dx_ref[at, :].astype(jnp.float32)).astype(dx_ref.dtype)

    _visit(offsets, group_of, tile_of, v, tm, body)


def _drhs_kernel(offsets, group_of, tile_of, x_ref, dy_ref, dw_ref, acc_ref,
                 *, tm: int):
    """One visit: acc += x[tile]^T dy[tile] over the group's rows; the
    group's last visit writes dw[group]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    group = group_of[v]

    @pl.when((v == 0) | (group_of[jnp.maximum(v - 1, 0)] != group))
    def _opens():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(r0, rows, mask):
        at = pl.ds(r0, rows)
        # another group's rows out of ONE operand is enough
        dy = dy_ref[at, :]
        dy = jnp.where(mask(dy.shape[1]), dy, jnp.zeros_like(dy))
        acc_ref[...] += jax.lax.dot_general(
            x_ref[at, :], dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _visit(offsets, group_of, tile_of, v, tm, body)

    @pl.when((v == last) | (group_of[jnp.minimum(v + 1, last)] != group))
    def _closes():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


@functools.lru_cache(maxsize=None)
def _calls(rows, groups, k, n, dtype, w_dtype, tm, interpret):
    """(dlhs, drhs) for one shape: jitted, and memoized so that the three
    grouped matmuls of every layer share one traced call each (as the
    flash kernels do: PERF.md, PR 27).

      dlhs(dy [rows, n], w [groups, k, n], counts) -> dx [rows, k]
      drhs(x [rows, k], dy [rows, n], counts)      -> dw [groups, k, n]
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    itemsize = jnp.dtype(dtype).itemsize
    tn = _dlhs_tile(tm, k, n, itemsize)
    bk, bn = _drhs_tiles(tm, k, n, itemsize)

    def params(*semantics):
        return pltpu.CompilerParams(dimension_semantics=semantics,
                                    vmem_limit_bytes=VMEM_LIMIT)

    def dlhs(dy, w, counts):
        offsets, group_of, tile_of, n_visits = _visits(counts, rows, tm)
        return pl.pallas_call(
            functools.partial(_dlhs_kernel, tm=tm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(k // tn, n_visits),
                in_specs=[
                    pl.BlockSpec((tm, n), lambda j, v, o, g, t: (t[v], 0)),
                    pl.BlockSpec((None, tn, n),
                                 lambda j, v, o, g, t: (g[v], j, 0)),
                ],
                out_specs=pl.BlockSpec((tm, tn),
                                       lambda j, v, o, g, t: (t[v], j))),
            out_shape=jax.ShapeDtypeStruct((rows, k), dtype),
            compiler_params=params("parallel", "arbitrary"),
            name=DLHS,
            interpret=interpret,
        )(offsets, group_of, tile_of, dy, w)

    def drhs(x, dy, counts):
        offsets, group_of, tile_of, n_visits = _visits(counts, rows, tm)
        return pl.pallas_call(
            functools.partial(_drhs_kernel, tm=tm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n // bn, k // bk, n_visits),
                in_specs=[
                    pl.BlockSpec((tm, bk),
                                 lambda j, i, v, o, g, t: (t[v], i)),
                    pl.BlockSpec((tm, bn),
                                 lambda j, i, v, o, g, t: (t[v], j)),
                ],
                out_specs=pl.BlockSpec((None, bk, bn),
                                       lambda j, i, v, o, g, t: (g[v], i, j)),
                scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((groups, k, n), w_dtype),
            compiler_params=params("parallel", "parallel", "arbitrary"),
            name=DRHS,
            interpret=interpret,
        )(offsets, group_of, tile_of, x, dy)

    return jax.jit(dlhs), jax.jit(drhs)


def grouped_matmul_bwd(x, w, counts, dy, interpret: bool = False):
    """(dx, dw) of `lax.ragged_dot(x, w, counts)` under the cotangent dy:
    what jax.vjp gives, in x's and w's dtypes.  `usable` shapes only, and
    counts that sum to x's rows."""
    import jax.numpy as jnp

    (rows, k), (groups, _, n) = x.shape, w.shape
    dlhs, drhs = _calls(rows, groups, k, n, jnp.dtype(x.dtype),
                        jnp.dtype(w.dtype), ROW_TILE, bool(interpret))
    dy = dy.astype(x.dtype)
    counts = counts.astype(jnp.int32)
    return dlhs(dy, w, counts), drhs(x, dy, counts)
