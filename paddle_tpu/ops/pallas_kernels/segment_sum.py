"""Rows summed into their tokens, as a Pallas TPU kernel.

`out [T, D] = zeros.at[token].add(rows * weight[:, None])` is what leaves
the buffer of an expert layer's share (ops/moe_ops.py `_moe_share`): the
forward combine of the weighted expert outputs, and the backward of the
row gather `x[token]`.  XLA:TPU runs a scatter-add of rows one row after
the other, ~100 ns a row whatever the row holds (PERF.md, PR 41).  Here
the rows come SORTED by token (`token_order`: one `lax.sort` of the rows'
tokens, unfilled rows last; the caller gathers the rows into that order),
and a sum over sorted rows is a product on the MXU:

  segment-sum-rows   out[tile of TOKEN_TILE tokens] = S x rows[row tile],
                     S[t, r] = weight[r] where row r is token t's, else 0,
                     accumulated in a float32 VMEM scratch over the row
                     tiles the token tile's rows touch, written once a
                     token tile in the output's dtype; zeros for a token
                     tile without rows.

It is grouped_matmul.py's `ragged-dot-drhs` product (`dw[g] = x[g]^T
dy[g]`) with the token tiles as the groups and a one-hot for `x`, so the
grid's visit axis is THAT file's visit list (`_visits`: (token tile, row
tile) pairs made from the rows a token tile has, scalar-prefetched), and
rows past the filled part belong to no token tile: never visited unless a
visited tile ends in them, and masked there, so the cost follows the
filled rows and not the buffer.

The arithmetic is the scatter-add's: float32 weights, float32 products,
a float32 sum.  A bf16 row times a 0/1 entry is exact in one bf16 pass.  A
float32 weight is handed to the MXU as three bf16 pieces (hi + mid + lo
IS the float32), whose products with a bf16 row are exact and add up in
float32: Mosaic's float32 product at default precision would be ONE bf16
pass, which rounds the weight to 8 bits (PERF.md, PR 31).  Float32 rows
go in at `Precision.HIGHEST`.

The name does not begin `ragged-dot`: benchmarks/reduce/moe_ops.py finds
the grouped matmul kernels by that head and counts their calls.
"""

from __future__ import annotations

import functools

from .grouped_matmul import VMEM_LIMIT, _visits

NAME = "segment-sum-rows"
TOKEN_TILE = 128
ROW_TILE = 128
# the row tile and the output tile, double-buffered, the float32
# accumulator and the three float32 products of a visit
BLOCK_BUDGET = VMEM_LIMIT // 2


def usable(rows: int, tokens: int, width: int, itemsize: int = 2) -> bool:
    """Shapes the kernel takes: whole 128-lane tiles of the width, whole
    token tiles, whole row tiles, and blocks that fit the budget."""
    return (itemsize in (2, 4) and rows > 0 and rows % ROW_TILE == 0
            and tokens > 0 and tokens % TOKEN_TILE == 0 and width % 128 == 0
            and (2 * itemsize * ROW_TILE * width
                 + (2 + 1 + 3) * 4 * TOKEN_TILE * width) <= BLOCK_BUDGET)


def token_order(token, filled, weight, tokens: int, sort_length: int = 0):
    """token [R] int32, filled [R] bool, weight [R] float32 ->
    (seg [R] int32, perm [R] int32, weight[perm] float32, counts
    [tokens / TOKEN_TILE] int32): the rows'
    tokens ascending with `tokens` for an unfilled row (behind every token
    tile), the permutation that sorts them, the weights carried by the same
    sort (a gather of R scalars costs the chip more than the sort: PERF.md,
    PR 30), and the rows each token tile has.  The sort is padded to
    `sort_length` entries where that is longer: XLA:TPU compiles a sort of
    tens of thousands of entries for 15-25 s, once for each length and
    operand list in a program, and `_sort_carrying`'s of the pairs is there
    already."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = token.shape[0]
    pad = max(int(sort_length) - n, 0)
    key = jnp.where(filled, token.astype(jnp.int32), tokens)
    key = jnp.concatenate([key, jnp.full(pad, tokens, jnp.int32)])
    carried = jnp.concatenate([
        lax.stop_gradient(weight).astype(jnp.float32),
        jnp.zeros(pad, jnp.float32)])
    seg, perm, carried = (s[:n] for s in lax.sort(
        (key, lax.iota(jnp.int32, n + pad), carried), num_keys=1,
        is_stable=True))
    counts = jnp.sum(jax.nn.one_hot(
        seg // TOKEN_TILE, tokens // TOKEN_TILE, dtype=jnp.int32), axis=0)
    return seg, perm, carried, counts


def _kernel(offsets, group_of, tile_of, seg_ref, *refs, tm: int, tt: int,
            weighted: bool):
    """One visit: acc += S x rows[tile]; the token tile's last visit
    writes out[token tile]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if weighted:
        w_ref, rows_ref, out_ref, acc_ref = refs
    else:
        rows_ref, out_ref, acc_ref = refs
    v = pl.program_id(0)
    last = pl.num_programs(0) - 1
    group, tile = group_of[v], tile_of[v]
    n_groups = offsets.shape[0] - 1

    @pl.when((v == 0) | (group_of[jnp.maximum(v - 1, 0)] != group))
    def _opens():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(offsets[group + 1] > offsets[group])
    def _adds():
        rows = rows_ref[...]
        # a visited tile may end in rows no token has: whatever they hold
        # (NaN included) is kept off the MXU, where 0 x NaN is NaN
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
        rows = jnp.where(row < offsets[n_groups], rows, jnp.zeros_like(rows))
        hit = (seg_ref[...] - group * tt
               == jax.lax.broadcasted_iota(jnp.int32, (tt, tm), 0))
        exact = rows.dtype == jnp.float32

        def times_rows(s):
            return jax.lax.dot_general(
                s, rows, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST if exact else None,
                preferred_element_type=jnp.float32)

        if not weighted:
            pieces = [hit.astype(rows.dtype)]
        else:
            s = jnp.where(hit, w_ref[...], 0.0)
            pieces = [s]
            if not exact:
                # hi + mid + lo is s: each a bf16, each product exact
                hi = s.astype(rows.dtype)
                rest = s - hi.astype(jnp.float32)
                mid = rest.astype(rows.dtype)
                pieces = [hi, mid,
                          (rest - mid.astype(jnp.float32)).astype(rows.dtype)]
        acc_ref[...] += sum(times_rows(p) for p in pieces)

    @pl.when((v == last) | (group_of[jnp.minimum(v + 1, last)] != group))
    def _closes():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _call(rows, tokens, width, out_dtype, weighted, tm, tt, interpret):
    """The jitted call for one shape, memoized so that every layer shares
    one traced call (as grouped_matmul.py's do):

      call(seg [rows] int32, rows_sorted [rows, width], counts
           [tokens / tt] int32 [, weight_sorted [rows] float32])
          -> out [tokens, width]
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(seg, rows_sorted, counts, *weight):
        offsets, group_of, tile_of, n_visits = _visits(counts, rows, tm)
        lane_row = pl.BlockSpec((1, tm), lambda v, o, g, t: (0, t[v]))
        return pl.pallas_call(
            functools.partial(_kernel, tm=tm, tt=tt, weighted=weighted),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_visits,),
                in_specs=[lane_row] * (1 + len(weight)) + [
                    pl.BlockSpec((tm, width),
                                 lambda v, o, g, t: (t[v], 0))],
                out_specs=pl.BlockSpec((tt, width),
                                       lambda v, o, g, t: (g[v], 0)),
                scratch_shapes=[pltpu.VMEM((tt, width), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((tokens, width), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            name=NAME,
            interpret=interpret,
        )(offsets, group_of, tile_of, seg.reshape(1, rows),
          *(w.reshape(1, rows) for w in weight), rows_sorted)

    return jax.jit(call)


def segment_sum(rows_sorted, seg, counts, tokens: int, weight_sorted=None,
                interpret: bool = False):
    """out [tokens, D]: out[t] = the sum of rows_sorted[r] (times
    weight_sorted[r], float32, where given) over the rows with seg[r] == t,
    summed in float32: float32 where weighted, else rounded once to the
    rows' dtype.  `seg` ascending with `tokens` for the rows no token has,
    `counts` the rows of each token tile, both as `token_order` makes
    them; `usable` shapes only."""
    import jax.numpy as jnp

    rows, width = rows_sorted.shape
    weighted = weight_sorted is not None
    call = _call(rows, int(tokens), width,
                 jnp.dtype(jnp.float32 if weighted else rows_sorted.dtype),
                 weighted, ROW_TILE, TOKEN_TILE, bool(interpret))
    return call(seg, rows_sorted, counts,
                *((weight_sorted,) if weighted else ()))
