"""LFM2's gated short convolution in one pass over HBM, and one pass back.

Between a convolution layer's two projections stands the op
`gated_short_conv` (ops/llm_ops.py has the equations): X [B, T, 3D] is the
input projection's result, three thirds B, C, u side by side; per channel
g = B * u, c the L causal taps on g, Out = C * c.  As plain jax.numpy XLA
widens the WHOLE of X to float32 in the projection's epilogue and hands a
float32 gradient of that size back (PERF.md, PR 33 and 46: 10.7 ms a step
of `lfm2_train_t8192` against 3.15 at the HBM roof).  The two kernels here
take X where the projection wrote it, in its own dtype, widen in VMEM and
round once at each output ([T, D]-sized tensors read + written):

  short_conv_fwd  X, Filter -> Out [B, T, D] in X's dtype (3 + 1)
  short_conv_bwd  X, Filter, dOut -> dX [B, T, 3D] in X's dtype, its three
                  thirds written where the projection's backward reads
                  them, and the taps' gradient as float32 partials [B, 8 L,
                  D] (tap j in rows 8j..8j+7) that the caller sums (4 + 3).
                  Nothing of the forward is kept: g and c are made again
                  for the tile.

Backward, per channel, with dc = dOut * C:  dC = dOut * c,  dg_t = sum_j
Filter[:, j] * dc_{t + (L - 1) - j} (the taps run the other way: no future
after the sequence's end),  dB = dg * u,  du = dg * B,  dFilter[:, j] =
sum_t dc_t * g_{t - (L - 1) + j}.

**Shape of a body.**  A grid step is a tile of whole rows of X (all 3D
columns: the three thirds are lane slices of one block, the block one
contiguous piece of HBM, and dX leaves as one tensor).  The taps reach L - 1
rows into the neighbour tiles: those come as blocks of ROWS rows of the
same operand (B's and u's third before the tile; C's third and dOut after
it, in the backward), clamped at the sequence's ends and zeroed there.
Inside, a loop over column chunks and, in it, one over chunks of ROWS rows
that carries the neighbour chunk's g (forward) or dc (backward, which walks
the rows upwards from the tile's end): a shift by s rows is ONE sublane
roll of the chunk with the neighbour's s edge rows selected in.

**Probed on the chip** (my chip runs, PR 46; TPU v5 lite; ms a call alone,
forward / backward, at the cell's shape [1, 8192, 6144] bf16, L 3, where
the least by bytes is 0.164 / 0.287 and XLA's plain emission reads 0.826 /
2.408).  Whole rows, rows a step x lanes a chunk: 64 x 128 0.254 / 0.430,
64 x 256 0.228 / 0.407, 128 x 256 0.213 / 0.385, **256 x 256 0.205 / 0.374**
(kept: 80% / 77%; in the step of `lfm2_train_t8192` 0.201 / 0.360), 256 x
128 0.218 / 0.378, 256 x 512 0.205 / 0.374, 512 x 256 0.210 / 0.377, 512 x
512 0.208 / 0.379: flat from 128 rows and 256 lanes up, bound by HBM.  **The
tiling that lost**: column blocks that hold the whole sequence, [8192, 128
or 256 lanes] of each third through its own index map, no neighbour rows, a
raised VMEM limit: 0.211 / 0.496 and 0.206 / 0.509.  Its forward ties; its
backward is a third slower though it wrote dB, dC and du as THREE tensors
(its best case: the projection's backward wants them as the column ranges
of one, which a column block cannot write without a fourth grid axis or a
copy), so whole rows stay.
"""

from __future__ import annotations

import functools

FWD, BWD = "short_conv_fwd", "short_conv_bwd"
LANES = 128
ROWS = 16          # rows a chunk and a neighbour block: one bf16 vreg's
ROW_TILE = 256     # most rows a grid step
COLS = 256         # most lanes a chunk
MAX_TAPS = ROWS    # a shift stays inside the neighbour chunk (L - 1 < ROWS)
VMEM_LIMIT = 64 * 1024 * 1024
# the backward's blocks (X and dOut in, dX out: 7 D a row), double-buffered
BLOCK_BUDGET = 40 * 1024 * 1024


def row_tile(T: int, D: int, itemsize: int, tile: int = ROW_TILE) -> int:
    """Rows a grid step: `tile` halved until it divides T and the
    backward's blocks fit BLOCK_BUDGET; 0 where no whole chunks do."""
    while tile >= ROWS:
        if T % tile == 0 and 2 * 7 * tile * D * itemsize <= BLOCK_BUDGET:
            return tile
        tile //= 2
    return 0


def usable(T: int, D: int, L: int, dtype) -> bool:
    """The kernels take X [B, T, 3D] under L taps: bf16 or float32, D in
    whole 128-lane blocks, T in whole row tiles."""
    size = {"bfloat16": 2, "float32": 4}.get(str(dtype))
    if not size or D % LANES or not 1 <= L <= MAX_TAPS:
        return False
    return bool(row_tile(T, D, size))


def _chunk_lanes(D: int, cols: int) -> int:
    """The most lanes a column chunk, up to `cols`, in whole blocks that
    divide D."""
    return max(c for c in range(LANES, max(cols, LANES) + 1, LANES)
               if D % c == 0)


def _wide(ref, rows, cols):
    import jax.numpy as jnp

    return ref[rows, cols].astype(jnp.float32)


def _row(shape):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _down(cur, before, s: int):
    """Row t holds row t - s of `cur`, of the chunk `before` it where t <
    s."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(jnp.where(_row(cur.shape) >= ROWS - s, before, cur),
                      s, 0)


def _up(cur, after, s: int):
    """Row t holds row t + s of `cur`, of the chunk `after` it where t + s
    >= ROWS."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(jnp.where(_row(cur.shape) < s, after, cur),
                      ROWS - s, 0)


def _columns(D: int, cw: int, body):
    """body(c0, third) for every chunk of `cw` columns: c0 its first
    column, third(k) the chunk's lanes in X's k-th third."""
    from jax import lax
    from jax.experimental import pallas as pl

    def step(c, carry):
        c0 = pl.multiple_of(c * cw, cw)
        body(c0, lambda k: pl.ds(pl.multiple_of(k * D + c0, LANES), cw))
        return carry

    lax.fori_loop(0, D // cw, step, None)


def _fwd_body(x_ref, hb_ref, hu_ref, w_ref, o_ref, *, taps, cw):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    tile, D = o_ref.shape
    starts = pl.program_id(1) == 0       # no history before the sequence

    def column(c0, third):
        cols = pl.ds(c0, cw)
        w = [w_ref[j:j + 1, cols] for j in range(taps)]

        def chunk(r0, before):
            rows = pl.ds(r0, ROWS)
            g = _wide(x_ref, rows, third(0)) * _wide(x_ref, rows, third(2))
            c = w[taps - 1] * g
            for s in range(1, taps):     # the tap s tokens ago
                c = c + w[taps - 1 - s] * _down(g, before, s)
            o_ref[rows, cols] = (_wide(x_ref, rows, third(1))
                                 * c).astype(o_ref.dtype)
            return g

        every = slice(None)
        halo = _wide(hb_ref, every, cols) * _wide(hu_ref, every, cols)
        g = chunk(0, jnp.where(starts, 0.0, halo))
        lax.fori_loop(
            1, tile // ROWS,
            lambda r, g: chunk(pl.multiple_of(r * ROWS, ROWS), g), g)

    _columns(D, cw, column)


def _bwd_body(x_ref, do_ref, hb_ref, hu_ref, hc_ref, hdo_ref, w_ref, dx_ref,
              dw_ref, *, taps, cw):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    tile, D = do_ref.shape
    n = tile // ROWS
    starts = pl.program_id(1) == 0
    ends = pl.program_id(1) == pl.num_programs(1) - 1   # no future after

    @pl.when(starts)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def column(c0, third):
        cols = pl.ds(c0, cw)
        w = [w_ref[j:j + 1, cols] for j in range(taps)]

        def chunk(r0, g_before, carry):
            """One chunk of rows; `carry` = (dc of the chunk after, the
            taps' partial sums) -> (this chunk's dc, the sums with it)."""
            dc_after, sums = carry
            rows = pl.ds(r0, ROWS)
            b, u = _wide(x_ref, rows, third(0)), _wide(x_ref, rows, third(2))
            do = _wide(do_ref, rows, cols)
            g, dc = b * u, do * _wide(x_ref, rows, third(1))
            c, dg = w[taps - 1] * g, w[taps - 1] * dc
            dws = [dc * g]
            for s in range(1, taps):
                gs = _down(g, g_before, s)
                c = c + w[taps - 1 - s] * gs
                dg = dg + w[taps - 1 - s] * _up(dc, dc_after, s)
                dws.append(dc * gs)
            dx_ref[rows, third(0)] = (dg * u).astype(dx_ref.dtype)
            dx_ref[rows, third(1)] = (do * c).astype(dx_ref.dtype)
            dx_ref[rows, third(2)] = (dg * b).astype(dx_ref.dtype)
            # [ROWS, cw] -> [8, cw] by adds of whole vregs; XLA sums the
            # eight sublanes with the tiles
            return dc, tuple(a + d.reshape(-1, 8, cw).sum(axis=0)
                             for a, d in zip(sums, dws))

        def inner(k, carry):             # the chunks 1.. from the last up
            r0 = pl.multiple_of((n - 1 - k) * ROWS, ROWS)
            before = pl.ds(r0 - ROWS, ROWS)
            return chunk(r0, _wide(x_ref, before, third(0))
                         * _wide(x_ref, before, third(2)), carry)

        every = slice(None)
        carry = (jnp.where(ends, 0.0, _wide(hdo_ref, every, cols)
                           * _wide(hc_ref, every, cols)),
                 (jnp.zeros((8, cw), jnp.float32),) * taps)
        carry = lax.fori_loop(0, n - 1, inner, carry)
        halo = _wide(hb_ref, every, cols) * _wide(hu_ref, every, cols)
        _, sums = chunk(0, jnp.where(starts, 0.0, halo), carry)
        for s, part in enumerate(sums):  # dws[s] is tap L - 1 - s's
            dw_ref[pl.ds(8 * (taps - 1 - s), 8), cols] += part

    _columns(D, cw, column)


@functools.lru_cache(maxsize=None)
def _calls(B, T, D, taps, dtype, interpret, tile, cols):
    """(forward, backward) calls on X [B, T, 3D]; memoized and jitted, so
    every layer of a model shares one trace of each body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = row_tile(T, D, jnp.dtype(dtype).itemsize, tile)
    if not tile:
        raise ValueError(f"short_conv: no row tile for T {T} at {D} channels")
    per, blocks = tile // ROWS, T // ROWS
    kw = dict(taps=taps, cw=_chunk_lanes(D, cols))

    def halo(third, after):
        """ROWS rows of X's `third` (of dOut: 0) next to the tile."""
        def at(b, i):
            edge = (i + 1) * per if after else i * per - 1
            return b, jnp.clip(edge, 0, blocks - 1), third
        return pl.BlockSpec((None, ROWS, D), at)

    rows = pl.BlockSpec((None, tile, 3 * D), lambda b, i: (b, i, 0))
    third = pl.BlockSpec((None, tile, D), lambda b, i: (b, i, 0))
    filt = pl.BlockSpec((taps, D), lambda b, i: (0, 0))
    fwd = pl.pallas_call(
        functools.partial(_fwd_body, **kw),
        grid=(B, T // tile),
        in_specs=[rows, halo(0, False), halo(2, False), filt],
        out_specs=third,
        out_shape=jax.ShapeDtypeStruct((B, T, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=FWD, interpret=interpret)
    bwd = pl.pallas_call(
        functools.partial(_bwd_body, **kw),
        grid=(B, T // tile),
        in_specs=[rows, third, halo(0, False), halo(2, False),
                  halo(1, True), halo(0, True), filt],
        # the taps' partial sums stay in VMEM across a sequence's tiles
        out_specs=[rows, pl.BlockSpec((None, 8 * taps, D),
                                      lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, 3 * D), dtype),
                   jax.ShapeDtypeStruct((B, 8 * taps, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=BWD, interpret=interpret)
    return jax.jit(fwd), jax.jit(bwd)


def _prepared(x, w, interpret, tile, cols):
    """((forward, backward) calls for X, the taps as float32 [L, D])."""
    import jax.numpy as jnp

    B, T, _ = x.shape
    D, taps = w.shape
    return (_calls(B, T, D, taps, str(x.dtype), interpret, tile, cols),
            jnp.transpose(w).astype(jnp.float32))


def short_conv_fwd(x, w, *, interpret=False, tile=ROW_TILE, cols=COLS):
    """X [B, T, 3D], Filter [D, L] -> Out [B, T, D] (module docstring)."""
    (fwd, _), wt = _prepared(x, w, interpret, tile, cols)
    return fwd(x, x, x, wt)


def short_conv_bwd(dout, x, w, *, interpret=False, tile=ROW_TILE, cols=COLS):
    """dOut [B, T, D], X, Filter -> (dX like X, dFilter float32 [D, L])."""
    (_, bwd), wt = _prepared(x, w, interpret, tile, cols)
    dx, parts = bwd(x, dout, x, x, x, dout, wt)
    D, taps = w.shape
    return dx, parts.reshape(-1, taps, 8, D).sum(axis=(0, 2)).T
