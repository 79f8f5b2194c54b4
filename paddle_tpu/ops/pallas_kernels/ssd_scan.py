"""Mamba-2's scan (ops/ssm_ops.py `ssd_scan` has the equations, `ssd_chunked`
the chunked dual form these kernels follow to the rounding) as a Pallas
kernel pair: a chunk's cumulative log-decays, C B^T, the decayed [Q, Q] tile
a head and the float32 [P, N] state a head live in VMEM, and only X, B, C,
Dt, Out, their gradients and one float32 state a chunk cross HBM.  X, B, C
and Dt are read TOKEN-major where the convolution and the input projection
leave them ([B, T, H P], [B, T, G N], [B, T, H]); Delta = softplus(Dt +
DtBias) and A = -exp(ALog) are made inside.

  ssd_fwd  grid (batch, chunk), the chunk axis sequential, the float32 state
           of ALL heads ([H P, N]: 2 MB at 64 heads of 64 on a state of 128)
           in VMEM scratch from the first chunk to the last.  A step makes
           Delta, the cumulative log-decays c [Q, H] (and their transpose:
           a tile needs c_i down the rows and c_j along the lanes) and C B^T
           ONCE a group, then walks the BLOCKS of 128 columns of X (two heads
           of 64, or one of 128) in a `fori_loop`: a head's tile exp(c_i -
           c_j) Delta_j (C_i . B_j) on the VPU in float32, rounded to X's
           dtype against the block's x; the read-out exp(c_i) C_i S_in^T and
           the summary (x_j w_j)^T B_j as full-width [128, N] products; the D
           term.  Beside Out it ALWAYS writes every chunk's INCOMING state
           ([n, H P, N] float32): one forward body (a second costs every
           process's set-up 2-3 s: kda.py).
  ssd_bwd  ONE reverse pass over the chunks with dS in VMEM scratch: from X,
           B, C, Dt, dOut and the kept states it makes the decays and tiles
           again and writes dX, dB, dC (the heads' W_ij = decay_ij Delta_j
           (dy_i . x_j) tiles SUM to one [Q, Q] tile a group before the two
           products with B and C), dDt (through the softplus) and float32
           partials of dALog and dDtBias [B, 8, H] and dD [B, 1, H P] that
           the caller sums.  What reaches a head's c and Delta arrives as
           [Q, 1] columns (lane sums) and [1, Q] rows (sublane sums): the
           columns are set into [Q, H] tiles lane by lane, the rows stored
           into an [2 H, Q] tile and turned once a chunk.

Two heads of 64 columns share a lane tile: their two tiles multiply the
block's [Q, 128] x as ONE [2 Q, Q] x [Q, 128] product (the v5e's MXU is 128
wide: the passes of [Q, 64]) and a lane select keeps each head's half; the
block's state is a [128, N] tile whose rows are (head, p).

Precision is `ssd_chunked`'s: Delta, A, every exponent (a difference of
cumulative log-decays, <= 0; above the diagonal -inf), the state and the
sums float32; the products take X, B and C in their own dtype with float32
accumulation; the decayed tile, the incoming state and x_j w_j are rounded to
X's dtype only as operands of a product, and so are the cotangents that meet
them (dOut, e^c dOut, dS, the summed W tile: what XLA's default precision
does to the float32 cotangents of `ssd_chunked`'s products on the chip); the
carried state and dS never; one rounding to X's dtype at the end.

`make_ssd_scan(heads, groups)` is the `kernel_pair` (_common.py) over the
two.

The chunk, ms a call alone on a v5e at the cell's shape (X [1, 8192, 4096]
bf16, 64 heads of 64, N 128, one group; a layer of `granite4h_train_t8192`
launches the forward twice, once in its segment's replay, and the reverse
pass once; my chip runs, PR 70):

  chunk   ssd_fwd   ssd_bwd   fwd x 2 + bwd
     64     1.71      2.25        5.67
    128     0.98      1.41        3.36
    256     0.66      1.45        2.78     <- CHUNK
    512     0.78      2.07        3.62
  `ssd_chunked` at 256, jitted alone: 2.98 forward, 6.70 forward + backward

The tiles' VPU work goes with T x Q (it is what 512 pays), the state's two
products and the kept states with T / Q (what 64 and 128 pay); in the cell's
step the launches read 0.60 and 1.39 ms.  Not built, by what the other
kernel files measured: a forward body that writes no states (a second body
costs every process's set-up 2-3 s, kda.py; the states are 0.08 ms of HBM
writes a launch) and a grid over heads (C B^T 64 times a chunk).  The reverse
pass asks for 24 MB of VMEM, what it needs at chunks of 256, and the forward
for the compiler's own 16: with 64 MB for both the cell's step read 413.0 ms
for 408.3, XLA's own fusions around the launches the slower
(PERF.md section 6, PR 70).
"""

from __future__ import annotations

import functools

from .gated_delta import LANES, _NN, _NT, _TN
from .kda import _cumsum

FWD, BWD = "ssd_fwd", "ssd_bwd"
ROWS = 16           # the rows of a bf16 sublane tile
# Tokens a chunk of the kernels (the attr `chunk` is the plain emission's).
CHUNK = 256
# What the reverse pass may hold of VMEM at chunks of 256 (its blocks of X,
# dOut, dX and the kept state twice over, dS and the [Q, Q] tiles: 23 MB); the
# forward fits the compiler's own 16 MB.
BWD_VMEM_LIMIT = 24 * 1024 * 1024


def usable(T: int, chunk: int, H: int, P: int, N: int, G: int, dtype) -> bool:
    """The kernels take X [B, T, H P], B and C [B, T, G N] in bf16 or float32
    where the chunk divides T and is whole row tiles (bf16's 16), the state's
    N is whole lane tiles, and a head is one lane tile, or half of one with
    an even number of heads a group (a pair of heads shares its B and C)."""
    if str(dtype) not in ("bfloat16", "float32"):
        return False
    if min(T, chunk, H, P, N, G) < 1 or H % G:
        return False
    if T % chunk or chunk % ROWS or N % LANES:
        return False
    return P == LANES or (2 * P == LANES and (H // G) % 2 == 0)


def _dot(a, b, dims=_NN):
    """A product of two operands of one dtype, float32 out."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _lane(shape):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _column(tile, h):
    """Column h of a [Q, H] tile as [Q, 1] (h a traced scalar: one term a
    sum, exact)."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(_lane(tile.shape) == h, tile, 0.0), axis=1,
                   keepdims=True)


def _halves(P):
    """For each head of a block of LANES columns, its lanes [1, LANES] (None
    where the block is one head)."""
    if P == LANES:
        return [None]
    first = _lane((1, LANES)) < P
    return [first, ~first]


def _by_head(values, masks, rows=False):
    """The heads' [Q, 1] (or [1, 1]) values spread over their lanes of the
    block -> [Q, LANES]; with `rows` over their rows of a [LANES, 1]
    column."""
    import jax
    import jax.numpy as jnp

    if masks[0] is None:
        return values[0]
    first = masks[0]
    if rows:
        first = jax.lax.broadcasted_iota(
            jnp.int32, (LANES, 1), 0) < LANES // len(masks)
    return jnp.where(first, values[0], values[1])


def _head_sums(tile, masks):
    """The sums of a [Q, LANES] tile over each head's lanes -> a [Q, 1]
    column a head."""
    import jax.numpy as jnp

    return [jnp.sum(tile if m is None else jnp.where(m, tile, 0.0), axis=1,
                    keepdims=True) for m in masks]


def _chunk(dt_ref, par_ref, b_ref, c_ref, cum_scr, delta_scr, rows_scr,
           sc_scr, tri_scr, G):
    """What both kernels make of a chunk before they walk its blocks, into
    scratch: Delta = softplus(Dt + DtBias) and the cumulative log-decays c =
    cumsum(Delta A), float32 [Q, H]; both turned into the rows of `rows_scr`
    [2 H, Q] (c above Delta); a group's C B^T, zero above the diagonal
    (`sc_scr[g]`); the triangle as what an exponent adds (0 where i >= j,
    -inf above).  -> (Dt + DtBias, Delta, A [1, H])."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    Q, H = dt_ref.shape
    N = b_ref.shape[1] // G
    pre = dt_ref[...].astype(f32) + par_ref[2:3, :]
    delta = jax.nn.softplus(pre)
    a = -jnp.exp(par_ref[0:1, :])
    cum = _cumsum(delta * a)
    cum_scr[...] = cum
    delta_scr[...] = delta
    rows_scr[0:H, :] = cum.T
    rows_scr[H:2 * H, :] = delta.T
    lower = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    tri_scr[...] = jnp.where(lower, 0.0, -jnp.inf)
    for g in range(G):
        at = slice(g * N, (g + 1) * N)
        sc_scr[g] = jnp.where(lower, _dot(c_ref[:, at], b_ref[:, at], _NT),
                              0.0)
    return pre, delta, a


def _block(blk, P, K, N, H, cum_scr, delta_scr, rows_scr, tri_scr):
    """A block's place and its heads' decays: (the block's 128 columns of X
    (its rows of the state), its group g, that group's columns of B and C,
    its first head, for each head (c [Q, 1], Delta [Q, 1], exp(c_i - c_j)
    Delta_j [Q, Q] where i >= j and 0 above))."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    per = LANES // P
    at = pl.ds(pl.multiple_of(blk * LANES, LANES), LANES)
    first = blk * per
    g = first // K
    group = pl.ds(pl.multiple_of(g * N, LANES), N)
    cum, delta = cum_scr[...], delta_scr[...]
    heads = []
    for k in range(per):
        h = first + k
        ccol, dcol = _column(cum, h), _column(delta, h)
        crow = rows_scr[pl.ds(h, 1), :]
        drow = rows_scr[pl.ds(H + h, 1), :]
        decay = jnp.exp((ccol - crow) + tri_scr[...]) * drow
        heads.append((ccol, dcol, decay))
    return at, g, group, first, heads


def _stacked(tiles, axis):
    import jax.numpy as jnp

    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=axis)


def _fwd_body(x_ref, b_ref, c_ref, dt_ref, par_ref, y_ref, st_ref, s_scr,
              cum_scr, delta_scr, rows_scr, sc_scr, tri_scr, *, P, G):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    Q, H = dt_ref.shape
    N = b_ref.shape[1] // G
    dtype = x_ref.dtype
    per = LANES // P

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    st_ref[...] = s_scr[...]
    _chunk(dt_ref, par_ref, b_ref, c_ref, cum_scr, delta_scr, rows_scr,
           sc_scr, tri_scr, G)

    def block(blk, carry):
        at, g, group, first, heads = _block(
            blk, P, H // G, N, H, cum_scr, delta_scr, rows_scr, tri_scr)
        masks = _halves(P)
        xb = x_ref[:, at]
        xf = xb.astype(f32)
        s_in = s_scr[at, :]                                    # [128, N]
        scores = sc_scr[g]
        inside = _dot(_stacked([(decay * scores).astype(dtype)
                                for _, _, decay in heads], 0), xb)
        y = _by_head([inside[k * Q:(k + 1) * Q] for k in range(per)], masks)
        cum = _by_head([c for c, _, _ in heads], masks)        # [Q, 128]
        delta = _by_head([d for _, d, _ in heads], masks)
        total = cum[Q - 1:Q, :]
        y = y + jnp.exp(cum) * _dot(c_ref[:, group], s_in.astype(dtype), _NT)
        skip = _by_head([_column(par_ref[1:2, :], first + k)
                         for k in range(per)], masks)
        y_ref[:, at] = (y + skip * xf).astype(dtype)
        xw = (xf * (jnp.exp(total - cum) * delta)).astype(dtype)
        keep = _by_head([jnp.exp(c[Q - 1:Q, :]) for c, _, _ in heads], masks,
                        rows=True)
        s_scr[at, :] = keep * s_in + _dot(xw, b_ref[:, group], _TN)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(H * P // LANES), block,
                      jnp.int32(0))


def _bwd_body(x_ref, b_ref, c_ref, dt_ref, par_ref, dy_ref, st_ref, dx_ref,
              db_ref, dc_ref, ddt_ref, dpar_ref, dskip_ref, ds_scr, cum_scr,
              delta_scr, rows_scr, sc_scr, tri_scr, w_scr, dbc_scr,
              dcols_scr, drows_scr, *, P, G):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    Q, H = dt_ref.shape
    N = b_ref.shape[1] // G
    dtype = x_ref.dtype
    per = LANES // P
    colsum = lambda t: jnp.sum(t, axis=0, keepdims=True)      # noqa: E731
    rowsum = lambda t: jnp.sum(t, axis=1, keepdims=True)      # noqa: E731

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dpar_ref[...] = jnp.zeros_like(dpar_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    pre, delta_all, a = _chunk(dt_ref, par_ref, b_ref, c_ref, cum_scr,
                               delta_scr, rows_scr, sc_scr, tri_scr, G)
    w_scr[...] = jnp.zeros_like(w_scr)
    dbc_scr[...] = jnp.zeros_like(dbc_scr)
    dcols_scr[...] = jnp.zeros_like(dcols_scr)

    def block(blk, carry):
        at, g, group, first, heads = _block(
            blk, P, H // G, N, H, cum_scr, delta_scr, rows_scr, tri_scr)
        masks = _halves(P)
        xb, dyb = x_ref[:, at], dy_ref[:, at]
        xf, dyf = xb.astype(f32), dyb.astype(f32)
        bg, cg = b_ref[:, group], c_ref[:, group]
        s_in, ds = st_ref[at, :], ds_scr[at, :]                # [128, N]
        sb, dsb = s_in.astype(dtype), ds.astype(dtype)
        scores = sc_scr[g]
        cum = _by_head([c for c, _, _ in heads], masks)        # [Q, 128]
        delta = _by_head([d for _, d, _ in heads], masks)
        totals = [c[Q - 1:Q, :] for c, _, _ in heads]          # [1, 1]
        eend = jnp.exp(_by_head(totals, masks) - cum)
        w = eend * delta
        # Out = ... + D x
        dskip_ref[:, at] += colsum(dyf * xf)
        # the read-out e^c C S_in^T: into C, S_in and c
        dye = dyf * jnp.exp(cum)
        dyeb = dye.astype(dtype)
        dbc_scr[1, :, group] += _dot(dyeb, sb)
        to_c = dye * _dot(cg, sb, _NT)
        # the summary (x w)^T B under dS: into x, w, B, S_in and the total
        dxw = _dot(bg, dsb, _NT)                               # [Q, 128]
        dbc_scr[0, :, group] += _dot((xf * w).astype(dtype), dsb)
        into_w = dxw * xf
        to_total = into_w * w
        keep = [jnp.exp(t) for t in totals]
        carried = rowsum(ds * s_in)                            # [128, 1]
        ds_scr[at, :] = (_by_head(keep, masks, rows=True) * ds
                         + _dot(dyeb, cg, _TN))
        # the heads' tiles L = decay * scores against x: dL_ij = dy_i . x_j
        dtile = _dot(_stacked([dyb if m is None else jnp.where(
            m, dyf, 0.0).astype(dtype) for m in masks], 0), xb, _NT)
        tiles = [decay * scores for _, _, decay in heads]
        turned = _dot(_stacked([t.astype(dtype) for t in tiles], 1), dyb,
                      _TN)                                     # [per Q, 128]
        skip = _by_head([_column(par_ref[1:2, :], first + k)
                         for k in range(per)], masks)
        dx_ref[:, at] = (dxw * w + skip * dyf + _by_head(
            [turned[k * Q:(k + 1) * Q] for k in range(per)],
            masks)).astype(dtype)
        to_c = _head_sums(to_c - to_total, masks)              # [Q, 1]
        to_delta = _head_sums(into_w * eend, masks)
        to_total = _head_sums(to_total, masks)
        rows_of = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0) // P
        last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
        wsum = None
        for k, (_, _, decay) in enumerate(heads):
            h = first + k
            drow = rows_scr[pl.ds(H + h, 1), :]
            both = dtile[k * Q:(k + 1) * Q] * decay            # W
            wsum = both if wsum is None else wsum + both
            # decay_ij = e^{c_i - c_j} Delta_j under W s = dL L: into c_i
            # (+), c_j (-) and Delta_j (a Delta that underflowed to 0 made a
            # zero column, and softplus' slope there is 0 too)
            m = dtile[k * Q:(k + 1) * Q] * tiles[k]
            per_j = colsum(m)                                  # [1, Q]
            drows_scr[pl.ds(h, 1), :] = -per_j
            drows_scr[pl.ds(H + h, 1), :] = jnp.where(
                drow > 0.0, per_j / drow, 0.0)
            total = colsum(to_total[k]) + keep[k] * colsum(
                jnp.where(rows_of == k, carried, 0.0))
            col = to_c[k] + rowsum(m) + jnp.where(last, total, 0.0)
            here = _lane((Q, H)) == h
            dcols_scr[0] = jnp.where(here, col, dcols_scr[0])
            dcols_scr[1] = jnp.where(here, to_delta[k], dcols_scr[1])
        w_scr[g] += wsum
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(H * P // LANES), block,
                      jnp.int32(0))
    # c = cumsum(Delta A): back up the rows; Delta through the softplus
    dcum = dcols_scr[0] + drows_scr[0:H, :].T
    da = _cumsum(dcum, reverse=True)
    ddelta = dcols_scr[1] + drows_scr[H:2 * H, :].T + da * a
    ddt = ddelta * jax.nn.sigmoid(pre)
    ddt_ref[...] = ddt.astype(ddt_ref.dtype)
    dpar_ref[0:1, :] += a * colsum(da * delta_all)      # dA / dALog = A
    dpar_ref[2:3, :] += colsum(ddt)
    for g in range(G):
        at = slice(g * N, (g + 1) * N)
        wb = w_scr[g].astype(dtype)
        db_ref[:, at] = (dbc_scr[0, :, at]
                         + _dot(wb, c_ref[:, at], _TN)).astype(dtype)
        dc_ref[:, at] = (dbc_scr[1, :, at]
                         + _dot(wb, b_ref[:, at])).astype(dtype)


@functools.lru_cache(maxsize=None)
def _calls(B, T, H, P, N, G, Q, dtype, dt_dtype, interpret):
    """(forward -> Out and every chunk's incoming state; backward) on X [B,
    T, H P], B and C [B, T, G N], Dt [B, T, H] and the heads' parameters [8,
    H] float32 (ALog, D, DtBias in rows 0, 1, 2) in chunks of Q tokens;
    memoized and jitted, so every layer of a model shares one trace of each
    body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, W = T // Q, H * P
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    vmem = lambda *shape: pltpu.VMEM(shape, f32)              # noqa: E731
    order = ("parallel", "arbitrary")

    def specs(at):
        """The chunk `at(i)`'s blocks of X, Out or their gradients; of B or
        C; of Dt; of the kept states."""
        return (pl.BlockSpec((None, Q, W), lambda b, i: (b, at(i), 0)),
                pl.BlockSpec((None, Q, G * N), lambda b, i: (b, at(i), 0)),
                pl.BlockSpec((None, Q, H), lambda b, i: (b, at(i), 0)),
                pl.BlockSpec((None, None, W, N),
                             lambda b, i: (b, at(i), 0, 0)))

    par = pl.BlockSpec((8, H), lambda b, i: (0, 0))
    # the state or dS; c and Delta [Q, H] and turned [2 H, Q]; C B^T a
    # group; the triangle
    shared = [vmem(W, N), vmem(Q, H), vmem(Q, H), vmem(2 * H, Q),
              vmem(G, Q, Q), vmem(Q, Q)]
    wide, bc, dt, kept = specs(lambda i: i)
    forward = jax.jit(pl.pallas_call(
        functools.partial(_fwd_body, P=P, G=G),
        grid=(B, n),
        in_specs=[wide, bc, bc, dt, par],
        out_specs=[wide, kept],
        out_shape=[sds((B, T, W), dtype), sds((B, n, W, N), f32)],
        scratch_shapes=shared,
        compiler_params=pltpu.CompilerParams(dimension_semantics=order),
        name=FWD, interpret=interpret))

    # the reverse pass walks the chunks from the last to the first; the
    # parameters' partial sums stay in VMEM while a sequence's chunks run
    rwide, rbc, rdt, rkept = specs(lambda i: n - 1 - i)
    per_batch = lambda rows, cols: pl.BlockSpec(              # noqa: E731
        (None, rows, cols), lambda b, i: (b, 0, 0))
    backward = jax.jit(pl.pallas_call(
        functools.partial(_bwd_body, P=P, G=G),
        grid=(B, n),
        in_specs=[rwide, rbc, rbc, rdt, par, rwide, rkept],
        out_specs=[rwide, rbc, rbc, rdt, per_batch(8, H), per_batch(1, W)],
        out_shape=[sds((B, T, W), dtype), sds((B, T, G * N), dtype),
                   sds((B, T, G * N), dtype), sds((B, T, H), dt_dtype),
                   sds((B, 8, H), f32), sds((B, 1, W), f32)],
        # beside the shared: the summed W tile a group; dB and dC's state
        # parts; what reaches c and Delta as columns, and as rows
        scratch_shapes=shared + [vmem(G, Q, Q), vmem(2, Q, G * N),
                                 vmem(2, Q, H), vmem(2 * H, Q)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order, vmem_limit_bytes=BWD_VMEM_LIMIT),
        name=BWD, interpret=interpret))
    return forward, backward


def _prepared(x, b, c, dt, a_log, d, bias, heads, groups, chunk, interpret):
    """The two calls and their operands: X, B, C and Dt as they are, the
    heads' parameters as rows of one [8, H] float32 tile."""
    import jax.numpy as jnp

    B, T, W = x.shape
    H, G = int(heads), int(groups)
    P, N = W // H, b.shape[2] // G
    if (not usable(T, chunk, H, P, N, G, x.dtype) or W != H * P
            or b.shape != (B, T, G * N) or c.shape != b.shape
            or b.dtype != x.dtype or c.dtype != x.dtype
            or dt.shape != (B, T, H)):
        raise ValueError(
            f"ssd scan kernels: X {x.shape} {x.dtype}, B {b.shape} {b.dtype}, "
            f"C {c.shape} {c.dtype}, Dt {dt.shape} at {H} heads in {G} "
            f"groups, chunks of {chunk}")
    f32 = jnp.float32
    par = jnp.concatenate(
        [jnp.stack([a_log.astype(f32), d.astype(f32), bias.astype(f32)]),
         jnp.zeros((5, H), f32)])
    calls = _calls(B, T, H, P, N, G, chunk, str(x.dtype), str(dt.dtype),
                   interpret)
    return calls, (x, b, c, dt, par)


def ssd_fwd(x, b, c, dt, a_log, d, bias, *, heads, groups=1, chunk=CHUNK,
            interpret=False):
    """X [B, T, H P], B, C [B, T, G N], Dt [B, T, H], ALog, D, DtBias [H] ->
    (Out [B, T, H P] in X's dtype: the scan's result with the D term; every
    chunk's incoming state [B, T / chunk, H P, N] float32: what `ssd_bwd`
    takes)."""
    (fwd, _), operands = _prepared(x, b, c, dt, a_log, d, bias, heads,
                                   groups, chunk, interpret)
    return tuple(fwd(*operands))


def ssd_bwd(do, x, b, c, dt, a_log, d, bias, states, *, heads, groups=1,
            chunk=CHUNK, interpret=False):
    """dOut [B, T, H P], the forward's operands and the states it kept ->
    (dX, dB, dC, dDt, dALog, dD, dDtBias) in their operands' dtypes."""
    (_, bwd), operands = _prepared(x, b, c, dt, a_log, d, bias, heads,
                                   groups, chunk, interpret)
    dx, db, dc, ddt, dpar, dskip = bwd(*operands, do.astype(x.dtype), states)
    dpar = dpar.sum(axis=0)
    return (dx, db, dc, ddt, dpar[0].astype(a_log.dtype),
            dskip.reshape(-1, int(heads), x.shape[2] // int(heads)).sum(
                axis=(0, 2)).astype(d.dtype),
            dpar[2].astype(bias.dtype))


@functools.lru_cache(maxsize=None)
def make_ssd_scan(heads: int, groups: int = 1, chunk: int = CHUNK,
                  interpret: bool = False):
    """The scan (X, B, C, Dt, ALog, D, DtBias) -> Out as a `kernel_pair`
    (_common.py: the differentiable pair, `.keeping -> (Out, states)`,
    `.from_saved(..., Out, states)`), memoized so that every trace meets
    the same function.  ONE forward launch, which always writes the chunks'
    states (kept or not, differentiated or not: one forward body a process
    to trace and lower), and the reverse pass over them."""
    from ._common import kernel_pair

    how = dict(heads=heads, groups=groups, chunk=chunk, interpret=interpret)
    return kernel_pair(
        7, lambda *ops: ssd_fwd(*ops, **how)[0],
        lambda *ops, keep: ssd_fwd(*ops, **how),
        lambda ops, do, kept: ssd_bwd(do, *ops, kept[1], **how))
