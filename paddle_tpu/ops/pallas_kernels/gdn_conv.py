"""The gated DeltaNet's convolution in one pass over HBM, and one pass back.

Between a gated-DeltaNet mixer's input projection and its scan stands the
`gdn.conv` part of the op `gated_delta_rule` (ops/sparse_linear_ops.py has
the equations): X [B, T, 2 Hk Dk + 2 Hv Dv] = [q | k | v | z] is the
projection's result; over the first `mixed` = 2 Hk Dk + Hv Dv columns, per
channel, pre = the L causal taps, a = SiLU(pre); a q or k head's Dk lanes
are l2-normed (y = a rsqrt(sum a^2 + eps), q's times Dk^-1/2), v is a; the
three leave head-major, q, k [B, Hk, T, Dk] and v [B, Hk, G, T, Dv].  As
plain jax.numpy XLA widens the whole of X to float32 in the projection's
epilogue and hands float32 gradients of that size back (PERF.md, PR 51:
39.7 ms a step of `qwen3next_train_t8192` against 2.95 at the HBM roof).
The two kernels here take X where the projection wrote it, in its own
dtype, widen in VMEM and round once at each output:

  gdn_conv_fwd  X, Conv -> q, k, v in X's dtype (one [T, mixed] tensor in,
                one out; the heads' split is the out specs' index maps)
  gdn_conv_bwd  X, Conv, dq, dk, dv, dz -> dX [B, T, width] in X's dtype
                and the taps' gradient as float32 partials [B, 8 L, mixed]
                (tap j in rows 8j..8j+7) that the caller sums.  Nothing of
                the forward is kept: pre, SiLU and the norms are made again
                for the tile.  dz, the gradient of X's last Hv Dv columns
                (the output gate's, another part's), is copied into its
                place tile by tile, so the kernel writes ALL of dX and XLA
                neither pads nor adds a [T, width] tensor.

Backward, per channel, with s = sigmoid(pre):  da = dy for v; for a normed
head with r = rsqrt(sum a^2 + eps), da = r dy - a r^3 sum(dy a) (q's dy
times Dk^-1/2 first);  dpre = da s (1 + pre (1 - s));  dX_t = sum_j Conv[:,
j] dpre_{t + (L - 1) - j} (the taps run the other way: no future after the
sequence's end);  dConv[:, j] = sum_t dpre_t X_{t - (L - 1) + j}.

**Shape of a body.**  short_conv.py's: a grid step is a tile of whole rows
(X's `mixed` columns as one block of the [B, T, width] operand, no slice
copy), the L - 1 neighbour rows come as blocks of ROWS rows clamped at the
sequence's ends and zeroed there, and inside a loop over column chunks of
whole heads and, in it, one over chunks of ROWS rows that carries the
neighbour chunk (forward: X's; backward, walking upwards: dpre's): a shift
is one select and one sublane roll (`_down`, `_up`).  New here: a head's
lane sums, and the backward's rows AFTER the tile need their own dpre, so
that halo is X's and the three gradients' next ROWS rows and the
convolution is made on them too.

**Probed on the chip** (my chip runs, PR 51; TPU v5 lite; ms a call alone,
forward / backward, at the cell's shape, X [1, 8192, 12288] bf16 of which
8192 columns are mixed, L 4, where the least by bytes is 0.328 / 0.655 (268
and 537 MB: dz passes through the backward) and XLA's plain emission reads
4.50 forward, 12.91 forward + backward).  Rows a grid step x lanes a column
chunk, one 16-row chunk a loop step: 256 x 256 1.52 / 1.96, 256 x 128 2.78
/ 3.45, 128 x 256 1.52 / 2.04, 512 x 256 1.52 / 1.95, 128 x 128 2.78 /
3.63: the time goes by LOOP STEPS, not by bytes or elements.  A step is one
chain of latencies (the taps, exp, the reciprocal, a lane sum, rsqrt) and
Mosaic overlaps nothing of it with the next step's, nor unrolls a loop
partly (`fori_loop(unroll=4)` is refused: whole or not at all), so `_trips`
writes several chunks into one step: 256 x 256 at 2 / 4 / 8 / **16** chunks a
step 0.99 / 1.41, 0.71 / 1.17, 0.62 / 1.05, **0.54 / 0.94** (kept: the
whole tile, 61% / 70% of HBM's peak); 256 x 512 at 1 / 2 / 4 / 8 / 16: 0.96
/ 1.38, 0.71 / 1.14, 0.58 / 1.03, 0.54 / 1.00, 0.53 / 0.94; 256 x 1024 at 1
/ 2: 0.70 / 1.25, 0.60 / 1.10; 512 x 256 at 16 / 32: 0.54 / 0.93, 0.54 /
0.94; 512 x 512 at 8: 0.54 / 1.01; 128 x 256 at 8: 0.57 / 0.96; 256 x 128
at 16: 0.58 / 0.95.  Flat at 0.53 / 0.93 once a step holds sixteen
chunk-columns: bound by the vector units' work an element (about 20
operations forward, 50 backward), no longer by latency and not yet by HBM.
**What a call's time is made of** (same runs; the kept form, 0.530 / 0.936,
with one thing changed): the lane sums as a product with a ones matrix on
the idle MXU LOSE, at HIGHEST 0.73 / 1.33, as three bf16 pieces against
exact ones 0.67 / 1.19; the sigmoid as 0.5 (tanh(x / 2) + 1) 0.53 / 0.91
with 0.17% of the bf16 results another number, through `pl.reciprocal(approx
=True)` 0.50 / 0.92 with 0.1% another: not taken (`delta_out` is held to 3%).
Ablations, wrong results on purpose: no sigmoid 0.52 / 0.91, no lane sums
0.57 / 0.93, no rolls or selects 0.48 / 0.91, none of the three 0.47 / 0.87.
So the transcendentals, the sums and the shifts together are a tenth of a
call; the rest is what any body pays here: the loads, the bf16 <-> float32
converts, the taps' multiply-adds and the stores, in step with the blocks'
DMA.  Do not try those again.
"""

from __future__ import annotations

import functools

from .short_conv import (BLOCK_BUDGET, LANES, MAX_TAPS, ROW_TILE, ROWS,
                         VMEM_LIMIT, _down, _up, _wide)

FWD, BWD = "gdn_conv_fwd", "gdn_conv_bwd"
COLS = 256         # most lanes a column chunk (whole heads)
UNROLL = 16        # row chunks a step of the inner loop (a whole tile)


def _widths(Hk: int, Hv: int, Dk: int, Dv: int):
    """(mixed, width) of X: the convolved columns [q | k | v], and those
    with z."""
    mixed = 2 * Hk * Dk + Hv * Dv
    return mixed, mixed + Hv * Dv


def row_tile(T, Hk, Hv, Dk, Dv, itemsize, tile: int = ROW_TILE) -> int:
    """Rows a grid step: `tile` halved until it divides T and the
    backward's blocks (X's mixed columns, dq, dk, dv, dz in, dX out),
    double-buffered, fit BLOCK_BUDGET; 0 where no whole chunks do."""
    mixed, width = _widths(Hk, Hv, Dk, Dv)
    row = (mixed + 2 * width) * itemsize
    while tile >= ROWS:
        if T % tile == 0 and 2 * tile * row <= BLOCK_BUDGET:
            return tile
        tile //= 2
    return 0


def usable(T, Hk, Hv, Dk, Dv, L, dtype) -> bool:
    """The kernels take X [B, T, 2 Hk Dk + 2 Hv Dv] under L taps: bf16 or
    float32, heads of whole 128-lane blocks, value heads a multiple of the
    key heads, T in whole row tiles, a shift inside the neighbour chunk."""
    size = {"bfloat16": 2, "float32": 4}.get(str(dtype))
    if (not size or min(Hk, Hv, Dk, Dv) < 1 or Hv % Hk or Dk % LANES
            or Dv % LANES or not 1 <= L <= MAX_TAPS):
        return False
    return bool(row_tile(T, Hk, Hv, Dk, Dv, size))


def _sections(Hk, Hv, Dk, Dv, cols):
    """The three column ranges of the mixed columns, in the order of the
    head-major tensors: (first column, heads, a head's lanes, heads a
    column chunk, the norm's scale or None where nothing is normed)."""
    def per(heads, D):
        return max(n for n in range(1, heads + 1)
                   if heads % n == 0 and (n == 1 or n * D <= cols))

    return ((0, Hk, Dk, per(Hk, Dk), Dk ** -0.5),
            (Hk * Dk, Hk, Dk, per(Hk, Dk), 1.0),
            (2 * Hk * Dk, Hv, Dv, per(Hv, Dv), None))


def _lanes(a, h, D):
    return a[:, h * D:(h + 1) * D]


def _chunks(heads, D, per, first, body):
    """body(c, cols) for every column chunk of `per` heads: c the chunk's
    number, cols its lanes among the mixed columns."""
    from jax import lax
    from jax.experimental import pallas as pl

    def step(c, carry):
        body(c, pl.ds(pl.multiple_of(first + c * (per * D), LANES), per * D))
        return carry

    lax.fori_loop(0, heads // per, step, None)


def _head_sum(y):
    """A head's sum over its lanes, a column [rows, 1]."""
    import jax.numpy as jnp

    return jnp.sum(y, axis=-1, keepdims=True)


def _trips(n, unroll, body, carry):
    """carry = body(i, carry) for i in 0 .. n - 1, `unroll` of them a loop
    step where that divides n (Mosaic unrolls a loop whole or not at all;
    a step of several independent chunks hides their latencies)."""
    import math

    from jax import lax

    unroll = math.gcd(n, unroll)

    def step(i, carry):
        for u in range(unroll):
            carry = body(i * unroll + u, carry)
        return carry

    return lax.fori_loop(0, n // unroll, step, carry)


def _pre_silu(x, before, w, taps):
    """(X's rows shifted by 0 .. L - 1 tokens, pre, sigmoid(pre)) of a
    chunk whose neighbour chunk `before` holds the rows above it; w [L,
    lanes] the taps."""
    import jax

    xs = [x] + [_down(x, before, s) for s in range(1, taps)]
    pre = w[taps - 1:taps] * x
    for s in range(1, taps):             # the tap s tokens ago
        pre = pre + w[taps - 1 - s:taps - s] * xs[s]
    return xs, pre, jax.nn.sigmoid(pre)


# A chunk's arithmetic on VALUES (float32 [ROWS, lanes] but the gradients,
# which come as their refs hold them), one function a kernel, traced once a
# section and inlined at each of a loop step's chunks (`_shared`): sixteen
# copies of the backward's body traced one by one cost every process's
# set-up 2.4 s of host time on the chip machine (PERF.md, PR 51).


def _fwd_chunk(x, before, w, *, taps, eps, D, per, scale):
    """-> a head's rows each: SiLU of the taps, l2-normed where `scale` is
    one."""
    from jax import lax

    _, pre, sig = _pre_silu(x, before, w, taps)
    a = pre * sig
    heads = []
    for h in range(per):
        y = _lanes(a, h, D)
        if scale is not None:
            y = y * lax.rsqrt(_head_sum(y * y) + eps)
            if scale != 1.0:
                y = y * scale
        heads.append(y)
    return tuple(heads)


def _dpre_chunk(x, before, dys, w, *, taps, eps, D, per, scale):
    """(dpre of a chunk of rows, X's shifted rows): pre, SiLU and the norm
    made again, then their backward; `dys` a head's cotangent rows each."""
    import jax.numpy as jnp
    from jax import lax

    xs, pre, sig = _pre_silu(x, before, w, taps)
    a = pre * sig
    parts = []
    for h, dy in enumerate(dys):
        dy = dy.astype(jnp.float32)
        if scale is not None:
            y = _lanes(a, h, D)
            r = lax.rsqrt(_head_sum(y * y) + eps)
            if scale != 1.0:
                dy = dy * scale
            dy = r * dy - y * (r * r * r * _head_sum(dy * y))
        parts.append(dy)
    da = parts[0] if per == 1 else jnp.concatenate(parts, axis=1)
    return da * (sig * (1.0 + pre * (1.0 - sig))), tuple(xs)


def _bwd_chunk(x, before, dys, after, sums, w, **how):
    """One chunk of rows, `after` the dpre of the chunk below it -> (this
    chunk's dpre, its dX rows, the taps' partial sums with it: `sums[s]`
    meets tap L - 1 - s)."""
    taps = how["taps"]
    dpre, xs = _dpre_chunk(x, before, dys, w, **how)
    dx = w[taps - 1:taps] * dpre
    for s in range(1, taps):
        dx = dx + w[taps - 1 - s:taps - s] * _up(dpre, after, s)
    # [ROWS, lanes] -> [8, lanes] by adds of whole vregs; XLA sums the
    # eight sublanes with the tiles
    return dpre, dx, tuple(
        a + (dpre * shifted).reshape(-1, 8, dpre.shape[1]).sum(axis=0)
        for a, shifted in zip(sums, xs))


_STATIC = ("taps", "eps", "D", "per", "scale")


def _fwd_body(x_ref, hx_ref, w_ref, q_ref, k_ref, v_ref, *, taps, eps,
              sections, unroll):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .flash_attention import _shared

    tile = x_ref.shape[0]
    starts = pl.program_id(1) == 0       # no history before the sequence
    math = _shared(_fwd_chunk, *_STATIC)

    for (first, heads, D, per, scale), o_ref in zip(
            sections, (q_ref, k_ref, v_ref)):
        how = dict(taps=taps, eps=eps, D=D, per=per, scale=scale)

        def column(c, cols, how=how, o_ref=o_ref):
            w = w_ref[:, cols]

            def chunk(r, before):
                rows = pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)
                x = _wide(x_ref, rows, cols)
                for h, y in enumerate(math(x, before, w, **how)):
                    o_ref[c * how["per"] + h, rows, :] = y.astype(o_ref.dtype)
                return x

            halo = _wide(hx_ref, slice(None), cols)
            _trips(tile // ROWS, unroll, chunk, jnp.where(starts, 0.0, halo))

        _chunks(heads, D, per, first, column)


def _bwd_body(x_ref, dq_ref, dk_ref, dv_ref, dz_ref, hb_ref, ha_ref,
              hdq_ref, hdk_ref, hdv_ref, w_ref, dx_ref, dw_ref, *, taps,
              eps, sections, unroll):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .flash_attention import _shared

    tile, mixed = x_ref.shape
    n = tile // ROWS
    starts = pl.program_id(1) == 0
    ends = pl.program_id(1) == pl.num_programs(1) - 1   # no future after
    math, halo_math = (_shared(fn, *_STATIC)
                       for fn in (_bwd_chunk, _dpre_chunk))

    @pl.when(starts)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dx_ref[:, mixed:] = dz_ref[...]      # the output gate's, into its place

    for (first, heads, D, per, scale), dy_ref, hdy_ref in zip(
            sections, (dq_ref, dk_ref, dv_ref), (hdq_ref, hdk_ref, hdv_ref)):
        how = dict(taps=taps, eps=eps, D=D, per=per, scale=scale)

        def column(c, cols, how=how, dy_ref=dy_ref, hdy_ref=hdy_ref):
            per = how["per"]
            w = w_ref[:, cols]
            every = slice(None)
            halo = jnp.where(starts, 0.0, _wide(hb_ref, every, cols))

            def upwards(k, carry):       # the chunks from the last up
                after, sums = carry
                r = n - 1 - k
                r0 = pl.multiple_of(r * ROWS, ROWS)
                rows = pl.ds(r0, ROWS)
                above = pl.ds(pl.multiple_of(jnp.maximum(r0 - ROWS, 0), ROWS),
                              ROWS)
                dpre, dx, sums = math(
                    _wide(x_ref, rows, cols),
                    jnp.where(r == 0, halo, _wide(x_ref, above, cols)),
                    tuple(dy_ref[c * per + h, rows, :] for h in range(per)),
                    after, sums, w, **how)
                dx_ref[rows, cols] = dx.astype(dx_ref.dtype)
                return dpre, sums

            # the rows after the tile need their own dpre: the convolution
            # on the halo, whose neighbour above is the tile's last chunk
            after, _ = halo_math(
                _wide(ha_ref, every, cols),
                _wide(x_ref, pl.ds(tile - ROWS, ROWS), cols),
                tuple(hdy_ref[c * per + h] for h in range(per)), w, **how)
            _, sums = _trips(n, unroll, upwards, (
                jnp.where(ends, 0.0, after),
                (jnp.zeros((8, per * how["D"]), jnp.float32),) * taps))
            for s, part in enumerate(sums):
                dw_ref[pl.ds(8 * (taps - 1 - s), 8), cols] += part

        _chunks(heads, D, per, first, column)


@functools.lru_cache(maxsize=None)
def _calls(B, T, Hk, Hv, Dk, Dv, taps, eps, dtype, interpret, tile, cols,
           unroll):
    """(forward, backward) calls on X [B, T, width]; memoized and jitted,
    so every layer of a model shares one trace of each body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = row_tile(T, Hk, Hv, Dk, Dv, jnp.dtype(dtype).itemsize, tile)
    if not tile:
        raise ValueError(f"gdn_conv: no row tile for T {T} at {Hk} key and "
                         f"{Hv} value heads of {Dk} and {Dv}")
    mixed, width = _widths(Hk, Hv, Dk, Dv)
    per, blocks = tile // ROWS, T // ROWS
    kw = dict(taps=taps, eps=eps, sections=_sections(Hk, Hv, Dk, Dv, cols),
              unroll=unroll)

    def edge(i, after):
        """The block of ROWS rows next to tile i, clamped at the ends."""
        return jnp.clip((i + 1) * per if after else i * per - 1, 0,
                        blocks - 1)

    def halo(after):                     # of X's mixed columns
        return pl.BlockSpec((None, ROWS, mixed),
                            lambda b, i: (b, edge(i, after), 0))

    def heads(H, D, rows=None):
        """A tile's rows of every head of [B, H, T, D]; `rows` ROWS: the
        block after the tile."""
        if rows is None:
            return pl.BlockSpec((None, H, tile, D), lambda b, i: (b, 0, i, 0))
        return pl.BlockSpec((None, H, ROWS, D),
                            lambda b, i: (b, 0, edge(i, True), 0))

    x_rows = pl.BlockSpec((None, tile, mixed), lambda b, i: (b, i, 0))
    filt = pl.BlockSpec((taps, mixed), lambda b, i: (0, 0))
    sds = jax.ShapeDtypeStruct
    fwd = pl.pallas_call(
        functools.partial(_fwd_body, **kw),
        grid=(B, T // tile),
        in_specs=[x_rows, halo(False), filt],
        out_specs=[heads(Hk, Dk), heads(Hk, Dk), heads(Hv, Dv)],
        out_shape=[sds((B, Hk, T, Dk), dtype), sds((B, Hk, T, Dk), dtype),
                   sds((B, Hv, T, Dv), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=FWD, interpret=interpret)
    bwd = pl.pallas_call(
        functools.partial(_bwd_body, **kw),
        grid=(B, T // tile),
        in_specs=[x_rows, heads(Hk, Dk), heads(Hk, Dk), heads(Hv, Dv),
                  pl.BlockSpec((None, tile, width - mixed),
                               lambda b, i: (b, i, 0)),
                  halo(False), halo(True), heads(Hk, Dk, ROWS),
                  heads(Hk, Dk, ROWS), heads(Hv, Dv, ROWS), filt],
        # the taps' partial sums stay in VMEM across a sequence's tiles
        out_specs=[pl.BlockSpec((None, tile, width), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((None, 8 * taps, mixed),
                                lambda b, i: (b, 0, 0))],
        out_shape=[sds((B, T, width), dtype),
                   sds((B, 8 * taps, mixed), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=BWD, interpret=interpret)
    return jax.jit(fwd), jax.jit(bwd)


def _prepared(x, w, Hk, Hv, Dk, eps, interpret, tile, cols, unroll):
    """((forward, backward) calls for X, the taps as float32 [L, mixed],
    Dv)."""
    import jax.numpy as jnp

    B, T, width = x.shape
    Dv = (width - 2 * Hk * Dk) // (2 * Hv)
    taps = w.shape[1]
    if w.shape[0] != _widths(Hk, Hv, Dk, Dv)[0]:
        raise ValueError(f"gdn_conv: X {x.shape}, Conv {w.shape} at {Hk} "
                         f"key heads of {Dk} and {Hv} value heads")
    return (_calls(B, T, Hk, Hv, Dk, Dv, taps, float(eps), str(x.dtype),
                   interpret, tile, cols, unroll),
            jnp.transpose(w).astype(jnp.float32), Dv)


def gdn_conv_fwd(x, w, Hk, Hv, Dk, eps, *, interpret=False, tile=ROW_TILE,
                 cols=COLS, unroll=UNROLL):
    """X [B, T, 2 Hk Dk + 2 Hv Dv], Conv [mixed, L] -> (q, k [B, Hk, T,
    Dk], v [B, Hk, Hv / Hk, T, Dv]) in X's dtype (module docstring)."""
    (fwd, _), wt, Dv = _prepared(x, w, Hk, Hv, Dk, eps, interpret, tile,
                                 cols, unroll)
    q, k, v = fwd(x, x, wt)
    return q, k, v.reshape(x.shape[0], Hk, Hv // Hk, x.shape[1], Dv)


def gdn_conv_bwd(dq, dk, dv, dz, x, w, Hk, Hv, Dk, eps, *, interpret=False,
                 tile=ROW_TILE, cols=COLS, unroll=UNROLL):
    """The cotangents of `gdn_conv_fwd`'s results, dz [B, T, Hv Dv] (X's
    last columns'), X, Conv -> (dX like X, dConv float32 [mixed, L])."""
    (_, bwd), wt, Dv = _prepared(x, w, Hk, Hv, Dk, eps, interpret, tile,
                                 cols, unroll)
    B, T, _ = x.shape
    dq, dk, dv, dz = (a.astype(x.dtype) for a in (dq, dk, dv, dz))
    dv = dv.reshape(B, Hv, T, Dv)
    dx, parts = bwd(x, dq, dk, dv, dz, x, x, dq, dk, dv, wt)
    mixed, taps = w.shape
    return dx, parts.reshape(-1, taps, 8, mixed).sum(axis=(0, 2)).T


@functools.lru_cache(maxsize=None)
def make_gdn_conv(Hk: int, Hv: int, Dk: int, eps: float,
                  interpret: bool = False):
    """The part as a `jax.custom_vjp` (X, Conv) -> (q, k, v, z), z = X's
    last Hv Dv columns as they lie (a slice XLA folds into its reader),
    handed out so that its cotangent comes back to the backward kernel,
    which writes it into dX.  `.from_saved(X, Conv, q, k, v)` launches
    nothing forward and differentiates as the backward kernel alone: what a
    forward op and its grad op's re-emission split between them
    (`ctx.keep_for_grad`).  Called OUTSIDE any part's scope: the kernels
    open `pdtpu.gdn.conv` themselves, forward and backward, and z's slice
    the part that reads it, `pdtpu.gdn.norm_gate`, or every fusion z ends
    in would count as the convolution's.  Memoized, so every trace meets
    one function."""
    import jax

    from ...observability.attribution import part_scope

    # the interpreter gains nothing from a longer loop step, and compiles
    # its chunks as many times over
    how = dict(interpret=interpret, unroll=1 if interpret else UNROLL)

    def gate_input(x, w):
        with part_scope("gdn.norm_gate"):
            return x[..., w.shape[0]:]

    def forward(x, w):
        with part_scope("gdn.conv"):
            q, k, v = gdn_conv_fwd(x, w, Hk, Hv, Dk, eps, **how)
        return q, k, v, gate_input(x, w)

    def backward(res, cts):
        x, w = res
        with part_scope("gdn.conv"):
            dx, dw = gdn_conv_bwd(*cts, x, w, Hk, Hv, Dk, eps, **how)
            return dx, dw.astype(w.dtype)

    conv = jax.custom_vjp(forward)
    conv.defvjp(lambda x, w: (forward(x, w), (x, w)), backward)

    def saved(x, w, q, k, v):
        return q, k, v, gate_input(x, w)

    from_saved = jax.custom_vjp(saved)
    from_saved.defvjp(
        lambda *ops: (saved(*ops), ops[:2]),
        lambda res, cts: backward(res, cts) + (None, None, None))
    conv.from_saved = from_saved
    return conv
