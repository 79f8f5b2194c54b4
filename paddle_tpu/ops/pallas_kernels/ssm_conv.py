"""The Mamba mixers' short convolution in one pass over HBM, and one pass
back.

Between a Mamba mixer's input projection and its scan stands the op
`causal_conv_silu` (ops/ssm_ops.py has the equations): X [B, T, W] is the
projection's result ([u' | z] of a Mamba-1 mixer, [z | xBC | dt] of a
Mamba-2 one); over its C columns from `offset` on, per channel, pre = Bias +
the L causal taps (the LAST tap on the current token, zeros before the
sequence), Out = SiLU(pre) = pre sigmoid(pre).  As plain jax.numpy
(`llm_ops.causal_taps`) XLA runs the tap loop over the slice in float32 with
a pad a tap, and writes the x, B and C column ranges a Mamba-2 scan reads as
copies of their own (PERF.md, PR 70: 41.9 + 11.9 ms a step of
`granite4h_train_t8192` against 5.5 at the HBM roof).  The two kernels here
take X where the projection wrote it, in its own dtype, widen in VMEM and
round once at each output:

  ssm_conv_fwd  X, Filter, Bias -> Out in X's dtype: one [B, T, width] tensor
                a section (`sections`: widths that sum to C, each whole lane
                tiles; Mamba-2's x, B and C, which the scan's kernels read
                as they lie), row-major, one out spec each.  X's C columns
                are a WINDOW of the operand (`pl.Element`: rows and lanes
                counted in elements, so `offset` need not be a multiple of
                C): no slice copy.
  ssm_conv_bwd  X, Filter, Bias, one cotangent a section -> dX's C columns
                [B, T, C] in X's dtype and the taps' and the bias's gradient
                as float32 partials [B, 8 (L + 1), C] (tap j in rows 8j ..
                8j + 7, the bias in the last eight) that the caller sums.
                Nothing of the forward is kept: pre and the sigmoid are
                made again for the tile.

Backward, per channel, with s = sigmoid(pre):  dpre = dOut s (1 + pre (1 -
s));  dX_t = sum_j Filter[:, j] dpre_{t + (L - 1) - j} (the taps run the
other way: no future after the sequence's end);  dFilter[:, j] = sum_t dpre_t
X_{t - (L - 1) + j};  dBias = sum_t dpre_t.  float32 inside, the sigmoid
`jax.nn.sigmoid`'s: the approximations gdn_conv.py lists as refused stay
refused.

**Shape of a body.**  short_conv.py's, with its geometry and shifts
(imported, not edited): a grid step is a tile of whole rows of X's C
columns, the L - 1 neighbour rows come as windows of ROWS rows clamped at
the sequence's ends and zeroed there, and inside, for each section, a loop
over its column chunks and, in it, one over chunks of ROWS rows that carries
the neighbour chunk (forward: X's; backward, walking upwards: dpre's): a
shift is one select and one sublane roll (`_down`, `_up`).  As in
gdn_conv.py, the backward's rows AFTER the tile need their own dpre, so that
halo is X's and each cotangent's next ROWS rows and the convolution is made
on them too; a chunk's arithmetic is traced once a shape and inlined
(`_shared`), several chunks a loop step (`_trips`).

**Probed on the chip** (my chip runs, PR 72; TPU v5 lite; ms a call alone,
forward / backward, bf16, L 4, with a bias; rows a grid step x lanes a
column chunk x row chunks a loop step).  Phi-4-mini-flash's shape, X [1,
8192, 10240], offset 0, C 5120, one section, where the least by bytes is
0.205 / 0.307 and the plain lines read 1.48 forward, 4.76 forward +
backward: 256 x 256 x 16 0.313 / 0.536, 512 x 256 x 16 0.308 / 0.570, 256 x
256 x 8 0.311 / 0.598, 128 x 256 x 8 0.326 / 0.571, 256 x 512 x 4 0.327 /
0.599, 256 x 256 x 4 0.327 / 0.605, 128 x 512 x 4 0.329 / 0.625, 128 x 256 x
4 0.338 / 0.643: flat within 0.16 ms a layer (two forwards and a backward)
from four chunk-columns of 256 lanes a step up, as gdn_conv.py found.
Granite's shape, X [1, 8192, 8512], offset 4096, sections 4096 + 128 + 128
(C 4352; least 0.174 / 0.261; plain 1.65, 4.08): alone, the probe's jit took
an X of 66.5 lane tiles T-minor and copied it row-major in front of every
launch (0.699 / 0.976 at 256 x 256 x 16; the same eight tilings within 0.16
ms a layer of each other again), which the cell's step, whose W_in writes X
row-major, does not do; at a W of 8576 0.266 / 0.469 with one section and
0.484 / 0.507 with three.  IN the step of `granite4h_train_t8192` (traced,
18 + 9 launches a step): 256 x 256 x 16 **0.236 / 0.422** (74% / 62% of HBM's
peak; the step 357.9 ms, 2.7881 samples/s), 256 x 256 x 4 **0.259 / 0.480**
(358.9 ms, 2.7811).  **Kept: 256 x 256 x 4**, the shortest body within 0.25
ms a layer of the best (0.105): a body's length is paid in every process's
set-up, traced and lowered at its start whatever the compile cache holds
(PERF.md, PR 59): in the cell's set-up timeline `jax.trace` / `jax.lower`
read 5.42 / 2.48 s at the parent, 6.15 / 2.82 with sixteen chunks a step and
5.73 / 2.52 as kept, for 0.25% of the cell's samples/s.  The backward's
blocks at 256 rows pass the compiler's own 16 MB of VMEM by 3 to 5, and the
launch asks for what they need (`_vmem_limit`) and no more.  Not tried
again: what gdn_conv.py's docstring lists (the approximate sigmoids, the
MXU for sums, column blocks that hold the whole sequence).
"""

from __future__ import annotations

import functools

from .gdn_conv import _trips
from .short_conv import (BLOCK_BUDGET, LANES, MAX_TAPS, ROW_TILE, ROWS,
                         _chunk_lanes, _down, _up, _wide)

FWD, BWD = "ssm_conv_fwd", "ssm_conv_bwd"
COLS = 256         # most lanes a column chunk
UNROLL = 4         # row chunks a step of the inner loop
# What the compiler gives a kernel unasked, and what a body may need beside
# its double-buffered blocks: a launch asks for more than the first only
# where its blocks do not leave the second (PERF.md, PR 70: a limit of 64 MB
# on every launch slowed XLA's fusions around them).
VMEM_DEFAULT = 16 * 1024 * 1024
VMEM_SPARE = 3 * 1024 * 1024


def row_tile(T: int, C: int, itemsize: int, tile: int = ROW_TILE) -> int:
    """Rows a grid step: `tile` halved until it divides T and the backward's
    blocks (X's C columns and the cotangents in, dX out: 3 C a row),
    double-buffered, fit BLOCK_BUDGET; 0 where no whole chunks do."""
    while tile >= ROWS:
        if T % tile == 0 and 2 * 3 * tile * C * itemsize <= BLOCK_BUDGET:
            return tile
        tile //= 2
    return 0


def usable(T: int, W: int, offset: int, sections, L: int, dtype) -> bool:
    """The kernels take X [B, T, W] under L taps on the sum(sections)
    columns from `offset`: bf16 or float32, `offset` and every section whole
    128-lane blocks inside W, T in whole row tiles, a shift inside the
    neighbour chunk."""
    size = {"bfloat16": 2, "float32": 4}.get(str(dtype))
    if (not size or not sections or offset < 0 or offset % LANES
            or any(s < 1 or s % LANES for s in sections)
            or offset + sum(sections) > W or not 1 <= L <= MAX_TAPS):
        return False
    return bool(row_tile(T, sum(sections), size))


def _columns(widths, cols, body):
    """body(s, at, to) for every column chunk of every section: s the
    section's number, `at` the chunk's lanes among the C columns, `to` among
    the section's own."""
    from jax import lax
    from jax.experimental import pallas as pl

    first = 0
    for s, width in enumerate(widths):
        cw = _chunk_lanes(width, cols)

        def step(c, carry, s=s, first=first, cw=cw):
            body(s, pl.ds(pl.multiple_of(first + c * cw, LANES), cw),
                 pl.ds(pl.multiple_of(c * cw, LANES), cw))
            return carry

        lax.fori_loop(0, width // cw, step, None)
        first += width


# A chunk's arithmetic on VALUES (float32 [ROWS, lanes] but the cotangent,
# which comes as its ref holds it), traced once a shape and inlined at each
# of a loop step's chunks (`_shared`; gdn_conv.py has what that saves).


def _pre(x, before, w, b, taps):
    """(X's rows shifted by 0 .. L - 1 tokens, pre) of a chunk whose
    neighbour chunk `before` holds the rows above it; w [L, lanes] the taps,
    b [1, lanes] the bias or None: `llm_ops.causal_taps`' order of sums."""
    xs = [x] + [_down(x, before, s) for s in range(1, taps)]
    pre = w[taps - 1:taps] * x
    for s in range(1, taps):             # the tap s tokens ago
        pre = pre + w[taps - 1 - s:taps - s] * xs[s]
    return xs, pre if b is None else pre + b


def _fwd_chunk(x, before, w, b, *, taps):
    import jax

    _, pre = _pre(x, before, w, b, taps)
    return pre * jax.nn.sigmoid(pre)


def _dpre_chunk(x, before, dy, w, b, *, taps):
    """(dpre of a chunk of rows, X's shifted rows): pre and the sigmoid made
    again, then SiLU's backward."""
    import jax
    import jax.numpy as jnp

    xs, pre = _pre(x, before, w, b, taps)
    sig = jax.nn.sigmoid(pre)
    return dy.astype(jnp.float32) * (sig * (1.0 + pre * (1.0 - sig))), xs


def _bwd_chunk(x, before, dy, after, sums, w, b, *, taps):
    """One chunk of rows, `after` the dpre of the chunk below it -> (this
    chunk's dpre, its dX rows, the partial sums with it: `sums[s]` meets tap
    L - 1 - s, `sums[L]` the bias where there is one)."""
    dpre, xs = _dpre_chunk(x, before, dy, w, b, taps=taps)
    dx = w[taps - 1:taps] * dpre
    for s in range(1, taps):
        dx = dx + w[taps - 1 - s:taps - s] * _up(dpre, after, s)
    # [ROWS, lanes] -> [8, lanes] by adds of whole vregs; XLA sums the eight
    # sublanes with the tiles
    parts = [dpre * shifted for shifted in xs]
    if len(sums) > taps:                 # the bias's
        parts.append(dpre)
    return dpre, dx, tuple(a + p.reshape(-1, 8, p.shape[1]).sum(axis=0)
                           for a, p in zip(sums, parts))


def _fwd_body(*refs, taps, bias, cols, unroll):
    """refs: X's C columns [tile, C]; its ROWS rows before the tile; the taps
    [L, C]; the bias [1, C] where there is one; then Out, a section each
    [tile, width]."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .flash_attention import _shared

    x_ref, hx_ref, w_ref = refs[:3]
    b_ref = refs[3] if bias else None
    o_refs = refs[3 + bias:]
    tile = x_ref.shape[0]
    starts = pl.program_id(1) == 0       # no history before the sequence
    math = _shared(_fwd_chunk, "taps")

    def column(s, at, to):
        w = w_ref[:, at]
        b = b_ref[:, at] if bias else None
        o_ref = o_refs[s]

        def chunk(r, before):
            rows = pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)
            x = _wide(x_ref, rows, at)
            o_ref[rows, to] = math(x, before, w, b, taps=taps).astype(
                o_ref.dtype)
            return x

        _trips(tile // ROWS, unroll, chunk,
               jnp.where(starts, 0.0, _wide(hx_ref, slice(None), at)))

    _columns([o.shape[1] for o in o_refs], cols, column)


def _bwd_body(*refs, taps, bias, sections, cols, unroll):
    """refs: X's C columns [tile, C]; dOut, a section each [tile, width];
    X's ROWS rows before the tile; after it; each cotangent's after it; the
    taps; the bias where there is one; then dX [tile, C] and the partial
    sums [8 (L + bias), C]."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .flash_attention import _shared

    refs = iter(refs)
    take = lambda n: [next(refs) for _ in range(n)]               # noqa: E731
    (x_ref,), dy_refs, (hb_ref, ha_ref) = take(1), take(sections), take(2)
    hdy_refs, (w_ref,) = take(sections), take(1)
    b_ref = next(refs) if bias else None
    dx_ref, sums_ref = refs
    tile = x_ref.shape[0]
    n = tile // ROWS
    starts = pl.program_id(1) == 0
    ends = pl.program_id(1) == pl.num_programs(1) - 1   # no future after
    math, halo_math = (_shared(fn, "taps") for fn in (_bwd_chunk,
                                                      _dpre_chunk))

    @pl.when(starts)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def column(s, at, to):
        w = w_ref[:, at]
        b = b_ref[:, at] if bias else None
        dy_ref, hdy_ref = dy_refs[s], hdy_refs[s]
        every = slice(None)
        halo = jnp.where(starts, 0.0, _wide(hb_ref, every, at))

        def upwards(k, carry):           # the chunks from the last up
            after, sums = carry
            r = n - 1 - k
            r0 = pl.multiple_of(r * ROWS, ROWS)
            rows = pl.ds(r0, ROWS)
            above = pl.ds(pl.multiple_of(jnp.maximum(r0 - ROWS, 0), ROWS),
                          ROWS)
            dpre, dx, sums = math(
                _wide(x_ref, rows, at),
                jnp.where(r == 0, halo, _wide(x_ref, above, at)),
                dy_ref[rows, to], after, sums, w, b, taps=taps)
            dx_ref[rows, at] = dx.astype(dx_ref.dtype)
            return dpre, sums

        # the rows after the tile need their own dpre: the convolution on
        # the halo, whose neighbour above is the tile's last chunk
        after, _ = halo_math(
            _wide(ha_ref, every, at),
            _wide(x_ref, pl.ds(tile - ROWS, ROWS), at), hdy_ref[every, to],
            w, b, taps=taps)
        lanes = w.shape[1]
        _, sums = _trips(n, unroll, upwards, (
            jnp.where(ends, 0.0, after),
            (jnp.zeros((8, lanes), jnp.float32),) * (taps + bias)))
        for i, part in enumerate(sums):  # sums[i] is tap L - 1 - i's
            row = taps - 1 - i if i < taps else taps
            sums_ref[pl.ds(8 * row, 8), at] += part

    _columns([dy.shape[1] for dy in dy_refs], cols, column)


def _vmem_limit(blocks: int):
    """What a launch asks of VMEM for `blocks` bytes of blocks a grid step,
    double-buffered: nothing where the compiler's own share holds them with
    room to spare, else what they need."""
    need = 2 * blocks + VMEM_SPARE
    return None if need <= VMEM_DEFAULT else need


@functools.lru_cache(maxsize=None)
def _calls(B, T, W, offset, sections, taps, bias, dtype, interpret, tile,
           cols, unroll):
    """(forward, backward) calls on X [B, T, W]; memoized and jitted, so
    every layer of a model, and a forward op and its grad op's re-emission,
    share one trace of each body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, size = sum(sections), jnp.dtype(dtype).itemsize
    tile = row_tile(T, C, size, tile)
    if not tile:
        raise ValueError(f"ssm_conv: no row tile for T {T} at {C} channels")
    per, blocks = tile // ROWS, T // ROWS
    kw = dict(taps=taps, bias=bias, cols=cols, unroll=unroll)
    rows_of = taps + bias                # of the filter; x 8: of the sums

    def edge(i, after):
        """The block of ROWS rows next to tile i, clamped at the ends."""
        return jnp.clip((i + 1) * per if after else i * per - 1, 0,
                        blocks - 1)

    def window(rows, first):
        """`rows` rows from row `first(i)` on of X's C columns."""
        return pl.BlockSpec(
            (None, pl.Element(rows), pl.Element(C)),
            lambda b, i: (b, first(i), offset))

    x_rows = window(tile, lambda i: i * tile)
    before, after = (window(ROWS, lambda i, a=a: edge(i, a) * ROWS)
                     for a in (False, True))
    outs = [pl.BlockSpec((None, tile, s), lambda b, i: (b, i, 0))
            for s in sections]
    outs_after = [pl.BlockSpec((None, ROWS, s),
                               lambda b, i: (b, edge(i, True), 0))
                  for s in sections]
    filt = [pl.BlockSpec((taps, C), lambda b, i: (0, 0))] + [
        pl.BlockSpec((1, C), lambda b, i: (0, 0))] * bias
    sds = jax.ShapeDtypeStruct
    halo, wide = ROWS * C * size, rows_of * C * 4
    fwd = pl.pallas_call(
        functools.partial(_fwd_body, **kw),
        grid=(B, T // tile),
        in_specs=[x_rows, before] + filt, out_specs=outs,
        out_shape=[sds((B, T, s), dtype) for s in sections],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit(2 * tile * C * size + halo + wide)),
        name=FWD, interpret=interpret)
    bwd = pl.pallas_call(
        functools.partial(_bwd_body, sections=len(sections), **kw),
        grid=(B, T // tile),
        in_specs=[x_rows] + outs + [before, after] + outs_after + filt,
        # the partial sums stay in VMEM across a sequence's tiles
        out_specs=[pl.BlockSpec((None, tile, C), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((None, 8 * rows_of, C),
                                lambda b, i: (b, 0, 0))],
        out_shape=[sds((B, T, C), dtype),
                   sds((B, 8 * rows_of, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(3 * tile * C * size + 3 * halo
                                         + 9 * wide)),
        name=BWD, interpret=interpret)
    return jax.jit(fwd), jax.jit(bwd)


def _prepared(x, w, b, offset, sections, interpret, tile, cols, unroll):
    """((forward, backward) calls for X, the taps as float32 [L, C] and the
    bias as float32 [1, C] where there is one, the sections' widths)."""
    import jax.numpy as jnp

    B, T, W = x.shape
    C, taps = w.shape
    sections = tuple(int(s) for s in sections or (C,))
    if sum(sections) != C or (b is not None and b.shape != (C,)):
        raise ValueError(f"ssm_conv: X {x.shape}, Filter {w.shape}, Bias "
                         f"{None if b is None else b.shape} in sections "
                         f"{sections}")
    wide = [jnp.transpose(w).astype(jnp.float32)]
    if b is not None:
        wide.append(b.astype(jnp.float32).reshape(1, C))
    return (_calls(B, T, W, int(offset), sections, taps, b is not None,
                   str(x.dtype), interpret, tile, cols, unroll), wide)


def ssm_conv_fwd(x, w, b=None, offset=0, sections=None, *, interpret=False,
                 tile=ROW_TILE, cols=COLS, unroll=UNROLL):
    """X [B, T, W], Filter [C, L], Bias [C] or None -> Out, a tuple of one
    [B, T, width] a section in X's dtype (module docstring)."""
    (fwd, _), wide = _prepared(x, w, b, offset, sections, interpret, tile,
                               cols, unroll)
    return tuple(fwd(x, x, *wide))


def ssm_conv_bwd(douts, x, w, b=None, offset=0, sections=None, *,
                 interpret=False, tile=ROW_TILE, cols=COLS, unroll=UNROLL):
    """The cotangents of `ssm_conv_fwd`'s results (one a section), X, Filter,
    Bias -> (dX's C columns [B, T, C] in X's dtype, dFilter float32 [C, L],
    dBias float32 [C] or None)."""
    (_, bwd), wide = _prepared(x, w, b, offset, sections, interpret, tile,
                               cols, unroll)
    douts = [d.astype(x.dtype) for d in douts]
    dx, parts = bwd(x, *douts, x, x, *douts, *wide)
    C, taps = w.shape
    parts = parts.reshape(parts.shape[0], -1, 8, C).sum(axis=(0, 2))
    return dx, parts[:taps].T, None if b is None else parts[taps]


@functools.lru_cache(maxsize=None)
def make_ssm_conv(offset: int, sections, bias: bool, interpret: bool = False):
    """The op (X, Filter[, Bias]) -> Out (a tuple, one a section; `sections`
    a tuple of widths, or None for one of all C) as a `kernel_pair` (_common.py),
    memoized so that every trace meets the same function.  Nothing is kept:
    the backward needs the operands and the cotangents alone, so `.keeping`
    returns Out and no residual, and a grad op handed that launches the
    backward alone.  dX is the backward's C columns between zeros (a pad XLA
    fuses into the sum of the projection's cotangents).  Called INSIDE the
    op's own `part_scope`, which the backward's launch inherits."""
    import jax.numpy as jnp

    from ._common import kernel_pair

    # the interpreter gains nothing from a longer loop step, and compiles
    # its chunks as many times over
    how = dict(interpret=interpret, unroll=1 if interpret else UNROLL)

    def split(ops):
        return ops[0], ops[1], ops[2] if bias else None

    def bare(*ops):
        return ssm_conv_fwd(*split(ops), offset, sections, **how)

    def backward(ops, do, kept):
        x, w, b = split(ops)
        dx, dw, db = ssm_conv_bwd(do, x, w, b, offset, sections, **how)
        dx = jnp.pad(dx, ((0, 0), (0, 0),
                          (offset, x.shape[2] - offset - w.shape[0])))
        return (dx, dw.astype(w.dtype)) + (
            (db.astype(b.dtype),) if bias else ())

    return kernel_pair(2 + bias, bare,
                       lambda *ops, keep=False: (bare(*ops),), backward)
