"""Q (or K) from projection to attention in one pass, and one pass back.

Between a decoder's Q and K projections and its attention stand a split
into heads, on some models a per-head RMSNorm, and the rotary turn.  As
separate ops they cross HBM several times each way (PERF.md, PR 38: 18.3 ms
a step of `sdar_train_bd_t4096` against 2.8 at the HBM roof).  The two
kernels here do it in one pass each:

  head_norm_rope      X [B, T, H * D] as the projection leaves it ->
                      Out [B, H, T, D] as the flash kernels read it;
  head_norm_rope_bwd  dOut [B, H, T, D], X -> dX [B, T, H * D] and the
                      gain's gradient as float32 partials [.., 128] that
                      the caller sums.

Per head and row, all in float32 with ONE rounding at the end:

  y   = x * rsqrt(mean(x^2) + eps) * gain          (where `eps` is given)
  out = y * cos(t) + partner(y) * sin'(t)

`partner` swaps a head's two halves (rotate-half: a lane roll by D / 2) and
sin' is sin with the first half's sign folded in, so the turn is two
multiply-adds.  Backward, analytically: dy = dOut * cos - partner(dOut) *
sin' (the turn by the negative angle), dx = r * (u - xhat * mean(u * xhat))
with u = dy * gain, xhat = x * r, and the gain's gradient the column sum of
dy * xhat.  The backward needs X, the gain and dOut only: the row statistic
is recomputed, nothing of the forward is kept.

**Two addressings of one body** (the flash kernels', PR 36).  A grid step
is a tile of rows by `hb` 128-lane column blocks of [T, H * D], read
through the index map and written to the blocks (h, tile) of [H, T, D]: the
head split costs nothing.  A block is one head of 128 lanes, or TWO of 64
(`pack` 2): each half's row statistic by a masked sum, the partner a roll
by 32 inside each half chosen by a lane select, the two halves written as
two [tile, 64] blocks.  Heads are innermost in the grid, so a tile's cos
and sin' rows (two float32 [T, 128] tables a call, made in XLA: `tables`)
are fetched once a tile.

**A partial turn** of 64 columns in a head of 128 (`rotary_dim`; Laguna's
full-span layers, PR 63) is the same body: the partner inside the first 64
lanes is the roll by 32 that a packed head of 64 takes (`turn` 2, while the
row statistic and the blocks written stay the whole head's, `pack` 1), and
the tables carry cos = 1 and sin' = 0 over the last 64 lanes, where the
partner's value is multiplied away.  No other partial turn is taken
(`turn_of`): the plain emission runs those.
"""

from __future__ import annotations

import functools

FWD, BWD = "head_norm_rope", "head_norm_rope_bwd"
LANES = 128
ROW_TILE = 1024   # rows a grid step at two bytes an element (_row_tile)
HEAD_BLOCKS = 4   # column blocks a grid step, where that many divide


def pack_of(T: int, D: int, heads: int, dtype) -> int:
    """Heads a 128-lane block (1 or 2) where the kernels take the shape: D
    128, or D 64 at an even head count; T in tiles of 128; bf16 or
    float32.  0 where they do not."""
    if str(dtype) not in ("bfloat16", "float32") or T % 128:
        return 0
    if D == LANES:
        return 1
    return 2 if D == 64 and heads % 2 == 0 else 0


def turn_of(D: int, rotary_dim: int) -> int:
    """How the lanes of a 128-lane block pair up for the rotary turn of
    the first `rotary_dim` columns of a head of D (0: all of them): 1, a
    roll by 64 (a whole head of 128); 2, by 32 inside each half (a whole
    head of 64, two a block, or the first 64 columns of a head of 128); 0
    where the kernels do not take the turn."""
    R = int(rotary_dim) or D
    if R == D:
        return LANES // D if D in (64, LANES) else 0
    return 2 if (D, R) == (LANES, 64) else 0


def tables(T: int, D: int, theta: float, period: int = 0, dtype=None,
           lanes: int = 0, inv_freq=None, factor: float = 1.0):
    """(cos, sin') [T, lanes or D]: row r holds the angles of position r
    (r mod `period` where given), t * theta ** (-2i / D) in both halves of
    a head, sin' negative in the first half; with `lanes` a multiple of D,
    the head's columns side by side that often.  `inv_freq` [D / 2] (a
    trace-time constant: YaRN's blend, `llm_ops.yarn_inv_freq`) stands in
    for the row theta gives; `factor` multiplies cos and sin (YaRN's
    attention factor).  D is the width that TURNS: under a partial turn
    the caller hands in `rotary_dim` and leaves the other columns alone."""
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    half = D // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=dtype) / half)
    else:
        inv_freq = jnp.asarray(inv_freq, dtype)
    pos = jnp.arange(T)
    if period:
        pos = pos % period
    ang = pos.astype(dtype)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    reps = max(lanes // D, 1)
    return (jnp.tile(jnp.concatenate([cos, cos], axis=1), (1, reps)),
            jnp.tile(jnp.concatenate([-sin, sin], axis=1), (1, reps)))


def _lane(shape):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _partner(y, pack: int):
    """Each lane's rotate-half partner inside its head: the other half of
    a 128-lane head, or of each 64-lane head of two."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    if pack == 1:
        return pltpu.roll(y, LANES // 2, 1)
    first = (_lane(y.shape) % 64) < 32
    return jnp.where(first, pltpu.roll(y, LANES - 32, 1),
                     pltpu.roll(y, 32, 1))


def _head_mean(a, pack: int):
    """The mean of `a` [rows, 128] over each head's lanes, as [rows, 1]
    (one head) or spread back over the two heads' lanes [rows, 128]."""
    import jax.numpy as jnp

    if pack == 1:
        return jnp.mean(a, axis=1, keepdims=True)
    low = _lane(a.shape) < 64
    lo = jnp.sum(jnp.where(low, a, 0.0), axis=1, keepdims=True)
    hi = jnp.sum(jnp.where(low, 0.0, a), axis=1, keepdims=True)
    return jnp.where(low, lo, hi) * (1.0 / 64)


def _fwd_body(*refs, eps, pack, hb, gain, turn):
    import jax
    import jax.numpy as jnp

    x_ref, cos_ref, sin_ref = refs[:3]
    g = refs[3][...] if gain else None
    o_ref = refs[-1]
    cos, sin = cos_ref[...], sin_ref[...]
    for j in range(hb):
        y = x_ref[:, j * LANES:(j + 1) * LANES].astype(jnp.float32)
        if eps is not None:
            y = y * jax.lax.rsqrt(_head_mean(y * y, pack) + eps)
        if gain:
            y = y * g
        out = (y * cos + _partner(y, turn) * sin).astype(o_ref.dtype)
        if pack == 1:
            o_ref[j] = out
        else:
            o_ref[2 * j] = out[:, :64]
            o_ref[2 * j + 1] = out[:, 64:]


def _bwd_body(*refs, eps, pack, hb, gain, turn):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    do_ref, x_ref, cos_ref, sin_ref = refs[:4]
    g = refs[4][...] if gain else None
    dx_ref = refs[4 + bool(gain)]
    cos, sin = cos_ref[...], sin_ref[...]
    dg = None
    for j in range(hb):
        if pack == 1:
            do = do_ref[j].astype(jnp.float32)
        else:
            do = jnp.concatenate([do_ref[2 * j], do_ref[2 * j + 1]],
                                 axis=1).astype(jnp.float32)
        dy = do * cos - _partner(do, turn) * sin
        dx = dy
        if eps is not None:
            x = x_ref[:, j * LANES:(j + 1) * LANES].astype(jnp.float32)
            r = jax.lax.rsqrt(_head_mean(x * x, pack) + eps)
            xhat = x * r
            u = dy * g if gain else dy
            dx = r * (u - xhat * _head_mean(u * xhat, pack))
        if gain:
            # [tile, 128] -> [8, 128] by adds of whole vregs; XLA sums
            # the eight sublanes with the tiles
            part = (dy * xhat).reshape(-1, 8, LANES).sum(axis=0)
            dg = part if dg is None else dg + part
        dx_ref[:, j * LANES:(j + 1) * LANES] = dx.astype(dx_ref.dtype)
    if gain:
        dg_ref = refs[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            dg_ref[...] = jnp.zeros_like(dg_ref)

        dg_ref[...] += dg


def _head_blocks(nb: int, hb: int) -> int:
    """The most column blocks a step, up to `hb`, that divide `nb`."""
    return max(d for d in range(1, min(hb, nb) + 1) if nb % d == 0)


def _row_tile(T: int, tile: int, itemsize: int) -> int:
    """`tile` rows at two bytes an element and half as many at four (the
    blocks' bytes are what VMEM holds), halved until they divide T (T is
    in 128s)."""
    tile = tile * 2 // itemsize
    while T % tile:
        tile //= 2
    return tile


@functools.lru_cache(maxsize=None)
def _calls(B, T, heads, D, dtype, eps, gain, interpret, tile, hb, turn):
    """(forward, backward) calls on X [B, T, heads * D]; memoized and
    jitted, so every layer of a model shares one trace of each body.
    `turn`: `turn_of`'s pairing of the lanes (the heads a block, `pack`,
    but under a partial turn)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pack = LANES // D
    nb = heads // pack
    hb = _head_blocks(nb, hb)
    tile = _row_tile(T, tile, jnp.dtype(dtype).itemsize)
    grid = (B, T // tile, nb // hb)
    wide = pl.BlockSpec((None, tile, hb * LANES), lambda b, i, h: (b, i, h))
    split = pl.BlockSpec((None, hb * pack, tile, D),
                         lambda b, i, h: (b, h, i, 0))
    table = pl.BlockSpec((tile, LANES), lambda b, i, h: (i, 0))
    row = [pl.BlockSpec((1, LANES), lambda b, i, h: (0, 0))] * bool(gain)
    kw = dict(eps=eps, pack=pack, hb=hb, gain=gain, turn=turn)
    fwd = pl.pallas_call(
        functools.partial(_fwd_body, **kw),
        grid=grid,
        in_specs=[wide, table, table] + row,
        out_specs=split,
        out_shape=jax.ShapeDtypeStruct((B, heads, T, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name=FWD, interpret=interpret)
    out_specs, out_shape = wide, jax.ShapeDtypeStruct(
        (B, T, heads * D), dtype)
    if gain:
        # one [8, 128] partial a row tile, added to across the heads
        out_specs = [wide, pl.BlockSpec(
            (None, None, 8, LANES), lambda b, i, h: (b, i, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (B, T // tile, 8, LANES), jnp.float32)]
    bwd = pl.pallas_call(
        functools.partial(_bwd_body, **kw),
        grid=grid,
        in_specs=[split, wide, table, table] + row,
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if gain else "parallel")),
        name=BWD, interpret=interpret)
    return jax.jit(fwd), jax.jit(bwd)


def _prepared(x, gain, heads, eps, theta, period, interpret, tile, hb,
              inv_freq=None, factor=1.0, rotary_dim=0):
    """((forward, backward) calls for X, their operands after X and dOut:
    the two tables and, where there is one, the gain as a [1, 128] row)."""
    import jax.numpy as jnp

    B, T, width = x.shape
    D = width // heads
    R = int(rotary_dim) or D
    cos, sin = tables(T, R, theta, period, lanes=LANES if R == D else 0,
                      inv_freq=inv_freq, factor=factor)
    if R != D:   # the unturned lanes: times one, plus nothing
        rest = jnp.zeros((T, D - R), cos.dtype)
        cos, sin = (jnp.concatenate([cos, jnp.ones_like(rest)], axis=1),
                    jnp.concatenate([sin, rest], axis=1))
    row = [] if gain is None else [jnp.tile(
        gain.astype(jnp.float32).reshape(1, D), (1, LANES // D))]
    calls = _calls(B, T, heads, D, str(x.dtype), eps, gain is not None,
                   interpret, tile, hb, turn_of(D, R))
    return calls, [cos, sin] + row


def head_norm_rope(x, gain, *, heads, eps, theta, period=0, rotary_dim=0,
                   inv_freq=None, factor=1.0, interpret=False,
                   tile=ROW_TILE, hb=HEAD_BLOCKS):
    """X [B, T, heads * D] -> Out [B, heads, T, D] (module docstring);
    `eps` None for no norm, `gain` [D] or None (only with a norm);
    `rotary_dim` a partial turn `turn_of` takes; `inv_freq` and `factor` as
    `tables` takes them (the kernels read the two tables and know nothing
    of the rule that made them)."""
    (fwd, _), rest = _prepared(x, gain, heads, eps, theta, period, interpret,
                               tile, hb, inv_freq, factor, rotary_dim)
    return fwd(x, *rest)


def head_norm_rope_bwd(dout, x, gain, *, heads, eps, theta, period=0,
                       rotary_dim=0, inv_freq=None, factor=1.0,
                       interpret=False, tile=ROW_TILE, hb=HEAD_BLOCKS):
    """dOut [B, heads, T, D], X [B, T, heads * D] -> (dX like X, dGain
    float32 [D] or None)."""
    (_, bwd), rest = _prepared(x, gain, heads, eps, theta, period, interpret,
                               tile, hb, inv_freq, factor, rotary_dim)
    if gain is None:
        return bwd(dout, x, *rest), None
    dx, parts = bwd(dout, x, *rest)
    return dx, parts.reshape(-1, gain.shape[0]).sum(axis=0)
