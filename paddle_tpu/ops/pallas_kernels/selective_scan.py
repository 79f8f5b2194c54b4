"""Mamba's selective scan (ops/ssm_ops.py `selective_scan` has the
equations) as a Pallas kernel pair: a channel tile's [N, tile] float32 state
lives in VMEM from the first chunk of tokens to the last, a chunk's tokens
run as straight-line code, and only U, Dt, B, C, Out and their gradients
cross HBM.

  selective_scan_fwd  grid (batch, channel tile, chunk), the chunk axis
                      sequential.  A step loads the chunk's U and Dt tiles
                      [C, tile] in their own dtype and the chunk's B and C
                      (one [2 N, C] float32 tile), makes Delta = softplus(Dt
                      + DtBias) and Delta u on the whole tile, then walks
                      the tokens: h <- exp(Delta_t A) h + (Delta_t u_t) B_t,
                      y_t = sum_n h C_t, and writes y + D u rounded once.
                      Asked to (`keep=True`) it also writes every chunk's
                      INCOMING state, [T / C, N, Di] float32: all the
                      backward needs of the forward.
  selective_scan_bwd  ONE reverse pass over the chunks with dh in VMEM
                      scratch: from U, Dt, B, C, dOut and the chunk's
                      incoming state it makes the chunk's per-token states
                      again in VMEM, walks the tokens backward and writes dU
                      and dDt (through softplus' sigmoid and the D term) in
                      their operands' dtypes, accumulates dALog [N, Di], dD
                      and dDtBias [Di] in VMEM across the chunks, and writes
                      dB and dC as per-channel-tile partials [Di / tile, T /
                      C, 2 N, C] that XLA sums.

The state's axis N lies along the SUBLANES and the channels along the lanes
(a [16, 128] piece of the state is two vregs): a token's row of Delta or u is
read where the projections left it and spread over the sublanes, B_t and C_t
are columns spread over the lanes once a (tile, chunk) and shared by the
tile's slabs, the read-out's sum over N is a sublane reduction, and the
backward's two sums over CHANNELS (dB_t, dC_t) are plain adds over the
tile's slabs and one lane reduction a token.  No [T, Di] float32 tensor and
no transpose of one is left around the kernels.

Precision is the configuration's: Delta, the exponent, the state and every
sum are float32; U, Dt and dOut are widened tile by tile in VMEM and Out, dU
and dDt rounded once.  `make_selective_scan()` is the `kernel_pair` over the
two (_common.py): what a forward op and its grad op split between them
(`.keeping`, `.from_saved`, gated_delta.py's way).
"""

from __future__ import annotations

import functools

FWD, BWD = "selective_scan_fwd", "selective_scan_bwd"
LANES = 128
SUBLANES = 8
# Tokens a grid step, the most channels a tile, the tokens of straight-line
# code a loop iteration (a float32 sublane tile's rows) and the channels an
# operation of that code spans: constants from the probe at the cell's shape
# ([1, 8192, 5120], N 16, bf16; PERF.md section 6, PR 55), not knobs.  The
# backward holds a chunk's per-token states, CHUNK x [N, tile] float32, in
# VMEM: STATES_BYTES bounds the tile where N is large.
CHUNK = 64
TILE = 2560
UNROLL = 8
SLAB = 512
STATES_BYTES = 12 * 1024 * 1024
VMEM_LIMIT = 48 * 1024 * 1024


def tile_of(Di: int, N: int, chunk: int = CHUNK) -> int:
    """The channels a tile: the most whole lane tiles, up to TILE, that
    divide Di and whose chunk of states fits STATES_BYTES; 0 where none
    does."""
    most = min(TILE, Di, STATES_BYTES // (4 * chunk * max(N, 1)))
    return next((t for t in range(most - most % LANES, 0, -LANES)
                 if Di % t == 0), 0)


def usable(T: int, chunk: int, Di: int, N: int, dtype) -> bool:
    """The kernels take U, Dt [B, T, Di] in bf16 or float32 where the chunk
    divides T and is whole groups of UNROLL tokens (and whole bf16 sublane
    tiles), Di is whole channel tiles and the state's N whole sublane
    tiles."""
    if str(dtype) not in ("bfloat16", "float32"):
        return False
    if min(T, chunk, Di, N) < 1 or not tile_of(Di, N, chunk):
        return False
    return not (T % chunk or N % SUBLANES or chunk % 16 or chunk % UNROLL)


def _grouped(tile):
    """A [C, tile] float32 tile as the scratch holds it, [C / UNROLL,
    UNROLL, tile]: a loop iteration's rows are one index of the leading
    axis and a token's a static sublane."""
    return tile.reshape(tile.shape[0] // UNROLL, UNROLL, tile.shape[1])


def _slabs(width):
    """The tile's channels in slabs of SLAB lanes (of the widest whole lane
    tiles under it that divide the tile)."""
    slab = next(w for w in range(min(SLAB, width), 0, -LANES)
                if width % w == 0)
    return [slice(j * slab, (j + 1) * slab) for j in range(width // slab)]


def _columns(spread_scr, t, slab):
    """Token t's B and C as [N, slab] tiles: its spread column beside
    itself, one vreg reused every 128 channels."""
    import jax.numpy as jnp

    both = jnp.concatenate([spread_scr[t]] * (slab // LANES), axis=1)
    half = both.shape[0] // 2
    return both[:half], both[half:]


def _chunk(u_ref, dt_ref, bc_ref, alog_ref, bias_ref, delta_scr, x_scr,
           spread_scr):
    """What both kernels make of a chunk's tile before they walk it: Delta =
    softplus(Dt + DtBias) and x = Delta u, float32, into scratch; every
    token's column of the [2 N, C] B | C tile spread over the lanes
    (spread_scr[t] [2 N, 128]); A a slab.  -> (u and Dt + DtBias float32,
    the slabs, A's, `advance`)."""
    import jax
    import jax.numpy as jnp

    uf = u_ref[...].astype(jnp.float32)
    pre = dt_ref[...].astype(jnp.float32) + bias_ref[...]
    delta = jax.nn.softplus(pre)
    delta_scr[...] = _grouped(delta)
    x_scr[...] = _grouped(delta * uf)
    bc = bc_ref[...]
    for t in range(bc.shape[1]):
        spread_scr[t] = jnp.broadcast_to(bc[:, t:t + 1],
                                         (bc.shape[0], LANES))
    slabs = _slabs(u_ref.shape[1])
    a = [-jnp.exp(alog_ref[:, s]) for s in slabs]

    def advance(h, g, k):
        """(h_t a slab from h_{t-1}, B_t, C_t) of token t = g UNROLL + k."""
        row = slice(k, k + 1)
        b, c = _columns(spread_scr, g * UNROLL + k, slabs[0].stop)
        return tuple(jnp.exp(delta_scr[g, row, s] * a[j]) * h[j]
                     + x_scr[g, row, s] * b
                     for j, s in enumerate(slabs)), b, c

    return uf, pre, slabs, a, advance


def _walk(tokens, group, carry, reverse=False):
    """carry = group(g, carry) over the chunk's tokens UNROLL at a time (g
    the group: tokens g UNROLL ...), in order (from the last group to the
    first under `reverse`): a group is straight-line code and the groups a
    `fori_loop`.  All 64 tokens unrolled read 1.63 ms forward for 2.59 at
    tiles of 512 channels, and 4.3 s of tracing a process for 0.9; a tile of
    2560 gives the scheduler the same room inside a group, and operations
    on slabs of SLAB channels a quarter of the trace of single lane tiles'
    at the same speed (PERF.md section 6, PR 55)."""
    import jax
    import jax.numpy as jnp

    groups = tokens // UNROLL
    return jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(groups),
        lambda g, carry: group(groups - 1 - g if reverse else g, carry),
        carry)


def _fwd_body(u_ref, dt_ref, bc_ref, alog_ref, d_ref, bias_ref, *rest, keep):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_ref = rest[0]
    states_ref = rest[1] if keep else None
    h_scr, delta_scr, x_scr, y_scr, spread_scr = rest[-5:]
    C, width = u_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    if keep:
        states_ref[...] = h_scr[...]
    uf, _, slabs, _, advance = _chunk(u_ref, dt_ref, bc_ref, alog_ref,
                                      bias_ref, delta_scr, x_scr, spread_scr)

    def group(g, h):
        for k in range(UNROLL):
            h, _, c = advance(h, g, k)
            for j, s in enumerate(slabs):
                y_scr[g, slice(k, k + 1), s] = jnp.sum(h[j] * c, axis=0,
                                                       keepdims=True)
        return h

    h = _walk(C, group, tuple(h_scr[:, s] for s in slabs))
    for j, s in enumerate(slabs):
        h_scr[:, s] = h[j]
    out_ref[...] = (y_scr[...].reshape(C, width)
                    + d_ref[...] * uf).astype(out_ref.dtype)


def _bwd_body(u_ref, dt_ref, bc_ref, alog_ref, d_ref, bias_ref, do_ref,
              states_ref, du_ref, ddt_ref, dbc_ref, dalog_ref, dd_ref,
              dbias_ref, dh_scr, delta_scr, x_scr, do_scr, s1_scr, s2_scr,
              spread_scr, hs_scr):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    C, width = u_ref.shape
    N = dh_scr.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dalog_ref[...] = jnp.zeros_like(dalog_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    uf, pre, slabs, a, advance = _chunk(u_ref, dt_ref, bc_ref, alog_ref,
                                        bias_ref, delta_scr, x_scr,
                                        spread_scr)
    wide = slabs[0].stop
    dof = do_ref[...].astype(f32)
    do_scr[...] = _grouped(dof)

    def again(g, h):
        """The chunk's states again: hs[t] is what token t finds."""
        for k in range(UNROLL):
            for j, s in enumerate(slabs):
                hs_scr[g * UNROLL + k, :, s] = h[j]
            h, _, _ = advance(h, g, k)
        return h

    h = _walk(C, again, tuple(states_ref[:, s] for s in slabs))
    lane = jax.lax.broadcasted_iota(jnp.int32, (2 * N, C), 1)

    def back(g, carry):
        """h: h_t; dh: d loss / d h_t from the tokens after t."""
        h, dh, da, dbc = carry
        for k in reversed(range(UNROLL)):
            t, row = g * UNROLL + k, slice(k, k + 1)
            b, c = _columns(spread_scr, t, wide)
            db = dc = None
            before, into, da_new = [], [], []
            for j, s in enumerate(slabs):
                before.append(hs_scr[t, :, s])
                delta, do = delta_scr[g, row, s], do_scr[g, row, s]
                decay = jnp.exp(delta * a[j])
                dht = dh[j] + c * do
                part = (dht * x_scr[g, row, s], h[j] * do)
                db, dc = part if j == 0 else (db + part[0], dc + part[1])
                s2_scr[g, row, s] = jnp.sum(dht * b, axis=0, keepdims=True)
                grad = dht * before[j] * decay  # d loss / d (Delta_t A)
                s1_scr[g, row, s] = jnp.sum(grad * a[j], axis=0,
                                            keepdims=True)
                da_new.append(da[j] + grad * delta)
                into.append(decay * dht)
            col = jnp.concatenate([jnp.sum(db, axis=1, keepdims=True),
                                   jnp.sum(dc, axis=1, keepdims=True)],
                                  axis=0)
            dbc = jnp.where(lane == t, col, dbc)
            h, dh, da = tuple(before), tuple(into), tuple(da_new)
        return h, dh, da, dbc

    zeros = tuple(jnp.zeros((N, wide), f32) for _ in slabs)
    _, dh, da, dbc = _walk(
        C, back, (h, tuple(dh_scr[:, s] for s in slabs), zeros,
                  jnp.zeros((2 * N, C), f32)), reverse=True)
    dbc_ref[...] = dbc
    for j, s in enumerate(slabs):
        dh_scr[:, s] = dh[j]
        dalog_ref[:, s] += da[j] * a[j]         # dA / dALog = A
    s2 = s2_scr[...].reshape(C, width)          # d loss / d (Delta u)
    du_ref[...] = (s2 * delta_scr[...].reshape(C, width)
                   + d_ref[...] * dof).astype(du_ref.dtype)
    ddt = (s1_scr[...].reshape(C, width) + s2 * uf) * jax.nn.sigmoid(pre)
    ddt_ref[...] = ddt.astype(ddt_ref.dtype)
    dbias_ref[...] += jnp.sum(ddt, axis=0, keepdims=True)
    dd_ref[...] += jnp.sum(dof * uf, axis=0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _calls(B, T, Di, N, C, tile, dtype, dt_dtype, interpret):
    """(forward, forward that also keeps the chunks' incoming states,
    backward) on U, Dt [B, T, Di], the B | C tiles [B, T / C, 2 N, C], ALog
    [N, Di], D and DtBias [1, Di]; memoized and jitted, so every layer of a
    model shares one trace of each body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunks, tiles = T // C, Di // tile
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)

    def specs(at):
        """The chunk `at(n)`'s blocks of U, Dt, Out or a gradient; of the
        B | C tile; of the kept states; then a channel tile's of ALog, and
        of D or DtBias."""
        return (pl.BlockSpec((None, C, tile), lambda b, i, n: (b, at(n), i)),
                pl.BlockSpec((None, None, 2 * N, C),
                             lambda b, i, n: (b, at(n), 0, 0)),
                pl.BlockSpec((None, None, N, tile),
                             lambda b, i, n: (b, at(n), 0, i)),
                pl.BlockSpec((N, tile), lambda b, i, n: (0, i)),
                pl.BlockSpec((1, tile), lambda b, i, n: (0, i)))

    on_tile = [pltpu.VMEM((C // UNROLL, UNROLL, tile), f32)]
    spread = [pltpu.VMEM((C, 2 * N, LANES), f32)]
    state = [pltpu.VMEM((N, tile), f32)]
    wide, bc, kept, alog, row = specs(lambda n: n)

    def forward(keep):
        outs = [(wide, sds((B, T, Di), dtype))]
        if keep:
            outs.append((kept, sds((B, chunks, N, Di), f32)))
        return jax.jit(pl.pallas_call(
            functools.partial(_fwd_body, keep=keep),
            grid=(B, tiles, chunks),
            in_specs=[wide, wide, bc, alog, row, row],
            out_specs=[spec for spec, _ in outs],
            out_shape=[shape for _, shape in outs],
            scratch_shapes=state + on_tile * 3 + spread,
            compiler_params=params, name=FWD, interpret=interpret))

    # the reverse pass walks the chunks from the last to the first; the
    # parameters' gradients stay in VMEM while a (batch, tile)'s chunks run
    rwide, rbc, rkept, _, _ = specs(lambda n: chunks - 1 - n)
    per_batch = lambda rows: pl.BlockSpec(                   # noqa: E731
        (None, rows, tile), lambda b, i, n: (b, 0, i))
    backward = jax.jit(pl.pallas_call(
        _bwd_body,
        grid=(B, tiles, chunks),
        in_specs=[rwide, rwide, rbc, alog, row, row, rwide, rkept],
        out_specs=[rwide, rwide,
                   pl.BlockSpec((None, None, None, 2 * N, C),
                                lambda b, i, n: (b, i, chunks - 1 - n, 0, 0)),
                   per_batch(N), per_batch(1), per_batch(1)],
        out_shape=[sds((B, T, Di), dtype), sds((B, T, Di), dt_dtype),
                   sds((B, tiles, chunks, 2 * N, C), f32),
                   sds((B, N, Di), f32), sds((B, 1, Di), f32),
                   sds((B, 1, Di), f32)],
        scratch_shapes=(state + on_tile * 5 + spread
                        + [pltpu.VMEM((C, N, tile), f32)]),
        compiler_params=params, name=BWD, interpret=interpret))
    return forward(False), forward(True), backward


def _prepared(u, dt, b, c, a_log, d, bias, chunk, interpret):
    """The three calls and their operands: U, Dt as they are, the B | C
    tiles [B, T / C, 2 N, C] float32 (a chunk's tokens along the lanes),
    ALog [N, Di], D and DtBias [1, Di] float32."""
    import jax.numpy as jnp

    B, T, Di = u.shape
    N = a_log.shape[1]
    if not usable(T, chunk, Di, N, u.dtype) or dt.shape != u.shape:
        raise ValueError(
            f"selective scan kernels: U {u.shape} {u.dtype}, Dt {dt.shape}, "
            f"a state of {N}, in chunks of {chunk}")
    f32 = jnp.float32
    bc = jnp.concatenate([b, c], axis=-1).astype(f32).reshape(
        B, T // chunk, chunk, 2 * N).swapaxes(2, 3)
    calls = _calls(B, T, Di, N, chunk, tile_of(Di, N, chunk), str(u.dtype),
                   str(dt.dtype), interpret)
    return calls, (u, dt, bc, a_log.astype(f32).T,
                   d.astype(f32).reshape(1, Di),
                   bias.astype(f32).reshape(1, Di))


def selective_scan_fwd(u, dt, b, c, a_log, d, bias, *, keep=False,
                       chunk=CHUNK, interpret=False):
    """U, Dt [B, T, Di], B, C [B, T, N], ALog [Di, N], D, DtBias [Di] ->
    Out [B, T, Di] in U's dtype (the scan's result with the D term); with
    `keep` (Out, every chunk's incoming state [B, T / chunk, N, Di]
    float32), what `selective_scan_bwd` takes."""
    calls, operands = _prepared(u, dt, b, c, a_log, d, bias, chunk,
                                interpret)
    got = calls[1 if keep else 0](*operands)
    return tuple(got) if keep else got[0]


def selective_scan_bwd(do, u, dt, b, c, a_log, d, bias, states, *,
                       chunk=CHUNK, interpret=False):
    """dOut [B, T, Di], the forward's operands and the states it kept ->
    (dU, dDt, dB, dC, dALog, dD, dDtBias) in their operands' dtypes."""
    (_, _, bwd), operands = _prepared(u, dt, b, c, a_log, d, bias, chunk,
                                      interpret)
    du, ddt, dbc, dalog, dd, dbias = bwd(*operands, do.astype(u.dtype),
                                         states)
    B, T, _ = u.shape
    N = a_log.shape[1]
    # the tiles' partials summed; a chunk's tokens back from the lanes
    dbc = dbc.sum(axis=1).swapaxes(2, 3).reshape(B, T, 2 * N)
    return (du, ddt, dbc[..., :N].astype(b.dtype),
            dbc[..., N:].astype(c.dtype),
            dalog.sum(axis=0).T.astype(a_log.dtype),
            dd.sum(axis=(0, 1)).astype(d.dtype),
            dbias.sum(axis=(0, 1)).astype(bias.dtype))


@functools.lru_cache(maxsize=None)
def make_selective_scan(chunk: int = CHUNK, interpret: bool = False):
    """The scan (U, Dt, B, C, ALog, D, DtBias) -> Out as a `kernel_pair`
    (_common.py: the differentiable pair, `.keeping -> (Out, states)`,
    `.from_saved(..., Out, states)`), memoized so that every trace meets
    the same function.  Its forward is the launch that keeps the chunks'
    states, under the plain rule too (differentiated or not: one kernel
    body a training step to trace, and a `jax.checkpoint` traces the primal
    beside the rule), and its backward the reverse pass over them."""
    from ._common import kernel_pair

    how = dict(chunk=chunk, interpret=interpret)
    return kernel_pair(
        7, functools.partial(selective_scan_fwd, **how),
        lambda *ops, keep: selective_scan_fwd(*ops, keep=True, **how),
        lambda ops, do, kept: selective_scan_bwd(do, *ops, kept[1], **how))
