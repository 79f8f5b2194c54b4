"""The hyper-connections' passes over the residual streams, one kernel each.

A sub-layer of a model with n residual streams X [B, n, T, C] reads them
through `hyper_connection_pre` and writes its result Y back through
`hyper_connection_post` (ops/llm_ops.py has the equations).  As plain
jax.numpy XLA reads the streams once for the norm's statistic, once for
vec(X) Phi and once for the weighted read, and hands gradients over as
float32 [T, C] tensors (PERF.md, PR 39: 5.8 ms a sub-layer of
`xing4_train_t4096` against 0.9 at the HBM roof).  Here every pass is one
kernel that holds a tile of tokens of all n streams in VMEM, does what a
token needs of them in float32 and writes each output once in the streams'
dtype ([T, C]-sized tensors read + written):

  pre_fwd    X -> U = sum_i H_pre[i] X[i] and, for the gates in XLA, the
             raw projection vec(X) Phi and the norm's factor (4 + 1);
             H_pre = sigmoid(a_pre inv proj + b_pre) is made in the kernel
             from the token's own product and statistic
  post_fwd   X, Y, H_post, M -> Out[i] = sum_j M[i, j] X[j] + H_post[i] Y
             (5 + 4)
  post_bwd   X, Y, dOut, H_post, M -> dX[j] = sum_i M[i, j] dOut[i], dY =
             sum_i H_post[i] dOut[i], and a token's column sums dH_post[i]
             = <dOut[i], Y>, dM[i, j] = <dOut[i], X[j]> (9 + 5)
  pre_bwd_a  X, dU -> dH_pre[i] = <dU, X[i]> (5 + 0): the gates' backward
             in XLA needs it before it can give dproj
  pre_bwd_b  X, dU, H_pre, dproj, the column -dinv inv^3 / (n C) -> dX[i] =
             H_pre[i] dU + column X[i] + dproj Phi[i]^T, and dPhi[i] =
             X[i]^T dproj accumulated over the token tiles in float32
             (5 + 4)

The gates and the Sinkhorn iterations stay XLA's: they never touch a [T,
C] tensor.  Small per-token tensors meet the streams as one column a token
([T, k] blocks: a [rows, 1] slice broadcasts along the lanes).

**Shape of a body.**  The products run on the whole tile at once (the MXU
wants many rows a weight tile); everything elementwise runs in a loop over
chunks of ROWS tokens (`lax.fori_loop`, a dynamic sublane offset), whole
rows a chunk: the bodies stay short to trace and to lower (twelve
sub-layers share one trace of each, but every process pays it), and on the
chip every kernel is bound by HBM, not by its vector work, with or without
a second loop over column chunks (PERF.md, PR 40).  Sums over a token's
columns are adds of whole 128-lane vregs with ONE cross-lane reduction a
row chunk.  Phi travels transposed and padded, [n, Kp, C] (K = 2n + n n
gates, Kp the next multiple of 32): the few gates lie on the sublanes, not
on 128 padded lanes.  The raw projection and the factor leave `pre_fwd` as
one [T, Kp] float32 tensor, the factor in column K.
"""

from __future__ import annotations

import functools

PRE_FWD, POST_FWD, POST_BWD = "hc_pre_fwd", "hc_post_fwd", "hc_post_bwd"
PRE_BWD_A, PRE_BWD_B = "hc_pre_bwd_a", "hc_pre_bwd_b"
LANES = 128
ROWS = 16              # tokens a chunk of the elementwise loops
TOKEN_TILE = 256       # most tokens a grid step
MAX_STREAMS = 8        # K = 2n + n n gates fit 128 lanes
VMEM_LIMIT = 96 * 1024 * 1024
# the [tile, C] blocks of one call, double-buffered: the rest is for the
# products' float32 results and what the elementwise loops spill
BLOCK_BUDGET = 40 * 1024 * 1024
# [tile, C] blocks a grid step of each kernel moves, at n streams
BLOCKS = {PRE_FWD: lambda n: n + 1, POST_FWD: lambda n: 2 * n + 1,
          POST_BWD: lambda n: 3 * n + 2, PRE_BWD_A: lambda n: n + 1,
          PRE_BWD_B: lambda n: 2 * n + 3}


def gates_of(n: int) -> int:
    """K: H_pre's n, H_post's n and M's n n entries a token."""
    return (2 + n) * n


def padded_gates(n: int) -> int:
    """Kp: K + 1 (the norm's factor rides in column K) up to 32s."""
    return -(-(gates_of(n) + 1) // 32) * 32


def token_tile(kernel: str, T: int, n: int, C: int, itemsize: int,
               tile: int = TOKEN_TILE) -> int:
    """Tokens a grid step of `kernel`: `tile` halved until it divides T
    and the step's blocks, double-buffered, fit BLOCK_BUDGET; whole 128s,
    or all of a T under 128.  0 where nothing fits."""
    fits = lambda t: (2 * BLOCKS[kernel](n) * t * C * itemsize  # noqa: E731
                      <= BLOCK_BUDGET)
    if T < LANES:
        return T if T % ROWS == 0 and fits(T) else 0
    while tile >= LANES:
        if T % tile == 0 and fits(tile):
            return tile
        tile //= 2
    return 0


def usable(n: int, T: int, C: int, dtype) -> bool:
    """The kernels take X [B, n, T, C]: bf16 or float32, C in whole
    128-lane blocks, T in whole token tiles, n small enough that a tile of
    every kernel fits VMEM."""
    size = {"bfloat16": 2, "float32": 4}.get(str(dtype))
    if not size or not 1 <= n <= MAX_STREAMS or C % LANES or T % ROWS:
        return False
    return all(token_tile(k, T, n, C, size) for k in BLOCKS)


def _row_chunks(rows: int, body):
    """body(first row of the chunk) for every chunk of ROWS rows."""
    from jax import lax
    from jax.experimental import pallas as pl

    def step(r, carry):
        body(pl.multiple_of(r * ROWS, ROWS))
        return carry

    lax.fori_loop(0, rows // ROWS, step, None)


def _fold(a):
    """[rows, k * 128] -> [rows, 128]: adds of whole vregs, halves onto
    each other while they split on a lane block (few equations to
    trace)."""
    while a.shape[1] % (2 * LANES) == 0:
        half = a.shape[1] // 2
        a = a[:, :half] + a[:, half:]
    return sum(a[:, k:k + LANES] for k in range(0, a.shape[1], LANES))


def _columns(vals, width: int):
    """[rows, 1] columns -> [rows, width], column k in lane k (a select a
    column: a lane concatenation of single lanes is not Mosaic's)."""
    import jax
    import jax.numpy as jnp

    rows = vals[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), jnp.float32)
    for k, v in enumerate(vals):
        out = jnp.where(lane == k, v, out)
    return out


def _total(acc):
    """[rows, 128] partial sums -> [rows, 1]."""
    import jax.numpy as jnp

    return jnp.sum(acc, axis=1, keepdims=True)


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _dot(a, b, dims, exact: bool):
    """a . b over `dims` into float32; float32 operands at full
    precision (`exact`), bf16 ones as they are."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if exact else None)


# ---------------------------------------------------------------------------
# bodies


def _pre_fwd_body(x_ref, phit_ref, ab_ref, u_ref, s_ref, p_scr, *, n,
                  norm_eps):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    _, tt, C = x_ref.shape
    K = gates_of(n)
    exact = x_ref.dtype == jnp.float32
    # vec(X) Phi of the whole tile, stream by stream: [tt, C] . [Kp, C]^T
    p_scr[...] = sum(_dot(x_ref[i], phit_ref[i], ((1,), (1,)), exact)
                     for i in range(n))
    a, b = ab_ref[0:1, :], ab_ref[1:2, :]

    def chunk(r0):
        rows = pl.ds(r0, ROWS)
        ss = jnp.zeros((ROWS, LANES), jnp.float32)
        for i in range(n):
            xf = _f32(x_ref[i, rows, :])
            ss = ss + _fold(xf * xf)
        inv = jax.lax.rsqrt(_total(ss) * (1.0 / (n * C)) + norm_eps)
        proj = p_scr[rows, :]
        h = jax.nn.sigmoid(proj * inv * a + b)             # [ROWS, Kp]
        lane = jax.lax.broadcasted_iota(jnp.int32, proj.shape, 1)
        s_ref[rows, :] = jnp.where(lane == K, inv, proj)
        u = sum(h[:, i:i + 1] * _f32(x_ref[i, rows, :]) for i in range(n))
        u_ref[rows, :] = u.astype(u_ref.dtype)

    _row_chunks(tt, chunk)


def _post_fwd_body(x_ref, y_ref, h_ref, m_ref, o_ref, *, n):
    from jax.experimental import pallas as pl

    tt = x_ref.shape[1]

    def chunk(r0):
        rows = pl.ds(r0, ROWS)
        h, m = h_ref[rows, :], m_ref[rows, :]
        y = _f32(y_ref[rows, :])
        xs = [_f32(x_ref[j, rows, :]) for j in range(n)]
        for i in range(n):
            # Out[i] = H_post[i] Y + sum_j M[i, j] X[j]
            out = h[:, i:i + 1] * y
            for j in range(n):
                out = out + m[:, i * n + j:i * n + j + 1] * xs[j]
            o_ref[i, rows, :] = out.astype(o_ref.dtype)

    _row_chunks(tt, chunk)


def _post_bwd_body(x_ref, y_ref, do_ref, h_ref, m_ref, dx_ref, dy_ref,
                   dh_ref, dm_ref, *, n):
    from jax.experimental import pallas as pl

    tt = x_ref.shape[1]

    def chunk(r0):
        rows = pl.ds(r0, ROWS)
        h, m = h_ref[rows, :], m_ref[rows, :]
        y = _f32(y_ref[rows, :])
        xs = [_f32(x_ref[j, rows, :]) for j in range(n)]
        ds = [_f32(do_ref[i, rows, :]) for i in range(n)]
        for j in range(n):
            # dX[j] = sum_i M[i, j] dOut[i]
            dx_ref[j, rows, :] = sum(
                m[:, i * n + j:i * n + j + 1] * ds[i]
                for i in range(n)).astype(dx_ref.dtype)
        dy_ref[rows, :] = sum(h[:, i:i + 1] * ds[i]
                              for i in range(n)).astype(dy_ref.dtype)
        dh_ref[rows, :] = _columns(
            [_total(_fold(ds[i] * y)) for i in range(n)], n)
        dm_ref[rows, :] = _columns(
            [_total(_fold(ds[i] * xs[j])) for i in range(n)
             for j in range(n)], n * n)

    _row_chunks(tt, chunk)


def _pre_bwd_a_body(x_ref, du_ref, dh_ref, *, n):
    from jax.experimental import pallas as pl

    tt = x_ref.shape[1]

    def chunk(r0):
        rows = pl.ds(r0, ROWS)
        du = _f32(du_ref[rows, :])
        dh_ref[rows, :] = _columns(
            [_total(_fold(du * _f32(x_ref[i, rows, :]))) for i in range(n)],
            n)

    _row_chunks(tt, chunk)


def _pre_bwd_b_body(x_ref, du_ref, cols_ref, dp_ref, dpt_ref, phit_ref,
                    dx_ref, dphit_ref, t_scr, *, n):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    tt = x_ref.shape[1]
    exact = x_ref.dtype == jnp.float32

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)

    for i in range(n):
        # dPhi[i]^T += dproj^T X[i]: [Kp, tt] . [tt, C]
        dphit_ref[i] += _dot(dpt_ref[...], x_ref[i], ((1,), (0,)), exact)
        # dproj Phi[i]^T: [tt, Kp] . [Kp, C]
        t_scr[...] = _dot(dp_ref[...], phit_ref[i], ((1,), (0,)), exact)

        def chunk(r0, i=i):
            rows = pl.ds(r0, ROWS)
            cols = cols_ref[rows, :]
            dx = (cols[:, i:i + 1] * _f32(du_ref[rows, :])
                  + cols[:, n:n + 1] * _f32(x_ref[i, rows, :])
                  + t_scr[rows, :])
            dx_ref[i, rows, :] = dx.astype(dx_ref.dtype)

        _row_chunks(tt, chunk)


# ---------------------------------------------------------------------------
# calls


@functools.lru_cache(maxsize=None)
def _calls(B, n, T, C, dtype, norm_eps, interpret, tile):
    """{kernel: call} on X [B, n, T, C]; memoized and jitted, so every
    sub-layer of a model shares one trace of each body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Kp = padded_gates(n)
    size = jnp.dtype(dtype).itemsize
    f32 = jnp.float32

    def build(kernel, body, ins, outs, scratch=(), accumulates=False,
              **kw):
        """One call: `ins` / `outs` name their blocks ('x': [n, tt, C] of
        the streams' shape, 'y': [tt, C], an int k: [tt, k] float32 a
        token, 'phit': all of Phi^T, 'ab': the two gate rows, 'dpt': [Kp,
        tt]); an out is (block, dtype)."""
        tt = token_tile(kernel, T, n, C, size, tile)
        if not tt:
            raise ValueError(f"{kernel}: no token tile for T {T}, {n} "
                             f"streams of {C} columns")

        # block and index map, whole shape, by name
        blocks = {
            "x": ((None, n, tt, C), lambda b, t: (b, 0, t, 0), (B, n, T, C)),
            "y": ((None, tt, C), lambda b, t: (b, t, 0), (B, T, C)),
            "phit": ((n, Kp, C), lambda b, t: (0, 0, 0), (n, Kp, C)),
            "ab": ((2, Kp), lambda b, t: (0, 0), (2, Kp)),
            "dpt": ((None, Kp, tt), lambda b, t: (b, 0, t), (B, Kp, T))}

        def block(b):
            return blocks.get(b) or ((None, tt, b), lambda b_, t: (b_, t, 0),
                                     (B, T, b))

        def spec(b):
            return pl.BlockSpec(*block(b)[:2])

        def shape(b, dt):
            return jax.ShapeDtypeStruct(block(b)[2], dt)

        sem = "arbitrary" if accumulates else "parallel"
        return jax.jit(pl.pallas_call(
            functools.partial(body, n=n, **kw),
            grid=(B, T // tt),
            in_specs=[spec(b) for b in ins],
            out_specs=[spec(b) for b, _ in outs],
            out_shape=[shape(b, dt) for b, dt in outs],
            scratch_shapes=[pltpu.VMEM((tt, w), f32) for w in scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(sem, sem),
                vmem_limit_bytes=VMEM_LIMIT),
            name=kernel, interpret=interpret))

    return {
        PRE_FWD: build(PRE_FWD, _pre_fwd_body, ["x", "phit", "ab"],
                       [("y", dtype), (Kp, f32)], scratch=(Kp,),
                       norm_eps=norm_eps),
        POST_FWD: build(POST_FWD, _post_fwd_body, ["x", "y", n, n * n],
                        [("x", dtype)]),
        POST_BWD: build(POST_BWD, _post_bwd_body,
                        ["x", "y", "x", n, n * n],
                        [("x", dtype), ("y", dtype), (n, f32),
                         (n * n, f32)]),
        PRE_BWD_A: build(PRE_BWD_A, _pre_bwd_a_body, ["x", "y"],
                         [(n, f32)]),
        PRE_BWD_B: build(PRE_BWD_B, _pre_bwd_b_body,
                         ["x", "y", n + 1, Kp, "dpt", "phit"],
                         [("x", dtype), ("phit", f32)], scratch=(C,),
                         accumulates=True),
    }


def _call(kernel, x, norm_eps=0.0, interpret=False, tile=TOKEN_TILE):
    B, n, T, C = x.shape
    return _calls(B, n, T, C, str(x.dtype), norm_eps, interpret,
                  tile)[kernel]


def phi_transposed(phi):
    """Phi [n, C, K] -> [n, Kp, C], zero rows after the K gates'."""
    import jax.numpy as jnp

    n, _, K = phi.shape
    return jnp.pad(jnp.transpose(phi, (0, 2, 1)),
                   ((0, 0), (0, padded_gates(n) - K), (0, 0)))


def pre_fwd(x, phit, alpha, beta, *, norm_eps, **how):
    """X [B, n, T, C], Phi^T [n, Kp, C] (`phi_transposed`), Alpha [3] and
    Beta [K] in float32 -> (U [B, T, C], the raw projection vec(X) Phi [B,
    T, K] float32, the norm's factor [B, T] float32)."""
    import jax.numpy as jnp

    n = x.shape[1]
    K, Kp = gates_of(n), padded_gates(n)
    a = jnp.concatenate([jnp.broadcast_to(v, (k,))
                         for v, k in zip(alpha, (n, n, n * n))])
    ab = jnp.pad(jnp.stack([a, beta]).astype(jnp.float32),
                 ((0, 0), (0, Kp - K)))
    u, stats = _call(PRE_FWD, x, float(norm_eps), **how)(x, phit, ab)
    return u, stats[..., :K], stats[..., K]


def post_fwd(x, y, h_post, m, **how):
    """X [B, n, T, C], Y [B, T, C], H_post [B, T, n] and M [B, T, n, n] in
    float32 -> Out [B, n, T, C]."""
    B, n, T, _ = x.shape
    (out,) = _call(POST_FWD, x, **how)(x, y, h_post,
                                       m.reshape(B, T, n * n))
    return out


def post_bwd(x, y, dout, h_post, m, **how):
    """-> (dX like X, dY like Y, dH_post [B, T, n], dM [B, T, n, n], both
    float32) of `post_fwd` at the cotangent dOut [B, n, T, C]."""
    B, n, T, _ = x.shape
    dx, dy, dh, dm = _call(POST_BWD, x, **how)(
        x, y, dout, h_post, m.reshape(B, T, n * n))
    return dx, dy, dh, dm.reshape(B, T, n, n)


def pre_bwd_a(x, du, **how):
    """-> dH_pre [B, T, n] float32: <dU, X[i]> a token."""
    (dh,) = _call(PRE_BWD_A, x, **how)(x, du)
    return dh


def pre_bwd_b(x, du, cols, dproj, phit, **how):
    """X, dU [B, T, C], `cols` [B, T, n + 1] float32 (H_pre's n columns,
    then -dinv inv^3 / (n C)), dproj [B, T, K] float32, Phi^T -> (dX like
    X, dPhi [n, C, K] float32).  The products take dproj in X's dtype."""
    import jax.numpy as jnp

    n = x.shape[1]
    K, Kp = gates_of(n), padded_gates(n)
    dp = jnp.pad(dproj.astype(x.dtype), ((0, 0), (0, 0), (0, Kp - K)))
    dx, dphit = _call(PRE_BWD_B, x, **how)(
        x, du, cols, dp, jnp.transpose(dp, (0, 2, 1)), phit)
    return dx, jnp.transpose(dphit[:, :K], (0, 2, 1))
