"""The gated delta rule's scan (ops/sparse_linear_ops.py
`gated_delta_chunked` has the equations) as a Pallas kernel pair: a chunk's
[C, C] and [C, d] intermediates and the running [Dk, Dv] state live in VMEM,
and only q, k, v, the two gates, o and their gradients cross HBM.

  gated_delta_fwd  grid (batch x key head, chunk), the chunk axis
                   sequential, the float32 state of the key head's G value
                   heads in VMEM scratch from the first chunk to the last.
                   A step loads the chunk's q and k tiles ONCE a key head,
                   makes K K^T and Q K^T (the operands' dtype in, float32
                   out) for its G value heads, and per value head the
                   decay mask, A, Tm = (I - A)^-1, V' = Tm (beta (V -
                   e^gamma K S)), O = (Q e^gamma) S + (Q K^T * decay) V' and
                   S <- e^{gamma_C} S + (K e^{gamma_C - gamma})^T V': the
                   docstring's `(e^{gamma_C} I - K~^T W) S + K~^T U`
                   regrouped, no [Dk, Dk] transition matrix, no U and W.
                   Asked to (`keep=True`), it also writes every chunk's
                   INCOMING state and Tm: what the backward reads.
  gated_delta_bwd  ONE reverse pass over the chunks with dS in VMEM
                   scratch: from q, k, v, the gates, dO, the chunk's
                   incoming state and Tm it makes V', the masks and A again
                   and writes dq and dk (summed over the key head's value
                   heads in the step, rounded once), dv, and the rows of
                   d gamma and d beta.

`make_gated_delta(chunk)` is the `kernel_pair` (_common.py) over the two: what
a forward op and its grad op split between them.  Nothing of size [C, C] or [N,
Dk, Dv] has to live across a step: the plain function keeps q, k, v, g and
beta, and its backward runs the forward kernel again with `keep=True` and no
O (the states and Tm, 134 MB each a layer at the cell's shape, alive while
that layer's backward runs), then the reverse pass.  `.keeping` is the same
forward handing out O, the states and Tm of ONE launch, and `.from_saved`
launches nothing forward and differentiates as the reverse pass over them:
the op `gated_delta_rule` keeps the three beside its output
(`ctx.keep_for_grad`), which saves the second forward (6.65 ms a layer of
`qwen3next_train_t8192`) for 0.27 GB a layer held from the forward to the
backward (the step's AOT `peak_bytes`: 14.19 GB plain, 13.37 re-made, 13.80
kept; PERF.md, PR 49).

The cumulated log-decay gamma (a cumsum over [B, Hv, T] float32) and its
way back to d g (a reversed one) are XLA's; the gates travel as rows of one
[8, C] float32 tile a (key head, chunk): gamma of the G value heads, then
beta.  A row becomes the column a [C, d] tile wants on the diagonal of a
[C, C] tile (`_turned`, flash_attention.py `_column_as_row`'s way).

Precision is the configuration's: the state, the gates, every decay and the
inverse are float32 and every product of float32 tiles runs at HIGHEST
(`#tpu.contract_precision<fp32>`); the two score products take q and k in
their own dtype with float32 accumulation, as the plain emission does.
"""

from __future__ import annotations

import functools

FWD, BWD = "gated_delta_fwd", "gated_delta_bwd"
LANES = 128
GATE_ROWS = 8       # a sublane tile of float32: gamma and beta of G <= 4

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def usable(T: int, chunk: int, Dk: int, Dv: int, dtype,
           group: int = 1) -> bool:
    """The kernels take q, k [B, Hk, T, Dk] and v [B, Hk, G, T, Dv] in bf16
    or float32 where Dk, Dv and the chunk are whole lane tiles, the chunk
    divides T and a key head's gates fit one [8, C] tile."""
    if str(dtype) not in ("bfloat16", "float32"):
        return False
    if min(T, chunk, Dk, Dv, group) < 1 or 2 * group > GATE_ROWS:
        return False
    return not (Dk % LANES or Dv % LANES or chunk % LANES or T % chunk)


def _product(a, b, dims=_NN):
    """A product of float32 tiles at HIGHEST precision -> float32."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _scores(a, b):
    """a b^T for the chunk's q or k tiles in their own dtype (bf16: one
    pass; float32: HIGHEST), float32 out."""
    import jax
    import jax.numpy as jnp

    if a.dtype == jnp.float32:
        return _product(a, b, _NT)
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def unit_lower_inverse(a):
    """a [n, n] strictly lower triangular float32 -> (I - a)^-1 as the
    product (I + a)(I + a^2)(I + a^4)... of the nilpotent a's powers, every
    product at HIGHEST: `_unit_lower_inverse` (sparse_linear_ops) on a tile
    in VMEM.  2 log2(n) - 2 products: splitting in halves (X21 = X22 A21
    X11) down to blocks of 64 or 32 rows does fewer multiply-adds and read
    8.67 and 11.56 ms a forward call for 7.16 (PERF.md, PR 49): on this MXU
    a product of small blocks costs a weight tile's load whatever its
    rows."""
    n = a.shape[0]
    x = a + _eye(n).astype(a.dtype)
    power, reach = a, 2
    while reach < n:
        power = _product(power, power)
        x = x + _product(x, power)
        reach *= 2
    return x


def _iota(n, axis):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, (n, n), axis)


def _eye(n):
    return _iota(n, 0) == _iota(n, 1)


def _turned(vector, axis):
    """A [1, n] lane row as the [n, 1] column (`axis` 1), a column as the
    row (`axis` 0): set on the diagonal of an [n, n] tile and summed along
    `axis` (exact: one term a sum)."""
    import jax.numpy as jnp

    n = max(vector.shape)
    return jnp.sum(jnp.where(_eye(n), jnp.broadcast_to(vector, (n, n)), 0.0),
                   axis=axis, keepdims=True)


def _as_column(row):
    return _turned(row, 1)


def _as_row(col):
    return _turned(col, 0)


def _last(row):
    """The last entry of a [1, n] row, [1, 1]."""
    import jax
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _chunk_gates(gates_ref, g, G):
    """What value head g's tiles of the chunk are made with: (beta, e^gamma
    and e^{gamma_C - gamma} as [C, 1] columns, gamma_C [1, 1], the decay
    e^{gamma_i - gamma_j} where i >= j and 0 elsewhere)."""
    import jax.numpy as jnp

    gam_r = gates_ref[g:g + 1, :]
    C = gam_r.shape[1]
    gam_c = _as_column(gam_r)
    beta_c = _as_column(gates_ref[G + g:G + g + 1, :])
    lower = _iota(C, 0) >= _iota(C, 1)
    decay = jnp.where(lower, jnp.exp(jnp.minimum(gam_c - gam_r, 0.0)), 0.0)
    last = _last(gam_r)
    return beta_c, jnp.exp(gam_c), jnp.exp(last - gam_c), last, decay


def _fwd_body(q_ref, k_ref, v_ref, gates_ref, *rest, G, emit_out, keep):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    rest = list(rest)
    s_scr = rest.pop()
    o_ref = rest.pop(0) if emit_out else None
    s_ref, tm_ref = rest if keep else (None, None)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    q, k = q_ref[...], k_ref[...]
    C = q.shape[0]
    kk = _scores(k, k)
    qk = _scores(q, k) if emit_out else None
    qf, kf = q.astype(f32), k.astype(f32)
    strict = _iota(C, 0) > _iota(C, 1)
    for g in range(G):
        beta_c, eg, ek, last, decay = _chunk_gates(gates_ref, g, G)
        a = jnp.where(strict, -(beta_c * kk) * decay, 0.0)
        tm = unit_lower_inverse(a)
        s = s_scr[g]
        inner = _product(tm, beta_c * (v_ref[g].astype(f32)
                                       - eg * _product(kf, s)))
        if emit_out:
            o_ref[g] = (_product(qf * eg, s)
                        + _product(qk * decay, inner))
        if keep:
            s_ref[g] = s
            tm_ref[g] = tm
        s_scr[g] = jnp.exp(last) * s + _product(kf * ek, inner, _TN)


def _bwd_body(q_ref, k_ref, v_ref, gates_ref, do_ref, s_ref, tm_ref,
              dq_ref, dk_ref, dv_ref, dgates_ref, ds_scr, *, G):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    q, k = q_ref[...], k_ref[...]
    C = q.shape[0]
    kk, qk = _scores(k, k), _scores(q, k)
    qf, kf = q.astype(f32), k.astype(f32)
    strict, lower = (_iota(C, 0) > _iota(C, 1), _iota(C, 0) >= _iota(C, 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)      # noqa: E731
    colsum = lambda x: jnp.sum(x, axis=0, keepdims=True)      # noqa: E731
    dq = dk = dqk = dkk = None
    dgam, dbeta = [], []
    for g in range(G):
        beta_c, eg, ek, last, decay = _chunk_gates(gates_ref, g, G)
        a = jnp.where(strict, -(beta_c * kk) * decay, 0.0)
        p = qk * decay
        s, ds, tm, do = s_ref[g], ds_scr[g], tm_ref[g], do_ref[g]
        m = _product(kf, s)
        z = v_ref[g].astype(f32) - eg * m
        inner = _product(tm, beta_c * z)
        qt, kt, egc = qf * eg, kf * ek, jnp.exp(last)
        # S' = e^{gamma_C} S + K~^T V'
        dkt = _product(inner, ds, _NT)
        dinner = _product(kt, ds) + _product(p, do, _TN)
        # O = Q~ S + P V'
        dqt = _product(do, s, _NT)
        dp = jnp.where(lower, _product(do, inner, _NT), 0.0)
        # V' = Tm R; R = beta (V - e^gamma M); M = K S
        dr = _product(tm, dinner, _TN)
        da = jnp.where(strict, _product(dr, inner, _NT), 0.0)
        dz = beta_c * dr
        dm = -eg * dz
        dv_ref[g] = dz.astype(dv_ref.dtype)
        ds_scr[g] = (egc * ds + _product(qt, do, _TN)
                     + _product(kf, dm, _TN))
        # the rows' scalars: gamma through e^gamma, e^{gamma_C - gamma},
        # e^{gamma_C} and the decay mask, beta through R and A
        to_end = rowsum(dkt * kt)
        e = dp * p + da * a
        dgam_c = (rowsum(e) + rowsum(dqt * qt) - to_end
                  - eg * rowsum(dz * m))
        dgam_r = (_as_row(dgam_c) - colsum(e) + jnp.where(
            lane == C - 1,
            colsum(to_end) + egc * colsum(rowsum(ds * s)), 0.0))
        dgam.append(dgam_r)
        dbeta.append(_as_row(rowsum(dr * z) - rowsum(da * kk * decay)))
        part = (dqt * eg, _product(dm, s, _NT) + dkt * ek, dp * decay,
                -(beta_c * da) * decay)
        dq, dk, dqk, dkk = part if g == 0 else (
            x + y for x, y in zip((dq, dk, dqk, dkk), part))
    dq_ref[...] = (dq + _product(dqk, kf)).astype(dq_ref.dtype)
    dk_ref[...] = (dk + _product(dqk, qf, _TN) + _product(dkk, kf)
                   + _product(dkk, kf, _TN)).astype(dk_ref.dtype)
    pad = dgates_ref.shape[0] - 2 * G
    rows = dgam + dbeta + ([jnp.zeros((pad, C), f32)] if pad else [])
    dgates_ref[...] = jnp.concatenate(rows, axis=0)


@functools.lru_cache(maxsize=None)
def _calls(BH, G, T, C, Dk, Dv, dtype, interpret):
    """(forward, forward that also keeps the states and Tm, forward that
    keeps them and writes no O, backward) on q, k [BH, T, Dk], v [BH, G, T,
    Dv], gates [BH, N, 8, C]; memoized and jitted, so every layer of a
    model shares one trace of each body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N = T // C
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct

    def specs(at):
        """The chunk `at(n)`'s blocks of q or k, of v or O, of the gates,
        and of the states or Tm."""
        return (pl.BlockSpec((None, C, Dk), lambda b, n: (b, at(n), 0)),
                pl.BlockSpec((None, G, C, Dv), lambda b, n: (b, 0, at(n), 0)),
                pl.BlockSpec((None, None, GATE_ROWS, C),
                             lambda b, n: (b, at(n), 0, 0)),
                lambda r, c: pl.BlockSpec(
                    (None, G, None, r, c), lambda b, n: (b, 0, at(n), 0, 0)))

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    qk, vo, gates, per_chunk = specs(lambda n: n)
    out = (vo, sds((BH, G, T, Dv), f32))
    kept = ((per_chunk(Dk, Dv), sds((BH, G, N, Dk, Dv), f32)),
            (per_chunk(C, C), sds((BH, G, N, C, C), f32)))

    def forward(emit_out, keep):
        outs = ((out,) if emit_out else ()) + (kept if keep else ())
        return jax.jit(pl.pallas_call(
            functools.partial(_fwd_body, G=G, emit_out=emit_out, keep=keep),
            grid=(BH, N),
            in_specs=[qk, qk, vo, gates],
            out_specs=[spec for spec, _ in outs],
            out_shape=[shape for _, shape in outs],
            scratch_shapes=[pltpu.VMEM((G, Dk, Dv), f32)],
            compiler_params=params, name=FWD, interpret=interpret))

    # the reverse pass walks the chunks from the last to the first
    rqk, rvo, rgates, rchunk = specs(lambda n: N - 1 - n)
    backward = jax.jit(pl.pallas_call(
        functools.partial(_bwd_body, G=G),
        grid=(BH, N),
        in_specs=[rqk, rqk, rvo, rgates, rvo, rchunk(Dk, Dv), rchunk(C, C)],
        out_specs=[rqk, rqk, rvo, rgates],
        out_shape=[sds((BH, T, Dk), dtype), sds((BH, T, Dk), dtype),
                   sds((BH, G, T, Dv), dtype),
                   sds((BH, N, GATE_ROWS, C), f32)],
        scratch_shapes=[pltpu.VMEM((G, Dk, Dv), f32)],
        compiler_params=params, name=BWD, interpret=interpret))
    return (forward(True, False), forward(True, True), forward(False, True),
            backward)


def _prepared(q, k, v, g, beta, chunk, interpret):
    """The three calls and their operands: q, k [BH, T, Dk], v [BH, G, T,
    Dv] and the gates' tiles [BH, N, 8, C] (rows gamma of the G value
    heads, the cumulated log-decay inside a chunk, then beta)."""
    import jax.numpy as jnp

    B, Hk, T, Dk = q.shape
    G, Dv = v.shape[2], v.shape[-1]
    C = min(int(chunk), T)
    if T % C or 2 * G > GATE_ROWS:
        raise ValueError(f"gated delta kernels: {T} tokens in chunks of {C}, "
                         f"{G} value heads a key head")
    N = T // C
    rows = lambda a: jnp.moveaxis(                            # noqa: E731
        a.astype(jnp.float32).reshape(B * Hk, G, N, C), 1, 2)
    gates = jnp.concatenate(
        [jnp.cumsum(rows(g), axis=-1), rows(beta),
         jnp.zeros((B * Hk, N, GATE_ROWS - 2 * G, C), jnp.float32)], axis=2)
    calls = _calls(B * Hk, G, T, C, Dk, Dv, str(q.dtype), interpret)
    return calls, (q.reshape(B * Hk, T, Dk), k.reshape(B * Hk, T, Dk),
                   v.reshape(B * Hk, G, T, Dv), gates)


def gated_delta_fwd(q, k, v, g, beta, chunk, *, keep=False, interpret=False):
    """`gated_delta_chunked`'s operands -> O [B, Hk, G, T, Dv] float32;
    with `keep` (O, (every chunk's incoming state, its Tm)), what
    `gated_delta_bwd` takes as `kept`."""
    calls, operands = _prepared(q, k, v, g, beta, chunk, interpret)
    out, *kept = calls[1 if keep else 0](*operands)
    out = out.reshape(v.shape)
    return (out, tuple(kept)) if keep else out


def gated_delta_bwd(do, q, k, v, g, beta, chunk, kept=None, *,
                    interpret=False):
    """dO [B, Hk, G, T, Dv] and the forward's operands -> (dq, dk, dv in
    their operands' dtypes, dg, dbeta float32): the reverse pass over the
    states and Tm the forward `kept`, made by the forward kernel again (no
    O) where it kept none."""
    import jax.numpy as jnp

    (_, _, remake, bwd), operands = _prepared(q, k, v, g, beta, chunk,
                                              interpret)
    states, tm = remake(*operands) if kept is None else kept
    dq, dk, dv, dgates = bwd(
        *operands, do.astype(jnp.float32).reshape(operands[2].shape), states,
        tm)
    G = v.shape[2]
    rows = lambda a: jnp.moveaxis(a, 2, 1).reshape(g.shape)   # noqa: E731
    # gamma is a chunk's cumsum of g: token j's g reaches gamma_j .. gamma_C
    dgam = dgates[:, :, :G]
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgam, -1), axis=-1), -1)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            rows(dg).astype(g.dtype), rows(dgates[:, :, G:2 * G]).astype(
                beta.dtype))


@functools.lru_cache(maxsize=None)
def make_gated_delta(chunk: int, interpret: bool = False):
    """The scan (q, k, v, g, beta) -> O as a `kernel_pair` (_common.py: the
    differentiable pair, `.keeping -> (O, states, Tm)`, `.from_saved(...,
    O, states, Tm)`), memoized a chunk so that every trace meets the same
    function.  Under the plain rule the forward keeps nothing and the
    backward makes the states and Tm again."""
    from ._common import kernel_pair

    def forward(*ops, keep=False):
        got = gated_delta_fwd(*ops, chunk, keep=keep, interpret=interpret)
        return (got[0], *got[1]) if keep else (got,)

    return kernel_pair(
        5, lambda *ops: forward(*ops)[0], forward,
        lambda ops, do, kept: gated_delta_bwd(
            do, *ops, chunk, kept[1:] or None, interpret=interpret))
