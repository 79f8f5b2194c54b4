"""Flash attention as a Pallas TPU kernel.

Single-chip fused attention: never materializes the [T,T] score matrix in
HBM.  Grid over (batch*heads, Tq/BQ, Tk/BK) with the K/V walk as the
INNERMOST grid dimension so the Pallas pipeline double-buffers the K/V
block DMAs against the MXU GEMMs (the r4 first-contact lesson: a
fori_loop over one VMEM-resident [T,D] K/V block compiles but runs at
0.7x of dense XLA attention — no DMA/compute overlap).  The online
softmax (running max m, normalizer l, unnormalized accumulator) lives in
VMEM scratch, initialized at the first K block and finalized into the
output block at the last.  Under causal masking the K/V index maps CLAMP
to the diagonal block so fully-masked future blocks are never fetched,
and `pl.when` skips their compute.

The logsumexp residual rides a (1, 1, T) full-row block: Mosaic's tile
contract wants the last two block dims (8,128)-divisible or equal to the
array's — a (1, bq) block over a (BH, T) array satisfies neither (first
real Mosaic compile, r4 kernels microbench).

Replaces what the reference would have hand-written in paddle/cuda
(SURVEY.md §2.10): the custom-fusion tier under the XLA-generated ops.
"""

from __future__ import annotations

import functools


def _snap_block(block: int, T: int, tile: int = 128) -> int:
    """Largest divisor of T that is <= block AND a multiple of `tile` — the
    requested block size is a performance hint, never a shape constraint
    (a seq len of 1536 must not fail the bk=1024 default — it runs at
    bk=768).  The tile floor enforces the (8,128)-divisible Mosaic block
    contract for every dtype the kernels accept: an unaligned divisor
    (ADVICE r4: T=10880 snapped block_q=512 to 340) would pass tracing
    and fail Mosaic at execution.  Returns 0 when no aligned divisor exists;
    callers raise at trace time, and the dispatch gates (T % 128 == 0 with
    default blocks >= 128) never reach that case."""
    # the 128 floor is deliberately stricter than the (8,128) sublane
    # contract alone: bq also becomes a LANE-dim dynamic-slice offset in
    # the (1,1,T) lse row blocks (pl.ds(qi*bq, bq)), and non-128-aligned
    # lane slices are the r4 "bf16 mask slice" Mosaic failure class — a
    # sublane-only floor (8/16/32) would trade a few grid iterations for
    # that crash on the training path
    b = (min(block, T) // tile) * tile
    while b and T % b:
        b -= tile
    if b:
        return b
    # whole-dimension block: Mosaic accepts block dims EQUAL to the
    # array's (the "or equal" arm of the tile contract) — the path ring
    # attention's zigzag short chunks (t2 <= 128) rely on
    return T if T <= block else 0


def _snap_blocks(block_q: int, block_k: int, T: int,
                 interpret: bool = False):
    """Aligned (bq, bk) for the public kernel entry points, failing with a
    clear Python error at trace time instead of a Mosaic one at run time.
    Interpret mode has no Mosaic tile contract (tests run tiny T/blocks
    there), so it keeps plain largest-divisor snapping.

    The requested blocks resolve through the autotune knob layer
    (paddle_tpu/autotune/knobs.py) at trace time: an active tuning
    trial's override first, then the PADDLE_TPU_FLASH_BQ/BK env vars
    (now VALIDATED — garbage raises a clear error instead of an
    int() traceback, and the values are still clamped to legal aligned
    divisors below), then the persisted winner for this sequence
    length, then the argument defaults.  Winner pickup means a
    `paddle tune` result configures every later trace with no env
    plumbing; the env vars remain the explicit operator override."""
    from ...autotune import knobs

    block_q, block_k = knobs.flash_blocks(block_q, block_k, T)
    tile = 1 if interpret else 128
    bq = _snap_block(block_q, T, tile)
    bk = _snap_block(block_k, T, tile)
    if not bq or not bk:
        raise ValueError(
            f"flash attention needs a 128-aligned divisor of T={T} at or "
            f"under block_q={block_q}/block_k={block_k}; use the dense "
            f"path for this shape")
    return bq, bk


def _causal_kv_idx(bq: int, bk: int):
    """K/V index map that CLAMPS fully-future fetches to the diagonal
    block: the DMA for a skipped block is a re-fetch of an already-
    buffered index (i.e. free), halving HBM traffic under causal.
    Shared by forward and _dq_kernel so the diagonal arithmetic cannot
    drift between them."""
    import jax.numpy as jnp

    def idx(b, i, j):
        return (b, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0)

    return idx


def _fwd_body(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
              scale: float, causal: bool, bq: int, bk: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -1e30, dtype=jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, dtype=jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, dtype=jnp.float32)

    def _compute():
        q = q_ref[0]  # [BQ, D] input dtype — keep bf16 for full-rate MXU
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]
        # bf16 GEMM, f32 accumulate (full-rate MXU), then scale in f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal:
            # position mask is a no-op on fully-past blocks, so apply it
            # unconditionally under causal (straddle-detection is traced)
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
        m_sc[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, None] + pv

    if causal:
        pl.when(kj * bk < (qi + 1) * bq)(_compute)
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / l_sc[...][:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0, pl.ds(qi * bq, bq)] = (
                m_sc[...] + jnp.log(l_sc[...]))


def _fwd_nolse(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, **kw):
    _fwd_body(q_ref, k_ref, v_ref, o_ref, None, m_sc, l_sc, acc_sc, **kw)


def _fwd_grid(B, H, T, D, bq, bk, causal, with_lse, dtype, interpret,
              scale):
    """Shared pallas_call plumbing for the two forward entry points."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nk = T // bk

    if causal:
        kv_idx = _causal_kv_idx(bq, bk)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)

    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), kv_idx),
        pl.BlockSpec((1, bk, D), kv_idx),
    ]
    out_specs = [pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, T, D), dtype)]
    kern = _fwd_body if with_lse else _fwd_nolse
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, T), lambda b, i, j: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32))
    return pl.pallas_call(
        functools.partial(kern, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(B * H, T // bq, nk),
        in_specs=in_specs,
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        # with_lse revisits the SHARED (b,0,0) lse row block across the i
        # dimension — on a Megacore part a "parallel" i could split that
        # block's writeback across cores and clobber slices, so i must be
        # sequential ("arbitrary") whenever the lse output exists
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if with_lse else "parallel",
                "arbitrary")),
        name="flash_fwd",
        interpret=interpret,
    )


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: bool = False):
    """q,k,v [B,H,T,D] → [B,H,T,D]. block_q/block_k are performance hints,
    snapped down to divisors of T; D ≤ 128 recommended (one lane tile)."""
    B, H, T, D = q.shape
    bq, bk = _snap_blocks(block_q, block_k, T, interpret)
    s = scale if scale is not None else 1.0 / (D ** 0.5)

    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    out = _fwd_grid(B, H, T, D, bq, bk, causal, False, q.dtype,
                    interpret, s)(qf, kf, vf)
    return out.reshape(B, H, T, D)


# ---------------------------------------------------------------------------
# Training: forward-with-logsumexp + blockwise backward (FlashAttention-2
# style recompute — P is never materialized in HBM in either direction).


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_sc, *, scale: float, causal: bool, bq: int, bk: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, dtype=jnp.float32)

    def _compute():
        q = q_ref[0]
        do = do_ref[0]  # consumed at v.dtype by the dp GEMM
        # lse/delta arrive as (1, 1, T) full-row blocks (Mosaic tile
        # contract, see module docstring); slice this program's bq rows
        lse = lse_ref[0, 0, pl.ds(qi * bq, bq)]
        delta = delta_ref[0, 0, pl.ds(qi * bq, bq)]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse[:, None])  # true softmax probs via saved lse
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc_sc[...] = acc_sc[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kj * bk < (qi + 1) * bq)(_compute)
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, scale: float,
                causal: bool, bq: int, bk: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, dtype=jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, dtype=jnp.float32)

    def _compute():
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]
        q = q_ref[0]  # [BQ, D]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * bq, bq)]
        delta = delta_ref[0, 0, pl.ds(qi * bq, bq)]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse[:, None])
        dv_sc[...] = dv_sc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[...] = dk_sc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # a q block contributes iff its last row reaches this k block
        pl.when((qi + 1) * bq > kj * bk)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=512,
                        block_k=1024, interpret=False):
    """Forward that also returns the per-row logsumexp (backward residual)."""
    B, H, T, D = q.shape
    bq, bk = _snap_blocks(block_q, block_k, T, interpret)
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    qf, kf, vf = (a.reshape(B * H, T, D) for a in (q, k, v))
    out, lse = _fwd_grid(B, H, T, D, bq, bk, causal, True, q.dtype,
                         interpret, s)(qf, kf, vf)
    return out.reshape(B, H, T, D), lse.reshape(B * H, T)


def flash_attention_bwd(q, k, v, o, lse, do, causal=False, scale=None,
                        block_q=512, block_k=1024, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    bq, bk = _snap_blocks(block_q, block_k, T, interpret)
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    qf, kf, vf, of, dof = (a.reshape(B * H, T, D)
                           for a in (q, k, v, o, do))
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32),
                    axis=-1)  # [BH, T]
    # (BH, 1, T) full-row layout for lse/delta: see module docstring
    lse3 = lse.reshape(B * H, 1, T).astype(jnp.float32)
    delta3 = delta.reshape(B * H, 1, T)
    row_spec = pl.BlockSpec((1, 1, T), lambda b, i, j: (b, 0, 0))

    if causal:
        kv_idx = _causal_kv_idx(bq, bk)

        def q_idx(b, j, i):
            # skip-early clamp: the first q block attending k block j
            return (b, jnp.maximum(i, (j * bk) // bq), 0)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)

        def q_idx(b, j, i):
            return (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=s, causal=causal, bq=bq,
                          bk=bk),
        grid=(B * H, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )(qf, kf, vf, dof, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=s, causal=causal, bq=bq,
                          bk=bk),
        grid=(B * H, T // bk, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_idx),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), q_idx),
            row_spec,
            row_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(qf, kf, vf, dof, lse3, delta3)
    rs = lambda a: a.reshape(B, H, T, D)
    return rs(dq), rs(dk), rs(dv)


_TRAIN_CACHE = {}


def make_flash_train(causal: bool = False, scale=None, interpret=False,
                     block_q: int = 512, block_k: int = 1024):
    """custom_vjp fused attention for TRAINING (honored by generic_grad's
    jax.vjp like the recurrence kernels).  Memoized per
    (causal, scale, interpret, blocks): emitters call this on every trace,
    and a fresh wrapper each time would defeat jit's function-identity
    caching (ADVICE r2).

    The returned function carries the pair a forward op and its grad op
    split between them, so the forward kernel runs once a layer and not
    again when generic_grad re-emits the op under jax.vjp (two Mosaic
    calls are not merged by XLA's CSE the way a re-emitted HLO forward
    is): `.with_lse(q, k, v) -> (out, lse)` is the same forward handing
    out its logsumexp, and `.from_saved(q, k, v, out, lse) -> out`
    launches nothing forward and differentiates as the flash backward on
    the saved pair.  scaled_dot_product_attention uses both."""
    key = (causal, scale, interpret, block_q, block_k)
    cached = _TRAIN_CACHE.get(key)
    if cached is not None:
        return cached
    import jax

    kw = dict(causal=causal, scale=scale, interpret=interpret,
              block_q=block_q, block_k=block_k)

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = flash_attention_fwd(q, k, v, **kw)
        return out

    def fwd(q, k, v):
        out, lse = flash_attention_fwd(q, k, v, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return flash_attention_bwd(q, k, v, out, lse, do, **kw)

    attn.defvjp(fwd, bwd)

    @jax.custom_vjp
    def with_lse(q, k, v):
        return flash_attention_fwd(q, k, v, **kw)

    def with_lse_fwd(q, k, v):
        out, lse = flash_attention_fwd(q, k, v, **kw)
        return (out, lse), (q, k, v, out, lse)

    def with_lse_bwd(res, cts):
        # lse leaves as a residual for `from_saved`, never as a value the
        # loss depends on: its cotangent is dropped
        return bwd(res, cts[0])

    with_lse.defvjp(with_lse_fwd, with_lse_bwd)

    @jax.custom_vjp
    def from_saved(q, k, v, out, lse):
        return out

    def from_saved_fwd(q, k, v, out, lse):
        return out, (q, k, v, out, lse)

    def from_saved_bwd(res, do):
        return bwd(res, do) + (None, None)

    from_saved.defvjp(from_saved_fwd, from_saved_bwd)
    attn.with_lse, attn.from_saved = with_lse, from_saved
    _TRAIN_CACHE[key] = attn
    return attn
