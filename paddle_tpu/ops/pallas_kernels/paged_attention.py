"""Paged (ragged) KV-cache attention for the serving decode step.

The serving tier (paddle_tpu/serving/) keeps every request's K/V in
fixed-size *pages* drawn from one shared pool per layer —
``[num_pages, n_heads, page_size, head_dim]`` — with a per-request page
table mapping logical sequence blocks to physical pages (PAPERS.md
"Ragged Paged Attention": the TPU-native kernel for continuous-batching
inference, where sequence lengths are ragged and pages are recycled as
requests finish).  This module is the attention core over that layout:
one query token per sequence against its own paged, ragged-length
context.

Two implementations behind one contract, mirroring flash_attention.py:

  * ``paged_attention_ref`` — pure JAX.  Gathers the page table into a
    dense ``[N, maxp*page_size, ...]`` view and runs masked softmax
    attention; this materialized gather is exactly the HBM traffic the
    kernel exists to avoid, but it runs everywhere (CPU tier-1 tests,
    sharded meshes) and is the numerical oracle.
  * ``paged_attention`` — Pallas TPU kernel.  The page table and context
    lengths ride scalar prefetch (PrefetchScalarGridSpec) so the BLOCK
    INDEX MAP itself walks the page table: grid step (n, j) DMAs physical
    page ``page_table[n, j]`` directly from the pool in HBM — no gather,
    no copy of the pool.  Pages past a sequence's length clamp to its
    last valid page (the flash-attention re-fetch trick: a repeated index
    is a free DMA) and ``pl.when`` skips their compute.  Online softmax
    (running max / normalizer / f32 accumulator in VMEM scratch) makes
    the page walk single-pass.

Contract (both entry points):
  q          [N, nh, dh]      one query token per sequence slot
  k_pages    [P, nh, ps, dh]  shared K pool (page 0 = reserved null page)
  v_pages    [P, nh, ps, dh]  shared V pool
  page_table [N, maxp] int32  logical block -> physical page; entries
                              beyond a sequence's pages must still be
                              valid pool indices (the allocator keeps
                              them 0, the null page)
  ctx_lens   [N] int32        valid context length per slot, >= 1
  -> [N, nh, dh]

Positions ``j*ps + t >= ctx_lens[n]`` are masked out; the query attends
to exactly the first ``ctx_lens[n]`` cached positions.

A MULTI-QUERY pair (``paged_attention_mq_ref`` / ``paged_attention_mq``)
generalizes the same walk to a Q-block of C rows per slot — the chunked-
prefill and speculative-verify attention, where row c is causally masked
to key positions <= q_starts[n] + c.  Same grid, same clamped page walk;
the scratch holds C rows.  There is ONE kernel body: ``paged_attention``
is the multi-query kernel at C=1 with ``q_starts = ctx_lens - 1``.  A
separate single-row body contracted a rank-2 ``q [nh, dh]`` against the
rank-3 page ``[nh, ps, dh]``, which the Mosaic lowering refuses (a
batched matmul needs a non-contracting dim on both sides).
"""

from __future__ import annotations

import functools


def paged_attention_ref(q, k_pages, v_pages, page_table, ctx_lens,
                        scale=None):
    """Pure-JAX oracle: dense gather + masked softmax.

    Kept numerically in step with transformer_ops._lm_fns.decode_step's
    dense attention (f32 scores, -1e30 mask, softmax back in the value
    dtype) so paged decode can match the contiguous-cache decode
    bit-for-bit on the positions both can express."""
    import jax
    import jax.numpy as jnp

    N, nh, dh = q.shape
    ps = k_pages.shape[2]
    maxp = page_table.shape[1]
    s = scale if scale is not None else 1.0 / (dh ** 0.5)

    def dense(pages):  # [P,nh,ps,dh] -> [N,nh,maxp*ps,dh]
        g = pages[page_table]  # [N,maxp,nh,ps,dh]
        return g.transpose(0, 2, 1, 3, 4).reshape(N, nh, maxp * ps, dh)

    k = dense(k_pages)
    v = dense(v_pages)
    scores = jnp.einsum("bhd,bhkd->bhk", q, k).astype(jnp.float32) * s
    pos = jnp.arange(maxp * ps)[None, None, :]
    scores = jnp.where(pos < ctx_lens[:, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhk,bhkd->bhd", p, v)


def paged_attention_mq_ref(q, k_pages, v_pages, page_table, ctx_lens,
                           q_starts, scale=None):
    """Pure-JAX oracle for the MULTI-QUERY page walk: C query rows per
    slot against the slot's paged context, causally masked per row.

    q          [N, nh, C, dh]   C query positions per slot (heads-major,
                                the layout _lm_fns.block hands attend)
    ctx_lens   [N] int32        TOTAL attended length per slot, >= 1 —
                                keys at positions >= ctx_lens[n] are
                                masked (they may hold garbage)
    q_starts   [N] int32        absolute position of query row 0; row c
                                attends keys at positions <= q_starts+c
    -> [N, nh, C, dh]

    Row c of slot n sees keys {p : p <= q_starts[n]+c and p <
    ctx_lens[n]}.  Rows past a lane's valid chunk (q_starts+c >=
    ctx_lens) still attend at least position 0 (q_starts >= 0,
    ctx_lens >= 1), so no row's softmax normalizer is ever zero —
    their output is garbage-but-finite, exactly like the dense chunk
    path, and callers mask their tokens."""
    import jax
    import jax.numpy as jnp

    N, nh, C, dh = q.shape
    ps = k_pages.shape[2]
    maxp = page_table.shape[1]
    s = scale if scale is not None else 1.0 / (dh ** 0.5)

    def dense(pages):  # [P,nh,ps,dh] -> [N,nh,maxp*ps,dh]
        g = pages[page_table]  # [N,maxp,nh,ps,dh]
        return g.transpose(0, 2, 1, 3, 4).reshape(N, nh, maxp * ps, dh)

    k = dense(k_pages)
    v = dense(v_pages)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * s
    kp = jnp.arange(maxp * ps)[None, None, None, :]
    qp = (q_starts[:, None] + jnp.arange(C)[None, :])[:, None, :, None]
    cl = ctx_lens[:, None, None, None]
    scores = jnp.where((kp <= qp) & (kp < cl), scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _mq_kernel_body(pt_ref, cl_ref, q0_ref, q_ref, k_ref, v_ref, o_ref,
                    m_sc, l_sc, acc_sc, *, scale: float, ps: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -1e30, dtype=jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, dtype=jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, dtype=jnp.float32)

    cl = cl_ref[n]
    q0 = q0_ref[n]
    n_pages = (cl + ps - 1) // ps

    def _compute():
        q = q_ref[0]  # [nh, C, dh] input dtype — full-rate MXU
        k = k_ref[0]  # [nh, ps, dh]
        v = v_ref[0]
        # batched over heads: s[h, c, t] = q[h, c] . k[h, t]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [nh, C, ps]
        kp = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qp = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kp <= qp) & (kp < cl), s, -1e30)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
        m_sc[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # [nh, C, dh]
        acc_sc[...] = acc_sc[...] * corr[..., None] + pv

    pl.when(j < n_pages)(_compute)

    @pl.when(j == nj - 1)
    def _finish():
        # every row attends at least position 0 (q_starts >= 0 and
        # ctx_lens >= 1), so l > 0 row-wise
        o_ref[0] = (acc_sc[...] / l_sc[...][..., None]).astype(o_ref.dtype)


def paged_attention_mq(q, k_pages, v_pages, page_table, ctx_lens, q_starts,
                       scale=None, interpret: bool = False):
    """Pallas MULTI-QUERY paged-attention kernel: the decode kernel's
    ragged page walk with a Q-block of C rows per slot (contract in
    paged_attention_mq_ref).  This is the chunked-prefill / speculative-
    verify step's attention: C positions score against the whole paged
    context in one walk, with NO dense gather of the pool.

    Grid (N, maxp) with the page walk innermost so the pipeline
    double-buffers page DMAs against the MXU GEMMs; the K/V index maps
    read the scalar-prefetched page table, clamping past-the-end steps
    to the sequence's last valid page (free re-fetch, compute skipped)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, nh, C, dh = q.shape
    ps = k_pages.shape[2]
    maxp = page_table.shape[1]
    s = scale if scale is not None else 1.0 / (dh ** 0.5)
    pt = page_table.astype(jnp.int32)
    cl = ctx_lens.astype(jnp.int32)
    q0 = q_starts.astype(jnp.int32)

    def q_idx(n, j, pt_ref, cl_ref, q0_ref):
        return (n, 0, 0, 0)

    def kv_idx(n, j, pt_ref, cl_ref, q0_ref):
        n_pages = (cl_ref[n] + ps - 1) // ps
        return (pt_ref[n, jnp.minimum(j, n_pages - 1)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, maxp),
        in_specs=[
            pl.BlockSpec((1, nh, C, dh), q_idx),
            pl.BlockSpec((1, nh, ps, dh), kv_idx),
            pl.BlockSpec((1, nh, ps, dh), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, nh, C, dh), q_idx),
        scratch_shapes=[
            pltpu.VMEM((nh, C), jnp.float32),
            pltpu.VMEM((nh, C), jnp.float32),
            pltpu.VMEM((nh, C, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mq_kernel_body, scale=s, ps=ps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, nh, C, dh), q.dtype),
        # the page walk accumulates into shared per-n scratch: j must stay
        # sequential; n iterations are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_attention_mq",
        interpret=interpret,
    )(pt, cl, q0, q, k_pages, v_pages)


def paged_attention(q, k_pages, v_pages, page_table, ctx_lens, scale=None,
                    interpret: bool = False):
    """Pallas paged-attention decode kernel (see module docstring): the
    multi-query page walk with one query row per slot, the row sitting at
    the last attended position."""
    import jax.numpy as jnp

    out = paged_attention_mq(
        q[:, :, None, :], k_pages, v_pages, page_table, ctx_lens,
        ctx_lens.astype(jnp.int32) - 1, scale=scale, interpret=interpret)
    return out[:, :, 0, :]


def paged_dispatch_ok(ctx, page_size: int, head_dim: int) -> bool:
    """Serving-kernel gate: the shared Pallas dispatch conditions
    (real TPU, unsharded lowering, kernels enabled) plus this kernel's
    shape contract — lane-width head dim and a page size that fills whole
    sublane tiles for every dtype the pools carry (16 covers f32's 8 and
    bf16's 16).  PADDLE_TPU_NO_PAGED_ATTN=1 disables just this kernel
    (the reference fallback takes over) without blacking out the other
    fused kernels."""
    import os

    from ._common import pallas_dispatch_ok

    return (pallas_dispatch_ok(ctx)
            and not os.environ.get("PADDLE_TPU_NO_PAGED_ATTN")
            and head_dim % 8 == 0 and head_dim <= 128
            and page_size % 16 == 0)
