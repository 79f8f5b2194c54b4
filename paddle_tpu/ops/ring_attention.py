"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

Beyond-reference capability (the 2018 reference predates attention — its
long-sequence story was LoD ragged tensors, SURVEY.md §5; modern long-context
needs the sequence axis *sharded*).  Implementation follows the ring-attention
pattern (PAPERS.md / scaling-book): Q, K, V are sharded along the sequence
axis across 'sp' devices; each device holds its Q chunk, and K/V chunks rotate
around the ring via `lax.ppermute` (ICI neighbor exchange) while a streaming
(flash-style) online softmax accumulates — max `m`, normalizer `l`, and
output `o` — so the full [T,T] score matrix never materializes and memory per
chip is O(T/S · D + (T/S)²).

`ring_attention` is pure JAX (usable directly under pjit/shard_map);
`attention` is the dense single-device reference the tests compare against.
"""

from __future__ import annotations

import functools
from typing import Optional


def attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
              allowed=None):
    """Dense reference: q,k,v [B, H, T, D] → [B, H, T, D]; under the causal
    mask, or under `allowed`, a boolean [T, T]."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    if causal:
        T = q.shape[2]
        allowed = jnp.tril(jnp.ones((T, T), dtype=bool))
    if allowed is not None:
        logits = jnp.where(allowed[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _ring_body(q, k, v, axis_name: str, causal: bool, scale: float):
    """Per-shard ring loop: local q [B,H,t,D]; k/v chunks rotate."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, H, t, D = q.shape
    S = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)

    qs = q * scale
    # derive accumulators from q so they carry the same device-varying type
    # as the rotating k/v (shard_map vma typing)
    zero = (qs[..., 0] * 0.0).astype(jnp.float32)
    m = zero - 1e30
    l = zero
    o = (qs * 0.0).astype(jnp.float32)

    def step(carry, s):
        m, l, o, k_cur, v_cur = carry
        # ppermute sends i -> i+1, so after s hops we hold chunk (my - s)
        src_chunk = (my - s) % S
        logits = jnp.einsum("bhqd,bhkd->bhqk", qs, k_cur).astype(jnp.float32)
        if causal:
            q_pos = my * t + jnp.arange(t)
            k_pos = src_chunk * t + jnp.arange(t)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * correction + p.sum(axis=-1)
        o_new = o * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        # rotate k/v to the next device on the ring (ICI neighbor hop)
        perm = [(i, (i + 1) % S) for i in range(S)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, o_new, k_nxt, v_nxt), None

    (m, l, o, _, _), _ = lax.scan(step, (m, l, o, k, v), jnp.arange(S))
    return (o / l[..., None]).astype(q.dtype)


def _ring_flash_fwd(q, k, v, axis_name: str, S: int, scale: float,
                    causal: bool, interpret: bool):
    """Ring loop whose per-chunk attention runs the Pallas flash kernel
    (VMEM-tiled online softmax — the [t,t] score block never touches
    HBM).  Each step yields the chunk's normalized output plus its
    logsumexp; chunks merge exactly via the standard attention-merge
    identity  o = Σ_s o_s · exp(lse_s − lse_tot),  lse_tot = ⊕ lse_s.

    Causal under SPMD: the kernel's causal flag is static, but whether
    the held chunk is past/diagonal/future depends on the traced
    axis_index.  The ring schedule resolves it statically per STEP: after
    s hops a device holds chunk (my − s) mod S, which is the diagonal iff
    s == 0 (causal kernel), strictly past iff my >= s (full kernel), and
    otherwise future — masked out by forcing its lse to −inf, so the
    merge weight exp(lse_s − lse_tot) is exactly 0.  Future chunks still
    run the (discarded) kernel: one SPMD program, no divergent control
    flow; the cost is the standard unbalanced-causal-ring compute bubble.

    Unrolled python loop (S is the static mesh-axis size): one kernel
    launch + one ppermute hop per step.  Returns (out, lse_tot) — the
    residuals the ring-level custom_vjp needs."""
    import jax.numpy as jnp
    from jax import lax

    from .pallas_kernels import flash_attention as fa

    my = lax.axis_index(axis_name)
    o_acc = jnp.zeros(q.shape, jnp.float32)
    lse_acc = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % S) for i in range(S)]
    for s in range(S):
        out_s, lse_s = fa.flash_attention_fwd(
            q, k_cur, v_cur, causal=causal and s == 0, scale=scale,
            interpret=interpret)
        lse_s = lse_s.reshape(lse_acc.shape).astype(jnp.float32)
        if causal and s > 0:
            lse_s = jnp.where(my >= s, lse_s, -jnp.inf)
        lse_new = jnp.logaddexp(lse_acc, lse_s)
        o_acc = (o_acc * jnp.exp(lse_acc - lse_new)[..., None]
                 + out_s.astype(jnp.float32)
                 * jnp.exp(lse_s - lse_new)[..., None])
        lse_acc = lse_new
        if s < S - 1:  # the final hop's result would be discarded
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return o_acc.astype(q.dtype), lse_acc


def _ring_flash_bwd(q, k, v, out, lse, do, axis_name: str, S: int,
                    scale: float, causal: bool, interpret: bool):
    """Ring backward: dk/dv accumulators ROTATE WITH their k/v chunks, so
    after S hops each chunk's gradient has collected every device's
    contribution and is home again.  Per step the blockwise flash
    backward runs against the TOTAL logsumexp (and the global
    delta = Σ out·do it derives from `out`), which makes each per-chunk
    p = exp(s − lse_tot) the exact global softmax probability — the same
    identity the forward merge uses, transposed."""
    import jax.numpy as jnp
    from jax import lax

    from .pallas_kernels import flash_attention as fa

    my = lax.axis_index(axis_name)
    dq_acc = jnp.zeros(q.shape, jnp.float32)
    dk_acc = jnp.zeros(k.shape, jnp.float32)
    dv_acc = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % S) for i in range(S)]
    # lse arrives [B,H,t] (merge shape); the kernel wants [B*H, t]
    lse_k = lse.reshape(-1, lse.shape[-1])
    for s in range(S):
        dq_s, dk_s, dv_s = fa.flash_attention_bwd(
            q, k_cur, v_cur, out, lse_k, do,
            causal=causal and s == 0, scale=scale, interpret=interpret)
        if causal and s > 0:
            take = my >= s  # future chunk: no contribution either way
            dq_s = jnp.where(take, dq_s, 0)
            dk_s = jnp.where(take, dk_s, 0)
            dv_s = jnp.where(take, dv_s, 0)
        dq_acc = dq_acc + dq_s.astype(jnp.float32)
        dk_acc = dk_acc + dk_s.astype(jnp.float32)
        dv_acc = dv_acc + dv_s.astype(jnp.float32)
        if s < S - 1:  # k/v's final hop would be discarded...
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        # ...but the GRAD accumulators need all S hops to arrive home
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    return (dq_acc.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


def zigzag_permutation(T: int, S: int):
    """Permutation placing chunk pair (d, 2S-1-d) contiguous for device d
    (T split into 2S half-chunks).  Returns (perm, inv) index arrays:
    x_zig = x[..., perm, :] shards the zigzag layout contiguously;
    x = x_zig[..., inv, :] undoes it."""
    import numpy as np

    t2 = T // (2 * S)
    order = []
    for d in range(S):
        order.extend(range(d * t2, (d + 1) * t2))
        order.extend(range((2 * S - 1 - d) * t2, (2 * S - d) * t2))
    perm = np.asarray(order)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(T)
    return perm, inv


def _ring_flash_zigzag_fwd(q, k, v, axis_name: str, S: int, scale: float,
                           interpret: bool):
    """Load-balanced CAUSAL flash ring over the zigzag layout: device d
    holds half-chunks (d, 2S-1-d) of 2S.  The causal block structure
    collapses to selects, never conditionals or discarded work:

      - (q_early, kv_late_visiting): ALWAYS fully masked — never computed
      - (q_late,  kv_early_visiting): ALWAYS fully attended — one full
        block per step
      - exactly ONE of (q_early, kv_early) / (q_late, kv_late) is live
        per step (s <= d vs s > d): computed as a single full block on
        where-SELECTED operands, accumulated into the matching chunk
      - step 0 adds the two causal diagonals

    Every device therefore does 2S+1 equal-size blocks — the ~2x causal
    utilization fix over the compute-and-mask schedule.  Inference entry;
    training rides make_ring_flash_zigzag_train over the same core."""
    return _ring_flash_zigzag_core(q, k, v, axis_name, S, scale,
                                   interpret)[0]


def _ring_flash_zigzag_core(q, k, v, axis_name, S, scale, interpret):
    import jax.numpy as jnp
    from jax import lax

    from .pallas_kernels import flash_attention as fa

    my = lax.axis_index(axis_name)
    B, H, t2x2, D = q.shape
    t2 = t2x2 // 2
    qe, ql = q[:, :, :t2], q[:, :, t2:]
    kv = jnp.stack([k, v])

    def merge(o_acc, lse_acc, o_s, lse_s):
        lse_s = lse_s.reshape(lse_acc.shape).astype(jnp.float32)
        lse_new = jnp.logaddexp(lse_acc, lse_s)
        o_new = (o_acc * jnp.exp(lse_acc - lse_new)[..., None]
                 + o_s.astype(jnp.float32)
                 * jnp.exp(lse_s - lse_new)[..., None])
        return o_new, lse_new

    acc = {
        "e": (jnp.zeros(qe.shape, jnp.float32),
              jnp.full(qe.shape[:-1], -jnp.inf, jnp.float32)),
        "l": (jnp.zeros(ql.shape, jnp.float32),
              jnp.full(ql.shape[:-1], -jnp.inf, jnp.float32)),
    }
    perm = [(i, (i + 1) % S) for i in range(S)]
    kv_cur = kv
    for s in range(S):
        ke, ve = kv_cur[0, :, :, :t2], kv_cur[1, :, :, :t2]
        kl, vl = kv_cur[0, :, :, t2:], kv_cur[1, :, :, t2:]
        if s == 0:
            o, l_ = fa.flash_attention_fwd(qe, ke, ve, causal=True,
                                           scale=scale, interpret=interpret)
            acc["e"] = merge(*acc["e"], o, l_)
            o, l_ = fa.flash_attention_fwd(ql, kl, vl, causal=True,
                                           scale=scale, interpret=interpret)
            acc["l"] = merge(*acc["l"], o, l_)
        else:
            take_e = my >= s
            q_sel = jnp.where(take_e, qe, ql)
            k_sel = jnp.where(take_e, ke, kl)
            v_sel = jnp.where(take_e, ve, vl)
            o, l_ = fa.flash_attention_fwd(q_sel, k_sel, v_sel,
                                           causal=False, scale=scale,
                                           interpret=interpret)
            l_ = l_.reshape(acc["e"][1].shape)
            acc["e"] = merge(*acc["e"], o,
                             jnp.where(take_e, l_, -jnp.inf))
            acc["l"] = merge(*acc["l"], o,
                             jnp.where(take_e, -jnp.inf, l_))
        o, l_ = fa.flash_attention_fwd(ql, ke, ve, causal=False,
                                       scale=scale, interpret=interpret)
        acc["l"] = merge(*acc["l"], o, l_)
        if s < S - 1:
            kv_cur = lax.ppermute(kv_cur, axis_name, perm)
    out = jnp.concatenate([acc["e"][0], acc["l"][0]], axis=2)
    lse = jnp.concatenate([acc["e"][1], acc["l"][1]], axis=2)
    return out.astype(q.dtype), lse


def _ring_flash_zigzag_bwd(q, k, v, out, lse, do, axis_name, S, scale,
                           interpret):
    """Zigzag backward: the SAME balanced block schedule transposed.  The
    dk/dv accumulator pair rotates with its kv pair (all S hops, arriving
    home); each block's blockwise flash backward runs against the global
    per-chunk logsumexp so per-block p = exp(s - lse_tot) is the exact
    global softmax probability.  The selected block's grads scatter into
    the early/late halves via the same take_e selects as the forward."""
    import jax.numpy as jnp
    from jax import lax

    from .pallas_kernels import flash_attention as fa

    my = lax.axis_index(axis_name)
    B, H, t2x2, D = q.shape
    t2 = t2x2 // 2
    qe, ql = q[:, :, :t2], q[:, :, t2:]
    oe, ol = out[:, :, :t2], out[:, :, t2:]
    doe, dol = do[:, :, :t2], do[:, :, t2:]
    lse_e = lse[:, :, :t2].reshape(B * H, t2)
    lse_l = lse[:, :, t2:].reshape(B * H, t2)
    kv_cur = jnp.stack([k, v])
    dq_e = jnp.zeros(qe.shape, jnp.float32)
    dq_l = jnp.zeros(ql.shape, jnp.float32)
    dkv_acc = jnp.zeros((2,) + k.shape, jnp.float32)  # rotates with kv
    perm = [(i, (i + 1) % S) for i in range(S)]

    def bwd_block(qc, kc, vc, oc, lsec, doc, causal):
        return fa.flash_attention_bwd(qc, kc, vc, oc, lsec, doc,
                                      causal=causal, scale=scale,
                                      interpret=interpret)

    for s in range(S):
        ke, ve = kv_cur[0, :, :, :t2], kv_cur[1, :, :, :t2]
        kl, vl = kv_cur[0, :, :, t2:], kv_cur[1, :, :, t2:]
        dke = jnp.zeros(ke.shape, jnp.float32)
        dve = jnp.zeros(ve.shape, jnp.float32)
        dkl = jnp.zeros(kl.shape, jnp.float32)
        dvl = jnp.zeros(vl.shape, jnp.float32)
        if s == 0:
            dq_s, dk_s, dv_s = bwd_block(qe, ke, ve, oe, lse_e, doe, True)
            dq_e += dq_s.astype(jnp.float32)
            dke += dk_s.astype(jnp.float32)
            dve += dv_s.astype(jnp.float32)
            dq_s, dk_s, dv_s = bwd_block(ql, kl, vl, ol, lse_l, dol, True)
            dq_l += dq_s.astype(jnp.float32)
            dkl += dk_s.astype(jnp.float32)
            dvl += dv_s.astype(jnp.float32)
        else:
            take_e = my >= s
            q_sel = jnp.where(take_e, qe, ql)
            k_sel = jnp.where(take_e, ke, kl)
            v_sel = jnp.where(take_e, ve, vl)
            o_sel = jnp.where(take_e, oe, ol)
            do_sel = jnp.where(take_e, doe, dol)
            lse_sel = jnp.where(take_e, lse_e, lse_l)
            dq_s, dk_s, dv_s = bwd_block(q_sel, k_sel, v_sel, o_sel,
                                         lse_sel, do_sel, False)
            dq_e += jnp.where(take_e, dq_s, 0).astype(jnp.float32)
            dq_l += jnp.where(take_e, 0, dq_s).astype(jnp.float32)
            dke += jnp.where(take_e, dk_s, 0).astype(jnp.float32)
            dve += jnp.where(take_e, dv_s, 0).astype(jnp.float32)
            dkl += jnp.where(take_e, 0, dk_s).astype(jnp.float32)
            dvl += jnp.where(take_e, 0, dv_s).astype(jnp.float32)
        dq_s, dk_s, dv_s = bwd_block(ql, ke, ve, ol, lse_l, dol, False)
        dq_l += dq_s.astype(jnp.float32)
        dke += dk_s.astype(jnp.float32)
        dve += dv_s.astype(jnp.float32)
        step = jnp.stack([jnp.concatenate([dke, dkl], axis=2),
                          jnp.concatenate([dve, dvl], axis=2)])
        dkv_acc = dkv_acc + step
        if s < S - 1:
            kv_cur = lax.ppermute(kv_cur, axis_name, perm)
        dkv_acc = lax.ppermute(dkv_acc, axis_name, perm)
    dq = jnp.concatenate([dq_e, dq_l], axis=2)
    return (dq.astype(q.dtype), dkv_acc[0].astype(k.dtype),
            dkv_acc[1].astype(v.dtype))


_ZIGZAG_TRAIN_CACHE = {}


def make_ring_flash_zigzag_train(axis_name: str, S: int, scale: float,
                                 interpret: bool = False):
    """Ring-level custom_vjp for the BALANCED causal schedule: training
    does 2S+1 equal blocks per device in fwd AND bwd (vs the plain
    schedule's compute-and-discard).  Operates on zigzag-laid-out shards
    (see zigzag_permutation); memoized per config."""
    key = (axis_name, S, scale, interpret)
    cached = _ZIGZAG_TRAIN_CACHE.get(key)
    if cached is not None:
        return cached
    import jax

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _ring_flash_zigzag_core(q, k, v, axis_name, S, scale,
                                         interpret)
        return out

    def fwd(q, k, v):
        out, lse = _ring_flash_zigzag_core(q, k, v, axis_name, S, scale,
                                           interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return _ring_flash_zigzag_bwd(q, k, v, out, lse, do, axis_name,
                                      S, scale, interpret)

    f.defvjp(fwd, bwd)
    _ZIGZAG_TRAIN_CACHE[key] = f
    return f


_RING_TRAIN_CACHE = {}


def make_ring_flash_train(axis_name: str, S: int, causal: bool,
                          scale: float, interpret: bool = False):
    """Ring-LEVEL custom_vjp (per-shard, applied inside shard_map): the
    kernel-level wrapper can't ride the ring because the merge needs each
    step's lse.  Memoized per config so jit's function-identity caching
    holds across traces."""
    key = (axis_name, S, causal, scale, interpret)
    cached = _RING_TRAIN_CACHE.get(key)
    if cached is not None:
        return cached
    import jax

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _ring_flash_fwd(q, k, v, axis_name, S, scale, causal,
                                 interpret)
        return out

    def fwd(q, k, v):
        out, lse = _ring_flash_fwd(q, k, v, axis_name, S, scale, causal,
                                   interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return _ring_flash_bwd(q, k, v, out, lse, do, axis_name, S, scale,
                               causal, interpret)

    f.defvjp(fwd, bwd)
    _RING_TRAIN_CACHE[key] = f
    return f


def flash_ring_eligible(q, mesh, axis_name: str, causal: bool,
                        is_train: bool) -> bool:
    """Static gate for the flash-kernel ring path: lane-width head dim
    and 128-tile chunks.  Causal rides the per-step static schedule
    (diagonal at s=0, past for my >= s, future lse-masked) and training
    rides the ring-level custom_vjp (_ring_flash_bwd) — both supported
    since r4; `causal`/`is_train` remain parameters so callers keep a
    single gate call site."""
    del causal, is_train  # supported; kept for call-site stability
    from .pallas_kernels._common import kernels_enabled

    from ..mesh import axis_size

    if not kernels_enabled():
        return False
    S = axis_size(mesh, axis_name)
    B, H, T, D = q.shape
    t = T // S
    return D <= 128 and t % 128 == 0


def ring_attention(q, k, v, mesh, axis_name: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   use_flash: bool = False, is_train: bool = False,
                   schedule: str = "plain", pre_permuted: bool = False,
                   interpret: bool = False):
    """q,k,v [B,H,T,D] (T divisible by mesh['sp']) → [B,H,T,D], computed with
    the sequence axis sharded over `axis_name`.  `use_flash=True` (gate
    with flash_ring_eligible) runs each per-chunk attention as a Pallas
    flash kernel and merges chunks by logsumexp — including causal (per-
    step static schedule) and training (`is_train=True`: the ring-level
    custom_vjp whose backward rotates dk/dv with their chunks).

    `schedule="zigzag"` (causal flash, inference AND training) runs the
    load-balanced zigzag schedule: inputs are permuted so each device
    holds one early + one late half-chunk, making per-device work equal
    (2S+1 blocks, fwd and bwd) where the plain causal ring discards half
    its compute on average.  The
    in/out permutations are global gathers (a reshard each) — amortize
    them across a multi-layer stack by permuting activations ONCE with
    `zigzag_permutation` and passing `pre_permuted=True` per layer."""
    import jax

    from jax import shard_map

    from ..mesh import pspec as P

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    spec = P(None, None, axis_name, None)
    zigzag = schedule == "zigzag"
    if zigzag and not (use_flash and causal):
        raise ValueError(
            "schedule='zigzag' supports causal flash attention "
            "(use_flash=True, causal=True)")
    if use_flash:
        from ..mesh import axis_size

        S = axis_size(mesh, axis_name)
        if zigzag:
            import jax.numpy as jnp

            T = q.shape[2]
            if T % (2 * S):
                raise ValueError(
                    f"zigzag needs T divisible by 2*S ({T} vs {2 * S})")
            t2 = T // (2 * S)
            if t2 > 128 and t2 % 128:
                # the flash kernel tiles at 128 (or one whole block for
                # short chunks); fail here with a readable contract error
                # rather than deep inside the pallas wrapper
                raise ValueError(
                    f"zigzag half-chunks of {t2} steps break the flash "
                    f"kernel's 128-tile contract (T={T}, S={S}): use T "
                    f"with T/(2S) a multiple of 128, or <= 128")
            if is_train:
                body = make_ring_flash_zigzag_train(axis_name, S, s,
                                                    interpret=interpret)
            else:
                body = functools.partial(_ring_flash_zigzag_fwd,
                                         axis_name=axis_name, S=S,
                                         scale=s, interpret=interpret)
            fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
            if pre_permuted:  # caller laid out zigzag once for the stack
                return fn(q, k, v)
            perm, inv = zigzag_permutation(T, S)
            out = fn(jnp.take(q, perm, axis=2), jnp.take(k, perm, axis=2),
                     jnp.take(v, perm, axis=2))
            return jnp.take(out, inv, axis=2)
        if is_train:
            body = make_ring_flash_train(axis_name, S, causal, s,
                                         interpret=interpret)
        else:
            def body(q, k, v):
                return _ring_flash_fwd(q, k, v, axis_name, S, s, causal,
                                       interpret)[0]
    else:
        body = functools.partial(_ring_body, axis_name=axis_name,
                                 causal=causal, scale=s)
    kw = {}
    if use_flash:
        # pallas_call out_shapes carry no vma annotation; disable the
        # shard_map replication check for the kernel path
        kw["check_vma"] = False
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        **kw,
    )
    return fn(q, k, v)


def _ulysses_body(q, k, v, axis_name: str, causal: bool, scale,
                  use_flash: bool = False, is_train: bool = False,
                  interpret: bool = False):
    """Per-shard Ulysses step: inputs arrive seq-sharded [B, H, t, D];
    all_to_all re-shards to head-sharded [B, H/S, T, D], attention runs
    dense over the FULL sequence locally, and a second all_to_all restores
    seq sharding.  One collective pair per layer (vs the ring's S hops) —
    the better trade when H >= S and T/S chunks are small.

    Because the local attention is FULL attention over the whole sequence,
    the Pallas flash kernel drops in unchanged — including the training
    custom_vjp pair (no cross-chunk merge to thread lse through)."""
    from jax import lax

    # [B, H, t, D] --split heads/concat seq--> [B, H/S, S*t, D]
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    if use_flash:
        from .pallas_kernels import flash_attention as fa

        if is_train:
            oh = fa.make_flash_train(causal=causal, scale=scale,
                                     interpret=interpret)(qh, kh, vh)
        else:
            oh = fa.flash_attention(qh, kh, vh, causal=causal, scale=scale,
                                    interpret=interpret)
    else:
        oh = attention(qh, kh, vh, causal=causal, scale=scale)
    # back: split seq, concat heads
    return lax.all_to_all(oh, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def flash_ulysses_eligible(q, mesh, axis_name: str) -> bool:
    """Static gate for flash-kernel Ulysses: after the head re-shard the
    local problem is full [B, H/S, T, D] attention, so the kernel's
    contract is just T % 128 == 0 and lane-width D (training included)."""
    from .pallas_kernels._common import kernels_enabled

    from ..mesh import axis_size

    if not kernels_enabled():
        return False
    B, H, T, D = q.shape
    return H % axis_size(mesh, axis_name) == 0 and T % 128 == 0 and D <= 128


def ulysses_attention(q, k, v, mesh, axis_name: str = "sp",
                      causal: bool = False, scale: Optional[float] = None,
                      use_flash: bool = False, is_train: bool = False,
                      interpret: bool = False):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism:
    q,k,v [B,H,T,D] with T divisible by mesh[axis_name] and H divisible by
    mesh[axis_name] → [B,H,T,D].  Numerically identical to dense attention
    (it IS dense attention, re-sharded head-wise).  `use_flash=True` (gate
    with flash_ulysses_eligible) runs the local attention as the Pallas
    flash kernel — the training custom_vjp pair when `is_train`."""
    import functools

    from jax import shard_map

    from ..mesh import axis_size, pspec as P

    S = axis_size(mesh, axis_name)
    if q.shape[1] % S:
        raise ValueError(
            f"ulysses attention: head count {q.shape[1]} must be a "
            f"multiple of the {axis_name!r} axis size {S}")
    if q.shape[2] % S:
        raise ValueError(
            f"ulysses attention: sequence length {q.shape[2]} must be a "
            f"multiple of the {axis_name!r} axis size {S}")
    spec = P(None, None, axis_name, None)
    kw = {"check_vma": False} if use_flash else {}
    fn = shard_map(
        functools.partial(_ulysses_body, axis_name=axis_name, causal=causal,
                          scale=scale, use_flash=use_flash,
                          is_train=is_train, interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        **kw,
    )
    return fn(q, k, v)
