"""The token mixers of the sparse and linear-attention hybrid decoders
(layers/nn.py `lightning_attention`, `gated_delta_net`,
`kimi_delta_attention`, `block_sparse_attention`, `block_topk_select`;
models/transformer.py `decoder_lm`).

`lightning_attention`: linear attention with a constant decay a head
(Lightning Attention-2, arXiv:2401.04658), run in chunks: O(T C), the
[d, d] state in float32 across chunks, never a T x T matrix.

`gated_delta_rule`: the gated delta rule (Gated DeltaNet,
arXiv:2412.06464), whose state update is data-dependent: S_t = e^{g_t}
S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T.  In chunks: a
unit-lower-triangular inverse inside every chunk and a SEQUENTIAL scan over
the chunks that carries the float32 [d, d] state; never a T x T matrix,
never a state a token.

`kimi_delta_attention`: Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692), the delta rule whose state decays CHANNEL BY CHANNEL: g_t
is a vector over the key axis, S~ = Diag(e^{g_t}) S_{t-1}.  The chunked form
no longer factors through one [C, C] decay mask; `kda_chunked` builds the
two decayed score matrices from pieces whose every exponent is <= 0.

`block_topk_select` and `block_sparse_attention`: InfLLM v2's trainable
sparse attention (arXiv:2506.07900) in its parameter-free form: every
query token chooses `topk` key blocks by the scores of its group's heads
against mean-pooled keys, and attends to the keys of those blocks that
are not after it.  The choice takes no gradient.  On one TPU the attention
runs in the block-sparse flash kernels
(pallas_kernels/sparse_flash.py); everywhere else as dense attention
under the explicit mask.
"""

from __future__ import annotations

import functools

from ..observability.attribution import part_scope
from ..observability.metrics import REGISTRY as _MET
from .llm_ops import causal_taps, head_norm_rope_plain, rms, wide_dtype
from .registry import register_cost, register_op

_MET_LINATTN = _MET.counter(
    "lightning_attention_layers_traced_total",
    "lightning (linear, decayed) attention ops traced (forward emission; "
    "once a compile, not once a step), by the heads the op holds, their "
    "width and the chunk its scan runs in")
_MET_GDN = _MET.counter(
    "gated_delta_layers_traced_total",
    "gated delta-rule ops traced (forward emission; once a compile, not "
    "once a step), by their key heads, value heads, head width, the chunk "
    "the scan runs in and the convolution's taps")
_MET_GDN_KERNELS = _MET.counter(
    "gated_delta_kernels_traced_total",
    "emissions of the gated delta rule's scan (once a compile, not once a "
    "step), by the op that emits it (fwd: gated_delta_rule; grad: its grad "
    "op's re-emission) and the path taken (pallas: the kernel pair of "
    "ops/pallas_kernels/gated_delta.py, a chunk's tiles and the state in "
    "VMEM; xla: gated_delta_chunked as plain jax.numpy under "
    "jax.checkpoint)")
_MET_GDN_CONV = _MET.counter(
    "gated_delta_conv_kernels_traced_total",
    "emissions of the gated delta rule's convolution part (taps, SiLU, the "
    "l2 norm of q and k, the heads' split; once a compile, not once a "
    "step), by the op that emits it (fwd: gated_delta_rule; grad: its grad "
    "op's re-emission) and the path taken (pallas: the kernel pair of "
    "ops/pallas_kernels/gdn_conv.py; xla: short_conv_silu and the plain "
    "norm and split)")
_MET_KDA = _MET.counter(
    "kda_layers_traced_total",
    "Kimi Delta Attention ops traced (forward emission; once a compile, not "
    "once a step), by their heads, head width, the chunk the scan runs in, "
    "the convolutions' taps and the rank of the gate projections")
_MET_SPARSE = _MET.counter(
    "sparse_attention_layers_traced_total",
    "block-sparse attention ops traced (forward emission; once a compile, "
    "not once a step), by the emitter's path (sparse_flash: the kernels "
    "of pallas_kernels/sparse_flash.py; dense_mask: dense attention under "
    "the explicit mask; causal: no selection, the sequence is no longer "
    "than dense_len), query heads, key/value heads and key block")


# ---------------------------------------------------------------------------
# lightning attention


def lightning_slopes(total_heads: int, first: int, count: int, layer: int,
                     total_layers: int) -> list:
    """The decay slopes of heads [first, first + count) of `total_heads`
    in layer `layer` of `total_layers` (Lightning Attention-2 as MiniMax-01
    builds them): s = 2 ** (-8 (head + 1) / heads) * (1 - layer / (layers
    - 1) + 1e-5); a head's decay a token is exp(-s)."""
    return [2.0 ** (-8.0 * (h + 1) / total_heads)
            * (1.0 - layer / max(total_layers - 1, 1) + 1e-5)
            for h in range(first, first + count)]


def lightning_chunked(q, k, v, slopes, chunk: int):
    """o_t = d^-1/2 sum_{j <= t} lam^(t - j) (q_t . k_j) v_j for q, k, v [B,
    H, T, D] and lam = exp(-slopes[h]), in chunks of `chunk` tokens: inside
    a chunk the masked, decayed (Q K^T) V with the operands in their own
    dtype and float32 accumulation; across chunks the state S = sum lam^..
    k^T v [D, D] in float32 (the chunks' summaries, then every chunk's
    incoming state as one decayed sum over the chunks before it: no
    sequential scan).  -> float32 [B, H, T, D] (float64 for float64
    inputs: the numeric gradient checks)."""
    import jax
    import jax.numpy as jnp

    B, H, T, D = q.shape
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"lightning attention: chunks of {C} do not "
                         f"divide {T} tokens")
    N = T // C
    hi = jax.lax.Precision.HIGHEST
    f32 = wide_dtype(q.dtype)       # float32 (float64 for a float64 input)
    s = jnp.asarray(slopes, f32)                                   # [H]
    i = jnp.arange(C, dtype=f32)
    ahead = i[:, None] - i[None, :]
    decay = jnp.where(ahead >= 0,
                      jnp.exp(-s[:, None, None] * jnp.maximum(ahead, 0.0)),
                      0.0)                                         # [H, C, C]
    qc, kc, vc = (a.reshape(B, H, N, C, D) for a in (q, k, v))
    scores = jnp.einsum("bhncd,bhnkd->bhnck", qc, kc,
                        preferred_element_type=f32)
    p = (scores * decay[None, :, None]).astype(v.dtype)
    intra = jnp.einsum("bhnck,bhnkd->bhncd", p, vc,
                       preferred_element_type=f32)
    # a chunk's summary: sum_j lam^(C - 1 - j) k_j^T v_j
    to_end = jnp.exp(-s[:, None] * (C - 1 - i))                    # [H, C]
    kv = jnp.einsum("bhncd,bhnce->bhnde",
                    kc.astype(f32) * to_end[None, :, None, :, None],
                    vc.astype(f32), precision=hi)
    n = jnp.arange(N, dtype=f32)
    before = n[:, None] - n[None, :] - 1
    carry = jnp.where(before >= 0, jnp.exp(
        -s[:, None, None] * C * jnp.maximum(before, 0.0)), 0.0)   # [H, N, N]
    state = jnp.einsum("hnm,bhmde->bhnde", carry, kv, precision=hi)
    from_start = jnp.exp(-s[:, None] * (i + 1))                    # [H, C]
    inter = jnp.einsum("bhncd,bhnde->bhnce",
                       qc.astype(f32) * from_start[None, :, None, :, None],
                       state, precision=hi)
    return ((intra + inter) * D ** -0.5).reshape(B, H, T, D)


@register_op("lightning_attention")
def lightning_attention(ctx, ins, attrs):
    """The core of a lightning-attention mixer between its projections: Q,
    K, V, Gate [B, T, H * D] as `fc` leaves them (H the heads this op
    holds), QNorm, KNorm, ONorm [D] gains.

      q, k = rope(rmsnorm_head(Q; QNorm)), rope(rmsnorm_head(K; KNorm))
             per head, rotate-half, `theta`              (pdtpu.linattn.qk)
      o_t = D^-1/2 sum_{j <= t} exp(-slopes[h])^(t - j) (q_t . k_j) v_j
             in chunks of `chunk` (lightning_chunked)    (pdtpu.linattn.scan)
      Out = rmsnorm_head(o; ONorm) * sigmoid(Gate)       (pdtpu.linattn.gate)

    No softmax, no normaliser.  The scan is recomputed in its backward
    (jax.checkpoint): the vjp keeps q, k, v and neither the chunks' score
    tiles nor their states."""
    import jax
    import jax.numpy as jnp

    q, k, v, gate = (ins[s][0] for s in ("Q", "K", "V", "Gate"))
    heads = int(attrs["num_heads"])
    slopes = tuple(float(s) for s in attrs["slopes"])
    eps = float(attrs.get("epsilon", 1e-6))
    theta = float(attrs.get("theta", 10000.0))
    chunk = int(attrs.get("chunk", 256))
    B, T, width = q.shape
    D = width // heads
    if len(slopes) != heads or D * heads != width:
        raise ValueError(f"lightning_attention: {heads} heads, "
                         f"{len(slopes)} slopes, Q {q.shape}")
    if not ctx.in_grad_replay():
        _MET_LINATTN.inc(heads=str(heads), head_dim=str(D),
                         chunk=str(min(chunk, T)))
    with part_scope("linattn.qk"):
        qh = head_norm_rope_plain(q, ins["QNorm"][0], heads, eps, theta)
        kh = head_norm_rope_plain(k, ins["KNorm"][0], heads, eps, theta)
        vh = v.reshape(B, T, heads, D).transpose(0, 2, 1, 3)
    with part_scope("linattn.scan"):
        o = jax.checkpoint(functools.partial(
            lightning_chunked, slopes=slopes, chunk=chunk))(qh, kh, vh)
    with part_scope("linattn.gate"):
        o = rms(o, eps, (3,), ins["ONorm"][0].astype(o.dtype))
        o = o.transpose(0, 2, 1, 3).reshape(B, T, width)
        out = o * jax.nn.sigmoid(gate.astype(o.dtype))
    return {"Out": [out.astype(q.dtype)]}


# ---------------------------------------------------------------------------
# the gated delta rule

# Tokens a chunk of the scan.  Not the published kernels' 64: on a v5e one
# layer's scan at 32 value heads of 128 over 8192 tokens reads, forward +
# backward, 48.3 ms at 32, 37.0 at 64 and 33.1 at 128 (the [d, d] chunk states
# and transition matrices, which are what this emission moves through HBM,
# halve), and the step's peak falls by 1.1 GB (PERF.md, PR 48); the result
# differs by 8e-7.  A shorter sequence is one chunk.
DELTA_CHUNK = 128


def _product(a, b):
    """a @ b over the last two axes at HIGHEST precision: the float32
    products of the delta rule (the state's, the inverse's)."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _unit_lower_inverse():
    """a [..., C, C] strictly lower triangular -> (I - a)^-1, as the
    product (I + a)(I + a^2)(I + a^4)... of the nilpotent a's powers
    (log2 C squarings, all on the MXU; no substitution loop).  Its vjp
    keeps the result alone: d a = X^T d X X^T."""
    import jax
    import jax.numpy as jnp

    def inverse(a):
        C = a.shape[-1]
        x = a + jnp.eye(C, dtype=a.dtype)
        power, reach = a, 2
        while reach < C:
            power = _product(power, power)
            x = x + _product(x, power)
            reach *= 2
        return x

    solve = jax.custom_vjp(inverse)

    def bwd(x, dx):
        xt = jnp.swapaxes(x, -1, -2)
        return (_product(_product(xt, dx), xt),)

    solve.defvjp(lambda a: (inverse(a),) * 2, bwd)
    return solve


def gated_delta_chunked(q, k, v, g, beta, chunk: int):
    """The gated delta rule in chunks of `chunk` tokens (the op's is
    DELTA_CHUNK).  q, k [B, Hk, T,
    Dk] (k of unit length, q scaled), v [B, Hk, G, T, Dv] (key head j
    serves its G value heads), g (a token's log-decay, <= 0) and beta [B,
    Hk, G, T] float32.  Per value head, from S = 0 [Dk, Dv]:

      S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
      o_t = S_t^T q_t

    Inside a chunk, with gamma_i = sum_{j <= i} g_j:
      A  = strict-lower(-(beta K) K^T * e^{gamma_i - gamma_j})
      Tm = (I - A)^-1;  U = Tm (beta V);  W = Tm (beta K e^{gamma})
    and with K~ = K e^{gamma_C - gamma} a chunk maps its incoming state by
      S' = (e^{gamma_C} I - K~^T W) S + K~^T U,
    one [Dk, Dk] x [Dk, Dv] product a step of the `lax.scan` over the
    chunks, which hands out every chunk's INCOMING state; then, for all
    chunks at once, V' = U - W S and
      O = (Q e^{gamma}) S + lower(Q K^T * e^{gamma_i - gamma_j}) V'.
    The two score products take q and k in their own dtype with float32
    accumulation; everything after them is float32 at HIGHEST precision
    (float64 for float64 inputs: the numeric gradient checks).
    -> [B, Hk, G, T, Dv] float32.

    This is the plain emission: what the op `gated_delta_rule` runs on the
    CPU, under a mesh, in float64, at widths that are no whole lane tiles
    and where T is no multiple of 128, and the oracle of the kernel pair
    that runs the same equations on one TPU
    (ops/pallas_kernels/gated_delta.py)."""
    import jax
    import jax.numpy as jnp

    B, Hk, T, Dk = q.shape
    G, Dv = v.shape[2], v.shape[-1]
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"gated delta rule: chunks of {C} do not divide "
                         f"{T} tokens")
    N = T // C
    f32 = wide_dtype(q.dtype)
    qc, kc = (a.reshape(B, Hk, 1, N, C, Dk) for a in (q, k))
    vc = v.reshape(B, Hk, G, N, C, Dv).astype(f32)
    gamma = jnp.cumsum(g.astype(f32).reshape(B, Hk, G, N, C), axis=-1)
    bc = beta.astype(f32).reshape(B, Hk, G, N, C)
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kk, qk = (jnp.einsum("bhgncd,bhgnjd->bhgncj", a, kc,
                         preferred_element_type=f32) for a in (kc, qc))
    a = jnp.where(i[:, None] > i[None, :],
                  -bc[..., None] * kk * decay, 0.0)
    tm = _unit_lower_inverse()(a)                       # [B, Hk, G, N, C, C]
    kf, qf = kc.astype(f32), qc.astype(f32)
    u = _product(tm, vc * bc[..., None])
    w = _product(tm, kf * (bc * jnp.exp(gamma))[..., None])
    last = gamma[..., -1:]                              # [B, Hk, G, N, 1]
    kt = jnp.swapaxes(kf * jnp.exp(last - gamma)[..., None], -1, -2)
    carry = (jnp.exp(last)[..., None] * jnp.eye(Dk, dtype=f32)
             - _product(kt, w))                         # [.., N, Dk, Dk]
    fresh = _product(kt, u)                             # [.., N, Dk, Dv]

    def step(s, chunk_maps):
        m, b = chunk_maps
        return _product(m, s) + b, s

    _, states = jax.lax.scan(
        step, jnp.zeros((B, Hk, G, Dk, Dv), f32),
        (jnp.moveaxis(carry, 3, 0), jnp.moveaxis(fresh, 3, 0)))
    states = jnp.moveaxis(states, 0, 3)                 # incoming, [.., N, ..]
    inner = u - _product(w, states)
    out = (_product(qf * jnp.exp(gamma)[..., None], states)
           + _product(qk * decay, inner))
    return out.reshape(B, Hk, G, T, Dv)


def short_conv_silu(x, w):
    """x [B, T, C], w [C, L] -> SiLU of the causal depthwise convolution
    c_t = sum_j w[:, j] x_{t - (L - 1) + j} (x zero before the sequence
    starts; w[:, L - 1] multiplies the current token: `causal_taps`), at
    least float32."""
    import jax

    wide = wide_dtype(x.dtype)
    return jax.nn.silu(causal_taps(x.astype(wide), w.astype(wide)))


def gdn_conv_plain(x, taps, Hk: int, Hv: int, Dk: int, eps: float):
    """X [B, T, 2 Hk Dk + 2 Hv Dv] = [q | k | v | z], Conv [2 Hk Dk + Hv Dv,
    L] -> (q, k [B, Hk, T, Dk], v [B, Hk, Hv / Hk, T, Dv]):
    `short_conv_silu` over [q | k | v], the l2 norm of a q and a k head (q
    times Dk^-1/2 too), one rounding to X's dtype, the heads' split.  The
    `gdn.conv` part of `gated_delta_rule` as plain jax.numpy, and the
    oracle of ops/pallas_kernels/gdn_conv.py."""
    import jax
    import jax.numpy as jnp

    B, T, width = x.shape
    Dv = (width - 2 * Hk * Dk) // (2 * Hv)
    mixed = 2 * Hk * Dk + Hv * Dv
    qkv = short_conv_silu(x[..., :mixed], taps)
    q, k = (qkv[..., n * Hk * Dk:(n + 1) * Hk * Dk].reshape(B, T, Hk, Dk)
            for n in (0, 1))
    unit = lambda a: a * jax.lax.rsqrt(                           # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + eps)
    q = (unit(q) * Dk ** -0.5).astype(x.dtype).transpose(0, 2, 1, 3)
    k = unit(k).astype(x.dtype).transpose(0, 2, 1, 3)
    v = qkv[..., 2 * Hk * Dk:].astype(x.dtype).reshape(
        B, T, Hk, Hv // Hk, Dv).transpose(0, 2, 3, 1, 4)
    return q, k, v


@register_op("gated_delta_rule")
def gated_delta_rule(ctx, ins, attrs):
    """The core of a gated-DeltaNet mixer between its projections: X [B, T,
    2 Hk Dk + 2 Hv Dv] = [q | k | v | z] as one `fc` leaves it, BA [B, T, 2
    Hv] = [b | a], Conv [2 Hk Dk + Hv Dv, L] the depthwise taps over [q | k
    | v], ALog and DtBias [Hv], Norm [Dv] the output norm's gain; attrs
    `key_heads` Hk, `value_heads` Hv (a multiple of Hk: key head j serves
    value heads j Hv / Hk ...), `key_dim` Dk, `epsilon`.

      [q | k | v] = SiLU(causal depthwise conv of [q | k | v]), no bias;
      q = l2norm(q) / sqrt(Dk), k = l2norm(k) per head  (pdtpu.gdn.conv)
      beta = sigmoid(b); g = -exp(ALog) softplus(a + DtBias), float32
                                                         (pdtpu.gdn.gates)
      o = the gated delta rule (gated_delta_chunked, in chunks of
          DELTA_CHUNK tokens)                            (pdtpu.gdn.scan)
      Out = rmsnorm_head(o; Norm) * SiLU(z)              (pdtpu.gdn.norm_gate)

    On one TPU, where Dk, Dv and the chunk are whole lane tiles and the
    chunk divides T, the scan is the kernel pair of
    ops/pallas_kernels/gated_delta.py (a chunk's [C, C] and [C, d] tiles
    and the state in VMEM) through `ctx.run_pair`: kept beside the op's
    output are O, every chunk's incoming state and Tm.  Everywhere else
    (the CPU, a mesh, float64, other widths, T under a chunk,
    `PADDLE_TPU_NO_FUSED_KERNELS`) `gated_delta_chunked` as plain jax.numpy,
    recomputed in its backward (jax.checkpoint): the vjp keeps q, k, v, g
    and beta, and of the scan's own at most the chunks' incoming states
    while that layer's backward runs.  `gated_delta_kernels_traced_total`
    says which.

    Independently of that, on one TPU at heads of whole lane tiles, T in
    whole row tiles and bf16 or float32 X, the convolution part is the
    kernel pair of ops/pallas_kernels/gdn_conv.py under a `custom_vjp` of
    its own: X's [q | k | v] columns read where the projection wrote them,
    q, k and v written head-major in one pass and kept for the grad op
    (XLA's CSE kept the same three), whose re-emission launches the
    backward kernel alone; it writes ALL of dX, the output gate's dz in its
    last columns.  Everywhere else `short_conv_silu` and the plain norm and
    split.  `gated_delta_conv_kernels_traced_total` says which."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels import gated_delta as kernels
    from .pallas_kernels import gdn_conv
    from .pallas_kernels._common import traced_path

    x, ba, taps = ins["X"][0], ins["BA"][0], ins["Conv"][0]
    Hk, Hv = int(attrs["key_heads"]), int(attrs["value_heads"])
    Dk = int(attrs["key_dim"])
    eps = float(attrs.get("epsilon", 1e-6))
    B, T, width = x.shape
    chunk = min(DELTA_CHUNK, T)
    G = Hv // max(Hk, 1)
    Dv = (width - 2 * Hk * Dk) // (2 * Hv)
    mixed = 2 * Hk * Dk + Hv * Dv
    if (G * Hk != Hv or 2 * Hk * Dk + 2 * Hv * Dv != width
            or ba.shape != (B, T, 2 * Hv) or taps.shape[0] != mixed):
        raise ValueError(
            f"gated_delta_rule: X {x.shape}, BA {ba.shape}, Conv "
            f"{taps.shape} at {Hk} key heads of {Dk} and {Hv} value heads")
    take = traced_path(ctx, _MET_GDN_KERNELS,
                       kernels.usable(T, chunk, Dk, Dv, x.dtype, G))
    take_conv = traced_path(ctx, _MET_GDN_CONV, gdn_conv.usable(
        T, Hk, Hv, Dk, Dv, taps.shape[1], x.dtype))
    replay = ctx.in_grad_replay()
    if not replay:
        _MET_GDN.inc(key_heads=str(Hk), value_heads=str(Hv),
                     head_dim=str(Dk), chunk=str(chunk),
                     conv_taps=str(taps.shape[1]))
    wide = wide_dtype(x.dtype)
    # what the forward emission kept for this re-emission, and what this
    # forward emission keeps: {"conv": (q, k, v), "scan": (O, states, Tm)}
    kept = ctx.kept_for_grad() or {}
    saved = {}
    if take_conv:       # opens the parts' scopes itself: z is norm_gate's
        conv = gdn_conv.make_gdn_conv(Hk, Hv, Dk, eps)
        if "conv" in kept:
            q, k, v, z = conv.from_saved(x, taps, *kept["conv"])
        else:
            q, k, v, z = conv(x, taps)
            if not (ctx.is_test or replay):
                saved["conv"] = (q, k, v)
        ctx.kernel_forward(reused="conv" in kept)
    else:
        with part_scope("gdn.conv"):
            q, k, v = gdn_conv_plain(x, taps, Hk, Hv, Dk, eps)
        z = None
    with part_scope("gdn.gates"):
        heads = lambda a: a.astype(wide).reshape(                 # noqa: E731
            B, T, Hk, G).transpose(0, 2, 3, 1)
        beta = jax.nn.sigmoid(heads(ba[..., :Hv]))
        g = heads(-jnp.exp(ins["ALog"][0].astype(wide)) * jax.nn.softplus(
            ba[..., Hv:].astype(wide) + ins["DtBias"][0].astype(wide)))
    with part_scope("gdn.scan"):
        if take:
            o, saved_scan = ctx.run_pair(kernels.make_gated_delta(chunk),
                                         (q, k, v, g, beta),
                                         kept=kept.get("scan", ()))
            if saved_scan is not None:
                saved["scan"] = saved_scan
        else:
            o = jax.checkpoint(functools.partial(
                gated_delta_chunked, chunk=chunk))(q, k, v, g, beta)
    with part_scope("gdn.norm_gate"):
        o = rms(o, eps, (4,), ins["Norm"][0].astype(o.dtype))
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, T, Hv * Dv)
        if z is None:
            z = x[..., mixed:]
        out = o * jax.nn.silu(z.astype(o.dtype))
    out = out.astype(x.dtype)
    if saved:
        ctx.keep_for_grad(attrs, [out], saved)
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# Kimi Delta Attention: the delta rule with a log-decay a CHANNEL

# Tokens a chunk of the scan, and the rows of a diagonal block whose decays
# are taken pair by pair (`_decayed_scores`).  The published kernels' 64 and
# 16.  PERF.md, PR 58, has the chip's readings at 64 and 128.
KDA_CHUNK = 64
KDA_SUB = 16


def _block_diagonal(blocks):
    """blocks [..., m, r, c] -> [..., m r, m c], block i at (i, i)."""
    import jax.numpy as jnp

    m, r, c = blocks.shape[-3:]
    eye = jnp.eye(m, dtype=blocks.dtype)
    out = blocks[..., :, :, None, :] * eye[:, None, :, None]
    return out.reshape(blocks.shape[:-3] + (m * r, m * c))


def _decayed_scores(rows, k, G, sub: int):
    """For every X [..., C, D] of `rows`: M_ij = sum_d X_i[d] k_j[d] e^{G_i[d]
    - G_j[d]} for i >= j and 0 above the diagonal, with G [..., C, D] a
    cumulative log-decay (non-increasing down the rows).  NOT (X e^G)(k
    e^-G)^T: e^{-G} overflows float32 inside one chunk wherever a channel
    forgets fast.  No exponent taken here is positive:

      a diagonal block of `sub` rows: the pairwise exponent G_i - G_j under
        the mask, summed over d (the VPU; XLA fuses the [.., sub, sub, D]
        exponentials into each row kind's reduction: sharing one product
        between the kinds made it a 2 GB tensor a layer);
      rows i in the SECOND half and columns j in the FIRST half of a block
        of 2 sub, 4 sub, ... C rows: with G_ref the last row of the first
        half, (X_i e^{G_i - G_ref}) . (k_j e^{G_ref - G_j}), both exponents
        <= 0.  A level is ONE [C, D] x [D, C] product a chunk and row kind
        (the MXU): the rows of first halves and the columns of second
        halves enter as zeros, and what a row finds in ANOTHER block's
        columns (finite: both factors are at most one) is masked away.

    C / sub is a power of two."""
    import jax.numpy as jnp

    C, D = G.shape[-2:]
    s = min(int(sub), C)
    if C % s or (C // s) & (C // s - 1):
        raise ValueError(f"kda: a chunk of {C} rows in diagonal blocks of "
                         f"{s}")
    lead = G.shape[:-2]
    blk = lambda a: a.reshape(lead + (C // s, s, D))              # noqa: E731
    Gb, kb = blk(G), blk(k)
    i = jnp.arange(s)
    e = jnp.exp(jnp.where((i[:, None] >= i[None, :])[..., None],
                          Gb[..., :, None, :] - Gb[..., None, :, :],
                          -jnp.inf))                     # [.., C/s, s, s, D]
    out = [_block_diagonal(jnp.sum(
        blk(x)[..., :, None, :] * e * kb[..., None, :, :], axis=-1))
        for x in rows]
    at = jnp.arange(C)
    half = s
    while half < C:
        second = ((at // half) % 2 == 1)[:, None]                 # [C, 1]
        pairs = lead + (C // (2 * half), 2 * half, D)
        ref = jnp.broadcast_to(G.reshape(pairs)[..., half - 1:half, :],
                               pairs).reshape(G.shape)
        fall = jnp.exp(jnp.where(second, G - ref, -jnp.inf))
        cols = jnp.swapaxes(
            k * jnp.exp(jnp.where(second, -jnp.inf, ref - G)), -1, -2)
        same = (at[:, None] // (2 * half)) == (at[None, :] // (2 * half))
        out = [m + jnp.where(same, _product(x * fall, cols), 0.0)
               for m, x in zip(out, rows)]
        half *= 2
    return out


def kda_chunked(q, k, v, g, beta, chunk: int, sub: int = KDA_SUB):
    """Kimi Delta Attention's recurrence in chunks of `chunk` tokens (the
    op's is KDA_CHUNK).  q, k [B, H, T, Dk] (k of unit length, q scaled), v
    [B, H, T, Dv], g [B, H, T, Dk] (a token's log-decay a CHANNEL of the
    key axis, <= 0) and beta [B, H, T].  Per head, from S = 0 [Dk, Dv]:

      S~  = Diag(e^{g_t}) S_{t-1}
      S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T;   o_t = S_t^T q_t

    Inside a chunk, with G_i = sum_{j <= i} g_j [C, Dk] and the two decayed
    score matrices of `_decayed_scores` (KK over rows k, QK over rows q):
      A  = strict-lower(-beta_i KK_ij);  Tm = (I - A)^-1
      U  = Tm (beta V);  W = Tm (beta K e^G);  K~ = K e^{G_C - G}
    a chunk maps its incoming state by
      S' = (Diag(e^{G_C}) - K~^T W) S + K~^T U,
    one [Dk, Dk] x [Dk, Dv] product a step of the `lax.scan` over the
    chunks, which hands out every chunk's INCOMING state; then, for all
    chunks at once, V' = U - W S and O = (Q e^G) S + lower(QK) V'.  Every
    exponent is <= 0.  Everything is float32 at HIGHEST precision
    (float64 for float64 inputs: the numeric gradient checks), whatever
    dtype q, k and v come in.  -> [B, H, T, Dv] float32.  With every
    channel's gate equal it is `gated_delta_chunked`'s result."""
    import jax
    import jax.numpy as jnp

    B, H, T, Dk = q.shape
    Dv = v.shape[-1]
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"kda: chunks of {C} do not divide {T} tokens")
    N = T // C
    f32 = wide_dtype(q.dtype)
    qc, kc = (a.astype(f32).reshape(B, H, N, C, Dk) for a in (q, k))
    vc = v.astype(f32).reshape(B, H, N, C, Dv)
    G = jnp.cumsum(g.astype(f32).reshape(B, H, N, C, Dk), axis=-2)
    bc = beta.astype(f32).reshape(B, H, N, C, 1)
    kk, qk = _decayed_scores((kc, qc), kc, G, sub)
    i = jnp.arange(C)
    a = jnp.where(i[:, None] > i[None, :], -bc * kk, 0.0)
    tm = _unit_lower_inverse()(a)                          # [B, H, N, C, C]
    decayed = jnp.exp(G)
    u = _product(tm, vc * bc)
    w = _product(tm, kc * decayed * bc)
    last = G[..., -1:, :]                                  # [B, H, N, 1, Dk]
    kt = jnp.swapaxes(kc * jnp.exp(last - G), -1, -2)
    carry = (jnp.swapaxes(jnp.exp(last), -1, -2) * jnp.eye(Dk, dtype=f32)
             - _product(kt, w))                            # [.., N, Dk, Dk]
    fresh = _product(kt, u)                                # [.., N, Dk, Dv]

    def step(s, chunk_maps):
        m, b = chunk_maps
        return _product(m, s) + b, s

    _, states = jax.lax.scan(
        step, jnp.zeros((B, H, Dk, Dv), f32),
        (jnp.moveaxis(carry, 2, 0), jnp.moveaxis(fresh, 2, 0)))
    states = jnp.moveaxis(states, 0, 2)                    # incoming
    inner = u - _product(w, states)
    out = _product(qc * decayed, states) + _product(qk, inner)
    return out.reshape(B, H, T, Dv)


@register_op("kimi_delta_attention")
def kimi_delta_attention(ctx, ins, attrs):
    """The core of a Kimi-Delta-Attention mixer between its projections
    (Kimi Linear, arXiv:2510.26692): Q, K, V [B, T, H D] as three `fc`s
    leave them, F [B, T, H D] the decay's low-rank projection, Beta [B, T,
    H], Gate [B, T, H D] the output gate's low-rank projection, ConvQ,
    ConvK, ConvV [H D, L] depthwise taps, ALog [H], DtBias [H D], Norm [D]
    the output norm's gain; attrs `num_heads` H, `epsilon` (the output
    norm's), `gate_rank` (for the counter alone).

      q, k, v = SiLU(causal depthwise conv of Q, K, V), no bias;
      q = l2norm(q) / sqrt(D), k = l2norm(k) per head    (pdtpu.kda.conv)
      beta = sigmoid(Beta);
      g = -exp(ALog)[head] softplus(F + DtBias), float32, a CHANNEL
                                                         (pdtpu.kda.gates)
      o = the delta rule under Diag(e^g) (kda_chunked, in chunks of
          KDA_CHUNK tokens)                              (pdtpu.kda.scan)
      Out = rmsnorm_head(o; Norm) * sigmoid(Gate)        (pdtpu.kda.norm_gate)

    Plain jax.numpy everywhere (no kernel yet: ROADMAP.md S18).  The rule,
    its norm and its gate run ONE HEAD AT A TIME (`lax.map` over the heads),
    each head a `jax.checkpoint` of its own: the vjp keeps q, k, v, g, beta
    and the gate, and of the rule's own what one head's chunks need while
    that head's backward runs, after making its forward once more.  All 32
    heads of 128 over 8192 tokens at once are 4.4 GB of chunk tensors,
    which one chip does not have beside the step's weights, and ran slower
    besides (PERF.md, PR 58: 77 ms a layer forward + backward for 47 head
    by head).  The norm is INSIDE the checkpoint because its backward needs
    o: outside, the grad op's re-emission ran the whole rule a third time
    for it (a `while` is never merged with the forward op's)."""
    import jax
    import jax.numpy as jnp

    q, k, v, f, b, gate = (ins[s][0] for s in ("Q", "K", "V", "F", "Beta",
                                               "Gate"))
    taps = [ins[s][0] for s in ("ConvQ", "ConvK", "ConvV")]
    H = int(attrs["num_heads"])
    eps = float(attrs.get("epsilon", 1e-5))
    B, T, width = q.shape
    D = width // max(H, 1)
    same = (B, T, width)
    if (H * D != width or any(a.shape != same for a in (k, v, f, gate))
            or b.shape != (B, T, H)
            or any(t.shape != (width, taps[0].shape[1]) for t in taps)
            or ins["ALog"][0].shape != (H,)
            or ins["DtBias"][0].shape != (width,)):
        raise ValueError(
            f"kimi_delta_attention: Q {q.shape}, K {k.shape}, V {v.shape}, "
            f"F {f.shape}, Beta {b.shape}, Gate {gate.shape}, taps "
            f"{[t.shape for t in taps]} at {H} heads")
    chunk = min(KDA_CHUNK, T)
    if not ctx.in_grad_replay():
        _MET_KDA.inc(heads=str(H), head_dim=str(D), chunk=str(chunk),
                     conv_taps=str(taps[0].shape[1]),
                     gate_rank=str(attrs.get("gate_rank", 0)))
    wide = wide_dtype(q.dtype)
    heads = lambda a: a.reshape(B, T, H, D).transpose(0, 2, 1, 3)  # noqa
    with part_scope("kda.conv"):
        unit = lambda a: a * jax.lax.rsqrt(                       # noqa: E731
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        qh, kh, vh = (heads(short_conv_silu(a, t))
                      for a, t in zip((q, k, v), taps))
        qh = (unit(qh) * D ** -0.5).astype(q.dtype)
        kh, vh = unit(kh).astype(q.dtype), vh.astype(q.dtype)
    with part_scope("kda.gates"):
        beta = jax.nn.sigmoid(b.astype(wide)).transpose(0, 2, 1)
        rate = -jnp.exp(ins["ALog"][0].astype(wide))              # [H]
        g = heads(jax.nn.softplus(f.astype(wide) + ins["DtBias"][0].astype(
            wide))) * rate[None, :, None, None]
    gain = ins["Norm"][0]

    def head(q1, k1, v1, g1, b1, z1):
        """One head, [B, T, D] each (beta [B, T]): the rule, its norm and
        gate."""
        with part_scope("kda.scan"):
            o = kda_chunked(*(a[:, None] for a in (q1, k1, v1, g1, b1)),
                            chunk=chunk, sub=KDA_SUB)[:, 0]
        with part_scope("kda.norm_gate"):
            o = rms(o, eps, (2,), gain.astype(o.dtype))
            return (o * jax.nn.sigmoid(z1.astype(o.dtype))).astype(q.dtype)

    out = jax.lax.map(
        lambda a: jax.checkpoint(head)(*a),
        tuple(jnp.moveaxis(a, 1, 0) for a in (qh, kh, vh, g, beta,
                                              heads(gate))))
    out = out.transpose(1, 2, 0, 3).reshape(B, T, width)     # from [H, B, T, D]
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# block-top-k selection


def pooled_block_scores(q, k, *, kernel: int, stride: int, block: int,
                        first_token: int = 0):
    """q [Tq, G, D] (a group's heads of Tq consecutive tokens from
    `first_token` on), k [T, D] (all keys) -> [Tq, T / block] float32:

      Kc[i]   = mean(k[stride i : stride i + kernel])
      p_g[t]  = softmax over the i with stride i + kernel - 1 <= t of
                (q_g[t] . Kc[i]) D^-1/2     (nothing where no i is visible)
      s[t, i] = sum_g p_g[t, i]
      B[t, b] = max of s[t, i] over the i whose keys touch block b

    float32 on whatever dtype q and k have, products at HIGHEST."""
    import jax
    import jax.numpy as jnp

    Tq, G, D = q.shape
    T = k.shape[0]
    per = block // stride
    spill = kernel // stride - 1       # windows that reach the next block
    if (block % stride or kernel % stride or T % block
            or not 0 <= spill <= per):
        raise ValueError(f"block_topk_select: kernel {kernel}, stride "
                         f"{stride}, block {block} over {T} keys")
    f32 = jnp.float32
    sums = k.astype(f32).reshape(T // stride, stride, D).sum(axis=1)
    n = T // stride                     # windows, the last `spill` cut short
    sums = jnp.concatenate([sums, jnp.zeros((spill, D), f32)])
    kc = sum(sums[m:m + n] for m in range(spill + 1)) / kernel   # [n, D]
    t = first_token + jnp.arange(Tq)
    i = jnp.arange(n)
    visible = ((stride * i + kernel - 1)[None, :] <= t[:, None])  # [Tq, n]
    logits = jnp.einsum("tgd,nd->gtn", q.astype(f32), kc,
                        precision=jax.lax.Precision.HIGHEST) * D ** -0.5
    logits = jnp.where(visible[None], logits, -jnp.inf)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(visible[None], jnp.exp(logits - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    s = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=0)     # [Tq, n]
    s = s.reshape(Tq, T // block, per)
    scores = jnp.max(s, axis=-1)
    if spill:
        reach = jnp.max(s[:, :, per - spill:], axis=-1)
        scores = jnp.maximum(scores, jnp.pad(reach, ((0, 0), (1, 0)))[:, :-1])
    return scores


def choose_blocks(scores, *, block: int, window: int, init_blocks: int,
                  topk: int, first_token: int = 0):
    """scores [Tq, blocks] -> bool [Tq, blocks]: of the blocks that start
    at or before token t, the `init_blocks` first and the window / block
    that end with t's own are certain, and the highest scores fill the set
    up to `topk` (the lower block wins a tie, as in `lax.top_k`); all of
    them where there are no more than `topk`."""
    import jax.numpy as jnp

    Tq, nb = scores.shape
    own = ((first_token + jnp.arange(Tq)) // block)[:, None]
    b = jnp.arange(nb)[None, :]
    forced = (b < init_blocks) | (own - b < window // block)
    ranked = jnp.where(forced, jnp.inf, scores)
    ranked = jnp.where(b <= own, ranked, -jnp.inf)
    # a block's rank by counting the blocks that beat it, [Tq, nb, nb]
    # compares: what `lax.top_k` gives (the lower block wins a tie) without
    # its sort, which took 50 ms a selection on the v5e at T 16384 (PERF.md,
    # PR 45)
    mine, other = ranked[:, :, None], ranked[:, None, :]
    beats = (other > mine) | ((other == mine) & (b[:, None, :] < b[:, :, None]))
    rank = jnp.sum(beats, axis=-1, dtype=jnp.int32)
    return (rank < topk) & (ranked > -jnp.inf)


@register_op("block_topk_select", grad=None)
def block_topk_select(ctx, ins, attrs):
    """Q [B, T * H, D] and K [B, T * Hkv, D] (per-head normed, a token's
    heads side by side: row t * H + h) -> Select [B, Hkv, T, T / block]
    int8, 1 where query token t of key/value head j's group chose key
    block b (`pooled_block_scores`, `choose_blocks`; attrs kernel, stride,
    block, window, init_blocks, topk, num_heads, num_kv_heads).  One choice
    for the `H / Hkv` query heads of a group.  Takes and gives no
    gradient.  Chunks of `chunk_tokens` query tokens at a time, so that
    the [group, chunk, T / stride] scores are what is alive.

    And Tiles [4] float32, what the choice costs a kernel whose q tile is
    the group's heads of 128 / group tokens: the (q tile, key block) pairs
    some token of the tile chose, the pairs at or below the diagonal, the
    blocks chosen by the tokens at or beyond topk x block, and those
    tokens (`layers.sparse_tile_counter` adds them up over the steps)."""
    import jax
    import jax.numpy as jnp

    q, k = ins["Q"][0], ins["K"][0]
    H, Hkv = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    block, topk = int(attrs["block"]), int(attrs["topk"])
    pool = dict(kernel=int(attrs["kernel"]), stride=int(attrs["stride"]),
                block=block)
    rule = dict(block=block, window=int(attrs["window"]),
                init_blocks=int(attrs["init_blocks"]), topk=topk)
    B, rows, D = q.shape
    T, G = rows // H, H // Hkv
    chunk = min(int(attrs.get("chunk_tokens", 1024)), T)
    if T % chunk or T % block:
        raise ValueError(f"block_topk_select: {T} tokens in chunks of "
                         f"{chunk}, blocks of {block}")
    qg = q.reshape(B, T // chunk, chunk, Hkv, G, D)
    kg = k.reshape(B, T, Hkv, D)

    def one(qc, kh, first):          # [chunk, G, D], [T, D]
        return choose_blocks(pooled_block_scores(
            qc, kh, first_token=first, **pool), first_token=first, **rule)

    def sequence(qb, kb):            # [n, chunk, Hkv, G, D], [T, Hkv, D]
        per_head = jax.vmap(one, in_axes=(1, 1, None), out_axes=0)
        firsts = jnp.arange(T // chunk) * chunk
        got = jax.lax.map(lambda a: per_head(a[0], kb, a[1]), (qb, firsts))
        return jnp.moveaxis(got, 1, 0).reshape(Hkv, T, T // block)

    select = jax.vmap(sequence)(qg, kg)
    tokens = 128 // G if G and 128 % G == 0 else 1
    live = jnp.any(select.reshape(B, Hkv, T // tokens, tokens, -1), axis=3)
    causal = sum(((i + 1) * tokens - 1) // block + 1
                 for i in range(T // tokens))
    late = (jnp.arange(T) >= topk * block)[None, None, :, None]
    tiles = jnp.stack([
        jnp.sum(live, dtype=jnp.float32),
        jnp.float32(B * Hkv * causal),
        jnp.sum(select & late, dtype=jnp.float32),
        jnp.float32(B * Hkv * max(T - topk * block, 0))])
    return {"Select": [select.astype(jnp.int8)], "Tiles": [tiles]}


# ---------------------------------------------------------------------------
# block-sparse attention


def selection_mask(select, block: int):
    """Select [..., T, T / block] -> bool [..., T, T]: key j is seen by
    token t where t chose j's block and j <= t."""
    import jax.numpy as jnp

    T = select.shape[-2]
    chosen = jnp.repeat(select != 0, block, axis=-1)
    return chosen & jnp.tril(jnp.ones((T, T), bool))


def _dense_masked(q, k, v, mask, scale):
    """q [B, Hkv, T, G, D], k, v [B, Hkv, T, D], mask [B, Hkv, T, T] (None:
    causal) -> [B, Hkv, T, G, D]; float32 softmax."""
    import jax
    import jax.numpy as jnp

    T = q.shape[2]
    wide = wide_dtype(q.dtype)
    if mask is None:
        mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    s = jnp.einsum("bhtgd,bhjd->bhgtj", q, k,
                   preferred_element_type=wide) * scale
    p = jax.nn.softmax(jnp.where(mask[:, :, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhgtj,bhjd->bhtgd", p.astype(v.dtype), v,
                      preferred_element_type=wide).astype(q.dtype)


@register_op("block_sparse_attention", non_diff_inputs=("Select",))
def block_sparse_attention(ctx, ins, attrs):
    """Q [B, T * H, D], K, V [B, T * Hkv, D] (a token's heads side by side:
    row t * H + h; query head h on key/value head h // (H / Hkv)) and
    optionally Select [B, Hkv, T, T / block] (`block_topk_select`) -> Out
    [B, T, H * D]: softmax attention, scale D^-1/2, of token t's heads
    over the keys j <= t inside the blocks the token chose; over all j <=
    t without a Select (plain causal attention: a sequence no longer than
    the layer's `dense_len`).  No position enters.  On one TPU, with heads
    of 128 and a group that divides 128, the block-sparse flash kernels
    (pallas_kernels/sparse_flash.py), whose q tile is the group's heads of
    128 / group tokens; else dense attention under the mask."""
    import jax.numpy as jnp

    from .pallas_kernels import sparse_flash as sf
    from .pallas_kernels._common import pallas_dispatch_ok

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    select = ins["Select"][0] if ins.get("Select") else None
    H, Hkv = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    block = int(attrs.get("block", 64))
    B, rows, D = q.shape
    T, G = rows // H, H // Hkv
    scale = D ** -0.5
    qg = jnp.moveaxis(q.reshape(B, T, Hkv, G, D), 2, 1)   # [B, Hkv, T, G, D]
    kh, vh = (jnp.moveaxis(a.reshape(B, T, Hkv, D), 2, 1) for a in (k, v))
    kernels = (select is not None and pallas_dispatch_ok(ctx)
               and sf.usable(T, D, G, block))
    path = ("causal" if select is None else
            "sparse_flash" if kernels else "dense_mask")
    if not ctx.in_grad_replay():
        _MET_SPARSE.inc(path=path, q_heads=str(H), kv_heads=str(Hkv),
                        block=str(block))
    if kernels:
        bits = sf.tile_bits(select.reshape(B * Hkv, T, -1), G)
        out = sf.make_sparse_flash(G, block, scale)(
            qg.reshape(B * Hkv, T * G, D), kh.reshape(B * Hkv, T, D),
            vh.reshape(B * Hkv, T, D), bits).reshape(B, Hkv, T, G, D)
    else:
        mask = None if select is None else selection_mask(select, block)
        out = _dense_masked(qg, kh, vh, mask, scale)
    return {"Out": [jnp.moveaxis(out, 1, 2).reshape(B, T, H * D)]}


# ---------------------------------------------------------------------------
# analytic cost formulas (analysis/cost.py)


def _lightning_cost(ins, outs, attrs):
    """A chunk's two [C, C] products and the state's two [D, D] ones, per
    head and token."""
    q = ins.get("Q", [None])[0]
    if q is None or len(q.shape) != 3:
        return {}
    b, t, width = q.shape
    d = width // int(attrs["num_heads"])
    c = min(int(attrs.get("chunk", 256)), t)
    return {"flops": b * t * width * (4 * c + 4 * d)}


def _sparse_attention_cost(ins, outs, attrs):
    """The two score products over the keys a token sees: `topk` blocks at
    most under a Select, the causal half without."""
    q = ins.get("Q", [None])[0]
    if q is None or len(q.shape) != 3:
        return {}
    b, rows, d = q.shape
    t = rows // int(attrs["num_heads"])
    keys = t / 2
    if ins.get("Select"):
        keys = min(int(attrs.get("topk", 64)) * int(attrs.get("block", 64)),
                   t)
    return {"flops": int(4 * b * rows * d * keys)}


def _gated_delta_cost(ins, outs, attrs):
    """Per value head and token, the products a chunked delta rule needs
    (benchmarks/flops_qwen3next.py `gated_delta_cost` has them one by one):
    4 C Dk + C (Dk + Dv) + 2 C Dv + 6 Dk Dv."""
    x = ins.get("X", [None])[0]
    if x is None or len(x.shape) != 3:
        return {}
    b, t, width = x.shape
    hk, hv = int(attrs["key_heads"]), int(attrs["value_heads"])
    dk = int(attrs["key_dim"])
    dv = (width - 2 * hk * dk) // (2 * hv)
    c = min(DELTA_CHUNK, t)
    return {"flops": b * t * hv * (4 * c * dk + c * (dk + dv) + 2 * c * dv
                                   + 6 * dk * dv)}


def _kda_cost(ins, outs, attrs):
    """Per head and token, the products a chunked delta rule needs
    (benchmarks/flops_kimi.py `kda_cost` has them one by one): 4 C D + 2 C
    D + 2 C D + 6 D D at Dk = Dv = D."""
    q = ins.get("Q", [None])[0]
    if q is None or len(q.shape) != 3:
        return {}
    b, t, width = q.shape
    d = width // int(attrs["num_heads"])
    c = min(KDA_CHUNK, t)
    return {"flops": b * t * width * (8 * c + 6 * d)}


register_cost("lightning_attention", _lightning_cost)
register_cost("kimi_delta_attention", _kda_cost)
register_cost("gated_delta_rule", _gated_delta_cost)
register_cost("block_sparse_attention", _sparse_attention_cost)
