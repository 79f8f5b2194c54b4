"""Ops of the post-2020 decoder block that the GPT-2-shaped tower lacks:
RMSNorm, rotary position embedding (beyond-reference, like the rest of
the transformer tier; first user: OLMoE, models/transformer.py), latent
attention (DeepSeek-V2's MLA; first user: Moonlight-16B-A3B), the gated
short convolution (LFM2's token mixer in the layers that do not attend) and
the noising of block-diffusion training (BD3-LM's objective; first user:
SDAR-30B-A3B) and the hyper-connection, a residual path of several streams
mixed per token (mHC, arXiv:2512.24880; first user: Xing4.0-29B-A4B).

All but latent attention, `head_norm_rope`, `gated_short_conv` and the two
hyper-connection ops are plain jax.numpy, so `generic_grad` differentiates
them by re-emission and XLA's CSE merges the re-emitted forward with the
first.  `head_norm_rope` (Q or K from the projection's layout to
attention's: the per-head norm, the rotary turn and the head split in one
pass; first users: OLMoE, LFM2, SDAR), `gated_short_conv` and
`hyper_connection_pre` / `_post` take Pallas kernels
on one TPU and plain jax.numpy everywhere else, and bring grad ops of their
own, whose emitters need the forward's inputs (and the small outputs it
kept) alone and never emit the forward: a Mosaic call is opaque to CSE, so
a re-emitted forward would run twice.  Statistics and rotations are at
least float32 whatever the compute dtype (`wide_dtype`); the result goes
back to the input's dtype."""

from __future__ import annotations

from ..observability.attribution import part_scope
from ..observability.metrics import REGISTRY as _MET
from .registry import GRAD_SUFFIX, register_op

_MET_QK_PREP = _MET.counter(
    "qk_prep_layers_traced_total",
    "head_norm_rope ops traced (forward emission; once a compile, not once "
    "a step; one for Q and one for K a layer), by the path the emitter took "
    "(pallas: the kernel on [B,T,H*D] one head of 128 lanes a block; "
    "pallas_packed: two heads of 64 a block; xla: plain jax.numpy), the "
    "head size (head_dim), the head count (heads) and the norm in front of "
    "the turn (norm: head, a per-head RMSNorm, or none)")
_MET_ROPE_TABLES = _MET.counter(
    "rope_tables_traced_total",
    "head_norm_rope ops traced (forward emission; once a compile, not once "
    "a step; one for Q and one for K a layer), by the rule their cos and "
    "sin tables were made by (rule: default, theta ** (-2i / rotary_dim); "
    "yarn: YaRN's blended frequencies, cos and sin times its attention "
    "factor), the columns of a head that turn (rotary_dim) and the base "
    "(theta)")
_MET_MLA_LAYERS = _MET.counter(
    "mla_layers_traced_total",
    "latent attention layers traced (forward emission; once a compile, not "
    "once a step), by the width of a head's queries and keys (qk_dim), of "
    "its values (v_dim) and the rank of the K/V latent (kv_rank)")


_MET_MLA_POSITIONS = _MET.counter(
    "latent_attention_positions_traced_total",
    "latent attention layers traced (forward emission; once a compile, not "
    "once a step), by what their shared key and the queries' last columns "
    "see of a position (positions: rope, the rotate-half turn; none: "
    "`rotary` false, Kimi-Linear's `mla_use_nope`)")
_MET_MLA_QUERY_LATENTS = _MET.counter(
    "mla_query_latents_traced_total",
    "latent attention layers traced whose queries come from a latent of "
    "their own (WQA, QNorm, WQB; forward emission, once a compile), by the "
    "latent's rank (q_rank) and the YaRN factor on the rotary frequencies "
    "(yarn_factor; 1: none)")
_MET_HC_LAYERS = _MET.counter(
    "hyper_connection_layers_traced_total",
    "hyper_connection_pre ops traced (forward emission; once a compile, not "
    "once a step; one a sub-layer), by the residual streams (streams), a "
    "stream's width (dim) and the Sinkhorn iterations on the stream-mixing "
    "matrix (sinkhorn_iters)")
_MET_HC_KERNELS = _MET.counter(
    "hyper_connection_kernels_traced_total",
    "hyper-connection ops traced (once a compile, not once a step; one of "
    "each a sub-layer), by the op (op: pre, post, pre_grad, post_grad) and "
    "the path its emitter took (pallas: the kernels of "
    "ops/pallas_kernels/hyper_connection.py, a token tile of all streams "
    "in VMEM; xla: plain jax.numpy)")
_MET_MTP_MODULES = _MET.counter(
    "mtp_modules_traced_total",
    "multi-token-prediction modules traced (mtp_project's forward emission; "
    "once a compile, not once a step), by how many tokens past the next one "
    "the module predicts (depth)")
_MET_CONV_LAYERS = _MET.counter(
    "short_conv_layers_traced_total",
    "gated short convolution ops traced (forward emission; once a compile, "
    "not once a step), by the channels convolved (dim) and the taps a "
    "channel (kernel)")
_MET_CONV_KERNELS = _MET.counter(
    "short_conv_kernels_traced_total",
    "gated short convolution ops traced (once a compile, not once a step; "
    "one of each a convolution layer), by the op (op: fwd, grad) and the "
    "path its emitter took (pallas: the kernels of "
    "ops/pallas_kernels/short_conv.py, a tile of whole rows of X in VMEM; "
    "xla: plain jax.numpy)")


def wide_dtype(dtype):
    """float32 for bf16 / f16 / f32 inputs, float64 for float64 ones."""
    import jax.numpy as jnp

    return jnp.promote_types(dtype, jnp.float32)


def rms(x, eps: float, axes, gain=None):
    """x over sqrt(mean of its squares over `axes` + eps), times `gain`
    (shaped like those axes) where given; at least float32 inside, x's
    dtype out."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(wide_dtype(x.dtype))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=axes, keepdims=True) + eps)
    if gain is not None:
        y = y * gain.astype(xf.dtype).reshape(tuple(x.shape[a] for a in axes))
    return y.astype(x.dtype)


@register_op("rms_norm")
def rms_norm(ctx, ins, attrs):
    """X [..., D...] -> Y = X / sqrt(mean(X^2 over the axes from
    `begin_norm_axis`) + epsilon) * Scale (Zhang & Sennrich 2019,
    arXiv:1910.07467).  No mean is subtracted and there is no bias."""
    x = ins["X"][0]
    begin = int(attrs.get("begin_norm_axis", 1))
    gain = ins["Scale"][0] if ins.get("Scale") else None
    return {"Y": [rms(x, float(attrs.get("epsilon", 1e-5)),
                      tuple(range(begin, x.ndim)), gain)]}


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """The dim / 2 rotary frequencies under YaRN (Peng et al. 2023,
    arXiv:2309.00071, as DeepSeek-V3's `DeepseekV3YarnRotaryEmbedding`
    blends them): frequency i is theta ** (-2i / dim) where it turns more
    than `beta_fast` times over `original_max` positions, that over
    `factor` where it turns fewer than `beta_slow` times, and a linear
    blend by i between the two indices.  A numpy array: a trace-time
    constant."""
    import math

    import numpy as np

    def turns_at(turns):    # the index whose frequency makes `turns` turns
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature, 0.1 * mscale * ln(factor) + 1."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rule(attrs, dim: int, theta: float):
    """(inv_freq [dim / 2] or None, factor) of an op whose desc may state
    YaRN on its rotary turn: with the attr `yarn_factor` (and
    `yarn_original_max`, `yarn_beta_fast`, `yarn_beta_slow`) the `dim`
    turning columns take `yarn_inv_freq`'s frequencies and cos and sin are
    multiplied by `attention_factor` (absent: 0.1 ln(factor) + 1,
    transformers' default); without it (None, 1.0): the plain rule."""
    if "yarn_factor" not in attrs:
        return None, 1.0
    factor = float(attrs["yarn_factor"])
    inv_freq = yarn_inv_freq(
        dim, theta, factor, int(attrs["yarn_original_max"]),
        float(attrs.get("yarn_beta_fast", 32.0)),
        float(attrs.get("yarn_beta_slow", 1.0)))
    scale = attrs.get("attention_factor")
    return inv_freq, (yarn_mscale(factor, 1.0) if scale is None
                      else float(scale))


def rotate_half(x, theta: float, period: int = 0, inv_freq=None):
    """X [..., T, D] with D even turned by position: the pair (x[i], x[i +
    D/2]) of position t by the angle t * theta ** (-2i / D), positions
    0..T-1, or, with a `period`, row r at position r mod period (copies of
    one sequence side by side); with `inv_freq` [D/2], by the angle t *
    inv_freq[i] instead (`yarn_inv_freq`); at least float32 inside, X's
    dtype out."""
    import jax.numpy as jnp

    T, D = x.shape[-2], x.shape[-1]
    if D % 2:
        raise ValueError(f"rope op: head size {D} must be even")
    half = D // 2
    xf = x.astype(wide_dtype(x.dtype))
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=xf.dtype) / half)
    else:
        inv_freq = jnp.asarray(inv_freq, xf.dtype)
    pos = jnp.arange(T, dtype=xf.dtype)
    if period:
        pos = (jnp.arange(T) % period).astype(xf.dtype)
    ang = pos[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)          # [T, D/2]
    a, b = xf[..., :half], xf[..., half:]
    y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return y.astype(x.dtype)


@register_op("rope")
def rope(ctx, ins, attrs):
    """Rotary position embedding in its rotate-half form (Su et al. 2021,
    arXiv:2104.09864, as GPT-NeoX and transformers apply it): X [B, H, T,
    D] with D even; position t of every head turns the pair (x[i], x[i +
    D/2]) by the angle t * theta ** (-2i / D).  Positions are 0..T-1; with
    the attr `period`, row r stands at position r mod period."""
    return {"Out": [rotate_half(ins["X"][0],
                                float(attrs.get("theta", 10000.0)),
                                int(attrs.get("period", 0)))]}


def head_norm_rope_plain(x, gain, heads: int, eps, theta: float,
                         period: int = 0, rotary_dim: int = 0,
                         inv_freq=None, factor: float = 1.0):
    """X [B, T, heads * D] -> [B, heads, T, D]: per head and row an
    RMSNorm over the head's D columns where `eps` is given (times `gain`
    [D], one for all heads, where given), then the rotate-half turn at the
    row's position (`rotate_half`'s angles; with `rotary_dim` on the
    head's first so many columns alone, rotate-half inside them at their
    own frequencies theta ** (-2i / rotary_dim), the other columns
    unturned; with `inv_freq` [rotary_dim / 2] at those frequencies, and
    cos and sin times `factor`: the turned columns alone carry it), at
    least float32 from end to end with ONE rounding to X's dtype.  What
    the kernels of ops/pallas_kernels/head_norm_rope.py compute, in plain
    jax.numpy."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels.head_norm_rope import tables

    B, T, width = x.shape
    D = width // heads
    wide = wide_dtype(x.dtype)
    y = x.astype(wide).reshape(B, T, heads, D)
    if eps is not None:
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    if gain is not None:
        y = y * gain.astype(wide)
    R = int(rotary_dim) or D
    cos, sin = (t[None, :, None, :] for t in tables(
        T, R, theta, period, dtype=wide, inv_freq=inv_freq, factor=factor))
    turned = y if R == D else y[..., :R]
    out = turned * cos + jnp.roll(turned, R // 2, axis=-1) * sin
    if R < D:
        out = jnp.concatenate([out, y[..., R:]], axis=-1)
    return out.transpose(0, 2, 1, 3).astype(x.dtype)


def _qk_prep(ctx, ins, attrs):
    """(x, gain, the arguments both emissions share, the kernels' heads a
    block or 0 where the plain emission runs) of a `head_norm_rope` op or
    its grad op."""
    from .pallas_kernels import head_norm_rope as kernels
    from .pallas_kernels._common import pallas_dispatch_ok

    x = ins["X"][0]
    gain = ins["Scale"][0] if ins.get("Scale") else None
    heads = int(attrs["num_heads"])
    eps = attrs.get("epsilon")
    if x.ndim != 3 or x.shape[2] % (2 * heads):
        raise ValueError(f"head_norm_rope: X {x.shape} is not [B, T, "
                         f"{heads} heads of an even size]")
    if gain is not None and eps is None:
        raise ValueError("head_norm_rope: Scale is the norm's gain: give "
                         "epsilon")
    kw = dict(heads=heads, eps=None if eps is None else float(eps),
              theta=float(attrs.get("theta", 10000.0)),
              period=int(attrs.get("period", 0)))
    D = x.shape[2] // heads
    rotary = int(attrs.get("rotary_dim") or D)
    if not 0 < rotary <= D or rotary % 2:
        raise ValueError(f"head_norm_rope: rotary_dim {rotary} of a head "
                         f"of {D}")
    # inside a sub-block (a `layers.recompute` segment) the backward is the
    # block's own jax.vjp, and these kernels have a grad op, no custom_vjp
    pack = kernels.pack_of(x.shape[1], D, heads, x.dtype) if (
        pallas_dispatch_ok(ctx) and not ctx.sub_depth) else 0
    if rotary != D:   # a partial turn: the kernels take 64 of 128 alone
        kw["rotary_dim"] = rotary
        pack = pack if kernels.turn_of(D, rotary) else 0
    if "yarn_factor" in attrs:   # both emissions read `tables`
        kw["inv_freq"], kw["factor"] = yarn_rule(attrs, rotary, kw["theta"])
    return x, gain, kw, pack


def _own_grad_maker(grad_type: str, kept=()):
    """A grad maker for an op whose backward is an op of its own: ONE
    `grad_type` desc with the forward op's inputs, its `kept` outputs and
    the other outputs' cotangents in, the wanted inputs' cotangents out,
    the forward's attrs (its `part` and `__uid__` with them).  Not a
    `generic_grad`: the backward needs no forward emitted again."""

    def maker(op, wanted):
        outs = {slot + GRAD_SUFFIX: [n + GRAD_SUFFIX if n in wanted else ""
                                     for n in names]
                for slot, names in op.inputs.items()}
        if not any(n for names in outs.values() for n in names):
            return []
        ins = {slot: list(names) for slot, names in op.inputs.items()}
        for slot, names in op.outputs.items():
            if slot in kept:
                ins[slot] = list(names)
            else:
                ins[slot + GRAD_SUFFIX] = [n + GRAD_SUFFIX for n in names]
        return [(grad_type, ins, outs, dict(op.attrs))]

    return maker


@register_op("head_norm_rope",
             grad=_own_grad_maker("head_norm_rope_grad"))
def head_norm_rope(ctx, ins, attrs):
    """Q (or K) from the projection's layout to attention's in one pass: X
    [B, T, H * D] -> Out [B, H, T, D] with, per head and row, an RMSNorm
    over the head's D columns (attr `epsilon`; absent: no norm) times
    Scale [D] (optional; ONE gain for all heads), then the rotate-half
    rotary turn (`rope`'s: attrs `theta`, `period`; with `rotary_dim` on
    the first so many columns of a head alone; with `yarn_factor`,
    `yarn_original_max`, `yarn_beta_fast`, `yarn_beta_slow` and
    `attention_factor` at YaRN's frequencies over those columns, cos and
    sin times the factor: `yarn_rule`; the unturned columns carry neither).
    At least float32 inside, one rounding at the end.  attrs: `num_heads`.
    `rope_tables_traced_total{rule, rotary_dim, theta}` says which tables
    a compile made.

    On one TPU with heads of 128 or 64 lanes and T in 128s a Pallas kernel
    reads each head's column block where it lies and writes it where the
    flash kernels read it (ops/pallas_kernels/head_norm_rope.py);
    everywhere else (the CPU, a mesh, inside a `layers.recompute` segment
    or another sub-block, other head sizes, a partial turn other than 64
    columns of 128: `qk_prep_layers_traced_total{path="xla"}` says so)
    plain jax.numpy (`head_norm_rope_plain`)."""
    from .pallas_kernels import head_norm_rope as kernels

    x, gain, kw, pack = _qk_prep(ctx, ins, attrs)
    if not ctx.in_grad_replay():
        _MET_QK_PREP.inc(
            path=("xla", "pallas", "pallas_packed")[pack],
            head_dim=str(x.shape[2] // kw["heads"]), heads=str(kw["heads"]),
            norm="none" if kw["eps"] is None else "head")
        _MET_ROPE_TABLES.inc(
            rule="yarn" if "inv_freq" in kw else "default",
            rotary_dim=str(kw.get("rotary_dim") or x.shape[2] // kw["heads"]),
            theta=f"{kw['theta']:g}")
    emit = kernels.head_norm_rope if pack else head_norm_rope_plain
    return {"Out": [emit(x, gain, **kw)]}


@register_op("head_norm_rope_grad", grad=None)
def head_norm_rope_grad(ctx, ins, attrs):
    """`head_norm_rope`'s backward from X, Scale and Out@GRAD alone: the
    turn by the negative angle, the norm's backward on a recomputed row
    statistic -> X@GRAD, Scale@GRAD.  The backward kernel where the
    forward took its kernel, else `jax.vjp` of the plain emission (plain
    HLO, which XLA merges with the forward's)."""
    import jax

    from .pallas_kernels import head_norm_rope as kernels

    x, gain, kw, pack = _qk_prep(ctx, ins, attrs)
    dout = ins["Out" + GRAD_SUFFIX][0].astype(x.dtype)
    if pack:
        dx, dgain = kernels.head_norm_rope_bwd(dout, x, gain, **kw)
    else:
        grads = jax.vjp(
            lambda a, g=None: head_norm_rope_plain(a, g, **kw),
            *((x,) if gain is None else (x, gain)))[1](dout)
        dx, dgain = grads[0], grads[-1]   # dgain unread without a gain
    out = {"X" + GRAD_SUFFIX: [dx]}
    if gain is not None:
        out["Scale" + GRAD_SUFFIX] = [dgain.astype(gain.dtype)]
    return out


@register_op("block_diffusion_noise", grad=None)
def block_diffusion_noise(ctx, ins, attrs):
    """The input of a block-diffusion training step (BD3-LM,
    arXiv:2503.09573, as SDAR, arXiv:2510.06303, trains): Tokens [B, L, 1]
    clean tokens x0, in blocks of `block_length`; BlockNoise [B, L /
    block_length, 1] uniform in [0, 1), one draw a block, gives the
    block's noise level t = t_min + (1 - t_min) * draw; TokenNoise [B, L,
    1] uniform in [0, 1), one draw a token, masks the token where it lies
    under its block's level.

      Mask   [B, L, 1] float32: m_i = [u_i < t_blk(i)]
      Weight [B, L, 1] float32: m_i / t_blk(i), the loss's weight
      Out    [B, 2L, 1]: [xt ; x0], xt_i = `mask_id` where m_i else x0_i

    The noise is FED, not drawn here: whoever feeds it knows the mask.
    Nothing here takes a gradient (`grad=None`): the tokens are integers
    and the draws are data."""
    import jax.numpy as jnp

    tokens, u, draw = (ins[k][0] for k in ("Tokens", "TokenNoise",
                                           "BlockNoise"))
    b = int(attrs["block_length"])
    t_min = float(attrs.get("t_min", 0.0))
    if tokens.shape[1] != draw.shape[1] * b or u.shape != tokens.shape:
        raise ValueError(
            f"block_diffusion_noise: {tokens.shape} tokens, {u.shape} token "
            f"draws and {draw.shape} block draws at {b} tokens a block")
    with part_scope("bd.noise"):
        t = t_min + (1.0 - t_min) * draw.astype(jnp.float32)
        t = jnp.repeat(t, b, axis=1)                        # [B, L, 1]
        masked = u.astype(jnp.float32) < t
        noisy = jnp.where(masked, jnp.asarray(int(attrs["mask_id"]),
                                              tokens.dtype), tokens)
        m = masked.astype(jnp.float32)
        return {"Out": [jnp.concatenate([noisy, tokens], axis=1)],
                "Mask": [m], "Weight": [m / t]}


@register_op("latent_attention")
def latent_attention(ctx, ins, attrs):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section
    2.1, as DeepSeek-V3's modelling code computes it) over X [B, T, D],
    causal, H heads:

      q = X WQ -> [B, T, H, dn + dr] = (q_nope, q_pe): the `q_lora_rank`
          null form; or, with a query latent (inputs WQA, QNorm, WQB in
          WQ's place), q = RMSNorm(X WQA; QNorm) WQB
      c = X WKVA -> [B, T, r + dr] = (c_kv, k_pe): the latent a serving
          cache would hold, and ONE rotary key all heads share
      kv = RMSNorm(c_kv; KVNorm) WKVB -> [B, T, H, dn + dv] = (k_nope, v)
      q = [q_nope; rope(q_pe)], k = [k_nope; rope(k_pe)] in every head,
      softmax(q k^T / sqrt(dn + dr)) v -> [B, T, H dv], times WO.

    attrs: num_heads, qk_nope_dim (dn), qk_rope_dim (dr), v_dim (dv), theta,
    epsilon.  RoPE is the rotate-half form (`rotate_half`).  With
    `yarn_factor` (and `yarn_original_max`, `yarn_beta_fast`,
    `yarn_beta_slow`, `yarn_mscale`, `yarn_mscale_all_dim`) the rotary
    frequencies are YaRN's (`yarn_inv_freq`), cos and sin times
    mscale(yarn_mscale) / mscale(yarn_mscale_all_dim), and the softmax
    scale mscale(yarn_mscale_all_dim)^2 / sqrt(dn + dr) (`yarn_mscale`).
    With `rotary` false (Kimi-Linear's `mla_use_nope`) NOTHING is turned:
    q_pe and the shared k_pe enter the scores as the projections leave
    them, no frequency table is made and `pdtpu.mla.rope` holds nothing;
    the scale stays 1 / sqrt(dn + dr).
    The keys are dn + dr wide and the values dv: on one TPU the two-width
    flash kernels (attention_ops.flash_single_chip), elsewhere dense
    attention."""
    import jax.numpy as jnp

    from .ring_attention import attention as dense_attention
    from .attention_ops import flash_single_chip

    x = ins["X"][0]
    wkva, wkvb, wo = (ins[k][0] for k in ("WKVA", "WKVB", "WO"))
    H = int(attrs["num_heads"])
    dn, dr, dv = (int(attrs[k]) for k in ("qk_nope_dim", "qk_rope_dim",
                                          "v_dim"))
    theta = float(attrs.get("theta", 10000.0))
    eps = float(attrs.get("epsilon", 1e-5))
    B, T, _ = x.shape
    rank = wkva.shape[1] - dr
    latent = bool(ins.get("WQA"))
    rotary = bool(attrs.get("rotary", True))
    factor = float(attrs.get("yarn_factor", 1.0))
    inv_freq = scale = None
    turn = 1.0
    if factor != 1.0 and not rotary:
        raise ValueError("latent_attention: YaRN scales the rotary "
                         "frequencies, and `rotary` is false")
    if factor != 1.0:
        inv_freq = yarn_inv_freq(
            dr, theta, factor, int(attrs["yarn_original_max"]),
            float(attrs["yarn_beta_fast"]), float(attrs["yarn_beta_slow"]))
        all_dim = yarn_mscale(factor, float(attrs["yarn_mscale_all_dim"]))
        turn = yarn_mscale(factor, float(attrs["yarn_mscale"])) / all_dim
        scale = all_dim * all_dim / (dn + dr) ** 0.5
    if not ctx.in_grad_replay():
        _MET_MLA_LAYERS.inc(qk_dim=str(dn + dr), v_dim=str(dv),
                            kv_rank=str(rank))
        _MET_MLA_POSITIONS.inc(positions="rope" if rotary else "none")
        if latent:
            _MET_MLA_QUERY_LATENTS.inc(q_rank=str(ins["WQA"][0].shape[1]),
                                       yarn_factor=f"{factor:g}")
    heads = lambda a: jnp.swapaxes(a, 1, 2)          # [B,T,H,d] <-> [B,H,T,d]

    def rope(a):
        out = rotate_half(a, theta, inv_freq=inv_freq)
        return out if turn == 1.0 else out * jnp.asarray(turn, a.dtype)

    with part_scope("mla.project"):
        if latent:
            q = rms(x @ ins["WQA"][0], eps, (2,), ins["QNorm"][0]) @ ins[
                "WQB"][0]
        else:
            q = x @ ins["WQ"][0]
        q = heads(q.reshape(B, T, H, dn + dr))
        c = x @ wkva
        c_kv = rms(c[..., :rank], eps, (2,), ins["KVNorm"][0])
        kv = heads((c_kv @ wkvb).reshape(B, T, H, dn + dv))
        if not rotary:      # the shared key as it lies, in every head
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                c[:, None, :, rank:], (B, H, T, dr))], axis=-1)
            v = kv[..., dn:]
    if rotary:
        with part_scope("mla.rope"):
            k_pe = rope(c[:, None, :, rank:])                  # [B,1,T,dr]
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_pe, (B, H, T, dr))],
                axis=-1)
            v = kv[..., dn:]
    with part_scope("mla.attend"):
        got = flash_single_chip(ctx, q, k, v, True, scale=scale)
        attn, saved = got if got is not None else (
            dense_attention(q, k, v, causal=True, scale=scale), None)
    out = heads(attn).reshape(B, T, H * dv) @ wo
    if saved is not None:
        ctx.keep_for_grad(attrs, [out], saved)
    return {"Out": [out]}


def _sinkhorn(m, iters: int, eps: float):
    """`iters` times: every row of M [n, n, ...] (row, column, then a
    token's axes) over its sum + eps, then every column over its sum +
    eps.  One `lax.scan` step an iteration, so the step's graph holds the
    iteration once; the sums are adds of slices, elementwise on whole
    token vectors (the token axis on the lanes)."""
    from jax import lax

    n = m.shape[0]

    def step(m, _):
        m = m / (sum(m[:, j] for j in range(n)) + eps)[:, None]
        m = m / (sum(m[i] for i in range(n)) + eps)[None]
        return m, None

    return lax.scan(step, m, None, length=iters)[0]


def _hc_gates(proj, inv, alpha, beta, n: int, iters: int, eps: float,
              clamp):
    """(H_pre [n, B, T], H_post [n, B, T], M [n, n, B, T]) of the raw
    projection vec(X) Phi [K, B, T], the norm's factor [B, T], Alpha [3]
    and Beta [K]; K = n + n + n n.  Small tensors, the token axis last."""
    import jax
    import jax.numpy as jnp

    a = jnp.concatenate([jnp.broadcast_to(v, (k,))
                         for v, k in zip(alpha, (n, n, n * n))])
    ht = proj * inv * a[:, None, None] + beta[:, None, None]
    raw = jnp.exp(jnp.clip(ht[2 * n:], clamp[0], clamp[1]))
    return (jax.nn.sigmoid(ht[:n]), 2.0 * jax.nn.sigmoid(ht[n:2 * n]),
            _sinkhorn(raw.reshape((n, n) + raw.shape[1:]), iters, eps))


def _streams_of(x):
    """X [B, n, T, C] -> its n streams [B, T, C], at least float32."""
    xw = x.astype(wide_dtype(x.dtype))
    return [xw[:, i] for i in range(x.shape[1])]


def _over_columns(a, b):
    """sum over the last axis of a * b: one number a token."""
    import jax.numpy as jnp

    return jnp.sum(a * b, axis=-1)


def _hc_pre(n: int, iters: int, eps: float, norm_eps: float, clamp,
            exact: bool, kernels: bool):
    """(forward, backward) of `hyper_connection_pre`, both written out.

      forward(X [B, n, T, C], Phi [n, C, K], Alpha, Beta) -> (U, H_post
        [B, T, n], M [B, T, n, n], the raw projection [K, B, T], the
        norm's factor [B, T])
      backward(X, Phi, Alpha, Beta, projection, factor, dU, dH_post, dM)
        -> (dX, dPhi, dAlpha, dBeta)

    The backward makes the gates and the Sinkhorn iterations again from
    the kept projection and factor (mHC's own recipe, arXiv:2512.24880
    section 4.3) and writes X's gradient stream by stream.  (Autodiff of a
    stream's slice is a `pad` to all the streams: n float32 tensors of the
    streams' whole size a sub-layer, and the op read 136 ms a step in
    `xing4_train_t4096` against 70 so; PERF.md, PR 39.)  `exact`: the
    products take X and Phi as stored (a TPU).  `kernels`: every pass over
    the streams is one kernel of ops/pallas_kernels/hyper_connection.py
    (the gates stay here: small tensors, the token axis last)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .pallas_kernels import hyper_connection as K

    gates = lambda *a: _hc_gates(*a, n, iters, eps, clamp)  # noqa: E731

    def stored(a, wide):
        return a if exact else a.astype(wide)

    def forward(x, phi, alpha, beta):
        wide = wide_dtype(x.dtype)
        if kernels:
            with part_scope("hc.read"):
                u, proj, inv = K.pre_fwd(
                    x, K.phi_transposed(phi), alpha.astype(wide),
                    beta.astype(wide), norm_eps=norm_eps)
                proj = jnp.moveaxis(proj, 2, 0)                 # [K, B, T]
            with part_scope("hc.gates"):
                _, h_post, m = gates(proj, inv, alpha.astype(wide),
                                     beta.astype(wide))
        else:
            with part_scope("hc.gates"):
                # vec(X) Phi stream by stream, X as it lies; the SMALL
                # result turned so that the token axis is last
                proj = jnp.moveaxis(sum(
                    lax.dot_general(stored(x[:, i], wide),
                                    stored(phi[i], wide),
                                    (((2,), (0,)), ((), ())),
                                    preferred_element_type=wide)
                    for i in range(n)), 2, 0)                   # [K, B, T]
                xw = x.astype(wide)
                inv = lax.rsqrt(jnp.mean(xw * xw, axis=(1, 3)) + norm_eps)
                h_pre, h_post, m = gates(proj, inv, alpha.astype(wide),
                                         beta.astype(wide))
                # what meets the streams leaves as one column a token ([B,
                # T, ..]: the token axis where the streams have it), so
                # that XLA turns these small tensors and not the streams
                read = jnp.moveaxis(h_pre, 0, 2)                # [B, T, n]
            with part_scope("hc.read"):
                u = sum(read[..., i:i + 1] * xi
                        for i, xi in enumerate(_streams_of(x))).astype(
                            x.dtype)
        return (u, jnp.moveaxis(h_post, 0, 2),
                jnp.transpose(m, (2, 3, 0, 1)), proj, inv)

    def backward(x, phi, alpha, beta, proj, inv, du, dh_post, dm):
        wide = wide_dtype(x.dtype)
        with part_scope("hc.gates"):
            (h_pre, _, _), back = jax.vjp(gates, proj, inv,
                                          alpha.astype(wide),
                                          beta.astype(wide))
        with part_scope("hc.read"):
            if kernels:
                du = du.astype(x.dtype)
                dh_pre = jnp.moveaxis(K.pre_bwd_a(x, du), 2, 0)
            else:
                du, xs = du.astype(wide), _streams_of(x)
                dh_pre = jnp.stack([_over_columns(du, xi) for xi in xs])
        with part_scope("hc.gates"):
            dproj, dinv, dalpha, dbeta = back(
                (dh_pre, jnp.moveaxis(dh_post.astype(wide), 2, 0),
                 jnp.transpose(dm.astype(wide), (2, 3, 0, 1))))
            dproj = jnp.moveaxis(dproj, 0, 2)                   # [B, T, K]
            # d inv / d x = -inv^3 x / (n C); a column a token, as `read`
            cols = jnp.stack(
                [*h_pre, -dinv * inv * inv * inv / (n * x.shape[3])], axis=-1)
            if not kernels:
                dphi = jnp.stack([
                    lax.dot_general(stored(x[:, i], wide), dproj,
                                    (((0, 1), (0, 1)), ((), ())),
                                    preferred_element_type=wide)
                    for i in range(n)])                         # [n, C, K]
                through = [lax.dot_general(dproj, phi[i].astype(wide),
                                           (((2,), (1,)), ((), ())))
                           for i in range(n)]                   # [B, T, C]
        with part_scope("hc.read"):
            if kernels:
                dx, dphi = K.pre_bwd_b(x, du, cols, dproj,
                                       K.phi_transposed(phi))
            else:
                dx = jnp.stack([
                    (cols[..., i:i + 1] * du + cols[..., n:] * xi
                     + t).astype(x.dtype)
                    for i, (xi, t) in enumerate(zip(xs, through))], axis=1)
        return (dx, dphi.astype(phi.dtype),
                dalpha.astype(alpha.dtype), dbeta.astype(beta.dtype))

    return forward, backward


def _hc_post(kernels: bool):
    """(forward, backward) of `hyper_connection_post` on (X [B, n, T, C],
    Y, H_post [B, T, n], M [B, T, n, n]), both written out: the new
    streams, and the streams' gradient, stream by stream and stacked once;
    the gates' gradients sums over a token's columns.  `kernels`: one
    kernel of ops/pallas_kernels/hyper_connection.py each."""
    import jax.numpy as jnp

    from .pallas_kernels import hyper_connection as K

    def forward(x, y, h_post, m):
        with part_scope("hc.write"):
            if kernels:
                return K.post_fwd(x, y, h_post, m)
            yw = y.astype(wide_dtype(x.dtype))
            # Out[:, i] = sum_j M[i, j] X[:, j] + H_post[i] Y
            xs = _streams_of(x)
            return jnp.stack([
                sum((m[:, :, i, j, None] * xs[j] for j in range(len(xs))),
                    h_post[:, :, i, None] * yw).astype(x.dtype)
                for i in range(len(xs))], axis=1)

    def backward(x, y, h_post, m, dout):
        with part_scope("hc.write"):
            if kernels:
                dx, dy, dh_post, dm = K.post_bwd(x, y, dout.astype(x.dtype),
                                                 h_post, m)
            else:
                xs, yw = _streams_of(x), y.astype(wide_dtype(x.dtype))
                ds = _streams_of(dout)
                # dX[:, j] = sum_i M[i, j] dOut[:, i]
                dx = jnp.stack([
                    sum(m[:, :, i, j, None] * ds[i]
                        for i in range(len(ds))).astype(x.dtype)
                    for j in range(len(ds))], axis=1)
                dy = sum(h_post[:, :, i, None] * di
                         for i, di in enumerate(ds))
                dh_post = jnp.stack([_over_columns(d, yw) for d in ds],
                                    axis=-1)
                dm = jnp.stack([jnp.stack([_over_columns(d, xj)
                                           for xj in xs], axis=-1)
                                for d in ds], axis=-2)
        return (dx.astype(x.dtype), dy.astype(y.dtype),
                dh_post.astype(h_post.dtype), dm.astype(m.dtype))

    return forward, backward


def _hc_kernels(ctx, x, op: str) -> bool:
    """Whether the hyper-connection op `op` on X [B, n, T, C] takes the
    kernels: one TPU, no mesh, kernels not disabled, and a shape they take
    (ops/pallas_kernels/hyper_connection.py `usable`).  Counts the
    emission by the path taken."""
    from .pallas_kernels import hyper_connection as K
    from .pallas_kernels._common import pallas_dispatch_ok

    _, n, T, C = x.shape
    kernels = pallas_dispatch_ok(ctx) and K.usable(n, T, C, x.dtype)
    if not ctx.in_grad_replay():
        _MET_HC_KERNELS.inc(op=op, path="pallas" if kernels else "xla")
    return kernels


def _hc_pre_parts(ctx, ins, attrs, op: str):
    """(X, Phi [n, C, K], Alpha, Beta, the three Phi slots' tensors,
    `_hc_pre`'s pair) of a `hyper_connection_pre` op or its grad op."""
    import jax.numpy as jnp

    x = ins["X"][0]
    n = int(attrs["streams"])
    phis = [ins[k][0] for k in ("PhiPre", "PhiPost", "PhiRes")]
    if x.ndim != 4 or x.shape[1] != n or [p.shape for p in phis] != [
            (n * x.shape[3], k) for k in (n, n, n * n)]:
        raise ValueError(f"hyper_connection_pre: X {x.shape} is not [B, "
                         f"{n} streams, T, C] for Phi "
                         f"{[p.shape for p in phis]}")
    phi = jnp.concatenate(phis, axis=1).astype(x.dtype).reshape(
        n, x.shape[3], (2 + n) * n)
    fns = _hc_pre(
        n, int(attrs["sinkhorn_iters"]), float(attrs["epsilon"]),
        float(attrs["norm_epsilon"]),
        (float(attrs["clamp_min"]), float(attrs["clamp_max"])),
        ctx.target_platform() == "tpu", _hc_kernels(ctx, x, op))
    return x, phi, ins["Alpha"][0], ins["Beta"][0], phis, fns


@register_op("hyper_connection_pre",
             grad=_own_grad_maker("hyper_connection_pre_grad",
                                  kept=("Proj", "Inv")))
def hyper_connection_pre(ctx, ins, attrs):
    """What a sub-layer reads of n residual streams, and how its result
    goes back (manifold-constrained hyper-connections, mHC,
    arXiv:2512.24880, over hyper-connections, arXiv:2409.19606).  X [B, n,
    T, C]: n streams of C columns a token, stream by stream (a stream is
    one slab; side by side in a token's row, [B, T, n C], every
    sub-layer's write would be a concatenation along the lanes, which XLA
    makes n pads of the whole width); vec(X) of a token is its n rows one
    after the other.  PhiPre, PhiPost [n C, n], PhiRes [n C, n n]; Alpha
    [3] = (a_pre, a_post, a_res); Beta [n + n + n n] = (b_pre, b_post,
    b_res row by row).

      xbar   = vec(X) / sqrt(mean(vec(X)^2) + norm_epsilon)   (no gain)
      Ht_pre = a_pre xbar PhiPre + b_pre,  Ht_post, Ht_res alike
      H_pre  = sigmoid(Ht_pre);  H_post = 2 sigmoid(Ht_post)
      M      = exp(clip(Ht_res, clamp_min, clamp_max)) as n x n, then
               `sinkhorn_iters` times: rows over their sum + epsilon,
               columns over their sum + epsilon
      U      = sum_i H_pre[i] X[i]                     [B, T, C]

    -> U (X's dtype), HPost [B, T, n] and HRes [B, T, n, n] (HRes[b, t, i,
    j] = M_t[i, j]; both at least float32; inside, the gates and the
    iterations run with the token axis last, on the lanes, and leave as one
    column a token, the orientation the streams meet them in), and what
    the grad op `hyper_connection_pre_grad` needs besides the inputs: Proj
    [n + n + n n, B, T], the raw product vec(X) Phi, and Inv [B, T], the
    norm's factor (both at least float32; no gradient flows into them).
    Everything but the product X Phi is at least float32 whatever X's
    dtype; on a TPU the product takes X and Phi as they are stored and
    accumulates in float32, so bf16 operands enter it exactly and X is read
    at its own width (elsewhere both are widened first: XLA's CPU runtime
    has no bf16 x bf16 -> f32 product); the norm's factor is a token's
    scalar and is applied to the product.

    On one TPU, with C in 128s, T in whole token tiles and bf16 or float32
    streams, one Pallas kernel reads the streams once for the product, the
    statistic and the weighted read (ops/pallas_kernels/
    hyper_connection.py; the gates and the Sinkhorn iterations stay XLA's);
    everywhere else (the CPU, a mesh, other shapes) plain jax.numpy
    (`_hc_pre`); `hyper_connection_kernels_traced_total` says which.
    attrs: streams (n), sinkhorn_iters, epsilon, norm_epsilon, clamp_min,
    clamp_max."""
    x, phi, alpha, beta, _, (forward, _) = _hc_pre_parts(ctx, ins, attrs,
                                                         "pre")
    if not ctx.in_grad_replay():
        _MET_HC_LAYERS.inc(streams=str(x.shape[1]), dim=str(x.shape[3]),
                           sinkhorn_iters=str(int(attrs["sinkhorn_iters"])))
    u, h_post, h_res, proj, inv = forward(x, phi, alpha, beta)
    return {"U": [u], "HPost": [h_post], "HRes": [h_res], "Proj": [proj],
            "Inv": [inv]}


@register_op("hyper_connection_pre_grad", grad=None)
def hyper_connection_pre_grad(ctx, ins, attrs):
    """`hyper_connection_pre`'s backward from the forward op's inputs, its
    outputs Proj and Inv and the cotangents of U, HPost and HRes (absent:
    zeros): the gates and the Sinkhorn iterations made again from Proj and
    Inv and differentiated in XLA, the passes over the streams (dH_pre =
    <dU, X[i]> a token; X@GRAD and Phi's gradient) as the two backward
    kernels where the forward took its kernel, else plain jax.numpy ->
    X@GRAD, PhiPre@GRAD, PhiPost@GRAD, PhiRes@GRAD, Alpha@GRAD,
    Beta@GRAD."""
    import jax.numpy as jnp

    x, phi, alpha, beta, phis, (_, backward) = _hc_pre_parts(
        ctx, ins, attrs, "pre_grad")
    if not (ins.get("Proj") and ins.get("Inv")):
        raise ValueError("hyper_connection_pre_grad: the forward op names "
                         "no Proj and Inv outputs to keep")
    B, n, T, _ = x.shape
    wide = wide_dtype(x.dtype)

    def ct(slot, shape, dtype):   # zeros where nothing reads the output
        got = (ins.get(slot + GRAD_SUFFIX) or [None])[0]
        return jnp.zeros(shape, dtype) if got is None else got

    dx, dphi, dalpha, dbeta = backward(
        x, phi, alpha, beta, ins["Proj"][0], ins["Inv"][0],
        ct("U", (B, T, x.shape[3]), x.dtype), ct("HPost", (B, T, n), wide),
        ct("HRes", (B, T, n, n), wide))
    dphis = jnp.split(dphi.reshape(n * x.shape[3], -1), (n, 2 * n), axis=1)
    out = {"X" + GRAD_SUFFIX: [dx], "Alpha" + GRAD_SUFFIX: [dalpha],
           "Beta" + GRAD_SUFFIX: [dbeta]}
    for slot, p, d in zip(("PhiPre", "PhiPost", "PhiRes"), phis, dphis):
        out[slot + GRAD_SUFFIX] = [d.astype(p.dtype)]
    return out


def _hc_post_parts(ctx, ins, op: str):
    """(X, Y, HPost, HRes, `_hc_post`'s pair) of a `hyper_connection_post`
    op or its grad op."""
    x, y, h_post, h_res = (ins[k][0] for k in ("X", "Y", "HPost", "HRes"))
    n = x.shape[1]
    if (x.ndim != 4 or x.shape[:1] + x.shape[2:] != y.shape
            or h_res.shape[2:] != (n, n)):
        raise ValueError(f"hyper_connection_post: X {x.shape}, Y {y.shape},"
                         f" HRes {h_res.shape}")
    return x, y, h_post, h_res, _hc_post(_hc_kernels(ctx, x, op))


@register_op("hyper_connection_post",
             grad=_own_grad_maker("hyper_connection_post_grad"))
def hyper_connection_post(ctx, ins, attrs):
    """A sub-layer's result Y [B, T, C] written back into the n streams X
    [B, n, T, C] through `hyper_connection_pre`'s HPost [B, T, n] and HRes
    [B, T, n, n]:  Out[i] = sum_j HRes[i, j] X[j] + HPost[i] Y, at least
    float32 inside and ONE rounding to X's dtype.  One Pallas kernel where
    `hyper_connection_pre` takes its kernel (one TPU, C in 128s, T in
    whole token tiles), plain jax.numpy everywhere else (`_hc_post`); the
    backward is the op `hyper_connection_post_grad`."""
    x, y, h_post, h_res, (forward, _) = _hc_post_parts(ctx, ins, "post")
    return {"Out": [forward(x, y, h_post, h_res)]}


@register_op("hyper_connection_post_grad", grad=None)
def hyper_connection_post_grad(ctx, ins, attrs):
    """`hyper_connection_post`'s backward from its inputs and Out@GRAD:
    X@GRAD[j] = sum_i HRes[i, j] Out@GRAD[i], Y@GRAD = sum_i HPost[i]
    Out@GRAD[i], and the gates' gradients, sums over a token's columns
    (HPost@GRAD[i] = <Out@GRAD[i], Y>, HRes@GRAD[i, j] = <Out@GRAD[i],
    X[j]>); one kernel where the forward took its kernel, else plain
    jax.numpy."""
    x, y, h_post, h_res, (_, backward) = _hc_post_parts(ctx, ins,
                                                        "post_grad")
    dx, dy, dh_post, dh_res = backward(
        x, y, h_post, h_res, ins["Out" + GRAD_SUFFIX][0])
    return {"X" + GRAD_SUFFIX: [dx], "Y" + GRAD_SUFFIX: [dy],
            "HPost" + GRAD_SUFFIX: [dh_post],
            "HRes" + GRAD_SUFFIX: [dh_res]}


@register_op("hyper_connection_sum")
def hyper_connection_sum(ctx, ins, attrs):
    """Where the streams end: X [B, n, T, C] -> the sum of its n streams
    [B, T, C] (hyper-connections' own, arXiv:2409.19606), added in at least
    float32 with one rounding."""
    import jax.numpy as jnp

    x = ins["X"][0]
    with part_scope("hc.read"):
        return {"Out": [jnp.sum(x.astype(wide_dtype(x.dtype)),
                                axis=1).astype(x.dtype)]}


@register_op("mtp_project")
def mtp_project(ctx, ins, attrs):
    """The way into a multi-token-prediction module (DeepSeek-V3,
    arXiv:2412.19437, section 2.2, equation 21): H [B, T, D] the hidden
    state a position has at the depth before (the main tower's, before its
    final norm), E [B, T, D] the embedding of the token `depth` places on;
    Out = [RMSNorm(H; HNorm) ; RMSNorm(E; ENorm)] W, W [2 D, D], computed
    as two products on W's halves (no [B, T, 2 D] is made).  attrs:
    epsilon, depth."""
    h, e, w = ins["H"][0], ins["E"][0], ins["W"][0]
    D = h.shape[-1]
    eps = float(attrs.get("epsilon", 1e-5))
    if not ctx.in_grad_replay():
        _MET_MTP_MODULES.inc(depth=str(int(attrs.get("depth", 1))))
    with part_scope("mtp.project"):
        return {"Out": [rms(h, eps, (2,), ins["HNorm"][0]) @ w[:D]
                        + rms(e, eps, (2,), ins["ENorm"][0]) @ w[D:]]}


def causal_taps(g, w):
    """g [B, T, C], w [C, L], both wide -> c_t = sum_j w[:, j] g_{t - (L -
    1) + j}, g zero before the sequence starts: depthwise and causal, the
    LAST tap on the current token, as L shifted multiply-adds that XLA
    fuses into one pass over g.  The plain emission of both short
    convolutions (`gated_short_conv`, `gated_delta_rule`) and the oracle of
    their kernels."""
    import jax.numpy as jnp

    T, taps = g.shape[1], w.shape[1]
    c = w[:, taps - 1] * g
    for back in range(1, min(taps, T)):       # the tap `back` tokens ago
        c = c + w[:, taps - 1 - back] * jnp.pad(
            g, ((0, 0), (back, 0), (0, 0)))[:, :T]
    return c


def gated_short_conv_plain(x, w):
    """X [B, T, 3D], Filter [D, L] -> Out [B, T, D] (`gated_short_conv`'s
    equations): `causal_taps` between the two gates, at least float32
    inside, X's dtype out.  What the kernels of
    ops/pallas_kernels/short_conv.py compute, in plain jax.numpy."""
    import jax.numpy as jnp

    wide = wide_dtype(x.dtype)
    gate_in, gate_out, u = jnp.split(x.astype(wide), 3, axis=-1)
    with part_scope("conv.gate"):
        g = gate_in * u
    with part_scope("conv.taps"):
        c = causal_taps(g, w.astype(wide))
    with part_scope("conv.gate"):
        out = gate_out * c
    return out.astype(x.dtype)


def _short_conv(ctx, ins, op: str):
    """(X, Filter, whether the kernels of ops/pallas_kernels/short_conv.py
    run) of a `gated_short_conv` op or its grad op (`op`: fwd, grad): one
    TPU, no mesh, kernels not disabled, and a shape they take (`usable`).
    Counts the emission by the path taken."""
    from .pallas_kernels import short_conv as kernels
    from .pallas_kernels._common import pallas_dispatch_ok

    x, w = ins["X"][0], ins["Filter"][0]
    dim, taps = w.shape
    if x.ndim != 3 or x.shape[-1] != 3 * dim:
        raise ValueError(f"gated_short_conv: X {x.shape} is not [B, T, 3 x "
                         f"{dim}] for a Filter {w.shape}")
    take = pallas_dispatch_ok(ctx) and kernels.usable(x.shape[1], dim, taps,
                                                      x.dtype)
    if not ctx.in_grad_replay():
        _MET_CONV_KERNELS.inc(op=op, path="pallas" if take else "xla")
    return x, w, take


@register_op("gated_short_conv",
             grad=_own_grad_maker("gated_short_conv_grad"))
def gated_short_conv(ctx, ins, attrs):
    """The gated short convolution of LFM2 (transformers' `Lfm2ShortConv`),
    without its two projections: X [B, T, 3D] is the input projection's
    result, three thirds B, C, u in that order; Filter [D, L] holds L taps
    a channel.

      g = B * u                                  (the input gate)
      c_t = sum_{j < L} Filter[:, j] * g_{t - (L - 1) + j}, g zero before
            the sequence starts: depthwise and causal, the LAST tap on the
            current token
      Out = C * c                                (the output gate)  [B, T, D]

    No position enters.  At least float32 inside, X's dtype out.  On one
    TPU, with D in 128-lane blocks, T in whole row tiles and bf16 or
    float32 X, one Pallas kernel reads the three thirds where the
    projection wrote them, widens in VMEM and writes Out once
    (ops/pallas_kernels/short_conv.py); everywhere else (the CPU, a mesh,
    other shapes) L shifted multiply-adds that XLA fuses into one pass over
    X (`gated_short_conv_plain`; bound by HBM: PERF.md, PR 33, has it
    against `lax.conv_general_dilated`); `short_conv_kernels_traced_total`
    says which.  The backward is the op `gated_short_conv_grad`."""
    from .pallas_kernels import short_conv as kernels

    x, w, take = _short_conv(ctx, ins, "fwd")
    if not ctx.in_grad_replay():
        _MET_CONV_LAYERS.inc(dim=str(w.shape[0]), kernel=str(w.shape[1]))
    if not take:
        return {"Out": [gated_short_conv_plain(x, w)]}
    with part_scope("conv.taps"):
        return {"Out": [kernels.short_conv_fwd(x, w)]}


@register_op("gated_short_conv_grad", grad=None)
def gated_short_conv_grad(ctx, ins, attrs):
    """`gated_short_conv`'s backward from X, Filter and Out@GRAD alone (g
    and c are made again; nothing of the forward is kept) -> X@GRAD in X's
    dtype, Filter@GRAD summed in float32.  The backward kernel where the
    forward took its kernel, else `jax.vjp` of the plain emission (plain
    HLO, which XLA merges with the forward's)."""
    import jax

    from .pallas_kernels import short_conv as kernels

    x, w, take = _short_conv(ctx, ins, "grad")
    dout = ins["Out" + GRAD_SUFFIX][0].astype(x.dtype)
    if take:
        with part_scope("conv.taps"):
            dx, dw = kernels.short_conv_bwd(dout, x, w)
    else:
        dx, dw = jax.vjp(gated_short_conv_plain, x, w)[1](dout)
    return {"X" + GRAD_SUFFIX: [dx],
            "Filter" + GRAD_SUFFIX: [dw.astype(w.dtype)]}


# ---------------------------------------------------------------------------
# analytic cost formulas (analysis/cost.py; mechanism in registry.py)

from .registry import register_cost  # noqa: E402


def _rms_norm_cost(ins, outs, attrs):
    """Square, mean, rsqrt-scale, gain: four passes' worth of elementwise
    work over X (the analyzer's default would count one)."""
    x = ins.get("X", [None])[0]
    return {} if x is None else {"flops": 4 * x.size}


def _rope_cost(ins, outs, attrs):
    """Four multiplies and two adds an element pair, and the table's
    cos/sin (T * D/2 each)."""
    x = ins.get("X", [None])[0]
    if x is None or len(x.shape) < 2:
        return {}
    return {"flops": 3 * x.size + x.shape[-2] * x.shape[-1]}


def _latent_attention_cost(ins, outs, attrs):
    """The four projections and the causal half of the two score products
    (q k^T over dn + dr, p v over dv)."""
    x = ins.get("X", [None])[0]
    if x is None or len(x.shape) != 3:
        return {}
    b, t, _ = x.shape
    heads = int(attrs["num_heads"])
    widths = sum(int(attrs[k]) for k in ("qk_nope_dim", "qk_rope_dim",
                                         "v_dim"))
    weights = sum(ins[k][0].size for k in ("WQ", "WQA", "WQB", "WKVA",
                                           "WKVB", "WO") if ins.get(k))
    return {"flops": 2 * b * t * weights + b * heads * t * t * widths}


def _gated_short_conv_cost(ins, outs, attrs):
    """A multiply-add a tap and the two gates, per output element."""
    x = ins.get("X", [None])[0]
    w = ins.get("Filter", [None])[0]
    if x is None or w is None:
        return {}
    return {"flops": (2 * w.shape[1] + 2) * x.size // 3}


def _head_norm_rope_cost(ins, outs, attrs):
    """`rope`'s count, and `rms_norm`'s where the op norms."""
    x = ins.get("X", [None])[0]
    if x is None or len(x.shape) != 3:
        return {}
    norm = 4 * x.size if attrs.get("epsilon") is not None else 0
    return {"flops": norm + 3 * x.size
            + x.shape[1] * x.shape[2] // int(attrs["num_heads"])}


def _head_norm_rope_grad_cost(ins, outs, attrs):
    """Twice the forward's, as `generic_grad` counts a backward."""
    return {k: 2 * v for k, v in _head_norm_rope_cost(ins, outs,
                                                      attrs).items()}


register_cost("gated_short_conv", _gated_short_conv_cost)
register_cost("head_norm_rope", _head_norm_rope_cost)
register_cost("head_norm_rope_grad", _head_norm_rope_grad_cost)
register_cost("latent_attention", _latent_attention_cost)
register_cost("rms_norm", _rms_norm_cost)
register_cost("rope", _rope_cost)
