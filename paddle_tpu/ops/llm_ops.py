"""Ops of the post-2020 decoder block that the GPT-2-shaped tower lacks:
RMSNorm, rotary position embedding (beyond-reference, like the rest of
the transformer tier; first user: OLMoE, models/transformer.py), latent
attention (DeepSeek-V2's MLA; first user: Moonlight-16B-A3B), the gated
short convolution (LFM2's token mixer in the layers that do not attend) and
the noising of block-diffusion training (BD3-LM's objective; first user:
SDAR-30B-A3B).

All but latent attention and `head_norm_rope` are plain jax.numpy, so
`generic_grad` differentiates them by re-emission and XLA's CSE merges the
re-emitted forward with the first.  `head_norm_rope` (Q or K from the
projection's layout to attention's: the per-head norm, the rotary turn and
the head split in one pass; first users: OLMoE, LFM2, SDAR) brings its own
grad op, whose emitter needs the forward's inputs alone and never emits the
forward.  Statistics and rotations are at least float32 whatever the
compute dtype (`wide_dtype`); the result goes back to the input's dtype."""

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
_MET_MLA_LAYERS = _MET.counter(
    "mla_layers_traced_total",
    "latent attention layers traced (forward emission; once a compile, not "
    "once a step), by the width of a head's queries and keys (qk_dim), of "
    "its values (v_dim) and the rank of the K/V latent (kv_rank)")


_MET_CONV_LAYERS = _MET.counter(
    "short_conv_layers_traced_total",
    "gated short convolution ops traced (forward emission; once a compile, "
    "not once a step), by the channels convolved (dim) and the taps a "
    "channel (kernel)")


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


def rotate_half(x, theta: float, period: int = 0):
    """X [..., T, D] with D even turned by position: the pair (x[i], x[i +
    D/2]) of position t by the angle t * theta ** (-2i / D), positions
    0..T-1, or, with a `period`, row r at position r mod period (copies of
    one sequence side by side); at least float32 inside, X's dtype out."""
    import jax.numpy as jnp

    T, D = x.shape[-2], x.shape[-1]
    if D % 2:
        raise ValueError(f"rope op: head size {D} must be even")
    half = D // 2
    xf = x.astype(wide_dtype(x.dtype))
    inv_freq = theta ** (-jnp.arange(half, dtype=xf.dtype) / half)
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
                         period: int = 0):
    """X [B, T, heads * D] -> [B, heads, T, D]: per head and row an
    RMSNorm over the head's D columns where `eps` is given (times `gain`
    [D], one for all heads, where given), then the rotate-half turn at the
    row's position (`rotate_half`'s angles), at least float32 from end to
    end with ONE rounding to X's dtype.  What the kernels of
    ops/pallas_kernels/head_norm_rope.py compute, in plain jax.numpy."""
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
    cos, sin = (t[None, :, None, :] for t in tables(T, D, theta, period,
                                                    dtype=wide))
    out = y * cos + jnp.roll(y, D // 2, axis=-1) * sin
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
    pack = kernels.pack_of(x.shape[1], x.shape[2] // heads, heads,
                           x.dtype) if pallas_dispatch_ok(ctx) else 0
    return x, gain, kw, pack


def _head_norm_rope_grad_maker(op, wanted):
    """One `head_norm_rope_grad` desc: the forward op's inputs and Out's
    cotangent in, the wanted inputs' cotangents out, the forward's attrs
    (its `part` and `__uid__` with them).  Not a `generic_grad`: the
    backward needs no forward emitted again."""
    outs = {slot + GRAD_SUFFIX: [n + GRAD_SUFFIX if n in wanted else ""
                                 for n in names]
            for slot, names in op.inputs.items()}
    if not any(n for names in outs.values() for n in names):
        return []
    ins = {slot: list(names) for slot, names in op.inputs.items()}
    ins["Out" + GRAD_SUFFIX] = [n + GRAD_SUFFIX for n in op.outputs["Out"]]
    return [("head_norm_rope_grad", ins, outs, dict(op.attrs))]


@register_op("head_norm_rope", grad=_head_norm_rope_grad_maker)
def head_norm_rope(ctx, ins, attrs):
    """Q (or K) from the projection's layout to attention's in one pass: X
    [B, T, H * D] -> Out [B, H, T, D] with, per head and row, an RMSNorm
    over the head's D columns (attr `epsilon`; absent: no norm) times
    Scale [D] (optional; ONE gain for all heads), then the rotate-half
    rotary turn (`rope`'s: attrs `theta`, `period`).  At least float32
    inside, one rounding at the end.  attrs: `num_heads`.

    On one TPU with heads of 128 or 64 lanes and T in 128s a Pallas kernel
    reads each head's column block where it lies and writes it where the
    flash kernels read it (ops/pallas_kernels/head_norm_rope.py);
    everywhere else (the CPU, a mesh, other head sizes) plain jax.numpy
    (`head_norm_rope_plain`)."""
    from .pallas_kernels import head_norm_rope as kernels

    x, gain, kw, pack = _qk_prep(ctx, ins, attrs)
    if not ctx.in_grad_replay():
        _MET_QK_PREP.inc(
            path=("xla", "pallas", "pallas_packed")[pack],
            head_dim=str(x.shape[2] // kw["heads"]), heads=str(kw["heads"]),
            norm="none" if kw["eps"] is None else "head")
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
    2.1; the `q_lora_rank` null form of DeepSeek-V3's modelling code) over
    X [B, T, D], causal, H heads:

      q = X WQ -> [B, T, H, dn + dr] = (q_nope, q_pe)
      c = X WKVA -> [B, T, r + dr] = (c_kv, k_pe): the latent a serving
          cache would hold, and ONE rotary key all heads share
      kv = RMSNorm(c_kv; KVNorm) WKVB -> [B, T, H, dn + dv] = (k_nope, v)
      q = [q_nope; rope(q_pe)], k = [k_nope; rope(k_pe)] in every head,
      softmax(q k^T / sqrt(dn + dr)) v -> [B, T, H dv], times WO.

    attrs: num_heads, qk_nope_dim (dn), qk_rope_dim (dr), v_dim (dv), theta,
    epsilon.  RoPE is the rotate-half form (`rotate_half`).  The keys are
    dn + dr wide and the values dv: on one TPU the two-width flash kernels
    (attention_ops.flash_single_chip), elsewhere dense attention."""
    import jax.numpy as jnp

    from ..parallel.ring_attention import attention as dense_attention
    from .attention_ops import flash_single_chip

    x = ins["X"][0]
    wq, wkva, wkvb, wo = (ins[k][0] for k in ("WQ", "WKVA", "WKVB", "WO"))
    H = int(attrs["num_heads"])
    dn, dr, dv = (int(attrs[k]) for k in ("qk_nope_dim", "qk_rope_dim",
                                          "v_dim"))
    theta = float(attrs.get("theta", 10000.0))
    B, T, _ = x.shape
    rank = wkva.shape[1] - dr
    if not ctx.in_grad_replay():
        _MET_MLA_LAYERS.inc(qk_dim=str(dn + dr), v_dim=str(dv),
                            kv_rank=str(rank))
    heads = lambda a: jnp.swapaxes(a, 1, 2)          # [B,T,H,d] <-> [B,H,T,d]
    with part_scope("mla.project"):
        q = heads((x @ wq).reshape(B, T, H, dn + dr))
        c = x @ wkva
        c_kv = rms(c[..., :rank], float(attrs.get("epsilon", 1e-5)), (2,),
                   ins["KVNorm"][0])
        kv = heads((c_kv @ wkvb).reshape(B, T, H, dn + dv))
    with part_scope("mla.rope"):
        k_pe = rotate_half(c[:, None, :, rank:], theta)        # [B,1,T,dr]
        q = jnp.concatenate([q[..., :dn], rotate_half(q[..., dn:], theta)],
                            axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (B, H, T, dr))], axis=-1)
        v = kv[..., dn:]
    with part_scope("mla.attend"):
        got = flash_single_chip(ctx, q, k, v, True)
        attn, saved = got if got is not None else (
            dense_attention(q, k, v, causal=True), None)
    out = heads(attn).reshape(B, T, H * dv) @ wo
    if saved is not None:
        ctx.keep_for_grad(attrs, [out], saved)
    return {"Out": [out]}


@register_op("gated_short_conv")
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

    No position enters.  L shifted multiply-adds that XLA fuses into one
    pass over X (bound by HBM: PERF.md, PR 33, has it against
    `lax.conv_general_dilated`); at least float32 inside, X's dtype out."""
    import jax.numpy as jnp

    x, w = ins["X"][0], ins["Filter"][0]
    dim, taps = w.shape
    if x.ndim != 3 or x.shape[-1] != 3 * dim:
        raise ValueError(f"gated_short_conv: X {x.shape} is not [B, T, 3 x "
                         f"{dim}] for a Filter {w.shape}")
    if not ctx.in_grad_replay():
        _MET_CONV_LAYERS.inc(dim=str(dim), kernel=str(taps))
    T = x.shape[1]
    wide = wide_dtype(x.dtype)
    gate_in, gate_out, u = jnp.split(x.astype(wide), 3, axis=-1)
    with part_scope("conv.gate"):
        g = gate_in * u
    with part_scope("conv.taps"):
        wf = w.astype(wide)
        c = wf[:, taps - 1] * g
        for back in range(1, min(taps, T)):   # the tap `back` tokens ago
            c = c + wf[:, taps - 1 - back] * jnp.pad(
                g, ((0, 0), (back, 0), (0, 0)))[:, :T]
    with part_scope("conv.gate"):
        out = gate_out * c
    return {"Out": [out.astype(x.dtype)]}


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
    weights = sum(ins[k][0].size for k in ("WQ", "WKVA", "WKVB", "WO"))
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
