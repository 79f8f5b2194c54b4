"""Ops of the post-2020 decoder block that the GPT-2-shaped tower lacks:
RMSNorm and rotary position embedding (beyond-reference, like the rest of
the transformer tier; first user: OLMoE, models/transformer.py).

Both are plain jax.numpy, so `generic_grad` differentiates them by
re-emission and XLA's CSE merges the re-emitted forward with the first.
Statistics and rotations are at least float32 whatever the compute dtype
(`wide_dtype`); the result goes back to the input's dtype."""

from __future__ import annotations

from .registry import register_op


def wide_dtype(dtype):
    """float32 for bf16 / f16 / f32 inputs, float64 for float64 ones."""
    import jax.numpy as jnp

    return jnp.promote_types(dtype, jnp.float32)


@register_op("rms_norm")
def rms_norm(ctx, ins, attrs):
    """X [..., D...] -> Y = X / sqrt(mean(X^2 over the axes from
    `begin_norm_axis`) + epsilon) * Scale (Zhang & Sennrich 2019,
    arXiv:1910.07467).  No mean is subtracted and there is no bias."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    eps = float(attrs.get("epsilon", 1e-5))
    begin = int(attrs.get("begin_norm_axis", 1))
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(wide_dtype(x.dtype))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=axes, keepdims=True) + eps)
    if ins.get("Scale") and ins["Scale"][0] is not None:
        y = y * ins["Scale"][0].astype(xf.dtype).reshape(x.shape[begin:])
    return {"Y": [y.astype(x.dtype)]}


@register_op("rope")
def rope(ctx, ins, attrs):
    """Rotary position embedding in its rotate-half form (Su et al. 2021,
    arXiv:2104.09864, as GPT-NeoX and transformers apply it): X [B, H, T,
    D] with D even; position t of every head turns the pair (x[i], x[i +
    D/2]) by the angle t * theta ** (-2i / D).  Positions are 0..T-1."""
    import jax.numpy as jnp

    x = ins["X"][0]
    theta = float(attrs.get("theta", 10000.0))
    T, D = x.shape[-2], x.shape[-1]
    if D % 2:
        raise ValueError(f"rope op: head size {D} must be even")
    half = D // 2
    xf = x.astype(wide_dtype(x.dtype))
    inv_freq = theta ** (-jnp.arange(half, dtype=xf.dtype) / half)
    ang = jnp.arange(T, dtype=xf.dtype)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)          # [T, D/2]
    a, b = xf[..., :half], xf[..., half:]
    y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return {"Out": [y.astype(x.dtype)]}


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


register_cost("rms_norm", _rms_norm_cost)
register_cost("rope", _rope_cost)
