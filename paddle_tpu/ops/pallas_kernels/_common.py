"""Shared plumbing for the fused recurrence kernels (lstm.py, gru.py):
VMEM handle, padded-step mask, and the common eligibility gates — one
place to adjust the VMEM budget or lane constraints for both."""

from __future__ import annotations

VMEM_BUDGET = 8 * 1024 * 1024  # comfortable share of ~16MB/core
# the backward kernels hold two weight-size buffers by design (w + the
# resident dW output accumulator); give training a larger — still safe —
# slice so the bench shapes (h512) stay eligible
TRAIN_VMEM_BUDGET = 12 * 1024 * 1024


def vmem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM


def step_mask(lengths, T, dtype):
    """[B] lengths -> [B,T] {0,1} mask in `dtype`."""
    import jax.numpy as jnp

    return (jnp.arange(T)[None, :] < lengths[:, None]).astype(dtype)


def lanes_ok(B: int, H: int) -> bool:
    """MXU/VPU-friendly shapes: full 128-lane H tiles, 8-sublane batches."""
    return H % 128 == 0 and B % 8 == 0


def pallas_dispatch_ok(ctx) -> bool:
    """The ONE gate every fused-kernel emitter must pass before taking a
    Pallas path: the trace targets a real TPU, lowering is NOT sharded
    (GSPMD cannot partition a Mosaic custom call — a ParallelExecutor
    mesh keeps the XLA-fusable fallback), and kernels aren't disabled.
    Centralized so a new emitter can't repeat the mesh-gate omission."""
    return (ctx.target_platform() == "tpu" and ctx.mesh is None
            and kernels_enabled())


def kernels_enabled() -> bool:
    """PADDLE_TPU_NO_FUSED_KERNELS=1 forces every op back to its XLA
    fallback — the one, explicit switch.  Nothing flips it at run time:
    a kernel the Mosaic compiler refuses is an error the caller sees,
    not a silent change of execution path."""
    import os

    return not os.environ.get("PADDLE_TPU_NO_FUSED_KERNELS")


def reverse_within_length(x, lengths, pad_fill=None):
    """Flip each row's first `lengths[b]` steps, keeping padding at the
    tail ([B,T,...]): a reversed recurrence over padded+lengths data is
    the forward kernel run on this view (with outputs flipped back).
    `pad_fill` (a [B,...] state, broadcast over time) overwrites the tail
    — the reversed-scan convention for OUTPUT arrays, whose pad steps
    carry the untouched initial state (h0/c0)."""
    import jax.numpy as jnp

    T = x.shape[1]
    idx = jnp.arange(T)[None, :]
    rev = lengths[:, None] - 1 - idx
    rev = jnp.where(rev >= 0, rev, idx)
    out = jnp.take_along_axis(
        x, rev.astype(jnp.int32).reshape(rev.shape + (1,) * (x.ndim - 2)),
        axis=1)
    if pad_fill is not None:
        m = step_mask(lengths, T, jnp.bool_)
        m = m.reshape(m.shape + (1,) * (out.ndim - 2))
        out = jnp.where(m, out, pad_fill[:, None].astype(out.dtype))
    return out
