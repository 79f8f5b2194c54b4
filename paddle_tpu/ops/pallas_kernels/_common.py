"""What the Pallas kernel files share: the gates an emitter passes before it
takes a kernel (`pallas_dispatch_ok`, `kernels_enabled`, `traced_path`), the
kernel-pair protocol (`kernel_pair`: the `jax.custom_vjp` triple a forward op
and its grad op's re-emission split between them, `EmitContext.run_pair`'s
other half), and the recurrence kernels' plumbing (lstm.py, gru.py: the VMEM
handle and budgets, the padded-step mask, the lane gate, the reversal within
a row's length)."""

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


def traced_path(ctx, family, usable: bool) -> bool:
    """Whether this emission takes its kernels: `pallas_dispatch_ok` and
    the kernel file's own `usable(...)` answer.  Counts the emission in
    `family`, a counter labelled {op, path}: op=fwd for a forward emission
    and grad for generic_grad's re-emission of it, path=pallas or xla."""
    take = pallas_dispatch_ok(ctx) and bool(usable)
    family.inc(op="grad" if ctx.in_grad_replay() else "fwd",
               path="pallas" if take else "xla")
    return take


def kernel_pair(operands: int, bare, forward, backward):
    """A kernel file's launches as what a forward op and its grad op's
    re-emission split between them (`EmitContext.run_pair` picks;
    ops/registry.py has the protocol).  The file declares, beside the count
    of its `operands`: `bare(*ops) -> out` for inference; `forward(*ops,
    keep) -> (out, *residuals)`, which under the plain rule (`keep` False)
    may keep all the same or return (out,) alone and make the residuals
    again in the backward: its own business; `backward(ops, do, kept) ->
    the operands' cotangents`, `kept` what the forward that ran returned.
    The two open whatever `part_scope` a launch must run under, and the
    file memoizes the pair (every trace must meet the same functions).  ->

      pair(*ops) -> out        a `jax.custom_vjp` anything differentiates:
                               a `layers.recompute` segment, a stage
      pair.keeping(*ops) -> (out, *residuals)
                               the residuals leave for `from_saved`, never
                               as values a loss depends on: their
                               cotangents are dropped
      pair.from_saved(*ops, out, *residuals) -> out
                               launches nothing forward, differentiates as
                               the backward alone (what was kept gets no
                               gradient)
      pair.bare                `bare` itself"""
    import jax

    def rule(keep):
        def fwd(*ops):
            kept = tuple(forward(*ops, keep=keep))
            return (kept if keep else kept[0]), (ops, kept)
        return fwd

    def bwd(res, do):
        ops, kept = res
        return tuple(backward(ops, do, kept))

    pair = jax.custom_vjp(lambda *ops: forward(*ops, keep=False)[0])
    pair.defvjp(rule(False), bwd)
    keeping = jax.custom_vjp(lambda *ops: tuple(forward(*ops, keep=True)))
    keeping.defvjp(rule(True), lambda res, cts: bwd(res, cts[0]))
    from_saved = jax.custom_vjp(lambda *a: a[operands])
    from_saved.defvjp(
        lambda *a: (a[operands], (a[:operands], a[operands:])),
        lambda res, do: bwd(res, do) + (None,) * len(res[1]))
    pair.bare, pair.keeping, pair.from_saved = bare, keeping, from_saved
    return pair


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
