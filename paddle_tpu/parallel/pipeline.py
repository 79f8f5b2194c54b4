"""Pipeline parallelism over a `pp` mesh axis (GPipe-style fill/drain).

Beyond-reference capability (SURVEY.md §2.16: pipeline parallelism is
'absent' in the 2018 codebase) built the TPU way: stage parameters are
stacked on a leading axis sharded over `pp`, the whole schedule runs inside
one `shard_map`, and activations hop stages with `lax.ppermute` over ICI.
Differentiable end-to-end — `jax.grad` through the schedule gives pipeline
backward for free (ppermute transposes to the reverse hop).

Schedule: classic GPipe fill/drain over `n_micro` microbatches;
`n_micro + n_stages - 1` ticks per step.  Each device computes every tick
(bubbles carry zeros), which keeps the schedule a dense `lax.scan` —
compiler-friendly static control flow instead of per-stage host loops."""

from __future__ import annotations

from functools import partial
from typing import Callable

import numpy as np


def _stage_fn(params, x):
    """Default per-stage compute: tanh MLP block (stage params: dict of
    stacked leaves with the pp axis already sliced off inside shard_map)."""
    import jax.numpy as jnp

    return jnp.tanh(x @ params["w"] + params["b"])


def init_pipeline_params(key, n_stages: int, width: int):
    """Stacked per-stage parameters: leading axis = pipeline stage."""
    import jax

    ks = jax.random.split(key, n_stages)
    import jax.numpy as jnp

    w = jax.vmap(lambda k: jax.random.normal(k, (width, width),
                                             dtype=jnp.float32)
                 * (1.0 / np.sqrt(width)))(ks)
    b = jnp.zeros((n_stages, width), jnp.float32)
    return {"w": w, "b": b}


def pipeline_apply(params, x_micro, *, axis_name: str = "pp",
                   stage_fn: Callable = _stage_fn):
    """Run the pipeline INSIDE shard_map over `axis_name`.

    params: stage-sliced pytree (leading pp axis removed by shard_map).
    x_micro: [n_micro, micro_bs, width] — microbatches, replicated input;
    returns [n_micro, micro_bs, width] outputs as produced by the LAST stage
    (replicated back via psum-masking).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    n_micro, micro_bs, width = x_micro.shape
    ticks = n_micro + n_stages - 1

    # ppermute spec: stage s sends to s+1 (last stage's output is collected,
    # not forwarded)
    fwd_perm = [(s, s + 1) for s in range(n_stages - 1)]

    def tick(carry, t):
        buf, outputs = carry  # buf: [micro_bs, width] activation in flight
        # stage 0 injects microbatch t (when valid), others take the hop
        inject = jnp.where(t < n_micro,
                           x_micro[jnp.minimum(t, n_micro - 1)], 0.0)
        x_in = jnp.where(stage == 0, inject, buf)
        y = stage_fn(params, x_in).astype(x_micro.dtype)
        # last stage emits microbatch (t - n_stages + 1) at tick t
        out_idx = t - (n_stages - 1)
        is_out = (stage == n_stages - 1) & (out_idx >= 0)
        outputs = lax.cond(
            is_out,
            lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
            lambda o: o,
            outputs)
        buf = lax.ppermute(y, axis_name, fwd_perm)
        return (buf, outputs), None

    buf0 = jnp.zeros((micro_bs, width), x_micro.dtype)
    outs0 = jnp.zeros_like(x_micro)
    (_, outputs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # replicate the last stage's collected outputs to every pp member
    mask = (stage == n_stages - 1).astype(x_micro.dtype)
    return lax.psum(outputs * mask, axis_name)


def build_pipeline_train_step(mesh, n_micro: int, width: int,
                              lr: float = 0.1,
                              stage_fn: Callable = _stage_fn):
    """jit-able (params, x [B, width], y [B, width]) -> (loss, new_params)
    with params sharded over the mesh's `pp` axis and data over `dp`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax import shard_map

    from ..mesh import pspec as P

    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)

    @partial(shard_map, mesh=mesh,
             in_specs=(P("pp"), P(None, "dp"), P(None, "dp")),
             out_specs=P(),
             check_vma=False)
    def forward_loss(params, xm, ym):
        # shard_map keeps the sharded pp axis as a length-1 leading dim:
        # slice it off so stage_fn sees this stage's own leaves
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        # xm/ym arrive [n_micro, micro_bs/dp, width] on each device
        out = pipeline_apply(params, xm, stage_fn=stage_fn)
        loss = jnp.mean((out - ym) ** 2)
        return jax.lax.pmean(jax.lax.pmean(loss, "dp"), "pp")

    def train_step(params, x, y):
        xm = x.reshape(n_micro, x.shape[0] // n_micro, width)
        ym = y.reshape(n_micro, y.shape[0] // n_micro, width)
        loss, grads = jax.value_and_grad(
            lambda p: forward_loss(p, xm, ym))(params)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, params, grads)
        return loss, new_params

    shard = NamedSharding(mesh, P("pp"))
    return jax.jit(train_step), shard
