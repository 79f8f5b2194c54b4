"""Mixture-of-experts op, reachable from the Program IR.

Beyond-reference capability (SURVEY.md §2.16 last row; the 2018 reference has
no MoE).  Top-1 gating with static per-expert capacity so the whole layer is
fixed-shape XLA.  Single-device: the dispatch/compute/combine runs locally
(stacked-expert einsum).  Under a ParallelExecutor whose mesh has an 'ep'
axis > 1, expert weights live one-expert-per-member and tokens are exchanged
with `lax.all_to_all` over ICI (the standard TPU MoE recipe) — same
dispatch semantics, so single-chip and ep-sharded results agree whenever no
token is capacity-dropped."""

from __future__ import annotations

from .registry import register_op


def _dispatch(x, gate_w, n_exp, capacity):
    """Token -> (expert, slot) routing shared by both paths.

    Returns (expert [T], src_slot [T], keep [T], gatew [T]): top-1 expert,
    the token's slot in that expert's capacity buffer, whether it fit, and
    its gate weight."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(x @ gate_w, axis=-1)      # [T, E]
    expert = jnp.argmax(probs, axis=-1)               # [T]
    gatew = jnp.max(probs, axis=-1)                   # [T]
    onehot = jax.nn.one_hot(expert, n_exp, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot         # 1-based slot
    pos_in_expert = jnp.sum(pos, axis=-1) - 1         # [T]
    keep = pos_in_expert < capacity
    src_slot = jnp.where(keep, pos_in_expert, capacity - 1)
    return expert, src_slot, keep, gatew


def _scatter_send(x, expert, src_slot, keep, n_exp, capacity):
    import jax.numpy as jnp

    send = jnp.zeros((n_exp, capacity, x.shape[-1]), x.dtype)
    return send.at[expert, src_slot].add(jnp.where(keep[:, None], x, 0.0))


def _combine(back, expert, src_slot, keep, gatew, x):
    """Gather expert outputs back to token order; dropped tokens ride the
    residual path."""
    import jax.numpy as jnp

    out = back[expert, src_slot] * jnp.where(keep, gatew, 0.0)[:, None]
    return jnp.where(keep[:, None], out.astype(x.dtype), x)


def _ffn(h_in, wi, wo, act):
    import jax
    import jax.numpy as jnp

    actf = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
            "tanh": jnp.tanh}[act]
    return actf(h_in @ wi) @ wo


@register_op("moe")
def moe(ctx, ins, attrs):
    """X [T, D] tokens; Gate [D, E]; WI [E, D, H]; WO [E, H, D] -> Out [T, D].

    attrs: capacity_factor (default 1.0), act ('relu').  Capacity is fixed
    at trace time: ceil(tokens_per_member / E * factor)."""
    import jax.numpy as jnp
    import math

    x = ins["X"][0]
    gate_w = ins["Gate"][0]
    wi, wo = ins["WI"][0], ins["WO"][0]
    n_exp = wi.shape[0]
    factor = float(attrs.get("capacity_factor", 1.0))
    act = str(attrs.get("act", "relu"))

    mesh = getattr(ctx, "mesh", None)
    ep = 1
    token_axes = ()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        ep = sizes.get("ep", 1)
        token_axes = tuple(a for a in ("dp", "ep")
                           if sizes.get(a, 1) > 1)

    T = x.shape[0]
    if ep > 1:
        if n_exp != ep:
            raise ValueError(
                f"moe op: {n_exp} experts must equal the mesh's ep axis "
                f"size {ep} (one expert per member)")
        out = _moe_sharded(ctx, x, gate_w, wi, wo, mesh, token_axes,
                           factor, act)
        return {"Out": [out]}

    capacity = max(1, math.ceil(T / n_exp * factor))
    expert, src_slot, keep, gatew = _dispatch(x, gate_w, n_exp, capacity)
    send = _scatter_send(x, expert, src_slot, keep, n_exp, capacity)
    h = _ffn(send, wi, wo, act)  # [E, C, D] batched over experts
    out = _combine(h, expert, src_slot, keep, gatew, x)
    return {"Out": [out]}


def _moe_sharded(ctx, x, gate_w, wi, wo, mesh, token_axes, factor, act):
    """shard_map over 'ep' (tokens also split over 'dp' when present):
    dispatch locally, all_to_all token exchange, this member's expert
    computes, exchange back, combine."""
    import math
    from functools import partial

    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n_members = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in token_axes:
        n_members *= sizes[a]
    T = x.shape[0]
    if T % max(n_members, 1) != 0:
        raise ValueError(
            f"moe op: token count {T} must divide the token-sharding "
            f"members {n_members} ({token_axes})")
    local_T = T // max(n_members, 1)
    n_exp = wi.shape[0]
    capacity = max(1, math.ceil(local_T / n_exp * factor))

    tok_spec = P(token_axes if len(token_axes) > 1 else token_axes[0]) \
        if token_axes else P()

    @partial(shard_map, mesh=mesh,
             in_specs=(tok_spec, P(), P("ep"), P("ep")),
             out_specs=tok_spec, check_vma=False)
    def run(xl, gate_l, wi_l, wo_l):
        expert, src_slot, keep, gatew = _dispatch(
            xl, gate_l, n_exp, capacity)
        send = _scatter_send(xl, expert, src_slot, keep, n_exp, capacity)
        # [E, C, D] -> exchange so this member holds every sender's tokens
        # for ITS expert: [senders(E), C, D]
        recv = lax.all_to_all(send, "ep", split_axis=0, concat_axis=0,
                              tiled=False)
        h = _ffn(recv, wi_l[0], wo_l[0], act)
        back = lax.all_to_all(h, "ep", split_axis=0, concat_axis=0,
                              tiled=False)
        return _combine(back, expert, src_slot, keep, gatew, xl)

    return run(x, gate_w, wi, wo)


# ---------------------------------------------------------------------------
# analytic cost formula (analysis/cost.py; mechanism in registry.py)

from .registry import register_cost, register_sharding  # noqa: E402


def _moe_cost(ins, outs, attrs):
    """Gate matmul (2*T*D*E) + the two expert matmuls over every routed
    token (4*T*D*H at capacity).  Bytes override adds the all_to_all
    dispatch/return buffers (2 x token bytes each way) — the collective
    traffic term the per-mode ICI ledgers (tools/hlo_analysis.py
    collectives) measure for the ep programs."""
    x = ins.get("X", [None])[0]
    gate = ins.get("Gate", [None])[0]
    wi = ins.get("WI", [None])[0]
    if x is None or gate is None or wi is None or len(x.shape) != 2:
        return {}
    t, d = x.shape
    e = gate.shape[1]
    h = wi.shape[2] if len(wi.shape) == 3 else d
    factor = float(attrs.get("capacity_factor", 1.0))
    routed = int(t * max(factor, 1.0))
    flops = 2 * t * d * e + 4 * routed * d * h
    item = 2 if str(x.dtype) == "bfloat16" else 4
    collective = 4 * t * d * item  # dispatch + return, both all_to_all
    return {"flops": flops, "collective_bytes": collective}


register_cost("moe", _moe_cost)


def _moe_sharding(ctx, ins, outs, attrs):
    """Expert-parallel dispatch: tokens ride an all_to_all to their
    expert's member and back (2x the send buffer each direction); the
    shard_map custom path re-pays both in the backward (bwd_retrace),
    matching the cost formula's collective_bytes above."""
    x = ins.get("X", [None])[0]
    out = outs.get("Out", [None])[0]
    if x is None or out is None:
        return {}
    ep = ctx.axis_size("ep")
    if ep > 1:
        ctx.collective("all-to-all", ("ep",),
                       2 * x.device_bytes(ctx.analysis.axis_sizes),
                       var=out.name,
                       why="token dispatch + return over the expert "
                           "axis", scales_with_axes=True)
    return {"Out": [tuple(x.spec)]}


_moe_sharding.bwd_retrace = True
register_sharding("moe", _moe_sharding)
