"""Expert parallelism (MoE) over an `ep` mesh axis.

Beyond-reference capability (SURVEY.md §2.16): expert weights live sharded
one-expert-per-`ep`-member; tokens are top-1 gated and exchanged with
`lax.all_to_all` over ICI, computed by their expert, and returned.  Capacity
is static (`capacity` tokens per expert per sender) so the whole layer is
fixed-shape XLA — dropped tokens pass through on the residual path, the
standard TPU MoE recipe."""

from __future__ import annotations

from functools import partial
import numpy as np


def init_moe_params(key, n_experts: int, d_model: int, d_hidden: int):
    """Stacked per-expert FFN params: leading axis = expert."""
    import jax
    import jax.numpy as jnp

    k1, k2, kg = jax.random.split(key, 3)
    scale = 1.0 / np.sqrt(d_model)
    return {
        "wi": jax.random.normal(k1, (n_experts, d_model, d_hidden)) * scale,
        "wo": jax.random.normal(k2, (n_experts, d_hidden, d_model))
        * (1.0 / np.sqrt(d_hidden)),
        "gate": jax.random.normal(kg, (d_model, n_experts)) * scale,
    }


def moe_apply(params, x, *, axis_name: str = "ep", capacity: int):
    """Inside shard_map: x [tokens, d_model] local shard; params expert-sliced
    (this member's expert only: wi [d_model,d_hidden], wo [d_hidden,d_model],
    gate replicated [d_model, n_experts])."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_exp = lax.psum(1, axis_name)
    T, D = x.shape

    logits = x @ params["gate"]           # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)   # [T] top-1
    gatew = jnp.max(probs, axis=-1)       # [T]

    # position of each token within its expert's send buffer (capacity-bound)
    onehot = jax.nn.one_hot(expert, n_exp, dtype=jnp.int32)   # [T, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot                  # 1-based
    pos_in_expert = jnp.sum(pos, axis=-1) - 1                  # [T]
    keep = pos_in_expert < capacity

    # scatter tokens into [E, capacity, D] send buffer
    send = jnp.zeros((n_exp, capacity, D), x.dtype)
    src_slot = jnp.where(keep, pos_in_expert, capacity - 1)
    send = send.at[expert, src_slot].add(
        jnp.where(keep[:, None], x, 0.0))
    # exchange: after all_to_all over axis 0, this member holds the tokens
    # every sender routed to ITS expert: [n_senders, capacity, D]
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    h = jax.nn.relu(recv @ params["wi"]) @ params["wo"]
    back = lax.all_to_all(h, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    # gather results back to token order
    out = back[expert, src_slot] * jnp.where(keep, gatew, 0.0)[:, None]
    # dropped tokens ride the residual connection
    return jnp.where(keep[:, None], out, x)


def build_moe_train_step(mesh, d_model: int, d_hidden: int, capacity: int,
                         lr: float = 0.1):
    """jit-able (params, x [B,T?,D]→[tokens,D], y) -> (loss, new_params);
    experts sharded over `ep`, tokens over `dp`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax import shard_map

    from ..mesh import pspec as P

    @partial(shard_map, mesh=mesh,
             in_specs=({"wi": P("ep"), "wo": P("ep"), "gate": P()},
                       P(("dp", "ep")), P(("dp", "ep"))),
             out_specs=P(),
             check_vma=False)
    def forward_loss(params, x, y):
        local = dict(params)
        local["wi"] = local["wi"][0]   # this member's expert
        local["wo"] = local["wo"][0]
        out = moe_apply(local, x, capacity=capacity)
        loss = jnp.mean((out - y) ** 2)
        return jax.lax.pmean(jax.lax.pmean(loss, "dp"), "ep")

    def train_step(params, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: forward_loss(p, x, y))(params)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, params, grads)
        return loss, new_params

    return jax.jit(train_step)
