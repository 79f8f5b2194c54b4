"""Control flow ops: compare/logical, select, cond, while, static_rnn,
tensor arrays.

Reference: operators/while_op.cc (345 LoC), cond_op.cc/conditional_block_op.cc,
recurrent_op.cc (635 LoC, StepScopes), compare/logical ops,
tensor_array_read_write + lod_tensor_array (SURVEY.md §2.2
'Recurrence/control flow').

TPU-first mapping: the reference interprets sub-blocks per iteration with
step scopes; here sub-blocks lower into `lax.while_loop` / `lax.cond` /
`lax.scan` bodies via ctx.lower_block — compiled once, no Python in the loop,
no dynamic shapes. Tensor arrays become fixed-capacity buffers with
dynamic_update_slice writes (the static-shape reading of LoDTensorArray).

Note on autodiff: `while`/`cond` are opaque to reverse-mode here (lax.while
is not reverse-differentiable); recurrent *training* flows through the
scan-based `static_rnn` and lstm/gru ops, which differentiate fine — same
stance as the reference, whose RNN training ran through RecurrentOp rather
than WhileOp in practice."""

from __future__ import annotations

import functools

from ..observability.metrics import REGISTRY as _MET
from .registry import np_dtype, register_op


def _jnp():
    import jax.numpy as jnp

    return jnp


# --- compare / logical (operators/compare_op.cc, logical_op.cc) ------------

def _cmp(fn):
    def emit(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        return {"Out": [fn(x, y)]}

    return emit


def _register_cmps():
    jnp = None

    import jax.numpy as jnp

    for name, fn in [
        ("less_than", lambda x, y: x < y),
        ("less_equal", lambda x, y: x <= y),
        ("greater_than", lambda x, y: x > y),
        ("greater_equal", lambda x, y: x >= y),
        ("equal", lambda x, y: x == y),
        ("not_equal", lambda x, y: x != y),
        ("logical_and", jnp.logical_and),
        ("logical_or", jnp.logical_or),
        ("logical_xor", jnp.logical_xor),
    ]:
        register_op(name, _cmp(fn), grad=None)
    register_op("logical_not",
                lambda ctx, ins, attrs: {"Out": [jnp.logical_not(
                    ins["X"][0])]},
                grad=None)


_register_cmps()


@register_op("select", non_diff_inputs=("Mask",))
def select(ctx, ins, attrs):
    """Masked select (the data-parallel IfElse): Out = Mask ? X : Y, with
    Mask broadcast from [B,1]."""
    jnp = _jnp()
    mask = ins["Mask"][0]
    x, y = ins["X"][0], ins["Y"][0]
    while mask.ndim < max(x.ndim, y.ndim):  # either side may be a scalar
        mask = mask[..., None]              # fill (split_lod_tensor)
    return {"Out": [jnp.where(mask != 0, x, y)]}


@register_op("is_empty", grad=None)
def is_empty(ctx, ins, attrs):
    jnp = _jnp()
    return {"Out": [jnp.asarray(ins["X"][0].size == 0)]}


# --- cond (cond_op.cc / conditional_block_op.cc) ---------------------------


@register_op("cond", non_diff_inputs=("Cond",))
def cond(ctx, ins, attrs):
    """Scalar-predicate two-branch conditional via lax.cond (differentiable).

    attrs: true_block/false_block (sub-block idx), out_names (produced by
    both branches), x_names (external vars both branches may read — declared
    as inputs so gradients flow to them)."""
    import jax

    pred = ins["Cond"][0].reshape(()) != 0
    out_names = attrs["out_names"]
    base_env = dict(zip(attrs.get("x_names", []), ins.get("X", [])))

    def run(block_idx):
        def fn(_):
            env = dict(base_env)
            ctx.lower_block(block_idx, env)
            return tuple(env[n] for n in out_names)

        return fn

    outs = jax.lax.cond(pred, run(int(attrs["true_block"])),
                        run(int(attrs["false_block"])), 0)
    return {"Out": list(outs)}


@register_op("while", grad=None)
def while_op(ctx, ins, attrs):
    """lax.while_loop over a sub-block (while_op.cc).

    attrs: sub_block (idx), carry_names (vars updated each iteration,
    including the condition var), cond_name, x_names (read-only externals).
    Inputs: Carry (initial values, ordered as carry_names) + X."""
    import jax

    carry_names = list(attrs["carry_names"])
    cond_name = attrs["cond_name"]
    sub_block = int(attrs["sub_block"])
    init = tuple(ins["Carry"])
    base_env = dict(zip(attrs.get("x_names", []), ins.get("X", [])))

    cond_pos = carry_names.index(cond_name)

    def cond_fun(carry):
        return carry[cond_pos].reshape(()) != 0

    def body_fun(carry):
        env = dict(base_env)
        env.update(zip(carry_names, carry))
        ctx.lower_block(sub_block, env)
        return tuple(env[n] for n in carry_names)

    final = jax.lax.while_loop(cond_fun, body_fun, init)
    return {"Out": list(final)}


# --- static_rnn (recurrent_op.cc as lax.scan) ------------------------------


@register_op("static_rnn", non_diff_inputs=("Length",))
def static_rnn(ctx, ins, attrs):
    """Scan a sub-block over the time axis (recurrent_op.cc:635 semantics).

    attrs: sub_block, step_input_names (outer [B,T,...] vars, sliced to
    [B,...] per step under the same names), memory_pairs [[mem, updated], ..]
    (mem var in sub-block reads previous step's `updated`), out_names
    (per-step outputs to stack to [B,T,...]), x_names (externals — weights
    read inside the step block; declared as inputs so gradients flow).
    Inputs: StepInputs (ordered), MemInit (ordered), X (externals). Optional
    Length masks memory updates past each sequence's end (DynamicRNN
    semantics: the static-shape stand-in for shrink_rnn_memory)."""
    import jax
    import jax.numpy as jnp

    step_names = list(attrs["step_input_names"])
    mem_pairs = [tuple(p) for p in attrs["memory_pairs"]]
    out_names = list(attrs["out_names"])
    sub_block = int(attrs["sub_block"])
    seq_inputs = ins["StepInputs"]
    mem_init = ins["MemInit"]
    lengths = None
    if ins.get("Length") and ins["Length"][0] is not None:
        lengths = ins["Length"][0]
    base_env = dict(zip(attrs.get("x_names", []), ins.get("X", [])))
    T = seq_inputs[0].shape[1]

    def step(mems, t):
        env = dict(base_env)
        for name, seq in zip(step_names, seq_inputs):
            env[name] = seq[:, t]
        for (mname, _), m in zip(mem_pairs, mems):
            env[mname] = m
        ctx.lower_block(sub_block, env)
        new_mems = []
        for (mname, uname), m in zip(mem_pairs, mems):
            nm = env[uname]
            if lengths is not None:
                alive = (t < lengths).astype(nm.dtype)
                shape = (-1,) + (1,) * (nm.ndim - 1)
                nm = alive.reshape(shape) * nm + (
                    1 - alive.reshape(shape)) * m
            new_mems.append(nm)
        outs = tuple(env[n] for n in out_names)
        return tuple(new_mems), outs

    final_mems, stacked = jax.lax.scan(step, tuple(mem_init),
                                       jnp.arange(T))
    outs = [jnp.moveaxis(s, 0, 1) for s in stacked]
    if lengths is not None:
        # LoD semantics: timesteps past a sequence's end don't exist — zero
        # them in the padded representation
        tmask = (jnp.arange(T)[None, :] < lengths[:, None])
        outs = [
            o * tmask.reshape(tmask.shape + (1,) * (o.ndim - 2)).astype(
                o.dtype)
            for o in outs
        ]
    return {"Out": outs, "MemFinal": list(final_mems)}


# --- tensor arrays (fixed-capacity static-shape LoDTensorArray) ------------


@register_op("array_write", grad=None)
def array_write(ctx, ins, attrs):
    """Array [cap, ...] buffer; writes X at index I via dynamic_update_slice
    (tensor_array_read_write_op.cc under static shapes)."""
    import jax

    arr, x, i = ins["Array"][0], ins["X"][0], ins["I"][0]
    idx = i.reshape(()).astype("int32")
    return {"Out": [jax.lax.dynamic_update_index_in_dim(
        arr, x.astype(arr.dtype), idx, 0)]}


@register_op("array_read", grad=None)
def array_read(ctx, ins, attrs):
    import jax

    arr, i = ins["Array"][0], ins["I"][0]
    idx = i.reshape(()).astype("int32")
    return {"Out": [jax.lax.dynamic_index_in_dim(arr, idx, 0,
                                                 keepdims=False)]}


@register_op("create_array", grad=None)
def create_array(ctx, ins, attrs):
    import jax.numpy as jnp

    shape = [int(s) for s in attrs["shape"]]  # [cap, ...]
    if any(s < 0 for s in shape):  # batch-dim element shape: size from Ref
        ref = ins["Ref"][0]
        shape = [ref.shape[0] if s < 0 else s for s in shape]
    return {"Out": [jnp.zeros(shape, dtype=np_dtype(
        attrs.get("dtype", "float32")))]}


_MET_KEPT = _MET.counter(
    "recompute_kept_traced_total",
    "what `layers.recompute(keep=...)` segments hold across to their "
    "backward (once a compile, not once a step), by pass (forward: the "
    "forward emission kept them beside its outputs; replay: the grad op's "
    "re-emission was handed them and put each in place of the one it makes) "
    "and unit (values, bytes); no series where no segment names anything")


@functools.lru_cache(maxsize=None)
def _in_place_of():
    """in_place_of(made, held) -> held, its cotangent handed to `made`: the
    op that made `made` is dead downstream (XLA removes it) while its
    backward, which reads its operands and not its result, stands."""
    import jax

    @jax.custom_vjp
    def in_place_of(made, held):
        return held

    in_place_of.defvjp(lambda made, held: (held, None),
                       lambda _, g: (g, _jnp().zeros_like(g)))
    return in_place_of


@register_op("recompute")
def recompute_op(ctx, ins, attrs):
    """Rematerialization segment (layers.recompute): the sub-block lowers
    as ONE `jax.checkpoint`-wrapped pure function of its externals, so the
    backward pass (generic vjp through this op) recomputes the segment's
    activations instead of keeping them resident in HBM.

    All but `keep_names`, the values its builder named: the forward emission
    keeps them beside its outputs (`ctx.keep_for_grad`: they are among the
    outputs, so this pins them and costs no copy), and a re-emission that
    is handed them back passes them INTO the checkpointed function (its
    arguments are what a checkpoint saves) and, after the op that makes
    each name, puts the held value in its place.  A re-emission handed
    nothing (another trace, a `__remat__` grad op) and a segment that names
    nothing trace what they always did."""
    import jax

    sub_block = int(attrs["sub_block"])
    x_names = list(attrs["x_names"])
    out_names = list(attrs["out_names"])
    keep_names = list(attrs.get("keep_names", ()))
    held = ctx.take_kept_for_grad() if keep_names else None

    @jax.checkpoint
    def segment(*vals):
        env = dict(zip(x_names, vals))
        put = dict(zip(keep_names, vals[len(x_names):]))

        def use_held(op, env):
            for n in op.output_names():
                if n in put:
                    env[n] = _in_place_of()(env[n], put[n])

        ctx.lower_block(sub_block, env, use_held if put else None)
        return tuple(env[n] for n in out_names)

    if held:
        outs = segment(*ins["X"], *(held[n] for n in keep_names))
        _count_kept("replay", held.values())
    else:
        outs = segment(*ins["X"])
        if keep_names and not (ctx.is_test or ctx.in_grad_replay()):
            kept = {n: outs[out_names.index(n)] for n in keep_names}
            ctx.keep_for_grad(attrs, outs, kept)
            _count_kept("forward", kept.values())
    return {"Out": list(outs)}


def _count_kept(which, values):
    values = list(values)
    for unit, n in (("values", len(values)),
                    ("bytes", sum(v.size * v.dtype.itemsize
                                  for v in values))):
        _MET_KEPT.inc(n, unit=unit, **{"pass": which})
