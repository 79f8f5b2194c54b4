"""Tensor creation & plumbing ops (reference operators/: fill_constant,
uniform_random, gaussian_random, cast, concat, split, reshape, transpose,
expand, gather, scatter, pad, assign, top_k, ... — SURVEY.md §2.2 'Tensor
plumbing')."""

from __future__ import annotations

import numpy as np

from .registry import np_dtype, register_op


def _j():
    import jax.numpy as jnp

    return jnp


@register_op("fill_constant", grad=None)
def fill_constant(ctx, ins, attrs):
    jnp = _j()
    shape = [int(s) for s in attrs["shape"]]
    dt = np_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dt)]}


@register_op("fill_constant_batch_size_like", grad=None)
def fill_constant_batch_size_like(ctx, ins, attrs):
    jnp = _j()
    x = ins["Input"][0]
    shape = [int(s) for s in attrs["shape"]]
    in_idx = int(attrs.get("input_dim_idx", 0))
    out_idx = int(attrs.get("output_dim_idx", 0))
    shape[out_idx] = x.shape[in_idx]
    dt = np_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dt)]}


@register_op("fill_zeros_like", grad=None)
def fill_zeros_like(ctx, ins, attrs):
    jnp = _j()
    return {"Out": [jnp.zeros_like(ins["X"][0])]}


@register_op("uniform_random", grad=None)
def uniform_random(ctx, ins, attrs):
    import jax

    jnp = _j()
    shape = [int(s) for s in attrs["shape"]]
    dt = np_dtype(attrs.get("dtype", "float32"))
    lo = float(attrs.get("min", -1.0))
    hi = float(attrs.get("max", 1.0))
    key = ctx.rng(attrs)
    return {"Out": [jax.random.uniform(key, shape, dtype=jnp.float32,
                                       minval=lo, maxval=hi).astype(dt)]}


@register_op("gaussian_random", grad=None)
def gaussian_random(ctx, ins, attrs):
    import jax

    jnp = _j()
    shape = [int(s) for s in attrs["shape"]]
    dt = np_dtype(attrs.get("dtype", "float32"))
    mean = float(attrs.get("mean", 0.0))
    std = float(attrs.get("std", 1.0))
    key = ctx.rng(attrs)
    if int(attrs.get("seed", 0)):
        # the reference's attr: a seed of its own draws the same values
        # whatever the program's random_seed (0: the program's)
        key = jax.random.fold_in(jax.random.PRNGKey(int(attrs["seed"])),
                                 int(attrs.get("__uid__", 0)))
    return {"Out": [(mean + std * jax.random.normal(key, shape, dtype=jnp.float32)
                     ).astype(dt)]}


@register_op("truncated_gaussian_random", grad=None)
def truncated_gaussian_random(ctx, ins, attrs):
    import jax

    jnp = _j()
    shape = [int(s) for s in attrs["shape"]]
    dt = np_dtype(attrs.get("dtype", "float32"))
    mean = float(attrs.get("mean", 0.0))
    std = float(attrs.get("std", 1.0))
    key = ctx.rng(attrs)
    # truncated to 2 std, matching the reference op's semantics
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype=jnp.float32)
    return {"Out": [(mean + std * x).astype(dt)]}


@register_op("assign")
def assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("cast")
def cast(ctx, ins, attrs):
    return {"Out": [ins["X"][0].astype(np_dtype(attrs["out_dtype"]))]}


@register_op("shape", grad=None)
def shape_op(ctx, ins, attrs):
    jnp = _j()
    return {"Out": [jnp.asarray(ins["Input"][0].shape, dtype=jnp.int64)]}


@register_op("concat")
def concat(ctx, ins, attrs):
    jnp = _j()
    return {"Out": [jnp.concatenate(ins["X"], axis=int(attrs.get("axis", 0)))]}


@register_op("split")
def split(ctx, ins, attrs):
    jnp = _j()
    x = ins["X"][0]
    axis = int(attrs.get("axis", 0))
    if attrs.get("sections"):
        idx = np.cumsum(attrs["sections"])[:-1].tolist()
        parts = jnp.split(x, idx, axis=axis)
    else:
        parts = jnp.split(x, int(attrs["num"]), axis=axis)
    return {"Out": list(parts)}


@register_op("reshape")
def reshape(ctx, ins, attrs):
    x = ins["X"][0]
    shape = [int(s) for s in attrs["shape"]]
    # paddle semantics: 0 keeps the input dim, -1 infers
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape[: x.ndim])] + [
        s for s in shape[x.ndim:]
    ]
    return {"Out": [x.reshape(shape)]}


@register_op("squeeze")
def squeeze(ctx, ins, attrs):
    jnp = _j()
    axes = tuple(attrs.get("axes", ()))
    x = ins["X"][0]
    return {"Out": [jnp.squeeze(x, axis=axes if axes else None)]}


@register_op("unsqueeze")
def unsqueeze(ctx, ins, attrs):
    jnp = _j()
    x = ins["X"][0]
    for ax in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, ax)
    return {"Out": [x]}


@register_op("transpose")
def transpose(ctx, ins, attrs):
    jnp = _j()
    return {"Out": [jnp.transpose(ins["X"][0], axes=attrs["axis"])]}


@register_op("expand")
def expand(ctx, ins, attrs):
    jnp = _j()
    x = ins["X"][0]
    times = [int(t) for t in attrs["expand_times"]]
    return {"Out": [jnp.tile(x, times)]}


@register_op("pad")
def pad(ctx, ins, attrs):
    jnp = _j()
    x = ins["X"][0]
    p = attrs["paddings"]  # flat [lo0, hi0, lo1, hi1, ...]
    pw = [(int(p[2 * i]), int(p[2 * i + 1])) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pw, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("crop")
def crop(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs["offsets"]
    shape = attrs["shape"]
    # -1 extent = keep the rest of the axis (deferred batch dim)
    idx = tuple(slice(int(o), None if int(s) == -1 else int(o) + int(s))
                for o, s in zip(offsets, shape))
    return {"Out": [x[idx]]}


@register_op("reverse")
def reverse(ctx, ins, attrs):
    """Flip along the given axes (used by v1 rotate_layer; the reference
    RotateLayer composes transpose+reverse in its CPU/GPU kernels)."""
    jnp = _j()
    axes = attrs.get("axis", [0])
    axes = [int(a) for a in (axes if isinstance(axes, (list, tuple))
                             else [axes])]
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(axes))]}


@register_op("sampling_id", grad=None)
def sampling_id(ctx, ins, attrs):
    """Sample one id per row from a multinomial distribution (reference
    SamplingIdLayer, gserver/layers/SamplingIdLayer.cpp): X [B, C] holds
    probabilities (rows sum to 1)."""
    import jax

    jnp = _j()
    x = ins["X"][0]
    logp = jnp.log(jnp.clip(x.astype(jnp.float32), 1e-30, None))
    ids = jax.random.categorical(ctx.rng(attrs), logp, axis=-1)
    return {"Out": [ids.astype(jnp.int64)]}


@register_op("gather", non_diff_inputs=("Index",))
def gather(ctx, ins, attrs):
    jnp = _j()
    x, index = ins["X"][0], ins["Index"][0]
    return {"Out": [jnp.take(x, index.astype(jnp.int32), axis=0)]}


@register_op("beam_gather", non_diff_inputs=("Index",))
def beam_gather(ctx, ins, attrs):
    """Reorder beam-lane state by parent pointers: X [B,K,...],
    Index [B,K] -> Out[b,k] = X[b, Index[b,k]] (the state shuffle after a
    beam_search step; reference did this via LoD offsets)."""
    jnp = _j()
    x, idx = ins["X"][0], ins["Index"][0].astype(jnp.int32)
    full = idx.reshape(idx.shape + (1,) * (x.ndim - 2))
    return {"Out": [jnp.take_along_axis(x, full, axis=1)]}


@register_op("scatter", non_diff_inputs=("Ids",))
def scatter(ctx, ins, attrs):
    x, ids, updates = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    return {"Out": [x.at[ids].set(updates)]}


@register_op("sequence_mask", grad=None)
def sequence_mask(ctx, ins, attrs):
    """lengths [N] -> mask [N, maxlen] (static maxlen attr)."""
    jnp = _j()
    lengths = ins["X"][0]
    maxlen = int(attrs["maxlen"])
    dt = np_dtype(attrs.get("out_dtype", "float32"))
    rng = jnp.arange(maxlen)
    return {"Y": [(rng[None, :] < lengths[:, None]).astype(dt)]}


@register_op("top_k", grad=None)
def top_k(ctx, ins, attrs):
    import jax

    jnp = _j()
    x = ins["X"][0]
    k = int(attrs["k"])
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


@register_op("multiplex", non_diff_inputs=("Ids",))
def multiplex(ctx, ins, attrs):
    jnp = _j()
    ids = ins["Ids"][0].reshape(-1).astype(jnp.int32)
    stacked = jnp.stack(ins["X"], axis=0)  # [n_candidates, batch, ...]
    return {"Out": [stacked[ids, jnp.arange(ids.shape[0])]]}


@register_op("one_hot", grad=None)
def one_hot(ctx, ins, attrs):
    import jax

    jnp = _j()
    x = ins["X"][0].reshape(-1).astype(jnp.int32)
    depth = int(attrs["depth"])
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


@register_op("arg_max", grad=None)
def arg_max(ctx, ins, attrs):
    jnp = _j()
    return {"Out": [jnp.argmax(ins["X"][0], axis=int(attrs.get("axis", -1)))
                    .astype(jnp.int64)]}


@register_op("slice")
def slice_op(ctx, ins, attrs):
    x = ins["Input"][0]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for ax, s, e in zip(axes, starts, ends):
        idx[int(ax)] = slice(int(s), int(e))
    return {"Out": [x[tuple(idx)]]}


@register_op("lookup_table", non_diff_inputs=("Ids",))
def lookup_table(ctx, ins, attrs):
    """Embedding lookup (reference operators/lookup_table_op.cc; sparse
    SelectedRows grads become dense segment-sum scatters under XLA — the
    generic vjp produces exactly a scatter-add)."""
    jnp = _j()
    w = ins["W"][0]
    ids = ins["Ids"][0]
    flat = ids.reshape(-1).astype(jnp.int32)
    if attrs.get("padding_idx") is not None and attrs.get("padding_idx", -1) >= 0:
        pad = int(attrs["padding_idx"])
        emb = jnp.take(w, flat, axis=0)
        emb = jnp.where((flat == pad)[:, None], 0.0, emb)
    else:
        emb = jnp.take(w, flat, axis=0)
    out_shape = tuple(ids.shape[:-1] if ids.shape[-1] == 1 else ids.shape) + (
        w.shape[-1],
    )
    return {"Out": [emb.reshape(out_shape)]}


@register_op("assign_value", grad=None)
def assign_value(ctx, ins, attrs):
    """Materialize attr-carried constants (reference assign_value_op.cc)."""
    import jax.numpy as jnp

    shape = [int(s) for s in attrs["shape"]]
    if "fp32_values" in attrs and attrs["fp32_values"]:
        vals = jnp.asarray(attrs["fp32_values"], dtype=jnp.float32)
    else:
        vals = jnp.asarray(attrs["int32_values"], dtype=jnp.int32)
    return {"Out": [vals.reshape(shape)]}


@register_op("print")
def print_op(ctx, ins, attrs):
    """Debug print (reference print_op.cc): identity passthrough that prints
    the tensor at runtime from inside the compiled program."""
    import jax

    x = ins["X"][0]
    msg = attrs.get("message", "")
    phase = attrs.get("print_phase", "forward")
    if phase != "none":
        safe = msg.replace("{", "{{").replace("}", "}}")
        jax.debug.print(safe + "{x}", x=x)
    return {"Out": [x]}


@register_op("increment")
def increment(ctx, ins, attrs):
    return {"Out": [ins["X"][0] + attrs.get("step", 1.0)]}


@register_op("save", grad=None)
def save_op(ctx, ins, attrs):
    """Tensor checkpoint as a graph op (reference save_op.cc:59): the traced
    value rides out of the compiled step as a reserved fetch; the executor
    writes `file_path` right after the step completes.  (io_callback would
    put the write inside the program, but host callbacks are not available
    on every PJRT backend.)"""
    if getattr(ctx, "sub_depth", 0) > 0:
        raise NotImplementedError(
            "save op inside a control-flow sub-block: its value cannot "
            "escape the traced while/cond body to the host")
    x = ins["X"][0]
    ctx.host_saves.append((str(attrs["file_path"]),
                           bool(attrs.get("overwrite", True)), x))
    return {}


@register_op("load", grad=None)
def load_op(ctx, ins, attrs):
    """Tensor restore as a graph op (reference load_op.cc:22).  The file is
    read when the program is compiled (first run) and embedded as a constant
    — the reference's usage pattern (load programs run once at startup)."""
    jnp = _j()
    path = str(attrs["file_path"])
    with open(path, "rb") as f:  # exact path — np.load would accept it too
        arr = np.load(f, allow_pickle=False)
    if attrs.get("dtype"):
        arr = arr.astype(np_dtype(attrs["dtype"]), copy=False)
    return {"Out": [jnp.asarray(arr)]}


@register_op("pipeline_stage", grad=None)
def pipeline_stage(ctx, ins, attrs):
    """Stage-boundary marker for parallel.ProgramPipeline; pure no-op under
    the single-device Executor so the same program runs unchanged there."""
    return {}


@register_op("arg_sort", grad=None)
def arg_sort(ctx, ins, attrs):
    """Ascending argsort along `axis` (backs lod_rank_table's
    length-descending order via a negated input).  A [B,1] column vector
    squeezes to [B] first (the length-var slot shape); every other shape
    sorts with plain jnp.argsort semantics."""
    jnp = _j()
    x = ins["X"][0]
    if x.ndim == 2 and x.shape[1] == 1:
        x = x[:, 0]
    return {"Out": [jnp.argsort(x, axis=int(attrs.get("axis", 0))
                                ).astype(jnp.int64)]}


@register_op("pruning_mask", grad=None)
def pruning_mask(ctx, ins, attrs):
    """Static pruning mask from parameter magnitudes (reference
    ParameterUpdaterHook.cpp StaticPruningHook::generateMask — sort
    |param|, zero the smallest sparsity_ratio fraction).  Runs in the
    startup program right after the parameter's initializer; the
    optimizer applies the mask after every update (maskParameter
    analog), keeping pruned weights at exactly zero through training."""
    jnp = _j()
    x = ins["X"][0].astype(jnp.float32)
    ratio = float(attrs.get("sparsity_ratio", 0.5))
    absx = jnp.abs(x).ravel()
    n = absx.shape[0]
    k = int(max(0.0, min(1.0, ratio)) * n)
    # count-based like the reference (sort, zero the smallest k by
    # COUNT): a quantile threshold under-prunes when values tie at the
    # boundary (e.g. a constant-initialized or already-pruned table
    # would prune nothing)
    order = jnp.argsort(absx)
    mask = jnp.zeros((n,), jnp.float32).at[order[k:]].set(1.0)
    return {"Out": [mask.reshape(x.shape)]}


# ---------------------------------------------------------------------------
# analytic cost formula (analysis/cost.py; mechanism in registry.py)

from .registry import register_cost, register_sharding  # noqa: E402


def _lookup_table_cost(ins, outs, attrs):
    """Bytes override: an embedding gather reads only the B*D selected
    rows, not the whole table — the generic input-bytes default would
    charge the full vocab to every lookup and wreck the roofline's
    arithmetic-intensity denominator.  FLOPs stay ~0 (copy)."""
    out = outs.get("Out", [None])[0]
    ids = ins.get("Ids", [None])[0]
    if out is None:
        return {}
    item = 2 if str(out.dtype) == "bfloat16" else 4
    read = out.size * item + (ids.size * 8 if ids is not None else 0)
    return {"flops": 0, "bytes": read + out.size * item}


register_cost("lookup_table", _lookup_table_cost)


def _lookup_table_sharding(ctx, ins, outs, attrs):
    """Vocab-sharded embedding: a table sharded over a FREE mesh axis
    is looked up masked-locally and the output all-reduced over that
    axis (the mp vocab path); a table sharded over the ids' own batch
    axis (FSDP) is all-gathered instead — the calibrated GSPMD pair."""
    from ..mesh import entry_axes

    w = ins.get("W", [None])[0]
    ids = ins.get("Ids", [None])[0]
    out = outs.get("Out", [None])[0]
    if w is None or out is None:
        return {}
    batch = set(entry_axes(ids.spec[0])) if ids is not None and ids.spec \
        else set()
    vocab = w.spec[0] if w.spec else None
    lead = ids.spec[0] if ids is not None and ids.spec else None
    ndim = len(out.shape)
    spec = ((lead,) + (None,) * max(0, ndim - 2)
            + ((w.spec[-1],) if ndim >= 2 and len(w.spec) >= 2 else ()))
    spec = tuple(spec[:ndim])
    for a in entry_axes(vocab):
        if ctx.axis_size(a) <= 1:
            continue
        if a in batch:
            ctx.collective("all-gather", (a,), w.global_bytes,
                           var=w.name,
                           why="table sharded over the batch axis is "
                               "gathered for the lookup")
        else:
            ctx.collective("all-reduce", (a,),
                           ctx.device_bytes(out.name, spec),
                           var=out.name,
                           why="masked lookup over the sharded vocab "
                               "dim leaves partial rows",
                           scales_with_axes=True)
    return {"Out": [spec]}


register_sharding("lookup_table", _lookup_table_sharding)
