"""Mixture-of-experts op, reachable from the Program IR.

Beyond-reference capability (SURVEY.md §2.16 last row; the 2018 reference has
no MoE).  Two forms of one op:

* capacity (the default): top-1 gating with static per-expert capacity so
  the whole layer is fixed-shape XLA.  Single-device: the
  dispatch/compute/combine runs locally (stacked-expert einsum).  Under a
  ParallelExecutor whose mesh has an 'ep' axis > 1, expert weights live
  one-expert-per-member and tokens are exchanged with `lax.all_to_all` over
  ICI (the standard TPU MoE recipe) — same dispatch semantics, so
  single-chip and ep-sharded results agree whenever no token is
  capacity-dropped.
* dropless (`dropless=True`; OLMoE, Mixtral, DeepSeek-style fine-grained
  experts): the `top_k` largest router probabilities a token, every chosen
  (token, expert) pair computed, many experts on one chip.  The token-slots
  are sorted by expert ONCE; the sort serves the two or three grouped
  matmuls (`lax.ragged_dot` over the sorted rows, one group an expert; on
  a TPU their backward products are the two Pallas kernels of
  pallas_kernels/grouped_matmul.py, `_grouped_matmul`) and the combine.
  `gated=True` makes an expert `WO(act(WI x) * (WU x))`.  The router's
  logits and the per-expert token counts leave the op for the auxiliary
  losses (`moe_router_loss`).  Not under an 'ep' mesh yet.
* a share of the dropless form (attr `first_expert`; DeepSeek-V3-style
  routing): this chip holds the contiguous experts [first, first + held)
  of a layer whose router still has an output for every expert of the
  deployment.  The router scores all of them (softmax or sigmoid, an
  untrained selection bias, renormalised and scaled top-k weights),
  `Counts` is over all of them, and the op computes the chosen pairs that
  land on held experts, in a static buffer of `buffer_rows` rows whose
  filled part is data-dependent (`_moe_share`); a shared expert every
  token passes may ride beside them.  The rows leave the buffer for their
  tokens, forward and backward, by a sum over rows sorted by token (on a
  TPU the kernel of pallas_kernels/segment_sum.py, `_rows_to_tokens`).
  The partial sum is the op's output: nothing stands in for the chips
  that hold the other experts."""

from __future__ import annotations

import functools

from ..observability.attribution import part_scope
from ..observability.metrics import REGISTRY as _MET
from .llm_ops import wide_dtype
from .registry import register_op

_MET_MOE_LAYERS = _MET.counter(
    "moe_layers_traced_total",
    "dropless expert layers traced (forward emission; once a compile, not "
    "once a step), by top_k, number of experts and the grouped matmul's "
    "implementation")
_MET_SHARE_LAYERS = _MET.counter(
    "moe_share_layers_traced_total",
    "expert layers traced that hold a share of their experts (forward "
    "emission; once a compile, not once a step), by experts held, experts "
    "routed over, top_k and the rows of the static buffer the held pairs "
    "are computed in")
_MET_ROWS_TO_TOKENS = _MET.counter(
    "moe_share_rows_to_tokens_traced_total",
    "sums of a share's buffer rows into their tokens traced (once a "
    "compile, not once a step), by op (combine: the forward's weighted "
    "expert outputs, counted at the forward emission; permute_grad: the "
    "backward of the row gather, counted in the backward rule or, for "
    "autodiff's transpose, at generic_grad's re-emission) and by path "
    "(segment_sum: the kernel segment-sum-rows over token-sorted rows; "
    "scatter_add: XLA's)")
_MET_ROUTER_INPUT = _MET.counter(
    "moe_router_input_traced_total",
    "dropless expert layers and shares traced (forward emission; once a "
    "compile, not once a step), by the tensor their router scores: "
    "source=block (the op's X, the rows the experts compute) or mixer (the "
    "op's RouterX: another tensor, which `decoder_lm` fills with the token "
    "mixer's normed input; SmallThinker's router before attention)")
_MET_GROUPED_BWD = _MET.counter(
    "moe_grouped_backward_total",
    "grouped expert matmuls whose backward was traced (once a compile, not "
    "once a step), by what runs the two backward products: impl=pallas "
    "(the kernels ragged-dot-dlhs and ragged-dot-drhs, counted in the "
    "backward rule) or ragged_dot (autodiff's transposes, counted at "
    "generic_grad's re-emission)")


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
    return _act_fn(act)(h_in @ wi) @ wo


def _act_fn(act):
    import jax
    import jax.numpy as jnp

    return {"relu": jax.nn.relu, "gelu": jax.nn.gelu, "tanh": jnp.tanh,
            "silu": jax.nn.silu}[act]


def _route_top_k(x, gate_w, top_k):
    """Router of the dropless form, float32 where X is bf16 (a bf16 logit's
    rounding is the size of the gap between the k-th and the next
    probability): -> (logits [T, E], weights [T, k], experts [T, k]).  The
    weights are the softmax probabilities as they are, not renormalised
    over the chosen k."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    wide = wide_dtype(x.dtype)
    logits = jnp.dot(x.astype(wide), gate_w.astype(wide),
                     precision=lax.Precision.HIGHEST)
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return logits, weights, experts


@functools.lru_cache(maxsize=None)
def _ragged_dot_kernel_backward():
    """`lax.ragged_dot` whose backward rule is the two Pallas kernels.  One
    function for every call (jit caches by identity).  The forward stays
    plain HLO, so generic_grad's re-emission of it merges with the first
    under CSE and nothing has to be kept for the grad op."""
    import jax
    from jax import lax

    from .pallas_kernels import grouped_matmul

    @jax.custom_vjp
    def product(xs, w, counts):
        return lax.ragged_dot(xs, w, counts)

    def backward(res, dy):
        xs, w, counts = res
        _MET_GROUPED_BWD.inc(impl="pallas")
        return grouped_matmul.grouped_matmul_bwd(xs, w, counts, dy) + (None,)

    product.defvjp(lambda xs, w, counts: (lax.ragged_dot(xs, w, counts),
                                          (xs, w, counts)), backward)
    return product


def _grouped_matmul(ctx, xs, w, counts):
    """xs [R, K] rows sorted by group, w [G, K, N], counts [G] int32 that
    sum to R -> [R, N]: the rows of group g times w[g].  Forward
    `lax.ragged_dot` always.  Where the trace targets one TPU and the
    shapes are whole tiles, the backward products dX and dW are the
    kernels of pallas_kernels/grouped_matmul.py, which read and write w in
    its stored layout; elsewhere (the CPU, a mesh, kernels switched off,
    an unaligned width) autodiff's transposes, as before."""
    from jax import lax

    from .pallas_kernels import grouped_matmul
    from .pallas_kernels._common import pallas_dispatch_ok

    if pallas_dispatch_ok(ctx) and grouped_matmul.usable(
            xs.shape[0], w.shape[1], w.shape[2], xs.dtype.itemsize):
        return _ragged_dot_kernel_backward()(xs, w, counts)
    if ctx.in_grad_replay():
        _MET_GROUPED_BWD.inc(impl="ragged_dot")
    return lax.ragged_dot(xs, w, counts)


def _moe_dropless(ctx, x, gate_w, wi, wu, wo, top_k, act, router_x=None):
    """-> (out [T, D], router logits [T, E] f32, counts [E] f32).  The
    router scores `router_x` [T, D] where given, else x.

    Slot s = t * k + j is token t's j-th choice.  `order` lists the slots
    by expert (stable, so by token within an expert), `inv` is its
    inverse; rows move only by gathers, forward and backward."""
    import jax
    import jax.numpy as jnp

    T, D = x.shape
    n_exp = wi.shape[0]
    with part_scope("moe.route"):
        logits, weights, experts = _route_top_k(
            x if router_x is None else router_x, gate_w, top_k)
    with part_scope("moe.permute"):
        flat = experts.reshape(-1).astype(jnp.int32)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        counts = jnp.sum(jax.nn.one_hot(flat, n_exp, dtype=jnp.int32),
                         axis=0)
        xs = _gather_slots(x, order, inv, top_k)            # [T*k, D]
    with part_scope("moe.experts"):
        wide = logits.dtype
        h = _grouped_matmul(ctx, xs, wi, counts).astype(wide)
        h = _act_fn(act)(h)
        if wu is not None:
            h = h * _grouped_matmul(ctx, xs, wu, counts).astype(wide)
        ys = _grouped_matmul(ctx, h.astype(x.dtype), wo, counts)  # [T*k, D]
    with part_scope("moe.combine"):
        y = _permute_rows(ys, inv, order).reshape(T, top_k, D)
        out = jnp.sum(y.astype(wide) * weights[..., None], axis=1)
    return out.astype(x.dtype), logits, counts.astype(jnp.float32)


def _route_scored(x, gate_w, bias, top_k, scoring, renormalise, scale,
                  epsilon=1e-20):
    """Router of the share form, float32 like `_route_top_k`: -> (scores
    [T, E], weights [T, k], experts [T, k]).  The `top_k` experts are the
    largest of scores + bias (DeepSeek-V3's `e_score_correction_bias`,
    which steers the choice and takes no gradient); their weights are the
    scores WITHOUT it, divided by their sum + `epsilon` where
    `renormalise`, times `scale`.  The epsilon is the model's own:
    DeepSeek-V3's and Moonlight's code adds 1e-20 (the default), LFM2's
    1e-6 (attr `renorm_epsilon`)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    wide = wide_dtype(x.dtype)
    logits = jnp.dot(x.astype(wide), gate_w.astype(wide),
                     precision=lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    chosen_by = scores if bias is None else scores + lax.stop_gradient(
        bias.astype(wide))
    _, experts = lax.top_k(chosen_by, top_k)
    # the scores at the chosen indices as a masked sum over the experts,
    # forward and backward elementwise: a gather of T * k scalars costs the
    # chip as much as one of T * k rows (0.45 ms at 8192 x 6; PERF.md, PR 30)
    chosen = experts[..., None] == jnp.arange(scores.shape[-1])
    weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + epsilon)
    return scores, weights * scale, experts


def _moe_share(ctx, x, gate_w, bias, wi, wu, wo, shared, top_k, act, first,
               rows, route, router_x=None):
    """-> (out [T, D], scores [T, E] f32, top-k weights [T, k] f32, counts
    [E] f32, held pairs [1] f32, dropped pairs [1] f32) for the experts
    [first, first + held) of E, held = wi.shape[0], E = gate_w.shape[1].
    The router scores `router_x` [T, D] where given, else x: scores, choice
    and weights come from it, the rows the experts compute from x.

    The (token, expert) pairs on held experts are sorted to the front, by
    expert (stable, so by token within one), and the first `rows` of them
    fill the buffer the grouped matmuls see; its groups are the held
    experts' counts, cut where the buffer ends (`_sort_carrying`: the
    pairs' weights ride the same sort).  Rows past the filled part
    are in no group: never multiplied, and whatever a grouped product
    leaves in them is replaced by zero as it comes out, forward and (the
    select's transpose) backward: on the chip `lax.ragged_dot` and the
    backward kernels leave rows no group has unwritten, NaN included, and
    a product with zero would keep the NaN (PERF.md, PR 30: a buffer of T x
    top_k rows, three quarters of its tiles unvisited, read NaN gradients
    until every product's output went through a select).  Tokens reach the
    buffer by a gather `rows` wide (`_tokens_to_rows`), and rows leave it
    for their tokens, the forward's weighted outputs and the backward's
    row gradients alike, by a sum over the rows sorted by token
    (`_rows_to_tokens`: one more sort a layer, of the rows' tokens, serves
    both; on one TPU at whole tiles the kernel `segment-sum-rows` of
    pallas_kernels/segment_sum.py, whose visit list is the grouped
    matmuls' `_visits` with token tiles for groups; elsewhere XLA's
    scatter-add, as before).  `shared` = (WI, WU or None, WO) of one
    expert every token passes, or None."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = x.shape
    held, n_exp = wi.shape[0], gate_w.shape[1]
    with part_scope("moe.route"):
        scores, weights, experts = _route_scored(
            x if router_x is None else router_x, gate_w, bias, top_k,
            **route)
    wide = scores.dtype
    with part_scope("moe.permute"):
        flat = experts.reshape(-1).astype(jnp.int32)           # [T * k]
        counts = jnp.sum(jax.nn.one_hot(flat, n_exp, dtype=jnp.int32),
                         axis=0)
        local = flat - first
        here = (local >= 0) & (local < held)
        order, w = _sort_carrying(jnp.where(here, local, held),
                                  weights.reshape(-1), rows)
        ends = jnp.minimum(jnp.cumsum(counts[first:first + held]), rows)
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        pairs = jnp.sum(counts[first:first + held])
        filled = (jnp.arange(rows, dtype=jnp.int32) < ends[-1])[:, None]
        token = order // top_k
        by_token = _token_order(ctx, x, token, filled, w, flat.shape[0])
        xs = _tokens_to_rows(ctx, x, token, filled, by_token)

    def grouped(rows_in, w):
        return jnp.where(filled, _grouped_matmul(ctx, rows_in, w, sizes),
                         jnp.zeros((), rows_in.dtype))

    with part_scope("moe.experts"):
        h = _act_fn(act)(grouped(xs, wi).astype(wide))
        if wu is not None:
            h = h * grouped(xs, wu).astype(wide)
        ys = grouped(h.astype(x.dtype), wo)                    # [rows, D]
    with part_scope("moe.combine"):
        if not ctx.in_grad_replay():
            _MET_ROWS_TO_TOKENS.inc(
                op="combine",
                path="scatter_add" if by_token is None else "segment_sum")
        out = _rows_to_tokens(ys, token, w, filled, T, by_token)
    if shared is not None:
        with part_scope("moe.shared"):
            si, su, so, *gate = shared
            sg = gate[0] if gate else None
            m = _act_fn(act)((x @ si).astype(wide))
            if su is not None:
                m = m * (x @ su).astype(wide)
            passed = (m.astype(x.dtype) @ so).astype(wide)
            if sg is not None:
                with part_scope("moe.shared_gate"):
                    passed = passed * jax.nn.sigmoid(jnp.dot(
                        x.astype(wide), sg.astype(wide),
                        precision=lax.Precision.HIGHEST))
            out = out + passed
    as_f32 = lambda n: n.astype(jnp.float32).reshape(1)
    # a token's weights largest first: where rounding swaps two of its
    # experts (or its last with the next) the sorted weights hardly move
    return (out.astype(x.dtype), scores, lax.top_k(weights, top_k)[0],
            counts.astype(jnp.float32), as_f32(pairs),
            as_f32(pairs - ends[-1]))


def _token_order(ctx, x, token, filled, w, pairs: int):
    """What `_rows_to_tokens` needs to take the kernel, made once a layer
    for its two calls: pallas_kernels/segment_sum.py `token_order` of the
    buffer's rows (their tokens ascending, the permutation, the weights in
    that order, the rows of each token tile), its sort as long as
    `_sort_carrying`'s of the `pairs`.  None where the trace does not
    target one TPU or the shapes are not whole tiles: then the rows leave
    by XLA's scatter-add."""
    from .pallas_kernels import segment_sum
    from .pallas_kernels._common import pallas_dispatch_ok

    if not (pallas_dispatch_ok(ctx) and segment_sum.usable(
            token.shape[0], x.shape[0], x.shape[1], x.dtype.itemsize)):
        return None
    return segment_sum.token_order(token, filled[:, 0], w, x.shape[0],
                                   sort_length=pairs)


def _tokens_to_rows(ctx, x, token, filled, by_token):
    """x [T, D] -> [R, D]: row r is x[token[r]], zero where not `filled`
    [R, 1].  Backward: the rows' gradients summed into their tokens,
    `_rows_to_tokens` without weights in x's dtype (by_token given), or
    autodiff's transpose of the gather, a scatter-add (None)."""
    import jax
    import jax.numpy as jnp

    def take(x, token, filled):
        return jnp.where(filled, x[token], jnp.zeros((), x.dtype))

    if by_token is None:
        if ctx.in_grad_replay():
            _MET_ROWS_TO_TOKENS.inc(op="permute_grad", path="scatter_add")
        return take(x, token, filled)

    @jax.custom_vjp
    def rows_of(x, token, filled, by_token):
        return take(x, token, filled)

    def bwd(res, g):
        token, filled, by_token = res
        _MET_ROWS_TO_TOKENS.inc(op="permute_grad", path="segment_sum")
        return (_rows_to_tokens(g, token, None, filled, x.shape[0],
                                by_token), None, None, None)

    rows_of.defvjp(lambda x, token, filled, by_token: (
        take(x, token, filled), (token, filled, by_token)), bwd)
    return rows_of(x, token, filled, by_token)


def _rows_to_tokens(rows_in, token, weight, filled, tokens: int, by_token):
    """rows_in [R, D], token [R], weight [R] float32 or None, filled [R, 1]
    -> [tokens, D]: out[t] = the sum of rows_in[r] (times weight[r]) over
    the filled rows of token t; weighted, the products and the sum are
    float32 and so is the result, else the sum is float32 and the result
    rows_in's dtype after one rounding.

    by_token None (the weighted sum only): `zeros.at[token].add(...)`,
    XLA's scatter-add (a row that is not filled must hold zeros), under
    plain autodiff.  Else (`_token_order`): the rows gathered into token
    order and summed by the kernel `segment-sum-rows`, whose backward is
    what autodiff gives the scatter-add: d rows_in = g[token] * weight,
    d weight[r] = <g[token[r]], rows_in[r]> in float32, both zero where
    not filled."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels import segment_sum

    if by_token is None:
        return jnp.zeros((tokens, rows_in.shape[1]), weight.dtype).at[
            token].add(rows_in.astype(weight.dtype) * weight[:, None])

    def run(rows_in, by_token, weighted=True):
        seg, perm, weight_sorted, counts = by_token
        return segment_sum.segment_sum(
            rows_in[perm], seg, counts, tokens,
            weight_sorted if weighted else None)

    if weight is None:
        return run(rows_in, by_token, weighted=False)

    @jax.custom_vjp
    def summed(rows_in, weight, token, filled, by_token):
        return run(rows_in, by_token)

    def bwd(res, g):
        rows_in, weight, token, filled = res
        g = g[token]
        return (jnp.where(filled, g * weight[:, None], 0.0).astype(
                    rows_in.dtype),
                jnp.where(filled[:, 0], jnp.sum(
                    g * rows_in.astype(g.dtype), axis=-1), 0.0).astype(
                        weight.dtype),
                None, None, None)

    summed.defvjp(lambda rows_in, weight, token, filled, by_token: (
        run(rows_in, by_token), (rows_in, weight, token, filled)), bwd)
    return summed(rows_in, weight, token, filled, by_token)


def _sort_carrying(key, values, rows: int):
    """-> (order [rows] int32, values[order] [rows]): the first `rows`
    entries of the stable sort of `key` [N], and `values` [N] carried
    through the same sort.  The values ride the sort forward, and their
    gradient rides a second sort back (by `order`, the inverse
    permutation): a sort of 49152 entries is 0.05 ms on the v5e where a
    gather of 12288 scalars is 0.28 (PERF.md, PR 30)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = key.shape[0]

    def run(key, values):
        _, order, carried = lax.sort(
            (key, lax.iota(jnp.int32, n), values), num_keys=1,
            is_stable=True)
        return order, carried

    @jax.custom_vjp
    def take(key, values):
        order, carried = run(key, values)
        return order[:rows], carried[:rows]

    def fwd(key, values):
        order, carried = run(key, values)
        return (order[:rows], carried[:rows]), order

    def bwd(order, cts):
        g = jnp.concatenate([cts[1], jnp.zeros(n - rows, cts[1].dtype)])
        return None, lax.sort((order, g), num_keys=1)[1]

    take.defvjp(fwd, bwd)
    return take(key, values)


def _permute_rows(x, perm, inv):
    """x[perm] for a permutation `perm` with inverse `inv`.  The transpose
    of a gather is a scatter-add; of a permutation it is the gather by the
    inverse, which is what the backward pass runs."""
    import jax

    @jax.custom_vjp
    def take(x, perm, inv):
        return x[perm]

    take.defvjp(lambda x, perm, inv: (x[perm], (perm, inv)),
                lambda res, g: (_permute_rows(g, res[1], res[0]), None,
                                None))
    return take(x, perm, inv)


def _gather_slots(x, order, inv, k):
    """[T, D] -> [T*k, D]: row i is token order[i] // k.  Backward: the
    slots' gradients back in token order (a gather by `inv`), summed over
    each token's k."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def take(x, order, inv):
        return x[order // k]

    def bwd(res, g):
        order, inv = res
        gx = _permute_rows(g, inv, order).reshape(-1, k, g.shape[-1])
        return (jnp.sum(gx.astype(wide_dtype(g.dtype)), axis=1).astype(g.dtype),
                None, None)

    take.defvjp(lambda x, order, inv: (x[order // k], (order, inv)), bwd)
    return take(x, order, inv)


@register_op("moe", non_diff_inputs=("Bias",),
             non_diff_outputs=("Counts", "RouterWeights", "HeldPairs",
                               "DroppedPairs"))
def moe(ctx, ins, attrs):
    """X [T, D] tokens; Gate [D, E]; WI [E, D, H]; WO [E, H, D] -> Out [T, D].

    attrs: capacity_factor (default 1.0), act ('relu').  Capacity is fixed
    at trace time: ceil(tokens_per_member / E * factor).

    With `dropless` (see the module's docstring): attrs top_k (1) and gated
    (False; then WU [E, D, H] is an input too), capacity_factor unused;
    outputs Out, RouterLogits [T, E] float32 and Counts [E] float32 (the
    (token, expert) pairs each expert computed; they sum to T * top_k).
    An optional input RouterX [T, D] is what the ROUTER reads in X's place
    (SmallThinker's router reads the attention's input): scores, choice and
    weights come from it and the weights' gradient goes to it; the rows the
    experts compute, and their gradient, are X's.  Absent, the op traces as
    it did before the input existed.

    With `dropless` and `first_expert` (a share; the module's docstring):
    Gate is [D, E] and WI / WU / WO stack the HELD experts [first_expert,
    first_expert + held); attrs scoring ('softmax' | 'sigmoid'),
    renormalise, renorm_epsilon (1e-20), routed_scale, buffer_rows (T *
    top_k by default: then no pair can be dropped); inputs Bias [E]
    (optional: the selection bias), SI / SU / SO (optional: the shared
    expert's [D, Hs], [D, Hs], [Hs, D]) and SG (optional: [D, 1], the
    shared expert's per-token sigmoid gate).  Outputs Out (the held experts' part of the layer plus the shared
    expert), RouterScores [T, E] float32, RouterWeights [T, top_k] float32
    (each token's weights, largest first; for a check, it passes no
    gradient on), Counts [E] (over ALL E: they sum to T * top_k),
    HeldPairs [1] (the pairs on held experts) and DroppedPairs [1] (those
    of them the buffer had no row for)."""
    import math

    x = ins["X"][0]
    gate_w = ins["Gate"][0]
    wi, wo = ins["WI"][0], ins["WO"][0]
    n_exp = wi.shape[0]
    factor = float(attrs.get("capacity_factor", 1.0))
    act = str(attrs.get("act", "relu"))
    top_k = int(attrs.get("top_k", 1))
    gated = bool(attrs.get("gated", False))
    dropless = bool(attrs.get("dropless", False))

    mesh = getattr(ctx, "mesh", None)
    ep = 1
    token_axes = ()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        ep = sizes.get("ep", 1)
        token_axes = tuple(a for a in ("dp", "ep")
                           if sizes.get(a, 1) > 1)

    if dropless:
        if ep > 1:
            raise NotImplementedError(
                "moe op: the dropless form (top_k, gated experts, many "
                "experts a chip) does not run under an 'ep' mesh yet "
                "(ROADMAP.md R2: 16 experts a chip over ep=4); use the "
                "single-chip Executor, or the capacity form with experts "
                "= ep")
        if not 1 <= top_k <= gate_w.shape[1]:
            raise ValueError(f"moe op: top_k {top_k} not in "
                             f"[1, {gate_w.shape[1]}]")
        wu = ins["WU"][0] if gated else None
        router_x = ins["RouterX"][0] if ins.get("RouterX") else None
        if router_x is not None and router_x.shape != x.shape:
            raise ValueError(f"moe op: RouterX {router_x.shape} is not X's "
                             f"{x.shape}: the router scores one row a token")
        if not ctx.in_grad_replay():
            _MET_ROUTER_INPUT.inc(
                source="block" if router_x is None else "mixer")
        if "first_expert" in attrs:
            return _emit_share(ctx, ins, attrs, x, gate_w, wi, wu, wo,
                               top_k, act, router_x)
        if not ctx.in_grad_replay():
            _MET_MOE_LAYERS.inc(top_k=str(top_k), experts=str(n_exp),
                                impl="ragged_dot")
        out, logits, counts = _moe_dropless(ctx, x, gate_w, wi, wu, wo,
                                            top_k, act, router_x)
        return {"Out": [out], "RouterLogits": [logits], "Counts": [counts]}
    if top_k != 1 or gated:
        raise ValueError(
            "moe op: top_k > 1 and gated experts exist only in the "
            "dropless form (dropless=True); the capacity form is top-1 "
            "with an ungated FFN")

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


def _emit_share(ctx, ins, attrs, x, gate_w, wi, wu, wo, top_k, act,
                router_x=None):
    """The `moe` op's share form: attrs and optional inputs to
    `_moe_share`, and its six outputs to their slots."""
    held, n_exp = wi.shape[0], gate_w.shape[1]
    first = int(attrs["first_expert"])
    rows = int(attrs.get("buffer_rows") or x.shape[0] * top_k)
    if not 0 <= first <= n_exp - held:
        raise ValueError(f"moe op: held experts [{first}, {first + held}) "
                         f"are not among the router's {n_exp}")
    scoring = str(attrs.get("scoring", "softmax"))
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"moe op: scoring {scoring!r}: use 'softmax' or "
                         f"'sigmoid'")
    if not 0 < rows <= x.shape[0] * top_k:
        raise ValueError(f"moe op: buffer_rows {rows} not in (0, "
                         f"{x.shape[0] * top_k}]")
    one = lambda slot: ins[slot][0] if ins.get(slot) else None
    shared = None
    if one("SI") is not None:
        shared = (one("SI"), one("SU"), one("SO"), one("SG"))
    if not ctx.in_grad_replay():
        _MET_SHARE_LAYERS.inc(held=str(held), experts=str(n_exp),
                              top_k=str(top_k), buffer_rows=str(rows))
    out, scores, weights, counts, pairs, dropped = _moe_share(
        ctx, x, gate_w, one("Bias"), wi, wu, wo, shared, top_k, act, first,
        rows, {"scoring": scoring,
               "renormalise": bool(attrs.get("renormalise", False)),
               "scale": float(attrs.get("routed_scale", 1.0)),
               "epsilon": float(attrs.get("renorm_epsilon", 1e-20))},
        router_x)
    return {"Out": [out], "RouterScores": [scores],
            "RouterWeights": [weights], "Counts": [counts],
            "HeldPairs": [pairs], "DroppedPairs": [dropped]}


@register_op("moe_router_loss", non_diff_inputs=("Counts",))
def moe_router_loss(ctx, ins, attrs):
    """The two auxiliary losses of one expert layer, from what the `moe` op
    hands out: RouterLogits [T, E] float32, Counts [E] ->

      Balance [1]  E * sum_e f_e P_e, f_e = Counts_e / T (the share of
                   tokens with e among their k), P_e the mean of
                   softmax(logits)_e (Switch Transformer, Fedus et al.
                   2021, arXiv:2101.03961, eq. 4, as transformers'
                   load_balancing_loss_func extends it to top-k);
      ZLoss [1]    the mean of logsumexp(logits)^2 (ST-MoE, Zoph et al.
                   2022, arXiv:2202.08906, eq. 5).

    Counts carries no gradient; both losses reach the router through the
    logits."""
    import jax
    import jax.numpy as jnp

    logits = ins["RouterLogits"][0]
    logits = logits.astype(wide_dtype(logits.dtype))
    counts = ins["Counts"][0].astype(logits.dtype)
    T, n_exp = logits.shape
    mean_prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    balance = n_exp * jnp.sum(counts / T * mean_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return {"Balance": [balance.reshape(1)], "ZLoss": [z.reshape(1)]}


@register_op("moe_sequence_balance_loss", non_diff_inputs=("Counts",))
def moe_sequence_balance_loss(ctx, ins, attrs):
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437, eq. 17
    to 20) of one expert layer, for ONE sequence: RouterScores [T, E]
    float32, Counts [E] over all E experts, attr top_k ->

      Balance [1]  sum_e f_e P_e, f_e = Counts_e * E / (top_k * T), P_e the
                   mean over the tokens of scores_e / sum_e' scores_e'.

    Counts carries no gradient; the loss reaches the router through the
    scores."""
    import jax.numpy as jnp

    scores = ins["RouterScores"][0]
    scores = scores.astype(wide_dtype(scores.dtype))
    T, n_exp = scores.shape
    f = ins["Counts"][0].astype(scores.dtype) * (
        n_exp / (float(attrs["top_k"]) * T))
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return {"Balance": [jnp.sum(f * p).reshape(1)]}


@register_op("moe_bias_update", grad=None)
def moe_bias_update(ctx, ins, attrs):
    """The auxiliary-loss-free balancing step (DeepSeek-V3, section 2.1.2):
    Bias [E] += rate * sign(mean(Counts) - Counts), an expert that got
    fewer pairs than the mean is preferred a little more next step.  Runs
    after the backward pass: the step's own routing used the old bias."""
    import jax.numpy as jnp

    bias, counts = ins["Bias"][0], ins["Counts"][0]
    step = float(attrs["rate"]) * jnp.sign(jnp.mean(counts) - counts)
    return {"BiasOut": [bias + step.astype(bias.dtype)]}


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
    """Gate matmul (2*T*D*E) + the expert matmuls over every routed token.
    Capacity form: two matmuls (4*T*D*H at capacity), and the bytes
    override adds the all_to_all dispatch/return buffers (2 x token bytes
    each way) — the collective traffic term the per-mode ICI ledgers
    (tools/hlo_analysis.py collectives) measure for the ep programs.
    Dropless form: top_k token-slots a token, each through two matmuls or,
    gated, three (2*D*H each); no capacity factor, and no collective (it
    runs on one chip)."""
    x = ins.get("X", [None])[0]
    gate = ins.get("Gate", [None])[0]
    wi = ins.get("WI", [None])[0]
    if x is None or gate is None or wi is None or len(x.shape) != 2:
        return {}
    t, d = x.shape
    e = gate.shape[1]
    h = wi.shape[2] if len(wi.shape) == 3 else d
    if bool(attrs.get("dropless", False)):
        matmuls = 3 if bool(attrs.get("gated", False)) else 2
        slots = t * int(attrs.get("top_k", 1))
        flops = 0
        if "first_expert" in attrs:
            # a share: the expected pairs on held experts under balanced
            # routing, and the shared expert every token passes
            slots = slots * wi.shape[0] // e
            si = ins.get("SI", [None])[0]
            if si is not None:
                flops = matmuls * 2 * t * d * si.shape[1]
        return {"flops": flops + 2 * t * d * e
                + matmuls * 2 * slots * d * h}
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
    matching the cost formula's collective_bytes above.  The dropless form
    has no ep path (the emitter refuses the mesh), so it declares no
    collective: tokens keep their spec, the router's logits follow the
    tokens' leading axis, the counts are replicated."""
    x = ins.get("X", [None])[0]
    out = outs.get("Out", [None])[0]
    if x is None or out is None:
        return {}
    if bool(attrs.get("dropless", False)):
        lead = tuple(x.spec)[:1]
        scores = "RouterScores" if "first_expert" in attrs else "RouterLogits"
        return {"Out": [tuple(x.spec)],
                scores: [lead + (None,) if lead else None],
                "Counts": [(None,)]}
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
