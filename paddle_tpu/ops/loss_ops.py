"""Loss & metric ops (reference operators/: cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, accuracy_op.cc, auc_op.cc, *_loss ops —
SURVEY.md §2.2 'Losses/metrics')."""

from __future__ import annotations

from .registry import register_op


@register_op("cross_entropy", non_diff_inputs=("Label",))
def cross_entropy(ctx, ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]  # [N, D] probabilities
    label = ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        idx = label.reshape(x.shape[:-1] + (1,)).astype(jnp.int32)
        picked = jnp.take_along_axis(x, idx, axis=-1)
        loss = -jnp.log(picked + eps)
    return {"Y": [loss]}


@register_op(
    "softmax_with_cross_entropy",
    non_diff_inputs=("Label",),
    non_diff_outputs=("Softmax",),
)
def softmax_with_cross_entropy(ctx, ins, attrs):
    import jax
    import jax.numpy as jnp

    logits = ins["Logits"][0]
    label = ins["Label"][0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        idx = label.reshape(logp.shape[:-1] + (1,)).astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, idx, axis=-1)
    return {"Loss": [loss], "Softmax": [jnp.exp(logp)]}


@register_op(
    "sigmoid_cross_entropy_with_logits", non_diff_inputs=("Label",)
)
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    label = ins["Label"][0].astype(x.dtype)
    loss = jnp.maximum(x, 0) - x * label + jax.nn.softplus(-jnp.abs(x))
    return {"Out": [loss]}


@register_op("log_loss", non_diff_inputs=("Labels",))
def log_loss(ctx, ins, attrs):
    import jax.numpy as jnp

    p = ins["Predicted"][0]
    y = ins["Labels"][0]
    eps = float(attrs.get("epsilon", 1e-7))
    return {"Loss": [-(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))]}


@register_op("hinge_loss", non_diff_inputs=("Labels",))
def hinge_loss(ctx, ins, attrs):
    import jax.numpy as jnp

    logits = ins["Logits"][0]
    y = ins["Labels"][0]
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2 * y - 1) * logits)]}


@register_op("huber_loss", non_diff_inputs=())
def huber_loss(ctx, ins, attrs):
    import jax.numpy as jnp

    x, y = ins["X"][0], ins["Y"][0]
    d = float(attrs.get("delta", 1.0))
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= d, 0.5 * r * r, d * (a - 0.5 * d))
    return {"Out": [loss], "Residual": [r]}


@register_op("smooth_l1_loss")
def smooth_l1_loss(ctx, ins, attrs):
    import jax.numpy as jnp

    x, y = ins["X"][0], ins["Y"][0]
    sigma = float(attrs.get("sigma", 1.0))
    s2 = sigma * sigma
    d = x - y
    if ins.get("InsideWeight") and ins["InsideWeight"][0] is not None:
        d = d * ins["InsideWeight"][0]
    a = jnp.abs(d)
    per = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    if ins.get("OutsideWeight") and ins["OutsideWeight"][0] is not None:
        per = per * ins["OutsideWeight"][0]
    out = jnp.sum(per.reshape(per.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "Diff": [d]}


@register_op("rank_loss", non_diff_inputs=("Label",))
def rank_loss(ctx, ins, attrs):
    import jax
    import jax.numpy as jnp

    label = ins["Label"][0]
    left, right = ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": [jax.nn.softplus(d) - label * d]}


@register_op("margin_rank_loss", non_diff_inputs=("Label",))
def margin_rank_loss(ctx, ins, attrs):
    import jax.numpy as jnp

    label = ins["Label"][0]
    x1, x2 = ins["X1"][0], ins["X2"][0]
    margin = float(attrs.get("margin", 0.0))
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register_op("modified_huber_loss", non_diff_inputs=("Y",))
def modified_huber_loss(ctx, ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]
    y = ins["Y"][0].astype(x.dtype)
    z = (2 * y - 1) * x
    loss = jnp.where(z < -1, -4 * z, jnp.maximum(0.0, 1 - z) ** 2)
    return {"Out": [loss], "IntermediateVal": [z]}


# --- metrics (not differentiated) ------------------------------------------


@register_op("accuracy", grad=None)
def accuracy(ctx, ins, attrs):
    import jax.numpy as jnp

    pred_idx = ins["Indices"][0]  # [N, k] from top_k
    label = ins["Label"][0].reshape(-1, 1)
    correct = jnp.any(pred_idx == label, axis=1)
    # count dtype: int64 when x64 is on (tests), else int32 — requesting
    # int64 with x64 off only buys a per-step truncation warning
    idt = jnp.asarray(1).dtype if jnp.asarray(1).dtype == jnp.int64 \
        else jnp.int32
    n = jnp.asarray([pred_idx.shape[0]], dtype=idt)
    c = jnp.sum(correct.astype(jnp.float32))
    return {
        "Accuracy": [(c / pred_idx.shape[0]).reshape((1,))],
        "Correct": [c.astype(idt).reshape((1,))],
        "Total": [n],
    }


@register_op("auc", grad=None)
def auc(ctx, ins, attrs):
    """Streaming-free batch AUC via rank statistic."""
    import jax.numpy as jnp

    probs = ins["Predict"][0][:, 1] if ins["Predict"][0].ndim == 2 else ins["Predict"][0]
    label = ins["Label"][0].reshape(-1).astype(jnp.float32)
    order = jnp.argsort(probs)
    ranks = jnp.empty_like(order).at[order].set(jnp.arange(1, probs.shape[0] + 1))
    npos = jnp.sum(label)
    nneg = label.shape[0] - npos
    auc_v = (jnp.sum(ranks * label) - npos * (npos + 1) / 2) / jnp.maximum(
        npos * nneg, 1.0
    )
    return {"AUC": [auc_v.reshape((1,))]}


@register_op("precision_recall", grad=None)
def precision_recall(ctx, ins, attrs):
    import jax.numpy as jnp

    idx = ins["Indices"][0].reshape(-1)
    label = ins["Label"][0].reshape(-1)
    ncls = int(attrs["class_number"])
    pred_1h = (idx[:, None] == jnp.arange(ncls)[None, :])
    lab_1h = (label[:, None] == jnp.arange(ncls)[None, :])
    tp = jnp.sum(pred_1h & lab_1h, axis=0).astype(jnp.float32)
    fp = jnp.sum(pred_1h & ~lab_1h, axis=0).astype(jnp.float32)
    fn = jnp.sum(~pred_1h & lab_1h, axis=0).astype(jnp.float32)
    prec = tp / jnp.maximum(tp + fp, 1.0)
    rec = tp / jnp.maximum(tp + fn, 1.0)
    f1 = 2 * prec * rec / jnp.maximum(prec + rec, 1e-6)
    macro = jnp.stack([jnp.mean(prec), jnp.mean(rec), jnp.mean(f1)])
    return {"BatchMetrics": [macro], "AccumMetrics": [macro]}


def _chunk_markers(labels, lengths, num_chunk_types, scheme):
    """Per-position chunk start/end/type/in-chunk markers for a [B,T] int tag
    sequence under a CoNLL tagging scheme (reference chunk_eval_op.h's
    Segment extraction, vectorized over the padded batch)."""
    import jax.numpy as jnp

    num_tag = {"plain": 1, "IOB": 2, "IOE": 2, "IOBES": 4}[scheme]
    o_label = num_chunk_types * num_tag
    T = labels.shape[1]
    valid = (jnp.arange(T)[None, :] < lengths[:, None]) & (labels < o_label)
    ctype = jnp.where(valid, labels // num_tag, -1)
    tag = jnp.where(valid, labels % num_tag, -1)
    prev_t = jnp.pad(ctype, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    next_t = jnp.pad(ctype, ((0, 0), (0, 1)), constant_values=-1)[:, 1:]
    prev_tag = jnp.pad(tag, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    next_tag = jnp.pad(tag, ((0, 0), (0, 1)), constant_values=-1)[:, 1:]
    diff_prev = (prev_t != ctype)
    diff_next = (next_t != ctype)
    if scheme == "plain":
        start, end = diff_prev, diff_next
    elif scheme == "IOB":  # B=0 I=1
        start = (tag == 0) | ((tag == 1) & diff_prev)
        end = diff_next | (next_tag == 0)
    elif scheme == "IOE":  # I=0 E=1
        start = diff_prev | (prev_tag == 1)
        end = (tag == 1) | ((tag == 0) & diff_next)
    else:  # IOBES: B=0 I=1 E=2 S=3
        start = (tag == 0) | (tag == 3) | ((tag != -1) & diff_prev)
        end = (tag == 2) | (tag == 3) | ((tag != -1) & diff_next)
    start = start & valid
    end = end & valid
    return start, end, ctype, valid


@register_op("chunk_eval", grad=None, non_diff_inputs=("Inference", "Label",
                                                       "Length"))
def chunk_eval(ctx, ins, attrs):
    """Chunk-level precision/recall/F1 (reference chunk_eval_op.cc; feeds the
    ChunkEvaluator).  A predicted chunk is correct iff a label chunk has the
    same [start, end] span and type — counted with one scan over time."""
    import jax
    import jax.numpy as jnp

    inf = ins["Inference"][0].astype(jnp.int32)
    lab = ins["Label"][0].astype(jnp.int32)
    if inf.ndim > 2:
        inf = inf.reshape(inf.shape[0], -1)
        lab = lab.reshape(lab.shape[0], -1)
    lengths = (ins["Length"][0].astype(jnp.int32) if ins.get("Length")
               and ins["Length"][0] is not None
               else jnp.full((inf.shape[0],), inf.shape[1], jnp.int32))
    ncls = int(attrs["num_chunk_types"])
    scheme = attrs.get("chunk_scheme", "IOB")

    i_start, i_end, i_type, _ = _chunk_markers(inf, lengths, ncls, scheme)
    l_start, l_end, l_type, _ = _chunk_markers(lab, lengths, ncls, scheme)
    n_inf = jnp.sum(i_start)
    n_lab = jnp.sum(l_start)

    # scan: `open` = inside chunks that started together, same type, and have
    # stayed span-identical; a simultaneous end while open is a correct chunk
    def step(open_, t):
        both_start = i_start[:, t] & l_start[:, t] & (i_type[:, t] == l_type[:, t])
        open_ = jnp.where(i_start[:, t] | l_start[:, t], both_start, open_)
        open_ = open_ & (i_type[:, t] == l_type[:, t])
        both_end = i_end[:, t] & l_end[:, t]
        any_end = i_end[:, t] | l_end[:, t]
        correct = open_ & both_end
        open_ = open_ & ~any_end
        return open_, jnp.sum(correct)

    B, T = inf.shape
    _, per_t = jax.lax.scan(step, jnp.zeros((B,), bool), jnp.arange(T))
    n_correct = jnp.sum(per_t)
    prec = n_correct / jnp.maximum(n_inf, 1)
    rec = n_correct / jnp.maximum(n_lab, 1)
    f1 = 2 * prec * rec / jnp.maximum(prec + rec, 1e-6)
    i64 = lambda v: v.astype(jnp.int64).reshape((1,))
    f32 = lambda v: v.astype(jnp.float32).reshape((1,))
    return {"Precision": [f32(prec)], "Recall": [f32(rec)],
            "F1-Score": [f32(f1)], "NumInferChunks": [i64(n_inf)],
            "NumLabelChunks": [i64(n_lab)],
            "NumCorrectChunks": [i64(n_correct)]}


@register_op("positive_negative_pair", grad=None)
def positive_negative_pair(ctx, ins, attrs):
    """Ranking pair statistics per query (reference
    positive_negative_pair_op.cc): among same-query pairs with different
    labels, count concordant / discordant / tied-score pairs."""
    import jax.numpy as jnp

    score = ins["Score"][0].reshape(-1)
    label = ins["Label"][0].reshape(-1).astype(jnp.float32)
    qid = ins["QueryID"][0].reshape(-1)
    same_q = qid[:, None] == qid[None, :]
    upper = jnp.triu(jnp.ones((score.shape[0],) * 2, bool), k=1)
    informative = same_q & upper & (label[:, None] != label[None, :])
    ds = score[:, None] - score[None, :]
    dl = label[:, None] - label[None, :]
    pos = jnp.sum((informative & (ds * dl > 0)).astype(jnp.float32))
    neg = jnp.sum((informative & (ds * dl < 0)).astype(jnp.float32))
    neu = jnp.sum((informative & (ds == 0)).astype(jnp.float32))
    acc = lambda slot, v: (v + ins[slot][0].reshape(-1)[0]
                           if ins.get(slot) and ins[slot][0] is not None else v)
    r = lambda v: v.reshape((1,))
    return {"PositivePair": [r(acc("AccumulatePositivePair", pos))],
            "NegativePair": [r(acc("AccumulateNegativePair", neg))],
            "NeutralPair": [r(acc("AccumulateNeutralPair", neu))]}


@register_op("hsigmoid", non_diff_inputs=("Label",))
def hsigmoid(ctx, ins, attrs):
    """Hierarchical sigmoid over a complete binary tree (reference
    gserver/layers/HierarchicalSigmoidLayer.cpp + math/MatrixBitCode):
    cost of routing each sample to its label leaf, O(log C) parameters
    touched per sample — here computed over the static max depth with
    per-depth masks so the whole thing is a few MXU matmuls."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]                      # [B, D]
    w = ins["W"][0]                      # [C-1, D] internal-node weights
    label = ins["Label"][0].reshape(-1).astype(jnp.int32)  # [B]
    bias = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None \
        else None
    import math

    num_classes = int(attrs["num_classes"])
    depth = max(int(math.ceil(math.log2(num_classes))), 1)

    code = label + num_classes           # 1-indexed heap leaf position
    losses = jnp.zeros(x.shape[0], x.dtype)
    for k in range(1, depth + 1):
        node = code >> k                 # ancestor (1-indexed internal node)
        valid = node >= 1
        idx = jnp.clip(node - 1, 0, num_classes - 2)
        bit = (code >> (k - 1)) & 1      # 1 = right child
        z = jnp.einsum("bd,bd->b", x, w[idx])
        if bias is not None:
            z = z + bias.reshape(-1)[idx]
        # reference MatrixBitCode convention: loss = softplus(z) - bit*z,
        # i.e. bit=1 → softplus(-z), bit=0 → softplus(z) — weights trained by
        # the reference route identically here
        t = 2.0 * bit.astype(x.dtype) - 1.0
        losses = losses + jnp.where(valid, jax.nn.softplus(-t * z), 0.0)
    return {"Out": [losses[:, None]]}


@register_op("huber_classification", non_diff_inputs=("Label",))
def huber_classification(ctx, ins, attrs):
    """Huber two-class loss (reference HuberTwoClassification,
    gserver/layers/CostLayer.cpp): labels in {0,1} mapped to y=±1;
    loss = 0 if y·f > 1, (1 - y·f)² if -1 ≤ y·f ≤ 1, -4·y·f if y·f < -1."""
    import jax.numpy as jnp

    f = ins["X"][0].reshape(-1)
    y = ins["Label"][0].reshape(-1).astype(jnp.float32) * 2.0 - 1.0
    m = y * f
    loss = jnp.where(m < -1.0, -4.0 * m,
                     jnp.where(m < 1.0, (1.0 - m) ** 2, 0.0))
    return {"Out": [loss.reshape(-1, 1)]}


@register_op("cross_entropy_selfnorm", non_diff_inputs=("Label",))
def cross_entropy_selfnorm(ctx, ins, attrs):
    """Self-normalizing cross entropy (reference
    CrossEntropyOverSelfNorm, gserver CostLayer): input rows are positive
    un-normalized scores; the alpha term pushes each row sum toward 1 so
    inference can skip normalization."""
    import jax.numpy as jnp

    x = ins["X"][0]
    label = ins["Label"][0].reshape(-1).astype(jnp.int32)
    alpha = float(attrs.get("softmax_selfnorm_alpha", 0.1))
    eps = 1e-8
    z = jnp.sum(x, axis=-1)
    picked = jnp.take_along_axis(x, label[:, None], axis=-1)[:, 0]
    ce = -jnp.log(picked / (z + eps) + eps)
    self_norm = alpha * jnp.log(z + eps) ** 2
    return {"Out": [(ce + self_norm).reshape(-1, 1)]}


@register_op("lambda_rank", non_diff_inputs=("Score", "Length"))
def lambda_rank(ctx, ins, attrs):
    """LambdaRank listwise cost (reference LambdaCost,
    gserver/layers/CostLayer.cpp:LambdaCost): per query (= sequence),
    pairwise logistic loss between mis-ordered documents weighted by the
    |ΔNDCG@k| of swapping them.  Padded form: X scores [B,T] or [B,T,1],
    Score relevance labels same shape, Length valid counts."""
    import jax
    import jax.numpy as jnp

    s = ins["X"][0]
    rel = ins["Score"][0]
    if s.ndim == 3:
        s = s[..., 0]
    if rel.ndim == 3:
        rel = rel[..., 0]
    lengths = ins["Length"][0].reshape(-1).astype(jnp.int32)
    ndcg_num = int(attrs.get("NDCG_num", 5))
    B, T = s.shape
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    relf = rel.astype(jnp.float32)
    gain = 2.0 ** relf - 1.0
    # ideal DCG@k normalizer from the top-k relevances per query
    topk = jax.lax.top_k(jnp.where(valid, relf, -jnp.inf),
                         min(ndcg_num, T))[0]
    disc = 1.0 / jnp.log2(jnp.arange(min(ndcg_num, T)) + 2.0)
    idcg = jnp.sum(jnp.where(jnp.isfinite(topk),
                             (2.0 ** topk - 1.0) * disc[None, :], 0.0),
                   axis=1)
    idcg = jnp.maximum(idcg, 1e-6)
    # rank positions by current score (0 = highest)
    order = jnp.argsort(jnp.argsort(
        jnp.where(valid, -s.astype(jnp.float32), jnp.inf), axis=1), axis=1)
    dr = 1.0 / jnp.log2(order.astype(jnp.float32) + 2.0)
    pair_valid = (valid[:, :, None] & valid[:, None, :]
                  & (relf[:, :, None] > relf[:, None, :]))
    delta_ndcg = jnp.abs(
        (gain[:, :, None] - gain[:, None, :])
        * (dr[:, :, None] - dr[:, None, :])) / idcg[:, None, None]
    sdiff = s.astype(jnp.float32)[:, :, None] - \
        s.astype(jnp.float32)[:, None, :]
    pair_loss = jnp.logaddexp(0.0, -sdiff)  # log(1 + e^{-(si - sj)})
    loss = jnp.sum(jnp.where(pair_valid, delta_ndcg * pair_loss, 0.0),
                   axis=(1, 2))
    return {"Out": [loss.reshape(-1, 1)]}


@register_op("cross_entropy_over_beam",
             non_diff_inputs=("Ids", "Label", "Length"))
def cross_entropy_over_beam(ctx, ins, attrs):
    """Cross-entropy over one beam expansion (reference
    gserver/layers/CrossEntropyOverBeam.cpp, layers.py
    cross_entropy_over_beam:5804): softmax over the scores of the
    beam-selected candidates, negative log-likelihood of the gold
    candidate's slot.  A gold that fell out of the beam contributes a
    constant -log(eps) penalty with no gradient (the reference trains with
    the gold forced into the beam, so this path only keeps mis-configured
    beams finite).

    Inputs: X [B,T] or [B,T,1] raw candidate scores, Ids [B,K] int selected
    candidate positions (kmax_seq_score output), Label [B,1] int gold
    position, optional Length [B] valid-candidate counts — when the beam
    width exceeds a sequence's length, kmax pads with positions >= length;
    those slots are excluded from the softmax.  Output: Out [B,1] loss.
    Gradient flows into X through the gather + softmax (default vjp)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    if x.ndim == 3:
        x = x[..., 0]
    ids = ins["Ids"][0].astype(jnp.int32)
    gold = ins["Label"][0].reshape(-1).astype(jnp.int32)
    fdt = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    sel = jnp.take_along_axis(x.astype(fdt), ids, axis=1)  # [B,K]
    valid = jnp.ones(ids.shape, bool)
    if ins.get("Length") and ins["Length"][0] is not None:
        lengths = ins["Length"][0].reshape(-1).astype(jnp.int32)
        valid = ids < lengths[:, None]
    sel = jnp.where(valid, sel, -jnp.inf)
    logp = sel - jnp.max(sel, axis=1, keepdims=True)
    logp = logp - jnp.log(
        jnp.sum(jnp.where(valid, jnp.exp(logp), 0.0), axis=1, keepdims=True))
    hit = (ids == gold[:, None]) & valid  # [B,K]
    in_beam = jnp.any(hit, axis=1)
    gold_logp = jnp.sum(jnp.where(hit, logp, 0.0), axis=1)
    floor = jnp.log(jnp.asarray(1e-10, fdt))
    loss = jnp.where(in_beam, -gold_logp, -floor)
    return {"Out": [loss.reshape(-1, 1)]}


# ---------------------------------------------------------------------------
# sharding-propagation rule (analysis/sharding.py; mechanism in registry)

from .registry import register_sharding  # noqa: E402


def _swce_sharding(ctx, ins, outs, attrs):
    """Softmax-with-cross-entropy over a vocab-sharded logits tensor
    pays the log-softmax max+sum reductions over the sharded dim (two
    row-shaped all-reduces); row-sharded (batch) logits are free."""
    from ..mesh import entry_axes

    logits = ins.get("Logits", [None])[0]
    loss = outs.get("Loss", [None])[0]
    soft = outs.get("Softmax", [None])[0]
    if logits is None or not logits.spec:
        return {}
    loss_spec = tuple(logits.spec[:-1]) + (None,)
    vocab_axes = tuple(a for a in entry_axes(logits.spec[-1])
                       if ctx.axis_size(a) > 1)
    if vocab_axes and loss is not None:
        ctx.collective("all-reduce", vocab_axes,
                       2 * ctx.device_bytes(loss.name, loss_spec),
                       var=loss.name,
                       why="log-softmax max+sum over the sharded vocab "
                           "dim", scales_with_axes=True)
    out = {"Loss": [loss_spec]}
    if soft is not None:
        out["Softmax"] = [tuple(logits.spec)]
    return out


register_sharding("softmax_with_cross_entropy", _swce_sharding)
