"""Sequence ops on (padded, lengths) pairs + scan recurrences.

Reference machinery being replaced (SURVEY.md §2.2 'Sequence/LoD ops'):
sequence_{pool,softmax,expand,concat,conv}_op.cc, lstm/gru ops with the
sequence2batch reordering (operators/math/sequence2batch.h) and fused cell
kernels (math/detail/lstm_gpu_kernel.h), shrink_rnn_memory / LoDRankTable
batch-shrinking.  Here every op takes the padded tensor plus an int32
`Length` input and masks; recurrences are single `lax.scan`s whose per-step
math XLA fuses into one kernel — batch stays MXU-shaped instead of shrinking.
"""

from __future__ import annotations

from .registry import register_op


def _mask(lengths, T, dtype):
    import jax.numpy as jnp

    return (jnp.arange(T)[None, :] < lengths[:, None]).astype(dtype)


@register_op("sequence_pool", non_diff_inputs=("Length",))
def sequence_pool(ctx, ins, attrs):
    """[B,T,D]+len → [B,D]; pooltype sum|average|sqrt|max|last|first."""
    import jax.numpy as jnp

    x = ins["X"][0]
    lengths = ins["Length"][0]
    ptype = attrs.get("pooltype", "average").lower()
    B, T = x.shape[0], x.shape[1]
    m = _mask(lengths, T, x.dtype)
    while m.ndim < x.ndim:
        m = m[..., None]
    if ptype == "sum":
        out = jnp.sum(x * m, axis=1)
    elif ptype == "average":
        out = jnp.sum(x * m, axis=1) / jnp.maximum(
            lengths.astype(x.dtype), 1)[:, None]
    elif ptype == "sqrt":
        out = jnp.sum(x * m, axis=1) / jnp.sqrt(
            jnp.maximum(lengths.astype(x.dtype), 1))[:, None]
    elif ptype == "max":
        neg = jnp.finfo(x.dtype).min
        out = jnp.max(jnp.where(m > 0, x, neg), axis=1)
    elif ptype == "last":
        idx = jnp.maximum(lengths - 1, 0)
        out = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    elif ptype == "first":
        out = x[:, 0]
    else:
        raise ValueError(f"unknown pooltype {ptype}")
    return {"Out": [out]}


@register_op("sequence_softmax", non_diff_inputs=("Length",))
def sequence_softmax(ctx, ins, attrs):
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]  # [B, T]
    lengths = ins["Length"][0]
    m = _mask(lengths, x.shape[1], jnp.float32)
    # promote, never downcast: a float64 trace (gradient checking) must not
    # lose precision through a hard-coded float32 softmax
    ft = jnp.promote_types(x.dtype, jnp.float32)
    logits = jnp.where(m > 0, x.astype(ft), ft.type(-1e9))
    return {"Out": [jax.nn.softmax(logits, axis=-1).astype(x.dtype) * m.astype(x.dtype)]}


@register_op("sequence_expand", non_diff_inputs=("Length", "Ref"))
def sequence_expand(ctx, ins, attrs):
    """Broadcast one row per sequence across its timesteps:
    [B,D]+len → [B,T,D] masked (the padded-batch reading of
    sequence_expand_op.cc)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    lengths = ins["Length"][0]
    T = int(attrs.get("max_len", -1))
    if T < 0:  # dynamic build-time T: take it from the reference sequence
        T = ins["Ref"][0].shape[1]
    out = jnp.broadcast_to(x[:, None], (x.shape[0], T) + x.shape[1:])
    m = _mask(lengths, T, x.dtype)
    while m.ndim < out.ndim:
        m = m[..., None]
    return {"Out": [out * m]}


@register_op("sequence_reverse", non_diff_inputs=("Length",))
def sequence_reverse(ctx, ins, attrs):
    """Reverse each sequence within its true length (for bi-RNNs)."""
    import jax.numpy as jnp

    from .pallas_kernels._common import reverse_within_length

    x = ins["X"][0]
    lengths = ins["Length"][0]
    return {"Y": [reverse_within_length(x, lengths)]}


@register_op("sequence_conv", non_diff_inputs=("Length",))
def sequence_conv(ctx, ins, attrs):
    """Context-window projection over time (sequence_conv_op.cc /
    ContextProjection): gather a [context_length] window per step, project."""
    import jax.numpy as jnp

    x = ins["X"][0]  # [B, T, D]
    w = ins["Filter"][0]  # [context_length*D, M]
    lengths = ins["Length"][0]
    ctx_len = int(attrs.get("contextLength", 3))
    ctx_start = int(attrs.get("contextStart", -(ctx_len // 2)))
    B, T, D = x.shape
    m = _mask(lengths, T, x.dtype)[..., None]
    xm = x * m
    cols = []
    for k in range(ctx_len):
        shift = ctx_start + k
        rolled = jnp.roll(xm, -shift, axis=1)
        if shift > 0:
            rolled = rolled.at[:, T - shift:].set(0.0)
        elif shift < 0:
            rolled = rolled.at[:, : -shift].set(0.0)
        cols.append(rolled)
    col = jnp.concatenate(cols, axis=-1)  # [B, T, ctx_len*D]
    out = col.reshape(B * T, -1) @ w
    return {"Out": [out.reshape(B, T, -1) * m]}


@register_op("sequence_concat")
def sequence_concat(ctx, ins, attrs):
    import jax.numpy as jnp

    return {"Out": [jnp.concatenate(ins["X"], axis=-1)]}


@register_op("sequence_erase", grad=None, non_diff_inputs=("Length",))
def sequence_erase(ctx, ins, attrs):
    """Mark erased tokens (can't compact under static shapes: tokens matching
    `tokens` are replaced by pad 0 and lengths recomputed)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    lengths = ins["Length"][0]
    tokens = jnp.asarray(attrs.get("tokens", []), dtype=x.dtype)
    keep = jnp.all(x[..., None] != tokens, axis=-1)
    m = _mask(lengths, x.shape[1], jnp.bool_)
    keep = keep & m
    return {"Out": [jnp.where(keep, x, 0)],
            "LengthOut": [jnp.sum(keep, axis=1).astype(jnp.int32)]}


@register_op("masked_seq_mean", non_diff_inputs=("Length",))
def masked_seq_mean(ctx, ins, attrs):
    """Mean of per-token values [B,T,...] over true (unpadded) tokens →
    scalar [1] (the masked-loss reduction for seq2seq training)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    lengths = ins["Length"][0]
    m = _mask(lengths, x.shape[1], x.dtype)
    while m.ndim < x.ndim:
        m = m[..., None]
    total = jnp.sum(x * m)
    count = jnp.maximum(jnp.sum(lengths).astype(x.dtype), 1.0)
    return {"Out": [(total / count).reshape((1,))]}


# ---------------------------------------------------------------------------
# Recurrences: lax.scan LSTM / GRU


def _lstm_scan(x_proj, h0, c0, w_h, lengths, gate_act, cell_act, cand_act,
               reverse=False, peep=None):
    """x_proj [B,T,4H] (input already projected), w_h [H,4H].
    Paddle gate layout (lstm_op.cc): i, f, c̃, o chunks.  `peep` =
    (W_ic, W_fc, W_oc) adds the peephole terms of lstm_kernel.h:
    i/f gates see c_{t-1}, the o gate sees c_t — all pre-activation."""
    import jax
    import jax.numpy as jnp

    B, T, H4 = x_proj.shape
    H = H4 // 4
    m = (jnp.arange(T)[None, :] < lengths[:, None]).astype(x_proj.dtype)
    w_ic, w_fc, w_oc = peep if peep is not None else (None, None, None)

    def step(carry, t):
        h, c = carry
        idx = T - 1 - t if reverse else t
        g = x_proj[:, idx] + h @ w_h
        gi = g[:, :H] + (c * w_ic if w_ic is not None else 0.0)
        gf = g[:, H: 2 * H] + (c * w_fc if w_fc is not None else 0.0)
        i = gate_act(gi)
        f = gate_act(gf)
        ct = cand_act(g[:, 2 * H: 3 * H])
        c_new = f * c + i * ct
        go = g[:, 3 * H:] + (c_new * w_oc if w_oc is not None else 0.0)
        o = gate_act(go)
        h_new = o * cell_act(c_new)
        mt = m[:, idx][:, None]
        h_new = mt * h_new + (1 - mt) * h
        c_new = mt * c_new + (1 - mt) * c
        return (h_new, c_new), (h_new, c_new)

    (h_T, c_T), (hs, cs) = jax.lax.scan(step, (h0, c0), jnp.arange(T))
    hs = jnp.moveaxis(hs, 0, 1)  # [B,T,H]
    cs = jnp.moveaxis(cs, 0, 1)
    if reverse:
        hs = hs[:, ::-1]
        cs = cs[:, ::-1]
    return hs, cs, h_T, c_T


def _acts():
    import jax
    import jax.numpy as jnp

    return {"sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
            "relu": jax.nn.relu, "identity": lambda v: v}


@register_op("lstm", non_diff_inputs=("Length",),
             non_diff_outputs=("Cell",))
def lstm(ctx, ins, attrs):
    """dynamic_lstm (operators/lstm_op.cc): Input [B,T,4H] pre-projected,
    Weight [H,4H], Bias [4H] — or [7H] with use_peepholes
    (= [4H gate bias, W_ic, W_fc, W_oc], the lstm_op.cc packing)."""
    import jax.numpy as jnp

    acts = _acts()
    x = ins["Input"][0]
    w = ins["Weight"][0]
    lengths = ins["Length"][0]
    bias = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None \
        else None
    B = x.shape[0]
    H = w.shape[0]
    peep = None
    if attrs.get("use_peepholes"):
        if bias is None or bias.shape[-1] < 7 * H:
            raise ValueError(
                f"lstm: use_peepholes needs a [7H]={7 * H} bias "
                f"([4H gate bias, W_ic, W_fc, W_oc]); got "
                f"{None if bias is None else bias.shape} — a silent "
                f"fallback would compute a plain LSTM under peephole "
                f"semantics")
        peep = (bias[4 * H:5 * H], bias[5 * H:6 * H], bias[6 * H:7 * H])
    if bias is not None:
        x = x + bias[: 4 * H][None, None, :]
    h0 = jnp.zeros((B, H), x.dtype)
    c0 = jnp.zeros((B, H), x.dtype)
    if ins.get("H0") and ins["H0"][0] is not None:
        h0 = ins["H0"][0]
    if ins.get("C0") and ins["C0"][0] is not None:
        c0 = ins["C0"][0]
    from .pallas_kernels._common import pallas_dispatch_ok as _pok

    if _pok(ctx):
        # fused Pallas time-loop (VMEM-resident state and weight): forward
        # kernel at inference, forward+fused-BPTT-backward (custom_vjp —
        # honored by the generic_grad jax.vjp) in training.  Gated by the
        # central pallas_dispatch_ok: the trace's target device (an
        # Executor(CPUPlace()) in a TPU process must not lower Pallas/TPU)
        # AND unsharded lowering (GSPMD cannot partition Mosaic calls).
        # is_reverse rides the same kernels through reverse-within-length
        # views of input/outputs (bidirectional nets use both directions).
        from .pallas_kernels import lstm as plstm
        from .pallas_kernels._common import reverse_within_length as _rev

        ok = (plstm.usable(x, attrs) if ctx.is_test
              else plstm.usable_train(x, attrs))
        if ok:
            rev = bool(attrs.get("is_reverse", False))
            xk = _rev(x, lengths) if rev else x
            if ctx.is_test:
                hs, cs, _, _ = plstm.lstm_forward(xk, h0, c0, w, lengths)
            else:
                hs, cs = plstm.make_lstm_train()(xk, h0, c0, w, lengths)
                ctx.kernel_forward(reused=False)
            if rev:
                # scan convention: reversed pads carry the initial state
                hs = _rev(hs, lengths, pad_fill=h0)
                cs = _rev(cs, lengths, pad_fill=c0)
            return {"Hidden": [hs], "Cell": [cs]}
    hs, cs, _, _ = _lstm_scan(
        x, h0, c0, w, lengths,
        acts[attrs.get("gate_activation", "sigmoid")],
        acts[attrs.get("cell_activation", "tanh")],
        acts[attrs.get("candidate_activation", "tanh")],
        reverse=bool(attrs.get("is_reverse", False)),
        peep=peep,
    )
    return {"Hidden": [hs], "Cell": [cs]}


def _gru_scan(x_proj, h0, w_h, lengths, gate_act, cand_act, reverse=False):
    """x_proj [B,T,3H], w_h [H,3H] split as [H,2H] gates + [H,H] candidate
    (gru_op.cc layout: update u, reset r, candidate c)."""
    import jax
    import jax.numpy as jnp

    B, T, H3 = x_proj.shape
    H = H3 // 3
    w_gates = w_h[:, : 2 * H]
    w_cand = w_h[:, 2 * H:]
    m = (jnp.arange(T)[None, :] < lengths[:, None]).astype(x_proj.dtype)

    def step(h, t):
        idx = T - 1 - t if reverse else t
        xt = x_proj[:, idx]
        g = xt[:, : 2 * H] + h @ w_gates
        u = gate_act(g[:, :H])
        r = gate_act(g[:, H:])
        c = cand_act(xt[:, 2 * H:] + (r * h) @ w_cand)
        h_new = u * h + (1 - u) * c
        mt = m[:, idx][:, None]
        h_new = mt * h_new + (1 - mt) * h
        return h_new, h_new

    h_T, hs = jax.lax.scan(step, h0, jnp.arange(T))
    hs = jnp.moveaxis(hs, 0, 1)
    if reverse:
        hs = hs[:, ::-1]
    return hs, h_T


@register_op("gru", non_diff_inputs=("Length",))
def gru(ctx, ins, attrs):
    import jax.numpy as jnp

    acts = _acts()
    x = ins["Input"][0]  # [B,T,3H]
    w = ins["Weight"][0]  # [H,3H]
    lengths = ins["Length"][0]
    H = w.shape[0]
    if ins.get("Bias") and ins["Bias"][0] is not None:
        x = x + ins["Bias"][0][None, None, :]
    B = x.shape[0]
    h0 = ins["H0"][0] if ins.get("H0") and ins["H0"][0] is not None else \
        jnp.zeros((B, H), x.dtype)
    from .pallas_kernels._common import pallas_dispatch_ok as _pok

    if _pok(ctx):
        # fused Pallas time loop (forward kernel at inference, custom_vjp
        # forward+BPTT pair in training) — see pallas_kernels/gru.py; same
        # device/mesh gating + reverse-within-length handling as the LSTM
        from .pallas_kernels import gru as pgru
        from .pallas_kernels._common import reverse_within_length as _rev

        ok = (pgru.usable(x, attrs) if ctx.is_test
              else pgru.usable_train(x, attrs))
        if ok:
            rev = bool(attrs.get("is_reverse", False))
            xk = _rev(x, lengths) if rev else x
            if ctx.is_test:
                hs, _ = pgru.gru_forward(xk, h0, w, lengths)
            else:
                hs = pgru.make_gru_train()(xk, h0, w, lengths)
                ctx.kernel_forward(reused=False)
            if rev:
                hs = _rev(hs, lengths, pad_fill=h0)
            return {"Hidden": [hs]}
    hs, _ = _gru_scan(
        x, h0, w, lengths,
        acts[attrs.get("gate_activation", "sigmoid")],
        acts[attrs.get("activation", "tanh")],
        reverse=bool(attrs.get("is_reverse", False)),
    )
    return {"Hidden": [hs]}


@register_op("lstm_unit")
def lstm_unit(ctx, ins, attrs):
    """Single LSTM step (lstm_unit_op.cc): X [B,4H] pre-projected incl.
    recurrent term, C_prev [B,H]."""
    import jax
    import jax.numpy as jnp

    x, c_prev = ins["X"][0], ins["C_prev"][0]
    H = c_prev.shape[-1]
    fb = float(attrs.get("forget_bias", 0.0))
    i = jax.nn.sigmoid(x[:, :H])
    f = jax.nn.sigmoid(x[:, H: 2 * H] + fb)
    ct = jnp.tanh(x[:, 2 * H: 3 * H])
    o = jax.nn.sigmoid(x[:, 3 * H:])
    c = f * c_prev + i * ct
    h = o * jnp.tanh(c)
    return {"C": [c], "H": [h]}


@register_op("gru_unit")
def gru_unit(ctx, ins, attrs):
    """Single GRU step (gru_unit_op.cc): Input [B,3H], HiddenPrev [B,H],
    Weight [H,3H]."""
    import jax
    import jax.numpy as jnp

    x, h_prev, w = ins["Input"][0], ins["HiddenPrev"][0], ins["Weight"][0]
    H = h_prev.shape[-1]
    b = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None \
        else None
    if b is not None:
        x = x + b[None, :]
    g = x[:, : 2 * H] + h_prev @ w[:, : 2 * H]
    u = jax.nn.sigmoid(g[:, :H])
    r = jax.nn.sigmoid(g[:, H:])
    c = jnp.tanh(x[:, 2 * H:] + (r * h_prev) @ w[:, 2 * H:])
    h = u * h_prev + (1 - u) * c
    return {"Hidden": [h], "Gate": [g], "ResetHiddenPrev": [r * h_prev]}


@register_op("sequence_slice", non_diff_inputs=("Offset", "SliceLength",
                                                "Length"))
def sequence_slice(ctx, ins, attrs):
    """Per-sequence sub-window (reference sequence_slice_op.cc): take
    SliceLength[b] steps starting at Offset[b] from each padded row; the time
    axis keeps its static extent, tail masked to 0."""
    import jax.numpy as jnp

    x = ins["X"][0]  # [B, T, ...]
    off = ins["Offset"][0].reshape(-1).astype(jnp.int32)
    slen = ins["SliceLength"][0].reshape(-1).astype(jnp.int32)
    T = x.shape[1]
    idx = off[:, None] + jnp.arange(T)[None, :]
    idx = jnp.clip(idx, 0, T - 1)
    out = jnp.take_along_axis(
        x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)), axis=1)
    m = _mask(slen, T, x.dtype)
    while m.ndim < out.ndim:
        m = m[..., None]
    return {"Out": [out * m], "LengthOut": [slen]}


@register_op("sequence_reshape", non_diff_inputs=("Length",))
def sequence_reshape(ctx, ins, attrs):
    """Re-chunk each sequence's payload to `new_dim` features (reference
    sequence_reshape_op.cc): row b holds len[b]*D contiguous values, so a
    per-row reshape preserves them; new length = len*D/new_dim."""
    import jax.numpy as jnp

    x = ins["X"][0]  # [B, T, D]
    lengths = ins["Length"][0]
    new_dim = int(attrs["new_dim"])
    B, T, D = x.shape
    assert (T * D) % new_dim == 0, "new_dim must divide T*D"
    out = x.reshape(B, (T * D) // new_dim, new_dim)
    # ceil division: a row whose len*D isn't a new_dim multiple keeps its
    # trailing values in a final partially-padded step (the reference errors
    # on that case; static shapes can't, so keep the payload instead)
    new_len = -(-(lengths * D) // new_dim)
    return {"Out": [out], "LengthOut": [new_len.astype(jnp.int32)]}


@register_op("kmax_seq_score", grad=None, non_diff_inputs=("Length",))
def kmax_seq_score(ctx, ins, attrs):
    """Indices of the beam_size highest scores within each sequence
    (reference KmaxSeqScoreLayer, gserver/layers/KmaxSeqScoreLayer.cpp):
    X [B,T] or [B,T,1] scores + Length → int64 [B, k], positions past the
    sequence end never selected (score forced to -inf)."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    if x.ndim == 3:
        x = x[..., 0]
    lengths = ins["Length"][0].reshape(-1).astype(jnp.int32)
    k = int(attrs.get("beam_size", 1))
    T = x.shape[1]
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    masked = jnp.where(valid, x.astype(jnp.float32), -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(k, T))
    return {"Out": [idx.astype(jnp.int64)]}


@register_op("sequence_concat_time", non_diff_inputs=("Length",))
def sequence_concat_time(ctx, ins, attrs):
    """Concatenate two sequences along TIME per batch row (reference
    SequenceConcatLayer / v1 seq_concat_layer — distinct from the fluid
    sequence_concat op, which concatenates features): row b becomes
    a[b,:la[b]] ++ b[b,:lb[b]], padded to Ta+Tb."""
    import jax.numpy as jnp

    a, b = ins["X"][0], ins["X"][1]  # [B,Ta,D], [B,Tb,D]
    la = ins["Length"][0].reshape(-1).astype(jnp.int32)
    lb = ins["Length"][1].reshape(-1).astype(jnp.int32)
    B, Ta = a.shape[0], a.shape[1]
    Tb = b.shape[1]
    T = Ta + Tb
    t = jnp.arange(T)[None, :]
    in_a = t < la[:, None]
    ai = jnp.clip(t, 0, Ta - 1)
    bi = jnp.clip(t - la[:, None], 0, Tb - 1)
    tail = (1,) * (a.ndim - 2)
    ga = jnp.take_along_axis(a, ai.reshape(ai.shape + tail), axis=1)
    gb = jnp.take_along_axis(b, bi.reshape(bi.shape + tail), axis=1)
    sel = in_a.reshape(in_a.shape + tail)
    out = jnp.where(sel, ga, gb)
    new_len = la + lb
    pad_mask = (t < new_len[:, None]).reshape(in_a.shape + tail)
    return {"Out": [jnp.where(pad_mask, out, 0)],
            "LengthOut": [new_len]}


@register_op("sub_nested_seq", grad=None,
             non_diff_inputs=("SelectedIndices", "Length"))
def sub_nested_seq(ctx, ins, attrs):
    """Select sub-sequences of a nested sequence by per-sample indices
    (reference SubNestedSequenceLayer, used in beam training): X
    [B, S, T, D] (S = sub-sequence slots, padded), SubLength [B, S],
    SelectedIndices [B, K] → Out [B, K, T, D] + selected lengths."""
    import jax.numpy as jnp

    x = ins["X"][0]
    sub_len = ins["Length"][0].astype(jnp.int32)  # [B, S]
    sel = ins["SelectedIndices"][0].astype(jnp.int32)  # [B, K]
    sel_c = jnp.clip(sel, 0, x.shape[1] - 1)
    idx = sel_c.reshape(sel_c.shape + (1,) * (x.ndim - 2))
    out = jnp.take_along_axis(x, idx, axis=1)
    new_len = jnp.take_along_axis(sub_len, sel_c, axis=1)
    # negative selected index = unused beam slot -> empty sequence
    new_len = jnp.where(sel >= 0, new_len, 0)
    return {"Out": [out], "LengthOut": [new_len]}


@register_op("lod_reset", grad=None, non_diff_inputs=("Y", "Length"))
def lod_reset(ctx, ins, attrs):
    """Replace a tensor's sequence segmentation (reference lod_reset_op.cc).
    In the padded representation the payload is untouched and only the
    companion lengths change — from input Y's lengths or attr target_lengths."""
    import jax.numpy as jnp

    x = ins["X"][0]
    if ins.get("Y") and ins["Y"][0] is not None:
        new_len = ins["Y"][0].reshape(-1).astype(jnp.int32)
    else:
        new_len = jnp.asarray(attrs["target_lengths"], dtype=jnp.int32)
    return {"Out": [x], "LengthOut": [new_len]}


# ---------------------------------------------------------------------------
# analytic cost formulas (analysis/cost.py; mechanism in registry.py)

from .registry import register_cost  # noqa: E402


def _lstm_cost(ins, outs, attrs):
    """Recurrent gate matmuls: T steps of [B,H]x[H,4H] = 8*B*T*H^2 (the
    input projection happened in the preceding fc/mul op)."""
    x = ins.get("Input", [None])[0]
    w = ins.get("Weight", [None])[0]
    if x is None or w is None or len(x.shape) != 3:
        return {}
    b, t, _ = x.shape
    h = w.shape[0]
    return {"flops": 8 * b * t * h * h}


register_cost("lstm", _lstm_cost)


def _gru_cost(ins, outs, attrs):
    """T steps of [B,H]x[H,3H] = 6*B*T*H^2."""
    x = ins.get("Input", [None])[0]
    w = ins.get("Weight", [None])[0]
    if x is None or w is None or len(x.shape) != 3:
        return {}
    b, t, _ = x.shape
    h = w.shape[0]
    return {"flops": 6 * b * t * h * h}


register_cost("gru", _gru_cost)
