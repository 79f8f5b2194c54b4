"""NN layer ops: conv, pool, batch_norm, dropout, lrn, layer_norm...

Reference: operators/conv_op.cc (+conv_cudnn_op.cu), pool_op.cc,
batch_norm_op.cc, dropout_op.cc, lrn_op.cc (SURVEY.md §2.2 'NN layers').
cuDNN-specific kernel variants collapse: lax.conv_general_dilated /
lax.reduce_window lower straight onto the MXU / VPU. Layout stays NCHW at the
IR level (the reference's contract); XLA re-lays-out internally for TPU."""

from __future__ import annotations

from .registry import register_op


def _pair(v):
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(v), int(v)]


@register_op("conv2d")
def conv2d(ctx, ins, attrs):
    """data_format NCHW (reference default) or NHWC — on TPU the NHWC
    activation layout avoids the relayout XLA otherwise inserts around each
    convolution (filters stay OIHW in both: their relayout is one-off and
    folded into the weight)."""
    import jax

    x = ins["Input"][0]
    w = ins["Filter"][0]  # OIHW
    fmt = str(attrs.get("data_format", "NCHW"))
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    if groups == -1:
        # per-sample convolution (v1 ConvOperator): caller packed the batch
        # into channels; one group per sample, resolved at trace time
        ch = x.shape[3] if fmt == "NHWC" else x.shape[1]
        groups = ch // w.shape[1]
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=(fmt, "OIHW", fmt),
        feature_group_count=groups,
        preferred_element_type=None,
    )
    return {"Output": [out]}


@register_op("depthwise_conv2d")
def depthwise_conv2d(ctx, ins, attrs):
    attrs = dict(attrs)
    ch_axis = 3 if str(attrs.get("data_format", "NCHW")) == "NHWC" else 1
    attrs["groups"] = ins["Input"][0].shape[ch_axis]
    return conv2d(ctx, ins, attrs)


@register_op("conv2d_transpose")
def conv2d_transpose(ctx, ins, attrs):
    import jax

    x = ins["Input"][0]
    w = ins["Filter"][0]  # IOHW in paddle conv_transpose
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    # paddle filter layout is [C_in, C_out, H, W]; with transpose_kernel=True
    # jax swaps the I/O roles of the rhs spec, so the spec names the
    # TRANSPOSED reading: "O"=C_in (must match input), "I"=C_out.
    # padding: paddle gives the FORWARD conv's pad p; the transposed conv
    # needs d*(k-1)-p so out = (in-1)*s - 2p + d*(k-1) + 1 (conv_transpose_op.h)
    jpad = [(dilations[i] * (w.shape[2 + i] - 1) - pads[i],) * 2
            for i in range(2)]
    out = jax.lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=jpad,
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True,
    )
    return {"Output": [out]}


def _triple(v):
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(v)] * 3


@register_op("conv3d")
def conv3d(ctx, ins, attrs):
    """Volumetric conv (reference conv_op.cc:321 conv3d; vol2col collapses
    into the XLA convolution)."""
    import jax

    x = ins["Input"][0]  # NCDHW
    w = ins["Filter"][0]  # OIDHW
    strides = _triple(attrs.get("strides", [1, 1, 1]))
    pads = _triple(attrs.get("paddings", [0, 0, 0]))
    dilations = _triple(attrs.get("dilations", [1, 1, 1]))
    groups = int(attrs.get("groups", 1))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=groups)
    return {"Output": [out]}


@register_op("conv3d_transpose")
def conv3d_transpose(ctx, ins, attrs):
    """Reference conv_transpose_op.cc:312."""
    import jax

    x = ins["Input"][0]
    w = ins["Filter"][0]  # IODHW
    strides = _triple(attrs.get("strides", [1, 1, 1]))
    pads = _triple(attrs.get("paddings", [0, 0, 0]))
    dilations = _triple(attrs.get("dilations", [1, 1, 1]))
    # see conv2d_transpose: spec + padding are the transposed reading of the
    # [C_in, C_out, D, H, W] paddle filter layout
    jpad = [(dilations[i] * (w.shape[2 + i] - 1) - pads[i],) * 2
            for i in range(3)]
    out = jax.lax.conv_transpose(
        x, w, strides=strides,
        padding=jpad,
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        transpose_kernel=True)
    return {"Output": [out]}


def _pool_nd(x, attrs, ndim):
    """Shared max/avg window pooling over the `ndim` spatial dims
    (pool_op.cc pool2d/pool3d common path).  data_format NCHW (spatial dims
    trailing) or NHWC (channels trailing)."""
    import jax
    import jax.numpy as jnp

    tup = _pair if ndim == 2 else _triple
    nhwc = str(attrs.get("data_format", "NCHW")) in ("NHWC", "NDHWC")
    ptype = attrs.get("pooling_type", "max")
    ksize = tup(attrs.get("ksize", [2] * ndim))
    strides = tup(attrs.get("strides", ksize))
    pads = tup(attrs.get("paddings", [0] * ndim))
    if attrs.get("global_pooling", False):
        ksize = list(x.shape[1:-1] if nhwc else x.shape[2:])
        strides = ksize
        pads = [0] * ndim
    if nhwc:
        window = (1,) + tuple(ksize) + (1,)
        stridesn = (1,) + tuple(strides) + (1,)
        padding = ((0, 0),) + tuple((p, p) for p in pads) + ((0, 0),)
    else:
        window = (1, 1) + tuple(ksize)
        stridesn = (1, 1) + tuple(strides)
        padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    if ptype == "max":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                     stridesn, padding)
    out = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, stridesn,
                                padding)
    if attrs.get("exclusive", True) and any(pads):
        cnt = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                    window, stridesn, padding)
        return out / cnt
    denom = 1
    for k in ksize:
        denom *= k
    return out / denom


@register_op("pool3d")
def pool3d(ctx, ins, attrs):
    """Reference pool_op.cc:298 pool3d (max/avg over NCDHW windows)."""
    return {"Out": [_pool_nd(ins["X"][0], attrs, 3)]}


@register_op("pool2d")
def pool2d(ctx, ins, attrs):
    """Reference pool_op.cc pool2d — shares _pool_nd with pool3d."""
    return {"Out": [_pool_nd(ins["X"][0], attrs, 2)]}


@register_op("batch_norm", non_diff_outputs=("MeanOut", "VarianceOut"))
def batch_norm(ctx, ins, attrs):
    # SavedMean/SavedVariance are DIFFABLE (they're pure functions of X in
    # train mode): an op that read them would send its cotangents through
    # them back into dX.  The layers leave the saved vars stop_gradient.
    """Reference batch_norm_op.cc. Train mode: batch stats + running-stat
    update (MeanOut/VarianceOut alias the Mean/Variance state vars, persisted
    by the executor's written-state logic). Test mode: running stats."""
    import jax.numpy as jnp

    x = ins["X"][0]  # NCHW or NC
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = float(attrs.get("epsilon", 1e-5))
    momentum = float(attrs.get("momentum", 0.9))
    is_test = bool(attrs.get("is_test", False)) or ctx.is_test

    fmt = str(attrs.get("data_layout", attrs.get("data_format", "NCHW")))
    ch = x.ndim - 1 if fmt in ("NHWC", "NDHWC", "NLC") else 1
    axes = tuple(i for i in range(x.ndim) if i != ch)
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    # stats dtype: f32 for stability under bf16/f16, but f64 inputs keep
    # f64 (a hard f32 cast would silently truncate double-precision runs)
    sdt = jnp.float64 if x.dtype == jnp.float64 else jnp.float32

    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean, saved_var = mean, var
    else:
        xs = x.astype(sdt)
        use_mean = jnp.mean(xs, axis=axes)
        use_var = jnp.var(xs, axis=axes)
        mean_out = momentum * mean + (1 - momentum) * use_mean.astype(mean.dtype)
        var_out = momentum * var + (1 - momentum) * use_var.astype(var.dtype)
        saved_mean, saved_var = use_mean, use_var

    inv = 1.0 / jnp.sqrt(use_var.astype(sdt) + eps)
    xhat = (x.astype(sdt) - use_mean.reshape(shape)) * inv.reshape(shape)
    y = (xhat * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_op("layer_norm")
def layer_norm(ctx, ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]
    eps = float(attrs.get("epsilon", 1e-5))
    begin = int(attrs.get("begin_norm_axis", 1))
    axes = tuple(range(begin, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    xhat = (x - mean) / jnp.sqrt(var + eps)
    y = xhat
    if ins.get("Scale") and ins["Scale"][0] is not None:
        y = y * ins["Scale"][0]
    if ins.get("Bias") and ins["Bias"][0] is not None:
        y = y + ins["Bias"][0]
    return {"Y": [y], "Mean": [mean.reshape(-1)], "Variance": [var.reshape(-1)]}


@register_op("dropout", non_diff_outputs=("Mask",))
def dropout(ctx, ins, attrs):
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    p = float(attrs.get("dropout_prob", 0.5))
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if bool(attrs.get("is_test", False)) or ctx.is_test or p == 0.0:
        # reference dropout_op.h:60: downgrade_in_infer scales by (1-p) at
        # inference; upscale_in_train is identity at inference
        out = x if (impl == "upscale_in_train" or p == 0.0) else x * (1.0 - p)
        return {"Out": [out], "Mask": [jnp.ones_like(x)]}
    key = ctx.rng(attrs)
    mask = (jax.random.uniform(key, x.shape) >= p).astype(x.dtype)
    if impl == "upscale_in_train":
        out = x * mask / (1.0 - p)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


@register_op("lrn")
def lrn(ctx, ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]  # NCHW
    n = int(attrs.get("n", 5))
    k = float(attrs.get("k", 2.0))
    alpha = float(attrs.get("alpha", 1e-4))
    beta = float(attrs.get("beta", 0.75))
    half = n // 2
    sq = x * x
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i : i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / mid**beta], "MidOut": [mid]}


@register_op("im2sequence")
def im2sequence(ctx, ins, attrs):
    import jax

    x = ins["X"][0]
    kernels = _pair(attrs["kernels"])
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = attrs.get("paddings", [0, 0, 0, 0])
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=kernels, window_strides=strides,
        padding=[(pads[0], pads[2] if len(pads) > 2 else pads[0]),
                 (pads[1], pads[3] if len(pads) > 3 else pads[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, ck, oh, ow = patches.shape
    return {"Out": [patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, ck)]}


@register_op("max_pool2d_with_index", non_diff_outputs=("Mask",))
def max_pool2d_with_index(ctx, ins, attrs):
    """Max pool that also returns the flat h*W+w argmax per window
    (reference pool_with_index_op.cc) — the index input of `unpool`."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]  # NCHW
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", ksize))
    pads = _pair(attrs.get("paddings", [0, 0]))
    N, C, H, W = x.shape
    pad_cfg = [(pads[0], pads[0]), (pads[1], pads[1])]
    neg = jnp.finfo(x.dtype).min

    def patches(a, fill):
        a = jnp.pad(a, ((0, 0), (0, 0), pad_cfg[0], pad_cfg[1]),
                    constant_values=fill)
        p = jax.lax.conv_general_dilated_patches(
            a, filter_shape=ksize, window_strides=strides,
            padding=[(0, 0), (0, 0)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        n, _, oh, ow = p.shape
        return p.reshape(n, a.shape[1], ksize[0] * ksize[1], oh, ow)

    # flat output-space index of every input pixel, broadcast over N and C.
    # Indices ride through the float patch extractor in float32 (exact up to
    # 2^24) — never in x.dtype, which may be bfloat16
    flat = (jnp.arange(H)[:, None] * W
            + jnp.arange(W)[None, :]).astype(jnp.float32)
    xp = patches(x, neg)
    ip = patches(jnp.broadcast_to(flat, (N, C, H, W)), -1.0)
    arg = jnp.argmax(xp, axis=2)
    out = jnp.max(xp, axis=2)
    idx = jnp.take_along_axis(ip, arg[:, :, None], axis=2)[:, :, 0]
    return {"Out": [out], "Mask": [idx.astype(jnp.int32)]}


@register_op("bilinear_interp")
def bilinear_interp(ctx, ins, attrs):
    """Bilinear up/down-sampling of NCHW feature maps with align-corners
    ratios (reference gserver/layers/BilinearInterpLayer.cpp: ratio =
    (in-1)/(out-1))."""
    import jax.numpy as jnp

    x = ins["X"][0]
    out_h, out_w = int(attrs["out_h"]), int(attrs["out_w"])
    N, C, H, W = x.shape

    def axis_coords(out_n, in_n):
        r = (in_n - 1) / (out_n - 1) if out_n > 1 else 0.0
        pos = jnp.arange(out_n, dtype=jnp.float32) * r
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, in_n - 1)
        frac = pos - lo.astype(jnp.float32)
        return lo, hi, frac

    h0, h1, fh = axis_coords(out_h, H)
    w0, w1, fw = axis_coords(out_w, W)
    f32 = x.astype(jnp.float32)
    top = f32[:, :, h0, :]
    bot = f32[:, :, h1, :]
    row = top * (1 - fh)[None, None, :, None] + bot * fh[None, None, :, None]
    left = row[:, :, :, w0]
    right = row[:, :, :, w1]
    out = left * (1 - fw)[None, None, None, :] + right * fw[None, None, None, :]
    return {"Out": [out.astype(x.dtype)]}


@register_op("scale_sub_region", non_diff_inputs=("Indices",))
def scale_sub_region(ctx, ins, attrs):
    """Multiply a per-sample CHW sub-box by a constant (reference
    ScaleSubRegionLayer; indices are 1-based inclusive [cs,ce,hs,he,ws,we]
    rows of shape [N,6])."""
    import jax.numpy as jnp

    x = ins["X"][0]  # [N,C,H,W]
    idx = ins["Indices"][0].astype(jnp.int32)  # [N,6]
    value = float(attrs.get("value", 1.0))
    N, C, H, W = x.shape

    def rng_mask(n, lo, hi):  # 1-based inclusive box bounds -> bool [N, n]
        pos = jnp.arange(n)[None, :]
        return (pos >= (lo - 1)[:, None]) & (pos <= (hi - 1)[:, None])

    m = (rng_mask(C, idx[:, 0], idx[:, 1])[:, :, None, None]
         & rng_mask(H, idx[:, 2], idx[:, 3])[:, None, :, None]
         & rng_mask(W, idx[:, 4], idx[:, 5])[:, None, None, :])
    return {"Out": [jnp.where(m, x * value, x)]}


@register_op("max_pool3d_with_index", non_diff_outputs=("Mask",))
def max_pool3d_with_index(ctx, ins, attrs):
    """3-D max pool returning flat d*H*W+h*W+w argmax per window (reference
    pool_with_index_op.cc:277 max_pool3d_with_index) — shares the
    float-index-patches trick with the 2-D variant."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]  # NCDHW
    ksize = _triple(attrs.get("ksize", [2, 2, 2]))
    strides = _triple(attrs.get("strides", ksize))
    pads = _triple(attrs.get("paddings", [0, 0, 0]))
    N, C, D, H, W = x.shape
    neg = jnp.finfo(x.dtype).min

    def patches(a, fill):
        a = jnp.pad(a, ((0, 0), (0, 0)) + tuple((p, p) for p in pads),
                    constant_values=fill)
        p = jax.lax.conv_general_dilated_patches(
            a, filter_shape=ksize, window_strides=strides,
            padding=[(0, 0)] * 3,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
        n, _, od, oh, ow = p.shape
        return p.reshape(n, a.shape[1], ksize[0] * ksize[1] * ksize[2],
                         od, oh, ow)

    flat = (jnp.arange(D)[:, None, None] * (H * W)
            + jnp.arange(H)[None, :, None] * W
            + jnp.arange(W)[None, None, :]).astype(jnp.float32)
    xp = patches(x, neg)
    ip = patches(jnp.broadcast_to(flat, (N, C, D, H, W)), -1.0)
    arg = jnp.argmax(xp, axis=2)
    out = jnp.max(xp, axis=2)
    idx = jnp.take_along_axis(ip, arg[:, :, None], axis=2)[:, :, 0]
    return {"Out": [out], "Mask": [idx.astype(jnp.int32)]}


@register_op("unpool", non_diff_inputs=("Indices",))
def unpool(ctx, ins, attrs):
    """Max unpooling (reference unpool_op.cc): scatter each pooled value back
    to the position its `max_pool2d_with_index` Mask recorded."""
    import jax.numpy as jnp

    x = ins["X"][0]  # [N, C, h, w]
    idx = ins["Indices"][0]  # flat H*W positions, same shape
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", ksize))
    N, C, h, w = x.shape
    if attrs.get("output_size"):
        OH, OW = _pair(attrs["output_size"])
    else:
        OH, OW = (h - 1) * strides[0] + ksize[0], (w - 1) * strides[1] + ksize[1]
    vals = x.reshape(N * C, h * w)
    flat_idx = idx.reshape(N * C, h * w).astype(jnp.int32)
    out = jnp.zeros((N * C, OH * OW), x.dtype)
    # Mask is -1 for a window lying entirely in padding; a raw scatter would
    # wrap -1 to the last flat cell.  Negative indices wrap even under
    # mode='drop', so remap them past the end first, then drop.
    flat_idx = jnp.where(flat_idx < 0, OH * OW, flat_idx)
    out = out.at[jnp.arange(N * C)[:, None], flat_idx].set(
        vals, mode="drop")
    return {"Out": [out.reshape(N, C, OH, OW)]}


@register_op("spp")
def spp(ctx, ins, attrs):
    """Spatial pyramid pooling (reference spp_op.cc): pyramid_height levels of
    adaptive 2**l x 2**l pooling, flattened + concatenated — fixed-length
    output for any input HxW."""
    import jax.numpy as jnp

    x = ins["X"][0]  # NCHW
    levels = int(attrs.get("pyramid_height", 2))
    ptype = attrs.get("pooling_type", "max").lower()
    N, C, H, W = x.shape
    outs = []
    for lvl in range(levels):
        bins = 2 ** lvl
        rows = []
        for bi in range(bins):
            h0, h1 = (bi * H) // bins, max(((bi + 1) * H + bins - 1) // bins, (bi * H) // bins + 1)
            cols = []
            for bj in range(bins):
                w0, w1 = (bj * W) // bins, max(((bj + 1) * W + bins - 1) // bins, (bj * W) // bins + 1)
                cell = x[:, :, h0:h1, w0:w1]
                if ptype == "max":
                    cols.append(jnp.max(cell, axis=(2, 3)))
                else:
                    cols.append(jnp.mean(cell, axis=(2, 3)))
            rows.append(jnp.stack(cols, axis=-1))
        outs.append(jnp.stack(rows, axis=-2).reshape(N, C * bins * bins))
    return {"Out": [jnp.concatenate(outs, axis=1)]}


@register_op("conv_shift")
def conv_shift(ctx, ins, attrs):
    """Circular convolution (reference conv_shift_op.cc, NTM attention-shift):
    Out[b,i] = sum_j X[b,(i+j-N//2) mod M] * Y[b,j], Y width N odd, N<=M."""
    import jax.numpy as jnp

    x, y = ins["X"][0], ins["Y"][0]  # [B, M], [B, N]
    n = y.shape[1]
    half = n // 2
    out = sum(jnp.roll(x, half - j, axis=1) * y[:, j:j + 1] for j in range(n))
    return {"Out": [out]}


@register_op("bilinear_tensor_product")
def bilinear_tensor_product(ctx, ins, attrs):
    import jax.numpy as jnp

    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]  # w: [out, dx, dy]
    out = jnp.einsum("bi,oij,bj->bo", x, w, y)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        out = out + ins["Bias"][0]
    return {"Out": [out]}


@register_op("row_conv")
def row_conv(ctx, ins, attrs):
    """Lookahead row convolution over [batch, time, dim] (reference
    row_conv_op.cc operates on LoD; here the padded-batch form)."""
    import jax.numpy as jnp

    x = ins["X"][0]  # [B, T, D]
    w = ins["Filter"][0]  # [future_context+1, D]
    ctx_len = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (0, ctx_len - 1), (0, 0)))
    out = sum(pad[:, i : i + x.shape[1]] * w[i] for i in range(ctx_len))
    return {"Out": [out]}


@register_op("factorization_machine")
def factorization_machine(ctx, ins, attrs):
    """FM second-order interaction term (reference
    gserver/layers/FactorizationMachineLayer.cpp):
    0.5 * sum_k [ (x·V_k)^2 - (x^2)·(V_k^2) ] — two GEMMs on the MXU."""
    import jax.numpy as jnp

    x = ins["Input"][0]      # [B, D]
    v = ins["Factors"][0]    # [D, K] latent factors
    xv = x @ v               # [B, K]
    x2v2 = (x * x) @ (v * v)
    out = 0.5 * jnp.sum(xv * xv - x2v2, axis=1, keepdims=True)
    return {"Out": [out]}


@register_op("selective_fc", non_diff_inputs=("Mask",))
def selective_fc(ctx, ins, attrs):
    """SelectiveFullyConnectedLayer (reference
    gserver/layers/SelectiveFullyConnectedLayer.cpp): fc over a huge output
    dimension where only selected columns matter.  The reference skips the
    unselected columns' FLOPs on CPU; on TPU the full GEMM is one dense MXU
    pass and selection becomes a mask on the result — same contract
    (unselected outputs are 0 and carry no gradient), better hardware fit."""
    import jax.numpy as jnp

    x = ins["X"][0]          # [B, D]
    w = ins["W"][0]          # [D, C]
    out = x @ w
    if ins.get("Bias") and ins["Bias"][0] is not None:
        out = out + ins["Bias"][0].reshape(1, -1)
    if ins.get("Mask") and ins["Mask"][0] is not None:
        out = out * (ins["Mask"][0] != 0)
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# analytic cost formulas (analysis/cost.py; mechanism in registry.py)

from .registry import register_cost  # noqa: E402


def _conv_cost(ins, outs, attrs):
    """2 * out_elements * (kernel_spatial * C_in / groups) MACs-as-flops —
    the standard conv roofline numerator, any spatial rank.  Filter layout
    is OIHW(D) (transpose convs keep I first; the product is the same)."""
    w = ins.get("Filter", [None])[0]
    out = outs.get("Output", outs.get("Out", [None]))[0]
    if w is None or out is None or len(w.shape) < 3:
        return {}
    k_spatial = 1
    for s in w.shape[2:]:
        k_spatial *= s
    cin_per_group = w.shape[1]  # OIHW: dim 1 is already C_in/groups
    return {"flops": 2 * out.size * k_spatial * cin_per_group}


for _t in ("conv2d", "depthwise_conv2d", "conv2d_transpose", "conv3d",
           "conv3d_transpose"):
    register_cost(_t, _conv_cost)


# ---------------------------------------------------------------------------
# sharding-propagation rule (analysis/sharding.py; mechanism in registry)

from .registry import register_sharding  # noqa: E402


def _batch_norm_sharding(ctx, ins, outs, attrs):
    """Training-mode batch statistics are means over the (sharded)
    batch: GSPMD all-reduces the per-channel mean and variance over the
    batch axes.  Channel-shaped buffers stay replicated."""
    from ..mesh import entry_axes

    x = ins.get("X", [None])[0]
    y = outs.get("Y", [None])[0]
    if x is None or not x.spec:
        return {}
    batch_axes = tuple(a for a in entry_axes(x.spec[0])
                       if ctx.axis_size(a) > 1)
    mean = outs.get("SavedMean", [None])[0]
    if batch_axes and mean is not None and not attrs.get("is_test"):
        ctx.collective(
            "all-reduce", batch_axes, 2 * mean.global_bytes,
            var=mean.name,
            why="batch mean+variance over the sharded batch")
    out = {}
    if y is not None:
        out["Y"] = [tuple(x.spec)]
    return out


register_sharding("batch_norm", _batch_norm_sharding)
