"""Layer functions building ops into the default main program.

The TPU-native counterpart of fluid's python/paddle/v2/fluid/layers/nn.py
(fc:35, embedding, conv2d, pool2d, batch_norm, dropout...) — same contract
(append OpDescs + create params via LayerHelper), emitting ops this framework
lowers to XLA in one piece."""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Union

from ..framework.core import Variable
from ..framework.initializer import ConstantInitializer, NormalInitializer
from ..framework.layer_helper import LayerHelper


def data(name, shape, dtype="float32", lod_level=0, append_batch_size=True):
    """Declare an input (fluid layers/io.py data): prepends batch dim -1."""
    helper = LayerHelper("data")
    full_shape = ([-1] + list(shape)) if append_batch_size else list(shape)
    return helper.block.create_var(
        name=name,
        shape=full_shape,
        dtype=dtype,
        lod_level=lod_level,
        stop_gradient=True,
        is_data=True,
    )


def _shape_prod(shape):
    p = 1
    for s in shape:
        p *= int(s)
    return p


def fc(
    input: Union[Variable, Sequence[Variable]],
    size: int,
    num_flatten_dims: int = 1,
    param_attr=None,
    bias_attr=None,
    act: Optional[str] = None,
    name=None,
):
    """Fully connected (fluid nn.py:35): mul per input + sum + bias + act.
    Lowered, it is one fused XLA GEMM chain on the MXU."""
    helper = LayerHelper("fc", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for inp in inputs:
        in_dims = inp.shape[num_flatten_dims:]
        w = helper.create_parameter(
            attr=param_attr if isinstance(param_attr, dict) else {},
            shape=[_shape_prod(in_dims), size],
            dtype=inp.dtype,
        )
        out = helper.create_tmp_variable(
            inp.dtype, shape=tuple(inp.shape[:num_flatten_dims]) + (size,)
        )
        helper.append_op(
            "mul",
            inputs={"X": [inp.name], "Y": [w.name]},
            outputs={"Out": [out.name]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(out)
    if len(mul_results) == 1:
        pre = mul_results[0]
    else:
        pre = helper.create_tmp_variable(mul_results[0].dtype,
                                         shape=mul_results[0].shape)
        helper.append_op("sum", inputs={"X": [m.name for m in mul_results]},
                         outputs={"Out": [pre.name]})
    pre = helper.append_bias_op(pre, dim_start=num_flatten_dims)
    return helper.append_activation(pre)


def embedding(input, size, is_sparse=False, padding_idx=None, param_attr=None,
              dtype="float32"):
    """fluid nn.py embedding → lookup_table op. `is_sparse` kept for API
    parity; under XLA the grad is a scatter-add either way."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=list(size), dtype=dtype,
    )
    in_shape = tuple(input.shape[:-1]) if input.shape and input.shape[-1] == 1 \
        else tuple(input.shape or ())
    out = helper.create_tmp_variable(dtype, shape=in_shape + (size[1],))
    helper.append_op(
        "lookup_table",
        inputs={"W": [w.name], "Ids": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"is_sparse": bool(is_sparse),
               "padding_idx": -1 if padding_idx is None else int(padding_idx)},
    )
    return out


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
    data_format="NCHW",
):
    helper = LayerHelper("conv2d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    nhwc = data_format == "NHWC"
    num_channels = input.shape[3] if nhwc else input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) else (
        filter_size, filter_size)
    stride = stride if isinstance(stride, (list, tuple)) else (stride, stride)
    padding = padding if isinstance(padding, (list, tuple)) else (
        padding, padding)
    dilation = dilation if isinstance(dilation, (list, tuple)) else (
        dilation, dilation)
    w = helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=[num_filters, num_channels // groups, fs[0], fs[1]],
        dtype=input.dtype,
        default_initializer=NormalInitializer(
            0.0, (2.0 / (fs[0] * fs[1] * num_channels)) ** 0.5),
    )

    def _od(i, k, s, p, d):
        if i is None or i < 0:
            return -1
        ke = d * (k - 1) + 1
        return (i + 2 * p - ke) // s + 1

    h_ax, w_ax = (1, 2) if nhwc else (2, 3)
    oh = _od(input.shape[h_ax], fs[0], stride[0], padding[0], dilation[0])
    ow = _od(input.shape[w_ax], fs[1], stride[1], padding[1], dilation[1])
    oshape = ((input.shape[0], oh, ow, num_filters) if nhwc
              else (input.shape[0], num_filters, oh, ow))
    out = helper.create_tmp_variable(input.dtype, shape=oshape)
    helper.append_op(
        "conv2d",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [out.name]},
        attrs={"strides": list(stride), "paddings": list(padding),
               "dilations": list(dilation), "groups": groups,
               "data_format": data_format},
    )
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=bias_attr if isinstance(bias_attr, dict) else {},
            shape=[num_filters], dtype=input.dtype, is_bias=True)
        tmp = helper.create_tmp_variable(out.dtype, shape=out.shape)
        helper.append_op(
            "elementwise_add",
            inputs={"X": [out.name], "Y": [b.name]},
            outputs={"Out": [tmp.name]},
            attrs={"axis": 3 if nhwc else 1},
        )
        out = tmp
    return helper.append_activation(out)


def pool2d(input, pool_size=2, pool_type="max", pool_stride=None,
            pool_padding=0, global_pooling=False, ceil_mode=False, name=None,
            data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    nhwc = data_format == "NHWC"
    ps = pool_size if isinstance(pool_size, (list, tuple)) else (
        pool_size, pool_size)
    st = pool_stride or ps
    st = st if isinstance(st, (list, tuple)) else (st, st)
    pd = pool_padding if isinstance(pool_padding, (list, tuple)) else (
        pool_padding, pool_padding)

    def _od(i, k, s, p):
        if i is None or i < 0:
            return -1
        return (i + 2 * p - k) // s + 1

    h_ax, w_ax = (1, 2) if nhwc else (2, 3)
    if global_pooling:
        oh = ow = 1
    else:
        oh = _od(input.shape[h_ax], ps[0], st[0], pd[0])
        ow = _od(input.shape[w_ax], ps[1], st[1], pd[1])
    ch = input.shape[3] if nhwc else input.shape[1]
    oshape = ((input.shape[0], oh, ow, ch) if nhwc
              else (input.shape[0], ch, oh, ow))
    out = helper.create_tmp_variable(input.dtype, shape=oshape)
    helper.append_op(
        "pool2d",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"pooling_type": pool_type, "ksize": list(ps),
               "strides": list(st), "paddings": list(pd),
               "global_pooling": global_pooling,
               "data_format": data_format},
    )
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW", name=None):
    helper = LayerHelper("batch_norm", act=act, name=name)
    c = input.shape[-1] if data_layout == "NHWC" else input.shape[1]
    dtype = input.dtype
    scale = helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(
        attr=bias_attr if isinstance(bias_attr, dict) else {},
        shape=[c], dtype=dtype, is_bias=True)
    mean = helper.create_global_variable(shape=(c,), dtype=dtype)
    variance = helper.create_global_variable(shape=(c,), dtype=dtype)
    helper.set_initialized(mean, ConstantInitializer(0.0))
    helper.set_initialized(variance, ConstantInitializer(1.0))
    saved_mean = helper.create_tmp_variable(dtype, shape=(c,),
                                            stop_gradient=True)
    saved_var = helper.create_tmp_variable(dtype, shape=(c,),
                                           stop_gradient=True)
    out = helper.create_tmp_variable(dtype, shape=input.shape)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input.name], "Scale": [scale.name], "Bias": [bias.name],
                "Mean": [mean.name], "Variance": [variance.name]},
        outputs={"Y": [out.name], "MeanOut": [mean.name],
                 "VarianceOut": [variance.name],
                 "SavedMean": [saved_mean.name],
                 "SavedVariance": [saved_var.name]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout},
    )
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    mask = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                      stop_gradient=True)
    helper.append_op(
        "dropout",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "Mask": [mask.name]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "dropout_implementation": dropout_implementation},
    )
    return out


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", act=act, name=name)
    norm_shape = [_shape_prod(input.shape[begin_norm_axis:])]
    ins = {"X": [input.name]}
    if scale:
        s = helper.create_parameter(
            attr=param_attr if isinstance(param_attr, dict) else {},
            shape=norm_shape, dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))
        ins["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(
            attr=bias_attr if isinstance(bias_attr, dict) else {},
            shape=norm_shape, dtype=input.dtype, is_bias=True)
        ins["Bias"] = [b.name]
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    mean = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    var = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    helper.append_op(
        "layer_norm", inputs=ins,
        outputs={"Y": [out.name], "Mean": [mean.name], "Variance": [var.name]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def _rms_gain(helper, width, dtype, param_attr=None):
    """An RMSNorm's learned gain [width], starting at one."""
    return helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=[width], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))


def rms_norm(input, begin_norm_axis=1, epsilon=1e-5, param_attr=None,
             name=None, part=None):
    """RMSNorm (ops/llm_ops.py): input over sqrt(mean of its squares over
    the axes from `begin_norm_axis` + epsilon), times a learned gain that
    starts at one.  No mean subtracted, no bias.  `part` names the scope
    the op's instructions carry in a trace (`pdtpu.<part>`)."""
    helper = LayerHelper("rms_norm", name=name)
    gain = _rms_gain(helper, _shape_prod(input.shape[begin_norm_axis:]),
                     input.dtype, param_attr)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op(
        "rms_norm", inputs={"X": [input.name], "Scale": [gain.name]},
        outputs={"Y": [out.name]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis,
               **({"part": part} if part else {})})
    return out


# --- losses / metrics -------------------------------------------------------


def multi_head_attention(queries, keys, values, num_heads, causal=False,
                         param_attr=None, name=None, sp_mode="ring",
                         sp_schedule="plain", qk_norm_epsilon=None,
                         rope_theta=None, out_param_attr=None,
                         num_kv_heads=None, qk_norm_per_head=False,
                         head_dim=None, block_diffusion=None):
    """Transformer multi-head attention over [B, T, D] (beyond-reference:
    the 2018 reference's closest construct is v1 simple_attention).  QKV and
    output projections are fc ops (MXU GEMMs); the core runs
    scaled_dot_product_attention — sequence-parallel when the executor's
    mesh has an 'sp' axis, as ring attention (sp_mode='ring') or Ulysses
    all-to-all head re-sharding (sp_mode='alltoall').

    `qk_norm_epsilon` puts an RMSNorm with that epsilon on the whole Q and
    the whole K projection, before the split into heads (OLMoE's QK-norm);
    with `qk_norm_per_head` on each head's D / num_heads columns instead,
    after the split and before any rotation, with ONE gain of that width
    for all query heads and one for all key heads (LFM2's `q_layernorm`,
    `k_layernorm`).  `rope_theta` rotates Q and K per head by their
    position (rotate-half form) instead of relying on positions added to
    the input: ONE `head_norm_rope` op each takes Q and K from the
    projection's [B, T, heads * head_dim] to attention's [B, heads, T,
    head_dim], the per-head norm (where asked for) and the turn inside
    it.  `num_kv_heads` (default `num_heads`, and a divisor
    of it) is how many heads the K and V projections have: query head h
    attends to key/value head h // (num_heads / num_kv_heads)
    (grouped-query attention).  `head_dim` is a head's width where it is
    not D / num_heads: Q is then D -> num_heads * head_dim and the output
    projection num_heads * head_dim -> D.  `block_diffusion` = (seq_len L,
    block_length): the T = 2L rows are the noised and the clean copy of L
    tokens; they attend under the block-diffusion mask (`mask` attrs of
    scaled_dot_product_attention) and row r is rotated as position r mod L.
    `param_attr` is the Q, K and V projections', `out_param_attr` the
    output projection's.

    Where nothing per head stands between the projections and attention
    (no `rope_theta`, no `qk_norm_per_head`) the attention op takes Q, K
    and V as [B, T, heads * head_dim] (`layout` "bthd") and no `reshape`
    or `transpose` op is emitted on either side of it; else V's heads are
    split to [B, heads, T, head_dim] by a `reshape` and a `transpose` (Q's
    and K's too where they are not rotated) and the output merged after."""
    helper = LayerHelper("multi_head_attention", name=name)
    if sp_mode not in ("ring", "alltoall"):
        raise ValueError(f"sp_mode {sp_mode!r}: use 'ring' or 'alltoall'")
    if sp_schedule not in ("plain", "zigzag"):
        raise ValueError(
            f"sp_schedule {sp_schedule!r}: use 'plain' or 'zigzag' "
            "(zigzag = load-balanced causal flash ring, fwd and bwd)")
    D = queries.shape[-1]
    if head_dim is None:
        if D % num_heads:
            raise ValueError(
                f"multi_head_attention: num_heads {num_heads} does not "
                f"divide the hidden size {D}; give head_dim")
        head_dim = D // num_heads
    head_dim = int(head_dim)
    kv_heads = num_heads if num_kv_heads is None else int(num_kv_heads)
    if not 0 < kv_heads <= num_heads or num_heads % kv_heads:
        raise ValueError(f"multi_head_attention: num_kv_heads {kv_heads} "
                         f"does not divide num_heads {num_heads}")
    if qk_norm_per_head and qk_norm_epsilon is None:
        raise ValueError("multi_head_attention: qk_norm_per_head is a form "
                         "of the QK-norm: give qk_norm_epsilon")
    q = fc(queries, num_heads * head_dim, num_flatten_dims=2,
           param_attr=param_attr, bias_attr=False)
    k = fc(keys, kv_heads * head_dim, num_flatten_dims=2,
           param_attr=param_attr, bias_attr=False)
    v = fc(values, kv_heads * head_dim, num_flatten_dims=2,
           param_attr=param_attr, bias_attr=False)
    qk_norm = functools.partial(rms_norm, epsilon=qk_norm_epsilon,
                                part="attn.qk_norm")
    if qk_norm_epsilon is not None and not qk_norm_per_head:
        q, k = qk_norm(q, begin_norm_axis=2), qk_norm(k, begin_norm_axis=2)

    def split_heads(x, heads):
        r = helper.create_tmp_variable(x.dtype)
        helper.append_op("reshape", inputs={"X": [x.name]},
                         outputs={"Out": [r.name]},
                         attrs={"shape": [0, 0, heads, head_dim]})
        t = helper.create_tmp_variable(
            x.dtype, shape=(x.shape[0], heads, x.shape[1], head_dim))
        helper.append_op("transpose", inputs={"X": [r.name]},
                         outputs={"Out": [t.name]},
                         attrs={"axis": [0, 2, 1, 3]})
        return t

    def prepared(x, heads):
        """Q or K [B, T, heads * head_dim] -> [B, heads, T, head_dim],
        normed per head (where asked for) and rotated, by one op."""
        ins, attrs = {"X": [x.name]}, {
            "num_heads": heads, "theta": float(rope_theta),
            "part": "attn.qk_prep"}
        if qk_norm_per_head:
            # named as the `rms_norm` layer's gain it was: saved models and
            # every later `rms_norm` keep their parameters' names
            gain = _rms_gain(LayerHelper("rms_norm"), head_dim, x.dtype)
            ins["Scale"] = [gain.name]
            attrs["epsilon"] = qk_norm_epsilon
        if block_diffusion:
            attrs["period"] = int(block_diffusion[0])
        r = helper.create_tmp_variable(
            x.dtype, shape=(x.shape[0], heads, x.shape[1], head_dim))
        helper.append_op("head_norm_rope", inputs=ins,
                         outputs={"Out": [r.name]}, attrs=attrs)
        return r

    wide = tuple(queries.shape[:-1]) + (num_heads * head_dim,)
    sdpa_attrs = {"causal": causal, "sp_mode": sp_mode,
                  "sp_schedule": sp_schedule}
    if block_diffusion:
        sdpa_attrs.update({"mask": "block_diffusion",
                           "seq_len": int(block_diffusion[0]),
                           "block_length": int(block_diffusion[1])})
    if rope_theta is None and not qk_norm_per_head:
        # nothing per head stands between the projections and attention:
        # the op takes Q, K, V as they lie and leaves its output as the
        # output projection reads it; no reshape, no transpose
        merged = helper.create_tmp_variable(queries.dtype, shape=wide)
        helper.append_op(
            "scaled_dot_product_attention",
            inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
            outputs={"Out": [merged.name]},
            attrs={**sdpa_attrs, "layout": "bthd", "num_heads": num_heads,
                   "num_kv_heads": kv_heads})
    else:
        if rope_theta is not None:
            qh, kh, vh = (prepared(q, num_heads), prepared(k, kv_heads),
                          split_heads(v, kv_heads))
        else:  # a per-head norm and no turn
            qh, kh, vh = (split_heads(q, num_heads),
                          split_heads(k, kv_heads), split_heads(v, kv_heads))
            qh, kh = (qk_norm(qh, begin_norm_axis=3),
                      qk_norm(kh, begin_norm_axis=3))
        attn = helper.create_tmp_variable(queries.dtype)
        helper.append_op(
            "scaled_dot_product_attention",
            inputs={"Q": [qh.name], "K": [kh.name], "V": [vh.name]},
            outputs={"Out": [attn.name]}, attrs=sdpa_attrs)
        back = helper.create_tmp_variable(queries.dtype)
        helper.append_op("transpose", inputs={"X": [attn.name]},
                         outputs={"Out": [back.name]},
                         attrs={"axis": [0, 2, 1, 3]})
        merged = helper.create_tmp_variable(queries.dtype, shape=wide)
        helper.append_op("reshape", inputs={"X": [back.name]},
                         outputs={"Out": [merged.name]},
                         attrs={"shape": [0, 0, num_heads * head_dim]})
    out = fc(merged, D, num_flatten_dims=2, param_attr=out_param_attr,
             bias_attr=False)
    from .sequence import propagate_length

    return propagate_length(queries, out)


def block_diffusion_noise(tokens, token_noise, block_noise, block_length,
                          mask_id, t_min=0.0, name=None):
    """The input of a block-diffusion training step (ops/llm_ops.py
    `block_diffusion_noise` has the equations): `tokens` [B, L, 1] clean,
    `token_noise` [B, L, 1] and `block_noise` [B, L / block_length, 1]
    uniform draws that are FED -> ([noisy ; clean] [B, 2L, 1], the mask m
    [B, L, 1] float32, the loss weights m / t [B, L, 1] float32)."""
    helper = LayerHelper("block_diffusion_noise", name=name)
    B, L = tokens.shape[0], tokens.shape[1]
    out = helper.create_tmp_variable(tokens.dtype, shape=(B, 2 * L, 1),
                                     stop_gradient=True)
    mask, weight = (helper.create_tmp_variable(
        "float32", shape=(B, L, 1), stop_gradient=True) for _ in range(2))
    helper.append_op(
        "block_diffusion_noise",
        inputs={"Tokens": [tokens.name], "TokenNoise": [token_noise.name],
                "BlockNoise": [block_noise.name]},
        outputs={"Out": [out.name], "Mask": [mask.name],
                 "Weight": [weight.name]},
        attrs={"block_length": int(block_length), "mask_id": int(mask_id),
               "t_min": float(t_min)})
    return out, mask, weight


def gated_short_conv(input, kernel_size=3, param_attr=None, name=None):
    """LFM2's gated short convolution over [B, T, D] (ops/llm_ops.py
    `gated_short_conv` has the equations): an input projection to three
    thirds B, C, u, a causal depthwise convolution of `kernel_size` taps
    over B * u, gated by C, and an output projection.  The two
    projections are `fc` ops (MXU GEMMs).  Three parameters, in creation
    order: W_in [D, 3D], the taps [D, kernel_size], W_out [D, D]; no
    bias."""
    helper = LayerHelper("gated_short_conv", name=name)
    D = input.shape[-1]
    bcu = fc(input, 3 * D, num_flatten_dims=2, param_attr=param_attr,
             bias_attr=False)
    taps = helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=[D, int(kernel_size)], dtype=input.dtype)
    gated = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op(
        "gated_short_conv", inputs={"X": [bcu.name], "Filter": [taps.name]},
        outputs={"Out": [gated.name]}, attrs={})
    out = fc(gated, D, num_flatten_dims=2, param_attr=param_attr,
             bias_attr=False)
    from .sequence import propagate_length

    return propagate_length(input, out)


def latent_attention(input, num_heads, kv_rank, qk_nope_dim, qk_rope_dim,
                     v_dim, rope_theta=10000.0, epsilon=1e-5,
                     param_attr=None, name=None, q_rank=None, yarn=None):
    """Causal multi-head latent attention over [B, T, D] (DeepSeek-V2's
    MLA; ops/llm_ops.py latent_attention has the equations): keys and
    values come from a latent of `kv_rank` columns with an RMSNorm of its
    own, one rotary key of `qk_rope_dim` columns is shared by all heads,
    queries and keys are `qk_nope_dim` + `qk_rope_dim` wide and values
    `v_dim`.  Five parameters, in creation order: WQ, WKVA, the latent
    norm's gain, WKVB, WO; no bias.  With `q_rank` the queries come from a
    latent of that many columns with an RMSNorm of its own (DeepSeek-V3's
    `q_lora_rank`): WQA, its gain and WQB stand where WQ stood, seven
    parameters.  `yarn` = {"factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} (a published
    `rope_scaling` of type yarn) blends the rotary frequencies and sets
    the softmax scale."""
    helper = LayerHelper("latent_attention", name=name)
    D = input.shape[-1]
    attr = param_attr if isinstance(param_attr, dict) else {}

    def weight(shape):
        return helper.create_parameter(attr=attr, shape=shape,
                                       dtype=input.dtype)

    def gain(width):
        return helper.create_parameter(
            attr={}, shape=[width], dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))

    q_width = num_heads * (qk_nope_dim + qk_rope_dim)
    ins = {"X": [input.name]}
    if q_rank:
        ins["WQA"] = [weight([D, q_rank]).name]
        ins["QNorm"] = [gain(q_rank).name]
        ins["WQB"] = [weight([q_rank, q_width]).name]
    else:
        ins["WQ"] = [weight([D, q_width]).name]
    ins["WKVA"] = [weight([D, kv_rank + qk_rope_dim]).name]
    ins["KVNorm"] = [gain(kv_rank).name]
    ins["WKVB"] = [weight([kv_rank, num_heads * (qk_nope_dim + v_dim)]).name]
    ins["WO"] = [weight([num_heads * v_dim, D]).name]
    attrs = {"num_heads": int(num_heads), "qk_nope_dim": int(qk_nope_dim),
             "qk_rope_dim": int(qk_rope_dim), "v_dim": int(v_dim),
             "theta": float(rope_theta), "epsilon": float(epsilon)}
    if yarn:
        attrs.update(
            yarn_factor=float(yarn["factor"]),
            yarn_original_max=int(yarn["original_max_position_embeddings"]),
            yarn_beta_fast=float(yarn.get("beta_fast", 32)),
            yarn_beta_slow=float(yarn.get("beta_slow", 1)),
            yarn_mscale=float(yarn.get("mscale", 1)),
            yarn_mscale_all_dim=float(yarn.get("mscale_all_dim", 0)))
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("latent_attention", inputs=ins,
                     outputs={"Out": [out.name]}, attrs=attrs)
    from .sequence import propagate_length

    return propagate_length(input, out)


def mtp_project(hidden, next_embedding, epsilon=1e-5, param_attr=None,
                name=None):
    """A multi-token-prediction module's way in (DeepSeek-V3,
    arXiv:2412.19437, section 2.2; ops/llm_ops.py mtp_project): W
    [RMSNorm(hidden) ; RMSNorm(next_embedding)] over [B, T, D] each -> [B,
    T, D].  Three parameters, in creation order: the two norms' gains and
    W [2 D, D] (`param_attr`); no bias."""
    helper = LayerHelper("mtp_project", name=name)
    D = hidden.shape[-1]
    gains = [helper.create_parameter(
        attr={}, shape=[D], dtype=hidden.dtype,
        default_initializer=ConstantInitializer(1.0)) for _ in range(2)]
    w = helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=[2 * D, D], dtype=hidden.dtype)
    out = helper.create_tmp_variable(hidden.dtype, shape=hidden.shape)
    helper.append_op(
        "mtp_project",
        inputs={"H": [hidden.name], "E": [next_embedding.name],
                "HNorm": [gains[0].name], "ENorm": [gains[1].name],
                "W": [w.name]},
        outputs={"Out": [out.name]},
        attrs={"epsilon": float(epsilon), "depth": 1})
    return out


def hyper_connection_pre(streams, sinkhorn_iters=20, epsilon=1e-6,
                         norm_epsilon=1e-6, clamp=(-30.0, 30.0),
                         param_attr=None, alpha_attr=None, beta_attr=None,
                         name=None):
    """What one sub-layer reads of the n residual streams [B, n, T, C]
    (manifold-constrained hyper-connections; ops/llm_ops.py
    hyper_connection_pre has the equations) -> (u [B, T, C], the
    sub-layer's input before its own norm; h_post, h_res: what
    `hyper_connection_post` writes its result back through).  Five
    parameters of the sub-layer's own, in creation order: PhiPre, PhiPost
    [n C, n], PhiRes [n C, n n] (`param_attr`), Alpha [3] (`alpha_attr`;
    mHC's 0.01 by default) and Beta [n + n + n n] (`beta_attr`; zeros).
    The op also leaves the raw projection and the norm's factor (Proj,
    Inv: small float32 tensors) for its grad op.  On one TPU the passes
    over the streams of this op, of `hyper_connection_post` and of their
    grad ops are Pallas kernels (C in 128s, T in whole token tiles);
    everywhere else plain jax.numpy."""
    helper = LayerHelper("hyper_connection", name=name)
    B, n, T, C = streams.shape

    def param(attr, shape, default=None):
        return helper.create_parameter(
            attr=attr if isinstance(attr, dict) else {}, shape=shape,
            dtype=streams.dtype, default_initializer=default)

    ins = {"X": [streams.name]}
    for slot, cols in (("PhiPre", n), ("PhiPost", n), ("PhiRes", n * n)):
        ins[slot] = [param(param_attr, [n * C, cols]).name]
    ins["Alpha"] = [param(alpha_attr, [3], ConstantInitializer(0.01)).name]
    ins["Beta"] = [param(beta_attr, [(2 + n) * n],
                         ConstantInitializer(0.0)).name]
    u = helper.create_tmp_variable(streams.dtype, shape=(B, T, C))
    h_post = helper.create_tmp_variable("float32", shape=(B, T, n))
    h_res = helper.create_tmp_variable("float32", shape=(B, T, n, n))
    kept = [helper.create_tmp_variable("float32", shape=shape,
                                       stop_gradient=True)
            for shape in (((2 + n) * n, B, T), (B, T))]
    helper.append_op(
        "hyper_connection_pre", inputs=ins,
        outputs={"U": [u.name], "HPost": [h_post.name],
                 "HRes": [h_res.name], "Proj": [kept[0].name],
                 "Inv": [kept[1].name]},
        attrs={"streams": int(n), "sinkhorn_iters": int(sinkhorn_iters),
               "epsilon": float(epsilon),
               "norm_epsilon": float(norm_epsilon),
               "clamp_min": float(clamp[0]), "clamp_max": float(clamp[1])})
    return u, h_post, h_res


def hyper_connection_post(streams, y, h_post, h_res, name=None):
    """The sub-layer's result `y` [B, T, C] written back into the streams
    [B, n, T, C]: stream i = sum_j h_res[i, j] stream j + h_post[i] y
    (ops/llm_ops.py hyper_connection_post; one Pallas kernel where
    `hyper_connection_pre` takes its own)."""
    helper = LayerHelper("hyper_connection", name=name)
    out = helper.create_tmp_variable(streams.dtype, shape=streams.shape)
    helper.append_op(
        "hyper_connection_post",
        inputs={"X": [streams.name], "Y": [y.name], "HPost": [h_post.name],
                "HRes": [h_res.name]},
        outputs={"Out": [out.name]})
    return out


def hyper_connection_streams(x, n, name=None):
    """Where the streams start: `n` copies of x [B, T, C], stream by
    stream [B, n, T, C] (hyper-connections' own, arXiv:2409.19606)."""
    helper = LayerHelper("hyper_connection", name=name)
    B, T, C = x.shape
    one = helper.create_tmp_variable(x.dtype, shape=(B, 1, T, C))
    helper.append_op("unsqueeze", inputs={"X": [x.name]},
                     outputs={"Out": [one.name]}, attrs={"axes": [1]})
    out = helper.create_tmp_variable(x.dtype, shape=(B, n, T, C))
    helper.append_op("expand", inputs={"X": [one.name]},
                     outputs={"Out": [out.name]},
                     attrs={"expand_times": [1, n, 1, 1]})
    return out


def hyper_connection_sum(streams, name=None):
    """Where the streams end: their sum [B, T, C]."""
    helper = LayerHelper("hyper_connection", name=name)
    B, _, T, C = streams.shape
    out = helper.create_tmp_variable(streams.dtype, shape=(B, T, C))
    helper.append_op("hyper_connection_sum", inputs={"X": [streams.name]},
                     outputs={"Out": [out.name]})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        "matmul",
        inputs={"X": [x.name], "Y": [y.name]},
        outputs={"Out": [out.name]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": alpha},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    minus_out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("elementwise_sub",
                     inputs={"X": [input.name], "Y": [label.name]},
                     outputs={"Out": [minus_out.name]}, attrs={"axis": -1})
    sq = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("square", inputs={"X": [minus_out.name]},
                     outputs={"Out": [sq.name]})
    return sq


def cross_entropy(input, label, soft_label=False):
    helper = LayerHelper("cross_entropy")
    out = helper.create_tmp_variable(
        input.dtype, shape=tuple(input.shape[:-1]) + (1,))
    helper.append_op(
        "cross_entropy",
        inputs={"X": [input.name], "Label": [label.name]},
        outputs={"Y": [out.name]},
        attrs={"soft_label": soft_label},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax = helper.create_tmp_variable(logits.dtype, shape=logits.shape)
    loss = helper.create_tmp_variable(
        logits.dtype, shape=tuple(logits.shape[:-1]) + (1,))
    helper.append_op(
        "softmax_with_cross_entropy",
        inputs={"Logits": [logits.name], "Label": [label.name]},
        outputs={"Loss": [loss.name], "Softmax": [softmax.name]},
        attrs={"soft_label": soft_label},
    )
    return loss


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_tmp_variable(x.dtype, shape=(1,))
    helper.append_op("mean", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]})
    return out


def softmax(input, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("softmax", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]})
    return out


def topk(input, k):
    helper = LayerHelper("top_k")
    values = helper.create_tmp_variable(
        input.dtype, shape=tuple(input.shape[:-1]) + (k,), stop_gradient=True)
    indices = helper.create_tmp_variable(
        "int64", shape=tuple(input.shape[:-1]) + (k,), stop_gradient=True)
    helper.append_op("top_k", inputs={"X": [input.name]},
                     outputs={"Out": [values.name], "Indices": [indices.name]},
                     attrs={"k": k})
    return values, indices


def beam_search(pre_ids, pre_scores, cand_ids, cand_scores, beam_size,
                end_id, is_accumulated=True, name=None):
    """One composable beam step (reference beam_search_op.h:96; fluid
    layers.beam_search), usable inside a While body around ANY user
    decoder: see ops/beam_ops.py for semantics.  Returns
    (selected_ids [B,K], selected_scores [B,K], parent_idx [B,K])."""
    helper = LayerHelper("beam_search", name=name)
    B, K = pre_ids.shape[0], int(beam_size)
    sel_ids = helper.create_tmp_variable(pre_ids.dtype, shape=(B, K),
                                         stop_gradient=True)
    sel_scores = helper.create_tmp_variable("float32", shape=(B, K),
                                            stop_gradient=True)
    parent = helper.create_tmp_variable("int32", shape=(B, K),
                                        stop_gradient=True)
    helper.append_op(
        "beam_search",
        inputs={"PreIds": [pre_ids.name], "PreScores": [pre_scores.name],
                "Ids": [cand_ids.name], "Scores": [cand_scores.name]},
        outputs={"SelectedIds": [sel_ids.name],
                 "SelectedScores": [sel_scores.name],
                 "ParentIdx": [parent.name]},
        attrs={"beam_size": int(beam_size), "end_id": int(end_id),
               "is_accumulated": bool(is_accumulated)})
    return sel_ids, sel_scores, parent


def beam_search_decode(ids, parent_idx, scores, end_id, step_count=None,
                       name=None):
    """Backtrack per-step beam selections into sentences (reference
    beam_search_decode_op.cc:41; fluid layers.beam_search_decode).  `ids`
    and `parent_idx` are the [L, B, K] arrays filled by array_write inside
    the generation loop.  Returns (sentence_ids [B,K,L],
    sentence_scores [B,K], sentence_length [B,K])."""
    helper = LayerHelper("beam_search_decode", name=name)
    L, B, K = ids.shape
    sent = helper.create_tmp_variable(ids.dtype, shape=(B, K, L),
                                      stop_gradient=True)
    sscores = helper.create_tmp_variable("float32", shape=(B, K),
                                         stop_gradient=True)
    slen = helper.create_tmp_variable("int32", shape=(B, K),
                                      stop_gradient=True)
    inputs = {"Ids": [ids.name], "ParentIdx": [parent_idx.name],
              "Scores": [scores.name]}
    if step_count is not None:
        inputs["StepCount"] = [step_count.name]
    helper.append_op(
        "beam_search_decode", inputs=inputs,
        outputs={"SentenceIds": [sent.name],
                 "SentenceScores": [sscores.name],
                 "SentenceLength": [slen.name]},
        attrs={"end_id": int(end_id)})
    return sent, sscores, slen


def accuracy(input, label, k=1):
    helper = LayerHelper("accuracy")
    _, indices = topk(input, k)
    acc = helper.create_tmp_variable("float32", shape=(1,),
                                     stop_gradient=True)
    correct = helper.create_tmp_variable("int64", shape=(1,),
                                         stop_gradient=True)
    total = helper.create_tmp_variable("int64", shape=(1,),
                                       stop_gradient=True)
    helper.append_op(
        "accuracy",
        inputs={"Indices": [indices.name], "Label": [label.name]},
        outputs={"Accuracy": [acc.name], "Correct": [correct.name],
                 "Total": [total.name]},
    )
    return acc


def auc(input, label):
    helper = LayerHelper("auc")
    out = helper.create_tmp_variable("float32", shape=(1,), stop_gradient=True)
    helper.append_op("auc",
                     inputs={"Predict": [input.name], "Label": [label.name]},
                     outputs={"AUC": [out.name]})
    return out


class MoeShare(NamedTuple):
    """What `moe` returns for a share of an expert layer (`held`)."""

    out: Variable             # [N, D]: the held experts' part + the shared one
    scores: Variable          # [N, E] float32 router scores
    weights: Variable         # [N, top_k] float32: each token's top-k weights
    counts: Variable          # [E]: pairs each of ALL E experts was chosen for
    held_pairs: Variable      # [1]: the pairs on held experts
    dropped_pairs: Variable   # [1]: those of them the buffer had no row for
    bias: Optional[Variable]  # [E] selection bias (no gradient), or None


def moe(input, num_experts, d_hidden, capacity_factor=1.0, act="relu",
        param_attr=None, name=None, top_k=1, gated=False, dropless=False,
        initializer=None, held=None, scoring="softmax", select_bias=None,
        renormalise=False, routed_scale=1.0, buffer_rows=None,
        shared_hidden=0, renorm_epsilon=None):
    """Mixture-of-experts FFN layer (beyond-reference — SURVEY.md §2.16 last
    row).  `input` [N, D] tokens -> [N, D].  Expert weights are stacked
    [E, D, H]/[E, H, D]; under a ParallelExecutor whose mesh has an 'ep'
    axis they are sharded one-expert-per-member and tokens ride
    `all_to_all` (ops/moe_ops.py).

    `dropless=True` is the fine-grained form: the `top_k` largest router
    probabilities a token, nothing dropped, `gated` experts `WO(act(WI x)
    * (WU x))`; it returns (out, router_logits [N, E] float32, counts
    [E]), the last two for `moe_router_loss`.

    `held=(first, n)` makes the layer ONE CHIP'S SHARE of a dropless layer
    whose experts are spread over chips: the router keeps `num_experts`
    outputs and `top_k` a token, the stacked weights hold the experts
    [first, first + n) only, and the result is their part of the layer's
    sum (-> `MoeShare`).  With it come DeepSeek-V3's router (`scoring`
    'sigmoid', `select_bias`: an initializer for a bias [E] that is added
    for the choice only and takes no gradient, `renormalise` the chosen
    weights to sum to one (over their sum + `renorm_epsilon`: DeepSeek's
    1e-20 where None), times `routed_scale`), `buffer_rows` (the
    static rows the held pairs are computed in; N * top_k, which nothing
    can overflow, by default) and `shared_hidden` (> 0: one more gated
    expert of that width which every token passes, inside the same op)."""
    helper = LayerHelper("moe", param_attr=param_attr, name=name)
    d_model = input.shape[-1]

    def init(fan_in):
        return initializer or NormalInitializer(0.0, fan_in ** -0.5)

    def weight(shape, fan_in, attr=None):
        return helper.create_parameter(
            attr=attr or {}, shape=shape, dtype=input.dtype,
            default_initializer=init(fan_in))

    # a "share" that is every expert under OLMoE's router is the whole
    # dropless layer: the op it always was
    share = held is not None and not (
        tuple(held) == (0, num_experts) and scoring == "softmax"
        and select_bias is None and not renormalise and routed_scale == 1.0
        and not buffer_rows and not shared_hidden
        and renorm_epsilon is None)
    if share and not dropless:
        raise ValueError("layers.moe: a share of the experts (held) needs "
                         "dropless=True (ops/moe_ops.py)")
    stacked = int(held[1]) if share else num_experts
    gate = weight([d_model, num_experts], d_model,
                  param_attr if isinstance(param_attr, dict) else None)
    wi = weight([stacked, d_model, d_hidden], d_model)
    ins = {"X": [input.name], "Gate": [gate.name], "WI": [wi.name]}
    if gated:
        ins["WU"] = [weight([stacked, d_model, d_hidden], d_model).name]
    ins["WO"] = [weight([stacked, d_hidden, d_model], d_hidden).name]
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    outs = {"Out": [out.name]}
    attrs = {"capacity_factor": capacity_factor, "act": act}
    if not dropless:
        if top_k != 1 or gated:
            raise ValueError("layers.moe: top_k > 1 and gated experts need "
                             "dropless=True (ops/moe_ops.py)")
        helper.append_op("moe", inputs=ins, outputs=outs, attrs=attrs)
        return out
    logits = helper.create_tmp_variable(
        "float32", shape=(input.shape[0], num_experts))
    counts = helper.create_tmp_variable("float32", shape=(num_experts,),
                                        stop_gradient=True)
    attrs.update({"top_k": int(top_k), "gated": bool(gated),
                  "dropless": True})
    if not share:
        outs.update({"RouterLogits": [logits.name], "Counts": [counts.name]})
        helper.append_op("moe", inputs=ins, outputs=outs, attrs=attrs)
        return out, logits, counts
    bias = None
    if select_bias is not None:
        bias = helper.create_parameter(
            attr={"trainable": False}, shape=[num_experts], dtype="float32",
            default_initializer=select_bias)
        ins["Bias"] = [bias.name]
    if shared_hidden:
        ins["SI"] = [weight([d_model, shared_hidden], d_model).name]
        if gated:
            ins["SU"] = [weight([d_model, shared_hidden], d_model).name]
        ins["SO"] = [weight([shared_hidden, d_model], shared_hidden).name]
    pairs, dropped = (helper.create_tmp_variable(
        "float32", shape=(1,), stop_gradient=True) for _ in range(2))
    weights = helper.create_tmp_variable(
        "float32", shape=(input.shape[0], int(top_k)), stop_gradient=True)
    outs.update({"RouterScores": [logits.name], "Counts": [counts.name],
                 "RouterWeights": [weights.name],
                 "HeldPairs": [pairs.name], "DroppedPairs": [dropped.name]})
    attrs.update({"first_expert": int(held[0]), "scoring": scoring,
                  "renormalise": bool(renormalise),
                  "routed_scale": float(routed_scale)})
    if buffer_rows:
        attrs["buffer_rows"] = int(buffer_rows)
    if renorm_epsilon is not None:
        attrs["renorm_epsilon"] = float(renorm_epsilon)
    helper.append_op("moe", inputs=ins, outputs=outs, attrs=attrs)
    return MoeShare(out, logits, weights, counts, pairs, dropped, bias)


def moe_router_loss(router_logits, counts):
    """(load-balancing loss [1], router z-loss [1]) of one dropless expert
    layer, from `moe`'s second and third result (ops/moe_ops.py
    moe_router_loss has the formulas)."""
    helper = LayerHelper("moe_router_loss")
    balance = helper.create_tmp_variable("float32", shape=(1,))
    z = helper.create_tmp_variable("float32", shape=(1,))
    helper.append_op(
        "moe_router_loss",
        inputs={"RouterLogits": [router_logits.name],
                "Counts": [counts.name]},
        outputs={"Balance": [balance.name], "ZLoss": [z.name]})
    return balance, z


def moe_sequence_balance_loss(scores, counts, top_k):
    """DeepSeek-V3's sequence-wise balance loss [1] of one expert layer,
    from a `MoeShare`'s scores and counts (ops/moe_ops.py
    moe_sequence_balance_loss has the formula)."""
    helper = LayerHelper("moe_sequence_balance_loss")
    balance = helper.create_tmp_variable("float32", shape=(1,))
    helper.append_op(
        "moe_sequence_balance_loss",
        inputs={"RouterScores": [scores.name], "Counts": [counts.name]},
        outputs={"Balance": [balance.name]}, attrs={"top_k": int(top_k)})
    return balance


def moe_bias_update(bias, counts, rate):
    """Append the auxiliary-loss-free balancing step on a `MoeShare`'s
    selection bias: bias += rate * sign(mean(counts) - counts), in place.
    Call it after `minimize`, so that it follows the backward pass."""
    helper = LayerHelper("moe_bias_update")
    helper.append_op(
        "moe_bias_update",
        inputs={"Bias": [bias.name], "Counts": [counts.name]},
        outputs={"BiasOut": [bias.name]}, attrs={"rate": float(rate)})
    return bias


def pipeline_stage(name=None):
    """Mark a pipeline-stage boundary in the program (consumed by
    parallel.ProgramPipeline; a no-op under the single-device Executor)."""
    helper = LayerHelper("pipeline_stage", name=name)
    helper.append_op("pipeline_stage", inputs={}, outputs={}, attrs={})
