"""Fused BatchNorm(+residual)+ReLU -> 3x3 convolution (stride 1 or 2,
pad 1, NHWC) as a Pallas TPU kernel — the companion of bn_matmul.py that completes the
fused ResNet bottleneck: with conv1/conv3 (1x1) riding bn_matmul and
conv2 (3x3) riding this kernel, every normalized activation between the
convolutions of stages 2-4 stays out of HBM.

Design: at ResNet's stage-2..4 shapes a whole per-image feature map fits
comfortably in VMEM (28x28x512 bf16 = 0.8 MB), so the grid is simply
(N,) images x (optionally) nothing else — each program:

  1. loads its image's RAW conv output X [H,W,K], normalizes + ReLUs it
     ONCE (the prologue is shift-invariant, unlike the output tiles),
  2. zero-pads to [H+2, W+2, K] in VMEM,
  3. accumulates nine shifted [H*W, K] @ [K, O] matmuls — one per filter
     tap, weights held as HWIO [3,3,K,O] — into an f32 [H*W, O] tile.

The backward is the same nine taps transposed, single sweep over N with
VMEM-resident dW [3,3,K,O] f32 and dgamma/dbeta accumulators: X and dOut
are read once, dX written once, no dA or A tensor ever materializes.
d(mean)/d(var) close over dgamma/dbeta exactly as in bn_matmul.

Eligibility is a VMEM budget check (train holds w + dw f32 + three
images): stage-4 training (512x512 taps) exceeds it and falls back, the
big spatial stages 2-3 are in.  Reference counterpart: the cuDNN fused
conv+BN epilogues (SURVEY.md §2.10), rebuilt TPU-style.
"""

from __future__ import annotations

import functools

from ._common import TRAIN_VMEM_BUDGET


def _normalize(x, params, eps, act):
    """[H,W,K] f32 normalize+act; params [4,K] f32 rows g,b,mu,var."""
    import jax
    import jax.numpy as jnp

    g, b, mu, var = (params[i] for i in range(4))
    inv = jax.lax.rsqrt(var + eps)
    pre = (x.astype(jnp.float32) - mu) * (inv * g) + b
    if act == "relu":
        pre = jnp.maximum(pre, 0.0)
    return pre


def _taps(a_pad, H_out, W_out, stride=1):
    """The nine [H_out*W_out, K] shifted (optionally strided) views of a
    zero-padded [H+2,W+2,K] map."""
    K = a_pad.shape[-1]
    return [a_pad[ky:ky + stride * H_out:stride,
                  kx:kx + stride * W_out:stride, :].reshape(
                      H_out * W_out, K)
            for ky in range(3) for kx in range(3)]


def _dilate2(do):
    """[H2,W2,O] -> [2*H2,2*W2,O] with do at even positions, zeros
    elsewhere — the stride-2 transposed-conv dilation, built from
    stack+reshape (no scatter: Mosaic-friendly)."""
    import jax.numpy as jnp

    H2, W2, O = do.shape
    z = jnp.zeros_like(do)
    rows = jnp.stack([do, z], axis=1).reshape(2 * H2, W2, O)
    zr = jnp.zeros_like(rows)
    return jnp.stack([rows, zr], axis=2).reshape(2 * H2, 2 * W2, O)


def _fwd_kernel(x_ref, params_ref, w_ref, out_ref, *, eps, act,
                stride=1):
    _fwd_body(x_ref, params_ref, w_ref, None, out_ref, eps=eps, act=act,
              stride=stride)


def _fwd_kernel_res(x_ref, params_ref, w_ref, r_ref, out_ref, *, eps,
                    act, stride=1):
    _fwd_body(x_ref, params_ref, w_ref, r_ref, out_ref, eps=eps, act=act,
              stride=stride)


def _prep_activation(x_ref, params_ref, r_ref, eps, act):
    """Shared prologue for both forward grids: normalize (+residual)
    (+act), zero-pad to [H+2, W+2, K] in f32 — ONE definition so the v1
    and v2 bodies cannot drift (code review r5)."""
    import jax.numpy as jnp

    a = _normalize(x_ref[0], params_ref[...], eps,
                   None if r_ref is not None else act)
    if r_ref is not None:
        a = a + r_ref[0].astype(a.dtype)
        if act == "relu":
            a = jnp.maximum(a, 0.0)
    return jnp.pad(a, ((1, 1), (1, 1), (0, 0)))


def _fwd_body(x_ref, params_ref, w_ref, r_ref, out_ref, *, eps, act,
              stride=1):
    import jax
    import jax.numpy as jnp

    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride
    O = w_ref.shape[-1]
    a_pad = _prep_activation(x_ref, params_ref, r_ref, eps, act).astype(
        w_ref.dtype)
    acc = jnp.zeros((Ho * Wo, O), jnp.float32)
    for i, tap in enumerate(_taps(a_pad, Ho, Wo, stride)):
        ky, kx = divmod(i, 3)
        acc += jax.lax.dot_general(
            tap, w_ref[ky, kx], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    out_ref[0] = acc.reshape(Ho, Wo, O).astype(out_ref.dtype)


def _fwd_body_v2(x_ref, params_ref, w_ref, r_ref, out_ref, apad_sc, *,
                 eps, act, stride=1):
    """O-blocked forward: grid (N, O/BO) with the weight walk innermost,
    so the Pallas pipeline double-buffers each [3,3,K,BO] weight-block
    DMA against the previous block's nine tap GEMMs — the 'pipelined
    operand prefetch' the r4 roofline named as the missing piece
    (perf_resnet50_roofline.md:146-153).  The normalized+padded map is
    computed once per image at j==0 into VMEM scratch and reused for
    every weight block, and the per-program VMEM footprint shrinks by
    O/BO versus the whole-weight v1 grid."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride
    BO = w_ref.shape[-1]

    @pl.when(j == 0)
    def _prep():
        apad_sc[...] = _prep_activation(
            x_ref, params_ref, r_ref, eps, act).astype(apad_sc.dtype)

    a_pad = apad_sc[...]
    acc = jnp.zeros((Ho * Wo, BO), jnp.float32)
    for i, tap in enumerate(_taps(a_pad, Ho, Wo, stride)):
        ky, kx = divmod(i, 3)
        acc += jax.lax.dot_general(
            tap, w_ref[ky, kx], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    out_ref[0] = acc.reshape(Ho, Wo, BO).astype(out_ref.dtype)


def _fwd_kernel_v2(x_ref, params_ref, w_ref, out_ref, apad_sc, **kw):
    _fwd_body_v2(x_ref, params_ref, w_ref, None, out_ref, apad_sc, **kw)


def _fwd_kernel_v2_res(x_ref, params_ref, w_ref, r_ref, out_ref, apad_sc,
                       **kw):
    _fwd_body_v2(x_ref, params_ref, w_ref, r_ref, out_ref, apad_sc, **kw)


def _v2_block_o(O: int) -> int:
    """Weight O-block: explicit override via the autotune knob layer
    (trial override > PADDLE_TPU_BNCONV_BO, validated > stored winner),
    else the largest 128-multiple divisor of O at or under 256 (>=2
    grid steps when O allows, so the weight-DMA/GEMM overlap actually
    exists)."""
    from ...autotune import knobs

    explicit = knobs.bnconv_block_o()
    if explicit and O % explicit == 0:
        return explicit
    if O % 128:
        return O  # un-tileable channel count: whole-weight fallback
    # 128-multiple blocks only (lane tiling), preferring >=2 grid steps
    # so the weight-DMA/GEMM overlap exists: 256 when O splits into >=2
    # such blocks, else 128 (every O%128==0 admits it)
    if O >= 512 and O % 256 == 0:
        return 256
    return 128


def bn_conv3x3_fwd_v2(x, gamma, beta, mean, var, w_hwio, r=None,
                      act="relu", eps=1e-5, stride=1, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, K = x.shape
    Ho, Wo = H // stride, W // stride
    O = w_hwio.shape[-1]
    BO = _v2_block_o(O)
    params = jnp.stack([gamma, beta, mean, var]).astype(jnp.float32)
    in_specs = [
        pl.BlockSpec((1, H, W, K), lambda n, j: (n, 0, 0, 0)),
        pl.BlockSpec((4, K), lambda n, j: (0, 0)),
        pl.BlockSpec((3, 3, K, BO), lambda n, j: (0, 0, 0, j)),
    ]
    args = [x, params, w_hwio]
    if r is not None:
        in_specs.append(
            pl.BlockSpec((1, H, W, K), lambda n, j: (n, 0, 0, 0)))
        args.append(r)
        kern = functools.partial(_fwd_kernel_v2_res, eps=eps, act=act,
                                 stride=stride)
    else:
        kern = functools.partial(_fwd_kernel_v2, eps=eps, act=act,
                                 stride=stride)
    return pl.pallas_call(
        kern,
        grid=(N, O // BO),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Ho, Wo, BO),
                               lambda n, j: (n, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, O), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((H + 2, W + 2, K), w_hwio.dtype)],
        # j must be sequential on a Megacore part: the scratch prep at
        # j==0 is reused by every later j of the same image
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)


def _bwd_kernel(x_ref, params_ref, w_ref, do_ref, dx_ref, dw_ref, dgb_ref,
                *, eps, act, stride=1):
    _bwd_body(x_ref, params_ref, w_ref, None, do_ref, dx_ref, dw_ref,
              dgb_ref, None, eps=eps, act=act, stride=stride)


def _bwd_kernel_res(x_ref, params_ref, w_ref, r_ref, do_ref, dx_ref,
                    dw_ref, dgb_ref, dr_ref, *, eps, act, stride=1):
    _bwd_body(x_ref, params_ref, w_ref, r_ref, do_ref, dx_ref, dw_ref,
              dgb_ref, dr_ref, eps=eps, act=act, stride=stride)


def _bwd_body(x_ref, params_ref, w_ref, r_ref, do_ref, dx_ref, dw_ref,
              dgb_ref, dr_ref, *, eps, act, stride=1):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n = pl.program_id(0)

    @pl.when(n == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dgb_ref[...] = jnp.zeros_like(dgb_ref)

    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride
    K = x_ref.shape[-1]
    params = params_ref[...]
    g, _, mu, var = (params[i] for i in range(4))
    inv = jax.lax.rsqrt(var + eps)
    x32 = x_ref[0].astype(jnp.float32)
    xhat = (x32 - mu) * inv
    pre = xhat * g + params[1]
    if r_ref is not None:
        pre = pre + r_ref[0].astype(jnp.float32)
    a32 = jnp.maximum(pre, 0.0) if act == "relu" else pre
    a = a32.astype(w_ref.dtype)
    a_pad = jnp.pad(a, ((1, 1), (1, 1), (0, 0)))
    do = do_ref[0]
    do2 = do.reshape(Ho * Wo, -1)

    # dW[ky,kx] += tap(ky,kx)^T @ dOut      (resident f32 accumulator)
    taps = _taps(a_pad, Ho, Wo, stride)
    for i, tap in enumerate(taps):
        ky, kx = divmod(i, 3)
        dw_ref[ky, kx] += jax.lax.dot_general(
            tap, do2.astype(w_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # dA = transposed conv: (stride-2: dilate dOut first — even grid
    # positions hold dO, zeros elsewhere) pad, REVERSED taps, w^T per tap
    do_t = do if stride == 1 else _dilate2(do)
    do_pad = jnp.pad(do_t, ((1, 1), (1, 1), (0, 0)))
    dA = jnp.zeros((H * W, K), jnp.float32)
    for ky in range(3):
        for kx in range(3):
            shifted = do_pad[2 - ky:2 - ky + H, 2 - kx:2 - kx + W, :]
            dA += jax.lax.dot_general(
                shifted.reshape(H * W, -1).astype(w_ref.dtype),
                w_ref[ky, kx], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    dA = dA.reshape(H, W, K)
    dpre = jnp.where(pre > 0.0, dA, 0.0) if act == "relu" else dA
    dx_ref[0] = (dpre * (g * inv)).astype(dx_ref.dtype)
    if dr_ref is not None:
        dr_ref[0] = dpre.astype(dr_ref.dtype)
    dgb_ref[0] += jnp.sum(dpre * xhat, axis=(0, 1))
    dgb_ref[1] += jnp.sum(dpre, axis=(0, 1))


def eligible(N, H, W, K, O, dtype_bytes=2, train=True,
             has_residual=False, stride=1) -> bool:
    """Lane-tiled channels, budgeted VMEM: weights (+f32 dW and the
    image working set when training) must fit."""
    if K % 128 or O % 128:
        return False
    if stride not in (1, 2):
        return False  # the backward dilation is built for stride 2 only
    if stride == 2 and (H % 2 or W % 2):
        return False
    w_bytes = 9 * K * O * dtype_bytes
    imgs = (H + 2) * (W + 2) * K * dtype_bytes * 2 + H * W * O * 4
    if has_residual:
        # r input always; the dr output buffer exists only in training
        imgs += (2 if train else 1) * H * W * K * dtype_bytes
    if not train:
        return w_bytes + imgs <= TRAIN_VMEM_BUDGET
    return w_bytes + 9 * K * O * 4 + imgs + H * W * O * dtype_bytes \
        <= TRAIN_VMEM_BUDGET


def bn_conv3x3_reference(x, gamma, beta, mean, var, w, r=None,
                         act="relu", eps=1e-5, stride=1):
    """jnp fallback: normalize(+residual)+act then lax 3x3 conv (XLA's
    conv path — exactly the unfused semantics, for ineligible shapes /
    CPU).  stride may be an int or an (sh, sw) pair (the non-square case
    only ever reaches this reference path)."""
    import jax
    import jax.numpy as jnp

    sdt = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    inv = 1.0 / jnp.sqrt(var.astype(sdt) + eps)
    pre = (x.astype(sdt) - mean.astype(sdt)) * (inv * gamma.astype(sdt)) \
        + beta.astype(sdt)
    if r is not None:
        pre = pre + r.astype(sdt)
    if act == "relu":
        pre = jnp.maximum(pre, 0.0)
    # lax.conv is dtype-strict (unlike dot): promote both operands so a
    # mixed f32/f64 call (e.g. per-input f64 numeric grad checks under
    # x64) doesn't raise
    cdt = jnp.promote_types(x.dtype, w.dtype)
    sh, sw = (stride, stride) if isinstance(stride, int) else stride
    return jax.lax.conv_general_dilated(
        pre.astype(cdt), w.astype(cdt), window_strides=(sh, sw),
        padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "OIHW", "NHWC")).astype(x.dtype)


def _w_hwio(w):
    """OIHW [O,K,3,3] -> HWIO [3,3,K,O] (the kernels' tap layout)."""
    return w.transpose(2, 3, 1, 0)


def bn_conv3x3_fwd(x, gamma, beta, mean, var, w_hwio, r=None,
                   act="relu", eps=1e-5, stride=1, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    N, H, W, K = x.shape
    Ho, Wo = H // stride, W // stride
    O = w_hwio.shape[-1]
    params = jnp.stack([gamma, beta, mean, var]).astype(jnp.float32)
    in_specs = [
        pl.BlockSpec((1, H, W, K), lambda n: (n, 0, 0, 0)),
        pl.BlockSpec((4, K), lambda n: (0, 0)),
        pl.BlockSpec((3, 3, K, O), lambda n: (0, 0, 0, 0)),
    ]
    args = [x, params, w_hwio]
    if r is not None:
        in_specs.append(pl.BlockSpec((1, H, W, K), lambda n: (n, 0, 0, 0)))
        args.append(r)
        kern = functools.partial(_fwd_kernel_res, eps=eps, act=act,
                                 stride=stride)
    else:
        kern = functools.partial(_fwd_kernel, eps=eps, act=act,
                                 stride=stride)
    return pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Ho, Wo, O), lambda n: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, O), x.dtype),
        interpret=interpret,
    )(*args)


def bn_conv3x3_bwd(x, gamma, beta, mean, var, w_hwio, do, r=None,
                   act="relu", eps=1e-5, stride=1, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    N, H, W, K = x.shape
    Ho, Wo = H // stride, W // stride
    O = w_hwio.shape[-1]
    params = jnp.stack([gamma, beta, mean, var]).astype(jnp.float32)
    in_specs = [
        pl.BlockSpec((1, H, W, K), lambda n: (n, 0, 0, 0)),
        pl.BlockSpec((4, K), lambda n: (0, 0)),
        pl.BlockSpec((3, 3, K, O), lambda n: (0, 0, 0, 0)),
    ]
    args = [x, params, w_hwio]
    if r is not None:
        in_specs.append(pl.BlockSpec((1, H, W, K), lambda n: (n, 0, 0, 0)))
        args.append(r)
    in_specs.append(pl.BlockSpec((1, Ho, Wo, O), lambda n: (n, 0, 0, 0)))
    args.append(do)
    out_specs = [
        pl.BlockSpec((1, H, W, K), lambda n: (n, 0, 0, 0)),
        pl.BlockSpec((3, 3, K, O), lambda n: (0, 0, 0, 0)),
        pl.BlockSpec((2, K), lambda n: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((N, H, W, K), x.dtype),
        jax.ShapeDtypeStruct((3, 3, K, O), jnp.float32),
        jax.ShapeDtypeStruct((2, K), jnp.float32),
    ]
    if r is not None:
        out_specs.append(pl.BlockSpec((1, H, W, K), lambda n: (n, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((N, H, W, K), r.dtype))
        kern = functools.partial(_bwd_kernel_res, eps=eps, act=act,
                                 stride=stride)
    else:
        kern = functools.partial(_bwd_kernel, eps=eps, act=act,
                                 stride=stride)
    outs = pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    dx, dw_f32, dgb = outs[0], outs[1], outs[2]
    dgamma, dbeta = dgb[0], dgb[1]
    inv = 1.0 / jnp.sqrt(var.astype(jnp.float32) + eps)
    dmean = -inv * gamma * dbeta
    dvar = -0.5 * inv * inv * gamma * dgamma
    dw = dw_f32.astype(w_hwio.dtype)
    if r is not None:
        return dx, dgamma, dbeta, dmean, dvar, dw, outs[3]
    return dx, dgamma, dbeta, dmean, dvar, dw


_TRAIN_CACHE = {}


def make_bn_conv3x3_train(act="relu", eps=1e-5, has_residual=False,
                          stride=1, interpret=False):
    """custom_vjp fused bn(+residual)+act+conv3x3 for training
    (generic_grad's jax.vjp honors it).  Takes HWIO weights; memoized
    per config.

    The forward implementation is a TUNABLE VARIANT resolved through
    the autotune knob layer (trial override > PADDLE_TPU_BNCONV_VARIANT
    / legacy PADDLE_TPU_BNCONV_V2=1 > stored winner > "v1"): "v1" is
    the whole-image nine-tap kernel, "v2" the O-blocked pipelined grid
    (the r5 attempt, now a first-class search-space member under the
    >=1.0x-or-delete contract — `paddle tune bn_conv` decides it per
    device from measurement), and "reference" the unfused jnp path (the
    demotion arm of the contract, selectable without deleting the
    kernels)."""
    from ...autotune import knobs

    variant = knobs.bnconv_variant()
    key = (act, eps, has_residual, stride, interpret, variant)
    cached = _TRAIN_CACHE.get(key)
    if cached is not None:
        return cached
    import jax

    if variant == "reference":
        # unfused semantics with jax's own autodiff — no custom_vjp
        # needed, and w arrives HWIO like the kernel wrappers
        if has_residual:
            def f(x, gamma, beta, mean, var, w_hwio, r):
                return bn_conv3x3_reference(
                    x, gamma, beta, mean, var,
                    w_hwio.transpose(3, 2, 0, 1), r=r, act=act, eps=eps,
                    stride=stride)
        else:
            def f(x, gamma, beta, mean, var, w_hwio):
                return bn_conv3x3_reference(
                    x, gamma, beta, mean, var,
                    w_hwio.transpose(3, 2, 0, 1), act=act, eps=eps,
                    stride=stride)
        _TRAIN_CACHE[key] = f
        return f

    fwd_impl = bn_conv3x3_fwd_v2 if variant == "v2" else bn_conv3x3_fwd

    if has_residual:
        @jax.custom_vjp
        def f(x, gamma, beta, mean, var, w_hwio, r):
            return fwd_impl(x, gamma, beta, mean, var, w_hwio, r=r,
                                  act=act, eps=eps, stride=stride,
                                  interpret=interpret)

        def fwd(x, gamma, beta, mean, var, w_hwio, r):
            return (f(x, gamma, beta, mean, var, w_hwio, r),
                    (x, gamma, beta, mean, var, w_hwio, r))

        def bwd(res, do):
            x, gamma, beta, mean, var, w_hwio, r = res
            return bn_conv3x3_bwd(x, gamma, beta, mean, var, w_hwio, do,
                                  r=r, act=act, eps=eps, stride=stride,
                                  interpret=interpret)
    else:
        @jax.custom_vjp
        def f(x, gamma, beta, mean, var, w_hwio):
            return fwd_impl(x, gamma, beta, mean, var, w_hwio,
                                  act=act, eps=eps, stride=stride,
                                  interpret=interpret)

        def fwd(x, gamma, beta, mean, var, w_hwio):
            return (f(x, gamma, beta, mean, var, w_hwio),
                    (x, gamma, beta, mean, var, w_hwio))

        def bwd(res, do):
            x, gamma, beta, mean, var, w_hwio = res
            return bn_conv3x3_bwd(x, gamma, beta, mean, var, w_hwio, do,
                                  act=act, eps=eps, stride=stride,
                                  interpret=interpret)

    f.defvjp(fwd, bwd)
    _TRAIN_CACHE[key] = f
    return f
