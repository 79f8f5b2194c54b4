"""Plain reference of ResNet v1 (He et al. 2015, arXiv:1512.03385): the
forward pass, the losses and their gradients in straightforward jax.numpy and
float32, matmul precision "highest", no kernels, no fusion passes, nothing
imported from the program under test.

Departures from the paper, all shared with models/resnet.py and noted in
configs/resnet50.json: synthetic data, lr 0.01.  Batch norm uses the batch's
own statistics (training mode), biased variance, eps 1e-5.  Each residual
block is wrapped in jax.checkpoint: the mathematics is unchanged, and a
float32 backward pass at batch 128 then fits beside the program's own state.

`params` is the list of the program's parameters in creation order:
[filter OIHW, bn scale, bn bias] for each convolution (a block's projection
shortcut first), then the classifier's weight [in, out] and bias.
"""

from __future__ import annotations

_BLOCKS = {18: ("basic", [2, 2, 2, 2]), 34: ("basic", [3, 4, 6, 3]),
           50: ("bottleneck", [3, 4, 6, 3]),
           101: ("bottleneck", [3, 4, 23, 3]),
           152: ("bottleneck", [3, 8, 36, 3])}
EPS = 1e-5

# What the driver fetches from the program beside the mean loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch):
#   sample_loss  the cross-entropy of each image of the batch (the
#                configuration's `train.check_fetch` names the op), compared
#                CENTERED: the mean is the `loss` entry's business, the
#                scatter between images is the whole forward pass.
#   grad_<i>     gradients by parameter index in creation order: the
#                classifier's weight (-2) and bias (-1), and the scale of the
#                last convolution's batch norm (-4), which has come back
#                through the pooling, the last ReLU and the residual addition.
#
# No gradient below that last batch norm is held, and cannot be on this
# data.  Measured on the v5e at batch 128 (PERF.md, PR 23, "gradient error
# by depth"): against this float32 reference the program's gradient is off
# by 0.25 of its norm at that scale, 0.88 at the last convolution's filter
# one operation further down, and 1.3 at the first filter.  That is bf16,
# not the program: this same plain reference with nothing changed but its
# stored activations rounded to bf16 (`act="bfloat16"`, arithmetic still
# float32) is off from itself by 0.23, 0.85 and 1.3 at the same places, and
# from the program by as much again.  The synthetic images are all alike,
# so what survives a batch norm's backward pass (the gradient less its
# batch mean and its projection on the normalised input) is the small
# difference between images, which 8 bits of mantissa do not carry.  In
# float32 the program's whole backward pass agrees with this file to 1e-5,
# first filter included (tests/benchmarks, at toy size on the CPU).
GRAD_PARAMS = (-2, -1, -4)
CENTERED = ("sample_loss",)

# Tolerances: program (bf16 weights and activations, f32 batch-norm
# statistics and loss) against this float32 reference; arrays by
# |got - want| / |want| in the 2-norm, the loss relative.  Three of them
# are 1.5 times the worst reading on the v5e (PERF.md, PR 23, "reference
# readings": sample_loss 0.087 to 0.121, grad_-2 0.067 to 0.105, grad_-4
# 0.178 to 0.274 over both cells' seeds; the four-chip cell's batch of 512
# reads 0.7 of the one-chip cell's in the two gradients and the same in
# sample_loss), and the bf16-rounded reference reads the same as the
# program (sample_loss 0.095 to 0.108, grad_-2 0.086 to 0.097, grad_-4
# 0.23 to 0.26), so the bound is bf16's own error with half as much again:
# a rounding 1.5 times coarser than bf16 fails.  `loss` and `grad_-1` are
# means whose roundings mostly cancel, so their readings scatter and their
# bounds are 1.5 to 2 times the worst of the widest sweep made: 75 seeds of
# the one-chip cell on the v5e (PERF.md, PR 32; reference_sweep.py; the
# other three keys read as PR 23 found them, 0.135, 0.103 and 0.277 at the
# worst).
#   loss     0.0000 to 0.00385, half-normal with an rms of 0.00147: the old
#            bound of 0.004 (from 0.0021, a dozen seeds) stood 2.7 rms out
#            and would have failed one honest run in 150.  0.007 is 1.8
#            times the worst and 4.8 rms out.
#   grad_-1  73 seeds read 0.0030 to 0.0059, two read 0.0096 and 0.0144
#            (seed 2800113, which failed the old 0.008 at every commit:
#            PERF.md, PR 28).  No fault: the images are all alike, so every
#            image gives a class the same probability, and in those two
#            seeds ONE class holds 0.04 and 0.09 of it (0.013 to 0.055
#            elsewhere); its logit, about 4.5, is kept in bf16 to 1/64, the
#            same rounding in all 128 images, which moves that one
#            coordinate of the mean by 2^-10 and 2^-9: 91% and 96% of the
#            whole error.  The mechanism is bounded by the rounding of one
#            logit under 8 over that class's share of the gradient's norm,
#            0.021 x 0.67 here, so 0.028, twice the worst, has room.  The
#            fp8 control reads 0.005 here (a mean again): this key holds
#            the bias gradient's arithmetic (a sum for a mean, a gradient
#            left out: errors of 1 and more), not the precision, which
#            `sample_loss`, `grad_-2` and `grad_-4` hold (control 0.32 to
#            0.46, 0.18 to 0.21, 0.44 to 0.48 on three seeds at batch 128).
TOL = {"loss": 0.007, "sample_loss": 0.18, "grad_-2": 0.16,
       "grad_-1": 0.028, "grad_-4": 0.4}


def _conv(x, w, stride, pad):
    from jax import lax

    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + EPS) * scale + bias


def _stored(x, act):
    """An activation as it is kept between two operations: float32 here;
    `act="bfloat16"` rounds it as the program's bf16 tensors are rounded
    (the arithmetic stays float32), which is how PERF.md's table of the
    gradient error by depth was made."""
    import jax.numpy as jnp

    return x if act == "float32" else x.astype(act).astype(jnp.float32)


def _conv_bn(x, it, stride, pad, relu, act):
    import jax.numpy as jnp

    w, scale, bias = next(it), next(it), next(it)
    y = _bn(_stored(_conv(x, w, stride, pad), act), scale, bias)
    return _stored(jnp.maximum(y, 0.0) if relu else y, act)


def _block(kind, x, it, cin, width, stride, act):
    import jax.numpy as jnp

    cout = width * (4 if kind == "bottleneck" else 1)
    short = x
    if cin != cout or stride != 1:
        short = _conv_bn(x, it, stride, 0, False, act)
    if kind == "bottleneck":
        y = _conv_bn(x, it, stride, 0, True, act)
        y = _conv_bn(y, it, 1, 1, True, act)
        y = _conv_bn(y, it, 1, 0, False, act)
    else:
        y = _conv_bn(x, it, stride, 1, True, act)
        y = _conv_bn(y, it, 1, 1, False, act)
    return _stored(jnp.maximum(short + y, 0.0), act), cout


def _block_params(kind, cin, width, stride) -> int:
    cout = width * (4 if kind == "bottleneck" else 1)
    convs = (3 if kind == "bottleneck" else 2) + (
        1 if (cin != cout or stride != 1) else 0)
    return 3 * convs


def sample_losses(params, image, label, depth: int = 50,
                  act: str = "float32"):
    """image [B, H, W, 3], label [B] int -> softmax cross-entropy [B]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    kind, counts = _BLOCKS[depth]
    params = [p.astype(jnp.float32) for p in params]
    x = image.astype(jnp.float32)
    x = _conv_bn(x, iter(params[:3]), 2, 3, True, act)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    at, cin = 3, 64
    for stage, (n, width) in enumerate(zip(counts, (64, 128, 256, 512))):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            k = _block_params(kind, cin, width, stride)

            def run(x, ps, cin=cin, width=width, stride=stride):
                return _block(kind, x, iter(ps), cin, width, stride, act)[0]

            x = jax.checkpoint(run)(x, params[at:at + k])
            at += k
            cin = width * (4 if kind == "bottleneck" else 1)
    x = jnp.mean(x, axis=(1, 2))
    w, b = params[at], params[at + 1]
    assert at + 2 == len(params), (at, len(params))
    logits = jnp.dot(x, w, precision=lax.Precision.HIGHEST) + b
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(
        logp, label.reshape(-1, 1).astype(jnp.int32), axis=1)[:, 0]


def check_fn(params, image, label, depth: int = 50,
             act: str = "float32") -> dict:
    """-> {"loss", "sample_loss" [B], "grad_<i>" for i in GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    def mean_loss(ps):
        per_sample = sample_losses(ps, image, label, depth, act)
        return jnp.mean(per_sample), per_sample

    (loss, per_sample), grads = jax.value_and_grad(
        mean_loss, has_aux=True)(list(params))
    out = {"loss": loss, "sample_loss": per_sample}
    for i in GRAD_PARAMS:
        out[f"grad_{i}"] = grads[i]
    return out


def train_check(params, feed: dict, config: dict,
                act: str = "float32") -> dict:
    """On the device, from the same weights and batch as the program's
    step."""
    import jax

    depth = int(config["depth"])
    return jax.jit(
        lambda ps, image, label: check_fn(ps, image, label, depth, act))(
        list(params), feed["image"], feed["label"].reshape(-1))


def control_check(params, feed: dict, config: dict) -> dict:
    """The control: this reference with its stored activations in the
    nearest precision below the configuration's bfloat16.  Put in the
    program's place it has to fail `TOL` (reference_sweep.py --control)."""
    return train_check(params, feed, config, act="float8_e4m3fn")
