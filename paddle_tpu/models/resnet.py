"""ResNet for ImageNet-scale image classification, built on the layers API.

Capability target: reference benchmark/paddle/image/resnet.py (v1 config,
layer_num 50/101/152) — the headline model of BASELINE.md (ResNet-50 train
81.69 img/s on Xeon MKL-DNN; public V100 fp32 ~360-400 img/s as stretch).

TPU-first: supports bfloat16 activations/weights (MXU native) with float32
batch-norm statistics; the whole train step (fwd+bwd+SGD/momentum) compiles to
one XLA program via the framework executor.  `layout="NHWC"` keeps
activations channels-last end-to-end — the layout the TPU conv pipeline
prefers (no relayout ops around each conv); "NCHW" remains the reference's
contract and the default."""

from __future__ import annotations

from .. import layers


def conv_bn_layer(input, ch_out, filter_size, stride, padding, act="relu",
                  layout="NCHW"):
    conv = layers.conv2d(
        input=input,
        num_filters=ch_out,
        filter_size=filter_size,
        stride=stride,
        padding=padding,
        act=None,
        bias_attr=False,
        data_format=layout,
    )
    return layers.batch_norm(input=conv, act=act, data_layout=layout)


def shortcut(input, ch_in, ch_out, stride, layout="NCHW"):
    if ch_in != ch_out or stride != 1:
        return conv_bn_layer(input, ch_out, 1, stride, 0, act=None,
                             layout=layout)
    return input


def basicblock(input, ch_in, ch_out, stride, layout="NCHW"):
    short = shortcut(input, ch_in, ch_out, stride, layout=layout)
    conv1 = conv_bn_layer(input, ch_out, 3, stride, 1, layout=layout)
    conv2 = conv_bn_layer(conv1, ch_out, 3, 1, 1, act=None, layout=layout)
    return layers.elementwise_add(x=short, y=conv2, act="relu")


def bottleneck(input, ch_in, ch_out, stride, layout="NCHW"):
    short = shortcut(input, ch_in, ch_out * 4, stride, layout=layout)
    conv1 = conv_bn_layer(input, ch_out, 1, stride, 0, layout=layout)
    conv2 = conv_bn_layer(conv1, ch_out, 3, 1, 1, layout=layout)
    conv3 = conv_bn_layer(conv2, ch_out * 4, 1, 1, 0, act=None, layout=layout)
    return layers.elementwise_add(x=short, y=conv3, act="relu")


def layer_warp(block_func, input, ch_in, ch_out, count, stride,
               layout="NCHW", remat=False):
    """`remat=True` wraps every residual block in layers.recompute()
    (jax.checkpoint): the block's activations are rematerialized in the
    backward pass instead of stored — the roofline doc
    (docs/perf_resnet50_roofline.md) measured 12.9 GB/step of fusion
    writes on the bs128 bench config while compute sat 4.5x under the HBM
    bound, exactly the trade remat makes."""
    import contextlib

    def scope():
        return layers.recompute() if remat else contextlib.nullcontext()

    with scope():
        res = block_func(input, ch_in, ch_out, stride, layout=layout)
    for _ in range(1, count):
        ch_in_cur = ch_out * (4 if block_func is bottleneck else 1)
        with scope():
            res = block_func(res, ch_in_cur, ch_out, 1, layout=layout)
    return res


_DEPTH_CFG = {
    50: (bottleneck, [3, 4, 6, 3]),
    101: (bottleneck, [3, 4, 23, 3]),
    152: (bottleneck, [3, 8, 36, 3]),
    18: (basicblock, [2, 2, 2, 2]),
    34: (basicblock, [3, 4, 6, 3]),
}


def resnet_imagenet(input, class_dim=1000, depth=50, layout="NCHW",
                    remat=False):
    """Reference resnet.py ImageNet topology (224x224)."""
    block, counts = _DEPTH_CFG[depth]
    expansion = 4 if block is bottleneck else 1
    conv1 = conv_bn_layer(input, ch_out=64, filter_size=7, stride=2,
                          padding=3, layout=layout)
    pool1 = layers.pool2d(input=conv1, pool_size=3, pool_stride=2,
                          pool_padding=1, pool_type="max",
                          data_format=layout)
    res1 = layer_warp(block, pool1, 64, 64, counts[0], 1, layout=layout,
                      remat=remat)
    res2 = layer_warp(block, res1, 64 * expansion, 128, counts[1], 2,
                      layout=layout, remat=remat)
    res3 = layer_warp(block, res2, 128 * expansion, 256, counts[2], 2,
                      layout=layout, remat=remat)
    res4 = layer_warp(block, res3, 256 * expansion, 512, counts[3], 2,
                      layout=layout, remat=remat)
    pool2 = layers.pool2d(input=res4, pool_size=7, pool_type="avg",
                          global_pooling=True, data_format=layout)
    logits = layers.fc(input=pool2, size=class_dim)
    return logits


def resnet_cifar10(input, class_dim=10, depth=32, layout="NCHW"):
    """Reference resnet.py cifar topology (32x32)."""
    n = (depth - 2) // 6
    conv1 = conv_bn_layer(input, ch_out=16, filter_size=3, stride=1,
                          padding=1, layout=layout)
    res1 = layer_warp(basicblock, conv1, 16, 16, n, 1, layout=layout)
    res2 = layer_warp(basicblock, res1, 16, 32, n, 2, layout=layout)
    res3 = layer_warp(basicblock, res2, 32, 64, n, 2, layout=layout)
    pool = layers.pool2d(input=res3, pool_size=8, pool_type="avg",
                         global_pooling=True, data_format=layout)
    return layers.fc(input=pool, size=class_dim)


def build_train_program(batch_size=64, depth=50, class_dim=1000,
                        image_shape=(3, 224, 224), dtype="float32",
                        learning_rate=0.1, momentum=0.9, layout="NCHW",
                        remat=False, fuse_bn=False):
    """Full training program: returns (avg_cost, accuracy).

    With dtype='bfloat16' the conv/GEMM path runs natively on the MXU; the
    softmax/loss head is computed in float32 for stability.  With
    layout='NHWC' the 'image' feed is expected channels-last
    ([H, W, C]).  `remat=True` checkpoints every residual block (see
    layer_warp) — the HBM-traffic lever for the bandwidth-bound train
    step."""
    import paddle_tpu as fluid

    # the keyword outlives the pass only because the benchmark's
    # resnet50.json still passes `"fuse_bn": false` (ROADMAP.md, D2)
    if fuse_bn:
        raise ValueError(
            "fuse_bn=True: the BN-fusion tier was deleted in PR 28; on the "
            "v5e it ran ResNet-50 bs128 at 986.5 images/s against 2403.2 "
            "with XLA's own fusions (PERF.md section 6)")
    # image_shape is always the reference's CHW spec; NHWC transposes the
    # feed contract to HWC
    shape = list(image_shape)
    if layout == "NHWC":
        shape = [shape[1], shape[2], shape[0]]
    img = layers.data(name="image", shape=shape, dtype=dtype)
    label = layers.data(name="label", shape=[1], dtype="int64")
    logits = resnet_imagenet(img, class_dim=class_dim, depth=depth,
                             layout=layout, remat=remat)
    logits32 = layers.cast(logits, "float32") if dtype != "float32" else logits
    loss = layers.softmax_with_cross_entropy(logits32, label)
    avg_cost = layers.mean(loss)
    prob = layers.softmax(logits32)
    acc = layers.accuracy(input=prob, label=label)
    opt = fluid.optimizer.Momentum(learning_rate=learning_rate,
                                   momentum=momentum)
    opt.minimize(avg_cost)
    return avg_cost, acc
