"""ResNet model-zoo smoke: tiny cifar ResNet trains end-to-end."""

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _startup
from paddle_tpu.models import resnet


def test_resnet_cifar_trains():
    img = fluid.layers.data(name="image", shape=[3, 32, 32], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    logits = resnet.resnet_cifar10(img, class_dim=10, depth=8)
    loss = fluid.layers.softmax_with_cross_entropy(logits, label)
    avg_cost = fluid.layers.mean(loss)
    fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
        avg_cost)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    # 4 classes of separable images
    temps = rng.rand(4, 3, 32, 32).astype(np.float32)
    ys = rng.randint(0, 4, 96)
    xs = temps[ys] + 0.1 * rng.rand(96, 3, 32, 32).astype(np.float32)
    ys = ys.reshape(-1, 1).astype(np.int64)

    losses = []
    for _ in range(6):
        (l,) = exe.run(feed={"image": xs[:32], "label": ys[:32]},
                       fetch_list=[avg_cost])
        losses.append(float(l.item()))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_resnet50_imagenet_builds():
    """Graph-construction check: full ResNet-50 program builds with the
    right op census (53 convs incl. shortcut projections)."""
    img = fluid.layers.data(name="image", shape=[3, 224, 224],
                            dtype="float32")
    logits = resnet.resnet_imagenet(img, class_dim=1000, depth=50)
    prog = fluid.default_main_program()
    n_conv = sum(1 for op in prog.global_block().ops if op.type == "conv2d")
    n_bn = sum(1 for op in prog.global_block().ops if op.type == "batch_norm")
    assert n_conv == 53, n_conv
    assert n_bn == 53, n_bn
    assert logits.shape[-1] == 1000


# ~30s (two full ResNet-50 builds).  The unfiltered run_tests.sh pass
# still runs it; the 'not slow' fast tier skips it to stay inside its
# wall-clock budget (ISSUE 20).
@pytest.mark.slow
def test_resnet_remat_matches_plain_numerics():
    """layers.recompute per residual block (the bench remat config) must be
    numerically identical to the plain build — remat changes WHERE
    activations come from in backward, never WHAT is computed."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    def losses(remat):
        fluid.reset()
        avg_cost, _ = resnet.build_train_program(
            batch_size=4, depth=18, class_dim=10, image_shape=(3, 32, 32),
            dtype="float32", layout="NCHW", remat=remat)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        img = rng.rand(4, 3, 32, 32).astype(np.float32)
        lbl = rng.randint(0, 10, (4, 1)).astype(np.int64)
        out = []
        for _ in range(3):
            (l,) = exe.run(feed={"image": img, "label": lbl},
                           fetch_list=[avg_cost])
            out.append(float(np.asarray(l).reshape(())))
        return out

    plain = losses(False)
    remat = losses(True)
    # not bit-identical: remat and plain are DIFFERENT XLA programs, so f32
    # fusion/reassociation differences accumulate across update steps
    # (measured ~5e-5 rel by step 3); the bound asserts same-trajectory,
    # catching any structural bug (wrong segment inputs, double-applied
    # BN stat updates) which would diverge by orders more
    np.testing.assert_allclose(remat, plain, rtol=1e-3)
    # parameters moved (the optimizer ran through the recompute op's vjp)
    assert plain[1] != plain[0] and remat[1] != remat[0]


# ---------------------------------------------------------------------------
# the BN -> conv chain of a residual block, against plain float64 numpy


def _chain_reference(x, gamma, beta, w, res, cot, relu, stride, eps=1e-5):
    """batch_norm (train mode, NHWC) (+ residual) (+ ReLU) -> conv2d
    (filter OIHW, pad k // 2) -> sum(out * cot): the output and the
    gradients of x, gamma, beta, w and the residual, written out by hand."""
    k = w.shape[2]
    pad = k // 2
    (h, wd), (ho, wo) = x.shape[1:3], cot.shape[1:3]

    mu = x.mean(axis=(0, 1, 2))
    inv = 1.0 / np.sqrt(x.var(axis=(0, 1, 2)) + eps)
    xhat = (x - mu) * inv
    pre = xhat * gamma + beta + (res if res is not None else 0.0)
    act = np.maximum(pre, 0.0) if relu else pre
    padded = np.pad(act, ((0, 0), (pad, pad), (pad, pad), (0, 0)))

    def window(arr, di, dj):
        return arr[:, di:di + stride * ho:stride,
                   dj:dj + stride * wo:stride, :]

    out = np.zeros(cot.shape)
    dw = np.zeros(w.shape)
    dpadded = np.zeros(padded.shape)
    for di in range(k):
        for dj in range(k):
            out += window(padded, di, dj) @ w[:, :, di, dj].T
            dw[:, :, di, dj] = np.einsum("nijo,nijc->oc", cot,
                                         window(padded, di, dj))
            window(dpadded, di, dj)[...] += cot @ w[:, :, di, dj]
    dpre = dpadded[:, pad:pad + h, pad:pad + wd, :]
    if relu:
        dpre = dpre * (pre > 0)
    dxhat = dpre * gamma
    dx = inv * (dxhat - dxhat.mean(axis=(0, 1, 2))
                - xhat * (dxhat * xhat).mean(axis=(0, 1, 2)))
    return {"out": out, "x": dx, "gamma": (dpre * xhat).sum(axis=(0, 1, 2)),
            "beta": dpre.sum(axis=(0, 1, 2)), "w": dw, "res": dpre}


_CHAIN_DRAWN = {}


@pytest.mark.parametrize("relu", [True, False], ids=["relu", "linear"])
@pytest.mark.parametrize("residual", [True, False], ids=["res", "plain"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("ksize", [1, 3])
def test_batch_norm_conv_chain_matches_float64_reference(ksize, stride,
                                                         residual, relu):
    """What every residual block is made of, as the layers build it and
    `append_backward` differentiates it, held to a reference that shares
    no code with the emitters."""
    layers = fluid.layers
    n, hw, c, o = 2, 6, 5, 4
    ho = (hw + 2 * (ksize // 2) - ksize) // stride + 1
    rng = np.random.RandomState(7 + 8 * ksize + 4 * stride + 2 * residual
                                + relu)
    vals = {"x": rng.randn(n, hw, hw, c) * 1.5 + 0.3,
            "cot": rng.randn(n, ho, ho, o)}
    if residual:
        vals["res"] = rng.randn(n, hw, hw, c)
    gamma, beta = rng.rand(c) + 0.5, rng.randn(c)
    w = rng.randn(o, c, ksize, ksize) * 0.5

    fluid.reset()
    feeds = {}
    for name, v in vals.items():
        feeds[name] = layers.data(name, shape=list(v.shape[1:]),
                                  dtype="float64")
        feeds[name].stop_gradient = name == "cot"
    hidden = layers.batch_norm(feeds["x"], data_layout="NHWC")
    if residual:
        hidden = layers.elementwise_add(hidden, feeds["res"])
    if relu:
        hidden = layers.relu(hidden)
    out = layers.conv2d(hidden, num_filters=o, filter_size=ksize,
                        stride=stride, padding=ksize // 2, bias_attr=False,
                        data_format="NHWC")
    loss = layers.reduce_sum(layers.elementwise_mul(out, feeds["cot"]))
    grads = {p.name: g.name for p, g in fluid.append_backward(loss)}
    assert len(grads) == 3
    names = dict(zip(("gamma", "beta", "w"), grads))  # creation order

    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, _CHAIN_DRAWN.setdefault(ksize, {}))   # 16 cases, 2 programs
    import jax.numpy as jnp

    for key, v in (("gamma", gamma), ("beta", beta), ("w", w)):
        assert fluid.global_scope().find(names[key]).shape == v.shape
        fluid.global_scope().set(names[key], jnp.asarray(v))
    fetch = {"out": out.name, "x": "x@GRAD"}
    fetch.update({k: grads[names[k]] for k in names})
    if residual:
        fetch["res"] = "res@GRAD"
    got = dict(zip(fetch, exe.run(feed=vals, fetch_list=list(fetch.values()))))

    want = _chain_reference(vals["x"], gamma, beta, w, vals.get("res"),
                            vals["cot"], relu, stride)
    for key, g in got.items():
        assert np.asarray(g).dtype == np.float64, key
        np.testing.assert_allclose(np.asarray(g), want[key], rtol=1e-9,
                                   atol=1e-11, err_msg=key)


def _train_program_desc(**kw):
    fluid.reset()
    resnet.build_train_program(batch_size=2, depth=18, class_dim=10,
                               image_shape=(3, 32, 32), layout="NHWC", **kw)
    return [(op.type, sorted((k, len(v)) for k, v in op.inputs.items()),
             sorted((k, repr(v)) for k, v in op.attrs.items()
                    if not k.startswith("__")))
            for op in fluid.default_main_program().global_block().ops]


def test_build_train_program_refuses_the_deleted_fusion_tier():
    with pytest.raises(ValueError, match="deleted in PR 28"):
        _train_program_desc(fuse_bn=True)


def test_build_train_program_fuse_bn_false_is_the_default_program():
    """`benchmarks/configs/resnet50.json` passes `"fuse_bn": false`; that
    is the program the keyword left out builds."""
    assert _train_program_desc(fuse_bn=False) == _train_program_desc()
