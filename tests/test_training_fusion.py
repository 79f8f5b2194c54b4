"""BatchNorm->1x1-conv training fusion: kernel parity, op grad checks,
pass structure, and end-to-end numerics (paddle_tpu/training_fusion.py +
ops/pallas_kernels/bn_matmul.py).

Proof strategy (the f32 trap): at ResNet-50 scale, ANY reassociation of
the f32 math shifts gradients by ~2% through cancellation-heavy
reductions — comparing fused-vs-unfused f32 gradients directly cannot
distinguish a real bug from noise.  The decisive checks here are (a)
float64 end-to-end equality in a subprocess (fused == unfused to ~1e-12)
and (b) numeric central-difference checks per op; the f32 checks assert
exactness only at small scale, where cancellation is absent.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from op_test import OpTestHarness

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)


def _r(*shape, lo=-1.0, hi=1.0, seed=None):
    rng = np.random.RandomState(seed if seed is not None else shape[0])
    return (rng.rand(*shape) * (hi - lo) + lo).astype("float32")


# ---------------------------------------------------------------- kernel
@pytest.mark.parametrize("act,has_r", [("relu", False), (None, False),
                                       ("relu", True), (None, True)])
def test_bn_matmul_kernel_parity_interpret(act, has_r):
    """Pallas fwd + custom_vjp bwd (interpret mode) vs the jnp reference,
    every gradient including the dmean/dvar closed forms."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import bn_matmul as bm

    rng = np.random.RandomState(0)
    M, K, N = 64, 128, 256
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    g = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    mu = jnp.asarray(rng.randn(K).astype(np.float32) * 0.1)
    var = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    r = jnp.asarray(rng.randn(M, K).astype(np.float32)) if has_r else None
    args = (x, g, b, mu, var, w) + ((r,) if has_r else ())

    def ref(*a):
        if has_r:
            return bm.bn_matmul_reference(*a[:6], r=a[6], act=act)
        return bm.bn_matmul_reference(*a, act=act)

    f = bm.make_bn_matmul_train(act=act, has_residual=has_r, interpret=True)
    out, out_ref = f(*args), ref(*args)
    assert np.allclose(out, out_ref, atol=2e-4)

    ct = jnp.asarray(rng.randn(M, N).astype(np.float32))
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * ct),
                  argnums=tuple(range(len(args))))(*args)
    gk = jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                  argnums=tuple(range(len(args))))(*args)
    for name, a, b_ in zip(["x", "gamma", "beta", "mean", "var", "w", "r"],
                           gr, gk):
        err = (np.abs(np.asarray(a) - np.asarray(b_)).max()
               / (np.abs(np.asarray(a)).max() + 1e-8))
        assert err < 2e-5, (name, err)


@pytest.mark.parametrize("act,has_r,stride",
                         [("relu", False, 1), (None, False, 1),
                          ("relu", True, 1), (None, True, 1),
                          ("relu", False, 2), ("relu", True, 2)])
def test_bn_conv3x3_kernel_parity_interpret(act, has_r, stride):
    """Pallas nine-tap fwd + transposed-tap bwd (interpret mode) vs the
    normalize+lax.conv reference, every gradient, with and without the
    residual input."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import bn_conv as bc

    rng = np.random.RandomState(0)
    N, H, W, K, O = 2, 6, 6, 128, 128
    x = jnp.asarray(rng.randn(N, H, W, K).astype(np.float32))
    w = jnp.asarray(rng.randn(O, K, 3, 3).astype(np.float32) * 0.05)
    g = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    mu = jnp.asarray(rng.randn(K).astype(np.float32) * 0.1)
    var = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    r = jnp.asarray(rng.randn(N, H, W, K).astype(np.float32))         if has_r else None
    wh = bc._w_hwio(w)
    args = (x, g, b, mu, var, wh) + ((r,) if has_r else ())

    def ref(*a):
        return bc.bn_conv3x3_reference(
            a[0], a[1], a[2], a[3], a[4], w,
            r=a[6] if has_r else None, act=act, stride=stride)

    f = bc.make_bn_conv3x3_train(act=act, has_residual=has_r,
                                 stride=stride, interpret=True)
    assert np.allclose(f(*args), ref(*args), atol=2e-4)

    ct = jnp.asarray(
        rng.randn(N, H // stride, W // stride, O).astype(np.float32))
    # reference grads wrt OIHW w need argnums against the ORIGINAL args
    ref_args = (x, g, b, mu, var, w) + ((r,) if has_r else ())

    def loss_ref(*a):
        return jnp.sum(bc.bn_conv3x3_reference(
            *a[:6], r=a[6] if has_r else None, act=act,
            stride=stride) * ct)

    gr = jax.grad(loss_ref, argnums=tuple(range(len(ref_args))))(*ref_args)
    gk = jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                  argnums=tuple(range(len(args))))(*args)
    names = ["x", "gamma", "beta", "mean", "var", "w"] +         (["r"] if has_r else [])
    for name, a, b_ in zip(names, gr, gk):
        a = np.asarray(a)
        if name == "w":
            a = a.transpose(2, 3, 1, 0)  # OIHW grad -> HWIO layout
        e = np.abs(a - np.asarray(b_)).max() / (np.abs(a).max() + 1e-8)
        assert e < 2e-5, (name, e)


def test_bn_conv3x3_eligibility_gates():
    from paddle_tpu.ops.pallas_kernels.bn_conv import eligible

    assert eligible(128, 28, 28, 128, 128)     # stage-2 middle conv
    assert eligible(128, 14, 14, 256, 256)     # stage-3
    assert not eligible(128, 7, 7, 512, 512)   # stage-4 train: VMEM
    assert eligible(128, 7, 7, 512, 512, train=False)
    assert not eligible(128, 56, 56, 64, 64)   # K not lane-tiled


def test_bn_matmul_eligibility_gates():
    from paddle_tpu.ops.pallas_kernels.bn_matmul import eligible

    assert eligible(6272, 2048, 512)          # stage-4 next-conv1 shape
    assert not eligible(6272, 64, 256)        # K not lane-tiled
    assert not eligible(6272, 2048, 130)      # N not lane-tiled
    assert not eligible(6273, 128, 128)       # M not sublane-tiled
    assert not eligible(392, 1024, 2048)      # dW+W accumulators blow VMEM


# ------------------------------------------------------------ op numerics
@pytest.mark.parametrize("strides,res", [([1, 1], False), ([2, 2], True)])
def test_bn_act_conv1x1_grad(strides, res):
    x = _r(2, 4, 4, 6, seed=8)
    ins = {"X": x,
           "Scale": _r(6, lo=0.5, hi=1.5, seed=9),
           "Bias": _r(6, seed=10),
           "SavedMean": _r(6, lo=-0.2, hi=0.2, seed=11),
           "SavedVariance": _r(6, lo=0.5, hi=1.5, seed=12),
           "Filter": _r(8, 6, 1, 1, lo=-0.5, hi=0.5, seed=13)}
    check = ["X", "Scale", "Bias", "SavedMean", "SavedVariance", "Filter"]
    if res:
        ins["Residual"] = _r(2, 4, 4, 6, seed=14)
        check = ["X", "Filter", "Residual"]
    OpTestHarness("bn_act_conv1x1", ins,
                  {"epsilon": 1e-5, "act": "relu", "strides": strides},
                  out_slots=["Output"]).check_grad(
        check, output_slot="Output", max_relative_error=1e-2, eps=1e-3)


@pytest.mark.parametrize("act", ["relu", ""])
def test_bn_act_conv3x3_grad(act):
    x = _r(2, 4, 4, 6, seed=15)
    ins = {"X": x,
           "Scale": _r(6, lo=0.5, hi=1.5, seed=16),
           "Bias": _r(6, seed=17),
           "SavedMean": _r(6, lo=-0.2, hi=0.2, seed=18),
           "SavedVariance": _r(6, lo=0.5, hi=1.5, seed=19),
           "Filter": _r(8, 6, 3, 3, lo=-0.3, hi=0.3, seed=20)}
    OpTestHarness("bn_act_conv3x3", ins,
                  {"epsilon": 1e-5, "act": act, "strides": [2, 2]}
                  if act == "relu" else {"epsilon": 1e-5, "act": act},
                  out_slots=["Output"]).check_grad(
        ["X", "Scale", "Bias", "SavedMean", "SavedVariance", "Filter"],
        output_slot="Output", max_relative_error=1e-2, eps=1e-3)


# ------------------------------------------------------------------ pass
def _two_block_net(layers, dtype="float32"):
    """conv3x3 stem; bn+relu->conv1x1; bn+add(+bn)+relu->2x stride-2
    conv1x1 — every chain shape the pass supports."""
    img = layers.data(name="image", shape=[8, 8, 64], dtype=dtype)
    a = layers.conv2d(img, num_filters=128, filter_size=3, padding=1,
                      bias_attr=False, data_format="NHWC")
    bn1 = layers.batch_norm(a, act="relu", data_layout="NHWC")
    c2 = layers.conv2d(bn1, num_filters=128, filter_size=1,
                       bias_attr=False, data_format="NHWC")
    bn2 = layers.batch_norm(c2, act=None, data_layout="NHWC")
    t = layers.elementwise_add(x=bn1, y=bn2, act="relu")
    p = layers.conv2d(t, num_filters=128, filter_size=1, stride=2,
                      bias_attr=False, data_format="NHWC")
    q = layers.conv2d(t, num_filters=128, filter_size=1, stride=2,
                      bias_attr=False, data_format="NHWC")
    # 3x3 chain (bn_act_conv3x3): plain bn+relu -> 3x3 stride-1 pad-1
    r3 = layers.conv2d(bn1, num_filters=128, filter_size=3, padding=1,
                       bias_attr=False, data_format="NHWC")
    # 3x3 RESIDUAL chain (basicblock conv1 shape): relu(bn+short) -> 3x3
    r4 = layers.conv2d(t, num_filters=128, filter_size=3, padding=1,
                       bias_attr=False, data_format="NHWC")
    loss = (layers.mean(layers.elementwise_mul(p, p))
            + layers.mean(layers.elementwise_mul(q, q))
            + layers.mean(layers.elementwise_mul(r3, r3))
            + layers.mean(layers.elementwise_mul(r4, r4)))
    return loss


def test_pass_structure_and_skips():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.training_fusion import fuse_bn_matmul

    fluid.reset()
    loss = _two_block_net(layers)
    n = fuse_bn_matmul(fluid.default_main_program())
    assert n == 5  # c2 plain + p/q residual 1x1 + plain/residual 3x3
    ops = [op.type for op in fluid.default_main_program().blocks[0].ops]
    assert ops.count("bn_act_conv1x1") == 3
    assert ops.count("bn_act_conv3x3") == 2
    res3 = [op for op in fluid.default_main_program().blocks[0].ops
            if op.type == "bn_act_conv3x3" and op.inputs.get("Residual")]
    assert len(res3) == 1
    # residual chains carry the Residual input
    res_ops = [op for op in fluid.default_main_program().blocks[0].ops
               if op.type == "bn_act_conv1x1" and op.inputs.get("Residual")]
    assert len(res_ops) == 2

    # NCHW, 3x3 consumers, and non-bn producers are not rewritten
    fluid.reset()
    img = layers.data(name="image", shape=[64, 8, 8], dtype="float32")
    c = layers.conv2d(img, num_filters=32, filter_size=1, bias_attr=False)
    bn = layers.batch_norm(c, act="relu")  # NCHW
    layers.conv2d(bn, num_filters=32, filter_size=1, bias_attr=False)
    assert fuse_bn_matmul(fluid.default_main_program()) == 0

    # running after minimize is refused
    fluid.reset()
    loss = _two_block_net(layers)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    with pytest.raises(ValueError):
        fuse_bn_matmul(fluid.default_main_program())


def test_fused_training_matches_unfused_small_scale():
    """At small scale the f32 trajectories must agree tightly for many
    steps (no cancellation amplification here — see module docstring)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.training_fusion import fuse_bn_matmul

    def run(fuse):
        fluid.reset()
        loss = _two_block_net(layers)
        if fuse:
            assert fuse_bn_matmul(fluid.default_main_program()) == 5
        fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
        exe = fluid.Executor(fluid.default_place())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(7)
        img = rng.rand(8, 8, 8, 64).astype("float32")
        return [float(np.asarray(
            exe.run(feed={"image": img}, fetch_list=[loss])[0]).ravel()[0])
            for _ in range(8)]

    a, b = run(False), run(True)
    assert a[-1] < a[0]  # it actually trains
    for x, y in zip(a, b):
        assert abs(x - y) / max(abs(x), 1e-8) < 1e-4, (a, b)


def test_fused_equals_unfused_in_float64():
    """The decisive correctness gate: in float64 the fused graph's
    gradients equal the unfused graph's to ~1e-12 (run in a subprocess so
    the x64 flag cannot leak into other tests)."""
    script = r"""
import sys, json
import numpy as np
sys.path.insert(0, %r)
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.training_fusion import fuse_bn_matmul
sys.path.insert(0, %r)
from test_training_fusion import _two_block_net

def grads(fuse):
    fluid.reset()
    loss = _two_block_net(layers, dtype="float64")
    if fuse:
        assert fuse_bn_matmul(fluid.default_main_program()) == 5
    fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
    prog = fluid.default_main_program()
    gvars = sorted(n for n in prog.blocks[0].vars if n.endswith("@GRAD")
                   and prog.blocks[0].vars[n.replace("@GRAD", "")]
                   .__class__.__name__ == "Parameter")
    exe = fluid.Executor(fluid.default_place())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    img = rng.rand(8, 8, 8, 64).astype("float64")
    vals = exe.run(feed={"image": img}, fetch_list=gvars)
    return gvars, [np.asarray(v) for v in vals]

gn, a = grads(False)
gn1, b = grads(True)
assert gn == gn1
err = max(np.linalg.norm(x - y) / (np.linalg.norm(x) + 1e-30)
          for x, y in zip(a, b))
print(json.dumps({"max_rel_err": err}))
""" % (REPO, TESTS_DIR)
    out = subprocess.run(
        [sys.executable, "-c", script],
        env={**os.environ, "JAX_ENABLE_X64": "1", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    err = json.loads([l for l in out.stdout.splitlines()
                      if l.startswith("{")][-1])["max_rel_err"]
    assert err < 1e-10, err


def test_resnet18_basicblocks_fuse():
    """resnet-18 basicblocks: every conv1 (stride 1 AND the stride-2
    boundary ones) rides the residual 3x3 chain, every conv2 the plain
    3x3 chain, stage-boundary shortcuts the 1x1 chain."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    fluid.reset()
    resnet.build_train_program(batch_size=2, depth=18, class_dim=10,
                               dtype="float32", layout="NHWC", fuse_bn=True)
    ops = [op.type for op in fluid.default_main_program().blocks[0].ops]
    # 8 conv2 (plain) + 4 stride-1 conv1 (residual) + 3 stride-2
    # boundary conv1 (residual) = 15 3x3 sites; 3 boundary 1x1 shortcuts
    assert ops.count("bn_act_conv3x3") == 15
    assert ops.count("bn_act_conv1x1") == 3
    fluid.reset()


def test_resnet50_builds_and_fuses_50_convs():
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    fluid.reset()
    resnet.build_train_program(batch_size=2, depth=50, class_dim=10,
                               dtype="float32", layout="NHWC", fuse_bn=True)
    n = sum(1 for op in fluid.default_main_program().blocks[0].ops
            if op.type == "bn_act_conv1x1")
    assert n == 34  # 1x1 sites
    n3 = sum(1 for op in fluid.default_main_program().blocks[0].ops
             if op.type == "bn_act_conv3x3")
    assert n3 == 16  # every bottleneck's middle conv
    fluid.reset()


def test_fused_program_under_dp_mesh_matches_unfused():
    """The fused ops must run correctly under a sharded ParallelExecutor:
    the emitters gate the Pallas path on ctx.mesh is None (GSPMD cannot
    partition Mosaic custom calls), so sharded lowering takes the
    XLA-fusable reference — numerics must be identical either way."""
    from paddle_tpu.parallel import ParallelExecutor

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.training_fusion import fuse_bn_matmul

    def run(fuse):
        fluid.reset()
        img = layers.data(name="image", shape=[8, 8, 128], dtype="float32")
        lab = layers.data(name="y", shape=[1], dtype="int64")
        a = layers.conv2d(img, num_filters=128, filter_size=3, padding=1,
                          bias_attr=False, data_format="NHWC")
        bn1 = layers.batch_norm(a, act="relu", data_layout="NHWC")
        c2 = layers.conv2d(bn1, num_filters=128, filter_size=1,
                           bias_attr=False, data_format="NHWC")
        c3 = layers.conv2d(bn1, num_filters=128, filter_size=3, padding=1,
                           bias_attr=False, data_format="NHWC")
        flat = layers.reshape(layers.elementwise_add(c2, c3),
                              [-1, 8 * 8 * 128])
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(input=flat, size=10), lab))
        if fuse:
            assert fuse_bn_matmul(fluid.default_main_program()) == 2
        fluid.optimizer.SGD(learning_rate=1e-3).minimize(loss)
        pe = ParallelExecutor(axes={"dp": 8})
        pe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        feed = {"image": rng.rand(16, 8, 8, 128).astype("float32"),
                "y": rng.randint(0, 10, (16, 1)).astype("int64")}
        return [float(np.asarray(
            pe.run(feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])
            for _ in range(4)]

    a, b = run(False), run(True)
    assert a[-1] < a[0]
    for x, y in zip(a, b):
        assert abs(x - y) / max(abs(x), 1e-8) < 1e-3, (a, b)


def test_pallas_dispatch_gate_unit(monkeypatch):
    """Pin the dispatch gate directly (the dp-mesh parity test above
    cannot: on the CPU backend the Pallas branch is dead either way).
    With a faked 'tpu' target: mesh set -> the kernel factory must NOT
    be consulted; mesh None -> it must be."""
    import jax.numpy as jnp

    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops.pallas_kernels import bn_matmul as bmm
    from paddle_tpu.ops.registry import EmitContext

    calls = []

    def sentinel(*a, **k):
        calls.append(1)
        raise RuntimeError("sentinel: kernel path taken")

    monkeypatch.setattr(bmm, "make_bn_matmul_train", sentinel)

    rng = np.random.RandomState(0)
    ins = {"X": [jnp.asarray(rng.rand(8, 2, 2, 128).astype("float32"))],
           "Scale": [jnp.ones(128)], "Bias": [jnp.zeros(128)],
           "SavedMean": [jnp.zeros(128)],
           "SavedVariance": [jnp.ones(128)],
           "Filter": [jnp.asarray(
               rng.rand(128, 128, 1, 1).astype("float32"))]}
    attrs = {"epsilon": 1e-5, "act": "relu", "strides": [1, 1]}

    import jax

    ctx = EmitContext(jax.random.PRNGKey(0), is_test=False)
    monkeypatch.setattr(EmitContext, "target_platform", lambda self: "tpu")

    ctx.mesh = object()  # sharded lowering: reference path, no sentinel
    nn_ops.bn_act_conv1x1(ctx, ins, attrs)
    assert not calls

    ctx.mesh = None      # single-chip: the kernel factory is consulted
    with pytest.raises(RuntimeError, match="sentinel"):
        nn_ops.bn_act_conv1x1(ctx, ins, attrs)
    assert calls


def test_fusion_reaches_recompute_sub_blocks():
    """With remat, chains live inside recompute sub-blocks; a block-0-only
    pass would silently fuse nothing (and the bench's remat+bnfuse A/B
    would measure an unfused program under a fused label)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.training_fusion import fuse_bn_matmul

    def build(fuse):
        fluid.reset()
        img = layers.data(name="image", shape=[8, 8, 128], dtype="float32")
        with layers.recompute():
            a = layers.conv2d(img, num_filters=128, filter_size=3,
                              padding=1, bias_attr=False,
                              data_format="NHWC")
            bn1 = layers.batch_norm(a, act="relu", data_layout="NHWC")
            c2 = layers.conv2d(bn1, num_filters=128, filter_size=1,
                               bias_attr=False, data_format="NHWC")
        loss = layers.mean(layers.elementwise_mul(c2, c2))
        n = fuse_bn_matmul(fluid.default_main_program()) if fuse else 0
        fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
        return loss, n

    loss, n = build(True)
    prog = fluid.default_main_program()
    fused_in_subblocks = sum(
        1 for b in prog.blocks[1:] for op in b.ops
        if op.type == "bn_act_conv1x1")
    assert n == 1 and fused_in_subblocks == 1

    def run(fuse):
        loss, _ = build(fuse)
        exe = fluid.Executor(fluid.default_place())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(7)
        img = rng.rand(8, 8, 8, 128).astype("float32")
        return [float(np.asarray(
            exe.run(feed={"image": img}, fetch_list=[loss])[0]).ravel()[0])
            for _ in range(6)]

    a, b = run(False), run(True)
    assert a[-1] < a[0]
    for x, y in zip(a, b):
        assert abs(x - y) / max(abs(x), 1e-8) < 1e-4, (a, b)


def test_fused_program_still_serves_intermediate_fetches():
    """The pass removes nothing: a user fetching the normalized
    activation (or the bn output) still gets the exact original values
    even though the fused convs no longer read them."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.training_fusion import fuse_bn_matmul

    def run(fuse):
        fluid.reset()
        img = layers.data(name="image", shape=[8, 8, 128], dtype="float32")
        a = layers.conv2d(img, num_filters=128, filter_size=3, padding=1,
                          bias_attr=False, data_format="NHWC")
        bn1 = layers.batch_norm(a, act="relu", data_layout="NHWC")
        c2 = layers.conv2d(bn1, num_filters=128, filter_size=1,
                           bias_attr=False, data_format="NHWC")
        loss = layers.mean(layers.elementwise_mul(c2, c2))
        if fuse:
            assert fuse_bn_matmul(fluid.default_main_program()) == 1
        fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
        exe = fluid.Executor(fluid.default_place())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(7)
        img_v = rng.rand(4, 8, 8, 128).astype("float32")
        vals = exe.run(feed={"image": img_v},
                       fetch_list=[loss, bn1])  # bn1: the eliminated chain
        return [np.asarray(v) for v in vals]

    base, fused = run(False), run(True)
    np.testing.assert_allclose(fused[0], base[0], rtol=1e-5)
    np.testing.assert_allclose(fused[1], base[1], rtol=1e-5)
    assert np.abs(np.asarray(fused[1])).max() > 0  # real values, not zeros


def test_fused_program_saves_loads_and_infers_identically(tmp_path):
    """save_inference_model prunes a FUSED training program down to the
    fused inference graph (bn_act_conv* ops serialize through the desc
    proto), and the loaded model's test-mode semantics — fused ops read
    SavedMean/SavedVariance, which a test-mode batch_norm sets to the
    RUNNING stats — match the unfused model exactly."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.training_fusion import fuse_bn_matmul

    def build(fuse):
        fluid.reset()
        img = layers.data(name="image", shape=[8, 8, 128], dtype="float32")
        a = layers.conv2d(img, num_filters=128, filter_size=3, padding=1,
                          bias_attr=False, data_format="NHWC")
        bn1 = layers.batch_norm(a, act="relu", data_layout="NHWC")
        c2 = layers.conv2d(bn1, num_filters=128, filter_size=1,
                           bias_attr=False, data_format="NHWC")
        bn2 = layers.batch_norm(c2, act=None, data_layout="NHWC")
        t = layers.elementwise_add(x=bn1, y=bn2, act="relu")
        out = layers.conv2d(t, num_filters=128, filter_size=3, padding=1,
                            bias_attr=False, data_format="NHWC")
        loss = layers.mean(layers.elementwise_mul(out, out))
        if fuse:
            assert fuse_bn_matmul(fluid.default_main_program()) == 2
        fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
        return out

    ys = {}
    for fuse in (False, True):
        out = build(fuse)
        exe = fluid.Executor(fluid.default_place())
        exe.run(fluid.default_startup_program())  # same deterministic init
        rng = np.random.RandomState(3)
        img_v = rng.rand(4, 8, 8, 128).astype("float32")
        d = str(tmp_path / f"model_{fuse}")
        fluid.io.save_inference_model(
            d, ["image"], [out], exe,
            main_program=fluid.default_main_program())
        prog2, feeds, fetches = fluid.io.load_inference_model(d, exe)
        fused_kinds = {op.type for op in prog2.blocks[0].ops}
        if fuse:
            assert {"bn_act_conv1x1", "bn_act_conv3x3"} <= fused_kinds
        (y2,) = exe.run(prog2, feed={"image": img_v}, fetch_list=fetches)
        ys[fuse] = np.asarray(y2)
    np.testing.assert_allclose(ys[True], ys[False], rtol=1e-5)


def test_mosaic_failure_in_fused_bn_propagates(monkeypatch):
    """A Mosaic failure from either opt-in bn kernel is the caller's
    error, naming the fused op: the FUSED training program does not
    quietly continue on the XLA reference path."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import _common
    from paddle_tpu.ops.pallas_kernels import bn_conv as bcv
    from paddle_tpu.ops.pallas_kernels import bn_matmul as bmm
    from paddle_tpu.training_fusion import fuse_bn_matmul

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")

    def boom(**kw):
        def f(*a, **k):
            raise RuntimeError(
                "Mosaic failed to lower: INTERNAL: unsupported layout")
        return f

    monkeypatch.setattr(bmm, "make_bn_matmul_train", boom)
    monkeypatch.setattr(bcv, "make_bn_conv3x3_train", boom)
    fluid.reset()
    img = layers.data(name="image", shape=[8, 8, 128], dtype="float32")
    a = layers.conv2d(img, num_filters=128, filter_size=3, padding=1,
                      bias_attr=False, data_format="NHWC")
    bn1 = layers.batch_norm(a, act="relu", data_layout="NHWC")
    c2 = layers.conv2d(bn1, num_filters=128, filter_size=1,
                       bias_attr=False, data_format="NHWC")
    loss = layers.mean(layers.elementwise_mul(c2, c2))
    assert fuse_bn_matmul(fluid.default_main_program()) == 1
    fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    feed = {"image": rng.rand(4, 8, 8, 128).astype("float32")}
    with pytest.raises(Exception, match="Mosaic failed to lower"):
        exe.run(feed=feed, fetch_list=[loss])
    assert _common.kernels_enabled()


@pytest.mark.parametrize("stride,has_r", [(1, False), (2, True)])
def test_bn_conv3x3_v2_pipelined_forward_parity(stride, has_r,
                                                monkeypatch):
    """The O-blocked pipelined forward (bn_conv3x3_fwd_v2 — the r5
    operand-prefetch attempt, VERDICT r4 Next #6) matches the reference
    in interpret mode, and PADDLE_TPU_BNCONV_V2=1 routes the train
    wrapper through it (memoization keyed on the flag)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import bn_conv as bc

    rng = np.random.RandomState(1)
    N, H, W, K, O = 2, 8, 8, 128, 256
    x = jnp.asarray(rng.randn(N, H, W, K).astype(np.float32))
    w = jnp.asarray(rng.randn(O, K, 3, 3).astype(np.float32) * 0.05)
    g = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    mu = jnp.asarray(rng.randn(K).astype(np.float32) * 0.1)
    var = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    r = (jnp.asarray(rng.randn(N, H, W, K).astype(np.float32))
         if has_r else None)
    ref = bc.bn_conv3x3_reference(x, g, b, mu, var, w, r=r, stride=stride)
    got = bc.bn_conv3x3_fwd_v2(x, g, b, mu, var, bc._w_hwio(w), r=r,
                               stride=stride, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    # O=256 with default BO=256... force 2 grid steps to exercise the
    # scratch-reuse path (j>0 reads the j==0 prep)
    monkeypatch.setenv("PADDLE_TPU_BNCONV_BO", "128")
    got2 = bc.bn_conv3x3_fwd_v2(x, g, b, mu, var, bc._w_hwio(w), r=r,
                                stride=stride, interpret=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    # env flag routes the memoized train wrapper to the v2 forward
    monkeypatch.setenv("PADDLE_TPU_BNCONV_V2", "1")
    f = bc.make_bn_conv3x3_train(act="relu", has_residual=has_r,
                                 stride=stride, interpret=True)
    args = (x, g, b, mu, var, bc._w_hwio(w)) + ((r,) if has_r else ())
    np.testing.assert_allclose(np.asarray(f(*args)), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
