"""InferenceTranspiler.fuse_batch_norm: conv+BN constant-folding for
inference programs (reference merge_model capability,
scripts/submit_local.sh.in:186) — numerics-equality tested."""

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _startup
from paddle_tpu import layers


_DRAWN = {}     # what `_build`'s startup program drew


def _build(layout, dtype):
    shape = [3, 16, 16] if layout == "NCHW" else [16, 16, 3]
    img = layers.data("ftx", shape=shape, dtype=dtype)
    c1 = layers.conv2d(img, num_filters=8, filter_size=3, padding=1,
                       bias_attr=False, data_format=layout)
    b1 = layers.batch_norm(c1, act="relu", data_layout=layout)
    c2 = layers.conv2d(b1, num_filters=4, filter_size=3, padding=1,
                       bias_attr=False, data_format=layout)
    b2 = layers.batch_norm(c2, act=None, data_layout=layout)
    out = layers.cast(b2, "float32") if dtype != "float32" else b2
    return out


@pytest.mark.parametrize("layout,dtype", [("NCHW", "float32"),
                                          ("NHWC", "float32"),
                                          ("NHWC", "bfloat16")])
def test_fuse_batch_norm_matches_unfused(layout, dtype):
    out = _build(layout, dtype)
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, _DRAWN.setdefault(dtype, {}))   # whatever the layout

    # non-trivial running stats: startup leaves mean=0/var=1, under which a
    # broken fold could pass by accident
    rng = np.random.RandomState(7)
    scope = fluid.global_scope()
    for op in prog.global_block().ops:
        if op.type != "batch_norm":
            continue
        C = None
        for slot, fill in (("Mean", None), ("Variance", None),
                           ("Scale", None), ("Bias", None)):
            name = op.inputs[slot][0]
            cur = np.asarray(scope.find_np(name))
            C = cur.shape[0]
            if slot == "Variance":
                val = rng.rand(C).astype(np.float32) + 0.5
            else:
                val = rng.randn(C).astype(np.float32) * 0.3 + (
                    1.0 if slot == "Scale" else 0.0)
            scope.set(name, val)

    shape = (2, 3, 16, 16) if layout == "NCHW" else (2, 16, 16, 3)
    from paddle_tpu.framework.core import np_dtype
    import jax.numpy as jnp
    feed = {"ftx": jnp.asarray(rng.rand(*shape).astype(np.float32),
                               dtype=np_dtype(dtype))}
    (before,) = exe.run(prog, feed=feed, fetch_list=[out])

    n = fluid.fuse_batch_norm(prog, scope)
    assert n == 2
    assert not any(op.type == "batch_norm"
                   for op in prog.global_block().ops)
    (after,) = exe.run(prog, feed=feed, fetch_list=[out])
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               atol=tol, rtol=tol)


def test_save_inference_model_fold_batch_norm_roundtrip(tmp_path):
    """save_inference_model(fold_batch_norm=True) ships folded weights in
    the saved model, leaves the live scope untouched, and the loaded model
    reproduces the unfolded outputs."""
    out = _build("NCHW", "float32")
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, _DRAWN.setdefault("float32", {}))
    rng = np.random.RandomState(3)
    scope = fluid.global_scope()
    for op in prog.global_block().ops:
        if op.type == "batch_norm":
            for slot in ("Mean", "Variance", "Scale", "Bias"):
                name = op.inputs[slot][0]
                C = np.asarray(scope.find_np(name)).shape[0]
                val = (rng.rand(C) + 0.5 if slot == "Variance"
                       else rng.randn(C) * 0.3).astype(np.float32)
                scope.set(name, val)

    feed = {"ftx": rng.rand(2, 3, 16, 16).astype(np.float32)}
    (before,) = exe.run(prog, feed=feed, fetch_list=[out])
    filt0 = prog.global_block().ops[0].inputs["Filter"][0]
    w_live = np.asarray(scope.find_np(filt0)).copy()

    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["ftx"], [out], exe,
                                  fold_batch_norm=True)
    # live scope untouched by the fold (child-scope overlay)
    np.testing.assert_array_equal(np.asarray(scope.find_np(filt0)), w_live)

    prog2, feeds, fetches = fluid.io.load_inference_model(d, exe)
    assert not any(op.type == "batch_norm"
                   for op in prog2.global_block().ops)
    (after,) = exe.run(prog2, feed={feeds[0]: feed["ftx"]},
                       fetch_list=fetches)
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               atol=2e-5, rtol=2e-5)


def test_fuse_refuses_training_program():
    img = layers.data("ftr", shape=[3, 8, 8], dtype="float32")
    c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                      bias_attr=False)
    b = layers.batch_norm(c)
    y = layers.data("ftry", shape=[1], dtype="int64")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(b, size=3), y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    with pytest.raises(ValueError, match="inference-only"):
        fluid.fuse_batch_norm(fluid.default_main_program(),
                              fluid.global_scope())


def test_fuse_skips_shared_conv_output():
    """conv out read by BN AND someone else: the rescaled filter would
    corrupt the other consumer — must skip."""
    img = layers.data("fts", shape=[3, 8, 8], dtype="float32")
    c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                      bias_attr=False)
    b = layers.batch_norm(c)
    other = layers.reduce_mean(c)  # second consumer of the conv output
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    n = fluid.fuse_batch_norm(prog, fluid.global_scope())
    assert n == 0
    assert any(op.type == "batch_norm" for op in prog.global_block().ops)


def test_folded_weights_pinned_to_device_buffers():
    """The fold writes numpy filters into the scope; the executor must
    promote them to device buffers on first use and KEEP them there.
    Re-staging host arrays every run re-uploads the whole folded weight
    set each step (~100 MB on the bs16 infer bench)."""
    import jax

    out = _build("NHWC", "float32")
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    n = fluid.fuse_batch_norm(prog, scope)
    assert n >= 1
    folded = [name for name in scope.local_names()
              if isinstance(scope.find(name), np.ndarray)]
    assert folded, "fold should have left host arrays in the scope"

    feed = {"ftx": np.random.RandomState(0).rand(2, 16, 16, 3)
            .astype(np.float32)}
    exe.run(prog, feed=feed, fetch_list=[out])
    for name in folded:
        v = scope.find(name)
        assert isinstance(v, jax.Array), (
            f"{name} still a host array after a run — every subsequent "
            f"step would re-upload it")
