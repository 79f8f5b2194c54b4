"""Diffusion U-Net family (models/unet.py): DDPM noise-prediction
training converges, the cloned test program serves ancestral sampling on
the trained scope, and the pieces (time embedding, transposed-conv
shapes) hold their contracts."""

import numpy as np

import paddle_tpu as fluid
from _kernel_refs import _startup
from paddle_tpu.models import unet


_DRAWN = {}     # the toy U-Net's first weights: two tests build that program


def _toy_batch(n=16, size=8):
    base = np.outer(np.hanning(size), np.hanning(size))
    return np.stack([base for _ in range(n)])[:, None].astype(np.float32)


def test_ddpm_trains_and_samples():
    loss, eps_hat, infer_prog = unet.build_ddpm_train_program(
        image_size=8, channels=1, base_ch=8, ch_mults=(1, 2),
        learning_rate=2e-3)
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, _DRAWN)
    sched = unet.ddpm_schedule(T=50)
    rng = np.random.RandomState(0)
    x0 = _toy_batch()
    ls = []
    for _ in range(30):
        (l,) = exe.run(feed=unet.ddpm_feed(x0, sched, rng),
                       fetch_list=[loss])
        ls.append(float(np.asarray(l).ravel()[0]))
    assert ls[-1] < ls[0] * 0.8, (ls[0], ls[-1])

    x = unet.ddpm_sample(exe, infer_prog, eps_hat, sched, (2, 1, 8, 8),
                         rng, steps=10)
    assert x.shape == (2, 1, 8, 8)
    assert np.isfinite(x).all()


def test_time_embedding_distinguishes_timesteps():
    """Different timesteps produce different embeddings; equal ones
    match (the conditioning signal the denoiser depends on)."""
    from paddle_tpu import layers

    t = layers.data("t", shape=[1], dtype="float32")
    emb = unet._time_embedding(t, 16)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    (e,) = exe.run(feed={"t": np.array([[0.0], [5.0], [5.0], [40.0]],
                                       np.float32)},
                   fetch_list=[emb])
    e = np.asarray(e)
    assert e.shape == (4, 16)
    np.testing.assert_allclose(e[1], e[2], rtol=1e-6)
    assert np.abs(e[0] - e[1]).max() > 0.1
    assert np.abs(e[1] - e[3]).max() > 0.1


def test_conv2d_transpose_static_shape():
    """conv2d_transpose now carries its static output shape (consumers
    like concat need it — the U-Net decoder path)."""
    from paddle_tpu import layers

    img = layers.data("ti", shape=[4, 8, 8], dtype="float32")
    up = layers.conv2d_transpose(img, num_filters=6, filter_size=2,
                                 stride=2)
    assert tuple(up.shape)[1:] == (6, 16, 16), up.shape
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    (o,) = exe.run(feed={"ti": np.ones((2, 4, 8, 8), np.float32)},
                   fetch_list=[up])
    assert np.asarray(o).shape == (2, 6, 16, 16)


def test_ddpm_trains_dp_sharded():
    """The diffusion family runs SPMD like every other: dp=8 over the
    CPU mesh, same program, finite decreasing loss."""
    from paddle_tpu.parallel import ParallelExecutor

    loss, _, _ = unet.build_ddpm_train_program(
        image_size=8, channels=1, base_ch=8, ch_mults=(1, 2),
        learning_rate=2e-3)
    pe = ParallelExecutor(axes={"dp": 8})
    pe.run(fluid.default_startup_program())
    sched = unet.ddpm_schedule(T=50)
    rng = np.random.RandomState(0)
    x0 = _toy_batch(16)
    ls = []
    for _ in range(12):
        (l,) = pe.run(feed=unet.ddpm_feed(x0, sched, rng),
                      fetch_list=[loss])
        ls.append(float(np.asarray(l).ravel()[0]))
    assert np.isfinite(ls).all()
    assert ls[-1] < ls[0], (ls[0], ls[-1])


def test_ddim_sampler_deterministic_and_finite():
    """DDIM (eta=0): deterministic given the same starting noise — two
    runs from the same rng state agree exactly — and finite at few
    steps."""
    loss, eps_hat, infer_prog = unet.build_ddpm_train_program(
        image_size=8, channels=1, base_ch=8, ch_mults=(1, 2),
        learning_rate=2e-3)
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, _DRAWN)
    sched = unet.ddpm_schedule(T=50)
    rng = np.random.RandomState(1)
    for _ in range(5):
        exe.run(feed=unet.ddpm_feed(_toy_batch(8), sched, rng),
                fetch_list=[loss])
    a = unet.ddim_sample(exe, infer_prog, eps_hat, sched, (2, 1, 8, 8),
                         np.random.RandomState(7), steps=8)
    b = unet.ddim_sample(exe, infer_prog, eps_hat, sched, (2, 1, 8, 8),
                         np.random.RandomState(7), steps=8)
    assert np.isfinite(a).all()
    np.testing.assert_allclose(a, b)
