"""Which path a kernel-backed op takes (the dispatch gate), and that a kernel's
failure reaches the caller: nothing falls back."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.ring_attention import attention


def test_sdp_op_dispatches_flash_on_tpu_inference(monkeypatch):
    """The scaled_dot_product_attention emitter takes the Pallas flash path
    exactly when (inference, TPU target, tile-compatible shapes) — checked
    by interposing the kernel entry (CPU runs keep the dense path)."""
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa_mod

    calls = []
    real = fa_mod.flash_attention

    def spy(q, k, v, causal=False, **kw):
        calls.append(q.shape)
        # run in interpret mode so the check executes on CPU
        return real(q, k, v, causal=causal, block_q=64, block_k=64,
                    interpret=True)

    monkeypatch.setattr(fa_mod, "flash_attention", spy)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(1, 2, 128, 16).astype(np.float32))

    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=True)
    monkeypatch.setattr(ctx, "target_platform", lambda: "tpu")
    out = attention_ops.scaled_dot_product_attention(
        ctx, {"Q": [q], "K": [q], "V": [q]}, {"causal": True})["Out"][0]
    assert calls == [(1, 2, 128, 16)]
    # numerics match dense
    from paddle_tpu.ops.ring_attention import attention
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention(q, q, q, causal=True)),
                               rtol=2e-5, atol=2e-5)

    # training mode takes the custom_vjp flash pair, not the plain kernel
    train_calls = []
    real_train = fa_mod.make_flash_train
    monkeypatch.setattr(
        fa_mod, "make_flash_train",
        lambda causal=False, scale=None, interpret=False:
        train_calls.append(1) or real_train(causal=causal, interpret=True))
    ctx2 = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
    monkeypatch.setattr(ctx2, "target_platform", lambda: "tpu")
    attention_ops.scaled_dot_product_attention(
        ctx2, {"Q": [q], "K": [q], "V": [q]}, {"causal": True})
    assert len(calls) == 1 and train_calls == [1]
    # odd T keeps dense
    q2 = jnp.asarray(rng.rand(1, 2, 96, 16).astype(np.float32))
    attention_ops.scaled_dot_product_attention(
        ctx, {"Q": [q2], "K": [q2], "V": [q2]}, {"causal": False})
    assert len(calls) == 1


def test_sdp_op_training_dispatch_uses_flash_vjp(monkeypatch):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    calls = []
    real = fa.make_flash_train
    monkeypatch.setattr(
        fa, "make_flash_train",
        lambda causal=False, scale=None, interpret=False:
        calls.append(1) or real(causal=causal, interpret=True))
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.rand(1, 2, 128, 32).astype(np.float32))
    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
    monkeypatch.setattr(ctx, "target_platform", lambda: "tpu")
    out = attention_ops.scaled_dot_product_attention(
        ctx, {"Q": [q], "K": [q], "V": [q]}, {"causal": True})
    assert calls == [1]
    assert out["Out"][0].shape == q.shape


@pytest.mark.parametrize("platform,mesh,switched_off,want", [
    ("tpu", None, False, True),
    ("tpu", object(), False, False),   # GSPMD cannot partition a Mosaic call
    ("cpu", None, False, False),
    ("tpu", None, True, False),        # PADDLE_TPU_NO_FUSED_KERNELS=1
])
def test_pallas_dispatch_gate(monkeypatch, platform, mesh, switched_off,
                              want):
    """The one gate every fused-kernel emitter asks: a TPU target, no
    mesh, kernels not switched off."""
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import _common

    monkeypatch.delenv("PADDLE_TPU_NO_FUSED_KERNELS", raising=False)
    if switched_off:
        monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
    monkeypatch.setattr(ctx, "target_platform", lambda: platform)
    ctx.mesh = mesh
    assert _common.pallas_dispatch_ok(ctx) is want


def test_mosaic_failure_propagates_and_disables_nothing(monkeypatch):
    """A Mosaic compilation failure in a fused kernel is the caller's
    error, carrying the op's name and the compiler's words: the executor
    neither retraces on the XLA scan path nor switches the fused kernels
    off for the rest of the process.  Injects a Mosaic-looking error from
    the fused LSTM training dispatch and asserts it surfaces on every
    run, with the dispatch gates left as they were."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.lod import LoDTensor
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import _common
    from paddle_tpu.ops.pallas_kernels import lstm as plstm

    H = 128
    rng = np.random.RandomState(0)
    seqs = [rng.randn(t, 4 * H).astype(np.float32) * 0.1
            for t in (5, 3, 5, 2, 5, 5, 4, 5)]
    labels = rng.rand(8, H).astype(np.float32)

    # route the trace at the fused kernel, then blow up like Mosaic would
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    calls = []

    def boom(interpret=False):
        def f(*a, **kw):
            calls.append(1)
            raise RuntimeError(
                "Mosaic failed to lower: INTERNAL: unsupported shape")
        return f

    monkeypatch.setattr(plstm, "make_lstm_train", boom)
    try:
        fluid.reset()
        x = fluid.layers.sequence_data("fbx", shape=[4 * H],
                                       dtype="float32")
        hidden, _ = fluid.layers.dynamic_lstm(x, size=4 * H)
        last = fluid.layers.sequence_pool(hidden, pool_type="last")
        y = fluid.layers.data("fby", shape=[H], dtype="float32")
        cost = fluid.layers.mean(fluid.layers.square_error_cost(last, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.5).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {"fbx": LoDTensor.from_sequences(seqs), "fby": labels}
        for attempt in (1, 2):  # the second run takes no other path either
            with warnings.catch_warnings():
                warnings.simplefilter("error")  # and nothing merely warns
                with pytest.raises(
                        Exception,
                        match=r"'lstm'[\s\S]*Mosaic failed to lower"):
                    exe.run(feed=feed, fetch_list=[cost])
            assert len(calls) == attempt
            assert _common.kernels_enabled()
    finally:
        fluid.reset()


def test_program_errors_propagate():
    """An ordinary program error surfaces unchanged from Executor.run."""
    import numpy as np
    import paddle_tpu as fluid

    fluid.reset()
    try:
        x = fluid.layers.data("npx", shape=[4], dtype="float32")
        y = fluid.layers.reshape(x, shape=[-1, 3])  # 4 is not divisible by 3
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(Exception):
            exe.run(feed={"npx": np.zeros((2, 4), np.float32)},
                    fetch_list=[y])
    finally:
        fluid.reset()
