"""The fused LSTM and GRU kernels in interpret mode (same code path as the chip)."""

import jax
import jax.numpy as jnp
import numpy as np


def test_pallas_lstm_matches_scan_reference():
    """Fused LSTM time-loop kernel vs step-by-step numpy (interpret mode)."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels.lstm import lstm_forward, usable

    B, T, H = 8, 6, 128
    rng = np.random.RandomState(0)
    x = (rng.randn(B, T, 4 * H) * 0.3).astype(np.float32)
    w = (rng.randn(H, 4 * H) * 0.1).astype(np.float32)
    h0 = np.zeros((B, H), np.float32)
    c0 = np.zeros((B, H), np.float32)
    lengths = np.array([6, 6, 4, 6, 2, 6, 6, 5], np.int32)
    assert usable(x, {})

    hs, cs, hT, cT = lstm_forward(jnp.asarray(x), jnp.asarray(h0),
                                  jnp.asarray(c0), jnp.asarray(w),
                                  jnp.asarray(lengths), interpret=True)

    h, c = h0.copy(), c0.copy()
    out = np.zeros((B, T, H), np.float32)
    for t in range(T):
        g = x[:, t] + h @ w
        i = 1 / (1 + np.exp(-g[:, :H]))
        f = 1 / (1 + np.exp(-g[:, H:2 * H]))
        cand = np.tanh(g[:, 2 * H:3 * H])
        o = 1 / (1 + np.exp(-g[:, 3 * H:]))
        cn = f * c + i * cand
        hn = o * np.tanh(cn)
        m = (t < lengths).astype(np.float32)[:, None]
        h, c = m * hn + (1 - m) * h, m * cn + (1 - m) * c
        out[:, t] = h
    np.testing.assert_allclose(np.asarray(hs), out, atol=5e-4)
    np.testing.assert_allclose(np.asarray(cs)[:, -1], c, atol=5e-4)
    np.testing.assert_allclose(np.asarray(hT), h, atol=5e-4)
    np.testing.assert_allclose(np.asarray(cT), c, atol=5e-4)


def test_pallas_lstm_usable_gate():
    import numpy as np
    from paddle_tpu.ops.pallas_kernels.lstm import usable

    x = np.zeros((8, 4, 512), np.float32)
    assert usable(x, {})
    # is_reverse is handled by reverse-within-length views, not gated out
    assert usable(x, {"is_reverse": True})
    assert not usable(x, {"gate_activation": "tanh"})
    assert not usable(np.zeros((7, 4, 512), np.float32), {})  # B % 8
    assert not usable(np.zeros((8, 4, 4 * 100), np.float32), {})  # H % 128


def test_pallas_lstm_fused_backward_matches_scan_grads():
    """The fused BPTT kernel's (dx, dh0, dc0, dw) vs jax.grad of a plain
    scan with identical masked semantics (interpret mode)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels.lstm import make_lstm_train

    B, T, H = 8, 5, 128
    rng = np.random.RandomState(3)
    x = jnp.asarray((rng.randn(B, T, 4 * H) * 0.3).astype(np.float32))
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.05).astype(np.float32))
    h0 = jnp.asarray((rng.randn(B, H) * 0.2).astype(np.float32))
    c0 = jnp.asarray((rng.randn(B, H) * 0.2).astype(np.float32))
    lengths = jnp.asarray(np.array([5, 4, 5, 2, 5, 3, 5, 1], np.int32))
    fused = make_lstm_train(interpret=True)

    def ref(x, h0, c0, w):
        mask = (jnp.arange(T)[None, :] < lengths[:, None]).astype(
            jnp.float32)

        def step(carry, tup):
            h, c = carry
            xt, mt = tup
            g = xt + h @ w
            i = jax.nn.sigmoid(g[:, :H])
            f = jax.nn.sigmoid(g[:, H:2 * H])
            u = jnp.tanh(g[:, 2 * H:3 * H])
            o = jax.nn.sigmoid(g[:, 3 * H:])
            cn = f * c + i * u
            hn = o * jnp.tanh(cn)
            m = mt[:, None]
            hn, cn = m * hn + (1 - m) * h, m * cn + (1 - m) * c
            return (hn, cn), (hn, cn)

        _, (hs, cs) = jax.lax.scan(step, (h0, c0),
                                   (jnp.moveaxis(x, 1, 0), mask.T))
        return jnp.moveaxis(hs, 0, 1), jnp.moveaxis(cs, 0, 1)

    def loss(fn):
        def inner(x, h0, c0, w):
            hs, cs = fn(x, h0, c0, w)
            weights = jnp.cos(jnp.arange(H))
            return (hs * weights).sum() + 0.5 * (cs ** 2).sum()
        return inner

    fused_fn = lambda x, h0, c0, w: fused(x, h0, c0, w, lengths)
    g1, g2 = (jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2, 3)))(
        x, h0, c0, w) for fn in (fused_fn, ref))     # a program each
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_lstm_op_training_dispatch_uses_fused_kernel(monkeypatch):
    """The lstm emitter routes TRAINING traces through the custom_vjp fused
    kernel when the target is TPU (forward compared against the scan)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops import sequence_ops
    from paddle_tpu.ops.pallas_kernels import lstm as plstm

    calls = []
    real = plstm.make_lstm_train

    def spy(interpret=False):
        calls.append("train")
        return real(interpret=True)  # CPU test: interpret mode

    monkeypatch.setattr(plstm, "make_lstm_train", spy)
    B, T, H = 8, 4, 128
    rng = np.random.RandomState(1)
    x = jnp.asarray((rng.randn(B, T, 4 * H) * 0.2).astype(np.float32))
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.05).astype(np.float32))
    lengths = jnp.asarray(np.full(B, T, np.int32))
    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
    monkeypatch.setattr(ctx, "target_platform", lambda: "tpu")
    ins = {"Input": [x], "Weight": [w], "Length": [lengths]}
    out = sequence_ops.lstm(ctx, ins, {})
    assert calls == ["train"]
    assert out["Hidden"][0].shape == (B, T, H)


def test_lstm_fused_training_through_desc_autodiff(monkeypatch):
    """End-to-end: a fluid program with dynamic_lstm trains through
    append_backward/generic_grad with the fused custom_vjp kernel active
    (interpret mode) and matches the scan path's losses — proving the
    custom_vjp composes with the desc-level autodiff (zero cotangents for
    the unused Cell output included)."""
    import numpy as np
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.lod import LoDTensor
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import lstm as plstm

    H = 128
    rng = np.random.RandomState(0)
    seqs = [rng.randn(t, 4 * H).astype(np.float32) * 0.1
            for t in (5, 3, 5, 2, 5, 5, 4, 5)]
    labels = rng.rand(8, H).astype(np.float32)

    def build_and_train(steps=4):
        fluid.reset()
        x = fluid.layers.sequence_data("plx", shape=[4 * H],
                                       dtype="float32")
        hidden, _ = fluid.layers.dynamic_lstm(x, size=4 * H)
        last = fluid.layers.sequence_pool(hidden, pool_type="last")
        y = fluid.layers.data("ply", shape=[H], dtype="float32")
        cost = fluid.layers.mean(fluid.layers.square_error_cost(last, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.5).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        out = []
        feed = {"plx": LoDTensor.from_sequences(seqs), "ply": labels}
        for _ in range(steps):
            (l,) = exe.run(feed=feed, fetch_list=[cost])
            out.append(float(np.asarray(l).reshape(())))
        return out

    scan_losses = build_and_train()

    # force the fused path: TPU-targeted trace + interpret-mode kernels
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    real_train = plstm.make_lstm_train
    real_fwd = plstm.lstm_forward
    used = []
    monkeypatch.setattr(
        plstm, "make_lstm_train",
        lambda interpret=False: used.append(1) or real_train(
            interpret=True))
    monkeypatch.setattr(
        plstm, "lstm_forward",
        lambda *a, **kw: real_fwd(*a, **{**kw, "interpret": True}))
    fused_losses = build_and_train()
    assert used, "fused training kernel was not dispatched"
    np.testing.assert_allclose(fused_losses, scan_losses, rtol=2e-3,
                               atol=2e-4)
    assert fused_losses[-1] < fused_losses[0]  # it actually trains


def test_pallas_gru_forward_and_backward_match_scan():
    """Fused GRU kernel pair vs a plain scan with identical semantics
    (interpret mode), forward and all three gradients."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import gru as pgru

    B, T, H = 8, 6, 128
    rng = np.random.RandomState(7)
    x = jnp.asarray((rng.randn(B, T, 3 * H) * 0.3).astype(np.float32))
    h0 = jnp.asarray((rng.randn(B, H) * 0.2).astype(np.float32))
    w = jnp.asarray((rng.randn(H, 3 * H) * 0.05).astype(np.float32))
    lengths = jnp.asarray(np.array([6, 6, 5, 4, 6, 3, 6, 2], np.int32))
    assert pgru.usable(x, {}) and pgru.usable_train(x, {})
    fused = pgru.make_gru_train(interpret=True)

    def ref(x, h0, w):
        mask = (jnp.arange(T)[None, :] < lengths[:, None]).astype(
            jnp.float32)
        wg, wc = w[:, :2 * H], w[:, 2 * H:]

        def step(h, tup):
            xt, mt = tup
            g = xt[:, :2 * H] + h @ wg
            u = jax.nn.sigmoid(g[:, :H])
            r = jax.nn.sigmoid(g[:, H:])
            c = jnp.tanh(xt[:, 2 * H:] + (r * h) @ wc)
            hn = u * h + (1 - u) * c
            m = mt[:, None]
            hn = m * hn + (1 - m) * h
            return hn, hn

        _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(x, 1, 0), mask.T))
        return jnp.moveaxis(hs, 0, 1)

    np.testing.assert_allclose(
        np.asarray(fused(x, h0, w, lengths)), np.asarray(ref(x, h0, w)),
        atol=1e-5)
    wv = jnp.cos(jnp.arange(H))
    g1, g2 = (jax.jit(jax.grad(lambda *a, fn=fn: (fn(*a) * wv).sum(),
                               argnums=(0, 1, 2)))(x, h0, w)
              for fn in (lambda *a: fused(*a, lengths), ref))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_gru_op_training_dispatch_uses_fused_kernel(monkeypatch):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops import sequence_ops
    from paddle_tpu.ops.pallas_kernels import gru as pgru

    calls = []
    real = pgru.make_gru_train
    monkeypatch.setattr(pgru, "make_gru_train",
                        lambda interpret=False: calls.append(1)
                        or real(interpret=True))
    B, T, H = 8, 4, 128
    rng = np.random.RandomState(2)
    x = jnp.asarray((rng.randn(B, T, 3 * H) * 0.2).astype(np.float32))
    w = jnp.asarray((rng.randn(H, 3 * H) * 0.05).astype(np.float32))
    lengths = jnp.asarray(np.full(B, T, np.int32))
    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
    monkeypatch.setattr(ctx, "target_platform", lambda: "tpu")
    out = sequence_ops.gru(ctx, {"Input": [x], "Weight": [w],
                                 "Length": [lengths]}, {})
    assert calls == [1]
    assert out["Hidden"][0].shape == (B, T, H)


def test_fused_rnn_kernels_bf16():
    """bf16 in/out (the bench dtype) flows through both fused training
    kernels with f32 accumulation and finite grads."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import gru as pgru
    from paddle_tpu.ops.pallas_kernels import lstm as plstm

    rng = np.random.RandomState(0)
    B, T, H = 8, 4, 128
    h0 = jnp.zeros((B, H), jnp.bfloat16)
    c0 = jnp.zeros((B, H), jnp.bfloat16)
    L = jnp.full((B,), T, jnp.int32)
    x = jnp.asarray((rng.randn(B, T, 4 * H) * 0.2).astype(np.float32),
                    dtype=jnp.bfloat16)
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.05).astype(np.float32),
                    dtype=jnp.bfloat16)
    f = plstm.make_lstm_train(interpret=True)
    g = jax.grad(lambda x, w: f(x, h0, c0, w, L)[0].astype(
        jnp.float32).sum(), argnums=(0, 1))(x, w)
    assert g[0].dtype == jnp.bfloat16 and g[1].dtype == jnp.bfloat16
    assert bool(jnp.isfinite(g[0].astype(jnp.float32)).all())

    xg = jnp.asarray((rng.randn(B, T, 3 * H) * 0.2).astype(np.float32),
                     dtype=jnp.bfloat16)
    wg = jnp.asarray((rng.randn(H, 3 * H) * 0.05).astype(np.float32),
                     dtype=jnp.bfloat16)
    fg = pgru.make_gru_train(interpret=True)
    gg = jax.grad(lambda x, w: fg(x, h0, w, L).astype(jnp.float32).sum(),
                  argnums=(0, 1))(xg, wg)
    assert gg[0].dtype == jnp.bfloat16 and gg[1].dtype == jnp.bfloat16
    assert bool(jnp.isfinite(gg[0].astype(jnp.float32)).all())


def test_fused_rnn_reverse_direction_matches_scan(monkeypatch):
    """is_reverse rides the fused kernels via reverse-within-length views;
    outputs must match the reversed scan (the bidirectional-net layer)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops import sequence_ops
    from paddle_tpu.ops.pallas_kernels import lstm as plstm

    real = plstm.lstm_forward
    monkeypatch.setattr(
        plstm, "lstm_forward",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    B, T, H = 8, 6, 128
    rng = np.random.RandomState(9)
    x = jnp.asarray((rng.randn(B, T, 4 * H) * 0.2).astype(np.float32))
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.05).astype(np.float32))
    lengths = jnp.asarray(np.array([6, 5, 4, 3, 6, 2, 6, 1], np.int32))
    ins = {"Input": [x], "Weight": [w], "Length": [lengths]}

    # nonzero initial state: pad positions must carry h0/c0 exactly like
    # the reversed scan does (bit-level convention, not just masked match)
    h0 = jnp.asarray((rng.randn(B, H) * 0.1).astype(np.float32))
    c0 = jnp.asarray((rng.randn(B, H) * 0.1).astype(np.float32))
    ins = {**ins, "H0": [h0], "C0": [c0]}
    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=True)
    monkeypatch.setattr(ctx, "target_platform", lambda: "tpu")
    out_fused = sequence_ops.lstm(ctx, ins, {"is_reverse": True})
    ctx2 = reg.EmitContext(jax.random.PRNGKey(0), is_test=True)  # cpu path
    out_scan = sequence_ops.lstm(ctx2, ins, {"is_reverse": True})
    np.testing.assert_allclose(np.asarray(out_fused["Hidden"][0]),
                               np.asarray(out_scan["Hidden"][0]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_fused["Cell"][0]),
                               np.asarray(out_scan["Cell"][0]), atol=2e-5)


def test_fused_rnn_reverse_training_and_gru(monkeypatch):
    """Reverse direction through the TRAINING custom_vjp paths (gradients
    vs the reversed scan) and the GRU reverse branch."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops import sequence_ops
    from paddle_tpu.ops.pallas_kernels import gru as pgru
    from paddle_tpu.ops.pallas_kernels import lstm as plstm

    B, T, H = 8, 5, 128
    rng = np.random.RandomState(11)
    xl = jnp.asarray((rng.randn(B, T, 4 * H) * 0.2).astype(np.float32))
    wl = jnp.asarray((rng.randn(H, 4 * H) * 0.05).astype(np.float32))
    lengths = jnp.asarray(np.array([5, 4, 3, 2, 5, 1, 5, 5], np.int32))

    import importlib
    lstm_mod = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.lstm")
    real_train = lstm_mod.make_lstm_train
    monkeypatch.setattr(lstm_mod, "make_lstm_train",
                        lambda interpret=False: real_train(interpret=True))

    def loss_emitter(x, w, is_test):
        ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=is_test)
        monkeypatch.setattr(ctx, "target_platform",
                            lambda: "tpu" if not is_test else "cpu")
        out = sequence_ops.lstm(
            ctx, {"Input": [x], "Weight": [wl], "Length": [lengths]},
            {"is_reverse": True})
        return out["Hidden"][0].sum()

    g_fused = jax.grad(lambda x: loss_emitter(x, wl, False))(xl)
    # scan reference gradient (cpu target)
    def loss_scan(x):
        ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
        out = sequence_ops.lstm(
            ctx, {"Input": [x], "Weight": [wl], "Length": [lengths]},
            {"is_reverse": True})
        return out["Hidden"][0].sum()
    g_scan = jax.grad(loss_scan)(xl)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_scan),
                               atol=3e-4)

    # GRU reverse inference branch vs scan
    gru_mod = importlib.import_module("paddle_tpu.ops.pallas_kernels.gru")
    real_g = gru_mod.gru_forward
    monkeypatch.setattr(
        gru_mod, "gru_forward",
        lambda *a, **kw: real_g(*a, **{**kw, "interpret": True}))
    xg = jnp.asarray((rng.randn(B, T, 3 * H) * 0.2).astype(np.float32))
    wg = jnp.asarray((rng.randn(H, 3 * H) * 0.05).astype(np.float32))
    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=True)
    monkeypatch.setattr(ctx, "target_platform", lambda: "tpu")
    fused = sequence_ops.gru(
        ctx, {"Input": [xg], "Weight": [wg], "Length": [lengths]},
        {"is_reverse": True})["Hidden"][0]
    ctx2 = reg.EmitContext(jax.random.PRNGKey(0), is_test=True)
    scan = sequence_ops.gru(
        ctx2, {"Input": [xg], "Weight": [wg], "Length": [lengths]},
        {"is_reverse": True})["Hidden"][0]
    np.testing.assert_allclose(np.asarray(fused), np.asarray(scan),
                               atol=2e-5)
