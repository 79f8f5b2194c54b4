"""Sequence-parallel ring attention tests on the 8-device mesh: exactness vs
dense attention (incl. causal), gradient parity, and a transformer block
training through the program IR with an sp-sharded mesh."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.parallel import ParallelExecutor, make_mesh
from paddle_tpu.ops.ring_attention import attention, ring_attention


def _qkv(B=2, H=4, T=32, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(B, H, T, D).astype(np.float32),
            rng.randn(B, H, T, D).astype(np.float32),
            rng.randn(B, H, T, D).astype(np.float32))


def _jit(fn, *args, **kw):
    """fn(*args, **kw) as ONE program, as a model's step holds it: run op
    by op the ring is hundreds of programs to compile (410 for the zigzag
    schedule's gradients), which was these tests' time."""
    import jax

    return jax.jit(lambda *a: fn(*a, **kw))(*args)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv()
    dense = attention(q, k, v, causal=causal)
    ring = _jit(ring_attention, q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_ring_gradient_matches_dense():
    import jax
    import jax.numpy as jnp

    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(T=16)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    gd = _jit(jax.grad(loss_dense, argnums=(0, 1, 2)), q, k, v)
    gr = _jit(jax.grad(loss_ring, argnums=(0, 1, 2)), q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4)


def test_ring_with_dp_mesh():
    """dp x sp mesh: batch and sequence sharded simultaneously."""
    mesh = make_mesh({"dp": 2, "sp": 4})
    q, k, v = _qkv(B=4, T=16)
    dense = attention(q, k, v)
    ring = _jit(ring_attention, q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_transformer_block_trains_sp_sharded():
    """multi_head_attention layer through the program IR on a dp x sp mesh;
    the attention op dispatches to ring attention."""
    T, D = 16, 32
    x = fluid.layers.data(name="x", shape=[T, D], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    attn = fluid.layers.multi_head_attention(x, x, x, num_heads=4,
                                             causal=True)
    res = fluid.layers.elementwise_add(x, attn)
    ln = fluid.layers.layer_norm(res, begin_norm_axis=2)
    ff = fluid.layers.fc(input=ln, size=D, num_flatten_dims=2, act="relu")
    pooled = fluid.layers.reshape(ff, [-1, T * D])
    logits = fluid.layers.fc(input=pooled, size=2)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    pe = ParallelExecutor(axes={"dp": 2, "sp": 4})
    pe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 2, (16, 1)).astype(np.int64)
    xs = rng.rand(16, T, D).astype(np.float32) + labels[:, :, None] * 0.3
    losses = []
    for _ in range(10):
        (l,) = pe.run(feed={"x": xs, "y": labels}, fetch_list=[loss])
        losses.append(float(l.item()))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    """All-to-all sequence parallelism IS dense attention re-sharded: exact
    match (up to float assoc) with the dense reference."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.ring_attention import attention, \
        ulysses_attention

    rng = np.random.RandomState(0)
    B, H, T, D = 2, 8, 16, 4
    q = rng.randn(B, H, T, D).astype(np.float32)
    k = rng.randn(B, H, T, D).astype(np.float32)
    v = rng.randn(B, H, T, D).astype(np.float32)
    mesh = make_mesh({"sp": 8})
    got = np.asarray(_jit(ulysses_attention, q, k, v, mesh=mesh,
                          causal=causal))
    want = np.asarray(attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.ring_attention import ulysses_attention

    rng = np.random.RandomState(1)
    q = rng.randn(1, 3, 16, 4).astype(np.float32)  # 3 heads, sp=8
    mesh = make_mesh({"sp": 8})
    with pytest.raises(ValueError, match="head count"):
        ulysses_attention(q, q, q, mesh)


def test_transformer_block_trains_sp_alltoall():
    """layers.multi_head_attention(sp_mode='alltoall') trains under an sp
    mesh through the ParallelExecutor."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import ParallelExecutor

    T, D = 8, 32
    seq = fluid.layers.data(name="seq", shape=[T, D], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    attn = fluid.layers.multi_head_attention(seq, seq, seq, num_heads=8,
                                             causal=True,
                                             sp_mode="alltoall")
    res = fluid.layers.elementwise_add(seq, attn)
    flat = fluid.layers.reshape(res, [-1, T * D])
    logits = fluid.layers.fc(input=flat, size=10)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    pe = ParallelExecutor(axes={"dp": 1, "sp": 8})
    pe.run(fluid.default_startup_program())
    rng = np.random.RandomState(2)
    feed = {"seq": rng.rand(4, T, D).astype(np.float32),
            "label": rng.randint(0, 10, (4, 1)).astype(np.int64)}
    losses = [float(np.asarray(pe.run(feed=feed, fetch_list=[loss])[0]
                               ).reshape(-1)[0]) for _ in range(8)]
    assert losses[-1] < losses[0]


def test_ring_flash_matches_dense():
    """Flash-kernel ring path (per-chunk Pallas attention + logsumexp
    merge) vs dense — interpret mode on the CPU mesh."""
    from paddle_tpu.ops.ring_attention import flash_ring_eligible

    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=256, D=32)
    assert flash_ring_eligible(q, mesh, "sp", causal=False, is_train=False)
    dense = attention(q, k, v)
    flash = _jit(ring_attention, q, k, v, mesh=mesh, use_flash=True,
                 interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=2e-4, rtol=2e-4)


def test_ulysses_flash_matches_dense_and_grads():
    """Flash-kernel Ulysses (local full attention as the Pallas kernel),
    inference and training-gradient parity vs dense."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.ring_attention import (flash_ulysses_eligible,
                                                    ulysses_attention)

    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=256, D=32)
    assert flash_ulysses_eligible(q, mesh, "sp")
    for causal in (False, True):
        dense = attention(q, k, v, causal=causal)
        flash = _jit(ulysses_attention, q, k, v, mesh=mesh, causal=causal,
                     use_flash=True, interpret=True)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                                   atol=2e-4, rtol=2e-4)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(ulysses_attention(
            q, k, v, mesh, causal=True, use_flash=True, is_train=True,
            interpret=True) ** 2)

    gd = _jit(jax.grad(loss_dense, argnums=(0, 1, 2)), q, k, v)
    gf = _jit(jax.grad(loss_flash, argnums=(0, 1, 2)), q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3)


def test_flash_sp_eligibility_gates():
    """The static gates hold the kernel to its contract: non-tile chunks
    and wide heads fall back to dense; causal and training ring are
    eligible since r4 (static per-step schedule + ring-level vjp)."""
    from paddle_tpu.ops.ring_attention import (flash_ring_eligible,
                                                    flash_ulysses_eligible)

    mesh = make_mesh({"sp": 2})
    q, _, _ = _qkv(B=1, H=2, T=256, D=32)
    assert flash_ring_eligible(q, mesh, "sp", False, False)
    assert flash_ring_eligible(q, mesh, "sp", True, False)   # causal: r4
    assert flash_ring_eligible(q, mesh, "sp", False, True)   # train: r4
    short, _, _ = _qkv(B=1, H=2, T=64, D=32)  # 32-step chunks: not tiles
    assert not flash_ring_eligible(short, mesh, "sp", False, False)
    assert not flash_ulysses_eligible(short, mesh, "sp")
    wide, _, _ = _qkv(B=1, H=2, T=256, D=256)  # D > one lane tile
    assert not flash_ring_eligible(wide, mesh, "sp", False, False)
    assert not flash_ulysses_eligible(wide, mesh, "sp")


def test_ring_flash_causal_matches_dense():
    """Causal flash ring (diagonal causal kernel at s=0, full kernel for
    past chunks, lse-masked future) vs dense causal attention."""
    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=256, D=32)
    dense = attention(q, k, v, causal=True)
    flash = _jit(ring_attention, q, k, v, mesh=mesh, causal=True,
                 use_flash=True, interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_causal_train_matches_dense(causal):
    """Training through the ring-level custom_vjp (backward rotates dk/dv
    with their chunks against the total logsumexp): gradient parity vs
    dense for both causal and non-causal."""
    import jax
    import jax.numpy as jnp

    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=256, D=32)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(ring_attention(
            q, k, v, mesh, causal=causal, use_flash=True, is_train=True,
            interpret=True) ** 2)

    # the loss and the gradients from one program: the forward's kernels
    # compile once (the compile is this test's time, at any T)
    want, gd = _jit(jax.value_and_grad(loss_dense, argnums=(0, 1, 2)),
                    q, k, v)
    got, gf = _jit(jax.value_and_grad(loss_flash, argnums=(0, 1, 2)),
                   q, k, v)
    assert np.allclose(got, want, rtol=2e-4)
    for name, a, b in zip("qkv", gd, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"d{name}")


def test_zigzag_causal_ring_matches_dense():
    """Load-balanced zigzag causal flash ring (every device computes the
    same 2S+1 full-size blocks; no discarded work) vs dense causal."""
    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=512, D=32)
    dense = attention(q, k, v, causal=True)
    zig = _jit(ring_attention, q, k, v, mesh=mesh, causal=True,
               use_flash=True, schedule="zigzag", interpret=True)
    np.testing.assert_allclose(np.asarray(zig), np.asarray(dense),
                               atol=2e-4, rtol=2e-4)


def test_zigzag_training_grads_match_dense():
    """The balanced schedule's custom_vjp: dq accumulates through the
    same selects, dk/dv pair-accumulators rotate home with their kv pair
    — gradient parity vs dense causal."""
    import jax
    import jax.numpy as jnp

    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=512, D=32)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    def loss_zig(q, k, v):
        return jnp.sum(ring_attention(
            q, k, v, mesh, causal=True, use_flash=True, is_train=True,
            schedule="zigzag", interpret=True) ** 2)

    want, gd = _jit(jax.value_and_grad(loss_dense, argnums=(0, 1, 2)),
                    q, k, v)
    got, gz = _jit(jax.value_and_grad(loss_zig, argnums=(0, 1, 2)), q, k, v)
    assert np.allclose(got, want, rtol=2e-4)
    for name, a, b in zip("qkv", gd, gz):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"d{name}")


def test_zigzag_contract_errors():
    import jax

    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=512, D=32)
    with pytest.raises(ValueError, match="zigzag"):
        ring_attention(q, k, v, mesh, causal=False, use_flash=True,
                       schedule="zigzag", interpret=True)
    bad_t, _, _ = _qkv(B=1, H=2, T=258, D=32)  # 258 % (2*2) != 0
    with pytest.raises(ValueError, match="divisible"):
        ring_attention(bad_t, bad_t, bad_t, mesh, causal=True,
                       use_flash=True, schedule="zigzag", interpret=True)


def test_zigzag_pre_permuted_path():
    """A layer stack can amortize the layout gathers: permute once with
    zigzag_permutation, run with pre_permuted=True, invert once."""
    from paddle_tpu.ops.ring_attention import zigzag_permutation

    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(B=1, H=2, T=512, D=32)
    perm, inv = zigzag_permutation(512, 2)
    zq, zk, zv = (np.take(a, perm, axis=2) for a in (q, k, v))
    out = _jit(ring_attention, zq, zk, zv, mesh=mesh, causal=True,
               use_flash=True, schedule="zigzag", pre_permuted=True,
               interpret=True)
    out = np.take(np.asarray(out), inv, axis=2)
    dense = attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, np.asarray(dense), atol=2e-4,
                               rtol=2e-4)


def test_zigzag_permutation_roundtrip():
    from paddle_tpu.ops.ring_attention import zigzag_permutation

    perm, inv = zigzag_permutation(16, 2)
    x = np.arange(16)
    assert (x[perm][inv] == x).all()
    # device 0's contiguous block = chunks 0 and 3; device 1's = 1 and 2
    assert list(perm[:8]) == [0, 1, 2, 3, 12, 13, 14, 15]
    assert list(perm[8:]) == [4, 5, 6, 7, 8, 9, 10, 11]
