#!/usr/bin/env python
"""On-chip microbenchmarks: fused Pallas kernels vs their XLA fallbacks.

Run on a real TPU (no args):
    python tools/bench_kernels.py

Covers the three custom-fusion-tier kernels (SURVEY.md §2.10): LSTM
train step (fused fwd+BPTT vs lax.scan), GRU train step, and flash
attention train step (custom_vjp pair vs XLA-fused dense)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


ROWS = []  # row dicts ({kernel, shape, *_ms, speedup} or {kernel, error,
# traceback}) — the end-of-run JSON summary


def _force(out):
    """Completion barrier: a device->host read of one element of every
    leaf."""
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        np.asarray(leaf.ravel()[0] if hasattr(leaf, "ravel") else leaf)


def _timeit(f, *args, iters=20):
    f(*args)  # compile
    for _ in range(3):
        out = f(*args)
    _force(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    _force(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _row(name, shape, fused_ms, fallback_ms, fallback_name):
    """Print the human line AND remember it for the final JSON summary."""
    ROWS.append({"kernel": name, "shape": shape,
                 "fused_ms": round(fused_ms, 2),
                 f"{fallback_name}_ms": round(fallback_ms, 2),
                 "speedup": round(fallback_ms / fused_ms, 2)
                 if fused_ms else None})
    print(f"{name} {shape}: fused {fused_ms:.2f} ms vs "
          f"{fallback_name} {fallback_ms:.2f} ms")


def bench_lstm():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import lstm as plstm
    from paddle_tpu.ops.sequence_ops import _lstm_scan

    B, T, H = 64, 96, 512
    rng = np.random.RandomState(0)
    x = jnp.asarray((rng.randn(B, T, 4 * H) * 0.1).astype(np.float32))
    h0 = jnp.zeros((B, H), jnp.float32)
    c0 = jnp.zeros((B, H), jnp.float32)
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.05).astype(np.float32))
    lengths = jnp.full((B,), T, jnp.int32)
    fused = plstm.make_lstm_train()
    sig = jax.nn.sigmoid

    @jax.jit
    def fused_step(x, h0, c0, w):
        def loss(x, w):
            hs, cs = fused(x, h0, c0, w, lengths)
            return hs.sum() + cs.sum()
        return jax.grad(loss, argnums=(0, 1))(x, w)

    @jax.jit
    def scan_step(x, h0, c0, w):
        def loss(x, w):
            hs, cs, _, _ = _lstm_scan(x, h0, c0, w, lengths, sig, jnp.tanh,
                                      jnp.tanh)
            return hs.sum() + cs.sum()
        return jax.grad(loss, argnums=(0, 1))(x, w)

    _row("lstm_train", f"bs{B} T{T} h{H}",
         _timeit(fused_step, x, h0, c0, w),
         _timeit(scan_step, x, h0, c0, w), "scan")


def bench_gru():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import gru as pgru
    from paddle_tpu.ops.sequence_ops import _gru_scan

    B, T, H = 64, 96, 512
    rng = np.random.RandomState(1)
    x = jnp.asarray((rng.randn(B, T, 3 * H) * 0.1).astype(np.float32))
    h0 = jnp.zeros((B, H), jnp.float32)
    w = jnp.asarray((rng.randn(H, 3 * H) * 0.05).astype(np.float32))
    lengths = jnp.full((B,), T, jnp.int32)
    fused = pgru.make_gru_train()

    @jax.jit
    def fused_step(x, h0, w):
        return jax.grad(
            lambda x, w: fused(x, h0, w, lengths).sum(),
            argnums=(0, 1))(x, w)

    @jax.jit
    def scan_step(x, h0, w):
        def loss(x, w):
            hs, _ = _gru_scan(x, h0, w, lengths, jax.nn.sigmoid, jnp.tanh)
            return hs.sum()
        return jax.grad(loss, argnums=(0, 1))(x, w)

    _row("gru_train", f"bs{B} T{T} h{H}",
         _timeit(fused_step, x, h0, w),
         _timeit(scan_step, x, h0, w), "scan")


def bench_flash():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa
    from paddle_tpu.parallel.ring_attention import attention as dense

    B, H, T, D = 8, 16, 2048, 64
    rng = np.random.RandomState(2)
    mk = lambda: jnp.asarray(
        (rng.randn(B, H, T, D) * 0.2).astype(np.float32), dtype=jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    fused = fa.make_flash_train(causal=True)

    @jax.jit
    def fused_step(q, k, v):
        return jax.grad(lambda *a: fused(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    @jax.jit
    def dense_step(q, k, v):
        return jax.grad(
            lambda *a: dense(*a, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    _row("flash_train", f"b{B} h{H} T{T} d{D} bf16",
         _timeit(fused_step, q, k, v),
         _timeit(dense_step, q, k, v), "dense")


def bench_flash_long():
    """The long-context point flash exists for: at T=16k the dense path's
    [T,T] scores (16 GB in f32 per head-batch) exceed the chip — dense
    fails to compile, flash trains.  Record flash's time and dense's
    failure as the row."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa
    from paddle_tpu.parallel.ring_attention import attention as dense

    B, H, T, D = 1, 16, 16384, 64
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(
        (rng.randn(B, H, T, D) * 0.2).astype(np.float32), dtype=jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    fused = fa.make_flash_train(causal=True)

    @jax.jit
    def fused_step(q, k, v):
        return jax.grad(lambda *a: fused(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    fused_ms = _timeit(fused_step, q, k, v, iters=5)
    row = {"kernel": "flash_train_long", "shape": f"b{B} h{H} T{T} d{D} bf16",
           "fused_ms": round(fused_ms, 2)}
    try:
        @jax.jit
        def dense_step(q, k, v):
            return jax.grad(
                lambda *a: dense(*a, causal=True).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)

        dense_ms = _timeit(dense_step, q, k, v, iters=5)
        row.update(dense_ms=round(dense_ms, 2),
                   speedup=round(dense_ms / fused_ms, 2))
    except Exception as e:  # noqa: BLE001 — the failure IS the datapoint
        row["dense_error"] = f"{type(e).__name__}: {e}"[:200]
    ROWS.append(row)
    print(f"flash_train_long {row['shape']}: fused {fused_ms:.2f} ms, "
          f"dense {row.get('dense_ms', row.get('dense_error'))}")


def bench_bn_matmul():
    """Fused BN+ReLU->matmul vs the XLA-composed reference, fwd+bwd, on
    the ResNet stage-4 next-conv1 shape (bs128: M=6272, K=2048, N=512 —
    the biggest eligible fusion site)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import bn_matmul as bm

    M, K, N = 6272, 2048, 512
    rng = np.random.RandomState(3)
    x = jnp.asarray((rng.randn(M, K) * 0.2).astype(np.float32),
                    dtype=jnp.bfloat16)
    w = jnp.asarray((rng.randn(K, N) * 0.05).astype(np.float32),
                    dtype=jnp.bfloat16)
    g = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    mu = jnp.asarray(rng.randn(K).astype(np.float32) * 0.1)
    var = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    assert bm.eligible(M, K, N)
    fused = bm.make_bn_matmul_train(act="relu")

    @jax.jit
    def fused_step(x, g, b, mu, var, w):
        return jax.grad(
            lambda *a: fused(*a).astype(jnp.float32).sum(),
            argnums=(0, 5))(x, g, b, mu, var, w)

    @jax.jit
    def ref_step(x, g, b, mu, var, w):
        return jax.grad(
            lambda *a: bm.bn_matmul_reference(*a).astype(jnp.float32).sum(),
            argnums=(0, 5))(x, g, b, mu, var, w)

    _row("bn_matmul_train", f"M{M} K{K} N{N} bf16",
         _timeit(fused_step, x, g, b, mu, var, w),
         _timeit(ref_step, x, g, b, mu, var, w), "xla")


def bench_bn_conv3x3():
    """Fused BN+ReLU->3x3 conv vs normalize + XLA conv, fwd+bwd, on the
    ResNet stage-3 middle-conv shape (bs64 to keep the microbench
    quick)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import bn_conv as bc

    N, H, W, K, O = 64, 14, 14, 256, 256
    rng = np.random.RandomState(4)
    x = jnp.asarray((rng.randn(N, H, W, K) * 0.2).astype(np.float32),
                    dtype=jnp.bfloat16)
    w = jnp.asarray((rng.randn(O, K, 3, 3) * 0.05).astype(np.float32),
                    dtype=jnp.bfloat16)
    g = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    mu = jnp.asarray(rng.randn(K).astype(np.float32) * 0.1)
    var = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    assert bc.eligible(N, H, W, K, O)
    wh = bc._w_hwio(w)
    fused = bc.make_bn_conv3x3_train()

    @jax.jit
    def fused_step(x, g, b, mu, var, wh):
        return jax.grad(
            lambda *a: fused(*a).astype(jnp.float32).sum(),
            argnums=(0, 5))(x, g, b, mu, var, wh)

    @jax.jit
    def ref_step(x, g, b, mu, var, w):
        return jax.grad(
            lambda *a: bc.bn_conv3x3_reference(*a)
            .astype(jnp.float32).sum(),
            argnums=(0, 5))(x, g, b, mu, var, w)

    _row("bn_conv3x3_train", f"n{N} {H}x{W} k{K} o{O} bf16",
         _timeit(fused_step, x, g, b, mu, var, wh),
         _timeit(ref_step, x, g, b, mu, var, w), "xla")


if __name__ == "__main__":
    import json
    import traceback

    # each bench is independent: a Mosaic failure in one must not cost
    # the rows already measured (first-contact evidence matters most)
    for fn in (bench_lstm, bench_gru, bench_flash, bench_flash_long,
               bench_bn_matmul, bench_bn_conv3x3):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — record and continue
            ROWS.append({"kernel": fn.__name__,
                         "error": f"{type(e).__name__}: {e}"[:400],
                         "traceback": traceback.format_exc()[-1200:]})
            traceback.print_exc()
    measured = [r for r in ROWS if "error" not in r]
    if measured:
        print(json.dumps({"metric": "kernel_microbench", "rows": ROWS}))
    else:
        # zero real numbers: exit non-zero WITHOUT the JSON line, so error
        # rows alone never read as a measurement
        print("no kernel measured; rows:", file=sys.stderr)
        print(json.dumps(ROWS), file=sys.stderr)
        sys.exit(1)
