"""The gated DeltaNet's convolution kernels (PR 51) in interpret mode (same
code path as the chip) against the op's plain emission (`gdn_conv_plain`:
`short_conv_silu`, the plain norm and split) and its jax.vjp, the gate
`usable`, and the op's choice between them with what its grad op's
re-emission is handed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import _f32, _with_vjp
from test_gated_delta_kernel import _gdn_step, _gdn_values, _series

from paddle_tpu import observability as obs
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops import sparse_linear_ops as slo
from paddle_tpu.ops.pallas_kernels import gdn_conv as K

HK, G, D, EPS = 2, 2, 128, 1e-6
HV = HK * G
MIXED = 2 * HK * D + HV * D
WIDTH = MIXED + HV * D
HOW = dict(interpret=True, tile=32, cols=256)


def _operands(B, T, L, dtype, seed=0):
    """X, Conv and the cotangents of (q, k, v, z)."""
    rs = np.random.RandomState(seed)
    shapes = ((B, HK, T, D), (B, HK, T, D), (B, HK, G, T, D), (B, T, HV * D))
    return (jnp.asarray(rs.randn(B, T, WIDTH), dtype),
            jnp.asarray(0.5 * rs.randn(MIXED, L), jnp.float32),
            tuple(jnp.asarray(rs.randn(*s), dtype) for s in shapes))


def _plain(x, w):
    """The plain part and z, X's last columns: what the `custom_vjp`
    hands out."""
    return (*slo.gdn_conv_plain(x, w, HK, HV, D, EPS), x[..., MIXED:])


@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("L", [4, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_gdn_conv_kernels_match_the_plain_emission(dtype, L, B):
    """Both kernels against the plain emission and its jax.vjp over tiles
    of 32 rows where B is 1 and of one 16-row chunk where it is 2 (three
    tiles: the halo both ways, the start's zeros, the end's missing future)
    and column chunks of two heads:
    q, k, v, dX with dz in its last columns, and the taps' gradient.
    float32 to a few last bits; bf16 the same bf16 numbers but for such
    bits (one rounding at the places the plain emission rounds), the
    taps' gradient in float32.  Every row is held, so nothing leaks across
    the batch or around the sequence's ends: what a roll wraps is
    replaced."""
    T, how = (96, HOW) if B == 1 else (48, dict(HOW, tile=16))
    x, w, cts = _operands(B, T, L, jnp.dtype(dtype))
    with jax.enable_x64(False):
        want, (gx, gw) = _with_vjp(_plain, cts, x, w)
        got = K.gdn_conv_fwd(x, w, HK, HV, D, EPS, **how)
        dx, dw = K.gdn_conv_bwd(*cts, x, w, HK, HV, D, EPS, **how)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype == x.dtype
    assert dx.shape == x.shape and dx.dtype == x.dtype
    assert dw.shape == w.shape and dw.dtype == jnp.float32
    np.testing.assert_array_equal(dx[..., MIXED:], cts[3])
    if dtype == "float32":
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)
        np.testing.assert_allclose(dx, gx, rtol=1e-5, atol=1e-5)
    else:
        for a, b in zip(got + (dx,), want[:3] + (gx,)):
            assert (a == b).mean() > 0.999
            err = np.abs(_f32(a) - _f32(b))
            assert (err <= 2.0 ** -7 * np.abs(_f32(b)) + 1e-6).all()
    np.testing.assert_allclose(dw, gw, rtol=1e-4, atol=5e-4)
    # row 0 has no history: the last tap alone
    v0 = jax.nn.silu(x[:, 0, 2 * HK * D:MIXED].astype(jnp.float32)
                     * w[2 * HK * D:, L - 1])
    np.testing.assert_allclose(
        _f32(got[2][:, :, :, 0]).reshape(B, HV * D), v0,
        rtol=2.0 ** -7, atol=1e-6)


def test_gdn_conv_from_saved_launches_no_forward(monkeypatch):
    """The `custom_vjp` hands z out beside q, k, v; `.from_saved` gives the
    kept q, k, v back and differentiates as the backward kernel alone: the
    same gradients, bit for bit."""
    x, w, cts = _operands(1, 32, 4, jnp.float32, seed=1)
    launched, real = [], K._calls

    def calls(*a):
        return tuple((lambda *x, name=name, call=call:
                      (launched.append(name), call(*x))[1])
                     for name, call in zip(("fwd", "bwd"), real(*a)))

    monkeypatch.setattr(K, "_calls", calls)
    conv = K.make_gdn_conv(HK, HV, D, EPS, True)
    with jax.enable_x64(False):
        out, back = jax.vjp(conv, x, w)
        want = back(cts)
        assert launched == ["fwd", "bwd"]
        np.testing.assert_array_equal(out[3], x[..., MIXED:])
        del launched[:]
        again, back = jax.vjp(
            lambda x, w: conv.from_saved(x, w, *out[:3]), x, w)
        got = back(cts)
    assert launched == ["bwd"]
    for a, b in zip(again + got, out + want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,Hk,Hv,Dk,Dv,L,dtype,want", [
    (8192, 16, 32, 128, 128, 4, "bfloat16", True),     # the cell's
    (32, 1, 1, 128, 128, 1, "float32", True),
    (48, 2, 4, 128, 256, 4, "bfloat16", True),         # three tiles of 16
    (8192, 16, 32, 128, 128, 4, "float64", False),
    (8192, 16, 32, 128, 128, 4, "float16", False),
    (8200, 16, 32, 128, 128, 4, "bfloat16", False),    # T off the chunks
    (8, 16, 32, 128, 128, 4, "float32", False),
    (8192, 16, 32, 64, 128, 4, "bfloat16", False),     # a head off the lanes
    (8192, 16, 32, 128, 192, 4, "bfloat16", False),
    (8192, 16, 24, 128, 128, 4, "bfloat16", False),    # 1.5 value heads a key
    (8192, 16, 32, 128, 128, 17, "bfloat16", False),   # a shift over a chunk
    (8192, 16, 32, 128, 128, 0, "bfloat16", False)])
def test_gdn_conv_kernels_take_whole_tiles(T, Hk, Hv, Dk, Dv, L, dtype, want):
    assert K.usable(T, Hk, Hv, Dk, Dv, L, jnp.dtype(dtype)) is want


def test_gdn_conv_row_tile_fits_the_block_budget():
    """At the cell's shape a grid step is 256 whole rows in bf16 and 128 in
    float32 (the backward's blocks, double-buffered); a column chunk is two
    heads of 128 or one of 256."""
    assert K.row_tile(8192, 16, 32, 128, 128, 2) == 256
    assert K.row_tile(8192, 16, 32, 128, 128, 4) == 128
    assert 2 * 256 * (8192 + 2 * 12288) * 2 <= K.BLOCK_BUDGET
    assert K.row_tile(48, 2, 4, 128, 128, 2) == 16
    assert K.row_tile(8, 2, 4, 128, 128, 2) == 0
    assert K._sections(16, 32, 128, 128, 256) == (
        (0, 16, 128, 2, 128 ** -0.5), (2048, 16, 128, 2, 1.0),
        (4096, 32, 128, 2, None))
    assert [s[3] for s in K._sections(3, 3, 128, 256, 256)] == [1, 1, 1]


# ---------------------------------------------------------------------------
# the op: which emission, counted; what the grad op's re-emission is handed


def test_gated_delta_rule_takes_the_conv_kernels_on_a_tpu(monkeypatch):
    """Where the trace targets one TPU the op's emitter launches the
    convolution's forward kernel ONCE and keeps q, k and v, and its grad
    op's re-emission launches the backward kernel alone
    (`executor_grad_kernel_forward_total` reused=1 only), whether or not
    the scan takes ITS kernels (64 tokens are under a chunk: it does not);
    the numbers are the plain emission's; the counter names the path; the
    switch sends both emissions the plain way."""
    values, attrs, weight = _gdn_values(64, 1, 2, 128, 128)
    obs.REGISTRY.reset()
    want, _ = _gdn_step(values, attrs, weight)
    assert _series("gated_delta_conv_kernels_traced_total") == [
        ({"op": "fwd", "path": "xla"}, 1.0),
        ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    launched, real_calls, real_make = [], K._calls, K.make_gdn_conv

    def calls(*a):
        return tuple((lambda *x, name=name, call=call:
                      (launched.append(name), call(*x))[1])
                     for name, call in zip(("fwd", "bwd"), real_calls(*a)))

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(K, "_calls", calls)
    monkeypatch.setattr(K, "make_gdn_conv", lambda *a: real_make(*a, True))
    real_make.cache_clear()
    obs.REGISTRY.reset()
    got, _ = _gdn_step(values, attrs, weight)
    assert launched == ["fwd", "bwd"]
    assert _series("gated_delta_conv_kernels_traced_total") == [
        ({"op": "fwd", "path": "pallas"}, 1.0),
        ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("gated_delta_kernels_traced_total") == [
        ({"op": "fwd", "path": "xla"}, 1.0),
        ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "gated_delta_rule", "reused": "1"}, 1.0)]
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max()
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    again, _ = _gdn_step(values, attrs, weight)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()
    real_make.cache_clear()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,taps,path", [
    ("one_tpu", "tpu", None, (256, 128), "bfloat16", 4, "pallas"),
    ("the_cpu", "cpu", None, (256, 128), "bfloat16", 4, "xla"),
    ("a_mesh", "tpu", object(), (256, 128), "bfloat16", 4, "xla"),
    ("odd_width", "tpu", None, (256, 64), "bfloat16", 4, "xla"),
    ("odd_length", "tpu", None, (200, 128), "float32", 4, "xla"),
    ("long_filter", "tpu", None, (256, 128), "bfloat16", 17, "xla")])
def test_gated_delta_rule_dispatch_counts_the_conv_path(
        case, platform, mesh, shape, dtype, taps, path, monkeypatch):
    """One gate: one TPU, no mesh and a shape the kernels take; what
    `usable` refuses falls back to the plain emission and counts `xla`
    (abstractly traced: no kernel runs)."""
    T, width = shape
    values, attrs, _ = _gdn_values(T, 1, 2, width, width, taps=taps)
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    monkeypatch.setattr(slo, "DELTA_CHUNK", 8)     # the scan's own business
    ctx = reg.EmitContext(None, is_test=True)
    ctx.mesh = mesh
    obs.REGISTRY.reset()
    ins = {slot: [jax.ShapeDtypeStruct(
        v.shape, jnp.dtype(dtype) if slot in ("X", "BA") else v.dtype)]
        for slot, v in values.items()}
    with jax.enable_x64(False):
        out = jax.eval_shape(
            lambda ins: reg.get_op_info("gated_delta_rule").emit(
                ctx, ins, attrs)["Out"][0], ins)
    assert out.shape == (1, T, 2 * width) and out.dtype == jnp.dtype(dtype)
    assert _series("gated_delta_conv_kernels_traced_total") == [
        ({"op": "fwd", "path": path}, 1.0)]
