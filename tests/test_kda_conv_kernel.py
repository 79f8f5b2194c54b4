"""Kimi Delta Attention's convolution kernels (PR 60) in interpret mode (same
code path as the chip) against the op's plain lines (`kda_conv_plain`:
`short_conv_silu` of Q, K and V, the plain norm and split) and their
jax.vjp, the gate `usable`, the op's choice between them, and what lets XLA
merge the forward op's launch with the one its grad op's re-emission makes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import _f32, _with_vjp
from test_kda_kernel import (_kda_step, _kda_values, _series,
                             _spy_on_calls)

from paddle_tpu import observability as obs
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops import sparse_linear_ops as slo
from paddle_tpu.ops.pallas_kernels import kda as scan_kernels
from paddle_tpu.ops.pallas_kernels import kda_conv as K

H, D, EPS = 2, 128, slo.KDA_L2_EPS
HOW = dict(interpret=True, tile=32, cols=256, unroll=1)
FAMILY = "kda_conv_kernels_traced_total"


def _operands(B, T, L, dtype, seed=0):
    """Q, K, V, their taps, and the cotangents of (q, k, v)."""
    rs = np.random.RandomState(seed)
    return ([jnp.asarray(rs.randn(B, T, H * D), dtype) for _ in range(3)]
            + [jnp.asarray(0.5 * rs.randn(H * D, L), jnp.float32)
               for _ in range(3)],
            tuple(jnp.asarray(rs.randn(B, H, T, D), dtype) for _ in range(3)))


def _plain(q, k, v, *taps):
    return slo.kda_conv_plain(q, k, v, taps, H)


@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("L", [4, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kda_conv_kernels_match_the_plain_lines(dtype, L, B):
    """Both kernels against the plain lines and their jax.vjp over tiles of
    32 rows where B is 1 and of one 16-row chunk where it is 2 (three tiles:
    the halo both ways, the start's zeros, the end's missing future), in
    column chunks of 256 lanes ([q's head | k's head], two heads of v) and
    of 512 ([two of q | two of k], v's two in one): q, k, v, dQ, dK, dV and
    the three taps' gradients.  float32 to a few last bits; bf16 the same bf16 numbers but
    for such bits (one rounding at the places the plain lines round), the
    taps' gradients in float32.  Every row is held, so nothing leaks across
    the batch or around the sequence's ends: what a roll wraps is
    replaced."""
    T, how = (96, HOW) if B == 1 else (48, dict(HOW, tile=16, cols=512))
    ops, cts = _operands(B, T, L, jnp.dtype(dtype))
    with jax.enable_x64(False):
        want, grads = _with_vjp(_plain, cts, *ops)
        got = K.kda_conv_fwd(*ops, H, EPS, **how)
        back = K.kda_conv_bwd(*cts, *ops, H, EPS, **how)
    for a, b in zip(got + back[:3], want + grads[:3]):
        assert a.shape == b.shape and a.dtype == b.dtype == ops[0].dtype
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        else:
            assert (a == b).mean() > 0.999
            err = np.abs(_f32(a) - _f32(b))
            assert (err <= 2.0 ** -7 * np.abs(_f32(b)) + 1e-6).all()
    for a, b in zip(back[3:], grads[3:]):
        assert a.shape == b.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-4)
    # row 0 has no history: the last tap alone
    v0 = jax.nn.silu(ops[2][:, 0].astype(jnp.float32) * ops[5][:, L - 1])
    np.testing.assert_allclose(_f32(got[2][:, :, 0]).reshape(B, H * D), v0,
                               rtol=2.0 ** -7, atol=1e-6)


def test_kda_conv_pair_keeps_nothing(monkeypatch):
    """Differentiated, the pair launches the forward once and the backward
    once; `.keeping`, what a forward op is handed, returns (q, k, v) and no
    residual, and `.from_saved` on it launches the backward alone: the same
    gradients, bit for bit."""
    ops, cts = _operands(1, 32, 4, jnp.float32, seed=1)
    conv = K.make_kda_conv(H, EPS, True)
    launched = _spy_on_calls(monkeypatch, K)
    with jax.enable_x64(False):
        out, back = jax.vjp(conv, *ops)
        want = back(cts)
        assert launched == ["fwd", "bwd"]
        del launched[:]
        kept = conv.keeping(*ops)
        assert launched == ["fwd"] and len(kept) == 1
        del launched[:]
        again, back = jax.vjp(lambda *a: conv.from_saved(*a, *kept), *ops)
        got = back(cts)
        assert launched == ["bwd"]
    for a, b in zip(tuple(again) + got, tuple(out) + want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [g.dtype for g in got[3:]] == [jnp.float32] * 3


@pytest.mark.parametrize("T,heads,width,L,dtype,want", [
    (8192, 32, 128, 4, "bfloat16", True),              # the cell's
    (8192, 32, 128, 4, "float32", True),
    (32, 1, 128, 1, "float32", True),
    (48, 2, 256, 4, "bfloat16", True),                 # three tiles of 16
    (8192, 32, 128, 16, "bfloat16", True),
    (8192, 32, 128, 4, "float64", False),
    (8192, 32, 128, 4, "float16", False),
    (8200, 32, 128, 4, "bfloat16", False),             # T off the chunks
    (8, 32, 128, 4, "float32", False),
    (8192, 32, 64, 4, "bfloat16", False),              # a head off the lanes
    (8192, 32, 192, 4, "bfloat16", False),
    (8192, 0, 128, 4, "bfloat16", False),
    (8192, 32, 128, 17, "bfloat16", False),            # a shift over a chunk
    (8192, 32, 128, 0, "bfloat16", False)])
def test_kda_conv_kernels_take_whole_tiles(T, heads, width, L, dtype, want):
    assert K.usable(T, heads, width, L, jnp.dtype(dtype)) is want


def test_kda_conv_row_tile_fits_the_block_budget():
    """At the cell's shape a grid step is 128 whole rows of Q, K and V, in
    bf16 and in float32 (the backward's nine blocks, double-buffered); 256
    rows, `short_conv.py`'s tile, fit in bf16 alone; a short sequence takes
    the most whole chunks that divide it; Q and K share a column chunk's
    lanes."""
    assert K.row_tile(8192, 32, 128, 2) == K.row_tile(8192, 32, 128, 4) == 128
    assert K.row_tile(8192, 32, 128, 2, tile=256) == 256
    assert K.row_tile(8192, 32, 128, 4, tile=256) == 128
    assert 2 * 256 * 9 * 4096 * 2 <= K.BLOCK_BUDGET < 2 * 256 * 9 * 4096 * 4
    assert K.row_tile(48, 2, 128, 2) == 16
    assert K.row_tile(8, 2, 128, 2) == 0
    # a column chunk of 512 lanes: two heads of q beside two of k; four of v
    assert K._groups(32, 128, 512) == (((0, 1), 2, (128 ** -0.5, 1.0)),
                                       ((2,), 4, None))
    assert [g[1] for g in K._groups(3, 256, 512)] == [1, 1]


# ---------------------------------------------------------------------------
# the op: which emission, counted; what the grad op's re-emission launches


@pytest.fixture
def described_tpu(monkeypatch):
    """The op's emitter sees one TPU and both kernel pairs run in interpret
    mode; -> the convolution's launches."""
    real_conv, real_scan = K.make_kda_conv, scan_kernels.make_kda
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    launched = _spy_on_calls(monkeypatch, K)
    monkeypatch.setattr(K, "make_kda_conv", lambda *a: real_conv(*a, True))
    monkeypatch.setattr(scan_kernels, "make_kda",
                        lambda chunk: real_scan(chunk, True))
    for make in (real_conv, real_scan):
        make.cache_clear()
    yield launched
    for make in (real_conv, real_scan):
        make.cache_clear()


def test_kimi_delta_attention_takes_the_conv_kernels_on_a_tpu(
        described_tpu, monkeypatch):
    """Where the trace targets one TPU the op's emitter launches the
    convolution's forward kernel and keeps NOTHING, and its grad op's
    re-emission launches the same forward once more and the backward once:
    three launches, none reported to `executor_grad_kernel_forward_total`
    (tests/benchmarks pins the cell's series); the numbers are the plain
    lines'; the counter names the path for `fwd` and `grad`; the switch
    sends both emissions the plain way."""
    values, attrs, weight = _kda_values(64, 1, 128)
    monkeypatch.setattr(scan_kernels, "CHUNK", 32)
    monkeypatch.setattr(scan_kernels, "SUB", 8)
    launched = described_tpu
    obs.REGISTRY.reset()
    got = _kda_step(values, attrs, weight)
    assert launched == ["fwd", "fwd", "bwd"]
    assert _series(FAMILY) == [({"op": "fwd", "path": "pallas"}, 1.0),
                               ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    del launched[:]
    obs.REGISTRY.reset()
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    want = _kda_step(values, attrs, weight)
    assert launched == []
    assert _series(FAMILY) == [({"op": "fwd", "path": "xla"}, 1.0),
                               ({"op": "grad", "path": "xla"}, 1.0)]
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,taps,conv,scan", [
    ("one_tpu", "tpu", None, (256, 128), "bfloat16", 4, "pallas", "pallas"),
    ("float32", "tpu", None, (256, 128), "float32", 2, "pallas", "pallas"),
    ("the_cpu", "cpu", None, (256, 128), "bfloat16", 4, "xla", "xla"),
    ("a_mesh", "tpu", object(), (256, 128), "bfloat16", 4, "xla", "xla"),
    ("float64", "tpu", None, (256, 128), "float64", 4, "xla", "xla"),
    ("odd_width", "tpu", None, (256, 64), "bfloat16", 4, "xla", "xla"),
    # the two gates answer apart: 320 tokens are whole row tiles of 64 and
    # no whole chunks of 128; seventeen taps reach over a neighbour chunk
    ("conv_alone", "tpu", None, (320, 128), "float32", 4, "pallas", "xla"),
    ("scan_alone", "tpu", None, (256, 128), "bfloat16", 17, "xla",
     "pallas")])
def test_kimi_delta_attention_dispatch_counts_the_conv_path(
        case, platform, mesh, shape, dtype, taps, conv, scan, monkeypatch):
    """One gate a part: one TPU, no mesh and a shape its kernels take; what
    `usable` refuses falls back to the plain lines and counts `xla`
    (abstractly traced: no kernel runs)."""
    T, width = shape
    values, attrs, _ = _kda_values(T, 2, width, taps=taps)
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    ctx = reg.EmitContext(None, is_test=True)
    ctx.mesh = mesh
    obs.REGISTRY.reset()
    ins = {slot: [jax.ShapeDtypeStruct(v.shape, jnp.dtype(dtype))]
           for slot, v in values.items()}
    with jax.enable_x64(dtype == "float64"):
        out = jax.eval_shape(
            lambda ins: reg.get_op_info("kimi_delta_attention").emit(
                ctx, ins, attrs)["Out"][0], ins)
    assert out.shape == (1, T, 2 * width) and out.dtype == jnp.dtype(dtype)
    assert _series(FAMILY) == [({"op": "fwd", "path": conv}, 1.0)]
    assert _series("kda_kernels_traced_total") == [
        ({"op": "fwd", "path": scan}, 1.0)]


def _forward_launches(jaxpr):
    """Every `kda_conv_fwd` pallas_call of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == K.FWD):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_forward_launches(sub))
    return found


def test_forward_op_and_re_emission_launch_the_same_forward_call():
    """The forward op launches the pair's bare forward, its grad op's
    re-emission the pair under jax.vjp: ONE memoized jit of one body on
    the same operands, so the two Mosaic calls are the same bytes (same
    body, same block specs, same operands' shapes in the same order),
    which is what lets XLA merge them in the compiled step."""
    ops, cts = _operands(1, 32, 4, jnp.bfloat16, seed=2)
    conv = K.make_kda_conv(H, EPS, True)
    with jax.enable_x64(False):
        forward_op = jax.make_jaxpr(conv.bare)(*ops)
        re_emitted = jax.make_jaxpr(
            lambda *a: jax.vjp(conv, *a)[1](cts))(*ops)
    (first,), (second,) = (_forward_launches(j.jaxpr)
                           for j in (forward_op, re_emitted))
    assert str(first.params["jaxpr"]) == str(second.params["jaxpr"])
    assert str(first.params["grid_mapping"]) == str(
        second.params["grid_mapping"])
    assert [v.aval for v in first.invars] == [v.aval for v in second.invars]
    # every trace meets one function: the same calls, not equal ones
    key = (1, 32, H, D, 4, EPS, "bfloat16", True, K.TILE, K.COLS, 1)
    assert K._calls(*key) is K._calls(*key)
