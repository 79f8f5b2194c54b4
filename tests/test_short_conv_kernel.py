"""The short_conv kernels (PR 46) in interpret mode (same code path as the
chip) against `gated_short_conv`'s plain emission and its jax.vjp, the op's
choice between them, its grad op, and the kernels compiled for a described
v5e at the cell's shape."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _f32, _with_vjp
from paddle_tpu import observability as obs
from paddle_tpu.ops import llm_ops
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops.pallas_kernels import short_conv as K


def _operands(B, T, D, L, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, T, 3 * D), dtype),
            jnp.asarray(rs.randn(D, L), jnp.float32),
            jnp.asarray(rs.randn(B, T, D), dtype))


# T, D, the rows a grid step, the lanes a chunk
TILINGS = {
    "one_tile_one_lane_block": (32, 128, 32, 128),
    "one_chunk": (16, 128, 256, 256),
    "three_tiles": (96, 128, 32, 128),            # the halo, both ways
    "three_tiles_three_lane_blocks": (96, 384, 32, 128),
    "two_tiles_wide_chunks": (128, 512, 64, 256),
}


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("tiling", list(TILINGS))
def test_short_conv_kernels_match_the_plain_emission(tiling, taps):
    """Both kernels in float32 against the op's plain emission and its
    jax.vjp: Out, dX and dFilter, every row (the first L - 1 with no
    history, the last with no future, the rows on both sides of a tile's
    edge)."""
    T, D, tile, cols = TILINGS[tiling]
    x, w, dout = _operands(2, T, D, taps)
    how = dict(interpret=True, tile=tile, cols=cols)
    with jax.enable_x64(False):
        want, (gx, gw) = _with_vjp(llm_ops.gated_short_conv_plain, dout,
                                   x, w)
        got = K.short_conv_fwd(x, w, **how)
        dx, dw = K.short_conv_bwd(dout, x, w, **how)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert dx.shape == x.shape and dx.dtype == x.dtype
    assert dw.shape == w.shape and dw.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dx, gx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, gw, rtol=1e-5, atol=2e-4)


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_kernels_wrap_nothing_around_the_sequence(taps):
    """The shifts are rolls: what they wrap around is replaced, at a
    chunk's and a tile's edge by the neighbour's rows and at the sequence's
    ends by zeros.  Out's first rows do not move with X's last rows (nor
    with the other sequence of the batch), and dX's last rows do not move
    with dOut's first."""
    T, D = 64, 128
    x, w, dout = _operands(2, T, D, taps, seed=1)
    how = dict(interpret=True, tile=32, cols=128)
    with jax.enable_x64(False):
        out = K.short_conv_fwd(x, w, **how)
        moved = K.short_conv_fwd(x.at[0, 32:].add(1.0).at[1].add(1.0), w,
                                 **how)
        np.testing.assert_array_equal(out[0, :32], moved[0, :32])
        assert np.abs(np.asarray(out[0, 32:] - moved[0, 32:])).min() > 0
        # row 0 has no history: the last tap alone
        np.testing.assert_allclose(
            out[:, 0], x[:, 0, D:2 * D] * w[:, taps - 1]
            * x[:, 0, :D] * x[:, 0, 2 * D:], rtol=1e-6, atol=1e-6)
        dx, _ = K.short_conv_bwd(dout, x, w, **how)
        dmoved, _ = K.short_conv_bwd(dout.at[0, :32].add(1.0), x, w, **how)
        np.testing.assert_array_equal(dx[0, 32:], dmoved[0, 32:])
        np.testing.assert_array_equal(dx[1], dmoved[1])
        # the last row has no future: dg = the last tap's dc alone
        dg = w[:, taps - 1] * dout[:, -1] * x[:, -1, D:2 * D]
        np.testing.assert_allclose(dx[:, -1, :D], dg * x[:, -1, 2 * D:],
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_kernels_round_bf16_once(taps):
    """bf16 in HBM, float32 inside: Out and dX are the float32 results
    within one rounding to bf16, and the taps' gradient leaves in
    float32."""
    x, w, dout = _operands(2, 96, 256, taps, jnp.bfloat16)
    how = dict(interpret=True, tile=32, cols=128)
    wide = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.enable_x64(False):
        got = K.short_conv_fwd(x, w, **how)
        exact = K.short_conv_fwd(wide(x), w, **how)
        dx, dw = K.short_conv_bwd(dout, x, w, **how)
        dxe, dwe = K.short_conv_bwd(wide(dout), wide(x), w, **how)
        want, back = jax.vjp(llm_ops.gated_short_conv_plain, x, w)
        gx, gw = back(dout)
    assert got.dtype == dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    for a, e in ((got, exact), (dx, dxe)):
        # the nearest bf16 or, where a float32 sum's last bit fell the
        # other way, its neighbour
        err = np.abs(_f32(a) - np.asarray(e))
        assert (err <= 2.0 ** -8 * np.abs(np.asarray(e)) + 1e-30).all()
        assert (a == e.astype(jnp.bfloat16)).mean() > 0.999
    np.testing.assert_allclose(dw, dwe, rtol=1e-5, atol=2e-4)
    # and what XLA's plain emission rounds to, but for such last bits
    assert (got == want).mean() > 0.999 and (dx == gx).mean() > 0.999
    np.testing.assert_allclose(dw, gw, rtol=1e-5, atol=2e-4)


@pytest.mark.parametrize("T,D,L,dtype,want", [
    (8192, 2048, 3, "bfloat16", True),      # the cell's
    (16, 128, 1, "float32", True),
    (48, 384, 4, "bfloat16", True),         # three tiles of one chunk
    (8192, 2048, 3, "float64", False), (8192, 2048, 3, "float16", False),
    (8200, 2048, 3, "bfloat16", False),     # T off the 16-row chunks
    (8, 128, 3, "float32", False),
    (8192, 2000, 3, "bfloat16", False),     # D off the 128 lanes
    (8192, 64, 3, "bfloat16", False),
    (8192, 2048, 17, "bfloat16", False),    # a shift longer than a chunk
    (8192, 2048, 0, "bfloat16", False)])
def test_short_conv_kernels_take_whole_tiles(T, D, L, dtype, want):
    assert K.usable(T, D, L, jnp.dtype(dtype)) is want


def test_short_conv_row_tile_fits_the_block_budget():
    """At the cell's shape a grid step is 256 whole rows; a wider model's
    tile shrinks until the backward's blocks, double-buffered, fit."""
    assert K.row_tile(8192, 2048, 2) == 256
    assert 2 * 7 * 256 * 2048 * 2 <= K.BLOCK_BUDGET
    assert K.row_tile(8192, 8192, 4) == 64
    assert K.row_tile(48, 128, 2) == 16 and K.row_tile(8, 128, 2) == 0
    assert K._chunk_lanes(2048, 256) == 256 and K._chunk_lanes(384, 256) == 128
    assert K._chunk_lanes(768, 512) == 384


# ---------------------------------------------------------------------------
# the op: which emission, counted; the grad op


class _Ctx:
    """What `_short_conv` asks of an EmitContext."""

    def __init__(self, platform, mesh=None):
        self.platform, self.mesh = platform, mesh

    def target_platform(self):
        return self.platform

    def in_grad_replay(self):
        return False


def _series(family):
    fam = obs.REGISTRY.snapshot()["families"].get(family)
    return [(s["labels"], s["value"]) for s in (fam["series"] if fam else [])]


@pytest.mark.parametrize("case,platform,mesh,shape,switch,path", [
    ("one_tpu", "tpu", None, (1, 256, 3 * 128), "", "pallas"),
    ("the_cpu", "cpu", None, (1, 256, 3 * 128), "", "xla"),
    ("a_mesh", "tpu", object(), (1, 256, 3 * 128), "", "xla"),
    ("odd_width", "tpu", None, (1, 256, 3 * 96), "", "xla"),
    ("odd_length", "tpu", None, (1, 200, 3 * 128), "", "xla"),
    ("the_switch", "tpu", None, (1, 256, 3 * 128), "1", "xla")])
@pytest.mark.parametrize("op", ["fwd", "grad"])
def test_short_conv_dispatch_counts_the_path(op, case, platform, mesh, shape,
                                             switch, path, monkeypatch):
    """One gate for the op and its grad op: one TPU, no mesh, kernels not
    switched off and a shape the kernels take; the counter reads the
    path."""
    if switch:
        monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", switch)
    obs.REGISTRY.reset()
    ins = {"X": [jax.ShapeDtypeStruct(shape, jnp.bfloat16)],
           "Filter": [jax.ShapeDtypeStruct((shape[2] // 3, 3), jnp.float32)]}
    assert llm_ops._short_conv(_Ctx(platform, mesh), ins, op)[2] is (
        path == "pallas")
    assert _series("short_conv_kernels_traced_total") == [
        ({"op": op, "path": path}, 1.0)]


def _parents_emission(x, w):
    """`gated_short_conv`'s emitter as PR 45 left it, word for word."""
    taps = w.shape[1]
    T = x.shape[1]
    wide = llm_ops.wide_dtype(x.dtype)
    gate_in, gate_out, u = jnp.split(x.astype(wide), 3, axis=-1)
    g = gate_in * u
    wf = w.astype(wide)
    c = wf[:, taps - 1] * g
    for back in range(1, min(taps, T)):
        c = c + wf[:, taps - 1 - back] * jnp.pad(
            g, ((0, 0), (back, 0), (0, 0)))[:, :T]
    out = gate_out * c
    return out.astype(x.dtype)


@pytest.mark.parametrize("case,mesh,dtype", [
    ("the_cpu", None, jnp.bfloat16), ("the_cpu_f32", None, jnp.float32),
    ("a_mesh", object(), jnp.bfloat16)])
def test_short_conv_fallback_traces_to_the_parents_jaxpr(case, mesh, dtype,
                                                         monkeypatch):
    """Off the kernels' path (the CPU; any mesh, here on a trace that
    targets a TPU) the op's emission is the parent's, equation for
    equation, at a shape the kernels would take; and the grad op's is its
    jax.vjp."""
    x, w, dout = _operands(1, 64, 128, 3, dtype)
    ctx = reg.EmitContext(None, is_test=True)
    ctx.mesh = mesh
    if mesh is not None:
        monkeypatch.setattr(reg.EmitContext, "target_platform",
                            lambda self: "tpu")
    emit = lambda x, w: reg.get_op_info("gated_short_conv").emit(  # noqa
        ctx, {"X": [x], "Filter": [w]}, {})["Out"][0]
    assert str(jax.make_jaxpr(emit)(x, w)) == str(
        jax.make_jaxpr(_parents_emission)(x, w))

    def grad(x, w, dout):
        got = reg.get_op_info("gated_short_conv_grad").emit(
            ctx, {"X": [x], "Filter": [w], "Out@GRAD": [dout]}, {})
        return got["X@GRAD"][0], got["Filter@GRAD"][0]

    def parents_grad(x, w, dout):
        return jax.vjp(_parents_emission, x, w)[1](dout)

    assert str(jax.make_jaxpr(grad)(x, w, dout)) == str(
        jax.make_jaxpr(parents_grad)(x, w, dout))


def _conv_step(x, w, weight):
    """A program of the one op under mean(Out * weight), X and Filter
    parameters; -> (Out, X@GRAD, Filter@GRAD) of one run, and the
    program's ops."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    block.create_parameter(name="x", shape=x.shape, dtype="float32")
    block.create_parameter(name="w", shape=w.shape, dtype="float32")
    block.create_var(name="weight", shape=weight.shape, dtype="float32",
                     stop_gradient=True)
    out = block.create_var(name="out", dtype="float32", shape=weight.shape)
    block.append_op("gated_short_conv",
                    inputs={"X": ["x"], "Filter": ["w"]},
                    outputs={"Out": ["out"]}, attrs={"part": "conv.block"})
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(
        out, block.var("weight")))
    grads = dict((p.name, g.name) for p, g in fluid.append_backward(loss))
    scope = fluid.global_scope()
    for name, value in (("x", x), ("w", w), ("weight", weight)):
        scope.set(name, value)
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={}, fetch_list=["out", grads["x"], grads["w"]])
    return [np.asarray(a) for a in got], list(block.ops)


def test_short_conv_grad_is_a_desc_op_of_its_own():
    """append_backward gives the op ONE `gated_short_conv_grad` desc (X,
    Filter, Out@GRAD in; X@GRAD, Filter@GRAD out; the forward's attrs, uid
    and part), not a `generic_grad`: nothing re-emits the forward.  On the
    CPU both count `xla`."""
    obs.REGISTRY.reset()
    x, w, weight = (np.asarray(a) for a in _operands(1, 32, 128, 3))
    _, ops = _conv_step(x, w, weight)
    (fwd,) = [op for op in ops if op.type == "gated_short_conv"]
    (bwd,) = [op for op in ops if op.type == "gated_short_conv_grad"]
    assert not [op for op in ops if op.type == "generic_grad"
                and op.attrs["__fwd_type__"] == "gated_short_conv"]
    assert bwd.attrs == fwd.attrs and bwd.attrs["part"] == "conv.block"
    assert bwd.inputs == {"X": ["x"], "Filter": ["w"],
                          "Out@GRAD": ["out@GRAD"]}
    assert sorted(bwd.outputs) == ["Filter@GRAD", "X@GRAD"]
    assert reg.get_op_info("gated_short_conv_grad").grad is None
    assert sorted(_series("short_conv_kernels_traced_total"),
                  key=lambda s: s[0]["op"]) == [
        ({"op": "fwd", "path": "xla"}, 1.0),
        ({"op": "grad", "path": "xla"}, 1.0)]


def test_short_conv_op_takes_the_kernels_on_a_tpu(monkeypatch):
    """Where the trace targets one TPU the op's emitter launches the
    forward kernel ONCE and its grad op's the backward kernel once and no
    forward; the numbers are the plain emission's; the counter names the
    path and `executor_grad_kernel_forward_total` gets no series; the
    switch sends both emitters the plain way."""
    x, w, weight = (np.asarray(a) for a in _operands(2, 96, 256, 3, seed=2))
    want, _ = _conv_step(x, w, weight)
    launched = []

    def spy(name, real):
        def call(*a, **k):
            launched.append(name)
            return real(*a, **k, interpret=True, tile=32, cols=128)
        return call

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(K, "short_conv_fwd", spy("fwd", K.short_conv_fwd))
    monkeypatch.setattr(K, "short_conv_bwd", spy("bwd", K.short_conv_bwd))
    obs.REGISTRY.reset()
    got, _ = _conv_step(x, w, weight)
    assert launched == ["fwd", "bwd"]
    assert sorted(_series("short_conv_kernels_traced_total"),
                  key=lambda s: s[0]["op"]) == [
        ({"op": "fwd", "path": "pallas"}, 1.0),
        ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("short_conv_layers_traced_total") == [
        ({"dim": "256", "kernel": "3"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    for a, b, tol in zip(got, want, (1e-5, 1e-9, 1e-7)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=tol)
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    again, _ = _conv_step(x, w, weight)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# AOT: the two kernels alone, compiled for a described v5e at the cell's
# shape (no whole step: tests/benchmarks/test_lfm2_cell.py compiles that)


@pytest.mark.slow
@pytest.mark.parametrize("kernel", [K.FWD, K.BWD])
def test_short_conv_kernels_compile_for_a_v5e_at_the_cells_shape(kernel,
                                                                 v5e):
    """[1, 8192, 6144] bf16 under 3 taps: ONE Mosaic call, named as the
    benchmark's readers find it: by the scope it was emitted in."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.observability.attribution import part_scope

    one = SingleDeviceSharding(v5e)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    x, dout = (sds((1, 8192, 3 * 2048), jnp.bfloat16),
               sds((1, 8192, 2048), jnp.bfloat16))
    w = sds((2048, 3), jnp.float32)

    def scoped(fn):
        @functools.wraps(fn)
        def call(*a):
            with part_scope("conv.taps"):
                return fn(*a)
        return call

    with jax.enable_x64(False):
        if kernel == K.FWD:
            lowered = jax.jit(scoped(K.short_conv_fwd)).lower(x, w)
        else:
            lowered = jax.jit(scoped(K.short_conv_bwd)).lower(dout, x, w)
        text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    (name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "pdtpu.conv.taps" in name and kernel in name, name
