"""The selective-scan kernels (PR 55) in interpret mode (same code path as the
chip) against `selective_scan_chunked` and its jax.vjp and against the
token-by-token recurrence in the widest float, the gate `usable`, the
float32 the kernels hold, and the op's choice between the kernels and the
plain emission with what its grad op's re-emission is handed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _inner_eqns, _series, _with_vjp
from paddle_tpu import observability as obs
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas_kernels import selective_scan as K

# Dt + DtBias before the softplus: a step that all but keeps the state
# (Delta near 0.001) and one that forgets it in a token (Delta near 5 under
# A's largest row 16: exp(-80) a token, underflowing inside a chunk)
STEPS = {"near_0.001": (-7.2, -6.6), "near_5": (4.5, 5.5)}
NAMES = ("U", "Dt", "B", "C", "ALog", "D", "DtBias")
CALLS = ("fwd", "fwd_keep", "bwd")


@pytest.fixture
def two_tiles(monkeypatch):
    """Tiles of 128 channels, so that 256 are two: the carried state, the
    kept states and dB / dC's per-tile partials of more than one tile."""
    monkeypatch.setattr(K, "TILE", 128)


def _operands(T, dtype, step="near_0.001", Di=256, N=16, seed=0, B=1):
    rs = np.random.RandomState(seed)
    lo, hi = STEPS[step]
    bias = rs.uniform(-0.2, 0.2, Di)
    cast = lambda a, to=dtype: jnp.asarray(a, to)              # noqa: E731
    return (cast(rs.randn(B, T, Di)),
            cast(rs.uniform(lo, hi, (B, T, Di)) - bias),
            cast(rs.randn(B, T, N)), cast(rs.randn(B, T, N)),
            cast(np.log(np.tile(np.arange(1.0, N + 1), (Di, 1))),
                 jnp.float32),
            cast(rs.uniform(0.5, 1.5, Di), jnp.float32),
            cast(bias, jnp.float32), cast(rs.randn(B, T, Di)))


def _plain(chunk):
    """The op's plain emission on the kernels' operands."""
    def scan(u, dt, b, c, a_log, d, bias):
        wide = ssm_ops.wide_dtype(u.dtype)
        uf = u.astype(wide)
        y = ssm_ops.selective_scan_chunked(
            uf, jax.nn.softplus(dt.astype(wide) + bias.astype(wide)),
            -jnp.exp(a_log.astype(wide)).T, b.astype(wide), c.astype(wide),
            chunk)
        return (y + d.astype(wide) * uf).astype(u.dtype)
    return scan


def _recurrence(u, dt, b, c, a_log, d, bias):
    """h_t = exp(Delta_t A) h_{t-1} + Delta_t u_t B_t^T, y_t = h_t C_t + D
    u_t, token by token, in the widest float; rounded once, to U's dtype."""
    wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    out = u.dtype
    u, dt, b, c, a_log, d, bias = (a.astype(wide) for a in (
        u, dt, b, c, a_log, d, bias))
    a = -jnp.exp(a_log)                                       # [Di, N]

    def one(u, dt, b, c):                  # [T, Di] x 2, [T, N] x 2
        def token(h, x):
            u, delta, b, c = x
            h = jnp.exp(delta[:, None] * a) * h + (delta * u)[:, None] * b
            return h, h @ c + d * u
        return jax.lax.scan(token, jnp.zeros_like(a),
                            (u, jax.nn.softplus(dt + bias), b, c))[1]

    return jax.vmap(one)(u, dt, b, c).astype(out)


def _close(got, want, tol):
    """Within `tol` of the largest entry."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("step", list(STEPS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_kernels_match_the_plain_emission(dtype, step, two_tiles):
    """Three chunks of 16 tokens on two channel tiles: Out and all seven
    gradients against `selective_scan_chunked` and its jax.vjp.  bf16
    operands: the same float32 inside, Out, dU, dDt, dB and dC rounded
    once."""
    *ops, do = _operands(48, jnp.dtype(dtype), step)
    how = dict(chunk=16, interpret=True)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_plain(16), do, *ops)
        got, states = K.selective_scan_fwd(*ops, keep=True, **how)
        mine = K.selective_scan_bwd(do, *ops, states, **how)
    assert got.dtype == ops[0].dtype and states.dtype == jnp.float32
    assert states.shape == (1, 3, 16, 256)          # [B, T / C, N, Di]
    assert [a.dtype for a in mine] == [a.dtype for a in ops]
    _close(got, want, 2e-6 if dtype == "float32" else 1e-2)
    for name, a, b in zip(NAMES, mine, grads):
        assert np.abs(np.asarray(b, np.float32)).max() > 0, name
        _close(a, b, 2e-5 if dtype == "float32" else 1e-2)


@pytest.mark.parametrize("step", list(STEPS))
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 1e-2)])
def test_scan_kernels_match_the_recurrence(dtype, tol, step, two_tiles):
    """The `custom_vjp` over the pair against the literal recurrence in the
    widest float and ITS jax.vjp: nothing of the chunked form (the carried
    state, the states made again) is shared with the oracle."""
    *ops, do = _operands(48, jnp.dtype(dtype), step, seed=3, B=2)
    want, grads = _with_vjp(_recurrence, do, *ops)
    with jax.enable_x64(False):
        got, mine = jax.vjp(K.make_selective_scan(16, True), *ops)
        mine = mine(do)
    _close(got, want, tol)
    for a, b in zip(mine, grads):
        _close(a, b, tol)


def _spy_on_calls(monkeypatch):
    """-> the list every launch of one of `_calls`' three appends its name
    to."""
    launched, real = [], K._calls

    def calls(*a):
        return tuple((lambda *x, name=name, call=call:
                      (launched.append(name), call(*x))[1])
                     for name, call in zip(CALLS, real(*a)))

    monkeypatch.setattr(K, "_calls", calls)
    return launched


def test_scan_from_saved_launches_no_forward(monkeypatch, two_tiles):
    """The plain `custom_vjp` launches the forward that keeps the states,
    once, and under a vjp the reverse pass; `.keeping` hands the states out
    of one launch and `.from_saved` differentiates as the reverse pass over
    them alone: the same gradients, bit for bit."""
    *ops, do = _operands(32, jnp.float32)
    scan = K.make_selective_scan(16, True)
    launched = _spy_on_calls(monkeypatch)
    with jax.enable_x64(False):
        assert scan(*ops).shape == do.shape and launched == ["fwd_keep"]
        del launched[:]
        want_o, want = jax.vjp(scan, *ops)
        want = want(do)
        assert launched == ["fwd_keep", "bwd"]
        del launched[:]
        out, states = scan.keeping(*ops)
        assert launched == ["fwd_keep"]
        assert not np.asarray(states[:, 0]).any()        # h = 0 comes in
        assert np.asarray(states[:, 1]).any()
        got_o, back = jax.vjp(
            lambda *a: scan.from_saved(*a, out, states), *ops)
        got = back(do)
        assert launched == ["fwd_keep", "bwd"] and got_o is out
        grads = jax.vjp(lambda *a: scan.keeping(*a)[0], *ops)[1](do)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_o))
    for a, b, c in zip(got, want, grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(b))


@pytest.mark.parametrize("T,chunk,Di,N,dtype,want", [
    (8192, 64, 5120, 16, "bfloat16", True),           # the cell's
    (128, 64, 256, 8, "float32", True),
    (8192, 64, 5120, 16, "float64", False),       # the numeric checks
    (8192, 64, 5120, 16, "float16", False),
    (8200, 64, 5120, 16, "bfloat16", False),          # T off the chunks
    (32, 64, 5120, 16, "bfloat16", False),            # T under a chunk
    (8192, 64, 5000, 16, "bfloat16", False),          # Di off the lanes
    (8192, 64, 5120, 12, "bfloat16", False),          # N off the sublanes
    (8192, 24, 5120, 16, "bfloat16", False)])     # a chunk off bf16's rows
def test_scan_kernels_take_whole_tiles(T, chunk, Di, N, dtype, want):
    assert K.usable(T, chunk, Di, N, jnp.dtype(dtype)) is want


@pytest.mark.parametrize("Di,N,tile", [
    (5120, 16, 2560), (4096, 16, 2048), (768, 16, 768), (5120, 64, 640),
    (5120, 1024, 0), (200, 16, 0)])
def test_tile_is_the_widest_that_divides_and_fits(Di, N, tile):
    """Whole lane tiles that divide Di, at most TILE, with the backward's
    chunk of states inside STATES_BYTES."""
    assert K.tile_of(Di, N) == tile
    assert not tile or 4 * K.CHUNK * N * tile <= K.STATES_BYTES


@pytest.mark.parametrize("which", CALLS)
def test_scan_kernels_keep_state_and_delta_in_float32(which):
    """On bf16 U, Dt and dOut the carried state (VMEM scratch), Delta,
    every exponent, product and sum are float32: nothing but the loads'
    widening and the stores' one rounding touches bf16."""
    with jax.enable_x64(False):
        *ops, do = _operands(32, jnp.bfloat16, Di=128)
        calls, operands = K._prepared(*ops, 16, True)
        call = dict(zip(CALLS, calls))[which]
        if which == "bwd":
            operands += (do, jnp.zeros((1, 2, 16, 128), jnp.float32))
        jaxpr = jax.make_jaxpr(call)(*operands)
    (kernel,) = [e for e in _inner_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
    body = kernel.params["jaxpr"]
    scratch = {(v.aval.shape, str(v.aval.dtype))
               for v in body.invars[-(8 if which == "bwd" else 5):]}
    assert ((16, 128), "float32") in scratch        # the state, or dh
    assert {dtype for _, dtype in scratch} == {"float32"}
    eqns = list(_inner_eqns(body))
    narrow = [e for e in eqns if any(
        str(getattr(v.aval, "dtype", "")) == "bfloat16"
        for v in list(e.invars) + list(e.outvars))]
    assert narrow and {e.primitive.name for e in narrow} <= {
        "get", "swap", "convert_element_type"}
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert exps and all(str(e.outvars[0].aval.dtype) == "float32"
                        for e in exps)
    wide = {"fwd": ["bfloat16"], "fwd_keep": ["bfloat16", "float32"],
            "bwd": ["bfloat16"] * 2 + ["float32"] * 4}[which]
    assert [str(a.dtype) for a in jaxpr.out_avals] == wide


# ---------------------------------------------------------------------------
# the op: which emission, counted; what the grad op's re-emission is handed


def _scan_values(T, Di=128, N=8, R=4, seed=0):
    rs = np.random.RandomState(seed)
    f32 = lambda a: np.asarray(a, np.float32)               # noqa: E731
    return ({"U": f32(rs.randn(1, T, Di)),
             "Dt": f32(rs.uniform(-3.0, 0.0, (1, T, Di))),
             "XProj": f32(rs.randn(1, T, R + 2 * N)),
             "ALog": f32(np.log(np.tile(np.arange(1.0, N + 1), (Di, 1)))),
             "D": f32(rs.uniform(0.5, 1.5, Di)),
             "DtBias": f32(rs.uniform(-0.5, 0.5, Di))},
            {"dt_rank": R}, f32(rs.uniform(-1, 1, (1, T, Di))))


def _scan_step(values, attrs, weight):
    """A program of the one op under mean(Out * weight), every input a
    parameter -> Out and every input's gradient of one run."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    for name, value in values.items():
        block.create_parameter(name=name, shape=value.shape, dtype="float32")
    block.create_var(name="weight", shape=weight.shape, dtype="float32",
                     stop_gradient=True)
    out = block.create_var(name="out", dtype="float32", shape=weight.shape)
    block.append_op("selective_scan",
                    inputs={slot: [slot] for slot in values},
                    outputs={"Out": ["out"]}, attrs=dict(attrs))
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(
        out, block.var("weight")))
    grads = dict((p.name, g.name) for p, g in fluid.append_backward(loss))
    scope = fluid.global_scope()
    for name, value in dict(values, weight=weight).items():
        scope.set(name, value)
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={}, fetch_list=["out"] + [grads[name] for name in values])
    return [np.asarray(a) for a in got]


FAMILY = "selective_scan_kernels_traced_total"


def test_selective_scan_takes_the_kernels_on_a_tpu(monkeypatch):
    """On a CPU the op and its grad op take `xla_chunked` and the counters
    say so.  Where the trace targets one TPU, at whole tiles, the op's
    emitter launches the forward kernel ONCE, keeping the chunks' states,
    and its grad op's re-emission launches the reverse pass alone
    (`executor_grad_kernel_forward_total` reused=1); the numbers are the
    plain emission's; the switch sends both emissions the plain way."""
    values, attrs, weight = _scan_values(128)
    labels = {"d_inner": "128", "d_state": "8", "chunk": "64"}
    obs.REGISTRY.reset()
    want = _scan_step(values, attrs, weight)
    assert _series(FAMILY) == [({"op": "fwd", "path": "xla"}, 1.0),
                               ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series("selective_scan_total") == [
        (dict(labels, impl="xla_chunked"), 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    real_make = K.make_selective_scan
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    launched = _spy_on_calls(monkeypatch)
    monkeypatch.setattr(K, "make_selective_scan",
                        lambda: real_make(K.CHUNK, True))
    real_make.cache_clear()
    obs.REGISTRY.reset()
    got = _scan_step(values, attrs, weight)
    assert launched == ["fwd_keep", "bwd"]
    assert _series(FAMILY) == [({"op": "fwd", "path": "pallas"}, 1.0),
                               ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("selective_scan_total") == [
        (dict(labels, impl="pallas"), 1.0)]
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "selective_scan", "reused": "1"}, 1.0)]
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    again = _scan_step(values, attrs, weight)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()
    real_make.cache_clear()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,path", [
    ("one_tpu", "tpu", None, (128, 128, 8), "bfloat16", "pallas"),
    ("the_cpu", "cpu", None, (128, 128, 8), "bfloat16", "xla"),
    ("a_mesh", "tpu", object(), (128, 128, 8), "bfloat16", "xla"),
    ("odd_width", "tpu", None, (128, 96, 8), "bfloat16", "xla"),
    ("odd_state", "tpu", None, (128, 128, 4), "bfloat16", "xla"),
    ("under_a_chunk", "tpu", None, (32, 128, 8), "float32", "xla"),
    ("doubles", "tpu", None, (128, 128, 8), "float64", "xla")])
def test_selective_scan_dispatch_counts_the_path(case, platform, mesh, shape,
                                                 dtype, path, monkeypatch):
    """One gate: one TPU, no mesh and a shape the kernels take; the
    counters read the path of the forward emission (abstractly traced: no
    kernel runs)."""
    T, Di, N = shape
    values, attrs, _ = _scan_values(T, Di, N)
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    ctx = reg.EmitContext(None, is_test=True)
    ctx.mesh = mesh
    obs.REGISTRY.reset()
    with jax.enable_x64(dtype == "float64"):
        ins = {slot: [jax.ShapeDtypeStruct(v.shape, jnp.dtype(dtype))]
               for slot, v in values.items()}
        out = jax.eval_shape(
            lambda ins: reg.get_op_info("selective_scan").emit(
                ctx, ins, attrs)["Out"][0], ins)
    assert out.shape == (1, T, Di) and out.dtype == jnp.dtype(dtype)
    assert _series(FAMILY) == [({"op": "fwd", "path": path}, 1.0)]
    (labels, _), = _series("selective_scan_total")
    assert labels["impl"] == ("pallas" if path == "pallas" else "xla_chunked")
