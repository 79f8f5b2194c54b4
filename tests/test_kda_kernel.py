"""The Kimi-Delta-Attention kernels (PR 59) in interpret mode (same code path
as the chip) against `kda_chunked` and its jax.vjp and against the
token-by-token recurrence, gates that overflow a split product, equal
channels against the scalar-gated rule, the gate `usable`, the float32 the
kernels hold, what the pair launches, and the op's choice between the kernels
and the plain emission."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _close, _inner_eqns, _series, _with_vjp
from paddle_tpu import observability as obs
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops import sparse_linear_ops as slo
from paddle_tpu.ops.pallas_kernels import kda as K
from paddle_tpu.ops.pallas_kernels import kda_conv

# a token's log-decay a channel: the state all but kept, forgotten within a
# token or two, and channels of both kinds side by side (what the
# initialisation draws lies between)
DECAYS = {"slow": (5e-4, 2e-3), "fast": (2.0, 6.0), "mixed": (1e-3, 8.0)}


def _operands(H, T, D, Dv, dtype, decay="mixed", seed=0, B=1):
    rs = np.random.RandomState(seed)
    q = rs.randn(B, H, T, D) / np.sqrt(D)
    k = rs.randn(B, H, T, D)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    lo, hi = DECAYS[decay]
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(rs.randn(B, H, T, Dv), dtype),
            jnp.asarray(-rs.uniform(lo, hi, (B, H, T, D)), jnp.float32),
            jnp.asarray(rs.uniform(0.05, 0.95, (B, H, T)), jnp.float32),
            jnp.asarray(rs.randn(B, H, T, Dv), jnp.float32))


def _recurrence(q, k, v, g, beta):
    """S~ = Diag(e^{g_t}) S_{t-1}; S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T;
    o_t = S_t^T q_t, token by token, in the widest float."""
    wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    q, k, v, g, beta = (a.astype(wide) for a in (q, k, v, g, beta))

    def head(q, k, v, g, beta):          # [T, D] x 2, [T, Dv], [T, D], [T]
        def token(s, x):
            q, k, v, g, beta = x
            s = jnp.exp(g)[:, None] * s
            s = s + beta * jnp.outer(k, v - s.T @ k)
            return s, s.T @ q
        zero = jnp.zeros((q.shape[-1], v.shape[-1]), wide)
        return jax.lax.scan(token, zero, (q, k, v, g, beta))[1]

    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


def _tm(ops):
    """`kda_chunked`'s (q, k, v, g, beta) as the kernels take them: g
    token-major [B, T, H, D]."""
    q, k, v, g, beta = ops
    return q, k, v, jnp.swapaxes(g, 1, 2), beta


def _hm(grads):
    """The kernels' gradients as `kda_chunked`'s: dg head-major."""
    return _tm(grads)


@pytest.fixture
def blocks_of_8(monkeypatch):
    """Diagonal blocks of 8 rows (the constant is a whole toy chunk), so
    that a chunk of 32 climbs two levels of halves."""
    monkeypatch.setattr(K, "SUB", 8)


@pytest.fixture
def blocks_of_two_tiles(monkeypatch):
    """Diagonal blocks of 16 rows in row tiles of 8, as the constants' 32
    in tiles of 16: a column's sums come from two tiles."""
    monkeypatch.setattr(K, "SUB", 16)
    monkeypatch.setattr(K, "ROWS", 8)


@functools.cache
def _chunked(chunk):    # ONE function a chunk: its program serves every decay
    return lambda *a: slo.kda_chunked(*a, chunk=chunk, sub=4)


@pytest.mark.parametrize("dtype,T,chunk,decay", [
    (dtype, 64, 32, decay) for dtype in ("float32", "bfloat16")
    for decay in DECAYS] + [("float32", 32, 8, "mixed")])
def test_kda_kernels_match_the_plain_emission(dtype, T, chunk, decay,
                                              blocks_of_8):
    """Two chunks of two levels of halves and four of none (one chunk:
    the next test), decays near 1, near 0 and both: O and all five
    gradients against
    `kda_chunked` and its jax.vjp.  bf16 operands: the same float32 inside,
    dq, dk and dv rounded once."""
    *ops, do = _operands(2, T, 16, 8, jnp.dtype(dtype), decay)
    how = dict(interpret=True)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_chunked(chunk), do, *ops)
        got = K.kda_fwd(*_tm(ops), chunk, **how)
        mine = _hm(K.kda_bwd(do, *_tm(ops), chunk, **how))
    assert got.dtype == jnp.float32
    assert [a.dtype for a in mine] == [a.dtype for a in ops]
    _close(got, want, 5e-6)
    for a, b in zip(mine, grads):
        _close(a, b, 1e-5 if dtype == "float32" else 1e-2)


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("T,chunk", [(16, 16), (64, 32)])
def test_kda_kernels_match_the_recurrence(T, chunk, decay, blocks_of_8):
    """The `custom_vjp` over the pair against the literal recurrence and
    ITS jax.vjp: nothing of the chunked form (the levels, the inverse, the
    carried state) is shared with the oracle."""
    *ops, do = _operands(2, T, 16, 8, jnp.float32, decay, seed=3)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_recurrence, do, *ops)
        got, mine = jax.vjp(K.make_kda(chunk, True), *_tm(ops))
        mine = _hm(mine(do))
    _close(got, want, 1e-5)
    for a, b in zip(mine, grads):
        _close(a, b, 2e-5)


def test_kda_kernels_survive_gates_that_overflow_a_split_product(
        blocks_of_two_tiles):
    """Blocks of two row tiles under one level of halves.  g = -40 a token
    on the even channels and 0 on the odd ones: inside
    one chunk of 32 the cumulative gate reaches -1280, e^{-G} is inf and
    `(K e^G)(K e^-G)^T` NaN (tests/test_kimi_linear.py shows it); the
    kernels take no exponential of a positive number: finite in value and
    in every gradient, and the plain emission's."""
    q, k, v, g, beta, do = _operands(2, 64, 16, 8, jnp.float32)
    g = jnp.broadcast_to(jnp.where(jnp.arange(16) % 2 == 0, -40.0, 0.0),
                         g.shape).astype(jnp.float32)
    ops = (q, k, v, g, beta)
    with jax.enable_x64(False):
        want, grads = _with_vjp(
            lambda *a: slo.kda_chunked(*a, chunk=32, sub=8), do, *ops)
        got, mine = jax.vjp(K.make_kda(32, True), *_tm(ops))
        mine = _hm(mine(do))
    assert np.isfinite(np.asarray(got)).all()
    assert all(np.isfinite(np.asarray(a)).all() for a in mine)
    _close(got, want, 5e-6)
    for a, b in zip(mine, grads):
        _close(a, b, 1e-5)


def test_kda_kernels_with_equal_channels_are_the_scalar_gated_rule(
        blocks_of_8):
    q, k, v, g, beta, _ = _operands(2, 64, 16, 8, jnp.float32, "mixed", 5)
    g = jnp.broadcast_to(g[..., :1], g.shape)
    with jax.enable_x64(False):
        got = K.kda_fwd(*_tm((q, k, v, g, beta)), 32, interpret=True)
        want = jax.jit(lambda *a: slo.gated_delta_chunked(*a, chunk=32))(
            q, k, v[:, :, None], g[:, :, None, :, 0],
            beta[:, :, None])[:, :, 0]
    _close(got, want, 5e-6)


CALLS = ("fwd", "bwd")


def _spy_on_calls(monkeypatch, kernels=K):
    """-> the list every launch of one of `kernels._calls`' two appends its
    name to."""
    launched, real = [], kernels._calls

    def calls(*a):
        return tuple((lambda *x, name=name, call=call:
                      (launched.append(name), call(*x))[1])
                     for name, call in zip(CALLS, real(*a)))

    monkeypatch.setattr(kernels, "_calls", calls)
    return launched


def test_kda_pair_launches_the_forward_once_and_the_reverse_pass_once(
        monkeypatch):
    """Differentiated, the pair runs ONE forward launch, which hands O, the
    chunks' incoming states, Tm, KK and QK to ONE reverse pass: no third
    launch.  `.keeping`, what a forward op is handed, returns O alone; its
    own vjp launches the forward again and gives the same gradients, bit
    for bit."""
    *ops, do = _operands(1, 32, 16, 8, jnp.float32)
    ops = _tm(ops)
    scan = K.make_kda(16, True)
    launched = _spy_on_calls(monkeypatch)
    with jax.enable_x64(False):
        want_o, back = jax.vjp(scan, *ops)
        want = back(do)
        assert launched == ["fwd", "bwd"]
        del launched[:]
        kept = scan.keeping(*ops)
        assert launched == ["fwd"] and len(kept) == 1
        assert scan.bare(*ops).shape == want_o.shape
        del launched[:]
        grads = jax.vjp(lambda *a: scan.keeping(*a)[0], *ops)[1](do)
        assert launched == ["fwd", "fwd", "bwd"]
        _, (states, tm, kk, qk) = K.kda_fwd(*ops, 16, keep=True,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(kept[0]), np.asarray(want_o))
    for a, b in zip(grads, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert states.shape == (1, 2, 8, 16)            # [B H, N, Dv, Dk]
    assert tm.shape == kk.shape == qk.shape == (1, 2, 16, 16)
    assert not np.asarray(states[:, 0]).any()       # S = 0 comes in
    assert not np.triu(np.asarray(kk), 0).any()     # strictly lower
    assert not np.triu(np.asarray(qk), 1).any()     # lower


@pytest.mark.parametrize("T,chunk,D,dtype,want", [
    (8192, 128, 128, "bfloat16", True),         # the cell's
    (8192, 64, 128, "float32", True),
    (64, 64, 256, "bfloat16", True),            # T of one short chunk
    (8192, 128, 128, "float64", False),         # the numeric checks
    (8192, 128, 128, "float16", False),
    (8192, 128, 96, "bfloat16", False),         # odd widths
    (8192, 128, 64, "bfloat16", False),
    (8200, 128, 128, "bfloat16", False),        # T off the chunks
    (60, 60, 128, "bfloat16", False),           # no whole row tiles
    (8192, 96, 128, "bfloat16", False)])        # three diagonal blocks
def test_kda_kernels_take_whole_tiles(T, chunk, D, dtype, want):
    assert K.usable(T, chunk, D, jnp.dtype(dtype)) is want


@pytest.mark.parametrize("which", CALLS)
def test_kda_kernels_hold_float32_at_highest(which, blocks_of_8):
    """On bf16 q, k, v the carried state (VMEM scratch), the gates, every
    exponential, both score tiles, the inverse, O and what is kept for the
    reverse pass are float32 and EVERY
    product takes float32 operands at HIGHEST (`assumed.precision`: a
    decayed operand has no bf16 form): the kernel's twin of
    `test_kda_chunked_is_float32_whatever_comes_in`."""
    with jax.enable_x64(False):
        *ops, do = _operands(1, 32, 16, 8, jnp.bfloat16)
        calls, operands = K._prepared(*_tm(ops), 16, True)
        call = dict(zip(CALLS, calls))[which]
        if which == "bwd":
            operands += (do.reshape(operands[2].shape),
                         jnp.zeros((1, 2, 8, 16), jnp.float32)) + (
                             jnp.zeros((1, 2, 16, 16), jnp.float32),) * 3
        jaxpr = jax.make_jaxpr(call)(*operands)
    (kernel,) = [e for e in _inner_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
    body = kernel.params["jaxpr"]
    scratch = [v.aval for v in body.invars[-(4 if which == "bwd" else 5):]]
    assert scratch[0].shape == (8, 16)          # the state, or its gradient
    assert all(str(a.dtype) == "float32" for a in scratch)
    eqns = list(_inner_eqns(body))
    exps = [e for e in eqns if e.primitive.name == "exp"]
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert exps and len(dots) >= 12
    for e in exps + dots:
        assert all(str(v.aval.dtype) == "float32"
                   for v in list(e.invars) + list(e.outvars))
    highest = (jax.lax.Precision.HIGHEST,) * 2
    assert all(tuple(e.params["precision"]) == highest for e in dots)
    wide = {"fwd": ["float32"] * 5,
            "bwd": ["bfloat16"] * 3 + ["float32"] * 2}[which]
    assert [str(a.dtype) for a in jaxpr.out_avals] == wide


# ---------------------------------------------------------------------------
# the op: which emission, counted; what the grad op's re-emission launches


def _kda_values(T, H, D, taps=4, seed=0):
    rs = np.random.RandomState(seed)
    f32 = lambda a: np.asarray(a, np.float32)               # noqa: E731
    W = H * D
    values = {slot: f32(rs.uniform(-1, 1, (1, T, W))) for slot in "QKVF"}
    values.update({
        "Beta": f32(rs.uniform(-1, 1, (1, T, H))),
        "Gate": f32(rs.uniform(-2, 2, (1, T, W))),
        **{"Conv" + a: f32(rs.uniform(-0.5, 0.5, (W, taps))) for a in "QKV"},
        "ALog": f32(np.log(rs.uniform(1.0, 4.0, H))),
        "DtBias": f32(rs.uniform(-3.0, -1.0, W)),
        "Norm": f32(rs.uniform(0.5, 1.5, D))})
    return (values, {"num_heads": H, "epsilon": 1e-5, "gate_rank": 8},
            f32(rs.uniform(-1, 1, (1, T, W))))


def _kda_step(values, attrs, weight):
    """A program of the one op under mean(Out * weight), every input a
    parameter -> Out and every input's gradient of one run."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    for name, value in values.items():
        block.create_parameter(name=name, shape=value.shape, dtype="float32")
    block.create_var(name="weight", shape=weight.shape, dtype="float32",
                     stop_gradient=True)
    out = block.create_var(name="out", dtype="float32", shape=weight.shape)
    block.append_op("kimi_delta_attention",
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


def test_kimi_delta_attention_takes_the_kernels_on_a_tpu(monkeypatch):
    """Where the trace targets one TPU, at heads of a whole lane tile, the
    op's emitter launches the forward kernel once and keeps NOTHING, and
    its grad op's re-emission launches the forward once more (it hands O
    and the chunks' residuals to the backward) and the reverse pass once:
    three launches, none reported to `executor_grad_kernel_forward_total`
    (tests/benchmarks pins the cell's series); the numbers are the plain
    emission's; the counter names the paths; the switch sends both
    emissions the plain way."""
    values, attrs, weight = _kda_values(64, 1, 128)
    monkeypatch.setattr(K, "CHUNK", 32)             # two chunks of 32
    monkeypatch.setattr(K, "SUB", 8)                # and two levels
    family = "kda_kernels_traced_total"
    obs.REGISTRY.reset()
    want = _kda_step(values, attrs, weight)
    assert _series(family) == [({"op": "fwd", "path": "xla"}, 1.0),
                               ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    real_make, real_conv = K.make_kda, kda_conv.make_kda_conv
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    launched = _spy_on_calls(monkeypatch)
    monkeypatch.setattr(K, "make_kda", lambda chunk: real_make(chunk, True))
    # the convolution part takes ITS kernels there too
    # (tests/test_kda_conv_kernel.py)
    monkeypatch.setattr(kda_conv, "make_kda_conv",
                        lambda *a: real_conv(*a, True))
    real_make.cache_clear()
    obs.REGISTRY.reset()
    got = _kda_step(values, attrs, weight)
    assert launched == ["fwd", "fwd", "bwd"]
    assert _series(family) == [({"op": "fwd", "path": "pallas"}, 1.0),
                               ({"op": "grad", "path": "pallas"}, 1.0)]
    # not reported: tests/benchmarks pins the cell's series without one
    assert _series("executor_grad_kernel_forward_total") == []
    assert _series("kda_layers_traced_total") == [      # the plain chunk
        ({"chunk": "64", "conv_taps": "4", "gate_rank": "8",
          "head_dim": "128", "heads": "1"}, 1.0)]
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max()
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    again = _kda_step(values, attrs, weight)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()
    real_make.cache_clear()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,path", [
    ("one_tpu", "tpu", None, (256, 128), "bfloat16", "pallas"),
    ("one_short_chunk", "tpu", None, (64, 128), "float32", "pallas"),
    ("the_cpu", "cpu", None, (256, 128), "bfloat16", "xla"),
    ("a_mesh", "tpu", object(), (256, 128), "bfloat16", "xla"),
    ("float64", "tpu", None, (256, 128), "float64", "xla"),
    ("odd_width", "tpu", None, (256, 64), "bfloat16", "xla"),
    ("odd_length", "tpu", None, (320, 128), "float32", "xla")])
def test_kimi_delta_attention_dispatch_counts_the_path(case, platform, mesh,
                                                       shape, dtype, path,
                                                       monkeypatch):
    """One gate: one TPU, no mesh and a shape the kernels take; the counter
    reads the path of the forward emission (abstractly traced: no kernel
    runs)."""
    T, D = shape
    values, attrs, _ = _kda_values(T, 2, D)
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
    assert out.shape == (1, T, 2 * D) and out.dtype == jnp.dtype(dtype)
    assert _series("kda_kernels_traced_total") == [
        ({"op": "fwd", "path": path}, 1.0)]
