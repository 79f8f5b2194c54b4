"""The gated delta kernels (PR 49) in interpret mode (same code path as the
chip) against `gated_delta_chunked` and its jax.vjp and against the
token-by-token recurrence, the inverse on a tile, the gate `usable`, the
float32 the kernels hold, and the op's choice between the kernels and the
plain emission with what its grad op's re-emission is handed."""

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
from paddle_tpu.ops.pallas_kernels import gated_delta as K
from paddle_tpu.ops.pallas_kernels import gdn_conv

# a token's decay e^g: the state all but kept, and forgotten in a token or
# two (a chunk's last decays underflow against its first)
DECAYS = {"near_0.999": (5e-4, 2e-3), "near_0.2": (1.2, 2.0)}


def _operands(Hk, G, T, Dk, Dv, dtype, decay="near_0.999", seed=0, B=1):
    rs = np.random.RandomState(seed)
    q = rs.randn(B, Hk, T, Dk) / np.sqrt(Dk)
    k = rs.randn(B, Hk, T, Dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    lo, hi = DECAYS[decay]
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(rs.randn(B, Hk, G, T, Dv), dtype),
            jnp.asarray(-rs.uniform(lo, hi, (B, Hk, G, T)), jnp.float32),
            jnp.asarray(rs.uniform(0.05, 0.95, (B, Hk, G, T)), jnp.float32),
            jnp.asarray(rs.randn(B, Hk, G, T, Dv), jnp.float32))


def _recurrence(q, k, v, g, beta):
    """S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T,
    o_t = S_t^T q_t, token by token, in the widest float."""
    wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    q, k, v, g, beta = (a.astype(wide) for a in (q, k, v, g, beta))

    def head(q, k, v, g, beta):          # [T, Dk] x 2, [T, Dv], [T] x 2
        def token(s, x):
            q, k, v, g, beta = x
            s = jnp.exp(g) * s
            s = s + beta * jnp.outer(k, v - s.T @ k)
            return s, s.T @ q
        zero = jnp.zeros((q.shape[-1], v.shape[-1]), wide)
        return jax.lax.scan(token, zero, (q, k, v, g, beta))[1]

    per_value_head = jax.vmap(head, in_axes=(None, None, 0, 0, 0))
    return jax.vmap(jax.vmap(per_value_head))(q, k, v, g, beta)


@functools.cache
def _chunked(chunk):    # ONE function a chunk: its program serves every decay
    return lambda *a: slo.gated_delta_chunked(*a, chunk=chunk)


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("T,chunk", [(16, 16), (48, 16), (64, 32)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_delta_kernels_match_the_plain_emission(dtype, T, chunk, G,
                                                      decay):
    """One chunk and several, one and two value heads a key head, decays
    near 1 and near 0: O and all five gradients against
    `gated_delta_chunked` and its jax.vjp.  bf16 operands: the same float32
    inside, dq, dk and dv rounded once."""
    *ops, do = _operands(2, G, T, 16, 8, jnp.dtype(dtype), decay)
    how = dict(interpret=True)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_chunked(chunk), do, *ops)
        got = K.gated_delta_fwd(*ops, chunk, **how)
        mine = K.gated_delta_bwd(do, *ops, chunk, **how)
    assert got.dtype == jnp.float32
    assert [a.dtype for a in mine] == [a.dtype for a in ops]
    tol = 2e-6 if dtype == "float32" else 1e-2
    _close(got, want, 2e-6 if dtype == "float32" else 2e-5)
    for a, b in zip(mine, grads):
        _close(a, b, tol)


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("T,chunk,G", [(16, 16, 2), (48, 16, 1), (64, 32, 2)])
def test_gated_delta_kernels_match_the_recurrence(T, chunk, G, decay):
    """The `custom_vjp` over the pair against the literal recurrence and
    ITS jax.vjp: nothing of the chunked form (the inverse, the carried
    state, the decay factors) is shared with the oracle."""
    *ops, do = _operands(2, G, T, 16, 8, jnp.float32, decay, seed=3)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_recurrence, do, *ops)
        got, mine = jax.vjp(K.make_gated_delta(chunk, True), *ops)
        mine = mine(do)
    _close(got, want, 1e-5)
    for a, b in zip(mine, grads):
        _close(a, b, 1e-5)


CALLS = ("fwd", "fwd_keep", "remake", "bwd")


def _spy_on_calls(monkeypatch):
    """-> the list every launch of one of `_calls`' four appends its name
    to."""
    launched, real = [], K._calls

    def calls(*a):
        return tuple((lambda *x, name=name, call=call:
                      (launched.append(name), call(*x))[1])
                     for name, call in zip(CALLS, real(*a)))

    monkeypatch.setattr(K, "_calls", calls)
    return launched


def test_gated_delta_from_saved_launches_no_forward(monkeypatch):
    """The plain `custom_vjp` makes the states and Tm again in its
    backward; `.keeping` hands them out of ONE forward launch and
    `.from_saved` differentiates as the reverse pass over them alone: the
    same gradients, bit for bit."""
    *ops, do = _operands(1, 2, 32, 16, 8, jnp.float32)
    scan = K.make_gated_delta(16, True)
    launched = _spy_on_calls(monkeypatch)
    with jax.enable_x64(False):
        want_o, want = jax.vjp(scan, *ops)
        want = want(do)
        assert launched == ["fwd", "remake", "bwd"]
        del launched[:]
        o, states, tm = scan.keeping(*ops)
        assert launched == ["fwd_keep"]
        assert states.shape == (1, 2, 2, 16, 8)     # [B Hk, G, N, Dk, Dv]
        assert tm.shape == (1, 2, 2, 16, 16)
        assert not np.asarray(states[:, :, 0]).any()     # S = 0 comes in
        got_o, back = jax.vjp(
            lambda *a: scan.from_saved(*a, o, states, tm), *ops)
        got = back(do)
        assert launched == ["fwd_keep", "bwd"] and got_o is o
        grads = jax.vjp(lambda *a: scan.keeping(*a)[0], *ops)[1](do)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    for a, b, c in zip(got, want, grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(b))


@pytest.mark.parametrize("n", [4, 16, 32, 128])
def test_tile_inverse_is_the_unit_lower_inverse(n):
    """(I - A)^-1 of a strictly lower A of n rows, as the kernels make it
    on a tile: against `_unit_lower_inverse` (the plain emission's) and
    numpy; unit diagonal, nothing above it."""
    rs = np.random.RandomState(7)
    a = np.tril(rs.uniform(-1, 1, (n, n)) * 4 / n, -1).astype(np.float32)
    with jax.enable_x64(False):
        got = K.unit_lower_inverse(jnp.asarray(a))
        plain = slo._unit_lower_inverse()(jnp.asarray(a))
    want = np.linalg.inv(np.eye(n) - a.astype(np.float64))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
    assert not np.triu(np.asarray(got), 1).any()
    np.testing.assert_array_equal(np.diag(np.asarray(got)), np.ones(n))


@pytest.mark.parametrize("T,chunk,Dk,Dv,dtype,group,want", [
    (8192, 128, 128, 128, "bfloat16", 2, True),       # the cell's
    (128, 128, 128, 256, "float32", 4, True),
    (8192, 128, 128, 128, "float64", 2, False),   # the numeric checks
    (8192, 128, 128, 128, "float16", 2, False),
    (8192, 128, 96, 128, "bfloat16", 2, False),       # odd widths
    (8192, 128, 128, 64, "bfloat16", 2, False),
    (8200, 128, 128, 128, "bfloat16", 2, False),      # T off the chunks
    (64, 64, 128, 128, "bfloat16", 2, False),         # T under a chunk
    (8192, 128, 128, 128, "bfloat16", 5, False)])     # gates over a tile
def test_gated_delta_kernels_take_whole_tiles(T, chunk, Dk, Dv, dtype, group,
                                              want):
    assert K.usable(T, chunk, Dk, Dv, jnp.dtype(dtype), group) is want


@pytest.mark.parametrize("which", CALLS)
def test_gated_delta_kernels_keep_state_and_gates_in_float32(which):
    """On bf16 q, k, v the carried state (VMEM scratch), every decay, the
    inverse and O are float32; the two score products take the bf16
    operands as they are and every other product is float32 at HIGHEST:
    the kernel's twin of
    `test_gated_delta_chunked_keeps_state_and_gates_in_float32`."""
    with jax.enable_x64(False):
        *ops, do = _operands(1, 2, 32, 16, 8, jnp.bfloat16)
        calls, operands = K._prepared(*ops, 16, True)
        call = dict(zip(CALLS, calls))[which]
        if which == "bwd":
            operands += (do.reshape(operands[2].shape),
                         jnp.zeros((1, 2, 2, 16, 8), jnp.float32),
                         jnp.zeros((1, 2, 2, 16, 16), jnp.float32))
        jaxpr = jax.make_jaxpr(call)(*operands)
    (kernel,) = [e for e in _inner_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
    body = kernel.params["jaxpr"]
    scratch = body.invars[-1].aval          # the state, or its gradient
    assert (scratch.shape, str(scratch.dtype)) == ((2, 16, 8), "float32")
    eqns = list(_inner_eqns(body))
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert exps and all(str(e.outvars[0].aval.dtype) == "float32"
                        for e in exps)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    bf16 = [e for e in dots
            if all(str(v.aval.dtype) == "bfloat16" for v in e.invars)]
    assert len(bf16) == (1 if which == "remake" else 2)   # K K^T (, Q K^T)
    assert all(str(e.outvars[0].aval.dtype) == "float32" for e in dots)
    highest = (jax.lax.Precision.HIGHEST,) * 2
    for e in dots:
        if e not in bf16:
            assert all(str(v.aval.dtype) == "float32" for v in e.invars)
            assert tuple(e.params["precision"]) == highest
    wide = {"fwd": ["float32"], "fwd_keep": ["float32"] * 3,
            "remake": ["float32"] * 2,
            "bwd": ["bfloat16"] * 3 + ["float32"]}[which]
    assert [str(a.dtype) for a in jaxpr.out_avals] == wide


# ---------------------------------------------------------------------------
# the op: which emission, counted; what the grad op's re-emission is handed


def _gdn_step(values, attrs, weight):
    """A program of the one op under mean(Out * weight), every input a
    parameter -> (Out and every input's gradient of one run, the ops)."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    for name, value in values.items():
        block.create_parameter(name=name, shape=value.shape, dtype="float32")
    block.create_var(name="weight", shape=weight.shape, dtype="float32",
                     stop_gradient=True)
    out = block.create_var(name="out", dtype="float32", shape=weight.shape)
    block.append_op("gated_delta_rule",
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
    return [np.asarray(a) for a in got], list(block.ops)


def _gdn_values(T, Hk, Hv, Dk, Dv, taps=4, seed=0):
    rs = np.random.RandomState(seed)
    mixed = 2 * Hk * Dk + Hv * Dv
    f32 = lambda a: np.asarray(a, np.float32)               # noqa: E731
    values = {"X": f32(rs.uniform(-1, 1, (1, T, mixed + Hv * Dv))),
              "BA": f32(rs.uniform(-1, 1, (1, T, 2 * Hv))),
              "Conv": f32(rs.uniform(-0.5, 0.5, (mixed, taps))),
              "ALog": f32(np.log(rs.uniform(1.0, 4.0, Hv))),
              "DtBias": f32(rs.uniform(-3.0, -1.0, Hv)),
              "Norm": f32(rs.uniform(0.5, 1.5, Dv))}
    attrs = {"key_heads": Hk, "value_heads": Hv, "key_dim": Dk,
             "epsilon": 1e-6}
    return values, attrs, f32(rs.uniform(-1, 1, (1, T, Hv * Dv)))


def test_gated_delta_rule_takes_the_kernels_on_a_tpu(monkeypatch):
    """Where the trace targets one TPU, at whole lane tiles, the op's
    emitter launches the forward kernel ONCE and keeps O, the states and
    Tm, and its grad op's re-emission launches the reverse pass alone
    (`executor_grad_kernel_forward_total` reused=1: the convolution's pair
    of PR 51, taken at this shape too, kept its q, k and v as well);
    the numbers are the plain emission's; the counters name the paths; the
    switch sends both emissions the plain way."""
    values, attrs, weight = _gdn_values(256, 1, 2, 128, 128)
    families = ("gated_delta_kernels_traced_total",
                "gated_delta_conv_kernels_traced_total")
    obs.REGISTRY.reset()
    want, ops = _gdn_step(values, attrs, weight)
    assert [op.type for op in ops].count("generic_grad") >= 1
    for family in families:
        assert _series(family) == [({"op": "fwd", "path": "xla"}, 1.0),
                                   ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    real_make, real_conv = K.make_gated_delta, gdn_conv.make_gdn_conv
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    launched = _spy_on_calls(monkeypatch)
    monkeypatch.setattr(K, "make_gated_delta",
                        lambda chunk: real_make(chunk, True))
    monkeypatch.setattr(gdn_conv, "make_gdn_conv",
                        lambda *a: real_conv(*a, True))
    real_make.cache_clear()
    obs.REGISTRY.reset()
    got, _ = _gdn_step(values, attrs, weight)
    assert launched == ["fwd_keep", "bwd"]
    for family in families:
        assert _series(family) == [({"op": "fwd", "path": "pallas"}, 1.0),
                                   ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "gated_delta_rule", "reused": "1"}, 1.0)]
    assert _series("gated_delta_layers_traced_total") == [
        ({"chunk": "128", "conv_taps": "4", "head_dim": "128",
          "key_heads": "1", "value_heads": "2"}, 1.0)]
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max()
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    again, _ = _gdn_step(values, attrs, weight)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()
    real_make.cache_clear()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,path", [
    ("one_tpu", "tpu", None, (256, 128), "bfloat16", "pallas"),
    ("the_cpu", "cpu", None, (256, 128), "bfloat16", "xla"),
    ("a_mesh", "tpu", object(), (256, 128), "bfloat16", "xla"),
    ("odd_width", "tpu", None, (256, 64), "bfloat16", "xla"),
    ("under_a_chunk", "tpu", None, (64, 128), "bfloat16", "xla"),
    ("odd_length", "tpu", None, (320, 128), "float32", "xla")])
def test_gated_delta_rule_dispatch_counts_the_path(case, platform, mesh,
                                                   shape, dtype, path,
                                                   monkeypatch):
    """One gate: one TPU, no mesh and a shape the kernels take; the counter
    reads the path of the forward emission (abstractly traced: no kernel
    runs)."""
    T, D = shape
    values, attrs, _ = _gdn_values(T, 1, 2, D, D)
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    if case == "odd_length":     # chunks of 128 do not divide 320: refused
        monkeypatch.setattr(slo, "DELTA_CHUNK", 64)   # 5 chunks of 64
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
    assert out.shape == (1, T, 2 * D) and out.dtype == jnp.dtype(dtype)
    assert _series("gated_delta_kernels_traced_total") == [
        ({"op": "fwd", "path": path}, 1.0)]
