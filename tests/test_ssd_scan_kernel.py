"""The Mamba-2 scan's kernels (PR 70) in interpret mode (same code path as the
chip) against `ssd_chunked` and its jax.vjp and against the token-by-token
recurrence in the widest float: pairs of heads of 64 and heads of 128, one
group and two, three chunks (the state carries); the gate `usable`; the
float32 the kernels hold; what `from_saved` launches; `slow`, the two
compiled for a described v5e at the cell's shape.  tests/test_mamba2.py has
the op's choice between the kernels and the plain emission."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import _inner_eqns, _spy_on_calls, _with_vjp
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas_kernels import ssd_scan as K

# Dt + DtBias before the softplus: a step that all but keeps the state
# (Delta near 0.01) and one that forgets it within a chunk (Delta near 0.3
# under A from -1 down to -16: exp(-0.3) to exp(-4.8) a token; at Delta near
# 2 dALog is a difference of sums a thousand times itself, and `ssd_chunked`
# in float32 is a fifth off the recurrence in float64)
STEPS = {"near_0.01": (-4.8, -4.4), "near_0.3": (-1.2, -0.9)}
# heads, a head's width, groups
LAYOUTS = {"pairs_one_group": (4, 64, 1), "pairs_two_groups": (4, 64, 2),
           "whole_one_group": (2, 128, 1), "whole_two_groups": (2, 128, 2)}
NAMES = ("X", "B", "C", "Dt", "ALog", "D", "DtBias")
CALLS = ("fwd", "bwd")
CHUNK, N = 16, 128


def _operands(T, layout, dtype, step="near_0.01", seed=0, B=1):
    H, P, G = LAYOUTS[layout]
    rs = np.random.RandomState(seed)
    lo, hi = STEPS[step]
    bias = rs.uniform(-0.2, 0.2, H)
    cast = lambda a, to=dtype: jnp.asarray(a, to)              # noqa: E731
    return (cast(rs.randn(B, T, H * P)),
            cast(rs.randn(B, T, G * N) / 4), cast(rs.randn(B, T, G * N) / 4),
            cast(rs.uniform(lo, hi, (B, T, H)) - bias),
            cast(np.log(rs.uniform(1.0, 16.0, H)), jnp.float32),
            cast(rs.uniform(0.5, 1.5, H), jnp.float32),
            cast(bias, jnp.float32), cast(rs.randn(B, T, H * P)))


def _plain(H, G, chunk):
    """The op's plain emission on the kernels' operands."""
    def scan(x, b, c, dt, a_log, d, bias):
        wide = ssm_ops.wide_dtype(x.dtype)
        Bt, T, width = x.shape
        xh = x.reshape(Bt, T, H, width // H)
        y = ssm_ops.ssd_chunked(
            xh, jax.nn.softplus(dt.astype(wide) + bias.astype(wide)),
            -jnp.exp(a_log.astype(wide)), b.reshape(Bt, T, G, -1),
            c.reshape(Bt, T, G, -1), chunk)
        out = y + d.astype(wide)[:, None] * xh.astype(wide)
        return out.astype(x.dtype).reshape(x.shape)
    return scan


def _recurrence(H, G):
    """S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T, y_t = S_t C_t + D
    x_t a head, token by token, in the widest float; rounded once, to X's
    dtype."""
    def scan(x, b, c, dt, a_log, d, bias):
        wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        out = x.dtype
        x, b, c, dt, a_log, d, bias = (t.astype(wide) for t in (
            x, b, c, dt, a_log, d, bias))
        Bt, T, width = x.shape
        a = -jnp.exp(a_log)

        def one(x, b, c, dt):       # [T, H, P], [T, G, N] x 2, [T, H]
            heads = lambda m: jnp.repeat(m, H // G, axis=1)   # noqa: E731

            def token(S, at):
                x, b, c, delta = at             # [H, P], [H, N] x 2, [H]
                S = (jnp.exp(delta * a)[:, None, None] * S
                     + (delta[:, None] * x)[:, :, None] * b[:, None, :])
                return S, jnp.einsum("hpn,hn->hp", S, c) + d[:, None] * x
            return jax.lax.scan(
                token, jnp.zeros((H, width // H, b.shape[-1]), wide),
                (x, heads(b), heads(c), jax.nn.softplus(dt + bias)))[1]

        y = jax.vmap(one)(x.reshape(Bt, T, H, -1), b.reshape(Bt, T, G, -1),
                          c.reshape(Bt, T, G, -1), dt)
        return y.reshape(Bt, T, width).astype(out)
    return scan


def _close(got, want, tol):
    """Within `tol` of the largest entry."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("step", list(STEPS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ssd_kernels_match_the_plain_emission(dtype, layout, step):
    """Three chunks of 16 tokens: Out and all seven gradients against
    `ssd_chunked` at the same chunk and its jax.vjp.  bf16 operands: the
    same float32 inside, the same operands rounded, Out, dX, dB, dC and dDt
    rounded once."""
    H, P, G = LAYOUTS[layout]
    *ops, do = _operands(3 * CHUNK, layout, jnp.dtype(dtype), step)
    how = dict(heads=H, groups=G, chunk=CHUNK, interpret=True)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_plain(H, G, CHUNK), do, *ops)
        got, states = K.ssd_fwd(*ops, **how)
        mine = K.ssd_bwd(do, *ops, states, **how)
    assert got.dtype == ops[0].dtype and states.dtype == jnp.float32
    assert states.shape == (1, 3, H * P, N)         # [B, T / Q, H P, N]
    assert not np.asarray(states[:, 0]).any()       # S = 0 comes in
    assert np.asarray(states[:, 1]).any()
    assert [a.dtype for a in mine] == [a.dtype for a in ops]
    _close(got, want, 5e-6 if dtype == "float32" else 1e-2)
    for name, a, b in zip(NAMES, mine, grads):
        assert np.abs(np.asarray(b, np.float32)).max() > 0, name
        # the heads' parameters: sums over every token of rounded terms
        wide = 3.0 if name in ("ALog", "D", "DtBias") else 1.0
        _close(a, b, wide * (5e-5 if dtype == "float32" else 1e-2))


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5), ("bfloat16", 2e-2)])
def test_ssd_kernels_match_the_recurrence(dtype, tol, layout):
    """The `custom_vjp` over the pair against the literal recurrence in the
    widest float and ITS jax.vjp, two sequences: nothing of the chunked form
    (the tiles, the carried state, the cumulative decays) is shared with
    the oracle."""
    H, P, G = LAYOUTS[layout]
    *ops, do = _operands(3 * CHUNK, layout, jnp.dtype(dtype), seed=3, B=2)
    want, grads = _with_vjp(_recurrence(H, G), do, *ops)
    with jax.enable_x64(False):
        got, mine = jax.vjp(K.make_ssd_scan(H, G, CHUNK, True), *ops)
        mine = mine(do)
    _close(got, want, tol)
    for a, b in zip(mine, grads):
        _close(a, b, tol)


def test_ssd_from_saved_launches_no_forward(monkeypatch):
    """The plain `custom_vjp` launches the ONE forward, which writes the
    states, and under a vjp the reverse pass; `.keeping` hands the states
    out of one launch and `.from_saved` differentiates as the reverse pass
    over them alone: the same gradients, bit for bit."""
    *ops, do = _operands(2 * CHUNK, "pairs_one_group", jnp.float32)
    scan = K.make_ssd_scan(4, 1, CHUNK, True)
    launched = _spy_on_calls(monkeypatch, K, CALLS)
    with jax.enable_x64(False):
        assert scan(*ops).shape == do.shape and launched == ["fwd"]
        del launched[:]
        assert scan.bare(*ops).shape == do.shape and launched == ["fwd"]
        del launched[:]
        want_o, want = jax.vjp(scan, *ops)
        want = want(do)
        assert launched == ["fwd", "bwd"]
        del launched[:]
        out, states = scan.keeping(*ops)
        assert launched == ["fwd"]
        got_o, back = jax.vjp(
            lambda *a: scan.from_saved(*a, out, states), *ops)
        got = back(do)
        assert launched == ["fwd", "bwd"] and got_o is out
        grads = jax.vjp(lambda *a: scan.keeping(*a)[0], *ops)[1](do)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_o))
    for a, b, c in zip(got, want, grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(b))


@pytest.mark.parametrize("T,chunk,H,P,N,G,dtype,want", [
    (8192, 256, 64, 64, 128, 1, "bfloat16", True),        # the cell's
    (8192, 256, 64, 64, 128, 8, "bfloat16", True),    # pairs inside groups
    (512, 128, 4, 128, 256, 2, "float32", True),
    (8192, 256, 64, 64, 128, 1, "float64", False),    # the numeric checks
    (8192, 256, 64, 64, 128, 1, "float16", False),
    (8200, 256, 64, 64, 128, 1, "bfloat16", False),   # T off the chunks
    (12, 256, 64, 64, 128, 1, "bfloat16", False),     # T under a chunk
    (8192, 250, 64, 64, 128, 1, "bfloat16", False),   # a chunk off the rows
    (8192, 256, 64, 64, 64, 1, "bfloat16", False),    # N off the lanes
    (8192, 256, 64, 32, 128, 1, "bfloat16", False),   # four heads a tile
    (8192, 256, 64, 256, 128, 1, "bfloat16", False),  # a head of two tiles
    (8192, 256, 64, 3, 128, 1, "bfloat16", False),
    (8192, 256, 64, 64, 128, 64, "bfloat16", False),  # a pair on two groups
    (8192, 256, 63, 64, 128, 1, "bfloat16", False),   # a head without a pair
    (8192, 256, 64, 64, 128, 3, "bfloat16", False)])  # groups off the heads
def test_ssd_kernels_take_whole_tiles(T, chunk, H, P, N, G, dtype, want):
    assert K.usable(T, chunk, H, P, N, G, jnp.dtype(dtype)) is want


@pytest.mark.parametrize("layout", ["pairs_one_group", "whole_two_groups"])
@pytest.mark.parametrize("which", CALLS)
def test_ssd_kernels_keep_state_delta_and_exponents_in_float32(which, layout):
    """On bf16 X, B, C, Dt and dOut the carried state (VMEM scratch), every
    other scratch tile, Delta (the softplus), every exponential and sum are
    float32; bf16 is what is loaded, stored, stacked and handed to a product,
    and every product accumulates into float32."""
    H, P, G = LAYOUTS[layout]
    with jax.enable_x64(False):
        *ops, do = _operands(2 * CHUNK, layout, jnp.bfloat16)
        calls, operands = K._prepared(*ops, H, G, CHUNK, True)
        call = dict(zip(CALLS, calls))[which]
        if which == "bwd":
            operands += (do, jnp.zeros((1, 2, H * P, N), jnp.float32))
        jaxpr = jax.make_jaxpr(call)(*operands)
    (kernel,) = [e for e in _inner_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
    body = kernel.params["jaxpr"]
    scratch = {(v.aval.shape, str(v.aval.dtype))
               for v in body.invars[-(10 if which == "bwd" else 6):]}
    assert ((H * P, N), "float32") in scratch       # the state, or dS
    assert ((CHUNK, H), "float32") in scratch       # c and Delta
    assert {dtype for _, dtype in scratch} == {"float32"}
    eqns = list(_inner_eqns(body))
    narrow = [e for e in eqns if any(
        str(getattr(v.aval, "dtype", "")) == "bfloat16"
        for v in list(e.invars) + list(e.outvars))]
    # (the `fori_loop` over the blocks is handed the refs themselves)
    assert narrow and {e.primitive.name for e in narrow} <= {
        "get", "swap", "convert_element_type", "concatenate", "dot_general",
        "while"}
    for name in ("exp", "log1p", "logistic", "reduce_sum", "dot_general"):
        made = [e for e in eqns if e.primitive.name == name]
        assert made or name == "logistic", name
        assert all(str(e.outvars[0].aval.dtype) == "float32"
                   for e in made), name
    wide = {"fwd": ["bfloat16", "float32"],
            "bwd": ["bfloat16"] * 4 + ["float32"] * 2}[which]
    assert [str(a.dtype) for a in jaxpr.out_avals] == wide


# ---------------------------------------------------------------------------
# AOT: the two kernels alone, compiled for a described v5e at the cell's
# shape (no whole step: tests/benchmarks/test_granite_cell.py compiles that)


@pytest.mark.slow
@pytest.mark.parametrize("kernel", [K.FWD, K.BWD])
def test_ssd_kernels_compile_for_a_v5e_at_the_cells_shape(kernel, v5e):
    """X [1, 8192, 4096] bf16, 64 heads of 64 on a state of 128, one group,
    at the kernels' own CHUNK: ONE Mosaic call inside the VMEM it asks for,
    named as the benchmark's readers find it: by the scope it was emitted
    in."""
    import functools
    import re

    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.observability.attribution import part_scope

    one = SingleDeviceSharding(v5e)
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    T, H, P = 8192, 64, 64
    ops = (sds((1, T, H * P)), sds((1, T, N)), sds((1, T, N)),
           sds((1, T, H))) + (sds((H,), jnp.float32),) * 3
    states = sds((1, T // K.CHUNK, H * P, N), jnp.float32)

    def scoped(fn):
        @functools.wraps(fn)
        def call(*a):
            with part_scope("ssd.scan"):
                return fn(*a, heads=H)
        return call

    with jax.enable_x64(False):
        if kernel == K.FWD:
            lowered = jax.jit(scoped(K.ssd_fwd)).lower(*ops)
        else:
            lowered = jax.jit(scoped(K.ssd_bwd)).lower(ops[0], *ops, states)
        text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    (name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "pdtpu.ssd.scan" in name and kernel in name, name
