"""The gated-DeltaNet + gated-attention hybrid decoder's parts, each against
plain numpy or the benchmark's plain reference: the chunked gated delta
rule against the token-by-token recurrence in float64 (values, and the op's
gradients against central differences), the convolution, the gates and the
gated norm inside the op, attention with its output gate and a partial
rotary turn, the flash kernels (interpreted) at D = Dv = 256 with 16 query
heads on 2 key/value heads, the shared expert's gate, the SHARE test (the
16 ranks' partial sums, the gated shared expert counted once, add up to the
uncut layer), and `decoder_lm`'s fifth mixer.
ops/sparse_linear_ops.py, ops/attention_ops.py, ops/llm_ops.py,
ops/moe_ops.py, layers/nn.py, models/transformer.py."""

import logging
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import (_by_labels, _dot, _r, _run_layer, _silu,
                          _with_vjp)
from op_test import OpTestHarness

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CONFIG = "qwen3-next-80b-a3b"


# ---------------------------------------------------------------------------
# the gated delta rule


def _delta_numpy(q, k, v, g, beta):
    """The literal recurrence.  q, k [Hk, T, Dk]; v [Hk, G, T, Dv]; g,
    beta [Hk, G, T] -> [Hk, G, T, Dv]."""
    Hk, T, Dk = q.shape
    G, Dv = v.shape[1], v.shape[-1]
    out = np.zeros((Hk, G, T, Dv))
    for h in range(Hk):
        for j in range(G):
            S = np.zeros((Dk, Dv))
            for t in range(T):
                S = np.exp(g[h, j, t]) * S
                S = S + beta[h, j, t] * np.outer(
                    k[h, t], v[h, j, t] - S.T @ k[h, t])
                out[h, j, t] = S.T @ q[h, t]
    return out


# a token's log-decay: near 0 (the state is kept), near -inf (forgotten
# within a token or two), and what the initialisation draws
DECAYS = {"kept": (1e-4, 1e-3), "forgotten": (2.0, 6.0),
          "drawn": (1e-3, 1.6)}


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("chunk, T", [(16, 48), (64, 192), (16, 16)])
def test_gated_delta_chunked_matches_the_recurrence(chunk, T, decay):
    """Three chunks of 16 and of 64, and one chunk; decays near 1 and near
    0: the chunk's inverse, the carried state and every decay factor."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import gated_delta_chunked

    Hk, G, Dk, Dv = 2, 2, 8, 6
    q, k = _r(Hk, T, Dk, seed=1), _r(Hk, T, Dk, seed=2)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = _r(Hk, G, T, Dv, seed=3)
    g = -_r(Hk, G, T, lo=DECAYS[decay][0], hi=DECAYS[decay][1], seed=4)
    beta = _r(Hk, G, T, lo=0.05, hi=0.95, seed=5)
    with jax.enable_x64(True):  # one program: op by op it is 40 to 60
        got = jax.jit(lambda *a: gated_delta_chunked(*a, chunk=chunk))(
            *(jnp.asarray(a[None]) for a in (q, k, v, g, beta)))
    assert got.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(got)[0],
                               _delta_numpy(q, k, v, g, beta), atol=1e-9)


def test_gated_delta_chunked_keeps_state_and_gates_in_float32():
    """On bf16 q, k, v the scan's carried state, every decay and the
    result are float32: the reference's check cannot see a bf16 state at
    the cell's size (PERF.md, PR 48), so the traced types hold it."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import gated_delta_chunked

    with jax.enable_x64(False):
        sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa
        jaxpr = jax.make_jaxpr(
            lambda *a: gated_delta_chunked(*a, chunk=16))(
            sds(1, 2, 64, 8), sds(1, 2, 64, 8), sds(1, 2, 2, 64, 8),
            sds(1, 2, 2, 64, dt=jnp.float32),
            sds(1, 2, 2, 64, dt=jnp.float32))
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    carried = [v.aval for v in scan.outvars[:scan.params["num_carry"]]]
    assert [(a.shape, str(a.dtype)) for a in carried] == [
        ((1, 2, 2, 8, 8), "float32")]
    assert str(jaxpr.out_avals[0].dtype) == "float32"
    exps = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "exp"]
    assert exps and all(str(e.outvars[0].aval.dtype) == "float32"
                        for e in exps)
    # the two score products take the bf16 operands as they are
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    bf16 = [e for e in dots
            if all(str(v.aval.dtype) == "bfloat16" for v in e.invars)]
    assert len(bf16) == 2 and all(
        str(e.outvars[0].aval.dtype) == "float32" for e in bf16)


def test_unit_lower_inverse_and_its_vjp():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import _unit_lower_inverse

    with jax.enable_x64(True):
        a = jnp.asarray(np.tril(_r(3, 16, 16, seed=7), -1))
        solve = _unit_lower_inverse()
        want = np.linalg.inv(np.eye(16) - np.asarray(a))
        np.testing.assert_allclose(np.asarray(solve(a)), want, atol=1e-10)
        w = jnp.asarray(_r(3, 16, 16, seed=8))
        got = jax.grad(lambda a: jnp.sum(solve(a) * w))(a)
        plain = jax.grad(lambda a: jnp.sum(
            jnp.linalg.inv(jnp.eye(16) - a) * w))(a)
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   atol=1e-9)


def _gdn_case(T=12, Hk=2, G=2, Dk=4, Dv=4, L=4, seed=0):
    Hv = Hk * G
    mixed = 2 * Hk * Dk + Hv * Dv
    ins = {"X": _r(1, T, mixed + Hv * Dv, seed=seed),
           "BA": _r(1, T, 2 * Hv, seed=seed + 1),
           "Conv": _r(mixed, L, lo=-0.5, hi=0.5, seed=seed + 2),
           "ALog": np.log(_r(Hv, lo=1.0, hi=4.0, seed=seed + 3)),
           "DtBias": _r(Hv, lo=-3.0, hi=-1.0, seed=seed + 4),
           "Norm": _r(Dv, lo=0.5, hi=1.5, seed=seed + 5)}
    attrs = {"key_heads": Hk, "value_heads": Hv, "key_dim": Dk,
             "epsilon": 1e-6}
    return ins, attrs


@pytest.fixture
def small_chunks(monkeypatch):
    """The op's scan in chunks of 4 tokens (its constant is 128: a toy
    sequence would be one chunk), so that a toy case carries a state."""
    from paddle_tpu.ops import sparse_linear_ops

    monkeypatch.setattr(sparse_linear_ops, "DELTA_CHUNK", 4)


def _gdn_numpy(ins, attrs):
    """The op from its docstring, token by token."""
    x, ba, w = ins["X"][0], ins["BA"][0], ins["Conv"]
    Hk, Hv, Dk = attrs["key_heads"], attrs["value_heads"], attrs["key_dim"]
    T, L = x.shape[0], w.shape[1]
    G = Hv // Hk
    mixed = w.shape[0]
    Dv = (mixed - 2 * Hk * Dk) // Hv
    padded = np.concatenate([np.zeros((L - 1, mixed)), x[:, :mixed]])
    c = _silu(sum(w[:, j] * padded[j:j + T] for j in range(L)))
    q = c[:, :Hk * Dk].reshape(T, Hk, Dk)
    k = c[:, Hk * Dk:2 * Hk * Dk].reshape(T, Hk, Dk)
    v = c[:, 2 * Hk * Dk:].reshape(T, Hk, G, Dv)
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa
    q, k = unit(q) / np.sqrt(Dk), unit(k)
    beta = 1 / (1 + np.exp(-ba[:, :Hv]))
    g = -np.exp(ins["ALog"]) * np.log1p(np.exp(ba[:, Hv:] + ins["DtBias"]))
    heads = lambda a: a.reshape(T, Hk, G).transpose(1, 2, 0)  # noqa: E731
    o = _delta_numpy(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                     v.transpose(1, 2, 0, 3), heads(g), heads(beta))
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * ins["Norm"]
    o = o.transpose(2, 0, 1, 3).reshape(T, Hv * Dv)
    return (o * _silu(x[:, mixed:]))[None]


def test_gated_delta_rule_output_and_grad(small_chunks):
    """Three chunks.  The convolution (taps, zero history, SiLU), the
    gates, the l2 norm, the key head a value head reads, the gated per-head
    norm: the op against the recurrence; every input's gradient, through the scan's
    `jax.checkpoint` and the inverse's own vjp, against central
    differences."""
    ins, attrs = _gdn_case()
    h = OpTestHarness("gated_delta_rule", ins, attrs)
    h.check_output({"Out": _gdn_numpy(ins, attrs)}, atol=1e-6)
    h.check_grad(sorted(ins), max_relative_error=1e-2)


def test_gated_delta_rule_refuses_shapes_that_do_not_add_up(small_chunks):
    ins, attrs = _gdn_case()
    ins["BA"] = ins["BA"][..., :-1]
    with pytest.raises(Exception, match="gated_delta_rule: X"):
        OpTestHarness("gated_delta_rule", ins, attrs).fetch()
    ins, attrs = _gdn_case(T=10)          # chunks of 4 do not divide 10
    with pytest.raises(Exception, match="do not divide"):
        OpTestHarness("gated_delta_rule", ins, attrs).fetch()


def test_gated_delta_net_draws_its_decay_parameters():
    """A_log is the log of a uniform draw on [1, 16); softplus(dt_bias) is
    log-uniform on [0.001, 0.1]."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[64, 32], dtype="float32")
    fluid.layers.gated_delta_net(x, 2, 256, 8, 8)
    startup = fluid.default_startup_program()
    startup.random_seed = 3
    fluid.Executor(fluid.CPUPlace()).run(startup)
    ps = fluid.default_main_program().global_block().all_parameters()
    assert [tuple(p.shape) for p in ps] == [
        (32, 2 * 2 * 8 + 2 * 256 * 8), (32, 512), (32 + 2048, 4), (256,),
        (256,), (8,), (2048, 32)]
    value = lambda p: np.asarray(fluid.global_scope().find(p.name))  # noqa
    a, dt = np.exp(value(ps[3])), np.log1p(np.exp(value(ps[4])))
    assert 1.0 <= a.min() < 2.0 and 14.0 < a.max() < 16.0
    assert 1e-3 <= dt.min() < 2e-3 and 5e-2 < dt.max() <= 0.1 + 1e-6
    taps = value(ps[2])
    assert -0.5 <= taps.min() < -0.45 and 0.45 < taps.max() <= 0.5
    assert np.all(value(ps[5]) == 1.0)
    # the draws are made and taken through log / exp in float32: the
    # generic `uniform_random` op knows nothing of them
    ops = [op.type for op in startup.global_block().ops]
    assert (ops.count("log"), ops.count("exp"), ops.count("cast")) == (
        2, 2, 2)
    assert all("transform" not in op.attrs
               for op in startup.global_block().ops)


# ---------------------------------------------------------------------------
# attention: the output gate, the partial rotary turn, the wide flash kernels


def test_attention_output_gate_output_and_grad():
    x, gate = _r(2, 5, 12, seed=1), _r(2, 5, 12, lo=-3, hi=3, seed=2)
    h = OpTestHarness("attention_output_gate", {"X": x, "Gate": gate},
                      {"num_heads": 3, "num_kv_heads": 1, "head_dim": 4,
                       "rotary_dim": 2})
    h.check_output({"Out": x / (1 + np.exp(-gate))}, atol=1e-6)
    h.check_grad(["X", "Gate"], max_relative_error=1e-2)


def _partial_rope_numpy(y, theta, rotary):
    """y [..., T, D]: rotate-half on the first `rotary` columns."""
    T = y.shape[-2]
    inv = 1.0 / theta ** (np.arange(0, rotary, 2) / rotary)
    ang = np.arange(T)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1)
    a = y[..., :rotary]
    rot = np.concatenate([-a[..., rotary // 2:], a[..., :rotary // 2]], -1)
    return np.concatenate([a * np.cos(ang) + rot * np.sin(ang),
                           y[..., rotary:]], -1)


@pytest.mark.parametrize("rotary", [4, 8, 16])
def test_head_norm_rope_turns_the_first_columns_alone(rotary):
    """rotary_dim 4 and 8 of a head of 16 (their own frequencies, the rest
    unturned, after the norm), and 16: the whole head, the op as it was."""
    heads, d = 3, 16
    x, gain = _r(2, 6, heads * d, seed=1), _r(d, lo=0.5, hi=1.5, seed=2)
    y = x.reshape(2, 6, heads, d).transpose(0, 2, 1, 3)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * gain
    h = OpTestHarness("head_norm_rope", {"X": x, "Scale": gain},
                      {"num_heads": heads, "theta": 100.0, "epsilon": 1e-6,
                       "rotary_dim": rotary})
    h.check_output({"Out": _partial_rope_numpy(y, 100.0, rotary)},
                   atol=1e-5)
    h.check_grad(["X", "Scale"], max_relative_error=1e-2)
    with pytest.raises(Exception, match="rotary_dim"):
        OpTestHarness("head_norm_rope", {"X": x},
                      {"num_heads": heads, "rotary_dim": 18}).fetch()


def _toy_ref_cfg():
    return {"num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 100.0,
            "partial_rotary_factor": 0.25, "linear_num_key_heads": 2,
            "linear_num_value_heads": 4, "linear_key_head_dim": 8,
            "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
            "num_experts_per_tok": 4, "share": {"first_expert": 0}}


def test_gated_attention_layer_is_the_plain_version():
    """`multi_head_attention` with an output gate and a rotary turn on 4
    of a head's 16 columns: seven parameters in the reference's order, the
    query projection twice as wide, and the plain reference's result."""
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", CONFIG)
    T, D = 24, 32
    x = _r(1, T, D, seed=1).astype(np.float32)
    got, ps = _run_layer(lambda x: fluid.layers.multi_head_attention(
        x, x, x, num_heads=4, causal=True, qk_norm_epsilon=1e-6,
        qk_norm_per_head=True, rope_theta=100.0, num_kv_heads=2,
        head_dim=16, output_gate=True, rotary_dim=4), x)
    assert [p.shape for p in ps] == [(D, 128), (D, 32), (D, 32), (16,),
                                     (16,), (64, D)]
    ops = [op.type for op in
           fluid.default_main_program().global_block().ops]
    assert ops.count("attention_output_gate") == 1
    assert ops.count("slice") == 2
    gates = [op for op in fluid.default_main_program().global_block().ops
             if op.attrs.get("part") == "attn.gate"]
    assert len(gates) == 3
    import jax

    # the layer and its two mutants as ONE program, not op by op
    plain = jax.jit(lambda x, ps: {mutant: ref.attention(
        x, ps, _toy_ref_cfg(), mutant, _dot, lambda a: a)
        for mutant in ("", "no_out_gate", "full_rotary")})(
        jnp.asarray(x[0]), [jnp.asarray(p) for p in ps])
    np.testing.assert_allclose(got[0], plain[""], atol=2e-5)
    # without the gate it is another function, and so with a whole turn
    for mutant in ("no_out_gate", "full_rotary"):
        assert np.abs(np.asarray(plain[mutant]) - got[0]).max() > 1e-3, (
            mutant)
    with pytest.raises(ValueError, match="rotary_dim"):
        fluid.reset()
        v = fluid.layers.data("x", shape=[T, D], dtype="float32")
        fluid.layers.multi_head_attention(v, v, v, 4, rotary_dim=4)


def _dense_attention(q, k, v):
    """q [B, H, T, D], k, v [B, Hkv, T, D] -> causal attention, float32."""
    import jax
    import jax.numpy as jnp

    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    T = q.shape[2]
    s = jnp.einsum("bhtd,bhjd->bhtj", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                                 -jnp.inf), axis=-1)
    return jnp.einsum("bhtj,bhjd->bhtd", p, v, precision="highest")


def test_flash_kernels_at_256_wide_heads_group_of_8():
    """The three kernels, interpreted, on 16 query heads on 2 key/value
    heads of 256 lanes in q, k AND v (several q and K blocks, so the
    forward carries its columns and dkv sums a group of 8): out, dq, dk,
    dv against dense float32 attention."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    B, H, Hkv, T, D = 1, 16, 2, 64, 256
    rng = np.random.RandomState(0)
    q, do = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))
            for _ in range(2))
    kw = dict(causal=True, block_q=16, block_k=32, interpret=True)
    out, lse = fa.flash_attention_fwd(q, k, v, **kw)
    dq, dk, dv = fa.flash_attention_bwd(q, k, v, out, lse, do, **kw)
    want, grads = _with_vjp(_dense_attention, do, q, k, v)
    for name, got, ref in zip(("out", "dq", "dk", "dv"),
                              (out, dq, dk, dv), (want,) + grads):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-4, rtol=3e-4, err_msg=name)


class _OneTpu:
    """What `flash_single_chip` asks of an emitter's context, on one TPU."""

    mesh, is_test = None, True

    def target_platform(self):
        return "tpu"


def test_flash_single_chip_takes_wide_values_and_names_what_it_refuses(
        monkeypatch, caplog):
    """Values of 256 lanes pass the gate under keys of 256; values of any
    other width stop at 128 (192 / 192 too: no kernel has seen it); and a
    shape that falls to the dense path at T >= 4096 is named in ONE
    warning."""
    import jax

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import _common
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    monkeypatch.setattr(_common, "pallas_dispatch_ok", lambda ctx: True)
    seen = []
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, **kw: seen.append(
            (q.shape[-1], v.shape[-1], kw.get("block_q"),
             kw.get("block_k"))) or q)
    sds = lambda *s: jax.ShapeDtypeStruct(s, "bfloat16")  # noqa: E731
    ok = lambda q, k, v: attention_ops.flash_single_chip(  # noqa: E731
        _OneTpu(), q, k, v, True)
    assert ok(sds(1, 16, 8192, 256), sds(1, 2, 8192, 256),
              sds(1, 2, 8192, 256)) is not None
    assert ok(sds(1, 16, 8192, 192), sds(1, 16, 8192, 192),
              sds(1, 16, 8192, 128)) is not None
    attention_ops._warn_dense_once.cache_clear()
    with caplog.at_level(logging.WARNING):
        for _ in range(2):
            assert ok(sds(1, 16, 8192, 256), sds(1, 16, 8192, 256),
                      sds(1, 16, 8192, 192)) is None
        assert ok(sds(1, 4, 4096, 320), sds(1, 4, 4096, 320),
                  sds(1, 4, 4096, 320)) is None
        assert ok(sds(1, 4, 4096, 192), sds(1, 4, 4096, 192),
                  sds(1, 4, 4096, 192)) is None
        assert ok(sds(1, 4, 1024, 320), sds(1, 4, 1024, 320),
                  sds(1, 4, 1024, 320)) is None     # short: no warning
    warned = [r.getMessage() for r in caplog.records
              if "flash kernels' contract" in r.getMessage()]
    assert len(warned) == 3 and "(1, 16, 8192, 192)" in warned[0]
    # no width names a block: the kernels choose theirs from the call's
    # shape (flash_attention.call_blocks; tests/test_flash_blocks.py)
    assert seen == [(256, 256, None, None), (192, 128, None, None)]


# ---------------------------------------------------------------------------
# the expert layer: the shared expert's gate, and the share


def _moe_layer(held, shared_gate=True, E=32, k=4, H=8):
    return lambda x: fluid.layers.moe(
        fluid.layers.reshape(x, [-1, x.shape[-1]]), E, H, act="silu",
        top_k=k, gated=True, dropless=True, held=held, scoring="softmax",
        renormalise=True, shared_hidden=H, shared_gate=shared_gate).out


def test_the_16_ranks_shares_add_up_to_the_uncut_layer():
    """32 experts over 16 ranks of 2: every rank routes all tokens over
    all 32 (softmax, top-4, renormalised) and computes the pairs on its
    own two; the partial sums, each WITHOUT the shared expert, plus the
    gated shared expert ONCE, are the uncut layer's result (`held` = all
    32), which is the plain reference's expert block.  Summed here over
    four shares that cover the 32: the first and the last rank's own two,
    and the 28 between them in two shares (a share is a program to
    compile, 3 s each; a wrong offset or count at either end or in the
    middle moves the sum)."""
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", CONFIG)
    T, D, E, H = 48, 16, 32, 8
    x = _r(1, T, D, seed=1).astype(np.float32)
    full, ps = _run_layer(_moe_layer((0, E)), x)
    assert [p.shape for p in ps] == [(D, E), (E, D, H), (E, D, H), (E, H, D),
                                     (D, H), (D, H), (H, D), (D, 1)]
    zero_shared = {6: np.zeros((H, D), np.float32)}
    total = 0.0
    for first, count in ((0, 2), (2, 14), (16, 14), (30, 2)):
        mine = slice(first, first + count)
        part, _ = _run_layer(
            _moe_layer((first, count)), x,
            {0: ps[0], 1: ps[1][mine], 2: ps[2][mine], 3: ps[3][mine],
             4: ps[4], 5: ps[5], 7: ps[7], **zero_shared})
        total = total + part
    shared_alone, _ = _run_layer(
        _moe_layer((0, 2)), x,
        {0: ps[0], 1: np.zeros_like(ps[1][:2]), 2: ps[2][:2],
         3: ps[3][:2], 4: ps[4], 5: ps[5], 6: ps[6], 7: ps[7]})
    np.testing.assert_allclose(total + shared_alone, full, atol=2e-5)
    assert np.abs(shared_alone).max() > 1e-3
    # the uncut layer is the reference's block (x + ... with a unit norm)
    h = jnp.asarray(x[0])
    _, w, _ = ref.route(h, jnp.asarray(ps[0]), _toy_ref_cfg())
    want = ref.held_experts(h, w, *(jnp.asarray(p) for p in ps[1:4]))
    shared = _dot(_silu_j(_dot(h, ps[4])) * _dot(h, ps[5]), ps[6])
    want = want + shared / (1 + jnp.exp(-_dot(h, ps[7])))
    np.testing.assert_allclose(full, np.asarray(want), atol=2e-5)
    # ungated it is another layer
    ungated, _ = _run_layer(_moe_layer((0, E), shared_gate=False), x,
                            dict(enumerate(ps[:7])))
    assert np.abs(ungated - full).max() > 1e-3


def _silu_j(a):
    import jax

    return jax.nn.silu(a)


def test_shared_gate_needs_a_shared_expert():
    fluid.reset()
    x = fluid.layers.data("x", shape=[16], dtype="float32")
    with pytest.raises(ValueError, match="shared_gate"):
        fluid.layers.moe(x, 8, 4, top_k=2, gated=True, dropless=True,
                         held=(0, 2), shared_gate=True)


# ---------------------------------------------------------------------------
# the mixer as a layer, and decoder_lm's fifth kind


def test_gated_delta_net_layer_is_the_plain_version(monkeypatch):
    """Eight parameters' worth of a DeltaNet block in the reference's
    order, the projections under `pdtpu.gdn.project`, and the plain
    reference's token-by-token result, over eight chunks."""
    import jax.numpy as jnp

    import harness

    from paddle_tpu.ops import sparse_linear_ops

    monkeypatch.setattr(sparse_linear_ops, "DELTA_CHUNK", 16)
    ref = harness.load_module("reference", CONFIG)
    T, D = 128, 32
    x = _r(1, T, D, seed=1).astype(np.float32)
    got, ps = _run_layer(lambda x: fluid.layers.gated_delta_net(
        x, 2, 4, 8, 8, conv_kernel=4), x)
    assert [p.shape for p in ps] == [(D, 96), (D, 8), (64, 4), (4,), (4,),
                                     (8,), (32, D)]
    block = fluid.default_main_program().global_block()
    assert [op.attrs.get("part") for op in block.ops
            if op.type == "mul"] == ["gdn.project"] * 3
    import jax

    with jax.enable_x64(False):   # the reference is float32, as on the chip
        mutants = ("no_state", "no_beta", "no_decay", "no_l2norm",
                   "key_head_mod", "taps_reversed", "no_z_gate")
        # the mixer and its seven mutants as ONE program, not op by op
        plain = jax.jit(lambda x, ps: {mutant: ref.delta_net(
            x, ps, _toy_ref_cfg(), mutant, _dot)[0]
            for mutant in ("",) + mutants})(
            jnp.asarray(x[0]), [jnp.asarray(p) for p in ps])
        np.testing.assert_allclose(got[0], plain[""], atol=2e-5)
        for mutant in mutants:
            assert np.abs(np.asarray(plain[mutant]) - got[0]).max() > 1e-3, (
                mutant)
    with pytest.raises(ValueError, match="value heads"):
        fluid.reset()
        v = fluid.layers.data("x", shape=[T, D], dtype="float32")
        fluid.layers.gated_delta_net(v, 3, 4, 8, 8)


def test_decoder_lm_names_its_five_mixers():
    from paddle_tpu.models import transformer

    # the five it had at PR 48; PR 52 appended three (test_phi4flash.py)
    assert transformer._MIXERS[:5] == (
        "attention", "conv", "sparse_attention", "linear_attention",
        "gated_delta_net")
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
    with pytest.raises(ValueError, match="gated_delta_net"):
        transformer.decoder_lm(tokens, 32, 16, 1, 2, 16,
                               layer_types=["delta"])
    with pytest.raises(ValueError, match="every block with experts"):
        transformer.build_qwen3_next_lm_train_program(
            16, 32, 16, ["sliding"], 2, 1, 8, 4, 1, 2, 8, 8, 4, 8, 4, 2, 1,
            2)


def test_qwen3_next_program_counts_what_it_traced(monkeypatch):
    """The builder at a toy size through `Executor`: the ops of a period
    (three `gated_delta_rule`, one gated attention), the loss falls, and
    the two counter families say what was traced, once a layer."""
    from paddle_tpu import observability as obs
    from paddle_tpu.models.transformer import (
        build_qwen3_next_lm_train_program)
    from paddle_tpu.ops import sparse_linear_ops

    monkeypatch.setattr(sparse_linear_ops, "DELTA_CHUNK", 16)
    obs.REGISTRY.reset()
    fluid.reset()
    loss = build_qwen3_next_lm_train_program(
        seq_len=64, vocab_size=64, dim=32,
        layer_types=["linear_attention"] * 3 + ["full_attention"], n_heads=4,
        n_kv_heads=2, head_dim=16, rotary_dim=4, linear_key_heads=2,
        linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
        conv_kernel=4, num_experts=16, expert_dim=8, top_k=4,
        shared_experts=1, held_experts=4, buffer_rows=128,
        dtype="float32", learning_rate=3e-3, emb_init_scale=1.0)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 5
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("gated_delta_rule") == 3
    assert ops.count("attention_output_gate") == 1
    assert ops.count("scaled_dot_product_attention") == 1
    assert len(main.global_block().all_parameters()) == 1 + 3 * 17 + 16 + 2
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    tok = np.random.RandomState(0).randint(0, 64, (1, 64, 1)).astype("int64")
    feed = {"tokens": tok, "targets": np.roll(tok, -1, 1)}
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0])
              for _ in range(6)]
    assert losses[-1] < losses[0]
    series = _by_labels
    assert series("gated_delta_layers_traced_total") == {
        (("chunk", "16"), ("conv_taps", "4"), ("head_dim", "8"),
         ("key_heads", "2"), ("value_heads", "4")): 3.0}
    assert series("gated_attention_layers_traced_total") == {
        (("head_dim", "16"), ("kv_heads", "2"), ("q_heads", "4"),
         ("rotary_dim", "4")): 1.0}
    # on the CPU the scan is the plain emission, forward and in the grad
    # op's re-emission, and no grad op is handed a kernel's kept result
    for family in ("gated_delta_kernels_traced_total",
                   "gated_delta_conv_kernels_traced_total"):
        assert series(family) == {(("op", "fwd"), ("path", "xla")): 3.0,
                                  (("op", "grad"), ("path", "xla")): 3.0}
    assert not any(
        ("op", "gated_delta_rule") in labels
        for labels in series("executor_grad_kernel_forward_total"))
    obs.REGISTRY.reset()


def test_gated_delta_net_layer_trains_through_the_kernels(monkeypatch):
    """A DeltaNet block at heads of 128 over two chunks of 128, one SGD
    step of mean(out^2): where the trace targets one TPU the scan and the
    convolution are their kernel pairs (interpreted here), the grad op's
    re-emission reuses the forward op's kept O and q, k, v
    (`executor_grad_kernel_forward_total` reused=1: what
    `kernel_forward_reruns` reads as 0) and the step's loss and updated
    parameters are the plain emission's."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import gated_delta, gdn_conv

    x = _r(1, 256, 32, seed=1).astype(np.float32)

    def step():
        obs.REGISTRY.reset()
        fluid.reset()
        data = fluid.layers.data("x", shape=[256, 32], dtype="float32")
        out = fluid.layers.gated_delta_net(data, 1, 2, 128, 128,
                                           conv_kernel=4)
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
        fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = 3
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (got,) = exe.run(feed={"x": x}, fetch_list=[loss])
        scope = fluid.global_scope()
        fam = obs.REGISTRY.snapshot()["families"]
        series = lambda name: {  # noqa: E731
            tuple(sorted(s["labels"].items())): s["value"]
            for s in fam.get(name, {"series": []})["series"]}
        return (float(got), [np.asarray(scope.find(p.name)) for p in
                             main.global_block().all_parameters()], series)

    families = ("gated_delta_kernels_traced_total",
                "gated_delta_conv_kernels_traced_total")
    loss, params, series = step()
    for family in families:
        assert series(family) == {(("op", "fwd"), ("path", "xla")): 1.0,
                                  (("op", "grad"), ("path", "xla")): 1.0}
    assert series("executor_grad_kernel_forward_total") == {}
    real, real_conv = gated_delta.make_gated_delta, gdn_conv.make_gdn_conv
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(gated_delta, "make_gated_delta",
                        lambda chunk: real(chunk, True))
    monkeypatch.setattr(gdn_conv, "make_gdn_conv",
                        lambda *a: real_conv(*a, True))
    kernel_loss, kernel_params, series = step()
    for family in families:
        assert series(family) == {(("op", "fwd"), ("path", "pallas")): 1.0,
                                  (("op", "grad"), ("path", "pallas")): 1.0}
    assert series("executor_grad_kernel_forward_total") == {
        (("op", "gated_delta_rule"), ("reused", "1")): 1.0}
    assert abs(kernel_loss - loss) <= 1e-5 * abs(loss)
    for a, b in zip(kernel_params, params):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max()
    obs.REGISTRY.reset()
