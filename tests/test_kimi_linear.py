"""Kimi-Linear's parts, each against plain numpy or the benchmark's plain
reference: the chunked delta rule under a decay a CHANNEL against the
token-by-token recurrence (values in float64 and every gradient; gates that
forget within a token on some channels and never on others in float32,
which a `(K e^G)(K e^-G)^T` emission cannot survive; with equal channels
the scalar-gated emission), the op's convolutions, gates and gated norm
against numpy and central differences, latent attention WITHOUT a rotary
turn against the reference and at its default against the parent's jaxpr,
the SHARE test (the 32 ranks' partial sums of 8 experts each, the shared
expert counted once, add up to the uncut layer), the mixer as a layer and
`decoder_lm`'s ninth mixer.
ops/sparse_linear_ops.py, ops/llm_ops.py, layers/nn.py,
models/transformer.py."""

import functools
import hashlib
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _by_labels, _dot, _r, _run_layer, _silu
from op_test import OpTestHarness

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CONFIG = "kimi-linear-48b-a3b"


# ---------------------------------------------------------------------------
# the chunked emission


def _kda_numpy(q, k, v, g, beta):
    """The literal recurrence.  q, k, g [H, T, Dk]; v [H, T, Dv]; beta [H,
    T] -> [H, T, Dv]."""
    H, T, Dk = q.shape
    out = np.zeros((H, T, v.shape[-1]))
    for h in range(H):
        S = np.zeros((Dk, v.shape[-1]))
        for t in range(T):
            S = np.exp(g[h, t])[:, None] * S
            S = S + beta[h, t] * np.outer(k[h, t], v[h, t] - S.T @ k[h, t])
            out[h, t] = S.T @ q[h, t]
    return out


def _kda_scan(q, k, v, g, beta):
    """The same recurrence as a `lax.scan` a token, for `jax.grad`."""
    import jax
    import jax.numpy as jnp

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None] * S
        S = S + bt[:, None, None] * kt[..., None] * (
            vt - jnp.einsum("hkv,hk->hv", S, kt))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    H, T, Dk = q.shape
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((H, Dk, v.shape[-1]), q.dtype), xs)
    return jnp.moveaxis(o, 0, 1)


def _case(T, H=2, Dk=8, Dv=6, decay=(1e-3, 1.6)):
    q, k = _r(H, T, Dk, seed=1), _r(H, T, Dk, seed=2)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, _r(H, T, Dv, seed=3),
            -_r(H, T, Dk, lo=decay[0], hi=decay[1], seed=4),
            _r(H, T, lo=0.05, hi=0.95, seed=5))


# a token's log-decay: near 0 (the state is kept), near -inf (forgotten
# within a token or two), and what the initialisation draws
DECAYS = {"kept": (1e-4, 1e-3), "forgotten": (2.0, 6.0),
          "drawn": (1e-3, 1.6)}


@pytest.mark.parametrize("chunk, sub, T, decay", [
    (16, 4, 48, "kept"), (16, 4, 48, "forgotten"), (16, 4, 48, "drawn"),
    (64, 16, 128, "drawn"), (16, 16, 16, "drawn")])
def test_kda_chunked_matches_the_recurrence(chunk, sub, T, decay):
    """Three chunks of 16 in diagonal blocks of 4 (two levels of halves
    above them), two chunks of 64 in blocks of 16, and one chunk that is
    one block; decays near 1 and near 0: both decayed score matrices, the
    chunk's inverse, the carried state and every decay factor."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import kda_chunked

    case = _case(T, decay=DECAYS[decay])
    with jax.enable_x64(True):  # one program
        got = jax.jit(lambda *a: kda_chunked(*a, chunk=chunk, sub=sub))(
            *(jnp.asarray(a[None]) for a in case))
    assert got.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(got)[0], _kda_numpy(*case),
                               atol=1e-9)


def test_kda_chunked_gradients_are_the_recurrences():
    """d / d(q, k, v, g, beta) of a weighted sum of the chunked result
    against `jax.grad` of the token-by-token scan, float64: through the
    scan over the chunks, the inverse's own vjp and the masked pairwise
    exponents (whose -inf branch must give zeros, not NaN)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import kda_chunked

    case = _case(48)
    w = _r(2, 48, 6, seed=9)
    with jax.enable_x64(True):
        args = tuple(jnp.asarray(a) for a in case)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(kda_chunked(
            *(x[None] for x in a), chunk=16, sub=4)[0] * w),
            argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(_kda_scan(*a) * w),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9,
                                   err_msg=name)


def test_kda_chunked_survives_gates_that_overflow_a_split_product():
    """g = -40 a token on the even channels and 0 on the odd ones, in
    FLOAT32: inside one chunk of 64 the cumulative gate reaches -2560, so
    e^{-G} is inf and `(K e^G)(K e^-G)^T` is NaN (shown here); the emission
    takes no exponential of a positive number: finite in value and in
    every gradient, and equal to the recurrence."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import kda_chunked

    q, k, v, g, beta = _case(128)
    g = np.broadcast_to(np.where(np.arange(8) % 2 == 0, -40.0, 0.0),
                        g.shape).copy()
    G = np.cumsum(g[:, :64].astype(np.float32), axis=1)
    with np.errstate(over="ignore", invalid="ignore"):
        split = (k[:, :64] * np.exp(G)).astype(np.float32) @ np.swapaxes(
            (k[:, :64] * np.exp(-G)).astype(np.float32), -1, -2)
    assert not np.isfinite(split).all()
    with jax.enable_x64(False):
        args = tuple(jnp.asarray(a[None], jnp.float32)
                     for a in (q, k, v, g, beta))
        f = lambda *a: kda_chunked(*a, chunk=64, sub=16)          # noqa: E731
        got = jax.jit(f)(*args)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                                 argnums=(0, 1, 2, 3, 4)))(*args)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)
    np.testing.assert_allclose(np.asarray(got)[0],
                               _kda_numpy(q, k, v, g, beta), atol=2e-5)


def test_kda_with_equal_channels_is_the_scalar_gated_rule():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import (gated_delta_chunked,
                                                  kda_chunked)

    q, k, v, _, beta = _case(64)
    g = -_r(2, 64, lo=1e-3, hi=1.6, seed=6)
    with jax.enable_x64(True):
        got = jax.jit(lambda *a: kda_chunked(*a, chunk=16, sub=4))(*(
            jnp.asarray(a[None]) for a in (
                q, k, v, np.repeat(g[..., None], 8, -1), beta)))
        want = jax.jit(lambda *a: gated_delta_chunked(*a, chunk=16))(
            jnp.asarray(q[None]), jnp.asarray(k[None]),
            jnp.asarray(v[None, :, None]), jnp.asarray(g[None, :, None]),
            jnp.asarray(beta[None, :, None]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[:, :, 0],
                               atol=1e-12)
    with pytest.raises(ValueError, match="do not divide"):
        kda_chunked(*(jnp.asarray(a[None, :, :40]) for a in _case(64)),
                    chunk=16)
    with pytest.raises(ValueError, match="diagonal blocks"):
        kda_chunked(*(jnp.asarray(a[None]) for a in _case(48)), chunk=48)


def test_kda_chunked_is_float32_whatever_comes_in():
    """On bf16 q, k, v the scan's carried state, every decay, both score
    matrices and the result are float32 (`assumed.precision`): no product
    of the emission takes a bf16 operand."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import kda_chunked

    with jax.enable_x64(False):
        sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa
        jaxpr = jax.make_jaxpr(lambda *a: kda_chunked(*a, chunk=32, sub=8))(
            sds(1, 2, 64, 8), sds(1, 2, 64, 8), sds(1, 2, 64, 8),
            sds(1, 2, 64, 8, dt=jnp.float32), sds(1, 2, 64, dt=jnp.float32))
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    carried = [v.aval for v in scan.outvars[:scan.params["num_carry"]]]
    assert [(a.shape, str(a.dtype)) for a in carried] == [
        ((1, 2, 8, 8), "float32")]
    assert str(jaxpr.out_avals[0].dtype) == "float32"
    for name in ("exp", "dot_general"):
        found = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == name]
        assert found and all(str(v.aval.dtype) == "float32" for e in found
                             for v in list(e.invars) + list(e.outvars)), name


# ---------------------------------------------------------------------------
# the op


def _sigmoid(x):
    return 1 / (1 + np.exp(-x))


def _op_case(T=16, H=2, D=4, L=4, seed=0):
    W = H * D
    ins = {"Q": _r(1, T, W, seed=seed), "K": _r(1, T, W, seed=seed + 1),
           "V": _r(1, T, W, seed=seed + 2), "F": _r(1, T, W, seed=seed + 3),
           "Beta": _r(1, T, H, seed=seed + 4),
           "Gate": _r(1, T, W, lo=-2, hi=2, seed=seed + 5),
           "ConvQ": _r(W, L, lo=-0.5, hi=0.5, seed=seed + 6),
           "ConvK": _r(W, L, lo=-0.5, hi=0.5, seed=seed + 7),
           "ConvV": _r(W, L, lo=-0.5, hi=0.5, seed=seed + 8),
           "ALog": np.log(_r(H, lo=1.0, hi=4.0, seed=seed + 9)),
           "DtBias": _r(W, lo=-3.0, hi=-1.0, seed=seed + 10),
           "Norm": _r(D, lo=0.5, hi=1.5, seed=seed + 11)}
    return ins, {"num_heads": H, "epsilon": 1e-5, "gate_rank": D}


def _op_numpy(ins, attrs):
    """The op from its docstring, token by token."""
    H = attrs["num_heads"]
    T, W = ins["Q"].shape[1:]
    D, L = W // H, ins["ConvQ"].shape[1]

    def conv(x, w):
        padded = np.concatenate([np.zeros((L - 1, W)), x[0]])
        return _silu(sum(w[:, j] * padded[j:j + T]
                         for j in range(L))).reshape(T, H, D)

    q, k, v = (conv(ins[a], ins["Conv" + a]) for a in "QKV")
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa
    q, k = unit(q) / np.sqrt(D), unit(k)
    g = -np.exp(ins["ALog"])[None, :, None] * np.log1p(np.exp(
        ins["F"][0] + ins["DtBias"])).reshape(T, H, D)
    o = _kda_numpy(*(a.transpose(1, 0, 2) for a in (q, k, v, g)),
                   _sigmoid(ins["Beta"][0]).T)
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) * ins["Norm"]
    o = o.transpose(1, 0, 2).reshape(T, W)
    return (o * _sigmoid(ins["Gate"][0]))[None]


@pytest.fixture
def small_chunks(monkeypatch):
    """The op's scan in chunks of 8 tokens in diagonal blocks of 2 (its
    constants are 64 and 16: a toy sequence would be one chunk), so that a
    toy case carries a state and climbs two levels of halves."""
    from paddle_tpu.ops import sparse_linear_ops

    monkeypatch.setattr(sparse_linear_ops, "KDA_CHUNK", 8)
    monkeypatch.setattr(sparse_linear_ops, "KDA_SUB", 2)


def test_kimi_delta_attention_output_and_grad(small_chunks):
    """Two chunks.  The three convolutions (taps, zero history, SiLU), the
    l2 norm, the gate a channel (A_log a head, dt_bias a channel), beta a
    head, the sigmoid-gated per-head norm: the op against the recurrence;
    every input's gradient, through the scan's `jax.checkpoint`, against
    central differences."""
    ins, attrs = _op_case()
    h = OpTestHarness("kimi_delta_attention", ins, attrs)
    h.check_output({"Out": _op_numpy(ins, attrs)}, atol=1e-6)
    h.check_grad(sorted(ins), max_relative_error=1e-2)


def test_kimi_delta_attention_refuses_shapes_that_do_not_add_up(
        small_chunks):
    ins, attrs = _op_case()
    ins["Beta"] = ins["Beta"][..., :-1]
    with pytest.raises(Exception, match="kimi_delta_attention: Q"):
        OpTestHarness("kimi_delta_attention", ins, attrs).fetch()
    ins, attrs = _op_case()
    ins["DtBias"] = ins["DtBias"][:2]      # a head's, not a channel's
    with pytest.raises(Exception, match="kimi_delta_attention: Q"):
        OpTestHarness("kimi_delta_attention", ins, attrs).fetch()
    ins, attrs = _op_case(T=12)            # chunks of 8 do not divide 12
    with pytest.raises(Exception, match="do not divide"):
        OpTestHarness("kimi_delta_attention", ins, attrs).fetch()


# ---------------------------------------------------------------------------
# latent attention without a rotary turn


def _toy_ref_cfg():
    return {"num_attention_heads": 2, "kv_lora_rank": 16,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
            "num_experts_per_token": 4, "routed_scaling_factor": 2.446,
            "linear_attn_config": {"num_heads": 2, "head_dim": 8,
                                   "short_conv_kernel_size": 4},
            "share": {"first_expert": 0}}


def test_latent_attention_without_a_turn_is_the_plain_version():
    """`rotary=False`: the same five parameters, the reference's result
    with NOTHING rotated, the scale 24^-1/2 and the shared key in the
    scores; with the turn (the default) it is the reference's `rope`
    mutant, so the argument is what makes the difference; the counter says
    which; `pdtpu.mla.rope` is the turn's alone."""
    import jax
    import jax.numpy as jnp

    import harness
    from paddle_tpu import observability as obs

    ref = harness.load_module("reference", CONFIG)
    x = _r(1, 64, 32, seed=1).astype(np.float32)
    layer = lambda rotary: lambda v: fluid.layers.latent_attention(  # noqa
        v, 2, kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
        rotary=rotary)
    positions = lambda: {                                         # noqa: E731
        s["labels"]["positions"]: s["value"]
        for s in obs.REGISTRY.snapshot()["families"][
            "latent_attention_positions_traced_total"]["series"]}
    obs.REGISTRY.reset()
    got, ps = _run_layer(layer(False), x)
    assert positions() == {"none": 1.0}
    assert [p.shape for p in ps] == [(32, 48), (32, 24), (16,), (16, 64),
                                     (32, 32)]
    (op,) = [op for op in fluid.default_main_program().global_block().ops
             if op.type == "latent_attention"]
    assert op.attrs["rotary"] is False
    turned, _ = _run_layer(layer(True), x, dict(enumerate(ps)))
    (op,) = [op for op in fluid.default_main_program().global_block().ops
             if op.type == "latent_attention"]
    assert "rotary" not in op.attrs        # the parent's op, attr for attr
    assert positions() == {"rope": 1.0}
    with jax.enable_x64(False):
        plain = functools.cache(lambda mutant: np.asarray(jax.jit(  # noqa
            lambda x, ps: ref.latent_attention(
                x, ps, _toy_ref_cfg(), mutant, _dot, lambda a: a))(
            jnp.asarray(x[0]), [jnp.asarray(p) for p in ps])))
        np.testing.assert_allclose(got[0], plain(""), atol=2e-5)
        np.testing.assert_allclose(turned[0], plain("rope"), atol=2e-5)
        for mutant in ("rope", "no_kp", "sqrt128"):
            assert np.abs(plain(mutant) - got[0]).max() > 1e-3, mutant


class _Ctx:
    mesh, is_test = None, True

    def in_grad_replay(self):
        return False

    def keep_for_grad(self, *a):
        pass

    def kept_for_grad(self):
        return None

    def target_platform(self):
        return "cpu"


def _latent_jaxpr(**extra):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.llm_ops import latent_attention

    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)         # noqa: E731
    ins = {"X": sds(1, 16, 32), "WQ": sds(32, 48), "WKVA": sds(32, 24),
           "KVNorm": sds(16), "WKVB": sds(16, 64), "WO": sds(32, 32)}
    attrs = dict({"num_heads": 2, "qk_nope_dim": 16, "qk_rope_dim": 8,
                  "v_dim": 16, "theta": 50000.0, "epsilon": 1e-5}, **extra)
    with jax.enable_x64(False):
        return str(jax.make_jaxpr(lambda d: latent_attention(
            _Ctx(), {k: [v] for k, v in d.items()}, attrs)["Out"][0])(ins))


def test_latent_attention_at_its_default_traces_the_parents_jaxpr():
    """Without the attr (Moonlight's and Xing4's ops carry none) the op
    traces to what PR 57's tree traced: the hash was taken from that tree
    with this very function.  `rotary` True is the same trace; False has
    neither a cosine nor a sine."""
    default = _latent_jaxpr()
    assert hashlib.sha256(default.encode()).hexdigest()[:16] == (
        "5ed2f6fa81811530")
    assert _latent_jaxpr(rotary=True) == default
    plain = _latent_jaxpr(rotary=False)
    assert " cos " in default and " sin " in default
    assert " cos " not in plain and " sin " not in plain
    with pytest.raises(ValueError, match="YaRN"):
        _latent_jaxpr(rotary=False, yarn_factor=4.0)


def test_latent_attention_refuses_what_has_no_meaning():
    fluid.reset()
    x = fluid.layers.data("x", shape=[16, 32], dtype="float32")
    yarn = {"factor": 4.0, "original_max_position_embeddings": 64}
    with pytest.raises(ValueError, match="`yarn` scales the rotary"):
        fluid.layers.latent_attention(x, 2, 16, 16, 8, 16, yarn=yarn,
                                      rotary=False)
    with pytest.raises(ValueError, match="qk_rope_dim 0"):
        fluid.layers.latent_attention(x, 2, 16, 16, 0, 16)


# ---------------------------------------------------------------------------
# the share: 32 ranks of 8 experts


def _moe_layer(held, E=256, k=8, H=4):
    from paddle_tpu.framework.initializer import NormalInitializer

    return lambda x: fluid.layers.moe(
        fluid.layers.reshape(x, [-1, x.shape[-1]]), E, H, act="silu",
        top_k=k, gated=True, dropless=True, held=held, scoring="sigmoid",
        renormalise=True, routed_scale=2.446,
        select_bias=NormalInitializer(scale=0.05), shared_hidden=H).out


def test_the_32_ranks_shares_add_up_to_the_uncut_layer():
    """256 experts over 32 ranks of 8: every rank routes all tokens over
    all 256 (sigmoid, top-8 by score + bias, renormalised, times 2.446)
    and computes the pairs on its own eight; the partial sums, each WITHOUT
    the shared expert, plus the shared expert ONCE, are the uncut layer's
    result (`held` = all 256), which is the plain reference's expert
    block.  Summed here over four shares that cover the 256: the first and
    the last rank's own eight, and the 240 between them in two shares (a
    share is a program to compile; a wrong offset or count at either end
    or in the middle moves the sum)."""
    import jax
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", CONFIG)
    T, D, E, H = 48, 16, 256, 4
    x = _r(1, T, D, seed=1).astype(np.float32)
    full, ps = _run_layer(_moe_layer((0, E)), x)
    assert [p.shape for p in ps] == [(D, E), (E, D, H), (E, D, H), (E, H, D),
                                     (E,), (D, H), (D, H), (H, D)]
    total = 0.0
    for first, count in ((0, 8), (8, 120), (128, 120), (248, 8)):
        mine = slice(first, first + count)
        part, _ = _run_layer(
            _moe_layer((first, count)), x,
            {0: ps[0], 1: ps[1][mine], 2: ps[2][mine], 3: ps[3][mine],
             4: ps[4], 5: ps[5], 6: ps[6], 7: np.zeros_like(ps[7])})
        total = total + part
    shared_alone, _ = _run_layer(
        _moe_layer((0, 8)), x,
        {0: ps[0], 1: np.zeros_like(ps[1][:8]), 2: ps[2][:8], 3: ps[3][:8],
         4: ps[4], 5: ps[5], 6: ps[6], 7: ps[7]})
    np.testing.assert_allclose(total + shared_alone, full, atol=2e-5)
    assert np.abs(shared_alone).max() > 1e-4
    # the uncut layer is the reference's block
    with jax.enable_x64(False):
        h = jnp.asarray(x[0])
        cfg = _toy_ref_cfg()
        cfg["num_experts_per_token"] = 8
        _, w, chosen = ref.route(h, jnp.asarray(ps[0]), jnp.asarray(ps[4]),
                                 cfg)
        want = ref.held_experts(h, w, *(jnp.asarray(p) for p in ps[1:4]))
        want = want + ref.swiglu(h, *(jnp.asarray(p) for p in ps[5:8]),
                                 _dot)
        assert int(chosen.sum()) == T * 8
        np.testing.assert_allclose(full, np.asarray(want), atol=2e-5)
        # without the bias another eight are chosen
        _, w0, _ = ref.route(h, jnp.asarray(ps[0]), jnp.asarray(ps[4]), cfg,
                             "no_bias")
        assert np.abs(np.asarray(w0) - np.asarray(w)).max() > 1e-3


# ---------------------------------------------------------------------------
# the mixer as a layer, and decoder_lm's ninth kind


def test_kimi_delta_attention_layer_is_the_plain_version():
    """Fifteen parameters in the reference's order, the nine projections
    under `pdtpu.kda.project`, A_log's and dt_bias's draws, and the plain
    reference's token-by-token result over two chunks of 64; each mutant of
    the mixer is another function."""
    import jax
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", CONFIG)
    T, D = 128, 32
    x = _r(1, T, D, seed=1).astype(np.float32)
    got, ps = _run_layer(lambda v: fluid.layers.kimi_delta_attention(
        v, 2, 8, conv_kernel=4, gate_rank=8), x)
    assert [p.shape for p in ps] == [
        (D, 16), (D, 16), (D, 16), (D, 8), (8, 16), (D, 2), (D, 8), (8, 16),
        (16, 4), (16, 4), (16, 4), (2,), (16,), (8,), (16, D)]
    assert len(ps) == ref.PER_MIXER["kda"]
    block = fluid.default_main_program().global_block()
    assert [op.attrs.get("part") for op in block.ops
            if op.type == "mul"] == ["kda.project"] * 9
    a, dt = np.exp(ps[11]), np.log1p(np.exp(ps[12]))
    assert 1.0 <= a.min() and a.max() < 16.0
    assert 1e-3 <= dt.min() and dt.max() <= 0.1 + 1e-6
    assert np.abs(ps[8]).max() <= 0.5 and np.all(ps[13] == 1.0)
    with jax.enable_x64(False):   # the reference is float32, as on the chip
        mutants = ("gate_mean", "no_dt_bias", "no_a_log", "no_beta",
                   "no_l2norm", "q_unscaled", "no_state", "no_conv_silu",
                   "silu_gate", "taps_reversed")
        # the mixer and its ten mutants as ONE program (eagerly each is a
        # scan and fifty small programs to compile)
        plain = jax.jit(lambda x, ps: {mutant: ref.kda(
            x, ps, _toy_ref_cfg(), mutant, _dot)[0]
            for mutant in ("",) + mutants})(
            jnp.asarray(x[0]), [jnp.asarray(p) for p in ps])
        np.testing.assert_allclose(got[0], plain[""], atol=2e-5)
        for mutant in mutants:
            assert np.abs(np.asarray(plain[mutant]) - got[0]).max() > 1e-4, (
                mutant)
    fluid.reset()
    v = fluid.layers.data("x", shape=[T, D], dtype="float32")
    with pytest.raises(ValueError, match="0 heads"):
        fluid.layers.kimi_delta_attention(v, 0, 8)


def test_decoder_lm_names_its_ninth_mixer():
    from paddle_tpu.models import transformer

    assert transformer._MIXERS[8] == "kda"
    assert transformer._GPT2_BLOCK["kda"] is None    # serving refuses it
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
    for bad in (None, {"n_heads": 2}, {"n_heads": 2, "head_dim": 0},
                {"n_heads": 2.5, "head_dim": 8},
                {"n_heads": 2, "head_dim": 8, "conv_kernel": 0}):
        with pytest.raises(ValueError, match="a 'kda' layer needs"):
            transformer.decoder_lm(tokens, 32, 16, 1, 2, 16,
                                   positions="none", layer_types=["kda"],
                                   kda=bad)
    with pytest.raises(ValueError, match="'kda' or 'full_attention'"):
        transformer.build_kimi_linear_lm_train_program(
            16, 32, 16, ["linear_attention"], 2, 8, 8, 4, 8, 2, 8, 4, 32,
            8, 8, 2, 1, 2)
    with pytest.raises(ValueError, match="dense layers"):
        transformer.build_kimi_linear_lm_train_program(
            16, 32, 16, ["kda"], 2, 8, 8, 4, 8, 2, 8, 4, 32, 8, 8, 2, 1, 2,
            dense_layers=1)


def test_kimi_linear_program_counts_what_it_traced():
    """The builder at a toy size through `Executor`: four
    `kimi_delta_attention` ops and ONE `latent_attention` op chosen by
    layer, without a position; the loss falls; the counters say what was
    traced, once a layer; the selection biases move and take no
    gradient."""
    from paddle_tpu import observability as obs
    from paddle_tpu.models.transformer import (
        build_kimi_linear_lm_train_program)

    obs.REGISTRY.reset()
    fluid.reset()
    loss = build_kimi_linear_lm_train_program(
        seq_len=128, vocab_size=64, dim=32,
        layer_types=["kda", "kda", "kda", "full_attention", "kda"],
        n_heads=2, kv_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_dim=8,
        linear_heads=2, linear_head_dim=8, conv_kernel=4, dense_dim=48,
        num_experts=16, expert_dim=8, top_k=2, shared_experts=1,
        held_experts=4, buffer_rows=128, routed_scale=2.446, gate_rank=8,
        bias_init_scale=0.02, dtype="float32", learning_rate=1e-2,
        emb_init_scale=1.0)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 5
    kinds = [op.type for op in main.global_block().ops
             if op.type in ("kimi_delta_attention", "latent_attention")]
    assert kinds == ["kimi_delta_attention"] * 3 + [
        "latent_attention", "kimi_delta_attention"]
    assert not [op for op in main.global_block().ops if op.type == "rope"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    assert len(params) == 1 + 20 + 3 * 25 + 15 + 2
    bias = params[42]
    assert tuple(bias.shape) == (16,)
    before = np.asarray(fluid.global_scope().find(bias.name)).copy()
    tok = np.random.RandomState(0).randint(0, 64, (1, 128, 1))
    feed = {"tokens": tok.astype(np.int64),
            "targets": np.roll(tok, -1, 1).astype(np.int64)}
    losses = [float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0])
                    .reshape(())) for _ in range(4)]
    assert losses[-1] < losses[0]
    after = np.asarray(fluid.global_scope().find(bias.name))
    assert np.abs(after - before).max() == pytest.approx(4e-3, rel=1e-3)
    series = _by_labels
    assert series("kda_layers_traced_total") == {
        (("chunk", "64"), ("conv_taps", "4"), ("gate_rank", "8"),
         ("head_dim", "8"), ("heads", "2")): 4.0}
    # on the CPU the scans run the plain emission, forward op and grad op
    assert series("kda_kernels_traced_total") == {
        (("op", "fwd"), ("path", "xla")): 4.0,
        (("op", "grad"), ("path", "xla")): 4.0}
    assert series("latent_attention_positions_traced_total") == {
        (("positions", "none"),): 1.0}
    assert series("mla_layers_traced_total") == {
        (("kv_rank", "16"), ("qk_dim", "12"), ("v_dim", "8")): 1.0}
    obs.REGISTRY.reset()
