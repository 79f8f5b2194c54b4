"""The head_norm_rope kernels in interpret mode (same code path as the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import _with_vjp


# ---------------------------------------------------------------------------
# head_norm_rope (PR 38): Q or K from the projection's [B, T, H * D] to
# attention's [B, H, T, D], the per-head norm and the rotary turn inside;
# one head of 128 lanes a block, or two of 64

PREP_FORMS = {"norm_and_gain": (1e-6, True), "norm_alone": (1e-6, False),
              "turn_alone": (None, False)}


# head size -> head count: three column blocks either way, so two blocks a
# step do not divide and the kernels step by one
PREP_HEADS = {128: 3, 64: 6}


def _prep_operands(D, form, dtype=np.float32, T=256, B=2, seed=0):
    eps, gained = PREP_FORMS[form]
    rs = np.random.RandomState(seed)
    H = PREP_HEADS[D]
    x = jnp.asarray(rs.randn(B, T, H * D), dtype)
    g = jnp.asarray(1 + 0.2 * rs.randn(D), dtype) if gained else None
    dout = jnp.asarray(rs.randn(B, H, T, D), dtype)
    return x, g, dout, dict(heads=H, eps=eps, theta=1e4)


@pytest.mark.parametrize("period", [0, 128], ids=["positions", "period_128"])
@pytest.mark.parametrize("form", list(PREP_FORMS))
@pytest.mark.parametrize("D", [128, 64], ids=["heads_of_128", "pairs_of_64"])
def test_head_norm_rope_kernels_match_the_plain_emission(D, form, period):
    """Both kernels in interpret mode, float32, against the op's plain
    emission and its jax.vjp: Out, dX and dScale; and against the chain of
    `rms` and `rotate_half` the layer ran before."""
    from paddle_tpu.ops import llm_ops
    from paddle_tpu.ops.pallas_kernels import head_norm_rope as K

    x, g, dout, kw = _prep_operands(D, form)
    kw["period"] = period
    blocks = dict(interpret=True, tile=128, hb=2)
    with jax.enable_x64(False):
        args = (x,) if g is None else (x, g)
        plain = lambda x, g=None: llm_ops.head_norm_rope_plain(  # noqa: E731
            x, g, kw["heads"], kw["eps"], kw["theta"], period)
        want, grads = _with_vjp(plain, dout, *args)   # ONE program
        got = K.head_norm_rope(x, g, **kw, **blocks)
        dx, dg = K.head_norm_rope_bwd(dout, x, g, **kw, **blocks)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(dx, grads[0], rtol=1e-5, atol=1e-5)
        assert (dg is None) == (g is None)
        if g is not None:
            assert dg.shape == g.shape and dg.dtype == jnp.float32
            np.testing.assert_allclose(dg, grads[1], rtol=1e-5, atol=1e-3)
        B, T, _ = x.shape

        @jax.jit
        def chain(y, g):
            if kw["eps"] is not None:
                y = llm_ops.rms(y, kw["eps"], (3,), g)
            return llm_ops.rotate_half(y, kw["theta"], period)

        y = x.reshape(B, T, kw["heads"], D).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got, chain(y, g), rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("D", [128, 64], ids=["heads_of_128", "pairs_of_64"])
def test_head_norm_rope_kernels_round_bf16_once(D):
    """bf16 in HBM, float32 inside: the forward is the float32 result
    within one rounding to bf16, dX likewise, and the gain's gradient
    leaves in float32."""
    from paddle_tpu.ops.pallas_kernels import head_norm_rope as K

    x, g, dout, kw = _prep_operands(D, "norm_and_gain", jnp.bfloat16)
    blocks = dict(interpret=True, tile=128, hb=2)
    wide = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.enable_x64(False):
        got = K.head_norm_rope(x, g, **kw, **blocks)
        exact = K.head_norm_rope(wide(x), wide(g), **kw, **blocks)
        assert got.dtype == jnp.bfloat16
        # the nearest bf16 or, where a float32 sum's last bit fell the
        # other way, its neighbour
        err = np.abs(np.asarray(wide(got)) - np.asarray(exact))
        assert (err <= 2.0 ** -8 * np.abs(np.asarray(exact)) + 1e-30).all()
        assert (got == exact.astype(jnp.bfloat16)).mean() > 0.999
        dx, dg = K.head_norm_rope_bwd(dout, x, g, **kw, **blocks)
        dxe, dge = K.head_norm_rope_bwd(wide(dout), wide(x), wide(g), **kw,
                                        **blocks)
        assert dx.dtype == jnp.bfloat16 and dg.dtype == jnp.float32
        err = np.abs(np.asarray(wide(dx)) - np.asarray(dxe))
        assert (err <= 2.0 ** -8 * np.abs(np.asarray(dxe)) + 1e-30).all()
        np.testing.assert_allclose(dg, dge, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("T,D,heads,dtype,want", [
    (256, 128, 5, "bfloat16", 1), (256, 64, 6, "float32", 2),
    (256, 64, 5, "bfloat16", 0),    # an odd head has no partner in a block
    (192, 128, 4, "bfloat16", 0),   # T off the 128-row grid
    (256, 32, 8, "bfloat16", 0), (256, 256, 2, "bfloat16", 0),
    (256, 128, 4, "float64", 0)])
def test_head_norm_rope_kernels_take_lane_wide_heads(T, D, heads, dtype,
                                                     want):
    from paddle_tpu.ops.pallas_kernels import head_norm_rope as K

    assert K.pack_of(T, D, heads, jnp.dtype(dtype)) == want


def _prep_step(x, g, attrs, w):
    """A program of the one op under mean(Out * w), X and Scale
    parameters; -> (Out, X@GRAD, Scale@GRAD or None) of one run."""
    import paddle_tpu as fluid

    fluid.reset()
    block = fluid.default_main_program().global_block()
    ins = {"X": ["x"]}
    block.create_parameter(name="x", shape=x.shape, dtype="float32")
    if g is not None:
        block.create_parameter(name="g", shape=g.shape, dtype="float32")
        ins["Scale"] = ["g"]
    wv = block.create_var(name="w", shape=w.shape, dtype="float32",
                          stop_gradient=True)
    out = block.create_var(name="out", dtype="float32", shape=w.shape)
    block.append_op("head_norm_rope", inputs=ins, outputs={"Out": ["out"]},
                    attrs=dict(attrs))
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, wv))
    grads = dict((p.name, gv.name) for p, gv in fluid.append_backward(loss))
    scope = fluid.global_scope()
    scope.set("x", x)
    scope.set("w", w)
    if g is not None:
        scope.set("g", g)
    exe = fluid.Executor(fluid.CPUPlace())
    fetch = ["out", grads["x"]] + ([grads["g"]] if g is not None else [])
    got = exe.run(feed={}, fetch_list=fetch)
    return [np.asarray(a) for a in got] + [None] * (g is None)


@pytest.mark.parametrize("D,path", [(128, "pallas"), (64, "pallas_packed")])
def test_head_norm_rope_op_takes_the_kernels_on_a_tpu(D, path, monkeypatch):
    """Where the trace targets one TPU the op's emitter launches the
    forward kernel ONCE and its grad op's the backward kernel once and no
    forward; the numbers are the plain emission's; the counter names the
    path and `executor_grad_kernel_forward_total` gets no series."""
    import functools

    from paddle_tpu import observability as obs
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import head_norm_rope as K

    x, g, w, kw = _prep_operands(D, "norm_and_gain")
    attrs = {"num_heads": kw["heads"], "epsilon": kw["eps"],
             "theta": kw["theta"], "part": "attn.qk_prep"}
    want = _prep_step(x, g, attrs, w)

    def series(family):
        fam = obs.REGISTRY.snapshot()["families"].get(family)
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in (fam["series"] if fam else [])}

    labels = dict(head_dim=str(D), heads=str(kw["heads"]), norm="head")
    assert series("qk_prep_layers_traced_total") == {
        tuple(sorted({**labels, "path": "xla"}.items())): 1.0}

    launched = []

    def spy(name, real):
        def call(*a, **k):
            launched.append(name)
            return real(*a, **k, interpret=True, tile=128)
        return call

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(K, "head_norm_rope", spy("fwd", K.head_norm_rope))
    monkeypatch.setattr(K, "head_norm_rope_bwd",
                        spy("bwd", K.head_norm_rope_bwd))
    got = _prep_step(x, g, attrs, w)
    assert launched == ["fwd", "bwd"]
    assert series("qk_prep_layers_traced_total") == {
        tuple(sorted({**labels, "path": path}.items())): 1.0}
    assert series("executor_grad_kernel_forward_total") == {}
    for a, b, tol in zip(got, want, (2e-6, 1e-8, 1e-6)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=tol)
    # a mesh, or the switch, sends both emitters the plain way
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    again = _prep_step(x, g, attrs, w)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()
