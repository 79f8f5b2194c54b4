"""Granite 4.0 H's mechanisms (PR 67), on the CPU at toy widths: the Mamba-2
scan (`ssd_scan`, `ssd_chunked`) against the token-by-token recurrence
written out here, values and every gradient, at chunks that do and do not
divide the sequence, one and two groups; the gated RMSNorm; the
convolution's `offset`; the softmax scale of `scaled_dot_product_attention`
/ `multi_head_attention(scale=)`; what `decoder_lm` refuses and what a
segment keeps of a 'mamba2' layer.  tests/test_granite_model.py holds the
whole tower to the reference file.
"""

from __future__ import annotations

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer

from _kernel_refs import (_conv_interpreted, _dense_scaled, _r, _series,
                          _silu, _spy_on_calls)
from op_test import OpTestHarness


# ---------------------------------------------------------------------------
# the scan


def _scan_case(T, H=2, P=3, N=2, G=1, B=1, chunk=4, seed=0):
    ins = {"X": _r(B, T, H * P, seed=seed), "B": _r(B, T, G * N, seed=seed + 1),
           "C": _r(B, T, G * N, seed=seed + 2),
           "Dt": _r(B, T, H, seed=seed + 3),
           "ALog": _r(H, lo=0.0, hi=1.5, seed=seed + 4),
           "D": _r(H, lo=0.5, hi=1.5, seed=seed + 5),
           "DtBias": _r(H, lo=-2.0, hi=0.0, seed=seed + 6)}
    return ins, {"heads": H, "groups": G, "chunk": chunk}


def _scan_numpy(ins, attrs):
    """The op from its docstring, token by token: ONE scalar decay a head
    and token, head h on the B and C of group h // (H / G)."""
    x, b, c, dt = ins["X"], ins["B"], ins["C"], ins["Dt"]
    H, G = attrs["heads"], attrs["groups"]
    Bt, T, width = x.shape
    P, N = width // H, b.shape[2] // G
    delta = np.log1p(np.exp(dt + ins["DtBias"]))
    a = -np.exp(ins["ALog"])
    out = np.zeros_like(x)
    for i in range(Bt):
        S = np.zeros((H, P, N))
        for t in range(T):
            xt = x[i, t].reshape(H, P)
            for h in range(H):
                g = h // (H // G)
                bt, ct = (m[i, t, g * N:(g + 1) * N] for m in (b, c))
                S[h] = (np.exp(delta[i, t, h] * a[h]) * S[h]
                        + delta[i, t, h] * np.outer(xt[h], bt))
                out[i, t, h * P:(h + 1) * P] = (S[h] @ ct
                                                + ins["D"][h] * xt[h])
    return out


@pytest.mark.parametrize("T,G,H", [(12, 1, 2), (10, 2, 4)],
                         ids=["three_chunks", "padded_two_groups"])
def test_ssd_scan_output_and_grad(T, G, H):
    """Chunks of four tokens over 12 (three whole chunks) and over 10 (the
    third chunk padded), one group and two groups of two heads: the op
    against the per-token loop (the state carried chunk to chunk, Delta's
    softplus with its bias, a scalar decay a head, B and C a group's, the D
    term), and every input's gradient against central differences."""
    ins, attrs = _scan_case(T, H=H, G=G)
    h = OpTestHarness("ssd_scan", ins, attrs)
    h.check_output({"Out": _scan_numpy(ins, attrs)}, atol=1e-9)
    h.check_grad(sorted(ins), max_relative_error=1e-5)


def test_ssd_chunked_gives_the_same_at_three_chunk_sizes():
    """Values and all five gradients at chunks of 4, 8 and 24 of 24 tokens
    (six, three, one chunk) and at 10 (the last chunk padded), against the
    recurrence as a `lax.scan` over the tokens: the result does not depend
    on the chunk.  In float64 to 1e-11; float32 (the wide type of a bf16
    program) at chunks of 8 to 2e-5."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.ssm_ops import ssd_chunked

    Bt, T, H, P, N, G = 2, 24, 4, 8, 6, 2
    r = np.random.RandomState(1)
    ops = [jnp.asarray(a) for a in (
        r.randn(Bt, T, H, P), np.exp(r.randn(Bt, T, H) - 1),
        -np.exp(r.randn(H)), r.randn(Bt, T, G, N), r.randn(Bt, T, G, N))]

    def recurrence(x, delta, a, b, c):
        def token(S, at):
            xt, dt, bt, ct = at
            bt, ct = (jnp.repeat(m, H // G, axis=1) for m in (bt, ct))
            S = (jnp.exp(dt * a)[..., None, None] * S
                 + (dt[..., None] * xt)[..., None] * bt[:, :, None, :])
            return S, jnp.einsum("bhpn,bhn->bhp", S, ct)
        _, y = jax.lax.scan(token, jnp.zeros((Bt, H, P, N), x.dtype), tuple(
            m.swapaxes(0, 1) for m in (x, delta, b, c)))
        return y.swapaxes(0, 1)

    def both(f, dtype="float64"):
        weigh = jnp.cos(jnp.arange(P, dtype=dtype))
        return jax.jit(jax.value_and_grad(
            lambda *o: (f(*o) * weigh).sum(), argnums=(0, 1, 2, 3, 4)))(
                *(o.astype(dtype) for o in ops))

    want, want_grads = both(recurrence)
    for chunk, dtype, tol in ((4, "float64", 1e-11), (8, "float64", 1e-11),
                              (24, "float64", 1e-11), (10, "float64", 1e-11),
                              (8, "float32", 2e-5)):
        got, grads = both(lambda *o: ssd_chunked(*o, chunk), dtype)
        assert got.dtype == dtype
        assert abs(got - want) <= tol * abs(want), chunk
        for g, w in zip(grads, want_grads):
            assert jnp.abs(g - w).max() <= tol * jnp.abs(w).max(), chunk


def test_ssd_scan_counts_what_ran_and_refuses_what_does_not_add_up():
    obs.REGISTRY.reset()
    OpTestHarness("ssd_scan", *_scan_case(8)).fetch()
    ((labels, count),) = _series("ssd_scan_total")
    assert count >= 1.0 and labels == {
        "impl": "xla_chunked", "heads": "2", "head_dim": "3", "d_state": "2",
        "groups": "1", "chunk": "4"}
    obs.REGISTRY.reset()
    for slot, cut in (("Dt", 1), ("C", 1), ("ALog", 1)):
        ins, attrs = _scan_case(8)
        ins[slot] = ins[slot][..., :-cut]
        with pytest.raises(Exception, match="ssd_scan: X"):
            OpTestHarness("ssd_scan", ins, attrs).fetch()
    ins, attrs = _scan_case(8, H=3)
    with pytest.raises(Exception, match="ssd_scan: X"):   # 3 heads, 2 groups
        OpTestHarness("ssd_scan", ins, dict(attrs, groups=2)).fetch()


# the kernels' path (ops/pallas_kernels/ssd_scan.py, PR 70;
# tests/test_ssd_scan_kernel.py holds the kernels themselves)

KERNELS = "ssd_scan_kernels_traced_total"
CONV_KERNELS = "causal_conv_silu_kernels_traced_total"


def _kernel_case(T, H=2, P=64, N=128, G=1, seed=0):
    """`_scan_case` at shapes the kernels take, float32."""
    ins, attrs = _scan_case(T, H=H, P=P, N=N, G=G, seed=seed)
    return {k: v.astype("float32") for k, v in ins.items()}, attrs


def _scan_step(ins, attrs):
    """A program of the one op under mean(Out * weight), every input a
    parameter -> Out and every input's gradient of one run."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    for name, value in ins.items():
        block.create_parameter(name=name, shape=value.shape, dtype="float32")
    weight = _r(*ins["X"].shape, seed=11).astype("float32")
    block.create_var(name="weight", shape=weight.shape, dtype="float32",
                     stop_gradient=True)
    out = block.create_var(name="out", dtype="float32", shape=weight.shape)
    block.append_op("ssd_scan", inputs={slot: [slot] for slot in ins},
                    outputs={"Out": ["out"]}, attrs=dict(attrs))
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(
        out, block.var("weight")))
    grads = dict((p.name, g.name) for p, g in fluid.append_backward(loss))
    scope = fluid.global_scope()
    for name, value in dict(ins, weight=weight).items():
        scope.set(name, value)
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={}, fetch_list=["out"] + [grads[name] for name in ins])
    return [np.asarray(a) for a in got]


@pytest.fixture
def kernels_in_interpret_mode(monkeypatch):
    """The trace targets one TPU and the kernels run in interpret mode in
    chunks of 16 tokens -> the list every launch appends its name to."""
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import ssd_scan as K

    real_make = K.make_ssd_scan
    launched = _spy_on_calls(monkeypatch, K, ("fwd", "bwd"))
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(K, "CHUNK", 16)
    monkeypatch.setattr(K, "make_ssd_scan",
                        lambda H, G: real_make(H, G, 16, True))
    real_make.cache_clear()
    yield launched
    real_make.cache_clear()


def test_ssd_scan_takes_the_kernels_on_a_tpu(kernels_in_interpret_mode,
                                             monkeypatch):
    """Where the trace targets one TPU, at whole tiles, the op's emitter
    launches the forward kernel ONCE, keeping the chunks' states, and its
    grad op's re-emission launches the reverse pass alone
    (`executor_grad_kernel_forward_total` reused=1); the numbers are the
    plain emission's, which the switch sends both emissions back to."""
    launched = kernels_in_interpret_mode
    ins, attrs = _kernel_case(32)
    labels = {"heads": "2", "head_dim": "64", "d_state": "128",
              "groups": "1"}
    obs.REGISTRY.reset()
    got = _scan_step(ins, attrs)
    assert launched == ["fwd", "bwd"]
    assert _series(KERNELS) == [({"op": "fwd", "path": "pallas"}, 1.0),
                                ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("ssd_scan_total") == [
        (dict(labels, impl="pallas", chunk="16"), 1.0)]
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "ssd_scan", "reused": "1"}, 1.0)]
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    obs.REGISTRY.reset()
    want = _scan_step(ins, attrs)
    assert launched == []
    assert _series(KERNELS) == [({"op": "fwd", "path": "xla"}, 1.0),
                                ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series("ssd_scan_total") == [
        (dict(labels, impl="xla_chunked", chunk="4"), 1.0)]
    assert _series("executor_grad_kernel_forward_total") == []
    for a, b in zip(got, want):
        assert np.abs(b).max() > 0
        assert np.abs(a - b).max() <= 2e-5 * np.abs(b).max()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,path", [
    ("one_tpu", "tpu", None, (512, 4, 64, 128, 1), "bfloat16", "pallas"),
    ("heads_of_128", "tpu", None, (512, 2, 128, 128, 2), "float32",
     "pallas"),
    ("the_cpu", "cpu", None, (512, 4, 64, 128, 1), "bfloat16", "xla"),
    ("a_mesh", "tpu", object(), (512, 4, 64, 128, 1), "bfloat16", "xla"),
    ("a_narrow_state", "tpu", None, (512, 4, 64, 64, 1), "bfloat16", "xla"),
    ("a_pair_on_two_groups", "tpu", None, (512, 4, 64, 128, 4), "bfloat16",
     "xla"),
    ("heads_of_32", "tpu", None, (512, 4, 32, 128, 1), "bfloat16", "xla"),
    ("off_the_chunks", "tpu", None, (520, 4, 64, 128, 1), "bfloat16", "xla"),
    ("under_a_chunk", "tpu", None, (12, 4, 64, 128, 1), "float32", "xla"),
    ("doubles", "tpu", None, (512, 4, 64, 128, 1), "float64", "xla")])
def test_ssd_scan_dispatch_counts_the_path(case, platform, mesh, shape,
                                           dtype, path, monkeypatch):
    """One gate: one TPU, no mesh and a shape the kernels take; the
    counters read the path of the forward emission (abstractly traced: no
    kernel runs) and the chunk of the emission taken."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import ssd_scan as K

    T, H, P, N, G = shape
    values, attrs = _scan_case(T, H=H, P=P, N=N, G=G, chunk=128)
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    ctx = reg.EmitContext(None, is_test=True)
    ctx.mesh = mesh
    obs.REGISTRY.reset()
    with jax.enable_x64(dtype == "float64"):
        ins = {slot: [jax.ShapeDtypeStruct(v.shape, jnp.dtype(dtype))]
               for slot, v in values.items()}
        out = jax.eval_shape(
            lambda ins: reg.get_op_info("ssd_scan").emit(
                ctx, ins, attrs)["Out"][0], ins)
    assert out.shape == (1, T, H * P) and out.dtype == jnp.dtype(dtype)
    assert _series(KERNELS) == [({"op": "fwd", "path": path}, 1.0)]
    (labels, _), = _series("ssd_scan_total")
    assert (labels["impl"], labels["chunk"]) == (
        ("pallas", str(K.CHUNK)) if path == "pallas"
        else ("xla_chunked", str(min(128, T))))


# ---------------------------------------------------------------------------
# the two passes beside it


@pytest.mark.parametrize("groups", [1, 2])
def test_gated_rms_norm_output_and_grad(groups):
    """The gate FIRST, then the norm over each group's columns; z is the
    FIRST columns of Gate, whose further columns are not read."""
    x, gate, gain = _r(2, 5, 8, seed=1), _r(2, 5, 13, seed=2), _r(
        8, lo=0.5, hi=1.5, seed=3)
    g = (x * _silu(gate[..., :8])).reshape(2, 5, groups, 8 // groups)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-3)).reshape(
        2, 5, 8) * gain
    h = OpTestHarness("gated_rms_norm",
                      {"X": x, "Gate": gate, "Scale": gain},
                      {"epsilon": 1e-3, "groups": groups}, out_slots=["Y"])
    h.check_output({"Y": want}, atol=1e-9)
    h.check_grad(["X", "Gate", "Scale"], output_slot="Y",
                 max_relative_error=1e-5)
    # not the norm before the gate
    y = x.reshape(2, 5, groups, -1)
    other = (y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-3)).reshape(
        2, 5, 8) * _silu(gate[..., :8]) * gain
    assert np.abs(other - want).max() > 0.05
    with pytest.raises(Exception, match="gated_rms_norm: X"):
        OpTestHarness("gated_rms_norm",
                      {"X": x, "Gate": gate[..., :7], "Scale": gain},
                      out_slots=["Y"]).fetch()
    with pytest.raises(Exception, match="gated_rms_norm: X"):
        OpTestHarness("gated_rms_norm",
                      {"X": x, "Gate": gate, "Scale": gain},
                      {"groups": 3}, out_slots=["Y"]).fetch()


def test_causal_conv_silu_reads_from_an_offset_and_traces_as_before_at_0():
    """With `offset` the taps cover the columns [offset, offset + C) of X (a
    Mamba-2 projection's xBC behind z); without it the op's jaxpr is the
    parent's expression."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import llm_ops, ssm_ops
    from paddle_tpu.ops import registry as reg

    x, w, b = _r(2, 7, 12, seed=1), _r(6, 4, seed=2), _r(6, seed=3)
    T, L = 7, 4
    padded = np.concatenate([np.zeros((2, L - 1, 6)), x[..., 3:9]], axis=1)
    want = _silu(b + sum(w[:, j] * padded[:, j:j + T] for j in range(L)))
    h = OpTestHarness("causal_conv_silu", {"X": x, "Filter": w, "Bias": b},
                      {"offset": 3})
    h.check_output({"Out": want}, atol=1e-9)
    h.check_grad(["X", "Filter", "Bias"], max_relative_error=1e-5)
    with pytest.raises(Exception, match="at offset 7"):
        OpTestHarness("causal_conv_silu", {"X": x, "Filter": w, "Bias": b},
                      {"offset": 7}).fetch()
    ctx = reg.EmitContext(None, is_test=False)
    xs, ws, bs = (jnp.asarray(a, jnp.float32) for a in (x, w, b))
    op = str(jax.make_jaxpr(lambda x, w, b: ssm_ops.causal_conv_silu(
        ctx, {"X": [x], "Filter": [w], "Bias": [b]}, {})["Out"][0])(
            xs, ws, bs))
    parent = str(jax.make_jaxpr(lambda x, w, b: jax.nn.silu(
        llm_ops.causal_taps(x[..., :6].astype(jnp.float32),
                            w.astype(jnp.float32))
        + b.astype(jnp.float32)).astype(x.dtype))(xs, ws, bs))
    assert op == parent


# ---------------------------------------------------------------------------
# the softmax scale


def _sdpa_case(seed=0):
    q, k, v = (_r(1, 4, 6, 8, seed=seed), _r(1, 2, 6, 8, seed=seed + 1),
               _r(1, 2, 6, 8, seed=seed + 2))
    return {"Q": q, "K": k, "V": v}, {"causal": True}


def test_attention_takes_a_softmax_scale_and_counts_it():
    """Four query heads on two key/value heads of 8 at scale 1/8 (not
    8^-1/2): values against dense softmax at that scale, every gradient
    against central differences; the counter names the scale; a scale that
    is not positive is refused."""
    import jax.numpy as jnp

    ins, attrs = _sdpa_case()
    want, _ = _dense_scaled(*(jnp.asarray(ins[s]) for s in "QKV"), True,
                            0.125)
    obs.REGISTRY.reset()
    h = OpTestHarness("scaled_dot_product_attention", ins,
                      dict(attrs, scale=0.125))
    h.check_output({"Out": np.asarray(want)}, atol=1e-9)
    ((labels, count),) = _series("attention_softmax_scale_traced_total")
    assert count >= 1.0 and labels == {"scale": "0.125"}
    ((labels, _),) = _series("gqa_attention_layers_traced_total")
    assert labels == {"q_heads": "4", "kv_heads": "2", "head_dim": "8"}
    obs.REGISTRY.reset()
    h.check_grad(["Q", "K", "V"], max_relative_error=1e-5)
    plain, _ = _dense_scaled(*(jnp.asarray(ins[s]) for s in "QKV"), True,
                             8 ** -0.5)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 0.01
    with pytest.raises(Exception, match="a positive number"):
        OpTestHarness("scaled_dot_product_attention", ins,
                      dict(attrs, scale=0.0)).fetch()


def test_attention_without_the_attr_is_the_parents_jaxpr_and_desc():
    """Absent, the op traces to what the parent traced (the dense path
    called without a scale) and counts no scale; the layer writes the attr
    only where `scale` is given, in both layouts."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops import ring_attention as ra

    ins, attrs = _sdpa_case()
    q, k, v = (jnp.asarray(ins[s], jnp.float32) for s in "QKV")
    ctx = reg.EmitContext(None, is_test=True)
    obs.REGISTRY.reset()
    op = str(jax.make_jaxpr(
        lambda q, k, v: attention_ops.scaled_dot_product_attention(
            ctx, {"Q": [q], "K": [k], "V": [v]}, dict(attrs))["Out"][0])(
                q, k, v))
    parent = str(jax.make_jaxpr(lambda q, k, v: ra.attention(
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), causal=True,
        allowed=None))(q, k, v))
    assert op == parent
    assert _series("attention_softmax_scale_traced_total") == []
    obs.REGISTRY.reset()

    def descs(**kw):
        fluid.reset()
        x = fluid.layers.data("x", shape=[6, 32], dtype="float32")
        fluid.layers.multi_head_attention(x, x, x, 4, causal=True,
                                          num_kv_heads=2, **kw)
        return [op.attrs for op in fluid.default_main_program(
            ).global_block().ops if op.type == "scaled_dot_product_attention"]

    (bare,), (scaled,), (turned,) = descs(), descs(scale=0.125), descs(
        scale=0.125, rope_theta=100.0)
    assert "scale" not in bare and scaled["scale"] == turned["scale"] == 0.125
    assert {k: v for k, v in scaled.items() if k != "scale"} == bare
    assert scaled["layout"] == "bthd" and "layout" not in turned


# ---------------------------------------------------------------------------
# the layer and the tower


def _mixer_grads(segment: bool, T=10, sizes=None):
    """The loss and every parameter's gradient of one `layers.mamba2` (two
    groups, a chunk that does not divide T; or `sizes`) inside or outside a
    `layers.recompute` segment, on the same seeded weights."""
    import contextlib

    fluid.reset()
    x = fluid.layers.data("x", shape=[T, 16], dtype="float32")
    sizes = sizes or dict(n_heads=4, head_dim=8, d_state=4, n_groups=2)
    with (fluid.layers.recompute() if segment else contextlib.nullcontext()):
        y = fluid.layers.mamba2(x, chunk=4, **sizes)
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(y, y))
    grads = fluid.append_backward(loss)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    out = exe.run(feed={"x": _r(2, T, 16, seed=5).astype("float32")},
                  fetch_list=[loss] + [g for _, g in grads])
    return [p.shape for p, _ in grads], [np.asarray(o) for o in out]


def test_mamba2_layer_inside_a_recompute_segment_gives_the_same_gradients():
    """The op stands inside a segment as it is (no grad op of its own: the
    segment's replay re-emits it under the generic vjp): the loss and all
    eight gradients equal the unsegmented program's, and the parameters are
    the eight the docstring lists, in its order."""
    shapes, plain = _mixer_grads(False)
    assert sorted(shapes) == sorted([
        (16, 32 + 48 + 4), (48, 4), (48,), (4,), (4,), (4,), (32,),
        (32, 16)])
    _, inside = _mixer_grads(True)
    assert all(np.abs(g).max() > 0 for g in plain[1:])
    for a, b in zip(plain, inside):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-9)


def test_mamba2_layer_in_a_segment_on_the_kernels_path(
        kernels_in_interpret_mode, monkeypatch):
    """On the kernels' path (interpret mode, two chunks of 16 tokens, a pair
    of heads of 64) the layer outside a segment launches the scan's forward
    once and the reverse pass over what it kept, and the convolution's
    forward once (three sections: x, B and C) and its backward, which the
    grad op handed the forward's outputs launches alone; inside a segment
    the forward emission, the replay's forward (handed nothing: the plain
    pair) and the reverse pass, of both.  The same loss and gradients either
    way, bit for bit but W_in's, and the plain emission's to float32's
    rounding."""
    launched = kernels_in_interpret_mode
    conv = _conv_interpreted(monkeypatch)
    sizes = dict(n_heads=2, head_dim=64, d_state=128, n_groups=1)
    obs.REGISTRY.reset()
    _, outside = _mixer_grads(False, T=32, sizes=sizes)
    assert launched == conv == ["fwd", "bwd"]
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "causal_conv_silu", "reused": "1"}, 1.0),
        ({"op": "ssd_scan", "reused": "1"}, 1.0)]
    del launched[:], conv[:]
    obs.REGISTRY.reset()
    _, inside = _mixer_grads(True, T=32, sizes=sizes)
    # (traces, not launches: differentiating the replay's plain pair traces
    # its primal beside its rule, and the compiled step drops the one
    # nothing reads)
    for calls in (launched, conv):
        assert calls.count("bwd") == 1 and calls.count("fwd") >= 2
    assert _series(KERNELS) == _series(CONV_KERNELS) == [
        ({"op": "fwd", "path": "pallas"}, 1.0),
        ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "recompute", "reused": "0"}, 1.0)]
    # W_in's gradient is a product whose cotangent operand, the sum of the
    # convolution's, the gate's and dt's column ranges, XLA's CPU backend
    # fuses another way in a segment: the same numbers to the last bits
    for a, b in zip(outside, inside):
        if a.shape == (16, 128 + 384 + 2):
            assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max()
        else:
            np.testing.assert_array_equal(a, b)
    del launched[:], conv[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    obs.REGISTRY.reset()
    _, plain = _mixer_grads(True, T=32, sizes=sizes)
    assert launched == conv == []
    assert _series(CONV_KERNELS) == [({"op": "fwd", "path": "xla"}, 1.0),
                                     ({"op": "grad", "path": "xla"}, 1.0)]
    assert all(np.abs(g).max() > 0 for g in plain[1:])
    for a, b in zip(inside, plain):
        assert np.abs(a - b).max() <= 5e-5 * np.abs(b).max()


def test_decoder_lm_knows_the_kind_and_refuses_what_is_missing():
    """'mamba2' reads its sizes from `ssm`; a missing size, heads that are
    no multiple of the groups and an unknown `remat_keep` are plain
    ValueErrors; serving's wiring refuses the softmax scale and the kind;
    a segment holds the layer's input projection and the MLP's two."""
    assert "mamba2" in transformer._MIXERS
    assert transformer._GPT2_BLOCK["attention_scale"] is None
    assert transformer._GPT2_BLOCK["ssm"] is None

    def tower(ssm, **kw):
        fluid.reset()
        tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
        return transformer.decoder_lm(
            tokens, 16, 8, 2, 2, 8, positions="none", norm="rms_norm",
            layer_types=["mamba2", "attention"], ssm=ssm, ffn="gated_mlp",
            dense_dim=12, attention_scale=0.25, **kw)

    sizes = {"n_heads": 4, "head_dim": 4, "d_state": 3}
    for bad in ({k: v for k, v in sizes.items() if k != "d_state"},
                dict(sizes, n_heads=None), None):
        with pytest.raises(ValueError, match="a 'mamba2' layer needs"):
            tower(bad)
    for bad in (dict(sizes, n_groups=3), dict(sizes, d_state=0)):
        with pytest.raises(ValueError, match="a multiple of the groups"):
            tower(bad)
    with pytest.raises(ValueError, match="remat_keep"):
        tower(dict(sizes), remat=True, remat_keep=("ssd.scan",))
    tower(dict(sizes, n_groups=2, chunk=4), remat=True,
          remat_keep=("mlp.up", "ssm.in_proj"))
    main = fluid.default_main_program()
    widths = [[main.blocks[op.attrs["sub_block"]].var(n).shape[-1]
               for n in op.attrs.get("keep_names", [])]
              for op in main.global_block().ops if op.type == "recompute"]
    assert widths == [[2 * 16 + 2 * 2 * 3 + 4, 12, 12], [12, 12]]
    (attn,) = [op for b in main.blocks for op in b.ops
               if op.type == "scaled_dot_product_attention"]
    assert attn.attrs["scale"] == 0.25
    (scan,) = [op for b in main.blocks for op in b.ops
               if op.type == "ssd_scan"]
    assert (scan.attrs["heads"], scan.attrs["groups"],
            scan.attrs["chunk"]) == (4, 2, 4)
