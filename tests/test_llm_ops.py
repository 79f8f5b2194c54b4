"""rms_norm, rope, head_norm_rope, QK-norm and the dropless form of the `moe`
op, each against plain jax.numpy: outputs directly, gradients through
`generic_grad` or the op's own grad op (the numeric sweep of
tests/op_test.py, and a dense evaluation under jax.grad).
ops/llm_ops.py, ops/moe_ops.py, layers/nn.py."""

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _r, _with_vjp
from op_test import OpTestHarness


# ---------------------------------------------------------------------------
# rms_norm, rope


def test_rms_norm_output_and_grad():
    x, g = _r(3, 5, 8), _r(8, lo=0.5, hi=1.5, seed=1)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    h = OpTestHarness("rms_norm", {"X": x, "Scale": g},
                      {"epsilon": 1e-5, "begin_norm_axis": 2}, ["Y"])
    h.check_output({"Y": want}, atol=1e-5)
    h.check_grad(["X", "Scale"], output_slot="Y", max_relative_error=1e-2)


def test_rms_norm_is_float32_inside_bf16():
    """A bf16 input is normalised in float32 and rounded once."""
    import jax.numpy as jnp

    x = _r(4, 64).astype(np.float32)
    xb = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    want = xb / np.sqrt((xb * xb).mean(-1, keepdims=True) + 1e-5)
    fluid.reset()
    from paddle_tpu.ops.registry import EmitContext, get_op_info

    out = get_op_info("rms_norm").emit(
        EmitContext(None, is_test=True),
        {"X": [jnp.asarray(x, jnp.bfloat16)],
         "Scale": [jnp.ones((64,), jnp.bfloat16)]},
        {"epsilon": 1e-5, "begin_norm_axis": 1})["Y"][0]
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=2 ** -8, atol=1e-6)


def _rope_numpy(x, theta):
    """transformers' apply_rotary_pos_emb, written out."""
    T, D = x.shape[-2:]
    inv = 1.0 / theta ** (np.arange(0, D, 2) / D)
    ang = np.arange(T)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1)
    rot = np.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * np.cos(ang) + rot * np.sin(ang)


def test_rope_output_and_grad():
    x = _r(2, 3, 6, 8)
    h = OpTestHarness("rope", {"X": x}, {"theta": 10000.0})
    h.check_output({"Out": _rope_numpy(x, 10000.0)}, atol=1e-5)
    h.check_grad(["X"], max_relative_error=1e-2)


def test_rope_is_relative():
    """Scores of rotated q and k depend on the distance alone: shifting
    both positions by one leaves q_t . k_s unchanged."""
    x = _r(1, 1, 1, 16, seed=3)
    q = np.repeat(x, 6, axis=2)          # the same vector at 6 positions
    rot = _rope_numpy(q, 100.0)[0, 0]
    scores = rot @ rot.T
    np.testing.assert_allclose(np.diag(scores, 1), scores[0, 1], rtol=1e-6)
    np.testing.assert_allclose(np.diag(scores, 2), scores[0, 2], rtol=1e-6)


def test_rope_refuses_an_odd_head_size():
    with pytest.raises(Exception, match="even"):
        OpTestHarness("rope", {"X": _r(1, 1, 4, 7)}).fetch()


def _attention_reference(x, params, n_heads, eps, theta):
    import jax
    import jax.numpy as jnp

    wq, wk, wv, gq, gk, wo = params
    B, T, D = x.shape

    def rms(a, g):
        return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + eps) * g

    def rope(a):                          # [B, H, T, dh]
        dh = a.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, dh, 2) / dh)
        ang = jnp.arange(T)[:, None] * inv[None, :]
        ang = jnp.concatenate([ang, ang], -1)
        rot = jnp.concatenate([-a[..., dh // 2:], a[..., :dh // 2]], -1)
        return a * jnp.cos(ang) + rot * jnp.sin(ang)

    def heads(a):
        return a.reshape(B, T, n_heads, D // n_heads).transpose(0, 2, 1, 3)

    q, k, v = rms(x @ wq, gq), rms(x @ wk, gk), x @ wv
    q, k, v = rope(heads(q)), rope(heads(k)), heads(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (D // n_heads) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return a.transpose(0, 2, 1, 3).reshape(B, T, D) @ wo


def test_qk_norm_and_rope_inside_multi_head_attention():
    """layers.multi_head_attention with QK-norm and RoPE against jax.numpy,
    the output and every parameter's gradient by generic_grad."""
    import jax
    import jax.numpy as jnp

    B, T, D, H = 2, 8, 16, 4
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, D], dtype="float32")
    out = fluid.layers.multi_head_attention(
        x, x, x, num_heads=H, causal=True, qk_norm_epsilon=1e-5,
        rope_theta=10000.0)
    loss = fluid.layers.mean(fluid.layers.square(out))
    pg = fluid.append_backward(loss)
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    assert [tuple(p.shape) for p in params] == [
        (D, D), (D, D), (D, D), (D,), (D,), (D, D)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.RandomState(5)
    for p in params[3:5]:                 # gains away from one
        scope.set(p.name, jnp.asarray(rng.uniform(0.5, 1.5, p.shape)))
    xv = rng.normal(size=(B, T, D)).astype(np.float32)
    grads = {p.name: g.name for p, g in pg}
    got = exe.run(feed={"x": xv},
                  fetch_list=[out] + [grads[p.name] for p in params])

    vals = [jnp.asarray(np.asarray(scope.find(p.name), np.float64))
            for p in params]
    ref = lambda ps: _attention_reference(jnp.asarray(xv, jnp.float64), ps,
                                          H, 1e-5, 10000.0)
    np.testing.assert_allclose(got[0], jax.jit(ref)(vals), atol=2e-5)
    want = jax.jit(jax.grad(lambda ps: jnp.mean(jnp.square(ref(ps)))))(vals)
    for g, w, p in zip(got[1:], want, params):
        np.testing.assert_allclose(g, w, atol=2e-6, err_msg=p.name)


# ---------------------------------------------------------------------------
# head_norm_rope: Q or K from the projection's layout to attention's, the
# per-head norm and the turn inside (PR 38)


def _chain_numpy(x, gain, heads, eps, theta, period=0):
    """What the layer emitted before the op: split the heads, `rms_norm`
    over a head's columns, `rope`.  float64."""
    B, T, W = x.shape
    y = x.astype(np.float64).reshape(B, T, heads, W // heads)
    y = y.transpose(0, 2, 1, 3)
    if eps is not None:
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + eps)
    if gain is not None:
        y = y * gain
    if period:
        return np.concatenate(
            [_rope_numpy(y[:, :, i:i + period], theta)
             for i in range(0, T, period)], axis=2)
    return _rope_numpy(y, theta)


PREP_CASES = {
    # heads, head size, epsilon, a gain, period
    "norm_and_gain": (3, 8, 1e-5, True, 0),
    "norm_without_gain": (2, 8, 1e-5, False, 0),
    "turn_alone": (4, 4, None, False, 0),
    "norm_gain_period": (2, 8, 1e-6, True, 3),
    "one_head": (1, 16, 1e-5, True, 0),
}


def _prep_case(case, T=6):
    heads, d, eps, gained, period = PREP_CASES[case]
    ins = {"X": _r(2, T, heads * d, seed=len(case))}
    if gained:
        ins["Scale"] = _r(d, lo=0.5, hi=1.5, seed=1)
    attrs = {"num_heads": heads, "theta": 100.0}
    if eps is not None:
        attrs["epsilon"] = eps
    if period:
        attrs["period"] = period
    want = _chain_numpy(ins["X"], ins.get("Scale"), heads, eps, 100.0,
                        period)
    return ins, attrs, want


@pytest.mark.parametrize("case", list(PREP_CASES))
def test_head_norm_rope_output_and_grad(case):
    """The op against the chain it stands for, and its own grad op (X and
    Scale) against central differences."""
    ins, attrs, want = _prep_case(case)
    h = OpTestHarness("head_norm_rope", ins, attrs)
    h.check_output({"Out": want}, atol=1e-5)
    h.check_grad(sorted(ins), max_relative_error=1e-2)


@pytest.mark.parametrize("case", list(PREP_CASES))
def test_head_norm_rope_plain_is_the_old_chain_in_float32(case):
    """`head_norm_rope_plain` against `rms` then `rotate_half` on the split
    heads, as the layer's three ops ran them, in float32: the result and,
    under jax.vjp, dX and dScale."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import llm_ops

    heads, d, eps, gained, period = PREP_CASES[case]
    ins, _, _ = _prep_case(case)
    x = jnp.asarray(ins["X"], jnp.float32)
    g = jnp.asarray(ins["Scale"], jnp.float32) if gained else None
    B, T, _ = x.shape

    def chain(x, g):
        y = x.reshape(B, T, heads, d).transpose(0, 2, 1, 3)
        if eps is not None:
            y = llm_ops.rms(y, eps, (3,), g)
        return llm_ops.rotate_half(y, 100.0, period)

    def plain(x, g):
        return llm_ops.head_norm_rope_plain(x, g, heads, eps, 100.0, period)

    with jax.enable_x64(False):
        dout = jnp.asarray(_r(B, heads, T, d, seed=9), jnp.float32)
        want, back = _with_vjp(chain, dout, x, g)  # one program each
        got, back_plain = _with_vjp(plain, dout, x, g)
        assert got.dtype == jnp.float32 and got.shape == (B, heads, T, d)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        for a, b in zip(back_plain, back):
            if b is not None and a is not None:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def test_head_norm_rope_rounds_bf16_once():
    """A bf16 input is normed and turned in float32 and rounded ONCE: the
    result is the float32 result's nearest bf16, where the chain of two
    ops (each rounding its own) is not always."""
    import jax.numpy as jnp

    from paddle_tpu.ops import llm_ops

    heads, d, T = 2, 64, 8
    x = jnp.asarray(_r(1, T, heads * d, seed=4), jnp.bfloat16)
    g = jnp.asarray(_r(d, lo=0.5, hi=1.5, seed=5), jnp.bfloat16)
    got = llm_ops.head_norm_rope_plain(x, g, heads, 1e-6, 1e4)
    assert got.dtype == jnp.bfloat16
    wide = llm_ops.head_norm_rope_plain(
        x.astype(jnp.float32), g.astype(jnp.float32), heads, 1e-6, 1e4)
    assert got.tobytes() == wide.astype(jnp.bfloat16).tobytes()
    chain = llm_ops.rotate_half(llm_ops.rms(
        x.reshape(1, T, heads, d).transpose(0, 2, 1, 3), 1e-6, (3,), g), 1e4)
    twice = np.asarray(chain, np.float32)
    once = np.asarray(got, np.float32)
    exact = np.asarray(wide, np.float32)
    assert np.abs(once - exact).max() <= np.abs(twice - exact).max()
    assert np.abs(once - exact).max() <= 2 ** -8 * np.abs(exact).max()


def test_head_norm_rope_grad_is_a_desc_op_of_its_own():
    """append_backward gives the op ONE `head_norm_rope_grad` desc (X,
    Scale, Out@GRAD in; X@GRAD, Scale@GRAD out; the forward's attrs, uid
    and part), not a `generic_grad`: nothing re-emits the forward."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[8, 16], dtype="float32")
    out = fluid.layers.multi_head_attention(
        x, x, x, 4, causal=True, qk_norm_epsilon=1e-5, rope_theta=100.0,
        qk_norm_per_head=True, num_kv_heads=2)
    fluid.append_backward(fluid.layers.mean(out))
    ops = fluid.default_main_program().global_block().ops
    fwd = [op for op in ops if op.type == "head_norm_rope"]
    bwd = [op for op in ops if op.type == "head_norm_rope_grad"]
    assert len(fwd) == len(bwd) == 2
    assert not [op for op in ops if op.type == "generic_grad"
                and op.attrs["__fwd_type__"] == "head_norm_rope"]
    for f, b in zip(fwd, reversed(bwd)):
        assert b.attrs == f.attrs and b.attrs["part"] == "attn.qk_prep"
        assert b.inputs == {**f.inputs,
                            "Out@GRAD": [f.outputs["Out"][0] + "@GRAD"]}
        assert sorted(b.outputs) == ["Scale@GRAD", "X@GRAD"]
        assert all(n.startswith(f.inputs[slot[:-5]][0])
                   for slot, (n,) in b.outputs.items())


def test_head_norm_rope_refuses_what_it_cannot_split():
    with pytest.raises(Exception, match="heads of an even size"):
        OpTestHarness("head_norm_rope", {"X": _r(1, 4, 10)},
                      {"num_heads": 3}).fetch()
    with pytest.raises(Exception, match="epsilon"):
        OpTestHarness("head_norm_rope",
                      {"X": _r(1, 4, 8), "Scale": _r(4)},
                      {"num_heads": 2}).fetch()


def test_head_norm_rope_cost_is_the_two_ops_it_replaces():
    from paddle_tpu.ops.registry import ShapeDtype, get_op_info

    x = ShapeDtype((2, 16, 4 * 8))
    rope = get_op_info("rope").cost({"X": [ShapeDtype((2, 4, 16, 8))]}, {},
                                    {})["flops"]
    norm = get_op_info("rms_norm").cost({"X": [x]}, {}, {})["flops"]
    cost = get_op_info("head_norm_rope").cost
    assert cost({"X": [x]}, {}, {"num_heads": 4})["flops"] == rope
    assert cost({"X": [x]}, {}, {"num_heads": 4,
                                 "epsilon": 1e-5})["flops"] == rope + norm
    assert get_op_info("head_norm_rope_grad").cost(
        {"X": [x]}, {}, {"num_heads": 4})["flops"] == 2 * rope


# ---------------------------------------------------------------------------
# the dropless form of `moe`, and `moe_router_loss`


def _moe_inputs(T=24, D=8, E=6, H=5, seed=0):
    rng = np.random.RandomState(seed)
    return {"X": rng.normal(size=(T, D)),
            "Gate": rng.normal(size=(D, E)),
            "WI": rng.normal(size=(E, D, H)) * 0.5,
            "WU": rng.normal(size=(E, D, H)) * 0.5,
            "WO": rng.normal(size=(E, H, D)) * 0.5}


def _moe_dense(x, gate, wi, wu, wo, top_k, act="silu"):
    """Every token through every expert, one-hot weights: no sort."""
    import jax
    import jax.numpy as jnp

    logits = x @ gate
    p = jax.nn.softmax(logits, -1)
    kth = jnp.sort(p, -1)[:, -top_k][:, None]
    w = jnp.where(p >= kth, p, 0.0)
    actf = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    h = actf(jnp.einsum("td,edh->eth", x, wi))
    if wu is not None:
        h = h * jnp.einsum("td,edh->eth", x, wu)
    y = jnp.einsum("eth,ehd->etd", h, wo)
    return jnp.einsum("te,etd->td", w, y), logits, jnp.sum(p >= kth, 0)


@pytest.mark.parametrize("top_k,gated,act", [(2, True, "silu"),
                                             (1, False, "relu"),
                                             (3, True, "silu"),
                                             (6, False, "silu")])
def test_moe_dropless_sorted_path_against_dense(top_k, gated, act):
    import jax
    import jax.numpy as jnp

    ins = _moe_inputs()
    if not gated:
        ins.pop("WU")
    attrs = {"dropless": True, "top_k": top_k, "gated": gated, "act": act}
    got = OpTestHarness("moe", ins, attrs,
                        ["Out", "RouterLogits", "Counts"]).fetch()
    dense = lambda a: _moe_dense(a["X"], a["Gate"], a["WI"], a.get("WU"),
                                 a["WO"], top_k, act)
    # the dense layer and the gradients each one program: op by op they
    # are 130 to compile
    want = jax.jit(dense)({k: jnp.asarray(v) for k, v in ins.items()})
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    assert float(np.sum(got[2])) == ins["X"].shape[0] * top_k  # dropless

    # gradients of a loss on Out and on the router's logits, by generic_grad
    from paddle_tpu.ops.registry import EmitContext, get_op_info

    def through_op(a):
        outs = get_op_info("moe").emit(
            EmitContext(None, is_test=False),
            {k: [v] for k, v in a.items()}, attrs)
        return outs["Out"][0], outs["RouterLogits"][0]

    def loss(fn, a):
        out, logits = fn(a)[:2]
        return jnp.sum(out * out) + jnp.sum(jnp.sin(logits))

    a = {k: jnp.asarray(v) for k, v in ins.items()}
    g_op = jax.jit(jax.grad(lambda a: loss(through_op, a)))(a)
    g_dense = jax.jit(jax.grad(lambda a: loss(dense, a)))(a)
    for k in a:
        np.testing.assert_allclose(g_op[k], g_dense[k], rtol=1e-5,
                                   atol=1e-5, err_msg=k)


def test_moe_dropless_numeric_grad():
    ins = _moe_inputs(T=6, D=4, E=3, H=3)
    OpTestHarness("moe", ins, {"dropless": True, "top_k": 2, "gated": True,
                               "act": "silu"},
                  ["Out", "RouterLogits", "Counts"]).check_grad(
        ["X", "Gate", "WI", "WU", "WO"], max_relative_error=1e-2)


def test_moe_router_loss_output_and_grad():
    rng = np.random.RandomState(2)
    logits = rng.normal(size=(10, 4))
    counts = np.array([7.0, 3.0, 6.0, 4.0])
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    balance = 4 * np.sum(counts / 10 * p.mean(0))
    z = np.mean(np.log(np.exp(logits).sum(-1)) ** 2)
    h = OpTestHarness("moe_router_loss",
                      {"RouterLogits": logits, "Counts": counts}, {},
                      ["Balance", "ZLoss"])
    h.check_output({"Balance": [balance], "ZLoss": [z]}, atol=1e-6)
    h.check_grad(["RouterLogits"], output_slot="Balance",
                 max_relative_error=1e-2)
    h.check_grad(["RouterLogits"], output_slot="ZLoss",
                 max_relative_error=1e-2)


def test_capacity_form_refuses_the_new_attributes():
    ins = _moe_inputs()
    with pytest.raises(Exception, match="dropless"):
        OpTestHarness("moe", ins, {"top_k": 2}).fetch()
    with pytest.raises(ValueError, match="dropless"):
        fluid.reset()
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        fluid.layers.moe(x, 4, 8, gated=True)


def test_moe_cost_follows_the_attributes():
    """k token-slots a token through three matmuls when gated, two when
    not; no capacity factor in the dropless form."""
    from paddle_tpu.ops.registry import ShapeDtype, get_op_info

    cost = get_op_info("moe").cost
    T, D, E, H = 4096, 2048, 64, 1024
    ins = {"X": [ShapeDtype((T, D), "bfloat16")],
           "Gate": [ShapeDtype((D, E), "bfloat16")],
           "WI": [ShapeDtype((E, D, H), "bfloat16")]}
    router = 2 * T * D * E
    got = cost(ins, {}, {"dropless": True, "top_k": 8, "gated": True,
                         "capacity_factor": 4.0})
    assert got == {"flops": router + 8 * T * 3 * 2 * D * H}
    got = cost(ins, {}, {"dropless": True, "top_k": 2, "gated": False})
    assert got == {"flops": router + 2 * T * 2 * 2 * D * H}
    old = cost(ins, {}, {"capacity_factor": 2.0})
    assert old["flops"] == router + 4 * (2 * T) * D * H
    assert old["collective_bytes"] == 4 * T * D * 2


def test_moe_dropless_refuses_an_ep_mesh():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.ops.registry import EmitContext, get_op_info

    ctx = EmitContext(None, is_test=False)
    ctx.mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
    ins = {k: [jnp.asarray(v)] for k, v in _moe_inputs().items()}
    with pytest.raises(NotImplementedError, match="R2"):
        get_op_info("moe").emit(ctx, ins, {"dropless": True, "top_k": 2,
                                           "gated": True, "act": "silu"})


# ---------------------------------------------------------------------------
# latent attention and the share's balance loss (PR 30)


def test_latent_attention_output_and_grad():
    """Against plain numpy: one rotary key for all heads, RoPE on the
    rotary columns only, scores over sqrt(dn + dr), values dv wide."""
    B, T, D, H, dn, dr, dv, r = 2, 6, 8, 2, 4, 2, 3, 5
    rng = np.random.RandomState(4)
    ins = {"X": rng.normal(size=(B, T, D)),
           "WQ": rng.normal(size=(D, H * (dn + dr))) * 0.5,
           "WKVA": rng.normal(size=(D, r + dr)) * 0.5,
           "KVNorm": rng.uniform(0.5, 1.5, size=(r,)),
           "WKVB": rng.normal(size=(r, H * (dn + dv))) * 0.5,
           "WO": rng.normal(size=(H * dv, D)) * 0.5}

    def rot(x):                                    # [..., T, dr]
        half = dr // 2
        ang = np.arange(T)[:, None] * 50000.0 ** (
            -np.arange(half) / half)[None, :]
        a, b = x[..., :half], x[..., half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1)

    x = ins["X"]
    q = (x @ ins["WQ"]).reshape(B, T, H, dn + dr).transpose(0, 2, 1, 3)
    c = x @ ins["WKVA"]
    ckv = c[..., :r]
    ckv = ckv / np.sqrt((ckv * ckv).mean(-1, keepdims=True) + 1e-5) * ins[
        "KVNorm"]
    kv = (ckv @ ins["WKVB"]).reshape(B, T, H, dn + dv).transpose(0, 2, 1, 3)
    qf = np.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    kf = np.concatenate([kv[..., :dn], np.broadcast_to(
        rot(c[:, None, :, r:]), (B, H, T, dr))], -1)
    s = np.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(dn + dr)
    s = np.where(np.tril(np.ones((T, T), bool)), s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bhkd->bhqd", p, kv[..., dn:])
    want = o.transpose(0, 2, 1, 3).reshape(B, T, H * dv) @ ins["WO"]
    h = OpTestHarness("latent_attention", ins,
                      {"num_heads": H, "qk_nope_dim": dn, "qk_rope_dim": dr,
                       "v_dim": dv, "theta": 50000.0, "epsilon": 1e-5},
                      ["Out"])
    h.check_output({"Out": want}, atol=1e-5)
    h.check_grad(["X", "WQ", "WKVA", "KVNorm", "WKVB", "WO"],
                 output_slot="Out", max_relative_error=1e-2)


def test_moe_sequence_balance_loss_output_and_grad():
    rng = np.random.RandomState(5)
    scores = 1 / (1 + np.exp(-rng.normal(size=(10, 4))))
    counts = np.array([7.0, 3.0, 6.0, 4.0])
    want = np.sum(counts * 4 / (2 * 10)
                  * (scores / scores.sum(-1, keepdims=True)).mean(0))
    h = OpTestHarness("moe_sequence_balance_loss",
                      {"RouterScores": scores, "Counts": counts},
                      {"top_k": 2}, ["Balance"])
    h.check_output({"Balance": [want]}, atol=1e-6)
    h.check_grad(["RouterScores"], output_slot="Balance",
                 max_relative_error=1e-2)


def test_moe_share_op_grad_against_numeric():
    """The share form through `generic_grad`: X, the router, the held
    experts and the shared expert, at a buffer twice the held pairs."""
    rng = np.random.RandomState(6)
    T, D, E, held, H, S = 12, 6, 8, 3, 4, 5
    ins = {"X": rng.normal(size=(T, D)),
           "Gate": rng.normal(size=(D, E)),
           "Bias": rng.normal(size=(E,)) * 0.1,
           "WI": rng.normal(size=(held, D, H)) * 0.5,
           "WU": rng.normal(size=(held, D, H)) * 0.5,
           "WO": rng.normal(size=(held, H, D)) * 0.5,
           "SI": rng.normal(size=(D, S)) * 0.5,
           "SU": rng.normal(size=(D, S)) * 0.5,
           "SO": rng.normal(size=(S, D)) * 0.5}
    h = OpTestHarness(
        "moe", ins,
        {"dropless": True, "gated": True, "top_k": 3, "act": "silu",
         "first_expert": 2, "scoring": "sigmoid", "renormalise": True,
         "routed_scale": 2.446, "buffer_rows": 24},
        ["Out", "RouterScores", "RouterWeights", "Counts", "HeldPairs",
         "DroppedPairs"])
    out = dict(zip(["Out", "RouterScores", "RouterWeights", "Counts",
                    "HeldPairs", "DroppedPairs"], h.fetch()))
    assert float(np.asarray(out["DroppedPairs"]).reshape(())) == 0.0
    assert np.asarray(out["Counts"]).sum() == T * 3
    h.check_grad(["X", "Gate", "WI", "WU", "WO", "SI", "SU", "SO"],
                 output_slot="Out", max_relative_error=2e-2)
