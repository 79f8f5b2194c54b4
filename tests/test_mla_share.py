"""What Moonlight-16B-A3B's block needed of the program (PR 30): the flash
kernels at two head widths, latent attention, and the expert layer as one
chip's share of its experts.  The program against its plain reference at
toy size is in tests/benchmarks/test_moonlight_cell.py (the reference is a
benchmark file)."""

import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _rand, _startup, _with_vjp
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer as tr
from paddle_tpu.ops import moe_ops, registry as reg
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# the flash kernels at Dqk != Dv, interpreted, against dense attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_two_widths_matches_dense_forward_and_gradients(causal):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.ring_attention import attention as dense

    B, H, T, dqk, dv = 1, 2, 128, 48, 32
    q, k = (jnp.asarray(_rand((B, H, T, dqk), i)) for i in (1, 2))
    v, do = (jnp.asarray(_rand((B, H, T, dv), i)) for i in (3, 4))
    train = fa.make_flash_train(causal=causal, interpret=True, block_q=32,
                                block_k=64)
    out, lse = fa.flash_attention_fwd(q, k, v, causal=causal,
                                      interpret=True, block_q=32, block_k=64)
    # dense attention and its gradients under `do`, one program
    want, ref = _with_vjp(lambda *a: dense(*a, causal=causal), do, q, k, v)
    assert out.shape == (B, H, T, dv) and lse.shape == (B * H, T)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        fa.flash_attention(q, k, v, causal=causal, interpret=True,
                           block_q=32, block_k=64), want, rtol=2e-5,
        atol=2e-5)
    got = jax.vjp(train, q, k, v)[1](do)
    for g, r, shape in zip(got, ref, (q.shape, k.shape, v.shape)):
        assert g.shape == shape
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4)
    # the default scale is over the width the scores contract over
    half = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                              scale=dqk ** -0.5, block_q=32, block_k=64)
    np.testing.assert_array_equal(half, fa.flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=32, block_k=64))


def _on_a_pretend_tpu(monkeypatch):
    """The executor's trace believes it targets a TPU and the flash kernels
    run interpreted (tests/test_kernel_forward_once.py's way)."""
    real_train = fa.make_flash_train
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(
        fa, "make_flash_train",
        lambda causal=False, scale=None, interpret=False:
        real_train(causal=causal, interpret=True, block_q=32, block_k=64))
    monkeypatch.setattr(fa, "_TRAIN_CACHE", {})


def _series(family):
    fam = obs.REGISTRY.snapshot()["families"].get(family, {"series": []})
    return [(s["labels"], s["value"]) for s in fam["series"]]


def test_latent_attention_takes_the_flash_kernels_once_a_layer(monkeypatch):
    """On a TPU target the op runs the two-width kernels, keeps (out, lse)
    for its grad op (no second forward launch), counts the layer, and
    agrees with the dense path the CPU takes."""
    def run(pretend):
        if pretend:
            _on_a_pretend_tpu(monkeypatch)
        fluid.reset()
        x = fluid.layers.data("x", shape=[128, 32], dtype="float32")
        y = fluid.layers.latent_attention(
            x, 2, kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
            rope_theta=50000.0)
        loss = fluid.layers.mean(y * y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = 5
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = main.global_block().all_parameters()
        assert [tuple(p.shape) for p in params] == [
            (32, 48), (32, 24), (16,), (16, 64), (32, 32)]
        fetch = [loss] + [p.name + "@GRAD" for p in params]
        return [np.asarray(o) for o in exe.run(
            feed={"x": _rand((2, 128, 32), 9)}, fetch_list=fetch)]

    dense = run(False)
    assert _series("executor_grad_kernel_forward_total") == []
    flash = run(True)
    assert _series("executor_grad_kernel_forward_total") == [
        ({"op": "latent_attention", "reused": "1"}, 1.0)]
    assert _series("mla_layers_traced_total") == [
        ({"qk_dim": "24", "v_dim": "16", "kv_rank": "16"}, 1.0)]
    squares = {(s[0]["kernel"], s[0]["part"]): s[1]
               for s in _series("flash_score_elements_total")}
    assert squares[("flash_fwd", "square")] == 2 * 2 * 128 * 128
    for a, b in zip(dense, flash):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_sdpa_gate_takes_unequal_widths_and_wide_equal_ones():
    import jax.numpy as jnp

    from paddle_tpu.ops.attention_ops import flash_single_chip

    class Ctx:
        mesh, is_test = None, True

        def target_platform(self):
            return "tpu"

    took = []
    real = fa.flash_attention
    fa.flash_attention = lambda q, k, v, causal, **blocks: took.append(
        (q.shape[-1], v.shape[-1])) or v
    try:
        # values stop at one lane tile, but for two whole tiles under keys
        # of two (PR 48: heads of 256 in q, k AND v); no width between
        for dqk, dv, want in ((192, 128, True), (128, 128, True),
                              (256, 128, True), (192, 192, False),
                              (256, 192, False), (256, 256, True),
                              (320, 128, False), (320, 320, False),
                              (64, 32, True)):
            q = jnp.zeros((1, 1, 128, dqk))
            v = jnp.zeros((1, 1, 128, dv))
            assert (flash_single_chip(Ctx(), q, q, v, True)
                    is not None) == want, (dqk, dv)
    finally:
        fa.flash_attention = real
    assert took == [(192, 128), (128, 128), (256, 128), (256, 256),
                    (64, 32)]


# ---------------------------------------------------------------------------
# the expert layer as a share


def _whole_layer(x, gate, bias, wi, wu, wo, top_k, scale, epsilon=1e-20):
    """The uncut layer in plain numpy/jnp: sigmoid scores, top-k of score +
    bias, renormalised weights times scale, every chosen pair computed."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ gate)
    _, idx = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + epsilon) * scale
    out = jnp.zeros_like(x)
    for e in range(wi.shape[0]):
        y = (jax.nn.silu(x @ wi[e]) * (x @ wu[e])) @ wo[e]
        out = out + y * jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
    return out, idx


@pytest.mark.parametrize("k,scale,epsilon,shared_hidden", [
    (6, 2.446, 1e-20, 24), (4, 1.0, 1e-6, 0), (4, 2.0, 1e-20, 16)],
    ids=["moonlight_with_a_shared_expert", "lfm2_without",
         "xing4_top4_scale2_one_shared_expert"])
def test_eight_shares_add_up_to_the_uncut_layer(k, scale, epsilon,
                                                shared_hidden):
    """held 8 of 64, sigmoid, bias; Moonlight's top-6, scale 2.446 and a
    shared expert, LFM2's top-4, scale 1, epsilon 1e-6 and NO shared
    expert, Xing4.0's top-4, scale 2 and ONE shared expert of the routed
    experts' width: the layer run 8 times with first = 0, 8, ..., 56 (and the
    shared expert, where there is one, counted ONCE) adds up to the whole
    layer; every share's counts are the whole layer's, its held pairs are
    its slice of them, nothing is dropped."""
    import jax
    import jax.numpy as jnp

    T, D, E, H, held = 64, 32, 64, 16, 8
    with jax.enable_x64(False):
        x = jnp.asarray(_rand((T, D), 1))
        gate = jnp.asarray(_rand((D, E), 2, 0.5))
        bias = jnp.asarray(_rand((E,), 3, 0.05))
        wi, wu = (jnp.asarray(_rand((E, D, H), i, 0.3)) for i in (4, 5))
        wo = jnp.asarray(_rand((E, H, D), 6, 0.3))
        want, idx = _whole_layer(x, gate, bias, wi, wu, wo, k, scale,
                                 epsilon)
        shared = None
        if shared_hidden:
            shared = tuple(jnp.asarray(_rand(s, i, 0.3)) for i, s in (
                (7, (D, shared_hidden)), (8, (D, shared_hidden)),
                (9, (shared_hidden, D))))
            want = want + (jax.nn.silu(x @ shared[0]) * (x @ shared[1])
                           ) @ shared[2]
        ctx = reg.EmitContext(None, is_test=False)
        route = {"scoring": "sigmoid", "renormalise": True, "scale": scale,
                 "epsilon": epsilon}
        total = jnp.zeros_like(x)
        whole_counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
        for first in range(0, E, held):
            at = slice(first, first + held)
            out, scores, weights, counts, pairs, dropped = (
                moe_ops._moe_share(
                    ctx, x, gate, bias, wi[at], wu[at], wo[at],
                    shared if first == 0 else None, k, "silu", first,
                    T * k if first % 16 else 128, route))
            total = total + out
            np.testing.assert_array_equal(counts, whole_counts)
            assert float(pairs[0]) == whole_counts[at].sum()
            assert float(dropped[0]) == 0.0
            assert scores.shape == (T, E) and weights.shape == (T, k)
            np.testing.assert_allclose(weights.sum(-1), scale, rtol=1e-5)
        assert whole_counts.sum() == T * k
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_a_buffer_too_small_reports_what_it_dropped():
    import jax
    import jax.numpy as jnp

    T, D, E, H, k = 32, 16, 8, 8, 2
    with jax.enable_x64(False):
        x, gate = jnp.asarray(_rand((T, D), 1)), jnp.asarray(
            _rand((D, E), 2))
        w = [jnp.asarray(_rand(s, i, 0.3)) for i, s in (
            (3, (4, D, H)), (4, (4, D, H)), (5, (4, H, D)))]
        ctx = reg.EmitContext(None, is_test=False)
        route = {"scoring": "softmax", "renormalise": False, "scale": 1.0}
        roomy, tight = (jax.jit(lambda x, gate, *w: moe_ops._moe_share(
            ctx, x, gate, None, *w, None, k, "silu", 2, rows, route))(
                x, gate, *w) for rows in (T * k, 8))   # a program each
    held = float(roomy[4][0])
    assert held > 8 and float(roomy[5][0]) == 0.0
    assert float(tight[4][0]) == held and float(tight[5][0]) == held - 8
    assert not np.allclose(roomy[0], tight[0])
    assert np.isfinite(np.asarray(tight[0])).all()


def _moonlight_toy(**over):
    args = dict(seq_len=32, vocab_size=97, dim=64, n_layers=3, n_heads=4,
                kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
                dense_dim=96, num_experts=16, expert_dim=32, top_k=3,
                shared_experts=2, held_experts=4, first_expert=4,
                buffer_rows=64, routed_scale=2.446, dtype="float32",
                learning_rate=3e-3, init_scale=0.3, bias_init_scale=0.05)
    args.update(over)
    fluid.reset()
    return tr.build_mla_moe_lm_train_program(**args)


def test_moonlight_program_is_built_from_the_new_layers():
    _moonlight_toy()
    main = fluid.default_main_program()
    ops = [op.type for op in main.global_block().ops]
    fwd = ops[:ops.index("generic_grad")]
    assert fwd.count("latent_attention") == 3 and fwd.count("moe") == 2
    assert fwd.count("moe_sequence_balance_loss") == 2
    assert "scaled_dot_product_attention" not in fwd and "rope" not in fwd
    assert "layer_norm" not in fwd and "slice" not in fwd   # no position table
    assert fwd.count("rms_norm") == 2 * 3 + 1
    # the bias moves after the backward pass, and takes no gradient
    assert ops.count("moe_bias_update") == 2
    assert ops.index("moe_bias_update") > max(
        i for i, t in enumerate(ops) if t == "generic_grad")
    params = main.global_block().all_parameters()
    frozen = [p for p in params if not p.trainable]
    assert [tuple(p.shape) for p in frozen] == [(16,), (16,)]
    assert all(p.name + "@GRAD" not in main.global_block().vars
               for p in frozen)
    shapes = [tuple(p.shape) for p in params]
    assert len(shapes) == 1 + 10 + 15 * 2 + 2
    assert shapes[8:11] == [(64, 96), (64, 96), (96, 64)]      # dense layer
    assert shapes[18:26] == [(64, 16), (4, 64, 32), (4, 64, 32),
                             (4, 32, 64), (16,), (64, 64), (64, 64),
                             (64, 64)]
    for op in main.global_block().ops:
        if op.type == "moe":
            assert op.attrs["first_expert"] == 4
            assert op.attrs["scoring"] == "sigmoid"
            assert op.attrs["renormalise"] and op.attrs["buffer_rows"] == 64
            assert op.attrs["routed_scale"] == 2.446


def test_moonlight_toy_trains_and_its_bias_follows_the_counts():
    loss = _moonlight_toy()
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    moe = [op for op in main.global_block().ops if op.type == "moe"][-1]
    bias = moe.input("Bias")[0]
    fetch = [loss] + [moe.output(s)[0] for s in (
        "Counts", "HeldPairs", "DroppedPairs", "RouterWeights")]
    tok = np.random.RandomState(0).randint(0, 97, (1, 32, 1)).astype("int64")
    feed = {"tokens": tok, "targets": np.roll(tok, -1, 1)}
    losses = []
    for _ in range(6):
        before = np.asarray(fluid.global_scope().find(bias)).copy()
        out = [np.asarray(o) for o in exe.run(feed=feed, fetch_list=fetch)]
        after = np.asarray(fluid.global_scope().find(bias))
        counts = out[1]
        assert counts.sum() == 32 * 3 and out[2][0] == counts[4:8].sum()
        assert out[3][0] == 0
        np.testing.assert_allclose(out[4].sum(-1), 2.446, rtol=1e-5)
        np.testing.assert_allclose(
            after - before, 1e-3 * np.sign(counts.mean() - counts),
            atol=1e-7)
        losses.append(float(out[0].reshape(())))
    assert losses[-1] < 0.5 * losses[0]
    assert _series("moe_share_layers_traced_total") == [
        ({"held": "4", "experts": "16", "top_k": "3", "buffer_rows": "64"},
         2.0)]
    assert _series("moe_layers_traced_total") == []


def _olmoe_helper():
    spec = importlib.util.spec_from_file_location(
        "olmoe_tests", os.path.join(HERE, "test_olmoe.py"))
    helper = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(helper)
    return helper


def test_the_parts_stated_float32_are_float32_in_a_bf16_step():
    """The configuration states float32 inside the norms, RoPE, the router's
    matmul and sigmoid and the loss while weights and activations are bf16.
    The reference check cannot see one of them dropped to bf16 (PERF.md
    section 6, PR 30: with a bf16 residual stream such a control reads what
    the program itself reads), so the lowered step is held to it by its
    types.  (The softmax is the flash kernels' own on the chip; the dense
    path this CPU lowering takes keeps bf16 scores, as it always has.)"""
    loss = _moonlight_toy(dtype="bfloat16")
    text = _olmoe_helper()._lowered(loss, 1, 32)

    def types(op, where=""):
        return [line.split(":")[-1].strip() for line in text.splitlines()
                if f"stablehlo.{op} " in line and where in line]

    # 3 layers x 2 norms, the latent's 3 and the final one, each way
    assert len(types("rsqrt")) >= 10
    for op in ("rsqrt", "cosine", "sine", "log"):
        assert types(op) and all(t.endswith("xf32>") for t in types(op)), op
    # the router: [32, 64] x [64, 16] in float32, and its sigmoid's exp
    routers = types("dot_general", "-> tensor<32x16x")
    assert routers and set(routers) == {
        "(tensor<32x64xf32>, tensor<64x16xf32>) -> tensor<32x16xf32>"}
    assert "tensor<32x16xf32>" in types("exponential")
    assert not types("exponential", "tensor<32x16xbf16>")
    # the loss over the vocabulary slice
    assert "tensor<32x97xf32>" in types("exponential")
    # and the weights and activations ARE bf16
    assert "xbf16>" in text


def test_holding_every_expert_is_olmoes_emitted_step():
    """`held` = all under OLMoE's router is the op the parent emits: the
    OLMoE tower built with held = (0, E) lowers byte for byte to the step
    it lowers to without."""
    helper = _olmoe_helper()

    def build(moe):
        fluid.reset()
        tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
        targets = fluid.layers.data("targets", shape=[16, 1], dtype="int64")
        routers = []
        logits = tr.decoder_lm(
            tokens, 31, 16, 2, 2, max_len=16, norm="rms_norm",
            positions="rope", qk_norm=True, ffn="moe", moe=moe,
            router_outputs=routers, init_scale=0.3)
        loss = tr.moe_lm_loss(logits, targets, routers)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return helper._lowered(loss, 1, 16)

    plain = {"num_experts": 4, "d_hidden": 8, "top_k": 2}
    assert build(plain) == build(dict(plain, held=(0, 4)))
    # and a real share of the same tower is another program
    fluid.reset()
    x = fluid.layers.data("x", shape=[16], dtype="float32")
    got = fluid.layers.moe(x, 4, 8, top_k=2, gated=True, dropless=True,
                           act="silu", held=(2, 2))
    assert isinstance(got, fluid.layers.MoeShare) and got.bias is None


def test_share_refusals():
    fluid.reset()
    x = fluid.layers.data("x", shape=[16], dtype="float32")
    with pytest.raises(ValueError, match="dropless=True"):
        fluid.layers.moe(x, 4, 8, held=(0, 2))
    import jax.numpy as jnp

    ctx = reg.EmitContext(None, is_test=False)
    ins = {"X": [jnp.zeros((8, 4))], "Gate": [jnp.zeros((4, 6))],
           "WI": [jnp.zeros((2, 4, 4))], "WO": [jnp.zeros((2, 4, 4))]}
    base = {"dropless": True, "top_k": 2, "act": "silu"}
    emit = reg.get_op_info("moe").emit
    for bad, match in (({"first_expert": 5}, "not among"),
                       ({"first_expert": 0, "scoring": "tanh"}, "scoring"),
                       ({"first_expert": 0, "buffer_rows": 99}, "buffer_rows")):
        with pytest.raises(ValueError, match=match):
            emit(ctx, ins, dict(base, **bad))


def test_rows_no_group_has_never_reach_a_result(monkeypatch):
    """On the chip `lax.ragged_dot` and the backward kernels leave the rows
    past the groups unwritten (PERF.md, PR 30: NaN among them).  Here every
    grouped product is made to leave NaN there: the layer's output and
    every gradient stay finite and equal to the clean ones."""
    import jax
    import jax.numpy as jnp

    T, D, E, H, k = 32, 16, 8, 8, 2
    real = moe_ops._grouped_matmul

    def dirty(ctx, xs, w, counts):
        past = (jnp.arange(xs.shape[0]) >= jnp.sum(counts))[:, None]

        @jax.custom_vjp
        def product(xs, w):
            return jnp.where(past, jnp.nan, real(ctx, xs, w, counts))

        def bwd(res, g):
            dx, dw = jax.vjp(lambda a, b: real(ctx, a, b, counts), *res)[1](
                jnp.where(past, 0.0, g).astype(g.dtype))
            return jnp.where(past, jnp.nan, dx), dw

        product.defvjp(lambda xs, w: (product(xs, w), (xs, w)), bwd)
        return product(xs, w)

    with jax.enable_x64(False):
        x, gate = jnp.asarray(_rand((T, D), 1)), jnp.asarray(
            _rand((D, E), 2))
        w = [jnp.asarray(_rand(s, i, 0.3)) for i, s in (
            (3, (4, D, H)), (4, (4, D, H)), (5, (4, H, D)))]
        ctx = reg.EmitContext(None, is_test=False)
        route = {"scoring": "sigmoid", "renormalise": True, "scale": 2.0}

        def loss(x, gate, *w):
            return jnp.sum(moe_ops._moe_share(
                ctx, x, gate, None, *w, None, k, "silu", 2, T * k,
                route)[0] ** 2)

        clean = jax.jit(jax.value_and_grad(loss, range(5)))(x, gate, *w)
        monkeypatch.setattr(moe_ops, "_grouped_matmul", dirty)
        got = jax.jit(jax.value_and_grad(loss, range(5)))(x, gate, *w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(clean)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# rows to tokens: the kernel `segment-sum-rows` against XLA's scatter-add


SHARE_ROWS = "moe_share_rows_to_tokens_traced_total"


def _share_step(dtype, buffer_rows, drawn, layers=2):
    """`layers` gated share layers (4 of 8 experts held from 2 on, sigmoid
    scores, a selection bias, a shared expert) under SGD, one step: the
    last layer's Out and RouterWeights, then the gradients of its input
    and of its trainable parameters (router, held Wgate, Wup, Wdown, the
    shared expert's three)."""
    from paddle_tpu.framework.initializer import NormalInitializer

    tokens, dim = 128, 128
    fluid.reset()
    x = fluid.layers.data("x", shape=[dim], dtype=dtype)
    # a projection first: every layer's input then wants its gradient
    h = fluid.layers.fc(x, dim, bias_attr=False)
    for _ in range(layers):
        before = len(fluid.default_main_program().global_block()
                     .all_parameters())
        inp = h
        share = fluid.layers.moe(
            inp, 8, 64, act="silu", top_k=2, gated=True, dropless=True,
            held=(2, 4), scoring="sigmoid", renormalise=True,
            routed_scale=2.0, select_bias=NormalInitializer(scale=0.05),
            buffer_rows=buffer_rows, shared_hidden=32)
        h = inp + share.out
    wide = fluid.layers.cast(h, "float32")
    fluid.optimizer.SGD(learning_rate=0.1).minimize(
        fluid.layers.mean(wide * wide))
    main = fluid.default_main_program()
    params = [p for p in main.global_block().all_parameters()[before:]
              if p.trainable]
    assert [len(p.shape) for p in params] == [2, 3, 3, 3, 2, 2, 2]
    fetch = [share.out, share.weights, share.dropped_pairs, inp.name + "@GRAD"] + [
        p.name + "@GRAD" for p in params]
    exe = fluid.Executor(fluid.CPUPlace())
    main.random_seed = fluid.default_startup_program().random_seed = 41
    _startup(exe, drawn)    # the same draws under both paths
    feed = {"x": _rand((tokens, dim), 5).astype(dtype)}
    return [np.asarray(g) for g in exe.run(feed=feed, fetch_list=fetch)]


SHARE_DRAWN = {}    # a dtype's startup program is its buffers' both: one draw


@pytest.mark.parametrize("buffer_rows", [None, 96],
                         ids=["a_roomy_buffer", "a_buffer_that_drops_pairs"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_share_with_the_segment_sum_kernel_equals_the_scatter_add(
        monkeypatch, dtype, buffer_rows):
    """The `moe` op's share form through the executor, forward and
    backward, with the kernel path forced through interpret mode (the
    trace claims a TPU target; the grouped matmuls keep autodiff's
    transposes, so the two scatter-adds a layer are ALL that differs):
    Out, RouterWeights and every gradient agree with the fallback's, the
    counter names the path for both sums of both layers, and the `moe` op
    launches no kernel's forward again."""
    import functools

    from paddle_tpu.ops.pallas_kernels import grouped_matmul as gm
    from paddle_tpu.ops.pallas_kernels import segment_sum as ss

    def paths():
        return {(s[0]["op"], s[0]["path"]): s[1]
                for s in _series(SHARE_ROWS)}

    layers, drawn = 2, SHARE_DRAWN.setdefault(dtype, {})
    fallback = _share_step(dtype, buffer_rows, drawn, layers)
    assert paths() == {("combine", "scatter_add"): float(layers),
                       ("permute_grad", "scatter_add"): float(layers)}

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(gm, "usable", lambda *shape: False)
    monkeypatch.setattr(ss, "ROW_TILE", 32)
    monkeypatch.setattr(ss, "segment_sum",
                        functools.partial(ss.segment_sum, interpret=True))
    kernel = _share_step(dtype, buffer_rows, drawn, layers)
    assert paths() == {("combine", "segment_sum"): float(layers),
                       ("permute_grad", "segment_sum"): float(layers)}
    assert _series("executor_grad_kernel_forward_total") == []

    assert (float(kernel[2][0]) > 0) == (buffer_rows is not None)
    np.testing.assert_array_equal(kernel[2], fallback[2])   # DroppedPairs
    names = ("Out", "RouterWeights", "DroppedPairs", "X", "Gate", "WI", "WU",
             "WO", "SI", "SU", "SO")
    for name, a, b in zip(names, kernel, fallback):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0 or name == "DroppedPairs", name
        if dtype == "float32":
            # float32 sums in another order
            np.testing.assert_allclose(a, b, rtol=1e-5,
                                       atol=1e-6 * np.abs(b).max(),
                                       err_msg=name)
        else:
            # x's gradient is rounded to bf16 once where the scatter-add
            # rounds at every add, and the first layer's gradients carry
            # that on
            np.testing.assert_allclose(a, b, rtol=2.0 ** -6,
                                       atol=2.0 ** -7 * np.abs(b).max(),
                                       err_msg=name)
