"""What LFM2-24B-A2B's block needed of the program (PR 33): grouped-query
attention inside the flash kernels, the gated short convolution, the
per-head QK-norm, a token mixer chosen layer by layer, and the router's own
renormalisation epsilon; and the towers that were there lower to the steps
they lowered to.  The toy tower against its plain reference, parameter by
parameter, is here too (the reference is a benchmark file; the cell's own
driver run and the mutants are in tests/benchmarks/test_lfm2_cell.py)."""

import hashlib
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _rand
from op_test import OpTestHarness
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer as tr
from paddle_tpu.ops import moe_ops, registry as reg
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _olmoe_helper():
    return _load("olmoe_tests", os.path.join(HERE, "test_olmoe.py"))


# ---------------------------------------------------------------------------
# grouped-query attention inside the flash kernels, interpreted


def _dense_gqa(q, k, v, causal):
    import jax.numpy as jnp

    from paddle_tpu.ops.ring_attention import attention as dense

    group = q.shape[1] // k.shape[1]
    return dense(q, jnp.repeat(k, group, axis=1),
                 jnp.repeat(v, group, axis=1), causal=causal)


@pytest.mark.parametrize("blocks", [(64, 64), (16, 32)],
                         ids=["one_block_a_head", "several_blocks"])
@pytest.mark.parametrize("dv", [16, 8], ids=["D_eq_Dv", "D_ne_Dv"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_flash_gqa_matches_dense_with_repeated_kv(group, causal, dv, blocks):
    """q [B, Hkv * group, T, D] on k, v [B, Hkv, T, D / Dv]: the forward,
    its logsumexp and the three gradients against dense attention on K and
    V repeated to the query heads; dk and dv come back in K's and V's own
    shapes, the sum over a group's query heads."""
    import jax
    import jax.numpy as jnp

    B, kv_heads, T, D = 2, 2, 64, 16
    with jax.enable_x64(False):
        q = jnp.asarray(_rand((B, kv_heads * group, T, D), 1))
        k = jnp.asarray(_rand((B, kv_heads, T, D), 2))
        v = jnp.asarray(_rand((B, kv_heads, T, dv), 3))
        do = jnp.asarray(_rand((B, kv_heads * group, T, dv), 4))
        kw = dict(causal=causal, interpret=True, block_q=blocks[0],
                  block_k=blocks[1])

        @jax.jit    # the case's kernels and its reference: ONE program
        def kernels(q, k, v, do):
            want, vjp = jax.vjp(lambda q, k, v: _dense_gqa(q, k, v, causal),
                                q, k, v)
            return (fa.flash_attention_fwd(q, k, v, **kw),
                    fa.flash_attention(q, k, v, **kw),
                    jax.vjp(fa.make_flash_train(**kw), q, k, v)[1](do),
                    want, vjp(do))

        (out, lse), nolse, got, want, grads = kernels(q, k, v, do)
        assert out.shape == do.shape and lse.shape == (
            B * kv_heads * group, T)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(nolse, want, rtol=2e-5, atol=2e-5)
        for g, r, like in zip(got, grads, (q, k, v)):
            assert g.shape == like.shape
            np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_flash_gqa_refuses_head_counts_that_do_not_divide():
    import jax.numpy as jnp

    q, k = jnp.zeros((1, 6, 32, 8)), jnp.zeros((1, 4, 32, 8))
    with pytest.raises(ValueError, match="query heads"):
        fa.flash_attention(q, k, k, interpret=True)
    with pytest.raises(ValueError, match="query heads"):
        fa.flash_attention(q, jnp.zeros((1, 3, 32, 8)), k, interpret=True)


def test_flash_gqa_counts_the_query_heads_squares():
    """`flash_score_elements_total` counts B x Hq x T x T a call."""
    import jax
    import jax.numpy as jnp

    obs.REGISTRY.reset()
    with jax.enable_x64(False):
        q, k = jnp.zeros((1, 8, 64, 16)), jnp.zeros((1, 2, 64, 16))
        out, lse = fa.flash_attention_fwd(q, k, k, causal=True,
                                          interpret=True, block_q=32,
                                          block_k=32)
        fa.flash_attention_bwd(q, k, k, out, lse, out, causal=True,
                               interpret=True, block_q=32, block_k=32)
    fam = obs.REGISTRY.snapshot()["families"]["flash_score_elements_total"]
    squares = {s["labels"]["kernel"]: s["value"] for s in fam["series"]
               if s["labels"]["part"] == "square"}
    assert squares == {k: 8.0 * 64 * 64 for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}


def test_sdpa_op_takes_two_head_counts_and_counts_the_layer():
    """The op on the dense path (the CPU's): K and V with fewer heads are
    repeated; output and gradients are those of attention on the repeated
    heads, dK and dV in K's and V's own shapes."""
    obs.REGISTRY.reset()
    q, k, v = _rand((2, 4, 8, 6), 1), _rand((2, 2, 8, 6), 2), _rand(
        (2, 2, 8, 6), 3)
    h = OpTestHarness("scaled_dot_product_attention",
                      {"Q": q, "K": k, "V": v}, {"causal": True})
    want = np.asarray(_dense_gqa(*(np.asarray(a, np.float64)
                                   for a in (q, k, v)), True))
    h.check_output({"Out": want}, atol=1e-5)
    h.check_grad(["Q", "K", "V"], max_relative_error=1e-2)
    fam = obs.REGISTRY.snapshot()["families"]
    series = fam["gqa_attention_layers_traced_total"]["series"]
    assert {tuple(sorted(s["labels"].items())) for s in series} == {
        (("head_dim", "6"), ("kv_heads", "2"), ("q_heads", "4"))}
    with pytest.raises(Exception, match="query heads"):
        OpTestHarness("scaled_dot_product_attention",
                      {"Q": q, "K": _rand((2, 3, 8, 6), 2),
                       "V": _rand((2, 3, 8, 6), 3)}, {}).fetch()


# ---------------------------------------------------------------------------
# the gated short convolution


def _conv_numpy(x, w):
    """C * conv(B * u), tap L - 1 on the current token, in float64."""
    D, L = w.shape
    b, c, u = np.split(np.asarray(x, np.float64), 3, axis=-1)
    g = b * u
    out = np.zeros_like(g)
    for t in range(g.shape[1]):
        for j in range(L):
            s = t - (L - 1) + j
            if s >= 0:
                out[:, t] += w[:, j] * g[:, s]
    return c * out


@pytest.mark.parametrize("taps", [3, 1, 4])
def test_gated_short_conv_output_and_grad(taps):
    x, w = (_rand((2, 7, 3 * 5), 1).astype("float64"),
            _rand((5, taps), 2).astype("float64"))
    h = OpTestHarness("gated_short_conv", {"X": x, "Filter": w}, {})
    h.check_output({"Out": _conv_numpy(x, w)}, atol=1e-5)
    h.check_grad(["X", "Filter"], max_relative_error=1e-2)


def test_gated_short_conv_is_causal_and_float32_inside_bf16():
    """Output t does not move when inputs after t do; a bf16 input is
    gated and convolved in float32 and rounded once."""
    import jax.numpy as jnp

    x, w = _rand((1, 12, 3 * 4), 3), _rand((4, 3), 4)
    emit = lambda x, w: reg.get_op_info("gated_short_conv").emit(  # noqa
        reg.EmitContext(None, is_test=True),
        {"X": [jnp.asarray(x)], "Filter": [jnp.asarray(w)]}, {})["Out"][0]
    moved = x.copy()
    moved[:, 7:] += 1.0
    a, b = np.asarray(emit(x, w)), np.asarray(emit(moved, w))
    np.testing.assert_array_equal(a[:, :7], b[:, :7])
    assert np.abs(a[:, 7:] - b[:, 7:]).min() > 0
    xb, wb = jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16)
    got = emit(xb, wb)
    assert got.dtype == jnp.bfloat16
    want = _conv_numpy(np.asarray(xb, np.float32), np.asarray(wb, np.float32))
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jnp.asarray(want, jnp.float32).astype(jnp.bfloat16),
                   np.float32))
    with pytest.raises(Exception, match="3 x"):
        emit(x[..., :11], w)


def test_gated_short_conv_layer_and_counter():
    obs.REGISTRY.reset()
    fluid.reset()
    x = fluid.layers.data("x", shape=[6, 8], dtype="float32")
    y = fluid.layers.gated_short_conv(x, kernel_size=3)
    main = fluid.default_main_program()
    assert [op.type for op in main.global_block().ops] == [
        "mul", "gated_short_conv", "mul"]
    assert [tuple(p.shape) for p in main.global_block().all_parameters()
            ] == [(8, 24), (8, 3), (8, 8)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xs = _rand((2, 6, 8), 5)
    (got,) = exe.run(feed={"x": xs}, fetch_list=[y])
    w_in, w, w_out = (np.asarray(fluid.global_scope().find(p.name))
                      for p in main.global_block().all_parameters())
    np.testing.assert_allclose(got, _conv_numpy(xs @ w_in, w) @ w_out,
                               rtol=1e-5, atol=1e-6)
    fam = obs.REGISTRY.snapshot()["families"]
    assert [(s["labels"], s["value"]) for s in
            fam["short_conv_layers_traced_total"]["series"]] == [
        ({"dim": "8", "kernel": "3"}, 1.0)]


# ---------------------------------------------------------------------------
# the per-head QK-norm, grouped heads in the layer


def test_multi_head_attention_per_head_qk_norm_and_kv_heads():
    """num_kv_heads narrows the K and V projections; qk_norm_per_head puts
    ONE gain of the head's width on Q and one on K, after the split and
    before RoPE, all inside Q's and K's `head_norm_rope` op; the result is
    attention computed by hand."""
    import jax
    import jax.numpy as jnp

    B, T, D, H, KV, eps, theta = 2, 6, 16, 4, 2, 1e-5, 100.0
    d = D // H
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, D], dtype="float32")
    y = fluid.layers.multi_head_attention(
        x, x, x, H, causal=True, qk_norm_epsilon=eps, rope_theta=theta,
        num_kv_heads=KV, qk_norm_per_head=True)
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    assert [tuple(p.shape) for p in params] == [
        (D, D), (D, KV * d), (D, KV * d), (d,), (d,), (D, D)]
    ops = [op.type for op in main.global_block().ops]
    assert "rope" not in ops and "rms_norm" not in ops
    prep = [op for op in main.global_block().ops
            if op.type == "head_norm_rope"]
    assert [(op.attrs["num_heads"], op.attrs["epsilon"], op.attrs["theta"],
             op.attrs["part"], op.inputs["Scale"]) for op in prep] == [
        (H, eps, theta, "attn.qk_prep", [params[3].name]),
        (KV, eps, theta, "attn.qk_prep", [params[4].name])]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    gq = _rand((d,), 7) + 1.5       # a gain that is not one
    scope.set(params[3].name, gq)
    xs = _rand((B, T, D), 6)
    (got,) = exe.run(feed={"x": xs}, fetch_list=[y])
    wq, wk, wv, _, gk, wo = (np.asarray(scope.find(p.name), np.float64)
                             for p in params)
    def heads(a, n):
        return a.reshape(B, T, n, d).transpose(0, 2, 1, 3)

    def rms(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) * g

    from test_llm_ops import _rope_numpy

    q = _rope_numpy(rms(heads(xs @ wq, H), gq), theta)
    k = _rope_numpy(rms(heads(xs @ wk, KV), gk), theta)
    v = heads(xs @ wv, KV)
    with jax.enable_x64(True):
        o = np.asarray(_dense_gqa(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), True))
    want = o.transpose(0, 2, 1, 3).reshape(B, T, D) @ wo
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="num_kv_heads"):
        fluid.layers.multi_head_attention(x, x, x, H, num_kv_heads=3)
    with pytest.raises(ValueError, match="qk_norm_epsilon"):
        fluid.layers.multi_head_attention(x, x, x, H, qk_norm_per_head=True)


# ---------------------------------------------------------------------------
# the router's epsilon


def test_renorm_epsilon_on_four_tiny_scores():
    """Four experts whose sigmoid scores are near 1e-7: over their sum +
    1e-20 (DeepSeek's, the default) the two chosen weights add up to one,
    over their sum + 1e-6 (LFM2's) to sum / (sum + 1e-6), far from it."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(False):
        x = jnp.ones((1, 1), jnp.float32)
        gate = jnp.asarray([[-16.0, -16.5, -17.0, -15.5]], jnp.float32)
        s = np.asarray(jax.nn.sigmoid(gate[0]), np.float64)
        top = np.sort(s)[-2:]
        route = lambda **kw: moe_ops._route_scored(  # noqa: E731
            x, gate, None, 2, "sigmoid", True, 1.0, **kw)
        _, w_default, idx = route()
        _, w_lfm2, idx2 = route(epsilon=1e-6)
    assert sorted(np.asarray(idx)[0]) == sorted(np.asarray(idx2)[0]) == [0, 3]
    np.testing.assert_allclose(np.asarray(w_default).sum(), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w_lfm2).sum(),
                               top.sum() / (top.sum() + 1e-6), rtol=1e-4)
    assert np.asarray(w_lfm2).sum() < 0.3
    # the attr reaches the router through layers.moe
    fluid.reset()
    xs = fluid.layers.data("x", shape=[8], dtype="float32")
    fluid.layers.moe(xs, 4, 8, top_k=2, gated=True, dropless=True,
                     act="silu", held=(0, 2), scoring="sigmoid",
                     renormalise=True, renorm_epsilon=1e-6)
    (op,) = [op for op in fluid.default_main_program().global_block().ops
             if op.type == "moe"]
    assert op.attrs["renorm_epsilon"] == 1e-6
    fluid.reset()
    xs = fluid.layers.data("x", shape=[8], dtype="float32")
    fluid.layers.moe(xs, 4, 8, top_k=2, gated=True, dropless=True,
                     act="silu", held=(0, 2), scoring="sigmoid",
                     renormalise=True)
    (op,) = [op for op in fluid.default_main_program().global_block().ops
             if op.type == "moe"]
    assert "renorm_epsilon" not in op.attrs


# ---------------------------------------------------------------------------
# the tower


TOY_LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]


def _lfm2_toy(**over):
    args = dict(seq_len=64, vocab_size=97, dim=64, layer_types=TOY_LAYERS,
                n_heads=8, n_kv_heads=2, conv_kernel=3, dense_dim=96,
                dense_layers=1, num_experts=8, expert_dim=16, top_k=4,
                held_experts=2, first_expert=2, buffer_rows=96,
                dtype="float32", learning_rate=3e-3, init_scale=0.3,
                emb_init_scale=1.0, bias_init_scale=0.05)
    args.update(over)
    fluid.reset()
    return tr.build_lfm2_moe_lm_train_program(**args)


def test_lfm2_program_is_built_from_the_new_layers():
    _lfm2_toy()
    main = fluid.default_main_program()
    ops = [op.type for op in main.global_block().ops]
    fwd = ops[:ops.index("generic_grad")]
    assert fwd.count("gated_short_conv") == 4
    assert fwd.count("scaled_dot_product_attention") == 1
    assert fwd.count("head_norm_rope") == 2        # the attention layer only
    assert "rope" not in fwd
    assert fwd.count("moe") == 4
    assert "moe_sequence_balance_loss" not in fwd  # no auxiliary loss
    assert "layer_norm" not in fwd and "slice" not in fwd
    # two a block and the final one; Q's and K's are inside head_norm_rope
    assert fwd.count("rms_norm") == 2 * 5 + 1
    (moe,) = {repr(sorted((k, v) for k, v in op.attrs.items()
                          if not k.startswith("__")))
              for op in main.global_block().ops if op.type == "moe"}
    assert "('renorm_epsilon', 1e-06)" in moe and "'sigmoid'" in moe
    assert ops.count("moe_bias_update") == 4
    assert ops.index("moe_bias_update") > ops.index("adam")


def test_lfm2_toy_tower_matches_the_reference_parameter_by_parameter():
    """Float32 on the CPU: the loss, every token's loss and the gradient of
    EVERY parameter (the selection biases, which take none, apart) against
    benchmarks/reference/lfm2-24b-a2b.py on the same weights."""
    import jax
    import jax.numpy as jnp

    ref = _load("bench_reference_lfm2",
                os.path.join(BENCH, "reference", "lfm2-24b-a2b.py"))
    loss = _lfm2_toy()
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    trained = [i for i, p in enumerate(params) if p.trainable]
    assert len(params) - len(trained) == 4         # the four biases
    scope = fluid.global_scope()
    before = [np.asarray(scope.find(p.name)) for p in params]
    tok = np.random.RandomState(3).randint(0, 97, (1, 64, 1))
    tgt = np.roll(tok, -1, axis=1)
    token_loss = [op for op in main.global_block().ops
                  if op.type == "softmax_with_cross_entropy"][-1].output(
                      "Loss")[0]
    got = exe.run(feed={"tokens": tok, "targets": tgt},
                  fetch_list=[loss, token_loss]
                  + [params[i].name + "@GRAD" for i in trained])
    cfg = {"layer_types": TOY_LAYERS, "num_dense_layers": 1,
           "num_attention_heads": 8, "num_key_value_heads": 2,
           "conv_L_cache": 3, "norm_eps": 1e-5,
           "rope_parameters": {"rope_theta": 1000000.0},
           "num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
           "share": {"first_expert": 2}}
    with jax.enable_x64(False):
        ps = [jnp.asarray(a, jnp.float32) for a in before]

        def total(picked):
            full = list(ps)
            for i, p in zip(trained, picked):
                full[i] = p
            hidden, head, _ = ref.forward(full, jnp.asarray(tok[0, :, 0]),
                                          cfg)
            per = ref.token_losses(hidden, head, jnp.asarray(tgt[0, :, 0]))
            return jnp.mean(per), per

        with jax.default_matmul_precision("highest"):
            (want, per), grads = jax.jit(jax.value_and_grad(
                total, has_aux=True))([ps[i] for i in trained])
    np.testing.assert_allclose(got[0].reshape(()), want, rtol=1e-5)
    np.testing.assert_allclose(got[1].reshape(-1), per, rtol=1e-4,
                               atol=1e-5)
    for i, g, w in zip(trained, got[2:], grads):
        w = np.asarray(w)
        err = np.linalg.norm(np.asarray(g).reshape(w.shape) - w) / max(
            np.linalg.norm(w), 1e-30)
        assert err < 2e-4, (i, params[i].name, err)


def test_the_parts_stated_float32_are_float32_in_a_bf16_step():
    """The configuration states float32 inside the norms, RoPE, the
    router's matmul and sigmoid, the loss and the convolution's gates and
    multiply-adds while weights and activations are bf16: the lowered step
    is held to it by its types (tests/test_mla_share.py says why the
    reference check cannot see one of them dropped)."""
    loss = _lfm2_toy(dtype="bfloat16")
    text = _olmoe_helper()._lowered(loss, 1, 64)

    def types(op, where=""):
        return [line.split(":")[-1].strip() for line in text.splitlines()
                if f"stablehlo.{op} " in line and where in line]

    # 5 layers x 2 norms, the attention layer's 2 and the final one
    assert len(types("rsqrt")) >= 13
    for op in ("rsqrt", "cosine", "sine", "log"):
        assert types(op) and all(t.endswith("xf32>") for t in types(op)), op
    routers = types("dot_general", "-> tensor<64x8x")
    assert routers and set(routers) == {
        "(tensor<64x64xf32>, tensor<64x8xf32>) -> tensor<64x8xf32>"}
    assert "tensor<64x8xf32>" in types("exponential")
    assert "tensor<64x97xf32>" in types("exponential")
    # the convolution: its thirds are widened before the gates, and no
    # multiply on [1, 64, 64] is left in bf16 (the projections are dots)
    assert types("multiply", "tensor<1x64x64xf32>")
    assert not types("multiply", "tensor<1x64x64xbf16>")
    assert not types("pad", "xbf16>")
    assert "xbf16>" in text


def test_decoder_lm_refuses_unknown_mixers_and_norm_forms():
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    for bad in ({"layer_types": ["conv"]},                 # 2 layers, 1 kind
                {"layer_types": ["conv", "fourier"]},
                {"qk_norm": "rows"}):
        with pytest.raises(ValueError, match="use "):
            tr.decoder_lm(tokens, 16, 8, 2, 2, max_len=8, **bad)


def test_decoder_lm_serving_still_refuses_every_block_but_gpt2s():
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    lm = tr.DecoderLM(16, 8, 2, 2, 8)
    lm.logits(tokens, n_kv_heads=1)
    assert lm._block == {"n_kv_heads": 1}
    with pytest.raises(NotImplementedError, match="n_kv_heads"):
        lm._decode_inputs(tokens)
    assert tr._GPT2_BLOCK["layer_types"] is None


# ---------------------------------------------------------------------------
# the towers that were there lower to the steps they lowered to

# sha256 of Executor._lowered(...).as_text() on the CPU under this suite's
# conftest (x64 on), computed by these same builders at `git archive
# aaa7b10`, the parent of PR 33 (CHANGES.md, PR 33, has them with x64 off
# too, where the first two are PR 31's).  GPT-2's is PR 36's, which moved
# that tower's head split from desc ops into the attention op's emitter on
# purpose (7723a900...04dc95 until then); OLMoE's is PR 38's, which put
# Q's and K's head split and turn into one op (`head_norm_rope`) on purpose
# (826a329c...70c32ba until then); Moonlight's stands.
PARENTS = {
    "gpt2": "ed922119d4ec49dd0c3f94be6df75340099a667662de0d6270d2933b074992eb",
    "olmoe": "5f5f6b496f58afc4eada129e5b397446423c1b89cd930b7b61c87f76210d14f0",
    "moonlight":
        "6dfbeb70dbad6033c1550246370189d715c90c8ee4197d48ce272d74f6f86969"}


@pytest.mark.parametrize("tower", sorted(PARENTS))
def test_lowered_steps_of_the_old_towers_are_the_parents(tower):
    """With one head count, no `layer_types` and `renorm_epsilon` unset the
    GPT-2, OLMoE and Moonlight toy towers lower byte for byte to the
    steps recorded above."""
    fluid.reset()
    if tower == "gpt2":
        loss = tr.build_lm_train_program(64, vocab_size=64, dim=32,
                                         n_layers=2, n_heads=4,
                                         dtype="bfloat16")
        batch, T = 2, 64
    elif tower == "olmoe":
        loss = tr.build_moe_lm_train_program(
            seq_len=64, vocab_size=64, dim=32, n_layers=2, n_heads=4,
            num_experts=8, expert_dim=16, top_k=2, dtype="bfloat16")
        batch, T = 2, 64
    else:
        loss = tr.build_mla_moe_lm_train_program(
            seq_len=32, vocab_size=97, dim=64, n_layers=3, n_heads=4,
            kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
            dense_dim=96, num_experts=16, expert_dim=32, top_k=3,
            shared_experts=2, held_experts=4, first_expert=4,
            buffer_rows=64, routed_scale=2.446, dtype="bfloat16",
            learning_rate=3e-3, init_scale=0.3, bias_init_scale=0.05)
        batch, T = 1, 32
    text = _olmoe_helper()._lowered(loss, batch, T)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS[tower]
