"""Xing4.0-29B-A4B's mechanisms at toy size on the CPU (PR 39): the
hyper-connection ops, latent attention with a query latent and YaRN, the
multi-token-prediction module and the summed loss, each against the plain
reference the benchmark's cell is held to (benchmarks/reference/
xing4-29b-a4b.py) on seeded weights in float32; mutants of the reference
that must each break a tolerance of the cell; and the towers that were
there stay what they were.  All seeded, none skipped on the CPU."""

import copy
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _by_labels as _series, _rand, _startup
from op_test import OpTestHarness
from paddle_tpu.models import transformer as tr
from paddle_tpu.ops import llm_ops
from paddle_tpu.ops import registry as reg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import harness  # noqa: E402

CONFIG = "xing4-29b-a4b"
T = 32
YARN = {"factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "type": "yarn"}
TOY = dict(seq_len=T, vocab_size=97, dim=64,
           layer_types=["full_attention"] * 4, n_heads=4, q_rank=24,
           kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, yarn=YARN,
           hc_streams=4, hc_sinkhorn_iters=20, dense_dim=96, num_experts=16,
           expert_dim=32, top_k=3, shared_experts=1, held_experts=4,
           first_expert=4, buffer_rows=64, routed_scale=2.0,
           hc_alpha_range=(0.1, 0.3), hc_beta_scale=1.0, dtype="float32",
           learning_rate=3e-3, init_scale=0.3, bias_init_scale=0.05,
           emb_init_scale=1.0)


def _toy_config() -> dict:
    """The cell's configuration with the toy's sizes under the published
    keys the reference reads."""
    c = copy.deepcopy(harness.load_json("configs", CONFIG))
    c.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_scaling=YARN, num_experts_per_tok=3,
             first_k_dense_replace=1)
    c["share"] = {"first_expert": 4, "buffer_rows": 64}
    return c


@pytest.fixture(scope="module")
def toy():
    """The toy program's first step on seeded weights, what the cell's
    driver fetches of it, and the reference's word on the same weights."""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    cfg = _toy_config()
    fluid.reset()
    loss = tr.build_hc_mla_moe_lm_train_program(**TOY)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    tok = np.random.RandomState(0).randint(0, 97, (1, T, 1)).astype(np.int64)
    feed = {"tokens": tok, "targets": np.roll(tok, -1, 1),
            "next_targets": np.roll(tok, -2, 1)}
    checked = {f"grad_{i}": params[i].name + "@GRAD"
               for i in ref.GRAD_PARAMS}
    checked.update(drv._check_vars(main, cfg["train"]["check_fetch"]))
    scope = fluid.global_scope()
    weights = [np.asarray(scope.find(p.name)) for p in params]
    want = {k: np.asarray(v, np.float32)
            for k, v in ref.train_check(weights, feed, cfg).items()}
    fetch = [loss] + list(checked.values())  # one step program to compile
    outs = exe.run(feed=feed, fetch_list=fetch)
    got = {"loss": float(np.asarray(outs[0]).reshape(()))}
    for k, g in zip(checked, outs[1:]):
        got[k] = np.asarray(g, np.float32).reshape(want[k].shape)
    ops = [op.type for op in main.global_block().ops]
    shapes = [tuple(p.shape) for p in params]
    losses = [got["loss"]] + [
        float(np.asarray(exe.run(feed=feed, fetch_list=fetch)[0]).reshape(
            ())) for _ in range(6)]
    return {"drv": drv, "ref": ref, "cfg": cfg, "weights": weights,
            "feed": feed, "got": got, "want": want, "ops": ops,
            "shapes": shapes, "losses": losses,
            "parts": [op.attrs.get("part") for op in
                      main.global_block().ops]}


def test_program_is_built_from_the_new_ops(toy):
    ops = toy["ops"]
    fwd = ops[:ops.index("generic_grad")]
    # 3 tower blocks + the module's: two hyper-connections a block
    assert fwd.count("hyper_connection_pre") == 8
    assert fwd.count("hyper_connection_post") == 8
    assert fwd.count("hyper_connection_sum") == 2
    assert fwd.count("expand") == fwd.count("unsqueeze") == 2
    assert fwd.count("latent_attention") == 4 and fwd.count("moe") == 3
    assert fwd.count("mtp_project") == 1
    assert fwd.count("lookup_table") == 2       # the tower's table, twice
    assert fwd.count("softmax_with_cross_entropy") == 2
    assert fwd.count("moe_sequence_balance_loss") == 3
    assert "elementwise_add" not in fwd[:fwd.index("mtp_project")]
    ref = toy["ref"]
    assert len(toy["shapes"]) == (1 + ref.PER_DENSE + 2 * ref.PER_EXPERT + 2
                                  + ref.PER_MODULE)
    # one table and one head, whoever reads them
    assert toy["shapes"].count((97, 64)) == 1
    assert toy["shapes"].count((64, 97)) == 1
    picked = [toy["shapes"][i] for i in ref.GRAD_PARAMS]
    assert picked == [(64, 24), (24, 96), (64, 24), (256, 16), (3,),
                      (4, 64, 32), (128, 64), (64,)]
    # the module's own head and loss carry both parts, the outer first
    parts = set(p for p in toy["parts"] if p)
    assert {"lm.head", "lm.loss", "mtp.project", "mtp.block",
            "mtp.head", "mtp.head/lm.head", "mtp.loss/lm.loss"} <= parts


def test_program_agrees_with_the_reference_in_float32(toy):
    """Losses per token (main and module), the last expert layer's routing,
    the first sub-layer's stream-mixing matrix and the eight gradients of
    the cell, on seeded weights: float32 against float32."""
    errors = toy["drv"].reference_errors(toy["got"], toy["want"],
                                         toy["ref"].CENTERED)
    assert set(errors) == set(toy["ref"].TOL)
    for key, err in errors.items():
        assert err <= (0.0 if key in ("routed_pairs", "dropped_pairs",
                                      "held_pairs", "expert_counts")
                       else 5e-5), (key, err)
    m = toy["got"]["h_res"]                               # [T, n, n]
    assert m.shape == (T, 4, 4) and (m > 0).all()
    # columns are the last thing normalised; rows are what 20 iterations
    # leave at this toy's spread (a of 0.1-0.3 on a projection of 4.8)
    np.testing.assert_allclose(m.sum(1), 1.0, atol=5e-6)
    np.testing.assert_allclose(m.sum(2), 1.0, atol=0.01)
    assert float(toy["got"]["routed_pairs"][0]) == T * 3
    assert float(toy["got"]["dropped_pairs"][0]) == 0.0


def test_toy_trains(toy):
    losses = toy["losses"]
    assert all(np.isfinite(losses)) and losses[-1] < 0.5 * losses[0]


@pytest.mark.parametrize("mutant,keys", [
    ("one_iteration", ("h_res", "grad_38", "token_loss")),
    ("no_dynamic", ("h_res", "grad_38", "grad_39", "token_loss")),
    ("no_columns", ("h_res", "grad_38", "token_loss")),
    ("post_without_2", ("token_loss", "mtp_token_loss", "grad_43")),
    ("yarn_off", ("grad_7", "grad_9", "grad_10", "token_loss")),
    ("plain_scale", ("grad_7", "grad_9", "grad_10", "token_loss")),
    ("no_qnorm", ("grad_7", "grad_9", "token_loss")),
    ("mtp_weight_0", ("loss", "grad_-29")),
    ("mtp_shift_1", ("mtp_token_loss", "grad_-29")),
    ("bf16_sinkhorn", ("h_res",))])
def test_mutants_of_the_reference_break_the_cells_tolerances(toy, mutant,
                                                             keys):
    """The program against the reference with one departure: each named
    key reads past the CELL's tolerance (TOL of the reference file, set
    from the chip's readings), so a program that computed the mutant
    would be refused there too."""
    import jax

    ref = toy["ref"]
    assert mutant in ref.MUTANTS
    # `ref._check` with the mutant, asked for the named keys alone: XLA
    # drops what the others would need (a whole reference is 7 s to compile)
    tokens, targets, ahead = (toy["feed"][k][..., 0] for k in (
        "tokens", "targets", "next_targets"))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda ps: {k: v for k, v in ref.check_fn(
            ps, tokens, targets, ahead, toy["cfg"], mutant).items()
            if k in keys})(toy["weights"])
    want = {k: np.asarray(v, np.float32) for k, v in want.items()}
    errors = toy["drv"].reference_errors(toy["got"], want, ref.CENTERED)
    for key in keys:
        assert errors[key] > ref.TOL[key], (mutant, key, errors[key])


# ---------------------------------------------------------------------------
# the ops alone


def test_hyper_connection_ops_in_plain_numpy():
    """pre and post against the equations written out a token at a time in
    numpy float64; the layouts (streams [B, n, T, C], the gates a column a
    token) and bf16 streams entering the projection exactly."""
    import jax.numpy as jnp

    n, C, B, Tn = 3, 8, 2, 5
    x = _rand((B, n, Tn, C), 1)
    phis = [_rand((n * C, k), s, 0.3) for s, k in ((2, n), (3, n),
                                                   (4, n * n))]
    alpha, beta = _rand((3,), 5), _rand(((2 + n) * n,), 6)
    y = _rand((B, Tn, C), 7)
    ctx = reg.EmitContext(None, is_test=False)
    attrs = {"streams": n, "sinkhorn_iters": 20, "epsilon": 1e-6,
             "norm_epsilon": 1e-6, "clamp_min": -30.0, "clamp_max": 30.0}
    ins = {"X": [jnp.asarray(x)], "PhiPre": [jnp.asarray(phis[0])],
           "PhiPost": [jnp.asarray(phis[1])],
           "PhiRes": [jnp.asarray(phis[2])], "Alpha": [jnp.asarray(alpha)],
           "Beta": [jnp.asarray(beta)]}
    pre = llm_ops.hyper_connection_pre(ctx, ins, attrs)
    u, h_post, h_res = (np.asarray(pre[k][0]) for k in ("U", "HPost",
                                                        "HRes"))
    assert u.shape == (B, Tn, C) and h_post.shape == (B, Tn, n)
    assert h_res.shape == (B, Tn, n, n)
    out = np.asarray(llm_ops.hyper_connection_post(
        ctx, {"X": [jnp.asarray(x)], "Y": [jnp.asarray(y)],
              "HPost": pre["HPost"], "HRes": pre["HRes"]}, {})["Out"][0])
    assert out.shape == x.shape
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))
    for b in range(B):
        for t in range(Tn):
            v = x[b, :, t].reshape(-1).astype(np.float64)
            xbar = v / np.sqrt(np.mean(v * v) + 1e-6)
            hp = sig(alpha[0] * xbar @ phis[0] + beta[:n])
            ho = 2 * sig(alpha[1] * xbar @ phis[1] + beta[n:2 * n])
            m = np.exp(np.clip(alpha[2] * xbar @ phis[2] + beta[2 * n:],
                               -30, 30)).reshape(n, n)
            for _ in range(20):
                m = m / (m.sum(1, keepdims=True) + 1e-6)
                m = m / (m.sum(0, keepdims=True) + 1e-6)
            streams = v.reshape(n, C)
            np.testing.assert_allclose(u[b, t], hp @ streams, rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(h_post[b, t], ho, rtol=2e-5)
            np.testing.assert_allclose(h_res[b, t], m, rtol=2e-5)
            np.testing.assert_allclose(
                out[b, :, t],
                m @ streams + ho[:, None] * y[b, t][None], rtol=2e-5,
                atol=2e-6)
    total = np.asarray(llm_ops.hyper_connection_sum(
        ctx, {"X": [jnp.asarray(x)]}, {})["Out"][0])
    np.testing.assert_allclose(total, x.sum(1), rtol=1e-5, atol=1e-6)
    # bf16 streams and Phi: the gates are those of the SAME values in f32
    lo = lambda a: jnp.asarray(a).astype(jnp.bfloat16)
    low = dict(ins, X=[lo(x)], **{k: [lo(ins[k][0])] for k in (
        "PhiPre", "PhiPost", "PhiRes", "Alpha", "Beta")})
    wide = {k: [v[0].astype(jnp.float32)] for k, v in low.items()}
    a, b = (llm_ops.hyper_connection_pre(ctx, d, attrs) for d in (low, wide))
    assert a["HRes"][0].dtype == jnp.float32 and a["U"][0].dtype == jnp.bfloat16
    np.testing.assert_allclose(a["HRes"][0], b["HRes"][0], rtol=1e-5)
    np.testing.assert_allclose(a["HPost"][0], b["HPost"][0], rtol=1e-5)


def _hc_inputs(n=2, C=4, B=1, Tn=3):
    x = _rand((B, n, Tn, C), 11).astype("float64")
    ins = {"X": x, "PhiPre": _rand((n * C, n), 12, 0.5).astype("float64"),
           "PhiPost": _rand((n * C, n), 13, 0.5).astype("float64"),
           "PhiRes": _rand((n * C, n * n), 14, 0.5).astype("float64"),
           "Alpha": np.asarray([0.7, -0.9, 1.1]),
           "Beta": _rand(((2 + n) * n,), 15, 0.5).astype("float64")}
    attrs = {"streams": n, "sinkhorn_iters": 5, "epsilon": 1e-6,
             "norm_epsilon": 1e-6, "clamp_min": -30.0, "clamp_max": 30.0}
    return ins, attrs


@pytest.mark.parametrize("slot", ["U", "HPost"])
def test_hyper_connection_pre_grad_is_the_numeric_one(slot):
    """The backward that is written out (the streams' gradient stream by
    stream, dPhi, the norm's term) against central differences in float64,
    through the read (U) and through a gate (HPost); the mixing matrix's
    mean is a constant (its columns sum to one), so its path is held by the
    reference (grad_38, grad_39 above)."""
    ins, attrs = _hc_inputs()
    h = OpTestHarness("hyper_connection_pre", ins, attrs,
                      out_slots=["U", "HPost", "HRes", "Proj", "Inv"])
    h.check_grad(["X", "PhiPre", "PhiPost", "Alpha", "Beta"],
                 output_slot=slot, max_relative_error=1e-2)


def test_hyper_connection_post_and_sum_grads_are_the_numeric_ones():
    n, C, B, Tn = 2, 4, 1, 3
    ins = {"X": _rand((B, n, Tn, C), 21).astype("float64"),
           "Y": _rand((B, Tn, C), 22).astype("float64"),
           "HPost": _rand((B, Tn, n), 23).astype("float64"),
           "HRes": _rand((B, Tn, n, n), 24).astype("float64")}
    OpTestHarness("hyper_connection_post", ins, {}).check_grad(
        ["X", "Y", "HPost", "HRes"], max_relative_error=1e-2)
    OpTestHarness("hyper_connection_sum", {"X": ins["X"]}, {}).check_grad(
        ["X"], max_relative_error=1e-2)


def _hc_sublayer_step(x, y_gain, params, attrs):
    """A program of one sub-layer around the two ops: U = pre(X), Y = U *
    gain, Out = post(X, Y), loss = mean(Out^2)-like; X and the five
    parameters trainable -> (op types, Out, the six gradients)."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    names = {"X": "x", **{k: k.lower() for k in params}}
    for slot, arr in dict(params, X=x).items():
        block.create_parameter(name=names[slot], shape=arr.shape,
                               dtype="float32")
    xv = block.var("x")
    gain = block.create_var(name="gain", shape=y_gain.shape,
                            dtype="float32", stop_gradient=True)
    B, n, Tn, C = x.shape
    outs = {"U": (B, Tn, C), "HPost": (B, Tn, n), "HRes": (B, Tn, n, n),
            "Proj": ((2 + n) * n, B, Tn), "Inv": (B, Tn)}
    made = {k: block.create_var(name=k.lower() + "_out", shape=sh,
                                dtype="float32",
                                stop_gradient=k in ("Proj", "Inv"))
            for k, sh in outs.items()}
    block.append_op("hyper_connection_pre",
                    inputs={k: [v] for k, v in names.items()},
                    outputs={k: [v.name] for k, v in made.items()},
                    attrs=dict(attrs, part="blk.one"))
    y = fluid.layers.elementwise_mul(made["U"], gain)
    new = fluid.layers.hyper_connection_post(xv, y, made["HPost"],
                                             made["HRes"])
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(new, new))
    grads = dict((p.name, g.name) for p, g in fluid.append_backward(loss))
    scope = fluid.global_scope()
    for slot, arr in dict(params, X=x).items():
        scope.set(names[slot], arr)
    scope.set("gain", y_gain)
    exe = fluid.Executor(fluid.CPUPlace())
    got = exe.run(feed={}, fetch_list=[new] + [grads[v]
                                              for v in names.values()])
    return ([op.type for op in block.ops], block.ops,
            [np.asarray(a) for a in got])


def _hc_step_operands(n=4, C=128, B=2, Tn=32):
    x = _rand((B, n, Tn, C), 41)
    params = {"PhiPre": _rand((n * C, n), 42, 0.05),
              "PhiPost": _rand((n * C, n), 43, 0.05),
              "PhiRes": _rand((n * C, n * n), 44, 0.05),
              "Alpha": np.asarray([0.3, 0.4, 0.5], np.float32),
              "Beta": _rand(((2 + n) * n,), 45)}
    attrs = {"streams": n, "sinkhorn_iters": 20, "epsilon": 1e-6,
             "norm_epsilon": 1e-6, "clamp_min": -30.0, "clamp_max": 30.0}
    return x, 1.0 + 0.3 * _rand((B, Tn, C), 46), params, attrs


def _kernels_on(monkeypatch, launched):
    """The trace believes in one TPU; the kernels run in interpret mode."""
    from paddle_tpu.ops.pallas_kernels import hyper_connection as K

    def call(kernel, x, norm_eps=0.0, **_):
        launched.append(kernel)
        return K._calls(*x.shape, str(x.dtype), norm_eps, True,
                        K.TOKEN_TILE)[kernel]

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(K, "_call", call)


def test_hyper_connection_grads_are_desc_ops_of_their_own():
    """append_backward gives each of the two ops ONE grad desc of its own
    type (the forward's inputs, the kept Proj and Inv, the cotangents; the
    forward's attrs, its part with them) and no `generic_grad`: nothing
    emits the forward a second time."""
    x, gain, params, attrs = _hc_step_operands()
    types, ops, _ = _hc_sublayer_step(x, gain, params, attrs)
    assert types.count("hyper_connection_pre_grad") == 1
    assert types.count("hyper_connection_post_grad") == 1
    assert not [op for op in ops if op.type == "generic_grad"
                and op.attrs["__fwd_type__"].startswith("hyper_connection")]
    (pre,) = [op for op in ops if op.type == "hyper_connection_pre_grad"]
    (post,) = [op for op in ops if op.type == "hyper_connection_post_grad"]
    assert sorted(pre.inputs) == sorted(
        ["X", "PhiPre", "PhiPost", "PhiRes", "Alpha", "Beta", "Proj", "Inv",
         "U@GRAD", "HPost@GRAD", "HRes@GRAD"])
    assert sorted(pre.outputs) == sorted(
        k + "@GRAD" for k in ("X", "PhiPre", "PhiPost", "PhiRes", "Alpha",
                              "Beta"))
    assert pre.attrs["part"] == "blk.one" and pre.attrs["streams"] == 4
    assert sorted(post.inputs) == ["HPost", "HRes", "Out@GRAD", "X", "Y"]
    assert sorted(post.outputs) == ["HPost@GRAD", "HRes@GRAD", "X@GRAD",
                                    "Y@GRAD"]
    for name in ("hyper_connection_pre_grad", "hyper_connection_post_grad"):
        assert reg.get_op_info(name).grad is None


def test_hyper_connection_ops_take_the_kernels_on_a_tpu(monkeypatch):
    """Where the trace targets one TPU each of the four emitters launches
    its kernels ONCE (the forward's never again in the backward), the
    numbers are the plain emission's, the counter names the path and
    `executor_grad_kernel_forward_total` gets no series; the switch sends
    all four the plain way, to the bit."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops.pallas_kernels import hyper_connection as K

    x, gain, params, attrs = _hc_step_operands()
    obs.REGISTRY.reset()
    _, _, want = _hc_sublayer_step(x, gain, params, attrs)
    four = ("pre", "post", "pre_grad", "post_grad")
    path = lambda p: {tuple(sorted({"op": o, "path": p}.items())): 1.0  # noqa
                      for o in four}
    assert _series("hyper_connection_kernels_traced_total") == path("xla")
    launched = []
    _kernels_on(monkeypatch, launched)
    obs.REGISTRY.reset()
    _, _, got = _hc_sublayer_step(x, gain, params, attrs)
    assert launched == [K.PRE_FWD, K.POST_FWD, K.POST_BWD, K.PRE_BWD_A,
                        K.PRE_BWD_B]
    assert _series("hyper_connection_kernels_traced_total") == path(
        "pallas")
    assert _series("executor_grad_kernel_forward_total") == {}
    assert _series("hyper_connection_layers_traced_total") == {
        (("dim", "128"), ("sinkhorn_iters", "20"), ("streams", "4")): 1.0}
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * np.abs(b).max())
    del launched[:]
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    _, _, again = _hc_sublayer_step(x, gain, params, attrs)
    assert launched == []
    for a, b in zip(again, want):
        assert a.tobytes() == b.tobytes()


def test_toy_step_is_the_same_under_both_paths(monkeypatch):
    """`build_hc_mla_moe_lm_train_program` at a width the kernels take:
    the first step's loss and the gradients of every hyper-connection
    parameter with the kernels (interpret mode) against the plain
    emission's, and the two sub-layers of each block (the module's is the
    last of `layer_types`) launch the five kernels once each."""
    from paddle_tpu.ops.pallas_kernels import hyper_connection as K

    toy = dict(TOY, dim=128, layer_types=["full_attention"] * 2)
    tok = np.random.RandomState(3).randint(0, 97, (1, T, 1)).astype(np.int64)
    feed = {"tokens": tok, "targets": np.roll(tok, -1, 1),
            "next_targets": np.roll(tok, -2, 1)}

    drawn = {}
    def step():
        fluid.reset()
        loss = tr.build_hc_mla_moe_lm_train_program(**toy)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = 11
        exe = fluid.Executor(fluid.CPUPlace())
        _startup(exe, drawn)
        hc = [p.name + "@GRAD" for p in main.global_block().all_parameters()
              if p.name.startswith("hyper_connection")]
        return [np.asarray(a) for a in exe.run(feed=feed,
                                               fetch_list=[loss] + hc)]

    want = step()
    launched = []
    _kernels_on(monkeypatch, launched)
    got = step()
    sublayers = 2 * len(toy["layer_types"])
    assert len(want) == 1 + 5 * sublayers
    assert sorted(launched) == sorted(list(K.BLOCKS) * sublayers)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    # against the largest gradient of its kind: the first sub-layer reads
    # four equal streams, and some of its gradients are rounding alone
    for kind in range(5):
        scale = max(np.abs(b).max() for b in want[1 + kind::5])
        for a, b in zip(got[1 + kind::5], want[1 + kind::5]):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4 * scale)


def test_mtp_project_output_and_grad():
    B, Tn, D = 1, 3, 4
    h, e = (_rand((B, Tn, D), s).astype("float64") for s in (31, 32))
    gh, ge = (1.0 + _rand((D,), s, 0.2).astype("float64") for s in (33, 34))
    w = _rand((2 * D, D), 35, 0.5).astype("float64")
    t = OpTestHarness("mtp_project", {"H": h, "E": e, "HNorm": gh,
                                      "ENorm": ge, "W": w},
                      {"epsilon": 1e-6, "depth": 1})
    norm = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True)  # noqa
                                    + 1e-6) * g
    t.check_output({"Out": np.concatenate([norm(h, gh), norm(e, ge)], -1)
                    @ w}, atol=1e-5)
    t.check_grad(["H", "E", "HNorm", "ENorm", "W"], max_relative_error=1e-2)


def test_yarn_frequencies_and_scale_are_deepseeks():
    """The published rope_scaling: 32 frequencies, the first 11 plain, from
    index 23 on divided by 64, a linear ramp between; mscale 1 both ways so
    cos and sin unscaled; softmax scale (0.1 ln 64 + 1)^2 / sqrt(192)."""
    import math

    f = llm_ops.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert f.shape == (32,) and f.dtype == np.float32
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(32) - 10) / 13.0
    np.testing.assert_allclose(
        f[11:23], (plain / 64 * ramp + plain * (1 - ramp))[11:23], rtol=1e-6)
    assert llm_ops.yarn_mscale(64, 1) == pytest.approx(
        0.1 * math.log(64) + 1)
    assert llm_ops.yarn_mscale(1, 1) == 1.0
    ref = harness.load_module("reference", CONFIG)
    cfg = harness.load_json("configs", CONFIG)
    mine, turn, scale = ref.yarn_frequencies(cfg)
    np.testing.assert_allclose(mine, f, rtol=1e-6)
    assert turn == 1.0
    assert scale == pytest.approx((0.1 * math.log(64) + 1) ** 2
                                  / math.sqrt(192))
    assert scale == pytest.approx(0.14468, rel=1e-4)


def test_latent_attention_without_a_query_latent_is_moonlights_op():
    """No `q_rank`, no `yarn`: the op desc is the one Moonlight's tower
    built before PR 39 (slots, attributes, five parameters), and with
    them seven parameters and the six YaRN attributes."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[16, 32], dtype="float32")
    fluid.layers.latent_attention(x, 2, kv_rank=8, qk_nope_dim=8,
                                  qk_rope_dim=4, v_dim=8)
    op = fluid.default_main_program().global_block().ops[-1]
    assert list(op.inputs) == ["X", "WQ", "WKVA", "KVNorm", "WKVB", "WO"]
    assert sorted(k for k in op.attrs if not k.startswith("__")) == [
        "epsilon", "num_heads", "qk_nope_dim", "qk_rope_dim", "theta",
        "v_dim"]
    fluid.layers.latent_attention(x, 2, kv_rank=8, qk_nope_dim=8,
                                  qk_rope_dim=4, v_dim=8, q_rank=6,
                                  yarn=YARN)
    op = fluid.default_main_program().global_block().ops[-1]
    assert list(op.inputs) == ["X", "WQA", "QNorm", "WQB", "WKVA", "KVNorm",
                               "WKVB", "WO"]
    assert op.attrs["yarn_factor"] == 64.0
    assert op.attrs["yarn_original_max"] == 16
    params = fluid.default_main_program().global_block().all_parameters()
    assert [tuple(p.shape) for p in params[5:]] == [
        (32, 6), (6,), (6, 24), (32, 12), (8,), (8, 32), (16, 32)]


def test_default_residual_form_has_no_new_op():
    """Without `hyper` and `mtp` the tower is the one it was: no op of this
    PR in Moonlight's toy program (tests/test_lfm2.py holds its lowered
    step to the parent's hash, untouched)."""
    fluid.reset()
    tr.build_mla_moe_lm_train_program(
        seq_len=32, vocab_size=97, dim=64, n_layers=3, n_heads=4,
        kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, dense_dim=96,
        num_experts=16, expert_dim=32, top_k=3, shared_experts=2,
        held_experts=4, first_expert=4, buffer_rows=64, routed_scale=2.446,
        dtype="float32")
    main = fluid.default_main_program()
    ops = [op.type for op in main.global_block().ops]
    assert not [t for t in ops if t.startswith(("hyper_connection", "mtp_"))]
    assert "expand" not in ops
    assert not any("/" in (op.attrs.get("part") or "")
                   for op in main.global_block().ops)
    names = [p.name for p in main.global_block().all_parameters()]
    assert not [n for n in names if n.startswith("decoder_lm.")]


def test_part_guards_nest_and_an_ops_own_part_stands():
    from paddle_tpu.observability import attribution

    fluid.reset()
    prog = fluid.default_main_program()
    x = fluid.layers.data("x", shape=[4], dtype="float32")
    with prog.part_guard("mtp.head"):
        a = fluid.layers.scale(x, 2.0)
        with prog.part_guard("lm.head"):
            b = fluid.layers.scale(a, 2.0)
        c = fluid.layers.scale(b, 2.0)
    d = fluid.layers.scale(c, 2.0)
    ops = prog.global_block().ops[-4:]
    assert [op.attrs.get("part") for op in ops] == [
        "mtp.head", "mtp.head/lm.head", "mtp.head", None]
    import jax

    def scopes(op):
        def f(v):
            with attribution.op_scope(op):
                return v + 1

        return jax.jit(f).lower(1.0).as_text(debug_info=True)

    assert "pdtpu.mtp.head/pdtpu.lm.head" in scopes(ops[1])
    assert "/pdtpu.mtp.head" in scopes(ops[0])
    assert "pdtpu.lm.head" not in scopes(ops[0])
    assert "pdtpu." not in scopes(ops[3])
    assert d is not None
