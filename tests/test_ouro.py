"""The looped tower (PR 71): Ouro's eight sandwich-normed blocks read four
times through ONE set of parameters, an exit gate a pass and the
expected-exit objective, against its plain reference on the CPU: a toy of
the cell's depth built by the configuration's own (generic) builder and run
by `fluid.Executor` with Adam under `layers.recompute` THROUGH THE CELL'S
DRIVER, in float32 and in bf16; every departure the reference file knows,
one at a time, through ONE compiled function; what `decoder_lm(loop=)` and
`LayerHelper`'s shared parameters build and refuse; and `append_backward`'s
sum of the gradient parts of a parameter that is read N times.
tests/benchmarks/test_ouro_cell.py holds the cell's files.
"""

from __future__ import annotations

import copy
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "ouro-2.6b"
TRAFFIC = "train_staged_bs1_16k"
T, VOCAB = 32, 96

MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "fp8": "grad_2",
    "three_passes": "exit_probs",
    "no_norm_between_passes": "token_loss",
    "no_result_norms": "token_loss",
    "last_pass_grad_only": "grad_2",
    "no_entropy": "grad_91",
    "last_gate_times_survival": "exit_probs",
    "gate_before_norm": "exit_probs",
    "no_rope": "grad_2",
    "gain_is_one": "token_loss",
}


def toy_config(dtype="float32"):
    """Hidden 32, MLP 48, 4 heads of 8, vocabulary 96, 32 tokens; the
    cell's 8 blocks read 4 times, every block application a segment and
    each pass's head and loss one."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=32, intermediate_size=48, num_attention_heads=4,
               num_key_value_heads=4, head_dim=8, vocab_size=VOCAB)
    cfg["train"]["args"].update(
        seq_len=T, vocab_size=VOCAB, dim=32, n_heads=4, n_kv_heads=4,
        head_dim=8, dense_dim=48, dtype=dtype, init_scale=0.1,
        learning_rate=0.003)
    cfg["train"]["feeds"]["tokens"].update(shape=[T, 1], high=VOCAB)
    return cfg


def _drive(cfg, tmp, seed=2 ** 31 + 71):
    import paddle_tpu as fluid

    traffic = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    traffic.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
                   trace_seconds=0.2)
    ctx = harness.Context(
        cell={"name": "toy"}, config=cfg, traffic=traffic, seed=seed,
        # a window of ONE step (the steps up to `loss_fell_step` follow): at
        # this learning rate thirty steps teach the gate to leave after the
        # first pass, and a mutant of the LAST pass's weight then moves little
        seconds=0.001, trace=False, t_start=time.monotonic(),
        place_of=lambda i: fluid.CPUPlace(), trace_dir=str(tmp / "trace"))
    return harness.load_module("drivers", "train_executor").run(ctx)


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

    cfg = toy_config()
    obs.REGISTRY.reset()
    rec = _drive(cfg, tmp_path_factory.mktemp("toy"))
    fam = obs.REGISTRY.snapshot()["families"]
    series = {name: {tuple(sorted(s["labels"].items())): s["value"]
                     for s in fam[name]["series"]}
              for name in ("backward_grad_parts_total",
                           "decoder_lm_loop_passes_total")}
    scope = fluid.global_scope()
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    params = [np.asarray(scope.find(p.name), np.float32)
              for p in main.global_block().all_parameters()]
    return cfg, rec, params, main, startup, series, scope


def test_driver_toy_ouro_float32_matches_the_reference(toy_run):
    """The objective, every pass's every token's loss, the exit distribution
    and every GRAD_PARAMS gradient (layer 0's eleven, each the sum of four
    passes' parts; the last layer's Wq, Wup and last gain; the embedding,
    the final gain, the head and the gate's vector) against the plain
    reference on the same seeded weights; the run is `correct`; the program
    is 32 block segments and four head segments over ONE set of 93
    parameters."""
    ref = harness.load_module("reference", CONFIG)
    cfg, rec, params, main = toy_run[:4]
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "exit_probs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert max(errs.values()) < 1e-4, errs
    assert rec["correct"] and rec["checks"]["loss_fell"]
    kinds = [op.type for b in main.blocks for op in b.ops]
    assert (kinds.count("recompute"),
            kinds.count("scaled_dot_product_attention"),
            kinds.count("softmax_with_cross_entropy")) == (36, 32, 4)
    shapes = [p.shape for p in params]
    assert len(shapes) == 93 == 1 + ref.PER_LAYER * 8 + 4
    # the indices GRAD_PARAMS names are what its comment says they are
    for at in (1, 78):
        assert shapes[at:at + 11] == [
            (32,), (32, 32), (32, 32), (32, 32), (32, 32), (32,), (32,),
            (32, 48), (32, 48), (48, 32), (32,)]
    assert shapes[0] == (VOCAB, 32) and shapes[89:] == [
        (32,), (32, VOCAB), (32, 1), (1,)]


def test_looped_tower_holds_one_pass_of_parameters(toy_run, tmp_path):
    """Main program, startup program and `io.save_params`' list each hold
    ONE copy of every shared parameter: 93 names, one init op each, and
    the second pass's ops read the first's by name."""
    import paddle_tpu as fluid

    main, startup, scope = toy_run[3], toy_run[4], toy_run[6]
    names = [p.name for p in main.global_block().all_parameters()]
    assert len(names) == len(set(names)) == 93
    made = [n for op in startup.global_block().ops
            for n in op.output_names() if n in set(names)]
    assert sorted(made) == sorted(names)
    fluid.io.save_params(fluid.Executor(fluid.CPUPlace()), str(tmp_path),
                         main_program=main, scope=scope)
    assert sorted(os.listdir(tmp_path)) == sorted(n + ".npy" for n in names)
    wq = names[2]
    readers = [op for b in main.blocks for op in b.ops
               if op.type == "mul" and wq in op.input_names()]
    assert len(readers) == 4
    assert {op.attrs["part"].split("/")[0] for op in readers} == {
        "loop.a", "loop.b", "loop.c", "loop.d"}


def test_counters_say_what_the_looped_program_does(toy_run):
    """`backward_grad_parts_total`: the 88 block parameters, the final
    gain and the head are finalized from 4 parts, the gate's vector and
    bias from 3 (the last pass's gate weighs nothing in the objective), the
    embedding from 1; `decoder_lm_loop_passes_total` 4."""
    series = toy_run[5]
    assert series["backward_grad_parts_total"] == {
        (("parts", "1"),): 1.0, (("parts", "3"),): 2.0,
        (("parts", "4"),): 90.0}
    assert series["decoder_lm_loop_passes_total"] == {(): 4.0}


def _every_gradient_error(dtype):
    """The toy tower at two blocks read four times (so that the CPU's bf16
    takes seconds) against the reference: the objective, the check's
    fetches and EVERY parameter's gradient, the gate's bias included."""
    import jax

    import paddle_tpu as fluid

    ref = harness.load_module("reference", CONFIG)
    cfg = toy_config(dtype)
    cfg["num_hidden_layers"] = cfg["train"]["args"]["n_layers"] = 2
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 71
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    drv = harness.load_module("drivers", "train_executor")
    checked = drv._check_vars(main, cfg["train"]["check_fetch"])
    tok = np.random.RandomState(0).randint(0, VOCAB, (1, T, 1)).astype(
        "int64")
    feed = {"tokens": tok, "targets": np.roll(tok, -1, axis=1)}
    scope = fluid.global_scope()
    every = tuple(range(len(params)))
    with jax.default_matmul_precision("highest"):
        want = ref.check_fn([scope.find(p.name) for p in params],
                            tok[..., 0], feed["targets"][..., 0], cfg,
                            grad_params=every)
    outs = exe.run(feed=feed, fetch_list=[loss] + list(checked.values()) + [
        p.name + "@GRAD" for p in params])
    want = {k: np.asarray(v, np.float32) for k, v in want.items()}
    names = ["loss"] + list(checked) + [f"grad_{i}" for i in every]
    got = {k: np.asarray(v, np.float32).reshape(want[k].shape)
           for k, v in zip(names, outs)}
    return drv.reference_errors(got, want, ref.CENTERED), every


def test_toy_ouro_bf16_stays_inside_the_cells_tolerances():
    """The cell's precision at toy size: bf16 weights, activations and
    gradient parts (added in bf16, four to a shared parameter) stay within a
    few percent of the float32 reference, EVERY gradient."""
    errs, every = _every_gradient_error("bfloat16")
    # about three times what this seed reads (32 tokens and 32 columns
    # average less than the cell's 4096 and 2048, so the cell's own
    # tolerances, set from the chip's readings, do not apply): loss 1.9e-4,
    # token_loss 0.012, exit_probs 0.004, the gradients 0.010 .. 0.038, the
    # gate's bias (ONE number: three rounded parts over a sum that can
    # cancel, which is why the cell does not compare it) 0.087
    limits = {"loss": 1e-3, "token_loss": 0.04, "exit_probs": 0.015,
              f"grad_{every[-1]}": 0.3}
    assert all(errs[k] <= limits.get(k, 0.1) for k in errs), errs
    assert min(errs[f"grad_{i}"] for i in every) > 1e-4   # bf16 it is


def test_toy_ouro_float32_every_gradient_the_gates_bias_included():
    """In float32 EVERY parameter's gradient is the reference's, the gate's
    bias too: the one gradient `GRAD_PARAMS` leaves out at the cell's size
    (one number, whose relative error in bf16 has no limit) is held
    here."""
    ref = harness.load_module("reference", CONFIG)
    errs, every = _every_gradient_error("float32")
    assert 92 not in ref.GRAD_PARAMS and len(every) == 27   # b_g: the last
    assert max(errs.values()) < 1e-4, errs


@pytest.fixture(scope="module")
def departures(toy_run):
    """{mutant: {key: the distance it moves the reference's own check}} for
    every mutant of the reference file, through ONE compiled function (the
    mutant is a traced one-hot)."""
    import jax

    ref = harness.load_module("reference", CONFIG)
    cfg, _, params = toy_run[:3]
    tokens = np.random.RandomState(3).randint(0, VOCAB, (1, T))
    targets = np.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        check = jax.jit(lambda ps, flags: ref.check_fn(
            ps, tokens, targets, cfg, flags))
        none = np.zeros(len(ref.MUTANTS), bool)
        drv = harness.load_module("drivers", "train_executor")
        want = {k: np.asarray(v) for k, v in check(params, none).items()}
        out = {}
        for i, name in enumerate(ref.MUTANTS):
            flags = none.copy()
            flags[i] = True
            out[name] = drv.reference_errors(
                {k: np.asarray(v) for k, v in check(params, flags).items()},
                want, ref.CENTERED)
    return out


def test_mutants_listed_here_are_the_reference_files():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS) and ref.MUTANTS[0] == "fp8"
    with pytest.raises(ValueError, match="one of"):
        ref.control_check([], {}, {}, control="no_such_control")


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_every_mutant_of_the_reference_fails_its_key(toy_run, departures,
                                                     mutant):
    """Three passes; the final norm left out between passes; the result
    norms left out; a block's gradient from the last pass alone; the
    entropy term left out; p_4 = lambda_4 x survival; the gate read before
    the final norm; no rotary turn; the gains taken as one; fp8 products:
    each moves the key named for it by over 100 times what the program
    itself reads there."""
    own = toy_run[1]["checks"]["reference_errors"]
    moved, key = departures[mutant], MUTANTS[mutant]
    assert moved[key] > 100 * max(own[key], 1e-7), (mutant, moved)
    assert moved[key] > 5e-3, (mutant, moved)


def test_mutants_touch_what_they_name(departures):
    """A departure of the objective alone leaves every pass's token losses
    where they were; a block's gradient from the last pass alone leaves the
    whole forward and the head's gradient where they were and moves a
    block's."""
    for name in ("no_entropy", "last_gate_times_survival"):
        assert departures[name]["token_loss"] == 0.0, name
    assert departures["no_entropy"]["exit_probs"] == 0.0
    last = departures["last_pass_grad_only"]
    assert last["loss"] == last["token_loss"] == last["exit_probs"] == 0.0
    assert last["grad_90"] == 0.0 and last["grad_2"] > 0.1


# ---------------------------------------------------------------------------
# what decoder_lm(loop=, sandwich=) builds


def _tower(loop=None, sandwich=False, remat=False, n_layers=2, **kw):
    """-> (loss, main program) of a float32 toy tower with Adam."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T_

    fluid.reset()
    loss = T_.build_decoder_lm_train_program(
        seq_len=16, learning_rate=1e-3, vocab_size=64, dim=32,
        n_layers=n_layers, n_heads=4, dense_dim=48, norm="rms_norm",
        norm_epsilon=1e-6, positions="rope", rope_theta=1e4,
        ffn="gated_mlp", sandwich=sandwich, remat=remat, dtype="float32",
        init_scale=0.1, **({"loop": loop} if loop is not None else {}),
        **kw)
    return loss, fluid.default_main_program()


def _losses(loss, steps=3, seed=11):
    import paddle_tpu as fluid

    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = seed
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    tok = np.random.RandomState(5).randint(0, 64, (2, 16, 1)).astype("int64")
    feed = {"tokens": tok, "targets": np.roll(tok, -1, axis=1)}
    return [float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0])
                  .reshape(())) for _ in range(steps)]


def test_one_pass_without_a_gate_is_the_unlooped_program_to_the_bit():
    """`loop={"passes": 1}` builds the unlooped tower op for op (the ops'
    `part` aside): the same parameters under the same names, and three
    steps' losses equal to the bit."""
    loss, main = _tower(sandwich=True)
    plain = _losses(loss)
    plain_ops = [op.type for b in main.blocks for op in b.ops]
    plain_names = [p.name for p in main.global_block().all_parameters()]
    loss, main = _tower(loop={"passes": 1}, sandwich=True)
    assert [op.type for b in main.blocks for op in b.ops] == plain_ops
    assert [p.name for p in main.global_block().all_parameters()
            ] == plain_names
    assert _losses(loss) == plain


def test_remat_changes_no_number_of_the_looped_tower():
    """Every block application a segment and each pass's head and loss one:
    the same three losses as the program that keeps everything (float32 on
    the CPU, where the replay's fusions may round the last bit)."""
    loop = {"passes": 3, "exit_gate": True}
    kept = _losses(_tower(loop=loop, sandwich=True)[0])
    loss, main = _tower(loop=loop, sandwich=True, remat=True)
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("recompute") == 3 * 2 + 3
    assert loop == {"passes": 3, "exit_gate": True}   # the caller's dict
    np.testing.assert_allclose(_losses(loss), kept, rtol=2e-6)
    assert kept[-1] < kept[0]


@pytest.mark.parametrize("name, extra", [
    ("mtp", {"mtp": {"tokens": None}}),
    ("block_diffusion", {"block_diffusion": {"block_length": 4}}),
    ("hyper", {"hyper": {"streams": 2}}),
    ("tie_embeddings", {"tie_embeddings": True}),
    ("positions='learned'", {"positions": "learned"}),
])
def test_a_combination_the_looped_tower_does_not_build_is_named(name, extra):
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import decoder_lm

    fluid.reset()
    tokens = layers.data("tokens", shape=[16, 1], dtype="int64")
    kw = dict(norm="rms_norm", positions="rope", ffn="gated_mlp",
              dense_dim=48, loop={"passes": 2}, dtype="float32")
    kw.update(extra)
    with pytest.raises(ValueError, match="does not build .*" + name.replace(
            "'", ".")):
        decoder_lm(tokens, 64, 32, 2, 4, 16, **kw)


def test_the_exit_loss_wants_two_gated_passes_and_the_loop_a_count():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import decoder_lm, ouro_exit_loss

    fluid.reset()
    tokens = layers.data("tokens", shape=[16, 1], dtype="int64")
    with pytest.raises(ValueError, match="1 to 26 passes"):
        decoder_lm(tokens, 64, 32, 2, 4, 16, positions="rope",
                   loop={"passes": 0})
    loop = {"passes": 2}
    decoder_lm(tokens, 64, 32, 1, 4, 16, positions="rope", loop=loop)
    assert len(loop["logits"]) == 2 and "gate" not in loop
    with pytest.raises(ValueError, match="two\\s+passes or more"):
        ouro_exit_loss(loop, tokens)


def test_exit_loss_from_logits_is_the_loss_built_beside_the_passes():
    """Without `targets` in the dict the objective's cross-entropies are
    made from the dict's logits: the same numbers."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer as opt
    from paddle_tpu.models.transformer import decoder_lm, ouro_exit_loss

    beside = _losses(_tower(loop={"passes": 3, "exit_gate": True})[0])
    fluid.reset()
    tokens = layers.data("tokens", shape=[16, 1], dtype="int64")
    targets = layers.data("targets", shape=[16, 1], dtype="int64")
    loop = {"passes": 3, "exit_gate": True}
    decoder_lm(tokens, vocab_size=64, dim=32, n_layers=2, n_heads=4,
               max_len=16, dense_dim=48, norm="rms_norm", norm_epsilon=1e-6,
               positions="rope", rope_theta=1e4, ffn="gated_mlp",
               dtype="float32", init_scale=0.1, loop=loop)
    loss, token_loss, exit_probs = ouro_exit_loss(loop, targets)
    assert token_loss.shape[-1] == exit_probs.shape[-1] == 3
    opt.Adam(learning_rate=1e-3).minimize(loss)
    np.testing.assert_allclose(_losses(loss), beside, rtol=1e-6)


# ---------------------------------------------------------------------------
# LayerHelper's shared parameters


def _two_fcs(widths):
    from paddle_tpu import layers

    x = layers.data("x", shape=[8], dtype="float32")
    for w in widths:
        x = layers.fc(x, w, bias_attr=False)
    return x


def test_shared_parameters_refuse_passes_that_are_not_one_stack():
    """A later pass that asks for another shape, for a parameter more, or
    for fewer by its end is an error that says which; temporaries keep
    fresh names."""
    import paddle_tpu as fluid
    from paddle_tpu.framework.layer_helper import SharedParameters

    fluid.reset()
    shared = SharedParameters()
    with shared.scope():
        first = _two_fcs([8, 8])
    with shared.scope():
        again = _two_fcs([8, 8])
    assert first.name != again.name and len(shared.names) == 2
    block = fluid.default_main_program().global_block()
    assert len(block.all_parameters()) == 2
    with pytest.raises(ValueError, match="asks for \\[8, 4\\]"):
        with shared.scope():
            _two_fcs([8, 4])
    with pytest.raises(ValueError, match="the first did not"):
        with shared.scope():
            _two_fcs([8, 8, 8])
    with pytest.raises(ValueError, match="read 1 of the 2"):
        with shared.scope():
            _two_fcs([8])
    assert len(block.all_parameters()) == 2
    # outside any scope a layer makes its own again
    _two_fcs([8])
    assert len(block.all_parameters()) == 3


# ---------------------------------------------------------------------------
# append_backward: a parameter read N times


def _read_n_times(n, segments, dtype="float32"):
    """y = x; n times y = tanh(y W) through ONE W, each application in a
    `layers.recompute` segment where `segments`; loss = mean(y^2) ->
    (loss, W's name, the program)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.layer_helper import SharedParameters
    import contextlib

    fluid.reset()
    x = layers.data("x", shape=[8], dtype=dtype)
    shared = SharedParameters()
    y = x
    for _ in range(n):
        with shared.scope(), (layers.recompute() if segments
                              else contextlib.nullcontext()):
            y = layers.tanh(layers.fc(y, 8, bias_attr=False))
    loss = layers.mean(layers.elementwise_mul(y, y))
    return loss, shared.names[0], fluid.default_main_program()


@pytest.mark.parametrize("segments", [False, True],
                         ids=["plain_ops", "recompute_segments"])
@pytest.mark.parametrize("n", [2, 4])
def test_parameter_read_n_times_gets_the_summed_gradient(n, segments):
    """Against float32 jax.grad of the same function of ONE matrix; the
    gradient is finalized from n parts, added in the order the backward
    makes them (the LAST reader's first), and the counter says n."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.framework.backward import append_backward

    loss, w, main = _read_n_times(n, segments)
    obs.REGISTRY.reset()
    append_backward(loss)
    fam = obs.REGISTRY.snapshot()["families"]["backward_grad_parts_total"]
    assert {s["labels"]["parts"]: s["value"] for s in fam["series"]} == {
        str(n): 1.0}
    block = main.global_block()
    sums = [op for op in block.ops if op.type == "sum"
            and op.output_names() == [w + "@GRAD"]]
    assert len(sums) == 1 and len(sums[0].input_names()) == n
    made = [name for op in block.ops if op is not sums[0]
            for name in op.output_names()
            if name in set(sums[0].input_names())]
    assert made == sums[0].input_names()   # the order they are made in
    assert made[0] == w + "@GRAD" and all("@RENAME" in m for m in made[1:])
    exe = fluid.Executor(fluid.CPUPlace())
    startup = fluid.default_startup_program()
    main.random_seed = startup.random_seed = 3
    exe.run(startup)
    xs = np.random.RandomState(0).randn(5, 8).astype("float32")
    got = np.asarray(exe.run(feed={"x": xs}, fetch_list=[w + "@GRAD"])[0])
    W = np.asarray(fluid.global_scope().find(w), np.float32)

    def f(W):
        y = jnp.asarray(xs)
        for _ in range(n):
            y = jnp.tanh(jnp.dot(y, W, precision="highest"))
        return jnp.mean(y * y)

    want = np.asarray(jax.grad(f)(jnp.asarray(W)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# head_norm_rope inside a segment


def test_head_norm_rope_takes_its_plain_emission_inside_a_sub_block(
        monkeypatch):
    """Its kernels have a grad op of their own and no custom_vjp: inside a
    `layers.recompute` segment (any sub-block) the backward is the block's
    own jax.vjp, which cannot differentiate the forward kernel's `roll`, so
    `_qk_prep` gives the kernels' heads-a-block as 0 there and at the
    program's top level what the shapes allow."""
    import jax.numpy as jnp

    from paddle_tpu.ops import llm_ops

    monkeypatch.delenv("PADDLE_TPU_NO_FUSED_KERNELS", raising=False)

    class Ctx:
        mesh, sub_depth = None, 0

        def target_platform(self):
            return "tpu"

    ctx = Ctx()
    ins = {"X": [jnp.zeros((1, 128, 256), jnp.bfloat16)]}
    attrs = {"num_heads": 2, "theta": 1e6}
    assert llm_ops._qk_prep(ctx, ins, attrs)[3] > 0
    ctx.sub_depth = 1
    assert llm_ops._qk_prep(ctx, ins, attrs)[3] == 0
