"""SmallThinker's 4-layer toy tower against its plain float32 reference
(benchmarks/reference/smallthinker-21b-a3b.py) on the CPU: the program
through the cell's own driver (loss, every token's loss, the last layer's
routing, the listed gradients), a PROGRAM that routes on the second norm's
output failing the same check, and the reference's committed tolerances
against every mutant of the reference.  The op-level pieces are in
tests/test_smallthinker.py, the cell's manifest, configuration, counts,
readers and size in tests/benchmarks/test_smallthinker_cell.py."""

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

CONFIG = "smallthinker-21b-a3b"
TRAFFIC = "train_staged_bs1_16k"
T, W = 64, 16     # the toy's tokens a sample and keys a window
MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "fp8": "grad_2", "router_second_norm": "router_weights",
    "silu": "token_loss", "rope_layer0": "grad_2",
    "no_rope_layer1": "grad_12", "window_minus": "grad_13",
    "window_plus": "grad_13", "no_window": "token_loss",
    "softmax_all": "router_weights", "kv_mod": "grad_3",
    "dropped_pair": "dropped_pairs"}


def toy_config(dtype="float32", layers=4):
    """Hidden 32, 14 query heads on 2 key/value heads of 8 (a group of
    SEVEN; 14 x 8 = 112 on a hidden size of 32, as 28 x 128 = 3584 is on
    2560), the published period [0, 1, 1, 1] under a window of 16 of 64
    tokens, 8 experts of 16 with 3 a token, experts 2-5 held in a buffer
    that nothing can overflow; weights of scale 0.3 so that every part
    moves the result.  `layers` 2 holds the published layers 0 and 1 alone,
    one of each kind (the mutants' case: half the programs to compile)."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=32, num_attention_heads=14,
               num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=16,
               vocab_size=97, num_hidden_layers=layers,
               moe_num_primary_experts=4,
               moe_num_active_primary_experts=3, sliding_window_size=W)
    cfg["share"].update(first_expert=2, buffer_rows=3 * T)
    cfg["deployment"]["layers_held"] = list(range(layers))
    cfg["train"]["args"].update(
        seq_len=T, vocab_size=97, dim=32, n_heads=14, n_kv_heads=2,
        head_dim=8, sliding_window=W, num_experts=8, expert_dim=16, top_k=3,
        held_experts=4, first_expert=2, buffer_rows=3 * T, dtype=dtype,
        init_scale=0.3, learning_rate=0.003,
        layer_types=cfg["train"]["args"]["layer_types"][:layers],
        rope_layout=cfg["train"]["args"]["rope_layout"][:layers])
    cfg["train"]["feeds"]["tokens"].update(shape=[T, 1], high=97)
    return cfg


def toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    t.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
             trace_seconds=0.2)
    return t


def _ctx(config, tmp_path):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=toy_traffic(),
        seed=2 ** 31 + 54, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_driver_toy_smallthinker_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain reference on the same
    seeded weights: the loss, every token's loss, the last layer's top-k
    weights (from the FIRST norm's output), its counts and their exact
    sum, the pairs on held experts, none dropped, and every GRAD_PARAMS
    gradient: the full-span layer's Wq and Wk without a position, the
    window layer's with RoPE, the router's (reached only through RouterX)
    and the first gain's (reached by the router beside the attention); and
    the run is `correct` (the loss fell, nothing compiled in the window)."""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(toy_config("float32"), tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    for exact in ("routed_pairs", "held_pairs", "dropped_pairs",
                  "expert_counts"):
        assert errs[exact] == 0.0, exact
    assert max(errs.values()) < 1e-4, errs
    assert rec["correct"], rec["checks"]
    assert rec["batch"] == 1 and rec["window"]["samples"] == rec[
        "window"]["steps"]


def test_a_program_that_routes_on_the_second_norm_fails(tmp_path,
                                                        monkeypatch):
    """The same builder with the hand-over cut (`layers.moe` called
    without its `router_input`: the router then reads the experts' own
    input, as every other share's does) has the same parameters and is NOT
    the model: the last layer's weights and counts, the router's gradient
    and the first gain's all leave their limits."""
    import paddle_tpu as fluid

    real = fluid.layers.moe
    monkeypatch.setattr(
        fluid.layers, "moe",
        lambda *a, router_input=None, **kw: real(*a, **kw))
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(toy_config("float32", layers=2), tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert not rec["checks"]["reference_ok"] and not rec["correct"]
    failed = {k for k, e in errs.items() if not e <= ref.TOL[k]}
    assert {"router_weights", "expert_counts", "grad_17", "grad_11",
            "token_loss"} <= failed, errs
    assert errs["routed_pairs"] == 0.0 == errs["dropped_pairs"]


# ---------------------------------------------------------------------------
# the committed tolerances against mutants of the reference


@pytest.fixture(scope="module")
def toy_case():
    """The toy program's own parameters (so the order is the builder's), a
    batch, and the reference's answers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    ref = harness.load_module("reference", CONFIG)
    cfg = toy_config("float32", layers=2)
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 54
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    # GRAD_PARAMS name what the reference's comment says they name
    D, E, held, H, Hq, Hkv, d = 32, 8, 4, 16, 14, 2, 8
    named = {2: (D, Hq * d), 3: (D, Hkv * d), 11: (D,), 12: (D, Hq * d),
             13: (D, Hkv * d), 17: (D, E), 18: (held, D, H),
             20: (held, H, D), -2: (D,)}
    assert set(named) == set(ref.GRAD_PARAMS)
    assert len(params) == 1 + ref.PER_LAYER * 2 + 2
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, 97)
        feed = (tok, jnp.roll(tok, -1, axis=1))
        want = jax.jit(lambda ps: ref.check_fn(ps, *feed, cfg))(ps)
    return ref, cfg, ps, feed, want


def test_every_mutant_of_the_reference_is_held():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS)


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_smallthinker_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself:
    every matmul in fp8 (the nearest precision below the stated bf16: the
    control), the router on the second norm's output, SiLU for ReLU, RoPE
    in the full-span layer, none in the first window layer, a window one
    key narrower or wider, no window, the softmax over all 64 without
    renormalisation, key/value head h % Hkv, and one pair the buffer had
    no row for must each fail, by the key named."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, cfg, ps, feed, want = toy_case
    # what is read below, under ONE jit: XLA drops what the other keys need
    keys = {MUTANTS[mutant], "dropped_pairs", "routed_pairs"}
    with jax.enable_x64(False):
        got = jax.jit(lambda ps: {k: v for k, v in ref.check_fn(
            ps, *feed, cfg, mutant).items() if k in keys})(ps)
    errors = drv.reference_errors(got, {k: want[k] for k in got},
                                  ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    assert MUTANTS[mutant] in failed, errors
    if mutant == "dropped_pair":
        assert float(got["dropped_pairs"][0]) == 1.0
        assert float(want["dropped_pairs"][0]) == 0.0
        assert float(got["routed_pairs"][0]) == T * 3


def test_the_reference_is_the_published_block(toy_case):
    """What the reference computes, at the points no mutant shows: the
    counts sum to T x top_k exactly, a token's weights sum to one, the
    held pairs are the counts' slice, the layers' kinds are the published
    period's, and the tower is causal: another token at position 20 moves
    the loss at 20 and at every later position (the full-span layer sees
    everything before it) and at none earlier."""
    import jax
    import jax.numpy as jnp

    ref, cfg, ps, (tok, tgt), want = toy_case
    assert float(want["routed_pairs"][0]) == T * 3
    assert float(want["dropped_pairs"][0]) == 0.0
    np.testing.assert_allclose(np.asarray(want["router_weights"]).sum(-1),
                               1.0, rtol=1e-5)
    first = cfg["share"]["first_expert"]
    assert float(want["held_pairs"][0]) == float(
        np.asarray(want["expert_counts"])[first:first + 4].sum())
    assert ref.layer_kinds(cfg) == [(0, False), (W, True)]
    assert ref.layer_kinds(toy_config()) == [(0, False)] + [(W, True)] * 3
    with jax.enable_x64(False):
        at = 20
        other = tok.at[0, at].set((tok[0, at] + 1) % 97)
        # ONE program on both batches: equal to the bit where it is causal
        loss_of = jax.jit(lambda t: ref.check_fn(ps, t, tgt, cfg)["token_loss"])
        moved = np.abs(np.asarray(loss_of(other)) - np.asarray(loss_of(tok)))
    assert moved[:at].max() == 0.0 and moved[at:].min() > 0.0
