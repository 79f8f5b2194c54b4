"""Laguna-S's 5-layer toy tower against its plain float32 reference
(benchmarks/reference/laguna-s-2.1.py) on the CPU: the program through the
cell's own driver (loss, every token's loss, the last layer's routing, the
listed gradients of a full AND a sliding layer), a PROGRAM that turns both
layer kinds by one rule failing the same check, and the
reference's committed tolerances against every mutant of the reference.
The op-level pieces are in tests/test_laguna.py, the cell's manifest,
configuration, counts, readers and size in
tests/benchmarks/test_laguna_cell.py."""

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

CONFIG = "laguna-s-2.1"
TRAFFIC = "train_staged_bs1_long"
T, W = 64, 16     # the toy's tokens a sample and keys a window
MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "fp8": "grad_2", "no_gate": "token_loss", "gate_token": "grad_5",
    "gate_element": "grad_17", "gate_silu": "grad_5",
    "full_group_sliding": "grad_3", "sliding_group_full": "grad_15",
    "window_on_full": "grad_2", "no_window": "grad_14",
    "window_minus": "grad_15", "window_plus": "grad_15",
    "full_turns_all": "grad_2", "full_rule_sliding": "grad_2",
    "sliding_theta_full": "grad_14", "no_yarn": "grad_2",
    "no_attention_factor": "grad_2", "factor_on_all": "grad_2",
    "no_qk_norm": "grad_6", "routed_scale_one": "router_weights",
    "no_renormalise": "router_weights", "no_shared_gate": "grad_29",
    "no_shared_expert": "grad_29", "sigmoid_scores": "router_weights",
    "dropped_pair": "dropped_pairs", "bf16_elementwise": "loss"}


def toy_config(dtype="float32", layers=5):
    """Hidden 32, 18 / 12 query heads (sliding / full) on 2 key/value heads
    of 8 (groups of NINE and SIX, as 72 and 48 on 8), the published layers
    0-4 (full + dense, sliding x 3, full) under a window of 16 of 64
    tokens, YaRN with an original length of 16 at factor 8 over 4 of a full
    head's 8 columns (so both ends of its ramp and the blend lie among the
    two frequencies), a dense MLP of 48, 8 experts of 16 with 3 a token,
    experts 2-5 held in a buffer that nothing can overflow, a shared
    expert of 16; weights of scale 0.3 so that every part moves the result.
    `layers` 2 holds the published layers 0 and 1 alone, one of each kind
    (the mutants' case: fewer programs to compile)."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    rope = copy.deepcopy(cfg["rope_parameters"])
    rope["full_attention"].update(original_max_position_embeddings=16,
                                  factor=8, rope_theta=100.0,
                                  attention_factor=1.3)
    rope["sliding_attention"].update(rope_theta=30.0)
    heads = [18 if t == "sliding_attention" else 12
             for t in cfg["layer_types"]]
    cfg.update(hidden_size=32, num_key_value_heads=2, head_dim=8,
               num_attention_heads=12, num_attention_heads_per_layer=heads,
               intermediate_size=48, moe_intermediate_size=16,
               shared_expert_intermediate_size=16, vocab_size=97,
               num_hidden_layers=layers, num_experts=4,
               num_experts_per_tok=3, sliding_window=W,
               rope_parameters=rope)
    cfg["share"].update(first_expert=2, buffer_rows=3 * T)
    cfg["deployment"]["layers_held"] = list(range(layers))
    a = cfg["train"]["args"]
    a.update(seq_len=T, vocab_size=97, dim=32, n_kv_heads=2, head_dim=8,
             sliding_window=W, rope_parameters=rope, dense_dim=48,
             num_experts=8, expert_dim=16, top_k=3, shared_dim=16,
             held_experts=4, first_expert=2, buffer_rows=3 * T, dtype=dtype,
             init_scale=0.3, learning_rate=0.003,
             layer_types=a["layer_types"][:layers],
             heads_per_layer=heads[:layers])
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
        seed=2 ** 31 + 63, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_driver_toy_laguna_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain reference on the same
    seeded weights (gains drawn on [0.5, 1.5)): the loss, every token's
    loss, the last layer's top-k weights (softmax over all, renormalised,
    times 2.5), its counts and their exact sum, the pairs on held experts,
    none dropped, and every GRAD_PARAMS gradient: the full layer's Wq, Wk,
    Wg and head gains (12 heads in groups of 6, YaRN over half a head), the
    sliding layer's (18 in groups of 9, the plain turn, the window), the
    dense Wup, a router, the stacked held experts, the shared gate; and
    the run is `correct` (the loss fell, nothing compiled in the
    window)."""
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


def test_a_program_with_one_rope_rule_for_both_layer_kinds_fails(
        tmp_path, monkeypatch):
    """The same builder with the hand-over cut inside `decoder_lm`'s call
    of `layers.multi_head_attention` has the same parameters and is NOT the
    model: with ONE rule for both layer kinds (every layer turned over its
    whole head at the sliding layers' theta, no YaRN, no factor) the full
    layer's gradients leave their limits.  (YaRN or its factor alone left
    out, the factor on the unturned half, one head grouping for both kinds
    and the gate's forms are held by the reference's mutants below.)"""
    import paddle_tpu as fluid

    real = fluid.layers.multi_head_attention
    cfg = toy_config("float32", layers=2)
    theta = cfg["rope_parameters"]["sliding_attention"]["rope_theta"]

    def cut_layer(*a, yarn=None, rotary_dim=None, **kw):
        kw["rope_theta"] = float(theta)
        return real(*a, **kw)

    monkeypatch.setattr(fluid.layers, "multi_head_attention", cut_layer)
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(cfg, tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert not rec["checks"]["reference_ok"] and not rec["correct"]
    failed = {k for k, e in errs.items() if not e <= ref.TOL[k]}
    assert {"grad_2", "grad_3", "grad_6", "grad_7", "token_loss"} <= failed, (
        errs)
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
    main.random_seed = startup.random_seed = 63
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    # GRAD_PARAMS name what the reference's comment says they name
    D, E, held, H, d = 32, 8, 4, 16, 8
    named = {2: (D, 12 * d), 3: (D, 2 * d), 5: (D, 12), 6: (d,), 7: (d,),
             11: (D, 48), 14: (D, 18 * d), 15: (D, 2 * d), 17: (D, 18),
             18: (d,), 19: (d,), 22: (D, E), 23: (held, D, H),
             25: (held, H, D), 29: (D, 1), -2: (D,)}
    assert set(named) == set(ref.GRAD_PARAMS)
    assert len(params) == 1 + (ref.PER_MIXER + ref.PER_FFN["dense"]) + (
        ref.PER_MIXER + ref.PER_FFN["sparse"]) + 2
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        # the gains were drawn, not left at one
        assert float(jnp.abs(ps[6] - 1).max()) > 0.1
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, 97)
        feed = (tok, jnp.roll(tok, -1, axis=1))
        want = jax.jit(lambda ps: ref.check_fn(ps, *feed, cfg))(ps)
    return ref, cfg, ps, feed, want


def test_every_mutant_of_the_reference_is_held():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS)


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_laguna_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself:
    every matmul in fp8 (the nearest precision below the stated bf16: the
    control); the gate dropped, one a token for all heads, one a column, or
    SiLU for its sigmoid; one grouping of the query heads for both layer
    kinds, either way; the window on a full layer, none on a sliding one,
    one key narrower or wider; the full layers turned on all columns, by
    the sliding rule, the sliding ones at the full layers' theta; YaRN left
    out, its factor left out, or put on the unturned half too; no QK-norm;
    routed scale 1, no renormalisation, sigmoid scores; the shared gate or
    the shared expert dropped; one pair the buffer had no row for; and what
    the configuration states as float32 (norms, the turn, the softmax, the
    router, the gate's sigmoid) rounded to bf16 after every step must each
    fail, by the key named."""
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
    counts sum to T x top_k exactly, a token's weights sum to the routed
    scale, the held pairs are the counts' slice, the layers' kinds are the
    published lists' (heads, group, window, turning columns, rule, MLP),
    YaRN's row written in the file is `ops/llm_ops.py`'s, and the tower is
    causal: another token at position 20 moves the loss at 20 and at every
    later position (the full layer sees everything before it) and at none
    earlier."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.llm_ops import yarn_inv_freq

    ref, cfg, ps, (tok, tgt), want = toy_case
    assert float(want["routed_pairs"][0]) == T * 3
    assert float(want["dropped_pairs"][0]) == 0.0
    np.testing.assert_allclose(np.asarray(want["router_weights"]).sum(-1),
                               2.5, rtol=1e-5)
    first = cfg["share"]["first_expert"]
    assert float(want["held_pairs"][0]) == float(
        np.asarray(want["expert_counts"])[first:first + 4].sum())
    kinds = ref.layer_kinds(toy_config())
    assert [(k["heads"], k["group"], k["window"], k["turned"], k["mlp"],
             k["rule"]["rope_type"]) for k in kinds] == [
        (12, 6, 0, 4, "dense", "yarn")] + [
        (18, 9, W, 8, "sparse", "default")] * 3 + [
        (12, 6, 0, 4, "sparse", "yarn")]
    real = harness.load_json("configs", CONFIG)
    assert [(k["heads"], k["group"], k["window"], k["turned"])
            for k in ref.layer_kinds(real)] == [
        (48, 6, 0, 64), (72, 9, 512, 128), (72, 9, 512, 128),
        (72, 9, 512, 128), (48, 6, 0, 64)]
    rule = real["rope_parameters"]["full_attention"]
    inv, factor = ref.rope_inv_freq(rule, 64)
    np.testing.assert_allclose(inv, yarn_inv_freq(
        64, 5e5, 128.0, 8192, 32.0, 1.0), rtol=1e-6)
    assert factor == 1.4852030263919618
    assert factor == pytest.approx(0.1 * np.log(128.0) + 1.0, rel=1e-12)
    with jax.enable_x64(False):
        at = 20
        moved = tok.at[0, at].set((tok[0, at] + 1) % 97)
        other = jax.jit(lambda ps: ref.check_fn(
            ps, moved, tgt, cfg)["token_loss"])(ps)
    diff = np.abs(np.asarray(other) - np.asarray(want["token_loss"]))
    assert diff[:at].max() == 0.0 and (diff[at:] > 0).all()
