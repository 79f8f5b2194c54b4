"""Kimi-Linear's five-layer tower (KDA + dense, KDA, KDA, latent attention
without a turn, KDA; experts from the second layer on) at a toy size,
against the benchmark's plain reference: the program built by the
configuration's own builder and run by the cell's own driver (loss, every
token's loss, the last layer's routing, the last KDA layer's result, the
seventeen listed gradients), and the committed tolerances against every
mutant of the reference (tests/benchmarks/test_kimilinear_cell.py has the
manifest, the configuration, the counts, the readers and the real size
compiled for the chip)."""

from __future__ import annotations

import copy
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "kimi-linear-48b-a3b"
TRAFFIC = "train_staged_bs1_long"
MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "gate_mean": "grad_2", "no_dt_bias": "grad_13", "no_a_log": "kda_out",
    "no_beta": "kda_out", "no_l2norm": "grad_3", "q_unscaled": "kda_out",
    "no_state": "grad_13", "no_conv_silu": "kda_out",
    "silu_gate": "grad_9", "taps_reversed": "grad_11", "rope": "grad_72",
    "no_kp": "grad_73", "sqrt128": "grad_72", "no_scale": "router_weights",
    "no_bias": "expert_counts", "bias_in_weight": "router_weights",
    "state_bf16": "grad_13", "fp8": "grad_13",
    "dropped_pair": "dropped_pairs",
    # bf16 gates move nothing past a limit at 128 tokens of 2 heads, and at
    # init_scale 0.3 the STATED bf16 alone passes the cell's limits: these
    # two are the chip's to hold (reference_sweep.py --control; PERF.md,
    # PR 58)
    "gate_bf16": None, "stated_low": None}


def _toy_config(dtype="float32", seq_len=128):
    """Hidden 48; KDA 2 heads of 16, 4 taps, gate rank 16; MLA 2 heads, 16 +
    8 query/key columns, 16 value columns, a latent of 16; dense 64; 4 of 32
    experts of 16 held, top-4, a shared expert of 16; vocabulary 96; T 128 =
    2 chunks of 64 (and 2 blocks of the reference's scan); the published
    layers 1-5, as the cell holds them."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=48, num_attention_heads=2, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=64, moe_intermediate_size=16,
               num_experts=4, num_experts_per_token=4, vocab_size=96)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], num_heads=2,
                                     head_dim=16)
    cfg["published"].update(num_experts=32)
    cfg["share"].update(buffer_rows=256)
    cfg["train"]["args"].update(
        seq_len=seq_len, vocab_size=96, dim=48, n_heads=2, kv_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_dim=16, linear_heads=2,
        linear_head_dim=16, gate_rank=16, dense_dim=64, num_experts=32,
        expert_dim=16, top_k=4, held_experts=4, buffer_rows=256,
        dtype=dtype, init_scale=0.3, learning_rate=0.003,
        bias_init_scale=0.05)
    cfg["train"]["feeds"]["tokens"].update(shape=[seq_len, 1], high=96)
    return cfg


def test_driver_toy_kimi_linear_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain token-by-token reference on
    the same seeded weights: every key of TOL; and the run is `correct`
    (the loss fell, nothing compiled in the window)."""
    import paddle_tpu as fluid

    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    traffic = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    traffic.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
                   trace_seconds=0.2)
    rec = drv.run(harness.Context(
        cell={"name": "toy"}, config=_toy_config(), traffic=traffic,
        seed=2 ** 31 + 58, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace")))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs", "kda_out"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert errs["routed_pairs"] == errs["dropped_pairs"] == 0.0
    assert max(errs.values()) < 5e-4, errs
    assert rec["correct"], rec["checks"]
    assert rec["batch"] == 1


@pytest.fixture(scope="module")
def toy_case():
    """The toy program's own parameters (so the order is the builder's),
    a batch, and the reference's answers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    ref = harness.load_module("reference", CONFIG)
    cfg = _toy_config()
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 58
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    D, V, E, held, H, W, r = 48, 96, 32, 4, 16, 32, 16
    kda = [(D,), (D, W), (D, W), (D, W), (D, r), (r, W), (D, 2), (D, r),
           (r, W), (W, 4), (W, 4), (W, 4), (2,), (W,), (16,), (W, D)]
    mla = [(D,), (D, 48), (D, 24), (16,), (16, 64), (32, D)]
    dense = [(D,), (D, 64), (D, 64), (64, D)]
    ffn = [(D,), (D, E), (held, D, H), (held, D, H), (held, H, D), (E,),
           (D, H), (D, H), (H, D)]
    assert [tuple(p.shape) for p in params] == (
        [(V, D)] + kda + dense + (kda + ffn) * 2 + mla + ffn + kda + ffn
        + [(D,), (D, V)])
    assert (len(kda) - 1, len(mla) - 1, len(dense) - 1, len(ffn) - 1) == (
        ref.PER_MIXER["kda"], ref.PER_MIXER["mla"], ref.PER_FFN["dense"],
        ref.PER_FFN["experts"])
    assert ref.layout(cfg) == ([("kda", "dense", 1), ("kda", "experts", 21),
                                ("kda", "experts", 46),
                                ("mla", "experts", 71),
                                ("kda", "experts", 86)], 113)
    # GRAD_PARAMS name what the reference's comment says they name
    named = {2: (D, W), 3: (D, W), 4: (D, W), 6: (r, W), 7: (D, 2),
             9: (r, W), 11: (W, 4), 13: (2,), 14: (W,), 15: (16,),
             72: (D, 48), 73: (D, 24), 75: (16, 64), 103: (D, E),
             104: (held, D, H), 106: (held, H, D), -2: (D,)}
    assert len(params) == 113 and set(named) == set(ref.GRAD_PARAMS)
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, 128), 0, V)
        tgt = jnp.roll(tok, -1, axis=1)
        want = jax.jit(lambda ps: ref.check_fn(ps, tok, tgt, cfg))(ps)
    return ref, cfg, ps, tok, tgt, want


def test_every_mutant_of_the_reference_is_held():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS)
    assert callable(ref.train_check) and callable(ref.control_check)
    assert ref.STATED == ("stated", "stated_low")


@pytest.mark.parametrize("mutant", sorted(m for m in MUTANTS if MUTANTS[m]))
def test_kimi_linear_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself: a
    gate averaged over a head's channels (the scalar form), dt_bias or
    A_log dropped, beta 1, no l2 norm, q unscaled, the carried state
    dropped at a chunk's border, the convolution without its SiLU or with
    reversed taps, SiLU for the output gate's sigmoid, a rotary turn in the
    MLA layer, the shared key out of the scores or the scale 128^-1/2, the
    routed scale 1, the choice without the bias or the bias in the weights,
    a bf16 state, every matmul in fp8 and a dropped pair must each fail, by
    the key named."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, cfg, ps, tok, tgt, want = toy_case
    # the named key alone, under ONE jit: XLA drops what it does not need
    key = MUTANTS[mutant]
    grad_params = (int(key[5:]),) if key.startswith("grad_") else ()
    with jax.enable_x64(False):
        got = jax.jit(lambda ps: {key: ref.check_fn(
            ps, tok, tgt, cfg, mutant, grad_params=grad_params)[key]})(ps)
    errors = drv.reference_errors(got, {key: want[key]}, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    assert MUTANTS[mutant] in failed, errors


def test_the_reference_recurrence_is_token_by_token(toy_case):
    """`kda_rule` is a scan of T single tokens whose carried state is one
    [H, Dk, Dv] float32 (no chunk algebra to share a mistake with the
    program), and a row of the state decays by ITS OWN channel's factor:
    one token after a unit write, row d holds e^{g[d]}."""
    import jax
    import jax.numpy as jnp

    ref = toy_case[0]
    with jax.enable_x64(False):
        sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)     # noqa: E731
        jaxpr = jax.make_jaxpr(ref.kda_rule)(
            sds(128, 2, 8), sds(128, 2, 8), sds(128, 2, 8), sds(128, 2, 8),
            sds(128, 2))
        (outer,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert outer.params["length"] == 128 // ref.SCAN_BLOCK
        carried = outer.outvars[0].aval
        assert (carried.shape, str(carried.dtype)) == ((2, 8, 8), "float32")
        text = str(jaxpr)
        assert f"length={ref.SCAN_BLOCK}" in text
        assert "cumsum" not in text and "triangular" not in text
        # two tokens: write k = e_0 + e_1 (unnormalised), v = 1, beta = 1,
        # then decay by g and read with q = e_d
        k = jnp.zeros((2, 1, 2)).at[0, 0, :].set(1.0)
        g = jnp.zeros((2, 1, 2)).at[1, 0].set(jnp.asarray([-1.0, -3.0]))
        for d, want in ((0, np.exp(-1.0)), (1, np.exp(-3.0))):
            q = jnp.zeros((2, 1, 2)).at[1, 0, d].set(1.0)
            o = ref.kda_rule(q, k, jnp.ones((2, 1, 1)) * jnp.asarray(
                [[[1.0]], [[0.0]]]), g, jnp.ones((2, 1)))
            assert float(o[1, 0, 0]) == pytest.approx(want, rel=1e-6)
