"""The granite-4.0-h-micro tower (PR 67) against its plain reference, on the
CPU in float32: a 10-layer toy of the held kinds (five mamba, the attention
layer, four mamba) built by the configuration's own builder and run by
`fluid.Executor` with Adam under `layers.recompute` THROUGH THE CELL'S
DRIVER, then every departure the reference file knows, one at a time,
through ONE compiled function: each has to move at least one key of the
check by far more than the program's own distance.
tests/test_mamba2.py holds the ops; tests/benchmarks/test_granite_cell.py
the cell's files.
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

CONFIG = "granite-4.0-h-micro"
TRAFFIC = "train_staged_bs1_long"
T, VOCAB = 64, 96


def toy_config(dtype="float32"):
    """Hidden 32, MLP 64; 4 query heads on 2 key/value heads of 8 at scale
    1/4 (not 8^-1/2); 4 Mamba-2 heads of 16 on a state of 8, one group, four
    chunks of 16 tokens; vocabulary 96; the cell's run of layers, published
    0-9, every block a segment that keeps its widest products."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=32, intermediate_size=64, num_attention_heads=4,
               num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16,
               mamba_d_state=8, mamba_chunk_size=16, vocab_size=VOCAB,
               attention_multiplier=0.25)
    cfg["train"]["args"].update(
        seq_len=T, vocab_size=VOCAB, dim=32, n_heads=4, n_kv_heads=2,
        dense_dim=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
        mamba_chunk=16, attention_multiplier=0.25, dtype=dtype,
        init_scale=0.3, learning_rate=0.003,
        remat_keep=["mlp.up", "ssm.in_proj"])
    cfg["train"]["feeds"]["tokens"].update(shape=[T, 1], high=VOCAB)
    return cfg


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    import paddle_tpu as fluid

    traffic = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    traffic.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
                   trace_seconds=0.2)
    cfg = toy_config()
    ctx = harness.Context(
        cell={"name": "toy"}, config=cfg, traffic=traffic,
        seed=2 ** 31 + 67, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path_factory.mktemp("toy") / "trace"))
    rec = harness.load_module("drivers", "train_executor").run(ctx)
    scope = fluid.global_scope()
    main = fluid.default_main_program()
    params = [np.asarray(scope.find(p.name), np.float32)
              for p in main.global_block().all_parameters()]
    kinds = [op.type for b in main.blocks for op in b.ops]
    return cfg, rec, params, kinds


def test_driver_toy_granite_float32_matches_the_reference(toy_run):
    """The loss, every token's loss, the last Mamba layer's scan result and
    every GRAD_PARAMS gradient (W_in, the taps, their bias, dt's bias,
    A_log, D, the gated norm's gain and W_out of the first and the last
    Mamba layer; the attention layer's W_q and W_k; the top MLP's W_up; the
    tied embedding; the final gain) against the plain reference on the same
    seeded weights; the run is `correct`; the program is ten segments of
    the held kinds."""
    ref = harness.load_module("reference", CONFIG)
    cfg, rec, params, kinds = toy_run
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {"loss", "token_loss", "scan"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert max(errs.values()) < 5e-5, errs
    assert rec["correct"] and rec["checks"]["loss_fell"]
    assert set(rec["compared"]) == set(errs) | {
        "loss_at_fell_step", "compile_events_in_window"}
    assert (kinds.count("recompute"), kinds.count("ssd_scan"),
            kinds.count("gated_rms_norm"),
            kinds.count("scaled_dot_product_attention")) == (10, 9, 9, 1)
    layers, n = ref.layout(cfg)
    assert n == len(params) == 128
    assert [k for k, _ in layers] == ["mamba"] * 5 + ["attention"] + [
        "mamba"] * 4
    shapes = [p.shape for p in params]
    assert shapes[0] == (VOCAB, 32) and shapes[127] == (32,)
    # the indices GRAD_PARAMS names are what its comment says they are
    for at in (1, 114):
        assert shapes[at + 1:at + 9] == [
            (32, 64 + 80 + 4), (80, 4), (80,), (4,), (4,), (4,), (64,),
            (64, 32)]
    assert shapes[67:69] == [(32, 32), (32, 16)] and shapes[125] == (32, 64)


@pytest.fixture(scope="module")
def departures(toy_run):
    """{control: {key: the distance it moves the reference's own check}} for
    every control of the reference file, through ONE compiled function (the
    control is a traced one-hot)."""
    import jax

    ref = harness.load_module("reference", CONFIG)
    cfg, _, params, _ = toy_run
    tokens = np.random.RandomState(3).randint(0, VOCAB, (1, T))
    targets = np.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        check = jax.jit(lambda ps, flags: ref.check_fn(
            ps, tokens, targets, cfg, flags))
        none = np.zeros(len(ref.KNOWN), bool)
        drv = harness.load_module("drivers", "train_executor")
        want = {k: np.asarray(v) for k, v in check(params, none).items()}
        out = {}
        for i, name in enumerate(ref.KNOWN):
            flags = none.copy()
            flags[i] = True
            out[name] = drv.reference_errors(
                {k: np.asarray(v) for k, v in check(params, flags).items()},
                want, ref.CENTERED)
    return out


def _known():
    return harness.load_module("reference", CONFIG).KNOWN


@pytest.mark.parametrize("control", _known())
def test_every_departure_of_the_reference_fails_a_key(toy_run, departures,
                                                      control):
    """D dropped; dt_bias dropped; exp for softplus; A = -A_log; the decay
    a channel, or one for all heads; B and C swapped; a B a head; the
    convolution over x alone, its bias or its SiLU dropped; Delta not
    multiplying the input; every chunk from a zero state; the state in
    bf16; the norm before the gate, or a head's; the gate dropped; a
    multiplier at 1; the softmax scale d^-1/2; a rotary turn; key/value
    heads interleaved or halved; an untied head; fp8 products: each moves
    its most telling key by over 100 times what the program itself reads
    there."""
    _, rec, _, _ = toy_run
    own = rec["checks"]["reference_errors"]
    moved = departures[control]
    worst = max(moved, key=lambda k: moved[k] / max(own[k], 1e-7))
    assert moved[worst] > 100 * max(own[worst], 1e-7), (control, worst,
                                                        moved[worst])
    assert max(moved.values()) > 5e-3, (control, moved)


def test_the_controls_touch_what_they_name(departures):
    """A departure inside the scan moves the scan's result; one behind it
    (the gate, the norm, the head) does not; the untied head moves the
    embedding's gradient alone."""
    ref = harness.load_module("reference", CONFIG)
    assert set(departures) == set(ref.KNOWN) == set(
        ref.CONTROLS + ref.CPU_ONLY) and "fp8" == ref.CONTROLS[0]
    untied = departures["untied_head"]
    assert untied["grad_0"] > 0.1 and max(
        v for k, v in untied.items() if k != "grad_0") == 0.0
    assert departures["logits_scaling_1"]["scan"] == 0.0
    for name in ("no_D", "swap_bc", "bc_per_head", "chunk_zero_state",
                 "decay_by_channel", "no_delta_on_input", "conv_x_only"):
        assert departures[name]["scan"] > 0.03, name
    for name in ("no_gate", "norm_before_gate", "norm_per_head",
                 "softmax_scale_sqrt", "rope", "kv_interleaved"):
        assert departures[name]["token_loss"] > 0.02, name
    with pytest.raises(ValueError, match="one of"):
        ref.control_check([], {}, {}, control="no_such_control")
    with pytest.raises(ValueError, match="one of"):
        ref.Departure("no_such_control")
