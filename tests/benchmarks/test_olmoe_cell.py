"""What PR 26 adds to the benchmark, checked on the CPU: the OLMoE program
against its plain reference at a toy size (through the cell's own driver),
the reference's tolerances against mutants of the reference, the counts of
benchmarks/flops_moe.py by hand, the expert layer's reduction and its four
readers on recorded instructions, and the AOT compile of the cell's real
step for a described v5e.  tests/benchmarks/test_benchmark.py (not edited)
holds the manifest-wide rules over the same files.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "olmoe-1b-7b"
CELL = "olmoe_train_t4096"
MUTANTS = {  # mutant of the reference -> the key that has to catch it
    "renormalised": "token_loss", "dropped_token": "routed_slots",
    "no_balance": "loss", "no_zloss": "loss", "no_rope": "token_loss",
    "no_qk_norm": "token_loss", "fp8": "token_loss"}


def _toy_config(dtype="float32"):
    """Hidden 64, 4 heads of 16, 8 experts of 32 with 2 a token, 2 layers,
    T 32; weights of scale 0.3 so that every part moves the result."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=64, num_attention_heads=4, num_experts=8,
               num_experts_per_tok=2, intermediate_size=32,
               max_position_embeddings=32, vocab_size=97,
               num_hidden_layers=2, n_head=4, n_embd=64, n_positions=32,
               n_layer=2)
    cfg["train"]["args"].update(
        seq_len=32, vocab_size=97, dim=64, n_layers=2, n_heads=4,
        num_experts=8, expert_dim=32, top_k=2, dtype=dtype,
        init_scale=0.3, learning_rate=0.003)
    cfg["train"]["feeds"]["tokens"].update(shape=[32, 1], high=97)
    return cfg


def _ctx(config, traffic, tmp_path, trace=False):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic,
        seed=2 ** 31 + 26, seconds=0.5, trace=trace,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


def _toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", "train_staged_bs1"))
    t.update(staged_batches=2, loss_read_every=2, trace_seconds=0.2)
    return t


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_staged_bs1", 1)
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert {"mfu_active_pct", "moe_device_share_pct",
            "moe_grouped_matmul_roofline", "moe_permute_device_ms",
            "flash_fwd_roofline", "flash_bwd_dq_roofline",
            "flash_bwd_dkv_roofline", "kernel_forward_reruns",
            "step_device_ms.train"} <= per
    assert "mfu_pct" not in per and "collective_exposed_ms" not in per


def test_config_keeps_every_published_width():
    """The catalog's `config` for OLMoE-1B-7B-0125-Instruct, key for key;
    only the depth differs, and `reduced` says so."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    cfg = harness.load_json("configs", CONFIG)
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["num_experts"], a["expert_dim"],
            a["top_k"], a["vocab_size"], a["seq_len"], a["n_layers"]) == (
        2048, 16, 64, 1024, 8, 50304, 4096, cfg["num_hidden_layers"])
    # the aliases the flash readers read repeat the published keys
    assert (cfg["n_head"], cfg["n_embd"], cfg["n_positions"],
            cfg["n_layer"]) == (16, 2048, 4096, cfg["num_hidden_layers"])


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "ragged" not in code
    assert "pallas" not in code and "argsort" not in code


def test_driver_toy_olmoe_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain reference on the same
    seeded weights: the three-part loss, every token's loss, the last
    layer's per-expert counts, their sum, and every GRAD_PARAMS gradient;
    and the run is `correct` (the loss fell, nothing compiled in the
    window)."""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(_toy_config("float32"), _toy_traffic(), tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "expert_counts", "routed_slots"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert errs["routed_slots"] == 0.0 and errs["expert_counts"] == 0.0
    assert max(errs.values()) < 1e-4, errs
    assert rec["correct"], rec["checks"]
    assert rec["batch"] == 1 and rec["window"]["samples"] == rec[
        "window"]["steps"]


@pytest.fixture(scope="module")
def toy_case():
    """The toy program's own parameters (so the order is the builder's),
    a batch, and the reference's answers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    ref = harness.load_module("reference", CONFIG)
    cfg = _toy_config("float32")
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 26
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    # creation order is the order the reference documents
    D, E, H, V = 64, 8, 32, 97
    layer = [(D,), (D, D), (D, D), (D, D), (D,), (D,), (D, D), (D,),
             (D, E), (E, D, H), (E, D, H), (E, H, D)]
    assert [tuple(p.shape) for p in params] == (
        [(V, D)] + layer * 2 + [(D,), (D, V)])
    assert len(layer) == ref.PER_LAYER
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, 32), 0, V)
        tgt = jnp.roll(tok, -1, axis=1)
        want = ref.check_fn(ps, tok, tgt, cfg)
    return ref, cfg, ps, tok, tgt, want


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_olmoe_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself:
    top-k weights renormalised, one (token, expert) pair dropped, either
    auxiliary loss missing, RoPE off, QK-norm off, and every matmul in
    fp8 (the nearest precision below the stated bf16) must each fail, by
    the key named."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, cfg, ps, tok, tgt, want = toy_case
    with jax.enable_x64(False):
        got = ref.check_fn(ps, tok, tgt, cfg, mutant)
    errors = drv.reference_errors(got, want, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    assert MUTANTS[mutant] in failed, errors
    if mutant == "dropped_token":
        assert float(got["routed_slots"][0]) == 32 * 2 - 1
        assert float(want["routed_slots"][0]) == 32 * 2
    elif mutant != "fp8":
        assert errors[MUTANTS[mutant]] > 3 * ref.TOL[MUTANTS[mutant]], errors


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_olmoe_flops_by_hand():
    F = harness.load_module(".", "flops_moe")
    # one layer, hidden 4, T 2, vocab 5, 3 experts of width 6, 2 a token
    per_token = (2 * 4 * 16 + 2 * 2 * 4 + 2 * 4 * 3 + 2 * 3 * 2 * 4 * 6
                 + 2 * 4 * 5)
    assert F.olmoe_train_flops_per_sample(
        dim=4, n_layers=1, vocab=5, seq_len=2, expert_dim=6, top_k=2,
        num_experts=3) == 3.0 * per_token * 2
    # the cell: 1.5 GFLOP a token (ISSUE 26), 6.2 TFLOP a step
    cfg = harness.load_json("configs", CONFIG)
    got = getattr(F, cfg["flops_moe"]["function"])(**cfg["flops_moe"]["args"])
    layer = 33_554_432 + 16_777_216 + 262_144 + 100_663_296
    assert got == 3.0 * (2 * layer + 206_045_184) * 4096
    assert 6.2e12 < got < 6.3e12
    # flops.py's dense count at mlp_ratio 6 is the same less the router
    dense = harness.load_module(".", "flops")
    spec = cfg["flops"]
    assert got - getattr(dense, spec["function"])(**spec["args"]) == (
        3.0 * 2 * 262_144 * 4096)


def test_grouped_matmul_cost_by_hand():
    F = harness.load_module(".", "flops_moe")
    assert F.grouped_matmul_cost(rows=10, k=4, n=3, groups=2) == (
        2.0 * 10 * 4 * 3, 2.0 * (10 * 4 + 10 * 3 + 2 * 4 * 3))
    # the cell's: compute-bound on the v5e, 0.70 ms a product
    flops, nbytes = F.grouped_matmul_cost(32768, 2048, 1024, 64)
    assert (flops, nbytes) == (137_438_953_472.0, 469_762_048.0)
    peaks = harness.peaks_for("TPU v5 lite")
    least, roof = harness.load_module(".", "flops").roofline_seconds(
        flops, nbytes, peaks)
    assert roof == "compute" and least == pytest.approx(0.6977e-3, rel=1e-3)
    # the backward products move the same operands with the roles turned
    assert F.grouped_matmul_cost(32768, 1024, 2048, 64) == (flops, nbytes)


# ---------------------------------------------------------------------------
# the expert layer in a trace: recorded instructions


def _recorded():
    with open(os.path.join(HERE, "recorded_moe_ops.json"),
              encoding="utf-8") as f:
        return json.load(f)["events"]


def test_classify_on_recorded_instructions():
    M = harness.load_module("reduce", "moe_ops")
    dims = M.dims_of(harness.load_json("configs", CONFIG), batch=1)
    assert dims == {"slots": 32768, "experts": 64, "dim": 2048,
                    "expert_dim": 1024}
    kinds = {t.split(" = ", 1)[0]: M.classify(t, dims)
             for t, _, _ in _recorded()}
    assert kinds == {
        "%ragged-dot-none": "grouped_matmul",        # dW: [64, 1024, 2048]
        "%ragged-dot-none.15": "grouped_matmul",     # forward
        "%copy.166": "relayout",
        "%fusion.1": "slots",                        # a gather by the sort
        "%add_any.58": "slots",
        "%fusion.48": "slots",                       # SiLU-gate product
        "%copy-done.33": "slots",
        # RoPE's [T, 64] tables are not the router's [T, 64] logits
        "%multiply_add_fusion.3": None,
        "%sort": None,                               # top-k over [T, E]
        "%subtract_convert_fusion.6": None,          # Adam on the head
        "%subtract_convert_fusion": None,            # Adam on the experts
        "%fusion.74": None, "%fusion.10": None,
        "%flash_fwd.2": None, "%transpose_jvp_flash_bwd_dq__.3": None}
    assert M.classify("%ragged-dot-metadata.2 = s32[65] custom-call()",
                      dims) == "grouped_matmul"
    # another configuration's shapes find nothing of this one's
    other = dict(dims, slots=8192, experts=8)
    assert M.classify("%fusion.1 = bf16[32768,2048]{1,0} fusion()",
                      other) is None


def test_sums_by_hand_and_the_four_readers():
    M = harness.load_module("reduce", "moe_ops")
    T = harness.load_module("reduce", "trace")
    cfg = harness.load_json("configs", CONFIG)
    dims = M.dims_of(cfg, batch=1)
    evs = _recorded()
    end = evs[-1][1] + evs[-1][2]
    by = {t.split(" = ", 1)[0]: d for t, _, d in evs}
    got = M.sums(evs, (0, end), dims)
    assert got["calls"] == 2
    assert got["grouped_matmul"] == pytest.approx(
        (by["%ragged-dot-none"] + by["%ragged-dot-none.15"]) / 1e9)
    assert got["relayout"] == pytest.approx(by["%copy.166"] / 1e9)
    assert got["slots"] == pytest.approx(
        (by["%fusion.1"] + by["%add_any.58"] + by["%fusion.48"]
         + by["%copy-done.33"]) / 1e9)
    # a window that cuts the first kernel in half counts half of it
    half = evs[0][1] + evs[0][2] // 2
    cut = M.sums(evs, (half, evs[0][1] + evs[0][2]), dims)
    assert cut["grouped_matmul"] == pytest.approx(
        (evs[0][2] - evs[0][2] // 2) / 1e9) and cut["calls"] == 1

    # the readers, on a run made of the recorded step
    busy = sum(d for _, _, d in evs) / 1e9

    class Ctx:
        config = cfg

    trace = {"devices": {"/device:TPU:0": [
        [T.op_name(t), s, d] for t, s, d in evs]}, "host": []}
    run = {"record": {"trace_path": "recorded", "batch": 1,
                      "traced": {"steps": 1}, "devices": [object()],
                      "values": {"train_samples_per_s": 8.75}},
           "ctx": Ctx, "trace": trace, "tracemod": T,
           "trace_summary": {"busy_s": busy}, "detail": {},
           "peaks": harness.peaks_for("TPU v5 lite"),
           "flops": harness.load_module(".", "flops")}
    M._loaded["recorded"] = evs
    try:
        read = lambda n: harness.load_module("layer_metrics", n).read(run)  # noqa
        layer_s = got["grouped_matmul"] + got["relayout"] + got["slots"]
        assert read("moe_device_share_pct") == pytest.approx(
            100.0 * layer_s / busy)
        assert read("moe_permute_device_ms") == pytest.approx(
            1e3 * (got["relayout"] + got["slots"]))
        share = read("moe_grouped_matmul_roofline")
        assert share == pytest.approx(
            100.0 * 2 * 0.69766e-3 / got["grouped_matmul"], rel=1e-3)
        assert 0 < share < 100
        note = run["detail"]["moe_grouped_matmul_roofline"]
        assert note["roof"] == "compute" and note["calls"] == 2
        assert note["calls_a_layer_a_step"] == 1.0   # 2 calls, 2 layers
        # 6.25 TFLOP a sample x 8.75 samples/s over 197 TFLOP/s
        assert read("mfu_active_pct") == pytest.approx(27.76, abs=0.05)
    finally:
        M._loaded.pop("recorded")

    # nothing to read: no trace, or a configuration with no expert layer
    run["detail"] = {}
    assert M.of_run(dict(run, trace=None)) is None

    class Dense:
        config = harness.load_json("configs", "gpt2-medium")

    dense = dict(run, ctx=Dense, detail={})
    for name in ("moe_device_share_pct", "moe_grouped_matmul_roofline",
                 "moe_permute_device_ms", "mfu_active_pct"):
        assert harness.load_module("layer_metrics", name).read(dense) is None


# ---------------------------------------------------------------------------
# AOT: the cell's real step, compiled for a described v5e


def test_aot_olmoe_train_step_fits_one_v5e():
    """One sequence of 4096 tokens through 2 layers at the published widths
    fits one chip without recomputation (PERF.md, PR 26, has the bytes);
    the compiled step holds the three flash kernels once a layer and nine
    grouped matmul kernels a layer (three forward, six backward: none
    launched twice), and each attention grad op reused its forward."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib.util

    from jax.experimental import topologies

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

    try:
        v5e = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the compile helper of the file beside this one (tests/benchmarks is
    # no package, and that file is not this PR's to edit)
    spec = importlib.util.spec_from_file_location(
        "bench_test_benchmark", os.path.join(HERE, "test_benchmark.py"))
    tb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tb)
    cfg = harness.load_json("configs", CONFIG)
    batch = harness.load_json("traffic", "train_staged_bs1")["batch"]
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((batch, cfg["max_position_embeddings"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT olmoe train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 12 * 10 ** 9, got
    layers = cfg["num_hidden_layers"]
    # 3 flash kernels + 9 grouped matmuls a layer (+ their metadata calls)
    assert got["mosaic_calls"] >= 12 * layers, got
    fam = obs.REGISTRY.snapshot()["families"]
    reused = {s["labels"]["reused"]: s["value"] for s in fam[
        "executor_grad_kernel_forward_total"]["series"]}
    assert reused == {"1": float(layers)}
    (series,) = fam["moe_layers_traced_total"]["series"]
    assert series["labels"] == {"top_k": "8", "experts": "64",
                                "impl": "ragged_dot"}
    assert series["value"] == float(layers)
