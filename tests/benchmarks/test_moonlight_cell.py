"""What PR 30 adds to the benchmark, checked on the CPU: the Moonlight
program (one chip's share) against its plain reference at a toy size
(through the cell's own driver), the reference's tolerances against mutants
of the reference, the counts of benchmarks/flops_mla.py by hand, the share's
reduction and the seven readers on recorded instructions, and the AOT
compile of the cell's real step for a described v5e.
tests/benchmarks/test_benchmark.py holds the manifest-wide rules over the
same files.
"""

from __future__ import annotations

import copy
import importlib.util
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

CONFIG = "moonlight-16b-a3b"
CELL = "moonlight_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("mla_flash_fwd_roofline", "mla_flash_bwd_dq_roofline",
           "mla_flash_bwd_dkv_roofline", "mfu_local_pct",
           "moe_share_grouped_matmul_roofline", "moe_share_device_pct",
           "moe_shared_expert_device_ms")
MUTANTS = {  # mutant of the reference -> the key that has to catch it
    "no_bias": "router_weights", "no_scale": "router_weights",
    "bias_in_weight": "router_weights", "softmax": "router_weights",
    "sqrt128": "grad_12", "rope_all": "grad_12", "no_shared": "grad_25",
    "dropped_pair": "dropped_pairs", "fp8": "grad_12",
    "bf16_inside": "expert_counts"}


def _toy_config(dtype="float32"):
    """Hidden 64, 4 heads of 24 / 16 over a latent of 16, a dense layer of
    96 and two expert layers of 16 experts of 32 with 3 a token, experts 4
    to 7 held in a buffer of 64 rows, 2 shared, T 32; weights of scale 0.3
    so that every part moves the result."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=96, moe_intermediate_size=32,
               num_experts_per_tok=3, max_position_embeddings=32,
               vocab_size=97, num_hidden_layers=3, n_routed_experts=4)
    cfg["share"].update(first_expert=4, buffer_rows=64)
    cfg["train"]["args"].update(
        seq_len=32, vocab_size=97, dim=64, n_layers=3, n_heads=4,
        kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, dense_dim=96,
        num_experts=16, expert_dim=32, top_k=3, held_experts=4,
        first_expert=4, buffer_rows=64, dtype=dtype, init_scale=0.3,
        learning_rate=0.003, bias_init_scale=0.05)
    cfg["train"]["feeds"]["tokens"].update(shape=[32, 1], high=97)
    return cfg


def _ctx(config, traffic, tmp_path):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic,
        seed=2 ** 31 + 30, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


def _toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    t.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
             trace_seconds=0.2)
    return t


# ---------------------------------------------------------------------------
# the manifest, the configuration, the reference's independence


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert {"dispatch_ms.train", "step_device_ms.train",
            "device_idle_pct.train", "executor_run_ms.train",
            "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
            "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
            "idle_in_dispatch_pct.train", "kernel_forward_reruns",
            "compile_s", "cache_misses"} <= per
    # the seven readers this cell brought, and the counter of the flash
    # kernels' score elements, which counts this cell's calls as any other's
    assert set(READERS) | {"flash_scores_computed_pct"} <= per
    # one head size, OLMoE's key names, flops_moe.py: not this cell's
    assert not per & {"mfu_pct", "mfu_active_pct", "flash_fwd_roofline",
                      "moe_device_share_pct", "collective_exposed_ms"}
    # the cell, its configuration and its name in each list are there
    # exactly once; WHERE in a list is the driver's business, never a
    # test's (benchmarks/README.md): a later cell is appended after them
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    traffic = harness.load_json("traffic", TRAFFIC)
    base = harness.load_json("traffic", "train_staged_bs1")
    differs = {k for k in base if base[k] != traffic[k]}
    assert differs == {"name", "about", "loss_read_every", "loss_fell_step",
                       "trace_seconds"}
    assert (traffic["loss_read_every"], traffic["loss_fell_step"],
            traffic["trace_seconds"]) == (8, 32, 3)


@pytest.mark.parametrize("name", READERS + ("flash_scores_computed_pct",))
def test_each_reader_of_the_cell_is_listed_for_it(name):
    """PR 32: the seven readers PR 30 brought have their entries, and the
    flash kernels' counter lists the cell; so the cell's traced line, and
    the ledger's, carry the numbers that say where its step goes."""
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert CELL in entry["workloads"]
    assert entry["moves"] == "train_samples_per_s"
    assert callable(harness.load_module("layer_metrics", name).read)


PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}


def test_config_keeps_every_published_width():
    """The catalog's `config` for Moonlight-16B-A3B, key for key; only the
    depth, the experts held and the vocabulary slice differ, `reduced`
    says so, and each stays within the floors (a dense layer + at least 4,
    at least 8 experts, at least 1/8 of the vocabulary)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = [json.loads(x) for x in f if '"Moonlight-16B-A3B"' in x][0]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == harness.load_json(
            "configs", CONFIG)["source"]
    cfg = harness.load_json("configs", CONFIG)
    differs = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert cfg["num_hidden_layers"] >= cfg["first_k_dense_replace"] + 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["kv_rank"], a["qk_nope_dim"],
            a["qk_rope_dim"], a["v_dim"], a["dense_dim"], a["expert_dim"],
            a["num_experts"], a["top_k"], a["shared_experts"],
            a["routed_scale"], a["rope_theta"], a["norm_epsilon"],
            a["dense_layers"], a["seq_len"]) == (
        2048, 16, 512, 128, 64, 128, 11264, 1408, 64, 6, 2, 2.446, 50000.0,
        1e-05, 1, 8192)
    assert (a["n_layers"], a["held_experts"], a["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["n_routed_experts"],
        cfg["vocab_size"])
    dep, share = cfg["deployment"], cfg["share"]
    assert dep["router_outputs"] == a["num_experts"] == 64
    assert dep["experts_held"] == [a["first_expert"], a["first_expert"]
                                   + a["held_experts"]]
    assert share["first_expert"] == a["first_expert"]
    assert share["buffer_rows"] == a["buffer_rows"]
    assert share["buffer_rows"] % 256 == 0        # the backward kernels' tile
    assert cfg["train"]["feeds"]["tokens"]["high"] == cfg["vocab_size"]
    assert cfg["tokens_per_sample"] == cfg["max_position_embeddings"]
    assert set(cfg["assumed"]) >= {"rope_form", "bias_update_speed",
                                   "seq_aux_weight", "selection_bias_init"}


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "ragged" not in code
    assert "pallas" not in code and "argsort" not in code
    assert "import harness" not in code and "sort(" not in code


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_driver_toy_moonlight_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain reference on the same
    seeded weights: the loss, every token's loss, the last layer's top-k
    weights, its 64 (here 16) counts and their exact sum, the pairs on
    held experts, none dropped, and every GRAD_PARAMS gradient; and the
    run is `correct` (the loss fell, nothing compiled in the window)."""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(_toy_config("float32"), _toy_traffic(), tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    for exact in ("routed_pairs", "held_pairs", "dropped_pairs",
                  "expert_counts"):
        assert errs[exact] == 0.0
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
    main.random_seed = startup.random_seed = 30
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    # creation order is the order the reference documents
    D, E, held, H, V, F, S = 64, 16, 4, 32, 97, 96, 64
    mla = [(D,), (D, 4 * 24), (D, 16 + 8), (16,), (16, 4 * 32), (D, D), (D,)]
    dense = mla + [(D, F), (D, F), (F, D)]
    expert = mla + [(D, E), (held, D, H), (held, D, H), (held, H, D), (E,),
                    (D, S), (D, S), (S, D)]
    assert [tuple(p.shape) for p in params] == (
        [(V, D)] + dense + expert * 2 + [(D,), (D, V)])
    assert (len(dense), len(expert)) == (ref.PER_DENSE, ref.PER_EXPERT)
    # GRAD_PARAMS name what the reference's comment says they name
    named = {12: (D, 96), 13: (D, 24), 15: (16, 128), 18: (D, E),
             19: (held, D, H), 21: (held, H, D), 25: (S, D), 10: (F, D),
             -2: (D,)}
    assert set(named) == set(ref.GRAD_PARAMS)
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, 32), 0, V)
        tgt = jnp.roll(tok, -1, axis=1)
        want = ref.check_fn(ps, tok, tgt, cfg)
    return ref, cfg, ps, tok, tgt, want


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_moonlight_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself: a
    missing selection bias, a missing 2.446, score + bias used as the
    weight, softmax in place of sigmoid, sqrt(128) in place of sqrt(192),
    RoPE on all 192 (here 24) columns, a dropped shared expert, one pair
    the buffer had no row for, every matmul in fp8 (the nearest precision
    below the stated bf16), and float32 matmuls around norms, RoPE, softmax
    and router in bf16 (below the float32 stated for THEM) must each fail,
    by the key named."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, cfg, ps, tok, tgt, want = toy_case
    with jax.enable_x64(False):
        got = ref.check_fn(ps, tok, tgt, cfg, mutant)
    errors = drv.reference_errors(got, want, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    assert MUTANTS[mutant] in failed, errors
    if mutant == "dropped_pair":
        assert float(got["dropped_pairs"][0]) == 1.0
        assert float(want["dropped_pairs"][0]) == 0.0
        assert float(got["routed_pairs"][0]) == 32 * 3
    elif mutant not in ("fp8", "bf16_inside"):
        assert errors[MUTANTS[mutant]] > 3 * ref.TOL[MUTANTS[mutant]], errors


def test_the_unmutated_reference_passes_itself_and_counts_exactly(toy_case):
    ref, cfg, ps, tok, tgt, want = toy_case
    counts = np.asarray(want["expert_counts"])
    assert counts.shape == (16,) and counts.sum() == 32 * 3
    assert float(want["routed_pairs"][0]) == 32 * 3
    assert float(want["held_pairs"][0]) == counts[4:8].sum()
    np.testing.assert_allclose(np.asarray(want["router_weights"]).sum(-1),
                               2.446, rtol=1e-5)


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_mla_flash_cost_by_hand():
    F = harness.load_module(".", "flops_mla")
    dense = harness.load_module(".", "flops")
    # one head, T 4, queries and keys 3 wide, values 2: whole square
    assert F.mla_flash_cost(1, 1, 4, 3, 2, "fwd", causal=False) == (
        2.0 * 16 * (3 + 2), 2.0 * 4 * (2 * 3 + 2 * 2))
    assert F.mla_flash_cost(1, 1, 4, 3, 2, "bwd_dq", causal=False) == (
        2.0 * 16 * (2 * 3 + 2), 2.0 * 4 * (3 * 3 + 2 * 2))
    assert F.mla_flash_cost(1, 1, 4, 3, 2, "bwd_dkv", causal=False) == (
        2.0 * 16 * (2 * 3 + 2 * 2), 2.0 * 4 * (3 * 3 + 3 * 2))
    # equal widths are flops.py's count, kind for kind
    for kind in ("fwd", "bwd_dq", "bwd_dkv"):
        assert F.mla_flash_cost(8, 16, 1024, 64, 64, kind) == (
            dense.flash_attention_cost(8, 16, 1024, 64, kind))
    # the cell's: compute-bound on the v5e, 1.74 / 2.79 / 3.49 ms a call,
    # and a value padded to 192 would cost an eighth to a fifth more
    peaks = harness.peaks_for("TPU v5 lite")
    least = {}
    for kind in ("fwd", "bwd_dq", "bwd_dkv"):
        flops, nbytes = F.mla_flash_cost(1, 16, 8192, 192, 128, kind)
        least[kind], roof = dense.roofline_seconds(flops, nbytes, peaks)
        assert roof == "compute"
        padded = dense.flash_attention_cost(1, 16, 8192, 192, kind)[0]
        assert 1.12 < padded / flops < 1.21
    assert least["fwd"] == pytest.approx(1.7441e-3, rel=1e-3)
    assert least["bwd_dq"] == pytest.approx(2.7906e-3, rel=1e-3)
    assert least["bwd_dkv"] == pytest.approx(3.4883e-3, rel=1e-3)


def test_share_train_flops_by_hand():
    F = harness.load_module(".", "flops_mla")
    # hidden 4, 2 heads of (2 + 2) / 3 over a latent of 5, one dense layer
    # of 7, one expert layer of 8 experts of 6 with 4 held, 2 a token, one
    # shared, vocabulary 9, T 2
    attn = 2 * (4 * 2 * 4 + 4 * (5 + 2) + 5 * 2 * (2 + 3) + 2 * 3 * 4) \
        + 2 * 2 * (4 + 3)
    per_token = (2 * attn + 3 * 2 * 4 * 7
                 + 2 * 4 * 8 + 3 * 2 * 4 * 6 + 2 * 4 / 8 * 3 * 2 * 4 * 6
                 + 2 * 4 * 9)
    assert F.mla_moe_share_train_flops_per_sample(
        dim=4, n_heads=2, kv_rank=5, qk_nope_dim=2, qk_rope_dim=2, v_dim=3,
        dense_layers=1, dense_dim=7, expert_layers=1, num_experts=8,
        held_experts=4, expert_dim=6, top_k=2, shared_experts=1, vocab=9,
        seq_len=2) == 3.0 * per_token * 2
    # the cell: 21.6 TFLOP a step, of which the issue's parts
    cfg = harness.load_json("configs", CONFIG)
    a = cfg["flops_mla"]["args"]
    got = getattr(F, cfg["flops_mla"]["function"])(**a)
    assert 21.5e12 < got < 21.7e12
    t = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["kv_rank"], a["dense_dim"],
            a["num_experts"], a["held_experts"], a["expert_dim"],
            a["top_k"], a["shared_experts"], a["vocab"], a["seq_len"]) == (
        t["dim"], t["n_heads"], t["kv_rank"], t["dense_dim"],
        t["num_experts"], t["held_experts"], t["expert_dim"], t["top_k"],
        t["shared_experts"], t["vocab_size"], t["seq_len"])
    assert a["dense_layers"] + a["expert_layers"] == t["n_layers"]
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048
    assert mla == 13_762_560                       # the issue's 13.76 M
    scores = 8192 * 16 * 320
    expert = (2 * 2048 * 64 + 6 * 2048 * 2816
              + 0.75 * 6 * 2048 * 1408)
    assert got == 3.0 * 8192 * (6 * (2 * mla + scores)
                                + 6 * 2048 * 11264 + 5 * expert
                                + 2 * 2048 * 20480)


# ---------------------------------------------------------------------------
# AOT: the cell's real step, compiled for a described v5e


def test_aot_moonlight_train_step_fits_one_v5e():
    """One sequence of 8192 tokens through the dense layer and 5 expert
    layers at the published widths fits one chip without recomputation and
    fills more than a quarter of it (PERF.md, PR 30, has the bytes); the
    compiled step holds the three flash kernels once a layer and nine
    grouped matmul kernels an expert layer (three forward, six backward:
    none launched twice), each attention grad op reused its forward, every
    grouped backward is the Pallas pair, and the two new counter families
    read what was built."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
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
    batch = harness.load_json("traffic", TRAFFIC)["batch"]
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
    print("AOT moonlight train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.25 * 16 * 2 ** 30, got
    # weights and Adam state alone: 669 M parameters at 10 bytes
    assert 6.6e9 < got["argument_bytes"] < 6.8e9, got
    layers = cfg["num_hidden_layers"]
    expert_layers = layers - cfg["first_k_dense_replace"]
    # 3 flash kernels a layer + 9 grouped matmuls an expert layer
    assert got["mosaic_calls"] >= 3 * layers + 9 * expert_layers, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: [(s["labels"], s["value"])  # noqa: E731
                           for s in fam[name]["series"]]
    assert series("executor_grad_kernel_forward_total") == [
        ({"op": "latent_attention", "reused": "1"}, float(layers))]
    assert series("mla_layers_traced_total") == [
        ({"qk_dim": "192", "v_dim": "128", "kv_rank": "512"}, float(layers))]
    assert series("moe_share_layers_traced_total") == [
        ({"held": "8", "experts": "64", "top_k": "6",
          "buffer_rows": str(cfg["share"]["buffer_rows"])},
         float(expert_layers))]
    assert series("moe_grouped_backward_total") == [
        ({"impl": "pallas"}, 3.0 * expert_layers)]
    assert not fam.get("moe_layers_traced_total", {"series": []})["series"]
    squares = {s[0]["kernel"]: s[1]
               for s in series("flash_score_elements_total")
               if s[0]["part"] == "square"}
    assert squares == {k: layers * 16.0 * 8192 * 8192 for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}


# ---------------------------------------------------------------------------
# the share in a trace: recorded instructions


def _recorded():
    with open(os.path.join(HERE, "recorded_moe_share_ops.json"),
              encoding="utf-8") as f:
        return json.load(f)["events"]


def test_classify_on_recorded_instructions():
    M = harness.load_module("reduce", "moe_share_ops")
    cfg = harness.load_json("configs", CONFIG)
    dims = M.dims_of(cfg, batch=1)
    assert dims == {"tokens": 8192, "rows": 12288, "pairs": 49152, "held": 8,
                    "dim": 2048, "expert_dim": 1408, "shared_dim": 2816}
    kinds = {t.split(" = ", 1)[0]: M.classify(t, dims)
             for t, _, _ in _recorded()}
    assert kinds == {
        "%ragged-dot-none.13": "grouped_matmul",       # forward
        "%ragged-dot-drhs.44": "grouped_matmul",       # dW, Pallas
        "%ragged-dot-dlhs.30": "grouped_matmul",       # dX, Pallas
        "%ragged-dot-metadata.4": "grouped_matmul",
        "%multiply_convert_fusion.4": "buffer",        # the weighting
        "%add_select_fusion.4": "buffer",              # dX of gate + up
        "%convert_multiply_fusion.21": "buffer",
        "%broadcast_select_fusion.14": "buffer",       # rows past the work
        "%sort.17": "pairs",                           # by held expert
        "%convert_reduce_fusion.4": "pairs",           # the 64 counts
        "%fusion.1257": "shared",                      # x Wgate_shared
        "%convolution_convert_fusion.9": "shared",
        "%convolution_add_fusion.11": "shared",
        # dW of the shared expert arrives fused with Adam's update of it
        "%subtract_convert_fusion.32": "shared",
        "%flash_fwd.6": None, "%flash_bwd_dq.6": None,
        "%flash_bwd_dkv.6": None,
        "%subtract_convert_fusion.25": None,           # RoPE's backward
        "%convolution_bitcast_fusion.11": None,        # x Wq
        # the router's [8192, 64] scores share RoPE's tables' shape
        "%multiply_reduce_fusion.6": None,
        "%maximum_bitcast_fusion": None, "%copy.667": None}
    # a configuration without a share finds nothing to look for
    assert M.dims_of(harness.load_json("configs", "olmoe-1b-7b"), 1) is None
    assert M.dims_of(harness.load_json("configs", "gpt2-medium"), 8) is None
    # OLMoE's shapes are not this one's
    other = dict(dims, rows=32768, pairs=32768, tokens=4096, shared_dim=0)
    assert M.classify("%fusion.1 = bf16[12288,2048]{1,0} fusion()",
                      other) is None


def test_sums_by_hand_and_the_seven_readers():
    M = harness.load_module("reduce", "moe_share_ops")
    T = harness.load_module("reduce", "trace")
    cfg = harness.load_json("configs", CONFIG)
    dims = M.dims_of(cfg, batch=1)
    evs = _recorded()
    end = evs[-1][1] + evs[-1][2]
    by = {t.split(" = ", 1)[0]: d for t, _, d in evs}
    got = M.sums(evs, (0, end), dims)
    assert got["calls"] == 3                       # the metadata call is none
    assert got["grouped_matmul"] == pytest.approx(
        (by["%ragged-dot-none.13"] + by["%ragged-dot-drhs.44"]
         + by["%ragged-dot-dlhs.30"] + by["%ragged-dot-metadata.4"]) / 1e9)
    assert got["buffer"] == pytest.approx(
        (by["%multiply_convert_fusion.4"] + by["%add_select_fusion.4"]
         + by["%convert_multiply_fusion.21"]
         + by["%broadcast_select_fusion.14"]) / 1e9)
    assert got["pairs"] == pytest.approx(
        (by["%sort.17"] + by["%convert_reduce_fusion.4"]) / 1e9)
    assert got["shared"] == pytest.approx(
        (by["%fusion.1257"] + by["%convolution_convert_fusion.9"]
         + by["%convolution_add_fusion.11"]
         + by["%subtract_convert_fusion.32"]) / 1e9)
    # a window that cuts the first kernel in half counts half of it
    half = evs[0][1] + evs[0][2] // 2
    cut = M.sums(evs, (half, evs[0][1] + evs[0][2]), dims)
    assert cut["grouped_matmul"] == pytest.approx(
        (evs[0][2] - evs[0][2] // 2) / 1e9) and cut["calls"] == 1

    # the readers, on a run made of the recorded events
    busy = sum(d for _, _, d in evs) / 1e9

    class Ctx:
        config = cfg

    trace = {"devices": {"/device:TPU:0": [
        [T.op_name(t), s, d] for t, s, d in evs]}, "host": []}
    run = {"record": {"trace_path": "recorded-share", "batch": 1,
                      "traced": {"steps": 1}, "devices": [object()],
                      "values": {"train_samples_per_s": 4.03}},
           "ctx": Ctx, "trace": trace, "tracemod": T,
           "trace_summary": {"busy_s": busy}, "detail": {},
           "peaks": harness.peaks_for("TPU v5 lite"),
           "flops": harness.load_module(".", "flops")}
    O = harness.load_module("reduce", "moe_ops")
    O._loaded["recorded-share"] = evs
    try:
        read = lambda n: harness.load_module("layer_metrics", n).read(run)  # noqa
        assert read("moe_share_device_pct") == pytest.approx(
            100.0 * (got["grouped_matmul"] + got["buffer"] + got["pairs"])
            / busy)
        assert read("moe_shared_expert_device_ms") == pytest.approx(
            1e3 * got["shared"])
        # least time of a product: 6144 rows with work through [2048 x
        # 1408], compute-bound: 35.4 GFLOP over 197 TFLOP/s = 0.180 ms
        share = read("moe_share_grouped_matmul_roofline")
        assert share == pytest.approx(
            100.0 * 3 * 0.17985e-3 / got["grouped_matmul"], rel=1e-3)
        assert 0 < share < 100
        note = run["detail"]["moe_share_grouped_matmul_roofline"]
        assert (note["roof"], note["rows_with_work"], note["buffer_rows"],
                note["calls"]) == ("compute", 6144, 12288, 3)
        # the three flash kernels at 192 / 128: least 1.744 / 2.791 / 3.488
        for name, kernel, least in (
                ("mla_flash_fwd_roofline", "%flash_fwd.6", 1.7441e-3),
                ("mla_flash_bwd_dq_roofline", "%flash_bwd_dq.6", 2.7906e-3),
                ("mla_flash_bwd_dkv_roofline", "%flash_bwd_dkv.6",
                 3.4883e-3)):
            assert read(name) == pytest.approx(
                100.0 * least / (by[kernel] / 1e9), rel=1e-3)
            assert 0 < read(name) < 100
        assert run["detail"]["mla_flash_fwd_roofline"]["roof"] == "compute"
        # 21.585 TFLOP a sample x 4.03 samples/s over 197 TFLOP/s
        assert read("mfu_local_pct") == pytest.approx(44.16, abs=0.05)
    finally:
        O._loaded.pop("recorded-share")

    # nothing to read: no trace, or a configuration with one head width
    # and no share (what the parent, or another cell, gives these readers)
    assert M.of_run(dict(run, trace=None, detail={})) is None

    class Dense:
        config = harness.load_json("configs", "olmoe-1b-7b")

    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(
            dict(run, ctx=Dense, detail={})) is None


def test_the_seven_readers_say_what_their_entries_will():
    """Each file carries its entry's unit, direction, source and layer,
    and since PR 32 (which lifted the pin that kept them out, PERF.md
    section 6) the manifest lists all seven: each entry agrees with its
    file and names this cell."""
    m = harness.load_manifest()
    listed = {x["name"]: x for x in m["per_layer"]}
    for name in READERS:
        mod = harness.load_module("layer_metrics", name)
        assert mod.__doc__.startswith(name) and callable(mod.read)
        assert (mod.UNIT == "%") == (name.endswith(("_roofline", "_pct")))
        assert mod.BETTER in ("lower", "higher")
        assert mod.SOURCE in ("device_trace", "host_clock")
        assert mod.LAYER in {x["layer"] for x in m["per_layer"]}
        entry = listed[name]
        assert CELL in entry["workloads"]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER,
            "train_samples_per_s")
