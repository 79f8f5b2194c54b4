"""What PR 33 adds to the benchmark, checked on the CPU: the LFM2 program
(one chip's share) against its plain reference at a toy size (through the
cell's own driver), the reference's tolerances against mutants of the
reference, the counts of benchmarks/flops_lfm2.py by hand, the share's
reduction and the seven readers on recorded instructions, and the AOT
compile of the cell's real step for a described v5e.
tests/benchmarks/test_benchmark.py holds the manifest-wide rules over the
same files; a test that reads BENCHMARK.json as a whole is named
`test_manifest...` and holds membership and content, never position.
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

CONFIG = "lfm2-24b-a2b"
CELL = "lfm2_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("gqa_flash_fwd_roofline", "gqa_flash_bwd_dq_roofline",
           "gqa_flash_bwd_dkv_roofline", "short_conv_hbm_roofline",
           "short_conv_device_ms", "expert_share_device_pct",
           "expert_share_grouped_matmul_roofline")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "kernel_forward_reruns",
         "flash_scores_computed_pct")
MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "fp8": "grad_10", "kv_mod": "grad_11", "dk_one_head": "grad_11",
    "taps_reversed": "grad_3", "conv_future": "token_loss",
    "no_C": "grad_2", "no_B": "grad_2", "qk_norm_whole": "grad_10",
    "rope_before_norm": "grad_13", "rope_in_conv": "grad_3",
    "scale_sqrt256": "grad_10", "no_bias": "router_weights",
    "bias_in_weight": "router_weights", "softmax": "router_weights",
    "dropped_pair": "dropped_pairs"}
TOY_LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]


def _toy_config(dtype="float32"):
    """Hidden 64, 8 query heads on 2 key/value heads of 8, layers conv /
    attention / conv / conv / conv, a dense layer of 96 and four expert
    layers of 8 experts of 16 with 4 a token, experts 2 and 3 held in a
    buffer of 96 rows, T 64; weights of scale 0.3 so that every part moves
    the result."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               intermediate_size=96, moe_intermediate_size=16,
               vocab_size=97, num_hidden_layers=5, num_experts=2,
               layer_types=list(TOY_LAYERS))
    cfg["share"].update(first_expert=2, buffer_rows=96)
    cfg["train"]["args"].update(
        seq_len=64, vocab_size=97, dim=64, layer_types=list(TOY_LAYERS),
        n_heads=8, n_kv_heads=2, dense_dim=96, num_experts=8, expert_dim=16,
        held_experts=2, first_expert=2, buffer_rows=96, dtype=dtype,
        init_scale=0.3, learning_rate=0.003, bias_init_scale=0.05)
    cfg["train"]["feeds"]["tokens"].update(shape=[64, 1], high=97)
    return cfg


def _ctx(config, traffic, tmp_path):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic,
        seed=2 ** 31 + 33, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


def _toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    t.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
             trace_seconds=0.2)
    return t


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_driver_toy_lfm2_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain reference on the same
    seeded weights: the loss, every token's loss, the last layer's top-k
    weights, its counts and their exact sum, the pairs on held experts,
    none dropped, and every GRAD_PARAMS gradient; and the run is `correct`
    (the loss fell, nothing compiled in the window)."""
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
    main.random_seed = startup.random_seed = 33
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    # creation order is the order the reference documents
    D, E, held, H, V, F, d = 64, 8, 2, 16, 97, 96, 8
    conv = [(D,), (D, 3 * D), (D, 3), (D, D)]
    attn = [(D,), (D, D), (D, 2 * d), (D, 2 * d), (d,), (d,), (D, D)]
    dense = [(D,), (D, F), (D, F), (F, D)]
    expert = [(D,), (D, E), (held, D, H), (held, D, H), (held, H, D), (E,)]
    assert [tuple(p.shape) for p in params] == (
        [(V, D)] + conv + dense + attn + expert + (conv + expert) * 3
        + [(D,), (D, V)])
    assert (len(conv), len(attn)) == (ref.PER_OP["conv"],
                                      ref.PER_OP["full_attention"])
    assert (len(dense), len(expert)) == (ref.PER_DENSE, ref.PER_EXPERT)
    # GRAD_PARAMS name what the reference's comment says they name
    named = {2: (D, 3 * D), 3: (D, 3), 4: (D, D), 10: (D, D),
             11: (D, 2 * d), 12: (D, 2 * d), 13: (d,), 14: (d,), 17: (D, E),
             18: (held, D, H), 20: (held, H, D), 8: (F, D), -2: (D,)}
    assert set(named) == set(ref.GRAD_PARAMS)
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, 64), 0, V)
        tgt = jnp.roll(tok, -1, axis=1)
        want = ref.check_fn(ps, tok, tgt, cfg)
    return ref, cfg, ps, tok, tgt, want


def test_every_mutant_of_the_reference_is_held():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS)


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_lfm2_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself:
    every matmul in fp8 (the nearest precision below the stated bf16: the
    control), key/value head h % Hkv for h // group, dk and dv from one
    query head of a group only, the taps reversed, a convolution that sees
    t + 1, either gate left out, OLMoE's whole-projection QK-norm, RoPE
    before the QK-norm, RoPE in a convolution layer, sqrt(hidden / Hkv) for
    the scale, a missing selection bias, score + bias used as the weight,
    softmax in place of sigmoid and one pair the buffer had no row for
    must each fail, by the key named."""
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
        assert float(got["routed_pairs"][0]) == 64 * 4


def test_the_unmutated_reference_passes_itself_and_counts_exactly(toy_case):
    ref, cfg, ps, tok, tgt, want = toy_case
    counts = np.asarray(want["expert_counts"])
    assert counts.shape == (8,) and counts.sum() == 64 * 4
    assert float(want["routed_pairs"][0]) == 64 * 4
    assert float(want["held_pairs"][0]) == counts[2:4].sum()
    # renormalised over the sum + 1e-6, scale 1
    np.testing.assert_allclose(np.asarray(want["router_weights"]).sum(-1),
                               1.0, rtol=1e-5)


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
    assert set(LISTS) | set(READERS) | {"compile_s", "cache_misses"} <= per
    # one head count, DeepSeek's or OLMoE's key names: not this cell's
    assert not per & {"flash_fwd_roofline", "mla_flash_fwd_roofline",
                      "moe_share_device_pct", "moe_device_share_pct",
                      "mfu_local_pct", "mfu_active_pct",
                      "collective_exposed_ms"}
    # there exactly once; WHERE in a list is the driver's business
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json("configs", CONFIG)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    # at most a quarter of the cells, rounded down, take four chips
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    """Each file carries its entry's unit, direction, source and layer; the
    entry agrees with its file and names this cell."""
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__.startswith(name) and callable(mod.read)
    assert (mod.UNIT == "%") == (name.endswith(("_roofline", "_pct")))
    assert entry["workloads"].count(CELL) == 1
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, "train_samples_per_s")
    assert mod.LAYER in {x["layer"] for x in m["per_layer"]
                         if x["name"] not in READERS}


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return None
    with open(catalog, encoding="utf-8") as f:
        return [json.loads(x) for x in f if '"LFM2-24B-A2B"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for LFM2-24B-A2B, key for key; only the
    depth, the layer pattern's cut, the leading dense layers (counted
    once), the experts held and the vocabulary slice differ, `reduced`
    says so, and each stays within the floors (a dense layer + at least 4
    in whole periods, at least 8 experts, at least 1/8 of the
    vocabulary)."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (40, 2, 64, 65536)
    assert len(pub["layer_types"]) == 40
    assert pub["layer_types"].count("full_attention") == 10
    # published layers 0 and 2-9: the leading dense layers once, then two
    # whole periods (conv, conv, conv, full_attention rotated)
    held = cfg["deployment"]["layers_held"]
    assert held == [0] + list(range(2, 10))
    assert cfg["layer_types"] == [pub["layer_types"][i] for i in held]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 9
    after = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert len(after) >= 4 and len(after) % 4 == 0
    assert after.count("full_attention") * 4 == len(after)
    assert cfg["num_dense_layers"] == 1
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["max_position_embeddings"]) == (
        2048, 32, 8, 3, 11776, 1536, 4, 128000)
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["n_kv_heads"], a["conv_kernel"],
            a["dense_dim"], a["expert_dim"], a["num_experts"], a["top_k"],
            a["routed_scale"], a["rope_theta"], a["norm_epsilon"],
            a["renorm_epsilon"], a["dense_layers"], a["seq_len"]) == (
        2048, 32, 8, 3, 11776, 1536, 64, 4, 1.0, 1000000.0, 1e-05, 1e-06,
        1, 8192)
    assert "balance_weight" not in a      # no auxiliary loss, and no knob
    assert (a["layer_types"], a["held_experts"], a["vocab_size"]) == (
        cfg["layer_types"], cfg["num_experts"], cfg["vocab_size"])
    dep, share = cfg["deployment"], cfg["share"]
    assert dep["router_outputs"] == a["num_experts"] == 64
    assert dep["experts_held"] == [a["first_expert"], a["first_expert"]
                                   + a["held_experts"]]
    assert share["first_expert"] == a["first_expert"]
    assert share["buffer_rows"] == a["buffer_rows"]
    assert share["buffer_rows"] % 256 == 0        # the backward kernels' tile
    # ISSUE 33's: twice what even routing puts here
    assert share["buffer_rows"] == 2 * 8192 * 4 * 8 // 64 == 8192
    assert cfg["train"]["feeds"]["tokens"]["high"] == cfg["vocab_size"]
    # T is the builder's argument, never max_position_embeddings
    assert cfg["tokens_per_sample"] == a["seq_len"] == cfg["train"]["feeds"][
        "tokens"]["shape"][0]
    assert set(cfg["assumed"]) >= {
        "bias_update_speed", "selection_bias_init", "auxiliary_loss", "head",
        "order_of_thirds_and_taps", "renorm_epsilon"}


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "ragged" not in code
    assert "pallas" not in code and "argsort" not in code
    assert "import harness" not in code and "sort(" not in code
    assert "conv_general" not in code and "repeat(" not in code
    ref = harness.load_module("reference", CONFIG)
    assert callable(ref.train_check) and callable(ref.control_check)


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_gqa_flash_cost_by_hand():
    F = harness.load_module(".", "flops_lfm2")
    dense = harness.load_module(".", "flops")
    # 4 query heads on 2 key/value heads, T 4, head size 3, whole square
    assert F.gqa_flash_cost(1, 4, 2, 4, 3, "fwd", causal=False) == (
        4 * 2.0 * 16 * 3 * 2, 2.0 * 4 * 3 * (2 * 4 + 2 * 2))
    assert F.gqa_flash_cost(1, 4, 2, 4, 3, "bwd_dq", causal=False) == (
        4 * 2.0 * 16 * 3 * 3, 2.0 * 4 * 3 * (3 * 4 + 2 * 2))
    assert F.gqa_flash_cost(1, 4, 2, 4, 3, "bwd_dkv", causal=False) == (
        4 * 2.0 * 16 * 3 * 4, 2.0 * 4 * 3 * (2 * 4 + 4 * 2))
    # equal head counts are flops.py's count, kind for kind
    for kind in ("fwd", "bwd_dq", "bwd_dkv"):
        assert F.gqa_flash_cost(8, 16, 16, 1024, 64, kind) == (
            dense.flash_attention_cost(8, 16, 1024, 64, kind))
    # the cell's: compute-bound on the v5e, 1.40 / 2.09 / 2.79 ms a call
    peaks = harness.peaks_for("TPU v5 lite")
    least = {}
    for kind in ("fwd", "bwd_dq", "bwd_dkv"):
        flops, nbytes = F.gqa_flash_cost(1, 32, 8, 8192, 64, kind)
        least[kind], roof = dense.roofline_seconds(flops, nbytes, peaks)
        assert roof == "compute"
        # K and V read once a key/value head: fewer bytes than 32 heads'
        assert nbytes < dense.flash_attention_cost(1, 32, 8192, 64, kind)[1]
    assert least["fwd"] == pytest.approx(1.3953e-3, rel=1e-3)
    assert least["bwd_dq"] == pytest.approx(2.0930e-3, rel=1e-3)
    assert least["bwd_dkv"] == pytest.approx(2.7906e-3, rel=1e-3)


def test_short_conv_cost_by_hand():
    F = harness.load_module(".", "flops_lfm2")
    dense = harness.load_module(".", "flops")
    # 2 x 5 tokens of 7 channels, 3 taps
    assert F.short_conv_cost(2, 5, 7, 3, "fwd") == (
        (2 * 3 + 2) * 10 * 7, 4.0 * 10 * 7 * 2)
    assert F.short_conv_cost(2, 5, 7, 3, "bwd") == (
        2 * (2 * 3 + 2) * 10 * 7, 7.0 * 10 * 7 * 2)
    # the cell's: 134 MB and 235 MB over 819 GB/s: 0.164 + 0.287 ms
    peaks = harness.peaks_for("TPU v5 lite")
    total = 0.0
    for kind, mb in (("fwd", 134.2), ("bwd", 234.9)):
        flops, nbytes = F.short_conv_cost(1, 8192, 2048, 3, kind)
        assert nbytes / 1e6 == pytest.approx(mb, abs=0.1)
        seconds, roof = dense.roofline_seconds(flops, nbytes, peaks)
        assert roof != "compute"
        total += seconds
    assert total == pytest.approx(0.4507e-3, rel=2e-3)


def test_share_train_flops_by_hand():
    F = harness.load_module(".", "flops_lfm2")
    # hidden 4, 2 query heads on 1 key/value head of 2, 2 conv layers and 1
    # attention layer, one dense layer of 7, two expert layers of 8 experts
    # of 6 with 4 held, 2 a token, vocabulary 9, T 2
    conv = 2 * (4 * 12 + 4 * 4)
    attn = 2 * (2 * 4 * 4 + 2 * 4 * 1 * 2) + 2 * 2 * 2 * 2
    per_token = (2 * conv + attn + 3 * 2 * 4 * 7
                 + 2 * (2 * 4 * 8 + 2 * 4 / 8 * 3 * 2 * 4 * 6) + 2 * 4 * 9)
    assert F.lfm2_share_train_flops_per_sample(
        dim=4, conv_layers=2, attention_layers=1, n_heads=2, n_kv_heads=1,
        dense_layers=1, dense_dim=7, expert_layers=2, num_experts=8,
        held_experts=4, expert_dim=6, top_k=2, vocab=9,
        seq_len=2) == 3.0 * per_token * 2
    # the cell: 14.7 TFLOP a step, 600 MFLOP a token forward
    cfg = harness.load_json("configs", CONFIG)
    assert cfg["flops"]["module"] == "flops_lfm2"
    a = cfg["flops"]["args"]
    got = harness.flops_per_sample(cfg)
    assert got == getattr(F, cfg["flops"]["function"])(**a)
    assert 14.7e12 < got < 14.8e12
    assert got / (3.0 * 8192) == pytest.approx(599.8e6, rel=1e-3)
    t = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["n_kv_heads"], a["dense_dim"],
            a["num_experts"], a["held_experts"], a["expert_dim"],
            a["top_k"], a["vocab"], a["seq_len"], a["dense_layers"]) == (
        t["dim"], t["n_heads"], t["n_kv_heads"], t["dense_dim"],
        t["num_experts"], t["held_experts"], t["expert_dim"], t["top_k"],
        t["vocab_size"], t["seq_len"], t["dense_layers"])
    kinds = t["layer_types"]
    assert (a["conv_layers"], a["attention_layers"]) == (
        kinds.count("conv"), kinds.count("full_attention"))
    assert a["dense_layers"] + a["expert_layers"] == len(kinds)
    # the issue's parts: operators 57%, dense MLP 24%, experts 13%, head 6%
    conv = 8 * 2048 * 2048
    attn = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 8192 * 32 * 128
    assert (7 * conv + 2 * attn) / 599.8e6 == pytest.approx(0.57, abs=0.01)
    assert 6 * 2048 * 11776 / 599.8e6 == pytest.approx(0.24, abs=0.01)


# ---------------------------------------------------------------------------
# the share and the convolution in a trace: recorded instructions


def _recorded():
    """([[text, start, duration]], {instruction name: hlo_scopes Note})."""
    H = harness.load_module("reduce", "hlo_scopes")
    with open(os.path.join(HERE, "recorded_lfm2_ops.json"),
              encoding="utf-8") as f:
        rec = json.load(f)
    return rec["events"], {
        name: H.Note(frozenset(n["scopes"]), n["own"], n["product_flops"])
        for name, n in rec["notes"].items()}


# the recorded instructions, and what each has to be read as: a kind of
# the routed part, 'conv' / 'product' of a convolution, or nothing
KINDS = {
    "ragged-dot-none.5": "grouped_matmul",        # forward
    "ragged-dot-drhs.62": "grouped_matmul",       # dW, Pallas
    "ragged-dot-dlhs.71": "grouped_matmul",       # dX, Pallas
    "ragged-dot-metadata.7": "grouped_matmul",
    # the buffer's, though every tensor in them has the stream's 8192
    # rows: the program puts them into the expert layer's scopes
    "fusion.169": "buffer",                       # the scatter-add back
    "fusion.177": "buffer",                       # the row gather's backward
    "fusion.873": "buffer",                       # the SiLU gate's dX
    # the two halves of an asynchronous copy carry no name: a tensor the
    # forward grouped matmul made
    "copy-done.112": "buffer",
    "fusion.179": "pairs",                        # the weights' gather
    # x W_in, widened to float32 in the product's epilogue: in no scope
    "convert_bitcast_fusion.6": None,
    "slice_multiply_fusion.5": "conv",            # B * u
    "fusion.757": "conv",                         # the taps, C * c
    # dOut W_out^T with dC and the taps' gradient in its epilogue
    "multiply_reduce_fusion.19": "product",
    "broadcast_multiply_fusion.4": "conv",        # the taps' backward
    "fusion.756": "conv",                         # backward: dB, du
    # a copy XLA makes of the convolution's result for its reader
    "copy-done.181": None,
    "subtract_convert_fusion.31": None,           # dW_in in Adam's update
    "flash_fwd.3": None, "flash_bwd_dq.3": None, "flash_bwd_dkv.3": None,
    "sort.4": None,                               # the router's top-k
    "subtract_convert_fusion.28": None,           # the dense MLP's dW
    # [8192, 2048] that are NOT the buffer's: the embedding's slice under
    # Adam, the residual stream in a pass no part names, and a copy of it
    "subtract_convert_fusion.30": None,
    "fusion.780": None,
    "copy-done.184": None}
CONV = tuple(k for k, v in KINDS.items() if v == "conv")
PRODUCT = "multiply_reduce_fusion.19"


def test_share_ops_on_recorded_instructions():
    S = harness.load_module("reduce", "share_ops")
    M = harness.load_module("reduce", "moe_share_ops")
    H = harness.load_module("reduce", "hlo_scopes")
    cfg = harness.load_json("configs", CONFIG)
    dims = S.dims_of(cfg, batch=1)
    # ISSUE 33's buffer: as long as the token stream
    assert dims == {"tokens": 8192, "rows": 8192, "pairs": 32768, "held": 8,
                    "experts": 64, "dim": 2048, "expert_dim": 1536,
                    "shared_dim": 0, "conv_kernel": 3}
    evs, notes = _recorded()
    assert set(notes) == set(KINDS) == {H.name_of(t) for t, _, _ in evs}
    kinds = {H.name_of(t): S.classify_conv(notes[H.name_of(t)])
             or S.classify(t, dims, notes[H.name_of(t)]) for t, _, _ in evs}
    assert kinds == KINDS
    # by its rows alone every instruction on the stream would be the
    # buffer's: the three that are not carry `[8192,` like the four that are
    by_rows = {H.name_of(t) for t, _, _ in evs
               if M.classify(t, dims) == "buffer"}
    assert by_rows >= {"fusion.169", "fusion.177", "fusion.873",
                       "copy-done.112", "subtract_convert_fusion.30",
                       "fusion.780", "copy-done.184"}
    # and without the program's word the reader counts none of them
    assert not [t for t, _, _ in evs
                if S.classify(t, dims, H.NOTHING) == "buffer"]
    # a buffer of another length is found by its rows, whatever the notes
    longer = dict(dims, rows=8448)
    text = "%fusion.1 = bf16[8448,2048]{1,0} fusion(s32[8448]{0} %p)"
    assert S.classify(text, longer, H.NOTHING) == "buffer"
    assert S.classify(evs[4][0], longer, notes["fusion.169"]) is None
    # K and V reach the kernels with 8 heads: never repeated in HBM
    (fwd,) = [t for t, _, _ in evs if t.startswith("%flash_fwd")]
    operands = fwd.split("custom-call(", 1)[1].split("), custom_call", 1)[0]
    assert operands.count("bf16[8,8192,64]") == 2
    assert operands.count("bf16[32,8192,64]") == 1
    # shapes come from train.args: Moonlight's share is described too
    # (its shared expert, no convolution), OLMoE and GPT-2 hold no share
    moon = S.dims_of(harness.load_json("configs", "moonlight-16b-a3b"), 1)
    assert (moon["rows"], moon["pairs"], moon["shared_dim"],
            moon["conv_kernel"]) == (12288, 49152, 2816, 0)
    assert S.dims_of(harness.load_json("configs", "olmoe-1b-7b"), 1) is None
    assert S.dims_of(harness.load_json("configs", "gpt2-medium"), 8) is None


def test_the_seven_readers_on_recorded_instructions():
    S = harness.load_module("reduce", "share_ops")
    T = harness.load_module("reduce", "trace")
    H = harness.load_module("reduce", "hlo_scopes")
    cfg = harness.load_json("configs", CONFIG)
    evs, notes = _recorded()
    by = {H.name_of(t): d for t, _, d in evs}
    busy = sum(d for _, _, d in evs) / 1e9

    class Ctx:
        config = cfg

    trace = {"devices": {"/device:TPU:0": [
        [T.op_name(t), s, d] for t, s, d in evs]}, "host": []}
    run = {"record": {"trace_path": "recorded-lfm2", "batch": 1,
                      "traced": {"steps": 1}, "devices": [object()],
                      "values": {"train_samples_per_s": 4.85}},
           "ctx": Ctx, "trace": trace, "tracemod": T,
           "trace_summary": {"busy_s": busy}, "detail": {},
           "peaks": harness.peaks_for("TPU v5 lite"),
           "flops": harness.load_module(".", "flops")}
    O = harness.load_module("reduce", "moe_ops")
    O._loaded["recorded-lfm2"] = evs
    H._loaded["recorded-lfm2"] = notes
    try:
        got = S.of_run(run)
        read = lambda n: harness.load_module(  # noqa: E731
            "layer_metrics", n).read(run)
        grouped = sum(by[k] for k, v in KINDS.items()
                      if v == "grouped_matmul") / 1e9
        assert got["calls"] == 3 and got["grouped_matmul"] == pytest.approx(
            grouped)
        assert got["buffer"] == pytest.approx(sum(
            by[k] for k, v in KINDS.items() if v == "buffer") / 1e9)
        assert got["pairs"] == pytest.approx(by["fusion.179"] / 1e9)
        assert got["shared"] == 0.0
        # of the product that carries some of the convolution, what is
        # over its own least: 2 x 8192 x 2048 x 2048 over 197 TFLOP/s =
        # 0.3488 ms of its 0.4959
        excess = by[PRODUCT] / 1e9 - 0.34883e-3
        assert 0.1e-3 < excess < 0.2e-3
        conv = sum(by[k] for k in CONV) / 1e9 + excess
        assert got["conv"] == pytest.approx(conv, rel=1e-4)
        assert (got["conv_events"], got["conv_products"]) == (
            len(CONV) + 1, 1)
        assert got["conv_products_s"] == pytest.approx(by[PRODUCT] / 1e9)
        assert read("expert_share_device_pct") == pytest.approx(
            100.0 * (got["grouped_matmul"] + got["buffer"] + got["pairs"])
            / busy)
        assert read("short_conv_device_ms") == pytest.approx(1e3 * conv,
                                                             rel=1e-4)
        # one layer's least, forward + backward: 0.4507 ms; the recorded
        # events are ONE layer's, the cell has seven
        share = read("short_conv_hbm_roofline")
        assert share == pytest.approx(100.0 * 7 * 0.4507e-3 / conv, rel=2e-3)
        one_layer = share / 7
        assert 0 < one_layer < 100
        note = run["detail"]["short_conv_hbm_roofline"]
        assert (note["roof"], note["conv_layers"], note["instructions"],
                note["in_products"]) == ("memory", 7, len(CONV) + 1, 1)
        assert note["in_products_whole_s"] == pytest.approx(
            by[PRODUCT] / 1e9)
        # least time of a product: 4096 rows with work through [2048 x
        # 1536], compute-bound: 25.8 GFLOP over 197 TFLOP/s = 0.131 ms
        g = read("expert_share_grouped_matmul_roofline")
        assert g == pytest.approx(100.0 * 3 * 0.13080e-3 / grouped, rel=1e-3)
        assert 0 < g < 100
        note = run["detail"]["expert_share_grouped_matmul_roofline"]
        assert (note["roof"], note["rows_with_work"], note["buffer_rows"],
                note["calls"]) == ("compute", 4096, 8192, 3)
        # the three flash kernels at 32 on 8 of 64: least 1.395 / 2.093 /
        # 2.791 ms a call
        for name, kernel, least in (
                ("gqa_flash_fwd_roofline", "flash_fwd.3", 1.3953e-3),
                ("gqa_flash_bwd_dq_roofline", "flash_bwd_dq.3", 2.0930e-3),
                ("gqa_flash_bwd_dkv_roofline", "flash_bwd_dkv.3",
                 2.7906e-3)):
            assert read(name) == pytest.approx(
                100.0 * least / (by[kernel] / 1e9), rel=1e-3)
            assert 0 < read(name) < 100
        assert run["detail"]["gqa_flash_fwd_roofline"]["roof"] == "compute"
        # mfu_pct reads the cell from flops_lfm2: 14.74 TFLOP a sample x
        # 4.85 samples/s over 197 TFLOP/s
        assert harness.load_module("layer_metrics", "mfu_pct").read(
            run) == pytest.approx(36.29, abs=0.05)

        # a trace that does not carry the program: nothing to read, never
        # the stream counted as the buffer
        H._loaded["recorded-lfm2"] = {}
        for name in READERS[3:]:
            assert harness.load_module("layer_metrics", name).read(
                dict(run, detail={})) is None
        H._loaded["recorded-lfm2"] = notes

        # one head count (OLMoE's arguments: 16 heads of 128 at T 4096, no
        # `n_kv_heads`, no share): the flash readers are flops.py's count,
        # the share's and the convolution's have nothing to read
        class Dense:
            config = harness.load_json("configs", "olmoe-1b-7b")

        F = harness.load_module(".", "flops")
        dense = dict(run, ctx=Dense, detail={})
        flops, nbytes = F.flash_attention_cost(1, 16, 4096, 128, "fwd")
        least, roof = F.roofline_seconds(flops, nbytes, run["peaks"])
        assert harness.load_module("layer_metrics", READERS[0]).read(
            dense) == pytest.approx(
                100.0 * least / (by["flash_fwd.3"] / 1e9), rel=1e-6)
        assert dense["detail"]["gqa_flash_fwd_roofline"][
            "calls_a_layer_a_step"] == pytest.approx(1 / 2)   # 2 layers
        for name in READERS[3:]:
            assert harness.load_module("layer_metrics", name).read(
                dict(run, ctx=Dense, detail={})) is None

        # latent attention has keys wider than values: mla_flash_*'s
        class Latent:
            config = harness.load_json("configs", "moonlight-16b-a3b")

        for name in READERS[:3]:
            assert harness.load_module("layer_metrics", name).read(
                dict(run, ctx=Latent, detail={})) is None
    finally:
        O._loaded.pop("recorded-lfm2")
        H._loaded.pop("recorded-lfm2")

    # nothing to read without a trace (what another run gives the readers)
    assert S.of_run(dict(run, trace=None, detail={})) is None
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(
            dict(run, trace=None, detail={})) is None


# ---------------------------------------------------------------------------
# the compiled program's metadata in a trace: a hand-made xplane


def _varint(n: int) -> bytes:
    out = b""
    while True:
        n, low = n >> 7, n & 0x7F
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def _field(number: int, value) -> bytes:
    """One proto field: a varint for an int, length-delimited for bytes."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(uid, name, opcode, dims, op_name="", operands=(),
                 called=()):
    """An HloInstructionProto by hlo_scopes.py's field numbers; operand
    ids packed, as protobuf writes them, called ids one a field."""
    shape = _field(2, 11) + b"".join(_field(3, d) for d in dims)
    return (_field(1, name) + _field(2, opcode) + _field(3, shape)
            + (_field(7, _field(2, op_name)) if op_name else b"")
            + _field(35, uid)
            + (_field(36, b"".join(_varint(o) for o in operands))
               if operands else b"")
            + b"".join(_field(38, c) for c in called))


def _xplane(tmp_path, modules) -> str:
    """An XSpace whose `/host:metadata` plane holds one HloProto a module,
    a module a list of (computation id, [instruction])."""
    entries = b""
    for n, comps in enumerate(modules):
        module = _field(1, "jit_step") + b"".join(
            _field(3, b"".join(_field(2, i) for i in found) + _field(5, cid))
            for cid, found in comps)
        meta = _field(1, n + 1) + _field(5, _field(1, 1) + _field(
            6, _field(1, module)))
        entries += _field(4, _field(1, n + 1) + _field(2, meta))
    path = tmp_path / "toy.xplane.pb"
    path.write_bytes(_field(1, _field(2, "/device:TPU:0"))
                     + _field(1, _field(2, "/host:metadata") + entries))
    return str(path)


def test_hlo_scopes_reads_the_program_out_of_a_trace(tmp_path):
    H = harness.load_module("reduce", "hlo_scopes")
    T, D = 64, 16
    fused = (7, [
        _instruction(20, "p0", "parameter", [T, D]),
        _instruction(21, "p1", "parameter", [D, D]),
        # XLA:TPU's form of the dot: 2 x 64 x 16 x 16 operations
        _instruction(22, "convolution.1", "convolution", [T, D],
                     "jit(step)/transpose(jvp())/dot_general", (20, 21)),
        _instruction(23, "mul.1", "multiply", [T, D],
                     "jit(step)/transpose(jvp(pdtpu.conv.gate))/mul",
                     (22, 20))])
    entry = (1, [
        _instruction(1, "x", "parameter", [T, D], "state['x']"),
        _instruction(2, "fusion.1", "fusion", [T, D],
                     "jit(step)/pdtpu.moe.combine/scatter-add", (1,)),
        # the two halves of an asynchronous copy carry no name at all
        _instruction(3, "copy-start.1", "copy-start", [T, D], "", (2,)),
        _instruction(4, "copy-done.1", "copy-done", [T, D], "", (3,)),
        # the kernel XLA makes of lax.ragged_dot: a name, but not JAX's
        _instruction(5, "ragged-dot-none.1", "custom-call", [T, D],
                     "ragged-dot-none", (4,)),
        _instruction(6, "fusion.2", "fusion", [T, D],
                     "jit(step)/dot_general", (5, 1), called=(7,)),
        # named by JAX, in no part: it takes nothing from its operand
        _instruction(8, "fusion.3", "fusion", [T, D],
                     "jit(step)/rms_norm/mul", (2,))])
    small = [(1, [_instruction(1, "fusion.1", "fusion", [2])])]
    got = H.of_trace(_xplane(tmp_path, [small, [fused, entry]]))
    # the larger program is read: `fusion.1` is the step's
    assert got["fusion.1"] == H.Note(frozenset({"moe.combine"}), True, 0.0)
    # ... and what JAX named nowhere takes its operand's producer's parts
    assert got["copy-start.1"] == got["copy-done.1"] == got[
        "ragged-dot-none.1"] == H.Note(frozenset({"moe.combine"}), False, 0.0)
    assert got["fusion.2"] == H.Note(frozenset({"conv.gate"}), True,
                                     2.0 * T * D * D)
    assert got["fusion.3"] == H.NOTHING == got["x"]
    assert H.name_of("%fusion.2 = f32[64,16]{1,0} fusion(f32[64,16] %p), "
                     "kind=kOutput") == "fusion.2"
    # a trace without the plane: nothing, and the share's readers read None
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(_field(1, _field(2, "/device:TPU:0")))
    assert H.of_trace(str(bare)) == {}


# ---------------------------------------------------------------------------
# AOT: the cell's real step, compiled for a described v5e


def test_aot_lfm2_train_step_fits_one_v5e():
    """One sequence of 8192 tokens through the dense layer and 8 expert
    layers (7 convolutions, 2 attention layers) at the published widths
    fits one chip without recomputation and fills more than a quarter of it
    (PERF.md, PR 33, has the bytes); the compiled step holds the three
    flash kernels once an attention layer and nine grouped matmul kernels
    an expert layer, each attention grad op reused its forward, every
    grouped backward is the Pallas pair, and the counter families read
    what was built."""
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
    obs.REGISTRY.reset()
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((batch, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT lfm2 train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.25 * 16 * 2 ** 30, got
    # weights and Adam state alone: 849 M parameters at 10 bytes
    assert sum(int(np.prod(p.shape)) for p in params) == 849_429_248
    assert 8.45e9 < got["argument_bytes"] < 8.55e9, got
    kinds = cfg["layer_types"]
    attention = kinds.count("full_attention")
    expert_layers = len(kinds) - cfg["num_dense_layers"]
    # 3 flash kernels an attention layer + 9 grouped matmuls an expert layer
    assert got["mosaic_calls"] >= 3 * attention + 9 * expert_layers, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: [(s["labels"], s["value"])  # noqa: E731
                           for s in fam[name]["series"]]
    # what the cell needs and no more (a later kernel with `keep_for_grad`
    # adds a series of its own): every attention grad op reused its
    # forward, and no grad op of any kind re-ran one
    forwards = series("executor_grad_kernel_forward_total")
    assert ({"op": "scaled_dot_product_attention", "reused": "1"},
            float(attention)) in forwards
    assert not [s for s in forwards if s[0]["reused"] == "0"], forwards
    assert series("short_conv_layers_traced_total") == [
        ({"dim": "2048", "kernel": "3"}, float(kinds.count("conv")))]
    assert series("gqa_attention_layers_traced_total") == [
        ({"head_dim": "64", "kv_heads": "8", "q_heads": "32"},
         float(attention))]
    assert series("moe_share_layers_traced_total") == [
        ({"held": "8", "experts": "64", "top_k": "4",
          "buffer_rows": str(cfg["share"]["buffer_rows"])},
         float(expert_layers))]
    grouped = dict((s[0]["impl"], s[1])
                   for s in series("moe_grouped_backward_total"))
    assert grouped["pallas"] >= 3.0 * expert_layers, grouped
    squares = {s[0]["kernel"]: s[1]
               for s in series("flash_score_elements_total")
               if s[0]["part"] == "square"}
    assert squares == {k: attention * 32.0 * 8192 * 8192 for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
