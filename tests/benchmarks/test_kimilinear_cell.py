"""What PR 58 adds to the benchmark, checked on the CPU: the manifest's
entries of the cell `kimilinear_train_t8192`, its configuration against
the catalog's row, the counts of benchmarks/flops_kimi.py by hand, the
seven new readers on made-up events, and the real size compiled for the
chip without one.  The program against the reference at a toy size
(through the cell's own driver) and the reference's mutants are in
tests/test_kimi_linear_model.py.  tests/benchmarks/test_benchmark.py holds
the manifest-wide rules over the same files; a test that reads
BENCHMARK.json as a whole is named `test_manifest...` and holds membership
and content, never position.
"""

from __future__ import annotations

import collections
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "kimi-linear-48b-a3b"
CELL = "kimilinear_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("kda_device_ms", "kda_scan_device_ms", "kda_scan_roofline",
           "kda_gates_hbm_roofline", "latent_flash_fwd_roofline",
           "latent_flash_bwd_dq_roofline", "latent_flash_bwd_dkv_roofline")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "kernel_forward_reruns",
         "flash_scores_computed_pct", "expert_share_device_pct",
         "expert_share_grouped_matmul_roofline", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms")


# ---------------------------------------------------------------------------
# the manifest and the configuration


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert set(LISTS) | set(READERS) | {"compile_s", "cache_misses"} <= per
    # not Moonlight's readers (T from `max_position_embeddings`, which this
    # configuration does not have; DeepSeek's key names), not the
    # scalar-gated DeltaNet's (other scopes, another least), no head norm or
    # rotary turn to prepare
    assert not per & {
        "mla_flash_fwd_roofline", "mla_flash_bwd_dq_roofline",
        "mla_flash_bwd_dkv_roofline", "mfu_local_pct",
        "moe_share_device_pct", "moe_share_grouped_matmul_roofline",
        "moe_shared_expert_device_ms", "gdn_device_ms",
        "gdn_scan_device_ms", "gdn_scan_roofline", "gdn_conv_hbm_roofline",
        "flash_fwd_roofline", "qk_prep_device_ms",
        "attention_relayout_device_ms", "collective_exposed_ms"}
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
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    # at most a quarter of the cells, rounded down, take four chips
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    # the traffic mix is the one that was there
    traffic = harness.load_json("traffic", TRAFFIC)
    assert (traffic["batch"], traffic["staged_batches"],
            traffic["loss_read_every"], traffic["loss_fell_step"],
            traffic["trace_seconds"]) == (1, 8, 8, 32, 3)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    """Each file carries its entry's unit, direction, source and layer; the
    entry agrees with its file and names this cell; its layer is one the
    manifest already names."""
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__.startswith(name) and callable(mod.read)
    assert (mod.UNIT == "%") == name.endswith("_roofline")
    assert (mod.UNIT == "ms") == name.endswith("_device_ms")
    assert entry["workloads"] == [CELL]
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
        return [json.loads(x) for x in f
                if '"Kimi-Linear-48B-A3B-Instruct"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for Kimi-Linear-48B-A3B-Instruct, key for key,
    `linear_attn_config` WHOLE; only the depth, the experts held and the
    vocabulary slice differ, `reduced` says so, and each stays within the
    floors (a whole period and at least 4 layers after the dense one, at
    least 8 experts, at least 1/8 of the vocabulary); the builder's
    arguments, the deployment, the share and the FLOPs' arguments say the
    same sizes."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (27, 256, 163840)
    assert cfg["num_hidden_layers"] == 5 and cfg["num_experts"] == 8 >= 8
    assert cfg["vocab_size"] == 20480 == pub["vocab_size"] // 8
    # no width is cut
    lin = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_token"], cfg["num_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"],
            cfg["mla_use_nope"], cfg["moe_router_activation_func"],
            cfg["moe_renormalize"], cfg["first_k_dense_replace"]) == (
        2304, 32, 512, 128, 64, 128, None, 9216, 1024, 8, 1, 2.446, 1e-05,
        True, "sigmoid", True, 1)
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(
        range(1, 28))
    assert "max_position_embeddings" not in cfg       # ROADMAP.md B5
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["kv_rank"], a["qk_nope_dim"],
            a["qk_rope_dim"], a["v_dim"], a["linear_heads"],
            a["linear_head_dim"], a["conv_kernel"], a["gate_rank"],
            a["dense_dim"], a["expert_dim"], a["num_experts"], a["top_k"],
            a["shared_experts"], a["routed_scale"], a["norm_epsilon"],
            a["seq_len"], a["dense_layers"]) == (
        2304, 32, 512, 128, 64, 128, 32, 128, 4, 128, 9216, 1024, 256, 8, 1,
        2.446, 1e-05, 8192, 1)
    assert "remat" not in a                                # (a) stood
    assert "balance_weight" not in a                       # no auxiliary loss
    assert (len(a["layer_types"]), a["held_experts"], a["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"])
    # the held layers are the published 1-5: a whole period turned by one
    dep, share = cfg["deployment"], cfg["share"]
    assert dep["layers_held"] == [1, 2, 3, 4, 5]
    assert dep["kda_layers_held"] == [
        i for i in dep["layers_held"] if i in lin["kda_layers"]] == [
        1, 2, 3, 5]
    assert dep["full_attn_layers_held"] == [4]
    assert a["layer_types"] == [
        "kda" if i in lin["kda_layers"] else "full_attention"
        for i in dep["layers_held"]]
    ref = harness.load_module("reference", CONFIG)
    assert [(m, f) for m, f, _ in ref.layout(cfg)[0]] == [
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
        ("mla", "experts"), ("kda", "experts")]
    assert dep["expert_parallel"] == 32
    assert dep["router_outputs"] == a["num_experts"] == 256
    assert dep["experts_held"] == [a["first_expert"], a["first_expert"]
                                   + a["held_experts"]] == [0, 8]
    assert dep["vocabulary_rows"] == [0, 20480]
    assert share["first_expert"] == a["first_expert"]
    assert share["buffer_rows"] == a["buffer_rows"]
    assert share["buffer_rows"] % 256 == 0        # the backward kernels' tile
    even = 8192 * 8 * 8 // 256
    assert even == 2048 and 2 * even <= share["buffer_rows"] < 8192
    feeds = cfg["train"]["feeds"]
    assert feeds["tokens"]["high"] == cfg["vocab_size"]
    assert feeds["targets"] == {"dist": "shift_left", "of": "tokens"}
    assert cfg["tokens_per_sample"] == a["seq_len"] == feeds["tokens"][
        "shape"][0]
    assert set(cfg["assumed"]) >= {
        "gate_projections", "decay_parameters", "convolutions", "l2_norm",
        "no_position", "selection_bias", "auxiliary_loss",
        "renormalisation", "initialisation", "learning_rate", "tokens",
        "chunk", "no_recomputation", "precision", "layers_by_config"}
    # share_ops.py's seven names, and what its roofline reader reads beside
    share_ops = harness.load_module("reduce", "share_ops")
    assert share_ops.dims_of(cfg, 1) == {
        "tokens": 8192, "rows": share["buffer_rows"], "pairs": 8192 * 8,
        "held": 8, "experts": 256, "dim": 2304, "expert_dim": 1024,
        "shared_dim": 1024, "conv_kernel": 4}
    f = cfg["flops"]
    assert (f["module"], f["function"]) == (
        "flops_kimi", "kimi_share_train_flops_per_sample")
    same = ("dim", "linear_heads", "linear_head_dim", "gate_rank", "n_heads",
            "kv_rank", "qk_nope_dim", "qk_rope_dim", "v_dim", "dense_layers",
            "dense_dim", "num_experts", "held_experts", "expert_dim",
            "top_k", "seq_len")
    assert {k: f["args"][k] for k in same} == {k: a[k] for k in same}
    assert f["args"]["vocab"] == a["vocab_size"]
    assert f["args"]["shared_dim"] == a["shared_experts"] * a["expert_dim"]
    assert f["args"]["kda_layers"] == a["layer_types"].count("kda") == 4
    assert f["args"]["mla_layers"] == a["layer_types"].count(
        "full_attention") == 1
    assert f["args"]["expert_layers"] == 5 - a["dense_layers"]


def test_parameter_count_is_the_stated_share():
    """602,434,432 parameters by the arithmetic ISSUE 58 states, from the
    builder's arguments (the AOT test counts them from the program)."""
    cfg = harness.load_json("configs", CONFIG)
    a = cfg["train"]["args"]
    d, w, r = a["dim"], a["linear_heads"] * a["linear_head_dim"], a[
        "gate_rank"]
    kda = (3 * d * w + 3 * w * a["conv_kernel"] + 2 * (d * r + r * w) + w
           + a["linear_heads"] + d * a["linear_heads"]
           + a["linear_head_dim"] + w * d)
    assert kda == 39_514_272
    qk = a["qk_nope_dim"] + a["qk_rope_dim"]
    mla = (d * a["n_heads"] * qk + d * (a["kv_rank"] + a["qk_rope_dim"])
           + a["kv_rank"] + a["kv_rank"] * a["n_heads"] * (
               a["qk_nope_dim"] + a["v_dim"]) + a["n_heads"] * a["v_dim"] * d)
    assert mla == 29_114_880
    expert = 3 * d * a["expert_dim"]
    experts = (d * a["num_experts"] + a["num_experts"] + a["shared_experts"]
               * expert + a["held_experts"] * expert)
    total = (4 * kda + mla + 3 * d * a["dense_dim"] + 4 * experts
             + 5 * 2 * d + 2 * a["vocab_size"] * d + d)
    assert total == cfg["parameters_held"] == 602_434_432
    assert "602,434,432" in cfg["deployment"]["about"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    ref = harness.load_module("reference", CONFIG)
    assert set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs", "kda_out"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert ref.TOL["routed_pairs"] == ref.TOL["dropped_pairs"] == 0.0
    cfg = harness.load_json("configs", CONFIG)
    assert set(cfg["train"]["check_fetch"]) | {"loss"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS} == set(ref.TOL)


# ---------------------------------------------------------------------------
# flops_kimi.py against hand counts


def test_kda_cost_by_hand():
    F = harness.load_module(".", "flops_kimi")
    T, H, D = 256, 3, 16
    C = F.PUBLISHED_CHUNK
    per_token = (2 * 2 * C * D        # the two decayed score matrices
                 + C * 2 * D          # the solve for U and W
                 + 2 * 2 * D * D      # W S and Q S
                 + 2 * D * D          # K~^T V'
                 + 2 * C * D)         # P V'
    flops, nbytes = F.kda_cost(2, T, H, D, "scan", "fwd")
    assert flops == 2 * T * H * per_token
    qkv, gates, o = 3 * H * D * 2, (H * D + H) * 4, H * D * 4
    assert nbytes == 2 * T * (qkv + gates + o)
    flops_b, bytes_b = F.kda_cost(2, T, H, D, "scan", "bwd")
    assert flops_b == 2 * flops
    assert bytes_b == 2 * T * (2 * (qkv + gates) + o)
    # a sequence under one chunk is one chunk
    assert F.kda_cost(1, 16, 1, 8, "scan", "fwd")[0] == 16 * (
        8 * 16 * 8 + 6 * 8 * 8)
    # the gates: the float32 [T, H D] tensor once each way
    assert F.kda_cost(1, T, H, D, "gates", "fwd") == (
        12.0 * T * H * D, T * (H * D + H) * 6)
    assert F.kda_cost(1, T, H, D, "gates", "bwd") == (
        24.0 * T * H * D, T * (H * D + H) * 8)
    with pytest.raises(ValueError, match="part"):
        F.kda_cost(1, T, H, D, "conv", "fwd")
    # at the cell's shape the scan's nominal count is 5.2 M a token, and HBM
    # binds even the scan's least (0.57 ms of bytes against 0.22 ms of
    # products forward: the float32 g and o are half of the bytes)
    peaks = harness.peaks_for("TPU v5 lite")
    flops, nbytes = F.kda_cost(1, 8192, 32, 128, "scan", "fwd")
    assert flops == 8192 * 32 * 163840
    assert nbytes == 8192 * (3 * 4096 * 2 + 4128 * 4 + 4096 * 4)
    for part in ("scan", "gates"):
        flops, nbytes = F.kda_cost(1, 8192, 32, 128, part, "fwd")
        assert flops / peaks["bf16_flops_per_s"] < nbytes / peaks[
            "hbm_bytes_per_s"]


def test_share_train_flops_by_hand():
    F = harness.load_module(".", "flops_kimi")
    # a toy, by hand: one KDA layer (dense) and one MLA layer (experts)
    got = F.kimi_share_train_flops_per_sample(
        dim=2, kda_layers=1, mla_layers=1, linear_heads=1, linear_head_dim=2,
        gate_rank=1, n_heads=1, kv_rank=3, qk_nope_dim=2, qk_rope_dim=1,
        v_dim=2, dense_layers=1, dense_dim=5, expert_layers=1, num_experts=4,
        held_experts=2, expert_dim=3, shared_dim=3, top_k=1, vocab=7,
        seq_len=4)
    kda = (2 * 2 * 3 * 2 + 2 * 2 * (2 * 1 + 1 * 2) + 2 * 2 * 1 + 2 * 2 * 2
           + (8 * 4 * 2 + 6 * 2 * 2))
    mla = 2 * (2 * 3 + 2 * 4 + 3 * 4 + 2 * 2) + 4 * (3 + 2)
    ffn = 2 * 2 * 4 + 3 * 2 * 2 * 3 + 0.5 * 3 * 2 * 2 * 3
    assert got == 3 * 4 * (kda + mla + 3 * 2 * 2 * 5 + ffn + 2 * 2 * 7)
    cfg = harness.load_json("configs", CONFIG)
    whole = harness.flops_per_sample(cfg)
    assert whole == pytest.approx(19.072e12, rel=1e-4)
    # at their nominal count the four scans are 2.7% of it (a plain float32
    # emission pays many times that), the MLA layer's scores a ninth
    scan = 3 * 4 * F.kda_cost(1, 8192, 32, 128, "scan", "fwd")[0]
    assert scan / whole == pytest.approx(0.027, abs=0.001)
    assert 3 * 8192 * 8192 * 32 * 320 / whole == pytest.approx(
        0.108, abs=0.003)


# ---------------------------------------------------------------------------
# the seven readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
    """A reduced trace with the three flash kernels' seconds and calls."""

    SECONDS = {"flash_fwd": 0.016, "flash_bwd_dq": 0.024,
               "flash_bwd_dkv": 0.030}
    CALLS = {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}

    @staticmethod
    def kernel_pattern(kernel):
        return kernel

    @classmethod
    def op_seconds(cls, trace, pattern):
        return cls.SECONDS.get(pattern, 0.0)

    @classmethod
    def op_count(cls, trace, pattern):
        return cls.CALLS.get(pattern, 0)

    @staticmethod
    def window_of(trace):
        return (0, 1_000_000_000)


def _run(events, monkeypatch, config=None, trace=True):
    """A `run` whose trace holds `events` = [(name, start, dur, parts, own,
    product flops)] on one device, 2 traced steps."""
    H = harness.load_module("reduce", "hlo_scopes")
    P = harness.load_module("reduce", "part_ms")
    notes = {name: Note(frozenset(parts), own, flops)
             for name, _, _, parts, own, flops in events}
    monkeypatch.setattr(H, "of_trace", lambda path: notes)
    P._events.clear()
    cfg = config or harness.load_json("configs", CONFIG)
    ctx = type("Ctx", (), {"config": cfg})()
    return {"record": {"trace_path": "made.up" if trace else None,
                       "batch": 1, "traced": {"steps": 2}},
            "trace": {"devices": {0: [[f"%{n} = f32[] fusion()", s, d]
                                      for n, s, d, _, _, _ in events]}}
            if trace else None,
            "tracemod": _Trace, "ctx": ctx,
            "peaks": harness.peaks_for("TPU v5 lite"),
            "flops": harness.load_module(".", "flops"), "detail": {}}


def test_kda_readers_add_up_their_parts_at_self_time(monkeypatch):
    """`kda_device_ms` is the four parts of the core; an event of the scan
    counts whole, a product of it too; a part fused into a projection
    counts by what it takes over the product's least; the projections
    alone go to `detail`; a `while` keeps what its body leaves; a copy that
    is not the part's own does not count; the two shares divide
    flops_kimi.py's least by their part's time."""
    ms = 1_000_000
    peaks = harness.peaks_for("TPU v5 lite")
    peak = peaks["bf16_flops_per_s"]
    events = [
        ("fusion.1", 0, 6 * ms, ("kda.project",), True, 5e-3 * peak),
        ("fusion.2", 6 * ms, 4 * ms, ("kda.conv",), True, 0.0),
        ("fusion.3", 10 * ms, 5 * ms, ("kda.project", "kda.gates"), True,
         3e-3 * peak),
        ("fusion.4", 15 * ms, 3 * ms, ("kda.gates",), True, 0.0),
        ("while.1", 18 * ms, 40 * ms, ("kda.scan",), True, 0.0),
        ("fusion.5", 20 * ms, 30 * ms, ("kda.scan",), True, 2e-3 * peak),
        ("fusion.6", 58 * ms, 20 * ms, ("kda.scan",), True, 0.0),
        ("fusion.7", 78 * ms, 2 * ms, ("kda.norm_gate",), True, 0.0),
        ("copy.1", 80 * ms, 1 * ms, ("kda.scan",), False, 0.0),
        ("fusion.8", 81 * ms, 1 * ms, ("lm.head",), True, 0.0)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)  # noqa
    # conv 4, gates (5 - 3) + 3, scan 10 + 30 + 20, norm_gate 2
    assert read("kda_device_ms") == pytest.approx((4 + 5 + 60 + 2) / 2)
    assert run["detail"]["kda_device_ms"] == {
        "kda.conv_ms_a_step": pytest.approx(2.0),
        "kda.gates_ms_a_step": pytest.approx(2.5),
        "kda.scan_ms_a_step": pytest.approx(30.0),
        "kda.norm_gate_ms_a_step": pytest.approx(1.0),
        "project_ms_a_step": pytest.approx(3.0)}
    assert read("kda_scan_device_ms") == pytest.approx(30.0)
    F = harness.load_module(".", "flops_kimi")
    flops = harness.load_module(".", "flops")
    for name, part, seconds in (("kda_scan_roofline", "scan", 0.060),
                                ("kda_gates_hbm_roofline", "gates", 0.005)):
        least = sum(flops.roofline_seconds(
            *F.kda_cost(1, 8192, 32, 128, part, kind), peaks)[0]
            for kind in ("fwd", "bwd"))
        got = read(name)
        assert got == pytest.approx(100.0 * least * 4 * 2 / seconds)
        assert run["detail"][name]["layers"] == 4
        assert run["detail"][name]["roofs"] == ["memory", "memory"]
        assert 0 < got < 100
    # the scan's least is 1.56 ms a layer and step
    assert run["detail"]["kda_scan_roofline"][
        "least_ms_a_layer_a_step"] == pytest.approx(1.56, abs=0.01)


def test_latent_flash_roofline_readers_on_a_recorded_trace(monkeypatch):
    cfg = harness.load_json("configs", CONFIG)
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    F = harness.load_module(".", "flops_mla")
    for name, kernel, kind in (
            ("latent_flash_fwd_roofline", "flash_fwd", "fwd"),
            ("latent_flash_bwd_dq_roofline", "flash_bwd_dq", "bwd_dq"),
            ("latent_flash_bwd_dkv_roofline", "flash_bwd_dkv", "bwd_dkv")):
        run = _run([], monkeypatch, cfg)
        reader = harness.load_module("layer_metrics", name)
        got = reader.read(run)
        least = F.mla_flash_cost(1, 32, 8192, 192, 128, kind)[0] / peak
        want = 100.0 * least * _Trace.CALLS[kernel] / _Trace.SECONDS[kernel]
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
        note = run["detail"]["latent_" + kernel + "_roofline"]
        assert note["roof"] == "compute"
        assert note["calls_a_layer_a_step"] == 1.0      # ONE MLA layer
        # nothing to read: no trace; a configuration whose `train.args`
        # name no latent widths or no 'full_attention' layer by that name
        assert reader.read(_run([], monkeypatch, cfg, trace=False)) is None
        for other in ("moonlight-16b-a3b", "qwen3-next-80b-a3b"):
            assert reader.read(_run([], monkeypatch, harness.load_json(
                "configs", other))) is None


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program cannot run this cell, and another cell's names
    no such part: each reader returns None, never raises, and a run
    without a trace likewise."""
    events = [("fusion.1", 0, 1000, ("lm.head",), True, 0.0),
              ("fusion.2", 1000, 1000, ("gdn.scan",), True, 0.0)]
    other = harness.load_json("configs", "qwen3-next-80b-a3b")
    monkeypatch.setattr(_Trace, "SECONDS", {})
    for config in (None, other):
        run = _run(events, monkeypatch, config)
        for name in READERS:
            assert harness.load_module("layer_metrics", name).read(
                run) is None, name
    run = _run(events, monkeypatch, trace=False)
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(run) is None


# ---------------------------------------------------------------------------
# the real size, compiled for the chip without one


def test_aot_kimilinear_train_step_fits_one_v5e():
    """One sequence of 8192 tokens through the published layers 1-5 at the
    published widths, 8 of 256 experts and 1/8 of the vocabulary, WITHOUT
    recomputation, fits one chip (PERF.md, PR 58, has the bytes) and fills
    most of it; 602,434,432 parameters counted from the program; the four
    KDA layers traced at 32 heads of 128, the ONE latent-attention layer
    without a position on the two-width flash kernels, every share's rows
    leave the buffer by the segment-sum kernel, and no grad op launches a
    kernel's forward again."""
    import importlib.util

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import sparse_linear_ops

    try:
        v5e = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    spec = importlib.util.spec_from_file_location(
        "bench_test_benchmark", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "test_benchmark.py"))
    tb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tb)
    cfg = harness.load_json("configs", CONFIG)
    obs.REGISTRY.reset()
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    assert sum(int(np.prod(p.shape)) for p in params) == 602_434_432
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT kimilinear train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.75 * 16 * 2 ** 30, got
    # weights and Adam state alone: 602.4 M parameters at 10 bytes
    assert 6.02e9 < got["argument_bytes"] < 6.03e9, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: {tuple(sorted(s["labels"].items())): s["value"]  # noqa
                           for s in fam[name]["series"]}
    assert series("kda_layers_traced_total") == {
        (("chunk", str(sparse_linear_ops.KDA_CHUNK)), ("conv_taps", "4"),
         ("gate_rank", "128"), ("head_dim", "128"), ("heads", "32")): 4.0}
    assert str(sparse_linear_ops.KDA_CHUNK) in cfg["assumed"]["chunk"]
    assert series("latent_attention_positions_traced_total") == {
        (("positions", "none"),): 1.0}
    assert series("mla_layers_traced_total") == {
        (("kv_rank", "512"), ("qk_dim", "192"), ("v_dim", "128")): 1.0}
    # the ONE layer's three flash kernels, each on the causal half of 32
    # heads x 8192 x 8192 scores (whole blocks: 50.78%)
    assert series("flash_score_elements_total") == {
        (("kernel", k), ("part", part)): n
        for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
        for part, n in (("computed", 1090519040.0),
                        ("square", 32.0 * 8192 * 8192))}
    rows = str(cfg["share"]["buffer_rows"])
    assert series("moe_share_layers_traced_total") == {
        (("buffer_rows", rows), ("experts", "256"), ("held", "8"),
         ("top_k", "8")): 4.0}
    assert series("moe_share_rows_to_tokens_traced_total") == {
        (("op", "combine"), ("path", "segment_sum")): 4.0,
        (("op", "permute_grad"), ("path", "segment_sum")): 4.0}
    assert series("moe_grouped_backward_total") == {
        (("impl", "pallas"),): 12.0}
    assert series("executor_grad_kernel_forward_total") == {
        (("op", "latent_attention"), ("reused", "1")): 1.0}
    obs.REGISTRY.reset()
