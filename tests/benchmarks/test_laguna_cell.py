"""What PR 63 adds to the benchmark, checked on the CPU: the manifest's
entries of the cell `laguna_s_train_t8192`, its configuration against the
catalog's row, the counts of benchmarks/flops_laguna.py by hand, the six
new readers on made-up events, and the real size compiled for the chip
without one.  The program against the reference at a toy size (through the
cell's own driver) and the reference's mutants are in
tests/test_laguna_model.py.  tests/benchmarks/test_benchmark.py holds the
manifest-wide rules over the same files; a test that reads BENCHMARK.json
as a whole is named `test_manifest...` and holds membership and content,
never position.
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

CONFIG = "laguna-s-2.1"
CELL = "laguna_s_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("mixedheads_flash_fwd_roofline",
           "mixedheads_flash_bwd_dq_roofline",
           "mixedheads_flash_bwd_dkv_roofline", "attn_head_gate_device_ms",
           "attn_head_gate_hbm_roofline", "expert_share_shared_device_ms")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "kernel_forward_reruns",
         "flash_scores_computed_pct", "expert_share_device_pct",
         "expert_share_grouped_matmul_roofline", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "attention_relayout_device_ms",
         "qk_prep_device_ms", "attn_window_device_ms", "attn_full_device_ms",
         "moe_route_device_ms")


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
    # one head count for every layer is not this cell's least
    # (flops_smallthinker by name), nor the causal half of every layer, nor
    # another family's keys
    assert not per & {
        "swa_gqa_flash_fwd_roofline", "swa_gqa_flash_bwd_dq_roofline",
        "swa_gqa_flash_bwd_dkv_roofline", "flash_fwd_roofline",
        "gqa_flash_fwd_roofline", "window_flash_fwd_roofline",
        "wide_flash_fwd_roofline", "mla_flash_fwd_roofline",
        "moe_share_device_pct", "moe_device_share_pct", "mfu_local_pct",
        "mfu_active_pct", "collective_exposed_ms", "gdn_device_ms"}
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
    # the configuration's name holds a dot: its reference loads by path
    ref = harness.load_module("reference", CONFIG)
    assert ref.__name__ == "bench_reference_laguna_s_2_1"


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    """Each file carries its entry's unit, direction, source and layer; the
    entry agrees with its file and names this cell alone; its layer is one
    the manifest already names."""
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
        return [json.loads(x) for x in f if '"Laguna-S-2.1"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for Laguna-S-2.1, key for key, the four
    per-layer lists and `rope_parameters` WHOLE; only the depth, the
    experts held and the vocabulary slice differ, `reduced` says so, and
    each stays within the floors (a whole period and at least 4 layers
    after the dense one, at least 8 experts, at least 1/8 of the
    vocabulary); the builder's arguments, the deployment, the share and the
    FLOPs' arguments say the same sizes."""
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
            pub["vocab_size"]) == (48, 256, 100352)
    assert cfg["num_hidden_layers"] == 5
    assert cfg["num_experts"] == 8 >= 8
    assert cfg["vocab_size"] == 12544 == pub["vocab_size"] // 8
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
            cfg["sliding_window"], cfg["moe_routed_scaling_factor"],
            cfg["gating"]) == (
        3072, 48, 8, 128, 12288, 1024, 1024, 10, 1e-06, 512, 2.5,
        "per-head")
    rope = cfg["rope_parameters"]
    assert rope["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert rope["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
    a = cfg["train"]["args"]
    assert a["rope_parameters"] == rope
    assert (a["dim"], a["n_kv_heads"], a["head_dim"], a["dense_dim"],
            a["expert_dim"], a["shared_dim"], a["num_experts"], a["top_k"],
            a["routed_scale"], a["norm_epsilon"], a["seq_len"],
            a["sliding_window"]) == (
        3072, 8, 128, 12288, 1024, 1024, 256, 10, 2.5, 1e-06, 8192, 512)
    assert "remat" not in a and a["dense_layers"] == 1   # (a) stood
    assert a["shared_experts"] * a["shared_dim"] == cfg[
        "shared_expert_intermediate_size"]
    assert a["gain_range"] == [0.5, 1.5]
    assert (len(a["layer_types"]), a["held_experts"], a["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"])
    # the held layers: the dense one and a whole period after it, as the
    # four published lists say at their entries
    dep, share = cfg["deployment"], cfg["share"]
    held = dep["layers_held"]
    assert held == [0, 1, 2, 3, 4]
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(cfg[key]) == 48, key
    assert cfg["layer_types"] == (["full_attention"]
                                  + ["sliding_attention"] * 3) * 12
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert a["layer_types"] == [cfg["layer_types"][i] for i in held]
    assert a["heads_per_layer"] == [
        cfg["num_attention_heads_per_layer"][i] for i in held] == [
        48, 72, 72, 72, 48]
    assert [cfg["mlp_layer_types"][i] for i in held] == [
        "dense"] + ["sparse"] * 4
    assert cfg["mlp_only_layers"] == [0]
    assert {cfg["gating_types"][i] for i in held} == {"per_head"}
    assert dep["expert_parallel"] == 32
    assert dep["router_outputs"] == a["num_experts"] == 256
    assert dep["experts_held"] == [a["first_expert"], a["first_expert"]
                                   + a["held_experts"]] == [0, 8]
    assert dep["vocabulary_rows"] == [0, 12544]
    assert share["first_expert"] == a["first_expert"]
    assert share["buffer_rows"] == a["buffer_rows"]
    assert share["buffer_rows"] % 256 == 0        # the backward kernels' tile
    even = 8192 * 10 * 8 // 256
    assert even == 2560 and 2 * even <= share["buffer_rows"] < 8192
    feeds = cfg["train"]["feeds"]
    assert feeds["tokens"]["high"] == cfg["vocab_size"]
    assert feeds["targets"] == {"dist": "shift_left", "of": "tokens"}
    assert cfg["tokens_per_sample"] == a["seq_len"] == feeds["tokens"][
        "shape"][0]
    assert set(cfg["assumed"]) >= {
        "rule", "activation", "qk_norm", "router", "shared_gate",
        "auxiliary_loss", "gate", "attention_factor", "yarn", "window",
        "learning_rate", "weights", "tokens", "precision",
        "no_recomputation"}
    # share_ops.py's names, and what its roofline reader reads beside
    share_ops = harness.load_module("reduce", "share_ops")
    assert share_ops.dims_of(cfg, 1) == {
        "tokens": 8192, "rows": share["buffer_rows"], "pairs": 8192 * 10,
        "held": 8, "experts": 256, "dim": 3072, "expert_dim": 1024,
        "shared_dim": 1024, "conv_kernel": 0}
    f = cfg["flops"]
    assert (f["module"], f["function"]) == (
        "flops_laguna", "laguna_share_train_flops_per_sample")
    same = ("dim", "n_kv_heads", "head_dim", "dense_layers", "dense_dim",
            "num_experts", "held_experts", "expert_dim", "shared_dim",
            "top_k", "seq_len")
    assert {k: f["args"][k] for k in same} == {k: a[k] for k in same}
    assert f["args"]["vocab"] == a["vocab_size"]
    assert f["args"]["window"] == a["sliding_window"]
    assert (f["args"]["sliding_layers"], f["args"]["sliding_heads"]) == (
        a["layer_types"].count("sliding_attention"), 72) == (3, 72)
    assert (f["args"]["full_layers"], f["args"]["full_heads"]) == (
        a["layer_types"].count("full_attention"), 48) == (2, 48)
    assert f["args"]["expert_layers"] == 4


def test_parameter_count_is_the_stated_share():
    """811,030,784 parameters by the arithmetic the configuration states
    (`parameters_held`), from the builder's arguments; the program's own
    count is held by the AOT test below."""
    cfg = harness.load_json("configs", CONFIG)
    a = cfg["train"]["args"]
    d, dh, kv = a["dim"], a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    mixer = lambda h: 2 * d * h * dh + 2 * d * kv + d * h + 2 * dh  # noqa
    assert (mixer(72), mixer(48)) == (63_136_000, 44_187_904)
    experts = (d * a["num_experts"] + a["held_experts"] * 3 * d
               * a["expert_dim"] + 3 * d * a["shared_dim"] + d)
    dense = 3 * d * a["dense_dim"]
    layers = [mixer(h) + 2 * d + (dense if i < a["dense_layers"]
                                  else experts)
              for i, h in enumerate(a["heads_per_layer"])]
    assert layers == [157_440_256, 148_866_304, 148_866_304, 148_866_304,
                      129_918_208]
    total = sum(layers) + 2 * a["vocab_size"] * d + d
    assert total == cfg["parameters_held"] == 811_030_784
    assert "811,030,784" in cfg["deployment"]["about"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "paddle_tpu" not in body and "llm_ops" not in body
    ref = harness.load_module("reference", CONFIG)
    assert set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert ref.TOL["routed_pairs"] == ref.TOL["dropped_pairs"] == 0.0
    assert callable(ref.train_check) and callable(ref.control_check)
    cfg = harness.load_json("configs", CONFIG)
    assert set(cfg["train"]["check_fetch"]) | {"loss"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS} == set(ref.TOL)


# ---------------------------------------------------------------------------
# flops_laguna.py against hand counts


def test_live_pairs_and_attention_cost_by_hand():
    F = harness.load_module(".", "flops_laguna")
    for T, w in ((64, 16), (64, 1), (64, 63), (128, 64)):
        i, j = np.arange(T)[:, None], np.arange(T)[None, :]
        assert F.live_pairs(T, w) == int(((j <= i) & (i - j < w)).sum())
    assert F.live_pairs(64) == F.live_pairs(64, 64) == 64 * 65 // 2
    # the cell's: a window layer's live pairs are an eighth of the triangle
    assert F.live_pairs(8192, 512) == 4_063_488
    assert F.live_pairs(8192) == 33_558_528
    T, kv, d, w = 256, 8, 128, 64
    for H in (72, 48):
        for kind, matmuls, q_t, kv_t in (("fwd", 2, 2, 2),
                                         ("bwd_dq", 3, 3, 2),
                                         ("bwd_dkv", 4, 2, 4)):
            flops, nbytes = F.attention_cost(1, H, kv, T, d, kind, w)
            assert flops == H * F.live_pairs(T, w) * matmuls * 2 * d
            assert nbytes == T * d * 2 * (q_t * H + kv_t * kv)
            full = F.attention_cost(2, H, kv, T, d, kind)
            assert full[0] == 2 * H * (T * (T + 1) // 2) * matmuls * 2 * d
            assert full[1] == 2 * nbytes
    # at the cell's shapes compute binds a full layer and memory a sliding
    # one's forward (72 heads of 8192 x 128 over 512 keys each)
    peaks = harness.peaks_for("TPU v5 lite")
    roof = harness.load_module(".", "flops").roofline_seconds
    assert roof(*F.attention_cost(1, 48, 8, 8192, 128, "fwd"), peaks)[
        1] == "compute"
    assert roof(*F.attention_cost(1, 72, 8, 8192, 128, "bwd_dkv", 512),
                peaks)[1] == "compute"


def test_head_gate_cost_by_hand():
    F = harness.load_module(".", "flops_laguna")
    T, H, d, D = 16, 3, 4, 8
    flops, nbytes = F.head_gate_cost(1, H, T, d, D, "fwd")
    assert flops == 2 * T * D * H
    # h and W_g read, g written; a read, gated a written, g read
    assert nbytes == 2 * (T * D + D * H + T * H + 2 * T * H * d + T * H)
    flops, nbytes = F.head_gate_cost(1, H, T, d, D, "bwd")
    assert flops == 4 * T * D * H
    # dOut, a read, da written; g read, dg written and read; h and W_g
    # read, dh's part and dW_g written
    assert nbytes == 2 * (3 * T * H * d + 3 * T * H + 2 * T * D + 2 * D * H)
    with pytest.raises(ValueError):
        F.head_gate_cost(1, H, T, d, D, "both")
    # at the cell's shape the bytes bind by far
    peaks = harness.peaks_for("TPU v5 lite")
    f, b = F.head_gate_cost(1, 72, 8192, 128, 3072, "fwd")
    assert b / peaks["hbm_bytes_per_s"] > 10 * f / peaks["bf16_flops_per_s"]


def test_share_train_flops_by_hand():
    F = harness.load_module(".", "flops_laguna")
    # a toy, by hand: one sliding layer of 2 heads and one full layer of 1
    # on 1 key/value head of 2, hidden 2; one dense layer of 3, one expert
    # layer: 2 of 4 experts of width 3 held, 1 a token, a shared expert of
    # 2; 5 rows of vocabulary, 4 tokens, a window of 2
    got = F.laguna_share_train_flops_per_sample(
        dim=2, sliding_layers=1, sliding_heads=2, full_layers=1,
        full_heads=1, window=2, n_kv_heads=1, head_dim=2, dense_layers=1,
        dense_dim=3, expert_layers=1, num_experts=4, held_experts=2,
        expert_dim=3, shared_dim=2, top_k=1, vocab=5, seq_len=4)
    per_token = (2 * 2 * (2 * 2 * 2 + 2 * 2 + 2)      # sliding: 2 heads
                 + 2 * 2 * (2 * 1 * 2 + 2 * 2 + 1)    # full: 1 head
                 + 3 * 2 * 2 * 3                      # dense
                 + 2 * 2 * 4 + 0.5 * 3 * 2 * 2 * 3 + 3 * 2 * 2 * 2)
    scores = 2 * 2 * 2 * 2 * (4 * 2 - 1) + 1 * 2 * 2 * 2 * 10
    assert got == 3 * (4 * per_token + scores + 4 * 2 * 2 * 5)
    cfg = harness.load_json("configs", CONFIG)
    whole = harness.flops_per_sample(cfg)
    assert whole == pytest.approx(30.000e12, rel=1e-4)
    # the two full layers' live pairs are a fifth of it, the three sliding
    # layers' a twentieth, the held experts under a fiftieth
    a = cfg["flops"]["args"]
    full = 3 * 2 * 48 * 4 * 128 * F.live_pairs(8192)
    sliding = 3 * 3 * 72 * 4 * 128 * F.live_pairs(8192, 512)
    assert full / whole == pytest.approx(0.165, abs=0.005)
    assert sliding / whole == pytest.approx(0.045, abs=0.005)
    held = 3 * 8192 * 4 * a["top_k"] * a["held_experts"] / a[
        "num_experts"] * 6 * 3072 * 1024
    assert held / whole < 0.02


# ---------------------------------------------------------------------------
# the six readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
    """A reduced trace with the three flash kernels' seconds and calls."""

    SECONDS = {"flash_fwd": 0.050, "flash_bwd_dq": 0.060,
               "flash_bwd_dkv": 0.080}
    CALLS = {"flash_fwd": 10, "flash_bwd_dq": 10, "flash_bwd_dkv": 10}

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
    monkeypatch.setattr(
        harness.load_module("reduce", "moe_ops"), "events",
        lambda path: [[f"%{n} = f32[] fusion()", s, d]
                      for n, s, d, _, _, _ in events])
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


def test_gate_readers_add_up_the_part_and_never_pass_the_roof(monkeypatch):
    """`attn_head_gate_device_ms`: every event that carries `attn.gate`, at
    its self time; one that holds ANOTHER matrix product (the multiply
    fused into W_o's operand) at what is over the product's least; a copy
    that is not the part's own does not count.
    `attn_head_gate_hbm_roofline`: the same events WHOLE under the least by
    flops_laguna.head_gate_cost, so a gate fused away reads low and never
    over 100."""
    ms = 1_000_000
    peaks = harness.peaks_for("TPU v5 lite")
    peak = peaks["bf16_flops_per_s"]
    events = [
        ("fusion.1", 0, 2 * ms, ("attn.window", "attn.gate"), True, 0.0),
        ("fusion.2", 2 * ms, 6 * ms, ("attn.window", "attn.gate"), True,
         4e-3 * peak),       # W_o's product with the multiply inside
        ("fusion.3", 8 * ms, 1 * ms, ("attn.full", "attn.gate"), True, 0.0),
        ("fusion.4", 9 * ms, 5 * ms, ("attn.full",), True, 3e-3 * peak),
        ("copy.1", 14 * ms, ms, ("attn.gate",), False, 0.0),
        ("fusion.5", 15 * ms, 3 * ms, ("lm.head",), True, 0.0)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)  # noqa
    assert read("attn_head_gate_device_ms") == pytest.approx(
        (2 + (6 - 4) + 1) / 2)
    assert run["detail"]["attn_head_gate_device_ms"] == {
        "events_a_step": 3 / 2, "in_product_events_a_step": 1 / 2,
        "in_product_events_ms_a_step": pytest.approx(6 / 2),
        "whole_events_ms_a_step": pytest.approx(9 / 2)}
    F = harness.load_module(".", "flops_laguna")
    least = sum(
        layers * sum(F.head_gate_cost(1, heads, 8192, 128, 3072, kind)[1]
                     for kind in ("fwd", "bwd")) / peaks["hbm_bytes_per_s"]
        for layers, heads in ((3, 72), (2, 48)))
    got = read("attn_head_gate_hbm_roofline")
    assert got == pytest.approx(100.0 * least * 2 / 9e-3, rel=1e-9)
    note = run["detail"]["attn_head_gate_hbm_roofline"]
    assert {k: (v["layers"], v["heads"])
            for k, v in note["by_kind"].items()} == {
        "sliding": (3, 72), "full": (2, 48)}
    assert note["least_ms_a_step"] == pytest.approx(1e3 * least)
    # the cell's least: 4.95 ms a step of bytes at the roof
    assert 1e3 * least == pytest.approx(4.95, abs=0.05)


def test_shared_expert_reader_reads_the_shares_shared_kind(monkeypatch):
    """`expert_share_shared_device_ms`: share_ops.py's seconds of the kind
    `shared` (instructions on [tokens, shared width]; the configuration's
    `shared_experts` x `expert_dim` = 1024 columns) a traced step; nothing
    where the share has none."""
    reader = harness.load_module("layer_metrics",
                                 "expert_share_shared_device_ms")
    cfg = harness.load_json("configs", CONFIG)
    dims = harness.load_module("reduce", "share_ops").dims_of(cfg, 1)
    assert (dims["tokens"], dims["shared_dim"]) == (8192, 1024)
    run = _run([], monkeypatch, cfg)
    run["detail"]["share_seconds"] = {"shared": 0.0402, "calls": 48}
    assert reader.read(run) == pytest.approx(20.1)
    run["detail"]["share_seconds"] = {"shared": 0.0, "calls": 48}
    assert reader.read(run) is None


def test_mixedheads_roofline_readers_on_a_recorded_trace(monkeypatch):
    cfg = harness.load_json("configs", CONFIG)
    peaks = harness.peaks_for("TPU v5 lite")
    F = harness.load_module(".", "flops_laguna")
    roof = harness.load_module(".", "flops").roofline_seconds
    for name, kernel, kind in (
            ("mixedheads_flash_fwd_roofline", "flash_fwd", "fwd"),
            ("mixedheads_flash_bwd_dq_roofline", "flash_bwd_dq", "bwd_dq"),
            ("mixedheads_flash_bwd_dkv_roofline", "flash_bwd_dkv",
             "bwd_dkv")):
        run = _run([], monkeypatch, cfg)
        reader = harness.load_module("layer_metrics", name)
        got = reader.read(run)
        # three layers of 72 heads under the window, two of 48 over the
        # whole sequence
        least = sum(roof(*F.attention_cost(1, h, 8, 8192, 128, kind, w),
                         peaks)[0]
                    for h, w in ((72, 512),) * 3 + ((48, 0),) * 2)
        want = 100.0 * least * 2 / _Trace.SECONDS[kernel]
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
        note = run["detail"]["mixedheads_" + kernel + "_roofline"]
        assert {k: (v["layers"], v["heads"], v["group"])
                for k, v in note["by_kind"].items()} == {
            "sliding": (3, 72, 9), "full": (2, 48, 6)}
        assert note["by_kind"]["full"]["roof"] == "compute"
        assert note["calls_a_step"] == _Trace.CALLS[kernel] / 2
        # nothing to read: no trace; another family's `flops` entry
        assert reader.read(_run([], monkeypatch, cfg, trace=False)) is None
        for other in ("smallthinker-21b-a3b", "phi4-mini-flash"):
            assert reader.read(_run([], monkeypatch, harness.load_json(
                "configs", other))) is None


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program cannot run this cell, and another cell's names
    no such part: each reader returns None, never raises, and a run
    without a trace likewise."""
    events = [("fusion.1", 0, 1000, ("lm.head",), True, 0.0),
              ("fusion.2", 1000, 1000, (), True, 0.0)]
    other = harness.load_json("configs", "moonlight-16b-a3b")
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


def test_aot_laguna_train_step_fits_one_v5e():
    """One sequence of 8192 tokens through the published layers 0-4 at the
    published widths, 8 of 256 experts and 1/8 of the vocabulary, WITHOUT
    recomputation, fits one chip (PERF.md, PR 63, has the bytes) and fills
    most of it; all five attention layers run the flash kernels, three at
    a group of nine under the window and two at a group of six over the
    whole sequence, each call shape at the blocks PR 62's rule gives it;
    five gates a head; every layer's heads prepared by the kernel, the
    full layers' turn of 64 columns in 128 too; every share's
    rows leave the buffer by the segment-sum kernel, and no grad op
    launches a kernel's forward again."""
    import importlib.util

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

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
    assert sum(int(np.prod(p.shape)) for p in params) == cfg[
        "parameters_held"] == 811_030_784
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT laguna train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.75 * 16 * 2 ** 30, got
    # weights and Adam state alone: 811.0 M parameters at 10 bytes
    assert 8.11e9 < got["argument_bytes"] < 8.12e9, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: {tuple(sorted(s["labels"].items())): s["value"]  # noqa
                           for s in fam[name]["series"]}
    assert series("attention_layers_traced_total") == {
        (("layout", "bhtd"), ("path", "flash_window")): 3.0,
        (("layout", "bhtd"), ("path", "flash")): 2.0}
    assert series("flash_calls_total") == {
        (("mask", "causal"),): 2.0, (("mask", "window"),): 3.0}
    assert series("gqa_attention_layers_traced_total") == {
        (("head_dim", "128"), ("kv_heads", "8"), ("q_heads", "72")): 3.0,
        (("head_dim", "128"), ("kv_heads", "8"), ("q_heads", "48")): 2.0}
    assert series("attention_head_gates_traced_total") == {
        (("form", "head"), ("heads", "72")): 3.0,
        (("form", "head"), ("heads", "48")): 2.0}
    # one emission, one counter: a gate a head is not a gate an element's
    assert not fam.get("gated_attention_layers_traced_total",
                       {}).get("series")
    # one for Q and one for K a layer
    assert series("rope_tables_traced_total") == {
        (("rotary_dim", "128"), ("rule", "default"),
         ("theta", "10000")): 6.0,
        (("rotary_dim", "64"), ("rule", "yarn"), ("theta", "500000")): 4.0}
    assert series("qk_prep_layers_traced_total") == {
        (("head_dim", "128"), ("heads", "72"), ("norm", "head"),
         ("path", "pallas")): 3.0,
        (("head_dim", "128"), ("heads", "8"), ("norm", "head"),
         ("path", "pallas")): 5.0,
        (("head_dim", "128"), ("heads", "48"), ("norm", "head"),
         ("path", "pallas")): 2.0}
    blocks = series("flash_call_blocks_total")
    assert {k: v for k, v in blocks.items()
            if dict(k)["kernel"] == "flash_fwd"} == {
        (("block_k", "1024"), ("block_q", "1024"),
         ("kernel", "flash_fwd")): 3.0,
        (("block_k", "1024"), ("block_q", "2048"),
         ("kernel", "flash_fwd")): 2.0}
    rows = str(cfg["share"]["buffer_rows"])
    assert series("moe_share_layers_traced_total") == {
        (("buffer_rows", rows), ("experts", "256"), ("held", "8"),
         ("top_k", "10")): 4.0}
    assert series("moe_share_rows_to_tokens_traced_total") == {
        (("op", "combine"), ("path", "segment_sum")): 4.0,
        (("op", "permute_grad"), ("path", "segment_sum")): 4.0}
    assert series("moe_grouped_backward_total") == {
        (("impl", "pallas"),): 12.0}
    assert series("executor_grad_kernel_forward_total") == {
        (("op", "scaled_dot_product_attention"), ("reused", "1")): 5.0}
    obs.REGISTRY.reset()
