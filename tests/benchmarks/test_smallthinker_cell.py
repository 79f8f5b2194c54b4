"""What PR 54 adds to the benchmark, checked on the CPU: the manifest's
entries of the cell `smallthinker_train_t16384`, its configuration against
the catalog's row, the counts of benchmarks/flops_smallthinker.py by hand,
the six new readers on made-up events, and the real size compiled for the
chip without one.  The program against the reference at a toy size
(through the cell's own driver) and the reference's mutants are in
tests/test_smallthinker_model.py.  tests/benchmarks/test_benchmark.py holds
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

CONFIG = "smallthinker-21b-a3b"
CELL = "smallthinker_train_t16384"
TRAFFIC = "train_staged_bs1_16k"
READERS = ("swa_gqa_flash_fwd_roofline", "swa_gqa_flash_bwd_dq_roofline",
           "swa_gqa_flash_bwd_dkv_roofline", "attn_window_device_ms",
           "attn_full_device_ms", "moe_route_device_ms")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "kernel_forward_reruns",
         "flash_scores_computed_pct", "expert_share_device_pct",
         "expert_share_grouped_matmul_roofline", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "attention_relayout_device_ms",
         "qk_prep_device_ms")


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
    # the causal half of EVERY layer is not this cell's least (a window
    # layer's live pairs are 43.7% of it: those shares would pass 100), nor
    # differential attention's count; nor another family's keys
    assert not per & {
        "flash_fwd_roofline", "gqa_flash_fwd_roofline",
        "gqa_flash_bwd_dq_roofline", "gqa_flash_bwd_dkv_roofline",
        "window_flash_fwd_roofline", "window_flash_bwd_dq_roofline",
        "window_flash_bwd_dkv_roofline", "bd_flash_fwd_roofline",
        "mla_flash_fwd_roofline", "moe_share_device_pct",
        "moe_device_share_pct", "mfu_local_pct", "mfu_active_pct",
        "collective_exposed_ms", "short_conv_device_ms"}
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
            traffic["trace_seconds"]) == (1, 8, 4, 16, 6)


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
        return [json.loads(x) for x in f
                if '"SmallThinker-21BA3B-Instruct"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for SmallThinker-21BA3B-Instruct, key for
    key, the two layouts WHOLE; only the depth, the experts held and the
    vocabulary slice differ, `reduced` says so, and each stays within the
    floors (a whole period and at least 4 layers, at least 8 experts, at
    least 1/8 of the vocabulary); the builder's arguments, the deployment,
    the share and the FLOPs' arguments say the same sizes."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["moe_num_primary_experts"],
            pub["vocab_size"]) == (52, 64, 151936)
    assert cfg["num_hidden_layers"] == 4 >= 4
    assert cfg["moe_num_primary_experts"] == 16 >= 8
    assert cfg["vocab_size"] == 37984 == pub["vocab_size"] // 4
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"],
            cfg["moe_num_active_primary_experts"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["sliding_window_size"],
            cfg["max_position_embeddings"]) == (
        2560, 28, 4, 128, 768, 6, 1500000, 1e-06, 4096, 16384)
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["n_kv_heads"], a["head_dim"],
            a["expert_dim"], a["num_experts"], a["top_k"], a["rope_theta"],
            a["norm_epsilon"], a["seq_len"], a["sliding_window"]) == (
        2560, 28, 4, 128, 768, 64, 6, 1500000.0, 1e-06, 16384, 4096)
    assert a["seq_len"] == cfg["max_position_embeddings"]
    assert "remat" not in a and a["dense_layers"] == 0   # (a) stood
    assert (len(a["layer_types"]), a["held_experts"], a["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
        cfg["vocab_size"])
    # the held layers are a whole period of the two published layouts
    dep, share = cfg["deployment"], cfg["share"]
    held = dep["layers_held"]
    assert held == [0, 1, 2, 3]
    assert len(cfg["sliding_window_layout"]) == len(cfg["rope_layout"]) == 52
    assert cfg["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert a["rope_layout"] == [cfg["rope_layout"][i] for i in held] == [
        0, 1, 1, 1]
    assert a["layer_types"] == [
        "sliding_attention" if cfg["sliding_window_layout"][i]
        else "full_attention" for i in held]
    assert dep["expert_parallel"] == 4
    assert dep["router_outputs"] == a["num_experts"] == 64
    assert dep["experts_held"] == [a["first_expert"], a["first_expert"]
                                   + a["held_experts"]] == [0, 16]
    assert dep["vocabulary_rows"] == [0, 37984]
    assert share["first_expert"] == a["first_expert"]
    assert share["buffer_rows"] == a["buffer_rows"]
    assert share["buffer_rows"] % 256 == 0        # the backward kernels' tile
    even = 16384 * 6 * 16 // 64
    assert even == 24576 and even < share["buffer_rows"] <= 16384 * 6
    feeds = cfg["train"]["feeds"]
    assert feeds["tokens"]["high"] == cfg["vocab_size"]
    assert feeds["targets"] == {"dist": "shift_left", "of": "tokens"}
    assert cfg["tokens_per_sample"] == a["seq_len"] == feeds["tokens"][
        "shape"][0]
    assert set(cfg["assumed"]) >= {
        "attention_bias", "qk_norm", "rope", "window", "router", "experts",
        "auxiliary_loss", "learning_rate", "weights", "tokens", "precision",
        "no_recomputation"}
    # share_ops.py's seven names, and what its roofline reader reads beside
    share_ops = harness.load_module("reduce", "share_ops")
    assert share_ops.dims_of(cfg, 1) == {
        "tokens": 16384, "rows": share["buffer_rows"], "pairs": 16384 * 6,
        "held": 16, "experts": 64, "dim": 2560, "expert_dim": 768,
        "shared_dim": 0, "conv_kernel": 0}
    f = cfg["flops"]
    assert (f["module"], f["function"]) == (
        "flops_smallthinker", "smallthinker_share_train_flops_per_sample")
    same = ("dim", "n_heads", "n_kv_heads", "head_dim", "num_experts",
            "held_experts", "expert_dim", "top_k", "seq_len")
    assert {k: f["args"][k] for k in same} == {k: a[k] for k in same}
    assert f["args"]["vocab"] == a["vocab_size"]
    assert f["args"]["window"] == a["sliding_window"]
    assert f["args"]["window_layers"] == a["layer_types"].count(
        "sliding_attention") == 3
    assert f["args"]["full_layers"] == a["layer_types"].count(
        "full_attention") == 1


def test_parameter_count_is_the_stated_share():
    """656,529,920 parameters by the arithmetic the configuration states
    (`parameters_held`), from the builder's arguments."""
    cfg = harness.load_json("configs", CONFIG)
    a = cfg["train"]["args"]
    d, hq, hkv = a["dim"], a["n_heads"] * a["head_dim"], a[
        "n_kv_heads"] * a["head_dim"]
    layer = (2 * d * hq + 2 * d * hkv + 2 * d + d * a["num_experts"]
             + a["held_experts"] * 3 * d * a["expert_dim"])
    assert layer == 115_512_320
    total = len(a["layer_types"]) * layer + 2 * a["vocab_size"] * d + d
    assert total == cfg["parameters_held"] == 656_529_920
    assert "656,529,920" in cfg["deployment"]["about"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    ref = harness.load_module("reference", CONFIG)
    assert set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert ref.TOL["routed_pairs"] == ref.TOL["dropped_pairs"] == 0.0
    assert callable(ref.train_check) and callable(ref.control_check)
    cfg = harness.load_json("configs", CONFIG)
    assert ref.layer_kinds(cfg) == [(0, False)] + [(4096, True)] * 3
    assert set(cfg["train"]["check_fetch"]) | {"loss"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS} == set(ref.TOL)


# ---------------------------------------------------------------------------
# flops_smallthinker.py against hand counts


def test_live_pairs_against_a_count_of_allowed():
    F = harness.load_module(".", "flops_smallthinker")
    for T, w in ((64, 16), (64, 1), (64, 63), (128, 64)):
        i, j = np.arange(T)[:, None], np.arange(T)[None, :]
        assert F.live_pairs(T, w) == int(((j <= i) & (i - j < w)).sum())
    assert F.live_pairs(64) == F.live_pairs(64, 64) == F.live_pairs(
        64, 512) == 64 * 65 // 2
    # the cell's: a window layer's live pairs are 43.7% of the triangle
    assert F.live_pairs(16384, 4096) == 58_722_304
    assert F.live_pairs(16384) == 134_225_920
    assert F.live_pairs(16384, 4096) / F.live_pairs(16384) == pytest.approx(
        0.4375, abs=2e-4)


def test_mixed_attention_cost_by_hand():
    F = harness.load_module(".", "flops_smallthinker")
    T, H, kv, d, w = 256, 28, 4, 128, 64
    for kind, matmuls, q_t, kv_t in (("fwd", 2, 2, 2), ("bwd_dq", 3, 3, 2),
                                     ("bwd_dkv", 4, 2, 4)):
        flops, nbytes = F.mixed_attention_cost(1, H, kv, T, d, kind, w)
        assert flops == H * F.live_pairs(T, w) * matmuls * 2 * d
        assert nbytes == T * d * 2 * (q_t * H + kv_t * kv)
        full = F.mixed_attention_cost(2, H, kv, T, d, kind)
        assert full[0] == 2 * H * (T * (T + 1) // 2) * matmuls * 2 * d
        assert full[1] == 2 * nbytes
    # at the cell's shape compute binds both kinds
    peaks = harness.peaks_for("TPU v5 lite")
    for w in (4096, 0):
        flops, nbytes = F.mixed_attention_cost(1, 28, 4, 16384, 128, "fwd", w)
        assert flops / peaks["bf16_flops_per_s"] > nbytes / peaks[
            "hbm_bytes_per_s"]


def test_share_train_flops_by_hand():
    F = harness.load_module(".", "flops_smallthinker")
    # a toy, by hand: one window layer and one full one, one head of 2 on a
    # hidden size of 2, 2 of 4 experts of width 3 held, 1 a token, 5 rows
    got = F.smallthinker_share_train_flops_per_sample(
        dim=2, window_layers=1, full_layers=1, window=2, n_heads=1,
        n_kv_heads=1, head_dim=2, num_experts=4, held_experts=2,
        expert_dim=3, top_k=1, vocab=5, seq_len=4)
    per_token = 2 * 2 * (2 * 2 + 2 * 2) + 2 * 2 * 4 + 0.5 * 3 * 2 * 2 * 3
    scores = 1 * 2 * 2 * 2 * ((4 * 2 - 1) + 10)
    assert got == 3 * (4 * 2 * per_token + scores + 4 * 2 * 2 * 5)
    cfg = harness.load_json("configs", CONFIG)
    whole = harness.flops_per_sample(cfg)
    assert whole == pytest.approx(34.698e12, rel=1e-4)
    # attention's live pairs are 38% of it, the held experts a tenth
    a = cfg["flops"]["args"]
    scores = 3 * a["n_heads"] * 4 * a["head_dim"] * (
        3 * F.live_pairs(16384, 4096) + F.live_pairs(16384))
    assert scores / whole == pytest.approx(0.385, abs=0.005)


# ---------------------------------------------------------------------------
# the six readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
    """A reduced trace with the three flash kernels' seconds and calls."""

    SECONDS = {"flash_fwd": 0.100, "flash_bwd_dq": 0.095,
               "flash_bwd_dkv": 0.115}
    CALLS = {"flash_fwd": 8, "flash_bwd_dq": 8, "flash_bwd_dkv": 8}

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


def test_part_readers_add_up_their_parts_at_self_time(monkeypatch):
    """`attn_window_device_ms`, `attn_full_device_ms` and
    `moe_route_device_ms`: an event counts whole, a matrix product too (the
    projections are the layer's), under every part its instruction carries
    (the kernels lie in `attn.attend` INSIDE `attn.window`); a `while`
    keeps what its body leaves; a copy that is not the part's own does not
    count."""
    ms = 1_000_000
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    events = [
        ("fusion.1", 0, 8 * ms, ("attn.window",), True, 6e-3 * peak),
        ("flash_fwd.1", 8 * ms, 20 * ms, ("attn.window", "attn.attend"),
         True, 0.0),
        ("fusion.2", 28 * ms, 2 * ms, ("attn.window", "attn.qk_prep"), True,
         0.0),
        ("fusion.3", 30 * ms, 5 * ms, ("attn.full",), True, 4e-3 * peak),
        ("flash_fwd.2", 35 * ms, 30 * ms, ("attn.full", "attn.attend"),
         True, 0.0),
        ("fusion.4", 65 * ms, 3 * ms, ("moe.route",), True, 1e-3 * peak),
        ("sort.1", 68 * ms, 1 * ms, ("moe.route",), True, 0.0),
        ("fusion.5", 69 * ms, 4 * ms, ("moe.experts",), True, 0.0),
        ("fusion.6", 73 * ms, 1 * ms, ("lm.head",), True, 0.0),
        ("copy.1", 74 * ms, ms // 2, ("attn.window",), False, 0.0)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)  # noqa
    assert read("attn_window_device_ms") == pytest.approx(30 / 2)
    assert run["detail"]["attn_window_device_ms"] == {
        "events_a_step": 3 / 2,
        "in_product_events_ms_a_step": pytest.approx(8 / 2)}
    assert read("attn_full_device_ms") == pytest.approx(35 / 2)
    assert read("moe_route_device_ms") == pytest.approx(4 / 2)
    assert run["detail"]["moe_route_device_ms"][
        "in_product_events_ms_a_step"] == pytest.approx(3 / 2)


def test_swa_gqa_roofline_readers_on_a_recorded_trace(monkeypatch):
    cfg = harness.load_json("configs", CONFIG)
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    F = harness.load_module(".", "flops_smallthinker")
    for name, kernel, kind in (
            ("swa_gqa_flash_fwd_roofline", "flash_fwd", "fwd"),
            ("swa_gqa_flash_bwd_dq_roofline", "flash_bwd_dq", "bwd_dq"),
            ("swa_gqa_flash_bwd_dkv_roofline", "flash_bwd_dkv", "bwd_dkv")):
        run = _run([], monkeypatch, cfg)
        reader = harness.load_module("layer_metrics", name)
        got = reader.read(run)
        # three layers under the window, one over the whole sequence
        least = sum(F.mixed_attention_cost(1, 28, 4, 16384, 128, kind, w)[0]
                    / peak for w in (4096, 4096, 4096, 0))
        want = 100.0 * least * 2 / _Trace.SECONDS[kernel]
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
        note = run["detail"]["swa_gqa_" + kernel + "_roofline"]
        assert {k: (v["roof"], v["layers"])
                for k, v in note["by_kind"].items()} == {
            "window": ("compute", 3), "full": ("compute", 1)}
        assert note["calls_a_step"] == _Trace.CALLS[kernel] / 2
        # nothing to read: no trace; another family's `flops` entry
        assert reader.read(_run([], monkeypatch, cfg, trace=False)) is None
        for other in ("phi4-mini-flash", "lfm2-24b-a2b"):
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


def test_aot_smallthinker_train_step_fits_one_v5e():
    """One sequence of 16384 tokens through the published layers 0-3 at the
    published widths, 16 of 64 experts and 1/4 of the vocabulary, WITHOUT
    recomputation, fits one chip (PERF.md, PR 54, has the bytes) and fills
    most of it; all four attention layers run the flash kernels at a group
    of seven query heads, three under the window on [B, H, T, D] and the
    full-span one on the projections' layout with its heads split inside;
    every router read the mixer's input, every share's rows leave the
    buffer by the segment-sum kernel, and no grad op launches a kernel's
    forward again."""
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
    assert sum(int(np.prod(p.shape)) for p in params) == 656_529_920
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT smallthinker train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.75 * 16 * 2 ** 30, got
    # weights and Adam state alone: 656.5 M parameters at 10 bytes
    assert 6.56e9 < got["argument_bytes"] < 6.57e9, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: {tuple(sorted(s["labels"].items())): s["value"]  # noqa
                           for s in fam[name]["series"]}
    assert series("attention_layers_traced_total") == {
        (("layout", "bhtd"), ("path", "flash_window")): 3.0,
        (("layout", "bthd"), ("path", "flash")): 1.0}
    assert series("attention_layer_kinds_traced_total") == {
        (("positions", "none"), ("window", "0")): 1.0,
        (("positions", "rope"), ("window", "4096")): 3.0}
    assert series("flash_calls_total") == {
        (("mask", "causal"),): 1.0, (("mask", "window"),): 3.0}
    assert series("gqa_attention_layers_traced_total") == {
        (("head_dim", "128"), ("kv_heads", "4"), ("q_heads", "28")): 4.0}
    assert series("qk_prep_layers_traced_total") == {
        (("head_dim", "128"), ("heads", "28"), ("norm", "none"),
         ("path", "pallas")): 3.0,
        (("head_dim", "128"), ("heads", "4"), ("norm", "none"),
         ("path", "pallas")): 3.0}
    assert series("moe_router_input_traced_total") == {
        (("source", "mixer"),): 4.0}
    rows = str(cfg["share"]["buffer_rows"])
    assert series("moe_share_layers_traced_total") == {
        (("buffer_rows", rows), ("experts", "64"), ("held", "16"),
         ("top_k", "6")): 4.0}
    assert series("moe_share_rows_to_tokens_traced_total") == {
        (("op", "combine"), ("path", "segment_sum")): 4.0,
        (("op", "permute_grad"), ("path", "segment_sum")): 4.0}
    assert series("moe_grouped_backward_total") == {
        (("impl", "pallas"),): 12.0}
    assert series("executor_grad_kernel_forward_total") == {
        (("op", "scaled_dot_product_attention"), ("reused", "1")): 4.0}
    obs.REGISTRY.reset()
