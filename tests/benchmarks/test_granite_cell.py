"""What PR 67 adds to the benchmark, checked on the CPU: the cell's entries in
the manifest (membership and content, never position), the configuration
against the catalog's row, the counts of benchmarks/flops_granite.py by
hand, the four readers on made-up events, the reference file's shape, the
parameter count from the cell's real program, and (slow) that program
compiled ONCE for a described v5e: its fit and what its counters say ran.
tests/test_granite_model.py holds the program to the reference through the
cell's driver at toy size; tests/benchmarks/test_benchmark.py holds the
manifest-wide rules.  A test that reads BENCHMARK.json as a whole is named
`test_manifest...`.
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

CONFIG = "granite-4.0-h-micro"
CELL = "granite4h_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("ssd_device_ms", "ssd_scan_device_ms", "ssd_scan_hbm_roofline",
           "ssd_conv_norm_device_ms")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "attention_relayout_device_ms",
         "flash_scores_computed_pct", "flash_dead_grid_steps_pct",
         "gqa_flash_fwd_roofline", "gqa_flash_bwd_dq_roofline",
         "gqa_flash_bwd_dkv_roofline", "executor_run_ms.window",
         "dispatch_execute_ms.window", "step_stall_pct.window")


# ---------------------------------------------------------------------------
# the manifest's entries, the configuration, the reference file


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert set(LISTS) | set(READERS) | {"compile_s", "cache_misses"} <= per
    # the diagonal scan's readers count another op; a segment's replay is a
    # rerun by design; the plain flash readers take GPT-2's keys
    assert not per & {
        "ssm_device_ms", "ssm_scan_device_ms", "ssm_scan_hbm_roofline",
        "ssm_scan_kernel_pct", "gmu_device_ms", "kernel_forward_reruns",
        "flash_fwd_roofline", "linattn_device_ms", "gdn_device_ms",
        "kda_device_ms", "collective_exposed_ms"}
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json("configs", CONFIG)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert len(cell["why"]) <= 200 and "Mamba-2" in cell["why"]
    traffic = harness.load_json("traffic", TRAFFIC)
    assert (traffic["driver"], traffic["generator"], traffic["batch"],
            traffic["staged_batches"], traffic["loss_read_every"],
            traffic["loss_fell_step"], traffic["trace_seconds"]) == (
        "train_executor", "staged_batches", 1, 8, 8, 32, 3)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__.startswith(name) and callable(mod.read)
    assert (mod.UNIT == "%") == (name.endswith(("_roofline", "_pct")))
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
                if '"name": "granite-4.0-h-micro"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for granite-4.0-h-micro, key for key
    (`layer_types` whole: the held ten are its entries 0-9); only the depth
    and the vocabulary slice differ, and `reduced` says so."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert cfg["vocab_size"] * 8 == 100352 and cfg["tie_word_embeddings"]
    assert (cfg["num_local_experts"], cfg["shared_intermediate_size"],
            cfg["position_embedding_type"]) == (0, 8192, "nope")
    a = cfg["train"]["args"]
    assert (a["dim"], a["dense_dim"], a["n_heads"], a["n_kv_heads"],
            a["mamba_n_heads"], a["mamba_d_head"], a["mamba_d_state"],
            a["mamba_n_groups"], a["mamba_d_conv"], a["norm_epsilon"]) == (
        cfg["hidden_size"], cfg["shared_intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
        cfg["mamba_n_groups"], cfg["mamba_d_conv"], cfg["rms_norm_eps"]) == (
            2048, 8192, 32, 8, 64, 64, 128, 1, 4, 1e-5)
    assert a["mamba_n_heads"] * a["mamba_d_head"] == cfg[
        "mamba_expand"] * cfg["hidden_size"] == 4096
    assert a["dim"] // a["n_heads"] == 64
    assert (a["attention_multiplier"], a["embedding_multiplier"],
            a["residual_multiplier"], a["logits_scaling"]) == (
        cfg["attention_multiplier"], cfg["embedding_multiplier"],
        cfg["residual_multiplier"], cfg["logits_scaling"]) == (
            0.015625, 12, 0.22, 8)
    assert a["attention_multiplier"] != 64 ** -0.5
    dep = cfg["deployment"]
    held = [cfg["layer_types"][i] for i in dep["layers_held"]]
    assert dep["layers_held"] == list(range(10)) and len(
        cfg["layer_types"]) == 40
    assert a["layer_types"] == held == ["mamba"] * 5 + ["attention"] + [
        "mamba"] * 4
    assert cfg["layer_types"].count("attention") * 10 == len(
        cfg["layer_types"])       # the held 9 : 1 is the published 36 : 4
    assert (dep["pipeline_stages"], dep["vocabulary_parallel"],
            dep["vocabulary_rows"]) == (4, 8, [0, 12544])
    assert a["remat"] is True and a["seq_len"] == cfg[
        "tokens_per_sample"] == 8192 and a["dtype"] == "bfloat16"
    assert set(a["remat_keep"]) <= {"mlp.up", "ssm.in_proj"}
    assert a["mamba_chunk"] in (64, 128, 256) and cfg[
        "mamba_chunk_size"] == 256
    assert (a["gain_range"], a["conv_bias_scale"]) == ([0.5, 1.5], 0.5)
    assert {"in_proj_order", "gate_then_norm", "convolution",
            "time_step_limit", "state", "attention", "mlp", "multipliers",
            "weights", "learning_rate", "tokens", "chunk", "precision",
            "memory_fit"} <= set(cfg["assumed"])
    assert cfg["train"]["feeds"]["tokens"]["high"] == 12544
    assert set(cfg["train"]["check_fetch"]) == {"token_loss", "scan"}
    held_params = cfg["parameters_held"]
    assert held_params["total"] == 772_160_448 == (
        9 * held_params["mamba_layer"] + held_params["attention_layer"]
        + held_params["embedding_rows_and_final_gain"])
    f = cfg["flops"]["args"]
    assert (f["mamba_layers"], f["attention_layers"]) == (
        held.count("mamba"), held.count("attention"))
    assert (f["seq_len"], f["vocab"], f["head_dim"], f["mamba_n_heads"],
            f["mamba_d_head"], f["mamba_d_state"], f["mamba_n_groups"],
            f["mamba_chunk_size"]) == (8192, 12544, 64, 64, 64, 128, 1, 256)


def test_reference_is_the_recurrence_and_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "pallas" not in code
    assert "import harness" not in code and "from ops" not in code
    # the recurrence token by token and dense masked softmax: no cumulative
    # decay, no associative scan, no chunked form
    assert "cumsum" not in code and "associative_scan" not in code
    assert "tril" not in code and "lax.scan(token" in code
    assert 'default_matmul_precision("highest")' in code
    assert 'cfg["attention_multiplier"]' in code
    # dots in the name: loaded by path, as laguna-s-2.1.py is
    ref = harness.load_module("reference", CONFIG)
    assert callable(ref.train_check) and callable(ref.control_check)
    assert set(ref.TOL) == {"loss", "token_loss", "scan"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert {0, 2, 3, 4, 5, 6, 7, 8, 9, 67, 68, 115, 122, 125, 127} <= set(
        ref.GRAD_PARAMS)
    assert ref.CENTERED == ("token_loss",)
    assert len(ref.KNOWN) == len(set(ref.KNOWN)) >= 24


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_train_flops_by_hand():
    """The issue's count, per token forward: a Mamba layer's W_in 34.9 M,
    W_out 16.8 M, the scan's products 4.3 M at Q 256, the MLP 100.7 M; the
    attention layer 21.0 M + 33.6 M of live scores + 100.7 M; the head 51.4
    M: 1.62 GFLOP, 39.7 TFLOP a sample forward and backward."""
    F = harness.load_module(".", "flops_granite")
    cfg = harness.load_json("configs", CONFIG)
    got = harness.flops_per_sample(cfg)
    T = 8192
    w_in, w_out, mlp = 2 * 2048 * 8512, 2 * 4096 * 2048, 6 * 2048 * 8192
    scan = 2 * 256 * 128 + 2 * 256 * 4096 + 4 * 4096 * 128
    taps = 2 * 4 * 4352
    attn = 2 * 2048 * (32 + 16) * 64 + 2 * 2048 * 2048
    pairs = 32 * (T * (T + 1) // 2) * 4 * 64
    hand = 3 * (T * (9 * (w_in + w_out + scan + taps + mlp) + attn + mlp
                     + 2 * 2048 * 12544) + pairs)
    assert got == pytest.approx(hand, rel=1e-12)
    assert abs(got - 39.7e12) < 0.005 * 39.7e12
    assert (round(w_in / 1e6, 1), round(w_out / 1e6, 1), round(
        scan / 1e6, 1), round(mlp / 1e6, 1)) == (34.9, 16.8, 4.3, 100.7)
    assert F.live_pairs(T) == sum(range(1, T + 1))
    ops, nbytes = F.ssd_scan_cost(1, T, 64, 64, 128, 1, "fwd")
    assert ops == T * scan
    assert nbytes == T * 2 * (2 * 4096 + 2 * 128 + 64)
    ops_b, bytes_b = F.ssd_scan_cost(1, T, 64, 64, 128, 1, "bwd")
    assert ops_b == 2 * ops
    assert bytes_b == T * 2 * (3 * 4096 + 4 * 128 + 2 * 64)
    # two groups: C B^T twice; a short sequence is one chunk
    assert F.ssd_scan_cost(1, T, 64, 64, 128, 2, "fwd")[0] == T * (
        scan + 2 * 256 * 128)
    assert F.ssd_scan_cost(1, 64, 64, 64, 128, 1, "fwd")[0] == 64 * (
        2 * 64 * 128 + 2 * 64 * 4096 + 4 * 4096 * 128)
    peaks = harness.peaks_for("TPU v5 lite")
    # at the published sizes the products' least is ABOVE the bytes': a
    # share of the HBM least cannot pass 100 while the MXU does the products
    assert (ops + ops_b) / peaks["bf16_flops_per_s"] > (
        nbytes + bytes_b) / peaks["hbm_bytes_per_s"]


# ---------------------------------------------------------------------------
# the four readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
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


def test_ssd_readers_add_up_their_parts_at_self_time(monkeypatch):
    ms = 1_000_000
    peaks = harness.peaks_for("TPU v5 lite")
    peak = peaks["bf16_flops_per_s"]
    mixer = "mixer.ssd"
    events = [
        # W_in's product alone: the projection's, not the core's
        ("fusion.1", 0, 8 * ms, (mixer, "ssm.in_proj"), True, 8e-3 * peak),
        ("fusion.2", 8 * ms, 3 * ms, (mixer, "ssd.conv"), True, 0.0),
        ("fusion.3", 11 * ms, 1 * ms, (mixer, "ssd.dt"), True, 0.0),
        # the scan's products ARE the scan: whole
        ("fusion.4", 12 * ms, 20 * ms, (mixer, "ssd.scan"), True,
         5e-3 * peak),
        ("while.1", 32 * ms, 4 * ms, (mixer, "ssd.scan"), True, 0.0),
        # the gated norm fused into W_out's product: what is over its least
        ("fusion.5", 36 * ms, 6 * ms, (mixer, "ssd.norm"), True,
         4e-3 * peak),
        ("fusion.6", 42 * ms, 2 * ms, (mixer, "ssd.norm"), True, 0.0),
        # W_out's product alone carries no part of the core
        ("fusion.7", 44 * ms, 5 * ms, (mixer,), True, 5e-3 * peak),
        ("fusion.8", 49 * ms, 1 * ms, ("lm.head",), True, 0.0),
        ("copy.1", 50 * ms, ms // 2, (mixer, "ssd.scan"), False, 0.0)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)
    assert read("ssd_device_ms") == pytest.approx(
        (3 + 1 + 20 + 4 + 2 + 2) / 2)
    detail = run["detail"]["ssd_device_ms"]
    assert detail["in_proj_ms_a_step"] == pytest.approx(8 / 2)
    assert detail["ssd.norm_ms_a_step"] == pytest.approx(4 / 2)
    assert detail["ssd.dt_ms_a_step"] == pytest.approx(1 / 2)
    assert read("ssd_scan_device_ms") == pytest.approx(24 / 2)
    assert read("ssd_conv_norm_device_ms") == pytest.approx((3 + 4) / 2)
    F = harness.load_module(".", "flops_granite")
    costs = [F.ssd_scan_cost(1, 8192, 64, 64, 128, 1, kind)
             for kind in ("fwd", "bwd")]
    least = sum(b for _, b in costs) / peaks["hbm_bytes_per_s"]
    # nine Mamba-2 layers (published 0-4 and 6-9), two steps
    got = read("ssd_scan_hbm_roofline")
    assert got == pytest.approx(100 * 9 * 2 * least / 24e-3)
    assert 0 < got < 100
    note = run["detail"]["ssd_scan_hbm_roofline"]
    assert note["layers"] == 9
    assert note["least_ms_a_layer_a_step"] == pytest.approx(1e3 * least)
    assert note["mxu_least_ms_a_layer_a_step"] == pytest.approx(
        1e3 * sum(f for f, _ in costs) / peak)
    # the same least whatever implements the scan: a kernel as fast as the
    # MXU allows reads under 100
    fast = [("custom-call.1", 0, int(note["mxu_least_ms_a_layer_a_step"]
                                     * 9 * 2 * ms), (mixer, "ssd.scan"),
             True, 0.0)]
    assert 50 < harness.load_module(
        "layer_metrics", "ssd_scan_hbm_roofline").read(
            _run(fast, monkeypatch)) < 100


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program names no such part (and another cell's
    configuration has no such layer): each reader returns None, never
    raises, and a run without a trace likewise."""
    events = [("fusion.1", 0, 1000, ("lm.head",), True, 0.0),
              ("fusion.2", 1000, 1000, ("mixer.mamba", "ssm.scan"), True,
               0.0),
              ("fusion.3", 2000, 1000, ("ssm.in_proj",), True, 1e9)]
    other = harness.load_json("configs", "phi4-mini-flash")
    for config in (None, other):
        run = _run(events, monkeypatch, config)
        for name in READERS:
            assert harness.load_module("layer_metrics", name).read(
                run) is None, name
    run = _run(events, monkeypatch, trace=False)
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(run) is None
    # the parts are there and the configuration names no Mamba-2 layer
    scan = [("fusion.1", 0, 1000, ("ssd.scan",), True, 0.0)]
    assert harness.load_module("layer_metrics", "ssd_scan_hbm_roofline").read(
        _run(scan, monkeypatch, other)) is None


# ---------------------------------------------------------------------------
# the real size: the program's descs in tier-1, its compile for a described
# v5e marked slow (the suite ran 1388 s of 1470 before this PR: CHANGES.md)


@pytest.fixture(scope="module")
def built():
    """The cell's real program, built and not compiled."""
    import paddle_tpu as fluid

    cfg = harness.load_json("configs", CONFIG)
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main = fluid.default_main_program()
    yield cfg, main, loss
    fluid.reset()


def test_parameter_count_from_the_program(built):
    """772,160,448 parameters, counted from the program the builder makes at
    the published widths: nine Mamba-2 layers of 76,182,976, the attention
    layer's 60,821,504, 12544 embedding rows and the final gain; every block
    a segment that holds what `remat_keep` names."""
    cfg, main, _ = built
    shapes = [tuple(p.shape) for p in main.global_block().all_parameters()]
    count = lambda some: sum(int(np.prod(s)) for s in some)
    assert count(shapes) == 772_160_448 == cfg["parameters_held"]["total"]
    assert len(shapes) == 128
    assert count(shapes[1:14]) == 76_182_976        # published layer 0
    assert count(shapes[2:10]) == 25_847_232        # its Mamba-2 mixer
    assert count(shapes[66:75]) == 60_821_504       # published layer 5
    assert shapes[2] == (2048, 4096 + 4352 + 64) and shapes[3] == (4352, 4)
    assert shapes[0] == (12544, 2048) and shapes[127] == (2048,)
    keep = cfg["train"]["args"]["remat_keep"]
    per_mamba = 2 * ("mlp.up" in keep) + ("ssm.in_proj" in keep)
    kept = [len(op.attrs.get("keep_names", []))
            for op in main.global_block().ops if op.type == "recompute"]
    assert kept == [per_mamba] * 5 + [2 * ("mlp.up" in keep)] + [
        per_mamba] * 4
    kinds = [op.type for b in main.blocks for op in b.ops]
    assert (kinds.count("ssd_scan"), kinds.count("gated_rms_norm"),
            kinds.count("scaled_dot_product_attention")) == (9, 9, 1)


@pytest.fixture(scope="module")
def aot_step(built):
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
    cfg, main, loss = built
    obs.REGISTRY.reset()
    params = main.global_block().all_parameters()
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT granite train step:", got)
    fam = obs.REGISTRY.snapshot()["families"]
    series = {name: {tuple(sorted(s["labels"].items())): s["value"]
                     for s in fam[name]["series"]} for name in fam}
    obs.REGISTRY.reset()
    return {"got": got, "hbm": tb.HBM, "series": series, "cfg": cfg}


@pytest.mark.slow
def test_aot_granite_train_step_fits_one_v5e(aot_step):
    """One sequence of 8192 tokens through published layers 0-9 at the
    published widths over 1/8 of the tied vocabulary, every block a
    recompute segment that holds what `remat_keep` names, fits one chip
    with 0.5 GB to spare for what the reference check fetches (PERF.md, PR
    67, has the bytes) and fills more than half of it.  Slow (50-130 s of
    compilation): the suite has no such second left (CHANGES.md, PR 67);
    `test_parameter_count_from_the_program` and tests/test_kernel_gates.py
    build the same program's descs in tier-1."""
    got = aot_step["got"]
    assert got["peak_bytes"] < aot_step["hbm"] - 0.5e9, got
    assert got["peak_bytes"] > 0.5 * 16 * 2 ** 30, got
    # weights and Adam state alone: 10 bytes a parameter
    assert 7.72e9 < got["argument_bytes"] < 7.73e9
    # the one attention layer's three flash kernels and its replay's forward
    assert got["mosaic_calls"] == 4


@pytest.mark.slow
def test_aot_counters_say_what_the_step_runs(aot_step):
    """Nine chunked scans of 64 heads of 64 on a state of 128 in one group;
    ONE attention layer, 32 query heads on 8 key/value heads of 64 in the
    flash kernels at the scale 1/64."""
    series, cfg = aot_step["series"], aot_step["cfg"]
    chunk = str(cfg["train"]["args"]["mamba_chunk"])
    assert series["ssd_scan_total"] == {
        (("chunk", chunk), ("d_state", "128"), ("groups", "1"),
         ("head_dim", "64"), ("heads", "64"), ("impl", "xla_chunked")): 9.0}
    assert series["attention_softmax_scale_traced_total"] == {
        (("scale", "0.015625"),): 1.0}
    assert series["gqa_attention_layers_traced_total"] == {
        (("head_dim", "64"), ("kv_heads", "8"), ("q_heads", "32")): 1.0}
    assert series["attention_layers_traced_total"] == {
        (("layout", "bthd"), ("path", "flash")): 1.0}
    assert series["flash_calls_total"] == {(("mask", "causal"),): 1.0}
    assert not series.get("selective_scan_total")
