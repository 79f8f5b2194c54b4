"""What PR 52 adds to the benchmark, checked on the CPU: the
Phi-4-mini-flash program (published layers 12-19: three Mamba selective
scans, two window and one full differential-attention layer, a gated memory
unit and a cross-attention layer that reuse layer 16's scan output and layer
17's keys and values, a tied head) against its plain reference at a toy
size THROUGH THE CELL'S OWN DRIVER, the reference's controls, the
configuration against the catalog, the counts of
benchmarks/flops_phi4flash.py by hand, and the seven readers on made-up
events.  tests/benchmarks/test_benchmark.py holds the manifest-wide rules
over the same files; a test that reads BENCHMARK.json as a whole is named
`test_manifest...` and holds membership and content, never position.
"""

from __future__ import annotations

import collections
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
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "phi4-mini-flash"
CELL = "phi4flash_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("ssm_device_ms", "ssm_scan_device_ms", "ssm_scan_hbm_roofline",
           "gmu_device_ms", "window_flash_fwd_roofline",
           "window_flash_bwd_dq_roofline", "window_flash_bwd_dkv_roofline")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "attention_relayout_device_ms",
         "flash_scores_computed_pct")
# a control of the reference -> a key of the check that has to move by it
CONTROLS = {"fp8": "token_loss", "scan_bf16": "memory", "no_D": "memory",
            "window_plus_one": "window_attention",
            "local_lambda_init": "window_attention"}


def _toy_config(dtype="float32", seq_len=128):
    """Hidden 64, MLP 128; 8 query heads on 4 key/value heads of 8; window
    32; d_inner 128, state 4, dt_rank 4; vocabulary 96; T 128 = four windows
    and two chunks of the scan's 64; the cell's run of layers, published
    12-19."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=32, vocab_size=96)
    cfg["ssm"].update(d_state=4, dt_rank=4)
    cfg["train"]["args"].update(
        seq_len=seq_len, vocab_size=96, dim=64, n_heads=8, n_kv_heads=4,
        dense_dim=128, sliding_window=32, d_state=4, dt_rank=4, dtype=dtype,
        init_scale=0.3, learning_rate=0.003)
    cfg["train"]["feeds"]["tokens"].update(shape=[seq_len, 1], high=96)
    return cfg


def _ctx(config, traffic, tmp_path):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic,
        seed=2 ** 31 + 52, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


def _toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    t.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
             trace_seconds=0.2)
    return t


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    drv = harness.load_module("drivers", "train_executor")
    cfg = _toy_config("float32")
    rec = drv.run(_ctx(cfg, _toy_traffic(), tmp_path_factory.mktemp("toy")))
    import paddle_tpu as fluid

    scope = fluid.global_scope()
    params = [np.asarray(scope.find(p.name)) for p in
              fluid.default_main_program().global_block().all_parameters()]
    return cfg, rec, params


def test_driver_toy_phi4flash_float32_matches_the_reference(toy_run):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam under `layers.recompute`, against the plain
    reference on the same seeded weights: the loss, every token's loss,
    layer 16's memory, layer 15's attention result and every GRAD_PARAMS
    gradient; and the run is `correct`."""
    ref = harness.load_module("reference", CONFIG)
    _, rec, _ = toy_run
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "memory", "window_attention"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert max(errs.values()) < 2e-4, errs
    assert rec["correct"] and rec["checks"]["loss_fell"]
    assert set(rec["compared"]) == set(errs) | {
        "loss_at_fell_step", "compile_events_in_window"}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_phi4flash_reference_controls_move_the_check(toy_run, control):
    """Each control of the reference (the precision control `control_check`
    runs by default, and the four departures the issue names) moves a key
    of the check at toy size by far more than the program's distance from
    the reference; the limits that make each FAIL are set at the cell's
    size (PERF.md, PR 52)."""
    import jax

    ref = harness.load_module("reference", CONFIG)
    assert set(CONTROLS) == set(ref.CONTROLS + ref.CPU_ONLY)
    cfg, rec, params = toy_run
    tokens = np.random.RandomState(3).randint(0, 96, (1, 128))
    targets = np.roll(tokens, -1, axis=1)
    key = CONTROLS[control]

    def read(control):
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda ps: ref.check_fn(
                ps, tokens, targets, cfg, control, grad_params=())[key])(
                    [np.asarray(p, np.float32) for p in params]))

    want, got = read(""), read(control)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err > 50 * rec["checks"]["reference_errors"][key], (control, err)
    with pytest.raises(ValueError, match="one of"):
        ref.control_check(params, {}, cfg, control="no_such_control")


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
    # a segment's replay is a rerun by design; the accepted flash readers
    # count one causal call a layer
    assert not per & {
        "kernel_forward_reruns", "gqa_flash_fwd_roofline",
        "gqa_flash_bwd_dq_roofline", "gqa_flash_bwd_dkv_roofline",
        "flash_fwd_roofline", "flash_bwd_dq_roofline",
        "flash_bwd_dkv_roofline", "wide_flash_fwd_roofline",
        "gdn_device_ms", "linattn_device_ms", "collective_exposed_ms"}
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
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
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
                if '"Phi-4-mini-flash-reasoning"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for Phi-4-mini-flash-reasoning, key for key;
    only the depth and the vocabulary slice differ, and `reduced` says so;
    the widths the catalog lacks are Mamba-1's, under `assumed`."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32,
                                "vocab_size": 200064}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (8, 25008)
    assert cfg["vocab_size"] * 8 == 200064 and cfg["tie_word_embeddings"]
    a = cfg["train"]["args"]
    assert (a["dim"], a["dense_dim"], a["n_heads"], a["n_kv_heads"],
            a["sliding_window"], a["d_state"], a["d_conv"], a["expand"],
            a["dt_rank"], a["mb_per_layer"], a["norm_epsilon"]) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["sliding_window"], 16, 4, 2, 160, cfg["mb_per_layer"],
        cfg["layer_norm_eps"]) == (2560, 10240, 40, 20, 512, 16, 4, 2, 160,
                                   2, 1e-5)
    assert a["dim"] // a["n_heads"] == 64 and a["expand"] * a["dim"] == 5120
    assert {k: cfg["ssm"][k] for k in ("d_state", "d_conv", "expand",
                                       "dt_rank")} == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    dep = cfg["deployment"]
    assert dep["layers_held"] == a["layer_indices"] == list(range(12, 20))
    assert a["total_layers"] == 32 and dep["vocabulary_rows"] == [0, 25008]
    assert dep["vocabulary_parallel"] == 8 and a["vocab_size"] == 25008
    assert "2 : 2 : 1 : 1 : 1 : 1" in dep["about"]
    assert "8 : 8 : 1 : 1 : 7 : 7" in dep["about"]
    assert a["remat"] is True and a["seq_len"] == cfg[
        "tokens_per_sample"] == 8192 and a["dtype"] == "bfloat16"
    assert (a["gain_range"], a["bias_range"]) == ([0.5, 1.5], [-0.5, 0.5])
    assert {"ssm", "differential_attention", "head_pairing", "flash_calls",
            "biases", "window", "memory", "weights", "learning_rate",
            "precision", "memory_fit"} <= set(cfg["assumed"])
    assert cfg["train"]["feeds"]["tokens"]["high"] == 25008
    assert set(cfg["train"]["check_fetch"]) == {
        "token_loss", "memory", "window_attention"}
    f = cfg["flops"]["args"]
    assert (f["mamba_layers"], f["window_layers"], f["full_layers"],
            f["gmu_layers"], f["cross_layers"]) == (3, 2, 1, 1, 1)
    assert (f["d_inner"], f["head_dim"], f["vocab"]) == (5120, 64, 25008)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "pallas" not in code
    assert "import harness" not in code
    # the recurrence token by token, and dense masked softmax: no
    # cumulative decay, no associative scan, no kernel
    assert "cumsum" not in code and "associative_scan" not in code
    assert 'default_matmul_precision("highest")' in code
    ref = harness.load_module("reference", CONFIG)
    assert callable(ref.train_check) and callable(ref.control_check)
    assert {0, 51, 70, 72, 73, 74, 83, 99, 108} <= set(ref.GRAD_PARAMS)


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_train_flops_by_hand():
    """The issue's count: 6 x 851.3 M x 8192 = 41.8 TFLOP, the head 3.1, the
    attention's live pairs about 3.5 at the least form: about 49 TFLOP a
    sample."""
    F = harness.load_module(".", "flops_phi4flash")
    cfg = harness.load_json("configs", CONFIG)
    got = harness.flops_per_sample(cfg)
    T = 8192
    blocks = 3 * 119.9e6 + 3 * 98.3e6 + 104.9e6 + 91.8e6
    pairs = 2 * (T * (T + 1) // 2) + 2 * (T * 512 - 512 * 511 // 2)
    hand = 6 * blocks * T + 3 * 2 * 2560 * 25008 * T + 3 * 40 * pairs * 6 * 64
    assert abs(got - hand) < 0.02 * hand
    assert abs(got - 49e12) < 0.02 * 49e12
    assert F.live_pairs(T) == T * (T + 1) // 2
    assert F.live_pairs(T, 512) == sum(min(t + 1, 512) for t in range(T))
    assert F.live_pairs(64, 512) == F.live_pairs(64)
    # by kernel: scores once (2 d), values twice a head wide
    for kind, per_pair in (("fwd", 6), ("bwd_dq", 8), ("bwd_dkv", 12)):
        flops, nbytes = F.differential_attention_cost(1, T, 40, 20, 64, kind,
                                                      512)
        assert flops == 40 * F.live_pairs(T, 512) * per_pair * 64
    assert nbytes == T * 64 * 2 * (2 * 40 + 4 * 20)
    ops, nbytes = F.selective_scan_cost(1, T, 5120, 16, "fwd")
    assert ops == 9 * T * 5120 * 16
    assert nbytes == T * 2 * (3 * 5120 + 2 * 16)
    ops_b, bytes_b = F.selective_scan_cost(1, T, 5120, 16, "bwd")
    assert ops_b == 2 * ops and bytes_b == T * 2 * (5 * 5120 + 4 * 16)
    peaks = harness.peaks_for("TPU v5 lite")
    # HBM binds the least form
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks[
        "bf16_flops_per_s"]


# ---------------------------------------------------------------------------
# the seven readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
    """A reduced trace with the three flash kernels' seconds and calls."""

    SECONDS = {"flash_fwd": 0.080, "flash_bwd_dq": 0.060,
               "flash_bwd_dkv": 0.070}
    CALLS = {"flash_fwd": 32, "flash_bwd_dq": 16, "flash_bwd_dkv": 16}

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


def test_ssm_and_gmu_readers_add_up_their_parts_at_self_time(monkeypatch):
    ms = 1_000_000
    peaks = harness.peaks_for("TPU v5 lite")
    peak = peaks["bf16_flops_per_s"]
    mamba = "mixer.mamba"
    events = [
        # W_in's product alone: the projections', not the core's
        ("fusion.1", 0, 8 * ms, (mamba, "ssm.in_proj"), True, 8e-3 * peak),
        ("fusion.2", 8 * ms, 2 * ms, (mamba, "ssm.conv"), True, 0.0),
        # the small projections W_x and W_dt ARE the mixer's own: whole
        ("fusion.3", 10 * ms, 3 * ms, (mamba, "ssm.xdt"), True,
         1e-3 * peak),
        ("while.1", 13 * ms, 40 * ms, (mamba, "ssm.scan"), True, 0.0),
        # the gate fused into W_out's product: what is over its least
        ("fusion.4", 53 * ms, 6 * ms, (mamba, "ssm.gate_out"), True,
         4e-3 * peak),
        ("fusion.5", 59 * ms, 1 * ms, (mamba, "ssm.gate_out"), True, 0.0),
        ("fusion.6", 60 * ms, 7 * ms, ("mixer.gmu",), True, 6e-3 * peak),
        ("fusion.7", 67 * ms, 1 * ms, ("mixer.gmu",), True, 0.0),
        ("fusion.8", 68 * ms, 1 * ms, ("lm.head",), True, 0.0),
        ("copy.1", 69 * ms, ms // 2, (mamba, "ssm.scan"), False, 0.0)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)
    assert read("ssm_device_ms") == pytest.approx(
        (0 + 2 + 3 + 40 + 2 + 1) / 2)
    detail = run["detail"]["ssm_device_ms"]
    assert detail["projections_least_ms_a_step"] == pytest.approx(12 / 2)
    assert detail["ssm.gate_out_ms_a_step"] == pytest.approx(3 / 2)
    assert detail["ssm.in_proj_ms_a_step"] == pytest.approx(0.0)
    assert read("ssm_scan_device_ms") == pytest.approx(40 / 2)
    assert read("gmu_device_ms") == pytest.approx(8 / 2)
    assert run["detail"]["gmu_device_ms"][
        "in_product_events_ms_a_step"] == pytest.approx(7 / 2)
    F = harness.load_module(".", "flops_phi4flash")
    least = sum(max(f / peak, b / peaks["hbm_bytes_per_s"])
                for f, b in (F.selective_scan_cost(1, 8192, 5120, 16, kind)
                             for kind in ("fwd", "bwd")))
    # three Mamba layers (12, 14, 16 of the held 12-19), two steps
    got = read("ssm_scan_hbm_roofline")
    assert got == pytest.approx(100 * 3 * 2 * least / 40e-3)
    assert 0 < got < 100
    assert run["detail"]["ssm_scan_hbm_roofline"]["roofs"] == [
        "memory", "memory"]
    assert run["detail"]["ssm_scan_hbm_roofline"]["layers"] == 3


def test_window_flash_roofline_readers_on_a_recorded_trace(monkeypatch):
    cfg = harness.load_json("configs", CONFIG)
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    F = harness.load_module(".", "flops_phi4flash")
    for name, kernel, kind in (
            ("window_flash_fwd_roofline", "flash_fwd", "fwd"),
            ("window_flash_bwd_dq_roofline", "flash_bwd_dq", "bwd_dq"),
            ("window_flash_bwd_dkv_roofline", "flash_bwd_dkv", "bwd_dkv")):
        run = _run([], monkeypatch, cfg)
        reader = harness.load_module("layer_metrics", name)
        got = reader.read(run)
        # layers 13 and 15 under the window, 17 and 19 over the sequence
        least = sum(F.differential_attention_cost(
            1, 8192, 40, 20, 64, kind, w)[0] / peak
            for w in (512, 512, 0, 0))
        want = 100.0 * least * 2 / _Trace.SECONDS[kernel]
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
        note = run["detail"]["window_" + kernel + "_roofline"]
        assert note["roofs"] == ["compute"] * 4 and note["layers"] == 4
        assert note["calls_a_step"] == _Trace.CALLS[kernel] / 2
        # nothing to read: no trace; a configuration without a window
        assert reader.read(_run([], monkeypatch, cfg, trace=False)) is None
        assert reader.read(_run([], monkeypatch, harness.load_json(
            "configs", "lfm2-24b-a2b"))) is None


def test_flops_args_count_the_layers_the_published_rule_gives():
    """The readers and `mfu_pct` take the layers' counts and the window from
    the configuration's `flops.args`: held here to the program's own layer
    rule over the held layers, so that a change of the cut cannot leave the
    two apart."""
    from paddle_tpu.models.transformer import phi4flash_layer_kinds

    cfg = harness.load_json("configs", CONFIG)
    a, f = cfg["train"]["args"], cfg["flops"]["args"]
    kinds, windows = phi4flash_layer_kinds(
        a["total_layers"], a["sliding_window"], a["mb_per_layer"])
    held = [(kinds[i], windows[i]) for i in a["layer_indices"]]
    assert f["mamba_layers"] == sum(k == "mamba" for k, _ in held)
    assert f["window_layers"] == sum(
        k == "attention" and w is not None for k, w in held)
    assert f["full_layers"] == sum(
        k == "attention" and w is None for k, w in held)
    assert f["gmu_layers"] == sum(k == "gmu" for k, _ in held)
    assert f["cross_layers"] == sum(k == "cross_attention" for k, _ in held)
    assert {w for _, w in held if w is not None} == {f["window"]}
    assert (f["seq_len"], f["d_inner"], f["d_state"], f["head_dim"]) == (
        a["seq_len"], a["expand"] * a["dim"], a["d_state"],
        a["dim"] // a["n_heads"])
    assert (f["n_heads"], f["n_kv_heads"]) == (a["n_heads"], a["n_kv_heads"])


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program names no such part and launches no such kernel
    (and another cell's configuration has no such layer): each reader
    returns None, never raises, and a run without a trace likewise."""
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


@pytest.mark.slow
def test_aot_phi4flash_train_step_fits_one_v5e():
    """One sequence of 8192 tokens through published layers 12-19 at the
    published widths over 1/8 of the tied vocabulary, every block a
    recompute segment, fits one chip (PERF.md, PR 52, has the bytes) and
    fills more than half of it; all four attention layers run the flash
    kernels, two of them under the window; the scans run in chunks.  Slow
    (70 s of compilation): tests/test_phi4flash.py
    `test_parameter_count_at_the_published_sizes` and
    tests/test_kernel_gates.py build the same program's descs in tier-1."""
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
    assert sum(int(np.prod(p.shape)) for p in params) == 915_311_616
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT phi4flash train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.5 * 16 * 2 ** 30, got
    # weights and Adam state alone: 915.3 M parameters at 10 bytes
    assert 9.15e9 < got["argument_bytes"] < 9.16e9, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: {tuple(sorted(s["labels"].items())): s["value"]  # noqa
                           for s in fam[name]["series"]}
    assert series("attention_layers_traced_total") == {
        (("layout", "bhtd"), ("path", "flash")): 4.0,
        (("layout", "bhtd"), ("path", "flash_window")): 4.0}
    assert series("flash_calls_total") == {
        (("mask", "causal"),): 4.0, (("mask", "window"),): 4.0}
    assert series("selective_scan_total") == {
        (("chunk", "64"), ("d_inner", "5120"), ("d_state", "16"),
         ("impl", "xla_chunked")): 3.0}
    assert len(series("differential_attention_layers_traced_total")) == 4
    obs.REGISTRY.reset()
