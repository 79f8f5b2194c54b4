"""What PR 71 adds to the benchmark, checked on the CPU: the cell's entries in
the manifest (membership and content, never position), the configuration
against the catalog's row, the counts of benchmarks/flops_ouro.py by hand,
the three readers on made-up events and rows, the reference file's shape,
the parameter count and the two counters' series from the cell's real
program (built, never compiled, in tier-1), and (slow) that program compiled
ONCE for a described v5e: the reading that chose the depth.
tests/test_ouro.py holds the program to the reference through the cell's
driver at toy size; tests/benchmarks/test_benchmark.py holds the
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

CONFIG = "ouro-2.6b"
CELL = "ouro_train_t4096"
TRAFFIC = "train_staged_bs1_16k"
READERS = ("loop_pass_device_ms", "shared_grad_sum_device_ms",
           "shared_grad_sum_hbm_roofline")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "qk_prep_device_ms",
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
    assert set(LISTS) | {"compile_s", "cache_misses"} <= per
    assert {"loop_pass_device_ms", "shared_grad_sum_device_ms"} <= per
    assert "shared_grad_sum_hbm_roofline" not in per
    # no state-space, linear or expert layer; a segment's replay is a rerun
    # by design; the plain flash readers take GPT-2's keys
    assert not per & {
        "ssd_device_ms", "ssm_device_ms", "gmu_device_ms",
        "kernel_forward_reruns", "flash_fwd_roofline", "linattn_device_ms",
        "gdn_device_ms", "kda_device_ms", "collective_exposed_ms",
        "moe_device_share_pct", "mtp_device_ms", "hc_device_ms"}
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json("configs", CONFIG)
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert len(cell["why"]) <= 200 and "read 4 times" in cell["why"]
    traffic = harness.load_json("traffic", TRAFFIC)
    assert (traffic["driver"], traffic["generator"], traffic["batch"],
            traffic["staged_batches"], traffic["loss_read_every"],
            traffic["loss_fell_step"], traffic["trace_seconds"]) == (
        "train_executor", "staged_batches", 1, 8, 4, 16, 6)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_with_its_own_values(name):
    """The roofline's reader is on disk and in NO entry: in the cell's step
    the adds ride in other fusions and it has nothing to read (PERF.md
    section 6, PR 71); a metric no cell reports is not listed."""
    m = harness.load_manifest()
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__.startswith(name) and callable(mod.read)
    assert (mod.UNIT == "%") == name.endswith("_roofline")
    if name == "shared_grad_sum_hbm_roofline":
        assert name not in {x["name"] for x in m["per_layer"]}
        return
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
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
        return [json.loads(x) for x in f if '"name": "Ouro-2.6B"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for Ouro-2.6B, key for key (`layer_types`
    whole: the held eight are its entries 0-7); only the depth differs,
    and `reduced` says so."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 8 and (
        cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (4, 1)
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["rms_norm_eps"],
            cfg["rope_theta"], cfg["tie_word_embeddings"]) == (
        2048, 5632, 16, 16, 128, 49152, 1e-6, 1000000, False)
    a = cfg["train"]["args"]
    assert cfg["train"]["builder"].endswith(
        ":build_decoder_lm_train_program")
    assert (a["dim"], a["dense_dim"], a["n_heads"], a["n_kv_heads"],
            a["head_dim"], a["vocab_size"], a["norm_epsilon"],
            a["rope_theta"], a["n_layers"]) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["vocab_size"], cfg["rms_norm_eps"],
        cfg["rope_theta"], cfg["num_hidden_layers"])
    assert a["loop"] == {"passes": cfg["total_ut_steps"],
                         "exit_gate": True}
    assert (a["sandwich"], a["norm"], a["positions"], a["ffn"], a["remat"],
            a["dtype"], a["exit_beta"]) == (
        True, "rms_norm", "rope", "gated_mlp", True, "bfloat16", 0.1)
    assert a["seq_len"] == cfg["tokens_per_sample"] == 4096
    dep = cfg["deployment"]
    assert dep["layers_held"] == list(range(cfg["num_hidden_layers"]))
    assert len(cfg["layer_types"]) == 48 == dep["pipeline_stages"] * cfg[
        "num_hidden_layers"]
    assert set(cfg["layer_types"]) == {"full_attention"}
    assert dep["ring"] is True and dep["vocabulary_parallel"] == 1
    assert {"sandwich_norms", "norm_between_passes", "positions",
            "exit_gate", "objective", "mlp", "weights", "learning_rate",
            "tokens", "precision", "memory_fit", "depth_rule"} <= set(
        cfg["assumed"])
    assert cfg["train"]["feeds"]["tokens"]["high"] == 49152
    assert set(cfg["train"]["check_fetch"]) == {"token_loss", "exit_probs"}
    held = cfg["parameters_held"]
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert held["block"] == layer == 51_388_416
    assert held["total"] == (cfg["num_hidden_layers"] * layer
                             + 2 * 49152 * 2048 + 2048 + 2049)
    f = cfg["flops"]["args"]
    assert (f["n_layers"], f["passes"], f["seq_len"], f["vocab"],
            f["head_dim"], f["dim"], f["dense_dim"]) == (
        cfg["num_hidden_layers"], 4, 4096, 49152, 128, 2048, 5632)
    assert cfg["flops"]["module"] == "flops_ouro"


def test_reference_is_plain_and_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "pallas" not in code
    assert "import harness" not in code and "from ops" not in code
    # dense masked softmax from the mask's definition, the passes a plain
    # Python loop over one list
    assert "tril" not in code and "for t in range(passes)" in code
    assert 'default_matmul_precision("highest")' in code
    ref = harness.load_module("reference", CONFIG)
    assert callable(ref.train_check) and callable(ref.control_check)
    assert set(ref.TOL) == {"loss", "token_loss", "exit_probs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert set(range(12)) | {89, 90, 91} <= set(ref.GRAD_PARAMS)
    # the gate's bias is ONE number: no relative limit holds for it in bf16
    assert 92 not in ref.GRAD_PARAMS
    assert ref.CENTERED == ("token_loss",)
    assert {"three_passes", "no_norm_between_passes", "no_result_norms",
            "last_pass_grad_only", "no_entropy", "last_gate_times_survival",
            "gate_before_norm", "fp8"} <= set(ref.MUTANTS)
    assert len(ref.MUTANTS) == len(set(ref.MUTANTS))


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_train_flops_by_hand():
    """The issue's count, per token forward: a block application 2 x 51.38 M
    of products and 16.8 M of causal attention at T 4096; 32 of them (48 at
    twelve layers: 80.4 TFLOP) and four head passes of 201.3 M: 4.63 GFLOP,
    56.9 TFLOP a step forward and backward.  Never flops.py's dense count at
    one pass."""
    F = harness.load_module(".", "flops_ouro")
    cfg = harness.load_json("configs", CONFIG)
    got = harness.flops_per_sample(cfg)
    T, L = 4096, cfg["num_hidden_layers"]
    products = 2 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    attention = 4 * 128 * 16 * (T + 1) / 2
    head = 2 * 2048 * 49152
    hand = 3 * T * 4 * (L * (products + attention) + head + 2 * 2048)
    assert got == pytest.approx(hand, rel=1e-12)
    assert abs(got - 56.9e12) < 0.001 * 56.9e12
    assert round(attention / 1e6, 1) == 16.8 and round(
        products / 1e6, 2) == 102.76
    one_pass = F.ouro_train_flops_per_sample(**dict(
        cfg["flops"]["args"], passes=1))
    assert got == pytest.approx(4 * one_pass)
    shared = F.shared_parameters(2048, 5632, 16, 16, 128, L, 49152)
    assert shared == L * 51_388_416 + 2048 + 2048 * 49152 + 2049
    assert shared == cfg["parameters_held"]["total"] - 49152 * 2048
    ops, nbytes = F.shared_grad_sum_cost(shared, 4)
    assert (ops, nbytes) == (3 * shared, 5 * 2 * shared)
    peaks = harness.peaks_for("TPU v5 lite")
    assert ops / peaks["bf16_flops_per_s"] < 0.05 * nbytes / peaks[
        "hbm_bytes_per_s"]


# ---------------------------------------------------------------------------
# the three readers on made-up events and rows


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
    @staticmethod
    def window_of(trace):
        return (0, 1_000_000_000)


def _run(events, monkeypatch, rows=None, config=None, trace=True):
    """A `run` whose trace holds `events` = [(name, start, dur, parts, own,
    product flops)] on one device, 2 traced steps, and whose op_scopes
    table is `rows` (None: under the coverage floor)."""
    H = harness.load_module("reduce", "hlo_scopes")
    P = harness.load_module("reduce", "part_ms")
    S = harness.load_module("reduce", "op_scopes")
    notes = {name: Note(frozenset(parts), own, flops)
             for name, _, _, parts, own, flops in events}
    monkeypatch.setattr(H, "of_trace", lambda path: notes)
    monkeypatch.setattr(S, "covered", lambda run: (
        None if rows is None else {"rows": rows, "coverage": 0.99}))
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


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def test_loop_pass_reader_adds_up_each_pass_at_self_time(monkeypatch):
    ms = 1_000_000
    events = [
        # a pass's products count WHOLE, with whatever inner part they carry
        ("fusion.1", 0, 10 * ms, ("loop.a",), True, 1e12),
        ("fusion.2", 10 * ms, 2 * ms, ("loop.a", "attn.qk_prep"), True, 0.0),
        ("fusion.3", 12 * ms, 8 * ms, ("loop.b",), True, 1e12),
        ("fusion.4", 20 * ms, 6 * ms, ("loop.c",), True, 0.0),
        ("fusion.5", 26 * ms, 8 * ms, ("loop.d",), True, 0.0),
        # head, loss and gate of a pass are beside its blocks
        ("fusion.6", 34 * ms, 4 * ms, ("loop.d", "lm.head"), True, 1e11),
        ("fusion.7", 38 * ms, 1 * ms, ("loop.b", "lm.loss"), True, 0.0),
        ("fusion.8", 39 * ms, 1 * ms, ("loop.c", "loop.gate"), True, 0.0),
        # two passes' instructions in one fusion (a computation XLA keeps
        # once for both, a part's add) are the blocks' and no one pass's
        ("fusion.9", 40 * ms, 3 * ms, ("loop.a", "loop.b", "grad.sum"),
         True, 0.0),
        ("fusion.11", 50 * ms, 1 * ms, ("loop.a", "loop.b", "lm.head"),
         True, 0.0),
        ("fusion.10", 43 * ms, 5 * ms, ("loop.exit",), True, 0.0),
        ("copy.1", 48 * ms, 2 * ms, ("loop.a",), False, 0.0)]
    run = _run(events, monkeypatch)
    got = _read("loop_pass_device_ms", run)
    assert got == pytest.approx((12 + 8 + 6 + 8 + 3) / 2 / 4)
    detail = run["detail"]["loop_pass_device_ms"]
    assert detail["passes"] == pytest.approx(
        {"loop.a": 6.0, "loop.b": 4.0, "loop.c": 3.0, "loop.d": 4.0})
    assert detail["head_loss_gate_ms"] == pytest.approx(3.5)
    assert detail["mixed_ms"] == pytest.approx(1.5)


def _row(ms, ops, parts, flops=0.0):
    return {"ms": ms, "inherited_ms": 0.0, "events": 1,
            "product_flops": flops, "ops": list(ops), "parts": list(parts)}


def test_shared_grad_sum_readers_read_the_adds_that_stand_alone(monkeypatch):
    rows = {"sum[grad.sum]": _row(6.0, ["sum"], ["grad.sum"]),
            "adam": _row(20.0, ["adam"], []),
            "mul[lm.head]": _row(9.0, ["mul"], ["lm.head"], 1e12)}
    run = _run([], monkeypatch, rows)
    assert _read("shared_grad_sum_device_ms", run) == pytest.approx(6.0)
    assert run["detail"]["shared_grad_sum_device_ms"]["rides_in"] == {}
    F = harness.load_module(".", "flops_ouro")
    cfg = harness.load_json("configs", CONFIG)
    shared = cfg["parameters_held"]["total"] - 49152 * 2048
    peaks = harness.peaks_for("TPU v5 lite")
    least_ms = 1e3 * F.shared_grad_sum_cost(shared, 4)[1] / peaks[
        "hbm_bytes_per_s"]
    got = _read("shared_grad_sum_hbm_roofline", run)
    assert got == pytest.approx(100 * least_ms / 6.0)
    note = run["detail"]["shared_grad_sum_hbm_roofline"]
    assert (note["roof"], note["parts"], note["shared_parameters"]) == (
        "memory", 4, shared)
    # 10 bytes a shared parameter at 819 GB/s: the adds alone cannot be
    # faster, so a reading as fast as HBM allows stays under 100
    fast = dict(rows, **{"sum[grad.sum]": _row(1.01 * least_ms, ["sum"],
                                               ["grad.sum"])})
    assert 95 < _read("shared_grad_sum_hbm_roofline",
                      _run([], monkeypatch, fast)) < 100


def test_shared_grad_sum_readers_say_where_the_adds_ride(monkeypatch):
    """Where XLA put the adds into the products' or the update's fusions no
    row is the adds' alone: both readers give None, and the detail names
    the rows; a roofline is not read over SOME of the adds."""
    riding = {"adam+mul_grad+sum[grad.sum]": _row(
        40.0, ["adam", "mul_grad", "sum"], ["grad.sum"], 1e12),
        "recompute_grad+sum[grad.sum]": _row(
            5.0, ["recompute_grad", "sum"], ["grad.sum"], 1e11)}
    run = _run([], monkeypatch, riding)
    assert _read("shared_grad_sum_device_ms", run) == 0.0
    assert list(run["detail"]["shared_grad_sum_device_ms"]["rides_in"]) == [
        "adam+mul_grad+sum[grad.sum]", "recompute_grad+sum[grad.sum]"]
    assert _read("shared_grad_sum_hbm_roofline", run) is None
    both = dict(riding, **{"sum[grad.sum]": _row(2.0, ["sum"],
                                                 ["grad.sum"])})
    run = _run([], monkeypatch, both)
    assert _read("shared_grad_sum_device_ms", run) == pytest.approx(2.0)
    assert _read("shared_grad_sum_hbm_roofline", run) is None


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program names no pass and no `grad.sum` (and another
    cell's configuration has no `passes`): each reader returns None, never
    raises, and a run without a trace or under the coverage floor
    likewise."""
    events = [("fusion.1", 0, 1000, ("lm.head",), True, 0.0),
              ("fusion.2", 1000, 1000, ("mixer.mamba", "ssm.scan"), True,
               0.0)]
    rows = {"adam+mul_grad": _row(30.0, ["adam", "mul_grad"], [], 1e12),
            "sum": _row(1.0, ["sum"], [])}
    other = harness.load_json("configs", "phi4-mini-flash")
    for config in (None, other):
        run = _run(events, monkeypatch, rows, config)
        for name in READERS:
            assert _read(name, run) is None, name
    for run in (_run(events, monkeypatch, rows, trace=False),
                _run(events, monkeypatch, None)):
        for name in READERS[1:]:
            assert _read(name, run) is None
    assert _read("loop_pass_device_ms",
                 _run(events, monkeypatch, rows, trace=False)) is None
    # the part is there and the configuration counts no passes
    alone = {"sum[grad.sum]": _row(6.0, ["sum"], ["grad.sum"])}
    assert _read("shared_grad_sum_hbm_roofline",
                 _run([], monkeypatch, alone, other)) is None
    assert _read("shared_grad_sum_device_ms",
                 _run([], monkeypatch, alone, other)) == pytest.approx(6.0)


# ---------------------------------------------------------------------------
# the real size: the program's descs in tier-1, its compile for a described
# v5e marked slow


@pytest.fixture(scope="module")
def built():
    """The cell's real program, built and not compiled, and what the two
    counters read while it was built."""
    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

    cfg = harness.load_json("configs", CONFIG)
    fluid.reset()
    obs.REGISTRY.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    fam = obs.REGISTRY.snapshot()["families"]
    series = {name: {tuple(sorted(s["labels"].items())): s["value"]
                     for s in fam[name]["series"]}
              for name in ("backward_grad_parts_total",
                           "decoder_lm_loop_passes_total")}
    obs.REGISTRY.reset()
    main = fluid.default_main_program()
    yield cfg, main, loss, series
    fluid.reset()


def test_parameter_count_from_the_program(built):
    """612,438,017 parameters at eight layers, counted from the program the
    generic builder makes at the published widths: ONE copy of every block
    however many passes read it; 32 block segments and four head
    segments; the caller's `loop` dict as it was written."""
    cfg, main, _, _ = built
    L = cfg["num_hidden_layers"]
    shapes = [tuple(p.shape) for p in main.global_block().all_parameters()]
    count = lambda some: sum(int(np.prod(s)) for s in some)
    assert count(shapes) == cfg["parameters_held"]["total"]
    assert count(shapes) == 612_438_017
    assert len(shapes) == 1 + 11 * L + 4
    assert count(shapes[1:12]) == 51_388_416
    assert shapes[0] == (49152, 2048) and shapes[-4:] == [
        (2048,), (2048, 49152), (2048, 1), (1,)]
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("recompute") == 4 * L + 4
    every = [op.type for b in main.blocks for op in b.ops]
    assert (every.count("scaled_dot_product_attention"),
            every.count("head_norm_rope"),
            every.count("softmax_with_cross_entropy")) == (
        4 * L, 8 * L, 4)
    assert cfg["train"]["args"]["loop"] == {"passes": 4, "exit_gate": True}
    drv = harness.load_module("drivers", "train_executor")
    fetched = drv._check_vars(main, cfg["train"]["check_fetch"])
    block = main.global_block()
    assert tuple(block.var(fetched["token_loss"]).shape)[-1] == 4
    assert tuple(block.var(fetched["exit_probs"]).shape)[-1] == 4


def test_counters_at_the_cells_args(built):
    """Traced (the program is built), not compiled: `backward_grad_parts_
    total` says the 11 L block parameters, the final gain and the head are
    finalized from 4 parts, the gate's vector and bias from 3 (the last
    pass's gate weighs nothing in the objective), the embedding from 1;
    `decoder_lm_loop_passes_total` 4; every parameter's `sum` of parts
    carries the part `grad.sum`."""
    cfg, main, _, series = built
    L = cfg["num_hidden_layers"]
    assert series["backward_grad_parts_total"] == {
        (("parts", "1"),): 1.0, (("parts", "3"),): 2.0,
        (("parts", "4"),): 11.0 * L + 2}
    assert series["decoder_lm_loop_passes_total"] == {(): 4.0}
    sums = [op for op in main.global_block().ops
            if op.type == "sum" and op.attrs.get("part") == "grad.sum"]
    assert len(sums) == 11 * L + 4
    assert sorted({len(op.input_names()) for op in sums}) == [3, 4]


@pytest.fixture(scope="module")
def aot_step(built):
    import importlib.util

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import paddle_tpu as fluid

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
    cfg, main, loss, _ = built
    params = main.global_block().all_parameters()
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT ouro train step:", got)
    return {"got": got, "cfg": cfg}


@pytest.mark.slow
def test_aot_ouro_train_step_fits_one_v5e_under_the_depth_rule(aot_step):
    """One sequence of 4096 tokens through eight blocks read four times at
    the published widths over the whole vocabulary, every block application
    and each pass's head and loss a recompute segment: `peak_bytes` under
    the 15.0 GB of the issue's depth rule with room for what the chip holds
    beside a step (at TWELVE layers it read 14.85-15.14 GB and the chip ran
    out: PERF.md section 6, PR 71) and over half the chip; with
    `append_backward`'s `sum` as it is the four parts of a shared
    parameter's gradient are NOT alive together.  Slow (100 s of
    compilation): `test_parameter_count_from_the_program`
    and `test_counters_at_the_cells_args` build the same program's descs in
    tier-1."""
    got, cfg = aot_step["got"], aot_step["cfg"]
    assert got["peak_bytes"] <= 13.5e9, got
    assert got["peak_bytes"] > 0.5 * 16 * 2 ** 30, got
    # weights and Adam state alone: 10 bytes a parameter
    total = cfg["parameters_held"]["total"]
    assert 10 * total <= got["argument_bytes"] < 10.001 * total
    # a block application's three flash kernels and its replay's forward
    assert got["mosaic_calls"] == 4 * 4 * cfg["num_hidden_layers"]
