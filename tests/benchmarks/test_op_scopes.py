"""What PR 35 adds to the benchmark, checked on the CPU: the join of a
trace's device events to the desc ops and model parts the compiled program
names (benchmarks/reduce/op_scopes.py) on instructions recorded from a
real trace, the arithmetic of its table, and the five readers
(`step_attributed_pct`, `optimizer_fused_device_ms`,
`optimizer_fused_roofline`, `head_loss_device_ms`, `op_emit_s`).
A test that reads BENCHMARK.json as a whole is named `test_manifest...`
and holds membership and content, never position.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

import paddle_tpu as fluid  # noqa: E402

M = harness.load_module("reduce", "op_scopes")
H = harness.load_module("reduce", "hlo_scopes")
T = harness.load_module("reduce", "trace")

SIX = {"resnet50_train_bs128", "gpt2m_train_bs8", "resnet50_dp4_train",
       "olmoe_train_t4096", "moonlight_train_t8192", "lfm2_train_t8192"}
LMS = SIX - {"resnet50_train_bs128", "resnet50_dp4_train"}
ENTRIES = {  # name -> (unit, better, source, layer, moves, cells or None)
    "step_attributed_pct": ("%", "higher", "device_trace", "model step",
                            "train_samples_per_s", SIX),
    "optimizer_fused_device_ms": ("ms", "lower", "device_trace",
                                  "model step", "train_samples_per_s", SIX),
    "optimizer_fused_roofline": ("%", "higher", "device_trace",
                                 "model step", "train_samples_per_s", SIX),
    "head_loss_device_ms": ("ms", "lower", "device_trace", "model step",
                            "train_samples_per_s", LMS),
    "op_emit_s": ("s", "lower", "program_counter", "compile cache",
                  "setup_s", None)}
GUARDED = ("optimizer_fused_device_ms", "optimizer_fused_roofline",
           "head_loss_device_ms")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def _recorded():
    """(events [[text, start, duration]], {instruction name: Row}, the
    file) of tests/benchmarks/recorded_op_scopes.json."""
    with open(os.path.join(HERE, "recorded_op_scopes.json"),
              encoding="utf-8") as f:
        rec = json.load(f)
    comps: dict = {}
    for ins in rec["instructions"]:
        comps.setdefault(ins["comp"], []).append(ins)
    return rec["events"], M.rows_of(comps), rec


def _run(rows, coverage=1.0, steps=4):
    """A traced run as a reader sees it, with the table already made."""
    class Ctx:
        config = {}

    return {"record": {"trace_path": "x.xplane.pb",
                       "traced": {"steps": steps}},
            "ctx": Ctx(), "trace": {"devices": {}, "host": []},
            "tracemod": T, "peaks": PEAKS,
            "detail": {"op_scopes": {
                "steps": steps, "busy_ms": sum(r["ms"] for r in rows.values()),
                "coverage": coverage, "rows": rows}}}


def _row(ms, ops, parts=(), flops=0.0, inherited=0.0):
    return {"ms": ms, "inherited_ms": inherited, "events": 1,
            "product_flops": flops, "ops": sorted(ops),
            "parts": sorted(parts)}


def _count_update(param, state, op="adam"):
    """What tracing a step of `param` + `state` bytes leaves in the
    program's counter."""
    from paddle_tpu.observability import REGISTRY

    fluid.reset()
    fam = REGISTRY.counter("optimizer_update_bytes_total", "")
    fam.inc(param, op=op, tensor="param")
    fam.inc(state, op=op, tensor="state")
    fam.inc(param / 2, op=op, tensor="grad")
    REGISTRY.counter("executor_op_emit_seconds_total", "").inc(0.5, op=op)


# ---------------------------------------------------------------------------
# the arithmetic: every instant of busy time in exactly one row


@pytest.mark.parametrize("spans, want", [
    ([(0, 10), (12, 15)], [10, 3]),                       # apart
    ([(0, 10), (2, 4), (3, 5)], [7, 1, 2]),               # nested, and a
    # child that outlives its sibling: the last started owns the instant
    ([(0, 10), (5, 15)], [5, 10]),                        # overlapping
    ([(0, 10), (0, 4)], [6, 4]),                          # started together
    ([(5, 9), (0, 2), (1, 7)], [4, 1, 4]),                # out of order
    ([], []),
])
def test_self_time_partitions_the_union(spans, want):
    got = M.self_ns(spans)
    assert got == want
    assert sum(got) == T.total([list(s) for s in spans])


def test_every_event_is_in_exactly_one_row_and_the_rows_sum_to_busy():
    events, rows, _ = _recorded()
    # the recorded events back to back, the last two laid INSIDE the first
    # (a loop's body inside its loop), and a window that cuts the first
    evs, t = [], 1000
    for text, _, dur in events:
        evs.append([text, t, dur])
        t += dur + 7
    evs[-1][1], evs[-2][1] = evs[0][1] + 10, evs[0][1] + 20
    window = (evs[0][1] + 5, t)
    got = M.table(evs, window, rows)
    inside = [e for e in evs if e[1] + e[2] > window[0]]
    assert sum(r["events"] for r in got["rows"].values()) == len(inside)
    busy = T.total([[max(s, window[0]), min(s + d, window[1])]
                    for _, s, d in inside]) / 1e9
    assert got["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert sum(r["s"] for r in got["rows"].values()) == pytest.approx(busy)
    assert all(r["inherited_s"] <= r["s"] + 1e-15
               for r in got["rows"].values())
    # an event no instruction of the program is found for is not dropped
    lost = M.table([["%never_heard_of.1 = f32[] add()", 0, 50]], (0, 100),
                   rows)
    assert lost["rows"][M.UNATTRIBUTED]["s"] == pytest.approx(50e-9)


# ---------------------------------------------------------------------------
# the join, on instructions recorded from olmoe_train_t4096's trace

HEAD_UPDATE = ("adam+cast[lm.loss]+cast_grad[lm.loss]+mul_grad[lm.head]"
               "+rms_norm+softmax_with_cross_entropy[lm.loss]"
               "+softmax_with_cross_entropy_grad[lm.loss]")
# the recorded events' instructions -> (row, whether the name is its own)
READ = {
    # Adam over one stacked expert weight [64, 1024, 2048], nothing else
    "subtract_convert_fusion": ("adam", True),
    # Wq's update with its dW product inside: TWO ops, one row
    "subtract_convert_fusion.16": ("adam+mul_grad", True),
    # the head's update, dW [2048, 50304] inside: the optimizer's
    "subtract_convert_fusion.6": (HEAD_UPDATE, True),
    # x W_head, the final norm and the loss's first pass in its epilogue
    "fusion.410": ("mul[lm.head]+rms_norm+softmax_with_cross_entropy"
                   "[lm.loss]", True),
    "fusion.86": ("cast[lm.loss]+softmax_with_cross_entropy[lm.loss]"
                  "+softmax_with_cross_entropy_grad[lm.loss]", True),
    # XLA's kernel for lax.ragged_dot carries no name: its operands' makers'
    "ragged-dot-none": ("moe[moe.experts]+moe[moe.permute]", False),
    "ragged-dot-none.2": ("moe[moe.permute]", False),
    "flash_bwd_dkv.3": ("scaled_dot_product_attention_grad[attn.attend]",
                        True),
    "ragged-dot-dlhs.16": ("moe_grad[moe.experts]", True),
    # the router, which no shape rule could tell from RoPE's tables
    "sort": ("moe[moe.route]", True),
    "convert_bitcast_fusion": ("rms_norm_grad[attn.qk_norm]"
                               "+rope_grad[attn.rope]", True),
    # named by JAX outside every op's scope / by nobody, makers unnamed too
    "pad_clamp_fusion.6": ("unattributed", True),
    "slice-done.151": ("unattributed", False),
    # a relayout copy of the Pallas dW kernel's result: its maker's
    "copy.300": ("moe_grad[moe.experts]", False),
    # holds a constant that XLA gave the embedding's gradient's name
    "bitcast_reduce_fusion.1": ("moe_grad[moe.permute]", True)}


def test_recorded_instructions_read_as_the_whole_program_did():
    events, rows, rec = _recorded()
    assert [H.name_of(e[0]) for e in events] == list(READ)
    for name, (label, own) in READ.items():
        assert (rows[name].label, rows[name].own) == (label, own), name
        assert rec["read"][name] == {"label": label, "own": own}
    # the products inside: the head's 2 x 4096 x 2048 x 50304, forward and dW
    for name in ("fusion.410", "subtract_convert_fusion.6"):
        assert rows[name].product_flops == pytest.approx(
            2.0 * 4096 * 2048 * 50304)
    assert rows["subtract_convert_fusion"].product_flops == 0.0


def test_a_fusion_that_names_two_ops_lands_in_the_combinations_row():
    _, rows, _ = _recorded()
    row = rows["subtract_convert_fusion.16"]
    assert row.ops == {"adam", "mul_grad"} and not row.parts
    assert row.label == "adam+mul_grad"
    head = rows["subtract_convert_fusion.6"]
    assert {"adam", "mul_grad"} < head.ops
    assert head.parts == {"lm.head", "lm.loss"}
    # innermost scope and innermost part of each path; paths joined by `;`
    assert M.pairs_of(
        "jit(f)/pdop__recompute__u3/pdop__mul__u5/pdtpu.lm.head/dot_general;"
        "jit(f)/pdop__moe_grad__u7/transpose(jvp(pdtpu.moe.permute))/gather;"
        "jit(f)/convert_element_type", H.PART) == {
            ("mul", "lm.head"), ("moe_grad", "moe.permute")}
    assert M.label_of({("mul", "lm.head"), ("adam", None)}) == \
        "adam+mul[lm.head]"
    assert M.label_of(()) == M.UNATTRIBUTED


def test_an_unnamed_copy_takes_its_producers():
    _, rows, rec = _recorded()
    by_name = {i["name"]: i for i in rec["instructions"]}
    by_id = {i["id"]: i for i in rec["instructions"]}
    copy_ = by_name["copy.300"]
    assert copy_["op_name"] == "" and copy_["opcode"] == "copy"
    (maker,) = [by_id[o]["name"] for o in copy_["operands"]]
    assert maker == "ragged-dot-drhs.13"
    assert rows[maker].label == rows["copy.300"].label == \
        "moe_grad[moe.experts]"
    assert rows[maker].own and not rows["copy.300"].own
    # and its time is kept apart as inherited
    got = M.table([["%copy.300 = bf16[64,1024,2048] copy(...)", 0, 800],
                   ["%ragged-dot-drhs.13 = custom-call(...)", 900, 1100]],
                  (0, 5000), rows)
    assert got["rows"]["moe_grad[moe.experts]"] == {
        "s": pytest.approx(1900e-9), "inherited_s": pytest.approx(800e-9),
        "events": 2, "product_flops": 0.0, "ops": ["moe_grad"],
        "parts": ["moe.experts"]}


def test_a_constant_inside_a_body_names_nothing(monkeypatch):
    """XLA merges equal constants across the program and keeps one op's
    name: the -inf a reduce starts from sat, with the embedding's
    gradient's name, in the fusion that sums the expert layer's rows."""
    _, rows, _ = _recorded()
    assert rows["bitcast_reduce_fusion.1"].ops == {"moe_grad"}
    monkeypatch.setattr(M, "VALUES", ())
    _, naive, _ = _recorded()
    assert naive["bitcast_reduce_fusion.1"].label == \
        "lookup_table_grad+moe_grad[moe.permute]"


def test_the_recorded_optimizer_and_head_rows_are_disjoint():
    events, rows, _ = _recorded()
    evs, t = [], 0
    for text, _, dur in events:
        evs.append([text, t, dur])
        t += dur + 7
    got = M.table(evs, (0, t), rows)
    table = {"rows": {k: dict(r, ms=1e3 * r["s"]) for k, r in
                      got["rows"].items()}}
    types = {"adam": {}, "adam_beta_pow_update": {}}
    opt = M.optimizer_rows(table, types)
    head = M.head_loss_rows(table, types)
    assert set(opt) == {"adam", "adam+mul_grad", HEAD_UPDATE}
    assert set(head) == {READ["fusion.410"][0], READ["fusion.86"][0]}
    assert not set(opt) & set(head)
    busy = sum(e[2] for e in evs) / 1e9
    assert got["busy_s"] == pytest.approx(busy)
    lost = got["rows"][M.UNATTRIBUTED]
    assert lost["events"] == 2 and lost["s"] == pytest.approx(6919e-9)


# ---------------------------------------------------------------------------
# the readers on a table


def test_optimizer_and_head_rows_never_share_an_event():
    rows = {"adam+mul_grad[lm.head]": _row(9.0, ["adam", "mul_grad"],
                                           ["lm.head"], flops=1e12),
            "adam": _row(20.0, ["adam"]),
            "mul[lm.head]": _row(4.0, ["mul"], ["lm.head"]),
            "softmax_with_cross_entropy[lm.loss]": _row(
                3.0, ["softmax_with_cross_entropy"], ["lm.loss"]),
            "mul_grad": _row(30.0, ["mul_grad"]),
            M.UNATTRIBUTED: _row(1.0, [])}
    _count_update(4e9, 16e9)
    run = _run(rows, coverage=1 - 1 / 67)
    got = M.of_run(run)
    opt = M.optimizer_rows(got, M.update_bytes())
    head = M.head_loss_rows(got, M.update_bytes())
    assert set(opt) == {"adam+mul_grad[lm.head]", "adam"}
    assert set(head) == {"mul[lm.head]",
                         "softmax_with_cross_entropy[lm.loss]"}
    assert not set(opt) & set(head)
    assert _read("optimizer_fused_device_ms", run) == pytest.approx(29.0)
    assert _read("head_loss_device_ms", run) == pytest.approx(7.0)
    assert _read("step_attributed_pct", run) == pytest.approx(100 * 66 / 67)
    assert list(run["detail"]["step_by_op_ms"]) == [
        "mul_grad", "adam", "adam+mul_grad[lm.head]", "mul[lm.head]",
        "softmax_with_cross_entropy[lm.loss]", M.UNATTRIBUTED]
    d = run["detail"]["optimizer_fused_device_ms"]
    assert d["hbm_least_ms"] == pytest.approx(1e3 * 20e9 / 819e9)
    assert d["product_least_ms"] == pytest.approx(1e3 * 1e12 / 197e12)
    # a model without a named head (ResNet-50) has no such metric
    del rows["mul[lm.head]"], rows["softmax_with_cross_entropy[lm.loss]"]
    assert _read("head_loss_device_ms", _run(rows)) is None


def test_roofline_cannot_pass_100_where_products_would_swallow_the_update():
    """Moonlight's case: 669 M parameters x 20 bytes need 16.3 ms, the dW
    products inside the same events ~33 ms at the peak, the events took 48.
    Both are lower bounds of the same 48 ms; the products' least taken OFF
    the time would leave 15 ms for 16.3 ms of bytes: 109%."""
    flops = 33e-3 * PEAKS["bf16_flops_per_s"]
    rows = {"adam+mul_grad": _row(48.0, ["adam", "mul_grad"], flops=flops),
            "mul": _row(52.0, ["mul"])}
    _count_update(669e6 * 4, 669e6 * 16)
    run = _run(rows)
    share = _read("optimizer_fused_roofline", run)
    assert share == pytest.approx(100 * 33 / 48)
    assert run["detail"]["optimizer_fused_roofline"]["bound"] == "mxu"
    hbm = run["detail"]["optimizer_fused_roofline"]["hbm_least_ms"]
    assert hbm == pytest.approx(16.34, abs=0.01)
    assert 100 * hbm / (48.0 - 33.0) > 100       # the formula not taken
    # without products inside, the bytes bind
    rows["adam+mul_grad"]["product_flops"] = 0.0
    run = _run(rows)
    assert _read("optimizer_fused_roofline", run) == pytest.approx(
        100 * hbm / 48)
    assert run["detail"]["optimizer_fused_roofline"]["bound"] == "hbm"
    # at worst every byte at the peak and nothing else in the events: 100
    rows["adam+mul_grad"]["ms"] = hbm
    assert _read("optimizer_fused_roofline", _run(rows)) == pytest.approx(100)


@pytest.mark.parametrize("name", GUARDED)
def test_a_device_reader_gives_none_under_90_pct_coverage(name):
    rows = {"adam": _row(20.0, ["adam"]),
            "mul[lm.head]": _row(4.0, ["mul"], ["lm.head"]),
            M.UNATTRIBUTED: _row(3.0, [])}
    _count_update(4e9, 16e9)
    assert _read(name, _run(rows, coverage=24 / 27)) is None
    run = _run(rows, coverage=24 / 27)
    assert _read(name, run) is None
    assert run["detail"]["op_scopes_coverage_too_low"] == pytest.approx(24 / 27)
    # the guard itself still reads: that is what it is for
    assert _read("step_attributed_pct", run) == pytest.approx(100 * 24 / 27)
    assert _read(name, _run(rows, coverage=0.91)) is not None


@pytest.mark.parametrize("name", GUARDED + ("step_attributed_pct",))
def test_a_device_reader_gives_none_without_a_trace_or_its_metadata(
        name, tmp_path, monkeypatch):
    _count_update(4e9, 16e9)
    run = _run({})
    del run["detail"]["op_scopes"]
    # a file with planes and no `/host:metadata` among them
    path = tmp_path / "no_metadata.xplane.pb"
    plane = b"\x12\x0b/device:TPU"      # XPlane.name = 2
    path.write_bytes(b"\x0a" + bytes([len(plane)]) + plane)
    run["record"]["trace_path"] = str(path)
    assert H.hlo_protos(str(path)) == [] and M.of_trace(str(path)) is None
    assert _read(name, run) is None and "op_scopes" not in run["detail"]
    run["record"]["trace_path"] = None          # an untraced record
    assert _read(name, run) is None
    run["record"]["trace_path"], run["trace"] = str(path), None
    assert _read(name, run) is None


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent of PR 35 stamps no identity and counts nothing: every
    reader returns None and raises nothing, whatever the trace holds."""
    rows = {"unattributed": _row(20.0, [])}
    fluid.reset()                  # the families exist, without a series
    assert M.update_bytes() is None and M.emit_seconds() is None
    for name in ENTRIES:
        assert _read(name, _run(rows, coverage=0.0)) is None, name


def test_a_stale_compile_cache_reads_zero_and_silences_the_others():
    """The program of this PR loading a step compiled by its parent (the
    cache's keys ignore metadata): the counters are there, the names are
    not."""
    _count_update(4e9, 16e9)
    run = _run({M.UNATTRIBUTED: _row(100.0, [])}, coverage=0.0)
    assert _read("step_attributed_pct", run) == 0.0
    assert all(_read(name, run) is None for name in GUARDED)
    assert _read("op_emit_s", run) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# the manifest, and the program's own counters through the cell's driver


def test_manifest_entries_of_the_five_metrics():
    m = harness.load_manifest()
    for name, (unit, better, source, layer, moves, cells) in ENTRIES.items():
        (entry,) = [x for x in m["per_layer"] if x["name"] == name]
        assert {k: v for k, v in entry.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves}
        mod = harness.load_module("layer_metrics", name)
        assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            unit, better, source, layer, moves)
        if cells is None:
            assert "workloads" not in entry
            continue
        assert cells <= set(entry["workloads"])
        assert len(set(entry["workloads"])) == len(entry["workloads"])
        for cell in cells:
            assert name in {x["name"] for x in
                            harness.metrics_of(m, "per_layer", cell)}
    # the head and the loss are named by the decoder's builder only
    (head,) = [x for x in m["per_layer"] if x["name"] == "head_loss_device_ms"]
    assert not {"resnet50_train_bs128", "resnet50_dp4_train"} & set(
        head["workloads"])


def test_the_toy_driver_fills_the_counters_the_readers_read(tmp_path):
    """The cell's own driver at toy size on the CPU: the emitters' seconds
    are part of the trace phase, the update's bytes are the parameters' by
    hand, and a trace without a device plane reads nothing."""
    cfg = copy.deepcopy(harness.load_json("configs", "gpt2-medium"))
    cfg.update(n_embd=32, n_layer=2, n_head=4, n_positions=64, vocab_size=64)
    cfg["train"]["args"].update(seq_len=64, vocab_size=64, dim=32,
                                n_layers=2, n_heads=4, dtype="bfloat16")
    cfg["train"]["feeds"]["tokens"].update(shape=[64, 1], high=64)
    traffic = copy.deepcopy(harness.load_json("traffic", "train_staged_bs8"))
    traffic.update(staged_batches=2, loss_read_every=2, trace_seconds=0.3,
                   batch=2)
    ctx = harness.Context(
        cell={"name": "toy"}, config=cfg, traffic=traffic, seed=2 ** 31 + 35,
        seconds=0.6, trace=True, t_start=time.monotonic(),
        place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))
    rec = harness.load_module("drivers", "train_executor").run(ctx)
    run = {"record": rec, "ctx": ctx, "tracemod": T, "detail": {},
           "peaks": PEAKS, "trace": T.load_xplane(rec["trace_path"]),
           "trace_summary": None}
    emit = _read("op_emit_s", run)
    assert 0 < emit <= _read("compile_trace_s", run)
    by_op = run["detail"]["op_emit_s"]["by_op"]
    assert len(by_op) == 8 and emit >= sum(by_op.values()) > 0.5 * emit
    assert any(op.endswith("_grad") for op in by_op)
    params = fluid.default_main_program().global_block().all_parameters()
    n = sum(int(__import__("math").prod(p.shape)) for p in params)
    moved = M.update_bytes()
    assert moved["adam"] == {"param": 4.0 * n, "state": 16.0 * n,
                             "grad": 2.0 * n}          # bf16, float32 moments
    assert set(moved) == {"adam", "adam_beta_pow_update"}
    # the CPU's trace has no TPU plane: nothing to put into rows
    for name in GUARDED + ("step_attributed_pct",):
        assert _read(name, run) is None, name
