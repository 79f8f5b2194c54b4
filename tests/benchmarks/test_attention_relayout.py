"""`attention_relayout_device_ms` (PR 36), checked on the CPU: the reader
on the instructions recorded from a real trace
(tests/benchmarks/recorded_op_scopes.json: OLMoE's step, whose relayouts
XLA folds into RoPE's and the QK-norm's fusions) with and without events
of relayout ops laid among them, the arithmetic on a made table, and the
manifest's entry.  A test that reads BENCHMARK.json as a whole is named
`test_manifest...` and holds membership and content, never position.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

M = harness.load_module("reduce", "op_scopes")
T = harness.load_module("reduce", "trace")
NAME = "attention_relayout_device_ms"
DECODER_CELLS = {"gpt2m_train_bs8", "olmoe_train_t4096", "lfm2_train_t8192"}


def _read(run):
    return harness.load_module("layer_metrics", NAME).read(run)


def _run(rows, coverage=1.0, steps=4):
    """A traced run as a reader sees it, with the table already made."""
    return {"record": {"trace_path": "x.xplane.pb",
                       "traced": {"steps": steps}},
            "trace": {"devices": {}, "host": []}, "tracemod": T,
            "detail": {"op_scopes": {
                "steps": steps, "busy_ms": sum(r["ms"] for r in rows.values()),
                "coverage": coverage, "rows": rows}}}


def _row(ms, ops, parts=(), inherited=0.0):
    return {"ms": ms, "inherited_ms": inherited, "events": 1,
            "product_flops": 0.0, "ops": sorted(ops), "parts": sorted(parts)}


def _instruction(name, ident, op_name, operands=()):
    return {"comp": "main", "name": name, "opcode": "fusion", "id": ident,
            "op_name": op_name, "operands": list(operands), "called": [],
            "elements": 8}


def _recorded_table(extra=()):
    """The table of the recorded events, each 1000 ns after the last, with
    the instructions `extra` (and an event of 500 ns each) among them."""
    with open(os.path.join(HERE, "recorded_op_scopes.json"),
              encoding="utf-8") as f:
        rec = json.load(f)
    comps: dict = {}
    for ins in list(rec["instructions"]) + list(extra):
        comps.setdefault(ins["comp"], []).append(ins)
    rows = M.rows_of(comps)
    evs, t = [], 0
    for text, _, dur in rec["events"]:
        evs.append([text, t, dur])
        t += dur + 1000
    for ins in extra:
        evs.append([f"%{ins['name']} = bf16[8]{{0}} fusion()", t, 500])
        t += 1500
    got = M.table(evs, (0, t), rows)
    steps = 2
    table = {"steps": steps, "busy_ms": 1e3 * got["busy_s"] / steps,
             "coverage": 1.0,
             "rows": {label: {"ms": 1e3 * r["s"] / steps,
                              "inherited_ms": 1e3 * r["inherited_s"] / steps,
                              "events": r["events"], "product_flops": 0.0,
                              "ops": r["ops"], "parts": r["parts"]}
                      for label, r in got["rows"].items()}}
    run = _run({}, steps=steps)
    run["detail"]["op_scopes"] = table
    return run


def test_recorded_step_has_no_relayout_of_its_own():
    """OLMoE's recorded events: RoPE's and the QK-norm's backward hold the
    heads' relayouts (`rms_norm_grad[attn.qk_norm]+rope_grad[attn.rope]`),
    no event is a relayout op's alone: the reader gives 0, not None."""
    run = _recorded_table()
    assert any("rope_grad" in label
               for label in run["detail"]["op_scopes"]["rows"])
    assert _read(run) == 0.0
    assert run["detail"][NAME] == {}


def test_relayout_events_among_the_recorded_are_summed():
    """Events of a `transpose`, of a `transpose_grad` fused with a
    `reshape_grad`, and an unnamed copy of the first's result (which takes
    its name: `inherited_ms`) are the metric; a transpose that rides in a
    product's fusion is the product's row and is not."""
    scope = "jit(step)/pdop__%s__u%d/transpose"
    extra = [
        _instruction("rel.1", 900001, scope % ("transpose", 7)),
        _instruction("rel.2", 900002, ";".join(
            (scope % ("transpose_grad", 8), scope % ("reshape_grad", 9)))),
        _instruction("rel.copy", 900003, "", operands=[900001]),
        _instruction("rel.mul", 900004, ";".join(
            (scope % ("transpose", 7), scope % ("mul", 6)))),
    ]
    run = _recorded_table(extra)
    before = _read(_recorded_table())
    got = _read(run)
    # three events of 500 ns over 2 steps, in ms
    assert got - before == pytest.approx(3 * 500 / 1e6 / 2)
    assert set(run["detail"][NAME]) == {"transpose",
                                        "reshape_grad+transpose_grad"}
    rows = run["detail"]["op_scopes"]["rows"]
    assert rows["transpose"]["events"] == 2
    assert rows["transpose"]["inherited_ms"] == pytest.approx(500 / 1e6 / 2)
    assert "mul+transpose" in rows and "mul+transpose" not in run["detail"][NAME]


def test_arithmetic_on_a_made_table_and_the_coverage_floor():
    rows = {"transpose": _row(6.5, ["transpose"], inherited=2.6),
            "transpose_grad": _row(6.9, ["transpose_grad"]),
            "reshape": _row(0.01, ["reshape"]),
            "layer_norm+mul+transpose": _row(
                6.2, ["layer_norm", "mul", "transpose"]),
            "scaled_dot_product_attention[attn.attend]": _row(
                10.0, ["scaled_dot_product_attention"], ["attn.attend"]),
            M.UNATTRIBUTED: _row(1.0, [])}
    run = _run(rows, coverage=1 - 1 / 37)
    assert _read(run) == pytest.approx(13.41)
    assert list(run["detail"][NAME]) == ["transpose_grad", "transpose",
                                         "reshape"]
    # a program named too thinly is not read (its neighbours' rule)
    thin = _run(rows, coverage=0.89)
    assert _read(thin) is None
    assert thin["detail"]["op_scopes_coverage_too_low"] == 0.89
    # no trace, nothing to read
    assert _read({"record": {}, "trace": None, "detail": {}}) is None


def test_manifest_lists_the_relayout_metric_in_the_three_decoder_cells():
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == NAME]
    mod = harness.load_module("layer_metrics", NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "ms", "lower", "device_trace", "model step", "train_samples_per_s")
    assert DECODER_CELLS <= set(entry["workloads"])
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    for cell in DECODER_CELLS:
        assert NAME in {x["name"] for x in
                        harness.metrics_of(m, "per_layer", cell)}
    # Moonlight's attention is another op with its own relayouts inside
    assert "moonlight_train_t8192" not in entry["workloads"]
