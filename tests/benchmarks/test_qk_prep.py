"""`qk_prep_device_ms` (PR 38), checked on the CPU: the reader on the rows a
real trace gave (`sdar_train_bd_t4096` at PR 38's parent, seed 3700000021,
16 traced steps: every row that carries one of the reader's parts, and the
seven largest of the others), on rows of the part the op `head_norm_rope`
brings, under the coverage floor, and the manifest's entry.  A test that
reads BENCHMARK.json as a whole is named `test_manifest...` and holds
membership and content, never position."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

M = harness.load_module("reduce", "op_scopes")
T = harness.load_module("reduce", "trace")
NAME = "qk_prep_device_ms"
QK_CELLS = {"sdar_train_bd_t4096", "lfm2_train_t8192", "olmoe_train_t4096"}
QK, ROPE, PREP = "attn.qk_norm", "attn.rope", "attn.qk_prep"

# label: (ms a step, events, product FLOPs a step, ops, parts)
RECORDED = {
    "scaled_dot_product_attention_grad[attn.attend]": (
        40.7237, 970, 0.0, ["scaled_dot_product_attention_grad"],
        ["attn.attend"]),
    "scaled_dot_product_attention[attn.attend]": (
        18.9117, 834, 0.0, ["scaled_dot_product_attention"],
        ["attn.attend"]),
    "adam": (15.2861, 1680, 0.0, ["adam"], []),
    "moe[moe.experts]+moe[moe.permute]": (
        14.6732, 1120, 0.0, ["moe"], ["moe.experts", "moe.permute"]),
    "moe_grad[moe.experts]": (14.2692, 4544, 0.0, ["moe_grad"],
                              ["moe.experts"]),
    "moe[moe.combine]": (12.9457, 1111, 0.0, ["moe"], ["moe.combine"]),
    "moe[moe.combine]+moe_grad[moe.permute]": (
        12.0635, 96, 0.0, ["moe", "moe_grad"],
        ["moe.combine", "moe.permute"]),
    "rope[attn.rope]": (4.9034, 5323, 0.0, ["rope"], [ROPE]),
    "rope[attn.rope]+rope_grad[attn.rope]": (
        4.8184, 192, 0.0, ["rope", "rope_grad"], [ROPE]),
    # the Q and K projections of five layers with the norm's forward in
    # their epilogue: a product, not this layer's to take
    "mul+rms_norm+rms_norm[attn.qk_norm]": (
        4.5449, 160, 773094113280.0, ["mul", "rms_norm"], [QK]),
    "rms_norm[attn.qk_norm]+rms_norm_grad[attn.qk_norm]"
    "+rope_grad[attn.rope]": (
        3.1080, 192, 0.0, ["rms_norm", "rms_norm_grad", "rope_grad"],
        [QK, ROPE]),
    "rope_grad[attn.rope]": (1.6417, 192, 0.0, ["rope_grad"], [ROPE]),
    "rms_norm[attn.qk_norm]+rms_norm_grad[attn.qk_norm]+transpose_grad": (
        1.3875, 192, 0.0, ["rms_norm", "rms_norm_grad", "transpose_grad"],
        [QK]),
    "rms_norm[attn.qk_norm]+rope[attn.rope]": (
        1.3341, 192, 0.0, ["rms_norm", "rope"], [QK, ROPE]),
    "rms_norm[attn.qk_norm]": (1.0875, 5970, 0.0, ["rms_norm"], [QK]),
    "lookup_table+mul+rms_norm+rms_norm[attn.qk_norm]": (
        0.9141, 32, 154618822656.0, ["lookup_table", "mul", "rms_norm"],
        [QK]),
    "rms_norm_grad[attn.qk_norm]": (0.0221, 80, 0.0, ["rms_norm_grad"],
                                    [QK]),
    "adam+rms_norm[attn.qk_norm]": (0.0147, 192, 0.0, ["adam", "rms_norm"],
                                    [QK]),
    "rms_norm[attn.qk_norm]+rms_norm_grad[attn.qk_norm]": (
        0.0088, 192, 0.0, ["rms_norm", "rms_norm_grad"], [QK]),
}
SEVEN = ["rope[attn.rope]", "rope[attn.rope]+rope_grad[attn.rope]",
         "rms_norm[attn.qk_norm]+rms_norm_grad[attn.qk_norm]"
         "+rope_grad[attn.rope]", "rope_grad[attn.rope]",
         "rms_norm[attn.qk_norm]+rms_norm_grad[attn.qk_norm]+transpose_grad",
         "rms_norm[attn.qk_norm]+rope[attn.rope]", "rms_norm[attn.qk_norm]"]


def _read(run):
    return harness.load_module("layer_metrics", NAME).read(run)


def _rows(table):
    return {label: {"ms": ms, "inherited_ms": 0.0, "events": events,
                    "product_flops": flops, "ops": sorted(ops),
                    "parts": sorted(parts)}
            for label, (ms, events, flops, ops, parts) in table.items()}


def _run(rows, coverage=0.9928, steps=16):
    """A traced run as a reader sees it, with the table already made."""
    return {"record": {"trace_path": "x.xplane.pb",
                       "traced": {"steps": steps}},
            "trace": {"devices": {}, "host": []}, "tracemod": T,
            "detail": {"op_scopes": {
                "steps": steps, "busy_ms": 219.887, "coverage": coverage,
                "rows": rows}}}


def test_recorded_parent_step_reads_the_seven_rows_and_no_product():
    """SDAR's step before `head_norm_rope`: the seven rows ISSUE 38 lists
    sum to 18.3 ms and three slivers ride along; the two rows with the
    projections' products inside (5.46 ms) are left out."""
    run = _run(_rows(RECORDED))
    got = _read(run)
    seven = sum(RECORDED[label][0] for label in SEVEN)
    assert seven == pytest.approx(18.28, abs=0.01)
    assert got == pytest.approx(seven + 0.0221 + 0.0147 + 0.0088)
    assert got == pytest.approx(18.33, abs=0.01)
    kept = run["detail"][NAME]
    assert list(kept)[:7] == sorted(SEVEN, key=lambda k: -RECORDED[k][0])
    assert len(kept) == 10
    assert "mul+rms_norm+rms_norm[attn.qk_norm]" not in kept
    assert "lookup_table+mul+rms_norm+rms_norm[attn.qk_norm]" not in kept
    assert not any("attend" in label or label == "adam" for label in kept)


@pytest.mark.parametrize("label,row,counted", [
    ("head_norm_rope[attn.qk_prep]",
     (1.5, 12, 0.0, ["head_norm_rope"], [PREP]), True),
    ("head_norm_rope_grad[attn.qk_prep]",
     (2.2, 12, 0.0, ["head_norm_rope_grad"], [PREP]), True),
    # the gain's gradient summed inside its Adam update
    ("adam+head_norm_rope_grad[attn.qk_prep]",
     (0.02, 12, 0.0, ["adam", "head_norm_rope_grad"], [PREP]), True),
    # the whole-width norm's backward with the turn's (OLMoE)
    ("head_norm_rope_grad[attn.qk_prep]+rms_norm_grad[attn.qk_norm]",
     (0.4, 4, 0.0, ["head_norm_rope_grad", "rms_norm_grad"], [PREP, QK]),
     True),
    # a product that took the op's name: the product's row
    ("head_norm_rope_grad[attn.qk_prep]+mul_grad",
     (3.0, 12, 5e11, ["head_norm_rope_grad", "mul_grad"], [PREP]), False),
    # another part beside it: not this layer's alone
    ("head_norm_rope[attn.qk_prep]+scaled_dot_product_attention"
     "[attn.attend]",
     (0.3, 6, 0.0, ["head_norm_rope", "scaled_dot_product_attention"],
      ["attn.attend", PREP]), False),
    # no part at all
    ("transpose", (1.9, 24, 0.0, ["transpose"], []), False),
])
def test_rows_of_the_new_part_are_read_alone(label, row, counted):
    others = {k: v for k, v in RECORDED.items() if not set(v[4]) & {
        QK, ROPE}}
    run = _run(_rows({**others, label: row}))
    got = _read(run)
    assert got == pytest.approx(row[0] if counted else 0.0)
    assert run["detail"][NAME] == ({label: row[0]} if counted else {})


def test_coverage_floor_and_a_run_without_a_trace():
    thin = _run(_rows(RECORDED), coverage=0.89)
    assert _read(thin) is None
    assert thin["detail"]["op_scopes_coverage_too_low"] == 0.89
    assert NAME not in thin["detail"]
    assert _read({"record": {}, "trace": None, "detail": {}}) is None
    # a program named well that holds no such event reads 0, not None
    bare = _run(_rows({"adam": RECORDED["adam"]}), coverage=1.0)
    assert _read(bare) == 0.0 and bare["detail"][NAME] == {}


def test_manifest_lists_the_metric_in_the_three_cells_that_turn_q_and_k():
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == NAME]
    mod = harness.load_module("layer_metrics", NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "ms", "lower", "device_trace", "Pallas kernels",
        "train_samples_per_s")
    assert QK_CELLS <= set(entry["workloads"])
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    for cell in QK_CELLS:
        assert NAME in {x["name"] for x in
                        harness.metrics_of(m, "per_layer", cell)}
    # no RoPE and no per-head norm (GPT-2), an attention op of its own
    # with the turn inside (Moonlight), no attention at all (ResNet)
    for cell in ("gpt2m_train_bs8", "moonlight_train_t8192",
                 "resnet50_train_bs128", "resnet50_dp4_train"):
        assert NAME not in {x["name"] for x in
                            harness.metrics_of(m, "per_layer", cell)}
    assert mod.LAYER in {x["layer"] for x in m["per_layer"]
                         if x["name"] != NAME}
