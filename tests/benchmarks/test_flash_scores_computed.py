"""flash_scores_computed_pct (PR 27): the reader on the program's counter
`flash_score_elements_total`, by hand on recorded values, and on a program
that has no such counter.  Counts of score elements, no device number."""

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

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.observability import REGISTRY  # noqa: E402

NAME = "flash_scores_computed_pct"
FAMILY = "flash_score_elements_total"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
P = harness.load_module("reduce", "program_spans")

# what a traced step leaves in the counter, an attention layer a kernel:
# GPT-2-medium (B 8, H 16, T 1024, 24 layers), the square walked in strips
# that compute 10 sixteenths of it and, before PR 27's walk, whole; OLMoE
# (B 1, H 16, T 4096, 2 layers) with 20 of 32 blocks visited, and 17
# block-equivalents with 8 of them walked
RECORDED = {
    "gpt2m_walked": (24 * 8 * 16 * 1024 * 1024, 10 / 16, 62.5),
    "gpt2m_whole_square": (24 * 8 * 16 * 1024 * 1024, 1.0, 100.0),
    "olmoe_blocks_only": (2 * 16 * 4096 * 4096, 20 / 32, 62.5),
    "olmoe_walked": (2 * 16 * 4096 * 4096, 17 / 32, 53.125),
}


def _read():
    run = {"record": {"trace_path": None}, "trace": None, "detail": {}}
    return harness.load_module("layer_metrics", NAME).read(run)


@pytest.mark.parametrize("case", list(RECORDED))
def test_reader_on_recorded_counter_values(case):
    square, share, want = RECORDED[case]
    fluid.reset()
    counter = REGISTRY.counter(FAMILY, "recorded")
    for kernel in KERNELS:
        counter.inc(square, kernel=kernel, part="square")
        counter.inc(square * share, kernel=kernel, part="computed")
    assert _read() == pytest.approx(want)
    # one kernel with nothing skipped pulls the share up by its third
    counter.inc(square * (1 - share), kernel="flash_fwd", part="computed")
    assert _read() == pytest.approx(want + (100 - want) / 3)
    fluid.reset()
    assert _read() is None  # the series go with the reset


def test_reader_finds_nothing_in_a_program_without_the_counter(monkeypatch):
    """The parent of PR 27 has no such family: `counter_sum` gives None,
    nothing is read and nothing raised, and the line leaves the metric
    out.  The same where no causal flash kernel was traced."""
    monkeypatch.setattr(P, "counter_sum", lambda *a: None)
    assert _read() is None
    monkeypatch.undo()
    fluid.reset()
    assert P.counter_sum(FAMILY, "part", ("square",)) is None
    assert _read() is None


def test_manifest_entry_names_the_two_lm_cells():
    """The two cells PR 27 named are in the list, and whichever later cell
    runs a causal flash kernel may join them; where the entry stands among
    the others is the driver's business, never a test's."""
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": "train_samples_per_s"}
    assert {"gpt2m_train_bs8", "olmoe_train_t4096"} <= set(
        entry["workloads"])
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    for cell in entry["workloads"]:
        assert NAME in {x["name"] for x in
                        harness.metrics_of(m, "per_layer", cell)}


def test_the_toy_lm_step_fills_the_counter_the_reader_reads(monkeypatch):
    """A causal attention block through the executor, the flash kernels
    interpreted on the CPU as tests/test_kernel_forward_once.py does it:
    what the reader returns is the schedule's share of the square."""
    import numpy as np

    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    real_train = fa.make_flash_train
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(
        fa, "make_flash_train",
        lambda causal=False, scale=None, interpret=False:
        real_train(causal=causal, interpret=True, block_q=32, block_k=64))
    monkeypatch.setattr(fa, "_TRAIN_CACHE", {})
    fluid.reset()
    T = 128
    x = fluid.layers.data("x", shape=[T, 32], dtype="float32")
    y = fluid.layers.multi_head_attention(x, x, x, 2, causal=True)
    loss = fluid.layers.mean(y * y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={"x": np.ones((2, T, 32), np.float32)}, fetch_list=[loss])
    computed = sum(
        fa._schedule(T, 32, 64, fa._strip_rows(k, 32, 64)).computed
        for k in KERNELS)
    assert _read() == pytest.approx(100.0 * computed / (3 * T * T))
    assert 50.0 < _read() < 62.5  # 4 x 2 blocks: 6 visited, 4 walked
