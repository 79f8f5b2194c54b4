"""flash_dead_grid_steps_pct (PR 64): the reader on the program's counter
`flash_grid_steps_total`, by hand on recorded values, on a program that has
no such counter (the parent), and on a window call traced here; its entry
in the manifest.  Counts of grid steps, no device number."""

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

NAME = "flash_dead_grid_steps_pct"
FAMILY = "flash_grid_steps_total"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
P = harness.load_module("reduce", "program_spans")

# what a traced step leaves in the counter, (grid, live) steps a kernel:
# Laguna-S (B 1, 72 query heads on 8, T 8192) with its 3 window layers'
# calls (512 keys, blocks of 1024: 2 K steps a q block, 15 live blocks a
# head of 16) and its 2 full-span layers' causal calls at (2048, 1024) (48
# heads, 32 steps a head, 20 live), on the spanned grid and on the parent's
# whole one (64 steps a head under the window); SmallThinker's window call
# alone (28 heads, 4096 keys of 16384: 5 of 16 K steps, 70 live of 80)
RECORDED = {
    "laguna_s_step_spanned": (3 * 72 * 16 + 2 * 48 * 32,
                              3 * 72 * 15 + 2 * 48 * 20, 20.9559),
    "laguna_s_step_whole_grid": (3 * 72 * 64 + 2 * 48 * 32,
                                 3 * 72 * 15 + 2 * 48 * 20, 69.4602),
    "window_512_call_spanned": (72 * 16, 72 * 15, 6.25),
    "window_512_call_whole_grid": (72 * 64, 72 * 15, 76.5625),
    "window_4096_call_spanned": (28 * 80, 28 * 70, 12.5),
    "window_4096_call_whole_grid": (28 * 256, 28 * 70, 72.65625),
    "nothing_dead": (16 * 4, 16 * 4, 0.0),
}


def _read():
    run = {"record": {"trace_path": None}, "trace": None, "detail": {}}
    return harness.load_module("layer_metrics", NAME).read(run)


@pytest.mark.parametrize("case", list(RECORDED))
def test_reader_on_recorded_counter_values(case):
    grid, live, want = RECORDED[case]
    fluid.reset()
    counter = REGISTRY.counter(FAMILY, "recorded")
    for kernel in KERNELS:
        counter.inc(grid, kernel=kernel, part="grid")
        counter.inc(live, kernel=kernel, part="live")
    assert _read() == pytest.approx(want, abs=1e-4)
    # one kernel launching as many dead steps again pushes the share up
    counter.inc(grid - live, kernel="flash_fwd", part="grid")
    dead = 4 * (grid - live)
    assert _read() == pytest.approx(100.0 * dead / (3 * live + dead))
    fluid.reset()
    assert _read() is None  # the series go with the reset


def test_reader_finds_nothing_in_a_program_without_the_counter(monkeypatch):
    """The parent of PR 64 has no such family: `counter_sum` gives None,
    nothing is read and nothing raised, and the line leaves the metric
    out.  The same where no masked flash kernel was traced."""
    monkeypatch.setattr(P, "counter_sum", lambda *a: None)
    assert _read() is None
    monkeypatch.undo()
    fluid.reset()
    assert P.counter_sum(FAMILY, "part", ("grid",)) is None
    assert _read() is None


def test_manifest_entry_lists_the_cells_of_flash_scores_computed_pct():
    """The cells that trace a masked flash kernel: the eleven
    `flash_scores_computed_pct` lists, in its order; a later cell that
    runs one may join both lists."""
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == NAME]
    (scores,) = [x for x in m["per_layer"]
                 if x["name"] == "flash_scores_computed_pct"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": "train_samples_per_s"}
    assert entry["workloads"][:11] == scores["workloads"][:11]
    assert {"laguna_s_train_t8192", "smallthinker_train_t16384",
            "phi4flash_train_t8192"} <= set(entry["workloads"])
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    for cell in entry["workloads"]:
        assert NAME in {x["name"] for x in
                        harness.metrics_of(m, "per_layer", cell)}


def test_a_traced_window_call_fills_the_counter_the_reader_reads():
    """The forward and backward of a window call and of a causal one,
    traced as the chip would (`jax.eval_shape` runs no Mosaic): what the
    reader returns is the dead share of the two calls' grids together."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    fluid.reset()
    B, H, Hkv, T, D = 1, 8, 2, 8192, 128
    sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    q, kv = sds(B, H, T, D), sds(B, Hkv, T, D)
    lse = sds(B * H, T, dt=jnp.float32)
    with jax.enable_x64(False):
        for kw in (dict(mask=fa.sliding_window_mask(T, 512)),
                   dict(causal=True)):
            jax.eval_shape(lambda q, k, v: fa.flash_attention_fwd(
                q, k, v, **kw), q, kv, kv)
            jax.eval_shape(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
                q, k, v, o, l, do, **kw), q, kv, kv, q, lse, q)
    # the window at (1024, 1024): 16 steps a head, 15 live; the causal call
    # at (2048, 1024): 32 steps a head, 20 live
    assert _read() == pytest.approx(100.0 * (1 + 12) / (16 + 32))
    fluid.reset()
