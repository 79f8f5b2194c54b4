#!/usr/bin/env python
"""Predicted-vs-measured accounting driver (ISSUE 13 / ROADMAP #3, #5).

Runs train steps of the three standing calibration programs —
fit-a-line, recognize-digits, and the small decoder LM — under the
telemetry layer (paddle_tpu/observability/), with the static
cost/memory predictions attached via ``accounting.track``, and emits ONE
bench-schema JSON line whose rows are the predicted/measured error
ratios:

    predvmeas_step_ratio_<model>   predicted/measured step time
    predvmeas_peak_ratio_<model>   predicted/measured HBM peak
                                   (Executor.memory_stats, the PR 8
                                   argument+temp formula)

The chip spec defaults to the DETECTED backend (cpu-host on the CPU
mesh), so a CPU run prices the roofline against the CPU's numbers: its
step-time ratio measures dispatch overhead on microscopic models, not
model error — a run of this tool on the chip is the number to tune
against.  Peak ratios are meaningful on
both (XLA's buffer assignment is the same machinery).

Flags:
  --smoke       fit-a-line only + hard schema/series asserts — the
                run_tests.sh fast-tier telemetry gate (traced step,
                trace + snapshot linted)
  --steps N     steady-state steps per model (default 8)
  --out FILE    also write the artifact line to FILE
  --trace FILE  write the Chrome/Perfetto trace of the whole run
  --metrics FILE  write the registry snapshot JSON
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _models():
    # builders moved to paddle_tpu/models/standing.py (ISSUE 16) so
    # `paddle attribute` and this driver measure the SAME descs; the
    # import is deferred because paddle_tpu pulls in jax
    from paddle_tpu.models.standing import MODELS

    return MODELS


def run_model(name, builder, steps, chip):
    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

    fluid.reset()  # NOTE: also resets the registry/tracer — see main()
    feed, fetch, bs = builder()
    program = fluid.default_main_program()
    prediction = obs.accounting.track(program, name, batch_size=bs,
                                      chip=chip)
    exe = fluid.Executor(fluid.default_place())
    exe.run(fluid.default_startup_program())
    with obs.span("predvmeas.model", model=name):
        for i in range(steps + 1):  # +1: the first run compiles
            with obs.span("predvmeas.step", model=name, step=i):
                exe.run(program, feed=feed, fetch_list=fetch,
                        rng_step=i)
        obs.accounting.record_measured_peak(program, exe, feed=feed,
                                            fetch_list=fetch)
    rows = obs.accounting.artifact_rows()
    report = obs.accounting.report()
    return prediction, rows, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fit-a-line only, with schema asserts (CI)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", default=None)
    ap.add_argument("--metrics", default=None)
    args = ap.parse_args(argv)

    from paddle_tpu import observability as obs
    from paddle_tpu.analysis import cost as acost

    chip = acost.detect_chip()
    all_models = _models()
    models = all_models[:1] if args.smoke else all_models
    all_rows, reports = [], []
    # fluid.reset() wipes telemetry between models, so each model's rows
    # and trace window are collected right after its run; the snapshot
    # export covers the LAST model's window (fit-a-line in --smoke)
    windows = []
    snapshot = None
    for name, builder in models:
        obs.enable_tracing()
        _, rows, report = run_model(name, builder, args.steps, chip)
        all_rows.extend(rows)
        reports.extend(report)
        windows.append(obs.TRACER.events())
        snapshot = obs.REGISTRY.snapshot()

    # each model ran in its own tracer window (fluid.reset() re-anchors
    # ts at 0): shift the windows onto one sequential timeline
    events = obs.concat_windows(windows)
    by_name = {r["metric"]: r for r in all_rows}
    headline = obs.artifact_metric(
        "predvmeas_rows", len(all_rows), "rows", vs_baseline=0.0,
        note=(f"predicted-vs-measured error ratios (predicted/measured; "
              f"1.0 = perfect static model) for "
              f"{', '.join(n for n, _ in models)} on chip spec "
              f"{chip!r}; step ratios on cpu-host measure dispatch "
              f"overhead on these microscopic models — the on-chip "
              f"capture is the ROADMAP #3 calibration number"),
        chip=chip, extra_metrics=all_rows, pred_vs_measured=reports)

    trace_obj = obs.chrome_envelope(events)
    problems = obs.export_telemetry(
        trace_obj=trace_obj, trace_path=args.trace,
        metrics_obj=snapshot, metrics_path=args.metrics)
    if args.smoke:
        # the run_tests.sh telemetry gate: a traced fit-a-line step must
        # yield (a) a schema-valid Perfetto trace containing the
        # executor phase spans, (b) a schema-valid registry snapshot
        # carrying the predicted-vs-measured series, (c) finite ratios
        assert not problems, f"telemetry artifact schema: {problems}"
        assert not obs.validate_chrome_trace(trace_obj)
        names = {e["name"] for e in events}
        for want in ("executor.build", "executor.execute",
                     "executor.donate", "executor.writeback",
                     "predvmeas.step"):
            assert want in names, f"missing span {want}: {sorted(names)}"
        assert snapshot is not None
        sp = obs.validate_snapshot(snapshot)
        assert not sp, f"snapshot schema: {sp}"
        fams = snapshot["families"]
        for fam in ("executor_step_seconds",
                    "pred_vs_measured_step_time_ratio",
                    "pred_vs_measured_peak_ratio",
                    "executor_steps_total"):
            assert fam in fams, f"missing family {fam}"
        assert by_name["predvmeas_step_ratio_fit_a_line"]["value"] > 0
        peak = by_name["predvmeas_peak_ratio_fit_a_line"]["value"]
        assert 0.2 < peak < 5.0, f"peak ratio {peak} out of sanity band"
        print("# telemetry smoke OK "
              f"(peak ratio {peak}, {len(events)} trace events)",
              file=sys.stderr)

    if problems:
        print(f"# telemetry schema problems: {problems}",
              file=sys.stderr)

    line = json.dumps(headline)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
