#!/usr/bin/env python
"""Autotune sweep artifact emitter (ISSUE 14 / ROADMAP #3).

Runs the analyzer-guided tuner over the standing CPU-measurable
workloads with EVERY feasible candidate measured (not just the
predicted top-k), then publishes the number that calibrates the cost
model: **rank error** — where the measured winner actually sat in the
prior's predicted order, and whether the default top-k gate would have
caught it — plus per-candidate predicted/measured times, all through
the PR 13 ``artifact_metric`` namespace.

The ``lstm`` workload additionally settles the 6.97-vs-9.89 ms
discrepancy (VERDICT r5 Weak #2) the only way it can be settled: both
statistics come from ONE run — best-of-N (the additive-noise
capability number, the 6.97-class methodology) and the steady-state
median (the honest headline, the 9.89-class methodology) — so the
artifact, not a human, says which number is which.  The on-chip
``autotune_sweep`` daemon capture re-emits this with real silicon
times.

Flags:
  --workloads a,b,c  (default gpt_small,lstm,mlp_depth)
  --smoke            mock measurer + schema asserts (the CI gate)
  --top-k N          the rank-error gate being judged (default 5)
  --iters/--repeats/--warmup   trial sizing
  --out FILE         also write the artifact line to FILE
  --metrics FILE     registry snapshot JSON
  --trace FILE       Chrome/Perfetto trace of the whole sweep
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_WORKLOADS = "gpt_small,lstm,mlp_depth"


def populate_calibration(models=("fit_a_line", "small_lm", "lstm")):
    """--calibrate: learn measured per-op factors for THIS host by
    running the attribution oracle over the standing programs
    (paddle_tpu/models/standing.py) into the calibration store the
    prior will consume (ISSUE 16)."""
    import paddle_tpu as fluid
    from paddle_tpu.models.standing import get_builder
    from paddle_tpu.observability import attribution, calibration

    for name in models:
        fluid.reset()
        feed, _fetch, bs = get_builder(name)()
        program = fluid.default_main_program()
        exe = fluid.Executor(fluid.default_place())
        exe.run(fluid.default_startup_program())
        table = attribution.attribute_cpu(program, feed, batch_size=bs,
                                          repeats=2)
        calibration.default_store().record_attribution(table)
        print(f"# calibrated from {name}: {table['n_ops']} ops, "
              f"coverage {table['coverage']:.3f} "
              f"(chip {table['chip']})", file=sys.stderr)
    fluid.reset()


def _calibrated_rank(wl, rep):
    """Re-rank the SAME candidate set with calibration consumption ON —
    no re-measurement, just a second prior pass — and return where the
    measured winner sits in the calibrated predicted order.  None when
    calibration is disabled, the chip has no factors, or the workload
    never reaches the program-cost path (analytic kernels stay raw)."""
    from paddle_tpu.autotune import prior
    from paddle_tpu.observability import calibration as calib

    if not calib.calibration_enabled():
        return None
    feasible, _ = prior.rank(wl, wl.space().candidates())
    if not feasible or not any(p.calibrated for p in feasible):
        return None
    order = [p.candidate.digest for p in feasible]
    win = rep["winner_row"]["digest"]
    return order.index(win) + 1 if win in order else None


def sweep_workload(name, args, measurer):
    from paddle_tpu import autotune
    from paddle_tpu import observability as obs
    from paddle_tpu.autotune import workloads as at_workloads

    wl = at_workloads.get_workload(name)
    # the tune() pass always ranks RAW (calibration consumption off for
    # its duration) so rank_error_<wl> stays comparable with the
    # recorded baseline; the calibrated re-rank below is a separate row
    prev_gate = os.environ.get("PADDLE_TPU_CALIBRATION")
    os.environ["PADDLE_TPU_CALIBRATION"] = "0"
    try:
        rep = autotune.tune(wl, measurer=measurer, top_k=args.top_k,
                            force=True, measure_all=True)
    finally:
        if prev_gate is None:
            os.environ.pop("PADDLE_TPU_CALIBRATION", None)
        else:
            os.environ["PADDLE_TPU_CALIBRATION"] = prev_gate
    cands = [{
        "digest": t["digest"], "params": t["params"],
        "predicted_s": round(t["predicted_step_s"], 9),
        "measured_best_s": round(t["best_s"], 6),
        "measured_median_s": round(t["median_s"], 6),
    } for t in rep["trials"]]
    rows = [obs.artifact_metric(
        f"autotune_rank_error_{name}", rep["rank_of_winner"],
        "predicted rank of measured winner (1 = prior nailed it)",
        in_top_k=rep["in_top_k"], top_k=args.top_k,
        n_candidates=rep["space_size"], n_measured=len(rep["trials"]),
        n_rejected=rep["n_rejected"],
        winner=rep["winner"], candidates=cands)]
    cal_rank = _calibrated_rank(wl, rep)
    if cal_rank is not None:
        rows.append(obs.artifact_metric(
            f"autotune_rank_error_calibrated_{name}", cal_rank,
            "predicted rank of measured winner under measured "
            "calibration factors (raw rank rides alongside)",
            raw_rank=rep["rank_of_winner"],
            improved=cal_rank < rep["rank_of_winner"],
            in_top_k=cal_rank <= args.top_k, top_k=args.top_k))
    base, win = rep.get("default_row"), rep["winner_row"]
    if base and win["best_s"]:
        rows.append(obs.artifact_metric(
            f"autotune_speedup_{name}",
            round(base["best_s"] / win["best_s"], 4),
            "measured default/winner step-time ratio (>=1.0 by "
            "construction: the default is always measured)",
            default_ms=round(base["best_s"] * 1e3, 4),
            winner_ms=round(win["best_s"] * 1e3, 4),
            winner_params=rep["winner"]))
    if name == "lstm" and base is not None:
        spread = ((base["median_s"] - base["best_s"]) / base["median_s"]
                  if base["median_s"] else 0.0)
        rows.append(obs.artifact_metric(
            "lstm_step_ms_reconciliation",
            round(base["median_s"] * 1e3, 4), "ms/step (median, the "
            "headline statistic)",
            best_ms=round(base["best_s"] * 1e3, 4),
            median_ms=round(base["median_s"] * 1e3, 4),
            best_vs_median_spread=round(spread, 4),
            passes_ms=base.get("passes_ms"),
            note=("the 6.97-vs-9.89 ms LSTM discrepancy (VERDICT r5 "
                  "Weak #2) was a methodology split, not a measurement "
                  "error: 6.97 was a best-of-N capability number, 9.89 "
                  "a per-run number under measured defaults.  This row "
                  "carries BOTH statistics from one run: quote "
                  "median_ms as the headline; best_ms only as the "
                  "additive-noise capability bound.  CPU numbers here "
                  "prove the harness; the on-chip autotune_sweep "
                  "capture supplies the silicon values.")))
    return rep, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", default=DEFAULT_WORKLOADS)
    ap.add_argument("--smoke", action="store_true",
                    help="mock measurer + schema asserts (CI)")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--store", default=None,
                    help="winner-store dir (default: a throwaway — the "
                         "sweep measures everything anyway and must "
                         "not overwrite a curated store implicitly)")
    ap.add_argument("--keep-store", action="store_true",
                    help="record winners into the DEFAULT store")
    ap.add_argument("--calibrate", action="store_true",
                    help="first learn measured per-op factors from the "
                         "standing programs (attribution oracle) and "
                         "rank with them — adds the "
                         "autotune_rank_error_calibrated_* rows")
    ap.add_argument("--calibration-root", default=None,
                    help="calibration store dir (default with "
                         "--calibrate: a throwaway, so the sweep never "
                         "implicitly rewrites a curated store)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--trace", default=None)
    args = ap.parse_args(argv)

    tmp_store = None
    if args.store:
        os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = os.path.abspath(
            args.store)
    elif not args.keep_store:
        tmp_store = tempfile.TemporaryDirectory(prefix="at_sweep_")
        os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = tmp_store.name

    tmp_cal = None
    if args.calibration_root:
        os.environ["PADDLE_TPU_CALIBRATION_CACHE"] = os.path.abspath(
            args.calibration_root)
    elif args.calibrate:
        tmp_cal = tempfile.TemporaryDirectory(prefix="at_calib_")
        os.environ["PADDLE_TPU_CALIBRATION_CACHE"] = tmp_cal.name

    from paddle_tpu import observability as obs
    from paddle_tpu.autotune.measure import MockMeasurer, TimedMeasurer

    obs.enable_tracing()
    if args.calibrate:
        populate_calibration()
    if args.smoke:
        measurer = MockMeasurer()
        args.workloads = "gpt_small"
    else:
        measurer = TimedMeasurer(warmup=args.warmup, iters=args.iters,
                                 repeats=args.repeats)

    names = [w.strip() for w in args.workloads.split(",") if w.strip()]
    all_rows, ranks = [], {}
    for name in names:
        with obs.span("autotune.sweep", workload=name):
            rep, rows = sweep_workload(name, args, measurer)
        all_rows.extend(rows)
        ranks[name] = {"rank": rep["rank_of_winner"],
                       "in_top_k": rep["in_top_k"]}
        print(f"# {name}: winner {rep['winner']} rank "
              f"{rep['rank_of_winner']} (top-{args.top_k}: "
              f"{rep['in_top_k']})", file=sys.stderr)

    headline = obs.artifact_metric(
        "autotune_sweep_workloads", len(names), "workloads swept",
        vs_baseline=0.0,
        note=("predicted-vs-measured rank error of the static cost "
              "prior per workload (did the prior's top-k contain the "
              "measured winner?) + per-candidate predicted/measured "
              "times.  A rank inside top-k means the compile gate "
              "loses nothing; a rank outside it is the calibration "
              "debt the next cost-model round pays down."),
        ranks=ranks, extra_metrics=all_rows)

    snapshot = obs.REGISTRY.snapshot()
    trace_obj = obs.chrome_envelope(obs.TRACER.events())
    problems = obs.export_telemetry(
        trace_obj=trace_obj, trace_path=args.trace,
        metrics_obj=snapshot, metrics_path=args.metrics)

    if args.smoke:
        assert not problems, f"telemetry schema: {problems}"
        sp = obs.validate_snapshot(snapshot)
        assert not sp, f"snapshot schema: {sp}"
        fams = snapshot["families"]
        for fam in ("autotune_rank_error", "autotune_trials_total"):
            assert fam in fams, f"missing family {fam}: {sorted(fams)}"
        names_seen = {e["name"] for e in obs.TRACER.events()}
        assert "autotune.rank" in names_seen, sorted(names_seen)
        by_name = {r["metric"]: r for r in all_rows}
        r = by_name["autotune_rank_error_gpt_small"]
        assert r["value"] >= 1 and r["candidates"], r
        print("# autotune sweep smoke OK", file=sys.stderr)

    if problems:
        print(f"# telemetry schema problems: {problems}",
              file=sys.stderr)
    line = json.dumps(headline)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if tmp_store is not None:
        tmp_store.cleanup()
    if tmp_cal is not None:
        tmp_cal.cleanup()
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
