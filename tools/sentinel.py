#!/usr/bin/env python
"""Perf-regression sentinel (ISSUE 16).

Diffs two bench-artifact files (JSON lines in the tools/bench_*.py
schema: ``{"metric": ..., "value": ..., "unit": ..., **fields}``, with
nested ``extra_metrics`` rows hoisted) and issues a verdict PER METRIC:

    PASS        |delta| within the metric's noise margin
    REGRESSED   moved beyond the margin in the WORSE direction
    IMPROVED    moved beyond the margin in the BETTER direction

Direction comes from the metric's name/unit (step_ms and rank errors
regress UP, coverage and speedups regress DOWN); metrics whose polarity
the sentinel cannot tell are reported but never fail the run.

Noise-aware thresholds: the margin floor is ``--threshold`` (relative),
but any row carrying a best/median spread — the autotune sweep's
``best_ms``/``median_ms`` reconciliation fields, or an explicit
``best_vs_median_spread`` — RAISES its own margin to 2x that measured
spread, so a metric whose own trials wobble 8% is not flagged at 5%.

When a regressed/improved metric carries a per-op table (``by_type``
from ``paddle attribute``), the verdict names the guilty ops: the op
types whose measured share moved the most in the verdict's direction.

Exit code 1 iff any metric REGRESSED.  ``--self-test`` proves both
behaviours on a deterministic synthetic pair (identical -> all PASS;
injected slowdown -> REGRESSED naming the metric and the guilty op) —
the run_tests.sh wiring runs the self-test plus a golden-baseline
compare of the fit-a-line attribution artifact.

stdlib only — usable on hosts without jax/numpy.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

_HIGHER_IS_BETTER = ("coverage", "speedup", "mfu", "throughput",
                     "tokens_per", "fraction", "accuracy", "hit_rate",
                     "goodput", "steps_per_s")
_LOWER_IS_BETTER = ("time", "_ms", "latency", "seconds", "step_s",
                    "rank_error", "bytes", "peak", "p50", "p99",
                    "stall", "overhead")


def polarity(name: str, unit: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 unknown (unscored)."""
    text = f"{name} {unit}".lower()
    for key in _HIGHER_IS_BETTER:
        if key in text:
            return 1
    for key in _LOWER_IS_BETTER:
        if key in text:
            return -1
    return 0


def load_rows(path: str) -> Dict[str, dict]:
    """metric name -> row, from a file of bench-schema JSON lines.
    ``extra_metrics`` rows are hoisted to top level (last write wins)."""
    rows: Dict[str, dict] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            obj = json.loads(line)
            for row in [obj] + list(obj.get("extra_metrics") or []):
                name = row.get("metric")
                if name is not None and "value" in row:
                    rows[name] = row
    return rows


def noise_margin(floor: float, *rows: Optional[dict]) -> float:
    """Relative margin for one metric: the --threshold floor, raised to
    2x any best/median spread either side's row carries."""
    spread = 0.0
    for row in rows:
        if not isinstance(row, dict):
            continue
        best, median = row.get("best_ms"), row.get("median_ms")
        if best and median and best > 0:
            spread = max(spread, (float(median) - float(best))
                         / float(best))
        explicit = row.get("best_vs_median_spread")
        if explicit:
            spread = max(spread, float(explicit))
    return max(floor, 2.0 * spread)


def _shares(row: dict) -> Dict[str, float]:
    by_type = row.get("by_type")
    if not isinstance(by_type, dict):
        return {}
    out = {}
    for op, entry in by_type.items():
        if isinstance(entry, dict) and "share" in entry:
            out[op] = float(entry["share"])
    return out


def guilty_ops(base_row: dict, cand_row: dict,
               direction: int) -> List[Tuple[str, float]]:
    """Op types whose measured share moved the most in the verdict's
    direction (+1: grew, the regression suspects; -1: shrank)."""
    base_s, cand_s = _shares(base_row), _shares(cand_row)
    if not base_s or not cand_s:
        return []
    deltas = [(op, cand_s.get(op, 0.0) - base_s.get(op, 0.0))
              for op in set(base_s) | set(cand_s)]
    deltas = [(op, d) for op, d in deltas if d * direction > 0.005]
    deltas.sort(key=lambda t: -abs(t[1]))
    return deltas[:3]


def compare(base_rows: Dict[str, dict], cand_rows: Dict[str, dict],
            threshold: float = 0.10) -> dict:
    """The sentinel verdict table for two row maps."""
    verdicts = []
    n_reg = n_imp = n_pass = n_unscored = 0
    for name in sorted(set(base_rows) & set(cand_rows)):
        base, cand = base_rows[name], cand_rows[name]
        try:
            bv, cv = float(base["value"]), float(cand["value"])
        except (TypeError, ValueError):
            continue
        pol = polarity(name, str(base.get("unit", "")))
        margin = noise_margin(threshold, base, cand)
        delta = (cv - bv) / abs(bv) if bv else (0.0 if cv == bv
                                               else float("inf"))
        verdict, guilty = "PASS", []
        if pol == 0:
            n_unscored += 1
            verdict = "PASS"  # unscored: reported, never fails the run
        elif abs(delta) > margin:
            worse = delta * pol < 0
            verdict = "REGRESSED" if worse else "IMPROVED"
            # slowdown -> ops whose share GREW are the suspects;
            # improvement -> the ops whose share shrank get the credit
            guilty = guilty_ops(base, cand, 1 if worse else -1)
        if verdict == "REGRESSED":
            n_reg += 1
        elif verdict == "IMPROVED":
            n_imp += 1
        else:
            n_pass += 1
        verdicts.append({
            "metric": name, "verdict": verdict,
            "baseline": bv, "candidate": cv,
            "delta_rel": round(delta, 6), "margin_rel": round(margin, 6),
            "polarity": {1: "higher_is_better", -1: "lower_is_better",
                         0: "unscored"}[pol],
            "guilty_ops": [{"op_type": op, "share_delta": round(d, 4)}
                           for op, d in guilty]})
    only_base = sorted(set(base_rows) - set(cand_rows))
    only_cand = sorted(set(cand_rows) - set(base_rows))
    return {"schema": "paddle_tpu.sentinel.v1",
            "verdict": "REGRESSED" if n_reg else "PASS",
            "compared": len(verdicts), "regressed": n_reg,
            "improved": n_imp, "passed": n_pass,
            "unscored": n_unscored,
            "missing_in_candidate": only_base,
            "new_in_candidate": only_cand,
            "metrics": verdicts}


def render(report: dict, file=sys.stderr) -> None:
    for m in report["metrics"]:
        line = (f"{m['verdict']:<9} {m['metric']:<40} "
                f"{m['baseline']:.6g} -> {m['candidate']:.6g} "
                f"({m['delta_rel'] * 100:+.1f}% vs margin "
                f"{m['margin_rel'] * 100:.1f}%)")
        if m["guilty_ops"]:
            ops = ", ".join(f"{g['op_type']} "
                            f"({g['share_delta'] * 100:+.1f}pp share)"
                            for g in m["guilty_ops"])
            line += f"  guilty: {ops}"
        print(line, file=file)
    for name in report["missing_in_candidate"]:
        print(f"MISSING   {name} (in baseline only)", file=file)
    print(f"sentinel: {report['verdict']} — {report['compared']} "
          f"compared, {report['regressed']} regressed, "
          f"{report['improved']} improved, {report['passed']} passed "
          f"({report['unscored']} unscored)", file=file)


def self_test() -> int:
    """Deterministic proof of both sentinel behaviours (the
    run_tests.sh gate): identical runs PASS; an injected slowdown is
    REGRESSED naming the metric and the guilty op; an injected rank
    improvement is IMPROVED; wobble within the recorded best/median
    spread stays PASS."""
    base = {
        "lstm_step_ms": {"metric": "lstm_step_ms", "value": 6.97,
                         "unit": "ms", "best_ms": 6.97,
                         "median_ms": 7.40,
                         "by_type": {"generic_grad": {"share": 0.55},
                                     "mul": {"share": 0.30},
                                     "sigmoid": {"share": 0.15}}},
        "op_attribution_fit_a_line": {
            "metric": "op_attribution_fit_a_line", "value": 0.97,
            "unit": "fraction of measured step time attributed"},
        "autotune_rank_error_lstm": {
            "metric": "autotune_rank_error_lstm", "value": 6,
            "unit": "rank of measured winner in predicted order"},
    }
    same = compare(base, json.loads(json.dumps(base)))
    assert same["verdict"] == "PASS" and same["regressed"] == 0, same

    # wobble INSIDE the recorded best/median spread (6.2%): margin is
    # 2x spread = 12.3%, so +8% stays PASS
    wobble = json.loads(json.dumps(base))
    wobble["lstm_step_ms"]["value"] = 6.97 * 1.08
    assert compare(base, wobble)["regressed"] == 0

    bad = json.loads(json.dumps(base))
    bad["lstm_step_ms"]["value"] = 6.97 * 1.8
    bad["lstm_step_ms"]["by_type"] = {"generic_grad": {"share": 0.75},
                                      "mul": {"share": 0.17},
                                      "sigmoid": {"share": 0.08}}
    bad["autotune_rank_error_lstm"]["value"] = 2
    rep = compare(base, bad)
    by = {m["metric"]: m for m in rep["metrics"]}
    assert rep["verdict"] == "REGRESSED"
    assert by["lstm_step_ms"]["verdict"] == "REGRESSED", by
    assert by["lstm_step_ms"]["guilty_ops"], "no guilty op named"
    assert (by["lstm_step_ms"]["guilty_ops"][0]["op_type"]
            == "generic_grad"), by["lstm_step_ms"]["guilty_ops"]
    assert by["autotune_rank_error_lstm"]["verdict"] == "IMPROVED", by
    assert by["op_attribution_fit_a_line"]["verdict"] == "PASS", by

    # coverage COLLAPSE (higher-is-better polarity) regresses
    low = json.loads(json.dumps(base))
    low["op_attribution_fit_a_line"]["value"] = 0.4
    rep2 = compare(base, low)
    by2 = {m["metric"]: m for m in rep2["metrics"]}
    assert by2["op_attribution_fit_a_line"]["verdict"] == "REGRESSED"

    # the ISSUE 20 step_loop artifact: steps/s is higher-is-better (a
    # drop regresses), despite "step" also living in lower-is-better
    # latency names like step_s/step_ms
    assert polarity("step_loop_steps_per_s_k8", "steps/s") == 1
    sl_base = {"step_loop_steps_per_s_k8": {
        "metric": "step_loop_steps_per_s_k8", "value": 22000.0,
        "unit": "steps/s"}}
    sl_bad = json.loads(json.dumps(sl_base))
    sl_bad["step_loop_steps_per_s_k8"]["value"] = 11000.0
    rep3 = compare(sl_base, sl_bad)
    assert rep3["verdict"] == "REGRESSED", rep3

    print("# sentinel self-test OK (identical=PASS, injected slowdown="
          "REGRESSED w/ guilty op, rank gain=IMPROVED, in-spread "
          "wobble=PASS)", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", help="bench-artifact JSON-lines file")
    ap.add_argument("--candidate", help="bench-artifact JSON-lines file")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative margin floor (default 0.10; rows "
                         "with best/median spreads raise their own)")
    ap.add_argument("--json", action="store_true",
                    help="print the machine report to stdout")
    ap.add_argument("--out", default=None,
                    help="also write the machine report to FILE")
    ap.add_argument("--no-fail", action="store_true",
                    help="exit 0 even on regressions (report-only)")
    ap.add_argument("--self-test", action="store_true",
                    help="prove PASS-on-identical and "
                         "REGRESSED-on-injected, then exit")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()
    if not args.baseline or not args.candidate:
        ap.error("--baseline and --candidate are required "
                 "(or --self-test)")

    report = compare(load_rows(args.baseline), load_rows(args.candidate),
                     threshold=args.threshold)
    render(report)
    if args.json:
        print(json.dumps(report), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f)
            f.write("\n")
    if report["regressed"] and not args.no_fail:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
