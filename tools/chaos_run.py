#!/usr/bin/env python
"""Chaos-matrix driver for the elastic training service (ISSUE 12).

Runs the fault-scenario catalog (paddle_tpu/distributed/chaos.py) against
the multi-job training service and demands an oracle-PROVEN recovery
after every cell: the interrupted-and-resumed run's written-back
parameter state must equal an uninterrupted reference run bitwise
(analysis/equivalence differential oracle, rtol=atol=0).

Modes:
  --smoke    1 scenario (worker_kill) x 1 seed — the run_tests.sh fast
             tier gate, <30s on CPU
  --matrix   all 5 scenarios x --seeds seeds + the 16k-context
             fit-because-remat admission demo

Emits one JSON artifact (stdout line + optional --out file); exits 1 if
any cell fails its proof.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--smoke", action="store_true",
                      help="single worker-kill cell (fast CI gate)")
    mode.add_argument("--matrix", action="store_true",
                      help="full scenario x seed matrix + admission demo")
    ap.add_argument("--seeds", type=int, default=2,
                    help="seeds per scenario in --matrix (default 2)")
    ap.add_argument("--scenario", action="append", default=[],
                    help="explicit scenario(s) instead of the catalog")
    ap.add_argument("--out", default=None, help="artifact path")
    ap.add_argument("--trace", default=None,
                    help="write the Perfetto trace of the run (rollback "
                         "spans, admission/recovery events, executor "
                         "phases) to this path")
    ap.add_argument("--metrics", default=None,
                    help="write the metrics-registry snapshot JSON "
                         "(recoveries, lease/requeue counters, "
                         "admissions) to this path")
    args = ap.parse_args(argv)

    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import chaos

    if args.trace:
        obs.enable_tracing()

    t0 = time.time()
    if args.smoke:
        cells = [("worker_kill", 0)]
        run_admission = False
    else:
        names = args.scenario or list(chaos.SCENARIOS)
        cells = [(sc, seed) for sc in names
                 for seed in range(max(1, args.seeds))]
        run_admission = not args.scenario

    results = []
    for sc, seed in cells:
        cell_t0 = time.time()
        rec = chaos.run_scenario(sc, seed=seed)
        rec["elapsed_s"] = round(time.time() - cell_t0, 1)
        results.append(rec)
        print(f"# {sc} seed={seed}: "
              f"{'PROVEN' if rec['proof']['equivalent'] else 'FAILED'} "
              f"(tier={rec['proof']['tier']}, "
              f"recoveries={len(rec['recoveries'])}, "
              f"{rec['elapsed_s']}s)", file=sys.stderr)

    admission = None
    if run_admission:
        cell_t0 = time.time()
        admission = chaos.admission_demo()
        admission["elapsed_s"] = round(time.time() - cell_t0, 1)
        print(f"# admission demo: "
              f"{'OK' if admission['ok'] else 'FAILED'} "
              f"({admission['elapsed_s']}s)", file=sys.stderr)

    proven = sum(1 for r in results if r["proof"]["equivalent"])
    ok = proven == len(results) and (admission is None
                                     or admission["ok"])
    artifact = {
        "metric": "chaos_matrix_proven_cells",
        "value": proven,
        "cells": len(results),
        # "ok" is assigned once, after the telemetry block may flip it
        "elapsed_s": round(time.time() - t0, 1),
        "scenarios": sorted({r["scenario"] for r in results}),
        "results": results,
        "admission_demo": admission,
    }
    # telemetry artifacts: the chaos run's whole window through the
    # shared registry/tracer (run_scenario never calls fluid.reset(), so
    # the counters accumulate across cells)
    if args.trace or args.metrics:
        problems = obs.export_telemetry(
            trace_obj=obs.TRACER.to_chrome() if args.trace else None,
            trace_path=args.trace,
            metrics_obj=obs.REGISTRY.snapshot() if args.metrics
            else None,
            metrics_path=args.metrics)
        if problems:
            print(f"# telemetry schema problems: {problems}",
                  file=sys.stderr)
            ok = False
        if args.trace:
            artifact["trace"] = args.trace
        if args.metrics:
            artifact["metrics"] = args.metrics
    artifact["ok"] = ok

    line = json.dumps(artifact, default=str)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
