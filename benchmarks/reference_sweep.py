#!/usr/bin/env python3
"""What a cell's reference check reads, over many seeds in one process:

    python3 benchmarks/reference_sweep.py --workload <name> \
        --seeds <n>,<n>,... [--control <k>]

A tolerance of `reference/<config>.py` is set from the worst of a dozen
seeds or more, and a run of run.py pays its whole set-up for one reading.
This drives the cell's own driver once a seed, with a window of one step
(the steps up to the traffic file's `loss_fell_step` follow, so `correct`
is the cell's own), and prints one JSON line a seed: every number `correct`
rests on beside its limit.  Where the reference has a `control_check`
(the same reference in the nearest precision below the configuration's),
the first `--control` seeds are also read with it in the program's place.
Everything goes to chiprun_out/reference_sweep.<cell>.json too.  A TPU or
nothing, as run.py: a tolerance is never set from a CPU reading.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # the program under test: paddle_tpu

import harness  # noqa: E402  (benchmarks/ is sys.path[0])


def sweep(cell: dict, config: dict, traffic: dict, seeds, place_of,
          trace_dir: str, control: int = 0, seconds: float = 0.05):
    """One dict a seed: the driver's `correct` and `compared` ({name:
    [number, limit]}), and for the first `control` seeds the control's
    numbers under the same names."""
    driver = harness.load_module("drivers", traffic["driver"])
    for n, seed in enumerate(seeds):
        ctx = harness.Context(
            cell=cell, config=config, traffic=traffic, seed=seed,
            seconds=seconds, trace=False, t_start=time.monotonic(),
            place_of=place_of, trace_dir=trace_dir)
        record = driver.run(ctx)
        row = {"seed": seed, "correct": record["correct"],
               "compared": record["compared"]}
        if n < control:
            row["control"] = _control(config, traffic, seed)
        yield row


def _control(config: dict, traffic: dict, seed: int) -> dict:
    """The reference against its own control on the weights the driver's
    run has just left in the scope: they have moved by the run's steps,
    which a precision control does not mind."""
    import numpy as np

    import paddle_tpu as fluid

    ref = harness.load_module("reference", config["name"])
    gen = harness.load_module("generators", traffic["generator"])
    feed = {k: v[0] for k, v in gen.generate(
        seed, config["train"]["feeds"], int(traffic["batch"]), 1).items()}
    scope = fluid.global_scope()
    params = [scope.find(p.name) for p in fluid.default_main_program()
              .global_block().all_parameters()]
    want, got = ({k: np.asarray(v, np.float32) for k, v in
                  check(params, feed, config).items()}
                 for check in (ref.train_check, ref.control_check))
    return harness.load_module("drivers", "train_executor").reference_errors(
        got, want, getattr(ref, "CENTERED", ()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, separated by commas")
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)

    manifest = harness.load_manifest()
    cell = harness.cell_of(manifest, args.workload)
    if harness.claim_tpu(int(cell["chips"]),
                         f"benchmarks/reference_sweep.py: workload "
                         f"{cell['name']!r}") is None:
        return 1
    import paddle_tpu as fluid

    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    ref = harness.load_module("reference", config["name"])
    control = args.control if hasattr(ref, "control_check") else 0
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for row in sweep(cell, config, traffic,
                     [int(s) for s in args.seeds.split(",")],
                     fluid.TPUPlace, os.path.join(out_dir, "trace"),
                     control):
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": cell["name"], "seeds": len(rows),
               "all_correct": all(r["correct"] for r in rows),
               "worst": {k: max(r["compared"][k][0] for r in rows)
                         for k in ref.TOL},
               "tolerances": ref.TOL}
    controls = [r["control"] for r in rows if "control" in r]
    if controls:
        summary["control_least"] = {k: min(c[k] for c in controls)
                                    for k in ref.TOL}
    with open(os.path.join(out_dir, f"reference_sweep.{cell['name']}.json"),
              "w", encoding="utf-8") as f:
        json.dump({"summary": summary, "rows": rows}, f, indent=1)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
