#!/usr/bin/env python3
"""One cell of the benchmark, once:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on.  It needs a TPU with at least
the chips the cell asks for and otherwise exits non-zero, naming what it
found, before building any program: there is no CPU mode.  The last line of
its standard output is the one JSON object the driver reads (`correct`,
`attempted`, `failed`, `metrics`, `device`, `breakdown` in a traced run, and
last `compared`: every number `correct` rests on beside its limit, which are
also the last lines of standard error); the line before it carries the
checks and every number in full, and the same goes to chiprun_out/
(git-ignored).

`--trace 0` reports the cell's end-to-end metrics, measured with the
profiler off.  `--trace 1` reports its per-layer metrics: host-clock ones
from the untraced part of the window, device ones from a profiler trace of
its last few seconds.

Everything a cell is made of is a file found by name (README.md); this
script knows none of them.
"""

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # the program under test: paddle_tpu

import harness  # noqa: E402  (benchmarks/ is sys.path[0])


def _plain(value):
    """What of a record can go into JSON as it is; the rest (devices, long
    lists) is dropped."""
    if isinstance(value, dict):
        return {str(k): _plain(v) for k, v in value.items()
                if v is None or _plain(v) is not None}
    if isinstance(value, (bool, int, float, str)) or value is None:
        return value
    if isinstance(value, (list, tuple)) and len(value) <= 64:
        return [_plain(v) for v in value]
    return None


def _cache_state() -> dict:
    """Where the persistent compile cache is, and what it holds now."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    names = os.listdir(path) if path and os.path.isdir(path) else []
    return {"dir": path, "entries": sum(1 for n in names
                                        if not n.endswith("-atime")),
            "bytes": sum(os.path.getsize(os.path.join(path, n))
                         for n in names)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = harness.load_manifest()
    cell = harness.cell_of(manifest, args.workload)

    found = harness.claim_tpu(int(cell["chips"]),
                              f"benchmarks/run.py: workload {cell['name']!r}")
    if found is None:
        return 1
    peaks = harness.peaks_for(found[0].device_kind)

    import paddle_tpu as fluid

    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    trace_dir = os.path.join(out_dir, "trace")
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          place_of=fluid.TPUPlace, trace_dir=trace_dir)
    record = harness.load_module("drivers", traffic["driver"]).run(ctx)

    os.makedirs(out_dir, exist_ok=True)
    tag = f"{cell['name']}.seed{args.seed}.trace{args.trace}"
    detail: dict = {}
    breakdown = summary = None
    if not args.trace:
        wanted = harness.metrics_of(manifest, "end_to_end", cell["name"])
        values = {m["name"]: record["values"].get(m["name"]) for m in wanted}
        missing = [k for k, v in values.items() if v is None]
        if missing:
            print(f"benchmarks/run.py: the run gave no value for {missing}",
                  file=sys.stderr)
            return 1
    else:
        T = harness.load_module("reduce", "trace")
        trace = T.load_xplane(record["trace_path"])
        with open(os.path.join(out_dir, tag + ".trace_sample.json"), "w",
                  encoding="utf-8") as f:
            json.dump(T.sample(trace), f)
        summary = T.summary(trace)
        breakdown = T.breakdown(trace)
        run = {"record": record, "ctx": ctx, "trace": trace,
               "trace_summary": summary, "tracemod": T, "peaks": peaks,
               "flops": harness.load_module(".", "flops"), "detail": detail}
        values = {}
        for m in harness.metrics_of(manifest, "per_layer", cell["name"]):
            v = harness.load_module("layer_metrics", m["name"]).read(run)
            if v is not None:  # nothing to read: left out of the line
                values[m["name"]] = v

    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    metrics = {k: {"value": float(v), "unit": units[k]}
               for k, v in values.items()}
    info = {"workload": cell["name"], "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            "checks": _plain(record["checks"]),
            "values": _plain(record["values"]),
            "window": _plain(record["window"]),
            "traced": _plain(record.get("traced")),
            "setup": _plain(record["setup"]),
            "allocator": _plain(found[0].memory_stats()),
            "compile_cache": _cache_state(),
            "spans_s": {k: sum(b - a for a, b in v)
                        for k, v in ctx.spans.times.items()},
            "trace_summary": summary, "detail": detail}
    line = harness.result_line(
        record["correct"], record["attempted"], record["failed"], metrics,
        harness.device_block(found, summary), breakdown, record["compared"])
    with open(os.path.join(out_dir, tag + ".json"), "w",
              encoding="utf-8") as f:
        json.dump({"info": info, "result": json.loads(line)}, f, indent=1)
    print(json.dumps({"info": info}), flush=True)
    print(line, flush=True)
    print("\n".join(harness.compared_lines(record["compared"])),
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
