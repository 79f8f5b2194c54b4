"""idle_in_dispatch_pct.train — the share of the traced window in which
the first device ran nothing while the dispatching thread was inside one of
the program's own spans (`pdtpu.executor.run` and its children, and
`pdtpu.executor.distribute` under ParallelExecutor): the idle time the
program's host code owns, as against the drain under the benchmark's loss
read.  Writes the idle seconds by innermost program span into
`detail["idle_by_program_span"]`; they add up to that device's idle time in
the window."""

LAYER = "XLA + device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    P = load_module("reduce", "program_spans")
    trace, spans = run.get("trace"), P.of_run(run)
    if not trace or not spans:
        return None
    idle = P.idle_by_program_span(trace, spans, run["tracemod"])
    if idle is None:
        return None
    run["detail"]["idle_by_program_span"] = idle
    lo, hi = spans["window"]
    inside = sum(s for name, s in idle.items() if name != P.OUTSIDE)
    return 100.0 * inside / ((hi - lo) / 1e9)
