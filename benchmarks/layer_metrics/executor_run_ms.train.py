"""executor_run_ms.train — median host milliseconds of the program's own
root span `pdtpu.executor.run` in the traced slice.  Writes the whole split
into `detail["executor_run_split_ms"]`: the median of every child, of the
root's self time, of what the program does beside the root (`top_level`),
and of the benchmark's own span around the same calls in the same slice
(`bench.executor_run`).  That last one less the root is what `run` spends
before its root (under ParallelExecutor, `pdtpu.executor.distribute`);
against `dispatch_ms.train`, the same span in the untraced window, it is
what the profiler session costs a dispatch."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    P = load_module("reduce", "program_spans")
    spans = P.of_run(run)
    table = P.split_ms(P.roots(spans)) if spans else {}
    if not table:
        return None
    table["top_level"] = P.top_level_ms(spans)
    outer = [d for name, _, d in (run.get("trace") or {}).get("host", ())
             if name == "bench.executor_run"]
    if outer:
        from harness import median

        table["bench.executor_run"] = median(outer) / 1e6
    run["detail"]["executor_run_split_ms"] = table
    return table[P.ROOT]
