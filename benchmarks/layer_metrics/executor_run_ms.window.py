"""executor_run_ms.window — median host milliseconds between `t_enter` and
`t_exit` of the program's step record over the steady dispatches of the
UNTRACED window: the root of `executor_run_ms.train`, in the seconds
`train_samples_per_s` is measured in, from INSIDE the program (PR 65;
`dispatch_ms.train` is the benchmark's span around the same calls, from
outside, and lies over this by what `run` does around its root: under
ParallelExecutor, `distribute`).  Writes `detail["executor_window_ms"]`: the
medians of the root, of `execute`, of the root before and after it and (four
chips) of `distribute`, `untraced` beside the traced slice's medians of the
same spans (`traced`, reduce/program_spans.py) and `session_costs`, traced
less untraced: what a profiler session costs each phase of a dispatch.  None
where the program keeps no step record (the parent of PR 65)."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    S = load_module("reduce", "step_record")
    v = S.of_run(run)
    if v is None or not v["rows"]:
        return None
    P = load_module("reduce", "program_spans")
    table = S.window_table(v, P.of_run(run), P)
    table["steps"] = run["record"]["window"]["steps"]
    run["detail"]["executor_window_ms"] = table
    return table["untraced"]["root"]
