"""step_attributed_pct — of the first device's busy time in the traced
window, the share in events whose instruction the compiled program itself
puts under a desc op's scope (`pdop__<type>__u<uid>`; an unnamed copy
takes its producer's): 100 less the row `unattributed` of
benchmarks/reduce/op_scopes.py.  The guard of every reader that goes by
those scopes: a step loaded from a compile cache written before the
program stamped them carries none (JAX's cache keys ignore metadata) and
reads 0 here.  `detail["step_by_op_ms"]`: the twelve largest rows, ms a
step.  Nothing to read where the trace has no metadata plane or the
program stamps no identity (no `executor_op_emit_seconds_total`: the
parent of PR 35)."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "op_scopes")
    got = M.of_run(run)
    if got is None or M.emit_seconds() is None:
        return None
    run["detail"]["step_by_op_ms"] = M.largest(got)
    return 100.0 * got["coverage"]
