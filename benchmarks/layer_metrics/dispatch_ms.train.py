"""dispatch_ms.train — median host milliseconds inside one
Executor.run / ParallelExecutor.run call of the untraced window, from the
benchmark's own span around the call.  The call returns before the device
finishes, so this is what the host pays to launch a step: it bounds the
step rate once a step's device time falls under it."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import median

    w = run["record"]["window"]
    d = run["ctx"].spans.durations("executor_run", w["t0"], w["t1"])
    return 1e3 * median(d) if d else None
