"""step_device_ms.train — device-busy milliseconds per training step: the
union of the intervals in which an operation ran on a chip during the
traced window (averaged over the chips), over the steps of that window."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    if run["trace_summary"] is None:
        return None
    return 1e3 * run["trace_summary"]["busy_s"] / run["record"]["traced"]["steps"]
