"""moe_device_share_pct — the expert layer's share of the traced window's
device-busy time, forward and backward: the grouped matmul kernels, the
relayout copies of the stacked expert weights, every instruction on the
token-slot rows (gathers by the sort, the SiLU-gate product, the weighted
combine); benchmarks/reduce/moe_ops.py finds them by the shapes in the
instruction text, and says why the router's own 0.3 ms a step are not
among them.  The optimizer's update of the expert weights is NOT the
layer's.  Writes the seconds by kind into
`detail["moe_seconds"]`."""

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "moe_ops")
    got = M.of_run(run)
    if got is None or run["trace_summary"]["busy_s"] <= 0:
        return None
    return 100.0 * sum(got[k] for k in M.KINDS) / run["trace_summary"]["busy_s"]
