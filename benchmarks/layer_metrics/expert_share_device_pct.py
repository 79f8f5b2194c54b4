"""expert_share_device_pct — the held experts' share of the traced window's
device-busy time, forward and backward: the grouped matmul kernels, every
instruction on the buffer's rows (the gather into it, the SiLU-gate
product, the weighting, the scatter-add back) and every instruction on the
tokens x top_k pairs (the sort by held expert, the counts).
`moe_share_device_pct`'s twin with the shapes from `train.args`
(benchmarks/reduce/share_ops.py), for any share; the optimizer's update of
the expert weights is not in it.  Writes the seconds by kind into
`detail["share_seconds"]`."""

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("reduce", "share_ops").of_run(run)
    if got is None or run["trace_summary"]["busy_s"] <= 0:
        return None
    kinds = load_module("reduce", "moe_share_ops").LAYER_KINDS
    return (100.0 * sum(got[k] for k in kinds)
            / run["trace_summary"]["busy_s"])
