"""moe_share_device_pct — the held experts' share of the traced window's
device-busy time, forward and backward: the grouped matmul kernels, every
instruction on the buffer's rows (the gather into it, the SiLU-gate
product, the weighting, the scatter-add back) and every instruction on the
tokens x top_k pairs (the sort by held expert, the counts);
benchmarks/reduce/moe_share_ops.py finds them by the shapes in the
instruction text and says why the router's own instructions are not among
them.  The shared expert is NOT in it (`moe_shared_expert_device_ms`), nor
the optimizer's update of the expert weights.  Writes the seconds by kind
into `detail["moe_share_seconds"]`."""

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "moe_share_ops")
    got = M.of_run(run)
    if got is None or run["trace_summary"]["busy_s"] <= 0:
        return None
    return (100.0 * sum(got[k] for k in M.LAYER_KINDS)
            / run["trace_summary"]["busy_s"])
