"""moe_permute_device_ms — device milliseconds a step in the expert layer
OUTSIDE its grouped matmul kernels: the argsorts' outputs, the gathers by
the sort and their backward, the SiLU-gate product and the weighted
combine on the token-slot rows, and the relayout copies of the stacked
expert weights: the part no MXU helps (the router's own instructions are
not found by shape: benchmarks/reduce/moe_ops.py).  `detail["moe_seconds"]` has the split by kind."""

LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "moe_ops")
    got = M.of_run(run)
    if got is None:
        return None
    other = sum(got[k] for k in M.KINDS if k != "grouped_matmul")
    return 1e3 * other / run["record"]["traced"]["steps"]
