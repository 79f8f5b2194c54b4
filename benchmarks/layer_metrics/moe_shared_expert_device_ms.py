"""moe_shared_expert_device_ms — device milliseconds a step in the shared
expert every token passes, forward and backward: the instructions on
[tokens, shared width] (its three matrix products, their six backward
products and the SiLU-gate product), a shape nothing else in the step has
(benchmarks/reduce/moe_share_ops.py, kind `shared`).  The dense work an
expert layer does whatever the routing."""

LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("reduce", "moe_share_ops").of_run(run)
    if got is None:
        return None
    return 1e3 * got["shared"] / run["record"]["traced"]["steps"]
