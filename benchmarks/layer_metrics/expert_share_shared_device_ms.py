"""expert_share_shared_device_ms — device milliseconds a step in the share's
shared expert, which every token passes whatever the routing, forward and
backward: the instructions on [tokens, shared width] (its three matrix
products, their six backward products and the SiLU-gate product), a shape
nothing else in the step has.  `moe_shared_expert_device_ms`'s twin with
the shapes from `train.args` (benchmarks/reduce/share_ops.py, kind
`shared`: `shared_experts` x `expert_dim` columns), for a share whose
builder names them so; `expert_share_device_pct` leaves this time out (it
is no held expert's).  Nothing to read where the configuration holds no
share or no shared expert, or the trace no grouped kernel."""

LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("reduce", "share_ops").of_run(run)
    if got is None or not got["shared"]:
        return None
    return 1e3 * got["shared"] / run["record"]["traced"]["steps"]
