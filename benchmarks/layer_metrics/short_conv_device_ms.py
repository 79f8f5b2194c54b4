"""short_conv_device_ms — device milliseconds a step in the gated short
convolutions, forward and backward, without their projections: every
instruction the compiled program puts into the op's two scopes
(benchmarks/reduce/share_ops.py, `classify_conv`): the widening of the
input, the two gates, the taps and their gradients.  Where XLA fused some
of that into a neighbouring matrix product, what the instruction takes
over the product's own least (its FLOPs over the bf16 peak) is in it, and
the rest is the product's."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("reduce", "share_ops").of_run(run)
    if got is None or not got["conv_events"]:
        return None
    return 1e3 * got["conv"] / run["record"]["traced"]["steps"]
