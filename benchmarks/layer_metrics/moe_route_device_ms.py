"""moe_route_device_ms — device milliseconds a step in the expert layers'
ROUTERS, forward and backward: every instruction the compiled program puts
into `pdtpu.moe.route` (ops/moe_ops.py: the float32 product of the
router's input with the gate, the scores, the top-k choice and the chosen
weights; where the op has a `RouterX` the product is on it, and its
gradient goes back to it) at its self time.  `detail` has what of it is
events that hold a matrix product.  Nothing to read where the program
names no such part or the trace lacks the program's metadata."""

LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics", "attn_window_device_ms").part_ms(
        run, "moe.route", "moe_route_device_ms")
