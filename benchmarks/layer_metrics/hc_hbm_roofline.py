"""hc_hbm_roofline — the least time the chip could take for the
hyper-connections of the traced window by the bytes they must move, over
the device time of their instructions (`hc_device_ms`'s, so the two sides
measure the same work).  The least of one sub-layer a step
(benchmarks/flops_xing.py `hyper_connection_cost`, 'fwd' + 'bwd'): forward
it reads the n streams and the sub-layer's result and writes the
sub-layer's input and the n new streams; backward it reads the streams,
the result and the gradients of both outputs and writes the gradients of
the streams and of the result: 5 n + 5 tensors of [T, hidden] in bf16
over the HBM peak, each moved ONCE (its FLOPs over the bf16 peak are a
twentieth of that, so HBM binds at any width).  Times the sub-layers,
two a block of `train.args.layer_types` (the module's block is its last),
and the traced steps.  XLA's passes read the streams more than once, and
the gradient arrives in float32 where a product's epilogue wrote it: that
is what the share is there to show."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "part_ms")
    got = M.seconds(run, M.under("hc."))
    args = run["ctx"].config["train"]["args"]
    if (got is None or got["s"] <= 0 or not args.get("hc_streams")
            or not args.get("layer_types")):
        return None
    rec = run["record"]
    F = load_module(".", "flops_xing")
    least = 0.0
    for kind in ("fwd", "bwd"):
        flops, nbytes = F.hyper_connection_cost(
            rec["batch"], int(args["seq_len"]), int(args["dim"]),
            int(args["hc_streams"]), kind)
        seconds, roof = run["flops"].roofline_seconds(flops, nbytes,
                                                      run["peaks"])
        least += seconds
    sublayers = 2 * len(args["layer_types"])
    run["detail"]["hc_hbm_roofline"] = {
        "roof": roof, "least_ms_a_sublayer_a_step": 1e3 * least,
        "sublayers": sublayers, "device_s": got["s"]}
    return 100.0 * least * sublayers * rec["traced"]["steps"] / got["s"]
