"""short_conv_hbm_roofline — the least time the chip could take for the
gated short convolutions of the traced window by the bytes they must move,
over the device time of their instructions (`short_conv_device_ms`'s: all
that the compiled program puts into the op's scopes, and of a matrix
product that some of it was fused into what is over the product's own
least, so the two sides measure the same work).  The least of one layer a
step: forward it reads the projection's [T, 3 x hidden] and writes [T,
hidden]; backward it reads [T, 3 x hidden] again and the output's gradient
and writes the [T, 3 x hidden] gradient (benchmarks/flops_lfm2.py
`short_conv_cost`, 'fwd' + 'bwd': 11 tensors of [T, hidden] in bf16 over
the HBM peak; its FLOPs over the bf16 peak are a hundredth of that, so HBM
binds at any width).  Times the convolution layers of
`train.args.layer_types` and the traced steps."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "share_ops")
    got = M.of_run(run)
    if got is None or got["conv"] <= 0:
        return None
    rec = run["record"]
    args = run["ctx"].config["train"]["args"]
    d = M.dims_of(run["ctx"].config, rec["batch"])
    F = load_module(".", "flops_lfm2")
    least = 0.0
    for kind in ("fwd", "bwd"):
        flops, nbytes = F.short_conv_cost(
            rec["batch"], int(args["seq_len"]), d["dim"], d["conv_kernel"],
            kind)
        seconds, roof = run["flops"].roofline_seconds(flops, nbytes,
                                                      run["peaks"])
        least += seconds
    layers = sum(t == "conv" for t in args["layer_types"])
    run["detail"]["short_conv_hbm_roofline"] = {
        "roof": roof, "least_ms_a_layer_a_step": 1e3 * least,
        "conv_layers": layers, "device_s": got["conv"],
        "instructions": got["conv_events"],
        "in_products": got["conv_products"],
        "in_products_whole_s": got["conv_products_s"]}
    return 100.0 * least * layers * rec["traced"]["steps"] / got["conv"]
