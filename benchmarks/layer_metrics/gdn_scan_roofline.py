"""gdn_scan_roofline — the least time the chip could take for the gated
delta rule of the traced window, the larger of its FLOPs over the bf16 peak
and its bytes over the HBM peak (benchmarks/flops_qwen3next.py
`gated_delta_cost`, part 'scan', 'fwd' + 'bwd': the products a chunked
delta rule cannot do without at the published kernels' chunk of 64,
whatever chunk the program runs; q, k, v, the gates, o and their gradients
once each), over the device time of `pdtpu.gdn.scan` (`gdn_scan_device_ms`'s,
so the two sides measure the same work).  Times the `linear_attention`
layers of `train.args.layer_types` and the traced steps.  The forward that
the scan's own `jax.checkpoint` makes again is NOT in the least, nor are
the emission's extra products (the inverse by squaring, the [Dk, Dk]
transition matrices, float32 at HIGHEST precision): the cell reads a lower
share for them, as it pays for them.  `share` serves the convolution's
reader too.  Nothing to read where the arguments name no such layer or the
program no such part."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def share(run, part: str, scope: str, name: str):
    from harness import load_module

    got = load_module("layer_metrics", "gdn_device_ms").parts(run)
    args = run["ctx"].config.get("train", {}).get("args", {})
    layers = list(args.get("layer_types", ())).count("linear_attention")
    if got is None or got[scope] <= 0 or not layers or any(
            not args.get(k) for k in ("linear_key_heads", "linear_key_dim")):
        return None
    rec = run["record"]
    F = load_module(".", "flops_qwen3next")
    least, roofs = 0.0, []
    for kind in ("fwd", "bwd"):
        flops, nbytes = F.gated_delta_cost(
            rec["batch"], int(args["seq_len"]),
            int(args["linear_key_heads"]), int(args["linear_value_heads"]),
            int(args["linear_key_dim"]), int(args["linear_value_dim"]),
            int(args["conv_kernel"]), part, kind)
        seconds, roof = run["flops"].roofline_seconds(flops, nbytes,
                                                      run["peaks"])
        least += seconds
        roofs.append(roof)
    run["detail"][name] = {
        "roofs": roofs, "least_ms_a_layer_a_step": 1e3 * least,
        "layers": layers, "device_s": got[scope]}
    return 100.0 * least * layers * rec["traced"]["steps"] / got[scope]


def read(run):
    return share(run, "scan", "gdn.scan", "gdn_scan_roofline")
