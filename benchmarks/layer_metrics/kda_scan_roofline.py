"""kda_scan_roofline — the least time the chip could take for Kimi Delta
Attention's delta rule of the traced window, the larger of its FLOPs over
the bf16 peak and its bytes over the HBM peak (benchmarks/flops_kimi.py
`kda_cost`, part 'scan', 'fwd' + 'bwd': the products a chunked delta rule
cannot do without at the published kernels' chunk of 64, whatever chunk the
program runs; q, k, v, the gates, o and their gradients once each), over
the device time of `pdtpu.kda.scan` (`kda_scan_device_ms`'s, so the two
sides measure the same work).  Times the 'kda' layers of
`train.args.layer_types` and the traced steps.  The forward that the scan's
own `jax.checkpoint` makes again is NOT in the least, nor are the
emission's extra products and its pairwise decays: the cell reads a lower
share for them, as it pays for them, and the share cannot pass 100.  The
yardstick a kernel pair for the scan will be read by.  `share` serves the
gates' reader too.  Nothing to read where the arguments name no such layer
or the program no such part."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def share(run, part: str, scope: str, name: str):
    from harness import load_module

    got = load_module("layer_metrics", "kda_device_ms").parts(run)
    args = run["ctx"].config.get("train", {}).get("args", {})
    layers = list(args.get("layer_types", ())).count("kda")
    if got is None or got[scope] <= 0 or not layers or any(
            not args.get(k) for k in ("linear_heads", "linear_head_dim",
                                      "seq_len")):
        return None
    rec = run["record"]
    F = load_module(".", "flops_kimi")
    least, roofs = 0.0, []
    for kind in ("fwd", "bwd"):
        flops, nbytes = F.kda_cost(
            rec["batch"], int(args["seq_len"]), int(args["linear_heads"]),
            int(args["linear_head_dim"]), part, kind)
        seconds, roof = run["flops"].roofline_seconds(flops, nbytes,
                                                      run["peaks"])
        least += seconds
        roofs.append(roof)
    run["detail"][name] = {
        "roofs": roofs, "least_ms_a_layer_a_step": 1e3 * least,
        "layers": layers, "device_s": got[scope]}
    return 100.0 * least * layers * rec["traced"]["steps"] / got[scope]


def read(run):
    return share(run, "scan", "kda.scan", "kda_scan_roofline")
