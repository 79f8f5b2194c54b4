"""ssd_scan_hbm_roofline — the least time the chip could take for the
Mamba-2 scans of the traced window by the bytes they must move, over the
device time of `pdtpu.ssd.scan` (`ssd_scan_device_ms`'s, so the two sides
measure the same work).  The least of one layer a step
(benchmarks/flops_granite.py `ssd_scan_cost`, 'fwd' + 'bwd'): x and y at [T,
d_inner], B and C at [T, groups x d_state] and Delta at [T, heads] once
forward; x, B, C, Delta and dy read and the four gradients written once
backward; bf16, over the HBM peak.  The SAME least whatever implements the
scan, so a later kernel is read by today's yardstick.  Times the 'mamba'
layers of `train.args.layer_types` and the traced steps.  The forward that
a `layers.recompute` segment makes again is NOT in the least: a cell under
recomputation reads a lower share for it, as it pays for it.
`detail["ssd_scan_hbm_roofline"]` holds the chunked products' least on the
MXU beside it (their FLOPs over the bf16 peak: near the bytes' at the
published sizes).  Nothing to read where the arguments name no Mamba-2
layer or the program no such part."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "ssd_device_ms").parts(run)
    args = run["ctx"].config.get("train", {}).get("args", {})
    layers = list(args.get("layer_types", ())).count("mamba")
    if (got is None or got["ssd.scan"] <= 0 or not layers or any(
            not args.get(k) for k in ("mamba_n_heads", "mamba_d_head",
                                      "mamba_d_state"))):
        return None
    rec = run["record"]
    F = load_module(".", "flops_granite")
    by_bytes = by_flops = 0.0
    for kind in ("fwd", "bwd"):
        flops, nbytes = F.ssd_scan_cost(
            rec["batch"], int(args["seq_len"]), int(args["mamba_n_heads"]),
            int(args["mamba_d_head"]), int(args["mamba_d_state"]),
            int(args.get("mamba_n_groups", 1)), kind)
        by_bytes += nbytes / run["peaks"]["hbm_bytes_per_s"]
        by_flops += flops / run["peaks"]["bf16_flops_per_s"]
    run["detail"]["ssd_scan_hbm_roofline"] = {
        "least_ms_a_layer_a_step": 1e3 * by_bytes,
        "mxu_least_ms_a_layer_a_step": 1e3 * by_flops,
        "layers": layers, "device_s": got["ssd.scan"]}
    return (100.0 * by_bytes * layers * rec["traced"]["steps"]
            / got["ssd.scan"])
