"""ssm_scan_hbm_roofline — the least time the chip could take for the
selective scans of the traced window by the bytes they must move, over the
device time of `pdtpu.ssm.scan` (`ssm_scan_device_ms`'s, so the two sides
measure the same work).  The least of one layer a step
(benchmarks/flops_phi4flash.py `selective_scan_cost`, 'fwd' + 'bwd'): u and
Delta read and y written forward, those, dy and the four gradients
backward, [T, d_inner] each at the stated type, B and C [T, d_state]; the
[d_inner, d_state] state never crosses HBM in the least form, whatever emits
the scan (its operations over the bf16 peak are less: HBM binds; `detail`
says which).  Times the configuration's `flops.args.mamba_layers` (the
count `mfu_pct` takes through flops_phi4flash.py; the shapes are the same
entry's `d_inner`, `d_state`, `seq_len`) and the traced steps.  The forward
that a `layers.recompute` segment and the chunks' checkpoints make again is
NOT in the least: the cell reads a lower share for it, as it pays for it.
Nothing to read where the configuration counts no such layer or the program
has no such part."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "ssm_device_ms").parts(run)
    args = run["ctx"].config.get("flops", {}).get("args", {})
    if got is None or got["ssm.scan"] <= 0 or any(
            not args.get(k) for k in ("mamba_layers", "d_inner", "d_state",
                                      "seq_len")):
        return None
    rec = run["record"]
    layers = int(args["mamba_layers"])
    F = load_module(".", "flops_phi4flash")
    least, roofs = 0.0, []
    for kind in ("fwd", "bwd"):
        ops, nbytes = F.selective_scan_cost(
            rec["batch"], int(args["seq_len"]), int(args["d_inner"]),
            int(args["d_state"]), kind)
        seconds, roof = run["flops"].roofline_seconds(ops, nbytes,
                                                      run["peaks"])
        least += seconds
        roofs.append(roof)
    run["detail"]["ssm_scan_hbm_roofline"] = {
        "roofs": roofs, "least_ms_a_layer_a_step": 1e3 * least,
        "layers": layers, "device_s": got["ssm.scan"]}
    return (100.0 * least * layers * rec["traced"]["steps"]
            / got["ssm.scan"])
