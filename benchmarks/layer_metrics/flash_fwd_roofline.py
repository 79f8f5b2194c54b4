"""flash_fwd_roofline — the least time the chip could take for the
`flash_fwd` calls of the traced window (the larger of FLOPs over the bf16
peak and bytes over the HBM peak, from shapes: benchmarks/flops.py
`flash_attention_cost`, times the calls the trace counts) over the device
time the trace gives them.
`kernel_share` serves the two backward kernels' readers too; it notes
which roof binds in the run's `detail`."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def kernel_share(run, kernel: str, kind: str):
    if run["trace"] is None:
        return None
    cfg, rec = run["ctx"].config, run["record"]
    F, T = run["flops"], run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    heads, dim = cfg["n_head"], cfg["n_embd"]
    flops, nbytes = F.flash_attention_cost(
        rec["batch"], heads, cfg["n_positions"], dim // heads, kind)
    least, roof = F.roofline_seconds(flops, nbytes, run["peaks"])
    run["detail"][kernel + "_roofline"] = {
        "roof": roof, "device_s": seconds, "calls": calls,
        "calls_a_layer_a_step": calls / (rec["traced"]["steps"]
                                         * cfg["n_layer"])}
    return 100.0 * least * calls / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
