"""optimizer_fused_roofline — the least time the chip could take for the
events that hold the optimizer's update, over their device time
(`optimizer_fused_device_ms`'s events, whole).  The least is the larger of
two lower bounds of the SAME events' time: the bytes no fusion can avoid
(`param` + `state` of the program's counter `optimizer_update_bytes_total`:
20 a parameter for bf16 weights under Adam with float32 moments) over the
HBM peak, and the FLOPs of the matrix products XLA fused in beside the
update over the bf16 peak.  Neither is taken off the time (the products'
least can exceed what is left over the bytes'), so the share cannot pass
100 whatever XLA overlaps.  `detail["bound"]`: `hbm` or `mxu`."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "optimizer_fused_device_ms").of_run(run)
    if got is None or got["ms"] <= 0:
        return None
    least = max(got["hbm_least_ms"], got["product_least_ms"])
    run["detail"]["optimizer_fused_roofline"] = {
        "bound": "hbm" if got["hbm_least_ms"] >= got["product_least_ms"]
        else "mxu",
        "hbm_least_ms": got["hbm_least_ms"],
        "product_least_ms": got["product_least_ms"],
        "device_ms": got["ms"]}
    return 100.0 * least / got["ms"]
