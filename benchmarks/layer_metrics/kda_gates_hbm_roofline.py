"""kda_gates_hbm_roofline — the least time the chip could take for the
Kimi-Delta-Attention layers' gates of the traced window by the bytes they
must move (benchmarks/flops_kimi.py `kda_cost`, part 'gates': the decay's
and beta's projections read and the float32 log-decay g [T, H D] and beta
written forward, their gradients read, the projections read again and the
projections' gradients written backward, over the HBM peak; its FLOPs are
far under that), over the device time of `pdtpu.kda.gates` (the softplus,
A_log, dt_bias, the sigmoid; an event fused into a projection by what it
takes over the product's least).  The float32 [T, H D] gate is the one
tensor a scalar-gated DeltaNet layer does not have.  See
kda_scan_roofline.py, whose `share` does the arithmetic."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics", "kda_scan_roofline").share(
        run, "gates", "kda.gates", "kda_gates_hbm_roofline")
