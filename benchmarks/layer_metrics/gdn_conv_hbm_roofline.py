"""gdn_conv_hbm_roofline — the least time the chip could take for the
gated DeltaNets' depthwise convolutions of the traced window by the bytes
they must move (benchmarks/flops_qwen3next.py `gated_delta_cost`, part
'conv': the projection's q, k and v channels read and written once forward,
read twice, the gradient read and one written backward, in bf16, over the
HBM peak; its FLOPs are a twentieth of that), over the device time of
`pdtpu.gdn.conv` (the taps, SiLU, the l2 norm and the heads' split; an
event fused into a projection by what it takes over the product's least).
See gdn_scan_roofline.py, whose `share` does the arithmetic."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics", "gdn_scan_roofline").share(
        run, "conv", "gdn.conv", "gdn_conv_hbm_roofline")
