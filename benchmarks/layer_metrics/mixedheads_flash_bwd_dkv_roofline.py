"""mixedheads_flash_bwd_dkv_roofline — the roofline share of ALL the
`flash_bwd_dkv` calls of the traced window in a decoder whose window and
full-span layers differ in their query head count: see
mixedheads_flash_fwd_roofline.py, whose `kernel_share` does the arithmetic
(the least FLOPs and bytes of kind 'bwd_dkv' from
benchmarks/flops_laguna.py by the live pairs, heads and group of each layer
kind, over the kernel's device time in the trace)."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics",
                       "mixedheads_flash_fwd_roofline").kernel_share(
        run, "flash_bwd_dkv", "bwd_dkv")
