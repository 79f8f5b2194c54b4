"""swa_gqa_flash_bwd_dq_roofline — the roofline share of ALL the
`flash_bwd_dq` calls of the traced window in a decoder that mixes window
and full-span layers under grouped-query attention: see
swa_gqa_flash_fwd_roofline.py, whose `kernel_share` does the arithmetic
(the least FLOPs and bytes of kind 'bwd_dq' from
benchmarks/flops_smallthinker.py by the live pairs of each layer kind, over
the kernel's device time in the trace)."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics",
                       "swa_gqa_flash_fwd_roofline").kernel_share(
        run, "flash_bwd_dq", "bwd_dq")
