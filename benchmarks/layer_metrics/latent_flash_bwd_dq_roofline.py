"""latent_flash_bwd_dq_roofline — the roofline share of the
`flash_bwd_dq` kernel in the traced window at two head widths, every
shape from `train.args`' own names: see latent_flash_fwd_roofline.py, whose
`kernel_share` does the arithmetic (FLOPs and bytes of kind 'bwd_dq' from
benchmarks/flops_mla.py over the kernel's device time in the trace)."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics",
                       "latent_flash_fwd_roofline").kernel_share(
        run, "flash_bwd_dq", "bwd_dq")
