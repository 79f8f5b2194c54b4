"""ssm_conv_kernel_pct — the share of the cell's emissions of the Mamba
mixers' short convolution (the op `causal_conv_silu`) that took the Pallas
kernel pair of ops/pallas_kernels/ssm_conv.py (the program's counter
`causal_conv_silu_kernels_traced_total{op, path}`, counted when the step is
traced in set-up: once a compile, not once a step): 100 x the `path="pallas"`
sum over the family's.  100 where every emission, forward and re-emitted
under a grad op's vjp (a `layers.recompute` segment's replay), reads xBC (or
u') where the input projection wrote it and writes each section once; 0 where
a gate quietly said no and XLA's float32 tap loop ran instead, which explains
an unmoved `ssd_conv_norm_device_ms` or `ssm_device_ms`; nothing to read
where the program has no such counter (the parent of PR 72) or built no such
layer."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"

FAMILY = "causal_conv_silu_kernels_traced_total"


def read(run):
    from harness import load_module

    counter_sum = load_module("reduce", "program_spans").counter_sum
    every = counter_sum(FAMILY, "path", ("pallas", "xla"))
    if not every:
        return None
    return 100.0 * counter_sum(FAMILY, "path", ("pallas",)) / every
