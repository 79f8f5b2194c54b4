"""ssd_scan_kernel_pct — the share of the cell's Mamba-2 scan emissions that
took the Pallas kernel pair of ops/pallas_kernels/ssd_scan.py (the program's
counter `ssd_scan_kernels_traced_total{op, path}`, counted when the step is
traced in set-up: once a compile, not once a step): 100 x the `path="pallas"`
sum over the family's.  100 where every emission, forward and re-emitted
under a grad op's vjp (a `layers.recompute` segment's replay), runs with the
chunk's decay tiles and the heads' state in VMEM; 0 where a gate quietly said
no and `ssd_chunked`'s batched tiles went through HBM instead, which explains
an unmoved `ssd_scan_device_ms`; nothing to read where the program has no
such counter (the parent of PR 70) or built no Mamba-2 scan."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"

FAMILY = "ssd_scan_kernels_traced_total"


def read(run):
    from harness import load_module

    counter_sum = load_module("reduce", "program_spans").counter_sum
    every = counter_sum(FAMILY, "path", ("pallas", "xla"))
    if not every:
        return None
    return 100.0 * counter_sum(FAMILY, "path", ("pallas",)) / every
