"""ssd_conv_norm_device_ms — device milliseconds a step in `pdtpu.ssd.conv`
and `pdtpu.ssd.norm` together: the two HBM-bound passes on either side of
the Mamba-2 scan (four taps, bias and SiLU over the [T, 4352] x, B and C
columns; the gate y * SiLU(z) and the RMSNorm over [T, 4096]), forward, the
segment's recomputed forward and backward, at self time; what XLA fused of
them into a projection counts by what the event takes over the product's
own least (ssd_device_ms.py `parts`).  What a later fusion with the scan's
kernel would take.  Nothing to read where the program names no such part."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "ssd_device_ms").parts(run)
    if got is None or got["ssd.conv"] + got["ssd.norm"] <= 0:
        return None
    return 1e3 * (got["ssd.conv"] + got["ssd.norm"]) / run["record"][
        "traced"]["steps"]
