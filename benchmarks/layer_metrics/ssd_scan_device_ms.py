"""ssd_scan_device_ms — device milliseconds a step in `pdtpu.ssd.scan`
alone: the Mamba-2 scan's emission (the cumulative log-decays, C B^T, the
decayed [Q, Q] tiles and their product with x, the chunks' summaries, the
`lax.scan` of the float32 state over the chunks, the read-out, the D term),
forward, the segment's recomputed forward and backward, each event whole at
its self time (ssd_device_ms.py `parts`).  Nothing to read where the
program names no such part."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "ssd_device_ms").parts(run)
    if got is None or got["ssd.scan"] <= 0:
        return None
    return 1e3 * got["ssd.scan"] / run["record"]["traced"]["steps"]
