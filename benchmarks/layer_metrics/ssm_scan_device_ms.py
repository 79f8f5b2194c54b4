"""ssm_scan_device_ms — device milliseconds a step in `pdtpu.ssm.scan`
alone: the selective scan's emission (the `lax.scan` over the chunks of
tokens, a chunk's per-token updates and read-outs, the D term), forward, the
segment's recomputed forward, the chunks' own recomputation and the
backward, each event at its self time (ssm_device_ms.py `parts`).  Nothing
to read where the program names no such part."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "ssm_device_ms").parts(run)
    if got is None or got["ssm.scan"] <= 0:
        return None
    return 1e3 * got["ssm.scan"] / run["record"]["traced"]["steps"]
