"""kda_scan_device_ms — device milliseconds a step in `pdtpu.kda.scan`
alone: Kimi Delta Attention's chunked emission (the cumulative gates, the
two decayed score matrices, the unit-lower-triangular inverse, U and W, the
chunks' transition matrices, the `lax.scan` over the chunks, the output),
forward, recomputed forward (the scan is a `jax.checkpoint`) and backward,
each event whole at its self time (kda_device_ms.py `parts`).  Nothing to
read where the program names no such part."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "kda_device_ms").parts(run)
    if got is None or got["kda.scan"] <= 0:
        return None
    return 1e3 * got["kda.scan"] / run["record"]["traced"]["steps"]
