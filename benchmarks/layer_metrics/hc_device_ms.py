"""hc_device_ms — device milliseconds a step in the hyper-connections, the
residual path of several streams around every sub-layer (forward and
backward): every instruction the compiled program puts into
`pdtpu.hc.gates` (the norm's statistic, the projection vec(X) Phi, the
gates, the exponential and the Sinkhorn iterations), `pdtpu.hc.read` (the
weighted read of the streams, their sum at the end) or `pdtpu.hc.write`
(the stream mixing and the write), each at its self time
(benchmarks/reduce/part_ms.py).  Where XLA fused some of that into a
neighbouring matrix product (the sub-layer's first or last), what the
instruction takes over the product's own least is in it and the rest is
the product's.  Nothing to read where the program names no such part (the
parent of PR 39) or the trace lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "part_ms")
    got = M.seconds(run, M.under("hc."))
    if got is None or not got["events"]:
        return None
    run["detail"]["hc_device_ms"] = got
    return 1e3 * got["s"] / run["record"]["traced"]["steps"]
