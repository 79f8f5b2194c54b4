"""collective_exposed_ms — per training step, the device milliseconds in
which a collective operation (all-reduce and its kin) ran while no other
operation ran on that chip, averaged over the chips: the part of the
gradient exchange the step could not hide behind compute."""

LAYER = "parallel executor"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    if run["trace"] is None:
        return None
    exposed = run["tracemod"].exposed_collective_seconds(run["trace"])
    return 1e3 * exposed / run["record"]["traced"]["steps"]
