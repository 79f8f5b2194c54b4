"""device_idle_pct.train — the share of the traced window in which no
operation ran on a chip (1 minus busy over window, averaged over the
chips), training cells.  `breakdown.idle_gaps` says what the host was doing
in the gaps."""

LAYER = "XLA + device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    s = run["trace_summary"]
    return None if s is None else 100.0 * (1.0 - s["busy_s"] / s["window_s"])
