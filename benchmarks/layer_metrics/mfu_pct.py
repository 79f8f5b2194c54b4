"""mfu_pct — model FLOP/s utilisation: the forward and backward FLOPs one
sample needs (the configuration's `flops` entry: a function of
benchmarks/flops.py, or of the file its `module` names; no recomputation
counted) times the samples per second of the untraced window,
over the chips used times the chip's published bf16 peak
(benchmarks/peaks.json).  An end-to-end utilisation, not a kernel's
roofline share, and blind to idle time."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(run):
    from harness import flops_per_sample

    rec = run["record"]
    per_sample = flops_per_sample(run["ctx"].config)
    rate = rec["values"]["train_samples_per_s"]
    peak = run["peaks"]["bf16_flops_per_s"] * len(rec["devices"])
    return 100.0 * per_sample * rate / peak
