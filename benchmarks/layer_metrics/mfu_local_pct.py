"""mfu_local_pct — model FLOP/s utilisation of a configuration run as one
chip's share of an expert-parallel deployment, counting the FLOPs THIS chip
does a sample: its held experts by the pairs even routing puts on them,
the shared expert, latent attention with the causal half of the scores,
the dense layer and the head over its slice of the vocabulary
(benchmarks/flops_mla.py, by the configuration's `flops_mla` entry; no
recomputation counted), times the samples per second of the untraced
window, over the chips used times the chip's published bf16 peak.
`mfu_pct`'s twin for the cells whose FLOPs function lives in flops_mla.py;
blind to idle time, like it.  `mfu_pct` must NOT list such a cell: it
reads the configuration's `flops` entry, which for a share is a dense
stand-in at the nearest shape (every configuration has to name a function
of flops.py) and counts neither two head widths nor the held experts."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    rec = run["record"]
    spec = run["ctx"].config.get("flops_mla")
    if spec is None:
        return None
    fn = getattr(load_module(".", "flops_mla"), spec["function"])
    rate = rec["values"]["train_samples_per_s"]
    peak = run["peaks"]["bf16_flops_per_s"] * len(rec["devices"])
    return 100.0 * fn(**spec["args"]) * rate / peak
