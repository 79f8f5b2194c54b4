"""mfu_active_pct — model FLOP/s utilisation of a mixture-of-experts
configuration, counting the ACTIVE parameters: the forward and backward
FLOPs one sample needs with `num_experts_per_tok` of the experts a token
and the causal half of the scores (benchmarks/flops_moe.py, by the
configuration's `flops_moe` entry; no recomputation counted) times the samples
per second of the untraced window, over the chips used times the chip's
published bf16 peak.  `mfu_pct`'s twin for the cells whose FLOPs function
lives in flops_moe.py; blind to idle time, like it."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    rec = run["record"]
    spec = run["ctx"].config.get("flops_moe")
    if spec is None:
        return None
    fn = getattr(load_module(".", "flops_moe"), spec["function"])
    rate = rec["values"]["train_samples_per_s"]
    peak = run["peaks"]["bf16_flops_per_s"] * len(rec["devices"])
    return 100.0 * fn(**spec["args"]) * rate / peak
