"""bd_live_tile_pct — of the score elements the flash kernels COMPUTE under
the block-diffusion mask, the share the mask keeps: 100 x live over
`part="computed"` of the program's counter
`flash_score_elements_total{kernel, part}`, summed over the three kernels
(counted when the step is traced in set-up).  Live is the counter's
`part="square"`, B H (2L)^2 a call, times (L^2 + L b) / (2L)^2 from the
configuration's `train.args` (`seq_len` L, `block_length` b;
benchmarks/flops_sdar.py `bd_live_scores`).  100 would be a schedule that
computes no masked element; what is lost is the part of a strip beyond a
staircase's reach and the 128-wide squares the block diagonal's 4-wide
blocks sit in.  `flash_scores_computed_pct` is computed over the square;
this is live over computed.  Nothing to read where the arguments name no
block length or the program has no such counter."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"

FAMILY = "flash_score_elements_total"


def read(run):
    from harness import load_module

    args = run["ctx"].config.get("train", {}).get("args", {})
    if not args.get("block_length") or not args.get("seq_len"):
        return None
    counter_sum = load_module("reduce", "program_spans").counter_sum
    square = counter_sum(FAMILY, "part", ("square",))
    computed = counter_sum(FAMILY, "part", ("computed",))
    if not square or not computed:
        return None
    L = int(args["seq_len"])
    live = load_module(".", "flops_sdar").bd_live_scores(
        L, int(args["block_length"]))
    return 100.0 * square * live / (4.0 * L * L) / computed
