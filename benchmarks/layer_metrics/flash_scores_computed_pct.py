"""flash_scores_computed_pct — of the T x T score squares of the cell's
causal flash kernel calls, the share the kernels' schedule computes: 100 x
`part="computed"` over `part="square"` of the program's counter
`flash_score_elements_total{kernel, part}`, summed over `flash_fwd`,
`flash_bwd_dq` and `flash_bwd_dkv` (counted when the step is traced in
set-up: once a compile, not once a step).  A grid block of the future and,
inside a block the diagonal crosses, what of a strip of q rows lies beyond
its last row's reach are both left out of `computed`.  50 is the causal half and the
`flash_*_roofline` metrics' roof; 100 is the whole square, masked
afterwards, which is what a schedule of one K block a head computes
(`gpt2m_train_bs8` before PR 27; PERF.md).  Nothing to read where the
program has no such counter (the parent of PR 27) or traced no causal
flash kernel."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"

FAMILY = "flash_score_elements_total"


def read(run):
    from harness import load_module

    counter_sum = load_module("reduce", "program_spans").counter_sum
    square = counter_sum(FAMILY, "part", ("square",))
    if not square:
        return None
    return 100.0 * counter_sum(FAMILY, "part", ("computed",)) / square
