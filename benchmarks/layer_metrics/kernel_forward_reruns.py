"""kernel_forward_reruns — grad ops of the cell's step whose re-emitted
forward launched a Pallas kernel's forward a second time, where the forward
op had run it already (the program's counter
`executor_grad_kernel_forward_total`, series `reused="0"`, counted when the
step is traced in set-up: once a compile, not once a step).  A Mosaic call
is opaque to XLA's CSE, so each one is a whole kernel's time in every step:
`flash_fwd` 0.90 ms a layer in `gpt2m_train_bs8` (PERF.md, PR 25).  0 when
every grad op took its forward op's kept results; nothing to read where the
program has no such counter (the parent of PR 25) or no grad op took a
kernel path."""

LAYER = "model step"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("reduce", "program_spans").counter_sum(
        "executor_grad_kernel_forward_total", "reused", ("0",))
