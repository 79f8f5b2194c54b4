"""dispatch_donate_ms.train — median host milliseconds one Executor.run
call of the traced slice spends in `pdtpu.executor.donate`: `_pin_state`
finding every donated and read-only state array in the scope (and pinning
host arrays to the device).  From the program's own spans in the profiler
trace (reduce/program_spans.py); None where it has none."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("reduce", "program_spans").child_ms(
        run, "executor.donate")
