"""dispatch_execute_ms.train — median host milliseconds one Executor.run
call of the traced slice spends in `pdtpu.executor.execute`: the jitted
call, which is argument flattening and the launch (and, in a program's first
call, JAX's trace, lowering and compile; none in a window that is
`correct`).  From the program's own spans in the profiler trace
(reduce/program_spans.py); None where it has none."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("reduce", "program_spans").child_ms(
        run, "executor.execute")
