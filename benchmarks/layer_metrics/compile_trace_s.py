"""compile_trace_s — Python's share of set-up's compile: the seconds JAX
spent tracing the program's step functions and lowering them to MLIR inside
Executor.run (the program's counter `executor_compile_seconds_total`, phases
`trace` + `lower`; a nested jit's tracing counts twice, as in `compile_s`).
The part only the program can shorten.  `compile_s` less this and
`compile_backend_s` is what the process compiled outside the executor: the
float32 reference and the benchmark's own jits."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    from harness import load_module

    return load_module("reduce", "program_spans").counter_sum(
        "executor_compile_seconds_total", "phase", ('trace', 'lower'))
