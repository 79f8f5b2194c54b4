"""compile_backend_s — XLA's share of set-up's compile: the seconds of JAX's
backend compile inside Executor.run (the program's counter
`executor_compile_seconds_total`, phase `backend`), which in a warm checkout
is the retrieval of the executables from the persistent cache."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    from harness import load_module

    return load_module("reduce", "program_spans").counter_sum(
        "executor_compile_seconds_total", "phase", ('backend',))
