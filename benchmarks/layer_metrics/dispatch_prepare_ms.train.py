"""dispatch_prepare_ms.train — median host milliseconds one Executor.run
call of the traced slice spends in `pdtpu.executor.prepare`: feed
preparation, the autotune winner lookup, the cache key, the load-file
signature and the executable-cache lookup (and `executor.build`, the desc
analysis, when the key is new).  From the program's own spans in the
profiler trace (reduce/program_spans.py); None where it has none."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("reduce", "program_spans").child_ms(
        run, "executor.prepare")
