"""setup_compile_wall_s — wall seconds of set-up inside JAX's trace, lower,
backend-compile and cache-load intervals of the program's cold dispatches:
the UNION of the start-up record's `jax.*` events inside the cold
`executor.run` roots, so nothing is counted twice and it never passes
`setup_cold_dispatch_s`.  The wall-clock twin of `compile_trace_s` +
`compile_backend_s`, which are sums of durations.
`detail["setup_compile_wall"]`: `by_phase` (the innermost phase over each
stretch: a cache load lies inside its `jax.backend`) and `by_function`, the
five largest `<fun_name> <role> <program>`.  Nothing to read where the
program keeps no record (the parent of PR 50)."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    from harness import load_module

    S = load_module("reduce", "startup_record")
    v = S.of_run(run)
    if v is None:
        return None
    found = S.compile_wall(v, run["tracemod"])
    run["detail"]["setup_compile_wall"] = found
    return found.pop("seconds")
