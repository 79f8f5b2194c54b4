"""setup_cold_dispatch_s — wall seconds of set-up inside the program's cold
`executor.run` roots: the dispatches that found no executable (the startup
program's and the training step's first, as a rule) from the moment they
found that out to their end.  `detail["setup_cold_dispatches"]`: a row a
root with `role`, `program`, `step`, `seconds`, its children `build`,
`donate`, `rng`, `execute`, `writeback`, `fetch`, the cold
`executor.distribute` before it (ParallelExecutor), and under `execute` the
wall of `jax.trace` / `jax.lower` / `jax.backend` / `jax.cache_load`
(`execute_phases`) and what is left, the launch (`execute_launch`).  Nothing
to read where the program keeps no record (the parent of PR 50)."""

LAYER = "executors"
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
    T = run["tracemod"]
    run["detail"]["setup_cold_dispatches"] = S.root_rows(v, T)
    return T.total([[a, b] for _, a, b, _ in S.cold_roots(v)])
