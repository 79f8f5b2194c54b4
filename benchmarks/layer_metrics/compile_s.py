"""compile_s — seconds JAX spent tracing, lowering and compiling (or
fetching from the persistent cache) during set-up, from jax.monitoring's
duration events.  Cold it is the compile; warm it is tracing plus loading
executables, the floor of `setup_s` that only the program can lower."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run["record"]["setup"]["compile_s"]
