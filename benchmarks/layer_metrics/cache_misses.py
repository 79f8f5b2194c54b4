"""cache_misses — executables compiled and written to the persistent
compile cache during set-up (jax.monitoring's
/jax/compilation_cache/cache_misses): 0 in every run after a checkout's
first.  A miss in a warm run means some program's cache key moves between
processes."""

LAYER = "compile cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run["record"]["setup"]["cache_misses"]
