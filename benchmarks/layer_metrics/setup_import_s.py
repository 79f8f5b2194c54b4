"""setup_import_s — seconds of `process.import`, the program's own stamp pair
around the import of `paddle_tpu`, first line to last (`jax` is imported
before it under run.py, so this is the package's own Python and what it
imports beside JAX).  Nothing to read where the program keeps no record (the
parent of PR 50)."""

LAYER = "process start-up"
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
    found = [e for e in v["events"] if e[0] == S.IMPORT]
    return sum(e[2] - e[1] for e in found) if found else None
