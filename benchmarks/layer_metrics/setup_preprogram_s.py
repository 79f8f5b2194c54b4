"""setup_preprogram_s — seconds from the process's start (`ctx.t_start`, the
first line of run.py) to the first stamp of the program's start-up record,
the beginning of `process.import`: the interpreter, the benchmark's own
imports, `import jax` and the TPU client's start under `claim_tpu`.  What
neither the program nor a change to it can shorten; run.py opens no span in
it.  Nothing to read where the program keeps no record (the parent of PR
50)."""

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
    began = [e[1] for e in v["events"] if e[0] == S.IMPORT]
    return min(began) - v["lo"] if began else None
