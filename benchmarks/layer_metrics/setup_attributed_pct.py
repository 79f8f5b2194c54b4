"""setup_attributed_pct — the share of `setup_s` that lies under a name:
under any event of the program's start-up record (`process.import`,
`device.init`, the spans of a cold dispatch, JAX's `jax.trace` / `.lower` /
`.backend` / `.cache_load` intervals, ...) or, outside those, under one of
the benchmark's own set-up spans (`bench.startup`, `.stage`, `.reference`,
`.warmup`).  What is left is `preprogram` (process start to the import's
first stamp: `setup_preprogram_s`) and `unnamed`; the three shares make 100.
`detail["setup_timeline_s"]`: wall seconds by INNERMOST name in the order of
first appearance, `unnamed` last; they add up to `setup_s`
(`reduce/startup_record.py`); `detail["setup_unnamed_gaps"]`: the three
longest stretches under no name, with the names on either side.  Nothing to
read where the program keeps no record (the parent of PR 50)."""

LAYER = "process start-up"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    from harness import load_module

    S = load_module("reduce", "startup_record")
    v = S.of_run(run)
    if v is None:
        return None
    table = S.timeline_s(v, run["tracemod"])
    run["detail"]["setup_timeline_s"] = table
    run["detail"]["setup_unnamed_gaps"] = S.unnamed_gaps(v, run["tracemod"])
    named = sum(s for k, s in table.items()
                if k not in (S.PREPROGRAM, S.UNNAMED))
    return 100.0 * named / (v["hi"] - v["lo"])
