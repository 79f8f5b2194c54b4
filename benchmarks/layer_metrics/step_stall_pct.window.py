"""step_stall_pct.window — how far the UNTRACED window's worst stretch of R
steps lies over its median one, in percent, from the `t_enter` stamps of the
program's step record (R = the traffic file's `loss_read_every`; with e_i the
i-th steady dispatch's `t_enter`, s_i = (e_(i+R) - e_i) / R over every run of
R consecutive dispatches, and the metric is 100 x (max s_i / median s_i - 1)).
One loss read that waits 1.5 s on a 166 ms step at R 8 reads about +110; a
run whose every step is slower reads about 0 with the median off the traced
slice's device step: the two shapes of a wild run (ROADMAP S10(a)) that one
run can tell apart.  `detail["step_series_ms"]`: the s_i (at most 64, evenly
thinned), their median beside `step_device_ms.train`, the worst, the row it
starts at and the row of it after which the host took longest to come back.
None where the window holds fewer than 2 R dispatches, or the program keeps
no step record (the parent of PR 65)."""

LAYER = "executors"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    S = load_module("reduce", "step_record")
    v = S.of_run(run)
    if v is None:
        return None
    found = S.stall(v, int(run["ctx"].traffic["loss_read_every"]))
    if found is None:
        return None
    pct = found.pop("pct")
    if run.get("trace_summary"):
        found["step_device_ms"] = load_module(
            "layer_metrics", "step_device_ms.train").read(run)
    run["detail"]["step_series_ms"] = found
    return pct
