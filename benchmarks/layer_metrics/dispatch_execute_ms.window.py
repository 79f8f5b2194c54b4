"""dispatch_execute_ms.window — median host milliseconds between `t_execute0`
and `t_execute1` of the program's step record over the steady dispatches of
the UNTRACED window: the jitted call, the twin of `dispatch_execute_ms.train`
in the seconds `train_samples_per_s` is measured in.  A call that blocks in
one window and returns at once in the other (ROADMAP S8) shows here from
inside, in both.  `detail["execute_share_of_window"]`: the share of the
window's seconds the dispatching thread spent inside the call (a step that
blocks in it: nearly all; what is left is what a host that must also feed
batches has); `detail["execute_blocked_rows"]`: how many of the window's
calls took more than half a step, and the call's mean beside this median
(a median of 3 ms hides six waits of 250).  None where the program keeps no
step record (the parent of PR 65)."""

LAYER = "executors"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    S = load_module("reduce", "step_record")
    v = S.of_run(run)
    if v is None or not v["rows"]:
        return None
    run["detail"]["execute_share_of_window"] = S.execute_share(v)
    run["detail"]["execute_blocked_rows"] = S.blocked(
        v, run["record"]["window"]["steps"])
    return S.medians_ms(v)["execute"]
