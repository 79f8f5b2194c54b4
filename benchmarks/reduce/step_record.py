"""The measured window from inside the program: its step record laid over
the UNTRACED seconds `train_samples_per_s` comes from.

Since PR 65 `paddle_tpu.observability.TRACER.step_rows()` holds one row a
dispatch of an executor whatever is switched on: `step`, `k`, `program`,
`cold`, and absolute stamps of `time.monotonic` (the clock of
`harness.monotime`, so a row lies beside `record["window"]` by
subtraction): `t_enter` / `t_exit` where the root `executor.run` opens and
closes, `t_execute0` / `t_execute1` around the jitted call where
`executor.execute` stands, and under `ParallelExecutor` `t_distribute0` /
`t_distribute1` around what `run` does BEFORE the root (None elsewhere).
The program's spans exist only inside a profiler session, so this is all
the program says of the window that is judged; `program_spans.py` reads
the same dispatches in the traced slice after it.

`view` turns the rows and the window into a plain structure (lists and
numbers, so a small one can sit in the repository as JSON and a test can
do the arithmetic by hand):

    {"t0": window start, "t1": window end,
     "rows": [[step, k, program, cold, t_enter, t_execute0, t_execute1,
               t_exit, t_distribute0, t_distribute1], ...]}

the STEADY rows that lie whole inside [t0, t1] (`run`'s first stamp to
its last, as `Spans.durations` clips the benchmark's own span), in time
order; a cold row and one a raising dispatch left are dropped.  A program
that keeps no record (the parent of PR 65) gives no view, and every reader
then returns None.
"""

from __future__ import annotations

FIELDS = ("step", "k", "program", "cold", "t_enter", "t_execute0",
          "t_execute1", "t_exit", "t_distribute0", "t_distribute1")
_COLD, _ENTER, _X0, _X1, _EXIT, _D0, _D1 = range(3, 10)
PHASES = ("root", "execute", "before_execute", "after_execute",
          "distribute")
SERIES = 64  # a series in `detail` is thinned to so many values


def program_rows():
    """The program's step record as [[step, k, ...], ...] in the order of
    FIELDS; None where the program keeps none."""
    from paddle_tpu.observability import TRACER

    get = getattr(TRACER, "step_rows", None)
    if get is None:
        return None
    return [[r[f] for f in FIELDS] for r in get()]


def view(rows, window: dict) -> dict:
    """The structure at the top of this file, from the program's rows and
    the driver's `record["window"]`."""
    t0, t1 = window["t0"], window["t1"]
    kept = [list(r) for r in rows
            if not r[_COLD] and r[_X0] is not None and r[_X1] is not None
            and (r[_D0] if r[_D0] is not None else r[_ENTER]) >= t0
            and r[_EXIT] <= t1]
    return {"t0": t0, "t1": t1, "rows": sorted(kept,
                                               key=lambda r: r[_ENTER])}


def of_run(run):
    """`view` for a reader, made once a run; None without a record."""
    if "step_view" not in run:
        rows = program_rows()
        run["step_view"] = None if rows is None else view(
            rows, run["record"]["window"])
    return run["step_view"]


def phases_ms(v: dict) -> dict:
    """{phase: [milliseconds, one a row]}: the root, the jitted call, the
    root before and after it, and (rows that have one) what ran before
    the root."""
    rows = v["rows"]
    out = {
        "root": [r[_EXIT] - r[_ENTER] for r in rows],
        "execute": [r[_X1] - r[_X0] for r in rows],
        "before_execute": [r[_X0] - r[_ENTER] for r in rows],
        "after_execute": [r[_EXIT] - r[_X1] for r in rows],
        "distribute": [r[_D1] - r[_D0] for r in rows
                       if r[_D0] is not None]}
    return {k: [1e3 * x for x in xs] for k, xs in out.items()}


def medians_ms(v: dict) -> dict:
    """{phase: median milliseconds} over the view's rows; a phase no row
    has is left out."""
    from harness import median

    return {k: median(xs) for k, xs in phases_ms(v).items() if xs}


def execute_share(v: dict) -> float:
    """The share of the window's seconds the dispatching thread spent
    inside the jitted call."""
    return sum(phases_ms(v)["execute"]) / 1e3 / (v["t1"] - v["t0"])


def blocked(v: dict, steps: int) -> dict:
    """The rows whose jitted call took more than half a step of the window
    (its seconds over the driver's count of its steps): a call that WAITED
    for the device, as against one that launched and came back.  `rows` of
    `of`, the bar `over_ms`, and the call's mean beside its median (a few
    waits of a whole step lift the mean and leave the median)."""
    execute = phases_ms(v)["execute"]
    over = 1e3 * (v["t1"] - v["t0"]) / steps / 2
    return {"rows": sum(1 for x in execute if x > over), "of": len(execute),
            "over_ms": over, "mean_ms": sum(execute) / len(execute)}


def traced_medians_ms(spans: dict, P) -> dict:
    """The same phases from the program's spans in the traced slice (`P`
    is reduce/program_spans.py): the root, its child `executor.execute`,
    the root before and after that child, and `executor.distribute`
    beside the root."""
    from harness import median

    per: dict = {k: [] for k in PHASES[:4]}
    for root in P.roots(spans):
        per["root"].append(root["dur"])
        for c in root["children"]:
            if c["name"] == P.PREFIX + "executor.execute":
                per["execute"].append(c["dur"])
                per["before_execute"].append(c["start"] - root["start"])
                per["after_execute"].append(
                    root["start"] + root["dur"] - c["start"] - c["dur"])
                break
    out = {k: median(xs) / 1e6 for k, xs in per.items() if xs}
    beside = P.top_level_ms(spans).get(P.PREFIX + "executor.distribute")
    if beside is not None:
        out["distribute"] = beside
    return out


def window_table(v: dict, spans, P) -> dict:
    """`detail["executor_window_ms"]`: the medians of the untraced window
    beside the traced slice's of the same dispatches, and traced less
    untraced: what a profiler session costs each phase of a dispatch."""
    table = {"rows": len(v["rows"]), "untraced": medians_ms(v)}
    if spans:
        traced = traced_medians_ms(spans, P)
        table["traced"] = traced
        table["session_costs"] = {
            k: traced[k] - x for k, x in table["untraced"].items()
            if k in traced}
    return table


def thinned(xs: list, n: int = SERIES) -> list:
    """At most `n` of `xs`, evenly spaced, the first and the last kept."""
    if len(xs) <= n:
        return list(xs)
    return [xs[round(i * (len(xs) - 1) / (n - 1))] for i in range(n)]


def stall(v: dict, r: int):
    """How far the window's worst stretch of `r` steps lies over its
    median one.  With e_i the `t_enter` of the i-th row,
    s_i = (e_(i+r) - e_i) / r over every run of `r` consecutive
    dispatches: whatever the phase of the host's run-ahead, `r`
    dispatches of a closed loop that reads the loss every `r`-th step
    span `r` steps.  -> {"pct": 100 (max s / median s - 1), "median_ms",
    "worst_ms", "worst_row": the i of the largest s_i, "worst_gap_row":
    the row of that run after which the host took longest to come back,
    "series_ms": the s_i thinned}; None where the window holds fewer than
    2 r rows."""
    from harness import median

    enter = [row[_ENTER] for row in v["rows"]]
    if r < 1 or len(enter) < 2 * r:
        return None
    s = [(enter[i + r] - enter[i]) / r for i in range(len(enter) - r)]
    mid = median(s)
    worst = max(range(len(s)), key=s.__getitem__)
    gap = max(range(worst, worst + r),
              key=lambda i: enter[i + 1] - enter[i])
    return {"pct": 100.0 * (s[worst] / mid - 1.0), "median_ms": 1e3 * mid,
            "worst_ms": 1e3 * s[worst], "worst_row": worst,
            "worst_gap_row": gap, "runs": len(s),
            "series_ms": [1e3 * x for x in thinned(s)]}
