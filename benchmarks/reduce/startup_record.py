"""Set-up as a timeline: the program's start-up record laid over
[`ctx.t_start`, `ctx.t_start` + `setup_s`] with the benchmark's own set-up
spans beneath it.

Since PR 50 `paddle_tpu.observability.TRACER.startup_events()` holds the
spans of the program's cold path whatever is switched on: `process.import`
(the package's import), `device.init`, `executor.cache_enable`,
`parallel.mesh`, `parallel.plan`, a cold `executor.distribute`, and for
every dispatch that found no executable a root `executor.run` (`role`
`startup` / `main`, `program`, `step`, `k`) over `executor.build`,
`.donate`, `.rng`, `.execute`, `.writeback`, `.fetch`, with JAX's own phases
as intervals `jax.trace` / `jax.lower` / `jax.backend` / `jax.cache_load`
(`fun_name`) where they ran.  An event is {name, t0, t1, args}: `t0` and `t1`
absolute seconds of `time.monotonic`, the clock of `harness.monotime` and of
run.py's `T_START`, so the record lies beside `ctx.spans.times` by
subtraction.

`view` turns the two into a plain structure (lists, strings and numbers, so
a small one can sit in the repository as JSON and a test can do the
arithmetic by hand):

    {"lo": t_start, "hi": t_start + setup_s,
     "events": [[name, t0, t1, args], ...],      # the program's record
     "beneath": [[name, t0, t1], ...]}           # bench.startup, .stage, ...

`pieces` cuts [lo, hi) into stretches labelled by the INNERMOST name over
each: events are painted in the order (start, longest first), each over
what lies under it, on the benchmark's four spans, on `preprogram` (lo to
the import's first stamp), on `unnamed`.  The interval arithmetic is
`reduce/trace.py`'s.  A program that keeps no record (the parent of PR 50)
gives no view, and every reader then returns None.
"""

from __future__ import annotations

BENEATH = ("startup", "stage", "reference", "warmup")
IMPORT = "process.import"
ROOT = "executor.run"
DISTRIBUTE = "executor.distribute"
CHILDREN = ("build", "donate", "rng", "execute", "writeback", "fetch")
PHASES = ("jax.trace", "jax.lower", "jax.backend", "jax.cache_load")
PREPROGRAM = "preprogram"
UNNAMED = "unnamed"


def program_record():
    """The program's start-up record as [[name, t0, t1, args], ...]; None
    where the program keeps none."""
    from paddle_tpu.observability import TRACER

    get = getattr(TRACER, "startup_events", None)
    if get is None:
        return None
    return [[e["name"], e["t0"], e["t1"], dict(e["args"])] for e in get()]


def view(events, times: dict, t_start: float, setup_s: float) -> dict:
    """The structure at the top of this file, from the program's record
    and the benchmark's `ctx.spans.times`; events wholly outside set-up
    are dropped, none is cut (clipping is `pieces`' business)."""
    lo, hi = t_start, t_start + setup_s
    return {
        "lo": lo, "hi": hi,
        "events": [list(e) for e in events if e[2] > lo and e[1] < hi],
        "beneath": [["bench." + n, a, b] for n in BENEATH
                    for a, b in times.get(n, ()) if b > lo and a < hi]}


def of_run(run):
    """`view` for a reader, made once a run; None without a record."""
    if "startup_view" not in run:
        events = program_record()
        run["startup_view"] = None if events is None else view(
            events, run["ctx"].spans.times, run["ctx"].t_start,
            run["record"]["values"]["setup_s"])
    return run["startup_view"]


def _paint(pieces: list, a: float, b: float, name: str, T) -> list:
    """`pieces` ([start, end, name], disjoint) with [a, b) given to `name`
    over whatever held it."""
    if b <= a:
        return pieces
    out = [[x, y, n] for p, q, n in pieces
           for x, y in T.subtract([[p, q]], [[a, b]])]
    out.append([a, b, name])
    return out


def _cut(a: float, b: float, lo: float, hi: float) -> tuple:
    return max(a, lo), min(b, hi)


def pieces(v: dict, T) -> list:
    """[lo, hi) as [start, end, name] in time order, the innermost name
    over each stretch."""
    lo, hi = v["lo"], v["hi"]
    out = [[lo, hi, UNNAMED]]
    first = min((e[1] for e in v["events"] if e[0] == IMPORT),
                default=None)
    if first is not None:
        out = _paint(out, *_cut(lo, first, lo, hi), PREPROGRAM, T)
    for name, a, b in sorted(v["beneath"], key=lambda s: (s[1], s[1] - s[2])):
        out = _paint(out, *_cut(a, b, lo, hi), name, T)
    for name, a, b, _ in sorted(v["events"],
                                key=lambda e: (e[1], e[1] - e[2])):
        out = _paint(out, *_cut(a, b, lo, hi), name, T)
    return sorted(out)


def timeline_s(v: dict, T) -> dict:
    """{name: wall seconds}, in the order of first appearance with
    `unnamed` last; the values add up to hi - lo."""
    acc: dict = {}
    for a, b, name in pieces(v, T):
        acc[name] = acc.get(name, 0.0) + (b - a)
    acc[UNNAMED] = acc.pop(UNNAMED, 0.0)
    return acc


def unnamed_gaps(v: dict, T, n: int = 3) -> list:
    """The `n` longest stretches under no name, each with where it begins
    (seconds after `lo`), how long it is, and the names on either side:
    what a span would have to be opened around to give it one."""
    cut = pieces(v, T)
    found = []
    for i, (a, b, name) in enumerate(cut):
        if name == UNNAMED:
            found.append({
                "at_s": a - v["lo"], "seconds": b - a,
                "after": cut[i - 1][2] if i else None,
                "before": cut[i + 1][2] if i + 1 < len(cut) else None})
    return sorted(found, key=lambda g: -g["seconds"])[:n]


def _inside(v: dict, a: float, b: float, names) -> list:
    return [e for e in v["events"]
            if e[0] in names and e[1] >= a and e[2] <= b]


def phases_s(v: dict, a: float, b: float, T) -> dict:
    """{phase: wall seconds} of JAX's intervals inside [a, b), the
    innermost phase over each stretch (a cache load lies inside its
    `jax.backend`), so the values add up to their union."""
    out: list = []
    for name, x, y, _ in sorted(_inside(v, a, b, PHASES),
                                key=lambda e: (e[1], e[1] - e[2])):
        out = _paint(out, x, y, name, T)
    acc: dict = {}
    for x, y, name in out:
        acc[name] = acc.get(name, 0.0) + (y - x)
    return acc


def cold_roots(v: dict) -> list:
    """The cold `executor.run` roots that begin inside set-up, in time
    order, cut to it."""
    found = [[n, *_cut(a, b, v["lo"], v["hi"]), args]
             for n, a, b, args in v["events"]
             if n == ROOT and v["lo"] <= a < v["hi"]]
    return sorted(found, key=lambda e: e[1])


def root_rows(v: dict, T) -> list:
    """A row a cold root: `role`, `program`, `step`, its wall seconds, its
    children's by name, the cold `executor.distribute` that came before it
    (ParallelExecutor), and under `execute` the wall of JAX's phases and
    what is left of it, the launch."""
    rows = []
    for _, a, b, args in cold_roots(v):
        row = {"role": args.get("role"), "program": args.get("program"),
               "step": args.get("step"), "seconds": b - a}
        for child in CHILDREN:
            found = _inside(v, a, b, ("executor." + child,))
            row[child] = sum(e[2] - e[1] for e in found)
            if child == "execute" and found:
                inner = phases_s(v, found[0][1], found[0][2], T)
                row["execute_phases"] = inner
                row["execute_launch"] = row[child] - sum(inner.values())
        before = [e for e in v["events"] if e[0] == DISTRIBUTE
                  and e[2] <= a and e[3].get("step") == args.get("step")]
        row["distribute"] = sum(e[2] - e[1] for e in before[-1:])
        rows.append(row)
    return rows


def compile_wall(v: dict, T) -> dict:
    """JAX's intervals inside the cold roots: `seconds` their union,
    `by_phase` the same by innermost phase, `by_function` the union of
    each function's intervals (`<fun_name> <role> <program>`; `jit(f)`
    read as `f`), the five largest."""
    by_phase: dict = {}
    by_fun: dict = {}
    for _, a, b, args in cold_roots(v):
        for k, s in phases_s(v, a, b, T).items():
            by_phase[k] = by_phase.get(k, 0.0) + s
        for name, x, y, eargs in _inside(v, a, b, PHASES):
            fun = str(eargs.get("fun_name") or "?")
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]
            key = f"{fun} {args.get('role')} {args.get('program')}"
            by_fun.setdefault(key, []).append([x, y])
    largest = sorted(((k, T.total(iv)) for k, iv in by_fun.items()),
                     key=lambda kv: -kv[1])[:5]
    return {"seconds": sum(by_phase.values()), "by_phase": by_phase,
            "by_function": dict(largest)}
