"""What the program records about itself, read from outside: its own spans
in a profiler trace, and its compile counters.

Since PR 24 every span of `paddle_tpu.observability.tracing` is also a
`jax.profiler.TraceAnnotation("pdtpu." + name, id=, parent=, **args)` while
a profiler session is active, so the traced slice of a `--trace 1` run holds
them in the same `.xplane.pb` as the device events, on one clock.  `load`
turns that file into a plain structure (lists, strings and numbers, so a
small one can sit in the repository as JSON and a test can do the
arithmetic by hand):

    {"window": [start_ns, end_ns] or None,           # the bench.window span
     "lines": {"/host:CPU|<thread>": [[name, start_ns, duration_ns, stats],
                                      ...]}}         # pdtpu.* events only

`roots` clips the events to the window, nests them by containment on their
line and gives each its self time (its duration less what its children
cover).  A program that has no such spans (the parent of PR 24) gives no
lines, and every reader then returns None.

What of `run` the readers in `layer_metrics/` use through this module:
`run["record"]["trace_path"]` (the `.xplane.pb`), `run["trace"]` and
`run["tracemod"]` (the device events and the interval arithmetic of
`reduce/trace.py`, for the idle time), `run["detail"]` (side tables), and
for the compile counters nothing of `run`: `paddle_tpu.observability
.REGISTRY`, which the driver's `fluid.reset()` cleared before set-up.
"""

from __future__ import annotations

PREFIX = "pdtpu."
ROOT = "pdtpu.executor.run"
WINDOW = "bench.window"
OUTSIDE = "outside"  # idle time under no program span

_loaded: dict = {}


def load(path: str) -> dict:
    """The program's spans of one `.xplane.pb`; parsed once per process.
    Device planes are skipped.  A TraceMe's keyword arguments arrive as the
    event's stats (seen so on the CPU and on the v5e, jax 0.9.0)."""
    if path in _loaded:
        return _loaded[path]
    from jax.profiler import ProfileData

    window, lines = None, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = []
            for e in line.events:
                if e.name == WINDOW:
                    window = [int(e.start_ns),
                              int(e.start_ns) + int(e.duration_ns)]
                elif e.name.startswith(PREFIX):
                    found.append([e.name, int(e.start_ns),
                                  int(e.duration_ns),
                                  {str(k): v for k, v in e.stats}])
            if found:
                lines[f"{plane.name}|{line.name}"] = found
    _loaded[path] = {"window": window, "lines": lines}
    return _loaded[path]


def of_run(run) -> dict:
    """`load` for a reader: None where the run has no trace."""
    path = run["record"].get("trace_path")
    return load(path) if path else None


def _clip(events, window) -> list:
    if window is None:
        return [list(e) for e in events]
    lo, hi = window
    out = []
    for name, start, dur, stats in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a, stats])
    return out


def _nest(events) -> list:
    """Events of one line -> the outermost ones, each a dict with its
    `children` (those it contains) and its `self_ns`."""
    top, stack = [], []
    for name, start, dur, stats in sorted(events,
                                          key=lambda e: (e[1], -e[2])):
        node = {"name": name, "start": start, "dur": dur, "stats": stats,
                "children": []}
        while stack and start >= stack[-1]["start"] + stack[-1]["dur"]:
            stack.pop()
        (stack[-1]["children"] if stack else top).append(node)
        stack.append(node)

    def finish(node):
        covered, edge = 0, node["start"]
        for c in node["children"]:
            finish(c)
            a = max(c["start"], edge)
            b = min(c["start"] + c["dur"], node["start"] + node["dur"])
            if b > a:
                covered += b - a
                edge = b
        node["self_ns"] = node["dur"] - covered

    for node in top:
        finish(node)
    return top


def forest(spans: dict) -> dict:
    """{line: [outermost spans, nested]} of the events inside the window."""
    return {line: _nest(_clip(events, spans["window"]))
            for line, events in spans["lines"].items()}


def roots(spans: dict, name: str = ROOT) -> list:
    """The outermost spans called `name`, of every line, in time order."""
    found = [n for top in forest(spans).values() for n in top
             if n["name"] == name]
    return sorted(found, key=lambda n: n["start"])


def split_ms(found: list) -> dict:
    """The roots' table: the median milliseconds of the root, of each
    child's name (a root's children of one name added up; the median over
    the roots that have one) and of the root's self time.  Medians do not
    add up, so `children_cover` says it directly: the median share of a
    root that its children cover."""
    from harness import median

    if not found:
        return {}
    per: dict = {}
    for root in found:
        mine: dict = {}
        for c in root["children"]:
            mine[c["name"]] = mine.get(c["name"], 0) + c["dur"]
        for k, v in mine.items():
            per.setdefault(k, []).append(v)
    table = {found[0]["name"]: median([r["dur"] for r in found]) / 1e6}
    table.update({k: median(v) / 1e6 for k, v in sorted(per.items())})
    table["self"] = median([r["self_ns"] for r in found]) / 1e6
    table["children_cover"] = median([1.0 - r["self_ns"] / r["dur"]
                                      for r in found])
    table["calls"] = len(found)
    return table


def top_level_ms(spans: dict) -> dict:
    """Median milliseconds of the outermost spans, by name: the roots, and
    what the program does beside them (`pdtpu.executor.distribute`, which
    ParallelExecutor.run spends before it calls Executor.run)."""
    from harness import median

    per: dict = {}
    for top in forest(spans).values():
        for n in top:
            per.setdefault(n["name"], []).append(n["dur"])
    return {k: median(v) / 1e6 for k, v in sorted(per.items())}


def child_ms(run, child: str):
    """Median milliseconds a call of `pdtpu.executor.run` spends in its
    child `child`; None where the trace holds no such span."""
    spans = of_run(run)
    if spans is None:
        return None
    return split_ms(roots(spans)).get(PREFIX + child)


def _innermost(nodes, lo, hi, label, out):
    """Cut [lo, hi) into pieces labelled by the deepest span over each."""
    edge = lo
    for n in nodes:
        a, b = max(n["start"], edge), min(n["start"] + n["dur"], hi)
        if b <= a:
            continue
        if a > edge:
            out.append([edge, a, label])
        _innermost(n["children"], a, b, n["name"], out)
        edge = b
    if hi > edge:
        out.append([edge, hi, label])


def idle_by_program_span(trace: dict, spans: dict, T) -> dict:
    """{span name: seconds}: the first device's idle time inside the
    window, every nanosecond of it given to the innermost program span the
    dispatching thread (the line that holds the roots) had open then, or
    to `outside`.  The values add up to the window's idle time on that
    device.  `T` is reduce/trace.py.  None without device events or
    without a root."""
    if not trace["devices"] or spans["window"] is None:
        return None
    lo, hi = spans["window"]
    lines = forest(spans)
    busiest = max(lines, default=None, key=lambda k: sum(
        1 for n in lines[k] if n["name"] == ROOT))
    if busiest is None or not any(n["name"] == ROOT
                                  for n in lines[busiest]):
        return None
    w = T.windowed(trace)
    first = w["devices"][sorted(w["devices"])[0]]
    gaps = T.subtract([[lo, hi]], [[s, s + d] for _, s, d in first])
    pieces: list = []
    _innermost(lines[busiest], lo, hi, OUTSIDE, pieces)
    acc: dict = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            over = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if over > 0:
                acc[pieces[k][2]] = acc.get(pieces[k][2], 0) + over
            k += 1
    return {name: ns / 1e9
            for name, ns in sorted(acc.items(), key=lambda kv: -kv[1])}


def counter_sum(family: str, label: str, values: tuple):
    """The sum of the program's counter `family` over the series whose
    `label` is one of `values`; None where the program has no such family
    (the parent of PR 24) or the family has no series yet."""
    from paddle_tpu.observability import REGISTRY

    fam = REGISTRY.snapshot()["families"].get(family)
    if fam is None or not fam["series"]:
        return None
    return sum(s["value"] for s in fam["series"]
               if s["labels"].get(label) in values)
