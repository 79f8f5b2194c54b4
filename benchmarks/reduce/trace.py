"""From a profiler trace to numbers: the one reduction every PR is read by.

`load_xplane` turns the `.xplane.pb` that jax.profiler writes into a plain
structure (nothing but lists and strings, so a small recorded trace can sit
in the repository as JSON and a test can check the arithmetic):

    {"devices": {"/device:TPU:0": [[name, start_ns, duration_ns], ...]},
     "host":    [[name, start_ns, duration_ns], ...]}      # bench.* spans

`devices` holds the events of each device plane's "XLA Ops" line: one event
per executed HLO operation, under the operation's name (`op_name`; a Pallas
kernel's carries the `name=` its pallas_call was given, `kernel_pattern`).  `host` holds the
benchmark's own spans, which harness.Spans writes as TraceAnnotations named
`bench.<span>`; they are on the same clock as the device events.  The span
`bench.window` marks the traced window; everything is clipped to it.

All results are in seconds.  Times of several devices are averaged over the
devices, as the result line's `busy_s` asks.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
MOSAIC = 'custom_call_target="tpu_custom_call"'  # a Pallas kernel's call
# HLO opcodes that move data between chips.  An async pair shows as
# `<op>-start` and `<op>-done`; both count, the time between them does not.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?(\.\d+)?$")


def op_name(text: str) -> str:
    """The trace names a device event by its whole HLO instruction
    (`%fusion.89 = (bf16[256]{0:T(256)...}) fusion(...)`); the operation's
    name is what stands before ` = `, without the `%`.  A Pallas kernel's
    call is named after the `name=` its pallas_call was given, with what
    the transformations it went through add around it: `flash_fwd.24`,
    `jvp_flash_fwd_.47`, `transpose_jvp_flash_bwd_dq__.24` (seen on the
    v5e, PR 23); `kernel_pattern` matches all of these."""
    return text.split(" = ", 1)[0].lstrip("%")


def kernel_pattern(kernel: str) -> str:
    """A regular expression for every call of the Pallas kernel `kernel`."""
    return r"(^|_)%s_*(\.\d+)?$" % re.escape(kernel)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def describe_xplane(path: str, per_line: int = 4) -> list:
    """Planes, lines, event counts and a few event names: what to look at
    by hand before trusting a reduction on a new installation."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            row = {"plane": plane.name, "line": line.name,
                   "events": len(events),
                   "sample": [e.name[:160] for e in events[:per_line]]}
            if line.name == OPS_LINE:
                seen = {}
                for e in events:
                    head = e.name.split(" = ", 1)[0]
                    kind = head.lstrip("%").split(".")[0]
                    if (MOSAIC in e.name or COLLECTIVE.match(
                            head.lstrip("%"))) and kind not in seen:
                        seen[kind] = {"text": e.name[-1500:],
                                      "stats": {str(k): str(v)[:300]
                                                for k, v in e.stats}}
                row["kernels_and_collectives"] = seen
            out.append(row)
    return out


# ---------------------------------------------------------------------------
# interval arithmetic (nanoseconds in, nanoseconds out)


def merge(intervals) -> list:
    """Sorted, disjoint union of [start, end) intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals) -> int:
    return sum(b - a for a, b in merge(intervals))


def subtract(intervals, holes) -> list:
    """The parts of `intervals` that no interval of `holes` covers."""
    out, holes = [], merge(holes)
    for a, b in merge(intervals):
        cur = a
        for ha, hb in holes:
            if hb <= cur:
                continue
            if ha >= b:
                break
            if ha > cur:
                out.append([cur, ha])
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append([cur, b])
    return out


def clip(events, lo: int, hi: int) -> list:
    """Events cut to [lo, hi); those wholly outside are dropped."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


# ---------------------------------------------------------------------------
# the reduction


def window_of(trace: dict) -> tuple:
    """(start_ns, end_ns) of the traced window: the `bench.window` span, or
    where a trace has none, the extent of its device events."""
    for name, start, dur in trace["host"]:
        if name == WINDOW:
            return start, start + dur
    starts = [e[1] for evs in trace["devices"].values() for e in evs]
    ends = [e[1] + e[2] for evs in trace["devices"].values() for e in evs]
    if not starts:
        raise ValueError("the trace holds no device event")
    return min(starts), max(ends)


def windowed(trace: dict) -> dict:
    lo, hi = window_of(trace)
    return {"devices": {d: clip(evs, lo, hi)
                        for d, evs in trace["devices"].items()},
            "host": clip(trace["host"], lo, hi)}


def _spans(events) -> list:
    return [[s, s + d] for _, s, d in events]


def summary(trace: dict) -> dict:
    """`window_s`, and `busy_s`: the union of the intervals in which an
    operation ran on a device, averaged over the devices."""
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    lo, hi = window_of(trace)
    w = windowed(trace)
    busy = [total(_spans(evs)) for evs in w["devices"].values()]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "devices": len(busy)}


def op_seconds(trace: dict, pattern: str) -> float:
    """Device seconds of the operations whose name matches `pattern`
    (re.search), averaged over the devices."""
    rx = re.compile(pattern)
    w = windowed(trace)
    per = [sum(d for n, _, d in evs if rx.search(n))
           for evs in w["devices"].values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_count(trace: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    w = windowed(trace)
    per = [sum(1 for n, _, _ in evs if rx.search(n))
           for evs in w["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]]: the operations that took most device time, under
    the names the trace gives them, averaged over the devices."""
    w = windowed(trace)
    acc: dict = {}
    for evs in w["devices"].values():
        for name, _, dur in evs:
            acc[name] = acc.get(name, 0) + dur
    k = max(len(w["devices"]), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def exposed_collective_seconds(trace: dict) -> float:
    """Collective operations' device time during which no other operation
    ran on that device, averaged over the devices."""
    w = windowed(trace)
    per = []
    for evs in w["devices"].values():
        coll = _spans(e for e in evs if COLLECTIVE.match(e[0]))
        comp = _spans(e for e in evs if not COLLECTIVE.match(e[0]))
        per.append(total(subtract(coll, comp)))
    return sum(per) / len(per) / 1e9 if per else 0.0


def idle_by_host_span(trace: dict, n: int = 10) -> list:
    """[[name, seconds]]: the first device's idle time inside the window,
    attributed gap by gap to the benchmark span (`bench.<name>`, the
    window's own span aside) that covers most of the gap; `host.other`
    where none does.  The longest first."""
    lo, hi = window_of(trace)
    w = windowed(trace)
    if not w["devices"]:
        return []
    first = w["devices"][sorted(w["devices"])[0]]
    gaps = subtract([[lo, hi]], _spans(first))
    host = sorted(([s, s + d, name[len(HOST_PREFIX):]]
                   for name, s, d in w["host"] if name != WINDOW))
    acc: dict = {}
    j = 0
    for a, b in gaps:
        while j < len(host) and host[j][1] <= a:
            j += 1
        best, best_ns = "host.other", 0
        k = j
        while k < len(host) and host[k][0] < b:
            over = min(b, host[k][1]) - max(a, host[k][0])
            if over > best_ns:
                best, best_ns = host[k][2], over
            k += 1
        acc[best] = acc.get(best, 0) + (b - a)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace, 10),
            "idle_gaps": idle_by_host_span(trace, 10)}


def sample(trace: dict, events_per_device: int = 400) -> dict:
    """A small cut of a trace, to keep as a recorded fixture: the first
    events of each device inside the window, and the host spans beside
    them."""
    w = windowed(trace)
    devices = {d: evs[:events_per_device] for d, evs in w["devices"].items()}
    ends = [e[1] + e[2] for evs in devices.values() for e in evs]
    hi = max(ends) if ends else 0
    return {"devices": devices,
            "host": [e for e in w["host"] if e[0] != WINDOW and e[1] < hi]}
