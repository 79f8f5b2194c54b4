"""Structured step tracing: one span, three sinks, and beside them the
step record; one clock (ISSUE 13, ISSUE 24, ISSUE 50, ISSUE 65).

The executor, serving engine, and training service open spans around
their phases (prepare vs execute vs donation, admission vs prefill-chunk
vs decode, lease/rollback events).  A span goes to up to three places:

  * **the profiler's trace, whenever a profiler session is active** -
    each span is then also a
    ``jax.profiler.TraceAnnotation("pdtpu." + name, id=, parent=, **args)``
    (a C++ ``TraceMe``).  Inside ``jax.profiler.start_trace`` ..
    ``stop_trace`` the program's spans land in the ``.xplane.pb`` on the
    device events' clock with nothing to switch on (open it, or its
    Perfetto export, and they sit over the device lines); outside a
    session none is built.
  * **the ring, when enabled** - one dict appended to a
    ``deque(maxlen=capacity)``, exportable as Chrome/Perfetto
    trace-event JSON: the operator's window (``paddle trace``, the
    ``/trace`` endpoint) of a long-lived service, in bounded memory,
    without a profiler.  Off by default (``PADDLE_TPU_TRACE=1`` or
    ``enable()``).
  * **the start-up record, when the call site marks the span cold** -
    a span on a path that runs once a process or once a compile (the
    package's import, the device's start, a dispatch that compiles and
    JAX's trace / lower / compile intervals inside it) passes
    ``cold=True`` and is ALSO kept in a small ``deque`` of its own
    (``COLD_CAPACITY`` events, so a service's steady spans never rotate
    its start-up out), with absolute stamps of the clock, whether or not
    anybody switched tracing on: ``startup_events()``, and ahead of the
    ring's events in ``to_chrome()``.  No switch: what a process did
    before its first step is always there to read.

With the ring off and no session, nothing would read a span that is not
cold, and ``Tracer.span`` hands out one shared stateless object: the cost
of the instrumentation that stays in the hot serving/executor paths at
all times is that of asking ``TraceAnnotation.is_enabled()``, the flag a
``TraceMe`` itself checks (PERF.md, PR 24 and PR 50, have the numbers).

The fourth thing the tracer keeps is no span: **the step record**, one
ROW a dispatch of an executor, written whatever is switched on
(``keep_step``, ``step_rows()``).  A row is a plain tuple of ``STEP_FIELDS``
(the executor's ``step``, ``k``, the program's token, ``cold``, and four
stamps of the clock: the root's two ends and the jitted call's two ends;
under ``ParallelExecutor`` two more around what it does before the root),
appended to a ``deque`` of ``STEP_CAPACITY`` rows without the lock, an id
or a dict: what a steady dispatch pays for it is the clock reads, the
tuple and the append (PERF.md, PR 65).  It is rows and not spans so that a
steady dispatch with nothing switched on still builds no span, and it has
no switch so that a process nobody prepared shows its last 4096
dispatches after the fact: ``to_chrome()`` lays them (category ``steady``)
behind the start-up record, a dispatch the ring or the start-up record
holds too exported once.

Every stamp (the ring's, the record's, the metrics registry's
``monotime``) is ``time.monotonic``, the clock the benchmark's harness
reads too: a reader lays the record beside its own stamps by
subtraction.

A span that a sink records has an ``id`` (process-wide, from 1) and its
``parent``'s id (0 for a root): the innermost recorded span open on the
same thread when it was entered.  Spans of one unit of work share the
identifier their call site passes (``step`` for ``executor.*``, ``rid``
for a request).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation as _Annotation

_clock = time.monotonic
now = _clock  # for a call site that stamps a `cold_event` itself
# the start-up record's bound: a cold dispatch writes ~10 spans and an
# event for every function JAX traces, lowers or compiles inside it
COLD_CAPACITY = 4096
# the step record's bound, in dispatches: at 100 ms a step the last seven
# minutes, and half a megabyte of tuples
STEP_CAPACITY = 4096
# what a row of the step record holds, in its order: the executor's step
# counter at the dispatch's first step, the steps it fused, the program's
# cache token, whether it found no executable; absolute stamps of the
# clock where the root `executor.run` opens, around the jitted call where
# `executor.execute` stands, where the root closes (None where a raising
# dispatch never came); under ParallelExecutor the two ends of
# `executor.distribute` before the root (None elsewhere)
STEP_FIELDS = ("step", "k", "program", "cold", "t_enter", "t_execute0",
               "t_execute1", "t_exit", "t_distribute0", "t_distribute1")
_ids = itertools.count(1)  # next() on a count is atomic under the GIL
# whether a profiler session is recording: what a TraceMe asks itself
# before it records, asked here before one is built
_session_active = _Annotation.is_enabled
ANNOTATION_PREFIX = "pdtpu."


class _NoopSpan:
    """What `Tracer.span` returns while no sink records: stateless, so
    one instance serves every call site and thread."""

    __slots__ = ()

    def note(self, **kw):
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("_tracer", "name", "cat", "args", "id", "parent", "_up",
                 "_ann", "_noted", "_t0", "_ring", "_cold")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict,
                 cold: bool = False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._cold = cold
        self._ann = self._noted = None

    def note(self, **kw):
        """Attach args discovered after entry (e.g. admitted count).  The
        annotation gets them once, when the span closes, so a key noted
        twice holds its last value in both sinks."""
        self.args.update(kw)
        if self._ann is not None:
            if self._noted is None:
                self._noted = kw
            else:
                self._noted.update(kw)
        return self

    def __enter__(self):
        tr = self._tracer
        local = tr._local
        up = self._up = getattr(local, "open", None)
        local.open = self
        self.id = next(_ids)
        self.parent = up.id if up is not None else 0
        if _session_active():
            self._ann = _Annotation(ANNOTATION_PREFIX + self.name,
                                    id=self.id, parent=self.parent,
                                    **self.args)
            self._ann.__enter__()
        self._ring = tr.enabled
        self._t0 = _clock() if self._ring or self._cold else None
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            if self._noted is not None:
                self._ann.set_metadata(**self._noted)
            self._ann.__exit__(exc_type, exc, tb)
        tr = self._tracer
        tr._local.open = self._up
        if self._t0 is not None:
            t1 = _clock()
            args = dict(self.args, id=self.id, parent=self.parent)
            if exc_type is not None:
                args["error"] = exc_type.__name__
            if self._ring:
                tr._record({
                    "name": self.name, "cat": self.cat, "ph": "X",
                    "ts": round((self._t0 - tr._epoch) * 1e6, 3),
                    "dur": round((t1 - self._t0) * 1e6, 3),
                    "pid": tr._pid, "tid": threading.get_ident(),
                    "args": args,
                })
            if self._cold:
                tr._keep(self.name, self._t0, t1, args)
        return False


class Tracer:
    """Bounded-ring span recorder with a start-up record and a step
    record beside it (each a bounded deque of its own, so none rotates
    another out) and Chrome trace-event export of the three."""

    def __init__(self, enabled: Optional[bool] = None,
                 capacity: int = 65536):
        if enabled is None:
            enabled = os.environ.get("PADDLE_TPU_TRACE", "0") == "1"
        self.enabled = bool(enabled)
        self.capacity = max(1, int(capacity))
        self._ring = collections.deque(maxlen=self.capacity)
        # the start-up record: cold events, and apart from them the facts
        # of the process, which reset() keeps
        self._cold = collections.deque(maxlen=COLD_CAPACITY)
        self._process: List[dict] = []
        # the step record: a tuple of STEP_FIELDS a dispatch
        self._steps = collections.deque(maxlen=STEP_CAPACITY)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._epoch = _clock()
        self._pid = os.getpid()

    # -- recording --------------------------------------------------------
    def span(self, name: str, cat: str = "pdtpu", cold: bool = False,
             **args):
        """A span context: a TraceAnnotation while a profiler session is
        active, a ring event while the tracer is enabled, an event of the
        start-up record when the call site says `cold` (its path runs
        once a process or once a compile); where none of the three holds,
        the shared no-op."""
        if cold:
            return _Span(self, name, cat, args, True)
        if not self.enabled and not _session_active():
            return _NOOP
        return _Span(self, name, cat, args)

    def turn_cold(self, span, name: str, cat: str = "pdtpu", **args):
        """For a call site that learns only inside its span that this
        pass is a cold one (a dispatch finds no executable): `span` as it
        got it from `span()` -> (the span that now stands for `name`, the
        span the caller has to `__exit__` itself or None).  A recorded
        span is marked cold where it stands; in place of the no-op a cold
        span is opened here, so that it starts when its coldness was
        found and a steady pass reads no clock for it."""
        if isinstance(span, _Span):
            span._cold = True
            if span._t0 is None:
                span._t0 = _clock()
            span.note(**args)
            return span, None
        opened = _Span(self, name, cat, args, True).__enter__()
        return opened, opened

    def cold_event(self, name: str, t0: float, t1: float,
                   process: bool = False, **args):
        """An interval somebody else measured (JAX's compile phases, the
        package's import), written into the start-up record with stamps
        of this module's clock, under the span open on the calling
        thread.  `process`: a fact of the process, which reset() keeps.
        Inside a profiler session also a mark at the interval's END in
        the profiler's trace, carrying `seconds` and the args."""
        up = self.current()
        args.update(id=next(_ids), parent=up.id if up is not None else 0)
        if _session_active():
            with _Annotation(ANNOTATION_PREFIX + name, seconds=t1 - t0,
                             **args):
                pass
        self._keep(name, t0, t1, args, process)

    def keep_step(self, row: tuple):
        """One dispatch's row of the step record (a tuple of STEP_FIELDS),
        from the executor, whatever is switched on.  No lock: a deque's
        append is atomic under the GIL."""
        self._steps.append(row)

    def current(self) -> Optional[_Span]:
        """The innermost recorded span open on the calling thread, or
        None."""
        return getattr(self._local, "open", None)

    def instant(self, name: str, cat: str = "pdtpu", **args):
        """A point event (lease grant, rollback, fault injection...)."""
        if not self.enabled:
            return
        self._record({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": round((_clock() - self._epoch) * 1e6, 3),
            "pid": self._pid, "tid": threading.get_ident(),
            **({"args": args} if args else {}),
        })

    def _record(self, ev: dict):
        with self._lock:
            self._ring.append(ev)

    def _keep(self, name: str, t0: float, t1: float, args: dict,
              process: bool = False):
        ev = {"name": name, "cat": "cold", "ph": "X", "t0": t0, "t1": t1,
              "pid": self._pid, "tid": threading.get_ident(), "args": args}
        with self._lock:
            (self._process if process else self._cold).append(ev)

    # -- export -----------------------------------------------------------
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def startup_events(self) -> List[dict]:
        """The start-up record: the process's facts, then the cold events
        in the order they ended.  `t0` and `t1` are absolute seconds of
        `time.monotonic`; `args` carry `id` and `parent` as a ring
        event's do."""
        with self._lock:
            return [dict(e) for e in self._process] + \
                [dict(e) for e in self._cold]

    def step_rows(self) -> List[dict]:
        """The step record: a dict of STEP_FIELDS a dispatch, oldest
        first, the stamps absolute seconds of `time.monotonic`."""
        with self._lock:
            rows = list(self._steps)
        return [dict(zip(STEP_FIELDS, r)) for r in rows]

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object format — loadable by Perfetto
        (ui.perfetto.dev) and chrome://tracing: the start-up record's
        events (category `cold`), then the step record's dispatches
        (category `steady`: `executor.run` over `executor.execute`, an
        `executor.distribute` before it under ParallelExecutor; a row
        keeps no thread, so they lie on a track of their own, tid 0),
        then the ring's, on one time axis that starts at the earliest
        stamp of the three; a cold span the ring holds too appears once,
        and so does a dispatch whose root the ring or the start-up
        record holds."""
        cold, ring = self.startup_events(), self.events()
        rows = self.step_rows()
        base = min([self._epoch] + [e["t0"] for e in cold]
                   + [r["t_distribute0"] or r["t_enter"] for r in rows])
        shift = (self._epoch - base) * 1e6
        out, ids = [], set()
        roots: dict = {}  # step -> where a held root `executor.run` began
        for e in cold:
            t0, t1 = e.pop("t0"), e.pop("t1")
            e["ts"] = round((t0 - base) * 1e6, 3)
            e["dur"] = round((t1 - t0) * 1e6, 3)
            ids.add(e["args"]["id"])
            out.append(e)
        ring = [dict(e, ts=round(e["ts"] + shift, 3)) if shift else e
                for e in ring if e.get("args", {}).get("id") not in ids]
        for e in out + ring:
            if e["name"] == "executor.run":
                roots.setdefault(e["args"].get("step"), []).append(e["ts"])
        for r in rows:
            lo, hi = ((r[k] - base) * 1e6 for k in ("t_enter", "t_exit"))
            if any(lo - 1 <= ts <= hi for ts in roots.get(r["step"], ())):
                continue  # its root is there already, with its children
            args = {k: r[k] for k in STEP_FIELDS[:4]}
            for name, a, b in (
                    ("executor.distribute", "t_distribute0",
                     "t_distribute1"),
                    ("executor.run", "t_enter", "t_exit"),
                    ("executor.execute", "t_execute0", "t_execute1")):
                if r[a] is not None and r[b] is not None:
                    out.append({
                        "name": name, "cat": "steady", "ph": "X",
                        "ts": round((r[a] - base) * 1e6, 3),
                        "dur": round((r[b] - r[a]) * 1e6, 3),
                        "pid": self._pid, "tid": 0, "args": args})
        return chrome_envelope(out + ring)

    def export(self, path: str) -> str:
        obj = self.to_chrome()
        with open(path, "w") as f:
            json.dump(obj, f)
        return path

    # -- control ----------------------------------------------------------
    def enable(self, capacity: Optional[int] = None):
        if capacity is not None and capacity != self.capacity:
            self.capacity = max(1, int(capacity))
            with self._lock:
                self._ring = collections.deque(self._ring,
                                               maxlen=self.capacity)
        self.enabled = True

    def disable(self):
        self.enabled = False

    def reset(self):
        with self._lock:
            self._ring.clear()
            self._cold.clear()
            self._steps.clear()
        self._epoch = _clock()


def chrome_envelope(events) -> dict:
    """The Chrome trace-event export envelope — the ONE place its
    schema lives.  ``Tracer.to_chrome`` and every tool writing a merged
    multi-window trace build through here, so envelope changes (and the
    validator's expectations) can never drift across files."""
    return {
        "traceEvents": list(events),
        "displayTimeUnit": "ms",
        "otherData": {"producer": "paddle_tpu.observability",
                      "schema": "chrome-trace-events"},
    }


def concat_windows(windows, gap_us: float = 1000.0) -> List[dict]:
    """Merge event lists captured in SEPARATE tracer windows (each
    re-anchored at ts~0 by ``Tracer.reset()``, e.g. the benches'
    per-run ``fluid.reset()``) onto one timeline: every window is
    shifted to start after the previous window's end plus a small gap,
    so the merged trace renders as sequential runs in Perfetto instead
    of impossibly overlapping same-track slices."""
    out: List[dict] = []
    base = 0.0
    for evs in windows:
        end = base
        for e in evs:
            ev = dict(e)
            ev["ts"] = round(float(ev.get("ts", 0.0)) + base, 3)
            end = max(end, ev["ts"] + float(ev.get("dur", 0.0)))
            out.append(ev)
        if evs:
            base = end + gap_us
    return out


def validate_chrome_trace(obj) -> List[str]:
    """Schema check for to_chrome() output (and for the files the smoke
    tier lints): returns problem strings, empty when Perfetto-loadable."""
    problems = []
    if not isinstance(obj, dict):
        return ["trace is not a JSON object"]
    evs = obj.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents missing or not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in ev:
                problems.append(f"event {i} ({ev.get('name')}): "
                                f"missing {k!r}")
        if ev.get("ph") == "X" and not isinstance(
                ev.get("dur"), (int, float)):
            problems.append(f"event {i} ({ev.get('name')}): complete "
                            f"event without numeric dur")
        if not isinstance(ev.get("ts"), (int, float)):
            problems.append(f"event {i} ({ev.get('name')}): "
                            f"non-numeric ts")
        if "args" in ev and not isinstance(ev["args"], dict):
            problems.append(f"event {i} ({ev.get('name')}): args not "
                            f"an object")
    try:
        json.dumps(obj)
    except (TypeError, ValueError) as e:
        problems.append(f"trace not JSON-serializable: {e}")
    return problems


# the process-global tracer
TRACER = Tracer()
