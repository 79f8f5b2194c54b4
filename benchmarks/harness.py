"""What every cell shares: finding files by name, the clock, spans, the
compile log, the statistics, and the shape of the result line.

Nothing here knows a configuration, a traffic mix or a metric by name: a
cell's entry in BENCHMARK.json names them, and each is a file of its own
under this directory (see README.md).  This directory is deliberately not a
package: modules are loaded by path, so a file called `dispatch_ms.train.py`
is as good a name as any.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

monotime = time.monotonic


# ---------------------------------------------------------------------------
# files by name


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def cell_of(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in manifest['workloads']]}")


def path_of(kind: str, name: str, ext: str) -> str:
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"benchmarks/{kind}/{name}{ext} does not exist: a {kind} entry "
            f"named {name!r} is a file of that name")
    return path


def load_json(kind: str, name: str) -> dict:
    with open(path_of(kind, name, ".json"), encoding="utf-8") as f:
        return json.load(f)


_modules: dict = {}


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module, loaded by path, once."""
    key = (kind, name)
    if key not in _modules:
        path = path_of(kind, name, ".py")
        ident = "bench_%s_%s" % (kind, "".join(
            c if c.isalnum() else "_" for c in name))
        spec = importlib.util.spec_from_file_location(ident, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[key] = mod
    return _modules[key]


def resolve(dotted: str):
    """'package.module:attribute' -> the object (a builder of the program
    under test, named in a configuration's file)."""
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


def flops_per_sample(config: dict) -> float:
    """The operations one sample needs, by a configuration's `flops` entry:
    a `function`, its `args`, and optionally the `module` it lives in, a
    file of benchmarks/ (default `flops`), so that a configuration whose
    count flops.py cannot make names its own file and no stand-in."""
    spec = config["flops"]
    fn = getattr(load_module(".", spec.get("module", "flops")),
                 spec["function"])
    return fn(**spec["args"])


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of `group` ('end_to_end' or 'per_layer') that `cell_name`
    reports: those with no `workloads` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks_for(device_kind: str) -> dict:
    """The published peaks of a device kind.  A kind that is not in the
    table is an error, never a default."""
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"benchmarks/peaks.json has no row for device kind "
                       f"{device_kind!r}; it has {sorted(table)}")
    return table[device_kind]


def claim_tpu(chips: int, who: str):
    """The TPU devices, or None after saying on stderr what was found
    instead: the benchmark has no CPU mode.  Fixes the compile cache first,
    before jax is imported: JAX_COMPILATION_CACHE_DIR where it is set, else
    <checkout>/.jax_cache, which is where the program's Executor would put
    it anyway, so the benchmark's own programs share it; and every program
    goes into it, the small ones too, so that a warm run compiles nothing."""
    import sys

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    found = jax.devices()
    if found[0].platform != "tpu" or len(found) < chips:
        print(f"{who} needs {chips} TPU chip(s), but JAX found platform "
              f"{found[0].platform!r} ({found[0].device_kind!r}, "
              f"{len(found)} device(s)); there is no CPU mode",
              file=sys.stderr)
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return found


def seed32(seed: int) -> int:
    """The driver's seeds pass 2**31; numpy's RandomState and a program's
    random_seed take 32 bits.  A fixed, documented fold."""
    return int(seed) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# the clock, spans and the compile log


class Spans:
    """The benchmark's own spans around its calls into each layer: name ->
    [(start, end)] on the monotonic clock.  In a traced run each span is
    also a jax.profiler.TraceAnnotation, which puts it into the profiler's
    trace on the device events' clock, so that an idle gap on the device
    can be attributed to what the host was doing in it."""

    def __init__(self, annotate: bool = False):
        self.times: dict = {}
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        note = contextlib.nullcontext()
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation("bench." + name)
        with note:
            t0 = monotime()
            try:
                yield
            finally:
                self.times.setdefault(name, []).append((t0, monotime()))

    def durations(self, name: str, since: float = -math.inf,
                  until: float = math.inf) -> list:
        return [b - a for a, b in self.times.get(name, ())
                if a >= since and b <= until]


class CompileLog:
    """What jax.monitoring says happened: seconds spent tracing, lowering
    and compiling (a persistent-cache hit counts its retrieval), the number
    of such events, and persistent-cache hits and misses.  Copied from
    chip_smoke.py's _CompileLog (PR 21), with the event count added: any
    event inside the measured window means something compiled there."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.events = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event in self.DURATIONS:
            self.seconds += duration
            self.events += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.seconds, self.events, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "compile_events": self.events - mark[1],
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}


@dataclasses.dataclass
class Context:
    """What run.py hands a driver: the cell's three files, the arguments of
    the command, the clock's origin, and where the devices come from
    (`place_of(i)` is TPUPlace(i) in the command; the tests pass CPUPlace
    and toy sizes, and call the driver as a function)."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    place_of: object
    trace_dir: str
    spans: Spans = None
    log: CompileLog = None

    def __post_init__(self):
        if self.spans is None:
            self.spans = Spans(annotate=self.trace)
        if self.log is None:
            self.log = CompileLog()


class Tracer:
    """jax.profiler around the last `trace_seconds` of a traced run.  The
    python tracer is off (it would write tens of thousands of events a
    second); the host tracer stays on for the benchmark's own
    TraceAnnotations."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.on = False
        self.path = None

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.ctx.trace_dir, profiler_options=opts)
        self.on = True

    def stop(self) -> str:
        import glob

        import jax

        jax.profiler.stop_trace()
        self.on = False
        found = glob.glob(os.path.join(self.ctx.trace_dir, "plugins",
                                       "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                               f"{self.ctx.trace_dir}")
        self.path = found[0]
        return self.path


# ---------------------------------------------------------------------------
# statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest ranks — numpy's default definition, written out so the yardstick
    does not move with a library."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def rate(count: float, seconds: float) -> float:
    """Work over all the time of the window."""
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} s")
    return count / seconds


# ---------------------------------------------------------------------------
# the result line


def device_block(devices, trace_summary=None) -> dict:
    """`device` of the result line: the platform, kind and count as JAX
    reports them, the peak bytes on the fullest chip, and in a traced run
    the busy seconds (averaged over the chips) and the traced window.

    The peak is the allocator's `peak_bytes_in_use` (arrays: weights,
    optimizer state, staged batches, caches) plus its `peak_bytes_reserved`,
    which is where the TPU runtime keeps a running program's temporaries:
    on the v5e it read 4.38 GB for the ResNet-50 step whose compiled
    temporaries are 4.42 GB, and 9.19 GB against 9.33 GB for GPT-2-medium
    (PR 23)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace_summary is not None:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None, compared=None) -> str:
    """The one JSON object the driver reads, with exactly its keys, and
    last `compared`: {name: [number, limit]}, every number `correct` rests
    on beside its limit (the driver keeps the line's end of a run that was
    not correct, so this is what the next session sees of it)."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if compared is not None:
        out["compared"] = compared
    return json.dumps(out)


def compared_lines(compared: dict) -> list:
    """`compared` as the last lines of standard error, one a number."""
    return [f"compared {name}: {value!r} limit {limit!r}"
            for name, (value, limit) in compared.items()]
