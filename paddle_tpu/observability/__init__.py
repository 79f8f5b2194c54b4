"""paddle_tpu.observability — the unified telemetry substrate (ISSUE 13).

Three modules, one namespace:

  * :mod:`.metrics` — the process-global ``REGISTRY`` of counters /
    gauges / histograms with labels; Prometheus text + JSON snapshot
    exports; the bench-artifact metric-name authority
    (``artifact_metric``);
  * :mod:`.tracing` — the process-global ``TRACER``: nested spans in a
    bounded ring, the start-up record of the process's cold path (kept
    with the ring off), Chrome/Perfetto trace-event export of both;
  * :mod:`.attribution` — the op identity every compiled step carries
    (named-scope threading, always on): what a traced run on the chip
    reads per-op device time by.

Usage:

    from paddle_tpu import observability as obs

    obs.enable_tracing()
    with obs.span("my.phase", detail="..."):
        ...
    obs.TRACER.export("trace.json")      # open in ui.perfetto.dev
    print(obs.REGISTRY.render_prometheus())

Everything is near-zero cost when disabled — instrumentation in the
executor/serving/service hot paths stays compiled in at all times.
"""

from . import attribution  # noqa: F401
from . import metrics  # noqa: F401
from . import tracing  # noqa: F401
from .httpd import TelemetryServer, serve_http  # noqa: F401
from .metrics import (  # noqa: F401
    REGISTRY,
    MetricsRegistry,
    MirroredCounters,
    artifact_metric,
    monotime,
    validate_snapshot,
)
from .tracing import (  # noqa: F401
    TRACER,
    Tracer,
    chrome_envelope,
    concat_windows,
    validate_chrome_trace,
)


def span(name: str, cat: str = "pdtpu", cold: bool = False, **args):
    """Open a span on the global tracer (the shared no-op while neither
    the ring nor a profiler session records and the span is not `cold`)."""
    return TRACER.span(name, cat=cat, cold=cold, **args)


def instant(name: str, cat: str = "pdtpu", **args):
    return TRACER.instant(name, cat=cat, **args)


def enable_tracing(capacity=None):
    TRACER.enable(capacity)


def disable_tracing():
    TRACER.disable()


def export_telemetry(trace_obj=None, trace_path=None,
                     metrics_obj=None, metrics_path=None):
    """Write + schema-validate telemetry artifacts in one place (the
    serve_bench / chaos_run export path — one
    implementation, so their validation semantics cannot drift).

    `metrics_obj` is either a bare registry snapshot or the multi-run
    form ``{"runs": [{"snapshot": ...}, ...]}``; every snapshot inside
    is validated.  Returns problem strings (empty = artifacts written
    and schema-clean); files are written regardless so a failed
    validation still leaves the evidence on disk."""
    import json

    problems = []
    if trace_path is not None and trace_obj is not None:
        problems += [f"trace: {p}"
                     for p in validate_chrome_trace(trace_obj)]
        with open(trace_path, "w") as f:
            json.dump(trace_obj, f)
    if metrics_path is not None and metrics_obj is not None:
        snaps = (metrics_obj.get("runs")
                 if isinstance(metrics_obj, dict)
                 and "runs" in metrics_obj
                 else [{"snapshot": metrics_obj}])
        for rec in snaps:
            problems += [f"metrics: {p}"
                         for p in validate_snapshot(rec["snapshot"])]
        with open(metrics_path, "w") as f:
            json.dump(metrics_obj, f)
    return problems


def reset():
    """Fresh registry/tracer state (fluid.reset() hook —
    clears series, the ring and the start-up record but the process's own
    facts in place, so held handles stay valid)."""
    REGISTRY.reset()
    TRACER.reset()
