"""Device time of a traced run by the parts of the model that the compiled
program itself names, for parts that NEST (a hyper-connection inside a
multi-token-prediction module's block carries `pdtpu.mtp.block` and
`pdtpu.hc.gates`): op_scopes.py's rows go by an instruction's innermost
part alone, share_ops.py's sums count an event whole, a `while` and the
fusions of its body both.  Here an event's time is its SELF time
(op_scopes.py `self_ns`: of overlapping events an instant belongs to the
one that started last, so a `while` keeps only what its body leaves) and
its parts are ALL that hlo_scopes.py finds in its instruction and in what
it calls.

`seconds(run, wanted)` adds up the events whose parts are their own
(hlo_scopes.py `own`: a copy XLA makes of a result for whoever reads it
next is not) and for which `wanted(parts)` holds.  Where XLA fused some of
that into a matrix product, of the event counts what is over the product's
own least, its FLOPs over the bf16 peak (share_ops.py's rule for the short
convolution): the most the part can have cost there.  -> {"s", "events",
"in_products", "in_products_s"} or None where the run has no trace or the
trace not the program's metadata.
"""

from __future__ import annotations

_events: dict = {}


def events_of(run):
    """[(Note, self seconds, the share of the event inside the window)] of
    the first device's events in the traced window; None where there is
    nothing to read.  Made once a trace."""
    from harness import load_module

    path = run["record"].get("trace_path")
    if not path or run.get("trace") is None:
        return None
    if path not in _events:
        H = load_module("reduce", "hlo_scopes")
        devices = run["trace"]["devices"]
        notes = H.of_trace(path) if devices else {}
        lo, hi = run["tracemod"].window_of(run["trace"])
        kept = []
        for text, start, dur in (devices[min(devices)] if notes else ()):
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                kept.append((a, b, dur, notes.get(H.name_of(text),
                                                  H.NOTHING)))
        own = load_module("reduce", "op_scopes").self_ns(
            [(a, b) for a, b, _, _ in kept])
        _events[path] = [(note, ns / 1e9, (b - a) / dur)
                         for (a, b, dur, note), ns in zip(kept, own)] or None
    return _events[path]


def seconds(run, wanted):
    got = events_of(run)
    if got is None:
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    out = {"s": 0.0, "events": 0, "in_products": 0, "in_products_s": 0.0}
    for note, s, inside in got:
        if not (note.own and wanted(note.scopes)):
            continue
        out["events"] += 1
        if note.product_flops:
            out["in_products"] += 1
            out["in_products_s"] += s
            s = max(0.0, s - note.product_flops / peak * inside)
        out["s"] += s
    return out


def under(prefix: str):
    """`wanted` for the parts whose names start with `prefix` ('hc.')."""
    return lambda parts: any(p.startswith(prefix) for p in parts)
