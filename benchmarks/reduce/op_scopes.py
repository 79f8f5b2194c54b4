"""Which desc op, and which part of the model, each device event of a
profiler trace came from, by the compiled program's own word.

The program lowers every op of a step inside `pdop__<type>__u<uid>`
(`observability/attribution.py` `op_scope`; a grad op is `<fwd>_grad`) and,
where the op's desc names one, inside `pdtpu.<part>` (`lm.head`, `lm.loss`,
`attn.rope`, an emitter's own `moe.permute`, ...).  Both reach the `op_name`
of every HLO instruction, and the xplane keeps the HloProto of the program
that ran (hlo_scopes.py, whose reading of the wire format this file uses:
`hlo_protos`, `instructions`, `name_of`, and `notes` for the matrix
products' FLOPs).  So every event of the first device's "XLA Ops" line,
clipped to the traced window, goes into exactly ONE row:

  - the row of the one op its instruction and its fusion's body name
    (`adam`, `mul_grad`, `layer_norm`), refined by the part where one is
    named (`mul[lm.head]`, `moe[moe.permute]`);
  - where the body names several, the row of the sorted combination
    (`adam+mul_grad`): no time is split by a guess and none counted twice;
  - an instruction JAX named nowhere, itself or inside (no path with a
    `/`), takes its operands' producers' (hlo_scopes.py's rule) and its
    time is also kept apart as the row's `inherited_ms`;
  - inside a fusion's body a constant, an iota, and what is computed from
    those alone name nothing: XLA merges equal ones across the program and
    keeps ONE op's name (the -inf of the loss's `reduce_max` sat in seven
    convolution layers' dX products of `lfm2_train_t8192`, my chip run,
    PR 35); an event that IS such an instruction keeps its name;
  - what is left is the row `unattributed`.

Of a nested op (`recompute`, `while`) the innermost scope counts: the op
that emitted the instruction.  Time is the event's SELF time: where events
of the line overlap, each instant belongs to the event that started last,
so the rows sum to the line's busy time whatever XLA nests.

`rows_of(comps)` and `table(evs, window, rows)` are pure (a test feeds them
recorded instructions and events); `of_run(run)` gives a reader

    {"steps", "busy_ms", "coverage" (0..1),
     "rows": {label: {"ms", "inherited_ms", "events", "product_flops",
                      "ops", "parts"}}}          # ms and FLOPs a step

or None where the run has no trace or the trace no metadata plane.
`update_bytes()` reads the program's counter
`optimizer_update_bytes_total` (its `op` labels are the optimizer's op
types), `emit_seconds()` its `executor_op_emit_seconds_total`; each None
where the program has no such counter (the parent of PR 35).
"""

from __future__ import annotations

import collections
import re

OP = re.compile(r"pdop__([A-Za-z0-9_]+)__u\d+")
VALUES = ("constant", "iota")   # opcodes that compute from nothing
UNATTRIBUTED = "unattributed"
COVERAGE_FLOOR = 0.9   # under it the device readers but the guard give None
HEAD_LOSS = frozenset(("lm.head", "lm.loss"))
TOP_ROWS = 12

Row = collections.namedtuple("Row", "label ops parts own product_flops")
NOTHING = Row(UNATTRIBUTED, frozenset(), frozenset(), True, 0.0)


def _hlo():
    from harness import load_module

    return load_module("reduce", "hlo_scopes")


def pairs_of(op_name: str, part_rx) -> set:
    """{(op type, part or None)} an `op_name` names: of each path in it
    (XLA joins the paths of what it merged with `;`) the innermost op
    scope and the innermost part."""
    out = set()
    for path in op_name.split(";"):
        ops = OP.findall(path)
        if ops:
            parts = part_rx.findall(path)
            out.add((ops[-1], parts[-1] if parts else None))
    return out


def label_of(pairs) -> str:
    if not pairs:
        return UNATTRIBUTED
    return "+".join(sorted(f"{op}[{part}]" if part else op
                           for op, part in pairs))


def rows_of(comps: dict) -> dict:
    """{instruction name: Row} of hlo_scopes.py `instructions`' result."""
    H = _hlo()
    flops = {name: note.product_flops
             for name, note in H.notes(comps).items()}
    by_id = {i["id"]: i for found in comps.values() for i in found}
    fixed: dict = {}

    def value_only(ins):
        """A constant, an iota, or what is computed from those alone."""
        if ins["id"] not in fixed:
            fixed[ins["id"]] = ins["opcode"] in VALUES or bool(
                ins["operands"] and not ins["called"]
                and ins["opcode"] != "parameter"
                and all(o in by_id and value_only(by_id[o])
                        for o in ins["operands"]))
        return fixed[ins["id"]]

    inside: dict = {}
    owned: dict = {}

    def body(ins):
        """(pairs, whether JAX named any of it) of an instruction as part
        of what another calls (a fusion's body), and all it calls."""
        if ins["id"] not in inside:
            pairs = (set() if value_only(ins)
                     else pairs_of(ins["op_name"], H.PART))
            p, named = called(ins)
            inside[ins["id"]] = (frozenset(pairs | p),
                                 named or "/" in ins["op_name"])
        return inside[ins["id"]]

    def called(ins):
        pairs, named = set(), False
        for cid in ins["called"]:
            for sub in comps.get(cid, ()):
                p, n = body(sub)
                pairs |= p
                named |= n
        return pairs, named

    def own(ins):
        """The same of an instruction as an event: what it is named
        itself counts whatever its opcode (a buffer of zeros that one op
        asked for is that op's work)."""
        if ins["id"] not in owned:
            pairs, named = called(ins)
            owned[ins["id"]] = (
                frozenset(pairs | pairs_of(ins["op_name"], H.PART)),
                named or "/" in ins["op_name"])
        return owned[ins["id"]]

    taken: dict = {}

    def inherited(ins, depth):
        pairs, named = own(ins)
        if named or depth == 0:
            return pairs
        key = (ins["id"], depth)
        if key not in taken:
            taken[key] = frozenset().union(*(
                inherited(by_id[o], depth - 1) for o in ins["operands"]
                if o in by_id))
        return taken[key]

    out = {}
    for found in comps.values():
        for ins in found:
            pairs = inherited(ins, H.INHERIT_DEPTH)
            out[ins["name"]] = Row(
                label_of(pairs), frozenset(op for op, _ in pairs),
                frozenset(part for _, part in pairs if part),
                own(ins)[1] or not ins["operands"],
                flops.get(ins["name"], 0.0))
    return out


def self_ns(spans) -> list:
    """Of [start, end) spans, the nanoseconds that are each one's own:
    an instant belongs to the span covering it that started last (of two
    that start together, the shorter).  The results sum to the union."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    out = [0] * len(spans)
    stack: list = []   # the open spans, the last started on top
    now = 0

    def advance(limit):
        """The time from `now` to `limit` goes to whoever is on top."""
        nonlocal now
        while stack and now < limit:
            top = stack[-1]
            end = spans[top][1]
            if end <= now:
                stack.pop()
                continue
            upto = min(end, limit)
            out[top] += upto - now
            now = upto
        now = max(now, limit)

    for i in order:
        advance(spans[i][0])
        stack.append(i)
    advance(max((end for _, end in spans), default=0))
    return out


def table(evs, window, rows: dict) -> dict:
    """{"busy_s", "rows": {label: {"s", "inherited_s", "events",
    "product_flops", "ops", "parts"}}} of the events [[instruction text or
    name, start_ns, duration_ns]] inside `window` = (start_ns, end_ns), each
    clipped to it; a product's FLOPs are cut as its event was clipped."""
    name_of = _hlo().name_of
    lo, hi = window
    kept = []
    for text, start, dur in evs:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            kept.append((a, b, dur, rows.get(name_of(text), NOTHING)))
    own = self_ns([(a, b) for a, b, _, _ in kept])
    out: dict = {}
    for (a, b, dur, row), ns in zip(kept, own):
        got = out.setdefault(row.label, {
            "s": 0.0, "inherited_s": 0.0, "events": 0, "product_flops": 0.0,
            "ops": sorted(row.ops), "parts": sorted(row.parts)})
        got["s"] += ns / 1e9
        got["inherited_s"] += 0.0 if row.own else ns / 1e9
        got["events"] += 1
        got["product_flops"] += row.product_flops * (b - a) / dur
    return {"busy_s": sum(own) / 1e9, "rows": out}


def of_trace(path: str):
    """{instruction name: Row} of the largest program in the trace's
    metadata plane; None where it holds none."""
    H = _hlo()
    protos = H.hlo_protos(path)
    if not protos:
        return None
    return rows_of(H.instructions(max(protos, key=len)))


def of_run(run):
    """The table of a traced run, ms and FLOPs a step (module docstring);
    None where there is nothing to read.  Made once a run, from the device
    events the command has loaded already (reduce/trace.py `load_xplane`:
    an event's name there is its instruction's)."""
    path = run["record"].get("trace_path")
    if not path or run.get("trace") is None:
        return None
    if "op_scopes" not in run["detail"]:
        devices = run["trace"]["devices"]
        rows = of_trace(path) if devices else None
        if rows is None:
            return None
        got = table(devices[min(devices)],
                    run["tracemod"].window_of(run["trace"]), rows)
        if got["busy_s"] <= 0:
            return None
        steps = run["record"]["traced"]["steps"]
        lost = got["rows"].get(UNATTRIBUTED, {"s": 0.0})["s"]
        run["detail"]["op_scopes"] = {
            "steps": steps, "busy_ms": 1e3 * got["busy_s"] / steps,
            "coverage": max(0.0, 1.0 - lost / got["busy_s"]),
            "rows": {label: {
                "ms": 1e3 * r["s"] / steps,
                "inherited_ms": 1e3 * r["inherited_s"] / steps,
                "events": r["events"],
                "product_flops": r["product_flops"] / steps,
                "ops": r["ops"], "parts": r["parts"]}
                for label, r in got["rows"].items()}}
    return run["detail"]["op_scopes"]


def largest(got, n=TOP_ROWS) -> dict:
    """{label: ms a step} of the `n` largest rows, the largest first."""
    ranked = sorted(got["rows"].items(), key=lambda kv: -kv[1]["ms"])[:n]
    return {label: r["ms"] for label, r in ranked}


def covered(run):
    """`of_run` for a reader that wants a program named well enough to be
    read: None, with the coverage in `detail`, where under COVERAGE_FLOOR
    of the busy time is in named rows."""
    got = of_run(run)
    if got is not None and got["coverage"] < COVERAGE_FLOOR:
        run["detail"]["op_scopes_coverage_too_low"] = got["coverage"]
        return None
    return got


def _series(family: str):
    """[(labels, value)] of the program's counter `family`; None where the
    program has no such family or it has no series yet."""
    from paddle_tpu.observability import REGISTRY

    fam = REGISTRY.snapshot()["families"].get(family)
    if fam is None or not fam["series"]:
        return None
    return [(s["labels"], s["value"]) for s in fam["series"]]


def update_bytes():
    """{op type: {tensor: bytes a step}} of `optimizer_update_bytes_total`
    (counted when the step is traced in set-up: once a compile)."""
    series = _series("optimizer_update_bytes_total")
    if series is None:
        return None
    out: dict = {}
    for labels, value in series:
        out.setdefault(labels["op"], {})[labels["tensor"]] = value
    return out


def emit_seconds():
    """{op type: host seconds in its emitter} of
    `executor_op_emit_seconds_total`, over the process."""
    series = _series("executor_op_emit_seconds_total")
    if series is None:
        return None
    return {labels["op"]: value for labels, value in series}


def optimizer_rows(got, types) -> dict:
    """The rows whose events hold an instruction of an optimizer op."""
    return {label: r for label, r in got["rows"].items()
            if set(r["ops"]) & set(types)}


def head_loss_rows(got, types) -> dict:
    """The rows that carry the head or the loss and hold no optimizer
    instruction (the head's weight update, with its dW product inside, is
    the optimizer's: `optimizer_rows`)."""
    return {label: r for label, r in got["rows"].items()
            if set(r["parts"]) & HEAD_LOSS and not set(r["ops"]) & set(types)}
