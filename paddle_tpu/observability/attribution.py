"""Per-op device-time attribution (ISSUE 16, ISSUE 35): profile -> ProgramDesc.

Two pieces, one identity:

  * **identity threading** — :func:`op_scope` and :func:`part_scope` are
    the repo's ONE ``jax.named_scope`` mint (repo_lint rule 10).  The
    executor wraps every lowered op in ``pdop__<type>__u<uid>`` (a grad
    op says whose gradient it is: ``pdop__mul_grad__u<uid>``) and, inside
    it, in the ``pdtpu.<part>`` its desc names (attr ``part``; a grad op
    takes the forward op's), so each HLO instruction's metadata traces
    back to the desc op and the model part that produced it.  Always on:
    a scope is metadata of the traced program, costs one context manager
    an op a TRACE and a step nothing.  On a chip the compiled program's
    own metadata is read back from a profiler trace by the benchmark
    (``benchmarks/reduce/op_scopes.py``: the xplane's HloProto joins an
    event's instruction to these names).
  * **the CPU oracle and the join** — :func:`attribute_cpu` is the
    deterministic CPU oracle: segment-timed eager execution over the
    hazard-respecting topological order derived from
    ``analysis/dataflow.py`` (RAW edges from ``dependency_graph`` plus
    every textual read/write-before-write ordering, so the schedule
    preserves exactly the semantics the linear executor's textual order
    guarantees).  :func:`build_table` joins its measured time share
    against ``analysis/cost.py``'s per-op FLOPs/bytes prediction,
    published as ``op_pred_vs_measured{op_type=...}`` /
    ``op_measured_time_share`` gauges and a bench-schema artifact row.
    The table is also what feeds the calibration store
    (observability/calibration.py) — measured/predicted per
    (op type, chip, dtype) is precisely the correction factor the cost
    model's roofline lacks.
"""

from __future__ import annotations

import contextlib
import re
from statistics import median
from typing import Dict, List

from .metrics import REGISTRY, artifact_metric, monotime

# ---------------------------------------------------------------------------
# identity threading: the one named-scope mint

_SCOPE_FMT = "pdop__{type}__u{uid}"
_SCOPE_RE = re.compile(r"pdop__([A-Za-z0-9_]+)__u(\d+)")

# gauge handles resolved once (families survive REGISTRY.reset(), the
# accounting.py idiom)
_G_PVM = REGISTRY.gauge(
    "op_pred_vs_measured",
    "per-op-type predicted/measured time ratio from the attribution "
    "table (1.0 = the static model prices this op type perfectly)")
_G_SHARE = REGISTRY.gauge(
    "op_measured_time_share",
    "per-op-type share of measured step time from the attribution table")
_G_COVERAGE = REGISTRY.gauge(
    "op_attribution_coverage",
    "fraction of measured step time attributed to named desc ops")


def op_type(op) -> str:
    """The type an op is known by in scopes and counters: its own, and for
    a `generic_grad` the forward op's with `_grad` (`mul_grad`)."""
    if op.type == "generic_grad":
        return f"{op.attrs.get('__fwd_type__', 'generic')}_grad"
    return op.type


def op_part(op):
    """The model part an op's desc names (attr `part`; a `generic_grad`
    carries the forward op's; `<outer>/<inner>` under nested
    `Program.part_guard`s), or None."""
    attrs = op.attrs
    if op.type == "generic_grad":
        attrs = attrs.get("__fwd_attrs__") or {}
    return attrs.get("part") or None


def scope_name(op) -> str:
    """The per-op scope string: type (:func:`op_type`) + desc uid
    (core.py's per-program monotonic ``__uid__``), the same identity
    ctx.rng folds in; a grad op shares its forward op's uid."""
    return _SCOPE_FMT.format(type=op_type(op),
                             uid=int(op.attrs.get("__uid__", 0)))


@contextlib.contextmanager
def op_scope(op):
    """Wraps one op's lowering in the ``jax.named_scope`` that carries its
    desc identity and, inside it, in its model part's where its desc names
    one (:func:`op_part`), so forward and backward instructions both carry
    `pdtpu.<part>`.  Reached once an op a TRACE, never by a step of a
    compiled program."""
    import jax

    part = op_part(op)
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.named_scope(scope_name(op)))
        for name in (part or "").split("/"):   # nested guards, outer first
            if name:
                stack.enter_context(part_scope(name))
        yield


def part_scope(name: str):
    """A named part of the model (`pdtpu.<name>`, as the tracer's spans
    are named), nested under the op's own scope and never looking like
    one (`parse_scope` matches `pdop__...` only).  The executor opens the
    one an op's desc names (attr `part`: `lm.head`, `attn.rope`); an
    emitter with several stages worth telling apart in a trace (the
    dropless `moe` op's route / permute / experts / combine) wraps each
    in one.  Beside `op_scope`, so that named scopes have one home."""
    import jax

    return jax.named_scope("pdtpu." + name)


def parse_scope(text: str):
    """(op_type, uid) from any string carrying a scope name, else None.
    Greedy type match + the terminal ``__u<digits>`` keeps op types with
    underscores (elementwise_add) unambiguous."""
    m = _SCOPE_RE.search(text or "")
    if not m:
        return None
    return m.group(1), int(m.group(2))


# ---------------------------------------------------------------------------
# the schedule: hazard-respecting topological order from the dataflow pass


def schedule(block) -> List[int]:
    """Deterministic topological order over the block's ops that the
    oracle may time one segment at a time.

    Edges: RAW from ``dataflow.dependency_graph`` plus, per name, every
    earlier textual access (read or write) before a later write.  The
    second family covers exactly the orderings ``dataflow.hazards``
    documents as the executor's textual-order guarantees — including the
    scope-read-then-optimizer-write training idiom that the hazard
    report deliberately exempts — so emitting ops in this order threads
    the same values as ``_lower_ops`` in textual order.  Ties break on
    lowest op index, making the schedule reproducible run to run."""
    import heapq

    from ..analysis import dataflow as _df

    n = len(block.ops)
    preds = _df.dependency_graph(block)
    succ: List[set] = [set() for _ in range(n)]
    indeg = [0] * n

    def edge(i, j):
        if i != j and j not in succ[i]:
            succ[i].add(j)
            indeg[j] += 1

    for j, ps in enumerate(preds):
        for i in ps:
            edge(i, j)
    defs, uses = _df.def_use(block)
    for name, dlist in defs.items():
        accesses = sorted(set(dlist) | set(uses.get(name, [])))
        for j in dlist:
            for i in accesses:
                if i < j:
                    edge(i, j)
    heap = [i for i in range(n) if indeg[i] == 0]
    heapq.heapify(heap)
    out: List[int] = []
    while heap:
        i = heapq.heappop(heap)
        out.append(i)
        for j in sorted(succ[i]):
            indeg[j] -= 1
            if indeg[j] == 0:
                heapq.heappush(heap, j)
    if len(out) != n:  # unreachable (textual order is acyclic); be safe
        return list(range(n))
    return out


# ---------------------------------------------------------------------------
# CPU fallback oracle: segment-timed eager execution


def _seed_state(program, block, feeds, scope):
    """State values for every name the block reads/updates, from `scope`
    (fluid global scope by default) — the executor's donation classes."""
    from ..analysis.dataflow import state_classes
    from ..framework.scope import global_scope

    scope = scope if scope is not None else global_scope()
    ext, rw, _ = state_classes(block, list(feeds))
    state = {}
    for name in list(ext) + list(rw):
        v = scope.find(name)
        if v is None:
            raise RuntimeError(
                f"attribution: variable {name!r} not initialized in "
                f"scope — run the startup program first")
        state[name] = v
    return state


def attribute_cpu(program, feed, *, scope=None, state=None, block_id=0,
                  repeats=3, batch_size=64, chip=None,
                  rng_seed=0) -> dict:
    """The deterministic CPU oracle: execute the block eagerly, one op
    segment at a time in :func:`schedule` order, timing each emit up to
    ``block_until_ready``.  Segment sums vs the walk's wall time give
    the attribution coverage; per-op medians over `repeats` walks give
    the measured column of the table.

    Per-op dispatch overhead is PART of the measurement by design — on
    cpu-host that overhead dominates microscopic ops, which is exactly
    the signal the calibration factors must learn (the same stance as
    pred_vs_measured's cpu-host caveat)."""
    import jax

    from ..framework.executor import _NOOP_TYPES, _lower_op
    from ..ops.registry import EmitContext

    block = program.blocks[block_id]
    if state is None:
        state = _seed_state(program, block, feed, scope)
    base_env = {}
    for n, v in state.items():
        base_env[n] = jax.numpy.asarray(v)
    for n, v in feed.items():
        base_env[n] = jax.numpy.asarray(v)
    is_test = not any(op.type.endswith("_grad")
                      or op.type == "generic_grad" for op in block.ops)
    order = schedule(block)
    n_ops = len(block.ops)
    per_op: List[List[float]] = [[] for _ in range(n_ops)]
    walls: List[float] = []
    for _ in range(max(1, int(repeats))):
        env = dict(base_env)
        ctx = EmitContext(
            jax.random.fold_in(
                jax.random.PRNGKey(program.random_seed), int(rng_seed)),
            is_test=is_test, program=program)

        def lower_sub(idx, sub_env, _ctx=ctx):
            # sub-blocks (while/cond bodies) execute inside the owning
            # op's segment and are attributed to it
            _ctx.sub_depth += 1
            try:
                from ..framework.executor import _lower_ops

                return _lower_ops(program.blocks[idx].ops, sub_env, _ctx)
            finally:
                _ctx.sub_depth -= 1

        ctx.lower_block = lower_sub
        t_wall = monotime()
        for i in order:
            op = block.ops[i]
            if op.type in _NOOP_TYPES:
                continue
            t0 = monotime()
            outs = _lower_op(op, env, ctx)
            vals = [v for vs in (outs or {}).values()
                    for v in vs if v is not None]
            if vals:
                jax.block_until_ready(vals)
            per_op[i].append(monotime() - t0)
        walls.append(monotime() - t_wall)
    measured = [median(ts) if ts else None for ts in per_op]
    return build_table(block, measured, median(walls),
                       batch_size=batch_size, chip=chip,
                       mode="cpu-oracle", repeats=int(repeats))


# ---------------------------------------------------------------------------
# the join: measured segments x static per-op cost


def build_table(block, measured, total_s, *, batch_size=64, chip=None,
                mode="cpu-oracle", **meta) -> dict:
    """Join measured per-op seconds (index-aligned with block.ops; None
    = unattributed) against cost.op_cost predictions into the canonical
    attribution table both capture paths return."""
    from ..analysis import cost as _cost

    spec = _cost.chip_spec(chip or _cost.detect_chip())
    peak, bw = spec["flops_bf16"], spec["hbm_gbps"] * 1e9
    rows: List[dict] = []
    pred_total = 0.0
    for i, op in enumerate(block.ops):
        m = measured[i] if i < len(measured) else None
        if m is None:
            continue
        c = _cost.op_cost(block, op, batch_size)
        dt = c["dtype"] or "float32"
        rate = peak * _cost._DTYPE_RATE.get(dt, 0.5)
        pred = max(c["flops"] / rate if rate else 0.0,
                   c["bytes"] / bw if bw else 0.0)
        pred_total += pred
        rows.append({"index": i, "op_type": op.type,
                     "uid": int(op.attrs.get("__uid__", -1)),
                     "dtype": dt, "measured_s": float(m),
                     "pred_time_s": pred, "pred_flops": c["flops"],
                     "pred_bytes": c["bytes"]})
    attributed = sum(r["measured_s"] for r in rows)
    total_s = float(total_s) or attributed
    by_type: Dict[str, dict] = {}
    for r in rows:
        r["measured_share"] = (r["measured_s"] / total_s
                               if total_s else 0.0)
        r["pred_share"] = (r["pred_time_s"] / pred_total
                           if pred_total else 0.0)
        e = by_type.setdefault(
            r["op_type"],
            {"count": 0, "measured_s": 0.0, "pred_time_s": 0.0,
             "dtype": r["dtype"]})
        e["count"] += 1
        e["measured_s"] += r["measured_s"]
        e["pred_time_s"] += r["pred_time_s"]
    for e in by_type.values():
        e["measured_share"] = (e["measured_s"] / total_s
                               if total_s else 0.0)
        e["pred_share"] = (e["pred_time_s"] / pred_total
                           if pred_total else 0.0)
        e["pred_vs_measured"] = (e["pred_time_s"] / e["measured_s"]
                                 if e["measured_s"] else 0.0)
    by_type = dict(sorted(by_type.items(),
                          key=lambda kv: -kv[1]["measured_s"]))
    top = next(iter(by_type), "")
    return {"mode": mode, "chip": spec["chip"],
            "batch_size": int(batch_size), "total_s": total_s,
            "attributed_s": attributed,
            "coverage": attributed / total_s if total_s else 0.0,
            "n_ops": len(rows), "pred_total_s": pred_total,
            "top_op": top, "rows": rows, "by_type": by_type, **meta}


def publish(table, program: str):
    """Materialize a table as registry gauges (the metric-namespace rows
    documented in docs/observability.md)."""
    for t, e in table["by_type"].items():
        _G_PVM.set(e["pred_vs_measured"], op_type=t, program=program)
        _G_SHARE.set(e["measured_share"], op_type=t, program=program)
    _G_COVERAGE.set(table["coverage"], program=program)


def artifact_row(table, program: str) -> dict:
    """One bench-schema row for a table: headline = coverage, with the
    per-type breakdown and a compact per-op table attached."""
    compact = [{"op_type": r["op_type"], "uid": r["uid"],
                "measured_us": round(r["measured_s"] * 1e6, 3),
                "share": round(r["measured_share"], 4),
                "pred_share": round(r["pred_share"], 4)}
               for r in table["rows"]]
    by_type = {t: {"count": e["count"],
                   "share": round(e["measured_share"], 4),
                   "pred_share": round(e["pred_share"], 4),
                   "pred_vs_measured": round(e["pred_vs_measured"], 6)}
               for t, e in table["by_type"].items()}
    return artifact_metric(
        f"op_attribution_{program}", round(table["coverage"], 4),
        "fraction of measured step time attributed to named desc ops",
        mode=table["mode"], chip=table["chip"], n_ops=table["n_ops"],
        total_ms=round(table["total_s"] * 1e3, 4),
        top_op=table["top_op"], by_type=by_type, op_table=compact)
