"""One parallelism mode's live plan against its snapshot
(tests/fixtures/mode_plans_golden.json).

The snapshot holds, for each of the eleven modes of parallel/modes.py,
the per-var specs and the propagated collective footprint the bespoke
per-mode wiring produced before PR 19 deleted it.  The one rule table
(parallel/partitioner.py) has to keep producing them: a dropped or
changed rule shows as a per-var spec diff and a per-kind collective
delta."""

import json
import os

from paddle_tpu.analysis.sharding import propagate
from paddle_tpu.mesh import spec_of
from paddle_tpu.parallel import modes as pmodes

SNAPSHOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "mode_plans_golden.json")


def _json_spec(plan, block, var) -> list:
    """`var`'s spec in the snapshot's form: trailing Nones dropped,
    tuples as lists."""
    v = block._find_var_recursive(var)
    ndim = len(v.shape) if v is not None and v.shape else None
    spec = spec_of(plan.get(var), ndim)
    while spec and spec[-1] is None:
        spec = spec[:-1]
    return [list(e) if isinstance(e, tuple) else e for e in spec]


def mode_plan_against_snapshot(name: str, batch_size: int = 8) -> dict:
    """Build mode `name`, take `ParallelExecutor.static_plan` and
    `propagate` over it, and compare both with the snapshot: `spec_diffs`
    (per var: `bespoke` the snapshot's spec, `logical` the live one) and
    `comm["delta"]` (per collective kind).  `verdict` is "PROVEN" when
    the snapshot has the mode and neither differs."""
    mode, program, _loss = pmodes.build_mode(name)
    mesh, plan, provenance = pmodes.mode_plan(mode, program)
    with open(SNAPSHOT) as f:
        entry = json.load(f)["modes"].get(name)
    golden = entry is not None and entry["batch_size"] == batch_size
    gspecs = entry["specs"] if golden else {}
    gprov = entry["provenance"] if golden else {}

    block = program.global_block()
    spec_diffs = []
    for var in sorted(set(plan) | set(gspecs)):
        live, snap = _json_spec(plan, block, var), list(gspecs.get(var, []))
        if live != snap:
            spec_diffs.append({
                "var": var, "bespoke": snap, "logical": live,
                "bespoke_rule": gprov.get(var, "transpiler default")})

    ana = propagate(program, mesh=mesh, plan=plan, batch_size=batch_size,
                    provenance=provenance)
    pk_live = ana.per_kind()
    pk_snap = {k: dict(v) for k, v in entry["per_kind"].items()} \
        if golden else {}
    none = {"count": 0, "bytes": 0}
    delta = {}
    for kind in sorted(set(pk_snap) | set(pk_live)):
        b, l = pk_snap.get(kind, none), pk_live.get(kind, none)
        if dict(b) != dict(l):
            delta[kind] = {"bespoke": dict(b), "logical": dict(l),
                           "bytes_delta": int(b["bytes"]) - int(l["bytes"])}

    proven = golden and not spec_diffs and not delta
    return {
        "mode": name,
        "mesh": dict(mode.mesh_axes),
        "verdict": "PROVEN" if proven else "DIVERGED",
        "golden": golden,
        "spec_diffs": spec_diffs,
        "comm": {"bespoke": pk_snap, "logical": pk_live, "delta": delta},
        "pipeline": bool(mode.pipeline),
    }
