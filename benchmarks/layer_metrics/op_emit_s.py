"""op_emit_s — host seconds the process spent inside the op emitters while
its programs were traced: the sum of the program's counter
`executor_op_emit_seconds_total{op}` (self time by op type, `<fwd>_grad`
for a grad op; once a compile, never a step).  Beside `compile_trace_s` it
says how much of set-up's trace phase is the emitters' own Python and not
JAX's tracing machinery around them.  `detail["by_op"]`: the eight largest
op types.  Nothing to read where the program has no such counter (the
parent of PR 35)."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    from harness import load_module

    spent = load_module("reduce", "op_scopes").emit_seconds()
    if spent is None:
        return None
    ranked = sorted(spent.items(), key=lambda kv: -kv[1])[:8]
    run["detail"]["op_emit_s"] = {"by_op": dict(ranked),
                                  "op_types": len(spent)}
    return sum(spent.values())
