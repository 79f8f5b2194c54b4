"""shared_grad_sum_device_ms — device milliseconds a step in the adds that
make ONE gradient of the parts of a parameter several ops read (a looped
tower's blocks: four parts; a tied embedding: two) and stand ALONE: the
rows of benchmarks/reduce/op_scopes.py whose one op is `sum` under the part
`grad.sum` (framework/backward.py names it where `append_backward`
finalizes a parameter from several parts), self time.  Where XLA puts the
adds into other fusions (the epilogue of the product that makes a part,
the optimizer's update: `ouro_train_t4096` at PR 71, all but 0.0007 ms)
they move no bytes of their own and the reading is what is left, 0 where
nothing is; `detail["shared_grad_sum_device_ms"]["rides_in"]` names the
rows that hold a `grad.sum` instruction beside other ops, ms a step each
(the whole event's, not the add's).  Nothing to read where the program
names no such part (the parent of PR 71), without a trace's metadata plane
or where under 90% of the busy time is named."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PART = "grad.sum"


def of_run(run):
    """{"ms", "rows", "rides_in"} a step, or None."""
    from harness import load_module

    got = load_module("reduce", "op_scopes").covered(run)
    if got is None:
        return None
    alone, rides = {}, {}
    for label, r in got["rows"].items():
        if PART in r["parts"]:
            (alone if set(r["ops"]) == {"sum"} and set(r["parts"]) == {PART}
             and not r["product_flops"] else rides)[label] = r["ms"]
    if not alone and not rides:
        return None
    by_ms = lambda rows: dict(sorted(rows.items(), key=lambda kv: -kv[1]))
    return {"ms": sum(alone.values()), "rows": by_ms(alone),
            "rides_in": by_ms(rides)}


def read(run):
    got = of_run(run)
    if got is None:
        return None
    run["detail"]["shared_grad_sum_device_ms"] = got
    return got["ms"]
