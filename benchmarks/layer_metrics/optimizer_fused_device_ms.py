"""optimizer_fused_device_ms — device milliseconds a step in every event
whose instruction or fusion body holds an optimizer op's instruction
(`sgd`, `momentum`, `adam`, ...: the op types of the program's counter
`optimizer_update_bytes_total`), WHOLE, with whatever XLA fused in beside
the update (the dW products that feed it).  Rows by
benchmarks/reduce/op_scopes.py.  `detail`: the least the update's bytes
(`param` + `state`, over the HBM peak) and the products inside (over the
bf16 peak) need, and the rows.  Nothing to read without the counter or a
trace's metadata plane, or where under 90% of the busy time is named (the
coverage is then in `detail`)."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def of_run(run):
    """{"ms", "hbm_least_ms", "product_least_ms", "rows"} a step, or None."""
    from harness import load_module

    M = load_module("reduce", "op_scopes")
    moved = M.update_bytes()
    got = M.covered(run) if moved else None
    if got is None:
        return None
    rows = M.optimizer_rows(got, moved)
    if not rows:
        return None
    nbytes = sum(m.get("param", 0.0) + m.get("state", 0.0)
                 for m in moved.values())
    return {
        "ms": sum(r["ms"] for r in rows.values()),
        "inherited_ms": sum(r["inherited_ms"] for r in rows.values()),
        "hbm_least_ms": 1e3 * nbytes / run["peaks"]["hbm_bytes_per_s"],
        "product_least_ms": 1e3 * sum(r["product_flops"]
                                      for r in rows.values())
        / run["peaks"]["bf16_flops_per_s"],
        "update_bytes": moved,
        "rows": {label: r["ms"] for label, r in sorted(
            rows.items(), key=lambda kv: -kv[1]["ms"])}}


def read(run):
    got = of_run(run)
    if got is None:
        return None
    run["detail"]["optimizer_fused_device_ms"] = got
    return got["ms"]
