"""attention_relayout_device_ms — device milliseconds a step in the events
whose instructions come from relayout desc ops alone: the rows of
benchmarks/reduce/op_scopes.py whose ops are all of `transpose`,
`transpose_grad`, `reshape`, `reshape_grad` (self time; an unnamed copy
that takes such an op's name, `inherited_ms`, included).  In a decoder LM
those ops are the heads' split to [B, H, T, D] in front of attention and
the merge behind it, forward and backward: moving a tensor, no product in
it.  A relayout that XLA folds into a neighbour's fusion (RoPE's, a
per-head norm's, a product's epilogue) is in that neighbour's row and not
here: this reads what the relayouts cost ALONE.  Rows in
`detail["attention_relayout_device_ms"]`.  0 where the program is named
and holds no such event; nothing to read without a trace's metadata plane
or where under 90% of the busy time is named."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

RELAYOUT = {"transpose", "transpose_grad", "reshape", "reshape_grad"}


def read(run):
    from harness import load_module

    got = load_module("reduce", "op_scopes").covered(run)
    if got is None:
        return None
    rows = {label: r["ms"] for label, r in got["rows"].items()
            if r["ops"] and set(r["ops"]) <= RELAYOUT}
    run["detail"]["attention_relayout_device_ms"] = dict(
        sorted(rows.items(), key=lambda kv: -kv[1]))
    return sum(rows.values())
