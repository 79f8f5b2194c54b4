"""qk_prep_device_ms — device milliseconds a step in the events that take Q
and K from their projections to attention and hold no matrix product: the
rows of benchmarks/reduce/op_scopes.py whose parts are a non-empty subset
of `attn.qk_norm` (the QK-norm), `attn.rope` (a `rope` op) and
`attn.qk_prep` (the op `head_norm_rope`: per-head norm, rotary turn and
head split in one pass, PR 38) and whose `product_flops` is 0, forward and
backward, self time, an unnamed copy that takes such an op's name
included.  A row with a product in it (the Q and K projections with a
norm's forward in their epilogue) is the projections' and is left out;
a row that also carries another part is not this layer's alone.  It reads
a program from before `head_norm_rope` by the first two parts and one
with it by the third.  Rows in `detail["qk_prep_device_ms"]`.  0 where the
program is named and holds no such event; nothing to read without a
trace's metadata plane or where under 90% of the busy time is named."""

LAYER = "Pallas kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PARTS = {"attn.qk_norm", "attn.rope", "attn.qk_prep"}


def read(run):
    from harness import load_module

    got = load_module("reduce", "op_scopes").covered(run)
    if got is None:
        return None
    rows = {label: r["ms"] for label, r in got["rows"].items()
            if r["parts"] and set(r["parts"]) <= PARTS
            and not r["product_flops"]}
    run["detail"]["qk_prep_device_ms"] = dict(
        sorted(rows.items(), key=lambda kv: -kv[1]))
    return sum(rows.values())
