"""head_loss_device_ms — device milliseconds a step in the events that
carry the head projection's or the loss's part (`pdtpu.lm.head`,
`pdtpu.lm.loss`: models/transformer.py names them, forward and backward)
and hold NO optimizer instruction: the head's weight update, with its dW
product inside, is `optimizer_fused_device_ms`'s, so the two never count
one event twice.  Rows by benchmarks/reduce/op_scopes.py, in
`detail["head_loss_device_ms"]`.  Nothing to read where the program names
no such part, without a trace's metadata plane, or where under 90% of the
busy time is named."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "op_scopes")
    got = M.covered(run)
    if got is None:
        return None
    rows = M.head_loss_rows(got, M.update_bytes() or ())
    if not rows:
        return None
    run["detail"]["head_loss_device_ms"] = {
        label: r["ms"] for label, r in sorted(
            rows.items(), key=lambda kv: -kv[1]["ms"])}
    return sum(r["ms"] for r in rows.values())
