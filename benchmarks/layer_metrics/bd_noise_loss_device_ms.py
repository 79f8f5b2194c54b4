"""bd_noise_loss_device_ms — device milliseconds a step in the two ends of
a block-diffusion training step that a next-token step lacks or has
otherwise: the events that carry the noising's part (`pdtpu.bd.noise`:
levels, mask, the [noisy ; clean] input, the weights m / t) or the loss's
(`pdtpu.lm.loss`: the float32 softmax over the noisy rows, the weighted
sum; forward and backward) and hold NO optimizer instruction.  An event
counts whole: where XLA runs the loss's first pass in the epilogue of the
head's product, that product is in it, as in `head_loss_device_ms` (which
also takes the events that carry `lm.head` alone); where it folds the
noising into the embedding's gather, the gather.  Rows by
benchmarks/reduce/op_scopes.py, in `detail["bd_noise_loss_device_ms"]`.
Nothing to read where the program names neither part, without a trace's
metadata plane, or where under 90% of the busy time is named."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PARTS = {"bd.noise", "lm.loss"}


def read(run):
    from harness import load_module

    M = load_module("reduce", "op_scopes")
    got = M.covered(run)
    if got is None:
        return None
    updates = set(M.update_bytes() or ())
    rows = {label: r for label, r in got["rows"].items()
            if set(r["parts"]) & PARTS and not set(r["ops"]) & updates}
    if not rows:
        return None
    run["detail"]["bd_noise_loss_device_ms"] = {
        label: r["ms"] for label, r in sorted(
            rows.items(), key=lambda kv: -kv[1]["ms"])}
    return sum(r["ms"] for r in rows.values())
