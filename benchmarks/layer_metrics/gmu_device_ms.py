"""gmu_device_ms — device milliseconds a step in the gated memory units,
forward, the segment's recomputed forward and backward: every instruction
the compiled program puts into `pdtpu.mixer.gmu` (`decoder_lm`'s 'gmu'
layers: the projection W_in, memory * SiLU(.), the projection W_out) at its
self time, the two products WHOLE: they are the unit.
`detail["gmu_device_ms"]` has what of it is events that hold a matrix
product.  The memory's own gradient path back into the layer that made it
is that layer's, not this.  Nothing to read where the program names no such
part (the parent of PR 52) or the trace lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("reduce", "part_ms").events_of(run)
    if got is None:
        return None
    total = products = 0.0
    for note, s, _ in got:
        if note.own and "mixer.gmu" in note.scopes:
            total += s
            if note.product_flops:
                products += s
    if total <= 0:
        return None
    steps = run["record"]["traced"]["steps"]
    run["detail"]["gmu_device_ms"] = {
        "in_product_events_ms_a_step": 1e3 * products / steps}
    return 1e3 * total / steps
