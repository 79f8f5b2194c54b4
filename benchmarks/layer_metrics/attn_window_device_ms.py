"""attn_window_device_ms — device milliseconds a step in the attention
layers that run under a SLIDING WINDOW, forward and backward: every
instruction the compiled program puts into `pdtpu.attn.window`
(`decoder_lm`'s part around a 'multi_head_attention' layer whose `window`
entry is set: the four projections, the heads' preparation and relayouts,
the flash kernels) at its self time, the products WHOLE: they are the
layer's.  Beside `attn_full_device_ms`: two layer kinds of unequal cost in
one tower.  `detail["attn_window_device_ms"]` has the events counted and
what of the time is events that hold a matrix product; `part_ms` serves the
twin reader.  Nothing to read where the program names no such part or the
trace lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def part_ms(run, part: str, key: str):
    from harness import load_module

    got = load_module("reduce", "part_ms").events_of(run)
    if got is None:
        return None
    total = products = 0.0
    events = 0
    for note, s, _ in got:
        if note.own and part in note.scopes:
            total += s
            events += 1
            if note.product_flops:
                products += s
    if total <= 0:
        return None
    steps = run["record"]["traced"]["steps"]
    run["detail"][key] = {
        "events_a_step": events / steps,
        "in_product_events_ms_a_step": 1e3 * products / steps}
    return 1e3 * total / steps


def read(run):
    return part_ms(run, "attn.window", "attn_window_device_ms")
