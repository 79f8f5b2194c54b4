"""attn_head_gate_device_ms — device milliseconds a step in the attention
layers' gate a HEAD, forward and backward: every instruction the compiled
program puts into `pdtpu.attn.gate` (`layers.multi_head_attention
(output_gate="head")`: W_g's product on the layer's input, the sigmoid, the
multiply of a head's columns, and their backward, the sum of a head's
columns into the gate's gradient included) at its self time.  Where XLA
fused some of that into ANOTHER matrix product (the multiply into W_o's
operand), of the event counts what is over that product's own least
(`reduce/part_ms.py` `seconds`: the most the gate can have cost there);
`gate_s` serves the roofline's reader, which counts such events whole.
`detail["attn_head_gate_device_ms"]` has the events, those that hold a
product and their time, and the time with every event whole.  Nothing to
read where the program names no such part (a gate an element carries it
too: Qwen3-Next's cell is not listed) or the trace lacks the program's
metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def gate_s(run):
    """-> {"s": the gate's device seconds in the traced window (an event
    fused into a product at what is over the product's least), "whole_s":
    every event of the part whole, "events", "in_products"} or None."""
    from harness import load_module

    P = load_module("reduce", "part_ms")
    wanted = lambda parts: "attn.gate" in parts  # noqa: E731
    got = P.seconds(run, wanted)
    if got is None or got["events"] == 0:
        return None
    whole = sum(s for note, s, _ in P.events_of(run)
                if note.own and wanted(note.scopes))
    return dict(got, whole_s=whole)


def read(run):
    got = gate_s(run)
    if got is None or got["whole_s"] <= 0:
        return None
    steps = run["record"]["traced"]["steps"]
    run["detail"]["attn_head_gate_device_ms"] = {
        "events_a_step": got["events"] / steps,
        "in_product_events_a_step": got["in_products"] / steps,
        "in_product_events_ms_a_step": 1e3 * got["in_products_s"] / steps,
        "whole_events_ms_a_step": 1e3 * got["whole_s"] / steps}
    return 1e3 * got["s"] / steps
