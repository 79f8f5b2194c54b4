"""attn_head_gate_hbm_roofline — the least time the chip could take for the
attention layers' gates a head of the traced window by the bytes they must
move (benchmarks/flops_laguna.py `head_gate_cost`, 'fwd' + 'bwd', each
layer kind at its own head count: the layer's input and W_g read and g [T,
H] written, the attention's result read and the gated one written at [T, H
x head_dim], and backward their gradients; over the HBM peak: W_g's FLOPs
are far under that), over the device time of `pdtpu.attn.gate` with every
event WHOLE (`attn_head_gate_device_ms.py` `gate_s`, `whole_s`): where XLA
fuses the multiply into a neighbouring product the gate rides on that
product's reads, and counting only what is over the product's least would
let the share pass 100.  So the share reads LOW where the gate is fused
away, and says how far a separate pass over [T, H x head_dim] is from the
roof where it is not.  Nothing to read where the configuration's `flops`
entry is not flops_laguna's or the program names no such part."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

WANTED = ("seq_len", "dim", "head_dim", "sliding_layers", "sliding_heads",
          "full_layers", "full_heads")


def read(run):
    from harness import load_module

    spec = run["ctx"].config.get("flops", {})
    args = spec.get("args", {})
    if spec.get("module") != "flops_laguna" or any(
            args.get(k) is None for k in WANTED):
        return None
    got = load_module("layer_metrics", "attn_head_gate_device_ms").gate_s(run)
    if got is None or got["whole_s"] <= 0:
        return None
    rec = run["record"]
    F = load_module(".", "flops_laguna")
    least, by_kind = 0.0, {}
    for what in ("sliding", "full"):
        layers, heads = (int(args[what + "_layers"]),
                         int(args[what + "_heads"]))
        a_layer = 0.0
        for kind in ("fwd", "bwd"):
            flops, nbytes = F.head_gate_cost(
                rec["batch"], heads, int(args["seq_len"]),
                int(args["head_dim"]), int(args["dim"]), kind)
            a_layer += run["flops"].roofline_seconds(flops, nbytes,
                                                     run["peaks"])[0]
        least += layers * a_layer
        by_kind[what] = {"layers": layers, "heads": heads,
                         "least_ms_a_layer": 1e3 * a_layer}
    steps = rec["traced"]["steps"]
    run["detail"]["attn_head_gate_hbm_roofline"] = {
        "by_kind": by_kind, "least_ms_a_step": 1e3 * least,
        "device_s": got["whole_s"]}
    return 100.0 * least * steps / got["whole_s"]
