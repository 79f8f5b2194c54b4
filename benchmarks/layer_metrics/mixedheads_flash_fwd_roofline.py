"""mixedheads_flash_fwd_roofline — the least time the chip could take for
the attention of the traced window's steps in a decoder whose WINDOW layers
and full-span layers differ in their QUERY head count on the same key/value
heads, over the device time the trace gives ALL its `flash_fwd` calls.  The
least is by the LIVE (token, key) pairs (benchmarks/flops_laguna.py
`attention_cost`: T w - w (w - 1) / 2 a window layer, T (T + 1) / 2 a
full-span one; every QUERY head's FLOPs at the layer kind's OWN head count
and group, the K, V, dK, dV bytes by the key/value heads; kind 'fwd'),
summed over the configuration's `flops.args` counts (the entry `mfu_pct`
reads through flops_laguna.py: `sliding_layers` of `sliding_heads` under
`window`, `full_layers` of `full_heads` over the whole sequence), times the
traced steps.  A window call visits K blocks the window only grazes and the
blocks' dead corners, and a replayed forward would be in the time and not
in the least: the share is the distance from the roof and cannot pass 100.
`kernel_share` serves the two backward kernels' readers too and notes by
layer kind which roof binds in the run's `detail`.  Nothing to read where
the configuration's `flops` entry is not flops_laguna's or the run has no
trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

WANTED = ("seq_len", "n_kv_heads", "head_dim", "window", "sliding_layers",
          "sliding_heads", "full_layers", "full_heads")


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    rec = run["record"]
    spec = run["ctx"].config.get("flops", {})
    args = spec.get("args", {})
    if (run["trace"] is None or spec.get("module") != "flops_laguna"
            or any(args.get(k) is None for k in WANTED)):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    F = load_module(".", "flops_laguna")
    least, roofs = 0.0, {}
    for what, window in (("sliding", int(args["window"])), ("full", 0)):
        layers, heads = (int(args[what + "_layers"]),
                         int(args[what + "_heads"]))
        if not layers:
            continue
        flops, nbytes = F.attention_cost(
            rec["batch"], heads, int(args["n_kv_heads"]),
            int(args["seq_len"]), int(args["head_dim"]), kind, window)
        s, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
        least += layers * s
        roofs[what] = {"roof": roof, "layers": layers, "heads": heads,
                       "group": heads // int(args["n_kv_heads"]),
                       "least_ms_a_layer": 1e3 * s}
    steps = rec["traced"]["steps"]
    run["detail"]["mixedheads_" + kernel + "_roofline"] = {
        "by_kind": roofs, "least_ms_a_step": 1e3 * least,
        "device_s": seconds, "calls": calls, "calls_a_step": calls / steps}
    return 100.0 * least * steps / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
