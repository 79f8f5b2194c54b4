"""swa_gqa_flash_fwd_roofline — the least time the chip could take for the
attention of the traced window's steps in a decoder that mixes WINDOW
layers with full-span ones under grouped-query attention, over the device
time the trace gives ALL its `flash_fwd` calls.  The least is by the LIVE
(token, key) pairs (benchmarks/flops_smallthinker.py
`mixed_attention_cost`: T w - w (w - 1) / 2 a window layer, T (T + 1) / 2 a
full-span one; every QUERY head's FLOPs, the K, V, dK, dV bytes by the
key/value heads; kind 'fwd'), summed over the configuration's `flops.args`
counts (the entry `mfu_pct` reads through flops_smallthinker.py:
`window_layers` under its `window`, `full_layers` over the whole
sequence), times the traced steps.  A window call visits K blocks the
window only grazes and the blocks' dead corners, and a replayed forward
would be in the time and not in the least: the share is the distance from
the roof and cannot pass 100.  `kernel_share` serves the two backward
kernels' readers too and notes which roof binds in the run's `detail`.
Nothing to read where the configuration's `flops` entry is not
flops_smallthinker's or the run has no trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

WANTED = ("seq_len", "n_heads", "n_kv_heads", "head_dim", "window",
          "window_layers", "full_layers")


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    rec = run["record"]
    spec = run["ctx"].config.get("flops", {})
    args = spec.get("args", {})
    if (run["trace"] is None or spec.get("module") != "flops_smallthinker"
            or any(args.get(k) is None for k in WANTED)):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    F = load_module(".", "flops_smallthinker")
    least, roofs = 0.0, {}
    for what, layers, window in (
            ("window", int(args["window_layers"]), int(args["window"])),
            ("full", int(args["full_layers"]), 0)):
        if not layers:
            continue
        flops, nbytes = F.mixed_attention_cost(
            rec["batch"], int(args["n_heads"]), int(args["n_kv_heads"]),
            int(args["seq_len"]), int(args["head_dim"]), kind, window)
        s, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
        least += layers * s
        roofs[what] = {"roof": roof, "layers": layers,
                       "least_ms_a_layer": 1e3 * s}
    steps = rec["traced"]["steps"]
    run["detail"]["swa_gqa_" + kernel + "_roofline"] = {
        "by_kind": roofs, "least_ms_a_step": 1e3 * least,
        "device_s": seconds, "calls": calls, "calls_a_step": calls / steps}
    return 100.0 * least * steps / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
