"""window_flash_fwd_roofline — the least time the chip could take for the
attention of the traced window's steps in a decoder that mixes WINDOW and
full-span differential attention, over the device time the trace gives ALL
its `flash_fwd` calls.  The least is by the LIVE (token, key) pairs at the
least form (benchmarks/flops_phi4flash.py `differential_attention_cost`:
every score ONCE against values twice a head wide; a window layer's pairs T
w - w (w - 1) / 2, the triangle's in a full-span and in a cross layer;
kind 'fwd'), summed over the configuration's `flops.args` counts (the
entry `mfu_pct` reads through flops_phi4flash.py: `window_layers` under its
`window`, `full_layers` and `cross_layers` over the whole sequence), times
the traced steps.
The program's two calls a layer compute every score twice, a window call
visits blocks the window only grazes, and a `layers.recompute` segment
launches the forward again: all of that is in the time and none of it in
the least, so the share is the distance from the roof, and cannot pass 100.
`kernel_share` serves the two backward kernels' readers too and notes which
roof binds in the run's `detail`.  Nothing to read where the configuration
counts no window layer or the run has no trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    rec = run["record"]
    args = run["ctx"].config.get("flops", {}).get("args", {})
    if run["trace"] is None or any(
            not args.get(k) for k in ("seq_len", "n_heads", "n_kv_heads",
                                      "head_dim", "window", "window_layers")):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    windows = ([int(args["window"])] * int(args["window_layers"])
               + [0] * (int(args.get("full_layers", 0))
                        + int(args.get("cross_layers", 0))))
    F = load_module(".", "flops_phi4flash")
    least, roofs = 0.0, []
    for window in windows:
        flops, nbytes = F.differential_attention_cost(
            rec["batch"], int(args["seq_len"]), int(args["n_heads"]),
            int(args["n_kv_heads"]), int(args["head_dim"]), kind, window)
        s, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
        least += s
        roofs.append(roof)
    steps = rec["traced"]["steps"]
    run["detail"]["window_" + kernel + "_roofline"] = {
        "roofs": roofs, "least_ms_a_step": 1e3 * least, "device_s": seconds,
        "calls": calls, "calls_a_step": calls / steps,
        "layers": len(windows)}
    return 100.0 * least * steps / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
