"""wide_flash_fwd_roofline — the least time the chip could take for the
`flash_fwd` calls of the traced window over the device time the trace gives
them, for heads WIDER than hidden / heads: `gqa_flash_fwd_roofline`'s
arithmetic (benchmarks/flops_lfm2.py `gqa_flash_cost`: every query head's
causal half; Q and O by the query heads, K and V by the key/value heads,
read ONCE) with the head's width from `train.args`' OWN `head_dim` (256 in
Qwen3-Next: two lane tiles in q, k AND v) and the attending layers the
'full_attention' entries of `train.args.layer_types`.  `kernel_share`
serves the two backward kernels' readers too and notes which roof binds in
the run's `detail`.  Nothing to read where the arguments lack a `head_dim`
or a shape, or the run a trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    rec = run["record"]
    args = run["ctx"].config.get("train", {}).get("args", {})
    if run["trace"] is None or any(
            not args.get(k) for k in ("seq_len", "n_heads", "head_dim",
                                      "layer_types")):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    layers = list(args["layer_types"]).count("full_attention")
    if seconds <= 0 or not layers:
        return None
    heads = int(args["n_heads"])
    flops, nbytes = load_module(".", "flops_lfm2").gqa_flash_cost(
        rec["batch"], heads, int(args.get("n_kv_heads") or heads),
        int(args["seq_len"]), int(args["head_dim"]), kind)
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    run["detail"]["wide_" + kernel + "_roofline"] = {
        "roof": roof, "least_ms_a_call": 1e3 * least, "device_s": seconds,
        "calls": calls,
        "calls_a_layer_a_step": calls / (rec["traced"]["steps"] * layers)}
    return 100.0 * least * calls / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
