"""gqa_flash_fwd_roofline — the least time the chip could take for the
`flash_fwd` calls of the traced window over the device time the trace
gives them, for `heads` query heads on `kv_heads` key/value heads: the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, from shapes
(benchmarks/flops_lfm2.py `gqa_flash_cost`: every query head's causal half;
Q and O by the query heads, K and V by the key/value heads, read ONCE),
times the calls the trace counts.  The shapes are the builder's own
arguments (`train.args`: `seq_len`, `dim`, `n_heads`, and `n_kv_heads`
where keys and values have fewer heads: without it they have `n_heads`, and
the cost is flops.py's `flash_attention_cost`), so any decoder built
through `train.args` whose heads are `dim / n_heads` wide can list it; a
latent-attention configuration (`qk_nope_dim`: keys wider than values) has
mla_flash_*_roofline.  `kernel_share` serves the two backward kernels'
readers too and notes which roof binds in the run's `detail`.  Nothing to
read where the arguments lack a shape or the run a trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    rec = run["record"]
    args = run["ctx"].config.get("train", {}).get("args", {})
    if (run["trace"] is None or "qk_nope_dim" in args or any(
            not args.get(k) for k in ("seq_len", "dim", "n_heads"))):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    heads = int(args["n_heads"])
    flops, nbytes = load_module(".", "flops_lfm2").gqa_flash_cost(
        rec["batch"], heads, int(args.get("n_kv_heads") or heads),
        int(args["seq_len"]), int(args["dim"]) // heads, kind)
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    attention_layers = (sum(t != "conv" for t in args["layer_types"])
                        if "layer_types" in args else int(args["n_layers"]))
    run["detail"]["gqa_" + kernel + "_roofline"] = {
        "roof": roof, "least_ms_a_call": 1e3 * least, "device_s": seconds,
        "calls": calls,
        "calls_a_layer_a_step": calls / (
            rec["traced"]["steps"] * attention_layers)}
    return 100.0 * least * calls / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
