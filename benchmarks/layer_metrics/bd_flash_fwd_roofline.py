"""bd_flash_fwd_roofline — the least time the chip could take for the
`flash_fwd` calls of the traced window over the device time the trace
gives them, under the BLOCK-DIFFUSION mask: the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, from shapes
(benchmarks/flops_sdar.py `bd_flash_cost`: the LIVE scores of every query
head, L^2 + L b of the (2L)^2 the call spans; Q and O by the query heads,
K and V by the key/value heads, read ONCE over the 2L rows), times the
calls the trace counts.  The shapes are the builder's own arguments
(`train.args`: `seq_len` L, `block_length` b, `n_heads`, `n_kv_heads`,
`head_dim`, `n_layers`).  The causal readers' count
(gqa_flash_*_roofline: half the square) would read twice the truth here,
so a block-diffusion cell lists these three and not those.
`kernel_share` serves the two backward kernels' readers too and notes
which roof binds, and the kernel's ms a call, in the run's `detail`.
Nothing to read where the arguments lack a shape (a configuration that
is not trained by block diffusion) or the run a trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

ARGS = ("seq_len", "block_length", "n_heads", "n_kv_heads", "head_dim",
        "n_layers")


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    rec = run["record"]
    args = run["ctx"].config.get("train", {}).get("args", {})
    if run["trace"] is None or any(not args.get(k) for k in ARGS):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    flops, nbytes = load_module(".", "flops_sdar").bd_flash_cost(
        rec["batch"], int(args["n_heads"]), int(args["n_kv_heads"]),
        int(args["seq_len"]), int(args["block_length"]),
        int(args["head_dim"]), kind)
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    run["detail"]["bd_" + kernel + "_roofline"] = {
        "roof": roof, "least_ms_a_call": 1e3 * least, "device_s": seconds,
        "calls": calls, "device_ms_a_call": 1e3 * seconds / calls,
        "calls_a_layer_a_step": calls / (
            rec["traced"]["steps"] * int(args["n_layers"]))}
    return 100.0 * least * calls / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
