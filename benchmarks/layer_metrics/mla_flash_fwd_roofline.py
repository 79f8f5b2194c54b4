"""mla_flash_fwd_roofline — the least time the chip could take for the
`flash_fwd` calls of the traced window where queries and keys are wider
than values (latent attention: 192 and 128), over the device time the
trace gives them: the larger of FLOPs over the bf16 peak and bytes over
the HBM peak, from shapes (benchmarks/flops_mla.py `mla_flash_cost`: B, the
heads, T, both widths, the causal half), times the calls the trace counts.
`flash_fwd_roofline`'s twin for a configuration with two head widths;
`kernel_share` serves the two backward kernels' readers too and notes
which roof binds in the run's `detail`.  Nothing to read where the
configuration has one head width or the run no trace."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def kernel_share(run, kernel: str, kind: str):
    from harness import load_module

    cfg, rec = run["ctx"].config, run["record"]
    if run["trace"] is None or "qk_rope_head_dim" not in cfg:
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    if seconds <= 0:
        return None
    flops, nbytes = load_module(".", "flops_mla").mla_flash_cost(
        rec["batch"], int(cfg["num_attention_heads"]),
        int(cfg["max_position_embeddings"]),
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]), kind)
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    run["detail"]["mla_" + kernel + "_roofline"] = {
        "roof": roof, "least_ms_a_call": 1e3 * least, "device_s": seconds,
        "calls": calls,
        "calls_a_layer_a_step": calls / (
            rec["traced"]["steps"] * int(cfg["num_hidden_layers"]))}
    return 100.0 * least * calls / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
