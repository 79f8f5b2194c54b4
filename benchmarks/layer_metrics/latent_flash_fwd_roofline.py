"""latent_flash_fwd_roofline — the least time the chip could take for the
`flash_fwd` calls of the traced window where queries and keys are wider
than values (latent attention), over the device time the trace gives them:
`mla_flash_fwd_roofline`'s arithmetic (benchmarks/flops_mla.py
`mla_flash_cost`, unedited: B, the heads, T, both widths, the causal half)
with every shape from `train.args`' OWN names (`seq_len`, `n_heads`,
`qk_nope_dim` + `qk_rope_dim`, `v_dim`) and not from a published key that
changes from family to family: `mla_flash_*` takes T from
`max_position_embeddings`, which Kimi-Linear's configuration does not have
(ROADMAP.md B5).  The attending layers are the 'full_attention' entries of
`train.args.layer_types`.  `kernel_share` serves the two backward kernels'
readers too and notes which roof binds in the run's `detail`.  Nothing to
read where the arguments lack a shape or such a layer, or the run a
trace."""

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
            not args.get(k) for k in ("seq_len", "n_heads", "qk_nope_dim",
                                      "qk_rope_dim", "v_dim",
                                      "layer_types")):
        return None
    T = run["tracemod"]
    pattern = T.kernel_pattern(kernel)
    seconds = T.op_seconds(run["trace"], pattern)
    calls = T.op_count(run["trace"], pattern)
    layers = list(args["layer_types"]).count("full_attention")
    if seconds <= 0 or not layers:
        return None
    flops, nbytes = load_module(".", "flops_mla").mla_flash_cost(
        rec["batch"], int(args["n_heads"]), int(args["seq_len"]),
        int(args["qk_nope_dim"]) + int(args["qk_rope_dim"]),
        int(args["v_dim"]), kind)
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    run["detail"]["latent_" + kernel + "_roofline"] = {
        "roof": roof, "least_ms_a_call": 1e3 * least, "device_s": seconds,
        "calls": calls,
        "calls_a_layer_a_step": calls / (rec["traced"]["steps"] * layers)}
    return 100.0 * least * calls / seconds


def read(run):
    return kernel_share(run, "flash_fwd", "fwd")
