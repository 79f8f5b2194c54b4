"""expert_share_grouped_matmul_roofline — the least time the chip could
take for the grouped matmuls of the held experts in the traced window over
the device time of the kernels that ran them (`ragged-dot-*` in the trace:
three forward and six backward products a layer a step).  The least of
one: the larger of its FLOPs over the bf16 peak and its bytes over the HBM
peak (benchmarks/flops_moe.py `grouped_matmul_cost`) at the ROWS THAT HOLD
WORK, not the buffer's: tokens x top_k x held / experts, what even routing
puts on the held experts, through [hidden, expert width] matrices, every
held expert's matrix moved once a product.
`moe_share_grouped_matmul_roofline`'s twin with the shapes from
`train.args` (benchmarks/reduce/share_ops.py), for any share."""

LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "share_ops")
    got = M.of_run(run)
    if got is None or got["grouped_matmul"] <= 0:
        return None
    args = run["ctx"].config["train"]["args"]
    d = M.dims_of(run["ctx"].config, run["record"]["batch"])
    rows = d["pairs"] * d["held"] // d["experts"]
    flops, nbytes = load_module(".", "flops_moe").grouped_matmul_cost(
        rows, d["dim"], d["expert_dim"], d["held"])
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    expert_layers = len(args["layer_types"]) - int(args["dense_layers"])
    run["detail"]["expert_share_grouped_matmul_roofline"] = {
        "roof": roof, "rows_with_work": rows, "buffer_rows": d["rows"],
        "least_ms_a_call": 1e3 * least, "device_s": got["grouped_matmul"],
        "calls": got["calls"],
        "calls_a_layer_a_step": got["calls"] / (
            run["record"]["traced"]["steps"] * expert_layers)}
    return 100.0 * least * got["calls"] / got["grouped_matmul"]
