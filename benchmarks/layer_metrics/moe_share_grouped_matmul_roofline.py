"""moe_share_grouped_matmul_roofline — the least time the chip could take
for the grouped matmuls of the held experts in the traced window over the
device time of the kernels that ran them (`ragged-dot-*` in the trace:
three forward and six backward products a layer a step).  The least of
one: the larger of its FLOPs over the bf16 peak and its bytes over the HBM
peak (benchmarks/flops_moe.py `grouped_matmul_cost`) at the ROWS THAT HOLD
WORK, not the buffer's: tokens x top_k x held / experts, what even routing
puts on the held experts (the run's fetched `held_pairs` has a step's own
count), through [hidden, expert width] matrices, every held expert's
matrix moved once a product.  `moe_grouped_matmul_roofline`'s twin for a
share."""

LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "moe_share_ops")
    got = M.of_run(run)
    if got is None or got["grouped_matmul"] <= 0:
        return None
    cfg = run["ctx"].config
    d = M.dims_of(cfg, run["record"]["batch"])
    rows = d["pairs"] * d["held"] // int(cfg["deployment"]["router_outputs"])
    flops, nbytes = load_module(".", "flops_moe").grouped_matmul_cost(
        rows, d["dim"], d["expert_dim"], d["held"])
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    expert_layers = int(cfg["num_hidden_layers"]) - int(
        cfg["first_k_dense_replace"])
    run["detail"]["moe_share_grouped_matmul_roofline"] = {
        "roof": roof, "rows_with_work": rows, "buffer_rows": d["rows"],
        "least_ms_a_call": 1e3 * least, "device_s": got["grouped_matmul"],
        "calls": got["calls"],
        "calls_a_layer_a_step": got["calls"] / (
            run["record"]["traced"]["steps"] * expert_layers)}
    return 100.0 * least * got["calls"] / got["grouped_matmul"]
