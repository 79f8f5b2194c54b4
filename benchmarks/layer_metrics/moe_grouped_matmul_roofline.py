"""moe_grouped_matmul_roofline — the least time the chip could take for
the grouped expert matmuls of the traced window over the device time of
the kernels that ran them (`ragged-dot-*` in the trace: three forward and
six backward products a layer a step).  The least of one: the larger of
its FLOPs over the bf16 peak and its bytes over the HBM peak
(benchmarks/flops_moe.py `grouped_matmul_cost`: tokens x top_k rows, which
the run's `routed_slots` check holds exactly, through [hidden, expert
width] matrices, every expert's matrix moved once a product), times the
kernels the trace counts.  The relayout copies the kernels ask for are not
in the denominator; `moe_permute_device_ms` has them."""

LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "moe_ops")
    got = M.of_run(run)
    if got is None or got["grouped_matmul"] <= 0:
        return None
    d = M.dims_of(run["ctx"].config, run["record"]["batch"])
    F = load_module(".", "flops_moe")
    flops, nbytes = F.grouped_matmul_cost(d["slots"], d["dim"],
                                          d["expert_dim"], d["experts"])
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    layers = int(run["ctx"].config["num_hidden_layers"])
    run["detail"]["moe_grouped_matmul_roofline"] = {
        "roof": roof, "least_ms_a_call": 1e3 * least,
        "device_s": got["grouped_matmul"], "calls": got["calls"],
        "calls_a_layer_a_step": got["calls"] / (
            run["record"]["traced"]["steps"] * layers)}
    return 100.0 * least * got["calls"] / got["grouped_matmul"]
