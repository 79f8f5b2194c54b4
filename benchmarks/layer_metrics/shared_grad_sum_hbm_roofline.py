"""shared_grad_sum_hbm_roofline — the least time the chip could take for the
adds of a looped tower's gradient parts by the bytes they must move, over
their device time (`shared_grad_sum_device_ms`'s rows, so the two sides
measure the same work).  The least (benchmarks/flops_ouro.py
`shared_grad_sum_cost`): every part of every parameter that all passes read
is read once and the sum written once, (parts + 1) x 2 bytes a parameter in
bf16 over the HBM peak of benchmarks/peaks.json; its additions over the
bf16 peak are a fortieth of that, so HBM binds.  The parameters and the
parts are the configuration's (`flops.args`: the blocks', the final gain,
the head and the gate, whose 2049 numbers have a part fewer; `passes`).
Nothing to read where ANY of the adds ride in other fusions (those move
none of these bytes, and the least is of all the adds: `rides_in` of
`shared_grad_sum_device_ms`'s detail says where) or the configuration has
no `passes`."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    got = load_module("layer_metrics", "shared_grad_sum_device_ms").of_run(
        run)
    args = run["ctx"].config.get("flops", {}).get("args", {})
    if (got is None or got["ms"] <= 0 or got["rides_in"]
            or not args.get("passes")):
        return None   # the least is of ALL the adds: never over some
    F = load_module(".", "flops_ouro")
    elements = F.shared_parameters(**{k: int(args[k]) for k in (
        "dim", "dense_dim", "n_heads", "n_kv_heads", "head_dim", "n_layers",
        "vocab")})
    flops, nbytes = F.shared_grad_sum_cost(elements, int(args["passes"]))
    least, roof = run["flops"].roofline_seconds(flops, nbytes, run["peaks"])
    run["detail"]["shared_grad_sum_hbm_roofline"] = {
        "roof": roof, "least_ms_a_step": 1e3 * least,
        "shared_parameters": elements, "parts": int(args["passes"]),
        "device_ms": got["ms"]}
    return 100.0 * 1e3 * least / got["ms"]
