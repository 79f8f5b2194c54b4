"""ssm_device_ms — device milliseconds a step in the Mamba mixers' own
work, forward, the segments' recomputed forward and backward: every
instruction the compiled program puts into `pdtpu.mixer.mamba`
(`decoder_lm`'s 'mamba' layers) at its self time, but for the two large
projections W_in (`pdtpu.ssm.in_proj`) and W_out (`pdtpu.ssm.gate_out`'s
product): of an event of those two parts that holds a matrix product, what
it takes over the product's own least counts (benchmarks/reduce/part_ms.py's
rule: the gate's SiLU fused into W_out's product, say).  So it is the
convolution (`ssm.conv`), the step sizes' and B's and C's projections and
softplus (`ssm.xdt`), the selective scan (`ssm.scan`) and the gate.
`detail["ssm_device_ms"]` has each part and the two projections.  `parts`
serves the scan's readers too.  Nothing to read where the program names no
such part (the parent of PR 52) or the trace lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

STAGES = ("ssm.in_proj", "ssm.conv", "ssm.xdt", "ssm.scan", "ssm.gate_out")
PROJECTIONS = ("ssm.in_proj", "ssm.gate_out")


def parts(run):
    """{stage: seconds in the traced window} for STAGES, 'core' (what the
    metric reads) and 'projections' (W_in's and W_out's products at their
    least); None where there is nothing to read."""
    from harness import load_module

    got = load_module("reduce", "part_ms").events_of(run)
    if got is None:
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    out = dict.fromkeys(STAGES + ("core", "projections"), 0.0)
    events = 0
    for note, s, inside in got:
        if not note.own or "mixer.mamba" not in note.scopes:
            continue
        events += 1
        mine = [p for p in STAGES if p in note.scopes]
        if note.product_flops and any(p in PROJECTIONS for p in mine):
            least = min(s, note.product_flops / peak * inside)
            out["projections"] += least
            s -= least
        for p in mine:
            out[p] += s
        out["core"] += s
    return out if events and out["core"] > 0 else None


def read(run):
    got = parts(run)
    if got is None:
        return None
    steps = run["record"]["traced"]["steps"]
    run["detail"]["ssm_device_ms"] = {
        **{p + "_ms_a_step": 1e3 * got[p] / steps for p in STAGES},
        "projections_least_ms_a_step": 1e3 * got["projections"] / steps}
    return 1e3 * got["core"] / steps
