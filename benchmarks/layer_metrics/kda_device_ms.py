"""kda_device_ms — device milliseconds a step in the Kimi-Delta-Attention
cores, forward, the scan's recomputed forward and backward: every
instruction the compiled program puts into `pdtpu.kda.conv` (the three
depthwise convolutions + SiLU, the l2 norm of q and k, the heads' split),
`pdtpu.kda.gates` (beta and the log-decay g a channel), `pdtpu.kda.scan`
(the cumulative gates, the decayed score matrices, the triangular inverse,
the `lax.scan` over the chunks and the output) or `pdtpu.kda.norm_gate`
(the per-head output norm times sigmoid of the gate), each at its self
time.  The projections around the core (`pdtpu.kda.project`) are NOT in
it: they are matrix products near their own least, and
`detail["kda_device_ms"]["project_ms_a_step"]` has them.  An event of the
scan counts whole (its products ARE the scan); an event of another part
that XLA fused into a projection counts by what it takes over the product's
own least (benchmarks/reduce/part_ms.py).  `parts` serves the scan's and
the gates' readers too.  Nothing to read where the program names no such
part (the parent of PR 58) or the trace lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

CORE = ("kda.conv", "kda.gates", "kda.scan", "kda.norm_gate")


def parts(run):
    """{part: seconds in the traced window} for CORE, 'core' (an event
    once, whatever parts it carries) and 'project'; None where there is
    nothing to read."""
    from harness import load_module

    M = load_module("reduce", "part_ms")
    got = M.events_of(run)
    if got is None:
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    out = dict.fromkeys(CORE + ("core", "project"), 0.0)
    events = 0
    for note, s, inside in got:
        mine = [p for p in CORE if p in note.scopes]
        if not note.own or not (mine or "kda.project" in note.scopes):
            continue
        events += 1
        if "kda.scan" not in mine and note.product_flops:
            if not mine:
                out["project"] += s
                continue
            s = max(0.0, s - note.product_flops / peak * inside)
        for p in mine:
            out[p] += s
        out["core"] += s
    return out if events and out["core"] > 0 else None


def read(run):
    got = parts(run)
    if got is None:
        return None
    steps = run["record"]["traced"]["steps"]
    run["detail"]["kda_device_ms"] = {
        **{p + "_ms_a_step": 1e3 * got[p] / steps for p in CORE},
        "project_ms_a_step": 1e3 * got["project"] / steps}
    return 1e3 * got["core"] / steps
