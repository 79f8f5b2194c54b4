"""gdn_device_ms — device milliseconds a step in the gated-DeltaNet cores,
forward, the scan's recomputed forward and backward: every instruction the
compiled program puts into `pdtpu.gdn.conv` (the depthwise convolution +
SiLU over q, k and v, the l2 norm of q and k, the heads' split),
`pdtpu.gdn.gates` (beta and the log-decay g), `pdtpu.gdn.scan` (the chunks'
score products, the triangular inverse, the `lax.scan` over the chunks and
the output) or `pdtpu.gdn.norm_gate` (the per-head output norm times
SiLU(z)), each at its self time.  The projections around the core
(`pdtpu.gdn.project`) are NOT in it: they are matrix products near their
own least, and `detail["gdn_device_ms"]["project_ms_a_step"]` has them.
An event of the scan counts whole (its products ARE the scan); an event of
another part that XLA fused into a projection counts by what it takes over
the product's own least (benchmarks/reduce/part_ms.py).  `parts` serves
the scan's and the convolution's readers too.  Nothing to read where the
program names no such part (the parent of PR 48) or the trace lacks the
program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

CORE = ("gdn.conv", "gdn.gates", "gdn.scan", "gdn.norm_gate")


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
        if not note.own or not (mine or "gdn.project" in note.scopes):
            continue
        events += 1
        if "gdn.scan" not in mine and note.product_flops:
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
    run["detail"]["gdn_device_ms"] = {
        **{p + "_ms_a_step": 1e3 * got[p] / steps for p in CORE},
        "project_ms_a_step": 1e3 * got["project"] / steps}
    return 1e3 * got["core"] / steps
