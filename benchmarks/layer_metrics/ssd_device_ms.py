"""ssd_device_ms — device milliseconds a step in the Mamba-2 cores, forward,
the segment's recomputed forward and backward: every instruction the
compiled program puts into `pdtpu.ssd.conv` (the four taps, bias and SiLU
over the x, B and C columns), `pdtpu.ssd.dt` (Delta = softplus(dt + bias),
A), `pdtpu.ssd.scan` (the chunks' cumulative log-decays, the decayed score
tiles, the chunks' summaries, the `lax.scan` of the state over the chunks,
the read-out, the D term) or `pdtpu.ssd.norm` (the gate and the RMSNorm over
all of d_inner), each at its self time.  The projections around the core
(`pdtpu.ssm.in_proj`, and W_out) are NOT in it: they are matrix products
near their own least, and `detail["ssd_device_ms"]["in_proj_ms_a_step"]`
has the first.  An event of the scan counts whole (its products ARE the
scan); an event of another part that XLA fused into a projection counts by
what it takes over the product's own least
(benchmarks/reduce/part_ms.py).  `parts` serves the three readers beside
it.  Nothing to read where the program names no such part (the parent of
PR 67) or the trace lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

CORE = ("ssd.conv", "ssd.dt", "ssd.scan", "ssd.norm")


def parts(run):
    """{part: seconds in the traced window} for CORE, 'core' (an event
    once, whatever parts it carries) and 'in_proj'; None where there is
    nothing to read."""
    from harness import load_module

    M = load_module("reduce", "part_ms")
    got = M.events_of(run)
    if got is None:
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    out = dict.fromkeys(CORE + ("core", "in_proj"), 0.0)
    for note, s, inside in got:
        mine = [p for p in CORE if p in note.scopes]
        if not note.own:
            continue
        if "ssd.scan" not in mine and note.product_flops:
            if not mine:
                if "ssm.in_proj" in note.scopes:
                    out["in_proj"] += s
                continue
            s = max(0.0, s - note.product_flops / peak * inside)
        for p in mine:
            out[p] += s
        if mine:
            out["core"] += s
    return out if out["core"] > 0 else None


def read(run):
    got = parts(run)
    if got is None:
        return None
    steps = run["record"]["traced"]["steps"]
    run["detail"]["ssd_device_ms"] = {
        **{p + "_ms_a_step": 1e3 * got[p] / steps for p in CORE},
        "in_proj_ms_a_step": 1e3 * got["in_proj"] / steps}
    return 1e3 * got["core"] / steps
