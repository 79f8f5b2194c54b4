"""mtp_device_ms — device milliseconds a step in the
multi-token-prediction module, forward and backward: every instruction the
compiled program puts into a part `pdtpu.mtp.*` (`mtp.project`: the next
tokens' embedding, the two norms and the projection; `mtp.block`: the
module's own block, its hyper-connections, attention and expert layer;
`mtp.head` and `mtp.loss`: its final norm and its own pass through the
head and the loss, which carry `pdtpu.lm.head` / `pdtpu.lm.loss` INSIDE
those, so `head_loss_device_ms` counts them too), each at its self time
(benchmarks/reduce/part_ms.py).  An event counts whole but for a matrix
product that something else was fused into: the module's products ARE its
work.  `detail["mtp_device_ms"]["of_which_hc_s"]` is what of it
`hc_device_ms` counts as well (the module's block has two
hyper-connections of the step's twelve), so the two are not added without
it.  The optimizer's update of the module's weights is not in it.  Nothing
to read where the program names no such part (the parent of PR 39)."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    M = load_module("reduce", "part_ms")
    got = M.events_of(run)
    if got is None:
        return None
    mine, hc = M.under("mtp."), M.under("hc.")
    total = both = 0.0
    events = 0
    for note, s, _ in got:
        if note.own and mine(note.scopes):
            events += 1
            total += s
            both += s if hc(note.scopes) else 0.0
    if not events:
        return None
    run["detail"]["mtp_device_ms"] = {"s": total, "events": events,
                                      "of_which_hc_s": both}
    return 1e3 * total / run["record"]["traced"]["steps"]
