"""loop_pass_device_ms — device milliseconds a step in ONE pass's blocks of a
looped tower, forward, replay and backward: the self time
(benchmarks/reduce/part_ms.py `events_of`) of every event whose instruction
the compiled program puts under a pass's part (`pdtpu.loop.a`,
`pdtpu.loop.b`, ...: models/transformer.py names a pass's ops by a LETTER,
hlo_scopes.py reads a part's name without digits) and under none of the
head's, the loss's or the gate's (`lm.head`, `lm.loss`, `loop.gate`:
`head_loss_device_ms` has the first two), over the number of passes.  The
products count WHOLE: the blocks' matrix work is the pass.  `detail`: what
carries ONE pass's letter alone, pass by pass, and `mixed_ms`, the events
that carry two or more: XLA keeps ONE computation for the fusions that
equal passes make alike, so a later pass's event names the first's
instructions inside beside its own (the pass that is named inside reads
high, the others low, their sum with `mixed_ms` is the blocks'), and a
shared parameter's parts are added across passes; `head_loss_gate_ms`, what
all passes' heads, losses and gates take.  Nothing to read where the
program names no pass (no looped tower; the parent of PR 71) or the trace
lacks the program's metadata."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

BESIDE = frozenset(("lm.head", "lm.loss", "loop.gate"))
A_PASS = "loop."   # + one letter: `loop.gate` and `loop.exit` are no pass


def read(run):
    from harness import load_module

    got = load_module("reduce", "part_ms").events_of(run)
    if got is None:
        return None
    steps = run["record"]["traced"]["steps"]
    passes, beside, mixed = {}, 0.0, 0.0
    for note, s, _ in got:
        letters = sorted(p for p in note.scopes if p.startswith(A_PASS)
                         and len(p) == len(A_PASS) + 1)
        if not letters or not note.own:
            continue
        if note.scopes & BESIDE:
            beside += s
        elif len(letters) > 1:
            mixed += s
        else:
            passes[letters[0]] = passes.get(letters[0], 0.0) + s
    if not passes:
        return None
    each = {k: 1e3 * v / steps for k, v in sorted(passes.items())}
    run["detail"]["loop_pass_device_ms"] = {
        "passes": each, "head_loss_gate_ms": 1e3 * beside / steps,
        "mixed_ms": 1e3 * mixed / steps}
    return (sum(each.values()) + 1e3 * mixed / steps) / len(each)
