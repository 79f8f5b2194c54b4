"""The expert layer's device events in a profiler trace, found by what the
HLO instruction says.

`jax.named_scope("pdtpu.moe.*")` reaches the compiled HLO's metadata, but
the v5e's xplane does not carry it (an "XLA Ops" event has its instruction
text and three timing stats, nothing else: my chip run, PR 26), and XLA
names its fusions `fusion.N`.  What an event does carry is the whole
instruction with its operand shapes, and the expert layer's tensors have
shapes nothing else in the step has:

  grouped_matmul  the Mosaic calls XLA:TPU makes of `lax.ragged_dot`:
                  `ragged-dot-none.N` (and their `ragged-dot-metadata.N`)
  relayout        copies and transposes of the stacked expert weights
                  [E, D, H] / [E, H, D] in the compute dtype (the kernel
                  wants another layout for a backward product); the Adam
                  fusions over the same shapes carry float32 moments and
                  are the optimizer's, not the layer's
  slots           every other instruction that touches a tensor with the
                  T * top_k token-slot rows: the gathers by the sort, the
                  SiLU-gate product, the weighted combine, their backward

The router's own instructions (its [T, hidden] x [hidden, E] matmul, the
softmax, the top-k, the two auxiliary losses; 0.3 ms of a 113 ms step) are
NOT found: the [T, E] logits share their shape with RoPE's cos and sin
tables wherever E is half the head size, as in OLMoE (64 and 128), so a
rule on that shape would count RoPE.  They are left out and said so.

`classify(text, dims)` is pure (a test feeds it recorded instruction
texts); `sums(events(path), window, dims)` adds up the first device's
"XLA Ops" line, clipped to the traced window.
"""

from __future__ import annotations

import re

KINDS = ("grouped_matmul", "relayout", "slots")
GROUPED = re.compile(r"^%?ragged-dot")
OPS_LINE = "XLA Ops"

_loaded: dict = {}


def dims_of(config: dict, batch: int) -> dict:
    """The shapes to look for, from a configuration's published keys."""
    tokens = int(batch) * int(config["max_position_embeddings"])
    return {"slots": tokens * int(config["num_experts_per_tok"]),
            "experts": int(config["num_experts"]),
            "dim": int(config["hidden_size"]),
            "expert_dim": int(config["intermediate_size"])}


def classify(text: str, dims: dict):
    """The kind of one HLO instruction (its whole text), or None."""
    head = text.split(" = ", 1)[0]
    if GROUPED.match(head):
        return "grouped_matmul"
    E, D, H = dims["experts"], dims["dim"], dims["expert_dim"]
    stacked = [f"[{E},{D},{H}]", f"[{E},{H},{D}]"]
    if any("f32" + s in text for s in stacked):
        return None                      # Adam's moments: the optimizer
    op = head.lstrip("%").split(".")[0]
    if op in ("copy", "transpose") and any(
            s in text for s in stacked):
        return "relayout"
    if re.search(r"\[%d[,\]]" % dims["slots"], text):
        return "slots"
    return None


def events(path: str) -> list:
    """[[instruction text, start_ns, duration_ns]] of the first device's
    "XLA Ops" line; parsed once per process."""
    if path not in _loaded:
        from jax.profiler import ProfileData

        out = []
        planes = sorted((p for p in ProfileData.from_file(path).planes
                         if re.match(r"^/device:TPU:\d+$", p.name)),
                        key=lambda p: p.name)
        for line in (planes[0].lines if planes else ()):
            if line.name == OPS_LINE:
                out = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events]
        _loaded[path] = out
    return _loaded[path]


def sums(evs, window, dims: dict) -> dict:
    """{kind: seconds} and {"calls": grouped matmul kernels} of the events
    inside `window` = (start_ns, end_ns), each clipped to it."""
    lo, hi = window
    out = {k: 0.0 for k in KINDS}
    calls = 0
    for text, start, dur in evs:
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        kind = classify(text, dims)
        if kind is None:
            continue
        out[kind] += (b - a) / 1e9
        if kind == "grouped_matmul" and "metadata" not in text.split(
                " = ", 1)[0]:
            calls += 1
    out["calls"] = calls
    return out


def of_run(run):
    """`sums` for a reader: None where the run has no trace, or its
    configuration no expert layer."""
    path = run["record"].get("trace_path")
    cfg = run["ctx"].config
    if not path or run.get("trace") is None or "num_experts" not in cfg:
        return None
    if "moe_seconds" not in run["detail"]:
        got = sums(events(path), run["tracemod"].window_of(run["trace"]),
                   dims_of(cfg, run["record"]["batch"]))
        if not got["calls"]:
            return None
        run["detail"]["moe_seconds"] = got
    return run["detail"]["moe_seconds"]
