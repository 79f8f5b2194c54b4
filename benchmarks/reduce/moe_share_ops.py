"""A share of an expert layer in a profiler trace, found by what the HLO
instruction says: the twin of moe_ops.py (not edited) for a configuration
that holds some of its experts and computes their pairs in a static buffer
(`moonlight-16b-a3b`).  An "XLA Ops" event carries its whole instruction
with the operand shapes, and three of the layer's shapes are nothing
else's in the step:

  grouped_matmul  the kernels of the grouped matmuls, forward `lax.ragged_dot`
                  (`ragged-dot-none.N`) and the two Pallas backward kernels
                  (`ragged-dot-dlhs`, `ragged-dot-drhs`), with their
                  metadata calls
  buffer          every other instruction on a tensor with the buffer's
                  rows: the gather of the tokens into it, the SiLU-gate
                  product, the weighting, the scatter-add back and their
                  backward
  pairs           every instruction on a tensor with tokens x top_k
                  entries: the sort of the pairs by held expert, the
                  counts, the weights' gather
  shared          every instruction on [tokens, shared width]: the shared
                  expert's nine matrix products and its SiLU-gate product

The router's own [tokens, experts] instructions are not found, for the
reason moe_ops.py gives (RoPE's tables share the shape where the rotary
width is the number of experts, as here: 64).

`classify(text, dims)` is pure (a test feeds it recorded instruction
texts); `of_run` adds up the first device's "XLA Ops" line, clipped to the
traced window, with moe_ops.py's `events`.
"""

from __future__ import annotations

import re

KINDS = ("grouped_matmul", "buffer", "pairs", "shared")
LAYER_KINDS = ("grouped_matmul", "buffer", "pairs")   # the routed part
GROUPED = re.compile(r"^%?ragged-dot")


def dims_of(config: dict, batch: int):
    """The shapes to look for, from a configuration's keys; None where it
    holds no share of an expert layer."""
    if "share" not in config or "moe_intermediate_size" not in config:
        return None
    tokens = int(batch) * int(config["max_position_embeddings"])
    return {"tokens": tokens,
            "rows": int(config["share"]["buffer_rows"]),
            "pairs": tokens * int(config["num_experts_per_tok"]),
            "held": int(config["n_routed_experts"]),
            "dim": int(config["hidden_size"]),
            "expert_dim": int(config["moe_intermediate_size"]),
            "shared_dim": int(config["n_shared_experts"])
            * int(config["moe_intermediate_size"])}


def classify(text: str, dims: dict):
    """The kind of one HLO instruction (its whole text), or None."""
    if GROUPED.match(text.split(" = ", 1)[0]):
        return "grouped_matmul"
    if re.search(r"\[%d[,\]]" % dims["rows"], text):
        return "buffer"
    if re.search(r"\[%d[,\]]" % dims["pairs"], text):
        return "pairs"
    if re.search(r"\[(\d+,)?%d,%d\]" % (dims["tokens"], dims["shared_dim"]),
                 text):
        return "shared"
    return None


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
    """`sums` for a reader: None where the run has no trace, its
    configuration holds no share, or the trace no grouped kernel."""
    from harness import load_module

    path = run["record"].get("trace_path")
    if not path or run.get("trace") is None:
        return None
    dims = dims_of(run["ctx"].config, run["record"]["batch"])
    if dims is None:
        return None
    if "moe_share_seconds" not in run["detail"]:
        got = sums(load_module("reduce", "moe_ops").events(path),
                   run["tracemod"].window_of(run["trace"]), dims)
        if not got["calls"]:
            return None
        run["detail"]["moe_share_seconds"] = got
    return run["detail"]["moe_share_seconds"]
