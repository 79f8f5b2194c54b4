"""One chip's share of an expert-parallel decoder in a profiler trace, with
the shapes taken from the configuration's `train.args`: the builder's own
argument names (`seq_len`, `top_k`, `held_experts`, `num_experts`,
`buffer_rows`, `dim`, `expert_dim`), alike for every share, and not a
published key name that changes from family to family (moe_share_ops.py
reads DeepSeek's, and takes T from `max_position_embeddings`, which here is
the published 128000 and not the 8192 trained).  The next share needs no
twin of this file.

The routed part has moe_share_ops.py's kinds, found by its rules (the
grouped matmul kernels by name, the buffer by its rows, the pairs by tokens
x top_k) with one more: where the buffer is as long as the token stream, a
tensor of its rows is the buffer's only in an instruction that the compiled
program puts into the expert layer's `pdtpu.moe.permute`, `.experts` or
`.combine` scope (hlo_scopes.py: the trace carries the program's
metadata), and an instruction on [tokens, ...] that it puts nowhere there
is the residual stream's.  A buffer of another length than the stream is
found by its rows alone, as moe_share_ops.py finds it.  A share without a
shared expert reads 0 there.

The gated short convolution (`conv_kernel` in the args) is every
instruction the program itself puts into `pdtpu.conv.gate` or
`pdtpu.conv.taps`, forward and backward (a copy XLA makes of the op's
result for whoever reads it next is not: its scopes are not its own).
Where XLA fused some of that into a matrix product beside it (dC and the
taps' gradient ride in `dOut W_out^T`), the instruction's time is the
product's too: of it counts what is over the product's own least, its
FLOPs over the bf16 peak.  That is the most the convolution can have cost
there (the product's own inefficiency, and whatever else rides in it, are
in it too), so `short_conv_device_ms` is an upper bound and
`short_conv_hbm_roofline` a lower one by that much, never the other way:
nothing of the convolution's work is left out of the time its least is
divided by, and a fusion into a product cannot raise the share.  The
widening of the op's input is no instruction of its own: XLA writes the
input projection's result as float32 (a product's epilogue, in no scope).

`classify(text, dims, note)` and `classify_conv(note)` are pure (a test
feeds them recorded instruction texts and notes); `of_run` adds up the
first device's "XLA Ops" line, clipped to the traced window.
"""

from __future__ import annotations

ARGS = ("seq_len", "top_k", "held_experts", "num_experts", "buffer_rows",
        "dim", "expert_dim")
ROUTED = frozenset(("moe.permute", "moe.experts", "moe.combine"))
CONV = frozenset(("conv.gate", "conv.taps"))


def dims_of(config: dict, batch: int):
    """The shapes to look for, from the configuration's `train.args`; None
    where they do not describe a share of an expert layer."""
    args = config.get("train", {}).get("args", {})
    if any(args.get(k) is None for k in ARGS):
        return None
    tokens = int(batch) * int(args["seq_len"])
    return {"tokens": tokens, "rows": int(args["buffer_rows"]),
            "pairs": tokens * int(args["top_k"]),
            "held": int(args["held_experts"]),
            "experts": int(args["num_experts"]),
            "dim": int(args["dim"]), "expert_dim": int(args["expert_dim"]),
            "shared_dim": int(args.get("shared_experts") or 0)
            * int(args["expert_dim"]),
            "conv_kernel": int(args.get("conv_kernel") or 0)}


def classify(text: str, dims: dict, note):
    """The kind of one HLO instruction (its whole text; `note` its
    hlo_scopes.py Note): one of moe_share_ops.py's KINDS, or None."""
    from harness import load_module

    M = load_module("reduce", "moe_share_ops")
    kind = M.classify(text, dims)
    if (kind == "buffer" and dims["rows"] == dims["tokens"]
            and not note.scopes & ROUTED):
        # the stream's, not the buffer's: what else its shapes make it
        kind = M.classify(text, dict(dims, rows=-1))
    return kind


def classify_conv(note) -> str:
    """'conv' for an instruction of a gated short convolution, 'product'
    for a matrix product that some of one was fused into, else None."""
    if not (note.own and note.scopes & CONV):
        return None
    return "product" if note.product_flops else "conv"


def sums(evs, window, dims: dict, notes: dict, flops_per_s: float) -> dict:
    """{kind: seconds} of moe_share_ops.py's KINDS and {"calls": grouped
    matmul kernels}, and of the convolutions {"conv": seconds (a product's
    over its own least), "conv_events", "conv_products": the products
    among them, "conv_products_s": their whole seconds}, of the events
    inside `window` = (start_ns, end_ns), each clipped to it."""
    from harness import load_module

    H = load_module("reduce", "hlo_scopes")
    lo, hi = window
    out = {k: 0.0 for k in load_module("reduce", "moe_share_ops").KINDS}
    out.update(calls=0, conv=0.0, conv_events=0, conv_products=0,
               conv_products_s=0.0)
    for text, start, dur in evs:
        seconds = (min(start + dur, hi) - max(start, lo)) / 1e9
        if seconds <= 0:
            continue
        note = notes.get(H.name_of(text), H.NOTHING)
        kind = classify(text, dims, note)
        if kind is not None:
            out[kind] += seconds
            out["calls"] += (kind == "grouped_matmul" and "metadata"
                             not in text.split(" = ", 1)[0])
        part = dims["conv_kernel"] and classify_conv(note)
        if part:
            out["conv_events"] += 1
            if part == "product":
                out["conv_products"] += 1
                out["conv_products_s"] += seconds
                # the product's least, cut as the event was clipped
                least = note.product_flops / flops_per_s * seconds * 1e9 / dur
                seconds = max(0.0, seconds - least)
            out["conv"] += seconds
    return out


def of_run(run):
    """`sums` for a reader; None where the run has no trace, its
    configuration holds no share, or the trace no grouped kernel or not
    the program's metadata."""
    from harness import load_module

    path = run["record"].get("trace_path")
    if not path or run.get("trace") is None:
        return None
    dims = dims_of(run["ctx"].config, run["record"]["batch"])
    if dims is None:
        return None
    if "share_seconds" not in run["detail"]:
        notes = load_module("reduce", "hlo_scopes").of_trace(path)
        got = sums(load_module("reduce", "moe_ops").events(path),
                   run["tracemod"].window_of(run["trace"]), dims, notes,
                   run["peaks"]["bf16_flops_per_s"])
        if not got["calls"] or not notes:
            return None
        run["detail"]["share_seconds"] = got
    return run["detail"]["share_seconds"]
