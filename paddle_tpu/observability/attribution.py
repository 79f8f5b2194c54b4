"""The op identity every compiled step carries: profile -> ProgramDesc.

:func:`op_scope` and :func:`part_scope` are the repo's ONE
``jax.named_scope`` mint (repo_lint rule 10).  The executor wraps every
lowered op in ``pdop__<type>__u<uid>`` (a grad op says whose gradient it
is: ``pdop__mul_grad__u<uid>``) and, inside it, in the ``pdtpu.<part>``
its desc names (attr ``part``; a grad op takes the forward op's), so each
HLO instruction's metadata traces back to the desc op and the model part
that produced it.  Always on: a scope is metadata of the traced program,
costs one context manager an op a TRACE and a step nothing.  On a chip the
compiled program's own metadata is read back from a profiler trace by the
benchmark (``benchmarks/reduce/op_scopes.py``: the xplane's HloProto joins
an event's instruction to these names); that traced run is where per-op
device time is read.
"""

from __future__ import annotations

import contextlib
import re

_SCOPE_FMT = "pdop__{type}__u{uid}"
_SCOPE_RE = re.compile(r"pdop__([A-Za-z0-9_]+)__u(\d+)")


def op_type(op) -> str:
    """The type an op is known by in scopes and counters: its own, and for
    a `generic_grad` the forward op's with `_grad` (`mul_grad`)."""
    if op.type == "generic_grad":
        return f"{op.attrs.get('__fwd_type__', 'generic')}_grad"
    return op.type


def op_part(op):
    """The model part an op's desc names (attr `part`; a `generic_grad`
    carries the forward op's; `<outer>/<inner>` under nested
    `Program.part_guard`s), or None."""
    attrs = op.attrs
    if op.type == "generic_grad":
        attrs = attrs.get("__fwd_attrs__") or {}
    return attrs.get("part") or None


def scope_name(op) -> str:
    """The per-op scope string: type (:func:`op_type`) + desc uid
    (core.py's per-program monotonic ``__uid__``), the same identity
    ctx.rng folds in; a grad op shares its forward op's uid."""
    return _SCOPE_FMT.format(type=op_type(op),
                             uid=int(op.attrs.get("__uid__", 0)))


@contextlib.contextmanager
def op_scope(op):
    """Wraps one op's lowering in the ``jax.named_scope`` that carries its
    desc identity and, inside it, in its model part's where its desc names
    one (:func:`op_part`), so forward and backward instructions both carry
    `pdtpu.<part>`.  Reached once an op a TRACE, never by a step of a
    compiled program."""
    import jax

    part = op_part(op)
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.named_scope(scope_name(op)))
        for name in (part or "").split("/"):   # nested guards, outer first
            if name:
                stack.enter_context(part_scope(name))
        yield


def part_scope(name: str):
    """A named part of the model (`pdtpu.<name>`, as the tracer's spans
    are named), nested under the op's own scope and never looking like
    one (`parse_scope` matches `pdop__...` only).  The executor opens the
    one an op's desc names (attr `part`: `lm.head`, `attn.rope`); an
    emitter with several stages worth telling apart in a trace (the
    dropless `moe` op's route / permute / experts / combine) wraps each
    in one.  Beside `op_scope`, so that named scopes have one home."""
    import jax

    return jax.named_scope("pdtpu." + name)


def parse_scope(text: str):
    """(op_type, uid) from any string carrying a scope name, else None.
    Greedy type match + the terminal ``__u<digits>`` keeps op types with
    underscores (elementwise_add) unambiguous."""
    m = _SCOPE_RE.search(text or "")
    if not m:
        return None
    return m.group(1), int(m.group(2))
