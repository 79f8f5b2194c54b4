"""Device-resident K-step training loop (ISSUE 20).

One `Executor.run(steps_per_dispatch=K)` compiles a SINGLE program that
runs K training steps via `jax.lax.scan` over a leading-stacked feed
block: the state carry stays resident in HBM (donated, exactly like the
single-step path), per-step PRNG keys are derived ON DEVICE from the
same `fold_in(PRNGKey(seed), step)` stream the sequential path uses, and
fetches come back stacked `(K, ...)` (or last-only).  The per-dispatch
host overhead is paid once per K steps instead of once per step, which
is the whole point (`analysis/cost.step_loop_cost` prices it).

Bitwise contract: the fused loop is provably identical to K sequential
`run()` calls on every fetch and every written-back state value
(`loop_parity_report` of tools/hlo_analysis.py, gated in run_tests.sh).
That hinges on two choices here:

  * per-step keys are `fold_in(base, step0 + i)` — the SAME integer
    fold the sequential path computes on the host, not a
    `jax.random.split` tree (which would be a different stream);
  * the scan body IS the single-step trace (`Executor._make_step_fn`),
    not a re-derivation, so both paths lower op-for-op identically.

This module is the one sanctioned home of a `lax.scan` training loop in
`paddle_tpu/framework/` (tools/repo_lint.py rule 11): loop semantics,
RNG stream and carry classification live here once, instead of being
re-invented per call site.
"""

from __future__ import annotations

import warnings
from typing import Dict, List, Sequence

from . import dataflow

# fetch_every modes: "all" stacks every step's fetches (K, ...); "last"
# returns only the final step's (the common training case — loss curves
# sampled per dispatch, not per step)
FETCH_MODES = ("all", "last")

# op types a fused loop cannot contain: `save` writes host files after
# every step (the executor's post-step writeback is once per dispatch),
# `load` reads its file at trace time but the sequential path re-checks
# the file signature per run (a mid-loop rewrite would diverge), and the
# sub-block control-flow ops carry data-dependent trip counts/shapes the
# K-step scan cannot honour per step.
_HOST_IO_TYPES = ("save", "load")


def safety_report(program, block_id: int = 0) -> dict:
    """Static loop-safety verdict for one block, from descs alone.

    Returns ``{"safe": bool, "reasons": [str, ...]}``.  Unsafe programs
    make `Executor.run(steps_per_dispatch=K)` fall back LOUDLY to K
    sequential dispatches (same results, none of the overhead
    amortization) — see docs/step_loop.md for the full list.
    """
    block = program.blocks[block_id]
    reasons: List[str] = []
    for i, op in enumerate(block.ops):
        if op.type in _HOST_IO_TYPES:
            reasons.append(
                f"op #{i} {op.type!r}: host file I/O cannot ride a "
                f"device-resident loop")
        elif dataflow.sub_block_indices(op):
            reasons.append(
                f"op #{i} {op.type!r}: nested control-flow block "
                f"(data-dependent trip count/shape)")
    return {"safe": not reasons, "reasons": reasons}


def warn_unsafe(k: int, report: dict):
    """The loud part of the loud fallback."""
    head = "; ".join(report["reasons"][:3])
    more = len(report["reasons"]) - 3
    if more > 0:
        head += f"; +{more} more"
    warnings.warn(
        f"steps_per_dispatch={k} requested but the program is "
        f"loop-unsafe ({head}) — falling back to {k} sequential "
        f"dispatches (correct, but the per-dispatch overhead is not "
        f"amortized)", stacklevel=3)


def split_feeds(feeds: Dict[str, object], k: int) -> List[dict]:
    """Per-step feed dicts from a leading-stacked block (the sequential
    fallback's slicer)."""
    return [{n: v[i] for n, v in feeds.items()} for i in range(k)]


def check_stacked(feeds: Dict[str, object], k: int):
    """Every feed in a fused dispatch must carry the K leading dim."""
    for n, v in feeds.items():
        shape = getattr(v, "shape", None)
        if not shape or int(shape[0]) != k:
            raise ValueError(
                f"steps_per_dispatch={k}: feed {n!r} must be stacked "
                f"with leading dim {k} (one slice per step), got shape "
                f"{tuple(shape) if shape else shape} — stack K batches "
                f"(reader.decorator.prefetch does this) or drop "
                f"steps_per_dispatch")


def build_loop_fn(step_fn, rw_names: Sequence[str], k: int,
                  fetch_every: str = "all"):
    """Wrap a single-step trace into the K-step scan.

    `step_fn(state_w, state_r, feeds, rng_key) -> (fetches, new_state)`
    is exactly what the executor jits for one step; the loop function's
    signature adds the stacked feeds and the RNG stream origin:

        loop_fn(state_w, state_r, feeds_K, rng_base, step0)
            -> (fetches_K | fetches_last, final_state)

    The carry is the rw (donated) state; write-only state is scanned
    out and its LAST slice persisted — identical to "last write wins"
    over K sequential scope writebacks.
    """
    if fetch_every not in FETCH_MODES:
        raise ValueError(
            f"fetch_every={fetch_every!r}: use one of {FETCH_MODES}")
    import jax
    import jax.numpy as jnp

    rw = tuple(rw_names)

    def loop_fn(state_w, state_r, feeds, rng_base, step0):
        def body(carry, xs):
            i, feeds_i = xs
            # the sequential path folds the host-side step counter into
            # the base key per run; same integer fold here, on device
            key = jax.random.fold_in(rng_base, step0 + i)
            fetches, new_state = step_fn(carry, state_r, feeds_i, key)
            nxt = {n: new_state.get(n, carry[n]) for n in rw}
            rest = {n: v for n, v in new_state.items() if n not in nxt}
            return nxt, (fetches, rest)

        xs = (jnp.arange(k, dtype=jnp.int32), feeds)
        final_rw, (fetches_k, rest_k) = jax.lax.scan(body, state_w, xs)
        final_state = dict(final_rw)
        for n, v in rest_k.items():
            final_state[n] = v[-1]
        if fetch_every == "last":
            return {n: v[-1] for n, v in fetches_k.items()}, final_state
        return fetches_k, final_state

    return loop_fn
