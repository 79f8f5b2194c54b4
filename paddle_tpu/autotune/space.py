"""Typed search space for the analyzer-guided autotuner (ISSUE 14).

A :class:`SearchSpace` is an ordered set of named :class:`Choice` axes;
its cartesian product enumerates :class:`Candidate` configurations.
Axis names follow the knob convention (``<namespace>.<field>`` for
kernel knobs resolved through :mod:`paddle_tpu.autotune.knobs`), plus
two program-level axes the measurement harness interprets itself:

  * ``remat`` — bool; True applies the desc-level blanket
    rematerialization pass (``memory_optimize(level=1)``) to the built
    program, exactly what the executor's winner pickup re-applies;
  * ``xla_flags`` — a curated flag string appended to XLA_FLAGS; a
    candidate whose flags differ from the current process's requires a
    fresh-process trial (flags bind at backend init).

The vocabulary is the Tensor Processing Primitives stance (PAPERS.md):
a small set of shape-legal kernel parameters, not a free-form grid —
block choices are generated against the actual tensor extents so the
space never contains a candidate the kernel would refuse.
"""

from __future__ import annotations

import hashlib
import itertools
import json
from typing import Dict, List, Sequence, Tuple

# axes whose effect is program/process-level, not a kernel knob
PROGRAM_AXES = ("remat", "xla_flags")

# curated XLA flag set (TPU): each entry is one candidate value of the
# xla_flags axis.  Kept deliberately short — flags multiply the space
# and each non-default value costs a fresh-process trial.
TPU_XLA_FLAG_CHOICES = (
    "",
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)


class Choice:
    """One named axis with a finite value tuple (first value = the
    default configuration's setting)."""

    __slots__ = ("name", "values")

    def __init__(self, name: str, values: Sequence):
        if not values:
            raise ValueError(f"axis {name!r} has no values")
        self.name = name
        self.values = tuple(values)

    def __repr__(self):
        return f"Choice({self.name!r}, {self.values!r})"


class Candidate:
    """One point of the space: a params dict + stable digest."""

    __slots__ = ("params", "digest")

    def __init__(self, params: Dict[str, object]):
        self.params = dict(params)
        blob = json.dumps(self.params, sort_keys=True,
                          separators=(",", ":"), default=str)
        self.digest = hashlib.sha256(blob.encode()).hexdigest()[:12]

    def knob_params(self) -> Dict[str, object]:
        """The kernel-knob subset (dotted names) — what a trial pins via
        ``knobs.trial_overrides``."""
        return {k: v for k, v in self.params.items()
                if k not in PROGRAM_AXES}

    def get(self, name, default=None):
        return self.params.get(name, default)

    def describe(self) -> str:
        return ",".join(f"{k}={v}" for k, v in sorted(self.params.items())
                        if v not in ("", None))

    def __repr__(self):
        return f"Candidate({self.describe() or 'default'})"


class SearchSpace:
    def __init__(self, axes: Sequence[Choice]):
        names = [a.name for a in axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate axis names in {names}")
        self.axes = list(axes)

    @property
    def size(self) -> int:
        n = 1
        for a in self.axes:
            n *= len(a.values)
        return n

    def default(self) -> Candidate:
        """The axis-first-values candidate — the configuration the
        framework runs with no tuning at all.  Winners are judged
        against its MEASURED time (acceptance: winner >= default)."""
        return Candidate({a.name: a.values[0] for a in self.axes})

    def candidates(self) -> List[Candidate]:
        out = []
        for combo in itertools.product(*(a.values for a in self.axes)):
            out.append(Candidate(dict(zip((a.name for a in self.axes),
                                          combo))))
        return out

    def __repr__(self):
        return (f"SearchSpace({len(self.axes)} axes, "
                f"{self.size} candidates)")


# ---------------------------------------------------------------------------
# axis builders


def flash_block_choices(T: int, defaults: Tuple[int, int] = (512, 1024),
                        menu: Sequence[int] = (128, 256, 512, 1024)
                        ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Legal (block_q values, block_k values) for sequence length T:
    128-aligned divisors of T from the menu (the kernel's Mosaic tile
    contract — see flash_attention._snap_block), default first.  A T
    that admits nothing (not 128-divisible) yields single-value axes so
    the space stays well-formed and the dense path is what runs."""

    def legal(default):
        vals = [b for b in menu if b <= T and T % b == 0 and b % 128 == 0]
        if not vals:
            return (default,)
        # default-equivalent first: the value the unsnapped default
        # would snap to, so Candidate/default() reflects reality
        snapped = max((b for b in vals if b <= default), default=vals[0])
        return tuple([snapped] + [v for v in vals if v != snapped])

    return legal(defaults[0]), legal(defaults[1])


def flash_space(T: int, remat: bool = True,
                xla_flags: Sequence[str] = ("",)) -> SearchSpace:
    """Standard space for a flash-attention training program: block
    sizes x remat on/off x curated flags."""
    bq, bk = flash_block_choices(T)
    axes = [Choice("flash_attention.block_q", bq),
            Choice("flash_attention.block_k", bk)]
    if remat:
        axes.append(Choice("remat", (False, True)))
    axes.append(Choice("xla_flags", tuple(xla_flags) or ("",)))
    return SearchSpace(axes)


def paged_space(max_ctx: int = 1024) -> SearchSpace:
    """Paged-attention tile space: tokens per KV page (the decode
    kernel's K/V tile and the allocator's granularity)."""
    sizes = [s for s in (16, 32, 64) if s <= max_ctx]
    return SearchSpace([
        Choice("paged_attention.page_size", tuple(sizes)),
    ])


def spec_decode_space(n_layers: int = 4,
                      max_new: int = 12) -> SearchSpace:
    """Speculative-decoding serve-loop space (ISSUE 18): proposal depth
    K x draft tower depth.  K is bounded by the per-request new-token
    budget (a K >= max_new round could never accept its tail) and the
    draft must be strictly shallower than the target (equal depth is
    the target itself — all cost, no speedup).  Defaults first: K=4 and
    the half-depth draft, matching ``knobs.speculation_k`` /
    ``spec_draft_layers``."""
    ks = [k for k in (4, 2, 8, 1) if 1 <= k < max_new] or [1]
    drafts = [d for d in (max(1, n_layers // 2), 1, n_layers - 1)
              if 1 <= d < n_layers]
    drafts = list(dict.fromkeys(drafts)) or [1]
    return SearchSpace([
        Choice("spec_decode.speculation_k", tuple(ks)),
        Choice("spec_decode.draft_layers", tuple(drafts)),
    ])


def step_loop_space(ks: Sequence[int] = (1, 2, 4, 8)) -> SearchSpace:
    """Fused K-step dispatch axis (framework/step_loop.py): how many
    training steps one device dispatch runs via `lax.scan`.  K=1 first
    — the plain dispatch-per-step path is the default an un-tuned
    `Executor.run` takes.  The winner lands under the
    ("step_loop", {}) site that ``knobs.steps_per_dispatch`` resolves
    for callers that opt in with ``store=True``."""
    return SearchSpace([
        Choice("step_loop.steps_per_dispatch", tuple(ks)),
    ])


def mlp_depth_space(depths: Sequence[int] = (16, 4, 1)) -> SearchSpace:
    """Depth-vs-width axis at ~constant hidden FLOPs (depth * width^2
    fixed): the op-COUNT workload.  The deepest stack is the default
    (first value) on purpose — the raw roofline prices it cheapest
    (slightly fewer projection FLOPs/bytes), while the measured winner
    on a dispatch-overhead-dominated host is the shallow build, so this
    axis is rankable only by a cost layer that charges per-op overhead
    (the calibration store's affine fit)."""
    return SearchSpace([Choice("mlp.depth", tuple(depths))])


def remat_space(xla_flags: Sequence[str] = ("",)) -> SearchSpace:
    """Generic program space (saved models): remat on/off x flags."""
    return SearchSpace([
        Choice("remat", (False, True)),
        Choice("xla_flags", tuple(xla_flags) or ("",)),
    ])
