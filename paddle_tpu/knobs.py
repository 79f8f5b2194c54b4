"""Tuning knobs: the ONE place kernel/runtime tuning parameters are read
from the environment (tools/repo_lint.py rule 10 forbids raw
``os.environ`` reads of them anywhere else).

Each reader is "the validated environment value, else the caller's
default".  Garbage raises a ``ValueError`` naming the variable instead
of feeding an ``int('x')`` traceback, or a silent default, into a trace.
A leaf: imports only the standard library.
"""

from __future__ import annotations

import os
from typing import Optional


def _env_int(var: str, what: str) -> Optional[int]:
    raw = os.environ.get(var)
    if raw is None or raw == "":
        return None
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"{var}={raw!r} is not an integer ({what}); unset it or "
            f"give a positive number of elements") from None
    if val <= 0:
        raise ValueError(
            f"{var}={val} must be a positive integer ({what})")
    return val


def paged_page_size(default: int = 16) -> int:
    """KV-cache page size (tokens per page; the paged-attention kernel's
    tile): PADDLE_TPU_PAGE_SIZE, else `default`.  Must fill whole
    sublane tiles (multiple of 16) for the Pallas kernel gate."""
    v = _env_int("PADDLE_TPU_PAGE_SIZE", "KV page size in tokens")
    if v is not None and v % 16:
        raise ValueError(
            f"PADDLE_TPU_PAGE_SIZE={v} must be a multiple of 16 "
            f"(whole sublane tiles for every pool dtype)")
    return v or int(default)


def speculation_k(default: int = 4) -> int:
    """Speculative-decoding depth K (draft tokens proposed per round;
    serving/speculative.py): PADDLE_TPU_SPEC_K, else `default`."""
    return (_env_int("PADDLE_TPU_SPEC_K", "speculation depth in tokens")
            or int(default))


def steps_per_dispatch(default: int = 1) -> int:
    """Fused K-step dispatch depth (framework/step_loop.py): how many
    training steps one Executor dispatch scans over.
    PADDLE_TPU_STEPS_PER_DISPATCH, else `default`."""
    return (_env_int("PADDLE_TPU_STEPS_PER_DISPATCH",
                     "fused steps per dispatch") or int(default))


def spec_draft_layers(default: int) -> int:
    """Draft-tower depth for self-speculation (the target's first N
    blocks; serving/speculative.py): PADDLE_TPU_SPEC_DRAFT_LAYERS, else
    `default`.  Callers clamp to the target's depth."""
    return (_env_int("PADDLE_TPU_SPEC_DRAFT_LAYERS",
                     "draft tower depth in layers") or int(default))
