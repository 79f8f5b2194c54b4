"""Tuning-knob resolution: the ONE place kernel/runtime tuning
parameters come from (ISSUE 14 satellite: no more raw ``os.environ``
knob reads scattered through kernels — tools/repo_lint.py rule 9
forbids them outside this package).

Resolution order, strongest first:

  1. **active trial override** — the measurement harness pins the
     candidate's parameters for the duration of one trial
     (:func:`trial_overrides`); nothing may shadow the A/B being run;
  2. **environment** — the explicit operator override layer
     (PADDLE_TPU_FLASH_BQ/BK, PADDLE_TPU_PAGE_SIZE, ...).  Values
     are VALIDATED here: garbage raises a clear error naming the
     variable instead of feeding ``int('x')`` tracebacks (or silent
     defaults) into a trace;
  3. **winner store** — the persisted measured winner for this site on
     this device/backend (:mod:`paddle_tpu.autotune.store`);
  4. the caller's **default**.

Knob names are dotted ``<namespace>.<field>`` strings; the namespace is
also the store's kernel-site kind (``flash_attention``, ``paged_attention``).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple  # noqa: F401 (Optional: API sigs)

from . import store as _store

_tls = threading.local()


class trial_overrides:
    """Context manager pinning knob values for one measurement trial.

    ``mapping`` uses dotted knob names (``{"flash_attention.block_q":
    256}``).  Nesting stacks; inner wins.  Also the harness-active
    signal :func:`in_trial` — program-winner auto-application
    (integration.py) stands down during a trial so a stored winner can
    never contaminate the A/B measuring its successor."""

    def __init__(self, mapping: Optional[Dict[str, object]] = None,
                 **kv):
        self._mapping = dict(mapping or {})
        self._mapping.update(kv)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self._mapping)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()
        return False


def in_trial() -> bool:
    return bool(getattr(_tls, "stack", None))


def _trial_value(name: str):
    for frame in reversed(getattr(_tls, "stack", []) or []):
        if name in frame:
            return frame[name]
    return None


def platform(init: bool = False) -> Tuple[str, str]:
    """(device_kind, backend) of the default jax device — the store's
    platform tag.  Without `init`, falls back to ("unknown", "none")
    when no backend is live yet, so desc-only tooling (an executor-run
    lookup before the first device touch) never triggers device init;
    the TUNER passes init=True — the platform tag is the winner's
    identity, and it is about to measure on that device anyway."""
    try:
        import jax

        if not init:
            from ..framework.place import backend_initialized

            if not backend_initialized():
                return ("unknown", "none")
        return (jax.devices()[0].device_kind, jax.default_backend())
    except Exception:
        return ("unknown", "none")


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


def _site_winner(ns: str, site: Dict[str, object]) -> Dict[str, object]:
    kind, backend = platform()
    w = _store.default_store().winner(ns, site, kind, backend)
    return w or {}


# ---------------------------------------------------------------------------
# domain knobs (each documents its env override + validation contract;
# all follow the module-docstring resolution order)


def flash_blocks(block_q: int, block_k: int, T: int) -> Tuple[int, int]:
    """Requested flash-attention (block_q, block_k) before snapping.

    Trial override > PADDLE_TPU_FLASH_BQ/BK (strict positive ints — the
    old raw ``int(os.environ[...])`` accepted garbage as a traceback
    and negative sizes silently) > stored winner for this sequence
    length > the caller's defaults.  Alignment/divisor clamping stays
    in the kernel's ``_snap_block`` (a hint, never a shape constraint)."""
    site = {"T": int(T)}
    bq = _trial_value("flash_attention.block_q")
    bk = _trial_value("flash_attention.block_k")
    env_bq = _env_int("PADDLE_TPU_FLASH_BQ", "flash-attention q block")
    env_bk = _env_int("PADDLE_TPU_FLASH_BK", "flash-attention k/v block")
    if bq is None:
        bq = env_bq
    if bk is None:
        bk = env_bk
    if bq is None or bk is None:
        w = _site_winner("flash_attention", site)
        if bq is None:
            bq = w.get("block_q")
        if bk is None:
            bk = w.get("block_k")
    return (int(bq) if bq else int(block_q),
            int(bk) if bk else int(block_k))


def paged_page_size(default: int = 16) -> int:
    """KV-cache page size (tokens per page; the paged-attention kernel's
    tile).  Trial override > PADDLE_TPU_PAGE_SIZE (validated: a garbage
    value used to silently fall back to the default — now it raises) >
    stored winner > `default`.  Must fill whole sublane tiles
    (multiple of 16) for the Pallas kernel gate."""
    v = _trial_value("paged_attention.page_size")
    if v is None:
        v = _env_int("PADDLE_TPU_PAGE_SIZE", "KV page size in tokens")
        if v is not None and v % 16:
            raise ValueError(
                f"PADDLE_TPU_PAGE_SIZE={v} must be a multiple of 16 "
                f"(whole sublane tiles for every pool dtype)")
    if v is None:
        v = _site_winner("paged_attention", {}).get("page_size")
    return int(v or default)


def speculation_k(default: int = 4) -> int:
    """Speculative-decoding depth K (draft tokens proposed per round;
    serving/speculative.py).  Trial override > PADDLE_TPU_SPEC_K
    (validated positive int) > stored ``spec_decode`` winner >
    `default`.  K trades one fused draft run + (K+1)-row verify against
    up to K saved decode dispatches — the right value depends on the
    measured accept rate, which is what ``paddle tune spec_decode``
    measures."""
    v = _trial_value("spec_decode.speculation_k")
    if v is None:
        v = _env_int("PADDLE_TPU_SPEC_K", "speculation depth in tokens")
    if v is None:
        v = _site_winner("spec_decode", {}).get("speculation_k")
    return int(v or default)


def steps_per_dispatch(default: int = 1, store: bool = True) -> int:
    """Fused K-step dispatch depth (framework/step_loop.py): how many
    training steps one Executor dispatch scans over.  Trial override >
    PADDLE_TPU_STEPS_PER_DISPATCH (validated positive int) > stored
    ``step_loop`` winner > `default`.

    ``store=False`` skips the winner lookup — Executor.run's default
    path uses it, because K>1 changes run()'s return contract (stacked
    fetches) and a persisted winner must never silently reshape a
    caller's results; only the explicit arg/env opt-ins may fuse."""
    v = _trial_value("step_loop.steps_per_dispatch")
    if v is None:
        v = _env_int("PADDLE_TPU_STEPS_PER_DISPATCH",
                     "fused steps per dispatch")
    if v is None and store:
        v = _site_winner("step_loop", {}).get("steps_per_dispatch")
    return int(v or default)


def spec_draft_layers(default: int) -> int:
    """Draft-tower depth for self-speculation (the target's first N
    blocks; serving/speculative.py).  Trial override >
    PADDLE_TPU_SPEC_DRAFT_LAYERS (validated positive int) > stored
    ``spec_decode`` winner > `default`.  Callers clamp to the target's
    depth — deeper drafts raise accept rate and draft cost together."""
    v = _trial_value("spec_decode.draft_layers")
    if v is None:
        v = _env_int("PADDLE_TPU_SPEC_DRAFT_LAYERS",
                     "draft tower depth in layers")
    if v is None:
        v = _site_winner("spec_decode", {}).get("draft_layers")
    return int(v or default)
