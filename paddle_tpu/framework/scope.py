"""Scope: name → value container (reference paddle/framework/scope.h:38).

The reference's Scope holds type-erased Variables with parent-chain lookup; ops
read/write it imperatively.  Here the Scope only holds *persistent* state
between executor runs — parameters, optimizer moments, learning-rate tensors,
metric states — as JAX arrays resident on the place's device.  Transient op
outputs never materialize: they are values inside the compiled XLA program.

Device-promotion contract: a numpy array written into the scope (set_value,
load paths, fuse_batch_norm's folded filters) is promoted IN PLACE to a
jax.Array device buffer on the first Executor.run that reads it
(executor._pin_host_array) — re-staging host memory every step re-uploads
the weights every step.  Consequences: (a) `find()` may return jax.Array
where numpy was written; readers needing numpy use `find_np()`; (b) holding
the original numpy object for later in-place mutation is unsupported — the
scope no longer references it after the first run; write via `set()`.
"""

from __future__ import annotations

from typing import Dict, Optional

import contextlib

import numpy as np


class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, object] = {}
        self.parent = parent
        self._kids = []

    def new_scope(self) -> "Scope":
        s = Scope(self)
        self._kids.append(s)
        return s

    def set(self, name: str, value):
        self._vars[name] = value

    def find(self, name: str):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has(self, name: str) -> bool:
        return self.find(name) is not None

    def drop(self, name: str):
        self._vars.pop(name, None)

    def local_names(self):
        return list(self._vars.keys())

    def find_np(self, name: str) -> np.ndarray:
        v = self.find(name)
        return None if v is None else np.asarray(v)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def reset_global_scope():
    global _global_scope
    _global_scope = Scope()
    return _global_scope


def switch_scope(scope: Scope) -> Scope:
    """Swap the process-global scope (reference executor.py switch_scope);
    returns the previous one."""
    global _global_scope
    prev = _global_scope
    _global_scope = scope
    return prev


@contextlib.contextmanager
def scope_guard(scope: Scope):
    """with scope_guard(Scope()): ... (reference executor.py scope_guard)."""
    prev = switch_scope(scope)
    try:
        yield scope
    finally:
        switch_scope(prev)
