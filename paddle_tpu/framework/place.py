"""Places: device identity (reference paddle/platform/place.h:24-71).

The reference's ``boost::variant<CPUPlace, CUDAPlace>`` becomes CPUPlace/TPUPlace
backed by JAX devices.  A Place resolves to a concrete ``jax.Device``; the
executor compiles per-place (XLA:TPU or XLA:CPU), which replaces the reference's
per-(place,dtype,layout,library) kernel dispatch (operator.cc:461-530).
"""

from __future__ import annotations

import functools


class Place:
    def jax_device(self):
        raise NotImplementedError

    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))


# whether a place of this process has asked JAX for its devices yet
_resolved = False


def _first_devices(platform: str):
    """`jax.devices(platform)` for a process's first resolution of a place,
    under the cold span `device.init` of the start-up record
    (observability/tracing.py): the backend's start, the TPU client's where
    the program is the first to touch it, and next to nothing where the
    caller already had (`backend_up`)."""
    global _resolved
    import jax

    from ..observability.tracing import TRACER

    _resolved = True
    with TRACER.span("device.init", cold=True, platform=platform,
                     backend_up=backend_initialized()):
        return jax.devices(platform)


class CPUPlace(Place):
    def jax_device(self):
        import jax

        return (jax.devices("cpu") if _resolved
                else _first_devices("cpu"))[0]

    def __repr__(self):
        return "CPUPlace()"


class TPUPlace(Place):
    """TPU chip `device_id` of this process: ``jax.devices("tpu")[device_id]``.
    A process with no TPU, or fewer than `device_id + 1` of them, has no such
    place, and resolving it raises — a program written against TPUPlace
    never runs somewhere else under that name."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def jax_device(self):
        import jax

        try:
            devs = (jax.devices("tpu") if _resolved
                    else _first_devices("tpu"))
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: this process has no TPU backend (default "
                f"backend {jax.default_backend()!r} with "
                f"{jax.device_count()} device(s)): {e}") from e
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: this process has {len(devs)} TPU device(s), "
                f"ids 0..{len(devs) - 1}")
        return devs[self.device_id]

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# Alias: code ported from the reference may say CUDAPlace; on this framework it
# means "the accelerator" (TPU).
CUDAPlace = TPUPlace


def backend_initialized() -> bool:
    """Whether this process has initialised a JAX backend.  On a TPU
    machine that is the moment it takes the chip: a chip belongs to one
    process at a time, so a child started afterwards that needs the chip
    fails or hangs.  The one place that asks JAX's private bridge."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def holds_accelerator() -> bool:
    """True once this process has initialised a non-CPU backend (asking
    never initialises one)."""
    return backend_initialized() and has_accelerator()


@functools.lru_cache(maxsize=None)
def has_accelerator() -> bool:
    import jax

    return jax.default_backend() not in ("cpu",)


def default_place() -> Place:
    return TPUPlace(0) if has_accelerator() else CPUPlace()
