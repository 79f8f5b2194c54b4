"""LayerHelper: parameter creation + op appending for layer functions
(reference python/paddle/v2/fluid/layer_helper.py:105 create_parameter).

Each created parameter gets its init op written into the *startup* program and
its Parameter var registered in the *main* program — the same two-program
contract as fluid."""

from __future__ import annotations

import contextlib

from . import unique_name
from .core import (canonical_dtype, default_main_program,
                   default_startup_program)
from .initializer import ConstantInitializer, XavierInitializer

_SHARING = []   # the SharedParameters scopes in force, innermost last


class SharedParameters:
    """ONE set of parameters for layers that are built several times (a
    looped tower's passes).  The first `with shared.scope():` builds as
    ever and notes the name of every parameter a layer makes inside it, in
    order; in each later scope `LayerHelper.create_parameter` makes nothing
    and hands out the first's parameters BY THOSE NAMES in the same order:
    the later ops read the one Parameter, which has one init op in the
    startup program, one gradient (`append_backward` adds its parts) and
    one entry wherever parameters are listed.  A later scope whose layers
    ask for another shape or dtype than the first made, for a parameter
    more, or for fewer by its end, is a ValueError: the passes are not one
    stack of layers.  Temporaries keep their fresh `unique_name`s."""

    def __init__(self):
        self.names = []     # the first scope's parameters, as made
        self._next = None   # None: the first scope (it records)

    @contextlib.contextmanager
    def scope(self):
        first = self._next is None
        if not first:
            self._next = 0
        _SHARING.append(self)
        try:
            yield
        finally:
            _SHARING.pop()
        if not first and self._next != len(self.names):
            raise ValueError(
                f"shared parameters: a later pass read {self._next} of the "
                f"{len(self.names)} parameters the first made (the next "
                f"would be {self.names[self._next]!r})")
        self._next = 0

    def take(self, block, shape, dtype):
        """Inside a later scope the first's next parameter, held to `shape`
        and `dtype`; inside the first None (the caller makes one and
        notes its name)."""
        if self._next is None:
            return None
        if self._next == len(self.names):
            raise ValueError(
                f"shared parameters: a later pass makes a parameter "
                f"{list(shape)} {dtype} that the first did not: the first "
                f"made {len(self.names)}")
        param = block.var(self.names[self._next])
        if (tuple(param.shape) != tuple(int(s) for s in shape)
                or param.dtype != canonical_dtype(dtype)):
            raise ValueError(
                f"shared parameters: a later pass asks for {list(shape)} "
                f"{dtype} where the first made {param.name!r} "
                f"{list(param.shape)} {param.dtype}")
        self._next += 1
        return param


class LayerHelper:
    def __init__(self, layer_type: str, **kwargs):
        self.kwargs = kwargs
        self.layer_type = layer_type
        name = kwargs.get("name")
        self.name = name if name else unique_name.generate(layer_type)

    @property
    def main_program(self):
        return self.kwargs.get("main_program") or default_main_program()

    @property
    def startup_program(self):
        return self.kwargs.get("startup_program") or default_startup_program()

    @property
    def block(self):
        return self.main_program.current_block()

    def append_op(self, *a, **kw):
        return self.block.append_op(*a, **kw)

    # ------------------------------------------------------------------
    def create_parameter(self, attr=None, shape=None, dtype="float32",
                         is_bias=False, default_initializer=None):
        sharing = _SHARING[-1] if _SHARING else None
        if sharing is not None:
            shared = sharing.take(self.block.program.global_block(), shape,
                                  dtype)
            if shared is not None:
                return shared
        attr = dict(attr or {})
        name = attr.get("name") or unique_name.generate(
            self.name + (".b" if is_bias else ".w")
        )
        if sharing is not None:
            sharing.names.append(name)
        init = attr.get("initializer") or default_initializer
        if init is None:
            init = ConstantInitializer(0.0) if is_bias else XavierInitializer()
        # main-program Parameter (trainable var)
        param = self.block.program.global_block().create_parameter(
            name=name,
            shape=shape,
            dtype=dtype,
            trainable=attr.get("trainable", True),
            regularizer=attr.get("regularizer"),
            gradient_clip_attr=attr.get("gradient_clip"),
            optimize_attr={"learning_rate": attr.get("learning_rate", 1.0)},
        )
        # ParameterUpdaterHook parity (reference ParameterUpdaterHook.cpp
        # via attrs.py HookAttribute): e.g. {"type": "pruning",
        # "sparsity_ratio": 0.6}; consumed by Optimizer's update pass
        if attr.get("update_hooks"):
            param.update_hooks = attr["update_hooks"]
        # startup-program twin + init op (trainable mirrored: the FSDP
        # plan collects trainable names across every planned program, and
        # a twin defaulting to trainable=True would dp-shard a frozen
        # weight — per-step all-gather traffic for a param that never
        # changes; code review r5)
        sblock = self.startup_program.global_block()
        if name not in sblock.vars:
            svar = sblock.create_parameter(
                name=name, shape=shape, dtype=dtype,
                trainable=attr.get("trainable", True))
            init(svar, sblock)
        return param

    def create_tmp_variable(self, dtype, shape=None, stop_gradient=False):
        return self.block.create_var(
            name=unique_name.generate(self.name + ".tmp"),
            shape=shape,
            dtype=dtype,
            stop_gradient=stop_gradient,
        )

    def create_global_variable(self, name=None, shape=None, dtype="float32",
                               persistable=True):
        return self.main_program.global_block().create_var(
            name=name or unique_name.generate(self.name + ".global"),
            shape=shape,
            dtype=dtype,
            persistable=persistable,
            stop_gradient=True,
        )

    def set_initialized(self, var, initializer):
        """Register an init op for a non-parameter persistable var (BN stats,
        optimizer accumulators, LR)."""
        sblock = self.startup_program.global_block()
        if var.name not in sblock.vars:
            svar = sblock.create_var(
                name=var.name, shape=var.shape, dtype=var.dtype,
                persistable=True,
            )
            svar.accumulator_for = getattr(var, "accumulator_for", None)
            initializer(svar, sblock)

    # ------------------------------------------------------------------
    def append_activation(self, out_var):
        act = self.kwargs.get("act")
        if act is None:
            return out_var
        if isinstance(act, dict):
            act = act["type"]
        tmp = self.create_tmp_variable(out_var.dtype, shape=out_var.shape)
        self.append_op(act, inputs={"X": [out_var.name]},
                       outputs={"Out": [tmp.name]})
        return tmp

    def append_bias_op(self, input_var, dim_start=1):
        bias_attr = self.kwargs.get("bias_attr")
        if bias_attr is False:
            return input_var
        size = input_var.shape[dim_start:]
        b = self.create_parameter(
            attr=bias_attr if isinstance(bias_attr, dict) else {},
            shape=list(size), dtype=input_var.dtype, is_bias=True,
        )
        tmp = self.create_tmp_variable(input_var.dtype, shape=input_var.shape)
        self.append_op(
            "elementwise_add",
            inputs={"X": [input_var.name], "Y": [b.name]},
            outputs={"Out": [tmp.name]},
            attrs={"axis": dim_start},
        )
        return tmp
