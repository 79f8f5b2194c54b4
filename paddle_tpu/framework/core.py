"""Program IR: the TPU-native equivalent of Fluid's ProgramDesc.

The reference (``/root/reference/paddle/framework/framework.proto:19-146``,
``python/paddle/v2/fluid/framework.py:124/349/620/788``) represents a model as a
``ProgramDesc`` holding ``BlockDesc``s of ``OpDesc``/``VarDesc``.  Its C++ Executor
interprets that graph one op at a time (executor.cc:116).  Here the same IR exists —
Program/Block/Operator/Variable with serialization, nested blocks for control flow,
desc-level autodiff — but it is a *compiler* IR: the Executor lowers a whole block to
one XLA computation via JAX tracing (see executor.py), so the per-op interpret loop
and per-(place,dtype,layout,library) kernel dispatch of the reference disappear.

Serialization is JSON-based (``Program.to_json``/``from_json``) fulfilling the
save/load/prune/transpile contract of framework.proto without carrying proto2.
"""

from __future__ import annotations

import contextlib
import copy
import itertools
import json
from typing import Any, Dict, List, Optional

import numpy as np

from ..ops.registry import np_dtype  # noqa: F401  (emitters read it too)
from . import unique_name

# ---------------------------------------------------------------------------
# dtypes


class VarType:
    """Variable kinds, mirroring VarDesc::VarType (framework.proto:109-126)."""

    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    FEED_MINIBATCH = "feed_minibatch"
    FETCH_LIST = "fetch_list"
    STEP_SCOPES = "step_scopes"
    LOD_RANK_TABLE = "lod_rank_table"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    RAW = "raw"


_DTYPE_ALIASES = {
    "float32": "float32",
    "fp32": "float32",
    "float64": "float64",
    "fp64": "float64",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "bf16": "bfloat16",
    "int8": "int8",
    "uint8": "uint8",
    "int16": "int16",
    "int32": "int32",
    "int64": "int64",
    "bool": "bool",
}


def canonical_dtype(dtype) -> str:
    if isinstance(dtype, str):
        if dtype not in _DTYPE_ALIASES:
            raise ValueError(f"unknown dtype {dtype!r}")
        return _DTYPE_ALIASES[dtype]
    return _DTYPE_ALIASES[np.dtype(dtype).name]


# ---------------------------------------------------------------------------
# Variable


class Variable:
    """A named tensor slot in a Block (fluid framework.py:124 `Variable`).

    Holds static metadata only — shape, dtype, persistability, LoD level; values
    live in a `Scope` (scope.py) or are produced inside the compiled step.
    A shape entry of -1 means inferred-at-feed-time (batch axis).
    """

    def __init__(
        self,
        block: "Block",
        name: str,
        shape=None,
        dtype="float32",
        type: str = VarType.LOD_TENSOR,
        persistable: bool = False,
        stop_gradient: bool = False,
        lod_level: int = 0,
        is_data: bool = False,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = canonical_dtype(dtype) if dtype is not None else None
        self.type = type
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.lod_level = lod_level
        self.is_data = is_data
        # set by Optimizer._add_accumulator: name of the parameter this var
        # is an optimizer accumulator for (positive id for ZeRO sharding —
        # never inferred from name prefixes)
        self.accumulator_for: Optional[str] = None

    # python operator sugar (+ - * /): layers/math_helper.py installs it at
    # import, as the reference's layers/math_op_patch.py does

    def __repr__(self):
        return (
            f"Variable(name={self.name!r}, shape={self.shape}, dtype={self.dtype}, "
            f"persistable={self.persistable})"
        )

    def to_dict(self):
        d = {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "type": self.type,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "lod_level": self.lod_level,
            "is_data": self.is_data,
        }
        if getattr(self, "accumulator_for", None):
            d["accumulator_for"] = self.accumulator_for
        if isinstance(self, Parameter):
            d["is_parameter"] = True
            d["trainable"] = self.trainable
        return d

    @staticmethod
    def from_dict(block, d):
        if d.get("is_parameter"):
            return Parameter(
                block,
                d["name"],
                shape=d["shape"],
                dtype=d["dtype"],
                trainable=d.get("trainable", True),
                stop_gradient=d["stop_gradient"],
                lod_level=d.get("lod_level", 0),
            )
        v = Variable(
            block,
            d["name"],
            shape=d["shape"],
            dtype=d["dtype"],
            type=d["type"],
            persistable=d["persistable"],
            stop_gradient=d["stop_gradient"],
            lod_level=d.get("lod_level", 0),
            is_data=d.get("is_data", False),
        )
        v.accumulator_for = d.get("accumulator_for")
        return v


class Parameter(Variable):
    """A persistable, trainable Variable (fluid framework.py:930).

    Carries optimizer-facing attrs: trainable flag, regularizer, gradient clip
    attr, and the initializer that seeded it into the startup program.
    """

    def __init__(self, block, name, shape, dtype, **kw):
        self.trainable = kw.pop("trainable", True)
        self.regularizer = kw.pop("regularizer", None)
        self.gradient_clip_attr = kw.pop("gradient_clip_attr", None)
        self.optimize_attr = kw.pop("optimize_attr", {"learning_rate": 1.0})
        super().__init__(
            block, name, shape=shape, dtype=dtype, persistable=True, **kw
        )


# ---------------------------------------------------------------------------
# Operator


class Operator:
    """One op in a block (fluid framework.py:349 / OpDesc framework.proto:30).

    ``inputs``/``outputs`` map slot name → list of variable names; ``attrs`` is a
    plain dict (ints, floats, strings, bools, lists, or a Block index for
    control-flow sub-blocks, mirroring AttrType.BLOCK).
    """

    def __init__(self, block, type: str, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = {
            k: list(v) for k, v in (inputs or {}).items()
        }
        self.outputs: Dict[str, List[str]] = {
            k: list(v) for k, v in (outputs or {}).items()
        }
        self.attrs: Dict[str, Any] = dict(attrs or {})

    def input_names(self) -> List[str]:
        return [n for vs in self.inputs.values() for n in vs]

    def output_names(self) -> List[str]:
        return [n for vs in self.outputs.values() for n in vs]

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    def __repr__(self):
        return f"Operator({self.type}, in={self.inputs}, out={self.outputs})"

    def to_dict(self):
        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": self.attrs,
        }

    @staticmethod
    def from_dict(block, d):
        return Operator(block, d["type"], d["inputs"], d["outputs"], d["attrs"])


# ---------------------------------------------------------------------------
# Block


class Block:
    """A straight-line op list + symbol table (fluid framework.py:620).

    Nested blocks (parent_idx) support control flow (while/cond) exactly like
    BlockDesc's parent_idx (framework.proto:128-146).
    """

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    # -- vars ---------------------------------------------------------------
    def create_var(self, name=None, **kw) -> Variable:
        if name is None:
            name = unique_name.generate("tmp")
        v = Variable(self, name, **kw)
        self.vars[name] = v
        self.program._bump()
        return v

    def create_parameter(self, name, shape, dtype, **kw) -> Parameter:
        p = Parameter(self, name, shape, dtype, **kw)
        self.vars[name] = p
        self.program._bump()
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError(f"variable {name!r} not found in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        blk = self
        while True:
            if name in blk.vars:
                return blk.vars[name]
            if blk.parent_idx < 0:
                return None
            blk = self.program.blocks[blk.parent_idx]

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops ----------------------------------------------------------------
    def _new_op(self, type, inputs, outputs, attrs) -> Operator:
        """The one place an op enters a block."""
        op = Operator(self, type, inputs, outputs, attrs)
        # stable per-op uid: the PRNG salt for stochastic ops (ops/registry.py
        # EmitContext.rng) — survives serialization so replays are exact
        op.attrs.setdefault("__uid__", self.program._take_uid())
        # the model part being built (Program.part_guard), unless the op
        # names its own
        if self.program._part and not op.attrs.get("part"):
            op.attrs["part"] = self.program._part
        self.program._bump()
        return op

    def append_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        op = self._new_op(type, inputs, outputs, attrs)
        self.ops.append(op)
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        op = self._new_op(type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        return op

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_dict() for v in self.vars.values()],
            "ops": [o.to_dict() for o in self.ops],
        }

    @staticmethod
    def from_dict(program, d):
        b = Block(program, d["idx"], d["parent_idx"])
        for vd in d["vars"]:
            v = Variable.from_dict(b, vd)
            b.vars[v.name] = v
        for od in d["ops"]:
            b.ops.append(Operator.from_dict(b, od))
        return b


# ---------------------------------------------------------------------------
# Program


class Program:
    """A whole model: list of blocks, block 0 is global (fluid framework.py:788)."""

    # process-wide monotonic id source: unlike id(), tokens are never reused
    # after garbage collection, so executor cache keys can't alias between a
    # dead Program and a new one at the same address
    _token_counter = itertools.count()

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self._version = 0  # bumped on mutation; executor cache key component
        self._cache_token = next(Program._token_counter)
        self._next_uid = 0
        self.random_seed = 0
        self._part = None  # the part_guard in force

    def _take_uid(self) -> int:
        self._next_uid += 1
        return self._next_uid - 1

    @contextlib.contextmanager
    def part_guard(self, name: str):
        """Every op appended to this program inside the guard that names no
        `part` of its own gets `part` = `name` (`lm.head`): the executor
        lowers it, and its grad op, inside the scope `pdtpu.<name>`
        (observability/attribution.py), so a trace says which part of the
        model an instruction belongs to.  Guards nest: inside an outer
        guard the part is `<outer>/<name>`, lowered as the scope
        `pdtpu.<name>` inside `pdtpu.<outer>` (a module's own head: `lm.head`
        inside `mtp.head`)."""
        outer = self._part
        self._part = f"{outer}/{name}" if outer else name
        try:
            yield
        finally:
            self._part = outer

    # -- structure ----------------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump()
        return b

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump(self):
        self._version += 1

    # -- introspection ------------------------------------------------------
    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def num_ops(self):
        return sum(len(b.ops) for b in self.blocks)

    def __repr__(self):
        return f"Program(blocks={len(self.blocks)}, ops={self.num_ops()})"

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy; with for_test=True, switch train-only ops to eval mode
        (dropout/batch_norm is_test attr), mirroring fluid Program.clone."""
        p = Program.from_json(self.to_json())
        if for_test:
            for b in p.blocks:
                for op in b.ops:
                    if op.type in ("dropout", "batch_norm"):
                        op.attrs["is_test"] = True
        p.random_seed = self.random_seed
        return p

    # -- serialization ------------------------------------------------------
    def to_proto(self) -> bytes:
        """Serialized ProgramDef wire bytes (framework.proto)."""
        from . import proto_io

        return proto_io.serialize_program(self)

    @staticmethod
    def from_proto(data: bytes) -> "Program":
        from . import proto_io

        return proto_io.parse_program(data)

    def to_json(self) -> str:
        return json.dumps(
            {
                "version": 1,
                "current_block_idx": self.current_block_idx,
                "random_seed": self.random_seed,
                "blocks": [b.to_dict() for b in self.blocks],
            }
        )

    @staticmethod
    def from_json(s: str) -> "Program":
        d = json.loads(s)
        p = Program()
        p.blocks = [Block.from_dict(p, bd) for bd in d["blocks"]]
        p.current_block_idx = d.get("current_block_idx", 0)
        p.random_seed = d.get("random_seed", 0)
        p._version = 0
        p._recompute_next_uid()
        return p

    def _recompute_next_uid(self):
        """Restore the uid counter after deserialization so future ops never
        collide with recorded __uid__ PRNG salts."""
        self._next_uid = 1 + max(
            (int(op.attrs.get("__uid__", 0))
             for b in self.blocks for op in b.ops),
            default=-1,
        )


# ---------------------------------------------------------------------------
# default program management (fluid framework.py bottom)

_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(p: Program) -> Program:
    global _main_program
    prev, _main_program = _main_program, p
    return prev


def switch_startup_program(p: Program) -> Program:
    global _startup_program
    prev, _startup_program = _startup_program, p
    return prev


class program_guard:
    """Context manager scoping default main/startup programs (fluid's
    program_guard)."""

    def __init__(self, main_program: Program, startup_program: Optional[Program] = None):
        self._main = main_program
        self._startup = startup_program

    def __enter__(self):
        self._prev_main = switch_main_program(self._main)
        if self._startup is not None:
            self._prev_startup = switch_startup_program(self._startup)
        return self

    def __exit__(self, *exc):
        switch_main_program(self._prev_main)
        if self._startup is not None:
            switch_startup_program(self._prev_startup)
        return False


GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def drop_orphaned_vars(block: Block, keep=()) -> int:
    """Delete declarations no op in `block` references (keeping persistable
    and data vars, plus `keep` names).  Passes that remove ops (io.prune,
    the conv+BN fold) call this so their output lints clean — the verifier
    (analysis PTV011) rightly flags var-table debris.  Returns #dropped."""
    referenced = set(keep)
    for op in block.ops:
        referenced.update(n for n in op.input_names() if n)
        referenced.update(n for n in op.output_names() if n)
    dropped = 0
    for name in list(block.vars):
        v = block.vars[name]
        if name not in referenced and not v.persistable and not v.is_data:
            del block.vars[name]
            dropped += 1
    return dropped
