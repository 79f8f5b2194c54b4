"""Which part of the program an instruction of a profiler trace came from,
by the compiled program's own word.

An "XLA Ops" event of the v5e's xplane carries its instruction's text and
its timing, and no metadata (moe_ops.py).  But the same file holds, in its
`/host:metadata` plane, the HloProto of every program that ran in the
trace, and there each instruction keeps the `op_name` JAX gave it: the path
of `jax.named_scope`s it was traced under, `transpose(jvp(...))` around
them in the backward.  The program names its parts `pdtpu.<part>`
(`observability/attribution.py` `part_scope`: `pdtpu.moe.permute`,
`pdtpu.conv.taps`, ...), and an instruction's name (`fusion.531`) is the
same in the event and in the proto.  So a reader can ask of an event which
parts it holds, where two tensors of one shape cannot be told apart by
their text (a buffer of the expert layer as long as the token stream).

`of_trace(path)` -> {instruction name: Note}:

  scopes         the parts (`moe.permute`, without `pdtpu.`) named by the
                 instruction's own `op_name` and by those of every
                 instruction of the computations it calls (a fusion's
                 body).
  own            whether they are.  An instruction that JAX named nowhere,
                 itself or inside (no path with a `/`: the two halves of an
                 asynchronous copy, a scatter XLA rewrote, the kernel XLA
                 makes of `lax.ragged_dot`), takes its operands'
                 producers' scopes, and `own` is False: a tensor moved
                 belongs to who made it, for a reader that counts every
                 instruction on a tensor, and to nobody for one that counts
                 an op's own work.
  product_flops  2 x M x N x K of the matrix products inside it (XLA:TPU
                 writes a `dot` as a `convolution`; the same opcode is not
                 used for anything else in these programs), 0 where it
                 holds none: a plain product, no batch dimension, so
                 M N K = sqrt(|lhs| |rhs| |out|).

Of the programs in the trace the largest is read (the training step beside
`jit_convert_element_type` and the seed's `fold_in`): an instruction name
means one thing.  The protos are read from their wire format by the field
numbers of xplane.proto and hlo.proto (given beside each use), since
`jax.profiler.ProfileData` does not show a plane's event metadata; a file
without the plane gives {}.
"""

from __future__ import annotations

import collections
import math
import re

Note = collections.namedtuple("Note", "scopes own product_flops")
NOTHING = Note(frozenset(), True, 0.0)
PART = re.compile(r"pdtpu\.([a-z_]+\.[a-z_]+)")
PRODUCTS = ("convolution", "dot")
INHERIT_DEPTH = 8

_loaded: dict = {}


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return out, i


def _fields(buf):
    """(field number, wire type, value) of one message: a varint's value,
    or a memoryview of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in a proto3 message")
        yield key >> 3, wire, value


def _ints(wire, value):
    """A repeated integer field's entry: packed, or one value."""
    if wire == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _elements(shape) -> int:
    """ShapeProto: dimensions = 3; a tuple's shape has none and gives 1."""
    dims = [d for f, w, v in _fields(shape) if f == 3 for d in _ints(w, v)]
    return math.prod(dims)


def hlo_protos(path: str) -> list:
    """The serialized HloProtos of an .xplane.pb.  XSpace: planes = 1;
    XPlane: name = 2, event_metadata = 4 (a map: value = 2); XEventMetadata:
    stats = 5; XStat: bytes_value = 6."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f1, _, plane in _fields(space):
        if f1 != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v) == b"/host:metadata"
                   for f, _, v in parts):
            continue
        for f2, _, entry in parts:
            if f2 != 4:
                continue
            for f3, _, meta in _fields(entry):
                if f3 != 2:
                    continue
                for f4, _, stat in _fields(meta):
                    if f4 == 5:
                        out += [v for f5, w, v in _fields(stat)
                                if f5 == 6 and w == 2]
    return out


def instructions(proto) -> dict:
    """{computation id: [instruction]} of one HloProto, an instruction a
    dict of name, opcode, op_name, id, operands (ids), called (computation
    ids) and elements (of its result).  HloProto: hlo_module = 1;
    HloModuleProto: computations = 3; HloComputationProto: instructions =
    2, id = 5; HloInstructionProto: name = 1, opcode = 2, shape = 3,
    metadata = 7 (OpMetadata: op_name = 2), id = 35, operand_ids = 36,
    called_computation_ids = 38."""
    out = {}
    for f1, _, module in _fields(proto):
        if f1 != 1:
            continue
        for f2, _, comp in _fields(module):
            if f2 != 3:
                continue
            cid, found = None, []
            for f3, w3, v3 in _fields(comp):
                if f3 == 5:
                    cid = v3
                elif f3 == 2:
                    ins = {"name": "", "opcode": "", "op_name": "",
                           "id": None, "operands": [], "called": [],
                           "elements": 1}
                    for f4, w4, v4 in _fields(v3):
                        if f4 == 1:
                            ins["name"] = bytes(v4).decode()
                        elif f4 == 2:
                            ins["opcode"] = bytes(v4).decode()
                        elif f4 == 3:
                            ins["elements"] = _elements(v4)
                        elif f4 == 7:
                            ins["op_name"] = "".join(
                                bytes(v).decode()
                                for f, _, v in _fields(v4) if f == 2)
                        elif f4 == 35:
                            ins["id"] = v4
                        elif f4 == 36:
                            ins["operands"] += _ints(w4, v4)
                        elif f4 == 38:
                            ins["called"] += _ints(w4, v4)
                    found.append(ins)
            out[cid] = found
    return out


def notes(comps: dict) -> dict:
    """{instruction name: Note} of `instructions`' result."""
    by_id = {i["id"]: i for found in comps.values() for i in found}
    inside: dict = {}

    def own(ins):
        """(parts, product flops, whether JAX named any of it) of an
        instruction and all it calls."""
        if ins["id"] not in inside:
            parts = set(PART.findall(ins["op_name"]))
            flops, named = 0.0, "/" in ins["op_name"]
            if ins["opcode"] in PRODUCTS and len(ins["operands"]) == 2:
                lhs, rhs = (by_id[o]["elements"] for o in ins["operands"])
                flops = 2.0 * math.sqrt(lhs * rhs * ins["elements"])
            for cid in ins["called"]:
                for sub in comps.get(cid, ()):
                    p, f, n = own(sub)
                    parts |= p
                    flops += f
                    named |= n
            inside[ins["id"]] = (frozenset(parts), flops, named)
        return inside[ins["id"]]

    taken: dict = {}

    def inherited(ins, depth):
        parts, _, named = own(ins)
        if named or depth == 0:
            return parts
        key = (ins["id"], depth)
        if key not in taken:
            taken[key] = frozenset().union(*(
                inherited(by_id[o], depth - 1) for o in ins["operands"]
                if o in by_id))
        return taken[key]

    return {i["name"]: Note(inherited(i, INHERIT_DEPTH),
                            own(i)[2] or not i["operands"], own(i)[1])
            for found in comps.values() for i in found}


def of_trace(path: str) -> dict:
    """{instruction name: Note} of the largest program in the trace's
    metadata plane; {} where it holds none.  Parsed once per process."""
    if path not in _loaded:
        protos = hlo_protos(path)
        _loaded[path] = (notes(instructions(max(protos, key=len)))
                         if protos else {})
    return _loaded[path]


def name_of(text: str) -> str:
    """An event's instruction name as the proto has it."""
    return text.split(" = ", 1)[0].strip().lstrip("%")
