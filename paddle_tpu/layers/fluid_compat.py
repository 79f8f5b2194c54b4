"""Fluid layer-API parity wrappers (reference python/paddle/v2/fluid/layers
{nn,tensor,control_flow,device}.py __all__ names that had ops but no
fluid-named wrapper here).

Everything lowers onto already-registered emitters; the LoD-machinery names
(lod_rank_table, *_lod_tensor*, shrink_memory) are the padded+lengths
design-shift equivalents (SURVEY.md §5 long-context): ragged batches ride
[B, T, ...] + length vectors, so rank tables become argsorts of the length
var and tensor<->array conversion is a time-major transpose."""

from __future__ import annotations

from ..framework.core import Variable, default_main_program
from ..framework.layer_helper import LayerHelper
from .sequence import get_length_var, propagate_length, sequence_pool
from . import tensor as _tensor
from .nn import fc  # noqa: F401  (re-exported fluid surface)

__all__ = [
    "gru_unit", "cos_sim", "chunk_eval", "conv2d_transpose",
    "sequence_expand", "lstm_unit", "sequence_first_step",
    "sequence_last_step", "split", "l2_normalize", "warpctc",
    "sequence_reshape", "create_tensor", "create_parameter",
    "fill_constant_batch_size_like", "ones", "zeros", "array_write",
    "array_read", "create_array", "array_length", "max_sequence_len",
    "lod_rank_table", "reorder_lod_tensor_by_rank", "lod_tensor_to_array",
    "array_to_lod_tensor", "shrink_memory", "split_lod_tensor",
    "merge_lod_tensor", "IfElse", "ParallelDo", "Print", "get_places",
    "BlockGuard", "WhileGuard", "ConditionalBlock",
    "BlockGuardWithCompletion", "StaticRNNMemoryLink",
]


# --- nn.py parity -----------------------------------------------------------

def gru_unit(input, hidden, size, weight=None, bias=None, activation="tanh",
             gate_activation="sigmoid", param_attr=None, bias_attr=None):
    """fluid nn.py:341 gru_unit -> gru_unit op (gru_unit_op.cc). `size` is
    3*H as in the reference; returns (updated_hidden, reset_hidden_prev,
    gate)."""
    helper = LayerHelper("gru_unit", param_attr=param_attr)
    H = size // 3
    if weight is None:
        weight = helper.create_parameter(
            attr=param_attr if isinstance(param_attr, dict) else {},
            shape=[H, 3 * H], dtype=input.dtype)
    inputs = {"Input": [input.name], "HiddenPrev": [hidden.name],
              "Weight": [weight.name]}
    if bias is None and bias_attr is not False:
        bias = helper.create_parameter(
            attr=bias_attr if isinstance(bias_attr, dict) else {},
            shape=[3 * H], dtype=input.dtype, is_bias=True)
    if bias is not None:
        inputs["Bias"] = [bias.name]
    h = helper.create_tmp_variable(input.dtype, shape=(-1, H))
    g = helper.create_tmp_variable(input.dtype, shape=None)
    r = helper.create_tmp_variable(input.dtype, shape=None)
    helper.append_op("gru_unit", inputs=inputs,
                     outputs={"Hidden": [h.name], "Gate": [g.name],
                              "ResetHiddenPrev": [r.name]},
                     attrs={"activation": activation,
                            "gate_activation": gate_activation})
    return h, r, g


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """fluid nn.py:1350 lstm_unit: fc([x_t, h_prev]) -> 4H gates -> lstm_unit
    op (lstm_unit_op.cc); returns (h, c)."""
    helper = LayerHelper("lstm_unit", name=name)
    H = int(cell_t_prev.shape[-1])
    gates = fc([x_t, hidden_t_prev], size=4 * H, param_attr=param_attr,
               bias_attr=bias_attr)
    c = helper.create_tmp_variable(x_t.dtype, shape=(-1, H))
    h = helper.create_tmp_variable(x_t.dtype, shape=(-1, H))
    helper.append_op("lstm_unit",
                     inputs={"X": [gates.name], "C_prev": [cell_t_prev.name]},
                     outputs={"C": [c.name], "H": [h.name]},
                     attrs={"forget_bias": float(forget_bias)})
    return h, c


def cos_sim(X, Y, **kwargs):
    helper = LayerHelper("cos_sim")
    out = helper.create_tmp_variable(X.dtype, shape=(-1, 1))
    helper.append_op("cos_sim", inputs={"X": [X.name], "Y": [Y.name]},
                     outputs={"Out": [out.name]})
    return out


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, **kwargs):
    """fluid nn.py:663 -> chunk_eval op; returns (precision, recall, f1,
    num_infer_chunks, num_label_chunks, num_correct_chunks)."""
    helper = LayerHelper("chunk_eval")
    inputs = {"Inference": [input.name], "Label": [label.name]}
    lv = get_length_var(input) or get_length_var(label)
    if lv is not None:
        inputs["Length"] = [lv.name]
    outs = [helper.create_tmp_variable("float32", shape=None)
            for _ in range(3)]
    counts = [helper.create_tmp_variable("int64", shape=None)
              for _ in range(3)]
    helper.append_op(
        "chunk_eval", inputs=inputs,
        outputs={"Precision": [outs[0].name], "Recall": [outs[1].name],
                 "F1-Score": [outs[2].name],
                 "NumInferChunks": [counts[0].name],
                 "NumLabelChunks": [counts[1].name],
                 "NumCorrectChunks": [counts[2].name]},
        attrs={"chunk_scheme": chunk_scheme,
               "num_chunk_types": int(num_chunk_types),
               "excluded_chunk_types": list(excluded_chunk_types or [])})
    return (*outs, *counts)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=None, stride=None, dilation=None,
                     param_attr=None, name=None):
    """fluid nn.py:1176 -> conv2d_transpose op (filter [C_in, C_out, kh, kw]
    as conv_transpose_op.h)."""
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         name=name)
    C = int(input.shape[1])
    stride = stride or 1
    padding = padding if padding is not None else 0
    dilation = dilation or 1
    pair = lambda v: [int(v)] * 2 if not isinstance(v, (list, tuple)) \
        else [int(x) for x in v]
    if filter_size is None:
        if output_size is None:
            raise ValueError("conv2d_transpose needs filter_size or "
                             "output_size")
        os, st, pd, dl = (pair(output_size), pair(stride), pair(padding),
                          pair(dilation))
        H, W = int(input.shape[2]), int(input.shape[3])
        filter_size = [
            (os[i] - (([H, W][i] - 1) * st[i] - 2 * pd[i] + 1)) // dl[i] + 1
            for i in range(2)]
    ks = pair(filter_size)
    w = helper.create_parameter(
        attr=param_attr if isinstance(param_attr, dict) else {},
        shape=[C, int(num_filters)] + ks, dtype=input.dtype)
    # static output shape when the spatial dims are known (transposed-
    # conv arithmetic) — consumers like concat need it (r5 unet).
    # Unknown dims are -1 in this codebase (conv2d's _od convention):
    # propagate the sentinel instead of computing garbage from it
    shape = None
    if input.shape is not None:
        st, pd, dl = pair(stride), pair(padding), pair(dilation)

        def _od(i, idx):
            if i is None or int(i) < 0:
                return -1
            return (int(i) - 1) * st[idx] - 2 * pd[idx] \
                + dl[idx] * (ks[idx] - 1) + 1

        shape = (input.shape[0], int(num_filters),
                 _od(input.shape[2], 0), _od(input.shape[3], 1))
    out = helper.create_tmp_variable(input.dtype, shape=shape)
    helper.append_op(
        "conv2d_transpose",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [out.name]},
        attrs={"strides": pair(stride), "paddings": pair(padding),
               "dilations": pair(dilation)})
    return out


def sequence_expand(x, y, name=None):
    """fluid nn.py:1283: broadcast one row of x per sequence of y over y's
    steps (sequence_expand_op.cc on the padded+lengths representation)."""
    helper = LayerHelper("sequence_expand", name=name)
    lv = get_length_var(y)
    if lv is None:
        raise ValueError("sequence_expand: y must be a sequence "
                         "(carry a length var)")
    T = int(y.shape[1]) if y.shape and int(y.shape[1]) > 0 else -1
    out = helper.create_tmp_variable(x.dtype, shape=None)
    inputs = {"X": [x.name], "Length": [lv.name]}
    if T < 0:  # padded T unknown at build: resolve from y at trace time
        inputs["Ref"] = [y.name]
    helper.append_op("sequence_expand", inputs=inputs,
                     outputs={"Out": [out.name]}, attrs={"max_len": T})
    propagate_length(y, out)
    return out


def sequence_first_step(input, **kwargs):
    return sequence_pool(input, pool_type="first")


def sequence_last_step(input, **kwargs):
    return sequence_pool(input, pool_type="last")


def sequence_reshape(input, new_dim):
    helper = LayerHelper("sequence_reshape")
    lv = get_length_var(input)
    if lv is None:
        raise ValueError("sequence_reshape: input must be a sequence")
    out = helper.create_tmp_variable(input.dtype, shape=None)
    newlen = helper.create_tmp_variable("int32", shape=None)
    helper.append_op("sequence_reshape",
                     inputs={"X": [input.name], "Length": [lv.name]},
                     outputs={"Out": [out.name], "LengthOut": [newlen.name]},
                     attrs={"new_dim": int(new_dim)})
    from .sequence import _set_length
    _set_length(out, newlen.name)
    return out


def split(input, num_or_sections, dim=-1, name=None):
    """fluid nn.py:1654 -> split op; returns a list of Variables."""
    helper = LayerHelper("split", name=name)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "axis": int(dim)}
    else:
        n = len(num_or_sections)
        attrs = {"sections": [int(s) for s in num_or_sections],
                 "axis": int(dim)}
    outs = [helper.create_tmp_variable(input.dtype, shape=None)
            for _ in range(n)]
    helper.append_op("split", inputs={"X": [input.name]},
                     outputs={"Out": [o.name for o in outs]}, attrs=attrs)
    return outs


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    """fluid nn.py:1714: x / sqrt(max(sum(x^2, axis), epsilon)) — composed
    from elementwise ops; XLA fuses the chain."""
    sq = _tensor.elementwise_mul(x, x)
    s = _tensor.reduce_sum(sq, dim=axis, keep_dim=True)
    helper = LayerHelper("l2_normalize", name=name)
    clipped = helper.create_tmp_variable(x.dtype, shape=None)
    helper.append_op("clip", inputs={"X": [s.name]},
                     outputs={"Out": [clipped.name]},
                     attrs={"min": float(epsilon), "max": 3.4e38})
    rsq = helper.create_tmp_variable(x.dtype, shape=None)
    helper.append_op("sqrt", inputs={"X": [clipped.name]},
                     outputs={"Out": [rsq.name]})
    return _tensor.elementwise_div(x, rsq)


def warpctc(input, label, blank=0, norm_by_times=False, **kwargs):
    """fluid nn.py warpctc -> warpctc op over padded logits/labels with
    companion lengths."""
    helper = LayerHelper("warpctc")
    ilen, llen = get_length_var(input), get_length_var(label)
    if ilen is None or llen is None:
        raise ValueError("warpctc: input and label must be sequences")
    loss = helper.create_tmp_variable(input.dtype, shape=None)
    grad = helper.create_tmp_variable(input.dtype, shape=None)
    helper.append_op(
        "warpctc",
        inputs={"Logits": [input.name], "Label": [label.name],
                "LogitsLength": [ilen.name], "LabelLength": [llen.name]},
        outputs={"Loss": [loss.name], "WarpCTCGrad": [grad.name]},
        attrs={"blank": int(blank), "norm_by_times": bool(norm_by_times)})
    return loss


# --- tensor.py parity -------------------------------------------------------

def create_tensor(dtype, name=None, persistable=False):
    block = default_main_program().current_block()
    from ..framework import unique_name
    return block.create_var(name=name or unique_name.generate("create_tensor"),
                            shape=None, dtype=dtype,
                            persistable=persistable)


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    helper = LayerHelper("create_parameter")
    attr = dict(attr or {})
    if name:
        attr.setdefault("name", name)
    return helper.create_parameter(attr=attr, shape=list(shape), dtype=dtype,
                                   is_bias=is_bias,
                                   default_initializer=default_initializer)


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0):
    helper = LayerHelper("fill_constant_batch_size_like")
    out = helper.create_tmp_variable(dtype, shape=tuple(shape),
                                     stop_gradient=True)
    helper.append_op("fill_constant_batch_size_like",
                     inputs={"Input": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"shape": list(shape), "dtype": dtype,
                            "value": float(value),
                            "input_dim_idx": int(input_dim_idx),
                            "output_dim_idx": int(output_dim_idx)})
    return out


def ones(shape, dtype, **kwargs):
    return _tensor.fill_constant(shape=shape, dtype=dtype, value=1.0)


def zeros(shape, dtype, **kwargs):
    return _tensor.fill_constant(shape=shape, dtype=dtype, value=0.0)


# --- control_flow.py parity -------------------------------------------------

def create_array(dtype, cap, elem_shape, ref=None):
    """Tensor array as a dense [cap, ...] buffer (design shift from
    LoDTensorArray: while-loop step outputs live in a preallocated static
    buffer; see ops/control_flow_ops.py create_array).  A -1 in elem_shape
    is the batch dim, resolved at trace time from `ref`."""
    helper = LayerHelper("create_array")
    out = helper.create_tmp_variable(dtype, shape=None, stop_gradient=True)
    shape = [int(cap)] + [int(s) for s in elem_shape]
    inputs = {}
    if any(s < 0 for s in shape[1:]):
        if ref is None:
            raise ValueError("create_array: elem_shape has a batch (-1) dim "
                             "-> pass ref= (a var whose dim 0 is the batch)")
        inputs["Ref"] = [ref.name]
    helper.append_op("create_array", inputs=inputs,
                     outputs={"Out": [out.name]},
                     attrs={"shape": shape, "dtype": dtype})
    return out


def array_write(x, i, array):
    helper = LayerHelper("array_write")
    out = helper.create_tmp_variable(x.dtype, shape=None, stop_gradient=True)
    helper.append_op("array_write",
                     inputs={"Array": [array.name], "X": [x.name],
                             "I": [i.name]},
                     outputs={"Out": [out.name]})
    return out


def array_read(array, i):
    helper = LayerHelper("array_read")
    out = helper.create_tmp_variable(array.dtype, shape=None,
                                     stop_gradient=True)
    helper.append_op("array_read",
                     inputs={"Array": [array.name], "I": [i.name]},
                     outputs={"Out": [out.name]})
    return out


def array_length(array):
    """Static capacity of a dense tensor array (shape op on dim 0)."""
    helper = LayerHelper("array_length")
    sh = helper.create_tmp_variable("int64", shape=None, stop_gradient=True)
    helper.append_op("shape", inputs={"Input": [array.name]},
                     outputs={"Out": [sh.name]})
    out = helper.create_tmp_variable("int64", shape=None, stop_gradient=True)
    helper.append_op("slice", inputs={"Input": [sh.name]},
                     outputs={"Out": [out.name]},
                     attrs={"axes": [0], "starts": [0], "ends": [1]})
    return out


def lod_rank_table(x, level=0):
    """Length-descending sequence order (reference lod_rank_table.cc sorted
    the batch by length so while-steps could shrink; with padded+lengths the
    rank table is just argsort(-lengths))."""
    lv = get_length_var(x)
    if lv is None:
        raise ValueError("lod_rank_table: x must be a sequence")
    helper = LayerHelper("lod_rank_table")
    neg = helper.create_tmp_variable("float32", shape=None,
                                     stop_gradient=True)
    helper.append_op("scale", inputs={"X": [lv.name]},
                     outputs={"Out": [neg.name]},
                     attrs={"scale": -1.0, "bias": 0.0})
    out = helper.create_tmp_variable("int64", shape=None, stop_gradient=True)
    helper.append_op("arg_sort", inputs={"X": [neg.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": 0})
    out._rank_source = x  # the Variable itself (program-safe)
    return out


def max_sequence_len(rank_table_or_seq):
    """reference max_sequence_len_op: longest sequence in the batch — here a
    reduce_max over the length var."""
    v = rank_table_or_seq
    src = getattr(v, "_rank_source", None)
    if src is not None:
        v = src
    lv = get_length_var(v)
    if lv is None:
        raise ValueError("max_sequence_len needs a sequence or rank table")
    helper = LayerHelper("max_sequence_len")
    out = helper.create_tmp_variable("int32", shape=None, stop_gradient=True)
    helper.append_op("reduce_max", inputs={"X": [lv.name]},
                     outputs={"Out": [out.name]},
                     attrs={"dim": 0, "keep_dim": True})
    return out


def reorder_lod_tensor_by_rank(x, rank_table):
    """Gather batch rows into rank-table order (reorder_lod_tensor_by_rank_
    op.cc)."""
    helper = LayerHelper("reorder_by_rank")
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op("gather", inputs={"X": [x.name],
                                       "Index": [rank_table.name]},
                     outputs={"Out": [out.name]})
    lv = get_length_var(x)
    if lv is not None:
        nl = helper.create_tmp_variable(lv.dtype, shape=None,
                                        stop_gradient=True)
        helper.append_op("gather", inputs={"X": [lv.name],
                                           "Index": [rank_table.name]},
                         outputs={"Out": [nl.name]})
        from .sequence import _set_length
        _set_length(out, nl.name)
    return out


def lod_tensor_to_array(x, table=None):
    """[B, T, D] sequence -> time-major [T, B, D] array view (the reference
    split sequences into per-step LoDTensorArray entries; static shapes make
    it one transpose)."""
    nd = len(x.shape) if x.shape else 3
    return _tensor.transpose(x, [1, 0] + list(range(2, nd)))


def array_to_lod_tensor(x, table=None):
    """Inverse of lod_tensor_to_array."""
    nd = len(x.shape) if x.shape else 3
    return _tensor.transpose(x, [1, 0] + list(range(2, nd)))


def shrink_memory(x, i, table):
    """reference shrink_rnn_memory_op shrank the live batch as sequences
    finished; masked scan keeps the batch static, so this is identity (the
    mask in sequence ops provides the same semantics)."""
    return x


def split_lod_tensor(input, mask, level=0):
    """IfElse data routing (split_lod_tensor_op.cc): both branches see the
    full batch with the opposite rows zero-masked — the static-shape
    reading of LoD row splitting."""
    helper = LayerHelper("split_lod_tensor")
    zero = _tensor.fill_constant(shape=[1], dtype=input.dtype, value=0.0)
    t = helper.create_tmp_variable(input.dtype, shape=input.shape)
    f = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("select", inputs={"Mask": [mask.name],
                                       "X": [input.name],
                                       "Y": [zero.name]},
                     outputs={"Out": [t.name]})
    helper.append_op("select", inputs={"Mask": [mask.name],
                                       "X": [zero.name],
                                       "Y": [input.name]},
                     outputs={"Out": [f.name]})
    return t, f


def merge_lod_tensor(in_true, in_false, x, mask, level=0):
    """Merge the two IfElse branch outputs row-wise by mask."""
    helper = LayerHelper("merge_lod_tensor")
    out = helper.create_tmp_variable(in_true.dtype, shape=in_true.shape)
    helper.append_op("select", inputs={"Mask": [mask.name],
                                       "X": [in_true.name],
                                       "Y": [in_false.name]},
                     outputs={"Out": [out.name]})
    return out


class IfElse:
    """fluid control_flow.py:1130 IfElse — per-ROW branching on a [B,1]
    bool/num mask.  Design shift: the reference split the LoD batch and ran
    each branch on its rows; under static shapes both branches run on the
    full batch and outputs merge row-wise by mask (select op), which is
    also how a TPU wants it (no dynamic shapes, branch cost is one fused
    where)."""

    def __init__(self, cond, name=None):
        self.cond = cond
        self._current = None
        self._true_outs = []
        self._false_outs = []

    class _Branch:
        def __init__(self, owner, is_true):
            self.owner, self.is_true = owner, is_true

        def __enter__(self):
            self.owner._current = (self.owner._true_outs if self.is_true
                                   else self.owner._false_outs)
            return self

        def __exit__(self, *exc):
            self.owner._current = None
            return False

    def true_block(self):
        return IfElse._Branch(self, True)

    def false_block(self):
        return IfElse._Branch(self, False)

    def input(self, x):
        # the reference masked rows here; full-batch execution makes this
        # the identity — the mask is applied at merge time
        return x

    def output(self, *outs):
        if self._current is None:
            raise ValueError("IfElse.output() must be called inside a "
                             "true_block()/false_block() context")
        self._current.extend(outs)

    def __call__(self):
        if len(self._true_outs) != len(self._false_outs):
            raise ValueError(
                f"IfElse branches produced {len(self._true_outs)} vs "
                f"{len(self._false_outs)} outputs; they must match")
        return [merge_lod_tensor(t, f, None, self.cond)
                for t, f in zip(self._true_outs, self._false_outs)]


class ParallelDo:
    """fluid control_flow.py:210 ParallelDo (parallel_do_op.cc:82 scope-per-
    device fan-out).  Design shift: pjit shards the WHOLE step over the mesh
    (parallel/parallel_executor.py), so the body builds once on the full
    batch and data parallelism is a sharding annotation, not an op.  The
    class keeps the book-script surface: do() yields a block context,
    read_input is identity, outputs pass through."""

    def __init__(self, places, name=None):
        self.places = places
        self._outs = []

    class _Block:
        def __init__(self, owner):
            self.owner = owner

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def do(self):
        return ParallelDo._Block(self)

    def read_input(self, x):
        return x

    def write_output(self, x):
        self._outs.append(x)

    def __call__(self):
        return list(self._outs)


def Print(input, first_n=-1, message=None, summarize=-1,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """fluid control_flow.py Print -> print op (jax.debug.print under jit)."""
    helper = LayerHelper("print")
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("print", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"message": message or f"{input.name}: "})
    return out


def get_places(device_count=None, device_type=None):
    """fluid device.py get_places (get_places_op.cc:34): enumerate execution
    places.  Returns real Place objects — under the SPMD design the mesh
    (paddle_tpu/mesh.py) is the multi-device story, so this is for surface
    parity and host-side iteration."""
    from ..framework.place import CPUPlace, TPUPlace, default_place
    import jax

    if device_type is None:
        on_chip = isinstance(default_place(), TPUPlace)
    else:
        on_chip = device_type in ("tpu", "gpu", "cuda")
    n = device_count or len(jax.devices())
    if on_chip:
        return [TPUPlace(i) for i in range(n)]
    return [CPUPlace() for _ in range(n)]


class BlockGuard:
    """Context manager that builds ops into a fresh sub-block (reference
    control_flow.py:21)."""

    def __init__(self, program=None):
        self.program = program or default_main_program()

    def __enter__(self):
        self.block = self.program.create_block()
        return self.block

    def __exit__(self, *exc):
        self.program.rollback()
        return False


WhileGuard = BlockGuard  # reference WhileGuard is BlockGuard + while wiring


class ConditionalBlock:
    """reference conditional_block_op.cc: run a block when a scalar cond is
    true; lowered on the existing ifelse/cond machinery."""

    def __init__(self, inputs, name=None):
        self.inputs = inputs

    def block(self):
        return BlockGuard()


class BlockGuardWithCompletion(BlockGuard):
    """reference control_flow.py:38: BlockGuard that notifies its RNN owner
    on exit (StaticRNN uses it); kept for surface parity — StaticRNN here
    manages its own step() context."""

    def __init__(self, rnn):
        super().__init__()
        self.rnn = rnn

    def __exit__(self, *exc):
        if hasattr(self.rnn, "_complete"):
            self.rnn._complete()
        return super().__exit__(*exc)


class StaticRNNMemoryLink:
    """reference control_flow.py:331: record linking a memory var to its
    updated twin inside StaticRNN (init, pre_mem, mem)."""

    def __init__(self, init, pre_mem, mem=None):
        self.init = init
        self.pre_mem = pre_mem
        self.mem = mem
