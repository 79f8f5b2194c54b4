"""Op registry: type → (JAX emitter, grad maker).

The reference registers ~190 ops into OpInfoMap (paddle/framework/op_registry.h:62,
op_info.h), each with a creator, per-(place,dtype,layout,library) kernels
(paddle/framework/operator.h:356), and a GradOpDescMaker
(paddle/framework/grad_op_desc_maker.h).  Here an op is a single *emitter*:

    emit(ctx, ins, attrs) -> outs

where ``ins``/``outs`` map slot name → list of JAX arrays.  One emitter serves
every place/dtype — XLA generates the device code, replacing the whole
paddle/cuda + operators/*.cu kernel corpus (SURVEY.md §2.10).

Desc-level autodiff keeps the reference's shape (backward.cc:353 MakeOpGrad): a
grad *maker* turns a forward OpDesc into grad OpDescs appended to the block.
The default maker builds one ``<type>_grad`` op carrying the forward op's
inputs/outputs/attrs; the default grad *emitter* re-traces the forward emitter
under ``jax.vjp`` and applies the output cotangents.  The recomputed forward
subgraph is CSE'd/fused by XLA (or acts as rematerialization, which is usually a
win on TPU where HBM bandwidth, not FLOPs, is the bottleneck).  Ops that want a
cheaper analytic backward (using their saved outputs) register a custom grad
emitter; stateful/optimizer ops register ``grad=None``.

A Pallas kernel is the exception to "CSE'd": two Mosaic calls are never merged,
so a kernel's forward re-emitted under ``jax.vjp`` would run twice a step.  The
kernel-pair protocol, in three places and no others:

* the kernel file declares a pair (``pallas_kernels/_common.py kernel_pair``):
  its operand count, its bare forward, ``forward(*ops, keep) -> (out,
  *residuals)`` and ``backward(ops, do, kept)``, memoized by what the launches
  are built from; the ``jax.custom_vjp`` triple (the differentiable pair,
  ``.keeping``, ``.from_saved``) is derived there, once;
* the emitter calls ``ctx.run_pair(pair, ops) -> (out, saved)``, which picks
  among the four (a re-emission handed what was kept: ``from_saved``;
  inference: the bare forward; a re-emission handed nothing, as a
  ``layers.recompute`` segment's replay is: the plain pair; a forward emission:
  ``keeping``), and then ``ctx.keep_for_grad(attrs, [ITS OWN output], saved)``
  where ``saved`` is not None;
* ``generic_grad`` hands ``saved`` back to the re-emission if the grad op
  received that very output, and counts what ``run_pair`` reported in
  ``executor_grad_kernel_forward_total{op, reused}``: ``reused="1"`` no forward
  launched again, ``"0"`` at least one (docs/observability.md).

``keep_for_grad`` has four callers: the three kinds of kernel emitter above
(one pair, several under a dict, a pair whose output is not the op's) and the
``recompute`` op (ops/control_flow_ops.py), which keeps no kernel's residuals
but the VALUES its builder named (``layers.recompute(keep=...)``), as ``{name:
value}`` beside all its outputs.  Its re-emission takes them with
``take_kept_for_grad`` and lowers the segment's ops itself, so an emitter
inside a replay is handed NOTHING (``kept_for_grad()`` is None there: what was
kept is the segment's, not the inner op's, and the inner op's own forward
emission kept under a uid no grad op asks for), runs its plain pair, and its
``kernel_forward`` report still lands on the recompute grad op's count
(``{op="recompute", reused="0"}``).  A ``jax.checkpoint`` policy
(``save_only_these_names``) is NOT a substitute: it saves from the primal pass
of the grad op's ``jax.vjp``, which XLA merges with the forward emission only
where both are plain HLO, and behind a Mosaic call nothing is (PERF.md, PR 66:
7 kernel launches and 8 products more, not 19 fewer).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from ..observability.metrics import REGISTRY as _MET


@dataclass
class OpInfo:
    type: str
    emit: Callable
    # grad maker: fn(op, requires_grad: set[str]) -> list of (type, ins, outs, attrs)
    # "default" → generic vjp-based grad; None → non-differentiable / stateful.
    grad: Optional[object] = "default"
    # slots whose values are integral / non-differentiable even if float
    non_diff_inputs: tuple = ()
    # output slots never given cotangents (e.g. saved state, masks, indices)
    non_diff_outputs: tuple = ()
    # analytic cost model: fn(ins, outs, attrs) -> {"flops": int, "bytes": int}
    # (either key optional) where ins/outs map slot -> [ShapeDtype|None].
    # None → the analyzer's shape-driven defaults (analysis/cost.py): one
    # flop per output element, bytes = inputs read + outputs written.
    cost: Optional[Callable] = None
    # sharding-propagation rule: fn(ctx, ins, outs, attrs) -> {slot:
    # [spec-tuple|None]} where ins/outs map slot -> [ShardedOperand|None]
    # (analysis/sharding.py) and ctx is its PropagationContext (mesh axis
    # sizes + ctx.collective(...) to declare implied communication).
    # None → the analyzer's structural defaults (elementwise join /
    # batch-led propagation).
    sharding: Optional[Callable] = None


_REGISTRY: Dict[str, OpInfo] = {}


def register_op(type: str, emit: Callable = None, **kw):
    """Register an op emitter. Usable as decorator or direct call."""

    def _do(fn):
        if type in _REGISTRY:
            raise ValueError(f"op {type!r} registered twice")
        _REGISTRY[type] = OpInfo(type=type, emit=fn, **kw)
        return fn

    if emit is not None:
        return _do(emit)
    return _do


def np_dtype(dtype: str):
    """The numpy/JAX dtype of a canonical dtype string of the IR."""
    import jax.numpy as jnp

    if dtype == "bfloat16":
        return jnp.bfloat16
    return np.dtype(dtype)


def dtype_bytes(dtype) -> int:
    """Item size of a dtype (string or numpy/JAX dtype; None and unknown
    names price as float32)."""
    try:
        return int(np.dtype(np_dtype(dtype or "float32")).itemsize)
    except Exception:
        return 4


class ShapeDtype:
    """Static (shape, dtype) of one op operand, as the cost model sees it:
    batch dims already bound, dtype a canonical string.  The cost-fn
    analog of the ShapeDtypeStruct the verifier's abstract eval uses."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def __repr__(self):
        return f"ShapeDtype({self.shape}, {self.dtype})"


def register_cost(type: str, fn: Callable = None):
    """Attach an analytic cost formula to an already-registered op.
    Usable as decorator or direct call; the formula lives beside the
    emitter in the op's module (matmul/conv/attention/collectives), the
    mechanism here.  fn(ins, outs, attrs) -> {"flops": int, "bytes": int}
    with either key optional — missing keys fall back to the analyzer's
    shape-driven defaults."""

    def _do(f):
        info = get_op_info(type)
        if info.cost is not None:
            raise ValueError(f"op {type!r} already has a cost formula")
        info.cost = f
        return f

    if fn is not None:
        return _do(fn)
    return _do


def register_sharding(type: str, fn: Callable = None):
    """Attach a sharding-propagation rule to an already-registered op.
    Usable as decorator or direct call; like `register_cost`, the rule
    lives beside the emitter in the op's module (matmul contraction
    resolution, the vocab-sharded lookup, sp ring/all-to-all attention,
    moe dispatch) — this is only the mechanism.  fn(ctx, ins, outs,
    attrs) -> {slot: [spec|None]} with specs as tuples of mesh-axis
    names/None; the rule declares implied collectives through
    ctx.collective(...)."""

    def _do(f):
        info = get_op_info(type)
        if info.sharding is not None:
            raise ValueError(f"op {type!r} already has a sharding rule")
        info.sharding = f
        return f

    if fn is not None:
        return _do(fn)
    return _do


def get_op_info(type: str) -> OpInfo:
    if type not in _REGISTRY:
        raise KeyError(
            f"no emitter registered for op {type!r} "
            f"(registered: {sorted(_REGISTRY)[:20]}...)"
        )
    return _REGISTRY[type]


def has_op(type: str) -> bool:
    return type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Emit context


class EmitContext:
    """Per-lowering state handed to emitters: RNG derivation, train/test mode,
    and program access for ops with sub-blocks (while/cond — AttrType.BLOCK)."""

    def __init__(self, key, is_test: bool, program=None, lower_block=None,
                 place=None):
        self.key = key
        self.is_test = is_test
        self.program = program
        # the Place this trace targets (None under ParallelExecutor, which
        # sets `mesh` instead); emitters gate backend-specific kernels
        # (Pallas) on target_platform(), not the process-global backend
        self.place = place
        self.mesh = None
        # callable(block_idx, env) -> env  provided by the executor so control
        # flow ops can lower nested blocks
        self.lower_block = lower_block
        # (path, overwrite) per `save` op, in op order; the executor fetches
        # the paired traced values and writes the files after the step (host
        # callbacks inside the program don't exist on all PJRT backends)
        self.host_saves = []
        # >0 while lowering a control-flow sub-block (while/cond body): ops
        # whose values must escape to the host (save) cannot live there
        self.sub_depth = 0
        # __uid__ -> (the forward op's outputs, what its emitter kept for
        # its grad op): see keep_for_grad
        self._kept = {}
        # the _Replay of the forward op generic_grad is re-emitting now
        self._replay = None
        # host seconds the ops lowered so far inside the op being lowered
        # took (a sub-block's): _lower_ops takes them off that op's own
        self.emit_nested_s = 0.0

    def rng(self, attrs) -> "object":
        """Deterministic per-op PRNG key: base key folded with the op's uid.

        Forward and generic-grad re-trace derive the same key, so stochastic
        ops (dropout, uniform_random) replay identically in backward."""
        import jax

        uid = int(attrs.get("__uid__", 0))
        return jax.random.fold_in(self.key, uid)

    def keep_for_grad(self, attrs, outs, saved):
        """A forward emitter that ran an opaque kernel (a Pallas custom
        call) keeps what its backward needs, for generic_grad to hand back
        when it re-emits the op under jax.vjp in this trace.  XLA's CSE
        merges a re-emitted forward made of plain HLO with the first; it
        does not merge two Mosaic calls, so without this the kernel's
        forward runs twice.  `outs` are the op's outputs as it returns
        them: the grad op must receive those very values for `saved` to
        be its.  Ignored inside a re-emission."""
        if self._replay is None:
            self._kept[int(attrs.get("__uid__", 0))] = (tuple(outs), saved)

    def in_grad_replay(self) -> bool:
        """True while generic_grad re-emits a forward op under jax.vjp: an
        emitter that counts or records its forward emission skips then."""
        return self._replay is not None

    def kept_for_grad(self):
        """Inside generic_grad's re-emission: what this op's forward
        emission kept in this trace, else None (no grad op around the
        call, another trace, a `__remat__` grad op, or nothing kept)."""
        return self._replay.saved if self._replay is not None else None

    def take_kept_for_grad(self):
        """`kept_for_grad()` for an op whose re-emission lowers other ops
        (the `recompute` op): what it kept is its alone, so the emitters it
        lowers from here on are handed nothing, while their `kernel_forward`
        reports still reach this grad op's count."""
        held = self.kept_for_grad()
        if held is not None:
            self._replay.saved = None
        return held

    def kernel_forward(self, reused: bool):
        """An emitter that took a Pallas custom_vjp path says whether its
        grad op's re-emission `reused` kept results or launches the
        kernel's forward again; generic_grad counts it
        (executor_grad_kernel_forward_total): reused=1 where every kernel
        of the re-emission found its own.  Nothing outside one."""
        if self._replay is not None:
            self._replay.kernel_forward_reused = bool(reused) and (
                self._replay.kernel_forward_reused is not False)

    def run_pair(self, pair, ops, kept=None):
        """Run a kernel pair (`pallas_kernels/_common.py kernel_pair`) on
        `ops` the way this emission needs it -> (out, saved): the ONE place
        that chooses (the module docstring has the four).  `saved` = (out,
        *residuals) comes from a forward emission alone, for the emitter
        to keep beside ITS OWN output (`keep_for_grad(attrs, [the op's
        output], saved)`: the kernel's `out` is not always the op's), and
        is None otherwise.  `kept`: what the re-emission was handed for
        THIS pair where the op keeps several (`gated_delta_rule`'s dict;
        () for nothing); by default all the op kept.  Reports
        `kernel_forward`."""
        if kept is None:
            kept = self.kept_for_grad()
        saved = None
        if kept:
            out = pair.from_saved(*ops, *kept)
        elif self.is_test:
            out = pair.bare(*ops)
        elif self.in_grad_replay():
            out = pair(*ops)
        else:
            saved = pair.keeping(*ops)
            out = saved[0]
        self.kernel_forward(reused=bool(kept))
        return out, saved

    def target_platform(self) -> str:
        """Platform ('tpu'/'cpu'/...) of the device(s) this trace will run
        on — the executor's pinned place or the mesh, falling back to the
        process default backend."""
        import jax

        if self.mesh is not None:
            return self.mesh.devices.flat[0].platform
        if self.place is not None:
            return self.place.jax_device().platform
        return jax.default_backend()


class _Replay:
    """generic_grad's word to the forward emitter it re-emits (`saved`:
    what that op's forward emission kept, or None), and the emitter's word
    back (`kernel_forward_reused`: None unless it took a kernel path)."""

    __slots__ = ("saved", "kernel_forward_reused")

    def __init__(self, saved):
        self.saved = saved
        self.kernel_forward_reused = None


# ---------------------------------------------------------------------------
# Generic grad: maker + emitter

GRAD_SUFFIX = "@GRAD"

_MET_GRAD_KERNEL_FORWARD = _MET.counter(
    "executor_grad_kernel_forward_total",
    "grad ops traced whose forward emitter took a Pallas custom_vjp path; "
    "reused=1 used the forward op's kept results, reused=0 emitted the "
    "kernel's forward a second time")


def default_grad_maker(op, requires_grad):
    """Build one `<type>_grad` op desc from a forward op desc.

    Inputs: forward inputs under their slots, forward outputs under theirs,
    plus `<slot>@GRAD` for each forward output.  Outputs: `<slot>@GRAD` per
    forward input slot, with "" placeholders for vars not requiring grad.
    Mirrors the structure DefaultGradOpDescMaker produces in the reference
    (grad_op_desc_maker.h)."""
    info = get_op_info(op.type)
    ins = {}
    for slot, names in op.inputs.items():
        ins[slot] = list(names)
    for slot, names in op.outputs.items():
        if slot in ins:
            raise ValueError(
                f"op {op.type}: output slot {slot} collides with input slot"
            )
        ins[slot] = list(names)
        ins[slot + GRAD_SUFFIX] = [n + GRAD_SUFFIX for n in names]
    outs = {}
    any_grad = False
    for slot, names in op.inputs.items():
        if slot in info.non_diff_inputs:
            continue
        grads = []
        for n in names:
            if n in requires_grad:
                grads.append(n + GRAD_SUFFIX)
                any_grad = True
            else:
                grads.append("")
        outs[slot + GRAD_SUFFIX] = grads
    if not any_grad:
        return []
    attrs = {
        "__fwd_type__": op.type,
        "__fwd_attrs__": dict(op.attrs),
        "__fwd_input_slots__": sorted(op.inputs.keys()),
        "__fwd_output_slots__": sorted(op.outputs.keys()),
        "__uid__": op.attrs.get("__uid__", 0),
    }
    return [("generic_grad", ins, outs, attrs)]


def _is_float_dtype(x) -> bool:
    dt = getattr(x, "dtype", None)
    if dt is None:
        return isinstance(x, float)
    s = str(dt)
    return s.startswith("float") or s in ("bfloat16", "float16")


def _generic_grad_emit(ctx, ins, attrs):
    import jax
    import jax.numpy as jnp

    fwd_type = attrs["__fwd_type__"]
    fwd_attrs = attrs["__fwd_attrs__"]
    in_slots = attrs["__fwd_input_slots__"]
    out_slots = attrs["__fwd_output_slots__"]
    info = get_op_info(fwd_type)

    fwd_ins = {s: list(ins.get(s, [])) for s in in_slots}

    # Which (slot, idx) to differentiate: grad op's *requested* outputs.
    diff_pos = []
    for s in in_slots:
        if s in info.non_diff_inputs:
            continue
        for i, v in enumerate(fwd_ins[s]):
            # requested iff the grad op declares a non-"" output there; the
            # executor passes that request via attrs["__wanted__"].
            if (s, i) in attrs["__wanted__"] and _is_float_dtype(v):
                diff_pos.append((s, i))

    diff_vals = [fwd_ins[s][i] for s, i in diff_pos]

    def fwd_fn(diff_flat):
        full = {s: list(vs) for s, vs in fwd_ins.items()}
        for (s, i), v in zip(diff_pos, diff_flat):
            full[s][i] = v
        outs = info.emit(ctx, full, fwd_attrs)
        flat = []
        for s in out_slots:
            for o in outs.get(s, []):
                flat.append(o)
        return flat

    # what the forward op's emitter kept for this grad op (keep_for_grad),
    # if the outputs it returned then are the very values this op receives
    kept = ctx._kept.pop(int(attrs.get("__uid__", 0)), None)
    fwd_outs = [o for s in out_slots for o in ins.get(s, [])]
    saved = None
    if kept is not None and len(kept[0]) == len(fwd_outs) and all(
            a is b for a, b in zip(kept[0], fwd_outs)):
        saved = kept[1]

    if attrs.get("__remat__"):
        # memory_optimize: force recompute-in-backward instead of XLA CSE
        # sharing activations with the forward pass (trades FLOPs for HBM)
        fwd_fn = jax.checkpoint(fwd_fn)
        saved = None

    replay = _Replay(saved)
    outer, ctx._replay = ctx._replay, replay
    try:
        primal_outs, vjp_fn = jax.vjp(fwd_fn, diff_vals)
    finally:
        ctx._replay = outer
    if replay.kernel_forward_reused is not None:
        _MET_GRAD_KERNEL_FORWARD.inc(
            op=fwd_type, reused=str(int(replay.kernel_forward_reused)))

    # Cotangents: grad inputs `<slot>@GRAD`; missing / non-diff outputs → zeros.
    cts = []
    k = 0
    for s in out_slots:
        n_out = len(ins.get(s, []))
        grads = ins.get(s + GRAD_SUFFIX, [])
        for i in range(n_out):
            primal = primal_outs[k]
            if (
                s in info.non_diff_outputs
                or i >= len(grads)
                or grads[i] is None
                or not _is_float_dtype(primal)
            ):
                cts.append(jnp.zeros_like(primal))
            else:
                cts.append(grads[i].astype(primal.dtype))
            k += 1
    (din_flat,) = vjp_fn(cts)

    out = {}
    for (s, i), g in zip(diff_pos, din_flat):
        out.setdefault(s + GRAD_SUFFIX, {})[i] = g
    # densify: executor zips by position; unrequested slots simply absent
    result = {}
    for s_grad, by_idx in out.items():
        n = max(by_idx) + 1
        result[s_grad] = [by_idx.get(i) for i in range(n)]
    return result


register_op("generic_grad", _generic_grad_emit, grad=None)


def _generic_grad_cost(ins, outs, attrs):
    """Backward ≈ 2x the forward's FLOPs (the dL/dX and dL/dW products of
    every matmul/conv); a remat-marked grad op re-runs its forward first,
    so __remat__ adds one more forward (the FLOPs-for-HBM trade the
    memory_optimize pass prices)."""
    info = _REGISTRY.get(attrs.get("__fwd_type__", ""))
    fwd_ins = {s: ins.get(s, [])
               for s in attrs.get("__fwd_input_slots__", ())}
    fwd_outs = {s: ins.get(s, [])
                for s in attrs.get("__fwd_output_slots__", ())}
    fwd_flops = None
    if info is not None and info.cost is not None:
        try:
            fwd_flops = info.cost(fwd_ins, fwd_outs,
                                  attrs.get("__fwd_attrs__", {})).get("flops")
        except Exception:
            fwd_flops = None
    if fwd_flops is None:
        fwd_flops = sum(v.size for vs in fwd_outs.values()
                        for v in vs if v is not None)
    mult = 3 if attrs.get("__remat__") else 2
    return {"flops": mult * int(fwd_flops)}


register_cost("generic_grad", _generic_grad_cost)
