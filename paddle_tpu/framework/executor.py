"""Executor: whole-block XLA compilation (replaces executor.cc:77's interpreter).

The reference Executor creates scope vars then interprets `OpDesc`s one at a
time, each op dispatching a device kernel (framework/executor.cc:116,
operator.cc:461-530).  Here `Executor.run` *lowers the whole block* into a
single pure JAX function

    (state_written, state_read, feeds, rng_key) -> (fetches, new_state)

jits it once per (program version, feed shapes, place), caches the executable,
and thereafter each `run` is one XLA invocation: parameters stay resident in
HBM, optimizer updates are fused into the same program as forward+backward, and
written state buffers are donated so updates are in-place.  This is the
"Executor as compiler" stance of SURVEY.md §7 step 3.

Feed/fetch (feed_fetch_method.h in the reference) become the function arguments
and results; host↔HBM transfer happens only there.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..observability import attribution as _attr
from ..observability.metrics import REGISTRY as _MET, monotime as _monotime
from ..observability.tracing import TRACER as _TRC, now as _trace_now
from ..ops.registry import EmitContext, get_op_info
from .core import Program, Variable, canonical_dtype, np_dtype
from .dataflow import state_classes
from .place import Place, default_place
from .scope import Scope, global_scope

logger = logging.getLogger("paddle_tpu")

# the last two fields of a dispatch's row of the step record where nothing
# ran before its root (tracing.STEP_FIELDS: `t_distribute0`, `t_distribute1`)
_NOT_DISTRIBUTED = (None, None)

# counter handles resolved once (families survive REGISTRY.reset()):
# these sit on the per-run hot path, where a per-step family lookup
# (name regex + registry lock) would be pure overhead
_MET_STEPS = _MET.counter("executor_steps_total",
                          "completed Executor.run invocations")
_MET_PROG_CACHE = _MET.counter(
    "executor_program_cache_total",
    "executable-cache lookups by Executor.run")
_MET_COMPILE_S = _MET.counter(
    "executor_compile_seconds_total",
    "seconds JAX spent tracing, lowering and compiling (or fetching from "
    "the persistent cache) inside Executor.run, by phase")
_MET_JAX_COMPILES = _MET.counter(
    "executor_jax_compiles_total",
    "XLA compiles inside Executor.run; cached=1 came from the persistent "
    "cache")
_MET_OP_EMIT_S = _MET.counter(
    "executor_op_emit_seconds_total",
    "host seconds inside each op's emitter while a program is traced "
    "(once a compile, never a step), by op type (`<fwd>_grad` for a "
    "generic_grad); self time: an op that lowers a sub-block does not "
    "count the ops of that block")

# jax.monitoring's duration events of one compile -> the counter's phase
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_PERSISTENT_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# sent when an executable has come out of the persistent cache, inside the
# `backend` interval of the compile it serves
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

# ops the lowerer skips: pure-desc markers with no computation
_NOOP_TYPES = ("feed", "fetch")

# the program verifier `Executor._verify_program` calls: `paddle_tpu.analysis`
# (a layer above this one; `paddle_tpu/__init__` imports it) installs its
# `verify_program` here at import
_program_verifier = None


def install_program_verifier(verify_program) -> None:
    global _program_verifier
    _program_verifier = verify_program


def env_verify_enabled() -> bool:
    """The PADDLE_TPU_VERIFY=1 gate (Executor.run / transpiler contracts)."""
    return os.environ.get("PADDLE_TPU_VERIFY", "") not in ("", "0")


class OpLoweringError(RuntimeError):
    """An op failed to lower, annotated with op type + variable names
    (EnforceNotMet parity — reference enforce.h:64)."""


_SAVE_PREFIX = "__save__"


class _Compiled:
    def __init__(self, fn, external_reads, rw_state, written_state, fetch_names,
                 save_specs=()):
        self.fn = fn
        self.external_reads = external_reads  # read-only state var names
        self.rw_state = rw_state  # read-then-written: must pre-exist, donated
        self.written_state = written_state  # all names persisted back to scope
        self.fetch_names = fetch_names
        # (path, overwrite) per `save` op, derived statically from the block
        # descs at compile time (order = op order = the order emitters append
        # their traced values); the trace asserts it produced exactly these
        self.save_specs = tuple(save_specs)


def _fetch_name(f) -> str:
    return f.name if isinstance(f, Variable) else str(f)


def as_numpy(x):
    return np.asarray(x)


_cc_enabled = False

# <checkout>/.jax_cache: a fixed path derived from the package's own
# location, because the directory is part of what makes a later process
# find the entries again — never under ~, a temporary name, a pid or a
# host fingerprint
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _enable_compilation_cache():
    """Persistent XLA compilation cache, so repeat processes (CLI runs,
    one bench process per mode, a second chip_smoke.py) load executables
    instead of recompiling.  Where JAX_COMPILATION_CACHE_DIR is set the
    directory is the caller's and JAX reads the variable itself: nothing
    here touches it.  Otherwise the cache goes to <checkout>/.jax_cache.
    Entry format, thresholds and size bound (jax_compilation_cache_max_size)
    are stock JAX.  PADDLE_TPU_NO_COMPILE_CACHE=1 leaves JAX's defaults
    alone entirely."""
    global _cc_enabled
    if _cc_enabled or os.environ.get("PADDLE_TPU_NO_COMPILE_CACHE"):
        return
    _cc_enabled = True
    with _TRC.span("executor.cache_enable", cold=True):
        _point_jax_at_the_cache()


def _point_jax_at_the_cache():
    import jax

    # CPU: never enable the persistent cache.  DESERIALIZED XLA:CPU
    # executables intermittently write non-finite garbage into donated
    # buffers (reproduced on the serving KV pools: ~50% of processes
    # corrupt once entries LOAD, sticky per process; fresh compile+store
    # runs are 100% clean — so the stored bytes are fine and no digest
    # check can catch it).  CPU compiles are cheap and in-process
    # executables are reused anyway; the cache exists for the TPU's
    # 20-40s headline compiles.
    if jax.default_backend() == "cpu":
        return
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)


def _on_jax_phase_start(event, value, **kw):
    """jax.monitoring scalar listener: JAX announces a phase of a compile
    as it starts.  Inside a dispatch the tracer's clock is read here, so
    the interval that `_on_jax_compile_seconds` writes into the start-up
    record has both its ends on that clock."""
    phase = _COMPILE_PHASES.get(event)
    if phase is None or not getattr(_compiling, "dispatches", 0):
        return
    began = getattr(_compiling, "open", None)
    if began is None:
        began = _compiling.open = []
    began.append((phase, _trace_now()))


def _on_jax_compile_seconds(event, duration, fun_name="", **kw):
    """jax.monitoring duration listener: what JAX compiles while this
    thread is inside Executor._dispatch is the program's, and goes to the
    counters and, where a sink records spans, onto the innermost open one
    (`executor.execute`, as a rule); a compile outside a dispatch (a
    reference, a test's own jit) is not.  JAX calls listeners only when
    something compiles, so a steady step pays nothing.  `trace` counts a
    nested jit's tracing twice, inside its caller's, as every sum of
    these events does.

    Each event is also an INTERVAL of the start-up record, `jax.trace` /
    `jax.lower` / `jax.backend` with JAX's `fun_name`, under the span open
    on the thread, from `_on_jax_phase_start`'s stamp (or, where that was
    not taken, `duration` back) to this call's.  A trace INSIDE another
    phase is not kept (every `jnp` function is a jit that the step
    function's trace walks through, and lowering traces more: thousands
    in a model's step), so the record stays a few events a compile, and
    the union of what is kept is wall clock with nothing counted twice.
    `jax.cache_load` (the retrieval from the persistent cache, which
    carries no name) waits for the `backend` event it lies in and takes
    that one's."""
    now = _trace_now()
    if event == _CACHE_RETRIEVAL:
        _compiling.load = (now - duration, now)
        return
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    cached, load = False, None
    if phase == "backend":  # one per XLA compile, and its last event
        cached, _compiling.hit = getattr(_compiling, "hit", False), False
        load, _compiling.load = getattr(_compiling, "load", None), None
    if not getattr(_compiling, "dispatches", 0):
        return
    began = getattr(_compiling, "open", None)
    t0 = now - duration
    if began and began[-1][0] == phase:
        t0 = began.pop()[1]
    if load is not None:
        _TRC.cold_event("jax.cache_load", *load, fun_name=fun_name)
    if phase != "trace" or not began:
        _TRC.cold_event("jax." + phase, t0, now, fun_name=fun_name)
    _MET_COMPILE_S.inc(duration, phase=phase)
    if phase == "backend":
        _MET_JAX_COMPILES.inc(cached="1" if cached else "0")
    sp = _TRC.current()
    if sp is None:
        return
    sp.note(compile_s=sp.args.get("compile_s", 0.0) + duration)
    if phase == "backend":
        sp.note(jax_compiles=sp.args.get("jax_compiles", 0) + 1)


def _on_jax_event(event, **kw):
    """jax.monitoring event listener: a persistent-cache hit precedes the
    `backend` duration of the compile it served, on the same thread."""
    if event == _PERSISTENT_CACHE_HIT:
        _compiling.hit = True


# per thread: `dispatches`, how many Executor._dispatch calls are open;
# `hit`, whether the compile in progress came from the persistent cache, and
# `load`, when it did; `open`, the phases JAX has begun and not ended
_compiling = threading.local()
_listening = False


def _listen_to_jax_compiles():
    """Register the three callbacks with jax.monitoring, once a process."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring as mon

    mon.register_event_duration_secs_listener(_on_jax_compile_seconds)
    mon.register_event_listener(_on_jax_event)
    mon.register_scalar_listener(_on_jax_phase_start)


def _role(compiled, feed_vals) -> str:
    """What a reader of the start-up record may know of a program without
    guessing: `startup` reads nothing (no feed, no state of the scope) and
    only initialises persistables; every other program is `main`."""
    reads = feed_vals or compiled.rw_state or compiled.external_reads
    return "main" if reads else "startup"


def _write_saves(save_specs, fetches):
    """The files of a block's `save` ops, after its step."""
    for i, (path, overwrite) in enumerate(save_specs):
        if os.path.exists(path) and not overwrite:
            raise IOError(
                f"save op: {path!r} exists and overwrite=False "
                f"(save_op.cc semantics)")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # write through a file object: np.save(path) would append ".npy"
        # to extension-less reference-style paths
        with open(path, "wb") as f:
            np.save(f, np.asarray(fetches[f"{_SAVE_PREFIX}{i}"]),
                    allow_pickle=False)


def _check_finite(fetches, new_state, step):
    """FLAGS_check_nan_inf analog (reference executor.cc:26, 120-128):
    scan fetches + updated state for non-finite values."""
    for n, v in list(fetches.items()) + list(new_state.items()):
        arr = np.asarray(v)
        if np.issubdtype(arr.dtype, np.floating) and not np.all(
                np.isfinite(arr)):
            raise FloatingPointError(
                f"non-finite values in {n!r} after step {step}")


class Executor:
    """fluid.Executor equivalent (python executor.py:70 / pybind.cc:424)."""

    def __init__(self, place: Optional[Place] = None):
        _enable_compilation_cache()
        _listen_to_jax_compiles()
        self.place = place if place is not None else default_place()
        self._cache: Dict[tuple, _Compiled] = {}
        self._load_paths: Dict[tuple, tuple] = {}
        self._rng_keys: Dict[int, object] = {}  # seed -> base key (_rng_key)
        self._step = 0
        # the stamps around what a subclass's run() did before the root of
        # the dispatch it is about to make, for that dispatch's row
        self._before_root = _NOT_DISTRIBUTED
        # subclasses running sharded over a mesh bypass single-device pinning
        self._pin_device = True
        # sharded subclasses need the step output pytree to match their
        # out_shardings exactly (no `if in env` guard)
        self._strict_state = False
        # loop-safety verdicts (framework/step_loop.safety_report), keyed
        # like _verified so only a desc mutation re-runs the scan
        self._loop_safety: Dict[tuple, dict] = {}
        # FLAGS_check_nan_inf analog: per-step non-finite scan of outputs
        self.check_nan_inf = False
        # programs already verified (analysis/verifier.py), keyed like the
        # executable cache so re-verification only happens on mutation
        self._verified: set = set()

    # -- resume hooks (distributed/service.py checkpoint/restore) -------
    @property
    def global_step(self) -> int:
        """Monotonic run counter — the default PRNG fold-in step.  A
        resumed trainer must restore it (or pin `rng_step` per run) so
        the recovered stochastic stream equals the uninterrupted one."""
        return self._step

    def snapshot_state(self) -> dict:
        """JSON-serializable executor state for trainer checkpoints."""
        return {"step": int(self._step)}

    def restore_state(self, state: dict):
        """Inverse of snapshot_state — the checkpoint/resume hook."""
        self._step = int(state.get("step", 0))

    def optimized_hlo(self, program=None, feed=None, fetch_list=None,
                      scope=None, block_id: int = 0) -> str:
        """Post-optimization HLO text of the step executable.

        The recompile hits jax's persistent compile cache when the
        program already ran and the cache is on.  Keeps the
        jit argument-tuple contract inside this file instead of tools
        reaching into _cache/_prepare_feeds (ADVICE-style: private layout
        changes must not silently break the roofline tooling)."""
        return self._lowered(program, feed, fetch_list, scope,
                             block_id).compile().as_text()

    def _lowered(self, program, feed, fetch_list, scope, block_id):
        """Shared analysis-path plumbing for optimized_hlo/memory_stats:
        resolve the cached executable under run()'s exact staleness
        contract (cache key + load-file signature; a recompile is stored
        back so a later run() reuses the trace — ADVICE r4) and return
        the jax Lowering of the step over the CURRENT scope state."""
        import jax

        from .core import default_main_program
        from .scope import global_scope as _gs

        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else _gs()
        feed = feed or {}
        fetch_names = [_fetch_name(f) for f in (fetch_list or [])]
        block = program.blocks[block_id]
        feed_vals = self._prepare_feeds(block, feed)
        key = self._cache_key(program, block_id, feed_vals, fetch_names)
        load_sig = self._load_file_sig(program)
        entry = self._cache.get(key)
        if entry is None or entry[0] != load_sig:
            compiled = self._compile(program, block_id, feed_vals,
                                     fetch_names)
            self._cache[key] = (load_sig, compiled)
        else:
            compiled = entry[1]
        state_w = {n: scope.find(n) for n in compiled.rw_state}
        state_r = {n: scope.find(n) for n in compiled.external_reads}
        return compiled.fn.lower(state_w, state_r, feed_vals,
                                 self._rng_key(0))

    def memory_stats(self, program=None, feed=None, fetch_list=None,
                     scope=None, block_id: int = 0) -> dict:
        """XLA buffer-assignment byte counts of the step executable —
        the MEASURED side of the static HBM-peak validation
        (analysis/memory.py vs tools/hlo_analysis.py).

        Returns argument/output/temp/alias sizes plus `peak_bytes` =
        argument + temp: donated outputs alias the argument buffers
        (counted once there), and non-donated outputs are the fetch
        list, which the static estimator's activation set already
        covers.  Deliberately NOT argument+temp+output-alias: an
        executable deserialized from the persistent compile cache
        reports alias_size 0 while output_size still counts the
        donated state, so that formula double-counts every parameter
        on cache hits and the "measured" number would depend on cache
        temperature.  Same cache contract as optimized_hlo (shared via
        _lowered)."""
        ma = self._lowered(program, feed, fetch_list, scope,
                           block_id).compile().memory_analysis()
        stats = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        stats["peak_bytes"] = stats["argument_bytes"] + stats["temp_bytes"]
        return stats

    def _pin_host_array(self, scope, name, v):
        """Promote a host (numpy) scope value to a device buffer ONCE,
        writing it back so later steps reuse the buffer.

        Anything that writes numpy into the scope (fuse_batch_norm's folded
        filters, parameters.set_value, load paths) would otherwise be
        re-staged to the device on EVERY run: ~100 MB of weight upload
        per inference batch on the bs16 ResNet-50 infer bench."""
        if not isinstance(v, np.ndarray):
            return v
        import jax

        dv = jax.device_put(
            v, self.place.jax_device() if self._pin_device else None)
        scope.set(name, dv)
        return dv

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, object]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        block_id: int = 0,
        verify: Optional[bool] = None,
        rng_step: Optional[int] = None,
        steps_per_dispatch: Optional[int] = None,
        fetch_every: str = "all",
    ):
        """`verify`: run the static program verifier (analysis/verifier.py)
        before execution and raise VerificationError on error findings.
        Default None defers to the PADDLE_TPU_VERIFY=1 env gate; results
        are cached per program version so steady-state runs pay nothing.

        `rng_step`: pin the per-step PRNG fold-in to a fixed step index
        instead of this executor's monotonic step counter — the
        translation-validation differential oracle
        (analysis/equivalence.py) runs an original/rewritten program
        pair with rng_step=0 so both sides draw the same stochastic
        stream regardless of executor history.

        `steps_per_dispatch`: run K training steps in ONE fused dispatch
        (framework/step_loop.py): every feed must be leading-stacked
        `(K, ...)` — one slice per step — and fetches come back stacked
        `(K, ...)` (`fetch_every="all"`) or last-only ("last"); written
        state is the post-K value, the PRNG stream matches K sequential
        runs bit-for-bit, and `rng_step` (when given) pins the FIRST
        step's index.  None defers to PADDLE_TPU_STEPS_PER_DISPATCH
        (paddle_tpu/knobs.py).
        Loop-unsafe programs (save/load ops, nested control flow) fall
        back loudly to K sequential dispatches."""
        from .core import default_main_program

        if steps_per_dispatch is None:
            from ..knobs import steps_per_dispatch as _k_knob

            steps_per_dispatch = _k_knob(default=1)
        k = int(steps_per_dispatch)
        if k < 1:
            raise ValueError(f"steps_per_dispatch={k} must be >= 1")
        if k > 1:
            return self._run_loop(program, feed, fetch_list, scope,
                                  return_numpy, block_id, verify, rng_step,
                                  k, fetch_every)

        program = program if program is not None else default_main_program()
        feed = feed or {}
        fetch_names = [_fetch_name(f) for f in (fetch_list or [])]
        scope = scope if scope is not None else global_scope()

        if verify is None:
            verify = env_verify_enabled()
        if verify:
            self._verify_program(program, block_id, sorted(feed),
                                 fetch_names)

        return self._dispatch(program, block_id, feed, fetch_names, scope,
                              return_numpy, rng_step, 1, None)

    def _dispatch(self, *args):
        """_dispatch_under_spans, marked on the thread for the compile
        listener: the counters need no span to know whose a compile is."""
        _compiling.dispatches = getattr(_compiling, "dispatches", 0) + 1
        try:
            return self._dispatch_under_spans(*args)
        finally:
            _compiling.dispatches -= 1

    def _dispatch_under_spans(self, program, block_id, feed, fetch_names,
                              scope, return_numpy, rng_step, k, fetch_every):
        """One dispatch of `k` steps under its spans, shared by run() (k=1)
        and the fused path of _run_loop(): a root `executor.run` and under
        it `executor.prepare` (feeds, cache key, load-file
        signature, cache lookup; `executor.build` inside it when the key
        is new), `executor.donate`, `executor.rng`, `executor.execute`
        (the jitted call: JAX traces, lowers and compiles in its first
        one), `executor.writeback` and, for `return_numpy`,
        `executor.fetch`.  All carry `step`, the executor's counter at
        the dispatch's first step.

        What is recorded when (observability/tracing.py): the spans
        inside a profiler session or with the ring on, and otherwise none
        is built.  A dispatch that finds no executable (`cold`) is the one
        a process spends its start-up in: from that moment on its spans
        are cold, kept in the start-up record whatever is switched on,
        the root with the program's `role`; a steady dispatch pays a
        branch on `cold` for it.  And ALWAYS one row of the step record,
        written as the root closes: `step`, `k`, the program's token,
        `cold`, and four stamps of the tracer's clock, where the root
        opens and closes and around the jitted call where
        `executor.execute` stands (None where a raising dispatch never
        came), then the two stamps `_before_root` holds (around
        ParallelExecutor's `executor.distribute`; None elsewhere).  Four
        clock reads, a tuple and an append are all a steady dispatch pays
        for it."""
        import jax

        step = self._step
        block = program.blocks[block_id]
        before, self._before_root = self._before_root, _NOT_DISTRIBUTED
        cold = False
        t_execute0 = t_execute1 = None
        t_enter = _trace_now()
        with _TRC.span("executor.run", step=step, k=k,
                       program=program._cache_token) as sp_run:
            late_root = None  # the cold root, where sp_run is the no-op
            try:
                with _TRC.span("executor.prepare", step=step):
                    feed_vals = self._prepare_feeds(block, feed,
                                                    stacked=k > 1)
                    if k > 1:
                        from . import step_loop

                        step_loop.check_stacked(feed_vals, k)
                    key = self._cache_key(program, block_id, feed_vals,
                                          fetch_names)
                    if k > 1:
                        key += ("loop", k, fetch_every)
                    # the load-file signature lives beside the entry, not
                    # in the key: a rewritten load file must *replace* the
                    # stale executable, not leak an unbounded trail of
                    # dead entries
                    load_sig = self._load_file_sig(program)
                    entry = self._cache.get(key)
                    cold = entry is None or entry[0] != load_sig
                    if cold:
                        sp_run, late_root = _TRC.turn_cold(
                            sp_run, "executor.run", step=step, k=k,
                            program=program._cache_token)
                        # the desc analysis and the jax.jit wrapper;
                        # nothing compiles before the wrapper's first call
                        with _TRC.span("executor.build", cold=True,
                                       step=step, ops=len(block.ops)):
                            if k == 1:
                                compiled = self._compile(
                                    program, block_id, feed_vals,
                                    fetch_names)
                            else:
                                compiled = self._compile_loop(
                                    program, block_id, feed_vals,
                                    fetch_names, k, fetch_every)
                        self._cache[key] = (load_sig, compiled)
                        sp_run.note(role=_role(compiled, feed_vals))
                    else:
                        compiled = entry[1]
                    _MET_PROG_CACHE.inc(result="miss" if cold else "hit")
                sp_run.note(cache_hit=not cold)
                # the DONATION phase: pinning the donated (rw) and
                # read-only state buffers into device memory before the step
                with _TRC.span("executor.donate", cold=cold, step=step,
                               feeds=len(feed)) as sp_don:
                    state_w, state_r = self._pin_state(compiled, scope,
                                                       block)
                    sp_don.note(donated=len(state_w), reads=len(state_r))

                with _TRC.span("executor.rng", cold=cold, step=step):
                    first = step if rng_step is None else int(rng_step)
                    key0 = self._rng_key(program.random_seed)
                    if k == 1:
                        rng = (jax.random.fold_in(key0, first),)
                    else:
                        # the loop folds (base key, step index) per step
                        # ON DEVICE - bitwise the same stream as K
                        # sequential host-side fold_ins
                        rng = (key0, np.int32(first))
                self._step += k

                t_execute0 = _trace_now()
                with _TRC.span("executor.execute", cold=cold, step=step,
                               cache_hit=not cold), \
                        self._device_scope():
                    fetches, new_state = compiled.fn(state_w, state_r,
                                                     feed_vals, *rng)
                t_execute1 = _trace_now()
                with _TRC.span("executor.writeback", cold=cold, step=step,
                               written=len(new_state)):
                    for n, v in new_state.items():
                        scope.set(n, v)
                    # the donated buffers are dead and the scope has let go
                    # of them: dropping the last references here puts the
                    # cost of freeing a few hundred arrays inside the span,
                    # not after the root at the frame's exit
                    del state_w, state_r
                    if compiled.save_specs:
                        _write_saves(compiled.save_specs, fetches)
                if self.check_nan_inf:
                    _check_finite(fetches, new_state, self._step)
                _MET_STEPS.inc()
                if not return_numpy:
                    return [fetches[n] for n in fetch_names]
                with _TRC.span("executor.fetch", cold=cold, step=step,
                               fetches=len(fetch_names)):
                    return [as_numpy(fetches[n]) for n in fetch_names]
            finally:
                if late_root is not None:
                    late_root.__exit__(*sys.exc_info())
                _TRC.keep_step((step, k, program._cache_token, cold,
                                t_enter, t_execute0, t_execute1,
                                _trace_now()) + before)

    # ------------------------------------------------------------------
    def _device_scope(self):
        """jax.default_device(place) for a pinned executor; sharded
        subclasses place everything by explicit shardings instead."""
        import contextlib

        import jax

        if not self._pin_device:
            return contextlib.nullcontext()
        return jax.default_device(self.place.jax_device())

    def _rng_key(self, seed: int):
        """The base PRNG key for `seed` (made once per seed), COMMITTED
        to a pinned executor's device.  One committed input makes every
        output of the step committed, so written state is committed from
        the startup program on.  Left to chance it flips: startup's
        outputs are uncommitted, the outputs of a step fed device-staged
        batches are committed, and that change of jit signature between
        step 1 and step 2 lowered and compiled every training step twice
        (seen on the chip, PR 21)."""
        import jax

        key = self._rng_keys.get(seed)
        if key is None:
            with self._device_scope():
                key = jax.random.PRNGKey(seed)
            if self._pin_device:
                key = jax.device_put(key, self.place.jax_device())
            self._rng_keys[seed] = key
        return key

    def _pin_state(self, compiled, scope, block):
        """Resolve + device-pin the donated (rw) and read-only state for
        one dispatch; missing state raises the fluid-semantics errors."""
        state_w = {}
        for n in compiled.rw_state:
            v = scope.find(n)
            if v is None:
                raise RuntimeError(
                    f"variable {n!r} used before initialization — run "
                    f"the startup program first (fluid semantics)"
                )
            state_w[n] = self._pin_host_array(scope, n, v)
        state_r = {}
        for n in compiled.external_reads:
            v = scope.find(n)
            if v is None:
                bvar = block._find_var_recursive(n)
                if bvar is not None and bvar.is_data:
                    raise RuntimeError(
                        f"data variable {n!r} was not fed — add it to "
                        f"`feed`"
                    )
                raise RuntimeError(
                    f"variable {n!r} not initialized in scope")
            state_r[n] = self._pin_host_array(scope, n, v)
        return state_w, state_r

    # ------------------------------------------------------------------
    def _run_loop(self, program, feed, fetch_list, scope, return_numpy,
                  block_id, verify, rng_step, k, fetch_every):
        """The fused K-step path of run() (framework/step_loop.py): one
        XLA dispatch scans the step over leading-stacked feeds with the
        state carry donated and resident for all K steps.  Loop-unsafe
        programs degrade loudly to K sequential run() calls with the
        same stacked-fetch return shape."""
        from . import step_loop
        from .core import default_main_program

        program = program if program is not None else default_main_program()
        feed = feed or {}
        fetch_names = [_fetch_name(f) for f in (fetch_list or [])]
        scope = scope if scope is not None else global_scope()

        if verify is None:
            verify = env_verify_enabled()
        if verify:
            self._verify_program(program, block_id, sorted(feed),
                                 fetch_names)

        skey = (program._cache_token, program._version, block_id)
        safety = self._loop_safety.get(skey)
        if safety is None:
            for old in [s for s in self._loop_safety
                        if s[0] == program._cache_token
                        and s[1] != program._version]:
                del self._loop_safety[old]
            safety = step_loop.safety_report(program, block_id)
            self._loop_safety[skey] = safety

        if not safety["safe"]:
            step_loop.warn_unsafe(k, safety)
            feed_vals = self._prepare_feeds(program.blocks[block_id], feed,
                                            stacked=True)
            step_loop.check_stacked(feed_vals, k)
            per_step = []
            for i, feeds_i in enumerate(step_loop.split_feeds(feed_vals, k)):
                per_step.append(self.run(
                    program, feeds_i, fetch_list, scope,
                    return_numpy=return_numpy, block_id=block_id,
                    verify=False, steps_per_dispatch=1,
                    rng_step=(None if rng_step is None
                              else int(rng_step) + i)))
            if fetch_every == "last":
                return per_step[-1]
            if return_numpy:
                return [np.stack([outs[j] for outs in per_step])
                        for j in range(len(fetch_names))]
            import jax.numpy as jnp

            return [jnp.stack([outs[j] for outs in per_step])
                    for j in range(len(fetch_names))]

        return self._dispatch(program, block_id, feed, fetch_names, scope,
                              return_numpy, rng_step, k, fetch_every)

    # ------------------------------------------------------------------
    def _verify_program(self, program, block_id, feed_names, fetch_names):
        """Static pre-execution check (the TensorFlow-paper placement/
        well-formedness validation stance): errors raise, warnings log
        once.  One verification per (program version, feed/fetch set)."""
        key = (program._cache_token, program._version, block_id,
               tuple(feed_names), tuple(fetch_names))
        if key in self._verified:
            return
        if _program_verifier is None:
            raise RuntimeError(
                "Executor.run(verify=True): no program verifier is "
                "installed (importing paddle_tpu.analysis installs it)")
        # no fetches this call -> no fetch CONTEXT: [] would make the
        # dead-op rule treat every unfetched terminal op as dead weight
        report = _program_verifier(program, feed_names=feed_names,
                                   fetch_names=fetch_names or None,
                                   block_id=block_id)
        for f in report.warnings:
            logger.warning("program verifier: %s", f.format())
        report.raise_if_errors("Executor.run")
        # a version bump obsoletes older entries for the same program
        # (mirrors _load_paths: never an unbounded trail of dead keys)
        for old in [k for k in self._verified
                    if k[0] == program._cache_token
                    and k[1] != program._version]:
            self._verified.discard(old)
        self._verified.add(key)

    # ------------------------------------------------------------------
    def _prepare_feeds(self, block, feed: Dict[str, object],
                       stacked: bool = False):
        # `stacked`: the values carry a leading steps_per_dispatch dim
        # (K batches in one dispatch); the base path prepares them the
        # same way — the flag exists for sharded subclasses, whose feed
        # shardings must prepend the K dim
        import jax

        from ..lod import LENGTH_SUFFIX, as_lod_tensor, is_lod_feed

        out = {}
        for name, value in feed.items():
            if isinstance(value, jax.Array):
                # already device-resident (e.g. from a prefetching DataFeeder):
                # no host-side cast/copy — feed as-is
                out[name] = value
                continue
            var = block.var(name) if block.has_var(name) else None
            if var is not None and var.lod_level > 0 and is_lod_feed(value):
                # ragged feed → bucket-padded dense + int32 lengths companion
                lt = as_lod_tensor(value)
                padded, lengths = lt.to_padded(bucket=True)
                if var.dtype is not None:
                    padded = padded.astype(np_dtype(var.dtype), copy=False)
                out[name] = padded
                out[name + LENGTH_SUFFIX] = lengths
                continue
            arr = np.asarray(value)
            if var is not None and var.dtype is not None:
                arr = arr.astype(np_dtype(var.dtype), copy=False)
            out[name] = arr
        return out

    def _cache_key(self, program, block_id, feed_vals, fetch_names):
        feed_sig = tuple(
            (n, v.shape, str(v.dtype)) for n, v in sorted(feed_vals.items())
        )
        # program._cache_token is a never-reused monotonic id; id(program)
        # could alias a garbage-collected Program and serve a stale executable
        return (program._cache_token, program._version, block_id, feed_sig,
                tuple(fetch_names), self.place)

    def _load_file_sig(self, program):
        """`load` ops read their file at trace time (reference load_op.cc
        reads per execution); comparing (mtime, size) per load file makes a
        changed file retrace instead of serving the stale embedded constant.
        The path list is computed once per program version (all blocks, so
        loads inside while/cond sub-blocks count too); the common no-load
        case costs one dict hit per run."""
        import os

        pkey = (program._cache_token, program._version)
        paths = self._load_paths.get(pkey)
        if paths is None:
            # a version bump obsoletes older entries for the same program
            for old in [k for k in self._load_paths
                        if k[0] == program._cache_token]:
                del self._load_paths[old]
            paths = tuple(
                str(op.attrs.get("file_path", ""))
                for b in program.blocks for op in b.ops if op.type == "load")
            self._load_paths[pkey] = paths
        if not paths:
            return ()
        sig = []
        for path in paths:
            try:
                st = os.stat(path)
                # size too: coarse-mtime filesystems can miss a rewrite
                # landing in the same tick
                stamp = (st.st_mtime, st.st_size)
            except OSError:
                stamp = (-1.0, -1)
            sig.append((path, stamp))
        return tuple(sig)

    # ------------------------------------------------------------------
    def _analyze(self, block, feed_names):
        """Static pass over the desc: which names are read from the scope and
        which scope/persistable names the block writes (params updated by
        optimizer ops, BN stats, metric states).  The classification lives in
        dataflow.state_classes so the donation-safety rules and the HBM
        estimator price exactly the buffers this executor donates."""
        return state_classes(block, feed_names, skip_types=_NOOP_TYPES)

    def _emit_ctx(self, rng_key, is_test, program):
        """EmitContext for one step trace — subclasses attach their mesh."""
        return EmitContext(rng_key, is_test=is_test, program=program,
                           place=self.place if self._pin_device else None)

    def _make_step_fn(self, program, block_id, fetch_names, written_state,
                      is_test, save_specs):
        """The untraced single-step function `(state_w, state_r, feeds,
        rng_key) -> (fetches, new_state)` — shared verbatim by the
        single-step jit (`_compile`) and the K-step scan body
        (`_compile_loop` via framework/step_loop.py), so the fused loop
        lowers op-for-op identically to the path it amortizes."""
        import jax

        block = program.blocks[block_id]

        def step_fn(state_w, state_r, feeds, rng_key):
            env = {}
            env.update(state_r)
            env.update(state_w)
            env.update({n: jax.numpy.asarray(v) for n, v in feeds.items()})
            ctx = self._emit_ctx(rng_key, is_test, program)
            bind_lower_block(ctx, program)
            _lower_ops(block.ops, env, ctx)
            fetches = {n: env[n] for n in fetch_names}
            # `save` ops: their traced values leave the program as reserved
            # fetches; Executor.run writes the files after the step.  Any
            # retrace must reproduce the static manifest exactly
            if [(p, o) for p, o, _ in ctx.host_saves] != save_specs:
                raise RuntimeError(
                    f"save ops traced {[(p, o) for p, o, _ in ctx.host_saves]}"
                    f" but the block declares {save_specs}")
            for i, (_, _, val) in enumerate(ctx.host_saves):
                fetches[f"{_SAVE_PREFIX}{i}"] = val
            if self._strict_state:
                # sharded subclass: the output pytree must match the
                # out_shardings built per written_state exactly
                new_state = {n: env[n] for n in written_state}
            else:
                new_state = {n: env[n] for n in written_state if n in env}
            return fetches, new_state

        return step_fn

    def _jit_step(self, step_fn, program, external_reads, rw_state,
                  written_state, feed_names):
        import jax

        return jax.jit(step_fn, donate_argnums=(0,))

    def _jit_loop(self, loop_fn, program, external_reads, rw_state,
                  written_state, feed_names):
        import jax

        return jax.jit(loop_fn, donate_argnums=(0,))

    def _compile_parts(self, program, block_id, feed_vals, fetch_names):
        block = program.blocks[block_id]
        feed_names = list(feed_vals.keys())
        external_reads, rw_state, written_state = self._analyze(block,
                                                                feed_names)
        is_test = not any(
            op.type.endswith("_grad") or op.type == "generic_grad"
            for op in block.ops
        )
        # static save manifest from the descs (save ops inside control-flow
        # sub-blocks are rejected at emit time, so the top block is complete)
        save_specs = [(str(op.attrs["file_path"]),
                       bool(op.attrs.get("overwrite", True)))
                      for op in block.ops if op.type == "save"]
        step_fn = self._make_step_fn(program, block_id, fetch_names,
                                     written_state, is_test, save_specs)
        return (step_fn, feed_names, external_reads, rw_state,
                written_state, save_specs)

    def _compile(self, program, block_id, feed_vals, fetch_names) -> _Compiled:
        (step_fn, feed_names, external_reads, rw_state, written_state,
         save_specs) = self._compile_parts(program, block_id, feed_vals,
                                           fetch_names)
        jitted = self._jit_step(step_fn, program, external_reads, rw_state,
                                written_state, feed_names)
        logger.debug(
            "compiled block %d: %d ops, %d reads, %d writes, feeds=%s",
            block_id, len(program.blocks[block_id].ops),
            len(external_reads), len(written_state), feed_names,
        )
        return _Compiled(jitted, external_reads, rw_state, written_state,
                         fetch_names, save_specs)

    def _compile_loop(self, program, block_id, feed_vals, fetch_names,
                      k, fetch_every) -> _Compiled:
        """Fused K-step executable: the SAME step trace as `_compile`,
        wrapped in the framework/step_loop.py scan."""
        from . import step_loop

        (step_fn, feed_names, external_reads, rw_state, written_state,
         save_specs) = self._compile_parts(program, block_id, feed_vals,
                                           fetch_names)
        assert not save_specs  # safety_report rejects save ops before here
        loop_fn = step_loop.build_loop_fn(step_fn, rw_state, k, fetch_every)
        jitted = self._jit_loop(loop_fn, program, external_reads, rw_state,
                                written_state, feed_names)
        logger.debug(
            "compiled %d-step loop for block %d: %d ops, %d reads, "
            "%d writes, feeds=%s", k, block_id,
            len(program.blocks[block_id].ops), len(external_reads),
            len(written_state), feed_names,
        )
        return _Compiled(jitted, external_reads, rw_state, written_state,
                         fetch_names)

    def close(self):
        self._cache.clear()


def _lower_op(op, env, ctx):
    """Lower ONE op: build its slot inputs from the SSA env, emit, write the
    outputs back.  Shared by the whole-block trace below and the attribution
    oracle's segment-timed eager walk (observability/attribution.py), so both
    thread values identically."""
    try:
        info = get_op_info(op.type)
        ins = {
            slot: [env[n] if n else None for n in names]
            for slot, names in op.inputs.items()
        }
        attrs = op.attrs
        if op.type == "generic_grad":
            attrs = dict(op.attrs)
            attrs["__wanted__"] = {
                (slot[: -len("@GRAD")], i)
                for slot, names in op.outputs.items()
                for i, n in enumerate(names)
                if n
            }
        outs = info.emit(ctx, ins, attrs)
    except OpLoweringError:
        raise
    except Exception as e:
        # PADDLE_ENFORCE parity (enforce.h:64): a failing op names itself
        # and its variables instead of surfacing a bare JAX traceback
        in_names = {s: list(ns) for s, ns in op.inputs.items() if ns}
        out_names = {s: list(ns) for s, ns in op.outputs.items() if ns}
        raise OpLoweringError(
            f"error lowering op {op.type!r} "
            f"(inputs={in_names}, outputs={out_names}): "
            f"{type(e).__name__}: {e}"
        ) from e
    for slot, names in op.outputs.items():
        vals = outs.get(slot, []) if outs else []
        for i, n in enumerate(names):
            if not n:
                continue
            if i < len(vals) and vals[i] is not None:
                env[n] = vals[i]
    return outs


def bind_lower_block(ctx, program):
    """Give `ctx` its `lower_block(idx, env, after_op=None) -> env`: what a
    control-flow emitter lowers a sub-block of `program` with, for every
    trace that lowers descs (the executors', `compiler.build_callable`'s, a
    pipeline stage's, the analyses').  `after_op(op, env)` runs after each
    op of that block has written its outputs, inside the op's own scope: a
    `recompute` segment's replay puts the values it was handed in place of
    the ones just made (ops/control_flow_ops.py)."""
    def lower_sub(idx, sub_env, after_op=None):
        ctx.sub_depth += 1
        try:
            return _lower_ops(program.blocks[idx].ops, sub_env, ctx, after_op)
        finally:
            ctx.sub_depth -= 1

    ctx.lower_block = lower_sub


def _lower_ops(ops, env, ctx, after_op=None):
    """Trace every op's emitter into the surrounding JAX trace, threading the
    SSA environment (name → traced array).  Each op is lowered inside its
    identity scope (`pdop__<type>__u<uid>`) and, where its desc names one,
    its model part's (`pdtpu.<part>`), so every HLO instruction maps back to
    the desc op that emitted it; the emitter's host time goes to
    `executor_op_emit_seconds_total`.  All of it once an op a TRACE: a step
    of a compiled program never comes here."""
    for op in ops:
        if op.type in _NOOP_TYPES:
            continue
        outer, ctx.emit_nested_s = ctx.emit_nested_s, 0.0
        t0 = _monotime()
        with _attr.op_scope(op):
            _lower_op(op, env, ctx)
            if after_op is not None:
                after_op(op, env)
        spent = _monotime() - t0
        _MET_OP_EMIT_S.inc(max(spent - ctx.emit_nested_s, 0.0),
                           op=_attr.op_type(op))
        ctx.emit_nested_s = outer + spent
    return env
