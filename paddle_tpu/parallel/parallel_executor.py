"""ParallelExecutor: SPMD execution of a program over a device mesh.

Replaces the reference's whole multi-device story (SURVEY.md §2.16):
MultiGradientMachine's thread-per-GPU + host aggregation
(gserver/gradientmachines/MultiGradientMachine.cpp:279/469/502), the
parallel_do op (operators/parallel_do_op.cc:82), NCCL allreduce ops, and the
pserver data-parallel path.  The SAME program the single-chip Executor runs is
jitted with NamedShardings: batch-sharded feeds ('dp'), optionally
tensor-sharded weights ('mp'), replicated small state.  XLA GSPMD partitions
the computation and emits ICI collectives (gradient all-reduce appears
automatically from the replicated-param + sharded-batch math).

The executor holds NO sharding logic of its own: the partitioner's
logical-axis rule table (parallel/partitioner.py) produces every spec —
the ZeRO-1/FSDP dim-0 reshards included — and the executor only applies the
plan (device_put, in_shardings/out_shardings, donation).  The
`zero_dp_states`/`fsdp_params` kwargs are rule-table flags
(arXiv:2004.13336 cross-replica weight-update sharding: the optimizer step
runs on the dim-0 shard and GSPMD all-gathers params once per step).  The
plans of the eleven modes of parallel/modes.py are pinned by the snapshot
tests/fixtures/mode_plans_golden.json (tests/test_sharding.py,
tests/test_equivalence.py).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..framework.core import np_dtype
from ..framework.executor import Executor
from ..framework.scope import global_scope
from ..observability.tracing import TRACER as _TRC, now as _trace_now
from ..ops.registry import EmitContext
from .. import mesh as mesh_lib
from ..mesh import make_mesh
from .partitioner import DistributeTranspiler, ShardingRules


class ParallelExecutor(Executor):
    def __init__(self, mesh=None, axes: Optional[Dict[str, int]] = None,
                 rules: Optional[ShardingRules] = None, devices=None,
                 zero_dp_states: bool = False, fsdp_params: bool = False):
        super().__init__(place=None)
        self._pin_device = False
        # the step output pytree must match out_shardings exactly
        self._strict_state = True
        if mesh is None:
            with _TRC.span("parallel.mesh", cold=True,
                           axes=str(dict(axes or {}))):
                mesh = make_mesh(axes, devices)
        self.mesh = mesh
        self.transpiler = DistributeTranspiler(
            rules, zero_dp_states=zero_dp_states, fsdp_params=fsdp_params)
        self._plans: Dict[int, tuple] = {}
        # program token -> the desc version whose state this executor has
        # distributed once: the next `executor.distribute` is a steady one
        self._distributed: Dict[int, int] = {}
        self.zero_dp_states = self.transpiler.rules.zero_dp_states
        self.fsdp_params = self.transpiler.rules.fsdp_params

    # ------------------------------------------------------------------
    def _plan_for(self, program):
        """(plan, provenance) for `program`, cached per desc version."""
        key = (program._cache_token, program._version)
        entry = self._plans.get(key)
        if entry is None:
            with _TRC.span("parallel.plan", cold=True,
                           program=program._cache_token):
                plan = self.transpiler.transpile(program, self.mesh)
            entry = (plan, dict(self.transpiler.last_provenance))
            self._plans[key] = entry
            # an accumulator-free optimizer (plain SGD) under fsdp_params
            # is working as intended — params are the sharded state — so
            # the missing-tag warning only applies to explicit ZeRO-1
            if (self.zero_dp_states and not self.fsdp_params
                    and not any(
                        getattr(v, "accumulator_for", None)
                        for v in program.global_block().vars.values())
                    and any(op.type.endswith("_grad") or
                            op.type == "generic_grad"
                            for op in program.global_block().ops)):
                import logging

                logging.getLogger("paddle_tpu").warning(
                    "zero_dp_states=True but no variable carries an "
                    "accumulator_for tag (program saved by an older build?) "
                    "— optimizer state will stay replicated")
        return entry

    def _replicated(self):
        return mesh_lib.replicated(self.mesh)

    def _shard_of(self, plan, name):
        s = plan.get(name)
        return s if s is not None else self._replicated()

    def static_plan(self, program, block_id: int = 0, provenance=None):
        """EFFECTIVE per-variable shardings from descs alone: no scope,
        no compilation, nothing runs.  Just the rule-table plan
        restricted to the persistable/feed vars the block touches — the
        ZeRO-1/FSDP reshards are table rows now, not an executor
        post-pass.  This is the `plan=` input to
        `analysis.verify_program` (sharded-donation rule PTV016,
        sharding-propagation rules PTV018-021),
        `analysis.memory.peak_estimate(per-shard)`, and
        `analysis.sharding.propagate`.  Pass `provenance={}` to collect
        {var: which rule produced the spec} — verify_program's
        `plan_provenance` input, so PTV016 findings name the axis rule
        that made the donated state sharded."""
        block = program.blocks[block_id]
        plan, prov = self._plan_for(program)
        names = set()
        for op in block.ops:
            names.update(n for n in op.input_names() if n)
            names.update(n for n in op.output_names() if n)
        out = {}
        for n in sorted(names):
            v = block._find_var_recursive(n)
            if v is None or not (v.persistable or v.is_data):
                # only the vars the executor actually CONSTRAINS:
                # transient shardings are GSPMD propagation, and a
                # replicated placeholder here would override the
                # estimator's batch-led heuristic with a lie
                continue
            out[n] = self._shard_of(plan, n)
            if provenance is not None and n in prov:
                provenance.setdefault(n, prov[n])
        return out

    # ------------------------------------------------------------------
    def _stacked_sharding(self, sharding):
        """The sharding of a leading-stacked (K, ...) feed block: the
        planned per-batch spec with the steps_per_dispatch dim
        unsharded in front (every device sees all K of its slices)."""
        return mesh_lib.named(sharding.mesh, None, *sharding.spec)

    def _prepare_feeds(self, block, feed, stacked: bool = False):
        import jax

        program = block.program
        plan, _ = self._plan_for(program)
        out = {}
        for name, value in feed.items():
            if isinstance(value, jax.Array):
                out[name] = value
                continue
            arr = np.asarray(value)
            if block.has_var(name):
                var = block.var(name)
                if var.dtype is not None:
                    arr = arr.astype(np_dtype(var.dtype), copy=False)
                sharding = plan.get(name) or self._replicated()
            else:
                sharding = self._replicated()
            if stacked:
                sharding = self._stacked_sharding(sharding)
            out[name] = jax.device_put(arr, sharding)
        return out

    def _distribute_state(self, program, scope, names):
        """device_put persistables to their planned shardings.

        Keyed on the value's ACTUAL sharding, not a seen-before tag: a
        re-run startup program may write state back with a different layout
        (e.g. replicated accumulators under ZeRO), and the cached training
        executable's in_shardings demand the planned one."""
        import jax

        plan, _ = self._plan_for(program)
        for n in names:
            v = scope.find(n)
            if v is None:
                continue
            target = self._shard_of(plan, n)
            current = getattr(v, "sharding", None)
            if current is not None and current == target:
                continue
            scope.set(n, jax.device_put(v, target))

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, block_id=0, verify=None, rng_step=None,
            steps_per_dispatch=None, fetch_every="all"):
        from ..framework.core import default_main_program

        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        block = program.blocks[block_id]
        # pre-shard all scope state the block touches: every call walks
        # the block's ops, before (and so outside) Executor.run's spans
        # (and inside the two stamps its row of the step record keeps).
        # A program version's first pass moves the state to its planned
        # shardings and is the one that compiles: a cold span
        cold = self._distributed.get(program._cache_token) != \
            program._version
        if cold:
            self._distributed[program._cache_token] = program._version
        t_distribute0 = _trace_now()
        with _TRC.span("executor.distribute", cold=cold, step=self._step,
                       ops=len(block.ops)):
            names = set()
            for op in block.ops:
                names.update(op.input_names())
                names.update(op.output_names())
            self._distribute_state(
                program, scope, [n for n in names if scope.has(n)])
        # the step record's row of the dispatch that follows carries them
        self._before_root = (t_distribute0, _trace_now())
        return super().run(program, feed, fetch_list, scope, return_numpy,
                           block_id, verify=verify, rng_step=rng_step,
                           steps_per_dispatch=steps_per_dispatch,
                           fetch_every=fetch_every)

    # ------------------------------------------------------------------
    # the step trace itself comes from Executor._make_step_fn (shared
    # with the single-chip path and the K-step loop); only the emit
    # context (mesh) and the jit shardings differ here

    def _emit_ctx(self, rng_key, is_test, program):
        ctx = EmitContext(rng_key, is_test=is_test, program=program)
        ctx.mesh = self.mesh
        return ctx

    def _compile_parts(self, program, block_id, feed_vals, fetch_names):
        if any(op.type == "save"
               for op in program.blocks[block_id].ops):
            raise NotImplementedError(
                "save ops are not supported under ParallelExecutor; "
                "checkpoint sharded state via distributed.checkpoint")
        return super()._compile_parts(program, block_id, feed_vals,
                                      fetch_names)

    def _jit_step(self, step_fn, program, external_reads, rw_state,
                  written_state, feed_names):
        import jax

        plan, _ = self._plan_for(program)
        in_shardings = (
            {n: self._shard_of(plan, n) for n in rw_state},
            {n: self._shard_of(plan, n) for n in external_reads},
            {n: (plan.get(n) or self._replicated()) for n in feed_names},
            self._replicated(),
        )
        # keep state shardings stable across steps; fetches unconstrained
        out_shardings = (
            None,
            {n: self._shard_of(plan, n) for n in written_state},
        )
        return jax.jit(
            step_fn,
            donate_argnums=(0,),
            in_shardings=in_shardings,
            out_shardings=out_shardings,
        )

    def _jit_loop(self, loop_fn, program, external_reads, rw_state,
                  written_state, feed_names):
        import jax

        plan, _ = self._plan_for(program)
        in_shardings = (
            {n: self._shard_of(plan, n) for n in rw_state},
            {n: self._shard_of(plan, n) for n in external_reads},
            # stacked (K, batch, ...) feed blocks: the planned per-batch
            # spec shifted one dim right — sharded state stays resident
            # across the whole loop, only the feeds carry the K dim
            {n: self._stacked_sharding(plan.get(n) or self._replicated())
             for n in feed_names},
            self._replicated(),
            self._replicated(),
        )
        out_shardings = (
            None,
            {n: self._shard_of(plan, n) for n in written_state},
        )
        return jax.jit(
            loop_fn,
            donate_argnums=(0,),
            in_shardings=in_shardings,
            out_shardings=out_shardings,
        )
