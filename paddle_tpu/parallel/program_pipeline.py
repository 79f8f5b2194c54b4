"""Pipeline parallelism for fluid-built Programs over a `pp` mesh axis.

The round-1 pipeline (pipeline.py) proved the GPipe-over-ppermute schedule on
a toy stacked-MLP; this module makes it a *framework capability*: any Program
whose global block is split by `layers.pipeline_stage()` markers runs its
stages one-per-`pp`-member, activations hopping stages over ICI.

TPU-first design points:
- **Heterogeneous stages in SPMD.** shard_map runs the same code on every
  member, but stages differ (conv stage vs fc stage).  Every member executes
  `lax.switch(stage_index, [stage_0_fn, ...])`; each branch lowers that
  stage's ops only.  XLA compiles all branches once; each member takes its
  own branch every tick.
- **Flat-packed parameters.** Each stage's parameters are flattened and
  packed into one float32 vector, padded to the longest stage, giving a
  dense [n_stages, L] array sharded over 'pp' — true 1/pp weight residency
  without requiring homogeneous stages.  Gradients arrive packed from
  `jax.grad` and the SGD/momentum update applies to the packed array, so
  the whole train step (fill/drain schedule + backward + update) is ONE
  XLA program.
- **Fixed-shape hops.** Stage-boundary activations are packed/cast into a
  float32 buffer sized to the largest interface, so the `lax.scan` over
  ticks carries a static-shape buffer through `lax.ppermute`.

Reference parity note: the 2018 reference has no pipeline parallelism
(SURVEY.md §2.16 'beyond-reference' row); the capability bar here is that a
user-built Program — not a toy — pipelines.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional

import numpy as np

from ..framework.executor import _lower_ops, bind_lower_block
from ..framework.scope import global_scope
from ..ops.registry import EmitContext

def split_stages(block) -> List[list]:
    """Partition the block's ops at pipeline_stage markers (markers and
    feed/fetch descs excluded)."""
    stages, cur = [], []
    for op in block.ops:
        if op.type == "pipeline_stage":
            stages.append(cur)
            cur = []
        elif op.type not in ("feed", "fetch"):
            cur.append(op)
    stages.append(cur)
    return stages


class _StageInfo:
    def __init__(self):
        self.ops = []
        self.params: List[str] = []      # persistable reads, in first-use order
        self.interface_in: List[str] = []   # activations from the prev stage
        self.produced: set = set()


class ProgramPipeline:
    """Compile + drive one Program as a `pp`-parallel GPipe schedule.

    Usage:
        prog builds ... layers.pipeline_stage() ... loss
        exe.run(startup)                   # init params (host values)
        pipe = ProgramPipeline(prog, loss, mesh, n_micro=8,
                               optimizer=("sgd", 0.1))
        for batch: loss = pipe.run(feed)
        pipe.sync_scope()                  # write trained params back

    The program must be the *forward+loss* graph (clone(for_test=True) of a
    train program, or a program built without minimize()); backward comes
    from jax.grad over the schedule.  BN running-stat updates inside stages
    are not persisted (scalar batch stats still normalize correctly)."""

    def __init__(self, program, loss, mesh, n_micro: int,
                 optimizer=("sgd", 0.1), scope=None, block_id: int = 0):
        import jax

        self.program = program
        self.mesh = mesh
        self.n_micro = int(n_micro)
        self.loss_name = loss if isinstance(loss, str) else loss.name
        self.scope = scope if scope is not None else global_scope()
        self.block = program.blocks[block_id]
        self.opt_kind = optimizer[0]
        self.opt_args = tuple(float(a) for a in optimizer[1:])
        if self.opt_kind not in ("sgd", "momentum", "none"):
            raise ValueError(f"ProgramPipeline optimizer {self.opt_kind!r}: "
                             f"use 'sgd', 'momentum' or 'none'")

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.pp = sizes.get("pp", 1)
        op_stages = split_stages(self.block)
        if len(op_stages) != self.pp:
            raise ValueError(
                f"program has {len(op_stages)} pipeline stages "
                f"(pipeline_stage markers + 1) but mesh 'pp' axis is "
                f"{self.pp}")
        self.stages = self._analyze(op_stages)
        self._build_packing()
        self._packed = None       # [pp, L] device array
        self._velocity = None
        self._step_fns: Dict[tuple, object] = {}  # per feed-shape signature
        self._step = 0
        self._jax = jax

    # ------------------------------------------------------------------
    def _analyze(self, op_stages) -> List[_StageInfo]:
        infos = []
        produced_before: Dict[str, int] = {}
        param_stage: Dict[str, int] = {}
        for s, ops in enumerate(op_stages):
            info = _StageInfo()
            info.ops = ops
            seen = set()
            for op in ops:
                for n in op.input_names():
                    if not n or n in seen or n in info.produced:
                        continue
                    seen.add(n)
                    v = self.block._find_var_recursive(n)
                    if v is not None and v.is_data:
                        continue  # feeds are broadcast to every stage
                    if v is not None and v.persistable:
                        owner = param_stage.get(n)
                        if owner is not None and owner != s:
                            raise ValueError(
                                f"parameter {n!r} is read by stages {owner} "
                                f"and {s}; flat-packed pipeline parameters "
                                f"cannot be shared across stages (gradients "
                                f"would not be summed) — duplicate the "
                                f"weight or keep its users in one stage")
                        param_stage[n] = s
                        info.params.append(n)
                    elif n in produced_before:
                        src = produced_before[n]
                        if src != s - 1:
                            raise ValueError(
                                f"variable {n!r} crosses stage boundary "
                                f"{src}->{s}; pipeline dataflow must be "
                                f"between consecutive stages (rematerialize "
                                f"or move the consumer)")
                        info.interface_in.append(n)
                    else:
                        raise ValueError(
                            f"stage {s} reads {n!r} which no earlier stage "
                            f"produces and is neither a feed nor a "
                            f"parameter")
                for n in op.output_names():
                    if n:
                        info.produced.add(n)
                        produced_before[n] = s
            infos.append(info)
        if self.loss_name not in infos[-1].produced:
            raise ValueError(
                f"loss {self.loss_name!r} must be produced by the LAST "
                f"pipeline stage")
        return infos

    # ------------------------------------------------------------------
    def _var_shape(self, name, micro_bs):
        v = self.block._find_var_recursive(name)
        if v is None or v.shape is None:
            raise ValueError(f"no static shape for interface var {name!r}")
        return tuple(micro_bs if d == -1 else int(d) for d in v.shape)

    def _build_packing(self):
        """Per-stage parameter packing offsets (shapes read from the scope at
        initialize(); here just the name layout)."""
        self._param_layout: List[List[str]] = [s.params for s in self.stages]

    # ------------------------------------------------------------------
    def initialize(self, scope=None):
        """Pack the scope's initialized parameter values into the [pp, L]
        sharded array (run the startup program on a plain Executor first)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from ..mesh import pspec as P

        scope = scope or self.scope
        self._param_meta = []  # per stage: list of (name, shape, dtype, off)
        flat_stages = []
        for names in self._param_layout:
            metas, chunks, off = [], [], 0
            for n in names:
                val = scope.find(n)
                if val is None:
                    raise RuntimeError(
                        f"parameter {n!r} not initialized — run the startup "
                        f"program first")
                arr = np.asarray(val, dtype=np.float32).reshape(-1)
                metas.append((n, tuple(np.asarray(val).shape),
                              str(np.asarray(val).dtype), off))
                chunks.append(arr)
                off += arr.size
            flat_stages.append(np.concatenate(chunks) if chunks
                               else np.zeros((0,), np.float32))
            self._param_meta.append(metas)
        L = max((f.size for f in flat_stages), default=1) or 1
        packed = np.zeros((self.pp, L), np.float32)
        for s, f in enumerate(flat_stages):
            packed[s, :f.size] = f
        shard = NamedSharding(self.mesh, P("pp"))
        self._packed = jax.device_put(jnp.asarray(packed), shard)
        if self.opt_kind == "momentum":
            self._velocity = jax.device_put(jnp.zeros_like(packed), shard)
        return self

    def sync_scope(self, scope=None):
        """Write the trained packed parameters back to scope variables."""
        scope = scope or self.scope
        host = np.asarray(self._packed)
        for s, metas in enumerate(self._param_meta):
            for (n, shape, dtype, off) in metas:
                size = int(np.prod(shape)) if shape else 1
                val = host[s, off:off + size].reshape(shape).astype(dtype)
                scope.set(n, self._jax.numpy.asarray(val))

    # ------------------------------------------------------------------
    def _stage_fn(self, s, micro_bs, act_len):
        """(flat_params [L], act_in [act_len] f32, feeds dict, key)
        -> act_out [act_len] f32 (last stage: loss scalar in slot 0)."""
        import jax.numpy as jnp

        info = self.stages[s]
        metas = self._param_meta[s]
        in_specs = [(n, self._var_shape(n, micro_bs),
                     self.block._find_var_recursive(n).dtype)
                    for n in info.interface_in]
        out_specs = None
        if s < self.pp - 1:
            nxt = self.stages[s + 1]
            out_specs = [(n, self._var_shape(n, micro_bs),
                          self.block._find_var_recursive(n).dtype)
                         for n in nxt.interface_in]

        def fn(flat, act_in, feeds, key):
            from ..framework.core import np_dtype

            env = dict(feeds)
            for (n, shape, dtype, poff) in metas:
                size = int(np.prod(shape)) if shape else 1
                env[n] = flat[poff:poff + size].reshape(shape).astype(
                    np_dtype(dtype))
            off = 0
            for (n, shape, dtype) in in_specs:
                size = int(np.prod(shape))
                env[n] = act_in[off:off + size].reshape(shape).astype(
                    np_dtype(dtype))
                off += size
            ctx = EmitContext(key, is_test=False, program=self.program)
            ctx.mesh = self.mesh
            bind_lower_block(ctx, self.program)
            _lower_ops(info.ops, env, ctx)
            if out_specs is None:
                out = jnp.zeros((act_len,), jnp.float32)
                return out.at[0].set(
                    env[self.loss_name].astype(jnp.float32).reshape(()))
            parts = [env[n].astype(jnp.float32).reshape(-1)
                     for (n, _, _) in out_specs]
            flat_out = jnp.concatenate(parts) if parts else jnp.zeros(
                (0,), jnp.float32)
            pad = act_len - flat_out.shape[0]
            return jnp.pad(flat_out, (0, pad))

        return fn

    def _interface_len(self, micro_bs):
        best = 1
        for s in self.stages[1:]:
            tot = sum(int(np.prod(self._var_shape(n, micro_bs)))
                      for n in s.interface_in)
            best = max(best, tot)
        return best

    # ------------------------------------------------------------------
    def _compile(self, feed_shapes):
        import jax
        import jax.numpy as jnp
        from jax import lax, shard_map
        from ..mesh import pspec as P

        batch = next(iter(feed_shapes.values()))[0]
        micro_bs = batch // self.n_micro
        # pp×dp composition: a 'dp' mesh axis splits every microbatch
        # across dp members — same pipeline schedule per slice, gradients
        # psum'd over dp by autodiff through the pmean'd loss
        dp = dict(zip(self.mesh.axis_names,
                      self.mesh.devices.shape)).get("dp", 1)
        if micro_bs % dp:
            raise ValueError(
                f"microbatch {micro_bs} not divisible by dp={dp}")
        local_bs = micro_bs // dp
        act_len = self._interface_len(local_bs)
        stage_fns = [self._stage_fn(s, local_bs, act_len)
                     for s in range(self.pp)]
        n_micro, pp = self.n_micro, self.pp
        fwd_perm = [(s, s + 1) for s in range(pp - 1)]
        feeds_spec = P(None, "dp") if dp > 1 else P()

        @partial(shard_map, mesh=self.mesh,
                 in_specs=(P("pp"), feeds_spec, P()),
                 out_specs=P(), check_vma=False)
        def forward_loss(packed_local, feeds_micro, key):
            flat = packed_local[0]  # shard_map keeps a length-1 pp dim
            stage = lax.axis_index("pp")
            ticks = n_micro + pp - 1

            def tick(carry, t):
                buf, losses = carry
                micro = jnp.clip(t - stage, 0, n_micro - 1)
                feeds_t = {k: v[micro] for k, v in feeds_micro.items()}
                y = lax.switch(
                    stage,
                    [lambda a, f=f: f(flat, a, feeds_t,
                                      jax.random.fold_in(key, t))
                     for f in stage_fns],
                    buf)
                valid = (t >= stage) & (t - stage < n_micro)
                y = jnp.where(valid, y, 0.0)
                is_last = stage == pp - 1
                losses = losses + jnp.where(
                    valid & is_last,
                    jnp.zeros((n_micro,)).at[micro].set(y[0]),
                    0.0)
                buf = lax.ppermute(y, "pp", fwd_perm)
                return (buf, losses), None

            buf0 = jnp.zeros((act_len,), jnp.float32)
            (buf, losses), _ = lax.scan(
                tick, (buf0, jnp.zeros((n_micro,))), jnp.arange(ticks))
            # only the last stage accumulated losses; share them.  Under
            # pp×dp each member saw its local_bs slice: pmean over dp
            # gives the global batch mean (its VJP psums the dp grads)
            loss = lax.psum(losses, "pp").mean()
            if dp > 1:
                loss = lax.pmean(loss, "dp")
            return loss

        def train_step(packed, velocity, feeds_micro, key):
            loss, g = jax.value_and_grad(
                lambda p: forward_loss(p, feeds_micro, key))(packed)
            if self.opt_kind == "sgd":
                lr = self.opt_args[0]
                packed = packed - lr * g
            elif self.opt_kind == "momentum":
                lr, mu = self.opt_args
                velocity = mu * velocity + g
                packed = packed - lr * velocity
            return loss, packed, velocity

        return jax.jit(train_step, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def run(self, feed: Dict[str, object], seed: Optional[int] = None):
        """One pipelined train step over `feed` (full batch on dim 0);
        returns the mean microbatch loss."""
        import jax
        import jax.numpy as jnp

        if self._packed is None:
            self.initialize()
        feeds_micro = {}
        shapes = {}
        from ..framework.core import np_dtype

        for name, value in feed.items():
            arr = np.asarray(value)
            if arr.shape[0] % self.n_micro:
                raise ValueError(
                    f"feed {name!r} batch {arr.shape[0]} not divisible by "
                    f"n_micro {self.n_micro}")
            v = self.block._find_var_recursive(name)
            if v is not None and v.dtype is not None:
                arr = arr.astype(np_dtype(v.dtype), copy=False)
            shapes[name] = arr.shape
            feeds_micro[name] = jnp.asarray(arr.reshape(
                (self.n_micro, arr.shape[0] // self.n_micro)
                + arr.shape[1:]))
        # one executable per feed-shape signature (micro_bs / act_len are
        # baked into the traced stage functions)
        sig = tuple(sorted(shapes.items()))
        step_fn = self._step_fns.get(sig)
        if step_fn is None:
            step_fn = self._compile(shapes)
            self._step_fns[sig] = step_fn
        key = jax.random.PRNGKey(self._step if seed is None else seed)
        self._step += 1
        vel = self._velocity if self._velocity is not None else jnp.zeros(
            (1,), jnp.float32)
        loss, self._packed, vel = step_fn(
            self._packed, vel, feeds_micro, key)
        if self._velocity is not None:
            self._velocity = vel
        return float(np.asarray(loss).ravel()[0])
