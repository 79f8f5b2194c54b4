"""Program → pure JAX callable (the AOT face of the executor).

Gives external tooling (serving, graft entry, export) a functional handle on a
program: `build_callable` returns (fn, state) where `fn(state, feeds) ->
{fetch_name: array}` is pure and jittable — the same lowering Executor.run
jits internally.
"""

from __future__ import annotations

from .framework.executor import Executor, _lower_ops, bind_lower_block
from .framework.scope import global_scope
from .ops.registry import EmitContext


def build_callable(program, fetch_list, scope=None, feed_names=None,
                   is_test=True, rng_seed=0):
    """Returns (fn, state_dict).

    fn(state, feeds) -> dict of fetches. `state` are the scope-resident
    persistables the block reads (parameters, BN stats...)."""
    import jax

    scope = scope or global_scope()
    block = program.global_block()
    fetch_names = [f.name if hasattr(f, "name") else f for f in fetch_list]
    feed_names = feed_names or [
        v.name for v in block.vars.values() if v.is_data
    ]
    helper = Executor.__new__(Executor)
    external_reads, rw_state, _ = helper._analyze(block, feed_names)
    state_names = [n for n in external_reads + rw_state if scope.has(n)]
    missing = [n for n in external_reads + rw_state if not scope.has(n)]
    if missing:
        raise RuntimeError(
            f"build_callable: state vars not initialized: {missing[:5]}")
    state = {n: scope.find(n) for n in state_names}

    def fn(state, feeds):
        env = dict(state)
        env.update(feeds)
        ctx = EmitContext(jax.random.PRNGKey(rng_seed), is_test=is_test,
                          program=program)
        bind_lower_block(ctx, program)
        _lower_ops(block.ops, env, ctx)
        if ctx.host_saves:
            raise NotImplementedError(
                "save ops require Executor.run (its post-step host write); "
                "compiler.build_callable has no host side")
        return {n: env[n] for n in fetch_names}

    return fn, state
