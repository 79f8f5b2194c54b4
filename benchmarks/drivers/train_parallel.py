"""Driver of the training cells that span chips: the same loop as
`train_executor`, under `ParallelExecutor(axes=<the traffic file's axes>)`.
Batches are global; each feed is laid over the mesh as the executor's own
static plan says, so the benchmark adds no sharding rule of its own.
"""

from __future__ import annotations

import math


def make_executor(ctx, fluid):
    import jax

    from paddle_tpu.parallel import ParallelExecutor

    axes = dict(ctx.traffic["axes"])
    n = math.prod(axes.values())
    devices = [ctx.place_of(i).jax_device() for i in range(n)]
    exe = ParallelExecutor(axes=axes, devices=devices)
    plan = {}

    def place(name, array):
        if not plan:
            plan.update(exe.static_plan(fluid.default_main_program()))
        return jax.device_put(array, plan[name])

    return exe, devices, place


def run(ctx) -> dict:
    from harness import load_module

    return load_module("drivers", "train_executor").run(
        ctx, make_executor=make_executor)
