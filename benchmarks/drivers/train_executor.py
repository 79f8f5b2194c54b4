"""Driver of the training cells on one chip: the configuration's training
program through `fluid.Executor`, one `run` a step, on batches staged on the
device.

run(ctx) -> a record (a dict) that run.py turns into the result line and
that the per-layer readers read:

  correct, attempted, failed      attempted = steps of the measured window
  values                          this driver's end-to-end metrics
  devices                         the jax devices the cell ran on
  setup                           what jax.monitoring saw during set-up
  window                          steps, samples, seconds, compile events
  traced                          the same for the traced slice, or None
  trace_path                      the .xplane.pb of a traced run, or None
  checks                          reference and loss checks, with numbers
  compared                        {name: [number, limit]}: every number
                                  `correct` rests on beside its limit

`train_parallel` reuses everything here and swaps the executor.
"""

from __future__ import annotations

import math

import numpy as np


def make_executor(ctx, fluid):
    """-> (executor, devices it runs on, place(array) for a feed)."""
    import jax

    place = ctx.place_of(0)
    device = place.jax_device()
    return (fluid.Executor(place), [device],
            lambda name, array: jax.device_put(array, device))


def _loop(ctx, exe, staged, fetch, read_every: int, losses: dict,
          seconds: float = None, until_step: int = None) -> dict:
    """Steps until `seconds` have passed (or until `until_step` steps have
    been made since the first measured one), then the barrier: every step's
    work is finished inside the returned `seconds`.  `losses` maps the
    number of steps made since the first measured one to the loss read
    after it: every `read_every`-th, and each loop's last."""
    from harness import monotime

    spans, n = ctx.spans, len(staged)
    mark = ctx.log.mark()
    done = max(losses, default=0)
    steps = 0
    t0 = monotime()
    while (monotime() - t0 < seconds if until_step is None
           else done + steps < until_step):
        with spans.span("executor_run"):
            outs = exe.run(feed=staged[(WARM_STEPS + done + steps) % n],
                           fetch_list=fetch, return_numpy=False)
        steps += 1
        if (done + steps) % read_every == 0:
            with spans.span("loss_read"):
                losses[done + steps] = float(np.asarray(outs[0]).reshape(()))
    with spans.span("loss_read"):
        losses[done + steps] = float(np.asarray(outs[0]).reshape(()))
    t1 = monotime()
    return {"steps": steps, "seconds": t1 - t0, "t0": t0, "t1": t1,
            "compile_events": ctx.log.since(mark)["compile_events"]}


WARM_STEPS = 4


def reference_errors(got: dict, want: dict, centered=()) -> dict:
    """The comparison behind `reference_ok`: for the scalar loss the
    relative difference; for an array |got - want| / |want| in the 2-norm,
    each side less its own mean first where the reference lists the key as
    `CENTERED` (a quantity whose mean carries no information)."""
    errors = {}
    for k, w in want.items():
        g, w = np.asarray(got[k], np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape:
            raise ValueError(f"{k}: the program gave {g.shape}, the "
                             f"reference {w.shape}")
        if k in centered:
            g, w = g - g.mean(), w - w.mean()
        errors[k] = float(np.linalg.norm(g - w)
                          / max(np.linalg.norm(w), 1e-30))
    return errors


def _check_vars(main, check_fetch: dict) -> dict:
    """{key: name of the variable} for a configuration's `train.check_fetch`
    = {key: [op type, output slot]}: the output of the program's last op of
    that type."""
    out = {}
    for key, (op_type, slot) in check_fetch.items():
        ops = [op for op in main.global_block().ops if op.type == op_type]
        if not ops:
            raise ValueError(f"check_fetch {key!r}: the program has no "
                             f"{op_type!r} op")
        out[key] = ops[-1].output(slot)[0]
    return out


def run(ctx, make_executor=make_executor) -> dict:
    import paddle_tpu as fluid
    from harness import (Tracer, load_module, monotime, rate, resolve,
                         seed32)

    cfg, traffic = ctx.config, ctx.traffic
    train = cfg["train"]
    batch = int(traffic["batch"])
    ref = load_module("reference", cfg["name"])
    gen = load_module("generators", traffic["generator"])

    # -- set-up: program, weights from the seed, batches, reference, warm-up
    fluid.reset()
    args = dict(train["args"])
    if train.get("batch_arg"):
        args[train["batch_arg"]] = batch
    built = resolve(train["builder"])(**args)
    loss = built[0] if train["loss"] == "first" else built
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = seed32(ctx.seed)
    exe, devices, place = make_executor(ctx, fluid)
    begin = ctx.log.mark()
    with ctx.spans.span("startup"):
        exe.run(startup)

    params = main.global_block().all_parameters()
    checked = {f"grad_{i}": params[i].name + "@GRAD"
               for i in ref.GRAD_PARAMS}
    checked.update(_check_vars(main, train.get("check_fetch", {})))
    fetch = [loss] + list(checked.values())

    with ctx.spans.span("stage"):
        stacked = gen.generate(ctx.seed, train["feeds"], batch,
                               int(traffic["staged_batches"]))
        staged = [{k: place(k, v[i]) for k, v in stacked.items()}
                  for i in range(int(traffic["staged_batches"]))]
        del stacked

    with ctx.spans.span("reference"):
        scope = fluid.global_scope()
        want = ref.train_check([scope.find(p.name) for p in params],
                               staged[0], cfg)
        want = {k: np.asarray(v, np.float32) for k, v in want.items()}

    with ctx.spans.span("warmup"):
        outs = exe.run(feed=staged[0], fetch_list=fetch, return_numpy=False)
        first_loss = float(np.asarray(outs[0]).reshape(()))
        got = {"loss": first_loss}
        for k, g in zip(checked, outs[1:]):
            got[k] = np.asarray(g, np.float32).reshape(want[k].shape)
        for i in range(1, WARM_STEPS):
            outs = exe.run(feed=staged[i % len(staged)], fetch_list=fetch,
                           return_numpy=False)
        float(np.asarray(outs[0]).reshape(()))

    errors = reference_errors(got, want, getattr(ref, "CENTERED", ()))
    ref_ok = all(errors[k] <= ref.TOL[k] for k in errors)
    setup = ctx.log.since(begin)
    setup_s = monotime() - ctx.t_start

    # -- the measured window, and in a traced run a traced slice after it
    losses: dict = {}
    read_every = int(traffic["loss_read_every"])
    fell_step = int(traffic["loss_fell_step"])
    if fell_step % read_every:
        raise ValueError(f"loss_fell_step {fell_step} is no multiple of "
                         f"loss_read_every {read_every}")
    trace_s = min(float(traffic["trace_seconds"]), ctx.seconds / 2.0)
    window = _loop(ctx, exe, staged, fetch, read_every, losses,
                   seconds=ctx.seconds - (trace_s if ctx.trace else 0.0))
    traced, trace_path = None, None
    if ctx.trace:
        tracer = Tracer(ctx)
        tracer.start()
        with ctx.spans.span("window"):
            traced = _loop(ctx, exe, staged, fetch, read_every, losses,
                           seconds=trace_s)
        trace_path = tracer.stop()
    # "the loss fell" is read at a fixed step, so `correct` does not depend
    # on the window's length; a window shorter than that many steps is
    # followed by the steps that are missing, outside every measurement
    if max(losses) < fell_step:
        with ctx.spans.span("after"):
            _loop(ctx, exe, staged, fetch, read_every, losses,
                  until_step=fell_step)

    finite = all(math.isfinite(x) for x in [first_loss, *losses.values()])
    fell = finite and losses[fell_step] < first_loss
    compile_events = window["compile_events"] + (
        traced["compile_events"] if traced else 0)
    no_compile = compile_events == 0
    compared = {k: [errors[k], ref.TOL[k]] for k in errors}
    compared["loss_at_fell_step"] = [losses[fell_step], first_loss]
    compared["compile_events_in_window"] = [compile_events, 0]
    window["samples"] = window["steps"] * batch
    if traced is not None:
        traced["samples"] = traced["steps"] * batch
    return {
        "correct": bool(ref_ok and finite and fell and no_compile),
        "attempted": window["steps"], "failed": 0,
        "values": {
            "train_samples_per_s": rate(window["samples"],
                                        window["seconds"]),
            "setup_s": setup_s},
        "devices": devices, "setup": setup, "window": window,
        "traced": traced, "trace_path": trace_path, "batch": batch,
        "compared": compared,
        "checks": {"reference_ok": ref_ok, "reference_errors": errors,
                   "tolerances": {k: ref.TOL[k] for k in errors},
                   "first_loss": first_loss, "loss_fell_step": fell_step,
                   "loss_at_that_step": losses[fell_step],
                   "loss_reads": {str(k): v for k, v in losses.items()},
                   "loss_fell": fell, "finite": finite,
                   "no_compile_in_window": no_compile},
    }
