#!/usr/bin/env python
"""The main path, once, on the chip: `python chip_smoke.py`.

One process, one chip (or one four-chip host), JAX's defaults: no
JAX_PLATFORMS, no XLA_FLAGS, no jax.config value, so x64 is off as in
deployment.  Everything goes through the entry points a user calls —
`import paddle_tpu as fluid`, `fluid.Executor(fluid.TPUPlace(0))`,
`ParallelExecutor`, `ServingEngine` — at the full width of the models the
repo benchmarks (depth may be cut; weights are random, from a seed).

It first checks `jax.devices()[0].platform == "tpu"` and otherwise exits
non-zero naming what it found, before building any program.  Then each
phase either passes or ends the run: no phase's exception is caught so that
a later one can run.  Per phase it prints one JSON line, then a summary
`{"summary": "chip_smoke", "phases": [...], "claim": null}`, and the last
line of stdout is exactly `{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}` with the device as JAX reports it — those keys and no
others, because the driver parses that line.  Step times are smoke readings
that show the program ran, not benchmark numbers.

The phases are plain functions of sizes and a place, so the tier-1 tests
call them at toy sizes on CPUPlace; the script itself has no CPU mode.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

MOSAIC_CALL = "tpu_custom_call"  # Pallas kernels' custom_call_target in HLO
# bf16 carries 8 significant bits (eps 2^-7).  Kernel and reference differ
# by the rounding of the probabilities to bf16, the rounding of the output,
# and the order of the f32 accumulation: four eps of the output's scale.
BF16_KERNEL_TOL = 4 * 2.0 ** -7
# The ResNet builder's default 0.1 with momentum 0.9 overshoots when one
# batch is repeated (on the chip: 7.74, 5.96, 5.55, 6.78, 8.30), so "the
# loss falls" would test the schedule, not the program.
RESNET_SMOKE_LR = 0.01


class _CompileLog:
    """What JAX's own monitoring says happened while a phase ran: seconds
    spent tracing, lowering and compiling (a persistent-cache hit counts
    its retrieval), and persistent-cache hits and misses."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event in self._DURATIONS:
            self.seconds += duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.seconds, self.hits, self.misses)

    def since(self, mark) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 3),
                "cache_hits": self.hits - mark[1],
                "cache_misses": self.misses - mark[2]}


def _versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _cache_state() -> tuple:
    """(directory the persistent compile cache is using, entries in it)."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path or not os.path.isdir(path):
        return path, 0
    return path, sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def _begin(log: _CompileLog) -> tuple:
    """(compile-log mark, cache entries) at a phase's start — taken once the
    phase's Executor exists, because building one is what places the cache,
    and before anything is compiled."""
    return log.mark(), _cache_state()[1]


def _record(phase: str, devices, log: _CompileLog, begin, **fields) -> dict:
    """One phase's JSON line: where it ran, what it cost to compile, the
    compile cache before and after, then the phase's own fields."""
    mark, cache_before = begin
    cache_dir, cache_after = _cache_state()
    return {"phase": phase,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            **_versions(), **log.since(mark),
            "cache_dir": cache_dir,
            "cache_entries_before": cache_before,
            "cache_entries_after": cache_after,
            **fields}


def _kernel_of(hlo: str, device) -> str:
    """"mosaic" when the compiled step holds a Pallas custom call, else
    "reference".  On a TPU the default gates select the fused kernels at
    every smoke shape, so "reference" there is a failure, not a note."""
    kernel = "mosaic" if MOSAIC_CALL in hlo else "reference"
    if device.platform == "tpu" and kernel != "mosaic":
        raise AssertionError(
            f"the compiled step holds no {MOSAIC_CALL}: the fused kernel "
            f"was passed over on {device.device_kind}")
    return kernel


def _train_steps(exe, feed, fetch_list, steps: int):
    """`steps` runs of the default main program on one staged batch ->
    (losses, seconds per run, the last run's fetches).  `fetch_list[0]` is
    the loss; reading it back every step is also the completion barrier."""
    import numpy as np

    from paddle_tpu.observability.metrics import monotime

    losses, seconds = [], []
    for _ in range(steps):
        t0 = monotime()
        outs = exe.run(feed=feed, fetch_list=fetch_list, return_numpy=False)
        losses.append(float(np.asarray(outs[0]).reshape(())))
        seconds.append(monotime() - t0)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    return losses, seconds, outs


def _timing(seconds) -> dict:
    """The first run holds the compile; the median of the rest is the
    smoke reading of one step (every run's seconds ride along, so a
    second compile hiding in step 2 shows)."""
    return {"first_step_s": round(seconds[0], 3),
            "step_s_smoke_reading": round(statistics.median(seconds[1:]), 5),
            "step_seconds": [round(t, 4) for t in seconds]}


def _stage(device, arrays: dict) -> dict:
    import jax

    return {k: jax.device_put(v, device) for k, v in arrays.items()}


def _resnet_batch(rng, batch_size: int, image: int) -> dict:
    import numpy as np

    from paddle_tpu.framework.core import np_dtype

    return {
        "image": rng.rand(batch_size, image, image, 3).astype(
            np.float32).astype(np_dtype("bfloat16")),
        "label": rng.randint(0, 1000, (batch_size, 1)).astype(np.int64),
    }


# ---------------------------------------------------------------------------
# phases


def phase_resnet_train(place, log: _CompileLog, batch_size: int = 128,
                       depth: int = 50, image: int = 224,
                       steps: int = 5) -> dict:
    """ResNet train, the repo's anchor: bf16 NHWC, `steps` steps on one
    fixed synthetic batch staged on the device.  The loss is finite at
    every step and lower at the last than at the first, and a parameter
    fetched from the scope lives on the place's device."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    fluid.reset()
    device = place.jax_device()
    avg_cost, _ = resnet.build_train_program(
        batch_size=batch_size, depth=depth, dtype="bfloat16", layout="NHWC",
        image_shape=(3, image, image), learning_rate=RESNET_SMOKE_LR)
    exe = fluid.Executor(place)
    begin = _begin(log)
    exe.run(fluid.default_startup_program())
    feed = _stage(device, _resnet_batch(np.random.RandomState(0),
                                        batch_size, image))
    losses, seconds, _ = _train_steps(exe, feed, [avg_cost], steps)
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall over {steps} steps: "
                             f"{losses}")
    param = fluid.default_main_program().global_block().all_parameters()[0]
    on = fluid.global_scope().find(param.name).devices()
    if on != {device}:
        raise AssertionError(f"parameter {param.name!r} lives on {on}, "
                             f"not on {device}")
    return _record("resnet_train", [device], log, begin,
                   config=f"resnet{depth}_bs{batch_size}_{image}px_bf16_nhwc",
                   losses=[round(l, 4) for l in losses],
                   param_device=str(device), **_timing(seconds))


def phase_recurrent_train(place, log: _CompileLog, cell: str = "lstm",
                          batch_size: int = 64, hidden: int = 512,
                          seq_len: int = 96, vocab: int = 30000,
                          steps: int = 3) -> dict:
    """The stacked recurrent text classifier (2 x LSTM, the second
    reversed; bf16; batch 64, 96 steps, hidden 512, vocabulary 30000), or
    the same tower with GRU cells.  The compiled step holds the fused
    forward and BPTT kernels."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import image_models

    fluid.reset()
    device = place.jax_device()
    layers = fluid.layers
    words = layers.sequence_data(name="words", shape=[1], dtype="int64",
                                 max_len=seq_len)
    label = layers.data(name="label", shape=[1], dtype="int64")
    emb = layers.sequence_embedding(words, size=[vocab, hidden],
                                    dtype="bfloat16")
    if cell == "lstm":
        logits = image_models.stacked_lstm_net(
            emb, hidden_dim=hidden, stacked_num=2, class_dim=2)
    else:
        inp = emb
        for i in range(2):
            proj = layers.sequence_fc(inp, size=hidden * 3)
            inp = layers.dynamic_gru(proj, size=hidden,
                                     is_reverse=(i % 2 == 1))
        logits = layers.fc(input=layers.sequence_pool(inp, pool_type="max"),
                           size=2)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.cast(logits, "float32"), label))
    fluid.optimizer.Adam(learning_rate=0.002).minimize(loss)
    exe = fluid.Executor(place)
    begin = _begin(log)
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    feed = _stage(device, {
        "words": rng.randint(0, vocab, (batch_size, seq_len, 1)),
        "words@LENGTH": np.full((batch_size,), seq_len, np.int32),
        "label": rng.randint(0, 2, (batch_size, 1)),
    })
    losses, seconds, _ = _train_steps(exe, feed, [loss], steps)
    kernel = _kernel_of(exe.optimized_hlo(feed=feed, fetch_list=[loss]),
                        device)
    return _record(f"{cell}_train", [device], log, begin,
                   config=f"{cell}2x_h{hidden}_bs{batch_size}_T{seq_len}_bf16",
                   losses=[round(l, 4) for l in losses], kernel=kernel,
                   **_timing(seconds))


def phase_lm_train(place, log: _CompileLog, batch_size: int = 8,
                   seq_len: int = 1024, dim: int = 512, n_layers: int = 8,
                   n_heads: int = 8, vocab: int = 32000,
                   steps: int = 3) -> dict:
    """Decoder-only LM train (bf16; 8 x 1024 tokens, 8 layers of width
    512, vocabulary 32000); the compiled step holds the flash-attention
    forward and backward kernels."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    fluid.reset()
    device = place.jax_device()
    loss = transformer.build_lm_train_program(
        seq_len=seq_len, vocab_size=vocab, dim=dim, n_layers=n_layers,
        n_heads=n_heads, dtype="bfloat16")
    exe = fluid.Executor(place)
    begin = _begin(log)
    exe.run(fluid.default_startup_program())
    toks = np.random.RandomState(0).randint(
        0, vocab, (batch_size, seq_len, 1)).astype(np.int64)
    feed = _stage(device, {"tokens": toks,
                           "targets": np.roll(toks, -1, axis=1)})
    losses, seconds, _ = _train_steps(exe, feed, [loss], steps)
    kernel = _kernel_of(exe.optimized_hlo(feed=feed, fetch_list=[loss]),
                        device)
    return _record("lm_train", [device], log, begin,
                   config=f"lm_d{dim}_l{n_layers}_h{n_heads}_T{seq_len}"
                          f"_bs{batch_size}_v{vocab}_bf16",
                   losses=[round(l, 4) for l in losses], kernel=kernel,
                   **_timing(seconds))


def _paged_kernel_errors(engine, device, layer: int = 0) -> dict:
    """The two paged-attention kernels against their pure-JAX references
    on the engine's OWN pools and page table, mid-flight: relative error
    (max |kernel - ref| over max |ref|) of the decode kernel and of the
    multi-query kernel at the engine's chunk width."""
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.framework.core import np_dtype
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    (op,) = [o for o in engine.programs()["decode"].global_block().ops
             if o.type == "paged_decode_step"]
    scope = fluid.global_scope()
    kpool = scope.find(op.inputs["KPool"][0])[layer]
    vpool = scope.find(op.inputs["VPool"][0])[layer]
    nh, dh = kpool.shape[1], kpool.shape[3]
    N, C = engine.num_slots, engine.chunk_size
    ctx = np.ones((N,), np.int32)  # idle slots: one position, null page
    for slot, r in engine.scheduler.active.items():
        ctx[slot] = max(r.ctx_len, 1)
    rng = np.random.RandomState(1)
    dt = np_dtype(engine.lm.dtype)
    interpret = device.platform != "tpu"

    def rel_err(kernel, ref):
        kernel, ref = (np.asarray(a, np.float32) for a in (kernel, ref))
        if not np.all(np.isfinite(kernel)):
            raise AssertionError("non-finite paged-attention output")
        return float(np.abs(kernel - ref).max() / np.abs(ref).max())

    pt, cl = _stage(device, {"pt": engine.cache.page_table,
                             "cl": ctx}).values()
    q1 = jax.device_put(rng.randn(N, nh, dh).astype(dt), device)
    qc = jax.device_put(rng.randn(N, nh, C, dh).astype(dt), device)
    q0 = jax.device_put(np.maximum(ctx - C, 0), device)
    errs = {
        "paged_attention": rel_err(
            pa.paged_attention(q1, kpool, vpool, pt, cl,
                               interpret=interpret),
            pa.paged_attention_ref(q1, kpool, vpool, pt, cl)),
        "paged_attention_mq": rel_err(
            pa.paged_attention_mq(qc, kpool, vpool, pt, cl, q0,
                                  interpret=interpret),
            pa.paged_attention_mq_ref(qc, kpool, vpool, pt, cl, q0)),
    }
    for name, err in errs.items():
        if not err <= BF16_KERNEL_TOL:
            raise AssertionError(
                f"{name} differs from its reference by {err:.4g} of the "
                f"output scale (tolerance {BF16_KERNEL_TOL:.4g}) over "
                f"context lengths {ctx.tolist()}")
    return {"context_lengths": ctx.tolist(), "rel_err": errs,
            "tolerance": BF16_KERNEL_TOL}


def phase_serve(place, log: _CompileLog, dim: int = 512, n_layers: int = 8,
                n_heads: int = 8, vocab: int = 32000, max_len: int = 1024,
                prompt_lens=(16, 32, 64, 96, 128, 256, 384, 512),
                max_new: int = 32, slots: int = 8) -> dict:
    """A server that answers: DecoderLM behind ServingEngine(scheduler="v2")
    with the default page size, one request per prompt length, run to
    drain.  All finish with `max_new` tokens, no page leaks; the decode and
    mixed-step programs hold the paged-attention kernels, whose output on
    the engine's own pools agrees with the references."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.observability.metrics import monotime
    from paddle_tpu.serving import ServingEngine

    fluid.reset()
    device = place.jax_device()
    lm = transformer.DecoderLM(vocab, dim, n_layers, n_heads,
                               max_len=max_len, dtype="bfloat16")
    lm.logits(fluid.layers.data("tokens", shape=[max_len, 1], dtype="int64"),
              is_test=True)
    exe = fluid.Executor(place)
    begin = _begin(log)
    exe.run(fluid.default_startup_program())
    engine = ServingEngine(lm, max_batch_size=slots, scheduler="v2",
                           place=place)
    rng = np.random.RandomState(0)
    rids = [engine.submit(rng.randint(1, vocab, size=n).tolist(), max_new)
            for n in prompt_lens]

    # to drain, checking the kernels once on the way: at the first step
    # where half the slots hold a context past the first page
    kernels, step_seconds = None, []
    alive = True
    while alive:
        t0 = monotime()
        alive = engine.step()
        step_seconds.append(monotime() - t0)
        deep = [r for r in engine.scheduler.active.values()
                if r.ctx_len > engine.page_size]
        if kernels is None and 2 * len(deep) >= min(slots, len(rids)):
            kernels = _paged_kernel_errors(engine, device)
        if len(step_seconds) > 100000:
            raise AssertionError("the engine did not drain")
    if kernels is None:
        raise AssertionError("no step had half the slots past one page; "
                             "the paged kernels went unchecked")

    done = engine.finished
    counts = [len(done[r].generated) if r in done else None for r in rids]
    if counts != [max_new] * len(rids):
        raise AssertionError(f"token counts {counts}, want {max_new} each")
    stats = engine.stats()
    pages, prefix = stats["page_stats"], stats["prefix"]
    if pages["reserved"] or (pages["free"] + prefix["reclaimable_pages"]
                             != pages["num_pages"] - 1):
        raise AssertionError(f"page leak: {pages} {prefix}")
    kernel = {name: _kernel_of(hlo, device)
              for name, hlo in engine.optimized_hlo().items()}
    return _record(
        "serve", [device], log, begin,
        config=f"lm_d{dim}_l{n_layers}_h{n_heads}_v{vocab}_bf16_v2_"
               f"slots{slots}_page{engine.page_size}_"
               f"chunk{engine.chunk_size}",
        prompt_lens=list(prompt_lens), tokens=counts, kernel=kernel,
        paged_kernels=kernels,
        mixed_steps=stats["mixed_steps"], decode_steps=stats["decode_steps"],
        preemptions=stats["preemptions"],
        pages={k: pages[k] for k in ("num_pages", "free", "held",
                                     "peak_held")},
        engine_steps=len(step_seconds),
        step_s_smoke_reading=round(statistics.median(step_seconds), 5))


def phase_dp_train(log: _CompileLog, n_devices: int = 4,
                   batch_size: int = 512, depth: int = 50, image: int = 224,
                   steps: int = 3, devices=None) -> dict:
    """Data parallelism over the chips of one host:
    ParallelExecutor(axes={"dp": n}) on the ResNet program at the global
    batch.  Feeds and gradients are spread over n distinct devices and the
    compiled step holds an all-reduce."""
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import ParallelExecutor

    fluid.reset()
    avg_cost, _ = resnet.build_train_program(
        batch_size=batch_size, depth=depth, dtype="bfloat16", layout="NHWC",
        image_shape=(3, image, image), learning_rate=RESNET_SMOKE_LR)
    program = fluid.default_main_program()
    exe = ParallelExecutor(axes={"dp": n_devices}, devices=devices)
    begin = _begin(log)
    exe.run(fluid.default_startup_program())
    plan = exe.static_plan(program)
    batch = _resnet_batch(np.random.RandomState(0), batch_size, image)
    feed = {k: jax.device_put(v, plan[k]) for k, v in batch.items()}
    fetch = [avg_cost,
             program.global_block().all_parameters()[0].name + "@GRAD"]

    losses, seconds, (_, grad) = _train_steps(exe, feed, fetch, steps)
    spread = {"feed": len(feed["image"].sharding.device_set),
              "gradient": len(grad.sharding.device_set)}
    if set(spread.values()) != {n_devices}:
        raise AssertionError(f"not spread over {n_devices} devices: "
                             f"{spread}")
    hlo = exe.optimized_hlo(feed=feed, fetch_list=fetch)
    if "all-reduce" not in hlo:
        raise AssertionError("the compiled dp step holds no all-reduce")
    return _record("dp_train", list(exe.mesh.devices.flat), log, begin,
                   config=f"resnet{depth}_dp{n_devices}_global_bs"
                          f"{batch_size}_{image}px_bf16_nhwc",
                   losses=[round(l, 4) for l in losses],
                   devices_spread=spread, all_reduce=True,
                   **_timing(seconds))


# ---------------------------------------------------------------------------


def main() -> int:
    import jax

    found = jax.devices()
    dev = found[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind!r}, {len(found)} "
              f"device(s)); this script has no CPU mode",
              file=sys.stderr)
        return 1

    import paddle_tpu as fluid

    place = fluid.TPUPlace(0)
    log = _CompileLog()
    phases = []

    def done(record: dict):
        phases.append(record["phase"])
        print(json.dumps(record), flush=True)

    done(phase_resnet_train(place, log))
    done(phase_recurrent_train(place, log, cell="lstm"))
    done(phase_recurrent_train(place, log, cell="gru"))
    done(phase_lm_train(place, log))
    done(phase_serve(place, log))
    if len(found) >= 4:
        done(phase_dp_train(log, n_devices=4))
    print(json.dumps({"summary": "chip_smoke", "phases": phases,
                      "claim": None}), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(found)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
