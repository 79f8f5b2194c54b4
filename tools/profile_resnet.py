#!/usr/bin/env python
"""MFU analysis for the ResNet-50 train step (VERDICT r1 Weak #1).

Measures the compiled step's wall time and asks XLA itself for the FLOP
count (compiled.cost_analysis), so the MFU figure is the compiler's own
accounting rather than a hand-derived per-image constant.

Usage: python tools/profile_resnet.py [--trace DIR]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

V5E_PEAK_BF16 = 197e12  # TPU v5e peak bf16 FLOP/s (public spec)
V5E_HBM_BPS = 819e9  # TPU v5e HBM bandwidth, bytes/s (public spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--trace", default=None,
                    help="jax.profiler trace output dir")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip AOT cost analysis (isolates its device-side "
                         "footprint from the timing)")
    ap.add_argument("--layout", default="NHWC", choices=["NCHW", "NHWC"],
                    help="activation layout (bench.py headline default NHWC)")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint residual blocks (bench default ON)")
    ap.add_argument("--fuse-bn", action="store_true",
                    help="BN->conv prologue fusion (training_fusion)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.core import np_dtype
    from paddle_tpu.models import resnet

    avg_cost, acc = resnet.build_train_program(
        batch_size=args.bs, depth=args.depth, dtype=args.dtype,
        layout=args.layout, remat=args.remat,
        fuse_bn=args.fuse_bn)
    place = fluid.default_place()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    dev = place.jax_device()
    img_shape = ((args.bs, 224, 224, 3) if args.layout == "NHWC"
                 else (args.bs, 3, 224, 224))
    feed = {
        "image": jax.device_put(
            jnp.asarray(rng.rand(*img_shape).astype(np.float32),
                        dtype=np_dtype(args.dtype)), dev),
        "label": jax.device_put(
            jnp.asarray(rng.randint(0, 1000, (args.bs, 1)).astype(np.int64)),
            dev),
    }

    for _ in range(3):
        (loss,) = exe.run(feed=feed, fetch_list=[avg_cost])

    # pick the train-step entry (the other cache entry is the startup program)
    compiled = next(c for _, c in exe._cache.values()
                    if avg_cost.name in c.fetch_names)
    if args.trace:
        jax.profiler.start_trace(args.trace)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        (loss,) = exe.run(feed=feed, fetch_list=[avg_cost],
                          return_numpy=False)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.iters
    if args.trace:
        jax.profiler.stop_trace()

    # cost analysis AFTER timing: the AOT-compiled duplicate executable
    # occupies HBM and would slow the measured loop by ~2.5x
    cost = {}
    try:
        if args.no_cost:
            raise RuntimeError("--no-cost")
        state_w = {n: fluid.global_scope().find(n) for n in compiled.rw_state}
        state_r = {n: fluid.global_scope().find(n)
                   for n in compiled.external_reads}
        rngk = jax.random.PRNGKey(0)
        lowered = compiled.fn.lower(state_w, state_r, feed, rngk)
        cost = lowered.compile().cost_analysis() or {}
        if isinstance(cost, list):
            cost = cost[0]
    except Exception as e:  # cost analysis is best-effort
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)

    img_s = args.bs / dt
    flops = float(cost.get("flops", 0.0))
    print(f"step time        : {dt*1e3:.2f} ms")
    print(f"throughput       : {img_s:.1f} img/s")
    if flops:
        print(f"XLA flops/step   : {flops/1e9:.2f} GFLOP "
              f"({flops/args.bs/1e9:.2f} GFLOP/img)")
        print(f"achieved         : {flops/dt/1e12:.1f} TFLOP/s")
        print(f"MFU (v5e bf16)   : {100*flops/dt/V5E_PEAK_BF16:.1f}%")
    gb = float(cost.get("bytes accessed", 0.0))
    if gb and flops:
        # roofline verdict (docs/perf_resnet50_roofline.md): which roof is
        # binding, and how close the measured step runs to it
        t_mem = gb / V5E_HBM_BPS
        t_flop = flops / V5E_PEAK_BF16
        bound = "HBM-bandwidth" if t_mem > t_flop else "compute"
        roof = max(t_mem, t_flop)
        print(f"bytes accessed   : {gb/1e9:.1f} GB/step")
        print(f"roofline         : mem {t_mem*1e3:.1f} ms vs "
              f"flop {t_flop*1e3:.1f} ms -> {bound}-bound; measured "
              f"{dt*1e3:.1f} ms = {100*roof/dt:.0f}% of the binding roof")
        print(f"arith intensity  : {flops/gb:.0f} FLOP/byte "
              f"(v5e balance {V5E_PEAK_BF16/V5E_HBM_BPS:.0f})")


if __name__ == "__main__":
    main()
