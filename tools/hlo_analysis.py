#!/usr/bin/env python
"""Static HLO analysis: materialized-buffer bytes and collective (ICI)
traffic of compiled training steps (VERDICT r3 Next #2/#8).

Two jobs, one methodology (parse XLA's post-optimization HLO dump):

  bytes        per-op-kind materialized output bytes of the ResNet-50
               train step (docs/perf_resnet50_roofline.md counted
               12.9 GB/step of elementwise fusion writes)
  collectives  per-mode collective op counts + buffer bytes for the
               multi-chip programs (dp / sp-ring / sp-ulysses / ep) on
               the 8-virtual-device CPU mesh — the honest substitute for
               scale-out numbers a single-chip environment cannot
               produce.  Collective BUFFER bytes are reported; actual
               wire traffic per algorithm (ring all-reduce ~2x bytes,
               all-gather (S-1)/S x bytes...) is noted per row.

Usage:
  python tools/hlo_analysis.py bytes [--no-remat] [--bs N]
  python tools/hlo_analysis.py collectives [--mode dp|sp_ring|sp_ulysses|ep]
  python tools/hlo_analysis.py peak      # static-vs-measured HBM peak on
                                         # the 3 validation programs
  python tools/hlo_analysis.py roofline [--tpu] [--bs N]
                                         # ResNet-50: static cost-model
                                         # prediction vs measured step
                                         # time/MFU
  python tools/hlo_analysis.py comm [--mode NAME]
                                         # sharding analyzer validation:
                                         # STATIC predicted collectives
                                         # (analysis/sharding.py) vs the
                                         # ACTUAL collectives in
                                         # optimized_hlo, per parallelism
                                         # mode (paddle_tpu.parallel.modes
                                         # catalog + the lm_dp/lm_mp/
                                         # lm_fsdp acceptance trio); one
                                         # static-vs-actual JSON line each
  python tools/hlo_analysis.py hybrid   # 2-slice simulated-DCN mesh vs one
                                         # slice, bitwise (8 virtual devices)
  python tools/hlo_analysis.py loop [--ks 1,4]
                                         # K fused steps vs K dispatches,
                                         # bitwise on fetches and state
  python tools/hlo_analysis.py all   # bytes+collectives, JSON per line

The workload runs in a re-exec'd child with XLA_FLAGS=--xla_dump_to so
the flags are set before jax imports; the parent parses the dump.
`peak` and `roofline` also anchor the static analyzer's validation:
`measured_peak_bytes` is the measured side tests/test_analysis.py holds
`analysis.memory.peak_estimate` within ±15% of.
"""

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
               "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
               "s8": 1, "u8": 1, "pred": 1,
               "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# "%name = <shape or tuple> kind(" — kind is the first identifier after
# the closing of the shape spec
_OPLINE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (\(?.*?\)?\{?[^=]*?)"
                     r"\s([a-z][\w\-]*)\(")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def shape_bytes(spec: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(spec):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


_COMP_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^=]*\)\s*->"
                          r".*\{\s*$")
# computations referenced this way are INLINED bodies whose values never
# materialize in HBM: fusion bodies (calls=), reduce/sort/scatter/select
# combinators (to_apply=, select=, scatter=).  Control-flow bodies
# (body=/condition=/branch_computations=) DO materialize their
# instruction outputs and are deliberately NOT in this set.
_INLINED_REF = re.compile(
    r"(?:calls|to_apply|select|scatter)=\{?%?([\w.\-]+)")


def parse_module(path: str):
    """Per-kind {count, out_bytes} + per-collective instances.

    Returns (kinds, top_kinds, colls): `kinds` counts EVERY instruction
    in the module text — including those inside fusion/combinator
    bodies, which never touch HBM (their values live in registers/VMEM)
    — while `top_kinds` counts only instructions in computations that
    materialize outputs (ENTRY, while/cond bodies).  Classification is
    by REFERENCE, not name: any computation referenced via
    calls=/to_apply=/select=/scatter= is an inlined body (code review
    r5: reduce regions named %region_N would slip a name-based filter).
    Only top_kinds supports an honest HBM-traffic roofline; the all-
    instruction table remains useful for fusion-content comparisons."""
    with open(path) as f:
        text = f.read()
    inlined = set()
    called = set()  # `call` also uses to_apply=, but its computation's
    # outputs DO materialize (like a while body) — keep those top-level
    for line in text.splitlines():
        m = _OPLINE.match(line)
        if not m:
            continue
        refs = _INLINED_REF.findall(line)
        (called if m.group(2) == "call" else inlined).update(refs)
    inlined -= called
    kinds = {}
    top_kinds = {}
    colls = []
    in_inlined = False
    for line in text.splitlines():
        h = _COMP_HEADER.match(line)
        if h:
            in_inlined = h.group(1) in inlined
            continue
        if line.strip() == "}":
            in_inlined = False
            continue
        m = _OPLINE.match(line)
        if not m:
            continue
        spec, kind = m.groups()
        b = shape_bytes(spec)
        k = kinds.setdefault(kind, {"count": 0, "out_bytes": 0})
        k["count"] += 1
        k["out_bytes"] += b
        if not in_inlined:
            t = top_kinds.setdefault(kind, {"count": 0, "out_bytes": 0})
            t["count"] += 1
            t["out_bytes"] += b
        if kind in COLLECTIVES:
            colls.append({"op": kind, "out_bytes": b,
                          "shape": spec.strip()[:120]})
    return kinds, top_kinds, colls


def find_main_module(dump_dir: str, markers) -> str:
    """The training-step module among the dumps: the startup program can
    be LARGER than the step (parameter-init RNG), so size alone picks
    wrong — score by occurrences of mode-relevant markers (collective ops
    / convolutions), size as tie-break."""
    cands = (glob.glob(os.path.join(dump_dir, "*after_optimizations.txt"))
             or [f for f in glob.glob(os.path.join(dump_dir, "*.txt"))
                 if not os.path.basename(f).startswith("child_")])
    if not cands:
        raise FileNotFoundError(f"no HLO dumps under {dump_dir}")

    def score(path):
        txt = open(path).read()
        return (sum(txt.count(f" {m}(") for m in markers),
                os.path.getsize(path))

    return max(cands, key=score)


def run_child(mode: str, dump_dir: str, args) -> None:
    env = dict(os.environ)
    env["PYTHONFAULTHANDLER"] = "1"  # SIGABRT dumps the stack to the
    # child_stderr file — cheap diagnosability for wedged children
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                       + f" --xla_dump_to={dump_dir}").strip()
    env["PDTPU_HLO_TEXT_DIR"] = dump_dir  # as_text() fallback target when
    # the dump flag writes no files
    if mode not in ("bytes", "roofline"):
        # multi-chip modes always use the virtual CPU mesh
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
    elif args.tpu:
        # the child takes the chip, and a chip belongs to one process: a
        # parent that already initialised JAX there would make it hang
        from paddle_tpu.framework.place import holds_accelerator

        if holds_accelerator():
            raise RuntimeError(
                "--tpu: this process already holds the accelerator, so "
                "the child that needs it would fail or hang; run "
                "hlo_analysis from a process that has not touched JAX")
        env.pop("JAX_PLATFORMS", None)
    elif not os.environ.get("JAX_PLATFORMS"):
        # bytes mode honors an explicit JAX_PLATFORMS but defaults to
        # cpu: without --tpu this tool must never take the chip
        env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, os.path.abspath(__file__), "--child", mode,
            "--bs", str(args.bs), "--image", str(args.image)]
    if args.no_remat:
        argv.append("--no-remat")
    if args.submode:
        argv += ["--mode", args.submode]
    # FILE-redirected output, not pipes: children of this environment's
    # python intermittently wedge when their (very chatty, multi-KB-line
    # cpu_aot_loader) stderr rides a subprocess PIPE; redirecting to a
    # file in the dump dir is reliable (observed r4, mechanism in the
    # XLA logging path, not ours)
    out_path = os.path.join(dump_dir, "child_stdout.txt")
    err_path = os.path.join(dump_dir, "child_stderr.txt")

    def _tail(path, n=2000):
        try:
            with open(path, "rb") as f:
                f.seek(max(0, os.path.getsize(path) - n))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no stderr captured>"

    with open(out_path, "w") as fo, open(err_path, "w") as fe:
        proc = subprocess.Popen(argv, env=env, stdout=fo, stderr=fe)
        try:
            rc = proc.wait(timeout=args.timeout)
        except subprocess.TimeoutExpired:
            # SIGABRT first: PYTHONFAULTHANDLER dumps the child's stack
            # into child_stderr.txt — the whole point of the wedge
            # diagnostics; then re-raise WITH the tail (the caller's
            # TemporaryDirectory is about to delete the file)
            proc.send_signal(subprocess.signal.SIGABRT)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            raise RuntimeError(
                f"child {mode} timed out after {args.timeout:.0f}s; "
                f"stderr tail (incl. faulthandler dump if any):\n"
                f"{_tail(err_path, 4000)}")
    if rc != 0:
        raise RuntimeError(f"child {mode} failed rc={rc}:\n"
                           f"{_tail(err_path)}")


def measured_peak_bytes(exe, program, feed, fetch_list, block_id=0) -> dict:
    """Measured side of the static-HBM validation: XLA's buffer
    assignment via Executor.memory_stats (argument + temp arena; see
    that docstring for why outputs are excluded).  Lives here so the
    cross-validation methodology stays beside the other measured-bytes
    ledgers this tool owns."""
    return exe.memory_stats(program, feed=feed, fetch_list=fetch_list,
                            block_id=block_id)


def validation_programs():
    """(name, build_fn, feed_fn, batch_size) for the 3 validation
    programs the ±15% contract runs on: fit-a-line, recognize-digits,
    and a small LM.  build_fn returns the fetch var after constructing
    the train program in the default program; feed_fn(bs) returns the
    feed dict."""
    import numpy as np

    import paddle_tpu as fluid

    def fit_a_line():
        x = fluid.layers.data(name="x", shape=[13])
        y = fluid.layers.data(name="y", shape=[1])
        pred = fluid.layers.fc(input=x, size=1)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
        return cost

    def fit_a_line_feed(bs):
        r = np.random.RandomState(0)
        return {"x": r.rand(bs, 13).astype("float32"),
                "y": r.rand(bs, 1).astype("float32")}

    def digits():
        img = fluid.layers.data(name="img", shape=[1, 28, 28])
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(img, num_filters=8, filter_size=5,
                                bias_attr=False)
        b = fluid.layers.batch_norm(c, act="relu")
        p = fluid.layers.pool2d(b, pool_size=2, pool_stride=2)
        flat = fluid.layers.reshape(p, [-1, 8 * 12 * 12])
        pred = fluid.layers.fc(flat, size=10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return loss

    def digits_feed(bs):
        r = np.random.RandomState(0)
        return {"img": r.rand(bs, 1, 28, 28).astype("float32"),
                "label": r.randint(0, 10, (bs, 1)).astype("int64")}

    def small_lm():
        from paddle_tpu.models.transformer import build_lm_train_program

        return build_lm_train_program(seq_len=64, vocab_size=512, dim=64,
                                      n_layers=2, n_heads=2,
                                      dtype="float32")

    def small_lm_feed(bs):
        r = np.random.RandomState(0)
        return {"tokens": r.randint(0, 512, (bs, 64, 1)).astype("int64"),
                "targets": r.randint(0, 512, (bs, 64, 1)).astype("int64")}

    return [("fit_a_line", fit_a_line, fit_a_line_feed, 64),
            ("recognize_digits", digits, digits_feed, 64),
            ("small_lm", small_lm, small_lm_feed, 8)]


def run_peak(args) -> None:
    """In-process static-vs-measured HBM peak over the validation
    programs, one JSON line each (the CI test asserts the same numbers
    through the library API)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import paddle_tpu as fluid
    from paddle_tpu.analysis import memory as amem

    for name, build, feed_fn, bs in validation_programs():
        fluid.reset()
        fetch = build()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        program = fluid.default_main_program()
        measured = measured_peak_bytes(exe, program, feed_fn(bs), [fetch])
        static = amem.peak_estimate(program, batch_size=bs)
        print(json.dumps({
            "analysis": "peak", "program": name, "batch_size": bs,
            "static_peak_bytes": static["total_peak_bytes"],
            "measured_peak_bytes": measured["peak_bytes"],
            "ratio": round(static["total_peak_bytes"]
                           / max(measured["peak_bytes"], 1), 4),
        }), flush=True)


def child_roofline(args) -> None:
    """Static roofline prediction vs measured step time for the
    ResNet-50 train step — the roofline-decomposition evidence row
    (static prediction trustworthy ⇔ measured/predicted gap is the
    tuner's headroom, ROADMAP #3)."""
    import time

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.analysis import cost as acost
    from paddle_tpu.analysis import memory as amem
    from paddle_tpu.models import resnet

    hw = args.image
    avg_cost, _ = resnet.build_train_program(
        batch_size=args.bs, depth=50, dtype="bfloat16", layout="NHWC",
        image_shape=(3, hw, hw), remat=not args.no_remat)
    program = fluid.default_main_program()
    chip = acost.detect_chip()
    static = acost.program_cost(program, batch_size=args.bs, chip=chip)
    peak = amem.peak_estimate(program, batch_size=args.bs,
                              infer_shapes=False)

    exe = fluid.Executor(fluid.default_place())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"image": rng.rand(args.bs, hw, hw, 3).astype("float32"),
            "label": rng.randint(0, 1000, (args.bs, 1)).astype("int64")}
    exe.run(feed=feed, fetch_list=[avg_cost])  # compile + warm
    iters = 5
    t0 = time.monotonic()
    for _ in range(iters):
        (out,) = exe.run(feed=feed, fetch_list=[avg_cost],
                         return_numpy=False)
    np.asarray(out)  # block on the last step
    measured_s = (time.monotonic() - t0) / iters
    spec = acost.chip_spec(chip)
    measured_mfu = (static["total_flops"]
                    / (measured_s * spec["flops_bf16"]))
    print(json.dumps({
        "analysis": "roofline", "chip": chip, "bs": args.bs,
        "image": hw,
        "static": {
            "total_flops": static["total_flops"],
            "hbm_bytes": static["hbm_bytes"],
            "arithmetic_intensity": round(
                static["arithmetic_intensity"], 2),
            "predicted_step_ms": round(
                static["predicted_step_time_s"] * 1e3, 3),
            "predicted_bound": static["predicted_bound"],
            "mfu_ceiling": round(static["mfu_ceiling"], 4),
            "hbm_peak_bytes": peak["total_peak_bytes"],
        },
        "measured": {
            "step_ms": round(measured_s * 1e3, 3),
            "mfu": round(measured_mfu, 4),
            "efficiency_vs_roofline": round(
                static["predicted_step_time_s"] / measured_s, 4),
        },
    }), flush=True)
    print("CHILD_OK")


# --------------------------------------------------------------- workloads
def child_bytes(args) -> None:
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    hw = args.image
    avg_cost, _ = resnet.build_train_program(
        batch_size=args.bs, depth=50, dtype="bfloat16", layout="NHWC",
        image_shape=(3, hw, hw), remat=not args.no_remat)
    exe = fluid.Executor(fluid.default_place())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"image": rng.rand(args.bs, hw, hw, 3).astype("float32"),
            "label": rng.randint(0, 1000, (args.bs, 1)).astype("int64")}
    exe.run(feed=feed, fetch_list=[avg_cost])
    # Where --xla_dump_to wrote nothing, take the text from the executable
    # API: re-lower the cached program and write compile().as_text() where
    # find_main_module will look.  With the persistent compile cache on,
    # the second compile is a load, not a full recompile.
    text_dir = os.environ.get("PDTPU_HLO_TEXT_DIR")
    if text_dir and not glob.glob(
            os.path.join(text_dir, "*after_optimizations.txt")):
        txt = exe.optimized_hlo(feed=feed, fetch_list=[avg_cost])
        with open(os.path.join(
                text_dir, "pjrt_module.after_optimizations.txt"), "w") as f:
            f.write(txt)
    print("CHILD_OK")


def child_collectives(mode: str) -> None:
    """One multi-chip training step on the 8-virtual-CPU mesh (the same
    program shapes dryrun_multichip validates)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.parallel import ParallelExecutor

    rng = np.random.RandomState(0)
    if mode == "dp":
        img = fluid.layers.data(name="x", shape=[64], dtype="float32")
        lab = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=img, size=256, act="relu")
        h = fluid.layers.fc(input=h, size=256, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=h, size=16), lab))
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9) \
            .minimize(loss)
        pe = ParallelExecutor(axes={"dp": 8})
        pe.run(fluid.default_startup_program())
        pe.run(feed={"x": rng.rand(32, 64).astype("float32"),
                     "y": rng.randint(0, 16, (32, 1)).astype("int64")},
               fetch_list=[loss])
    elif mode in ("sp_ring", "sp_ulysses"):
        T, D = 256, 32
        seq = fluid.layers.data(name="seq", shape=[T, D], dtype="float32")
        lab = fluid.layers.data(name="y", shape=[1], dtype="int64")
        attn = fluid.layers.multi_head_attention(
            seq, seq, seq, num_heads=4, causal=True,
            sp_mode="ring" if mode == "sp_ring" else "alltoall")
        flat = fluid.layers.reshape(
            fluid.layers.elementwise_add(seq, attn), [-1, T * D])
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=flat, size=10), lab))
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9) \
            .minimize(loss)
        pe = ParallelExecutor(axes={"dp": 4, "sp": 2})
        pe.run(fluid.default_startup_program())
        pe.run(feed={"seq": rng.rand(8, T, D).astype("float32"),
                     "y": rng.randint(0, 10, (8, 1)).astype("int64")},
               fetch_list=[loss])
    elif mode == "ep":
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[64], dtype="float32")
        out = fluid.layers.moe(x, num_experts=4, d_hidden=128,
                               capacity_factor=2.0)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=out, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        pe = ParallelExecutor(axes={"ep": 4, "dp": 2})
        pe.run(fluid.default_startup_program())
        xm = rng.rand(64, 64).astype("float32")
        pe.run(feed={"x": xm, "y": 2 * xm}, fetch_list=[loss])
    else:
        raise ValueError(mode)
    print("CHILD_OK")


# --------------------------------------------------------------- comm mode
def comm_validation_programs():
    """The ISSUE 9 acceptance trio: the small-LM train step under dp,
    mp (dp×mp), and fsdp — (name, executor_kwargs, feed_fn).  The test
    suite asserts the static analyzer's collective SET matches the
    optimized_hlo truth exactly on these, bytes within ±10%."""

    def build():
        from paddle_tpu.models.transformer import build_lm_train_program

        return build_lm_train_program(seq_len=16, vocab_size=64, dim=32,
                                      n_layers=1, n_heads=2,
                                      dtype="float32").name

    def feed(rng, bs):
        import numpy as np

        toks = rng.randint(0, 64, (bs, 16, 1)).astype("int64")
        return {"tokens": toks, "targets": np.roll(toks, -1, axis=1)}

    return [
        ("lm_dp", build, dict(axes={"dp": 8}), feed),
        ("lm_mp", build, dict(axes={"dp": 4, "mp": 2}), feed),
        ("lm_fsdp", build, dict(axes={"dp": 8}, fsdp_params=True), feed),
    ]


def _comm_mode_entry(name):
    """(build_fn, executor_kwargs, feed_fn, pipeline) for `name` — a
    catalog mode or one of the lm_* validation configs."""
    for vname, build, cfg, feed in comm_validation_programs():
        if vname == name:
            return build, cfg, feed, False
    from paddle_tpu.parallel import modes as pmodes

    m = pmodes.get_mode(name)
    cfg = dict(m.executor_kwargs)
    cfg["axes"] = dict(m.mesh_axes)
    return m.build, cfg, m.feed_fn, m.pipeline


def comm_static(name, batch_size=8):
    """Static side: build the mode's program, derive the plan, run the
    sharding propagation — desc-only, returns (per_kind, analysis)."""
    import paddle_tpu as fluid
    from paddle_tpu.analysis import sharding as ash
    from paddle_tpu.parallel import ParallelExecutor
    from paddle_tpu.parallel import modes as pmodes
    from paddle_tpu.mesh import make_mesh

    pmodes.ensure_virtual_devices(8)
    build, cfg, _, pipeline = _comm_mode_entry(name)
    fluid.reset()
    build()
    program = fluid.default_main_program()
    if pipeline:
        mesh = make_mesh(cfg["axes"])
        ana = ash.propagate(program, mesh=mesh, plan={},
                            batch_size=batch_size)
    else:
        pe = ParallelExecutor(**cfg)
        plan = pe.static_plan(program)
        ana = ash.propagate(program, plan=plan, batch_size=batch_size)
    return ana.per_kind(), ana


def child_comm(name, bs=8):
    """One training step of mode `name` on the 8-virtual-CPU mesh;
    always writes optimized_hlo text where find_main_module looks (the
    persistent compile cache suppresses --xla_dump_to on cache hits)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.parallel import ParallelExecutor

    build, cfg, feed_fn, pipeline = _comm_mode_entry(name)
    if pipeline:
        print("CHILD_SKIP pipeline mode has no ParallelExecutor HLO")
        return
    rng = np.random.RandomState(0)
    fluid.reset()
    loss_name = build()
    pe = ParallelExecutor(**cfg)
    pe.run(fluid.default_startup_program())
    dp = cfg["axes"].get("dp", 1)
    feed = feed_fn(rng, max(dp * 2, 8))
    pe.run(feed=feed, fetch_list=[loss_name])
    txt = pe.optimized_hlo(feed=feed, fetch_list=[loss_name])
    text_dir = os.environ.get("PDTPU_HLO_TEXT_DIR")
    if text_dir:
        with open(os.path.join(
                text_dir, "pjrt_module.after_optimizations.txt"),
                "w") as f:
            f.write(txt)
    print("CHILD_OK")


def run_comm(args) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.parallel.modes import MODE_NAMES

    names = ([args.submode] if args.submode else
             [n for n, *_ in comm_validation_programs()]
             + list(MODE_NAMES))
    for name in names:
        static, ana = comm_static(name)
        rec = {"analysis": "comm", "mode": name,
               "static": {k: dict(v) for k, v in static.items()}}
        _, _, _, pipeline = _comm_mode_entry(name)
        if pipeline:
            rec["actual"] = None
            rec["note"] = ("pipeline modes run through ProgramPipeline, "
                           "not ParallelExecutor — no step HLO to parse; "
                           "static side only")
            print(json.dumps(rec), flush=True)
            continue
        with tempfile.TemporaryDirectory(prefix=f"comm_{name}_") as dump:
            args.submode = name
            run_child("comm", dump, args)
            module = find_main_module(dump, COLLECTIVES)
            _, _, colls = parse_module(module)
        actual = {}
        for c in colls:
            e = actual.setdefault(c["op"], {"count": 0, "bytes": 0})
            e["count"] += 1
            e["bytes"] += c["out_bytes"]
        rec["actual"] = actual
        rec["set_match"] = set(static) == set(actual)
        rec["byte_ratio"] = {
            k: round(static.get(k, {}).get("bytes", 0)
                     / max(actual.get(k, {}).get("bytes", 0), 1), 4)
            for k in set(static) | set(actual)}
        print(json.dumps(rec), flush=True)


# ------------------------------------------------------------------ driver
def analyze(mode: str, args) -> dict:
    with tempfile.TemporaryDirectory(prefix=f"hlo_{mode}_") as dump:
        run_child("bytes" if mode == "bytes" else "collectives", dump,
                  args)
        module = find_main_module(
            dump, COLLECTIVES if mode != "bytes"
            else ("convolution", "custom-call"))
        kinds, top_kinds, colls = parse_module(module)
    total = sum(k["out_bytes"] for k in kinds.values())
    top_total = sum(k["out_bytes"] for k in top_kinds.values())
    # HBM write-traffic estimate: top-level compute outputs only —
    # parameter/tuple/get-tuple-element/bitcast produce no new bytes
    meta = ("parameter", "tuple", "get-tuple-element", "bitcast",
            "constant")
    hbm_writes = sum(v["out_bytes"] for k, v in top_kinds.items()
                     if k not in meta)
    rec = {
        "analysis": mode if mode == "bytes" else f"collectives:{args.submode}",
        "module": os.path.basename(module),
        "total_out_bytes": total,
        "top_level_out_bytes": top_total,
        "hbm_write_bytes_estimate": hbm_writes,
        "by_kind": {k: v for k, v in sorted(
            kinds.items(), key=lambda kv: -kv[1]["out_bytes"])
            if v["out_bytes"] > total * 0.001 or k in COLLECTIVES},
        "top_level_by_kind": {k: v for k, v in sorted(
            top_kinds.items(), key=lambda kv: -kv[1]["out_bytes"])
            if v["out_bytes"] > max(top_total, 1) * 0.001
            or k in COLLECTIVES},
    }
    if mode == "bytes":
        rec["config"] = {"bs": args.bs, "remat": not args.no_remat}
        rec["fusion_bytes"] = kinds.get("fusion", {}).get("out_bytes", 0)
        rec["conv_bytes"] = (
            kinds.get("convolution", {}).get("out_bytes", 0)
            + kinds.get("custom-call", {}).get("out_bytes", 0))
    else:
        per = {}
        for c in colls:
            e = per.setdefault(c["op"], {"count": 0, "buffer_bytes": 0})
            e["count"] += 1
            e["buffer_bytes"] += c["out_bytes"]
        rec["collectives"] = per
        rec["note"] = ("buffer bytes, not wire bytes: ring all-reduce "
                       "moves ~2x buffer over ICI, all-gather/reduce-"
                       "scatter ~(S-1)/S x, collective-permute ~1x")
    return rec


# -------------------------------------------------- the two parity procedures
def hybrid_parity_report(batch_size=8) -> dict:
    """2-slice simulated-DCN run vs single-slice, judged by the
    differential oracle at BITWISE tolerance (rtol=atol=0).

    Both sides run the same Momentum-MLP training step with
    cross-replica weight-update sharding active (`zero_dp_states=True`,
    arXiv:2004.13336): side A on a flat `{dp: 8}` mesh, side B on a
    `make_hybrid_mesh({dp: 4}, {dcn_dp: 2})` multi-slice mesh whose
    batch and state0 dims shard over the ``("dcn_dp", "dp")`` tuple.
    Same 8 devices in the same order → XLA lowers identical collectives
    → every fetch and every written state value (params AND sharded
    velocities) must match bit-for-bit.  The record also publishes the
    analyzer's predicted wire bytes per link class for both layouts —
    the bench artifact for the ICI-reduce-scatter → DCN-all-reduce →
    ICI-all-gather decomposition."""
    from paddle_tpu.analysis.equivalence import differential_run
    from paddle_tpu.analysis.sharding import comm_report, propagate
    from paddle_tpu.mesh import make_hybrid_mesh, spec_of
    from paddle_tpu.parallel import ParallelExecutor
    from paddle_tpu.parallel import modes as pmodes

    pmodes.ensure_virtual_devices(8)
    mode, program, loss_name = pmodes.build_mode("dp")
    block = program.global_block()
    feed_names = sorted(n for n, v in block.vars.items() if v.is_data)

    exe_a = ParallelExecutor(axes={"dp": 8}, zero_dp_states=True)
    mesh_b = make_hybrid_mesh({"dp": 4}, {"dcn_dp": 2})
    exe_b = ParallelExecutor(mesh=mesh_b, zero_dp_states=True)

    findings = differential_run(
        program, program, feed_names, [loss_name],
        batch_size=batch_size, rtol=0.0, atol=0.0,
        executor_a=exe_a, executor_b=exe_b)

    def link_report(exe):
        prov = {}
        plan = exe.static_plan(program, provenance=prov)
        ana = propagate(program, mesh=exe.mesh, plan=plan,
                        batch_size=batch_size, provenance=prov)
        rep = comm_report(ana)
        return plan, {
            "per_kind": ana.per_kind(),
            "link_bytes": rep["link_bytes"],
            "ici_time_s": rep["ici_time_s"],
            "dcn_time_s": rep["dcn_time_s"],
            "decomposed": [e["decomposed"] for e in rep["breakdown"]
                           if "decomposed" in e],
        }

    plan_a, comm_a = link_report(exe_a)
    plan_b, comm_b = link_report(exe_b)
    velocity_specs = {
        n: [list(e) if isinstance(e, tuple) else e
            for e in spec_of(s)]
        for n, s in sorted(plan_b.items()) if "velocity" in n}
    return {
        "analysis": "hybrid_parity",
        "mesh_single": {"dp": 8},
        "mesh_hybrid": {"dcn_dp": 2, "dp": 4},
        "weight_update_sharding": True,
        "bitwise": not findings,
        "verdict": "PROVEN" if not findings else "DIVERGED",
        "findings": [f.format() for f in findings],
        "fetches": [loss_name],
        "velocity_specs_hybrid": velocity_specs,
        "comm": {"single": comm_a, "hybrid": comm_b},
    }


# ISSUE 20: fused K-step dispatch vs K sequential dispatches


def _loop_models():
    """The two loop-parity obligations: a Momentum-MLP (hidden layer +
    velocity state, the smallest real training step) and the standing
    small decoder LM (attention, layernorm, Adam moments — the stateful
    stochastic program family step_loop must not perturb)."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.core import Program, program_guard

    def mlp():
        x = fluid.layers.data(name="x", shape=[16])
        y = fluid.layers.data(name="y", shape=[1])
        h = fluid.layers.fc(x, size=32, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(loss)
        return loss.name, ["x", "y"]

    def small_lm():
        from paddle_tpu.models import standing

        feed, fetches, _bs = standing.build_small_lm()
        return _name_of(fetches[0]), sorted(feed)

    for kind, build in (("mlp", mlp), ("small_lm", small_lm)):
        main, startup = Program(), Program()
        with unique_name.guard(), program_guard(main, startup):
            loss_name, feed_names = build()
        yield kind, main, startup, loss_name, feed_names


def _name_of(f):
    return f if isinstance(f, str) else f.name


def loop_parity_report(ks=(1, 2, 4, 8), batch_size=4) -> dict:
    """K-step fused dispatch (`Executor.run(steps_per_dispatch=K)`,
    framework/step_loop.py) vs K sequential `run()` calls, judged at
    BITWISE tolerance on every per-step fetch AND every written-back
    state value (params, velocities, Adam moments).

    Both sides start from an identical copy of the startup-initialized
    state and see the same K deterministic feed batches (`build_feeds`
    seeded per step); the sequential side pins `rng_step=i`, the fused
    side `rng_step=0` with the on-device `fold_in(base, step0+i)`
    stream — so agreement proves the fused loop IS K steps, RNG
    included, not merely close.  The run_tests.sh `loop` gate consumes
    the verdict (PROVEN required)."""
    import numpy as np

    from paddle_tpu.analysis.equivalence import build_feeds
    from paddle_tpu.framework import dataflow
    from paddle_tpu.framework.executor import Executor
    from paddle_tpu.framework.place import CPUPlace
    from paddle_tpu.framework.scope import Scope

    cases = []
    for kind, main, startup, loss_name, feed_names in _loop_models():
        block = main.global_block()
        ext, rw, written = dataflow.state_classes(block, feed_names)
        exe = Executor(CPUPlace())
        for k in ks:
            k = int(k)
            sa, sb = Scope(), Scope()
            exe.run(startup, scope=sa, verify=False)
            for n in set(ext) | set(rw):
                v = sa.find(n)
                if v is not None:
                    sb.set(n, np.array(np.asarray(v)))
            feeds = [build_feeds(main, feed_names, batch_size, seed=i)
                     for i in range(k)]
            # K=1 is the identity path (no stacking in, none out): its
            # "parity" is plain run-to-run determinism
            stacked = (feeds[0] if k == 1 else
                       {n: np.stack([f[n] for f in feeds])
                        for n in feed_names})
            seq = [np.asarray(exe.run(main, feed=feeds[i],
                                      fetch_list=[loss_name], scope=sb,
                                      rng_step=i, verify=False)[0])
                   for i in range(k)]
            fused = np.asarray(exe.run(
                main, feed=stacked, fetch_list=[loss_name], scope=sa,
                rng_step=0, verify=False, steps_per_dispatch=k)[0])
            findings = []
            if k > 1 and tuple(fused.shape[:1]) != (k,):
                findings.append(
                    f"fetch {loss_name!r} not stacked (K, ...): "
                    f"{fused.shape}")
            for i in range(k):
                a = fused[i] if k > 1 else fused
                if a.shape != seq[i].shape or not np.array_equal(a, seq[i]):
                    findings.append(
                        f"fetch {loss_name!r} step {i} diverged: "
                        f"fused={a!r} sequential={seq[i]!r}")
            for n in written:
                a, b = np.asarray(sa.find(n)), np.asarray(sb.find(n))
                if a.shape != b.shape:
                    findings.append(
                        f"written state {n!r} shape diverged: "
                        f"{a.shape} vs {b.shape}")
                elif not np.array_equal(a, b):
                    d = np.max(np.abs(a.astype(np.float64)
                                      - b.astype(np.float64)))
                    findings.append(
                        f"written state {n!r} diverged after {k} steps: "
                        f"max|a-b|={d:.3e}")
            cases.append({
                "model": kind, "k": k,
                "fetches": [loss_name],
                "written_state": len(written),
                "bitwise": not findings,
                "findings": findings,
            })
    all_ok = all(c["bitwise"] for c in cases)
    return {
        "analysis": "loop_parity",
        "ks": [int(k) for k in ks],
        "batch_size": int(batch_size),
        "models": sorted({c["model"] for c in cases}),
        "cases": cases,
        "bitwise": all_ok,
        "verdict": "PROVEN" if all_ok else "DIVERGED",
        "findings": [f for c in cases for f in c["findings"]],
    }


def run_hybrid(args) -> None:
    """The 2-slice simulated-DCN parity capture: bitwise differential
    run (flat dp=8 vs dcn_dp=2 x dp=4 with weight-update sharding) plus
    predicted wire bytes per link class — the ISSUE 19 bench artifact.
    Executes real jitted steps on 8 virtual CPU devices."""
    rec = hybrid_parity_report()
    print(json.dumps(rec), flush=True)
    if rec["verdict"] != "PROVEN":
        sys.exit(1)


def run_loop(args) -> None:
    """The fused K-step dispatch parity capture: for each K in --ks
    and each standing model (MLP + small LM), one fused
    steps_per_dispatch=K run vs K sequential dispatches, bitwise on
    every per-step fetch AND all written state — the
    framework/step_loop.py contract.  Exits 1 unless every case is
    PROVEN — run_tests.sh's `loop` gate."""
    ks = tuple(int(k) for k in (args.ks or "1,4").split(","))
    rec = loop_parity_report(ks=ks)
    print(json.dumps(rec), flush=True)
    if rec["verdict"] != "PROVEN":
        sys.exit(1)


def analyze_roofline(args) -> None:
    """Driver half of the roofline capture: run the child (accelerator-
    honoring, like bytes mode), pass its JSON line through."""
    with tempfile.TemporaryDirectory(prefix="hlo_roofline_") as dump:
        run_child("roofline", dump, args)
        with open(os.path.join(dump, "child_stdout.txt")) as f:
            for line in f:
                if line.startswith("{"):
                    print(line.rstrip(), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", nargs="?", default="all",
                    choices=["bytes", "collectives", "peak", "roofline",
                             "comm", "hybrid", "loop", "all"])
    ap.add_argument("--child", default=None)
    ap.add_argument("--mode", dest="submode", default=None)
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--image", type=int, default=224,
                    help="input height/width (a CPU evidence run wants a "
                         "small proxy; the chip capture keeps 224)")
    ap.add_argument("--timeout", type=float, default=1800)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--tpu", action="store_true",
                    help="bytes mode: use the environment's accelerator "
                         "instead of defaulting to cpu")
    ap.add_argument("--ks", default=None,
                    help="loop mode: comma-separated steps_per_dispatch "
                         "values to prove (default 1,4)")
    args = ap.parse_args()

    if args.child:
        if args.child == "bytes":
            child_bytes(args)
        elif args.child == "roofline":
            child_roofline(args)
        elif args.child == "comm":
            child_comm(args.submode)
        else:
            child_collectives(args.submode)
        return

    if args.what == "peak":
        run_peak(args)
        return
    if args.what == "roofline":
        analyze_roofline(args)
        return
    if args.what == "comm":
        run_comm(args)
        return
    if args.what == "hybrid":
        run_hybrid(args)
        return
    if args.what == "loop":
        run_loop(args)
        return
    if args.what in ("bytes", "all"):
        print(json.dumps(analyze("bytes", args)), flush=True)
    if args.what in ("collectives", "all"):
        modes = ([args.submode] if args.submode
                 else ["dp", "sp_ring", "sp_ulysses", "ep"])
        for m in modes:
            args.submode = m
            print(json.dumps(analyze("collectives", args)), flush=True)


if __name__ == "__main__":
    main()
