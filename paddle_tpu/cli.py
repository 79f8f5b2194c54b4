"""The `paddle` command-line tool (reference paddle/scripts/
submit_local.sh.in:173-198: `paddle train|pserver|version|merge_model|
dump_config`), TPU edition.

Usage: python -m paddle_tpu <subcommand> [args]

  version               — framework + jax/device report
  train --script S      — run a training script with the package on path
  dump_config DIR|FILE  — text-proto dump of a saved model / __model__ file
  stats DIR|FILE        — one JSON line of program stats (native lib)
  merge_model DIR OUT   — bundle a saved inference model into one file
  validate DIR|FILE     — structural check via the native desc library
  lint DIR|FILE         — static dataflow verifier (analysis/verifier.py):
                          PTV rule findings report; exit 1 on errors
  analyze DIR|FILE      — static cost & memory analyzer (analysis/cost.py,
                          analysis/memory.py): FLOPs, HBM traffic and
                          peak, arithmetic intensity, predicted step time
                          for a chip spec; --json for one machine line.
                          --sharding adds the sharding/communication
                          analysis (analysis/sharding.py) over --axes;
                          with no MODEL it analyzes the 11 dryrun
                          parallelism modes and exits 1 on any
                          PTV018/PTV019 finding (the CI gate)
  diff A [B]            — translation validation (analysis/
                          equivalence.py): canonicalize both programs
                          and prove/refute semantic equivalence
                          (structural → abstract → differential tiers);
                          human semantic diff or --json; exit 1 when
                          NOT equivalent.  With one argument: self-check
                          mode — the program must prove equivalent to
                          its own canonical form and canonicalization
                          must be idempotent through a serialize round
                          trip (the CI fast tier runs this over the
                          book models)
  metrics DIR|FILE      — run N traced steps of a saved model under the
                          telemetry layer (observability/) and print the
                          metrics registry: Prometheus text, or --json
                          for the snapshot
  trace DIR|FILE        — same run, writing the Chrome/Perfetto
                          trace-event JSON (open in ui.perfetto.dev)
  show_pb DIR|FILE      — human-readable dump of blocks/ops/vars
  pserver ...           — host parameter service (distributed/pserver)
  master ...            — fault-tolerant task-dispatch service
                          (distributed/master; the Go master+etcd role,
                          with a file snapshot as the etcd replacement)
  cluster_train ...     — one-command multi-host job launch
                          (distributed/cluster_launch; the reference's
                          scripts/cluster_train/paddle.py role)
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _model_bytes(path: str) -> bytes:
    """Accept a model dir (containing __model__) or a raw proto file."""
    if os.path.isdir(path):
        path = os.path.join(path, "__model__")
    with open(path, "rb") as f:
        return f.read()


def cmd_version(args) -> int:
    import jax

    import paddle_tpu

    print(f"paddle_tpu {paddle_tpu.__version__}")
    print(f"jax {jax.__version__}")
    try:
        print("devices:", ", ".join(str(d) for d in jax.devices()))
    except RuntimeError as e:
        print("devices: unavailable:", e)
    return 0


def cmd_train(args) -> int:
    import runpy

    if args.script:
        sys.argv = [args.script] + args.script_args
        runpy.run_path(args.script, run_name="__main__")
        return 0

    # --config: the reference trainer flow (submit_local.sh `paddle train
    # --config=conf.py [--job=time]`): exec a v1 config that declares data
    # sources, topology ending in outputs(cost), and settings(); then train
    # unconditional: an empty value must CLEAR a previous run's args
    # (module-global state; code review r5)
    from .trainer.config_parser import set_config_args

    set_config_args(args.config_args or "")
    runpy.run_path(args.config, run_name="__config__")
    from .v1 import V1Trainer
    from .v1.layers import declared_outputs

    outs = declared_outputs()
    if not outs:
        print("config did not call outputs(cost)", file=sys.stderr)
        return 1
    trainer = V1Trainer(outs[0], batch_size=args.batch_size or None)
    if args.job == "time":
        import math

        ms, last_loss = trainer.time(args.time_batches)
        print(json.dumps({"job": "time", "ms_per_batch": round(ms, 3),
                          "batch_size": trainer.batch_size,
                          # strict JSON: NaN/Inf are not valid tokens
                          "last_loss": last_loss
                          if math.isfinite(last_loss) else None}))
        return 0
    save_dir = args.save_dir
    if save_dir:
        # reference --save_dir layout: persistables under pass-%05d/
        from . import io as fluid_io

        losses = []
        for p in range(args.num_passes):
            losses += trainer.train(num_passes=1, start_pass=p)
            d = os.path.join(save_dir, f"pass-{p:05d}")
            os.makedirs(d, exist_ok=True)
            fluid_io.save_persistables(trainer.exe, d)
            print(f"saved pass {p} -> {d}")
    else:
        losses = trainer.train(num_passes=args.num_passes)
    for i, l in enumerate(losses):
        print(f"Pass {i}: cost={l:.6f}")
    return 0


def cmd_dump_config(args) -> int:
    # one implementation for the CLI and paddle.utils.dump_config
    from .utils.dump_config import dump_config

    dump_config(args.model)
    return 0


def cmd_stats(args) -> int:
    from .native import program_desc as npd

    line = npd.stats(_model_bytes(args.model))
    if line is None:
        from .framework import proto_io

        prog = proto_io.parse_program(_model_bytes(args.model))
        line = json.dumps({
            "blocks": len(prog.blocks),
            "ops": sum(len(b.ops) for b in prog.blocks),
            "vars": sum(len(b.vars) for b in prog.blocks),
        })
    print(line)
    return 0


def cmd_validate(args) -> int:
    from .native import program_desc as npd

    ok, diag = npd.validate(_model_bytes(args.model))
    if ok:
        print("OK")
        return 0
    print(diag, file=sys.stderr)
    return 1


def _load_program_any(path):
    """(program, feed_names, fetch_names) from a saved-model dir or a raw
    program file.  Dirs go through io.load_program_desc (the same loader
    load_inference_model uses — __model__ preferred, program.json
    fallback, truncation guard); raw files are sniffed: JSON vs proto."""
    from . import io as fluid_io
    from .framework.core import Program

    if os.path.isdir(path):
        return fluid_io.load_program_desc(path)
    with open(path, "rb") as f:
        data = f.read()
    if data[:1] == b"{":
        program = Program.from_json(data.decode())
        if not any(b.ops for b in program.blocks):
            # same truncation guard as parse_program_bytes: an empty
            # program must never lint "OK: 0 findings"
            raise ValueError(f"{path} holds an empty program — "
                             f"truncated save?")
        return program, None, None
    return fluid_io.parse_program_bytes(data, path), None, None


def cmd_lint(args) -> int:
    from .analysis import verify_program

    program, feed, fetch = _load_program_any(args.model)
    suppress = set()
    for s in args.suppress or []:
        suppress.update(p.strip() for p in s.split(",") if p.strip())
    report = verify_program(
        program, feed_names=feed, fetch_names=fetch,
        batch_size=args.batch_size, suppress=suppress,
        check_shapes=not args.no_shapes)
    print(report.render())
    if report.errors or (args.strict and report.warnings):
        return 1
    return 0


def _parse_axes(spec: str):
    """"dp=4,mp=2" -> {"dp": 4, "mp": 2}; raises ValueError with a
    usage-worthy message on malformed input (caller turns it into
    exit code 2, not a traceback)."""
    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, size = part.partition("=")
        if not eq or not name.strip() or not size.strip().isdigit():
            raise ValueError(
                f"--axes entry {part!r} is not NAME=SIZE (e.g. "
                f"dp=4,mp=2)")
        out[name.strip()] = int(size)
    return out


def _sharding_reports(args):
    """`analyze --sharding` without a model: run the sharding analyzer
    over the built-in dryrun parallelism-mode catalog (the CI gate —
    exit 1 on any PTV018/PTV019 finding)."""
    from .analysis import cost as acost
    from .analysis import sharding as ash
    from .parallel import modes as pmodes

    pmodes.ensure_virtual_devices(8)
    names = [args.mode] if args.mode else list(pmodes.MODE_NAMES)
    rc = 0
    for name in names:
        mode, program, loss_name = pmodes.build_mode(name)
        mesh, plan, provenance = pmodes.mode_plan(mode, program)
        findings, ana = ash.sharding_findings(
            program, plan, batch_size=args.batch_size,
            provenance=provenance, mesh=mesh)
        comm = ash.comm_report(ana, chip=args.chip)
        gate = [f for f in findings if f.rule in ("PTV018", "PTV019")]
        if gate:
            rc = 1
        # per-mode scaling-efficiency projection over the mode's
        # primary (largest) mesh axis
        cost_rep = acost.program_cost(program,
                                      batch_size=args.batch_size,
                                      chip=args.chip)
        axis = max(mode.mesh_axes, key=mode.mesh_axes.get)
        curve = ash.scaling_curve(ana, cost_rep, axis=axis,
                                  sizes=(1, 2, 4, 8, 16, 64),
                                  chip=args.chip)
        if args.json:
            print(json.dumps({
                "mode": name, "mesh": dict(mode.mesh_axes),
                "findings": [f.format() for f in findings],
                "gate_failed": bool(gate),
                "per_kind": comm["per_kind"],
                "comm_time_s": comm["comm_time_s"],
                "scaling_axis": axis,
                "scaling_curve": [
                    {"n": p["n"],
                     "efficiency": round(p["efficiency"], 4)}
                    for p in curve]}))
            continue
        print(f"== mode {name} (mesh {dict(mode.mesh_axes)})")
        for f in findings:
            print("  " + f.format())
        if not findings:
            print(f"  OK: no findings "
                  f"({len(ana.collectives)} collectives classified)")
        print("  " + ash.render_comm(comm).replace("\n", "\n  "))
        eff = "  ".join(f"{p['n']}x{p['efficiency'] * 100:.0f}%"
                        for p in curve)
        print(f"  scaling over {axis!r} (strong, n x eff): {eff}")
    return rc


def cmd_analyze(args) -> int:
    from .analysis import cost as acost
    from .analysis import memory as amem

    if args.model is None:
        if not args.sharding:
            print("analyze: MODEL required unless --sharding runs the "
                  "built-in parallelism-mode catalog", file=sys.stderr)
            return 2
        return _sharding_reports(args)

    program, feed, fetch = _load_program_any(args.model)
    cost_rep = acost.program_cost(program, batch_size=args.batch_size,
                                  chip=args.chip)
    mem_rep = amem.peak_estimate(program, batch_size=args.batch_size,
                                 infer_shapes=not args.no_shapes)
    shard_rep = comm = None
    if args.sharding:
        from .analysis import sharding as ash
        from .parallel import modes as pmodes
        from .parallel.parallel_executor import ParallelExecutor

        try:
            axes = _parse_axes(args.axes) or {"dp": 8}
        except ValueError as e:
            print(f"analyze: {e}", file=sys.stderr)
            return 2
        n_devices = 1
        for s in axes.values():
            n_devices *= s
        pmodes.ensure_virtual_devices(max(1, n_devices))
        pe = ParallelExecutor(axes=axes)
        provenance = {}
        plan = pe.static_plan(program, provenance=provenance)
        findings, ana = ash.sharding_findings(
            program, plan, batch_size=args.batch_size,
            provenance=provenance, mesh=pe.mesh)
        comm = ash.comm_report(ana, chip=args.chip)
        cost_rep = acost.roofline_with_comm(cost_rep, comm,
                                            devices=n_devices)
        shard_rep = {"axes": axes,
                     "findings": [f.format() for f in findings],
                     "per_kind": comm["per_kind"],
                     "comm_time_s": comm["comm_time_s"]}
    if args.json:
        rec = {"model": args.model, "cost": cost_rep, "memory": mem_rep}
        if shard_rep is not None:
            rec["sharding"] = shard_rep
        print(json.dumps(rec))
    else:
        print(acost.render(cost_rep))
        print(amem.render(mem_rep))
        if shard_rep is not None:
            from .analysis import sharding as ash

            print(ash.render_comm(comm))
            for f in shard_rep["findings"]:
                print(f)
    return 0


def _load_scope_for(path):
    """Scope of saved values when `path` is a saved-model dir (the
    persistables.json manifest), else None — the differential oracle
    then seeds missing state deterministically by name."""
    if not os.path.isdir(path):
        return None
    manifest = os.path.join(path, "persistables.json")
    if not os.path.exists(manifest):
        return None
    from . import io as fluid_io
    from .framework.scope import Scope

    with open(manifest) as f:
        names = json.load(f)
    scope = Scope()
    fluid_io.load_vars(path, names, scope)  # the one saved-model loader
    return scope


def cmd_diff(args) -> int:
    from .analysis import equivalence as eqv

    prog_a, feed_a, fetch_a = _load_program_any(args.prog_a)
    execute = "never" if args.no_exec else "auto"

    if args.prog_b is None:
        # self-check: prove the program equivalent to its own canonical
        # form, and canonicalization idempotent through a JSON round
        # trip.  A bare program dump carries no meta: derive the
        # interface FIRST, so the canonical form and the proof agree on
        # it (deriving sinks after canonicalization would chase names
        # the alpha-renaming already replaced)
        if fetch_a is None:
            fetch_a = eqv.sink_outputs(prog_a.global_block())
        if feed_a is None:
            feed_a = [v.name for v in prog_a.global_block().vars.values()
                      if v.is_data]
        canon, info = eqv.canonicalize(prog_a, fetch_a, feed_a)
        from .framework.core import Program

        canon_rt = Program.from_json(canon.to_json())
        canon2, _ = eqv.canonicalize(canon_rt, fetch_a, feed_a)
        idem = not eqv.semantic_diff(canon, canon2)
        proof = eqv.prove_equivalent(prog_a, canon, feed_names=feed_a,
                                     fetch_names=fetch_a,
                                     batch_size=args.batch_size,
                                     execute="never")
        ok = proof.equivalent and idem
        if args.json:
            print(json.dumps({
                "mode": "self_check", "model": args.prog_a,
                "equivalent": bool(proof.equivalent),
                "idempotent": bool(idem), "tier": proof.tier,
                "ops": len(canon.global_block().ops),
                "dead_removed": info.dead_removed,
                "renamed": info.renamed,
                "duplicates": len(info.duplicates)}))
        else:
            print(f"self-check {args.prog_a}: "
                  f"{'OK' if ok else 'FAILED'} "
                  f"(canonical ops {len(canon.global_block().ops)}, "
                  f"dead removed {info.dead_removed}, renamed "
                  f"{info.renamed}, duplicates {len(info.duplicates)}, "
                  f"idempotent {idem})")
            if not proof.equivalent:
                print(proof.render())
        return 0 if ok else 1

    prog_b, feed_b, fetch_b = _load_program_any(args.prog_b)
    feed = feed_a if feed_a is not None else feed_b
    fetch = fetch_a if fetch_a is not None else fetch_b
    scope_a = _load_scope_for(args.prog_a)
    scope_b = _load_scope_for(args.prog_b)
    # one side with values, one bare program (dir vs its program.json):
    # share the scope — seeding only the bare side with synthetic
    # weights would fabricate a divergence between identical programs
    if scope_a is None:
        scope_a = scope_b
    elif scope_b is None:
        scope_b = scope_a
    if scope_a is not None and not args.no_exec:
        # saved VALUES are part of a model: two desc-identical dirs with
        # different weights must diff, so the oracle always runs
        execute = "always"
    proof = eqv.prove_equivalent(
        prog_a, prog_b, feed_names=feed, fetch_names=fetch,
        batch_size=args.batch_size, scope_before=scope_a,
        scope_after=scope_b, execute=execute, rtol=args.rtol,
        atol=args.atol)
    if args.json:
        print(json.dumps({
            "a": args.prog_a, "b": args.prog_b,
            "equivalent": bool(proof.equivalent), "tier": proof.tier,
            "findings": [f.format() for f in proof.findings],
            "diff": proof.diff.render() if proof.diff else None,
            "detail": proof.detail}))
    else:
        print(proof.render())
    return 0 if proof.equivalent else 1


def _telemetry_run(args):
    """Shared runner for the `metrics` and `trace` subcommands: load a
    saved model and drive N executor steps on deterministic synthetic
    feeds (the equivalence oracle's feed/state seeding) with the tracer
    enabled.  Returns the observability module, whose registry/tracer
    now hold the run."""
    from . import observability as obs
    from .analysis import equivalence as eqv
    from .framework.dataflow import state_classes
    from .framework.executor import Executor
    from .framework.place import CPUPlace
    from .framework.scope import Scope

    program, feed, fetch = _load_program_any(args.model)
    block = program.global_block()
    if fetch is None:
        fetch = eqv.sink_outputs(block)
    if feed is None:
        feed = [v.name for v in block.vars.values() if v.is_data]
    obs.enable_tracing()
    feeds = eqv.build_feeds(program, feed, batch_size=args.batch_size)
    scope = _load_scope_for(args.model) or Scope()
    # saved dirs carry persistables; anything else the block reads is
    # seeded deterministically by name, the differential-oracle idiom
    ext, rw, _ = state_classes(block, list(feeds))
    for name in list(ext) + list(rw):
        if scope.find(name) is not None:
            continue
        dv = block._find_var_recursive(name)
        if dv is not None and dv.shape is not None:
            scope.set(name, eqv._seed_array(
                name, eqv._bind(dv.shape, 1), dv.dtype or "float32", 0))
    exe = Executor(CPUPlace())
    for i in range(max(1, args.steps)):
        with obs.span("telemetry.step", step=i):
            exe.run(program, feed=dict(feeds), fetch_list=list(fetch),
                    scope=scope, rng_step=i)
    return obs


def cmd_metrics(args) -> int:
    """Run a saved model under the telemetry layer and print the
    registry state: Prometheus text by default, --json for the
    snapshot."""
    import json as _json

    obs = _telemetry_run(args)
    if args.trace_out:
        obs.TRACER.export(args.trace_out)
        print(f"# trace written to {args.trace_out}", file=sys.stderr)
    if args.json:
        print(_json.dumps(obs.REGISTRY.snapshot()))
    else:
        print(obs.REGISTRY.render_prometheus(), end="")
    return 0


def cmd_trace(args) -> int:
    """Run a saved model under the tracer and write the Chrome/Perfetto
    trace-event JSON (open it at https://ui.perfetto.dev): the process's
    start-up record (category `cold`), then the ring's spans; a dispatch
    of the step record the two do not hold would follow as `steady`."""
    obs = _telemetry_run(args)
    out = args.out or (os.path.basename(os.path.normpath(args.model))
                       + ".trace.json")
    obs.TRACER.export(out)
    exported = obs.TRACER.to_chrome()
    problems = obs.validate_chrome_trace(exported)
    n = len(exported["traceEvents"])
    cold = len(obs.TRACER.startup_events())
    rows = len(obs.TRACER.step_rows())
    print(f"{out}: {n} events, {cold} of the start-up record, "
          f"{rows} dispatches in the step record"
          + (f"; SCHEMA PROBLEMS: {problems}" if problems else ""))
    return 1 if problems else 0


def cmd_show_pb(args) -> int:
    from .utils import show_pb

    show_pb.dump_program(_model_bytes(args.model))
    return 0


def cmd_merge_model(args) -> int:
    from . import io

    out = io.merge_model(args.model_dir, args.out)
    print(out)
    return 0


def cmd_pserver(args) -> int:
    from .distributed import pserver

    pserver.serve_forever(host=args.host, port=args.port,
                          num_trainers=args.num_trainers,
                          checkpoint_dir=args.checkpoint_dir,
                          checkpoint_period_s=args.checkpoint_period)
    return 0


def cmd_master(args) -> int:
    from .distributed.master import MasterServer, MasterService

    svc = MasterService(timeout_s=args.task_timeout,
                        failure_max=args.failure_max,
                        snapshot_path=args.snapshot)
    srv = MasterServer(svc, host=args.host, port=args.port).start()
    if args.telemetry_port is not None:
        from .observability.httpd import serve_http

        tele = serve_http(args.telemetry_port)
        print(f"telemetry on http://127.0.0.1:{tele.port}/metrics "
              f"(+ /metrics.json, /trace)", flush=True)
    print(f"master serving on {srv.addr[0]}:{srv.addr[1]}", flush=True)
    try:
        srv._thread.join()
    except KeyboardInterrupt:
        srv.stop()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="paddle", description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    sub.add_parser("version").set_defaults(fn=cmd_version)

    p = sub.add_parser("train")
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--script", help="run a python training script")
    g.add_argument("--config",
                   help="v1 config (data sources + topology + settings)")
    p.add_argument("--job", choices=["train", "time"], default="train")
    p.add_argument("--num-passes", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=0)
    p.add_argument("--time-batches", type=int, default=5)
    p.add_argument("--config_args", "--config-args", default="",
                   help="a=1,b=x values config scripts read via "
                        "get_config_arg (reference --config_args)")
    p.add_argument("--save-dir", "--save_dir", default=None,
                   help="save persistables per pass under "
                        "SAVE_DIR/pass-%%05d (reference --save_dir)")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_train)

    for name, fn in (("dump_config", cmd_dump_config), ("stats", cmd_stats),
                     ("validate", cmd_validate), ("show_pb", cmd_show_pb)):
        p = sub.add_parser(name)
        p.add_argument("model", help="saved model dir or __model__ file")
        p.set_defaults(fn=fn)

    p = sub.add_parser("lint")
    p.add_argument("model", help="saved model dir, __model__ file, or "
                                 "program.json")
    p.add_argument("--batch-size", type=int, default=2,
                   help="value binding -1 feed dims during abstract eval")
    p.add_argument("--suppress", action="append", default=[],
                   help="comma-separated PTV rule ids to silence")
    p.add_argument("--strict", action="store_true",
                   help="exit 1 on warnings too, not just errors")
    p.add_argument("--no-shapes", action="store_true",
                   help="skip abstract shape/dtype eval (PTV006)")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("analyze")
    p.add_argument("model", nargs="?", default=None,
                   help="saved model dir, __model__ file, or "
                        "program.json; omit with --sharding to run the "
                        "built-in dryrun parallelism-mode catalog")
    p.add_argument("--batch-size", type=int, default=64,
                   help="value binding -1 feed dims in the cost/peak model")
    p.add_argument("--chip", default=None,
                   help="chip spec for the roofline prediction "
                        f"(default $PADDLE_TPU_CHIP or v5e)")
    p.add_argument("--json", action="store_true",
                   help="one JSON line instead of the human tables")
    p.add_argument("--no-shapes", action="store_true",
                   help="skip the abstract-eval shape oracle (desc-only "
                        "speed; -1 dims bind to --batch-size)")
    p.add_argument("--sharding", action="store_true",
                   help="sharding-propagation & communication analysis "
                        "(analysis/sharding.py): with MODEL, shard it "
                        "over --axes and add the comm-aware roofline; "
                        "without MODEL, analyze the 11 dryrun "
                        "parallelism modes and exit 1 on any "
                        "PTV018/PTV019 finding")
    p.add_argument("--mode", default=None,
                   help="restrict the catalog run to one mode name")
    p.add_argument("--axes", default="",
                   help="mesh axes for --sharding on a saved model, "
                        "e.g. dp=4,mp=2 (default dp=8)")
    p.set_defaults(fn=cmd_analyze)

    p = sub.add_parser("diff")
    p.add_argument("prog_a", help="saved model dir, __model__ file, or "
                                  "program.json")
    p.add_argument("prog_b", nargs="?", default=None,
                   help="second program; omit for self-check mode "
                        "(program vs its own canonical form)")
    p.add_argument("--batch-size", type=int, default=2,
                   help="binds -1 feed dims for the abstract and "
                        "differential tiers")
    p.add_argument("--no-exec", action="store_true",
                   help="desc-only: a structural mismatch is final "
                        "(skip the differential oracle)")
    p.add_argument("--rtol", type=float, default=1e-4,
                   help="differential-tier relative tolerance")
    p.add_argument("--atol", type=float, default=1e-6,
                   help="differential-tier absolute tolerance")
    p.add_argument("--json", action="store_true",
                   help="one JSON line instead of the human report")
    p.set_defaults(fn=cmd_diff)

    p = sub.add_parser("metrics")
    p.add_argument("model", help="saved model dir, __model__ file, or "
                                 "program.json")
    p.add_argument("--steps", type=int, default=5,
                   help="executor steps to drive (first compiles)")
    p.add_argument("--batch-size", type=int, default=2,
                   help="binds -1 feed dims of the synthetic feeds")
    p.add_argument("--json", action="store_true",
                   help="registry snapshot JSON instead of Prometheus "
                        "text")
    p.add_argument("--trace-out", default=None,
                   help="also write the step trace JSON here")
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser("trace")
    p.add_argument("model", help="saved model dir, __model__ file, or "
                                 "program.json")
    p.add_argument("--steps", type=int, default=5,
                   help="executor steps to drive (first compiles)")
    p.add_argument("--batch-size", type=int, default=2,
                   help="binds -1 feed dims of the synthetic feeds")
    p.add_argument("--out", default=None,
                   help="trace path (default MODEL.trace.json)")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("merge_model")
    p.add_argument("model_dir")
    p.add_argument("out")
    p.set_defaults(fn=cmd_merge_model)

    p = sub.add_parser("pserver")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=7164)
    p.add_argument("--num-trainers", type=int, default=1)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-period", type=float, default=600.0)
    p.set_defaults(fn=cmd_pserver)

    p = sub.add_parser("master")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--task-timeout", type=float, default=60.0)
    p.add_argument("--failure-max", type=int, default=3)
    p.add_argument("--snapshot", default=None,
                   help="task-queue snapshot file (restart recovery)")
    p.add_argument("--telemetry-port", type=int, default=None,
                   help="opt-in localhost /metrics + /trace endpoint "
                        "(0 = any free port)")
    p.set_defaults(fn=cmd_master)

    # `paddle cluster_train ...` — one-command multi-host launch
    # (reference paddle/scripts/cluster_train/paddle.py).  Dispatched
    # BEFORE argparse: REMAINDER can't capture leading --options, and
    # the launcher owns its whole argv anyway.
    sub.add_parser(
        "cluster_train",
        help="launch a multi-host job (see distributed/cluster_launch.py)")

    real_argv = sys.argv[1:] if argv is None else list(argv)
    if real_argv[:1] == ["cluster_train"]:
        from .distributed.cluster_launch import main as launch_main

        return launch_main(real_argv[1:])

    args = parser.parse_args(real_argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
