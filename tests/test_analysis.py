"""ProgramDesc verifier: dataflow analysis, the PTV rule engine, the
transpiler verified-in/verified-out contracts, Executor.run(verify=),
the `paddle lint` CLI, and repo_lint.

The mutation tests are the acceptance spine: each seeded defect class —
dropped send (grad producer) in a distribute-transpiled program, a
memory_optimize "reuse" reordered to extend a live range, a dropped grad
op for a trainable parameter, a dependency-free duplicate write — must be
flagged with its expected stable rule ID, while the clean versions of all
four transpiler runs produce zero findings."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import (contracts, verify_program,
                                 VerificationError)
from paddle_tpu.framework import dataflow
from paddle_tpu.analysis.verifier import RULES


def _mlp(prefix=""):
    x = fluid.layers.data(name=prefix + "x", shape=[4])
    y = fluid.layers.data(name=prefix + "y", shape=[1])
    h = fluid.layers.fc(input=x, size=8, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    return fluid.layers.mean(fluid.layers.square_error_cost(pred, y))


def _train_mlp():
    cost = _mlp()
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    return cost, fluid.default_main_program()


# ---------------------------------------------------------------------------
# dataflow primitives


def test_def_use_and_dependency_graph():
    cost, prog = _train_mlp()
    block = prog.global_block()
    defs, uses = dataflow.def_use(block)
    assert cost.name in defs
    # the loss is read by the seed fill_constant consumer chain (backward)
    preds = dataflow.dependency_graph(block)
    assert len(preds) == len(block.ops)
    # the mean op depends on the op producing its input
    mean_i = next(i for i, op in enumerate(block.ops) if op.type == "mean")
    src = block.ops[mean_i].input_names()[0]
    assert defs[src][-1] in preds[mean_i]


def test_happens_before_transitive():
    cost, prog = _train_mlp()
    block = prog.global_block()
    anc = dataflow.happens_before(block)
    mean_i = next(i for i, op in enumerate(block.ops) if op.type == "mean")
    mul_i = next(i for i, op in enumerate(block.ops) if op.type == "mul")
    assert (anc[mean_i] >> mul_i) & 1  # mul feeds the loss transitively
    assert not (anc[mul_i] >> mean_i) & 1


def test_var_intervals():
    cost, prog = _train_mlp()
    iv = dataflow.var_intervals(prog.global_block())
    fd, lu = iv[cost.name]
    assert 0 <= fd <= lu < len(prog.global_block().ops)


def test_clean_training_program_verifies_clean():
    cost, prog = _train_mlp()
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name])
    assert not rep.findings, rep.render()
    rep2 = verify_program(fluid.default_startup_program())
    assert not rep2.findings, rep2.render()


# ---------------------------------------------------------------------------
# rule-by-rule seeded defects


def test_use_before_def_flagged_ptv001():
    cost, prog = _train_mlp()
    block = prog.global_block()
    op0 = next(op for op in block.ops if op.type == "mul")
    block.ops.remove(op0)
    block.ops.append(op0)
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name], check_shapes=False)
    assert any(f.rule == "PTV001" for f in rep.findings), rep.render()
    assert rep.errors


def test_unregistered_op_flagged_ptv002():
    cost, prog = _train_mlp()
    prog.global_block().append_op("totally_bogus_op", outputs={"Out": ["z"]})
    rep = verify_program(prog, check_shapes=False)
    assert any(f.rule == "PTV002" for f in rep.errors)


def test_dangling_feed_and_fetch_ptv003_ptv004():
    cost, prog = _train_mlp()
    rep = verify_program(prog, feed_names=["nope"],
                         fetch_names=["also_nope"], check_shapes=False)
    # superset feeds are legal at run time (Executor._prepare_feeds passes
    # them through) -> warning; a fetch nothing materializes -> error
    assert any(f.rule == "PTV003" for f in rep.warnings)
    assert any(f.rule == "PTV004" for f in rep.errors)
    # fetching a fed name is fine: feeds land in the executor env directly
    rep2 = verify_program(prog, feed_names=["x", "y"],
                          fetch_names=["x", cost.name], check_shapes=False)
    assert not any(f.rule == "PTV004" for f in rep2.findings), rep2.render()


def test_invalid_sub_block_flagged_ptv005():
    cost, prog = _train_mlp()
    prog.global_block().append_op(
        "while", inputs={}, outputs={}, attrs={"sub_block": 42})
    rep = verify_program(prog, check_shapes=False)
    assert any(f.rule == "PTV005" for f in rep.errors)


def test_shape_mismatch_flagged_ptv006():
    fluid.layers.data(name="x", shape=[4])
    block = fluid.default_main_program().global_block()
    block.create_var(name="bad", shape=(3, 3), dtype="float32")
    block.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["bad"]},
                    attrs={"scale": 2.0})
    rep = verify_program(fluid.default_main_program(), feed_names=["x"],
                         fetch_names=["bad"])
    assert any(f.rule == "PTV006" for f in rep.findings), rep.render()


def test_duplicate_write_flagged_ptv007():
    """Acceptance mutation: a dependency-free duplicate write is a WAW
    race — whichever write a reordering pass schedules last wins."""
    cost, prog = _train_mlp()
    block = prog.global_block()
    tmp = next(op for op in block.ops if op.type == "mul").output_names()[0]
    block.append_op("fill_constant", outputs={"Out": [tmp]},
                    attrs={"shape": [1], "value": 0.0, "dtype": "float32"})
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name], check_shapes=False)
    assert any(f.rule == "PTV007" for f in rep.findings), rep.render()


def test_missing_grad_flagged_ptv009():
    """Acceptance mutation: dropping the grad op of a trainable parameter
    on the loss path must be flagged — the param would silently freeze
    (the round-5 DDPM clone bug's defect class)."""
    cost, prog = _train_mlp()
    block = prog.global_block()
    gname = "fc_0.w_0@GRAD"
    drop = [i for i, op in enumerate(block.ops)
            if gname in op.output_names()
            or (op.type == "sgd" and "fc_0.w_0" in op.inputs["Param"])]
    block.ops[:] = [op for i, op in enumerate(block.ops) if i not in drop]
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name], check_shapes=False)
    hits = [f for f in rep.findings if f.rule == "PTV009"]
    assert hits and hits[0].var == "fc_0.w_0", rep.render()


def test_dead_op_flagged_ptv010():
    cost, prog = _train_mlp()
    block = prog.global_block()
    block.create_var(name="orphan", shape=(1,), dtype="float32")
    block.append_op("fill_constant", outputs={"Out": ["orphan"]},
                    attrs={"shape": [1], "value": 1.0, "dtype": "float32"})
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name], check_shapes=False)
    assert any(f.rule == "PTV010" for f in rep.findings), rep.render()
    # without fetch context the rule must stay silent, not guess
    rep2 = verify_program(prog, check_shapes=False)
    assert not any(f.rule == "PTV010" for f in rep2.findings)


def test_suppression_per_op_and_per_call():
    cost, prog = _train_mlp()
    block = prog.global_block()
    tmp = next(op for op in block.ops if op.type == "mul").output_names()[0]
    op = block.append_op("fill_constant", outputs={"Out": [tmp]},
                         attrs={"shape": [1], "value": 0.0,
                                "dtype": "float32"})
    kw = dict(feed_names=["x", "y"], fetch_names=[cost.name],
              check_shapes=False)
    assert any(f.rule == "PTV007" for f in verify_program(prog, **kw).findings)
    # per-call
    rep = verify_program(prog, suppress={"PTV007", "PTV008"}, **kw)
    assert not any(f.rule in ("PTV007", "PTV008") for f in rep.findings)
    # per-op attr
    op.attrs["__verify_suppress__"] = "PTV007,PTV008"
    rep = verify_program(prog, **kw)
    assert not any(f.rule == "PTV007" for f in rep.findings), rep.render()


def test_rule_catalog_stable():
    """IDs are load-bearing (suppressions, CI greps): assert the catalog."""
    assert [r for r in RULES] == [f"PTV{i:03d}" for i in range(1, 25)]
    assert RULES["PTV001"].severity == "error"
    assert RULES["PTV003"].severity == "warning"
    assert RULES["PTV009"].severity == "warning"
    assert RULES["PTV014"].severity == "error"
    assert RULES["PTV015"].severity == "warning"
    assert RULES["PTV016"].severity == "warning"
    assert RULES["PTV017"].severity == "error"
    assert RULES["PTV018"].severity == "error"
    assert RULES["PTV019"].severity == "warning"
    assert RULES["PTV020"].severity == "info"
    assert RULES["PTV021"].severity == "warning"
    assert RULES["PTV022"].severity == "error"
    assert RULES["PTV023"].severity == "info"
    assert RULES["PTV024"].severity == "error"


def test_donated_overwrite_race_ptv015():
    """Mutation: a BLIND overwrite (fill_constant) of a donated
    parameter racing the forward ops that read it must be PTV015; the
    clean program (every state write is the sgd self-update idiom, which
    consumes the old value) stays silent."""
    cost, prog = _train_mlp()
    kw = dict(feed_names=["x", "y"], fetch_names=[cost.name],
              check_shapes=False)
    rep = verify_program(prog, **kw)
    assert not any(f.rule == "PTV015" for f in rep.findings), rep.render()

    block = prog.global_block()
    # blind overwrite of a read-then-written param, dependency-free —
    # and the param's FIRST write is still the clean sgd self-update:
    # a later blind write must not hide behind it
    block.append_op("fill_constant", outputs={"Out": ["fc_0.w_0"]},
                    attrs={"shape": [4, 8], "value": 0.0,
                           "dtype": "float32"})
    rep = verify_program(prog, **kw)
    hits = [f for f in rep.findings if f.rule == "PTV015"]
    assert hits and hits[0].var == "fc_0.w_0", rep.render()

    # same verdict when the blind write is the ONLY write
    block.ops[:] = [op for op in block.ops
                    if not (op.type == "sgd"
                            and "fc_0.w_0" in op.input("Param"))]
    rep = verify_program(prog, **kw)
    hits = [f for f in rep.findings if f.rule == "PTV015"]
    assert hits and hits[0].var == "fc_0.w_0", rep.render()


def _mesh8():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device test mesh")
    from paddle_tpu.parallel import make_mesh

    return make_mesh


def test_sharded_donation_ptv016():
    """Mutation pair: a donated param sharded over dp under the plan is
    PTV016; the same program with a replicated plan is silent."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    make_mesh = _mesh8()
    cost, prog = _train_mlp()
    mesh = make_mesh({"dp": 8})
    kw = dict(feed_names=["x", "y"], fetch_names=[cost.name],
              check_shapes=False)
    replicated = {"fc_0.w_0": NamedSharding(mesh, P())}
    rep = verify_program(prog, plan=replicated, **kw)
    assert not any(f.rule == "PTV016" for f in rep.findings), rep.render()

    sharded = {"fc_0.w_0": NamedSharding(mesh, P("dp", None))}
    rep = verify_program(prog, plan=sharded, **kw)
    hits = [f for f in rep.findings if f.rule == "PTV016"]
    assert hits and hits[0].var == "fc_0.w_0", rep.render()
    # a bare PartitionSpec (no mesh attached) still counts as sharded —
    # the documented plan contract must not go silently inert
    rep = verify_program(prog, plan={"fc_0.w_0": P("dp", None)}, **kw)
    assert any(f.rule == "PTV016" for f in rep.findings), rep.render()
    # no plan -> rule silent (single-device programs can't trip it)
    rep = verify_program(prog, **kw)
    assert not any(f.rule == "PTV016" for f in rep.findings)


def test_known_crash_parallel_programs_flagged_ptv016():
    """The 3 test_parallel programs whose donated-state materialization
    natively crashes jax-CPU (contained as 'native crash in isolation
    child' skips — see their docstrings) must each be statically flagged
    by the donation rule family: the analyzer turns the mystery skips
    into documented, detected hazards.  Nothing here runs or compiles —
    ParallelExecutor.static_plan is desc-only."""
    _mesh8()
    from paddle_tpu.parallel import ParallelExecutor

    def momentum_mlp():
        fluid.reset()
        x = fluid.layers.data(name="x", shape=[32])
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=64, act="relu")
        h2 = fluid.layers.fc(input=h, size=64, act="relu")
        logits = fluid.layers.fc(input=h2, size=10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
        return loss, fluid.default_main_program()

    configs = [
        # test_zero_dp_optimizer_state_sharding
        ("zero_dp8", dict(axes={"dp": 8}, zero_dp_states=True)),
        # test_sharded_checkpoint_roundtrip
        ("zero_dp4_mp2", dict(axes={"dp": 4, "mp": 2},
                              zero_dp_states=True)),
        # test_sharded_checkpoint_roundtrip_fsdp
        ("fsdp_dp8", dict(axes={"dp": 8}, fsdp_params=True)),
    ]
    for name, cfg in configs:
        loss, prog = momentum_mlp()
        pe = ParallelExecutor(**cfg)
        provenance = {}
        plan = pe.static_plan(prog, provenance=provenance)
        rep = verify_program(prog, feed_names=["x", "y"],
                             fetch_names=[loss.name], plan=plan,
                             plan_provenance=provenance,
                             check_shapes=False)
        hits = [f for f in rep.findings if f.rule == "PTV016"]
        assert hits, f"{name}: no PTV016 finding\n{rep.render()}"
        flagged = {f.var for f in hits}
        # the donated-and-sharded state is exactly the crash surface:
        # params under fsdp, velocity accumulators under zero
        assert any("velocity" in v or "fc_" in v for v in flagged), \
            (name, flagged)
        # ISSUE 9: each finding pinpoints WHICH axis rule sharded the
        # donated state (the ZeRO/FSDP reshard, via static_plan
        # provenance routed through the new sharding rule engine)
        assert all("sharded by rule" in f.message for f in hits), \
            [f.message for f in hits]
        expect = ("FSDP/ZeRO-3 parameter shard" if cfg.get("fsdp_params")
                  else "ZeRO-1 accumulator reshard")
        assert any(expect in f.message for f in hits), \
            (name, expect, [f.message for f in hits])

        # ISSUE 10: the crash triage also cites the DIVERGING COLLECTIVE
        # FOOTPRINT — the same ZeRO/FSDP reshard that makes the donated
        # state sharded (the PTV016 provenance above) is exactly where
        # the bespoke plan departs from the logical-axis declaration: a
        # plan-equivalence comparison of the two shows the extra
        # all-gather traffic the reshard implies (gather-back of
        # optimizer state / parameter gathers), quantified in bytes.
        from paddle_tpu.analysis.sharding import propagate
        from paddle_tpu.mesh import spec_of
        from paddle_tpu.parallel.partitioner import LogicalPartitioner

        lp = LogicalPartitioner()
        lplan = lp.plan(prog, pe.mesh)
        diverging = [v for v in plan
                     if spec_of(plan[v]) != spec_of(lplan.get(v))
                     and any(e for e in spec_of(plan[v]))]
        assert any(v in flagged for v in diverging), (name, diverging)
        pk_b = propagate(prog, mesh=pe.mesh, plan=plan,
                         batch_size=8).per_kind()
        pk_l = propagate(prog, mesh=pe.mesh, plan=lplan,
                         batch_size=8).per_kind()
        gather_b = pk_b.get("all-gather", {"bytes": 0})["bytes"]
        gather_l = pk_l.get("all-gather", {"bytes": 0})["bytes"]
        assert gather_b > gather_l, \
            (name, "expected the ZeRO/FSDP reshard to imply extra "
             "all-gather traffic vs the logical declaration", pk_b, pk_l)


# ---------------------------------------------------------------------------
# translation validation: the PTV022/023/024 mutation spine (ISSUE 10).
# Each seeded rewrite class is caught with its expected stable rule ID;
# the deep engine tests live in tests/test_equivalence.py.


def test_equivalence_dropped_op_ptv022():
    """Seeded rewrite: a pass silently drops an op — refuted with
    PTV022 (the fetch's producer is gone; the differential oracle sees
    scope garbage where the loss was)."""
    from paddle_tpu.analysis import prove_equivalent
    from paddle_tpu.framework.core import Program

    cost, prog = _train_mlp()
    mut = Program.from_json(prog.to_json())
    blk = mut.global_block()
    blk.ops.pop(next(i for i, op in enumerate(blk.ops)
                     if op.type == "mean"))
    proof = prove_equivalent(prog, mut, feed_names=["x", "y"],
                             fetch_names=[cost.name])
    assert not proof.equivalent
    assert any(f.rule == "PTV022" for f in proof.findings), proof.render()
    assert proof.diff and proof.diff.only_in_a  # names the dropped op


def test_equivalence_reordered_noncommutative_ptv024():
    """Seeded rewrite: swapping a NON-commutative op's operands — the
    canonical forms differ and the differential oracle produces the
    counterexample (PTV024 with max-error in the message), while the
    same swap on a commutative add canonicalizes away."""
    from paddle_tpu.analysis import prove_equivalent
    from paddle_tpu.framework.core import Program

    cost, prog = _train_mlp()
    mut = Program.from_json(prog.to_json())
    sub = next(op for op in mut.global_block().ops
               if op.type == "elementwise_sub")
    sub.inputs["X"], sub.inputs["Y"] = sub.inputs["Y"], sub.inputs["X"]
    proof = prove_equivalent(prog, mut, feed_names=["x", "y"],
                             fetch_names=[cost.name])
    # |pred - y| == |y - pred| keeps the LOSS equal; the param UPDATES
    # flip sign — the written-state comparison is what catches it
    assert not proof.equivalent
    hits = [f for f in proof.findings if f.rule == "PTV024"]
    assert hits, proof.render()
    assert any("max|a-b|" in f.message for f in hits)


def test_equivalence_perturbed_weight_ptv024():
    """Seeded rewrite: descs untouched, a weight VALUE perturbed (the
    corrupt-fold bug class) — only the differential tier can see it;
    execute="always" arms it on a structural match."""
    from paddle_tpu.analysis import prove_equivalent
    from paddle_tpu.framework.scope import Scope

    cost, prog = _train_mlp()
    sa, sb = Scope(), Scope()
    w = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    sa.set("fc_0.w_0", w)
    w2 = np.array(w)
    w2[0, 0] += 0.5
    sb.set("fc_0.w_0", w2)
    proof = prove_equivalent(prog, prog, feed_names=["x", "y"],
                             fetch_names=[cost.name], scope_before=sa,
                             scope_after=sb, execute="always")
    assert not proof.equivalent and proof.tier == "differential"
    assert any(f.rule == "PTV024" for f in proof.findings), proof.render()
    # same scopes -> validated
    proof2 = prove_equivalent(prog, prog, feed_names=["x", "y"],
                              fetch_names=[cost.name], scope_before=sa,
                              scope_after=sa, execute="always")
    assert proof2.equivalent


def test_equivalence_duplicated_subgraph_ptv023():
    """Seeded rewrite: duplicating a subgraph (same op, same operand
    value numbers, fresh output name) — PTV023 info from
    verify_program's duplicate-canonical-subgraph detector, and from
    the rewrite proof; renaming-only clones are still caught because
    detection runs on VALUE NUMBERS, not names."""
    from paddle_tpu.framework.core import Program

    cost, prog = _train_mlp()
    blk = prog.global_block()
    mul_i, mul = next((i, op) for i, op in enumerate(blk.ops)
                      if op.type == "mul")
    blk.create_var(name="dup_out", shape=(-1, 8), dtype="float32")
    blk.append_op("mul",
                  inputs={k: list(v) for k, v in mul.inputs.items()},
                  outputs={"Out": ["dup_out"]}, attrs=dict(mul.attrs))
    # the duplicate feeds something live so dead-op elim keeps it
    blk.append_op("save", inputs={"X": ["dup_out"]}, outputs={},
                  attrs={"file_path": "/tmp/never_written",
                         "overwrite": True})
    # place the clone BESIDE the original: after the optimizer updates
    # fc_0.w_0 it would read a different VALUE NUMBER and be a
    # genuinely different computation (correctly not flagged)
    save_op = blk.ops.pop()
    dup_op = blk.ops.pop()
    blk.ops.insert(mul_i + 1, save_op)
    blk.ops.insert(mul_i + 1, dup_op)
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name], check_shapes=False)
    hits = [f for f in rep.findings if f.rule == "PTV023"]
    assert hits and "missed CSE" in hits[0].message, rep.render()
    assert hits[0].severity == "info"  # advice, not a failure

    # and the proof engine reports it as a rewrite regression
    from paddle_tpu.analysis import prove_equivalent

    clean = Program.from_json(prog.to_json())
    b2 = clean.global_block()
    b2.ops.pop(mul_i + 1)
    b2.ops.pop(mul_i + 1)
    proof = prove_equivalent(clean, prog, feed_names=["x", "y"],
                             fetch_names=[cost.name])
    assert any(f.rule == "PTV023" for f in proof.findings), proof.render()


def test_memory_optimize_quantified_reduction():
    """The upgraded contract PROVES a peak reduction: a budget-forced
    marking must come back with peak_after < peak_before in the report
    dict (not just 'no live range extended')."""
    cost, prog = _train_mlp()
    report = {}
    n = contracts.checked_memory_optimize(prog, batch_size=512,
                                          hbm_bytes=4096, report=report)
    assert n > 0 and report["marked"] == n
    assert report["reduction_bytes"] > 0
    assert report["peak_after"] < report["peak_before"]


def test_memory_optimize_peak_not_reduced_ptv017():
    """Mutation: a pass that CLAIMS markings but moved no bytes (peak
    unchanged) must be PTV017 — remat FLOPs paid for no memory win."""
    cost, prog = _train_mlp()
    before = contracts.planner_peak_bytes(prog, batch_size=64)
    after, findings = contracts.quantified_peak_reduction(
        before, prog, batch_size=64, marked=3)
    assert after == before
    assert findings and all(f.rule == "PTV017" for f in findings)
    # the honest case: marked=0 (pass did nothing) is not a violation
    _, clean = contracts.quantified_peak_reduction(
        before, prog, batch_size=64, marked=0)
    assert not clean


# ---------------------------------------------------------------------------
# transpiler contracts


def test_distribute_transpile_contract_clean_and_dropped_send():
    """Acceptance mutation: delete the op producing a fetched gradient
    from the distribute-transpiled trainer program (the reference's lost
    send op) — PTV004, the pserver round would never see that grad."""
    cost, prog = _train_mlp()
    t = fluid.DistributeTranspiler()
    contracts.checked_distribute_transpile(
        t, trainer_id=0, pservers="127.0.0.1:0", trainers=1)
    # clean transpiled program: still verifies with zero findings
    grads = sorted(t.param_grad.values())
    rep = verify_program(t.program, feed_names=["x", "y"],
                         fetch_names=grads, check_shapes=False)
    assert not rep.findings, rep.render()

    gname = grads[0]
    block = t.program.global_block()
    block.ops[:] = [op for op in block.ops
                    if gname not in op.output_names()]
    with pytest.raises(VerificationError) as ei:
        contracts.verify_distribute_result(t)
    assert any(f.rule == "PTV004" for f in ei.value.findings)


def test_memory_optimize_contract_clean():
    cost, prog = _train_mlp()
    # tiny budget forces marking; the contract's liveness diff must stay
    # clean (remat only ever SHRINKS effective live ranges)
    n = contracts.checked_memory_optimize(prog, batch_size=512,
                                          hbm_bytes=4096)
    marked = [op for op in prog.global_block().ops
              if op.attrs.get("__remat__")]
    assert len(marked) == n


def test_memory_optimize_contract_catches_extended_range_ptv012():
    """Acceptance mutation: a buffer-'reuse' reorder that extends a live
    range — simulated by a corrupted pass moving an early op's last use
    to the end of the block — must be PTV012."""
    cost, prog = _train_mlp()
    block = prog.global_block()

    def corrupted_pass():
        early = next(op for op in block.ops if op.type == "mul")
        block.ops.remove(early)
        block.ops.insert(len(block.ops) - 1, early)

    before = contracts.liveness_snapshot(prog, batch_size=64)
    corrupted_pass()
    bad = contracts.liveness_diff(before, prog, batch_size=64)
    assert bad and all(f.rule == "PTV012" for f in bad)


def test_fuse_batch_norm_contract_clean():
    img = fluid.layers.data(name="img", shape=[1, 8, 8])
    c = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                            bias_attr=False)
    b = fluid.layers.batch_norm(c, act="relu")
    pred = fluid.layers.fc(fluid.layers.reshape(b, [-1, 4 * 6 * 6]),
                           size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    inf = fluid.default_main_program().clone(for_test=True)
    n = contracts.checked_fuse_batch_norm(inf, fluid.global_scope(),
                                          fetch_names=[pred.name])
    assert n == 1
    rep = verify_program(inf, feed_names=["img"], fetch_names=[pred.name],
                         check_shapes=False)
    assert not rep.findings, rep.render()


def test_sharding_plan_contract_clean():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device test mesh")
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.partitioner import (
        DistributeTranspiler as ShardingTranspiler)

    x = fluid.layers.data(name="x", shape=[32])
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=256, act="relu")
    logits = fluid.layers.fc(input=h, size=10)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    mesh = make_mesh({"dp": 4, "mp": 2})
    plan = contracts.checked_sharding_plan(
        ShardingTranspiler(), fluid.default_main_program(), mesh)
    assert plan and all(isinstance(k, str) for k in plan)


# ---------------------------------------------------------------------------
# surfacing: Executor.run(verify=) and the lint CLI


def test_executor_run_verify_kwarg():
    cost, prog = _train_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program(), verify=True)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(4, 4).astype(np.float32),
            "y": rng.rand(4, 1).astype(np.float32)}
    (loss,) = exe.run(feed=feed, fetch_list=[cost], verify=True)
    assert np.isfinite(float(np.asarray(loss).ravel()[0]))
    prog.global_block().append_op("bogus_xyz", outputs={"Out": ["zz"]})
    with pytest.raises(VerificationError):
        exe.run(feed=feed, fetch_list=[cost], verify=True)


def test_executor_env_gate(monkeypatch):
    cost, prog = _train_mlp()
    prog.global_block().append_op("bogus_xyz", outputs={"Out": ["zz"]})
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(4, 4).astype(np.float32),
            "y": rng.rand(4, 1).astype(np.float32)}
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "1")
    with pytest.raises(VerificationError):
        exe.run(feed=feed, fetch_list=[cost])


def test_lint_cli_on_saved_model(tmp_path):
    from paddle_tpu import cli

    img = fluid.layers.data(name="x", shape=[13])
    pred = fluid.layers.fc(input=img, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "fit_a_line_model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    assert cli.main(["lint", d]) == 0
    assert cli.main(["lint", os.path.join(d, "program.json")]) == 0

    # corrupt the saved program: drop the op producing the fetch target
    with open(os.path.join(d, "program.json")) as f:
        desc = json.load(f)
    desc["blocks"][0]["ops"] = [
        op for op in desc["blocks"][0]["ops"]
        if pred.name not in [n for ns in op["outputs"].values() for n in ns]]
    with open(os.path.join(d, "program.json"), "w") as f:
        json.dump(desc, f)
    model = os.path.join(d, "__model__")
    if os.path.exists(model):
        os.remove(model)  # force the JSON load path for the corrupt copy
    assert cli.main(["lint", d]) == 1

    # a truncated/empty __model__ must be rejected, not blessed as
    # "0 findings" (an empty desc parses cleanly from corrupt bytes).
    # Without the protoc toolchain the proto load path raises OSError
    # before the guard; with it, the guard's ValueError("truncated").
    with open(model, "wb"):
        pass
    with pytest.raises((ValueError, OSError)):
        cli.main(["lint", d])


def test_lint_cli_suppress_and_strict(tmp_path, capsys):
    from paddle_tpu import cli

    cost, prog = _train_mlp()
    block = prog.global_block()
    tmp = next(op for op in block.ops if op.type == "mul").output_names()[0]
    block.append_op("fill_constant", outputs={"Out": [tmp]},
                    attrs={"shape": [1], "value": 0.0, "dtype": "float32"})
    p = str(tmp_path / "prog.json")
    with open(p, "w") as f:
        f.write(prog.to_json())
    assert cli.main(["lint", p, "--no-shapes"]) == 0  # warnings only
    assert cli.main(["lint", p, "--no-shapes", "--strict"]) == 1
    assert cli.main(["lint", p, "--no-shapes", "--strict",
                     "--suppress", "PTV007,PTV008"]) == 0
    out = capsys.readouterr().out
    assert "PTV007" in out and "OK" in out


# ---------------------------------------------------------------------------
# repo hygiene lint


def _repo_lint_module():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "repo_lint.py")
    spec = importlib.util.spec_from_file_location("repo_lint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_lint_clean_on_this_repo():
    rl = _repo_lint_module()

    assert rl.lint(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))) == []


def test_repo_lint_catches_orphans(tmp_path):
    rl = _repo_lint_module()

    pkg = tmp_path / "pkg"
    (pkg / "sub" / "__pycache__").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "sub" / "mod.py").write_text("")
    (pkg / "sub" / "__pycache__" / "gone.cpython-310.pyc").write_text("")
    findings = rl.lint(str(tmp_path))
    assert any("orphaned bytecode" in f for f in findings)
    assert any("missing __init__.py" in f for f in findings)
    # dead package dir: only bytecode, no sources at all
    dead = tmp_path / "pkg" / "dead" / "__pycache__"
    dead.mkdir(parents=True)
    (dead / "ghost.cpython-310.pyc").write_text("")
    (pkg / "sub" / "__init__.py").write_text("")
    findings = rl.lint(str(tmp_path))
    assert any("dead package dir" in f for f in findings)


def test_repo_lint_page_table_mutation_guard(tmp_path):
    """Writes through `.page_table[...]` anywhere under paddle_tpu/
    outside serving/kv_cache.py are findings (they desync the cached
    feed view and the refcount accounting); reads and the allocator
    module itself are exempt (ISSUE 11)."""
    rl = _repo_lint_module()

    serving = tmp_path / "paddle_tpu" / "serving"
    serving.mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "__init__.py").write_text("")
    (serving / "__init__.py").write_text("")
    # the allocator module may mutate; a read elsewhere is fine
    (serving / "kv_cache.py").write_text(
        "self.page_table[slot, :] = 0\n")
    (serving / "engine.py").write_text(
        "row = self.cache.page_table[r.slot]\n")
    assert rl.lint(str(tmp_path)) == []
    # raw writes (plain, augmented, nested-subscript index) outside
    # kv_cache.py are findings
    (serving / "engine.py").write_text(
        "self.cache.page_table[slot, 0] = page\n"
        "self.cache.page_table[slot] += 1\n"
        "self.cache.page_table[idx[0], blocks[j]] = page\n")
    findings = [f for f in rl.lint(str(tmp_path))
                if "page-table mutation" in f]
    assert len(findings) == 3 and "engine.py:1" in findings[0]
    # outside the paddle_tpu tree (e.g. tests poking fixtures): exempt
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "x.py").write_text(
        "cache.page_table[0, 0] = 3\n")
    assert not any("tools" in f for f in rl.lint(str(tmp_path))
                   if "page-table" in f)


def test_repo_lint_truncated_mint_guard(tmp_path):
    """`.truncated(` outside serving/speculative.py is a finding — the
    draft view shares the target's weights and KV pools, and only
    build_draft_lm owns that contract (ISSUE 18).  The speculative
    module itself and anything outside paddle_tpu//tools are exempt."""
    rl = _repo_lint_module()

    serving = tmp_path / "paddle_tpu" / "serving"
    serving.mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "__init__.py").write_text("")
    (serving / "__init__.py").write_text("")
    (serving / "speculative.py").write_text(
        "draft = lm.truncated(n_layers)\n")
    assert rl.lint(str(tmp_path)) == []
    (serving / "engine.py").write_text(
        "self.draft = self.lm.truncated(2)\n")
    findings = [f for f in rl.lint(str(tmp_path))
                if "draft-model mint" in f]
    assert len(findings) == 1 and "engine.py:1" in findings[0]
    # tests/ (any dir outside paddle_tpu + tools) stay exempt so
    # oracle tests can build truncated references directly
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_x.py").write_text(
        "ref = lm.truncated(1)\n")
    assert not any("tests" in f for f in rl.lint(str(tmp_path))
                   if "draft-model mint" in f)


def test_repo_lint_spec_knob_env_guard(tmp_path):
    """Raw reads of the speculation knobs outside paddle_tpu/knobs.py are
    findings; plain exports (os.environ[...] = ...) are the knob
    layer's input side and stay exempt (ISSUE 18)."""
    rl = _repo_lint_module()

    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        'k = int(os.environ.get("PADDLE_TPU_SPEC_K", "4"))\n'
        'os.environ["PADDLE_TPU_SPEC_DRAFT_LAYERS"] = "1"\n')
    findings = [f for f in rl.lint(str(tmp_path))
                if "tuning-knob env read" in f]
    assert len(findings) == 1 and "mod.py:1" in findings[0]


# ---------------------------------------------------------------------------
# static cost model (analysis/cost.py)


def test_cost_mul_flops_exact():
    """The matmul formula is exact: fit-a-line's fc is [64,13]x[13,1]."""
    from paddle_tpu.analysis import cost as acost

    cost, prog = _train_mlp()  # fc 4->8, fc 8->1 on [N,4] input
    block = prog.global_block()
    muls = [op for op in block.ops if op.type == "mul"]
    c = acost.op_cost(block, muls[0], batch_size=64)
    assert c["flops"] == 2 * 64 * 4 * 8
    assert c["modeled"]


def test_cost_conv_formula():
    from paddle_tpu.analysis import cost as acost

    fluid.reset()
    img = fluid.layers.data(name="img", shape=[3, 16, 16])
    fluid.layers.conv2d(img, num_filters=8, filter_size=3, padding=1)
    block = fluid.default_main_program().global_block()
    conv = next(op for op in block.ops if op.type == "conv2d")
    c = acost.op_cost(block, conv, batch_size=4)
    # 2 * out_elems * k_spatial * cin : out [4,8,16,16], k 3x3, cin 3
    assert c["flops"] == 2 * (4 * 8 * 16 * 16) * 9 * 3


def test_generic_grad_cost_2x_forward_and_remat_3x():
    from paddle_tpu.analysis import cost as acost

    cost, prog = _train_mlp()
    block = prog.global_block()
    fwd = next(op for op in block.ops if op.type == "mul"
               and op.input("Y") == ["fc_0.w_0"])
    grad = next(op for op in block.ops if op.type == "generic_grad"
                and op.attrs.get("__fwd_type__") == "mul"
                and op.input("Y") == ["fc_0.w_0"])
    f = acost.op_cost(block, fwd, batch_size=64)["flops"]
    assert f == 2 * 64 * 4 * 8
    g = acost.op_cost(block, grad, batch_size=64)["flops"]
    assert g == 2 * f
    grad.attrs["__remat__"] = True
    g3 = acost.op_cost(block, grad, batch_size=64)["flops"]
    assert g3 == 3 * f
    del grad.attrs["__remat__"]


def test_program_cost_report_consistency():
    from paddle_tpu.analysis import cost as acost

    cost, prog = _train_mlp()
    rep = acost.program_cost(prog, batch_size=64, chip="v5e")
    assert rep["total_flops"] == sum(e["flops"]
                                     for e in rep["by_type"].values())
    assert rep["hbm_bytes"] == sum(e["bytes"]
                                   for e in rep["by_type"].values())
    assert rep["total_flops"] > 0 and rep["hbm_bytes"] > 0
    assert rep["arithmetic_intensity"] == pytest.approx(
        rep["total_flops"] / rep["hbm_bytes"])
    assert rep["predicted_step_time_s"] == pytest.approx(
        max(rep["compute_time_s"], rep["memory_time_s"]))
    assert rep["predicted_bound"] in ("compute", "memory")
    assert 0 < rep["mfu_ceiling"] <= 1
    assert rep["unmodeled_ops"] == 0
    assert "roofline" in acost.render(rep)


def test_chip_spec_env_and_unknown(monkeypatch):
    from paddle_tpu.analysis import cost as acost

    monkeypatch.setenv("PADDLE_TPU_CHIP", "v4")
    assert acost.chip_spec()["chip"] == "v4"
    with pytest.raises(ValueError, match="unknown chip"):
        acost.chip_spec("warp-drive")


# ---------------------------------------------------------------------------
# static HBM-peak estimator (analysis/memory.py)


def test_peak_estimate_exact_parts():
    """Persistent and feed bytes are EXACT desc arithmetic; donation
    savings price the read-then-written persistables once."""
    from paddle_tpu.analysis import memory as amem

    cost, prog = _train_mlp()
    est = amem.peak_estimate(prog, batch_size=64, infer_shapes=False)
    block = prog.global_block()
    persistent = sum(amem.var_bytes(v, 64) for v in block.vars.values()
                     if v.persistable)
    feeds = sum(amem.var_bytes(v, 64) for v in block.vars.values()
                if v.is_data)
    assert est["persistent_bytes"] == persistent
    assert est["feed_bytes"] == feeds
    assert est["activation_peak_bytes"] > 0
    assert est["total_peak_bytes"] == (persistent + feeds
                                       + est["activation_peak_bytes"])
    # sgd updates both fc params in place: they are the donated set
    assert est["donated_bytes"] > 0
    no_donate = amem.peak_estimate(prog, batch_size=64,
                                   infer_shapes=False, donate=False)
    assert no_donate["total_peak_bytes"] == (
        est["total_peak_bytes"] + est["donated_bytes"])


def test_remat_marking_shrinks_planner_peak():
    """level=1 blanket remat must strictly shrink the planner-model
    projected peak of an activation-heavy program (the FLOPs-for-HBM
    trade, quantified in the currency the PTV017 contract referees);
    the validated estimator tracks the marking count either way."""
    from paddle_tpu.analysis import memory as amem

    fluid.reset()
    x = fluid.layers.data(name="x", shape=[256])
    y = fluid.layers.data(name="y", shape=[1])
    h = x
    for _ in range(4):
        h = fluid.layers.fc(input=h, size=256, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    prog = fluid.default_main_program()
    before = contracts.planner_peak_bytes(prog, batch_size=256)
    n = fluid.memory_optimize(prog, level=1, batch_size=256)
    assert n > 0
    after = contracts.planner_peak_bytes(prog, batch_size=256)
    assert after < before
    est = amem.peak_estimate(prog, batch_size=256, infer_shapes=False)
    assert est["remat_marked_ops"] == n


def test_peak_estimate_per_shard():
    """An FSDP plan divides the persistent share by the dp size for the
    divisible params — the per-replica-shard accounting of the
    weight-update-sharding paper."""
    _mesh8()
    from paddle_tpu.analysis import memory as amem
    from paddle_tpu.parallel import ParallelExecutor

    cost, prog = _train_mlp()
    full = amem.peak_estimate(prog, batch_size=64, infer_shapes=False)
    pe = ParallelExecutor(axes={"dp": 8}, fsdp_params=True)
    plan = pe.static_plan(prog)
    shard = amem.peak_estimate(prog, batch_size=64, plan=plan,
                               infer_shapes=False)
    assert shard["per_shard"]
    assert shard["persistent_bytes"] < full["persistent_bytes"]
    assert shard["feed_bytes"] == full["feed_bytes"] // 8
    assert shard["total_peak_bytes"] < full["total_peak_bytes"]

    # an mp-only plan with REPLICATED feeds must not shrink activations:
    # only feed entries drive the batch-led transient divisor
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel import make_mesh

    mesh = make_mesh({"mp": 8})
    mp_plan = {"fc_0.w_0": NamedSharding(mesh, P("mp", None)),
               "x": NamedSharding(mesh, P()),
               "y": NamedSharding(mesh, P())}
    mp = amem.peak_estimate(prog, batch_size=64, plan=mp_plan,
                            infer_shapes=False)
    assert mp["activation_peak_bytes"] == full["activation_peak_bytes"]

    # with the shape oracle ON, abstract-sized helper tmps must shard
    # like their declared siblings (batch-led heuristic on inferred
    # leading dims), not stay full-size per shard
    full_inf = amem.peak_estimate(prog, batch_size=64)
    shard_inf = amem.peak_estimate(prog, batch_size=64, plan=plan)
    assert shard_inf["activation_peak_bytes"] \
        <= full_inf["activation_peak_bytes"] // 4


def test_state_classes_matches_executor():
    """dataflow.state_classes IS the executor's donation classifier —
    one truth for what gets donated."""
    from paddle_tpu.framework.dataflow import state_classes

    cost, prog = _train_mlp()
    block = prog.global_block()
    exe = fluid.Executor(fluid.CPUPlace())
    assert exe._analyze(block, ["x", "y"]) == state_classes(
        block, ["x", "y"])
    _, rw, _ = state_classes(block, ["x", "y"])
    assert "fc_0.w_0" in rw and "fc_1.w_0" in rw  # sgd in-place updates


def test_executor_memory_stats():
    """memory_stats returns XLA's buffer-assignment numbers; arguments
    are exactly the scope state + feeds the step consumes."""
    import numpy as np

    cost, prog = _train_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(16, 4).astype(np.float32),
            "y": rng.rand(16, 1).astype(np.float32)}
    stats = exe.memory_stats(prog, feed=feed, fetch_list=[cost])
    for k in ("argument_bytes", "output_bytes", "temp_bytes",
              "alias_bytes", "peak_bytes"):
        assert k in stats
    assert stats["peak_bytes"] == (stats["argument_bytes"]
                                   + stats["temp_bytes"])
    # params (4*8 + 8 + 8*1 + 1 + shared lr = 50 floats) + feeds (16*5)
    assert stats["argument_bytes"] == 4 * (50 + 16 * 5)


_VALIDATION = None


def _validation_programs():
    global _VALIDATION
    if _VALIDATION is None:
        import importlib.util

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "hlo_analysis.py")
        spec = importlib.util.spec_from_file_location("hlo_analysis", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _VALIDATION = mod
    return _VALIDATION


@pytest.mark.parametrize("which", [
    "fit_a_line",
    pytest.param("recognize_digits", marks=pytest.mark.slow),
    pytest.param("small_lm", marks=pytest.mark.slow),
])
def test_static_peak_within_15pct_of_measured(which):
    """ISSUE 8 acceptance: the static HBM-peak estimate is within ±15%
    of the XLA buffer-assignment measurement
    (tools/hlo_analysis.measured_peak_bytes) on the three validation
    programs.  digits/LM variants are `slow` (they compile a real train
    step); tier-1 runs the fit-a-line anchor, run_tests.sh runs all."""
    mod = _validation_programs()
    entry = next(e for e in mod.validation_programs() if e[0] == which)
    name, build, feed_fn, bs = entry
    from paddle_tpu.analysis import memory as amem

    fluid.reset()
    fetch = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    prog = fluid.default_main_program()
    measured = mod.measured_peak_bytes(exe, prog, feed_fn(bs), [fetch])
    static = amem.peak_estimate(prog, batch_size=bs)
    ratio = static["total_peak_bytes"] / measured["peak_bytes"]
    assert 0.85 <= ratio <= 1.15, (
        f"{name}: static {static['total_peak_bytes']} vs measured "
        f"{measured['peak_bytes']} (ratio {ratio:.3f})")


# ---------------------------------------------------------------------------
# analyze CLI


def test_analyze_cli_on_saved_model(tmp_path, capsys):
    from paddle_tpu import cli

    img = fluid.layers.data(name="x", shape=[13])
    pred = fluid.layers.fc(input=img, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "fit_a_line_model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    assert cli.main(["analyze", d]) == 0
    out = capsys.readouterr().out
    assert "roofline" in out and "HBM peak" in out
    assert cli.main(["analyze", d, "--json", "--batch-size", "32",
                     "--chip", "v4"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["cost"]["chip"] == "v4"
    assert rec["cost"]["batch_size"] == 32
    assert rec["cost"]["total_flops"] > 0
    assert rec["memory"]["total_peak_bytes"] > 0


# ---------------------------------------------------------------------------
# repo_lint


def test_repo_lint_ptv_docs_drift_guard(tmp_path):
    """Every PTV rule registered in verifier.py needs a docs/analysis.md
    catalog row, and stale doc rows are flagged too; foreign trees
    without a verifier are exempt (the synthetic-repo tests above)."""
    rl = _repo_lint_module()
    # this repo is currently in sync
    assert not [f for f in rl.lint(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))) if "PTV" in f]

    v = tmp_path / "paddle_tpu" / "analysis"
    v.mkdir(parents=True)
    for d in (tmp_path / "paddle_tpu", v):
        (d / "__init__.py").write_text("")
    docs = tmp_path / "docs"
    docs.mkdir()
    (v / "verifier.py").write_text(
        'RULES = [Rule("PTV001", "a", ERROR, "x"),\n'
        '         Rule("PTV002", "b", ERROR, "y")]\n')
    (docs / "analysis.md").write_text(
        "| PTV001 | a | error | x |\n| PTV099 | ghost | info | z |\n")
    findings = rl.lint(str(tmp_path))
    assert any("undocumented verifier rule: PTV002" in f
               for f in findings), findings
    assert any("stale rule doc: PTV099" in f for f in findings), findings


def test_repo_lint_flags_partition_spec_in_parallel(tmp_path):
    """The rule-derived-specs guard: PartitionSpec named anywhere in
    paddle_tpu/parallel/ (construction OR import alias) is flagged;
    paddle_tpu/mesh.py beside it is the blessed mint."""
    rl = _repo_lint_module()

    pkg = tmp_path / "paddle_tpu" / "parallel"
    pkg.mkdir(parents=True)
    for d in (tmp_path / "paddle_tpu", pkg):
        (d / "__init__.py").write_text("")
    cls = "Partition" + "Spec"
    (tmp_path / "paddle_tpu" / "mesh.py").write_text(
        f"def pspec(*e):\n"
        f"    from jax.sharding import {cls}\n"
        f"    return {cls}(*e)\n")
    assert rl.lint(str(tmp_path)) == []
    (pkg / "rogue_mode.py").write_text(
        f"from jax.sharding import {cls} as P\n"
        f"spec = P('dp')\n")
    findings = rl.lint(str(tmp_path))
    assert any("PartitionSpec literal in parallel/" in f
               and "rogue_mode.py:1" in f for f in findings), findings
