"""Static sharding-propagation & communication analyzer (ISSUE 9):
logical-axis rules, the propagation engine, PTV018-PTV021 mutation
tests, collective-bytes exactness against analytic formulas, the
comm-aware roofline, and the static-vs-actual ground-truth validation
(the acceptance spine: predicted collective set == optimized_hlo's on
the dp/mp/fsdp small-LM programs, bytes within ±10%)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import sharding as ash
from paddle_tpu.analysis import verify_program
from paddle_tpu.parallel import ParallelExecutor, ShardingRules, make_mesh
from paddle_tpu.parallel import modes as pmodes
from paddle_tpu.parallel import partitioner
from paddle_tpu.parallel.partitioner import (AxisNames, LogicalPartitioner,
                                             logical_to_mesh_axes)


def _mesh8(axes=None):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device test mesh")
    return make_mesh(axes or {"dp": 8})


def _param_bytes(prog, trainable_only=True):
    block = prog.global_block()
    total = 0
    for v in block.vars.values():
        if v.persistable and (getattr(v, "trainable", False)
                              or not trainable_only):
            n = 1
            for s in v.shape:
                n *= int(s)
            total += n * 4
    return total


def _train_mlp(width=8):
    x = fluid.layers.data(name="x", shape=[4])
    y = fluid.layers.data(name="y", shape=[1])
    h = fluid.layers.fc(input=x, size=width, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    return cost, fluid.default_main_program()


# ---------------------------------------------------------------------------
# logical-axis rules (the t5x vocabulary)


def test_logical_to_mesh_axes_resolution_and_fallback():
    rules = [("batch", "dp"), ("vocab", "mp"), ("vocab", "dp"),
             ("embed", None)]
    sizes = {"dp": 4, "mp": 2}
    # plain resolution
    assert logical_to_mesh_axes(AxisNames("batch", "embed"), rules,
                                sizes, (8, 32)) == ("dp", None)
    # indivisible dim falls through to the fallback rule
    assert logical_to_mesh_axes(AxisNames("vocab", "embed"), rules,
                                {"dp": 2, "mp": 4},
                                (6, 32))[0] == "dp"  # 6 % 4 != 0
    # absent mesh axis -> fallback; no fallback -> unsharded
    assert logical_to_mesh_axes(AxisNames("vocab",), rules,
                                {"dp": 1, "mp": 1}, (8,)) == (None,)
    # explicit (logical, None) pins replicated
    assert logical_to_mesh_axes(AxisNames("embed",), rules, sizes,
                                (32,)) == (None,)


def test_logical_axis_conflict_recorded():
    """Two dims of one var resolving to the SAME mesh axis is a
    conflict, not a silent double-shard (the PTV018 seed)."""
    rules = [("batch", "dp"), ("length", "dp")]
    conflicts = []
    spec = logical_to_mesh_axes(AxisNames("batch", "length"), rules,
                                {"dp": 4}, (8, 8), conflicts=conflicts)
    assert spec == ("dp", None)
    assert conflicts and conflicts[0][1] == "dp"


def test_logical_partitioner_plans_like_transpiler():
    """The rule engine reproduces the transpiler's decisions on the LM
    program from NAMED axes: vocab-sharded embedding, batch-led feeds —
    the ROADMAP #2 collapse target."""
    mesh = _mesh8({"dp": 4, "mp": 2})
    from paddle_tpu.models.transformer import build_lm_train_program

    build_lm_train_program(seq_len=16, vocab_size=64, dim=32,
                           n_layers=1, n_heads=2, dtype="float32")
    prog = fluid.default_main_program()
    part = LogicalPartitioner()
    plan = part.plan(prog, mesh)
    assert not part.conflicts
    assert tuple(plan["tokens"].spec) == ("dp", None, None)
    emb = tuple(plan["embedding_0.w_0"].spec)
    assert emb[0] == "mp"  # vocab axis
    # explicit constraint wins but a contradiction is recorded
    part2 = LogicalPartitioner(
        constraints={"embedding_0.w_0": (None, None)})
    plan2 = part2.plan(prog, mesh)
    assert tuple(plan2["embedding_0.w_0"].spec) == (None, None)
    assert any(c["var"] == "embedding_0.w_0" for c in part2.conflicts)


# ---------------------------------------------------------------------------
# PTV018-PTV021 mutation tests


def test_sharding_conflict_flagged_ptv018():
    """Mutation: a plan claiming one mesh axis on two dims of a var —
    no device assignment satisfies it."""
    mesh = _mesh8({"dp": 4, "mp": 2})
    cost, prog = _train_mlp()
    from paddle_tpu.mesh import named

    kw = dict(feed_names=["x", "y"], fetch_names=[cost.name],
              check_shapes=False)
    clean = {"fc_0.w_0": named(mesh, "dp", None)}
    rep = verify_program(prog, plan=clean, **kw)
    assert not any(f.rule == "PTV018" for f in rep.findings), rep.render()
    # jax's NamedSharding rejects duplicate axes at construction, so the
    # defect arrives as a raw spec tuple (a documented plan input)
    bad = {"fc_0.w_0": ("dp", "dp")}
    rep = verify_program(prog, plan=bad, **kw)
    hits = [f for f in rep.findings if f.rule == "PTV018"]
    assert hits and hits[0].var == "fc_0.w_0", rep.render()
    assert hits[0].severity == "error"


def test_hot_loop_reshard_flagged_ptv019():
    """Mutation: two TRANSIENT operands arriving at one elementwise op
    with incompatible specs — the implicit gather is re-paid every
    step.  Feeds resharding once at distribution time stay exempt."""
    mesh = _mesh8({"dp": 4, "mp": 2})
    from paddle_tpu.mesh import named

    a = fluid.layers.data(name="a", shape=[16])
    b = fluid.layers.data(name="b", shape=[16])
    s = fluid.layers.elementwise_add(fluid.layers.relu(a),
                                     fluid.layers.relu(b))
    loss = fluid.layers.mean(s)
    prog = fluid.default_main_program()
    plan = {"a": named(mesh, "dp", None), "b": named(mesh, "mp", None)}
    rep = verify_program(prog, feed_names=["a", "b"],
                         fetch_names=[loss.name], plan=plan,
                         check_shapes=False)
    hits = [f for f in rep.findings if f.rule == "PTV019"]
    assert hits, rep.render()
    # the flagged operand is one of the transient relu outputs
    assert all("tmp" in (f.var or "") for f in hits), rep.render()


def test_replicated_large_tensor_flagged_ptv020():
    """A >=1 MiB param left fully replicated while a mesh axis divides
    its shape is sizing advice (info tier)."""
    _mesh8()
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[512])
    y = fluid.layers.data(name="y", shape=[1])
    h = fluid.layers.fc(input=x, size=1024)  # [512,1024] = 2 MiB
    pred = fluid.layers.fc(input=h, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    prog = fluid.default_main_program()
    pe = ParallelExecutor(axes={"dp": 8})
    plan = pe.static_plan(prog)
    rep = verify_program(prog, feed_names=["x", "y"],
                         fetch_names=[cost.name], plan=plan,
                         check_shapes=False)
    hits = [f for f in rep.findings if f.rule == "PTV020"]
    assert hits and hits[0].var == "fc_0.w_0", rep.render()
    assert hits[0].severity == "info"


def test_dcn_crossing_collective_flagged_ptv021():
    """Mutation: the SAME dp program on a mesh whose replica axis is
    DCN-named — every per-step grad all-reduce now crosses DCN and must
    be flagged; the ICI-named mesh stays silent."""
    _mesh8()
    cost, prog = _train_mlp()
    kw = dict(feed_names=["x", "y"], fetch_names=[cost.name],
              check_shapes=False)
    pe = ParallelExecutor(axes={"dp": 8})
    rep = verify_program(prog, plan=pe.static_plan(prog), **kw)
    assert not any(f.rule == "PTV021" for f in rep.findings), rep.render()

    pe_dcn = ParallelExecutor(axes={"dcn_dp": 8},
                              rules=ShardingRules(dp_axis="dcn_dp"))
    rep = verify_program(prog, plan=pe_dcn.static_plan(prog), **kw)
    hits = [f for f in rep.findings if f.rule == "PTV021"]
    assert hits, rep.render()
    assert any("dcn_dp" in f.message for f in hits)


def test_ptv016_findings_name_the_axis_rule():
    """ISSUE 9 extension of the known-crash coverage: with
    static_plan(provenance=...), each PTV016 finding pinpoints WHICH
    axis rule made the donated state sharded (ZeRO-1 accumulator
    reshard vs FSDP parameter shard)."""
    _mesh8()

    def momentum_mlp():
        fluid.reset()
        x = fluid.layers.data(name="x", shape=[32])
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=64, act="relu")
        logits = fluid.layers.fc(input=h, size=10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
        return loss, fluid.default_main_program()

    for cfg, expect in [
            (dict(axes={"dp": 8}, zero_dp_states=True),
             "ZeRO-1 accumulator reshard over 'dp'"),
            (dict(axes={"dp": 8}, fsdp_params=True),
             "FSDP/ZeRO-3 parameter shard over 'dp'")]:
        loss, prog = momentum_mlp()
        pe = ParallelExecutor(**cfg)
        provenance = {}
        plan = pe.static_plan(prog, provenance=provenance)
        rep = verify_program(prog, feed_names=["x", "y"],
                             fetch_names=[loss.name], plan=plan,
                             plan_provenance=provenance,
                             check_shapes=False)
        hits = [f for f in rep.findings if f.rule == "PTV016"]
        assert hits, rep.render()
        assert any(expect in f.message for f in hits), \
            (expect, [f.message for f in hits])


# ---------------------------------------------------------------------------
# collective-bytes exactness against analytic formulas


def test_dp_grad_allreduce_bytes_exact():
    """dp: one all-reduce per trainable-param grad at full param bytes
    plus the 4-byte batch-mean loss scalar — the analytic formula the
    ground-truth run confirmed byte-for-byte."""
    _mesh8()
    cost, prog = _train_mlp()
    pe = ParallelExecutor(axes={"dp": 8})
    ana = ash.propagate(prog, plan=pe.static_plan(prog), batch_size=64)
    per = ana.per_kind()
    assert set(per) == {"all-reduce"}
    assert per["all-reduce"]["bytes"] == _param_bytes(prog) + 4


def test_mp_vocab_lookup_allreduce_bytes_exact():
    """mp: the vocab-sharded lookup leaves partial rows — all-reduce of
    the per-device output, B/dp * D * 4 bytes."""
    _mesh8()
    fluid.reset()
    ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(ids, size=[128, 32])
    loss = fluid.layers.mean(emb)
    prog = fluid.default_main_program()
    pe = ParallelExecutor(axes={"dp": 4, "mp": 2})
    ana = ash.propagate(prog, plan=pe.static_plan(prog), batch_size=8)
    lookups = [c for c in ana.collectives
               if c.kind == "all-reduce" and c.axes == ("mp",)]
    assert len(lookups) == 1
    assert lookups[0].bytes == (8 // 4) * 32 * 4  # [B/dp, D] f32


def test_fsdp_gather_and_allreduce_bytes_exact():
    """fsdp: every dp-sharded param is all-gathered once for compute
    (full bytes) and its grad all-reduced FULL (GSPMD's all-reduce +
    slice lowering, not reduce-scatter — the calibrated decision)."""
    _mesh8()
    cost, prog = _train_mlp(width=8)  # all dims divisible by 8
    pe = ParallelExecutor(axes={"dp": 8}, fsdp_params=True)
    plan = pe.static_plan(prog)
    ana = ash.propagate(prog, plan=plan, batch_size=64)
    per = ana.per_kind()
    assert set(per) == {"all-gather", "all-reduce"}
    from paddle_tpu.mesh import spec_axes

    sharded = 0
    block = prog.global_block()
    for name, sh in plan.items():
        v = block._find_var_recursive(name)
        if v is None or not v.persistable or not spec_axes(sh.spec):
            continue
        n = 1
        for s in v.shape:
            n *= int(s)
        sharded += n * 4
    assert sharded > 0
    assert per["all-gather"]["bytes"] == sharded
    assert per["all-reduce"]["bytes"] == _param_bytes(prog) + 4


def test_pp_point_to_point_bytes_exact():
    """pp: each pipeline_stage marker prices its live cut set crossing
    the boundary, once forward (activations) and once backward
    (cotangents): 2 x cut bytes per boundary."""
    _mesh8()
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=32, act="tanh")
    fluid.layers.pipeline_stage()
    logits = fluid.layers.fc(input=h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    prog = fluid.default_main_program()
    mesh = make_mesh({"pp": 4})
    bs = 16
    ana = ash.propagate(prog, mesh=mesh, plan={}, batch_size=bs)
    p2p = [c for c in ana.collectives if c.kind == "collective-permute"]
    assert len(p2p) == 2  # fwd activations + bwd cotangents
    cut = bs * 32 * 4  # h [B, 32] f32 is the only live value
    assert all(c.bytes == cut for c in p2p)


# ---------------------------------------------------------------------------
# comm pricing: wire factors, DCN vs ICI, roofline, scaling curve


def test_comm_report_wire_factors_and_dcn_pricing():
    n8 = ash.wire_factor("all-reduce", 8)
    assert n8 == pytest.approx(2 * 7 / 8)
    assert ash.wire_factor("all-gather", 8) == pytest.approx(7 / 8)
    assert ash.wire_factor("reduce-scatter", 8) == 7
    assert ash.wire_factor("collective-permute", 8) == 1.0
    assert ash.wire_factor("all-reduce", 1) == 0.0

    ana = ash.ShardingAnalysis(axis_sizes={"dp": 8, "dcn_dp": 2})
    ana.collectives.append(ash.Collective("all-reduce", ("dp",), 1 << 20))
    ici = ash.comm_report(ana, chip="v5e")
    ana2 = ash.ShardingAnalysis(axis_sizes={"dp": 8, "dcn_dp": 2})
    ana2.collectives.append(
        ash.Collective("all-reduce", ("dcn_dp",), 1 << 20))
    dcn = ash.comm_report(ana2, chip="v5e")
    assert dcn["dcn_time_s"] > 0 and ici["dcn_time_s"] == 0
    # same bytes, ~10x slower over DCN (modulo the n-dependent factor)
    assert dcn["comm_time_s"] > ici["comm_time_s"]
    assert dcn["dcn_axes"] == ["dcn_dp"]


def test_roofline_with_comm_bound_switch():
    from paddle_tpu.analysis import cost as acost

    cost, prog = _train_mlp()
    rep = acost.program_cost(prog, batch_size=64, chip="v5e")
    merged = acost.roofline_with_comm(
        rep, {"comm_time_s": rep["predicted_step_time_s"] * 100,
              "collective_bytes": 123, "per_kind": {}})
    assert merged["predicted_bound"] == "comm"
    assert merged["predicted_step_time_s"] == pytest.approx(
        rep["predicted_step_time_s"] * 100)
    assert merged["mfu_ceiling"] < rep["mfu_ceiling"]
    # the original report is untouched
    assert rep["predicted_bound"] in ("compute", "memory")


def test_scaling_curve_shape():
    """Strong scaling over dp: efficiency starts at 1 and is
    non-increasing once comm (constant-byte grad all-reduce) meets the
    shrinking per-device compute."""
    _mesh8()
    from paddle_tpu.analysis import cost as acost

    cost, prog = _train_mlp(width=256)
    pe = ParallelExecutor(axes={"dp": 8})
    ana = ash.propagate(prog, plan=pe.static_plan(prog), batch_size=256)
    rep = acost.program_cost(prog, batch_size=256, chip="v5e")
    curve = ash.scaling_curve(ana, rep, axis="dp",
                              sizes=(1, 2, 4, 8, 64, 512))
    assert [p["n"] for p in curve] == [1, 2, 4, 8, 64, 512]
    assert curve[0]["efficiency"] == pytest.approx(1.0)
    assert all(0 < p["efficiency"] <= 1.0 for p in curve)
    assert curve[-1]["efficiency"] <= curve[0]["efficiency"]
    assert curve[0]["comm_time_s"] == 0.0  # n=1: no communication


# ---------------------------------------------------------------------------
# the 11-mode catalog analyzes clean (the CI gate's contract)


def test_all_dryrun_modes_analyze_clean():
    _mesh8()
    for name in pmodes.MODE_NAMES:
        mode, prog, loss_name = pmodes.build_mode(name)
        mesh, plan, provenance = pmodes.mode_plan(mode, prog)
        findings, ana = ash.sharding_findings(
            prog, plan, batch_size=8, provenance=provenance, mesh=mesh)
        gate = [f for f in findings if f.rule in ("PTV018", "PTV019")]
        assert not gate, (name, [f.format() for f in gate])
        assert ana.axis_sizes == dict(mode.mesh_axes)
        if not mode.pipeline and name != "host_emb":
            assert ana.collectives, f"{name}: no collectives classified"


def test_mode_catalog_is_the_eleven_dryrun_modes():
    assert len(pmodes.MODES) == 11
    assert pmodes.MODE_NAMES == (
        "dp", "dp_mp", "fsdp", "sp_ring", "sp_ulysses", "pp", "ep_dp",
        "lm_dp_sp", "pp_dp", "emb_mp", "host_emb")
    with pytest.raises(KeyError):
        pmodes.get_mode("warp")


# ---------------------------------------------------------------------------
# ISSUE 19: rule-family mutation tests — rule present -> PROVEN against
# the archived bespoke plans, rule removed -> the exact PR 10 diff
# reappears.  The mutation swaps `standard_logical_axis_rules` for a
# filtered table where the partitioner reads it, so the divergence shows
# up against the snapshot — exactly how a silently dropped rule would
# present.


def _mutate_rules(monkeypatch, mutate):
    real = partitioner.standard_logical_axis_rules

    def wrapped(*a, **kw):
        return mutate(list(real(*a, **kw)))

    monkeypatch.setattr(partitioner, "standard_logical_axis_rules", wrapped)


def _equiv(name):
    from _mode_plans import mode_plan_against_snapshot

    return mode_plan_against_snapshot(name)


@pytest.mark.parametrize("name", ["dp_mp", "fsdp", "sp_ring", "emb_mp",
                                  "pp_dp"])
def test_rule_family_modes_proven_against_golden(name):
    """Rule present: the modes the 4 new rule families unlocked are
    PROVEN equal to the deleted wiring's archived plans (the other
    modes ride the full 11/11 run_tests.sh gate)."""
    _mesh8()
    rec = _equiv(name)
    assert rec["golden"], "tests/fixtures/mode_plans_golden.json missing"
    assert rec["verdict"] == "PROVEN", rec


def test_zero_state_rule_removed_reopens_pr10_diff(monkeypatch):
    """Family 1 (ZeRO-1 dim-0 optimizer-state reshard): drop the
    state0/param0 dp rows and dp_mp diverges from the archive exactly
    where PR 10 said — accumulators replicated instead of dim-0
    sharded, and the weight-update-sharding all-gathers gone."""
    _mesh8()
    _mutate_rules(monkeypatch, lambda rules: [
        r for r in rules
        if not (r[0] in ("state0", "param0") and r[1] is not None)])
    rec = _equiv("dp_mp")
    assert rec["verdict"] == "DIVERGED"
    vel = [d for d in rec["spec_diffs"] if "velocity" in d["var"]]
    assert vel, rec["spec_diffs"]
    for d in vel:
        assert d["bespoke"][0] == "dp" and d["logical"] == []
    assert rec["comm"]["delta"]


def test_fsdp_param_rule_removed_reopens_pr10_diff(monkeypatch):
    """Family 1, fsdp face: without the param0/state0 rows every
    trainable param falls back to replicated — the PR 10 fsdp diff
    (params+velocities ['dp'] vs [])."""
    _mesh8()
    _mutate_rules(monkeypatch, lambda rules: [
        r for r in rules
        if not (r[0] in ("state0", "param0") and r[1] is not None)])
    rec = _equiv("fsdp")
    assert rec["verdict"] == "DIVERGED"
    dropped = [d for d in rec["spec_diffs"]
               if d["bespoke"] and d["bespoke"][0] == "dp"
               and d["logical"] == []]
    assert dropped, rec["spec_diffs"]
    assert rec["comm"]["delta"]


def test_length_rule_removed_reopens_pr10_diff(monkeypatch):
    """Family 2 (op-internal sequence parallelism as a `length` feed
    rule): drop it and sp_ring's feeds lose the sp dim — the PR 10
    seq/tokens diff (['dp','sp'] vs ['dp'])."""
    _mesh8()
    _mutate_rules(monkeypatch,
                  lambda rules: [r for r in rules if r[0] != "length"])
    rec = _equiv("sp_ring")
    assert rec["verdict"] == "DIVERGED"
    assert rec["spec_diffs"]
    for d in rec["spec_diffs"]:
        assert d["bespoke"][:2] == ["dp", "sp"]
        assert d["logical"] == ["dp"]


def test_column_parallel_gate_removed_reopens_pr10_diff(monkeypatch):
    """Family 3 (the >=128 column-parallel width threshold): un-gate
    the mlp row and emb_mp's 8-wide fc shards where the bespoke wiring
    (and the archive) kept it replicated — the PR 10 fc_0.w_0 diff
    ([] vs [None,'mp'])."""
    _mesh8()
    _mutate_rules(monkeypatch, lambda rules: [
        (r[0], r[1]) if len(r) == 3 else r for r in rules])
    rec = _equiv("emb_mp")
    assert rec["verdict"] == "DIVERGED"
    d = next(d for d in rec["spec_diffs"] if d["var"] == "fc_0.w_0")
    assert d["bespoke"] == [] and d["logical"][-1] == "mp"
    assert rec["comm"]["delta"]


def test_microbatch_dp_rule_removed_reopens_pr10_diff(monkeypatch):
    """Family 4 (pipeline-driven microbatch dp): drop the batch row and
    pp_dp's microbatch feeds lose dp — the PR 10 x/y diff — and the
    stage-boundary permutes grow back to full-batch bytes."""
    _mesh8()
    _mutate_rules(monkeypatch,
                  lambda rules: [r for r in rules if r[0] != "batch"])
    rec = _equiv("pp_dp")
    assert rec["verdict"] == "DIVERGED"
    assert {d["var"] for d in rec["spec_diffs"]} >= {"x", "y"}
    for d in rec["spec_diffs"]:
        assert d["bespoke"] == ["dp"] and d["logical"] == []
    assert rec["comm"]["delta"]


# ---------------------------------------------------------------------------
# ISSUE 19: hybrid ICI x DCN collective-bytes exactness


def test_hybrid_allreduce_decomposition_bytes_exact():
    """One all-reduce over ("dcn_dp","dp") on a 2-slice 4x mesh prices
    as the hierarchical decomposition, byte-exact: ICI carries the flat
    all-reduce wire bytes (RS+AG legs), DCN carries 2(n_d-1)/n_d of the
    1/n_ici reduce-scattered shard."""
    b = 1 << 20
    ana = ash.ShardingAnalysis(axis_sizes={"dp": 4, "dcn_dp": 2})
    ana.collectives.append(
        ash.Collective("all-reduce", ("dcn_dp", "dp"), b))
    rep = ash.comm_report(ana, chip="v5e")
    w_ici = 2 * (4 - 1) / 4 * b
    w_dcn = 2 * (2 - 1) / 2 * (b // 4)
    assert rep["link_bytes"] == {"ici": int(w_ici), "dcn": int(w_dcn)}
    dec = rep["breakdown"][0]["decomposed"]
    assert dec["ici_reduce_scatter_bytes"] == (4 - 1) * (b // 4)
    assert dec["dcn_all_reduce_bytes"] == int(w_dcn)
    assert dec["ici_all_gather_bytes"] == int((4 - 1) / 4 * b)
    # the three stages' ICI legs sum to the flat-all-reduce wire bytes
    assert (dec["ici_reduce_scatter_bytes"]
            + dec["ici_all_gather_bytes"]) == int(w_ici)
    # pure single-class collectives don't decompose
    ana2 = ash.ShardingAnalysis(axis_sizes={"dp": 4, "dcn_dp": 2})
    ana2.collectives.append(ash.Collective("all-reduce", ("dp",), b))
    ana2.collectives.append(ash.Collective("all-reduce", ("dcn_dp",), b))
    rep2 = ash.comm_report(ana2, chip="v5e")
    assert all("decomposed" not in e for e in rep2["breakdown"])
    assert rep2["link_bytes"]["ici"] == int(2 * 3 / 4 * b)
    assert rep2["link_bytes"]["dcn"] == int(2 * 1 / 2 * b)


def test_hybrid_allgather_decomposition_bytes_exact():
    """One all-gather over ("dcn_dp","dp") on a 2-slice 4x mesh prices
    hierarchically (ISSUE 20): DCN all-gathers the 1/n_ici co-shard
    first ((n_d-1)/n_d of bytes/n_ici), then a per-slice ICI all-gather
    completes the buffer ((n_i-1)/n_i of the full bytes) — vs a flat
    pricing that would push (n-1)/n of the FULL buffer over DCN."""
    b = 1 << 20
    ana = ash.ShardingAnalysis(axis_sizes={"dp": 4, "dcn_dp": 2})
    ana.collectives.append(
        ash.Collective("all-gather", ("dcn_dp", "dp"), b))
    rep = ash.comm_report(ana, chip="v5e")
    w_dcn = (2 - 1) / 2 * (b // 4)
    w_ici = (4 - 1) / 4 * b
    assert rep["link_bytes"] == {"ici": int(w_ici), "dcn": int(w_dcn)}
    dec = rep["breakdown"][0]["decomposed"]
    assert dec["dcn_all_gather_bytes"] == int(w_dcn)
    assert dec["ici_all_gather_bytes"] == int(w_ici)
    # the decomposition is what the hybrid buys: flat pricing would put
    # (n-1)/n of the full buffer on the slow link
    assert w_dcn < (8 - 1) / 8 * b
    # single-class all-gathers still price flat, no decomposed entry
    ana2 = ash.ShardingAnalysis(axis_sizes={"dp": 4, "dcn_dp": 2})
    ana2.collectives.append(ash.Collective("all-gather", ("dp",), b))
    rep2 = ash.comm_report(ana2, chip="v5e")
    assert "decomposed" not in rep2["breakdown"][0]
    assert rep2["link_bytes"] == {"ici": int(3 / 4 * b), "dcn": 0}


def test_hybrid_mesh_step_link_bytes_per_collective():
    """The dp-MLP training step planned on the 2-slice mesh: every
    gradient all-reduce spans both link classes and its breakdown entry
    matches the decomposition formula row by row (ICI vs DCN bytes per
    step, the ISSUE 19 exactness contract)."""
    _mesh8()
    from paddle_tpu.mesh import make_hybrid_mesh

    mode, prog, _loss = pmodes.build_mode("dp")
    mesh = make_hybrid_mesh({"dp": 4}, {"dcn_dp": 2})
    pe = ParallelExecutor(mesh=mesh, zero_dp_states=True)
    ana = ash.propagate(prog, mesh=mesh, plan=pe.static_plan(prog),
                        batch_size=8)
    rep = ash.comm_report(ana)
    hybrid_ars = [e for e in rep["breakdown"]
                  if e["kind"] == "all-reduce"
                  and set(e["axes"]) == {"dcn_dp", "dp"}]
    assert hybrid_ars, rep["breakdown"]
    for e in hybrid_ars:
        b = e["bytes"]
        dec = e["decomposed"]
        assert dec["ici_reduce_scatter_bytes"] == 3 * (b // 4)
        assert dec["dcn_all_reduce_bytes"] == int(2 * (1 / 2) * (b // 4))
        assert dec["ici_all_gather_bytes"] == int(3 / 4 * b)
    assert rep["link_bytes"]["ici"] > 0
    assert rep["link_bytes"]["dcn"] > 0
    # DCN carries strictly less than ICI: only 1/n_ici shards cross it
    assert rep["link_bytes"]["dcn"] < rep["link_bytes"]["ici"]


def test_make_hybrid_mesh_shape_and_prefix_contract():
    _mesh8()
    from paddle_tpu.mesh import (dcn_axes, make_hybrid_mesh,
                                          mesh_axis_sizes)

    mesh = make_hybrid_mesh({"dp": 4}, {"dcn_dp": 2})
    assert mesh_axis_sizes(mesh) == {"dcn_dp": 2, "dp": 4}
    assert dcn_axes(mesh) == ("dcn_dp",)
    # outer dim walks slices: each row is one slice's contiguous chunk
    import jax

    devs = jax.devices()[:8]
    assert list(mesh.devices[0].ravel()) == devs[:4]
    assert list(mesh.devices[1].ravel()) == devs[4:]
    with pytest.raises(ValueError):
        make_hybrid_mesh({"dp": 4}, {"slices": 2})  # missing dcn prefix
    with pytest.raises(ValueError):
        make_hybrid_mesh({"dp": 8}, {"dcn_dp": 2})  # 16 > 8 devices


# ---------------------------------------------------------------------------
# analyze CLI (--sharding)


def test_analyze_cli_sharding_single_mode(capsys):
    _mesh8()
    from paddle_tpu import cli

    assert cli.main(["analyze", "--sharding", "--mode", "dp",
                     "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["mode"] == "dp"
    assert not rec["gate_failed"]
    assert "all-reduce" in rec["per_kind"]


def test_analyze_cli_sharding_on_saved_model(tmp_path, capsys):
    _mesh8()
    from paddle_tpu import cli

    x = fluid.layers.data(name="x", shape=[13])
    pred = fluid.layers.fc(input=x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    assert cli.main(["analyze", d, "--sharding", "--axes", "dp=8",
                     "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["sharding"]["axes"] == {"dp": 8}
    assert "comm_time_s" in rec["cost"]
    # model-less analyze without --sharding is a usage error
    assert cli.main(["analyze"]) == 2
    # malformed --axes is a usage error, not a traceback
    assert cli.main(["analyze", d, "--sharding", "--axes", "dp"]) == 2


# ---------------------------------------------------------------------------
# ground truth: static vs optimized_hlo (the acceptance criterion)


_HLO = None


def _hlo_module():
    global _HLO
    if _HLO is None:
        import importlib.util

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "hlo_analysis.py")
        spec = importlib.util.spec_from_file_location("hlo_analysis",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _HLO = mod
    return _HLO


@pytest.mark.slow
@pytest.mark.parametrize("which", ["lm_dp", "lm_mp", "lm_fsdp"])
def test_static_collectives_match_optimized_hlo(which):
    """ISSUE 9 acceptance: on the small-LM train step under dp, mp, and
    fsdp, the predicted collective SET equals the set extracted from
    Executor.optimized_hlo and per-kind bytes agree within ±10%.
    Compiles a real SPMD step (slow tier; the run_tests.sh pass runs
    it, tier-1 keeps the desc-only exactness tests above)."""
    mod = _hlo_module()
    name, build, cfg, feed_fn = next(
        e for e in mod.comm_validation_programs() if e[0] == which)
    static, ana = mod.comm_static(name)

    rng = np.random.RandomState(0)
    fluid.reset()
    loss_name = build()
    pe = ParallelExecutor(**cfg)
    pe.run(fluid.default_startup_program())
    feed = feed_fn(rng, 8)
    pe.run(feed=feed, fetch_list=[loss_name])
    txt = pe.optimized_hlo(feed=feed, fetch_list=[loss_name])
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                     delete=False) as f:
        f.write(txt)
        path = f.name
    try:
        _, _, colls = mod.parse_module(path)
    finally:
        os.unlink(path)
    actual = {}
    for c in colls:
        e = actual.setdefault(c["op"], {"count": 0, "bytes": 0})
        e["count"] += 1
        e["bytes"] += c["out_bytes"]
    assert set(static) == set(actual), (static, actual)
    for kind in actual:
        ratio = static[kind]["bytes"] / max(actual[kind]["bytes"], 1)
        assert 0.9 <= ratio <= 1.1, (which, kind, static[kind],
                                     actual[kind])
