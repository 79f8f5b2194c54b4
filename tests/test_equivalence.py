"""Translation-validation engine (analysis/equivalence.py): the
canonicalizer's algebra (idempotence, alpha/commutativity/order
invariance), the three proof tiers, the save→load→canonicalize→prove
round trip over the book models (ISSUE 10 satellite — the orphaned-var
bug class PR 6 pruned by hand), the four transpiler proof obligations,
the `paddle_tpu diff` CLI, and the eleven modes' plans against their
snapshot (tests/_mode_plans.py)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import equivalence as eqv
from paddle_tpu.analysis import contracts
from paddle_tpu.framework.core import Program
from paddle_tpu.parallel import modes as pmodes

from _mode_plans import mode_plan_against_snapshot


def _train_mlp(prefix=""):
    x = fluid.layers.data(name=prefix + "x", shape=[4])
    y = fluid.layers.data(name=prefix + "y", shape=[1])
    h = fluid.layers.fc(input=x, size=8, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    return cost, fluid.default_main_program()


# ---------------------------------------------------------------------------
# canonicalizer algebra


def test_canonicalize_idempotent_and_roundtrip():
    cost, prog = _train_mlp()
    c1, info = eqv.canonicalize(prog, [cost.name], ["x", "y"])
    assert len(c1.global_block().ops) == len(prog.global_block().ops)
    assert info.renamed > 0
    # idempotent through a JSON round trip (the CLI self-check contract)
    c_rt = Program.from_json(c1.to_json())
    c2, _ = eqv.canonicalize(c_rt, [cost.name], ["x", "y"])
    assert not eqv.semantic_diff(c1, c2), \
        eqv.semantic_diff(c1, c2).render()


def test_canonicalize_alpha_invariance():
    """Renaming TRANSIENT vars wholesale (every generated temp gets a
    fresh name) must canonicalize away: transient names are not
    semantics.  Interface names — feeds, fetches, persistables — stay
    the ABI, so they are left alone here."""
    cost_a, prog_a = _train_mlp()
    json_a = prog_a.to_json()
    blk = prog_a.global_block()
    interface = {cost_a.name, "x", "y"}
    interface.update(n for n, v in blk.vars.items()
                     if v.persistable or v.is_data)
    renamed = json_a
    k = 0
    for name in sorted(blk.vars):
        if name in interface:
            continue
        renamed = renamed.replace(f'"{name}"', f'"alpha_{k}"')
        k += 1
    assert k > 3 and renamed != json_a
    prog_b = Program.from_json(renamed)
    proof = eqv.prove_equivalent(Program.from_json(json_a), prog_b,
                                 feed_names=["x", "y"],
                                 fetch_names=[cost_a.name])
    assert proof.equivalent and proof.tier == "structural", proof.render()


def test_canonicalize_commutative_and_order_invariance():
    """Swapped add operands and a legal op reorder both canonicalize
    away (structural proof), while swapping a NON-commutative op's
    operands does not."""
    def build():
        a = fluid.layers.data(name="a", shape=[4])
        b = fluid.layers.data(name="b", shape=[4])
        s = fluid.layers.elementwise_add(a, b)
        d = fluid.layers.elementwise_sub(a, b)
        out = fluid.layers.elementwise_mul(s, d)
        return out, fluid.default_main_program()

    out, prog = build()
    mut = Program.from_json(prog.to_json())
    add = next(op for op in mut.global_block().ops
               if op.type == "elementwise_add")
    add.inputs["X"], add.inputs["Y"] = add.inputs["Y"], add.inputs["X"]
    proof = eqv.prove_equivalent(prog, mut, feed_names=["a", "b"],
                                 fetch_names=[out.name])
    assert proof.equivalent and proof.tier == "structural", proof.render()

    # legal reorder: move the sub op ahead of the add (no data dep)
    mut2 = Program.from_json(prog.to_json())
    ops = mut2.global_block().ops
    sub_i = next(i for i, op in enumerate(ops)
                 if op.type == "elementwise_sub")
    add_i = next(i for i, op in enumerate(ops)
                 if op.type == "elementwise_add")
    ops[sub_i], ops[add_i] = ops[add_i], ops[sub_i]
    proof2 = eqv.prove_equivalent(prog, mut2, feed_names=["a", "b"],
                                  fetch_names=[out.name])
    assert proof2.equivalent and proof2.tier == "structural"

    # NON-commutative swap: sub(a,b) != sub(b,a) — refuted, and the
    # differential oracle names the diverging fetch
    mut3 = Program.from_json(prog.to_json())
    sub = next(op for op in mut3.global_block().ops
               if op.type == "elementwise_sub")
    sub.inputs["X"], sub.inputs["Y"] = sub.inputs["Y"], sub.inputs["X"]
    proof3 = eqv.prove_equivalent(prog, mut3, feed_names=["a", "b"],
                                  fetch_names=[out.name])
    assert not proof3.equivalent
    assert any(f.rule == "PTV024" for f in proof3.findings), \
        proof3.render()


def test_canonicalize_dead_op_elimination():
    cost, prog = _train_mlp()
    blk = prog.global_block()
    # dangling compute: consumed by nothing, not persistable, not fetched
    blk.append_op("relu", inputs={"X": [cost.name]},
                  outputs={"Out": ["dangling_tmp"]})
    blk.create_var(name="dangling_tmp", shape=(1,), dtype="float32")
    c, info = eqv.canonicalize(prog, [cost.name], ["x", "y"])
    assert info.dead_removed == 1
    assert all("dangling_tmp" not in op.output_names()
               for op in c.global_block().ops)
    # and a program WITH the junk still proves equivalent to one without
    clean = Program.from_json(prog.to_json())
    clean.global_block().ops.pop()
    proof = eqv.prove_equivalent(clean, prog, feed_names=["x", "y"],
                                 fetch_names=[cost.name])
    assert proof.equivalent and proof.tier == "structural"


def test_canonicalize_control_flow_stays_executable():
    """Nested-block programs: names a sub-block references are pinned
    as interface (never SSA-renamed), sub-block owners are never dead —
    the canonical form of a while loop still runs and still sums."""
    i = fluid.layers.fill_constant(shape=[1], dtype="float32", value=0)
    n = fluid.layers.fill_constant(shape=[1], dtype="float32", value=10)
    total = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                       value=0)
    cond = fluid.layers.less_than(i, n)
    w = fluid.layers.While(cond)
    with w.block():
        new_total = fluid.layers.elementwise_add(total, i)
        fluid.layers.assign(new_total, total)
        fluid.layers.increment(i, 1.0)
        fluid.layers.less_than(i, n, cond=cond)
    prog = fluid.default_main_program()
    proof = eqv.prove_equivalent(prog, prog, feed_names=[],
                                 fetch_names=[total.name])
    assert proof.equivalent and proof.tier == "structural"
    c, _ = eqv.canonicalize(prog, [total.name], [])
    exe = fluid.Executor(fluid.CPUPlace())
    (res,) = exe.run(c, feed={}, fetch_list=[total.name])
    assert float(np.asarray(res).item()) == float(sum(range(10)))

    # a rewrite INSIDE the nested block must not be structurally
    # proven: the op hash covers sub-block CONTENT (recursive digest),
    # not just the sub_block index
    mut = Program.from_json(prog.to_json())
    w_op = next(op for op in mut.global_block().ops
                if op.type == "while")
    body = mut.blocks[w_op.attrs["sub_block"]]
    inc = next(op for op in body.ops if op.type == "increment")
    inc.attrs["step"] = float(inc.attrs.get("step", 1.0)) * 2.0
    ca, _ = eqv.canonicalize(prog, [total.name], [])
    cb, _ = eqv.canonicalize(mut, [total.name], [])
    assert eqv.semantic_diff(ca, cb), \
        "sub-block mutation invisible to the structural tier"


# ---------------------------------------------------------------------------
# proof tiers


def test_differential_tier_validates_fused_rewrite():
    """A structurally different but semantically equal rewrite (the
    fused-op case, hand-made: x*2 vs x+x) must fall through structure
    and validate on the differential oracle."""
    x = fluid.layers.data(name="x", shape=[4])
    doubled = fluid.layers.elementwise_add(x, x)
    prog_a = fluid.default_main_program()
    fetch = doubled.name

    prog_b = Program.from_json(prog_a.to_json())
    add = next(op for op in prog_b.global_block().ops
               if op.type == "elementwise_add")
    add.type = "scale"
    add.inputs = {"X": [add.inputs["X"][0]]}
    add.attrs = {k: v for k, v in add.attrs.items() if k == "__uid__"}
    add.attrs["scale"] = 2.0
    proof = eqv.prove_equivalent(prog_a, prog_b, feed_names=["x"],
                                 fetch_names=[fetch])
    assert proof.equivalent, proof.render()
    assert proof.tier == "differential"
    assert proof.diff  # the structural delta is reported as context


def test_abstract_tier_refutes_shape_change():
    x = fluid.layers.data(name="x", shape=[4])
    out = fluid.layers.reduce_sum(x, dim=1, keep_dim=True)
    prog_a = fluid.default_main_program()
    prog_b = Program.from_json(prog_a.to_json())
    rs = next(op for op in prog_b.global_block().ops
              if op.type == "reduce_sum")
    rs.attrs["keep_dim"] = False
    proof = eqv.prove_equivalent(prog_a, prog_b, feed_names=["x"],
                                 fetch_names=[out.name])
    assert not proof.equivalent
    assert proof.tier == "abstract"
    assert any(f.rule == "PTV022" for f in proof.findings), proof.render()


def test_semantic_diff_names_the_offending_ops():
    cost, prog = _train_mlp()
    mut = Program.from_json(prog.to_json())
    blk = mut.global_block()
    mean_i = next(i for i, op in enumerate(blk.ops)
                  if op.type == "mean")
    blk.ops.pop(mean_i)
    ca, _ = eqv.canonicalize(prog, [cost.name], ["x", "y"])
    cb, _ = eqv.canonicalize(mut, [cost.name], ["x", "y"])
    diff = eqv.semantic_diff(ca, cb)
    assert diff
    assert any("mean" in s for s in diff.only_in_a), diff.render()
    assert "only in A" in diff.render()


# ---------------------------------------------------------------------------
# save/load round-trip proof (satellite: the orphaned-var bug class)


def _save_fit_a_line(d):
    x = fluid.layers.data(name="x", shape=[13])
    y = fluid.layers.data(name="y", shape=[1])
    pred = fluid.layers.fc(input=x, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    inf = fluid.io.save_inference_model(d, ["x"], [pred], exe)
    return inf, ["x"], [pred.name]


def _save_recognize_digits(d):
    img = fluid.layers.data(name="img", shape=[1, 12, 12])
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    c = fluid.layers.conv2d(img, num_filters=4, filter_size=5,
                            bias_attr=False)
    b = fluid.layers.batch_norm(c, act="relu")
    p = fluid.layers.pool2d(b, pool_size=2, pool_stride=2)
    flat = fluid.layers.reshape(p, [-1, 4 * 4 * 4])
    pred = fluid.layers.fc(flat, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    inf = fluid.io.save_inference_model(d, ["img"], [pred], exe,
                                        fold_batch_norm=True)
    return inf, ["img"], [pred.name]


@pytest.mark.parametrize("which", ["fit_a_line", "recognize_digits"])
def test_save_load_roundtrip_proves_equivalent(tmp_path, which):
    """io.prune + save → load → canonicalize → prove_equivalent: the
    program that comes back from disk must PROVE equal to the one that
    went in (catches the orphaned-var/dropped-op class of save bugs),
    and the loaded model must self-check."""
    build = (_save_fit_a_line if which == "fit_a_line"
             else _save_recognize_digits)
    d = str(tmp_path / which)
    inf_prog, feeds, fetches = build(d)
    loaded, l_feeds, l_fetches = fluid.io.load_program_desc(d)
    assert l_feeds == feeds and l_fetches == fetches
    proof = eqv.prove_equivalent(inf_prog, loaded, feed_names=feeds,
                                 fetch_names=fetches)
    assert proof.equivalent, proof.render()
    assert proof.tier == "structural"  # serialization must not rewrite
    # no duplicate canonical subgraphs in a book model (PTV023 clean)
    assert not eqv.duplicate_findings(loaded)
    # the CLI self-check agrees end-to-end
    from paddle_tpu import cli

    assert cli.main(["diff", d]) == 0


def test_roundtrip_catches_dropped_op(tmp_path):
    """Mutate the saved program on disk (drop the producing op) — the
    round-trip proof must refute, not shrug."""
    d = str(tmp_path / "fit")
    inf_prog, feeds, fetches = _save_fit_a_line(d)
    with open(os.path.join(d, "program.json")) as f:
        desc = json.load(f)
    desc["blocks"][0]["ops"] = desc["blocks"][0]["ops"][:-1]
    with open(os.path.join(d, "program.json"), "w") as f:
        json.dump(desc, f)
    model = os.path.join(d, "__model__")
    if os.path.exists(model):
        os.remove(model)
    loaded, _, _ = fluid.io.load_program_desc(d)
    proof = eqv.prove_equivalent(inf_prog, loaded, feed_names=feeds,
                                 fetch_names=fetches)
    assert not proof.equivalent
    assert any(f.rule in ("PTV022", "PTV024") for f in proof.findings)


# ---------------------------------------------------------------------------
# the four transpiler proof obligations on the book-model fixtures


def test_memory_optimize_proof_on_book_model():
    """The fit-a-line-shaped training step under a forced marking:
    checked_memory_optimize now carries the structural proof — and a
    pass that rewrites structure under the remat flag is refuted."""
    cost, prog = _train_mlp()
    n = contracts.checked_memory_optimize(prog, batch_size=512,
                                          hbm_bytes=4096)
    assert n >= 1  # tiny budget forces marking; proof rode along

    # mutated pass: marking plus a smuggled non-commutative operand
    # swap -> PTV022 under the desc-only obligation
    cost2, prog2 = (lambda: (_train_mlp("m_")))()
    before = Program.from_json(prog2.to_json())
    blk = prog2.global_block()
    sub = next(op for op in blk.ops if op.type == "elementwise_sub")
    sub.inputs["X"], sub.inputs["Y"] = sub.inputs["Y"], sub.inputs["X"]
    proof = eqv.prove_equivalent(before, prog2, execute="never")
    assert not proof.equivalent
    assert any(f.rule == "PTV022" for f in proof.findings)


def test_fuse_batch_norm_proof_differential(tmp_path):
    """The conv+BN fold is structurally different by design: its
    contract proof must land on the differential tier and hold on the
    recognize-digits fixture (already exercised inside
    save_inference_model via checked_fuse_batch_norm when the verify
    gate is on — here we drive the contract directly)."""
    img = fluid.layers.data(name="img", shape=[1, 8, 8])
    c = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                            bias_attr=False)
    b = fluid.layers.batch_norm(c, act="relu")
    pred = fluid.layers.fc(fluid.layers.reshape(b, [-1, 4 * 6 * 6]),
                           size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    inf = fluid.default_main_program().clone(for_test=True)
    before = Program.from_json(inf.to_json())
    scope_snapshot = contracts._scope_snapshot(inf, fluid.global_scope())
    n = contracts.checked_fuse_batch_norm(inf, fluid.global_scope(),
                                          fetch_names=[pred.name])
    assert n == 1
    # the proof the contract ran: replay it visibly
    from paddle_tpu.framework.scope import Scope

    s_before = Scope()
    for k, v in scope_snapshot.items():
        s_before.set(k, v)
    proof = eqv.prove_equivalent(before, inf, fetch_names=[pred.name],
                                 scope_before=s_before,
                                 scope_after=fluid.global_scope(),
                                 preserve_state=False,
                                 rtol=1e-3, atol=1e-5)
    assert proof.equivalent, proof.render()
    assert proof.tier == "differential"


def test_fuse_batch_norm_proof_catches_corrupt_fold():
    """A fold that perturbs the folded filter (the bad-BN-fold bug
    class) leaves descs folded but values wrong — PTV024."""
    img = fluid.layers.data(name="img", shape=[1, 8, 8])
    c = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                            bias_attr=False)
    b = fluid.layers.batch_norm(c, act="relu")
    pred = fluid.layers.fc(fluid.layers.reshape(b, [-1, 4 * 6 * 6]),
                           size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    inf = fluid.default_main_program().clone(for_test=True)
    before = Program.from_json(inf.to_json())
    from paddle_tpu.framework.scope import Scope

    s_before = Scope()
    for k, v in contracts._scope_snapshot(inf,
                                          fluid.global_scope()).items():
        s_before.set(k, v)
    from paddle_tpu.inference_transpiler import fuse_batch_norm

    assert fuse_batch_norm(inf, fluid.global_scope(),
                           fetch_names=[pred.name]) == 1
    # corrupt the folded filter AFTER the (raw) fold
    filt = next(op for op in inf.global_block().ops
                if op.type == "conv2d").inputs["Filter"][0]
    w = np.array(fluid.global_scope().find_np(filt))
    w[0] *= 1.5
    fluid.global_scope().set(filt, w)
    proof = eqv.prove_equivalent(before, inf, fetch_names=[pred.name],
                                 scope_before=s_before,
                                 scope_after=fluid.global_scope(),
                                 preserve_state=False,
                                 rtol=1e-3, atol=1e-5)
    assert not proof.equivalent
    assert any(f.rule == "PTV024" for f in proof.findings), proof.render()


def test_distribute_transpile_proof_same_gradients():
    """The split's obligation: pruned to the gradient fetches, trainer
    and original canonicalize identically (preserve_state=False — the
    optimizer writes now live on the pserver)."""
    cost, prog = _train_mlp()
    before = Program.from_json(prog.to_json())
    t = fluid.DistributeTranspiler()
    contracts.checked_distribute_transpile(
        t, trainer_id=0, pservers="127.0.0.1:0", trainers=1)
    grads = sorted(t.param_grad.values())
    proof = eqv.prove_equivalent(before, t.program, fetch_names=grads,
                                 preserve_state=False)
    assert proof.equivalent, proof.render()


def test_distribute_transpile_proof_structural_with_lr_schedule():
    """A model with an LR schedule: transpile flips persistable=True on
    the schedule's tmp var (after-program only), and the schedule ops
    dead-eliminate away from the grad obligation — the orphaned
    declaration must NOT demote the proof below the structural tier
    (it changes nothing the trainer computes)."""
    x = fluid.layers.data(name="x", shape=[4])
    y = fluid.layers.data(name="y", shape=[1])
    pred = fluid.layers.fc(input=x, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    lr = fluid.learning_rate_decay.exponential_decay(
        learning_rate=0.1, decay_steps=10, decay_rate=0.9)
    fluid.optimizer.SGD(learning_rate=lr).minimize(cost)
    prog = fluid.default_main_program()
    before = Program.from_json(prog.to_json())
    t = fluid.DistributeTranspiler()
    t.transpile(0, program=prog, pservers="127.0.0.1:0", trainers=1)
    grads = sorted(t.param_grad.values())
    proof = eqv.prove_equivalent(before, t.program, fetch_names=grads,
                                 preserve_state=False)
    assert proof.equivalent, proof.render()
    assert proof.tier == "structural", proof.render()


def test_sharding_plan_proof_program_unmutated():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device test mesh")
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.partitioner import (
        DistributeTranspiler as ShardingTranspiler)

    cost, prog = _train_mlp()
    mesh = make_mesh({"dp": 4, "mp": 2})
    plan = contracts.checked_sharding_plan(ShardingTranspiler(), prog,
                                           mesh)
    assert plan  # the equivalence proof rode inside the contract


# ---------------------------------------------------------------------------
# plan equivalence: every mode's plan against the snapshot


@pytest.mark.parametrize("name", pmodes.MODE_NAMES)
def test_plan_equivalence_covers_all_modes(name):
    """Every catalog mode gets a verdict; a PROVEN mode has no diffs, a
    DIVERGED mode carries a concrete explanation (per-var spec diff with
    the bespoke rule's provenance, or a collective-footprint delta)."""
    r = mode_plan_against_snapshot(name)
    assert r["mode"] == name
    assert r["verdict"] in ("PROVEN", "DIVERGED")
    if r["verdict"] == "PROVEN":
        assert not r["spec_diffs"] and not r["comm"]["delta"]
    else:
        assert r["spec_diffs"] or r["comm"]["delta"]
        for d in r["spec_diffs"]:
            assert d["var"] and "bespoke" in d and "logical" in d
            assert d["bespoke_rule"]
    # ISSUE 19: the partitioner collapse is done — the floor is the
    # whole catalog, PROVEN against the golden archive of the deleted
    # bespoke wiring
    assert r["verdict"] == "PROVEN", (r["mode"], r["verdict"])
    assert r["golden"]


def test_plan_equivalence_zero_fsdp_gap_closed():
    """The dp_mp (ZeRO-1) and fsdp modes used to diverge from the
    logical declaration EXACTLY on the dim-0 dp state reshard — the
    same rule the PTV016 crash-triage findings cite for the 3
    isolation-skip test_parallel programs.  ISSUE 19 closed the gap:
    the ("state0", dp)/("param0", dp) rule families landed, the
    bespoke wiring is deleted, and both modes are PROVEN against its
    archived plans.  The old divergence stays pinned by the mutation
    tests (test_sharding.py::test_zero_state_rule_removed_
    reopens_pr10_diff and test_fsdp_param_rule_removed_reopens_
    pr10_diff): remove the rule and the archived diff reappears."""
    for name in ("dp_mp", "fsdp"):
        rec = mode_plan_against_snapshot(name)
        assert rec["verdict"] == "PROVEN", (name, rec)
        assert rec["golden"], "golden archive missing"
        assert not rec["comm"]["delta"]   # gather-back bytes archived


# ---------------------------------------------------------------------------
# CLI


def test_diff_cli_two_programs_and_json(tmp_path):
    from paddle_tpu import cli

    cost, prog = _train_mlp()
    pa = str(tmp_path / "a.json")
    with open(pa, "w") as f:
        f.write(prog.to_json())
    # drop one parameter's sgd update: with no fetch context (bare
    # program files carry no meta) the obligation is the WRITTEN STATE,
    # and one param now updates on only one side
    mut = Program.from_json(prog.to_json())
    blk = mut.global_block()
    blk.ops.pop(next(i for i, op in enumerate(blk.ops)
                     if op.type == "sgd"))
    pb = str(tmp_path / "b.json")
    with open(pb, "w") as f:
        f.write(mut.to_json())
    assert cli.main(["diff", pa, pa]) == 0
    assert cli.main(["diff", pa, pb]) == 1
    assert cli.main(["diff", pa, pb, "--no-exec"]) == 1
    assert cli.main(["diff", pa]) == 0  # self-check

    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["diff", pa, pb, "--json", "--no-exec"])
    assert rc == 1
    rec = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert rec["equivalent"] is False
    assert any("PTV022" in f for f in rec["findings"])
    assert rec["diff"]


def test_diff_cli_self_check_bare_inference_dump(tmp_path):
    """Self-check on a raw program.json with NO meta (no feed/fetch
    context) and real sink outputs: the interface must be derived
    BEFORE canonicalization — chasing original sink names after
    alpha-renaming dead-eliminated the whole canonical program."""
    from paddle_tpu import cli

    x = fluid.layers.data(name="x", shape=[4])
    h = fluid.layers.fc(input=x, size=8, act="relu")
    fluid.layers.fc(input=h, size=2)  # sink: consumed by nothing
    p = str(tmp_path / "bare.json")
    with open(p, "w") as f:
        f.write(fluid.default_main_program().to_json())
    assert cli.main(["diff", p]) == 0


def test_diff_cli_dir_vs_bare_program_shares_scope(tmp_path):
    """A saved-model dir vs its own bare program.json: only one side
    carries values — the scope must be SHARED, not synthetically
    seeded on the bare side (which would fabricate a PTV024
    counterexample between byte-identical programs)."""
    from paddle_tpu import cli

    d = str(tmp_path / "m")
    _save_fit_a_line(d)
    assert cli.main(["diff", d, os.path.join(d, "program.json")]) == 0
    assert cli.main(["diff", os.path.join(d, "program.json"), d]) == 0
