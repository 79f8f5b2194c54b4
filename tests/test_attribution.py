"""Per-op attribution + measured calibration + sentinel (ISSUE 16).

Five families: (1) identity threading reaches compiled HLO with nothing
switched on, names a grad op by its forward op and carries the model part
forward and backward, and its two trace-time counters count what a hand
counts (ISSUE 35); (2) the CPU segment oracle attributes ~all of the
measured walk; (3) the sealed calibration store round-trips, survives a
process "restart" (fresh instance, same root) and evicts corruption;
(4) calibration factors change the autotune prior's ranking on a
synthetic workload while the raw price rides along; (5) the regression
sentinel passes identical runs and flags an injected slowdown naming
the guilty op."""

import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import attribution as attr
from paddle_tpu.observability import calibration as calib


def _tiny_infer_program():
    """x -> fc(3): one mul + one elementwise_add, is_test lowering."""
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[4])
    y = fluid.layers.fc(x, size=3)
    program = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return program, y


def _lowered_text(program, out_name):
    """HLO text of the block lowered exactly the way the executor does
    (framework/executor._lower_ops), with no flag set."""
    import jax

    from paddle_tpu.analysis.dataflow import state_classes
    from paddle_tpu.framework.executor import _lower_ops
    from paddle_tpu.framework.scope import global_scope
    from paddle_tpu.ops.registry import EmitContext

    block = program.global_block()
    ext, rw, _ = state_classes(block, ["x"])
    state = {n: np.asarray(global_scope().find(n))
             for n in list(ext) + list(rw)}
    feed = {"x": np.random.RandomState(0).rand(2, 4).astype(np.float32)}

    def run(feed_vals, state_vals):
        env = dict(state_vals)
        env.update(feed_vals)
        ctx = EmitContext(jax.random.PRNGKey(0), is_test=True,
                          program=program)
        _lower_ops(block.ops, env, ctx)
        return env[out_name]

    # scope names live in the compiled HLO's op metadata, which the
    # pre-compile StableHLO dump does not carry
    return jax.jit(run).lower(feed, state).compile().as_text()


# ---------------------------------------------------------------------------
# (1) identity threading


def test_named_scope_reaches_compiled_hlo_with_no_flag_set():
    program, y = _tiny_infer_program()
    txt = _lowered_text(program, y.name)
    assert "pdop__mul__u" in txt, txt[:2000]
    assert "pdop__elementwise_add__u" in txt


def test_scope_name_roundtrip():
    program, _ = _tiny_infer_program()
    for op in program.global_block().ops:
        if op.type in ("feed", "fetch"):
            continue
        parsed = attr.parse_scope("fused." + attr.scope_name(op) + "/x")
        assert parsed == (op.type, int(op.attrs["__uid__"])), (op.type,
                                                              parsed)
    # underscored types stay unambiguous under the greedy match
    assert attr.parse_scope("pdop__elementwise_add__u17") == \
        ("elementwise_add", 17)
    assert attr.parse_scope("no scope here") is None


def _op_names(exe, feed, fetch):
    """Every `op_name` of the executor's compiled step."""
    txt = exe.optimized_hlo(fluid.default_main_program(), feed, fetch)
    return re.findall(r'op_name="([^"]+)"', txt)


def _toy_lm():
    """decoder_lm + lm_loss at toy size, trained one step."""
    from paddle_tpu.models import transformer as T

    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    targets = fluid.layers.data("targets", shape=[8, 1], dtype="int64")
    logits = T.decoder_lm(tokens, 32, 16, 1, 2, max_len=8,
                          norm="rms_norm", positions="rope", qk_norm=True)
    loss = T.lm_loss(logits, targets)
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"tokens": np.ones((2, 8, 1), np.int64),
            "targets": np.ones((2, 8, 1), np.int64)}
    exe.run(feed=feed, fetch_list=[loss])
    return exe, feed, loss


def test_grad_op_is_named_after_its_forward_op():
    program, _ = _tiny_infer_program()
    mul = next(op for op in program.global_block().ops if op.type == "mul")

    class Grad:
        type = "generic_grad"
        attrs = {"__fwd_type__": "mul", "__uid__": mul.attrs["__uid__"],
                 "__fwd_attrs__": {"part": "lm.head"}}

    assert attr.scope_name(Grad) == f"pdop__mul_grad__u{mul.attrs['__uid__']}"
    assert attr.op_type(Grad) == "mul_grad" and attr.op_type(mul) == "mul"
    assert attr.op_part(Grad) == "lm.head" and attr.op_part(mul) is None
    # parse_scope reads the grad form and the old one alike
    assert attr.parse_scope(attr.scope_name(Grad)) == \
        ("mul_grad", int(mul.attrs["__uid__"]))
    assert attr.parse_scope("x/pdop__generic_grad__u3/y") == \
        ("generic_grad", 3)


def test_toy_lm_names_grads_parts_head_and_loss():
    exe, feed, loss = _toy_lm()
    names = _op_names(exe, feed, [loss])
    joined = "\n".join(names)
    # a generic_grad's instructions say whose gradient they are
    assert "pdop__mul_grad__u" in joined
    assert "pdop__generic_grad__u" not in joined
    # an op with `part` carries it forward AND backward (the layer's own:
    # Q's and K's turn, whose grad op is a desc op of its own type, and the
    # QK-norm; the guard's: the head and the loss)
    for part, fwd, bwd in (("attn.qk_prep", "head_norm_rope",
                            "head_norm_rope_grad"),
                           ("attn.qk_norm", "rms_norm", "rms_norm_grad"),
                           ("lm.head", "mul", "mul_grad"),
                           ("lm.loss", "softmax_with_cross_entropy",
                            "softmax_with_cross_entropy_grad")):
        for op in (fwd, bwd):
            rx = re.compile(r"pdop__%s__u\d+/[^;]*pdtpu\.%s"
                            % (op, re.escape(part)))
            assert any(rx.search(n) for n in names), (op, part)
    # the head is ONE projection: the other products carry no `lm.head`
    head = {m for n in names
            for m in re.findall(r"pdop__mul__u(\d+)/pdtpu\.lm\.head", n)}
    assert len(head) == 1, head
    assert any(re.search(r"pdop__mul__u\d+/dot_general", n) for n in names)


def test_part_guard_stamps_ops_appended_inside_and_keeps_an_ops_own():
    fluid.reset()
    main = fluid.default_main_program()
    x = fluid.layers.data(name="x", shape=[2, 4, 8])
    before = fluid.layers.scale(x, scale=2.0)
    with main.part_guard("blk.outer"):
        inside = fluid.layers.scale(before, scale=2.0)
        own = fluid.layers.rms_norm(inside, begin_norm_axis=2,
                                    part="attn.qk_norm")
        with main.part_guard("blk.inner"):
            nested = fluid.layers.scale(own, scale=2.0)
        again = fluid.layers.scale(nested, scale=2.0)
    after = fluid.layers.scale(again, scale=2.0)
    part = {v.name: op.attrs.get("part")
            for op in main.global_block().ops for v in (before, inside, own,
                                                        nested, again, after)
            if v.name in op.output_names()}
    assert part == {before.name: None, inside.name: "blk.outer",
                    own.name: "attn.qk_norm",
                    # guards nest since PR 39: outer first, `/` between
                    nested.name: "blk.outer/blk.inner",
                    again.name: "blk.outer", after.name: None}
    # the startup program's ops (the gain's initializer) are not the guard's
    assert not any(op.attrs.get("part")
                   for op in fluid.default_startup_program().global_block().ops)


def test_compiled_program_never_reaches_op_scope(monkeypatch):
    exe, feed, loss = _toy_lm()
    calls = []
    real = attr.op_scope
    monkeypatch.setattr(attr, "op_scope",
                        lambda op: calls.append(op.type) or real(op))
    emitted = _emit_seconds()
    exe.run(feed=feed, fetch_list=[loss])
    assert calls == []
    # nor its emitters: the trace-time counters stand still
    assert _emit_seconds() == emitted
    # a new feed shape is a new trace, and that one does
    exe.run(feed={k: np.ones((3, 8, 1), np.int64) for k in feed},
            fetch_list=[loss])
    assert "mul" in calls


def _series(family):
    fam = obs.REGISTRY.snapshot()["families"].get(family)
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in (fam["series"] if fam else ())}


def _emit_seconds():
    return _series("executor_op_emit_seconds_total")


@pytest.mark.parametrize("optimizer", ["adam", "momentum"])
def test_optimizer_update_bytes_equal_the_hand_count(optimizer):
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[4])
    y = fluid.layers.data(name="y", shape=[1])
    h = fluid.layers.cast(
        fluid.layers.fc(fluid.layers.cast(x, "bfloat16"), size=3,
                        param_attr=fluid.ParamAttr(name="w"),
                        bias_attr=fluid.ParamAttr(name="b")), "float32")
    loss = fluid.layers.mean(fluid.layers.square_error_cost(
        fluid.layers.reduce_sum(h, dim=1, keep_dim=True), y))
    opt = (fluid.optimizer.Adam(learning_rate=1e-3) if optimizer == "adam"
           else fluid.optimizer.Momentum(learning_rate=1e-3, momentum=0.9))
    opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((2, 4), np.float32), "y": np.ones((2, 1), np.float32)}
    exe.run(feed=feed, fetch_list=[loss])
    exe.run(feed=feed, fetch_list=[loss])    # a step counts nothing
    params = fluid.default_main_program().global_block().all_parameters()
    sizes = {p.name: (int(np.prod(p.shape)), np.dtype(
        fluid.framework.core.np_dtype(p.dtype)).itemsize) for p in params}
    assert sorted(sizes) == ["b", "w"] and sizes["w"][0] == 12
    moments = 2 if optimizer == "adam" else 1     # float32, whatever the
    want = {"param": sum(2 * n * b for n, b in sizes.values()),   # weight's
            "state": sum(2 * moments * n * 4 for n, _ in sizes.values()),
            "grad": sum(n * b for n, b in sizes.values())}
    got = {dict(k)["tensor"]: v
           for k, v in _series("optimizer_update_bytes_total").items()
           if dict(k)["op"] == optimizer}
    assert got == want, (got, want)
    if optimizer == "adam":
        # the two beta powers: 2 x (read + written) x one float32
        assert _series("optimizer_update_bytes_total")[
            (("op", "adam_beta_pow_update"), ("tensor", "state"))] == 16


def test_op_emit_seconds_one_series_a_type_and_a_while_body_once(monkeypatch):
    """A `while` op's emitter lowers its body through _lower_ops again:
    the body's ops have their own series and the while's is what is left.
    With a clock that advances one second a reading, an op costs the one
    second between its two readings."""
    from paddle_tpu.framework import executor as ex

    fluid.reset()
    i = fluid.layers.fill_constant(shape=[1], dtype="float32", value=0)
    n = fluid.layers.fill_constant(shape=[1], dtype="float32", value=3)
    acc = fluid.layers.fill_constant(shape=[1], dtype="float32", value=1.0)
    cond = fluid.layers.less_than(i, n)
    loop = fluid.layers.While(cond)
    with loop.block():
        fluid.layers.assign(fluid.layers.scale(acc, scale=2.0), acc)
        fluid.layers.increment(i, 1.0)
        fluid.layers.less_than(i, n, cond=cond)
    ticks = iter(range(10 ** 6))
    monkeypatch.setattr(ex, "_monotime", lambda: float(next(ticks)))
    exe = fluid.Executor(fluid.CPUPlace())
    out, = exe.run(feed={}, fetch_list=[acc])
    assert float(np.asarray(out).reshape(())) == 8.0
    got = {dict(k)["op"]: v for k, v in _emit_seconds().items()}
    main = fluid.default_main_program()
    types = [op.type for b in main.blocks for op in b.ops]
    assert len(got) == len(set(types)) and set(got) == set(types)
    body = len(main.blocks[1].ops)
    # every op but the while: one second a lowering
    assert all(got[t] == types.count(t) for t in got if t != "while"), got
    # the while: its own two readings' second and one between each of its
    # children's spans, not the spans themselves (1 + 2 x body if it did)
    assert got["while"] == 1 + body, (got["while"], body)


# ---------------------------------------------------------------------------
# (2) the CPU oracle


def test_oracle_attributes_whole_walk():
    from paddle_tpu.models.standing import build_fit_a_line

    fluid.reset()
    feed, _fetch, bs = build_fit_a_line()
    program = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    table = attr.attribute_cpu(program, feed, batch_size=bs, repeats=2)
    # acceptance: >=80% of the measured walk lands on named desc ops
    # (the sum of per-op medians can honestly exceed one walk's wall a
    # little, hence the loose upper bound)
    assert 0.8 <= table["coverage"] <= 1.5, table["coverage"]
    assert table["n_ops"] > 0
    assert all(r["uid"] >= 0 for r in table["rows"])
    assert abs(sum(r["measured_share"] for r in table["rows"])
               - table["coverage"]) < 1e-6
    # the training program's backward dominates a CPU walk
    assert table["top_op"] == "generic_grad", table["by_type"]
    # the join carries the static prediction for every attributed op
    assert table["pred_total_s"] > 0
    # gauges + artifact row materialize without violating the schema
    attr.publish(table, "fit_a_line")
    row = attr.artifact_row(table, "fit_a_line")
    assert row["metric"] == "op_attribution_fit_a_line"
    snap = obs.REGISTRY.snapshot()
    assert not obs.validate_snapshot(snap)
    assert "op_pred_vs_measured" in snap["families"]


def test_oracle_schedule_respects_textual_write_order():
    """The schedule may reorder independent ops but never hoists a write
    above an earlier textual access of the same name — the
    scope-read-then-optimizer-write idiom hazards() exempts."""
    from paddle_tpu.analysis import dataflow as df
    from paddle_tpu.models.standing import build_fit_a_line

    fluid.reset()
    build_fit_a_line()
    block = fluid.default_main_program().global_block()
    order = attr.schedule(block)
    assert sorted(order) == list(range(len(block.ops)))
    pos = {op_i: k for k, op_i in enumerate(order)}
    defs, uses = df.def_use(block)
    for name, dlist in defs.items():
        accesses = sorted(set(dlist) | set(uses.get(name, [])))
        for j in dlist:
            for i in accesses:
                if i < j:
                    assert pos[i] < pos[j], (name, i, j, order)


# ---------------------------------------------------------------------------
# (3) the calibration store


def _table_for(chip="cpu-host"):
    # per-op rows (what record_attribution fits from) + the by_type
    # roll-up consumers read; mul measures 2x its prediction, gelu 0.5x
    return {"chip": chip,
            "rows": [{"op_type": "mul", "dtype": "float32",
                      "measured_s": 1.0, "pred_time_s": 0.5},
                     {"op_type": "mul", "dtype": "float32",
                      "measured_s": 1.0, "pred_time_s": 0.5},
                     {"op_type": "gelu", "dtype": "float32",
                      "measured_s": 0.5, "pred_time_s": 1.0}],
            "by_type": {"mul": {"dtype": "float32", "count": 2,
                                "measured_s": 2.0, "pred_time_s": 1.0},
                        "gelu": {"dtype": "float32", "count": 1,
                                 "measured_s": 0.5,
                                 "pred_time_s": 1.0}}}


def test_calibration_store_roundtrip_and_restart(tmp_path):
    store = calib.CalibrationStore(str(tmp_path))
    entry = store.record_attribution(_table_for())
    assert entry is not None
    assert store.factor("cpu-host", "mul", "float32") == pytest.approx(2.0)
    assert store.factor("cpu-host", "gelu", "float32") == pytest.approx(0.5)
    # unknown op types fall back to the identity factor
    assert store.factor("cpu-host", "softmax", "float32") == 1.0

    # "restart": a FRESH instance over the same root reads the sealed
    # file, not the dead process's memory
    again = calib.CalibrationStore(str(tmp_path))
    assert again.factor("cpu-host", "mul", "float32") == pytest.approx(2.0)

    # a second observation round blends by weight, not replaces
    again.update("cpu-host", [{"op_type": "mul", "dtype": "float32",
                               "measured_s": 4.0, "predicted_s": 1.0,
                               "count": 2}])
    blended = again.factor("cpu-host", "mul", "float32")
    assert 2.0 < blended < 4.0, blended


def test_calibration_store_evicts_corruption(tmp_path):
    store = calib.CalibrationStore(str(tmp_path))
    store.record_attribution(_table_for())
    path = store._path("cpu-host")
    assert os.path.exists(path)

    # bit rot: flip a payload byte under the seal -> evicted, read empty
    raw = bytearray(open(path, "rb").read())
    raw[-1] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    fresh = calib.CalibrationStore(str(tmp_path))
    assert fresh.factors("cpu-host") == {}
    assert not os.path.exists(path), "corrupt entry must be evicted"

    # unsealed garbage likewise
    open(path, "wb").write(b'{"schema": "not-sealed"}')
    fresh2 = calib.CalibrationStore(str(tmp_path))
    assert fresh2.factors("cpu-host") == {}
    assert not os.path.exists(path)


def test_calibration_factor_clamp():
    assert calib.clamp(1e30) == calib.FACTOR_MAX
    assert calib.clamp(1e-30) == calib.FACTOR_MIN
    assert calib.clamp(3.5) == 3.5


# ---------------------------------------------------------------------------
# (4) calibration changes the prior's ranking


def _mul_heavy():
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[64])
    h = fluid.layers.fc(x, size=64)
    h = fluid.layers.fc(h, size=64)
    h = fluid.layers.fc(h, size=64)
    return fluid.default_main_program(), 8


def _gelu_heavy():
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[64])
    h = fluid.layers.fc(x, size=64)
    for _ in range(20):
        h = fluid.layers.gelu(h)
    return fluid.default_main_program(), 8


class _SynthWL:
    """Synthetic workload: the candidate's `arch` knob picks which
    program is priced, so two candidates genuinely differ in desc."""

    name = "synthetic_attr"

    def program_for(self, cand):
        return (_mul_heavy() if cand.get("arch") == "mul"
                else _gelu_heavy())


def test_calibrated_prior_changes_ranking(tmp_path, monkeypatch):
    from paddle_tpu.autotune import prior
    from paddle_tpu.autotune.space import Candidate

    monkeypatch.setenv("PADDLE_TPU_CALIBRATION_CACHE", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_CALIBRATION", raising=False)
    wl = _SynthWL()
    c_mul, c_gelu = Candidate({"arch": "mul"}), Candidate({"arch": "gelu"})

    def rank_pair():
        a = prior.price(wl, c_mul, chip="v5e")
        b = prior.price(wl, c_gelu, chip="v5e")
        return a, b

    # empty store: the prior prices raw and says so
    a0, b0 = rank_pair()
    assert not a0.calibrated and not b0.calibrated
    raw_says_mul_first = a0.predicted_step_s < b0.predicted_step_s

    # measured "truth": mul is catastrophically mispriced (1000x slower
    # than the roofline says), gelu is priced fairly
    calib.default_store().update("v5e", [
        {"op_type": "mul", "dtype": "float32",
         "measured_s": 1000.0, "predicted_s": 1.0},
        {"op_type": "gelu", "dtype": "float32",
         "measured_s": 1.0, "predicted_s": 1.0},
    ])
    a1, b1 = rank_pair()
    assert a1.calibrated and b1.calibrated
    # the raw price always rides along, unchanged by calibration
    assert a1.raw_step_s == pytest.approx(a0.predicted_step_s)
    assert a1.row()["predicted_raw_step_s"] == a1.raw_step_s
    # ... and the calibrated ranking flips the raw one
    cal_says_mul_first = a1.predicted_step_s < b1.predicted_step_s
    assert raw_says_mul_first and not cal_says_mul_first, (
        a0.predicted_step_s, b0.predicted_step_s,
        a1.predicted_step_s, b1.predicted_step_s)

    # the kill switch restores raw ranking without touching the store
    monkeypatch.setenv("PADDLE_TPU_CALIBRATION", "0")
    a2, b2 = rank_pair()
    assert not a2.calibrated
    assert a2.predicted_step_s == pytest.approx(a0.predicted_step_s)


def test_program_cost_reports_raw_alongside_calibrated(tmp_path,
                                                       monkeypatch):
    from paddle_tpu.analysis import cost as acost

    monkeypatch.setenv("PADDLE_TPU_CALIBRATION_CACHE", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_CALIBRATION", raising=False)
    program, bs = _mul_heavy()
    plain = acost.program_cost(program, batch_size=bs, chip="v5e")
    assert "calibrated_step_time_s" not in plain
    assert plain["per_op_time_s"] > 0

    calib.default_store().update("v5e", [
        {"op_type": "mul", "dtype": "float32",
         "measured_s": 10.0, "predicted_s": 1.0}])
    cal = acost.program_cost(program, batch_size=bs, chip="v5e")
    assert cal["calibrated_step_time_s"] > cal["per_op_time_s"]
    # the raw report keys are untouched by the calibrated layer
    for key in ("predicted_step_time_s", "compute_time_s", "hbm_bytes"):
        assert cal[key] == pytest.approx(plain[key])
    assert cal["calibration"]["factors_applied"] >= 1


def test_overhead_fit_and_op_count_rerank(tmp_path, monkeypatch):
    """The affine fit recovers slope+intercept, and the fitted per-op
    overhead re-ranks the op-count axis (mlp_depth) that a pure ratio
    provably cannot: equal-FLOPs candidates scale proportionally under
    any factor, so only the intercept separates 1x from 16x ops."""
    f, c = calib._fit_affine([(1.0, 2.5), (2.0, 4.5), (4.0, 8.5)])
    assert f == pytest.approx(2.0) and c == pytest.approx(0.5)
    # no size spread -> slope unidentifiable -> ratio, zero overhead
    f2, c2 = calib._fit_affine([(1.0, 2.0), (1.0, 2.0), (1.0, 2.0)])
    assert f2 == pytest.approx(2.0) and c2 == 0.0

    monkeypatch.setenv("PADDLE_TPU_CALIBRATION_CACHE", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_CALIBRATION", raising=False)
    from paddle_tpu.autotune import prior, workloads
    wl = workloads.get_workload("mlp_depth")
    cands = wl.space().candidates()
    feas, _ = prior.rank(wl, cands, chip="cpu-host")
    raw_order = [p.candidate.get("mlp.depth") for p in feas]
    assert raw_order[0] != 1  # the raw roofline prefers a deeper stack

    # measured "truth" for this host: every op costs a constant 1 ms
    # dispatch floor on top of its roofline time (three sizes per op
    # type give the fit its spread)
    rows = [{"op_type": t, "dtype": "float32",
             "measured_s": p + 1e-3, "predicted_s": p}
            for t in ("mul", "elementwise_add", "relu")
            for p in (1e-7, 2e-7, 4e-7)]
    calib.default_store().update("cpu-host", rows)
    ent = calib.default_store().factors("cpu-host")["mul|float32"]
    assert ent["overhead_s"] == pytest.approx(1e-3, rel=1e-3)

    feas2, _ = prior.rank(wl, cands, chip="cpu-host")
    assert feas2[0].calibrated
    cal_order = [p.candidate.get("mlp.depth") for p in feas2]
    assert cal_order == [1, 4, 16], (raw_order, cal_order)
    # the raw price rides along untouched by the overhead term
    raw_d1 = next(p for p in feas if p.candidate.get("mlp.depth") == 1)
    assert feas2[0].raw_step_s == pytest.approx(raw_d1.predicted_step_s)


# ---------------------------------------------------------------------------
# (5) the sentinel


def test_sentinel_self_test_and_verdicts():
    from tools import sentinel

    assert sentinel.self_test() == 0

    base = {"step_ms": {"metric": "step_ms", "value": 10.0, "unit": "ms",
                        "by_type": {"mul": {"share": 0.5},
                                    "gelu": {"share": 0.5}}}}
    same = sentinel.compare(base, json.loads(json.dumps(base)))
    assert same["verdict"] == "PASS" and same["regressed"] == 0

    bad = json.loads(json.dumps(base))
    bad["step_ms"]["value"] = 15.0
    bad["step_ms"]["by_type"] = {"mul": {"share": 0.8},
                                 "gelu": {"share": 0.2}}
    rep = sentinel.compare(base, bad)
    assert rep["verdict"] == "REGRESSED"
    (m,) = rep["metrics"]
    assert m["metric"] == "step_ms" and m["verdict"] == "REGRESSED"
    assert m["guilty_ops"][0]["op_type"] == "mul"


def test_sentinel_noise_margin_from_spread():
    from tools import sentinel

    row = {"metric": "lstm_step_ms", "value": 7.0, "unit": "ms",
           "best_ms": 7.0, "median_ms": 9.0}
    # spread (9-7)/7 = 28.6% -> margin 2x = 57%; a 40% move stays PASS
    wob = dict(row, value=7.0 * 1.4)
    rep = sentinel.compare({"lstm_step_ms": row}, {"lstm_step_ms": wob})
    assert rep["verdict"] == "PASS"
    # but the floor still catches it once the spread is gone
    rep2 = sentinel.compare(
        {"lstm_step_ms": {"metric": "lstm_step_ms", "value": 7.0,
                          "unit": "ms"}},
        {"lstm_step_ms": {"metric": "lstm_step_ms", "value": 7.0 * 1.4,
                          "unit": "ms"}})
    assert rep2["verdict"] == "REGRESSED"


def test_sentinel_loads_attribution_artifacts(tmp_path):
    from tools import sentinel

    row = {"metric": "op_attribution_x", "value": 0.99,
           "unit": "fraction attributed",
           "by_type": {"mul": {"share": 0.9}}}
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    p1.write_text(json.dumps(row) + "\n")
    row2 = dict(row, value=0.4)
    p2.write_text(json.dumps(row2) + "\n")
    rep = sentinel.compare(sentinel.load_rows(str(p1)),
                           sentinel.load_rows(str(p2)))
    # coverage collapse regresses (higher-is-better polarity)
    assert rep["verdict"] == "REGRESSED"
