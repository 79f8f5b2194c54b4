"""The op identity (observability/attribution.py): identity threading reaches
compiled HLO with nothing switched on, names a grad op by its forward op
and carries the model part forward and backward, and its two trace-time
counters count what a hand counts (ISSUE 35)."""

import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import attribution as attr


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

    from paddle_tpu.framework.dataflow import state_classes
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
