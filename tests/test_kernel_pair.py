"""The kernel-pair protocol with no kernel in it: a pair over two plain
jax.numpy functions (jitted under names of their own, so a step's jaxpr
shows what it launches) driven through `kernel_pair`
(ops/pallas_kernels/_common.py) and `EmitContext.run_pair`
(ops/registry.py) in the four situations an emitter can be in.  What the
real pairs' own test files show of one kernel each (test_kernel_forward_once,
test_gated_delta_kernel, test_selective_scan_kernel), shown here of the
shared code, in a second."""

import collections
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.framework.layer_helper import LayerHelper
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops.pallas_kernels._common import kernel_pair

OP = "toy_pair"
CALLS = collections.Counter()   # the declared functions' Python calls
KEPT = []                       # an emission's `saved is not None`


@jax.jit
def toy_fwd(x, w):              # out = tanh(x) w; the residual is tanh(x)
    t = jnp.tanh(x)
    return t * w, t


@jax.jit
def toy_bare(x, w):
    return jnp.tanh(x) * w


@jax.jit
def toy_bwd(x, w, t, do):
    return do * w * (1 - t * t), (do * t).sum(axis=0)


def _forward(x, w, keep):       # the selective scan's way: keeps either way
    CALLS["forward", keep] += 1
    return toy_fwd(x, w)


def _backward(ops, do, kept):
    CALLS["backward"] += 1
    assert len(kept) == 2       # (out, the residual)
    return toy_bwd(*ops, kept[1], do)


def _bare(x, w):
    CALLS["bare"] += 1
    return toy_bare(x, w)


PAIR = kernel_pair(2, _bare, _forward, _backward)


def _toy_pair(ctx, ins, attrs):
    """Out = 2 pair(X, W): the op's output is not the kernel's."""
    out, saved = ctx.run_pair(PAIR, (ins["X"][0], ins["W"][0]))
    KEPT.append(saved is not None)
    out = out * 2
    if saved is not None:
        ctx.keep_for_grad(attrs, [out], saved)
    return {"Out": [out]}


@pytest.fixture
def toy_op():
    """The op, registered while a test runs and no longer (the registry is
    the process's: tests that walk it must not meet this one)."""
    reg.register_op(OP, _toy_pair)
    yield
    del reg._REGISTRY[OP]


def _build(train=True, remat=False):
    """x [8, 4] -> toy_pair (inside a recompute segment with `remat`) ->
    mean of squares (-> SGD); the names to fetch."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[4], dtype="float32")
    x.stop_gradient = False
    helper = LayerHelper(OP)
    with (fluid.layers.recompute if remat else contextlib.nullcontext)():
        w = helper.create_parameter(attr={}, shape=[4], dtype="float32")
        y = helper.create_tmp_variable("float32", shape=x.shape)
        helper.append_op(OP, inputs={"X": [x.name], "W": [w.name]},
                         outputs={"Out": [y.name]}, attrs={})
    loss = fluid.layers.mean(y * y)
    if not train:
        return [loss.name]
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return [loss.name, w.name + "@GRAD", x.name + "@GRAD"]


def _launches(fetch, feed):
    """The toy functions a step LAUNCHES, in program order: the jit calls
    of the step as the executor hands it to XLA, less the dead ones (the
    primal pass of a vjp over a `jax.checkpoint`, as XLA's DCE takes it)."""
    from jax._src.interpreters import partial_eval as pe

    from paddle_tpu.framework.core import np_dtype

    main = fluid.default_main_program()
    block = main.blocks[0]
    exe = fluid.Executor(fluid.CPUPlace())
    feed_vals = exe._prepare_feeds(block, feed)
    compiled = exe._compile(main, 0, feed_vals, fetch)

    def of_var(n):
        v = block._find_var_recursive(n)
        return jax.ShapeDtypeStruct(tuple(v.shape), np_dtype(v.dtype))

    jaxpr = jax.make_jaxpr(compiled.fn)(
        {n: of_var(n) for n in compiled.rw_state},
        {n: of_var(n) for n in compiled.external_reads}, feed_vals,
        jax.ShapeDtypeStruct((2,), np.uint32))

    def eqns(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    live, _ = pe.dce_jaxpr(jaxpr.jaxpr, [True] * len(jaxpr.jaxpr.outvars))
    return [e.params["name"] for e in eqns(live)
            if str(e.params.get("name", "")).startswith("toy_")]


def _reused() -> dict:
    fam = obs.REGISTRY.snapshot()["families"].get(
        "executor_grad_kernel_forward_total")
    return {(s["labels"]["op"], s["labels"]["reused"]): s["value"]
            for s in (fam["series"] if fam else [])}


CASES = {
    # a forward op and its generic_grad re-emission: ONE forward, keeping;
    # the re-emission is handed (out, residual) and launches the reverse
    # pass alone
    "grad_op": dict(launches=["toy_fwd", "toy_bwd"],
                    calls={("forward", True): 1, "backward": 1},
                    kept=[True, False], reused={(OP, "1"): 1.0}),
    # inside a `layers.recompute` segment the forward emission's launch
    # serves the forward pass; the replay under the segment's vjp is handed
    # nothing and differentiates the plain pair, whose ONE forward (the
    # rule's; its primal's is dead) keeps what the ONE reverse pass reads
    "recompute": dict(remat=True,
                      launches=["toy_fwd", "toy_fwd", "toy_bwd"],
                      calls={("forward", True): 1, "backward": 1},
                      kept=[True, False], reused={("recompute", "0"): 1.0}),
    # inference: the bare forward, nothing kept, nothing counted
    "is_test": dict(train=False, launches=["toy_bare"], calls={"bare": 1},
                    kept=[False], reused={}),
    # a grad op that receives another value than the output kept with the
    # residuals is handed nothing: the plain pair, a second forward launch
    "not_its_output": dict(another=True,
                           launches=["toy_fwd", "toy_fwd", "toy_bwd"],
                           calls={("forward", True): 1, "backward": 1},
                           kept=[True, False], reused={(OP, "0"): 1.0}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_run_pair_picks_the_function_the_emission_needs(case, toy_op,
                                                        monkeypatch):
    want = dict(CASES[case])
    train, remat = want.pop("train", True), want.pop("remat", False)
    if want.pop("another", False):
        real = reg.EmitContext.keep_for_grad
        monkeypatch.setattr(
            reg.EmitContext, "keep_for_grad",
            lambda self, attrs, outs, saved:
            real(self, attrs, [o + 0 for o in outs], saved))
    rng = np.random.RandomState(56)
    feed = {"x": rng.randn(8, 4).astype(np.float32)}
    fetch = _build(train, remat)
    CALLS.clear()
    del KEPT[:]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    w = np.asarray(scope.find(fluid.default_main_program().global_block()
                              .all_parameters()[0].name))
    got = [np.asarray(g) for g in exe.run(feed=feed, fetch_list=fetch)]

    assert KEPT == want["kept"]
    # under the plain rule the forward is called with keep False; a
    # `jax.checkpoint` traces the primal beside the rule
    plain = {k: v for k, v in CALLS.items() if k == ("forward", False)}
    others = {k: v for k, v in CALLS.items() if k != ("forward", False)}
    assert others == want["calls"], CALLS
    assert bool(plain) == (case in ("recompute", "not_its_output")), CALLS
    assert _reused() == want["reused"]

    def loss(x, w):
        y = 2 * jnp.tanh(x) * w
        return jnp.mean(y * y)

    ref = [loss(feed["x"], w)]
    if train:
        dx, dw = jax.grad(loss, argnums=(0, 1))(feed["x"], w)
        ref += [dw, dx]
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a.reshape(np.shape(b)), b, rtol=1e-5,
                                   atol=1e-6)
    assert _launches(_build(train, remat), feed) == want["launches"]


def test_pair_is_what_the_real_pairs_callers_use():
    """Outside any emitter: `.keeping` hands out (out, residual), its
    residual's cotangent is dropped, `.from_saved` returns the kept out and
    gives no gradient to what was kept, and the three differentiate to the
    same numbers."""
    rng = np.random.RandomState(5)
    x, w, do = (jnp.asarray(rng.randn(*s).astype(np.float32))
                for s in ((8, 4), (4,), (8, 4)))
    out, t = PAIR.keeping(x, w)
    np.testing.assert_array_equal(out, PAIR.bare(x, w))
    plain = jax.vjp(PAIR, x, w)[1](do)
    kept = jax.vjp(PAIR.keeping, x, w)[1]((do, jnp.ones_like(t)))
    saved = jax.vjp(lambda *a: PAIR.from_saved(*a), x, w, out, t)[1](do)
    assert saved[2] is None or not np.asarray(saved[2]).any()
    assert saved[3] is None or not np.asarray(saved[3]).any()
    for a, b, c in zip(plain, kept, saved):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
