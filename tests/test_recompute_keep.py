"""What a `layers.recompute` segment keeps (PR 66): the values its builder
names are held from the forward to the backward by the program's own
`keep_for_grad` protocol, and the replay puts each in place of the one it
makes, so the op that makes it runs once a step.

On the CPU, in float32: the numbers are the plain segment's and the plain
ops', bit for bit for a gated MLP and to rounding with a kernel pair in front
of the kept product (the selective scan, interpreted, reached as
tests/test_kernel_forward_once.py reaches it; those two cases, three steps
to compile, run from tests/test_phi4flash.py: a file of six tests starts
last under the driver's scheduler, tests/conftest.py).  Compiled for a
described v5e: the products leave the step and no Mosaic call is added, the
case a `jax.checkpoint` policy fails on (the last test, in plain JAX)."""

import contextlib
import functools
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _by_labels, _described_step, _startup
from paddle_tpu import observability as obs

KEPT = "recompute_kept_traced_total"
MODES = ("plain", "segment", "keep")
B, T, DIM, WIDE = 2, 64, 64, 128


def _kept() -> dict:   # {(pass, unit): count}
    return _by_labels(KEPT, "pass", "unit")


def _gated_mlp(h, kept):
    """h + W_down(SiLU(W_gate h) * (W_up h)); `kept` gains the two
    up-projections BEFORE the activation."""
    pre, up = (fluid.layers.fc(h, WIDE, num_flatten_dims=2, bias_attr=False)
               for _ in range(2))
    kept += [pre, up]
    gated = fluid.layers.elementwise_mul(fluid.layers.silu(pre), up)
    return h + fluid.layers.fc(gated, DIM, num_flatten_dims=2,
                               bias_attr=False)


def _build(mode, mixer=None):
    """x [B, T, DIM] -> (`mixer`, a Mamba layer, ->) a gated MLP -> mean of
    squares -> SGD, the block as plain ops, a recompute segment, or a segment
    that keeps its wide products; the names to fetch: the loss and every
    parameter's gradient."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, DIM], dtype="float32")
    x.stop_gradient = False
    kept = []
    scope = {"plain": contextlib.nullcontext,
             "segment": fluid.layers.recompute,
             "keep": lambda: fluid.layers.recompute(keep=kept)}[mode]
    with scope():
        h = x
        if mixer == "mamba":
            since = len(fluid.default_main_program().current_block().ops)
            h = h + fluid.layers.mamba(h, d_state=8)
            ops = fluid.default_main_program().current_block().ops[since:]
            kept += [op.outputs["Out"][0] for op in ops
                     if op.attrs.get("part") == "ssm.in_proj"]
        y = _gated_mlp(h, kept)
    loss = fluid.layers.mean(y * y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    block = fluid.default_main_program().global_block()
    return [loss.name, x.name + "@GRAD"] + [
        p.name + "@GRAD" for p in block.all_parameters()]


FEED = {"x": np.random.RandomState(66).randn(B, T, DIM).astype(np.float32)}
DRAWN = {None: {}, "mamba": {}}     # a mixer's programs draw once a module


def _step(mode, mixer):
    fetch = _build(mode, mixer)
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, DRAWN[mixer])
    return [np.asarray(g) for g in exe.run(feed=FEED, fetch_list=fetch)]


# what a test's cases are held to, stepped once for them all: a step is a
# program to compile (10 to 15 s with the interpreted scan)
_want = functools.cache(_step)


@pytest.mark.parametrize("mode", MODES[1:])
def test_a_gated_mlps_numbers_are_the_plain_ops_bit_for_bit(mode):
    """(a) The loss, the input's gradient and every parameter's: a segment,
    and a segment that keeps the two up-projections, against the same ops
    with no segment."""
    want = _want("plain", None)
    obs.REGISTRY.reset()
    got = _step(mode, None)
    assert len(got) == len(want) == 5
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.abs(b).max() > 0
        np.testing.assert_array_equal(a, b)
    # (f) the counter's series after one trace: two [B, T, WIDE] float32
    # values kept by the forward emission and used by the replay; nothing
    # where nothing is named
    size = 2.0 * B * T * WIDE * 4
    assert _kept() == ({} if mode == "segment" else {
        ("forward", "bytes"): size, ("forward", "values"): 2.0,
        ("replay", "bytes"): size, ("replay", "values"): 2.0})


def test_keep_names_survive_the_descs_round_trip_and_must_be_made():
    """(d) `keep_names` is an attribute of the op's desc: through
    `proto_io` and back it names the same values and the loaded program's
    step is the built one's; a name no op of the segment makes, or one
    named twice, is refused where the scope closes."""
    from paddle_tpu.framework import proto_io

    fetch = _build("keep")
    main = fluid.default_main_program()
    op, = [o for o in main.global_block().ops if o.type == "recompute"]
    names = op.attrs["keep_names"]
    made = [n for o in main.blocks[op.attrs["sub_block"]].ops
            if o.type == "mul" for n in o.output_names()]
    assert len(names) == 2 and names == made[:2]
    exe = fluid.Executor(fluid.CPUPlace())
    drawn = {}
    _startup(exe, drawn)
    want = [np.asarray(g) for g in exe.run(feed=FEED, fetch_list=fetch)]

    loaded = proto_io.parse_program(proto_io.serialize_program(main))
    op2, = [o for o in loaded.global_block().ops if o.type == "recompute"]
    assert op2.attrs["keep_names"] == names
    obs.REGISTRY.reset()
    _startup(exe, drawn)        # the step above moved the parameters
    got = exe.run(loaded, feed=FEED, fetch_list=fetch)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert _kept()[("replay", "values")] == 2.0

    for bad in (["nobody_makes_this"], lambda up: [up, up]):
        fluid.reset()
        x = fluid.layers.data("x", shape=[T, DIM], dtype="float32")
        keep = []
        with pytest.raises(ValueError, match="recompute: keep names"):
            with fluid.layers.recompute(keep=keep):
                up = fluid.layers.fc(x, WIDE, num_flatten_dims=2)
                keep += bad if isinstance(bad, list) else bad(up)
    # a value made OUTSIDE the scope is not the segment's to keep either
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, DIM], dtype="float32")
    outside = fluid.layers.fc(x, WIDE, num_flatten_dims=2)
    with pytest.raises(ValueError, match="recompute: keep names"):
        with fluid.layers.recompute(keep=[outside]):
            fluid.layers.fc(outside, DIM, num_flatten_dims=2)


def _segments(main):
    """[(kept names' last dims) of each recompute op of `main`]."""
    block = main.global_block()
    return [[block._find_var_recursive(n).shape[-1]
             for n in op.attrs.get("keep_names", [])]
            for op in block.ops if op.type == "recompute"]


def test_phi4flash_builder_names_19_values_and_salas_names_none():
    """(e) The two builders whose every block is a segment, at toy widths:
    Phi-4-mini-flash's eight segments keep the MLP's two products of
    `dense_dim` each and the three Mamba layers' [u' | z] of 2 x d_inner
    before them, 19 values; with `remat_keep=()` none, and the program is
    otherwise the same; SALA's four name none (its step has no byte left:
    ROADMAP W11)."""
    from paddle_tpu.models import transformer as tr

    def phi(**more):
        fluid.reset()
        tr.build_phi4flash_lm_train_program(
            64, vocab_size=48, dim=32, layer_indices=list(range(12, 20)),
            total_layers=32, n_heads=8, n_kv_heads=4, dense_dim=96,
            sliding_window=16, d_state=4, dt_rank=2, dtype="float32", **more)
        return fluid.default_main_program()

    main = phi()
    mamba, other = [128, 96, 96], [96, 96]        # d_inner 64, dense_dim 96
    assert _segments(main) == [mamba, other, mamba, other, mamba, other,
                               other, other]
    assert sum(map(len, _segments(main))) == 19
    types = [op.type for b in main.blocks for op in b.ops]
    bare = phi(remat_keep=())
    assert _segments(bare) == [[]] * 8
    assert [op.type for b in bare.blocks for op in b.ops] == types
    assert _segments(phi(remat_keep=("mlp.up",))) == [other] * 8
    assert _segments(phi(remat=False)) == []
    with pytest.raises(ValueError, match="remat_keep"):
        phi(remat_keep=("attn.qkv",))

    fluid.reset()
    tr.build_sala_lm_train_program(
        256, vocab_size=64, dim=32,
        mixer_types=["minicpm4", "lightning-attn"] * 2,
        layer_indices=[0, 1, 2, 3], total_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=8, linear_heads=4, dense_dim=64, dtype="float32")
    assert _segments(fluid.default_main_program()) == [[]] * 4


# ---------------------------------------------------------------------------
# compiled for a described v5e: the products leave, no kernel is added


def _compiled_step(device, fetch):
    """The step for `fetch` compiled for `device` -> its text and
    `temp_bytes`."""
    done = _described_step(device, {"x": np.zeros(
        (AOT_B, AOT_T, AOT_DIM), np.float32)}, fetch).compile()
    return done.as_text(), done.memory_analysis().temp_size_in_bytes


AOT_B, AOT_T, AOT_DIM = 2, 1024, 256
_PRODUCT = re.compile(r"= \S+ (?:convolution|dot)\(")


@pytest.mark.slow
def test_aot_kept_products_leave_the_step_and_no_kernel_is_added(
        v5e, monkeypatch):
    """(c) A Mamba layer and a gated MLP in one segment at [2, 1024, 256]:
    with the three wide products kept the compiled step holds three
    products fewer, the same Mosaic calls (the scan's forward twice and its
    reverse pass once, and the short convolution's in front of it likewise:
    the replay's kernels still run) and at most the kept bytes more.  A `jax.checkpoint` policy naming the same values adds a
    THIRD forward launch instead (the next test), which is why the segment
    keeps by the program's own protocol."""
    from paddle_tpu.ops.pallas_kernels import selective_scan as ss
    from paddle_tpu.ops.pallas_kernels import ssm_conv

    monkeypatch.setitem(globals(), "T", AOT_T)
    monkeypatch.setitem(globals(), "DIM", AOT_DIM)
    monkeypatch.setitem(globals(), "WIDE", 4 * AOT_DIM)
    read = {}
    for mode in ("segment", "keep"):
        obs.REGISTRY.reset()
        text, temp = _compiled_step(v5e, _build(mode, "mamba")[:1])
        read[mode] = (len(_PRODUCT.findall(text)),
                      text.count("tpu_custom_call"), temp)
        # by the instruction's own name (the scan's lines name the
        # convolution's result among their operands)
        launches = [len(re.findall(rf"%{k}[.\d]* = [^\n]*tpu_custom_call",
                                   text))
                    for k in (ss.FWD, ss.BWD, ssm_conv.FWD, ssm_conv.BWD)]
        assert launches == [2, 1, 2, 1] and read[mode][1] == 6, read
    kept = _kept()
    assert kept[("replay", "values")] == kept[("forward", "values")] == 3.0
    (products, calls, temp), (products_k, calls_k, temp_k) = (
        read["segment"], read["keep"])
    assert products - products_k == 3, read
    assert calls_k == calls, read
    assert temp_k - temp <= kept[("forward", "bytes")], (read, kept)


@pytest.mark.slow
def test_aot_a_checkpoint_policy_launches_the_kernel_a_third_time(v5e):
    """Why not `jax.checkpoint(policy=save_only_these_names)`: in this
    framework the forward op's emission and the grad op's `jax.vjp` of the
    re-emitted segment are two computations that XLA merges only where they
    are plain HLO.  A policy saves the named value from the vjp's OWN primal
    pass; behind a Mosaic call that pass shares nothing with the forward's,
    so the kernel is launched for the forward, for the primal pass and for
    the replay, and the named product is made in the first two.  In plain
    JAX, with a kernel in front of a named product: three launches under
    the policy, two without it."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from jax.experimental import pallas as pl
    from jax.sharding import SingleDeviceSharding

    def double(x):      # any kernel: XLA never merges two Mosaic calls
        def body(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2
        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    @jax.custom_vjp
    def kernel(x):
        return double(x)

    kernel.defvjp(lambda x: (double(x), None), lambda _, g: (double(g),))

    def segment(x, w1, w2):
        up = checkpoint_name(kernel(x) @ w1, "up")
        return jnp.tanh(up) @ w2

    def step(policy):
        def fn(x, w1, w2):
            y = segment(x, w1, w2)                  # the forward op
            _, back = jax.vjp(jax.checkpoint(segment, policy=policy),
                              x, w1, w2)            # its grad op
            return back(2 * y)
        return fn

    one = SingleDeviceSharding(v5e)
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
              for s in ((512, 256), (256, 1024), (1024, 256))]
    launches = {}
    for name, policy in (
            ("none", None),
            ("policy", jax.checkpoint_policies.save_only_these_names("up"))):
        text = jax.jit(step(policy)).lower(*shapes).compile().as_text()
        launches[name] = text.count("tpu_custom_call")
    # forward, replay, the reverse pass; and the policy's primal pass
    assert launches == {"none": 3, "policy": 4}, launches
