"""The Mamba mixers' short convolution kernels (PR 72) in interpret mode (same
code path as the chip) against the op's plain lines (`llm_ops.causal_taps`,
the bias and SiLU) and their jax.vjp, the gate `usable`, the op's choice
between the two and what it counts, and the two layers that emit the op:
`layers.mamba2` hands the scan the convolution's three outputs with no slice
between, `layers.mamba` builds the program it built."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import (_conv_interpreted, _f32, _r, _series, _spy_on_calls,
                          _with_vjp)
from paddle_tpu import observability as obs
from paddle_tpu.ops import llm_ops
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops.pallas_kernels import ssm_conv as K

FAMILY = "causal_conv_silu_kernels_traced_total"
REUSED = "executor_grad_kernel_forward_total"


def _plain(offset, sections):
    """The op's plain lines on (X, Filter[, Bias]) -> one result a
    section."""
    def fn(x, w, b=None):
        C = w.shape[0]
        pre = llm_ops.causal_taps(
            x[..., offset:offset + C].astype(jnp.float32),
            w.astype(jnp.float32))
        if b is not None:
            pre = pre + b.astype(jnp.float32)
        ends = [sum(sections[:i]) for i in range(1, len(sections))]
        return tuple(jnp.split(jax.nn.silu(pre).astype(x.dtype), ends,
                               axis=-1))
    return fn


def _operands(B, T, W, sections, L, bias, dtype, seed=0):
    """(X, Filter[, Bias]) and one cotangent a section."""
    rs = np.random.RandomState(seed)
    C = sum(sections)
    ops = [jnp.asarray(rs.randn(B, T, W), dtype),
           jnp.asarray(0.5 * rs.randn(C, L), jnp.float32)]
    if bias:
        ops.append(jnp.asarray(rs.randn(C), jnp.float32))
    return ops, tuple(jnp.asarray(rs.randn(B, T, s), dtype) for s in sections)


@pytest.mark.parametrize("case,dtype,B,T,W,offset,sections,L,bias,how", [
    # Mamba-2's: z before the block, dt's columns after it, three sections
    ("mamba2", "bfloat16", 1, 48, 640 + 8, 128, (256, 128, 128), 4, True,
     dict(tile=16, cols=256)),
    ("mamba2", "float32", 1, 48, 640 + 8, 128, (256, 128, 128), 4, True,
     dict(tile=16, cols=256)),
    # Mamba-1's: the block first, z after it, one section, chunks of 128
    ("mamba", "bfloat16", 2, 48, 512, 0, (256,), 4, True,
     dict(tile=16, cols=128)),
    ("mamba", "float32", 2, 48, 512, 0, (256,), 4, True,
     dict(tile=16, cols=128)),
    ("no_bias", "bfloat16", 2, 48, 384, 128, (128, 128), 2, False,
     dict(tile=16, cols=256)),
    ("one_tap", "float32", 1, 32, 256, 128, (128,), 1, False,
     dict(tile=16, cols=256))])
def test_ssm_conv_kernels_match_the_plain_lines(case, dtype, B, T, W, offset,
                                                sections, L, bias, how):
    """Both kernels against the plain lines and their jax.vjp over three
    tiles of rows (the halo both ways, the start's zeros, the end's missing
    future), a block at an offset with columns after it, one section and
    three, bias and none: Out, dX's C columns (the rest of dX zero), the
    taps' and the bias's gradients.  float32 to a few last bits; bf16 the
    same bf16 numbers but for such bits (one rounding where the plain lines
    round), the parameters' gradients in float32.  Every row is held, so
    nothing leaks across the batch or around the sequence's ends."""
    ops, cts = _operands(B, T, W, sections, L, bias, jnp.dtype(dtype))
    how = dict(how, interpret=True, unroll=1)
    x, w, b = ops[0], ops[1], ops[2] if bias else None
    C = sum(sections)
    with jax.enable_x64(False):
        want, grads = _with_vjp(_plain(offset, sections), cts, *ops)
        got = K.ssm_conv_fwd(x, w, b, offset, sections, **how)
        dx, dw, db = K.ssm_conv_bwd(cts, x, w, b, offset, sections, **how)
    gx = np.asarray(_f32(grads[0]))
    assert not gx[..., :offset].any() and not gx[..., offset + C:].any()
    assert len(got) == len(sections)
    for a, c in zip(got + (dx,), want + (grads[0][..., offset:offset + C],)):
        assert a.shape == c.shape and a.dtype == c.dtype == x.dtype
        if dtype == "float32":
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5)
        else:
            assert (a == c).mean() > 0.999
            err = np.abs(_f32(a) - _f32(c))
            assert (err <= 2.0 ** -7 * np.abs(_f32(c)) + 1e-6).all()
    params = [(dw, grads[1])] + ([(db, grads[2])] if bias else [])
    assert db is not None or not bias
    for a, c in params:
        assert a.shape == c.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=5e-4)
    # row 0 has no history: the last tap alone
    first = x[:, 0, offset:offset + C].astype(jnp.float32) * w[:, L - 1]
    first = jax.nn.silu(first + (0.0 if b is None else b))
    np.testing.assert_allclose(
        np.concatenate([_f32(a[:, 0]) for a in got], axis=-1), first,
        rtol=2.0 ** -7, atol=1e-6)


def test_ssm_conv_pair_keeps_nothing(monkeypatch):
    """Differentiated, the pair launches the forward once and the backward
    once; `.keeping`, what a forward op is handed, returns Out (one a
    section) and no residual, and `.from_saved` on it launches the backward
    alone: the same gradients, bit for bit, dX as wide as X."""
    sections = (128, 128)
    ops, cts = _operands(1, 32, 384, sections, 4, True, jnp.float32, seed=1)
    conv = K.make_ssm_conv(128, sections, True, True)
    launched = _spy_on_calls(monkeypatch, K, ("fwd", "bwd"))
    with jax.enable_x64(False):
        out, back = jax.vjp(conv, *ops)
        want = back(cts)
        assert launched == ["fwd", "bwd"]
        del launched[:]
        kept = conv.keeping(*ops)
        assert launched == ["fwd"] and len(kept) == 1 and len(kept[0]) == 2
        del launched[:]
        again, back = jax.vjp(lambda *a: conv.from_saved(*a, *kept), *ops)
        got = back(cts)
        assert launched == ["bwd"]
    for a, b in zip(tuple(again) + got, tuple(out) + want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [g.shape for g in got] == [o.shape for o in ops]


@pytest.mark.parametrize("T,W,offset,sections,L,dtype,want", [
    (8192, 8512, 4096, (4096, 128, 128), 4, "bfloat16", True),   # Granite's
    (8192, 10240, 0, (5120,), 4, "bfloat16", True),     # Phi-4-mini-flash's
    (8192, 8512, 4096, (4096, 128, 128), 4, "float32", True),
    (48, 384, 128, (128, 128), 16, "float32", True),   # three tiles of 16
    (8192, 8512, 4096, (4096, 128, 128), 4, "float64", False),
    (8192, 8512, 4096, (4096, 128, 128), 4, "float16", False),
    (8200, 8512, 4096, (4096, 128, 128), 4, "bfloat16", False),  # T off
    (8, 384, 128, (128, 128), 4, "float32", False),
    (8192, 8512, 4096, (4096, 192, 64), 4, "bfloat16", False),  # off lanes
    (8192, 8512, 4032, (4096, 128, 128), 4, "bfloat16", False),
    (8192, 8512, 4096, (4224, 128, 128), 4, "bfloat16", False),  # beyond W
    (8192, 8512, 4096, (), 4, "bfloat16", False),
    (8192, 8512, 4096, (4096, 128, 128), 17, "bfloat16", False),  # a shift
    (8192, 8512, 4096, (4096, 128, 128), 0, "bfloat16", False)])  # over a
def test_ssm_conv_kernels_take_whole_tiles(T, W, offset, sections, L, dtype,
                                           want):
    assert K.usable(T, W, offset, sections, L, jnp.dtype(dtype)) is want


def test_ssm_conv_row_tile_and_vmem_ask():
    """At both cells' shapes a grid step is `short_conv.py`'s 256 whole rows
    in bf16 (the backward's three blocks, double-buffered, inside
    BLOCK_BUDGET); a short sequence takes the most whole chunks that divide
    it; a launch asks for VMEM only where its blocks pass the compiler's own
    share."""
    assert K.row_tile(8192, 4352, 2) == K.row_tile(8192, 5120, 2) == 256
    assert K.row_tile(8192, 5120, 4) == 256
    assert K.row_tile(48, 256, 2) == 16 and K.row_tile(8, 256, 2) == 0
    assert K.row_tile(8192, 16384, 4) == 64
    assert K._vmem_limit(4 << 20) is None
    assert K._vmem_limit(8 << 20) == (16 << 20) + K.VMEM_SPARE
    assert K._chunk_lanes(4096, 256) == 256 and K._chunk_lanes(128, 256) == 128
    assert K._chunk_lanes(384, 256) == 128


# ---------------------------------------------------------------------------
# the op: which emission, counted; what the grad op's re-emission launches


def _conv_step(values, attrs, outs=1):
    """A program of the one op under mean(sum of Out_s * weight_s), every
    input a parameter -> every Out and every input's gradient of one run."""
    fluid.reset()
    block = fluid.default_main_program().global_block()
    for name, value in values.items():
        block.create_parameter(name=name, shape=value.shape, dtype="float32")
    B, T, _ = values["X"].shape
    widths = attrs.get("sections") or [values["Filter"].shape[0]]
    names, loss = [], None
    for i, width in enumerate(widths):
        weight = _r(B, T, width, seed=11 + i).astype("float32")
        block.create_var(name=f"weight{i}", shape=weight.shape,
                         dtype="float32", stop_gradient=True)
        fluid.global_scope().set(f"weight{i}", weight)
        names.append(block.create_var(name=f"out{i}", dtype="float32",
                                      shape=weight.shape).name)
    block.append_op("causal_conv_silu",
                    inputs={slot: [slot] for slot in values},
                    outputs={"Out": names}, attrs=dict(attrs))
    for i, name in enumerate(names):
        term = fluid.layers.mean(fluid.layers.elementwise_mul(
            block.var(name), block.var(f"weight{i}")))
        loss = term if loss is None else loss + term
    grads = dict((p.name, g.name) for p, g in fluid.append_backward(loss))
    for name, value in values.items():
        fluid.global_scope().set(name, value)
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed={}, fetch_list=names + [grads[name] for name in values])
    return [np.asarray(a) for a in got]


def _values(T, W, C, L=4, bias=True, seed=3):
    values = {"X": _r(1, T, W, seed=seed), "Filter": _r(C, L, seed=seed + 1)}
    if bias:
        values["Bias"] = _r(C, seed=seed + 2)
    return {k: v.astype("float32") for k, v in values.items()}


@pytest.mark.parametrize("sections", [None, [128, 128, 128]])
def test_the_op_takes_the_kernels_on_a_tpu(sections, monkeypatch):
    """Where the trace targets one TPU the op's emitter launches the forward
    kernel once and keeps nothing but its result, and its grad op's
    re-emission, handed that, launches the backward alone
    (`executor_grad_kernel_forward_total` reused=1); the counter names the
    path for `fwd` and `grad`; the numbers are the plain lines', which the
    switch sends both emissions back to."""
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    launched = _conv_interpreted(monkeypatch)
    values = _values(32, 128 + 384 + 2, 384)
    attrs = {"offset": 128, **({"sections": sections} if sections else {})}
    obs.REGISTRY.reset()
    got = _conv_step(values, attrs)
    assert launched == ["fwd", "bwd"]
    assert _series(FAMILY) == [({"op": "fwd", "path": "pallas"}, 1.0),
                               ({"op": "grad", "path": "pallas"}, 1.0)]
    assert _series(REUSED) == [
        ({"op": "causal_conv_silu", "reused": "1"}, 1.0)]
    del launched[:]
    obs.REGISTRY.reset()
    monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    want = _conv_step(values, attrs)
    assert launched == []
    assert _series(FAMILY) == [({"op": "fwd", "path": "xla"}, 1.0),
                               ({"op": "grad", "path": "xla"}, 1.0)]
    assert _series(REUSED) == []
    assert len(got) == len(want) == len(sections or [0]) + 3
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() <= 2e-5 * np.abs(b).max()


@pytest.mark.parametrize("case,platform,mesh,shape,dtype,taps,path", [
    ("one_tpu", "tpu", None, (256, 640, 128, (256, 128, 128)), "bfloat16", 4,
     "pallas"),
    ("mamba_1", "tpu", None, (256, 512, 0, None), "float32", 4, "pallas"),
    ("the_cpu", "cpu", None, (256, 640, 128, (256, 128, 128)), "bfloat16", 4,
     "xla"),
    ("a_mesh", "tpu", object(), (256, 640, 128, (256, 128, 128)), "bfloat16",
     4, "xla"),
    ("float64", "tpu", None, (256, 640, 128, (256, 128, 128)), "float64", 4,
     "xla"),
    ("odd_width", "tpu", None, (256, 640, 128, (256, 192, 64)), "bfloat16",
     4, "xla"),
    ("odd_offset", "tpu", None, (256, 640, 64, (256, 128, 128)), "bfloat16",
     4, "xla"),
    ("off_the_rows", "tpu", None, (250, 640, 128, (256, 128, 128)),
     "bfloat16", 4, "xla"),
    ("seventeen_taps", "tpu", None, (256, 640, 128, (256, 128, 128)),
     "bfloat16", 17, "xla")])
def test_the_op_counts_the_path_it_takes(case, platform, mesh, shape, dtype,
                                         taps, path, monkeypatch):
    """One gate: one TPU, no mesh and a shape the kernels take (`usable`
    reads the input alone); what it refuses falls back to the plain lines
    and counts `xla` (abstractly traced: no kernel runs), one variable a
    section either way."""
    T, W, offset, sections = shape
    C = sum(sections) if sections else 256
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    ctx = reg.EmitContext(None, is_test=True)
    ctx.mesh = mesh
    obs.REGISTRY.reset()
    sds = lambda *s: [jax.ShapeDtypeStruct(s, jnp.dtype(dtype))]  # noqa: E731
    ins = {"X": sds(2, T, W), "Filter": sds(C, taps), "Bias": sds(C)}
    attrs = {"offset": offset, **({"sections": list(sections)}
                                  if sections else {})}
    with jax.enable_x64(dtype == "float64"):
        outs = jax.eval_shape(
            lambda ins: reg.get_op_info("causal_conv_silu").emit(
                ctx, ins, attrs)["Out"], ins)
    assert [(o.shape, o.dtype) for o in outs] == [
        ((2, T, s), jnp.dtype(dtype)) for s in sections or (C,)]
    assert _series(FAMILY) == [({"op": "fwd", "path": path}, 1.0)]


def test_the_op_refuses_sections_that_do_not_add_up():
    values = _values(8, 24, 12)
    with pytest.raises(Exception, match=r"in sections \(4, 4\)"):
        _conv_step(values, {"offset": 4, "sections": [4, 4]})
    with pytest.raises(Exception, match=r"in sections \(12, 0\)"):
        _conv_step(values, {"offset": 4, "sections": [12, 0]})


def test_sections_on_the_plain_path_are_the_one_results_columns():
    """On the CPU `sections` split the one result: the same numbers bit for
    bit, and the same gradients, as the op without them."""
    values = _values(10, 40, 24)
    whole = _conv_step(values, {"offset": 8})
    parts = _conv_step(values, {"offset": 8, "sections": [16, 4, 4]})
    np.testing.assert_array_equal(np.concatenate(parts[:3], axis=-1),
                                  whole[0])
    assert [p.shape[-1] for p in parts[:3]] == [16, 4, 4]
    assert all(np.abs(g).max() > 0 for g in parts[3:])


# ---------------------------------------------------------------------------
# the two layers that emit the op


def _layer_program(kind):
    fluid.reset()
    x = fluid.layers.data("x", shape=[12, 16], dtype="float32")
    y = (fluid.layers.mamba2(x, n_heads=4, head_dim=8, d_state=4, n_groups=2,
                             chunk=4) if kind == "mamba2"
         else fluid.layers.mamba(x, d_state=4))
    return fluid.layers.mean(fluid.layers.elementwise_mul(y, y))


def test_mamba2_hands_the_scan_the_convolutions_outputs():
    """`layers.mamba2` builds ONE `causal_conv_silu` whose three outputs are
    the scan's X, B and C: no `slice` of x, B or C stands between the two
    (the one `slice` left is dt's, of the projection); the same eight
    parameters in the same order."""
    _layer_program("mamba2")
    block = fluid.default_main_program().global_block()
    ops = block.ops
    (conv,) = [op for op in ops if op.type == "causal_conv_silu"]
    (scan,) = [op for op in ops if op.type == "ssd_scan"]
    assert conv.attrs["sections"] == [32, 8, 8]
    assert conv.attrs["offset"] == 32 and conv.attrs["part"] == "ssd.conv"
    assert conv.outputs["Out"] == [scan.inputs[s][0] for s in "XBC"]
    assert [block.var(n).shape[-1] for n in conv.outputs["Out"]] == [32, 8, 8]
    (dt,) = [op for op in ops if op.type == "slice"]
    assert dt.inputs["Input"] == conv.inputs["X"]
    assert dt.outputs["Out"] == scan.inputs["Dt"]
    assert (dt.attrs["starts"], dt.attrs["ends"]) == ([80], [84])
    assert [tuple(p.shape) for p in block.all_parameters()] == [
        (16, 32 + 48 + 4), (48, 4), (48,), (4,), (4,), (4,), (32,), (32, 16)]


def test_mamba_builds_the_program_it_built():
    """`layers.mamba` passes no `sections`: its ops are the parent's, op for
    op, and the convolution's desc carries its part and nothing else."""
    _layer_program("mamba")
    ops = fluid.default_main_program().global_block().ops
    assert [op.type for op in ops][:8] == [
        "mul", "causal_conv_silu", "mul", "slice", "mul", "selective_scan",
        "silu_gate", "mul"]
    conv = ops[1]
    assert {k: v for k, v in conv.attrs.items()
            if not k.startswith("__")} == {"part": "ssm.conv"}
    assert len(conv.outputs["Out"]) == 1


@pytest.mark.parametrize("kind,first,second", [
    ("mamba2", 1.40283334, 0.529328108),
    ("mamba", 0.00140027609, 0.00139428)])
def test_the_layers_train_a_step_to_the_parents_loss(kind, first, second):
    """One SGD step of each layer on the CPU from seeded weights: the loss
    before and after it are the parent commit's (PR 71's tree, the same
    script) to float32's rounding."""
    loss = _layer_program(kind)
    fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.random.RandomState(5).uniform(-1, 1, (2, 12, 16))
            .astype("float32")}
    got = [np.asarray(exe.run(feed=feed, fetch_list=[loss])[0]).item()
           for _ in range(2)]
    np.testing.assert_allclose(got, [first, second], rtol=2e-6)


# ---------------------------------------------------------------------------
# AOT: the two kernels alone, compiled for a described v5e at both cells'
# shapes (`slow`: nothing that compiles for the chip runs in tier-1, PR 68)


@pytest.mark.slow
@pytest.mark.parametrize("kernel", [K.FWD, K.BWD])
@pytest.mark.parametrize("cell,W,offset,sections", [
    ("granite4h_train_t8192", 8512, 4096, (4096, 128, 128)),
    ("phi4flash_train_t8192", 10240, 0, (5120,))])
def test_ssm_conv_kernels_compile_for_a_v5e_at_the_cells_shapes(
        cell, W, offset, sections, kernel, v5e):
    """X [1, 8192, W] bf16 under 4 taps and a bias: ONE Mosaic call, named
    as the benchmark's readers find it: by the scope it was emitted in."""
    import functools
    import re

    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.observability.attribution import part_scope

    one = SingleDeviceSharding(v5e)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    C = sum(sections)
    x, w, b = (sds((1, 8192, W), jnp.bfloat16), sds((C, 4), jnp.bfloat16),
               sds((C,), jnp.bfloat16))
    douts = tuple(sds((1, 8192, s), jnp.bfloat16) for s in sections)

    def scoped(fn):
        @functools.wraps(fn)
        def call(*a):
            with part_scope("ssd.conv"):
                return fn(*a, offset, sections)
        return call

    with jax.enable_x64(False):
        if kernel == K.FWD:
            lowered = jax.jit(scoped(K.ssm_conv_fwd)).lower(x, w, b)
        else:
            lowered = jax.jit(scoped(K.ssm_conv_bwd)).lower(douts, x, w, b)
        text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    (name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "pdtpu.ssd.conv" in name and kernel in name, name
