"""The flash forward runs once a layer: the forward op keeps (out, lse) on
the EmitContext and its generic_grad differentiates through them instead
of launching `flash_fwd` a second time (ops/registry.py keep_for_grad,
ops/pallas_kernels/flash_attention.py make_flash_train).

On the CPU the Pallas path is reached as tests/test_kernel_dispatch.py does
it: the emit context claims a TPU target and the kernels run in interpret
mode.  The AOT test compiles the real kernels for a described v5e."""

import re

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import (_by_labels, _conv_interpreted, _described_step,
                          _startup)
from paddle_tpu import observability as obs
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

COUNTER = "executor_grad_kernel_forward_total"
SDPA = "scaled_dot_product_attention"
T, HEADS = 128, 2
# the model width by the path the emitter takes there: heads of 16 go the
# [B, H, T, D] way, heads of 64 ride two to a lane block of [B, T, H * D]
WIDTHS = {"flash": 32, "flash_packed": 128}
LAYERS = "attention_layers_traced_total"


def _counter() -> dict:
    return _by_labels(COUNTER, "op", "reused")


def _paths() -> dict:
    return _by_labels(LAYERS, "layout", "path")


@pytest.fixture(params=sorted(WIDTHS))
def pallas_on_cpu(monkeypatch, request):
    """Every trace claims a TPU target and the flash kernels interpret;
    returns the list of forward-kernel launches traced, `.path` the way
    through the emitter the parameter's width takes."""

    class Launches(list):
        path = request.param

    launches = Launches()
    monkeypatch.setitem(globals(), "DIM", WIDTHS[request.param])
    real_train, real_fwd = fa.make_flash_train, fa.flash_attention_fwd

    def spy_fwd(q, k, v, **kw):
        launches.append(q.shape)
        return real_fwd(q, k, v, **kw)

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(fa, "flash_attention_fwd", spy_fwd)
    monkeypatch.setattr(
        fa, "make_flash_train",
        lambda causal=False, scale=None, interpret=False, heads=None:
        real_train(causal=causal, interpret=True, block_q=64, block_k=64,
                   heads=heads))
    # the memo holds closures over the real forward: start from none, and
    # leave none behind that holds the spy
    monkeypatch.setattr(fa, "_TRAIN_CACHE", {})
    return launches


def _attention_block(remat=False):
    """x -> multi-head attention -> mean -> SGD; the loss, the parameters'
    gradient names and a feed."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, DIM], dtype="float32")
    y = fluid.layers.multi_head_attention(x, x, x, HEADS, causal=True)
    loss = fluid.layers.mean(y * y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main = fluid.default_main_program()
    if remat:
        for op in main.global_block().ops:
            if (op.type == "generic_grad"
                    and op.attrs["__fwd_type__"] == SDPA):
                op.attrs["__remat__"] = True
    grads = [p.name + "@GRAD" for p in main.global_block().all_parameters()]
    feed = {"x": np.random.RandomState(7).randn(2, T, DIM)
            .astype(np.float32)}
    return loss, grads, feed


def _step(remat=False):
    loss, grads, feed = _attention_block(remat)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got = exe.run(feed=feed, fetch_list=[loss] + grads)
    assert len(grads) == 4  # Wq, Wk, Wv, Wo
    return [np.asarray(g) for g in got]


_KEPT = {}


def _kept_pair_step(launches):
    """`_step()` as it is, once a path (a program to compile) -> (its
    results, what it launched, the two counters after it)."""
    if launches.path not in _KEPT:
        _KEPT[launches.path] = (_step(), list(launches), _counter(),
                                _paths())
    return _KEPT[launches.path]


def test_saved_pair_gives_the_fallbacks_bits(pallas_on_cpu, monkeypatch):
    """Loss and every parameter gradient are equal to the last bit with
    the kept pair and without it, and each path is the one it claims."""
    with_pair, launched, counted, paths = _kept_pair_step(pallas_on_cpu)
    assert len(launched) == 1, launched
    assert counted == {(SDPA, "1"): 1.0}
    # the layer's layout, the emitter's path, and the forward emission
    # alone counted (the grad op's re-emission adds nothing)
    assert paths == {("bthd", pallas_on_cpu.path): 1.0}
    packed = pallas_on_cpu.path == "flash_packed"
    assert launched[0] == ((2, T, DIM) if packed
                           else (2, HEADS, T, DIM // HEADS))

    del pallas_on_cpu[:]
    # the table emptied before the grad op: nothing is ever kept
    monkeypatch.setattr(reg.EmitContext, "keep_for_grad",
                        lambda self, attrs, outs, saved: None)
    fallback = _step()
    assert len(pallas_on_cpu) == 2, pallas_on_cpu
    assert _counter() == {(SDPA, "0"): 1.0}

    assert np.isfinite(with_pair[0]) and np.abs(with_pair[1]).max() > 0
    for a, b in zip(with_pair, fallback):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_pair_of_another_value_is_not_used(pallas_on_cpu, monkeypatch):
    """The kept pair is the grad op's only when the forward output the
    grad op receives IS the one kept with it."""
    real = reg.EmitContext.keep_for_grad
    monkeypatch.setattr(
        reg.EmitContext, "keep_for_grad",
        lambda self, attrs, outs, saved:
        real(self, attrs, [o + 0 for o in outs], saved))
    _step()
    assert len(pallas_on_cpu) == 2
    assert _counter() == {(SDPA, "0"): 1.0}


def test_remat_grad_op_still_recomputes(pallas_on_cpu):
    """`__remat__` asks for the forward again in the backward: the kept
    pair is left alone, and the gradients are the same numbers."""
    plain = _kept_pair_step(pallas_on_cpu)[0]
    del pallas_on_cpu[:]
    remat = _step(remat=True)
    # jax.checkpoint traces the custom_vjp's primal as well as its rule
    assert len(pallas_on_cpu) >= 2, pallas_on_cpu
    assert _counter() == {(SDPA, "0"): 1.0}
    for a, b in zip(plain, remat):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_forward_emission_alone_stays_differentiable(pallas_on_cpu):
    """A caller that differentiates the forward emission itself (the
    program pipeline's jax.grad over a stage) meets a custom_vjp, as
    before, and the flash backward's numbers."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.ring_attention import attention

    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray((rng.randn(1, 2, T, 16) * 0.3)
                           .astype(np.float32)) for _ in range(3))

    def through_op(q, k, v):
        ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=False)
        out = attention_ops.scaled_dot_product_attention(
            ctx, {"Q": [q], "K": [k], "V": [v]},
            {"causal": True, "__uid__": 5})["Out"][0]
        return (out * out).sum()

    def dense(q, k, v):
        out = attention(q, k, v, causal=True)
        return (out * out).sum()

    got = jax.jit(jax.grad(through_op, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
    assert _counter() == {}  # no grad op, nothing to count


# ---------------------------------------------------------------------------
# the selective scan's pair (PR 55): one forward launch a trace of the layer


def _step_kernels(fetch, feed):
    """[(kernel, outputs)] of the Pallas calls in the step as the executor
    hands it to XLA, less the dead ones (a call whose results feed nothing
    goes, as XLA's DCE takes it: the primal pass of generic_grad's jax.vjp
    over a `jax.checkpoint`), in program order: what a step LAUNCHES (a spy
    on the Python calls also sees what is traced and never run)."""
    import jax
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
    return [(e.params["name"], len(e.outvars)) for e in eqns(live)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("remat,reused", [
    (False, {("selective_scan", "1"): 1.0, ("causal_conv_silu", "1"): 1.0}),
    (True, {("recompute", "0"): 1.0})])
def test_mamba_layers_reverse_pass_is_handed_the_kept_states(remat, reused,
                                                             monkeypatch):
    """A Mamba layer at toy size.  Alone, its grad op's re-emission is
    handed what the forward op kept (Out and the chunks' states: two
    outputs) and launches the reverse pass alone.  Inside a
    `layers.recompute` segment the replay under the segment's vjp launches
    the forward ONCE more, keeping the states, and the reverse pass is
    handed those: never a forward of its own, never a third.  The short
    convolution in front of the scan goes the same way with nothing to keep
    but its result: one forward launch a forward emission and a replay, one
    backward.  The gradients are the plain emission's."""
    import contextlib

    from paddle_tpu.ops.pallas_kernels import selective_scan as ss
    from paddle_tpu.ops.pallas_kernels import ssm_conv

    feed = {"x": np.random.RandomState(7).randn(2, 64, 64)
            .astype(np.float32)}

    def build():
        fluid.reset()
        x = fluid.layers.data("x", shape=[64, 64], dtype="float32")
        with (fluid.layers.recompute if remat else contextlib.nullcontext)():
            y = fluid.layers.mamba(x, d_state=8)
        loss = fluid.layers.mean(y * y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        block = fluid.default_main_program().global_block()
        return [loss.name] + [p.name + "@GRAD"
                              for p in block.all_parameters()]

    drawn = {}
    def step():
        fetch = build()
        exe = fluid.Executor(fluid.CPUPlace())
        _startup(exe, drawn)
        return [np.asarray(g) for g in exe.run(feed=feed, fetch_list=fetch)]

    want = step()
    assert _counter() == {}
    real_make = ss.make_selective_scan
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(ss, "make_selective_scan",
                        lambda: real_make(ss.CHUNK, True))
    _conv_interpreted(monkeypatch)
    got = step()
    assert _counter() == reused
    assert len(got) == 10       # the loss and the mixer's nine parameters
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()
    forwards = [(ssm_conv.FWD, 1), (ss.FWD, 2)] * (2 if remat else 1)
    assert _step_kernels(build(), feed) == forwards + [
        (ss.BWD, 6), (ssm_conv.BWD, 2)]


# ---------------------------------------------------------------------------
# flash_score_elements_total: how much of the square the causal kernels do

SCORES = "flash_score_elements_total"


def _scores() -> dict:
    return _by_labels(SCORES, "kernel", "part")


def test_score_counter_is_the_schedules_geometry():
    """Tracing the three kernels at GPT-2-medium's shape (T 1024 under the
    default blocks) counts the square and what the strips of the walk
    compute of it: at most 75% a kernel, and what `_schedule` says."""
    import jax
    import jax.numpy as jnp

    fluid.reset()
    B, H, T, D = 2, 4, 1024, 64
    x = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((B * H, T), jnp.float32)
    jax.eval_shape(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, causal=True), x, x, x)
    jax.eval_shape(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
        q, k, v, o, l, do, causal=True), x, x, x, x, lse, x)
    got = _scores()
    # on the chip T 1024 runs one block a head; the tracing here is the
    # CPU's and keeps the (512, 1024) asked for only in interpret mode
    bq, bk = fa._snap_blocks(512, 1024, T, causal_head=D)
    assert (bq, bk) == (1024, 1024)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        plan = fa._schedule(T, bq, bk, fa._strip_rows(kernel, bq, bk))
        assert T * T / 2 < plan.computed <= 0.75 * T * T
        assert got[(kernel, "square")] == B * H * T * T
        assert got[(kernel, "computed")] == B * H * plan.computed
    assert len(got) == 6
    assert (sum(v for (_, part), v in got.items() if part == "computed")
            / sum(v for (_, part), v in got.items() if part == "square")
            ) == 0.5625

    # a non-causal call has no half to skip: the family gets nothing
    fluid.reset()
    jax.eval_shape(lambda q, k, v: fa.flash_attention_fwd(q, k, v), x, x, x)
    jax.eval_shape(lambda q, k, v: fa.flash_attention(q, k, v), x, x, x)
    jax.eval_shape(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
        q, k, v, o, l, do), x, x, x, x, lse, x)
    assert _scores() == {}


def test_score_counter_counts_at_trace_time_only(pallas_on_cpu):
    """Counted when the step is traced, once a compile: a second run of
    the compiled step adds nothing."""
    loss, grads, feed = _attention_block()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=feed, fetch_list=[loss])
    first = _scores()
    assert {k for k, _part in first} == {"flash_fwd", "flash_bwd_dq",
                                         "flash_bwd_dkv"}
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        # T 128 under (64, 64) blocks: three of four blocks visited, the
        # two crossed ones walked in strips of an eighth
        assert (first[(kernel, "computed")] / first[(kernel, "square")]
                == (1 + 2 * 0.5625) / 4)
    exe.run(feed=feed, fetch_list=[loss])
    assert _scores() == first


# ---------------------------------------------------------------------------
# AOT: the real kernels, compiled for a described v5e


def _lowered_step(loss, device, batch, seq_len, stage="lower"):
    toks = np.zeros((batch, seq_len, 1), np.int64)
    return _described_step(device, {"tokens": toks, "targets": toks},
                           [loss.name], stage)


def _kernel_calls(text):
    """{kernel: Mosaic calls} of a compiled step's text, by the kernels'
    names (the longest first: a name is another's head)."""
    calls = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    kinds = {}
    for name in calls:
        for kernel in ("head_norm_rope_bwd", "head_norm_rope",
                       "flash_bwd_dq", "flash_bwd_dkv", "flash_fwd"):
            if kernel in name:
                kinds[kernel] = kinds.get(kernel, 0) + 1
                break
        else:
            kinds[name] = 1
    return kinds


@pytest.mark.slow
def test_aot_one_forward_kernel_a_layer(v5e):
    """A 2-layer LM at T 1024 and head size 64: the compiled step holds one
    forward, one dq and one dkv Mosaic call a layer (it held two forwards),
    and every grad op reused its forward op's pair."""
    from paddle_tpu.models.transformer import build_lm_train_program

    layers = 2
    loss = build_lm_train_program(1024, vocab_size=512, dim=128,
                                  n_layers=layers, n_heads=2)
    lowered = _lowered_step(loss, v5e, batch=2, seq_len=1024)
    # the kernels read Q, K, V where the projections left them: the step
    # as JAX hands it to XLA moves no [B, T, H, D] tensor to [B, H, T, D]
    # or back (four a layer forward, four backward before the layout)
    relayouts = re.findall(
        r"stablehlo\.transpose[^\n]*tensor<2x(?:1024x2|2x1024)x64xbf16>",
        lowered.as_text())
    assert not relayouts, relayouts
    kinds = _kernel_calls(lowered.compile().as_text())
    assert kinds == {"flash_fwd": layers, "flash_bwd_dq": layers,
                     "flash_bwd_dkv": layers}, kinds
    assert _counter() == {(SDPA, "1"): float(layers)}
    assert _paths() == {("bthd", "flash_packed"): float(layers)}
    # and each layer's dq kernel makes the backward's delta itself (PR 47)
    assert _by_labels("flash_backward_delta_traced_total", "where") == {
        "dq": float(layers)}


@pytest.mark.slow
@pytest.mark.parametrize("head_dim,kv_heads,path", [
    (128, 1, "pallas"), (64, 2, "pallas_packed")],
    ids=["heads_of_128", "pairs_of_64"])
def test_aot_one_qk_prep_kernel_each_way_for_q_and_for_k(v5e, head_dim,
                                                         kv_heads, path):
    """A 2-layer decoder with RoPE and a per-head QK-norm at T 1024, four
    query heads on `kv_heads`: the compiled step holds ONE `head_norm_rope`
    call for Q and one for K a layer and as many backward calls (the grad
    op launches no forward), beside the three flash kernels;
    `executor_grad_kernel_forward_total` keeps the attention op's series
    alone; of the transposes between [B, T, H, D] and [B, H, T, D] in the
    step JAX hands to XLA, V's and the output's are left."""
    from paddle_tpu.models import transformer as tr

    layers, heads, T = 2, 4, 1024
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[T, 1], dtype="int64")
    targets = fluid.layers.data("targets", shape=[T, 1], dtype="int64")
    logits = tr.decoder_lm(
        tokens, 512, heads * head_dim, layers, heads, max_len=T,
        dtype="bfloat16", norm="rms_norm", positions="rope",
        rope_theta=1e6, qk_norm="head", n_kv_heads=kv_heads)
    loss = tr.lm_loss(logits, targets)
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    lowered = _lowered_step(loss, v5e, batch=2, seq_len=T)
    def moved(n):  # transposes to or from [B, n, T, D] in the step
        return len(re.findall(
            r"stablehlo\.transpose[^\n]*-> tensor<2x(?:1024x%dx%d|%dx1024x%d)"
            r"xbf16>" % (n, head_dim, n, head_dim), lowered.as_text()))

    # the output's merge and its gradient; V's split and its gradient:
    # nothing of Q's or K's (it was three times as many)
    assert (moved(heads), moved(kv_heads)) == (2 * layers, 2 * layers)
    kinds = _kernel_calls(lowered.compile().as_text())
    assert kinds == {"head_norm_rope": 2 * layers,
                     "head_norm_rope_bwd": 2 * layers, "flash_fwd": layers,
                     "flash_bwd_dq": layers, "flash_bwd_dkv": layers}, kinds
    assert _counter() == {(SDPA, "1"): float(layers)}
    assert _by_labels("qk_prep_layers_traced_total", "path", "heads") == {
        (path, str(heads)): float(layers),
        (path, str(kv_heads)): float(layers)}


# B, H, T, D, Dv (and, where K and V have fewer heads, Hkv) of the cells'
# attention: GPT-2-medium at batch 8, OLMoE and Moonlight (keys 192 wide,
# values 128) at 1, LFM2 (32 query heads on 8 key/value heads of 64) at 1,
# SmallThinker's full-span layer (28 on 4 of 128 at T 16384) and Xing4's 32
# heads of 192 / 128 at T 4096; and a short chunk of ring
# attention's, whose blocks are the whole dimension (one UNDER 128 long is
# refused by Mosaic in all three kernels: a lane offset into the logsumexp
# row it cannot prove aligned, at PR 31's parent as after it: PERF.md 7)
@pytest.mark.slow
@pytest.mark.parametrize("shape", [
    (8, 16, 1024, 64, 64), (1, 16, 4096, 128, 128), (1, 16, 8192, 192, 128),
    (2, 4, 256, 64, 64), (1, 32, 8192, 64, 64, 8),
    (1, 28, 16384, 128, 128, 4), (1, 32, 4096, 192, 128)],
    ids=["gpt2m_train_bs8", "olmoe_train_t4096", "moonlight_train_t8192",
         "whole_dimension_blocks", "lfm2_train_t8192",
         "smallthinker_train_t16384", "xing4_train_t4096"])
def test_aot_the_walks_compile_at_the_cells_shapes(v5e, shape):
    """The three kernels with their walks, bf16 under the blocks the rule
    gives the call (`call_blocks`) and x64 off as the chip runs them, through Mosaic for the described
    v5e: a strip's edge it cannot align (the lane offset into the
    (1, 1, T) logsumexp row, a sublane offset into a K block, dkv's
    [K rows, q rows] tile) fails here and not first on the chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    B, H, T, D, Dv = shape[:5]
    kv_heads = shape[5] if len(shape) > 5 else H
    one = SingleDeviceSharding(v5e)
    x = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16, sharding=one)
    y = jax.ShapeDtypeStruct((B, H, T, Dv), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((B, kv_heads, T, D), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((B, kv_heads, T, Dv), jnp.bfloat16,
                             sharding=one)
    lse = jax.ShapeDtypeStruct((B * H, T), jnp.float32, sharding=one)
    call = fa._Call(B * H, T, D, Dv, H // kv_heads, 0)
    bq, bk = fa._blocks(call, True, None, None, None, False)
    assert (bq, bk) == ((T, T) if T <= 1024
                        else fa.call_blocks(call))
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        plan = fa._schedule(T, bq, bk, fa._strip_rows(kernel, bq, bk))
        assert plan.computed <= T * T * 0.75
    with jax.enable_x64(False):
        calls = {
            "flash_fwd": jax.jit(lambda q, k, v: fa.flash_attention_fwd(
                q, k, v, causal=True)).lower(x, k, v),
            "flash_fwd_nolse": jax.jit(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True)).lower(x, k, v),
            "flash_bwd": jax.jit(
                lambda q, k, v, o, l, do: fa.flash_attention_bwd(
                    q, k, v, o, l, do, causal=True)).lower(
                        x, k, v, y, lse, y),
            # the single-shot body, as a ring step off the diagonal runs it
            "flash_bwd_whole": jax.jit(
                lambda q, k, v, o, l, do: fa.flash_attention_bwd(
                    q, k, v, o, l, do, causal=False)).lower(
                        x, k, v, y, lse, y)}
        for name, lowered in calls.items():
            text = lowered.compile().as_text()
            assert text.count('custom_call_target="tpu_custom_call"') == (
                2 if name.startswith("flash_bwd") else 1), name


@pytest.mark.slow
@pytest.mark.parametrize("shape,heads", [((8, 1024, 1024), 16),
                                         ((1, 4096, 2048), 16)],
                         ids=["gpt2m_train_bs8", "heads_of_128_T4096"])
def test_aot_packed_backward_makes_its_own_delta(v5e, shape, heads):
    """The backward on [B, T, H * D] operands, bf16 and x64 off as the chip
    runs it, through Mosaic for the described v5e: `flash_bwd_dq` takes O
    beside dO and writes the delta rows `flash_bwd_dkv` reads (two heads
    of 64 a lane block at GPT-2-medium's call, one of 128), and the
    compiled program holds the two kernels and NO float32 tensor of O's
    shape: XLA's product dO * O, which it wrote out at four bytes an
    element, is gone (PR 47)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    B, T, W = shape
    one = SingleDeviceSharding(v5e)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((B * heads, T), jnp.float32, sharding=one)
    with jax.enable_x64(False):
        text = jax.jit(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
            q, k, v, o, l, do, causal=True, heads=heads)).lower(
                x, x, x, x, lse, x).compile().as_text()
    assert _kernel_calls(text) == {"flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert f"f32[{B},{T},{W}]" not in text
    assert f"f32[{B},{T},{heads},{W // heads}]" not in text


# ---------------------------------------------------------------------------
# AOT: the expert layer's backward kernels in OLMoE's real step


OLMOE_LAYERS = 2
OLMOE_STEP = dict(seq_len=4096, vocab_size=50304, dim=2048,
                  n_layers=OLMOE_LAYERS, n_heads=16, num_experts=64,
                  expert_dim=1024, top_k=8, dtype="bfloat16")


def _olmoe_step(v5e, stage):
    from paddle_tpu.models.transformer import build_moe_lm_train_program

    fluid.reset()
    loss = build_moe_lm_train_program(**OLMOE_STEP)
    return _lowered_step(loss, v5e, batch=1, seq_len=4096, stage=stage)


def _grouped_backward():
    return _by_labels("moe_grouped_backward_total", "impl")


def test_olmoe_step_traced_for_a_v5e_counts_its_backward_kernels(
        v5e, monkeypatch):
    """The slow test's step below, TRACED for the described v5e and no more
    (PR 68): its counters are written there, so tier-1 keeps them; the
    gate closed, every grouped backward is autodiff's."""
    from paddle_tpu.ops.pallas_kernels import grouped_matmul as gm

    _olmoe_step(v5e, "trace")
    assert _grouped_backward() == {"pallas": 3.0 * OLMOE_LAYERS}
    assert _counter() == {(SDPA, "1"): float(OLMOE_LAYERS)}
    assert _paths() == {("bhtd", "flash"): float(OLMOE_LAYERS)}
    obs.REGISTRY.reset()
    monkeypatch.setattr(gm, "usable", lambda *shape: False)
    _olmoe_step(v5e, "trace")
    assert _grouped_backward() == {"ragged_dot": 3.0 * OLMOE_LAYERS}


@pytest.mark.slow
def test_aot_olmoe_step_backward_kernels_and_no_weight_relayout(
        v5e, monkeypatch):
    """`olmoe_train_t4096`'s step at the published widths (2 layers, 64
    experts of 1024, 4096 tokens with 8 experts each, bf16): the compiled
    HLO holds the 6 forward `ragged-dot` products XLA lowers itself and 12
    calls of the two Pallas kernels, no backward `ragged-dot`, and NO copy
    or transpose of the stacked expert weights.  With the gate closed the
    step as JAX hands it to XLA holds autodiff's six more `ragged_dot` a
    layer (not compiled: 15 s, for a step no cell runs)."""
    from paddle_tpu.ops.pallas_kernels import grouped_matmul as gm

    layers = OLMOE_LAYERS

    def instructions(text, pattern):
        return re.findall(r"^\s*(?:ROOT )?%(" + pattern + r")[.\d]* = ",
                          text, re.M)

    stacked = r"bf16\[64,(?:2048,1024|1024,2048)\]"
    relayout = (r"^\s*%(?:copy|transpose)[.\d]* = " + stacked
                + r"[^=\n]* (?:copy|transpose)\(")

    change = _olmoe_step(v5e, "lower")
    # the forward's three a layer, and their re-emission in the grad op
    text = change.as_text()
    assert text.count('"chlo.ragged_dot"(') == 6 * layers
    assert gm.DLHS in text and gm.DRHS in text
    text = change.compile().as_text()
    assert len(instructions(text, gm.DLHS)) == 3 * layers, "dlhs"
    assert len(instructions(text, gm.DRHS)) == 3 * layers, "drhs"
    assert len(instructions(text, "ragged-dot-none")) == 3 * layers
    assert not re.findall(relayout, text, re.M)
    assert _grouped_backward() == {"pallas": 3.0 * layers}
    assert _counter() == {(SDPA, "1"): float(layers)}  # flash's, no moe
    # RoPE stands between projection and attention: the old desc, the
    # kernels' [B, H, T, D] entry
    assert _paths() == {("bhtd", "flash"): float(layers)}

    # the parent's step: the gate closed for these kernels alone
    monkeypatch.setattr(gm, "usable", lambda *shape: False)
    text = _olmoe_step(v5e, "lower").as_text()
    assert gm.DLHS not in text and gm.DRHS not in text
    assert text.count('"chlo.ragged_dot"(') == 12 * layers


SHARE_ROWS = "moe_share_rows_to_tokens_traced_total"


SHARE_T, SHARE_DIM, SHARE_BUFFER = 2048, 2048, 3072
SHARE_STEP = dict(seq_len=SHARE_T, vocab_size=1024, dim=SHARE_DIM,
                  n_layers=2, n_heads=16, kv_rank=512, qk_nope_dim=128,
                  qk_rope_dim=64, v_dim=128, dense_dim=1024, dense_layers=1,
                  num_experts=64, expert_dim=1408, top_k=6, shared_experts=2,
                  held_experts=8, buffer_rows=SHARE_BUFFER,
                  routed_scale=2.446, dtype="bfloat16")


def _share_step(v5e, stage):
    from paddle_tpu.models.transformer import build_mla_moe_lm_train_program

    fluid.reset()
    loss = build_mla_moe_lm_train_program(**SHARE_STEP)
    return _lowered_step(loss, v5e, batch=1, seq_len=SHARE_T, stage=stage)


def _share_rows():
    return _by_labels(SHARE_ROWS, "op", "path")


def test_share_step_traced_for_a_v5e_counts_its_rows_to_tokens(
        v5e, monkeypatch):
    """The slow test's step below, traced alone: the kernel takes the
    forward combine and the row gather's backward, the `moe` op launches no
    kernel's forward again; the gate closed, both are XLA's scatter-adds."""
    from paddle_tpu.ops.pallas_kernels import segment_sum as ss

    _share_step(v5e, "trace")
    assert _share_rows() == {("combine", "segment_sum"): 1.0,
                             ("permute_grad", "segment_sum"): 1.0}
    assert _counter() == {("latent_attention", "1"): 2.0}
    monkeypatch.setattr(ss, "usable", lambda *shape: False)
    obs.REGISTRY.reset()
    _share_step(v5e, "trace")
    assert _share_rows() == {("combine", "scatter_add"): 1.0,
                             ("permute_grad", "scatter_add"): 1.0}
    assert _counter() == {("latent_attention", "1"): 2.0}


@pytest.mark.slow
def test_aot_share_rows_leave_the_buffer_by_two_kernel_calls_a_layer(
        v5e, monkeypatch):
    """Moonlight's share at its published widths, cut to the dense layer
    and ONE expert layer and to T 2048 (XLA:TPU compiles the cell's sorts
    of 49152 pairs for 25 s; 8 of 64 experts held, a 3072-row buffer,
    bf16): the compiled step holds TWO `segment-sum-rows` calls, the
    forward combine's and the row gather's backward (not three: the
    forward that generic_grad re-emits feeds nothing and goes), no scatter
    whose result is [T, D] is left in the expert layer, the counter reads
    the kernel for both, and the `moe` op has no series among the grad
    ops that launched a kernel's forward again.  With the gate closed the
    same step holds the parent's two scatter-adds."""
    from paddle_tpu.ops.pallas_kernels import segment_sum as ss

    t, dim, rows = SHARE_T, SHARE_DIM, SHARE_BUFFER
    on_tokens = rf"tensor<{t}x{dim}x(?:f32|bf16)>"
    text = _share_step(v5e, "lower").compile().as_text()
    calls = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sum(ss.NAME in name for name in calls) == 2, calls
    # no scatter onto [T, D] that the combine or the permute part made
    assert not [line for line in text.splitlines() if re.search(
        rf" = (?:f32|bf16)\[{t},{dim}\][^=]* scatter\(", line)
        and re.search(r"pdtpu\.moe\.(?:combine|permute)", line)]
    assert _share_rows() == {("combine", "segment_sum"): 1.0,
                             ("permute_grad", "segment_sum"): 1.0}
    # the attention op's kept pair, and nothing of the expert layer's
    assert _counter() == {("latent_attention", "1"): 2.0}

    # the gate closed: the step as JAX hands it to XLA (not compiled: 40 s)
    monkeypatch.setattr(ss, "usable", lambda *shape: False)
    text = _share_step(v5e, "lower").as_text()
    assert ss.NAME not in text
    # a scatter's last line: its region closes, then (operand, indices,
    # updates) -> result
    assert len(re.findall(
        rf"\}}\) : \({on_tokens}, tensor<{rows}x1xi32>, "
        rf"tensor<{rows}x{dim}x(?:f32|bf16)>\) -> {on_tokens}", text)) == 2
    assert _share_rows() == {("combine", "scatter_add"): 1.0,
                             ("permute_grad", "scatter_add"): 1.0}
