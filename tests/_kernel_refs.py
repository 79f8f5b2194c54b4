"""References, jaxpr helpers and the startup run once, shared by test files."""

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=0)
def _with_vjp(fn, cotangents, *operands):
    """fn(*operands) and its vjp under `cotangents`, as ONE program: run
    op by op a plain reference and its backward are 50 to 100 programs to
    compile, which costs more than the kernels they are held against."""
    out, back = jax.vjp(fn, *operands)
    return out, back(cotangents)


def _dense_f32(q, k, v, causal):
    T, D = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.float32(D ** 0.5)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


def _assert_named(got, want, lse=(1e-5, 0)):
    """Each result of `got` against `want`'s of its name, named in the
    failure, to 2e-5: "nolse" (the forward that makes no logsumexp) against
    "out", and the logsumexp row, by which ring attention merges partial
    outputs, to `lse` (atol, rtol): 1e-5, absolute."""
    made = ("out", "lse", "dq", "dk", "dv", "nolse")   # a jit sorts a dict
    for name in sorted(got, key=made.index):
        atol, rtol = lse if name == "lse" else (2e-5, 2e-5)
        np.testing.assert_allclose(
            np.asarray(got[name]),
            np.asarray(want["out" if name == "nolse" else name]),
            atol=atol, rtol=rtol, err_msg=name)


def _flash_results(q, k, v, do, kw, lse_shape, fwd=None, backward=True):
    """{out, lse} (and dq, dk, dv, nolse): a case's kernels as ONE program."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    @jax.jit
    def kernels(q, k, v, do):
        out, lse = (fwd or fa.flash_attention_fwd)(q, k, v, **kw)
        got = dict(out=out, lse=lse.reshape(lse_shape))
        if backward:
            got.update(zip(("dq", "dk", "dv"), fa.flash_attention_bwd(
                q, k, v, out, lse, do, **kw)))
            got["nolse"] = fa.flash_attention(q, k, v, **kw)
        return got

    return kernels(q, k, v, do)


def _dense_masked(q, k, v, allowed):
    """Dense attention on [B, H, T, D] in the operands' float, K/V head
    h // group under each query head, the scores outside `allowed` at
    -inf."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    p = jax.nn.softmax(jnp.where(jnp.asarray(allowed), s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _heads_last(a):  # [B, H, T, D] -> [B, T, H * D]
    return a.transpose(0, 2, 1, 3).reshape(a.shape[0], a.shape[2], -1)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations
    (a pallas_call's body, the branches of a `pl.when`)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _dense_scaled(q, k, v, causal, scale):
    """(out, logsumexp of the SCALED scores) of dense float32 attention,
    K/V head h // group under each query head."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * np.float32(scale)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


def _startup(exe, kept):
    """Run the default startup program; or, where `kept` ({} at first) holds
    what an earlier run of the SAME one wrote, set that and count the run not
    made (the steps draw what they drew): it is 2 to 10 s to compile."""
    import paddle_tpu as fluid
    from paddle_tpu.framework.core import np_dtype

    startup, scope = fluid.default_startup_program(), fluid.global_scope()
    names = {n for op in startup.global_block().ops for n in op.output_names()}
    if not kept:
        exe.run(startup)    # (a draw's temporaries stay out of the scope)
        kept.update({n: np.asarray(scope.find(n)) for n in names
                     if scope.find(n) is not None})
        return
    assert set(kept) <= names
    for name, value in kept.items():
        var = startup.global_block().var(name)
        assert (tuple(var.shape), np_dtype(var.dtype)) == (
            value.shape, value.dtype), name     # the SAME program's draw
        scope.set(name, jnp.asarray(value))
    exe.restore_state({"step": exe.global_step + 1})


def _described_step(device, feed, fetch, stage="lower"):
    """The default main program's step for `fetch`, lowered for `device` (a
    described chip's) from shapes alone, x64 off as the chip compiles it
    (as tests/benchmarks/test_benchmark.py `_aot`); or, `stage` "trace",
    traced for it and no more: the counters are written there."""
    import paddle_tpu as fluid
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.framework.core import np_dtype

    class DescribedPlace(fluid.CPUPlace):
        def jax_device(self):
            return device

    main = fluid.default_main_program()
    block = main.blocks[0]
    exe = fluid.Executor(DescribedPlace())
    one = SingleDeviceSharding(device)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            tuple(shape), jax.dtypes.canonicalize_dtype(dtype), sharding=one)

    def of_var(n):
        v = block._find_var_recursive(n)
        return sds(v.shape, np_dtype(v.dtype))

    with jax.enable_x64(False):
        feed_vals = exe._prepare_feeds(block, feed)
        compiled = exe._compile(main, 0, feed_vals, fetch)
        return getattr(compiled.fn, stage)(
            {n: of_var(n) for n in compiled.rw_state},
            {n: of_var(n) for n in compiled.external_reads},
            {k: sds(v.shape, v.dtype) for k, v in feed_vals.items()},
            sds((2,), np.uint32))


def _run_layer(build, feeds, weights=None, seed=11):
    """Build a program with `build(x)` -> out, set `weights` {index: array}
    over the parameters in creation order, run -> (out, parameters)."""
    import paddle_tpu as fluid

    fluid.reset()
    x = fluid.layers.data("x", shape=list(feeds.shape[1:]), dtype="float32")
    out = build(x)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = seed
    exe = fluid.Executor(fluid.CPUPlace())
    params = main.global_block().all_parameters()
    if set(weights or ()) != set(range(len(params))):
        exe.run(startup)    # else every draw would be overwritten: 2-3 s
    scope = fluid.global_scope()
    for i, w in (weights or {}).items():
        scope.set(params[i].name, jnp.asarray(w, jnp.float32))
    (got,) = exe.run(feed={"x": feeds}, fetch_list=[out])
    return np.asarray(got), [np.asarray(scope.find(p.name)) for p in params]


def _rand(shape, seed, scale=1.0):
    return (np.random.RandomState(seed).standard_normal(shape)
            * scale).astype("float32")


def _silu(x):   # numpy's
    return x / (1 + np.exp(-x))


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


def _ctx(monkeypatch, platform="tpu", mesh=None):
    """An emit context whose trace claims `platform` as its target."""
    from paddle_tpu.ops import registry as reg

    ctx = reg.EmitContext(None, is_test=False)
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: platform)
    ctx.mesh = mesh
    return ctx


def _r(*shape, lo=-1.0, hi=1.0, seed=0):
    return np.random.RandomState(seed).uniform(lo, hi, shape)


def _series(family):    # [(labels, value)] of a family, sorted by labels
    from paddle_tpu import observability as obs

    fam = obs.REGISTRY.snapshot()["families"].get(family)
    return sorted(((s["labels"], s["value"])
                   for s in (fam["series"] if fam else [])),
                  key=lambda s: sorted(s[0].items()))


def _by_labels(family, *keys):
    """{a series' values of the labels `keys` (of one key: the value alone;
    of none: its sorted (label, value) pairs): its count} of a counter
    family, {} where nothing counted."""
    from paddle_tpu import observability as obs

    def key(labels):
        if not keys:
            return tuple(sorted(labels.items()))
        return (labels[keys[0]] if len(keys) == 1
                else tuple(labels[k] for k in keys))

    fam = obs.REGISTRY.snapshot()["families"].get(family)
    return {key(s["labels"]): s["value"]
            for s in (fam["series"] if fam else [])}


def _spy_on_calls(monkeypatch, kernels, names):
    """-> the list every launch of one of `kernels._calls`' jitted calls
    (`names`, in its order) appends its name to."""
    launched, real = [], kernels._calls

    def calls(*a):
        return tuple((lambda *x, name=name, call=call:
                      (launched.append(name), call(*x))[1])
                     for name, call in zip(names, real(*a)))

    monkeypatch.setattr(kernels, "_calls", calls)
    return launched


def _conv_interpreted(monkeypatch):
    """Where a test's trace claims a TPU, the short convolution in front of a
    Mamba layer's scan takes its kernel pair too
    (ops/pallas_kernels/ssm_conv.py): interpreted from here on -> the list
    every launch of it appends "fwd" / "bwd" to."""
    from paddle_tpu.ops.pallas_kernels import ssm_conv

    real = ssm_conv.make_ssm_conv
    launched = _spy_on_calls(monkeypatch, ssm_conv, ("fwd", "bwd"))
    monkeypatch.setattr(ssm_conv, "make_ssm_conv",
                        lambda at, sections, bias: real(at, sections, bias,
                                                        True))
    real.cache_clear()
    return launched


def _inner_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _inner_eqns(sub)


def _close(got, want, tol):
    """Within `tol` of the largest entry."""
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def _dot(a, b):
    return jnp.dot(a, b.astype(jnp.float32), precision="highest")


_LM_DRAWN = {}      # {a toy tower's sizes: what its startup program drew}


def _build_lm(V=50, D=32, L=2, NH=2, ML=64, seed=11):     # serving's toy
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    lm = transformer.DecoderLM(V, D, L, NH, max_len=ML, dtype="float32")
    tokens = fluid.layers.data("tokens", shape=[ML, 1], dtype="int64")
    logits = lm.logits(tokens)
    fluid.default_main_program().random_seed = seed
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, _LM_DRAWN.setdefault((V, D, L, NH, ML), {}))
    return lm, exe, logits
