"""Dense references and jaxpr helpers shared by the flash kernels' test files."""

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
    for name in got:
        atol, rtol = lse if name == "lse" else (2e-5, 2e-5)
        np.testing.assert_allclose(
            np.asarray(got[name]),
            np.asarray(want["out" if name == "nolse" else name]),
            atol=atol, rtol=rtol, err_msg=name)


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
