"""Dense references and jaxpr helpers shared by the flash kernels' test files."""

import jax
import jax.numpy as jnp
import numpy as np


def _dense_f32(q, k, v, causal):
    T, D = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.float32(D ** 0.5)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


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
