"""Training batches, made on the device in one jitted call from the seed.

generate(seed, feeds, batch, n_batches) -> {feed name: array [n_batches,
batch, *shape]}: a pure function of its arguments.  `feeds` is a
configuration's `train.feeds`: for each feed its shape without the batch
dimension, its dtype ('int' is int32, what an int64 feed becomes on the
chip, where x64 is off) and how it is drawn:

  uniform     floats in [0, 1)
  randint     integers in [0, high)
  shift_left  another feed (`of`) rolled one position left along the axis
              after the batch: next-token targets

Every seed makes the same shapes, so no seed changes the work.
"""

from __future__ import annotations

import functools

from harness import seed32


@functools.lru_cache(maxsize=None)
def _maker(feeds_key: tuple, batch: int, n_batches: int):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, shape, dtype, dist, high, of) in enumerate(feeds_key):
            k = jax.random.fold_in(key, i)
            if dist == "uniform":
                out[name] = jax.random.uniform(
                    k, (n_batches, batch) + shape, jnp.float32).astype(dtype)
            elif dist == "randint":
                out[name] = jax.random.randint(
                    k, (n_batches, batch) + shape, 0, high,
                    jnp.int32 if dtype == "int" else dtype)
            elif dist == "shift_left":
                out[name] = jnp.roll(out[of], -1, axis=2)
            else:
                raise ValueError(f"feed {name!r}: unknown dist {dist!r}")
        return out

    return jax.jit(make)


def generate(seed: int, feeds: dict, batch: int, n_batches: int) -> dict:
    import jax

    key = tuple((name, tuple(spec.get("shape", ())), spec.get("dtype"),
                 spec["dist"], spec.get("high"), spec.get("of"))
                for name, spec in feeds.items())
    return _maker(key, int(batch), int(n_batches))(
        jax.random.PRNGKey(seed32(seed)))
