"""Random walks: the collection and its queries from a seed.

A random walk is the cumulative sum of N(0, 1) steps, drawn in float32 on
the device in one jitted call per batch and copied to the host, where the
system ingests it. Batch ``b`` of a collection depends only on the seed and
``b``, so a stream can be generated batch by batch and the same seed always
gives the same series. Queries are walks of their own, from a stream no
data batch uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_STREAM = 1 << 30  # the fold of the seed that queries are drawn from


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number: the low 32 bits make
    the key and the higher bits are folded in, so seeds above 2**32 stay
    distinct without 64-bit mode."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "d"))
def _walks(key, stream, n: int, d: int):
    steps = jax.random.normal(jax.random.fold_in(key, stream), (n, d),
                              jnp.float32)
    return jnp.cumsum(steps, axis=1)


def random_walks(seed: int, stream: int, n: int, d: int) -> np.ndarray:
    """(n, d) float32 random walks of one stream of the seed, on the host."""
    return np.array(_walks(seed_key(seed), stream, n, d))


def batches(seed: int, n_batches: int, batch: int, d: int,
            first: int = 0) -> list[np.ndarray]:
    """Batches ``first .. first + n_batches - 1`` of the seed's collection.
    The device draws the next batch while the host copies the last."""
    key = seed_key(seed)
    out, pending = [], None
    for b in range(first, first + n_batches):
        nxt = _walks(key, b, batch, d)
        if pending is not None:
            out.append(np.array(pending))
        pending = nxt
    if pending is not None:
        out.append(np.array(pending))
    return out


def queries(seed: int, n: int, d: int, history: list) -> np.ndarray:
    """(n, d) query walks from a stream no data batch uses; the stored
    ``history`` plays no part."""
    return random_walks(seed, QUERY_STREAM, n, d)
