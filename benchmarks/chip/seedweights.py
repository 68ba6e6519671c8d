"""Weights made by the benchmark from ``--seed``, on the device.

The program is handed these weights; the reference reads the same
arrays.  The benchmark takes from the program only the layout of its
parameter tree (names and shapes, by ``jax.eval_shape`` of its init)
and the sharding it serves them with.  The values follow the
benchmark's own rule, one draw per leaf keyed by the leaf's path:

  norm scales ("w" of a norm)     0.1 * normal  (stored as offset from 1)
  embedding table [V, D]          truncated normal / sqrt(D)
  every other matrix [..., K, N]  truncated normal / sqrt(K)

All of it is one jitted call, made in the serving dtype and placed with
its sharding, so nothing is built leaf by leaf or on the host.
"""

from __future__ import annotations

import zlib


def seed_key(seed: int):
    """A PRNG key from a whole-number seed of any size up to 64 bits."""
    import jax
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside 0 .. 2**64 - 1")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _path(path) -> tuple:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _leaf(key, names: tuple, shape, dtype):
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(key, zlib.crc32("/".join(names).encode()))
    if names[-1] == "w" and "unembed" not in names:
        value = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        fan_in = shape[-1] if names[-1] == "emb" else shape[-2]
        value = jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                            jnp.float32) / fan_in ** 0.5
    return value.astype(dtype)


def make_params(init_fn, seed: int, dtype, shardings=None):
    """Weights shaped like ``init_fn``'s tree, made from ``seed`` in
    ``dtype``; ``shardings``: a callable from the tree of shapes to the
    tree of shardings to make them in (None: one device)."""
    import jax
    key = seed_key(seed)
    shapes = jax.eval_shape(init_fn, key)

    def make(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: _leaf(key, _path(path), leaf.shape, dtype),
            shapes)

    out = None if shardings is None else shardings(shapes)
    return jax.jit(make, out_shardings=out)(key)
