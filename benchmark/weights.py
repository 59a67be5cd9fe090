"""Weights from ``--seed``: made on the device in one jitted call.

A family says which leaves it has (``param_shapes``: name -> (shape,
kind)); this says what goes into each kind.  The same call gives the
program its weights and, after the window, the reference its own:
nothing the program has held is handed to the reference.
"""
import zlib

import jax
import jax.numpy as jnp

# kind -> (base, spread): value = base + spread * standard normal
_FIXED = {"matrix": (0.0, 0.02),  # facebook/opt config.json: init_std
          "bias": (0.0, 0.02), "gamma": (1.0, 0.1), "beta": (0.0, 0.1)}


def fold(seed):
    """Any whole number up to a little over 2**31 -> a PRNG key."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, name, shape, kind, dtype):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(key, shape, jnp.float32)
    base, spread = _FIXED[kind]
    return (base + spread * noise).astype(dtype)


def traced(shapes, key, dtype=jnp.float32):
    """name -> array for ``shapes``, to be called under a jit."""
    return {n: _leaf(key, n, *shapes[n], dtype) for n in sorted(shapes)}


def make(shapes, seed, dtype=jnp.float32, sharding=None):
    """name -> array for ``shapes`` (name -> (shape, kind)), in one
    jitted call."""
    return jax.jit(lambda key: traced(shapes, key, dtype),
                   out_shardings=sharding)(fold(seed))
