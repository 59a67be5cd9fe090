"""Weights from ``--seed``: made on the device, a group of leaves at
a time.

A family says which leaves it has (``param_shapes``: name -> (shape,
kind)); this says what goes into each kind.  The same call gives the
program its weights and, after the window, the reference its own:
nothing the program has held is handed to the reference.

A leaf's value is keyed by the seed and its own name alone, so it is
the same whether the leaves are made group by group (``in_groups``,
``make``) or all inside one trace (``traced``).  Group by group is how
a model is set up: whoever takes each group as it comes
(``train.settled_block`` puts it into its Parameters) never holds more
than the model and one group.  A group is the leaves whose names agree
up to the end of their first number (``transformerblock3_...``: a
layer's leaves, where the family numbers its layers); groups of the
same shapes and kinds share one compiled program (the names' hashes
are an argument of it), so a model of sixty layers compiles what a
model of one layer does.
"""
import re
import zlib

import numpy as np

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


def _name_hash(name):
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _leaf(key, name_hash, shape, kind, dtype):
    key = jax.random.fold_in(key, name_hash)
    noise = jax.random.normal(key, shape, jnp.float32)
    base, spread = _FIXED[kind]
    return (base + spread * noise).astype(dtype)


def traced(shapes, key, dtype=jnp.float32):
    """name -> array for ``shapes``, to be called under a jit."""
    return {n: _leaf(key, _name_hash(n), *shapes[n], dtype)
            for n in sorted(shapes)}


def groups(shapes):
    """The leaves' names, in their order, by group."""
    out = {}
    for name in sorted(shapes):
        out.setdefault(re.match(r"\D*\d*", name).group(0),
                       []).append(name)
    return list(out.values())


def in_groups(shapes, seed, dtype=jnp.float32, sharding=None):
    """name -> array for each group of ``shapes`` (name -> (shape,
    kind)) in turn, each made by one jitted call when it is asked
    for."""
    key, programs = fold(seed), {}
    for names in groups(shapes):
        sig = tuple((tuple(shapes[n][0]), shapes[n][1]) for n in names)
        if sig not in programs:
            programs[sig] = jax.jit(
                lambda key, hashes, sig=sig: [
                    _leaf(key, hashes[i], shape, kind, dtype)
                    for i, (shape, kind) in enumerate(sig)],
                out_shardings=sharding)
        hashes = np.asarray([_name_hash(n) for n in names], np.uint32)
        yield dict(zip(names, programs[sig](key, hashes)))


def make(shapes, seed, dtype=jnp.float32, sharding=None):
    """name -> array for ``shapes``: all the groups."""
    return {n: v for group in in_groups(shapes, seed, dtype, sharding)
            for n, v in group.items()}
