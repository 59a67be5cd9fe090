"""Plain optimizers of the training reference, as the configurations
state them (``train.optimizer`` in configs/*.json).

``first_gradient`` undoes one update: the gradient as the optimizer
got it, worked out from the optimizer's state after its first step.
The benchmark applies it to the program's state; the reference has
its gradient at hand.
"""
import jax
import jax.numpy as jnp


def init(kind, params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    if kind == "adam":
        return {"mean": zeros,
                "var": jax.tree_util.tree_map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}
    raise ValueError(f"no reference optimizer {kind!r}")


def update(kind, hp, params, g, state):
    lr = hp["learning_rate"]
    if kind == "adam":
        b1, b2 = hp.get("beta1", 0.9), hp.get("beta2", 0.999)
        eps = hp.get("epsilon", 1e-8)
        t = state["t"] + 1
        tf = t.astype(jnp.float32)
        lr_t = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
        mean = jax.tree_util.tree_map(
            lambda m, x: b1 * m + (1 - b1) * x, state["mean"], g)
        var = jax.tree_util.tree_map(
            lambda v, x: b2 * v + (1 - b2) * x * x, state["var"], g)
        new = jax.tree_util.tree_map(
            lambda w, m, v: w - lr_t * m / (jnp.sqrt(v) + eps),
            params, mean, var)
        return new, {"mean": mean, "var": var, "t": t}
    raise ValueError(f"no reference optimizer {kind!r}")


SAMPLE = 4096      # elements of a leaf kept for the element-wise look


def sample(v):
    """Up to SAMPLE elements of a leaf at an even stride: small enough
    to keep beside the window, the same places on both sides."""
    flat = v.reshape(-1).astype(jnp.float32)
    return flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE]


def gradient_readings(tree, scale=1.0):
    """(name -> norm, name -> sampled elements) of a gradient tree."""
    return jax.jit(lambda t: (
        {n: scale * jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
         for n, v in t.items()},
        {n: scale * sample(v) for n, v in t.items()}))(tree)


def first_gradient(kind, hp, state_after_one):
    """The first gradient's norms and sampled elements, from
    a state (the program's) after exactly one update."""
    if kind == "adam":
        scale, tree = 1.0 / (1.0 - hp.get("beta1", 0.9)), "mean"
    else:
        raise ValueError(f"no reference optimizer {kind!r}")
    norms, samples = gradient_readings(state_after_one[tree], scale)
    return {n: abs(v) for n, v in norms.items()}, samples
