"""The comparison that decides ``correct``.

Training: the program's first steps against the plain reference's
(reference/), number by number, each with a limit of its own from
limits/<cell>.json.  Serving: how far the served tokens' logits lie
below the reference's best, against what the control is expected to
lose in the same text (``serve.gaps``).  PERF.md gives the readings
each limit was set from.
"""
import statistics

import numpy as np
import jax
import jax.numpy as jnp

from . import weights
from .reference import optim

# a leaf whose first gradient is under this share of the median
# leaf's is nought to rounding (a key's bias under softmax): Adam
# moves it by round-off alone, so its change is not compared
ZERO_GRADIENT_SHARE = 1e-3


def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


def change_norms(shapes, seed, params_now, strip=""):
    """name -> norm of (leaf now - leaf as made from the seed).  The
    seed's key is an argument of the program, not a constant in it:
    one program serves every seed (as a constant it cost the train
    cell a compilation of 30 s in every run with a new seed: PERF.md,
    PR 26)."""
    def fn(now, key):
        made = weights.traced(shapes, key)
        return _norms({n: now[strip + n] - made[n] for n in made})
    return jax.jit(fn)(params_now, weights.fold(seed))


def reference_training(fam, cfg, seed, batches, mode="f32",
                       rows=None):
    """The reference's first ``len(batches)`` steps from the seed:
    losses, the first gradient's norms, the change's norms.
    ``rows`` keeps only the first rows of each batch (a planted
    fault: half of the batch left out)."""
    shapes = fam.param_shapes(cfg)
    kind = cfg["train"]["optimizer"]
    hp = cfg["train"]["optimizer_params"]
    params = weights.make(shapes, seed)
    state = optim.init(kind, params)

    def step(params, state, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: fam.reference_loss(p, x, y, cfg, mode))(params)
        gnorms = _norms(grads)
        picked = {n: optim.sample(v) for n, v in grads.items()}
        params, state = optim.update(kind, hp, params, grads, state)
        return params, state, loss, gnorms, picked

    step = jax.jit(step, donate_argnums=(0, 1))
    losses, first, elements = [], None, None
    for x, y in batches:
        if rows is not None:
            x, y = x[:rows], y[:rows]
        params, state, loss, gnorms, picked = step(params, state, x, y)
        losses.append(float(loss))
        if first is None:
            first = {n: float(v) for n, v in gnorms.items()}
            elements = {n: np.asarray(v) for n, v in picked.items()}
    change = change_norms(shapes, seed, params)
    return {"losses": losses, "grad1": first, "grad1_elements": elements,
            "change": {n: float(v) for n, v in change.items()}}


def _worst_leaf(prog, ref, leaves):
    """Largest gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    median = statistics.median(ref[n] for n in leaves)
    worst, where = 0.0, None
    for n in leaves:
        gap = abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
        if gap > worst or where is None:
            worst, where = gap, n
    return worst, where


def compare_training(prog, ref):
    """name -> (number, where) for every number compared."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]),
                                 start=1):
        out[f"loss{i}"] = (abs(lp - lr) / abs(lr), None)
    leaves = sorted(ref["grad1"])
    out["grad1"] = _worst_leaf(prog["grad1"], ref["grad1"], leaves)
    floor = ZERO_GRADIENT_SHARE * statistics.median(
        ref["grad1"].values())
    moved = [n for n in leaves if ref["grad1"][n] >= floor]
    out[f"change{len(ref['losses'])}"] = _worst_leaf(
        prog["change"], ref["change"], moved)
    out["grad1_diff"] = (_median_difference(prog["grad1_elements"],
                                            ref["grad1_elements"]), None)
    return out


def _median_difference(prog, ref):
    """Norms of a gradient hide rounding that has no sign: it adds in
    quadrature and leaves the norm where it was.  So, beside the norms,
    the elements themselves, on a sample of each leaf: the norm of the
    difference against the reference's norm of that sample (or the
    median leaf's), and of these the median leaf's."""
    norms = {n: float(np.linalg.norm(v)) for n, v in ref.items()}
    floor = statistics.median(norms.values())
    return statistics.median(
        float(np.linalg.norm(np.asarray(prog[n]) - ref[n]))
        / max(norms[n], floor, 1e-30) for n in ref)


def verdict(numbers, limits):
    """(correct, {name: {"value", "limit", "where"}}): every number
    has to be in the cell's limits, and at or under its limit.  A
    limit of null marks a number that is read and shown but not
    compared (PERF.md says why no limit could hold for it)."""
    table, ok = {}, True
    limits = limits or {}
    for name, (value, where) in numbers.items():
        limit = limits.get(name)
        if limit is None and name in limits:
            good = True     # read and shown, not compared: the file
            #                 says null where no limit could hold
        else:
            good = limit is not None and value == value \
                and value <= limit
        ok = ok and good
        table[name] = {"value": value, "limit": limit}
        if where:
            table[name]["where"] = where
    return ok, table


def judged(numbers, limits):
    """A calibration row: the numbers, where each was read, and what
    ``verdict`` says of them."""
    ok, _ = verdict(numbers, limits)
    return {**{k: v[0] for k, v in numbers.items()},
            "where": {k: v[1] for k, v in numbers.items() if v[1]},
            "correct": ok}
