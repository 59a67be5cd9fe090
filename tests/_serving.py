"""Shared by the serving tests: a decode step that holds the device
long enough for the engine's next launch to find it still running, and
a loop that serves to the end, landing every step before the next
launch or not.

The engine launches a decode step before the one in flight has ended
(docs/serving.md, "The chained decode loop"); on the CPU a tiny
model's step ends before the host's loop comes round again, so the
tests that need a launch onto a running step make the step longer."""
import jax
import jax.numpy as jnp
from jax import lax


def slow_decode(monkeypatch, model, products=24, size=192):
    """Make ``model``'s paged decode step run ``products`` matrix
    products of ``size`` x ``size`` before the step itself, joined to
    its tokens so that the compiler keeps them and the tokens stay
    what they were (``tanh`` never passes 1)."""
    build = model._build_paged_step

    def build_slow(*shape):
        step = build(*shape)

        def run(wts, *args):
            *args, tokens = args
            x = jnp.full((size, size), 1e-3, jnp.float32) \
                * (1 + tokens[0])
            x = lax.fori_loop(0, products,
                              lambda i, x: jnp.tanh(x @ x), x)
            tokens = jnp.where(x[0, 0] > 2, tokens + 1, tokens)
            return step(wts, *args, tokens)
        return run

    monkeypatch.setattr(model, "_build_paged_step", build_slow)
    return model


def serve(eng, prompts, budgets, land_each):
    """Serve the prompts to the end; with ``land_each`` every decode
    step has ended before the next launch, as a loop that waits for
    its pools would have it."""
    reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    while eng.has_work():
        eng.step()
        if land_each and eng._flight is not None:
            jax.block_until_ready(eng._flight.nxt)
    return reqs
