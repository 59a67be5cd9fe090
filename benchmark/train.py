"""Driver of a ``train`` cell: the program's ``ShardedTrainStep`` on
batches resident on the device, timed over a whole window.

Set-up builds one step object, gives it weights made from the seed,
and drives it through its first ``checked_steps`` steps (the first of
them compiles); those steps are what ``correct`` compares, and the
same object goes on, through one untimed group of ``fetch_every``
steps, into the window.  The window dispatches steps back
to back, fetches the loss every ``fetch_every``-th step (never every
step) and closes on ``block_until_ready`` of the last step's outputs.
After the window: peak memory is read, the program's state is freed,
and the reference takes the same first steps.
"""
import os
import time

import numpy as np

from . import correct, trace, weights
from .reduce_trace import WINDOW_SPAN
from .reference import optim


def _dtype(name):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": None}[name]


def settled_block(fam, mx, cfg, ctx, shapes, seed, trained=True,
                  dtype="float32"):
    """The program's model with weights made from the seed in its
    Parameters, as if loaded from a checkpoint.  Built with
    ``grad_req`` null and one short eager forward (it settles the
    deferred shapes), then ``grad_req`` restored: the eager tape's
    gradient buffers, which no compiled step reads, would otherwise
    hold 4 bytes a parameter on the chip (PERF.md, section 7).  A
    model that is only served (``trained`` off) keeps it null.

    The Parameters are of ``dtype`` from the start (the family casts
    the block before it initializes it), and each group of leaves
    (``weights.groups``: a layer's) goes into its Parameters as it is
    made, in place of the zeros there: set-up never holds more than
    the model and one group."""
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.parallel.functional import PureBlock
    # as a user's script does first; it also keeps random_state from
    # making its root key inside the step's trace (PERF.md, section 7)
    mx.random.seed(seed % (2 ** 31))
    block = fam.build_program(mx, cfg, ctx, grad_req="null", dtype=dtype)
    with autograd.pause():
        block.forward(*fam.example_args(mx, cfg, ctx))
    params = block.collect_params()
    mine = {block.prefix + n: (tuple(s), dtype)
            for n, (s, _) in shapes.items()}
    theirs = {n: (p.shape, str(p.dtype)) for n, p in params.items()}
    if mine != theirs:
        raise RuntimeError(
            "the family's leaves are not the program's: "
            f"{sorted(set(mine.items()) ^ set(theirs.items()))[:6]}")
    pure = PureBlock(block)
    for group in weights.in_groups(shapes, seed, dtype):
        pure.write_back({block.prefix + n: v for n, v in group.items()})
    if trained:
        params.setattr("grad_req", "write")
    return block


def build(h, cell, seed, mx, dev):
    """The step with seeded weights, and its batches on the device."""
    cfg, traffic = cell.config, cell.traffic
    fam = h.family(cfg)
    block = settled_block(fam, mx, cfg, mx.tpu(0), fam.param_shapes(cfg),
                          seed)
    t_block = time.perf_counter()
    mesh = mx.parallel.make_mesh(devices=[dev])
    step = mx.parallel.ShardedTrainStep(
        block, optimizer=cfg["train"]["optimizer"],
        optimizer_params=dict(cfg["train"]["optimizer_params"]),
        loss_fn=fam.program_loss, mesh=mesh,
        compute_dtype=_dtype(cfg["train"]["compute_dtype"]))
    # the step owns a copy; the block gives its own up (it now names
    # the step's buffers, which the first step donates): on one chip
    # there is no room for the same 613M parameters twice
    step.pure.write_back(step.params, step.states)
    t_step = time.perf_counter()
    batches = make_batches(fam, cfg, traffic, seed, mesh)
    print({"step_object_s": t_step - t_block,
           "batches_s": time.perf_counter() - t_step}, flush=True)
    return fam, block, step, batches


def make_batches(fam, cfg, traffic, seed, mesh):
    """The traffic's resident batches, made on the device from the
    seed in one jitted call: a list of (inputs, labels)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    n = traffic["resident_batches"]
    xs, ys = jax.jit(
        lambda key: tuple(
            tuple(a[i] for i in range(n))
            for a in fam.train_batches(cfg, traffic, key)),
        out_shardings=NamedSharding(mesh, PartitionSpec()))(
            weights.fold(seed + 1))
    return list(zip(xs, ys))


def first_steps(step, batches, cfg, shapes, seed, prefix, n_steps):
    """The program's readings: each step's loss, the first
    gradient's norms out of the optimizer's state after one step, the
    parameters' change after the last."""
    kind = cfg["train"]["optimizer"]
    hp = cfg["train"]["optimizer_params"]
    import jax
    losses, grad1, marks = [], None, [time.perf_counter()]
    for x, y in batches[:n_steps]:
        losses.append(step(x, y))
        if grad1 is None:
            jax.block_until_ready(losses[0])
            marks.append(time.perf_counter())
            grad1, picked = optim.first_gradient(kind, hp,
                                                 step.opt_state)
    change = correct.change_norms(shapes, seed, step.params, prefix)
    jax.block_until_ready(change)
    marks.append(time.perf_counter())
    print({"first_step_s": marks[1] - marks[0],
           "other_checked_steps_and_norms_s": marks[2] - marks[1]},
          flush=True)
    cut = len(prefix)
    return {"losses": [float(v) for v in losses],
            "grad1": {n[cut:]: float(v) for n, v in grad1.items()},
            "grad1_elements": {n[cut:]: np.asarray(v)
                               for n, v in picked.items()},
            "change": {n: float(v) for n, v in change.items()}}


def window(step, batches, seconds, fetch_every, clock=time.perf_counter):
    """Steps for at least ``seconds``; (steps, window seconds, losses
    fetched).  Ends on a fetch, so it is whole groups of
    ``fetch_every`` steps."""
    import jax
    import jax.profiler as prof
    n, steps, fetched = len(batches), 0, []
    t0 = clock()
    while True:
        with prof.TraceAnnotation("bench.dispatch"):
            for _ in range(fetch_every):
                x, y = batches[steps % n]
                loss = step(x, y)
                steps += 1
        with prof.TraceAnnotation("bench.fetch"):
            fetched.append(float(loss))
        if clock() - t0 >= seconds:
            break
    jax.block_until_ready((loss, step.params))
    return steps, clock() - t0, fetched


def memory_peak(dev):
    """Peak bytes on the chip, and the counters it was reckoned from.
    The runtime's ``peak_bytes_in_use`` counts arrays and leaves out
    the scratch space that a loaded program reserves while it runs
    (PERF.md, section 4: the serve cell's 4.9 GB for the 2048 prefill
    does not show in it); that is in ``peak_bytes_reserved``.  So the peak
    is the larger of the arrays' own peak and what is held now plus
    the largest reservation."""
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None and "peak_bytes_reserved" in stats:
        peak = max(peak, stats["bytes_in_use"]
                   + stats["peak_bytes_reserved"])
    return peak, {k: stats[k] for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit") if k in stats}


def run(h, cell, args, t_start, dev, mx):
    import jax
    cfg, traffic = cell.config, cell.traffic
    fam, block, step, batches = build(h, cell, args.seed, mx, dev)
    shapes, prefix = fam.param_shapes(cfg), block.prefix
    n_checked = traffic["checked_steps"]
    built_s = time.perf_counter() - t_start
    prog = first_steps(step, batches, cfg, shapes, args.seed, prefix,
                       n_checked)
    # one group more, dispatched as the window dispatches it: the
    # first burst of calls back to back after set-up takes 6-11 ms a
    # call where every later one takes 2-3, some 100 ms in all, and
    # how much of it showed turned on what had compiled just before
    # (PERF.md, PR 26).  It is warm-up, so it is set-up.
    window(step, batches, 0.0, traffic["fetch_every"])
    setup_s = time.perf_counter() - t_start

    traced = None
    seconds = args.seconds
    if args.trace:
        with trace.Recording() as rec:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                t_steps, t_window, _ = window(
                    step, batches, 0.0, traffic["trace_steps"])
        traced = rec.reduce(keep_as=os.environ.get("BENCH_KEEP_TRACE"),
                            required=not args.rehearse)
        if traced:
            traced["steps"] = t_steps
        seconds = max(0.0, seconds - t_window)
    steps, window_s, fetched = window(step, batches, seconds,
                                      traffic["fetch_every"])
    peak, memory = memory_peak(dev)
    units, unit_name = fam.units_per_step(traffic)
    print({"memory": memory, "setup_built_s": built_s, "setup_first_steps_s":
           setup_s - built_s, "steps": steps, "window_s": window_s,
           f"{unit_name}_per_s": steps * units / window_s,
           "last_losses": fetched[-3:]}, flush=True)

    # free the program's state before the reference takes the chip
    keep = batches[:n_checked]
    step.params = step.states = step.opt_state = None
    del step, block, batches
    ref = correct.reference_training(fam, cfg, args.seed, keep)
    numbers = correct.compare_training(prog, ref)
    bad = sum(1 for v in fetched if v != v)
    return {
        "attempted": steps, "failed": bad,
        "numbers": numbers,
        "end_to_end": {"setup_s": setup_s,
                       "train_step_ms": 1e3 * window_s / steps},
        "memory_peak_bytes": peak,
        "ctx": {"trace": traced,
                "flops_per_step": fam.train_flops(cfg, traffic),
                "config": cfg, "traffic": traffic},
    }
