#!/usr/bin/env python3
"""Takes the readings that a cell's limits are set from ("How correct
is decided", steps 3 to 5).  Not run by the benchmark's own runs.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3,... \
        --controls 3 [--out chiprun_out/<file>.jsonl]
    python3 benchmark/calibrate.py --workload <cell> --judge <rows.jsonl>

One process, one compiled program, many seeds.  For each seed: the
program's numbers against the reference (the lower reading is the
largest over the seeds).  For the first ``--controls`` seeds also the
control (the reference computed one precision below what the
configuration states, put in the program's place) and, for a training
cell, the planted fault "half of the batch left out" (the reference on
half the rows put in the program's place).  A state returned
unchanged reads 1 by the measure and needs no run.  Every row goes
through ``correct.verdict`` with the cell's limits and says
``correct``.  Needs a TPU, as run.py does; ``--rehearse DIR`` as
there.

``--judge`` takes no reading: it puts rows that an earlier call wrote
(readings/<cell>.jsonl keeps the chip's) through ``correct.verdict``
with the limits as they are committed now, and prints each row's
``who``, ``seed`` and ``correct``.  Needs no chip.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def judge(cell, path, emit):
    """Rows of an earlier call under the limits committed now."""
    from benchmark import correct
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    for row in rows:
        numbers = {k: (row[k], None) for k in cell.limits if k in row}
        if not numbers:
            continue
        ok, _ = correct.verdict(numbers, cell.limits)
        emit({"seed": row["seed"], "who": row["who"], "correct": ok})


def give_weights(step, shapes, seed, prefix):
    """Start the one compiled step over from another seed: its
    parameters, states and optimizer state replaced by fresh ones."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from benchmark import weights
    step.params = step.states = step.opt_state = None
    made = weights.make(shapes, seed, sharding=NamedSharding(
        step.mesh, PartitionSpec()))
    step.params = {prefix + n: v for n, v in made.items()}
    step.states = {}
    step.opt_state = step.opt.init(step.params)
    step.step_count = jnp.zeros((), jnp.int32)


def train_cell(h, cell, seeds, n_controls, emit, dev, mx):
    import jax
    from benchmark import correct, train
    cfg, traffic = cell.config, cell.traffic
    fam, block, step, batches = train.build(h, cell, seeds[0], mx, dev)
    shapes, prefix = fam.param_shapes(cfg), block.prefix
    n = traffic["checked_steps"]
    progs = {}
    for i, seed in enumerate(seeds):
        if i:
            give_weights(step, shapes, seed, prefix)
            batches = train.make_batches(fam, cfg, traffic, seed,
                                         step.mesh)
        progs[seed] = (train.first_steps(step, batches, cfg, shapes,
                                         seed, prefix, n), batches[:n])
        if i == 0:
            # after the first steps: the step that was compiled is the
            # one the program's own call built (memory gate and all)
            mem = step.memory_analysis(*batches[0])
            emit({"remat": step.remat, "grad_accum": step.grad_accum,
                  "compiler_memory": {
                      k: getattr(mem, k) for k in (
                          "argument_size_in_bytes",
                          "temp_size_in_bytes") if hasattr(mem, k)},
                  "memory_stats": train.memory_peak(dev)[1]})
    step.params = step.states = step.opt_state = None
    del step, block, batches
    control = cfg["train"]["control_precision"]
    half = traffic["batch"] // 2
    for i, seed in enumerate(seeds):
        prog, keep = progs[seed]
        ref = correct.reference_training(fam, cfg, seed, keep)
        emit({"seed": seed, "who": "program", "losses": prog["losses"],
              "ref_losses": ref["losses"],
              **correct.judged(correct.compare_training(prog, ref),
                       cell.limits)})
        if i < n_controls:
            low = correct.reference_training(fam, cfg, seed, keep,
                                             mode=control)
            emit({"seed": seed, "who": f"control_{control}",
                  **correct.judged(correct.compare_training(low, ref),
                           cell.limits)})
            if half:
                cut = correct.reference_training(fam, cfg, seed, keep,
                                                 rows=half)
                emit({"seed": seed, "who": "fault_half_batch",
                      **correct.judged(correct.compare_training(cut, ref),
                               cell.limits)})
        progs[seed] = None
    jax.clear_caches()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds")
    p.add_argument("--judge", metavar="ROWS")
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--out")
    p.add_argument("--rehearse", metavar="DIR")
    args = p.parse_args(argv)
    from benchmark.harness import Harness
    from benchmark.run import compile_cache
    h = Harness(args.rehearse, os.path.join(
        args.rehearse, "BENCHMARK.json")) if args.rehearse \
        else Harness()
    cell = h.cell(args.workload)
    if args.judge:
        judge(cell, args.judge, lambda row: print(json.dumps(row)))
        return 0
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"calibrate: needs a TPU, jax found {dev.platform}",
              file=sys.stderr)
        return 1
    if not args.rehearse:
        compile_cache()
    import incubator_mxnet_tpu as mx
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps({"cell": cell.name, **row})
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        kind = cell.traffic["kind"]
        if kind == "train":
            train_cell(h, cell, seeds, args.controls, emit, dev, mx)
        else:
            from benchmark import serve
            serve.calibrate(h, cell, seeds, args.controls, emit, dev,
                            mx)
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
