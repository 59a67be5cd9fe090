#!/usr/bin/env python3
"""Readings of a serve cell with planted faults, beside the program's
and the control's: what ``calibrate.py`` takes for a serve cell, and,
for a family whose ``reference_logits`` takes ``fault=`` (it lists
them as ``reference.FAULTS``), one row a fault.  Not run by the
benchmark's own runs.

    python3 benchmark/faults.py --workload <cell> --seeds 1,2,3 \
        --controls 3 --faults top7,no_shared_expert --fault-seeds 2 \
        [--witness bf16] --out chiprun_out/<file>.jsonl \
        [--positions chiprun_out/dir]

Per seed a short window at the cell's own load (``serve.short_load``),
then one pass of the reference in ``serve.reference_precision`` over
prompt and served tokens of each sampled request.  The program's row
reads its served tokens; the control's and a fault's row read, as
``serve.gaps(of_control=True)`` does, the tokens that the other
computation puts first as if they had been served: the control is the
reference in ``serve.control_precision``, a fault is the reference
with the fault planted, computed in ``f32_default`` (a program's own
precision; only its first choice is read); a witness (``--witness``)
is the sound reference in another precision, which says what a sound
computation of that precision reads and is no row for ``readings/``.
Every row is reduced by ``serve.far_gap_share`` and judged by
``correct.judged`` under the cell's limits, like a row of
``calibrate.py``.  ``--positions`` keeps every position's numbers
(npz a seed) for a look by hand at other ``far_gap_sigmas``.  Every
request is padded to the longest one's multiple of ``serve.PAD_TO``:
one shape a pass to compile.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def passes(fam, cfg):
    """(sound(params, toks) -> (logits, best, margin, served gap),
    other(params, toks, logits, best, mode, fault) -> (the other's
    first choice's gap, its error on the margin))."""
    import jax
    import jax.numpy as jnp
    stated = cfg["serve"]["reference_precision"]

    @jax.jit
    def sound(params, toks):
        lg = fam.reference_logits(params, toks[None], cfg, stated)[0]
        top, at = jax.lax.top_k(lg, 2)
        served = jnp.take_along_axis(
            lg, jnp.roll(toks, -1)[:, None], axis=-1)[:, 0]
        return lg, at, top[:, 0] - top[:, 1], top[:, 0] - served

    def other(params, toks, lg, at, margin, mode, fault):
        kw = {"fault": fault} if fault else {}
        low = fam.reference_logits(params, toks[None], cfg, mode,
                                   **kw)[0]
        low_top = jnp.take_along_axis(low, at, axis=-1)
        first = jnp.argmax(low, axis=-1)[:, None]
        return (jnp.max(lg, -1)
                - jnp.take_along_axis(lg, first, axis=-1)[:, 0],
                low_top[:, 0] - low_top[:, 1] - margin)

    return sound, jax.jit(other, static_argnames=("mode", "fault"))


def read(cell, fam, seed, sample, width, fns, faults, witnesses,
         keep_control, emit, positions=None):
    """One seed's rows over ``sample`` ((prompt, served tokens) each,
    padded to ``width``): the program's, the control's where
    ``keep_control``, one a fault and one a witness."""
    import numpy as np
    from benchmark import correct, serve, weights
    cfg = cell.config
    sound, other = fns
    control = cfg["serve"]["control_precision"]
    t0 = time.time()
    # the control runs on every seed: its errors are the program's
    # yardstick
    others = [(f"control_{control}", control, None)]
    others += [(f"fault_{f}", "f32_default", f) for f in faults]
    others += [(f"witness_{m}", m, None) for m in witnesses]
    params = weights.make(fam.param_shapes(cfg), seed,
                          cfg["serve"]["weights_dtype"])
    rows = {who: [] for who, _, _ in others}
    mine, margins, errors = [], [], []
    for prompt, tokens in sample:
        n = len(prompt) + len(tokens)
        padded = np.zeros(width, np.int32)
        padded[:len(prompt)] = prompt
        padded[len(prompt):n] = tokens
        cut = slice(len(prompt) - 1, n - 1)
        lg, at, margin, gap = sound(params, padded)
        mine.append(np.asarray(gap)[cut])
        margins.append(np.asarray(margin)[cut])
        for who, mode, fault in others:
            gap, err = other(params, padded, lg, at, margin,
                             mode=mode, fault=fault)
            rows[who].append(np.asarray(gap)[cut])
            if who.startswith("control_"):
                errors.append(np.asarray(err)[cut])
    del params, lg
    mine, margin = np.concatenate(mine), np.concatenate(margins)
    of = f"{len(mine)} tokens of {len(sample)} requests"
    error = np.concatenate(errors)
    kept = {"mine": mine, "margin": margin, "error": error}

    def row(who, served):
        share, where = serve.far_gap_share(
            served, margin, error, cfg["serve"]["far_gap_sigmas"],
            cfg["serve"]["yardstick_flips"])
        emit({"seed": seed, "who": who, **correct.judged(
            {"far_gap_share": (share, f"{of}; {where}"),
             "token_gap": (float(served.max()), of)},
            cell.limits),
            "sigma": float(np.sqrt(np.mean(error ** 2))),
            "median_margin": float(np.median(margin)),
            "turned": int((served > 0).sum())})
    row("program", mine)
    for who, _, _ in others:
        kept[who] = np.concatenate(rows[who])
        if keep_control or not who.startswith("control_"):
            row(who, kept[who])
    print({"seed": seed, "reference_s": time.time() - t0,
           "padded_to": width}, flush=True)
    if positions:
        os.makedirs(positions, exist_ok=True)
        np.savez_compressed(os.path.join(
            positions, f"positions_{seed}.npz"), **kept)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--faults", default="all")
    p.add_argument("--fault-seeds", type=int, default=2)
    p.add_argument("--witness", default="",
                   help="precisions of the reference to put in the "
                        "program's place, unfaulted (bf16)")
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--out")
    p.add_argument("--positions", metavar="DIR")
    p.add_argument("--rehearse", metavar="DIR")
    args = p.parse_args(argv)
    from benchmark import serve
    from benchmark.harness import Harness
    from benchmark.run import compile_cache
    from benchmark.train import memory_peak
    h = Harness(args.rehearse, os.path.join(
        args.rehearse, "BENCHMARK.json")) if args.rehearse \
        else Harness()
    cell = h.cell(args.workload)
    cfg = cell.config
    fam = h.family(cfg)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"faults: needs a TPU, jax found {dev.platform}",
              file=sys.stderr)
        return 1
    if not args.rehearse:
        compile_cache()
    import incubator_mxnet_tpu as mx
    known = getattr(sys.modules[fam.reference_logits.__module__],
                    "FAULTS", {})
    faults = sorted(known) if args.faults == "all" \
        else [f for f in args.faults.split(",") if f]
    unknown = [f for f in faults if f not in known]
    if unknown:
        raise SystemExit(f"family {cfg['family']!r} plants no "
                         f"{unknown}: {sorted(known)}")
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps({"cell": cell.name, **row})
        print(line[:700], flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    seeds = [int(s) for s in args.seeds.split(",")]
    samples = {}
    for seed in seeds:
        t0 = time.time()
        samples[seed], e2e, counts = serve.short_load(
            h, cell, seed, mx, args.seconds)
        gc.collect()    # the engine and its jitted closures: a cycle
        emit({"seed": seed, "who": "load", "counts": counts, **e2e,
              "memory": memory_peak(dev)[1],
              "load_s": time.time() - t0})
    sound, other = passes(fam, cfg)
    longest = max(len(pr) + len(tk) for s in samples.values()
                  for pr, tk in s)
    width = min(-(-longest // serve.PAD_TO) * serve.PAD_TO,
                cfg["max_position_embeddings"])
    witnesses = [m for m in args.witness.split(",") if m]
    for i, seed in enumerate(seeds):
        read(cell, fam, seed, samples[seed], width, (sound, other),
             faults if i < args.fault_seeds else [], witnesses,
             i < args.controls, emit, args.positions)
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
