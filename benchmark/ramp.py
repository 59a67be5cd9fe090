#!/usr/bin/env python3
"""One long run of a serve cell at its own load, read in slices: where
the steady state begins, which is what a mix's ``ramp_seconds`` is set
from.  Not run by the benchmark's own runs.

    python3 benchmark/ramp.py --workload <cell> --seed 1 --seconds 200

The callers of the closed loop start together with no ramp
(``serve.Drive``), and every token's time is kept.  One line a slice
of ``SLICE`` seconds: tokens a second, the requests taken in, and the
95th gap between tokens; then, for every start that is a whole number
of slices, what a window of the benchmark's ``run_seconds`` opened
there would have reported (``serve_tok_per_s``, ``itl_p95_ms`` as
``Drive.end_to_end`` counts them).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

SLICE = 10.0


def long_run(h, cell, seed, mx, seconds):
    """(requests, sample): per request ``(due, prompt length, token
    times)`` on a clock that starts with the load, and the drive's
    sample of finished requests."""
    from benchmark import serve
    from benchmark.traffic import Plan
    cfg, traffic = cell.config, cell.traffic
    _, _, eng = serve.build(h, cell, seed, mx)
    serve.warm(eng, traffic, cfg["vocab_size"], seed)
    drive = serve.Drive(eng, Plan(traffic, cfg["vocab_size"], seed))
    drive.run(seconds)
    t0 = drive.t0
    return ([(r["due"] - t0, len(r["prompt"]),
              [t - t0 for t in r["times"]])
             for r in drive.records.values()],
            drive.sample(traffic["checked_requests"], seed))


def windows(requests, lo, hi):
    """What a window (lo, hi] reports, as ``Drive.end_to_end``."""
    import numpy as np
    times = [np.asarray(t) for _, _, t in requests]
    tokens = sum(int(((t > lo) & (t <= hi)).sum()) for t in times)
    gaps = np.concatenate([np.diff(t)[(t[1:] > lo) & (t[1:] <= hi)]
                           for t in times if len(t) > 1])
    return {"from_s": lo, "to_s": hi,
            "serve_tok_per_s": tokens / (hi - lo),
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            "taken_in": sum(1 for _, _, t in requests
                            if len(t) and lo < t[0] <= hi)}


def report(requests, seconds, window):
    starts = [i * SLICE for i in range(int(seconds // SLICE))]
    for lo in starts:
        print(json.dumps({"slice": windows(requests, lo, lo + SLICE)}),
              flush=True)
    for lo in starts:
        if lo + window <= seconds:
            print(json.dumps({"window": windows(requests, lo,
                                                lo + window)}),
                  flush=True)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=200.0)
    args = p.parse_args(argv)
    from benchmark.harness import Harness
    from benchmark.run import compile_cache
    import jax
    if jax.devices()[0].platform != "tpu":
        print("ramp: needs a TPU", file=sys.stderr)
        return 1
    compile_cache()
    import incubator_mxnet_tpu as mx
    h = Harness()
    requests, _ = long_run(h, h.cell(args.workload), args.seed, mx,
                           args.seconds)
    report(requests, args.seconds, h.spec["run_seconds"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
