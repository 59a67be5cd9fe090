#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Inputs and weights come from ``--seed``.  Set-up (imports, weights,
warm-up of the cell's own shapes, compilation on a first run) is
reported as ``setup_s``; then the cell is measured for ``--seconds``;
then what the timed path produced is compared with the plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``compared``:
every number compared beside its limit.  The same table ends standard
error.

Without a TPU, or with fewer chips than the cell asks for, it exits 1
at once and prints no result.  ``--rehearse <dir>`` goes on with
whatever platform jax found, for cells described under ``<dir>`` at
tiny sizes; it exits 2 and prints no result line either: a rehearsal
is never a measurement.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import importlib    # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", metavar="DIR",
                   help="a directory laid out like benchmark/ with a "
                        "BENCHMARK.json in it; never a result")
    return p.parse_args(argv)


def compile_cache():
    """jax's persistent cache: where JAX_COMPILATION_CACHE_DIR says,
    else at a fixed path in the checkout; every program kept, no cap
    (under the chip machine's 192 MiB cap an LRU cache evicts each
    program just before it is wanted: PERF.md, PR 21)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def measure(args, look_for_chip=True):
    """One run of one cell: the result line as a dict.  Raises
    SystemExit(1) where there is no TPU (``look_for_chip`` off, as in
    a rehearsal, goes on with whatever platform jax found)."""
    from benchmark.harness import Harness
    if args.rehearse:
        h = Harness(args.rehearse,
                    os.path.join(args.rehearse, "BENCHMARK.json"))
    else:
        h = Harness()
    cell = h.cell(args.workload)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if look_for_chip:
        for bad, why in (
                (dev.platform != "tpu",
                 f"needs a TPU, jax found {dev.platform}"),
                (len(devices) < cell.chips,
                 f"{cell.name} needs {cell.chips} chips, jax found "
                 f"{len(devices)}"),
                (cell.limits is None,
                 f"no limits/{cell.name}.json")):
            if bad:
                print(f"benchmark: {why}", file=sys.stderr)
                raise SystemExit(1)
        peaks = h.peaks(dev.device_kind)    # unknown kind: an error
        compile_cache()
    else:
        peaks = next(iter(h._json("peaks.json").values()))

    import incubator_mxnet_tpu as mx
    from benchmark import correct
    driver = importlib.import_module(
        f"benchmark.{cell.traffic['kind']}")
    out = driver.run(h, cell, args, T_START, dev, mx)

    ok, compared = correct.verdict(out["numbers"], cell.limits)
    ctx = out["ctx"]
    if args.trace:
        ctx.update(peaks=peaks, chips=cell.chips)
        if ctx["trace"]:
            ctx.setdefault("flops_in_trace", ctx.get(
                "flops_per_step", 0) * ctx["trace"]["steps"])
        values = h.read_per_layer(cell.name, ctx)
        if ctx.get("notes"):
            print(json.dumps({"notes": ctx["notes"]}), flush=True)
        if ctx["trace"]:
            from benchmark.reduce_trace import families
            print(json.dumps({"families": families(
                ctx["trace"]["op_seconds"])}), flush=True)
    else:
        values = {m["name"]: {"value": out["end_to_end"][m["name"]],
                              "unit": m["unit"]}
                  for m in h.metrics(cell.name, "end_to_end")}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": ok, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": values,
              "device": device}
    if args.trace and ctx["trace"]:
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["compared"] = compared
    return result


def main(argv=None):
    args = parse_args(argv)
    result = measure(args, look_for_chip=not args.rehearse)
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']!r} limit "
              f"{row['limit']!r} {row.get('where', '')}",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print(f"benchmark: rehearsal, not a result: "
              f"{json.dumps(result)}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
