"""Collective / transfer bandwidth benchmark (TPU-native analog of
ref: tools/bandwidth/measure.py — which pushes model-sized gradients
through KVStore and reports GB/s per batch).

Here the comm substrate is XLA collectives over the jax device mesh
(ICI on real pods), so what gets measured is:

* ``collectives`` — psum (allreduce), psum_scatter (reduce-scatter),
  all_gather and ppermute over an N-device mesh, graduated sizes.
  Reported as *bus bandwidth* per device: for allreduce the data a
  device moves is ``2 (n-1)/n * bytes`` (ring lower bound), for
  reduce-scatter / all-gather ``(n-1)/n * bytes``, for ppermute
  ``bytes``.
* ``kvstore`` — the framework path the reference measures: push+pull
  of ResNet-50-shaped gradients through ``mx.kv.create('device')``.
* ``h2d`` — host→device + device→host numpy transfer.

Run on the 8-virtual-device CPU mesh for correctness, on hardware for
numbers.  Prints one JSON line per measurement + a summary line.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from anywhere: put the repo root on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _time_op(fn, x, iters):
    """Independent calls on the same input (outputs may change shape,
    so chaining is wrong); device execution is serial, one sync at
    the end."""
    jax.block_until_ready(fn(x))             # warmup/compile
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_collectives(sizes_mb, iters, emit):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        emit({"bench": "collectives", "skipped":
              f"needs >=2 devices, have {n}"})
        return
    mesh = Mesh(np.asarray(devs), ("x",))
    sharded = NamedSharding(mesh, P("x"))

    def shmap(f):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x")))

    # stable under iteration: mean keeps values bounded
    ops = {
        "allreduce": (shmap(lambda x: jax.lax.psum(x, "x") / n),
                      2.0 * (n - 1) / n),
        "reduce_scatter": (
            shmap(lambda x: jax.lax.psum_scatter(
                x, "x", tiled=True) / n),
            (n - 1) / n),
        "all_gather": (
            shmap(lambda x: jax.lax.all_gather(
                x, "x", tiled=True) / n),
            (n - 1) / n),
        "ppermute": (
            shmap(lambda x: jax.lax.ppermute(
                x, "x", [(i, (i + 1) % n) for i in range(n)])),
            1.0),
    }
    for mb in sizes_mb:
        nelem = int(mb * (1 << 20) // 4)
        nelem -= nelem % (n * n)          # divisible for scatter/gather
        per_dev_bytes = nelem // n * 4
        base = jax.device_put(
            jax.numpy.ones((nelem,), jax.numpy.float32), sharded)
        for name, (fn, factor) in ops.items():
            if name == "reduce_scatter":
                x = base
            elif name == "all_gather":
                small = int(nelem // n) - int(nelem // n) % n
                x = jax.device_put(
                    jax.numpy.ones((small,), jax.numpy.float32),
                    sharded)
            else:
                x = base
            # per-call shapes differ for scatter/gather; re-time from
            # their own input size
            in_bytes = x.nbytes // n
            dt = _time_op(fn, x, iters)
            emit({"bench": "collectives", "op": name, "devices": n,
                  "per_device_mb": round(in_bytes / (1 << 20), 3),
                  "ms": round(dt * 1e3, 3),
                  "bus_gbps": round(factor * in_bytes / dt / 1e9, 3)})


def bench_kvstore(iters, emit):
    """Reference-parity path: ResNet-50-shaped grads via KVStore."""
    import incubator_mxnet_tpu as mx
    shapes = [(64, 3, 7, 7), (512, 512, 3, 3), (2048, 512, 1, 1),
              (1000, 2048), (2048,), (512, 1024, 1, 1),
              (1024, 256, 1, 1), (256, 256, 3, 3)]
    kv = mx.kv.create("device")
    vals = [mx.nd.ones(s) for s in shapes]
    for i, v in enumerate(vals):
        kv.init(i, v)
    outs = [mx.nd.zeros(s) for s in shapes]
    total = sum(int(np.prod(s)) * 4 for s in shapes)

    def step():
        for i, v in enumerate(vals):
            kv.push(i, v)
        for i, o in enumerate(outs):
            kv.pull(i, out=o)
        for o in outs:                   # sync every pull, not just one
            o.asnumpy()
    step()
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    dt = (time.perf_counter() - t0) / iters
    emit({"bench": "kvstore", "type": "device",
          "payload_mb": round(total / (1 << 20), 2),
          "ms": round(dt * 1e3, 3),
          "gbps": round(2 * total / dt / 1e9, 3)})


def bench_h2d(sizes_mb, iters, emit):
    dev = jax.devices()[0]
    for mb in sizes_mb:
        host = np.ones((int(mb * (1 << 20) // 4),), np.float32)
        jax.device_get(jax.device_put(host, dev))    # warmup
        t0 = time.perf_counter()
        for _ in range(iters):
            x = jax.device_put(host, dev)
            jax.block_until_ready(x)
        h2d = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            np.asarray(jax.device_get(x))
        d2h = (time.perf_counter() - t0) / iters
        emit({"bench": "h2d", "mb": mb,
              "h2d_ms": round(h2d * 1e3, 3),
              "h2d_gbps": round(host.nbytes / h2d / 1e9, 3),
              "d2h_ms": round(d2h * 1e3, 3),
              "d2h_gbps": round(host.nbytes / d2h / 1e9, 3)})


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--benches", default="collectives,kvstore,h2d")
    p.add_argument("--sizes-mb", default="1,16,64")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                   help="force N virtual CPU devices (testing)")
    args = p.parse_args(argv)
    if args.cpu_mesh:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_mesh}")
        import jax as _jax
        _jax.config.update("jax_platforms", "cpu")
    global jax
    import jax

    results = []

    def emit(rec):
        rec["device_kind"] = jax.devices()[0].device_kind
        results.append(rec)
        print(json.dumps(rec), flush=True)

    sizes = [float(s) for s in args.sizes_mb.split(",")]
    benches = set(args.benches.split(","))
    if "collectives" in benches:
        bench_collectives(sizes, args.iters, emit)
    if "kvstore" in benches:
        bench_kvstore(args.iters, emit)
    if "h2d" in benches:
        bench_h2d(sizes, args.iters, emit)
    best = max((r["bus_gbps"] for r in results
                if r.get("op") == "allreduce"), default=0)
    print(json.dumps({"summary": "bandwidth", "n_results": len(results),
                      "peak_allreduce_bus_gbps": best}))
    return results


if __name__ == "__main__":
    main()
