"""Profile the ResNet-50 train step on the device jax finds.

Splits the bench's step into:
  1. host->device transfer of the input batch
  2. compiled step with device-resident inputs
  3. compiled step fed host numpy on every call
  4. forward-only compiled time
so PERF.md can state where the time goes.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def timed(fn, n=10, warmup=2, sync_each=False):
    """sync_each=True serializes iterations (use when the work itself
    is async w.r.t. dispatch, e.g. transfers); the default syncs once
    at the end so compute steps pipeline as they do in training."""
    import jax
    for _ in range(warmup):
        r = fn()
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
        if sync_each:
            jax.block_until_ready(r)
    if not sync_each:
        jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print("device:", dev, flush=True)

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.utils.platform import \
        enable_compile_cache
    enable_compile_cache()

    mx.random.seed(0)
    net = mx.gluon.model_zoo.vision.resnet50_v1()
    net.initialize(mx.initializer.Xavier())
    pure = parallel.functionalize(net, jnp.zeros((1, 3, 224, 224),
                                                 jnp.float32))

    B = 32
    rs = np.random.RandomState(0)
    x_np = np.asarray(rs.rand(B, 3, 224, 224), np.float32)
    y_np = np.asarray(rs.randint(0, 1000, (B,)), np.int32)

    # --- 1. raw transfer cost ------------------------------------------
    def xfer():
        return jax.device_put(x_np, dev)
    t = timed(xfer, n=5, warmup=1, sync_each=True)
    mb = x_np.nbytes / 1e6
    print(f"transfer {mb:.1f} MB fp32: {t*1e3:.1f} ms "
          f"({mb/t/1e3:.2f} GB/s)", flush=True)

    # --- 2. compiled step, device-resident inputs ----------------------
    step = parallel.ShardedTrainStep(
        pure, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9, wd=1e-4),
        mesh=parallel.make_mesh(devices=[dev]),
        compute_dtype=jnp.bfloat16)
    jax.block_until_ready(step.params)

    rng = jax.random.PRNGKey(0)
    x_dev = jax.device_put(x_np, dev)
    y_dev = jax.device_put(y_np, dev)

    t0 = time.perf_counter()
    loss = step(x_dev, y_dev, rng=rng)
    float(loss)
    print(f"compile+first step: {time.perf_counter()-t0:.1f} s",
          flush=True)

    def dev_step():
        return step(x_dev, y_dev, rng=rng)
    t = timed(dev_step, n=20, warmup=3)
    print(f"step (device-resident x/y): {t*1e3:.2f} ms "
          f"-> {B/t:.0f} img/s", flush=True)

    # --- 3. step with per-call numpy transfer (old bench behavior) -----
    def np_step():
        return step(x_np, y_np, rng=rng)
    t = timed(np_step, n=5, warmup=1)
    print(f"step (numpy x/y each call): {t*1e3:.2f} ms "
          f"-> {B/t:.0f} img/s", flush=True)

    # --- 4. forward only ----------------------------------------------
    @jax.jit
    def fwd(p, s, x):
        cast = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.bfloat16)
            if jnp.issubdtype(v.dtype, jnp.floating) else v, p)
        outs, _ = pure.apply(cast, s, [x.astype(jnp.bfloat16)], rng,
                             training=False)
        return outs[0]

    def fwd_step():
        return fwd(step.params, step.states, x_dev)
    try:
        t = timed(fwd_step, n=20, warmup=3)
        print(f"forward only (bf16): {t*1e3:.2f} ms", flush=True)
    except Exception as e:
        print("forward-only probe failed:", e, flush=True)


if __name__ == "__main__":
    main()
