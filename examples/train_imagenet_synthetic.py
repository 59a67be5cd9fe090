#!/usr/bin/env python
"""ImageNet-style image-classification training on synthetic data —
driver config 2 (ref: example/image-classification/common/fit.py:108,
train_imagenet.py).

Trains a model-zoo convnet through the mesh path: with
``--kv-store tpu`` (default) the whole step — forward, backward, dp
gradient psum, bf16-with-fp32-masters optimizer — is one compiled
executable (parallel.ShardedTrainStep); batches are prefetched to
device, so the step never waits on a host-to-device copy.

Runs unchanged on CPU (virtual mesh) and TPU.  --quick is the CI
gate: tiny shapes (resnet18_v1, 32x32, batch 32, 2 epochs of 4
steps), asserts the loss dropped.
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


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="synthetic image-classification training")
    p.add_argument("--network", default="resnet18_v1",
                   help="model-zoo factory name "
                   "(resnet18_v1/resnet50_v1/vgg11/alexnet/...)")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--num-epochs", type=int, default=2)
    p.add_argument("--iters-per-epoch", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--mom", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=1e-4)
    p.add_argument("--kv-store", default="tpu")
    p.add_argument("--compute-dtype", default="auto",
                   choices=["auto", "bfloat16", "float32"])
    p.add_argument("--quick", action="store_true",
                   help="tiny CI mode with a convergence gate")
    return p.parse_args(argv)


def main(argv=None):
    from incubator_mxnet_tpu.utils.platform import maybe_force_cpu
    maybe_force_cpu()
    args = parse_args(argv)
    if args.quick:
        args.network = "resnet18_v1"
        args.image_shape = "3,32,32"
        args.batch_size = 32
        args.num_classes = 10
        args.num_epochs = 2
        args.iters_per_epoch = 4
        args.lr = 0.05

    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel

    shape = tuple(int(s) for s in args.image_shape.split(","))
    platform = jax.devices()[0].platform
    if args.compute_dtype == "auto":
        cdt = jnp.bfloat16 if platform == "tpu" else None
    else:
        cdt = jnp.bfloat16 if args.compute_dtype == "bfloat16" \
            else None

    mx.random.seed(0)
    net = getattr(mx.gluon.model_zoo.vision, args.network)(
        classes=args.num_classes)
    net.initialize(mx.initializer.Xavier())
    pure = parallel.functionalize(
        net, jnp.zeros((1,) + shape, jnp.float32))

    mesh = parallel.current_mesh() or parallel.make_mesh()
    step = parallel.ShardedTrainStep(
        pure, optimizer="sgd",
        optimizer_params=dict(learning_rate=args.lr, momentum=args.mom,
                              wd=args.wd),
        mesh=mesh, compute_dtype=cdt)

    # synthetic dataset with learnable signal: class = brightest
    # channel-stripe, so accuracy/loss genuinely improve
    rs = np.random.RandomState(0)
    n_batches = 4
    xs, ys = [], []
    in_sh = step._input_sharding(1 + len(shape))
    lab_sh = step._input_sharding(1, is_label=True)
    for _ in range(n_batches):
        y = rs.randint(0, args.num_classes, (args.batch_size,))
        x = rs.rand(args.batch_size, *shape).astype(np.float32) * .1
        stripe = np.linspace(0.5, 1.5, args.num_classes)[y]
        x[np.arange(args.batch_size), y % shape[0]] += \
            stripe[:, None, None].astype(np.float32)
        xs.append(jax.device_put(x, in_sh))
        ys.append(jax.device_put(y.astype(np.int32), lab_sh))

    losses = []
    for epoch in range(args.num_epochs):
        t0 = time.perf_counter()
        ep = []
        for i in range(args.iters_per_epoch):
            loss = step(xs[i % n_batches], ys[i % n_batches])
        ep.append(float(loss))  # sync once per epoch
        dt = time.perf_counter() - t0
        img_s = args.batch_size * args.iters_per_epoch / dt
        losses.append(np.mean(ep))
        print(f"Epoch[{epoch}] loss={losses[-1]:.4f} "
              f"speed={img_s:.1f} samples/sec", flush=True)

    summary = {"network": args.network, "final_loss": losses[-1],
               "first_loss": losses[0], "platform": platform,
               "mesh_dp": mesh.shape["dp"]}
    print(json.dumps(summary), flush=True)
    if args.quick:
        assert losses[-1] < losses[0] * 0.7, losses
    return summary


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
