#!/usr/bin/env python
"""DCGAN through the Gluon imperative path (ref role:
example/gluon/dcgan.py — ConvTranspose generator vs conv
discriminator, alternating adversarial updates with
SigmoidBinaryCrossEntropyLoss and two Trainers).  Both nets are
hybridized: the loop is imperative, a net's forward is one program.

Data is synthetic (zero-egress): 16x16 single-channel images of a
bright centered disk over a dark field, with per-sample radius and
intensity jitter.  The generator has to learn the global disk
structure from noise; the discriminator has to tell disks from the
generator's early blobs.

--quick is the CI gate.  Adversarial losses oscillate by design, so
the gate is distributional, not a loss curve: after training, the
generated images' disk-ness statistic (energy inside the disk region
vs outside) must move decisively from its init value toward the real
data's, and the discriminator must no longer separate real from fake
perfectly.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

IMG = 16


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="Gluon DCGAN")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps", type=int, default=400)
    p.add_argument("--latent", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--quick", action="store_true",
                   help="CI mode: short run + distribution gate")
    return p.parse_args(argv)


def real_batch(rs, n):
    """Bright disk, radius 3-5, centered +-1 px, on a dark field."""
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    out = np.empty((n, 1, IMG, IMG), np.float32)
    for i in range(n):
        cy = IMG / 2 + rs.uniform(-1, 1)
        cx = IMG / 2 + rs.uniform(-1, 1)
        r = rs.uniform(3.0, 5.0)
        d = np.sqrt((yy - cy) ** 2 + (xx - cx) ** 2)
        img = np.where(d < r, rs.uniform(0.7, 1.0), 0.0)
        out[i, 0] = img + rs.randn(IMG, IMG) * 0.05
    return np.clip(out, -1, 1) * 2 - 1   # in [-1, 1] like tanh


def diskness(imgs):
    """Energy ratio: mean pixel inside the canonical disk region
    minus mean outside.  Real data scores ~+1.4; random noise ~0."""
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    d = np.sqrt((yy - IMG / 2) ** 2 + (xx - IMG / 2) ** 2)
    inside = d < 3.0
    outside = d > 6.0
    x = imgs.reshape(-1, IMG, IMG)
    return float(x[:, inside].mean() - x[:, outside].mean())


def build_nets(latent):
    from incubator_mxnet_tpu.gluon import nn

    g = nn.HybridSequential(prefix="gen_")
    with g.name_scope():
        g.add(nn.Dense(4 * 4 * 32))
        g.add(nn.HybridLambda(
            lambda F, x: F.reshape(x, shape=(-1, 32, 4, 4)), "to4x4"))
        g.add(nn.BatchNorm())
        g.add(nn.Activation("relu"))
        # 4x4 -> 8x8 -> 16x16
        g.add(nn.Conv2DTranspose(16, 4, strides=2, padding=1))
        g.add(nn.BatchNorm())
        g.add(nn.Activation("relu"))
        g.add(nn.Conv2DTranspose(1, 4, strides=2, padding=1,
                                 activation="tanh"))

    d = nn.HybridSequential(prefix="disc_")
    with d.name_scope():
        d.add(nn.Conv2D(16, 4, strides=2, padding=1))   # 16 -> 8
        d.add(nn.LeakyReLU(0.2))
        d.add(nn.Conv2D(32, 4, strides=2, padding=1))   # 8 -> 4
        d.add(nn.LeakyReLU(0.2))
        d.add(nn.Flatten())
        d.add(nn.Dense(1))
    return g, d


def main(argv=None):
    from incubator_mxnet_tpu.utils.platform import maybe_force_cpu
    maybe_force_cpu()
    args = parse_args(argv)
    if args.quick:
        args.steps = 400

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, nd

    mx.random.seed(0)
    rs = np.random.RandomState(0)

    gen, disc = build_nets(args.latent)
    gen.initialize(mx.init.Normal(0.02))
    disc.initialize(mx.init.Normal(0.02))
    # one compiled program a net and batch shape instead of an eager
    # dispatch an operator: the loop below stays imperative
    gen.hybridize()
    disc.hybridize()

    g_tr = gluon.Trainer(gen.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    d_tr = gluon.Trainer(disc.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()

    def sample_fakes(n):
        z = nd.array(rs.randn(n, args.latent).astype(np.float32))
        return gen(z)

    init_disk = diskness(sample_fakes(64).asnumpy())
    real_disk = diskness(real_batch(rs, 64))

    ones = nd.array(np.ones((args.batch_size,), np.float32))
    zeros = nd.array(np.zeros((args.batch_size,), np.float32))
    d_loss = g_loss = None
    for it in range(args.steps):
        real = nd.array(real_batch(rs, args.batch_size))
        # --- discriminator: real -> 1, fake -> 0 ---
        fake = sample_fakes(args.batch_size)
        with autograd.record():
            lr_ = bce(disc(real), ones)
            lf_ = bce(disc(fake.detach()), zeros)
            d_loss = (lr_ + lf_).mean()
        d_loss.backward()
        d_tr.step(args.batch_size)
        # --- generator: fool the discriminator ---
        with autograd.record():
            fake = sample_fakes(args.batch_size)
            g_loss = bce(disc(fake), ones).mean()
        g_loss.backward()
        g_tr.step(args.batch_size)
        if it % 50 == 0:
            print(f"step {it}: d_loss={float(d_loss.asnumpy()):.4f} "
                  f"g_loss={float(g_loss.asnumpy()):.4f}", flush=True)

    fakes = sample_fakes(64)
    final_disk = diskness(fakes.asnumpy())
    # how well does D still separate? (0.5 = fooled)
    import jax.nn as jnn
    d_fake = np.asarray(jnn.sigmoid(
        disc(fakes).asnumpy())).mean()

    summary = dict(
        steps=args.steps,
        real_diskness=real_disk, init_diskness=init_disk,
        final_diskness=final_disk, d_on_fake=float(d_fake),
        d_loss=float(d_loss.asnumpy()),
        g_loss=float(g_loss.asnumpy()))
    print(json.dumps(summary))
    if args.quick:
        # generator moved >=50% of the way from its init statistic
        # to the real data's (GAN training is noisy; the point the
        # gate proves is that the adversarial game moves the
        # generator's distribution, not photorealism in 400 steps)
        gap0 = abs(real_disk - init_disk)
        gap1 = abs(real_disk - final_disk)
        assert gap1 < 0.5 * gap0, (gap0, gap1)
        # discriminator no longer calls every fake a fake
        assert d_fake > 0.05, d_fake
    return summary


if __name__ == "__main__":
    main()
