#!/usr/bin/env python
"""Bring-up smoke: the train and serve paths on one TPU, in one process.

    python chip_smoke.py            # one chip, four phases, full sizes
    python chip_smoke.py --chips 4  # only the dp=4 step against dp=1

Through ``import incubator_mxnet_tpu as mx``, any exception failing
the run:

1. eager + Module: an ``nd`` op under ``autograd.record()`` on
   ``mx.tpu(0)`` against numpy, then ``mx.mod.Module.fit`` of the
   MNIST-style MLP with ``kvstore='tpu'``;
2. ResNet-50 (batch 32, 224x224, bf16 compute over float32 masters,
   SGD+momentum) through ``parallel.ShardedTrainStep``;
3. the 150M ``TransformerLM`` (B=8, L=1024, Adam) through the same
   step, with the Pallas flash kernel in the lowered step, and that
   kernel's output and gradients against ``_reference_attention``;
4. ``serving.ServingEngine`` on that model: requests submitted
   together, streamed to the end, greedy tokens equal to
   ``generate()``, no block leaked.

The lines before the last are smoke observations (seconds, losses,
peak bytes), not benchmark metrics.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a
TPU the script exits non-zero at once and prints no result;
``--rehearse`` goes on with whatever platform jax found (tiny sizes
on the CPU, ``MXTPU_FLASH=1`` for the interpreted kernel) and still
exits non-zero with no result.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# kernel (f32 accumulation, bf16 result) against an f32 oracle: a few
# stacked bf16 roundings (2^-9 each), relative to the largest value
KERNEL_TOL = 2e-2
# dp=4 against dp=1, bf16 compute: the same sums in another order
DP_LOSS_RTOL = 1e-2
# eager f32 matmul on the TPU's default precision is one bf16 pass
EAGER_RTOL = 2e-2


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="go on without a TPU; never a result")
    p.add_argument("--mlp-samples", type=int, default=2048)
    p.add_argument("--resnet-batch", type=int, default=32)
    p.add_argument("--resnet-hw", type=int, default=224)
    p.add_argument("--resnet-steps", type=int, default=6)
    p.add_argument("--lm-vocab", type=int, default=32000)
    p.add_argument("--lm-d-model", type=int, default=1024)
    p.add_argument("--lm-layers", type=int, default=12)
    p.add_argument("--lm-heads", type=int, default=16)
    p.add_argument("--lm-batch", type=int, default=8)
    p.add_argument("--lm-seq", type=int, default=1024)
    p.add_argument("--lm-steps", type=int, default=5)
    p.add_argument("--kernel-shape", default="128,1024,64",
                   help="BH,L,d of the kernel comparison")
    p.add_argument("--kernel-window", type=int, default=256)
    p.add_argument("--serve-prompts", default="16,60,200,450,700,900",
                   help="prompt lengths, one request each")
    p.add_argument("--serve-new", type=int, default=32)
    return p.parse_args(argv)


def say(phase, **obs):
    print(json.dumps({"phase": phase, **obs}), flush=True)


def peak_bytes(dev):
    stats = dev.memory_stats()      # None where the backend has none
    return stats.get("peak_bytes_in_use") if stats else None


def run_steps(step, x, y, n):
    """n steps on one fixed batch: the losses, the cold first step's
    seconds (compile included) and the median warm step's."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(step(x, y)))     # the fetch is the barrier
        secs.append(time.perf_counter() - t0)
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return losses, secs[0], float(np.median(secs[1:]))


def lm_loss(outputs, labels):
    import jax
    import jax.numpy as jnp
    logits = outputs[0]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked.astype(jnp.float32))


def build_lm(mx, args, ctx):
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    mx.random.seed(args.seed)
    lm = TransformerLM(
        args.lm_vocab, d_model=args.lm_d_model,
        n_layers=args.lm_layers, n_heads=args.lm_heads,
        max_len=args.lm_seq)
    lm.initialize(mx.initializer.Xavier(), ctx=ctx)
    return lm


def lm_batch(args):
    rs = np.random.RandomState(args.seed + 2)
    shape = (args.lm_batch, args.lm_seq)
    return (rs.randint(0, args.lm_vocab, shape).astype(np.int32),
            rs.randint(0, args.lm_vocab, shape).astype(np.int32))


def lm_step(mx, lm, mesh, args, ctx):
    import jax.numpy as jnp
    return mx.parallel.ShardedTrainStep(
        lm, optimizer="adam",
        optimizer_params=dict(learning_rate=3e-4), loss_fn=lm_loss,
        example_args=[mx.nd.zeros((1, args.lm_seq), ctx=ctx,
                                  dtype="int32")],
        mesh=mesh, compute_dtype=jnp.bfloat16)


# ------------------------------------------------------------ phases
def phase_eager_module(mx, args, ctx, dev):
    from incubator_mxnet_tpu import autograd, nd
    rs = np.random.RandomState(args.seed)
    a = rs.randn(64, 128).astype(np.float32)
    w = rs.randn(128, 32).astype(np.float32)
    x, wt = nd.array(a, ctx=ctx), nd.array(w, ctx=ctx)
    wt.attach_grad()
    with autograd.record():
        loss = nd.sum(nd.relu(nd.dot(x, wt)) ** 2)
    loss.backward()
    assert wt.grad._data.devices() == {dev}, wt.grad._data.devices()
    h = np.maximum(a @ w, 0.0)
    np.testing.assert_allclose(loss.asnumpy(), (h ** 2).sum(),
                               rtol=EAGER_RTOL)
    want = a.T @ (2.0 * h)
    np.testing.assert_allclose(wt.grad.asnumpy(), want,
                               rtol=EAGER_RTOL,
                               atol=EAGER_RTOL * np.abs(want).max())

    # the MNIST-style MLP of examples/train_mnist.py on its stand-in
    # data: a bright bar whose place and direction is the class
    def digits(n):
        img = rs.rand(n, 28, 28).astype(np.float32) * 0.3
        y = rs.randint(0, 10, n)
        for i, c in enumerate(y):
            if c < 5:
                img[i, 4 + 4 * c:7 + 4 * c, 4:24] += 0.7
            else:
                img[i, 4:24, 4 + 4 * (c - 5):7 + 4 * (c - 5)] += 0.7
        return img.reshape(n, 784), y.astype(np.float32)

    net = mx.sym.Variable("data")
    for i, hidden in enumerate((128, 64)):
        net = mx.sym.Activation(
            mx.sym.FullyConnected(net, name=f"fc{i + 1}",
                                  num_hidden=hidden),
            name=f"relu{i + 1}", act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, name="fc3", num_hidden=10),
        name="softmax")
    xtr, ytr = digits(args.mlp_samples)
    xva, yva = digits(512)
    mod = mx.mod.Module(net, context=ctx)
    t0 = time.perf_counter()
    mod.fit(mx.io.NDArrayIter(xtr, ytr, 64, shuffle=True),
            num_epoch=2, kvstore="tpu", optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1, momentum=0.9),
            initializer=mx.initializer.Xavier())
    acc = float(mod.score(mx.io.NDArrayIter(xva, yva, 64), "acc")[0][1])
    assert acc > 0.8, f"Module.fit val acc {acc} (chance is 0.1)"
    say("eager+module", eager_loss=float(loss.asnumpy()),
        module_val_acc=acc, fit_seconds=time.perf_counter() - t0)


def phase_resnet(mx, args, ctx, dev):
    import jax.numpy as jnp
    mx.random.seed(args.seed)
    rs = np.random.RandomState(args.seed + 1)
    b, hw = args.resnet_batch, args.resnet_hw
    x = rs.rand(b, 3, hw, hw).astype(np.float32)
    y = rs.randint(0, 1000, (b,)).astype(np.int32)
    net = mx.gluon.model_zoo.vision.resnet50_v1()
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    step = mx.parallel.ShardedTrainStep(
        net, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.02, momentum=0.9,
                              wd=1e-4),
        example_args=[mx.nd.array(x[:1], ctx=ctx)],
        mesh=mx.parallel.make_mesh(devices=[dev]),
        compute_dtype=jnp.bfloat16)
    losses, cold_s, warm_s = run_steps(step, x, y, args.resnet_steps)
    say("resnet50", batch=b, hw=hw, losses=losses,
        cold_step_seconds=cold_s, warm_step_seconds=warm_s,
        peak_bytes_in_use=peak_bytes(dev))


def phase_lm(mx, args, ctx, dev):
    """Returns the trained model for the serve phase."""
    lm = build_lm(mx, args, ctx)
    toks, labels = lm_batch(args)
    step = lm_step(mx, lm, mx.parallel.make_mesh(devices=[dev]),
                   args, ctx)
    kernel_in_step = "tpu_custom_call" in step.lowered(
        toks, labels).as_text()
    assert kernel_in_step or dev.platform != "tpu", \
        "no Pallas kernel (tpu_custom_call) in the lowered LM step"
    losses, cold_s, warm_s = run_steps(step, toks, labels,
                                       args.lm_steps)
    step.write_back()       # the serve phase serves what was trained
    say("transformer_lm", batch=args.lm_batch, seq=args.lm_seq,
        layers=args.lm_layers, kernel_in_step=kernel_in_step,
        losses=losses, cold_step_seconds=cold_s,
        warm_step_seconds=warm_s, peak_bytes_in_use=peak_bytes(dev))
    return lm


def phase_kernel(mx, args, ctx):
    """The flash op's output and tape gradients against the XLA
    reference held to f32 matmuls, causal and sliding-window."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import autograd, nd
    from incubator_mxnet_tpu.ops.flash import _reference_attention

    bh, length, d = (int(v) for v in args.kernel_shape.split(","))
    rs = np.random.RandomState(args.seed + 3)
    q, k, v, g = (nd.array(rs.randn(bh, length, d), ctx=ctx,
                           dtype="bfloat16") for _ in range(4))
    errs = {}
    for window in (0, args.kernel_window):
        for t in (q, k, v):
            t.attach_grad()
        with autograd.record():
            out = nd._internal._flash_attention(
                q, k, v, causal=True, window=window)
            loss = nd.sum(out * g)
        loss.backward()

        def ref_loss(rq, rk, rv):
            o = _reference_attention(rq, rk, rv, True, d ** -0.5,
                                     window=window)
            return jnp.sum(o.astype(jnp.float32)
                           * g._data.astype(jnp.float32)), o

        with jax.default_matmul_precision("float32"):
            (_, ref_out), ref_grads = jax.value_and_grad(
                ref_loss, argnums=(0, 1, 2), has_aux=True)(
                    q._data, k._data, v._data)
        pairs = [("out", out._data, ref_out)] + [
            (f"d{n}", t.grad._data, r)
            for n, t, r in zip("qkv", (q, k, v), ref_grads)]
        for name, got, want in pairs:
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            err = float(np.abs(got - want).max() / np.abs(want).max())
            assert err <= KERNEL_TOL, (name, window, err)
            errs[f"{name}_w{window}"] = err
    say("flash_kernel", shape=[bh, length, d], dtype="bfloat16",
        tolerance=KERNEL_TOL, max_err_over_max_ref=errs)


def phase_serve(mx, args, lm):
    """The engine's greedy tokens against ``generate()``, both held to
    f32 matmuls.  The weights are served in float32, and at the TPU's
    default precision (one bf16 pass) the two differently shaped
    programs can settle a near-tie between the two best logits
    differently: one token flips and the rest follow (seen on the
    chip, PERF.md ISSUE 21).  That is arithmetic, not scheduling,
    paging or batching, which is what this phase checks."""
    import jax
    rs = np.random.RandomState(args.seed + 4)
    prompts = [[int(t) for t in rs.randint(0, args.lm_vocab, n)]
               for n in (int(v) for v in args.serve_prompts.split(","))]
    with jax.default_matmul_precision("float32"):
        eng = mx.serving.ServingEngine(lm)
        reqs = [eng.submit(p, args.serve_new) for p in prompts]
        t0 = time.perf_counter()
        streamed = {r.id: [] for r in reqs}
        for req, tok in eng.stream():
            streamed[req.id].append(int(tok))
        serve_s = time.perf_counter() - t0
        for req, prompt in zip(reqs, prompts):
            assert req.state == "finished", (req.id, req.state)
            want = lm.generate(
                mx.nd.array(np.asarray([prompt], np.int32)),
                args.serve_new).asnumpy()[0][len(prompt):]
            assert streamed[req.id] == [int(t) for t in want], (
                f"request {req.id} (prompt {len(prompt)}): engine "
                f"{streamed[req.id]} != generate "
                f"{[int(t) for t in want]}")
    # what is still held is held by the prefix cache alone
    assert eng.pool.live() == eng.cache.block_refs(), eng.pool.live()
    say("serve", requests=len(reqs),
        prompt_lengths=[len(p) for p in prompts],
        new_tokens=args.serve_new, stream_seconds=serve_s,
        weights_dtype=str(lm.head.weight.data().dtype),
        matmul_precision="float32")


def phase_four_chips(mx, args, ctx):
    """The dp=4 step against the same steps on one device."""
    import jax
    devs = jax.devices()
    assert len(devs) >= 4, f"--chips 4 needs 4 devices, found {devs}"
    lm = build_lm(mx, args, ctx)
    toks, labels = lm_batch(args)
    mesh4 = mx.parallel.make_mesh(dp=4, devices=devs[:4])
    step4 = lm_step(mx, lm, mesh4, args, ctx)
    step1 = lm_step(mx, lm, mx.parallel.make_mesh(devices=devs[:1]),
                    args, ctx)
    text = step4.lowered(toks, labels).compile().as_text()
    assert "all-reduce" in text, "no all-reduce in the dp=4 step"
    xs = jax.device_put(toks, mx.parallel.shard_batch(mesh4, 2))
    ys = jax.device_put(labels, mx.parallel.shard_batch(mesh4, 2))
    a_param = next(iter(step4.params.values()))
    for name, arr in (("batch", xs), ("param", a_param)):
        held = {s.device for s in arr.addressable_shards}
        assert held == set(devs[:4]), (name, held)
    losses = {4: run_steps(step4, xs, ys, args.lm_steps),
              1: run_steps(step1, toks, labels, args.lm_steps)}
    np.testing.assert_allclose(losses[4][0], losses[1][0],
                               rtol=DP_LOSS_RTOL)
    say("four_chips",
        batch_shards={str(s.device): [str(i) for i in s.index]
                      for s in xs.addressable_shards},
        param_devices=sorted(str(s.device)
                             for s in a_param.addressable_shards),
        all_reduce_in_step=True,
        kernel_in_step="tpu_custom_call" in text,
        losses_dp4=losses[4][0], losses_dp1=losses[1][0],
        loss_rtol=DP_LOSS_RTOL,
        cold_step_seconds={n: v[1] for n, v in losses.items()},
        warm_step_seconds={n: v[2] for n, v in losses.items()},
        peak_bytes_in_use=peak_bytes(devs[0]))


def main(argv=None):
    args = parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, jax found {dev.platform}",
              file=sys.stderr)
        return 1

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import perf
    from incubator_mxnet_tpu.utils.platform import enable_compile_cache
    cache_dir = enable_compile_cache()
    ctx = mx.tpu(0)
    assert ctx.jax_device == dev, (ctx.jax_device, dev)
    caps = perf.caps_for(dev)       # an unknown kind is an error
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), device_db_row=caps.kind,
        hbm_bytes=caps.hbm_bytes, compile_cache_dir=cache_dir)
    if dev.platform == "tpu":
        assert caps.kind in ("v5e", "v5 lite", "v5litepod"), caps.kind

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(mx, args, ctx)
    else:
        phase_eager_module(mx, args, ctx, dev)
        phase_resnet(mx, args, ctx, dev)
        lm = phase_lm(mx, args, ctx, dev)
        phase_kernel(mx, args, ctx)
        phase_serve(mx, args, lm)
    say("done", seconds=time.perf_counter() - t0,
        compile_cache_entries=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0)

    if dev.platform != "tpu":
        print(f"chip_smoke: rehearsal on {dev.platform} passed; "
              "not a result", file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
