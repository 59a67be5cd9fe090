#!/usr/bin/env python
"""Model-parallel LSTM: each layer pinned to a different device via
ctx_group / group2ctx (ref role: example/model-parallel-lstm/lstm.py,
which unrolls a symbolic LSTM and places each layer's weights on its
own GPU through `group2ctx`).

On the 8-virtual-device CPU mesh (or real chips) the layers land on
distinct jax devices with cross-device copies inserted at the stage
boundaries — the reference's manual model-parallelism, TPU-style.

The task is synthetic sequence regression (zero-egress): predict the
next value of a noisy two-tone sine from the previous `seq_len`
samples.  --quick is the CI gate: placement is asserted per layer
and final MSE must drop below 30% of the first epoch's.  It unrolls 4
steps, not 12: with ``group2ctx`` the executor walks the unrolled graph
operator by operator and linearizes it anew every step, so a step
costs what the graph has nodes; 5 epochs of 20 batches.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="model-parallel LSTM")
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=12)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--quick", action="store_true",
                   help="CI mode: placement + convergence gate")
    return p.parse_args(argv)


def build(mx, num_layers, hidden, seq_len):
    """Unrolled multi-layer LSTM; layer i lives in ctx group
    ``layer_i``.  Weights are shared across time by name: the t-index
    is only on the node name, the arg names come from explicit
    Variables."""
    data = mx.sym.Variable("data")        # (N, T)
    label = mx.sym.Variable("label")      # (N,)
    xs = mx.sym.SliceChannel(data, num_outputs=seq_len, axis=1,
                             squeeze_axis=False, name="tslice")
    # per-layer shared weights
    weights = {}
    for l in range(num_layers):
        with mx.AttrScope(ctx_group=f"layer_{l}"):
            weights[l] = dict(
                i2h_w=mx.sym.Variable(f"l{l}_i2h_weight"),
                i2h_b=mx.sym.Variable(f"l{l}_i2h_bias"),
                h2h_w=mx.sym.Variable(f"l{l}_h2h_weight"),
                h2h_b=mx.sym.Variable(f"l{l}_h2h_bias"),
                h0=mx.sym.Variable(f"l{l}_init_h"),
                c0=mx.sym.Variable(f"l{l}_init_c"))

    def step(x, h, c, l, t):
        w = weights[l]
        i2h = mx.sym.FullyConnected(
            x, weight=w["i2h_w"], bias=w["i2h_b"],
            num_hidden=4 * build.hidden, name=f"l{l}_i2h_t{t}")
        h2h = mx.sym.FullyConnected(
            h, weight=w["h2h_w"], bias=w["h2h_b"],
            num_hidden=4 * build.hidden, name=f"l{l}_h2h_t{t}")
        sl = mx.sym.SliceChannel(i2h + h2h, num_outputs=4,
                                 name=f"l{l}_slice_t{t}")
        c = mx.sym.sigmoid(sl[2]) * c + \
            mx.sym.sigmoid(sl[0]) * mx.sym.tanh(sl[1])
        h = mx.sym.sigmoid(sl[3]) * mx.sym.tanh(c)
        return h, c

    build.hidden = hidden
    hs = {l: weights[l]["h0"] for l in range(num_layers)}
    cs = {l: weights[l]["c0"] for l in range(num_layers)}
    for t in range(seq_len):
        inp = xs[t]
        for l in range(num_layers):
            with mx.AttrScope(ctx_group=f"layer_{l}"):
                hs[l], cs[l] = step(inp, hs[l], cs[l], l, t)
            inp = hs[l]
    with mx.AttrScope(ctx_group=f"layer_{num_layers - 1}"):
        pred = mx.sym.FullyConnected(inp, num_hidden=1, name="pred")
        out = mx.sym.LinearRegressionOutput(pred, label=label,
                                            name="out")
    return out


def make_data(rs, n, seq_len):
    t0 = rs.uniform(0, 20, n)[:, None]
    t = t0 + np.arange(seq_len + 1)[None, :] * 0.3
    wave = (np.sin(t) + 0.5 * np.sin(2.3 * t)).astype(np.float32)
    wave += rs.randn(*wave.shape).astype(np.float32) * 0.02
    return wave[:, :-1], wave[:, -1:]


def main(argv=None):
    from incubator_mxnet_tpu.utils.platform import maybe_force_cpu
    maybe_force_cpu()
    args = parse_args(argv)
    if args.quick:
        args.epochs = 5
        args.seq_len = 4

    import jax
    import incubator_mxnet_tpu as mx

    mx.random.seed(0)
    rs = np.random.RandomState(0)

    n_dev = len(jax.devices())
    group2ctx = {f"layer_{l}": mx.cpu(l % n_dev)
                 if jax.devices()[0].platform == "cpu"
                 else mx.gpu(l % n_dev)
                 for l in range(args.num_layers)}
    sym = build(mx, args.num_layers, args.hidden, args.seq_len)

    shapes = dict(data=(args.batch_size, args.seq_len),
                  label=(args.batch_size, 1))
    for l in range(args.num_layers):
        shapes[f"l{l}_init_h"] = (args.batch_size, args.hidden)
        shapes[f"l{l}_init_c"] = (args.batch_size, args.hidden)
    grad_req = {n: "null" if n.endswith(("init_h", "init_c"))
                or n in ("data", "label") else "write"
                for n in sym.list_arguments()}
    texec = sym.simple_bind(mx.current_context(),
                            group2ctx=group2ctx, grad_req=grad_req,
                            **shapes)

    # --- the model-parallel assertion: each layer on its device ---
    placements = {}
    for arr, name in zip(texec.arg_arrays, sym.list_arguments()):
        if name.startswith("l") and "_" in name:
            l = int(name[1])
            want = group2ctx[f"layer_{l}"]
            assert arr.context == want, (name, arr.context, want)
            placements[name] = str(arr.context)

    # init
    init = mx.init.Xavier()
    for name, arr in zip(sym.list_arguments(), texec.arg_arrays):
        if name.endswith("weight"):
            init(mx.init.InitDesc(name), arr)
        elif name.endswith("bias") or name.endswith(("_h", "_c")):
            arr[:] = 0

    first = last = None
    n_batches = 20
    for ep in range(args.epochs):
        tot = 0.0
        for b in range(n_batches):
            x, y = make_data(rs, args.batch_size, args.seq_len)
            texec.arg_dict["data"][:] = x
            texec.arg_dict["label"][:] = y
            out = texec.forward(is_train=True)[0]
            texec.backward()
            mse = float(((out.asnumpy() - y) ** 2).mean())
            tot += mse
            for name, arr in zip(sym.list_arguments(),
                                 texec.arg_arrays):
                g = texec.grad_dict.get(name)
                if g is not None and grad_req.get(name) == "write":
                    arr[:] = arr.asnumpy() - args.lr * g.asnumpy()
        tot /= n_batches
        if first is None:
            first = tot
        last = tot
        print(f"epoch {ep}: mse={tot:.5f}", flush=True)

    summary = dict(layers=args.num_layers, devices=n_dev,
                   placements=sorted(set(placements.values())),
                   first_mse=first, final_mse=last)
    print(json.dumps(summary))
    if args.quick:
        assert len(set(placements.values())) == \
            min(args.num_layers, n_dev)
        assert last < 0.3 * first, (first, last)
    return summary


if __name__ == "__main__":
    main()
