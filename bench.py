"""Benchmark: ResNet-50 training throughput + MFU on one chip.

Mirrors the reference's headline number — ResNet-50 ImageNet training
throughput at batch 32 (ref: example/image-classification/README.md:
147-156 — 109 img/s on 1x K80) — and reports MFU against the chip's
peak, since the north star (BASELINE.json) is >=55% MFU.

The measured step is the full compiled fwd+bwd+SGD-momentum update
through ShardedTrainStep (the kvstore='tpu' path) on synthetic
ImageNet-shaped data, bf16 compute with fp32 master weights on TPU
(the reference's multi_precision analog).

Runs on the device jax finds.  With no accelerator it exits non-zero
at once, unless ``JAX_PLATFORMS=cpu`` says the CPU is what was asked
for; such a run is stamped ``"platform": "cpu"`` and reports no MFU.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "mfu", "platform",
   "device_kind", "device_count", ...}
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 109.0  # ResNet-50 batch 32, 1x K80 (BASELINE.md)


def _stage(msg, tag=""):
    """Timestamped stderr breadcrumb: a run killed by a time limit
    must show WHERE it was."""
    label = f"bench[{tag} " if tag else "bench["
    print(f"{label}{time.strftime('%H:%M:%S')}]: {msg}",
          file=sys.stderr, flush=True)
BATCH = int(os.environ.get("MXTPU_BENCH_BATCH", "32"))
WARMUP_STEPS = 3
MEASURE_STEPS = 20
# ResNet-50 @224 train FLOPs per image with multiply-add counted as
# 2 — the convention of both perf.cost_model and the hardware peaks,
# so MFU numerator and denominator finally agree.  7.826 GFLOPs fwd
# is the graph cost pass's count for resnet50_v1 at (1,3,224,224);
# train step ~= 3x fwd.  No longer a source of truth: the bench
# recomputes it from the traced graph and dies loudly past +-2%
# drift (_crosscheck_resnet_flops).  The pre-r18 constant 3*4.089e9
# counted multiply-adds as 1, halving reported MFU.
FLOPS_PER_IMG = 3 * 7.826e9


def _device_stamp(dev):
    """What every result line says about where it ran."""
    import jax
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _mfu_fields(dev, achieved_flops):
    """MFU against the device DB's peak (perf/device_db.py; a kind it
    does not know is an error).  A CPU run reports none: its time is
    not a device metric."""
    if dev.platform == "cpu":
        return {}
    from incubator_mxnet_tpu.perf import peak_flops
    peak = peak_flops(dev, "bfloat16")
    return {"mfu": round(achieved_flops / peak, 4),
            "achieved_tflops": round(achieved_flops / 1e12, 2),
            "peak_tflops": round(peak / 1e12, 1)}


def _crosscheck_resnet_flops(net):
    """FLOPS_PER_IMG is a cross-check, not a source of truth: the
    graph cost pass recomputes the traced model's train FLOPs and a
    >2% disagreement (model edit, cost-model regression) kills the
    bench before it prints a wrong MFU."""
    from incubator_mxnet_tpu import perf, sym
    s = net._to_symbol(sym.Variable("data"))
    rep = perf.symbol_cost(s, {"data": (1, 3, 224, 224)}).scaled(3.0)
    drift = abs(rep.flops - FLOPS_PER_IMG) / FLOPS_PER_IMG
    assert drift <= 0.02, (
        f"FLOPS_PER_IMG={FLOPS_PER_IMG:.4e} disagrees with the graph "
        f"cost pass {rep.flops:.4e} by {drift:.1%} (>2%)")
    return rep


def _bench_transformer(dev, platform):
    """Secondary headline: decoder-LM training step MFU.  ResNet-50 is
    HBM-bound at ~0.12-0.15 MFU on one chip (PERF.md); the >=0.55 MFU
    north star is a matmul-dominated workload, which this measures.
    Run with MXTPU_BENCH_MODEL=transformer."""
    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM

    B = int(os.environ.get("MXTPU_BENCH_BATCH", "8"))
    L = int(os.environ.get("MXTPU_BENCH_SEQ", "1024"))
    MOE = int(os.environ.get("MXTPU_BENCH_MOE", "0"))
    WINDOW = int(os.environ.get("MXTPU_BENCH_WINDOW", "0"))
    V, D, LAYERS, HEADS = 32000, 1024, 12, 16

    def stage(msg):
        _stage(msg, tag="transformer")

    stage("building model")
    mx.random.seed(0)
    net = TransformerLM(V, d_model=D, n_layers=LAYERS,
                        n_heads=HEADS, max_len=L,
                        moe_experts=MOE, attn_window=WINDOW)
    net.initialize(mx.initializer.Xavier())
    ex = mx.nd.array(np.zeros((2, L), "int32"))
    stage("model built; creating mesh step")

    def lm_loss(outputs, labels):
        # logsumexp - picked, NOT log_softmax: avoids materializing
        # the full [B, L, V] fp32 log-prob tensor (~1 GB at these
        # shapes) — the lse reduction fuses with the convert and the
        # gather touches only [B, L]
        logits = outputs[0]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(lse - picked.astype(jnp.float32))
        if MOE:
            ce = ce + 0.01 * outputs[1]   # router load-balance aux
        return ce

    compute_dtype = jnp.bfloat16 if platform != "cpu" else None
    step = parallel.ShardedTrainStep(
        net, optimizer="adam",
        optimizer_params=dict(learning_rate=1e-4),
        loss_fn=lm_loss, example_args=[ex],
        mesh=parallel.make_mesh(devices=[dev]),
        compute_dtype=compute_dtype)

    rs = np.random.RandomState(0)
    stage("step created; transferring token batch")
    toks = jax.device_put(
        np.asarray(rs.randint(0, V, (B, L)), np.int32), dev)
    labels = jax.device_put(
        np.asarray(rs.randint(0, V, (B, L)), np.int32), dev)
    jax.block_until_ready((toks, labels))
    stage("batch resident; compiling + warming up")

    warm, meas = 2, 10
    t0 = time.perf_counter()
    for _ in range(warm):
        loss = step(toks, labels)
    float(loss)
    print(f"bench[transformer]: warmup+compile "
          f"{time.perf_counter() - t0:.1f}s on {platform}",
          file=sys.stderr)
    t0 = time.perf_counter()
    for _ in range(meas):
        loss = step(toks, labels)
    final_loss = float(loss)
    dt = time.perf_counter() - t0

    tok_s = B * L * meas / dt
    flops_tok = net.train_flops_per_token(L)
    # cross-check (not two truths): the model's own accounting must
    # agree with the perf package's transformer formula within 2%
    from incubator_mxnet_tpu import perf
    ref_tok = perf.transformer_train_flops_per_token(
        d_model=D, n_layers=LAYERS, vocab=V, seq_len=L,
        n_heads=HEADS, attn_window=WINDOW, moe_experts=MOE)
    assert abs(flops_tok - ref_tok) <= 0.02 * ref_tok, (
        f"train_flops_per_token {flops_tok:.4e} vs cost model "
        f"{ref_tok:.4e}")
    assert np.isfinite(final_loss), final_loss
    print(json.dumps({
        "metric": f"transformer_lm_150m{'_moe%d' % MOE if MOE else ''}"
                  f"{'_win%d' % WINDOW if WINDOW else ''}"
                  f"_train_tokens_per_sec_batch{B}_seq{L}_1chip",
        "value": round(tok_s, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,   # the reference predates transformers
        **_device_stamp(dev),
        **_mfu_fields(dev, flops_tok * tok_s),
        "step_ms": round(1e3 * dt / meas, 2),
        "compute_dtype": "bfloat16" if compute_dtype else "float32",
        "final_loss": round(final_loss, 4),
        "model_tflops_per_step": round(flops_tok * B * L / 1e12, 3),
        # the Mosaic kernel, as the lowered step's own text shows it
        "flash_kernel": "tpu_custom_call" in step.lowered(
            toks, labels).as_text(),
    }))


def _graph_mlp(sym, depth=4, width=256, classes=10, batch=32):
    """MLP + primitive-level softmax-CE loss (what a frontend without
    a fused loss op emits)."""
    x = sym.Variable("data")
    label = sym.Variable("label")
    h = x
    for i in range(depth):
        h = sym.Activation(
            sym.FullyConnected(h, num_hidden=width, name=f"fc{i}"),
            act_type="relu", name=f"act{i}")
    logits = sym.FullyConnected(h, num_hidden=classes, name="mlphead")
    m = sym.max(logits, axis=-1, keepdims=True)
    z = logits - m
    lse = sym.log(sym.sum(sym.exp(z), axis=-1, keepdims=True))
    logp = z - lse
    onehot = sym.one_hot(label, depth=classes)
    loss = 0.0 - sym.mean(sym.sum(logp * onehot, axis=-1))
    shapes = {"data": (batch, width), "label": (batch,)}
    return sym.Group([logits, loss]), shapes


def _graph_resnet_block(sym, channels=64, hw=16, batch=2):
    """BasicBlockV1 traced through the gluon symbol frontend."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import \
        BasicBlockV1
    with mx.name.Prefix("rb_"):
        blk = BasicBlockV1(channels, 1, in_channels=channels)
    blk.initialize(mx.init.Xavier())
    blk(nd.zeros((batch, channels, hw, hw)))   # settle deferred shapes
    with mx.name.Prefix("rb_"):
        out = blk._to_symbol(sym.Variable("data"))
    return out, {"data": (batch, channels, hw, hw)}


def _graph_transformer_step(sym, B=4, L=64, D=128, H=4, n_layers=2,
                            V=1000):
    """Decoder-LM training-step graph at the primitive level:
    layernorm/GELU/causal-mask arithmetic written out (no fused ops),
    the shape a symbolic frontend hands the compiler."""
    dh = D // H

    def layer_norm(t, tag):
        g, b = sym.Variable(f"{tag}_gamma"), sym.Variable(f"{tag}_beta")
        mu = sym.mean(t, axis=-1, keepdims=True)
        xc = t - mu
        var = sym.mean(xc * xc, axis=-1, keepdims=True)
        return (xc / sym.sqrt(var + 1e-5)) * g + b

    def split_heads(t):
        t = sym.Reshape(t, shape=(B, L, H, dh))
        t = sym.transpose(t, axes=(0, 2, 1, 3))
        return sym.Reshape(t, shape=(B * H, L, dh))

    def attention(y, tag):
        q = sym.FullyConnected(y, num_hidden=D, flatten=False,
                               no_bias=True, name=f"{tag}_q")
        k = sym.FullyConnected(y, num_hidden=D, flatten=False,
                               no_bias=True, name=f"{tag}_k")
        v = sym.FullyConnected(y, num_hidden=D, flatten=False,
                               no_bias=True, name=f"{tag}_v")
        scale = sym.full((1,), float(dh)) ** -0.5     # folds to const
        scores = sym.batch_dot(split_heads(q), split_heads(k),
                               transpose_b=True) * scale
        # causal mask rebuilt per layer (as a naive frontend does):
        # a pure-const subtree -> folded once, CSE'd across layers
        rows = sym.Reshape(sym.arange(0, L), shape=(L, 1))
        cols = sym.Reshape(sym.arange(0, L), shape=(1, L))
        neg = (sym.broadcast_greater_equal(rows, cols) - 1.0) * 1e9
        attn = sym.softmax(sym.broadcast_add(scores, neg), axis=-1)
        ctx = sym.Reshape(
            sym.transpose(sym.Reshape(sym.batch_dot(attn,
                                                    split_heads(v)),
                                      shape=(B, H, L, dh)),
                          axes=(0, 2, 1, 3)), shape=(B, L, D))
        return sym.FullyConnected(ctx, num_hidden=D, flatten=False,
                                  no_bias=True, name=f"{tag}_o")

    def gelu(t):
        return 0.5 * t * (1.0 + sym.erf(t / 1.4142135623730951))

    tokens = sym.Variable("tokens")
    labels = sym.Variable("labels")
    h = sym.Embedding(tokens, sym.Variable("embed_weight"),
                      input_dim=V, output_dim=D, name="embed")
    for i in range(n_layers):
        h = h + attention(layer_norm(h, f"l{i}_ln1"), f"l{i}")
        u = sym.FullyConnected(layer_norm(h, f"l{i}_ln2"),
                               num_hidden=4 * D, flatten=False,
                               name=f"l{i}_ff1")
        h = h + sym.FullyConnected(gelu(u), num_hidden=D,
                                   flatten=False, name=f"l{i}_ff2")
    logits = sym.FullyConnected(layer_norm(h, "lnf"), num_hidden=V,
                                flatten=False, name="lmhead")
    m = sym.max(logits, axis=-1, keepdims=True)
    z = logits - m
    lse = sym.log(sym.sum(sym.exp(z), axis=-1, keepdims=True))
    loss = 0.0 - sym.mean(
        sym.sum((z - lse) * sym.one_hot(labels, depth=V), axis=-1))
    shapes = {"tokens": (B, L), "labels": (B, L),
              "embed_weight": (V, D),
              "lmhead_weight": (V, D), "lmhead_bias": (V,),
              "lnf_gamma": (D,), "lnf_beta": (D,)}
    for i in range(n_layers):
        for ln in (f"l{i}_ln1", f"l{i}_ln2"):
            shapes[f"{ln}_gamma"] = (D,)
            shapes[f"{ln}_beta"] = (D,)
        for w in "qkvo":
            shapes[f"l{i}_{w}_weight"] = (D, D)
        shapes[f"l{i}_ff1_weight"] = (4 * D, D)
        shapes[f"l{i}_ff1_bias"] = (4 * D,)
        shapes[f"l{i}_ff2_weight"] = (D, 4 * D)
        shapes[f"l{i}_ff2_bias"] = (D,)
    return sym.Group([logits, loss]), shapes


def _analytic_vs_xla(s, shapes):
    """(CostReport, xla cost dict | None, rel FLOPs delta | None)
    for one bench graph's forward at fixed shapes — the analytic
    pass vs XLA's own ``compiled.cost_analysis()``."""
    import jax

    from incubator_mxnet_tpu import perf
    from incubator_mxnet_tpu.executor import build_graph_fn
    rep = perf.symbol_cost(s, shapes)
    arg_names = s.list_arguments()
    aux_names = s.list_auxiliary_states()
    known = {k: v for k, v in shapes.items()
             if k in set(arg_names) | set(aux_names)}
    arg_shapes, _, aux_shapes = s.infer_shape_partial(**known)
    run = build_graph_fn(s)
    args = {n: jax.ShapeDtypeStruct(tuple(sh), np.float32)
            for n, sh in zip(arg_names, arg_shapes)}
    auxs = {n: jax.ShapeDtypeStruct(tuple(sh), np.float32)
            for n, sh in zip(aux_names, aux_shapes)}
    rng = jax.ShapeDtypeStruct((2,), np.uint32)

    def fwd(av, xv, r, _run=run):
        return _run(av, xv, r, False)

    xc = perf.jit_cost(fwd, args, auxs, rng)
    delta = (abs(rep.flops - xc["flops"]) / xc["flops"]
             if xc and xc.get("flops") else None)
    return rep, xc, delta


def _bench_perf_report(dev, platform):
    """Perf observatory artifact (ISSUE 18, BENCH_r18.json):
    analytic-vs-XLA deltas on the three bench graphs, per-family
    cost/roofline tables for a transformer train step and serving
    decode, measured MFU through the live gauges, and the bench_gate
    trajectory summary.  CPU-runnable end to end.
    Run with MXTPU_BENCH_MODEL=perf_report."""
    import jax

    import incubator_mxnet_tpu as mx
    import incubator_mxnet_tpu.symbol as symmod
    from incubator_mxnet_tpu import parallel, perf, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM

    def stage(msg):
        _stage(msg, tag="perf_report")

    caps = perf.caps_for(dev)
    dtype = "bfloat16" if platform != "cpu" else "float32"

    # ---- analytic vs XLA on the three bench graphs ----------------
    stage("costing the three bench graphs (analytic + XLA)")
    graphs = {}
    for name, builder in [("mlp", _graph_mlp),
                          ("resnet_block", _graph_resnet_block),
                          ("transformer_step",
                           _graph_transformer_step)]:
        s, shapes = builder(symmod)
        rep, xc, delta = _analytic_vs_xla(s, shapes)
        graphs[name] = {
            "analytic_gflops": round(rep.flops / 1e9, 4),
            "xla_gflops": round(xc["flops"] / 1e9, 4) if xc else None,
            "rel_delta": round(delta, 4) if delta is not None
            else None,
            "coverage": rep.coverage,
        }

    # ---- transformer train step: live gauges + per-family table ---
    stage("train step: arming gauges, measuring")
    V, D, LAYERS, HEADS, B, L = 512, 128, 2, 4, 4, 64
    mx.random.seed(0)
    net = TransformerLM(V, d_model=D, n_layers=LAYERS, n_heads=HEADS,
                        max_len=L)
    net.initialize(mx.initializer.Xavier())
    ex = mx.nd.array(np.zeros((2, L), "int32"))
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd", optimizer_params=dict(learning_rate=.1),
        example_args=[ex], mesh=parallel.make_mesh(devices=[dev]))
    rs = np.random.RandomState(0)
    toks = jax.device_put(
        np.asarray(rs.randint(0, V, (B, L)), np.int32), dev)
    labels = jax.device_put(
        np.asarray(rs.randint(0, V, (B, L)), np.int32), dev)
    xla_step = step.cost_analysis(toks, labels)  # arms the MFU clock
    flops_tok = net.train_flops_per_token(L)
    step.arm_perf(flops_per_step=flops_tok * B * L,
                  bytes_per_step=(xla_step or {}).get("bytes", 0.0),
                  tokens_per_step=B * L)
    for _ in range(2):
        loss = step(toks, labels)
    float(loss)
    n_steps = 20            # 2x the default MXTPU_PERF_INTERVAL
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step(toks, labels)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), final_loss
    snap = telemetry.snapshot()
    g = snap.get("gauges", snap) or {}
    train_flops_step = flops_tok * B * L
    train_bytes_step = (xla_step or {}).get("bytes", 0.0)
    train = {
        "model": {"vocab": V, "d_model": D, "n_layers": LAYERS,
                  "n_heads": HEADS, "batch": B, "seq": L},
        "step_ms": round(1e3 * dt / n_steps, 2),
        "tokens_per_s": round(B * L * n_steps / dt, 1),
        "mfu": g.get("train_mfu"),
        "mbu": g.get("train_mbu"),
        "gauge_tokens_per_s": g.get("train_tokens_per_sec"),
        "analytic_step_gflops": round(train_flops_step / 1e9, 4),
        "xla_step_cost": xla_step,
        "roofline": perf.roofline(train_flops_step, train_bytes_step,
                                  caps, dtype),
    }
    srep, _, sdelta = _analytic_vs_xla(
        *_graph_transformer_step(symmod))
    train["per_family"] = srep.scaled(3.0).table(caps, dtype)
    train["graph_rel_delta"] = round(sdelta, 4) \
        if sdelta is not None else None

    # ---- serving decode: live engine + analytic decode report -----
    stage("serving decode: streaming through the engine")
    from incubator_mxnet_tpu.serving.engine import ServingEngine
    srv = TransformerLM(256, d_model=D, n_layers=LAYERS,
                        n_heads=HEADS, max_len=96)
    srv.initialize(mx.initializer.Xavier())
    srv(mx.nd.array(np.zeros((1, 4), "int32")))
    eng = ServingEngine(srv, max_batch=4, block_size=8,
                        num_blocks=64)
    rs = np.random.RandomState(1)
    for _ in range(8):
        eng.submit([int(t) for t in rs.randint(1, 256, 12)],
                   max_new_tokens=16)
    t0 = time.perf_counter()
    events = list(eng.stream())
    s_dt = time.perf_counter() - t0
    snap = telemetry.snapshot()
    g = snap.get("gauges", snap) or {}
    serving = {
        "requests": 8, "tokens": len(events),
        "tokens_per_s": round(len(events) / s_dt, 1),
        "mfu": g.get("serving_mfu"),
        "flops_per_token": g.get("serving_flops_per_token"),
        "report": eng.perf_report(),
    }

    # ---- bench_gate trajectory over the committed history ---------
    stage("normalizing the BENCH history")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import bench_gate
    history = bench_gate.load_history()
    gate = {
        "band": float(os.environ.get("MXTPU_PERF_GATE_BAND", 0.10)),
        "records": len(history),
        "metrics": bench_gate.trajectory_summary(history),
    }

    doc = {
        "metric": "perf_report",
        **_device_stamp(dev),
        "compute_dtype": dtype,
        "nominal_peaks": bool(caps.nominal),
        "graphs": graphs,
        "train": train,
        "serving": serving,
        "bench_gate": gate,
    }
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_r18.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
    print(json.dumps({
        "metric": "perf_report",
        "platform": platform,
        "graph_deltas": {k: v["rel_delta"]
                         for k, v in graphs.items()},
        "train_mfu": train["mfu"],
        "train_bound": train["roofline"]["bound"],
        "serving_tokens_per_s": serving["tokens_per_s"],
        "serving_mfu": serving["mfu"],
        "gate_metrics": len(gate["metrics"]),
        "wrote": out,
    }))


def _bench_memory(dev, platform):
    """Memory-pressure survival artifact (BENCH_r19.json,
    docs/memory.md): planner-vs-XLA peak-HBM deltas on the three
    bench train graphs, a deterministic degrade-ladder walk under a
    shrunk MXTPU_HBM_BYTES, timed recovery from an injected mem:oom
    (loss bitwise-identical across the remat rung), and the
    auto-sized serving KV pool against the static configuration.
    CPU-runnable end to end.  Run with MXTPU_BENCH_MODEL=memory."""
    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    import incubator_mxnet_tpu.symbol as symmod
    from incubator_mxnet_tpu import parallel, resilience, telemetry
    from incubator_mxnet_tpu.executor import build_graph_fn
    from incubator_mxnet_tpu.perf import memory_planner as mp

    def stage(msg):
        _stage(msg, tag="memory")

    graph_inputs = {"mlp": {"data", "label"},
                    "resnet_block": {"data"},
                    "transformer_step": {"tokens", "labels"}}

    def train_compiled(s, shapes, inputs, grad_accum=1):
        """Donated SGD train step lowered straight from the Symbol —
        abstract specs only, nothing executes."""
        arg_names = s.list_arguments()
        aux_names = s.list_auxiliary_states()
        known = {k: v for k, v in shapes.items()
                 if k in set(arg_names) | set(aux_names)}
        arg_shapes, _, aux_shapes = s.infer_shape_partial(**known)
        run = build_graph_fn(s)
        all_args = {n: tuple(sh)
                    for n, sh in zip(arg_names, arg_shapes)}
        auxs = {n: jax.ShapeDtypeStruct(tuple(sh), np.float32)
                for n, sh in zip(aux_names, aux_shapes)}
        params = {n: jax.ShapeDtypeStruct(sh, np.float32)
                  for n, sh in all_args.items() if n not in inputs}
        datas = {n: jax.ShapeDtypeStruct(
            sh, np.int32 if ("label" in n or "tokens" in n)
            else np.float32)
            for n, sh in all_args.items() if n in inputs}
        rng = jax.ShapeDtypeStruct((2,), np.uint32)

        def lossf(p, d, av, r):
            fwd = run({**p, **{k: v.astype(np.float32)
                               for k, v in d.items()}}, av, r, True)
            outs = fwd[0] if isinstance(fwd, tuple) else fwd
            loss = outs[-1] if isinstance(outs, (list, tuple)) \
                else outs
            return jnp.mean(loss)

        def step(p, d, av, r):
            if grad_accum <= 1:
                loss, g = jax.value_and_grad(lossf)(p, d, av, r)
            else:
                def micro(carry, dslice):
                    gsum, lsum = carry
                    mloss, mg = jax.value_and_grad(lossf)(
                        p, dslice, av, r)
                    gsum = jax.tree_util.tree_map(
                        lambda a, b: a + b, gsum, mg)
                    return (gsum, lsum + mloss), None

                dm = {k: d[k].reshape(
                    (grad_accum, d[k].shape[0] // grad_accum)
                    + d[k].shape[1:]) for k in sorted(datas)}
                zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
                (g, loss), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros((), jnp.float32)), dm)
            newp = jax.tree_util.tree_map(
                lambda a, b: a - 0.1 * b, p, g)
            return loss, newp

        return (jax.jit(step, donate_argnums=(0,))
                .lower(params, datas, auxs, rng).compile())

    # ---- planner vs XLA on the three bench train graphs -----------
    stage("planner vs memory_analysis on the bench graphs")
    graphs, deltas = {}, []
    # executables loaded back from the persistent compile cache lose
    # their alias table (alias_size_in_bytes=0), which double-counts
    # every donated output — force fresh compiles for the cross-check
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for name, builder in [("mlp", _graph_mlp),
                              ("resnet_block", _graph_resnet_block),
                              ("transformer_step",
                               _graph_transformer_step)]:
            s, shapes = builder(symmod)
            inputs = graph_inputs[name]
            entry = {}
            for accum in (1, 2):
                if name == "transformer_step" and accum > 1:
                    continue     # hardcoded batch in head reshapes
                c = train_compiled(s, shapes, inputs,
                                   grad_accum=accum)
                xla = mp.xla_live_bytes(c.memory_analysis())
                plan = mp.plan_memory(s, shapes, input_names=inputs,
                                      grad_accum=accum)
                rel = ((plan.total() - xla) / xla) if xla else None
                if rel is not None:
                    deltas.append(abs(rel))
                entry[f"accum{accum}"] = {
                    "planned_mb": round(plan.total() / (1 << 20), 2),
                    "xla_mb": round(xla / (1 << 20), 2)
                    if xla else None,
                    "rel_delta": round(rel, 4) if rel is not None
                    else None,
                }
            live = mp.symbol_liveness(s, shapes, input_names=inputs)
            b = mp.plan_memory(liveness=live)
            r = mp.plan_memory(liveness=live, remat=True)
            entry["remat_activation_shrink"] = round(
                1.0 - r.activations / b.activations, 4) \
                if b.activations else None
            graphs[name] = entry
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    max_abs_delta = max(deltas) if deltas else None

    # ---- degrade ladder under a shrunk HBM override ---------------
    stage("walking the degrade ladder under a shrunk capacity")
    s, shapes = _graph_mlp(symmod)
    live = mp.symbol_liveness(s, shapes,
                              input_names=graph_inputs["mlp"])

    def make(remat, accum):
        return mp.plan_memory(liveness=live, remat=remat,
                              grad_accum=accum)

    base_b, remat_b = make(False, 1).total(), make(True, 1).total()
    mem_keys = ("MXTPU_HBM_BYTES", "MXTPU_MEM_GATE_MARGIN",
                "MXTPU_MEM_POLICY", "MXTPU_FAULT_SPEC")
    saved = {k: os.environ.get(k) for k in mem_keys}
    try:
        os.environ["MXTPU_MEM_GATE_MARGIN"] = "0"
        os.environ["MXTPU_HBM_BYTES"] = \
            str(int((base_b + remat_b) / 2))
        res = mp.preflight(make, site="bench_memory",
                           can_remat=True, batch_size=32)
        ladder = {
            "base_mb": round(base_b / (1 << 20), 2),
            "capacity_mb": round((base_b + remat_b) / 2 / (1 << 20),
                                 2),
            "rungs": list(res.rungs),
            "settled_mb": round(res.plan.total() / (1 << 20), 2),
        }
        os.environ["MXTPU_HBM_BYTES"] = "4096"
        try:
            mp.preflight(make, site="bench_memory", can_remat=True,
                         batch_size=32)
            ladder["dry_ladder_typed"] = False
        except resilience.MemoryPlanError as err:
            ladder["dry_ladder_typed"] = True
            ladder["dry_rungs"] = list(err.rungs)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None \
                else os.environ.__setitem__(k, v)

    # ---- injected mem:oom: one rung + retry, timed ----------------
    stage("injected mem:oom: timing the rung + retry")

    def tiny_step():
        mx.random.seed(0)
        net = mx.gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(mx.gluon.nn.Dense(64, activation="relu",
                                      in_units=32))
            net.add(mx.gluon.nn.Dense(8, in_units=64))
        net.initialize(mx.initializer.Xavier())
        return parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            mesh=parallel.make_mesh())

    rs = np.random.RandomState(0)
    x = np.asarray(rs.rand(16, 32), np.float32)
    y = np.asarray(rs.randint(0, 8, (16,)), np.int32)
    ref_step = tiny_step()
    ref = [float(np.asarray(ref_step(x, y, rng=jax.random.PRNGKey(i))))
           for i in range(3)]
    try:
        os.environ["MXTPU_FAULT_SPEC"] = "mem:oom:2:error"
        resilience.reset_faults()
        retries0 = telemetry.get_registry().counter(
            "oom_retries_total").value
        step = tiny_step()
        got = [float(np.asarray(
            step(x, y, rng=jax.random.PRNGKey(0))))]
        t0 = time.perf_counter()        # this call eats the OOM
        got.append(float(np.asarray(
            step(x, y, rng=jax.random.PRNGKey(1)))))
        recovery_s = time.perf_counter() - t0
        got.append(float(np.asarray(
            step(x, y, rng=jax.random.PRNGKey(2)))))
        oom_doc = {
            "rung": "remat" if step.remat else
            f"grad_accum={step.grad_accum}",
            "recovery_ms": round(1e3 * recovery_s, 1),
            "losses_bitwise_identical": got == ref,
            "oom_retries_total": telemetry.get_registry().counter(
                "oom_retries_total").value - retries0,
        }
    finally:
        os.environ.pop("MXTPU_FAULT_SPEC", None)
        resilience.reset_faults()

    # ---- serving KV pool: auto-sized vs static --------------------
    stage("auto-sizing the serving KV pool")
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving.engine import ServingEngine

    def tiny_lm():
        mx.random.seed(0)
        net = TransformerLM(256, d_model=64, n_layers=2, n_heads=4,
                            max_len=96)
        net.initialize(mx.initializer.Xavier())
        net(mx.nd.array(np.zeros((1, 4), "int32")))
        return net

    try:
        os.environ["MXTPU_HBM_BYTES"] = str(16 << 20)
        auto = ServingEngine(tiny_lm(), max_batch=4, block_size=8,
                             num_blocks="auto")
        static = ServingEngine(tiny_lm(), max_batch=4, block_size=8,
                               num_blocks=64)
        serving = {
            "hbm_override_mb": 16,
            "auto_num_blocks": auto.num_blocks,
            "static_num_blocks": static.num_blocks,
            "floor": auto.max_batch + 1,
            "cap": auto.max_batch * auto.max_blocks + 1,
            "auto_kv_pool_mb": round(
                2.0 * 2 * auto.block_size * 4 * 16
                * auto.num_blocks * 4 / (1 << 20), 2),
        }
    finally:
        os.environ.pop("MXTPU_HBM_BYTES", None)

    doc = {
        "metric": "memory_pressure",
        **_device_stamp(dev),
        "graphs": graphs,
        "max_abs_rel_delta": round(max_abs_delta, 4)
        if max_abs_delta is not None else None,
        "ladder": ladder,
        "oom_recovery": oom_doc,
        "serving_auto": serving,
    }
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_r19.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
    print(json.dumps({
        "metric": "memory_pressure",
        "platform": platform,
        "max_abs_rel_delta": doc["max_abs_rel_delta"],
        "ladder_rungs": ladder["rungs"],
        "oom_recovery_ms": oom_doc["recovery_ms"],
        "losses_bitwise_identical":
            oom_doc["losses_bitwise_identical"],
        "auto_num_blocks": serving["auto_num_blocks"],
        "wrote": out,
    }))


def _bench_graph(dev, platform):
    """Graph-optimization pipeline bench (ISSUE 6 acceptance): pre/
    post-pass node counts per level, golden equivalence of the bound
    executors, CachedOp trace counts, and hybridized-replay vs
    non-hybridized eager wall clock.  CPU-measurable by design (the
    ROADMAP standing item); writes the BENCH_r06.json artifact."""
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, sym
    from incubator_mxnet_tpu.gluon import nn

    del jax, dev
    rs = np.random.RandomState(0)
    artifact = {"metric": "graph_opt_pipeline", "platform": platform,
                "graphs": {}, "cachedop": {}}

    builders = {
        "mlp": _graph_mlp,
        "resnet_block": _graph_resnet_block,
        "transformer_lm_step": _graph_transformer_step,
    }
    for gname, build in builders.items():
        _stage(f"building {gname}", tag="graph")
        s, shapes = build(sym)
        entry = {"levels": {}}
        for level in (1, 2):
            t0 = time.perf_counter()
            _opt, report = s.optimize(level=level)
            entry["levels"][str(level)] = {
                "nodes_before": report["nodes_before"],
                "nodes_after": report["nodes_after"],
                "reduction_pct": round(
                    100.0 * (1 - report["nodes_after"]
                             / report["nodes_before"]), 1),
                "optimize_ms": round(
                    1e3 * (time.perf_counter() - t0), 1),
                "passes": report["passes"],
            }
        # golden equivalence of the bound executors at 0 vs 2
        outs = {}
        for level in (0, 2):
            os.environ["MXTPU_GRAPH_OPT"] = str(level)
            try:
                exe = s.simple_bind(mx.cpu(), grad_req="null",
                                    **shapes)
                vals, rl = {}, np.random.RandomState(42)
                for name in sorted(exe.arg_dict):
                    shape = exe.arg_dict[name].shape
                    if name in ("label", "labels", "tokens"):
                        vals[name] = nd.array(rl.randint(
                            0, 10, shape).astype("float32"))
                    else:
                        vals[name] = nd.array(
                            (rl.rand(*shape) * 0.1 - 0.05)
                            .astype("float32"))
                exe.copy_params_from(vals)
                outs[level] = [o.asnumpy() for o in exe.forward()]
            finally:
                del os.environ["MXTPU_GRAPH_OPT"]
        entry["bitwise_equal_opt0_vs_opt2"] = all(
            np.array_equal(a, b)
            for a, b in zip(outs[0], outs[2]))
        artifact["graphs"][gname] = entry
        _stage(f"{gname}: L1 {entry['levels']['1']['reduction_pct']}% "
               f"L2 {entry['levels']['2']['reduction_pct']}% "
               f"bitwise={entry['bitwise_equal_opt0_vs_opt2']}",
               tag="graph")

    # ---- CachedOp: hybridized replay vs non-hybridized eager --------
    _stage("cachedop replay bench", tag="graph")
    depth, width, batch = 24, 64, 32
    with mx.name.Prefix("gbench_"):
        net = nn.HybridSequential()
        for _ in range(depth):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(10))
    net.initialize(mx.init.Xavier())
    x = nd.array(rs.rand(batch, width).astype("float32"))

    def timed(n_iter):
        t0 = time.perf_counter()
        for _ in range(n_iter):
            net(x).asnumpy()
        return 1e3 * (time.perf_counter() - t0) / n_iter

    timed(3)                                   # eager warmup
    eager_ms = timed(30)
    net.hybridize()
    net(x).asnumpy()                           # trace + compile
    replay_ms = timed(200)
    co = net._cached_op
    stats_same_shape = dict(co.stats())
    net(nd.array(rs.rand(batch // 2, width)
                 .astype("float32"))).asnumpy()  # second signature
    artifact["cachedop"] = {
        "eager_ms_per_call": round(eager_ms, 3),
        "replay_ms_per_call": round(replay_ms, 3),
        "replay_speedup": round(eager_ms / replay_ms, 1),
        "stats_after_201_same_shape_calls": stats_same_shape,
        "stats_after_second_shape": co.stats(),
        "mode": co.stats()["modes"],
    }
    artifact["trace_once_proven"] = (
        stats_same_shape["traces"] == 1
        and co.stats()["traces"] == 2)

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r06.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "graph_opt_pipeline",
        "value": artifact["cachedop"]["replay_speedup"],
        "unit": "x_eager_replay_speedup",
        "platform": platform,
        "best_node_reduction_pct": max(
            e["levels"]["2"]["reduction_pct"]
            for e in artifact["graphs"].values()),
        "bitwise_equal": all(
            e["bitwise_equal_opt0_vs_opt2"]
            for e in artifact["graphs"].values()),
        "trace_once_proven": artifact["trace_once_proven"],
        "artifact": "BENCH_r06.json",
    }))


def _bench_serving(dev, platform):
    """Serving-tier bench (ISSUE 7 acceptance): a mixed-length
    Poisson request stream decoded (a) statically — one unpadded
    ``generate()`` call per request, sequential — and (b) through
    the continuous-batching paged-KV ``ServingEngine``.  Reports
    throughput, p50/p99 TTFT, block-pool utilization, prefix-cache
    hit rate, and int8-vs-fp32 logit deltas.  CPU-measurable by
    design; writes the BENCH_r07.json artifact."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving import (ServingEngine,
                                             quantize_weights,
                                             weights_nbytes)

    del dev
    mx.random.seed(0)
    rs = np.random.RandomState(7)
    vocab, d, layers, heads, max_len = 512, 256, 4, 8, 128
    n_req = int(os.environ.get("MXTPU_BENCH_SERVE_REQS", "16"))
    max_new = int(os.environ.get("MXTPU_BENCH_SERVE_NEW", "32"))
    _stage(f"building LM d={d} L={layers} ({n_req} requests x "
           f"{max_new} new tokens)", tag="serve")
    net = TransformerLM(vocab, d_model=d, n_layers=layers,
                        n_heads=heads, max_len=max_len)
    net.initialize(mx.init.Xavier())

    # mixed-length stream; half the requests share a system prompt
    # (the prefix-cache workload); Poisson arrivals
    system = list(rs.randint(0, vocab, 24))
    prompts = []
    for i in range(n_req):
        own = list(rs.randint(0, vocab, int(rs.randint(8, 40))))
        p = (system + own) if i % 2 == 0 else own
        prompts.append(p[:max_len - max_new - 1])
    arrivals = np.cumsum(rs.exponential(0.01, n_req))
    ntok = n_req * max_new

    # ---- static per-request decode ------------------------------
    def static_pass(measure):
        outs, ttfts = [], []
        t_start = time.perf_counter()
        for arr, p in zip(arrivals, prompts):
            now = time.perf_counter() - t_start
            if measure and now < arr:
                time.sleep(arr - now)
            out = net.generate(
                mx.nd.array(np.asarray([p], np.int32)),
                max_new).asnumpy()[0]
            outs.append([int(t) for t in out])
            # generate() is monolithic: the first token exists only
            # when the whole call returns — head-of-line blocking
            # is static batching's TTFT story
            ttfts.append(time.perf_counter() - t_start - arr)
        return time.perf_counter() - t_start, outs, ttfts

    _stage("static: warm per-signature compiles", tag="serve")
    static_pass(measure=False)
    _stage("static: measured pass", tag="serve")
    static_s, static_outs, static_ttft = static_pass(measure=True)
    _stage(f"static {ntok / static_s:.1f} tok/s", tag="serve")

    # ---- continuous batching ------------------------------------
    eng = ServingEngine(net, max_batch=8, block_size=16,
                        num_blocks=192)

    def serve_pass(engine, measure):
        reqs, util_max = [], 0.0
        t_start = time.perf_counter()
        pending = list(zip(arrivals, prompts))
        while pending or engine.has_work():
            now = time.perf_counter() - t_start
            while pending and (not measure or pending[0][0] <= now):
                _arr, p = pending.pop(0)
                reqs.append(engine.submit(p, max_new))
            if engine.has_work():
                engine.step()
                util_max = max(util_max,
                               engine.pool.utilization())
            elif pending and measure:
                time.sleep(max(0.0, pending[0][0] - now))
        wall = time.perf_counter() - t_start
        ttfts = [r.first_token_ts - r.submit_ts for r in reqs]
        outs = [[int(t) for t in r.tokens] for r in reqs]
        return wall, outs, ttfts, util_max

    # two warm passes: the first compiles the cache-cold prefill
    # buckets + the decode step, the second the (smaller) buckets a
    # warm prefix cache produces; the measured pass then starts from
    # a CLEARED cache so its hit rate reports genuine cross-request
    # sharing within the stream, not self-hits on warm-up residue
    _stage("continuous: warm (2 passes)", tag="serve")
    serve_pass(eng, measure=False)
    serve_pass(eng, measure=False)
    eng.cache.clear()
    reg = telemetry.get_registry()
    hits0 = reg.counter("serving_prefix_cache_hits_total").value
    miss0 = reg.counter("serving_prefix_cache_misses_total").value
    pre0 = reg.counter("serving_preemptions_total").value
    _stage("continuous: measured pass", tag="serve")
    cont_s, cont_outs, cont_ttft, util_max = serve_pass(
        eng, measure=True)
    hits = reg.counter("serving_prefix_cache_hits_total").value \
        - hits0
    misses = reg.counter("serving_prefix_cache_misses_total").value \
        - miss0
    _stage(f"continuous {ntok / cont_s:.1f} tok/s", tag="serve")

    greedy_equal = cont_outs == static_outs
    pool_clean = eng.pool.num_allocated == len(eng.cache)

    # ---- int8 quantization --------------------------------------
    _stage("int8: density + logit delta", tag="serve")
    wts = net._decode_weights()
    qwts = quantize_weights(wts)
    logits = {}
    for mode in ("off", "int8"):
        e = ServingEngine(net, max_batch=1, block_size=16,
                          num_blocks=64, quantize=mode,
                          keep_logits=True)
        r = e.submit(prompts[0], 1)
        e.run()
        logits[mode] = np.asarray(r.logits)
    dlogit = float(np.abs(logits["int8"] - logits["off"]).max())
    lscale = float(np.abs(logits["off"]).max())

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q))

    artifact = {
        "metric": "serving_continuous_batching",
        "platform": platform,
        "model": {"vocab": vocab, "d_model": d, "n_layers": layers,
                  "n_heads": heads, "max_len": max_len},
        "stream": {"requests": n_req, "max_new_tokens": max_new,
                   "prompt_lens": [len(p) for p in prompts],
                   "poisson_mean_interarrival_s": 0.01},
        "static": {"wall_s": round(static_s, 3),
                   "tokens_per_s": round(ntok / static_s, 1),
                   "ttft_p50_s": round(pct(static_ttft, 50), 4),
                   "ttft_p99_s": round(pct(static_ttft, 99), 4)},
        "continuous": {
            "wall_s": round(cont_s, 3),
            "tokens_per_s": round(ntok / cont_s, 1),
            "ttft_p50_s": round(pct(cont_ttft, 50), 4),
            "ttft_p99_s": round(pct(cont_ttft, 99), 4),
            "block_pool_utilization_max": round(util_max, 3),
            "prefix_cache_hit_rate": round(
                hits / max(1, hits + misses), 3),
            "preemptions": reg.counter(
                "serving_preemptions_total").value - pre0,
            "trace_counts": dict(eng.trace_counts)},
        "speedup_continuous_vs_static": round(static_s / cont_s, 2),
        "greedy_outputs_equal_sequential_generate": greedy_equal,
        "no_leaked_blocks": pool_clean,
        "int8": {"fp32_bytes": weights_nbytes(wts),
                 "int8_bytes": weights_nbytes(qwts),
                 "density_ratio": round(
                     weights_nbytes(wts) / weights_nbytes(qwts), 2),
                 "max_abs_logit_delta": round(dlogit, 5),
                 "logit_scale": round(lscale, 4)},
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r07.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "serving_continuous_batching",
        "value": artifact["speedup_continuous_vs_static"],
        "unit": "x_static_throughput",
        "platform": platform,
        "continuous_tok_s": artifact["continuous"]["tokens_per_s"],
        "static_tok_s": artifact["static"]["tokens_per_s"],
        "ttft_p99_speedup": round(
            pct(static_ttft, 99) / max(1e-9, pct(cont_ttft, 99)), 1),
        "prefix_cache_hit_rate":
            artifact["continuous"]["prefix_cache_hit_rate"],
        "greedy_equal": greedy_equal,
        "artifact": "BENCH_r07.json",
    }))


def _bench_serving_slo(dev, platform):
    """Serving survival-layer bench (ISSUE 11 acceptance): the same
    Poisson request stream replayed at 0.25x measured capacity
    ("uncontended" — the TTFT an SLO would be written against) and
    at 4x capacity against (a) an UNBOUNDED wait queue and (b) the
    admission controller (``MXTPU_SERVE_QUEUE_LIMIT``).  The claim
    under test: shedding keeps *admitted*-request p99 TTFT within 2x
    the uncontended value while the unbounded baseline degrades with
    queue depth (its p99 TTFT is dominated by queue wait that grows
    with every arrival the engine cannot absorb).  CPU-measurable;
    writes the BENCH_r11.json artifact."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving import (ServeRejectedError,
                                             ServingEngine)

    del dev
    mx.random.seed(0)
    rs = np.random.RandomState(11)
    vocab, d, layers, heads, max_len = 256, 128, 2, 4, 96
    max_batch, max_new = 4, 8
    n_req = int(os.environ.get("MXTPU_BENCH_SERVE_REQS", "96"))
    queue_limit = int(os.environ.get("MXTPU_BENCH_SLO_QUEUE", "4"))
    _stage(f"building LM d={d} L={layers} ({n_req} requests x "
           f"{max_new} new tokens, queue_limit={queue_limit})",
           tag="slo")
    net = TransformerLM(vocab, d_model=d, n_layers=layers,
                        n_heads=heads, max_len=max_len)
    net.initialize(mx.init.Xavier())
    prompts = [list(rs.randint(0, vocab, int(rs.randint(8, 32))))
               for _ in range(n_req)]

    def engine(limit):
        """One FULLY-WARMED engine per pass: jit caches are
        per-engine, so a fresh engine's first requests would pay
        prefill-bucket + decode-step compiles — seconds that would
        dominate p99 TTFT and drown the queueing signal this bench
        exists to measure.  Warm with admission control off (the
        bound would shed most of the warming stream), then set the
        pass's limit."""
        eng = ServingEngine(net, max_batch=max_batch,
                            block_size=16, num_blocks=160,
                            prefix_cache=False, queue_limit=0)
        stream_pass(eng, [0.0] * n_req, measure=False)
        eng.queue_limit = limit
        return eng

    def stream_pass(eng, arrivals, measure=True):
        """Replay the stream; returns (admitted reqs, rejects)."""
        reqs, rejected = [], 0
        pending = list(zip(arrivals, prompts))
        t0 = time.perf_counter()
        while pending or eng.has_work():
            now = time.perf_counter() - t0
            while pending and (not measure or pending[0][0] <= now):
                _arr, p = pending.pop(0)
                try:
                    reqs.append(eng.submit(p, max_new))
                except ServeRejectedError:
                    rejected += 1
            if eng.has_work():
                eng.step()
            elif pending and measure:
                time.sleep(max(0.0, pending[0][0] - now))
        return reqs, rejected

    def p99_ttft(reqs):
        ttfts = [r.first_token_ts - r.submit_ts for r in reqs
                 if r.first_token_ts is not None]
        return float(np.percentile(np.asarray(ttfts), 99)), ttfts

    # warm compiles (prefill buckets + the decode step), then
    # measure capacity: saturated decode throughput -> request rate
    _stage("warm + capacity probe", tag="slo")
    eng = engine(0)     # engine() already ran one full warm stream
    t0 = time.perf_counter()
    reqs, _ = stream_pass(eng, [0.0] * n_req, measure=False)
    sat_wall = time.perf_counter() - t0
    cap_req_s = n_req / sat_wall
    _stage(f"capacity ~{cap_req_s:.1f} req/s "
           f"({n_req * max_new / sat_wall:.0f} tok/s)", tag="slo")

    def arrivals(rate):
        """Poisson arrival times from a FIXED fresh seed: every
        pass at a given rate replays the same arrival sequence (and
        across rates the inter-arrival pattern is identical, just
        scaled) — the published comparison is a controlled replay,
        not two different random streams."""
        ia = np.random.RandomState(1211).exponential(
            1.0 / rate, n_req)
        return np.cumsum(ia)

    # ---- uncontended: 25% of capacity, no shedding ---------------
    # (light enough that queueing is incidental — the TTFT an SLO
    # would be written against)
    _stage("uncontended pass (0.25x capacity)", tag="slo")
    uncont_reqs, _ = stream_pass(engine(0),
                                 arrivals(0.25 * cap_req_s))
    uncont_p99, uncont_ttfts = p99_ttft(uncont_reqs)

    # ---- 4x overload, unbounded queue ----------------------------
    _stage("overload pass: 4x capacity, UNBOUNDED queue", tag="slo")
    base_reqs, _ = stream_pass(engine(0), arrivals(4.0 * cap_req_s))
    base_p99, base_ttfts = p99_ttft(base_reqs)

    # ---- 4x overload, bounded queue (shedding) -------------------
    _stage(f"overload pass: 4x capacity, queue_limit="
           f"{queue_limit}", tag="slo")
    shed_eng = engine(queue_limit)
    # terminal counts accumulate per engine — subtract the warm
    # stream's finishes so the artifact reports the measured pass
    warm_counts = dict(shed_eng.stats()["terminal_counts"])
    shed_reqs, shed_rejected = stream_pass(shed_eng,
                                           arrivals(4.0 * cap_req_s))
    shed_p99, shed_ttfts = p99_ttft(shed_reqs)
    leak_free = shed_eng.pool.num_allocated == 0

    held = shed_p99 <= 2.0 * uncont_p99
    artifact = {
        "metric": "serving_overload_shedding",
        "platform": platform,
        "model": {"vocab": vocab, "d_model": d, "n_layers": layers,
                  "n_heads": heads, "max_len": max_len},
        "stream": {"requests": n_req, "max_new_tokens": max_new,
                   "max_batch": max_batch,
                   "capacity_req_per_s": round(cap_req_s, 2),
                   "overload_factor": 4.0,
                   "queue_limit": queue_limit},
        "uncontended": {
            "ttft_p50_s": round(float(np.percentile(
                uncont_ttfts, 50)), 4),
            "ttft_p99_s": round(uncont_p99, 4)},
        "overload_unbounded": {
            "ttft_p50_s": round(float(np.percentile(
                base_ttfts, 50)), 4),
            "ttft_p99_s": round(base_p99, 4),
            "p99_vs_uncontended_x": round(base_p99 / uncont_p99, 1),
            "admitted": len(base_reqs), "rejected": 0},
        "overload_shed": {
            "ttft_p50_s": round(float(np.percentile(
                shed_ttfts, 50)), 4),
            "ttft_p99_s": round(shed_p99, 4),
            "p99_vs_uncontended_x": round(shed_p99 / uncont_p99, 2),
            "admitted": len(shed_reqs),
            "rejected": shed_rejected,
            "rejected_fraction": round(shed_rejected / n_req, 3),
            "terminal_counts": {
                k: v - warm_counts.get(k, 0)
                for k, v in
                shed_eng.stats()["terminal_counts"].items()
                if v - warm_counts.get(k, 0)}},
        "admitted_p99_within_2x_uncontended": held,
        "no_leaked_blocks": leak_free,
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r11.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "serving_overload_shedding",
        "value": artifact["overload_shed"]["p99_vs_uncontended_x"],
        "unit": "x_uncontended_p99_ttft_when_shedding",
        "platform": platform,
        "unbounded_p99_x": artifact["overload_unbounded"][
            "p99_vs_uncontended_x"],
        "rejected_fraction": artifact["overload_shed"][
            "rejected_fraction"],
        "held_2x": held,
        "no_leaked_blocks": leak_free,
        "artifact": "BENCH_r11.json",
    }))


def _bench_serving_fleet(dev, platform):
    """Serving-fleet failover bench (ISSUE 16 acceptance): a fixed-
    seed Poisson request stream over a 3-replica CPU fleet with one
    replica hard-killed mid-stream (``router:replica:N:kill`` —
    ``os._exit``, no teardown).  Reports failover latency (link-down
    to first re-dispatched token, the ``router_failover_seconds``
    histogram), verifies zero lost and zero duplicated terminals
    fleet-wide, and checks every surviving output bitwise-equal to an
    unkilled single-engine run of the same stream.  CPU-measurable;
    writes the BENCH_r16.json artifact."""
    import subprocess
    import tempfile

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry, tracing
    from incubator_mxnet_tpu.serving import ServingEngine
    from incubator_mxnet_tpu.serving.replica import _build_tiny
    from incubator_mxnet_tpu.serving.router import ServingRouter

    del dev
    rs = np.random.RandomState(16)
    n_req = int(os.environ.get("MXTPU_BENCH_FLEET_REQS", "24"))
    n_replicas, max_new, max_batch = 3, 8, 2
    kill_nth = 5        # replica 0 dies serving its 5th dispatch
    net = _build_tiny("")       # the same weights every replica holds
    vocab = 37
    prompts = [list(rs.randint(0, vocab, int(rs.randint(3, 12))))
               for _ in range(n_req)]
    eng_kw = dict(max_batch=max_batch, block_size=4, num_blocks=64,
                  prefix_cache=False, queue_limit=0)

    # ---- reference: the same stream through ONE unkilled engine ----
    _stage(f"single-engine reference ({n_req} requests x {max_new} "
           "new tokens)", tag="fleet")
    eng = ServingEngine(net, **eng_kw)
    for p in prompts[:2]:       # warm prefill buckets + decode step
        eng.submit(p, max_new)
    eng.run()
    ids = [eng.submit(p, max_new).id for p in prompts]
    t0 = time.perf_counter()
    ref_out = eng.run()
    ref_wall = time.perf_counter() - t0
    refs = [ref_out[i] for i in ids]
    cap_req_s = n_req / ref_wall
    _stage(f"single-engine capacity ~{cap_req_s:.1f} req/s",
           tag="fleet")

    # ---- fleet pass: 3 replicas, one killed mid-stream -------------
    # fixed-seed Poisson arrivals at 1x single-engine capacity: the
    # 3-replica fleet absorbs it with headroom, so the measured
    # failover cost is the fault's, not queueing's
    arrivals = np.cumsum(np.random.RandomState(1611).exponential(
        1.0 / cap_req_s, n_req))
    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="mxtpu_fleet_bench_")
    procs, port_files = [], []
    for i in range(n_replicas):
        pf = os.path.join(tmp, f"port{i}")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("MXTPU_FAULT_SPEC", None)
        if i == 0:
            env["MXTPU_FAULT_SPEC"] = \
                f"router:replica:{kill_nth}:kill"
        log = open(os.path.join(tmp, f"replica{i}.log"), "wb")
        procs.append((subprocess.Popen(
            [sys.executable, "-m",
             "incubator_mxnet_tpu.serving.replica",
             "--port-file", pf, "--name", f"bench{i}",
             "--max-batch", str(max_batch), "--block-size", "4",
             "--num-blocks", "64", "--prefix-cache", "0"],
            cwd=repo, env=env, stdout=log, stderr=log), log))
    _stage(f"booting {n_replicas} replica processes "
           f"(replica0 dies on dispatch #{kill_nth})", tag="fleet")
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if all(os.path.exists(os.path.join(tmp, f"port{i}"))
               for i in range(n_replicas)):
            break
        time.sleep(0.1)
    ports = [int(open(os.path.join(tmp, f"port{i}")).read())
             for i in range(n_replicas)]

    tracing.get_recorder().clear()
    router = ServingRouter(
        replicas=[("127.0.0.1", p) for p in ports],
        poll_interval=0.02, stale_after=5.0).connect()
    try:
        _stage("replaying Poisson stream through the router",
               tag="fleet")
        pending = list(zip(arrivals, prompts))
        reqs = []
        t0 = time.perf_counter()
        while pending:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _arr, p = pending.pop(0)
                reqs.append(router.submit(p, max_new,
                                          deadline=300.0))
            router.poll()
            time.sleep(0.005)
        router.wait(reqs, timeout=300.0)
        fleet_wall = time.perf_counter() - t0

        finished = [r for r in reqs if r.state == "finished"]
        lost = len(reqs) - len(finished)
        dup = sum(
            1 for r in reqs
            if len(tracing.events("router_terminal", rid=r.id)) != 1)
        mismatched = sum(1 for r, ref in zip(reqs, refs)
                         if r.state == "finished"
                         and r.tokens != ref)
        redispatches = sum(r.redispatches for r in reqs)
        failover = telemetry.get_registry().histogram(
            "router_failover_seconds").stats()
        killed_rc = procs[0][0].wait(timeout=60)
        leaks = {}
        for name in ("replica1", "replica2"):
            st = router.replica_stats(name)
            leaks[name] = {"num_allocated": st["num_allocated"],
                           "pool_live": st["pool_live"]}
        _stage("draining survivors", tag="fleet")
        drained = sorted(router.drain(wait=True, timeout=60.0))
        survivor_rcs = [p.wait(timeout=60) for p, _ in procs[1:]]
    finally:
        router.close()
        for p, log in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
            log.close()

    ok = (lost == 0 and dup == 0 and mismatched == 0
          and redispatches >= 1 and killed_rc != 0
          and all(v["num_allocated"] == 0 for v in leaks.values()))
    artifact = {
        "metric": "serving_fleet_failover",
        "platform": platform,
        "fleet": {"replicas": n_replicas, "max_batch": max_batch,
                  "killed": "replica0",
                  "kill_spec": f"router:replica:{kill_nth}:kill"},
        "stream": {"requests": n_req, "max_new_tokens": max_new,
                   "arrival_rate_req_per_s": round(cap_req_s, 2),
                   "arrival_seed": 1611,
                   "single_engine_wall_s": round(ref_wall, 3),
                   "fleet_wall_s": round(fleet_wall, 3)},
        "failover": {
            "redispatched_requests": redispatches,
            "latency_s": {k: (round(v, 4)
                              if isinstance(v, float) else v)
                          for k, v in failover.items()},
            "note": "link-down to first re-dispatched token; on CPU "
                    "this is dominated by the survivors' cold "
                    "prefill-bucket jit compiles for the re-homed "
                    "prompt lengths (a production fleet pre-warms "
                    "buckets at boot)"},
        "terminals": {"finished": len(finished), "lost": lost,
                      "duplicated": dup,
                      "token_mismatches": mismatched},
        "killed_replica_exit_code": killed_rc,
        "survivor_exit_codes": survivor_rcs,
        "survivor_block_leaks": leaks,
        "drained": drained,
        "all_invariants_held": ok,
    }
    out_path = os.path.join(repo, "BENCH_r16.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "serving_fleet_failover",
        "value": artifact["failover"]["latency_s"].get("p50"),
        "unit": "s_failover_p50",
        "platform": platform,
        "redispatched": redispatches,
        "lost": lost, "duplicated": dup,
        "token_mismatches": mismatched,
        "all_invariants_held": ok,
        "artifact": "BENCH_r16.json",
    }))


def _bench_tracing(dev, platform):
    """Flight-recorder bench (ISSUE 9 acceptance): the serving
    stream from the ISSUE 7 bench run (a) with MXTPU_TELEMETRY=0 and
    (b) with tracing ON — reporting per-request TTFT decomposition
    (queue wait / prefill / decode per request from
    ``ServingEngine.stats()``), the compile-event ledger (one compile
    per signature, each carrying its attribution reason), tracing
    overhead on serving throughput, and a fault-injected
    (serve:request eviction + grad:nonfinite divergence) run's
    flight-recorder dump.  CPU-measurable; writes BENCH_r09.json."""
    import tempfile
    import warnings

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import (autograd, gluon, nd, resilience,
                                     tracing)
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving import ServingEngine

    del dev
    mx.random.seed(0)
    rs = np.random.RandomState(7)
    vocab, d, layers, heads, max_len = 512, 256, 4, 8, 128
    n_req = int(os.environ.get("MXTPU_BENCH_SERVE_REQS", "16"))
    max_new = int(os.environ.get("MXTPU_BENCH_SERVE_NEW", "32"))
    _stage(f"building LM d={d} L={layers} ({n_req} requests x "
           f"{max_new} new tokens)", tag="trace")
    net = TransformerLM(vocab, d_model=d, n_layers=layers,
                        n_heads=heads, max_len=max_len)
    net.initialize(mx.init.Xavier())
    system = list(rs.randint(0, vocab, 24))
    prompts = []
    for i in range(n_req):
        own = list(rs.randint(0, vocab, int(rs.randint(8, 40))))
        p = (system + own) if i % 2 == 0 else own
        prompts.append(p[:max_len - max_new - 1])
    ntok = n_req * max_new

    def measured_engine():
        """One engine: compile-warm + cache-warm passes, then the
        best of three measured saturated passes (tokens/s)."""
        eng = ServingEngine(net, max_batch=8, block_size=16,
                            num_blocks=192)

        def one_pass():
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new)
            eng.run()
            return time.perf_counter() - t0

        one_pass()      # compiles prefill buckets + decode step
        one_pass()      # warm prefix cache's smaller buckets
        return min(one_pass() for _ in range(3)), eng

    prev_tel = os.environ.get("MXTPU_TELEMETRY")
    try:
        os.environ["MXTPU_TELEMETRY"] = "0"
        _stage("serving pass, tracing OFF (MXTPU_TELEMETRY=0)",
               tag="trace")
        off_s, _ = measured_engine()
        os.environ["MXTPU_TELEMETRY"] = "1"
        tracing.reset_for_tests()   # clean ledger for the ON run
        _stage("serving pass, tracing ON", tag="trace")
        on_s, eng = measured_engine()
    finally:
        if prev_tel is None:
            os.environ.pop("MXTPU_TELEMETRY", None)
        else:
            os.environ["MXTPU_TELEMETRY"] = prev_tel
    overhead = (on_s - off_s) / off_s
    _stage(f"tracing overhead {overhead * 100:.2f}% "
           f"({ntok / off_s:.0f} -> {ntok / on_s:.0f} tok/s)",
           tag="trace")

    # ---- per-request TTFT decomposition -------------------------
    summaries = list(eng.stats()["requests"])[-n_req:]
    decomposition = [
        {k: s[k] for k in ("id", "state", "queue_wait_s",
                           "prefill_s", "ttft_s", "decode_s",
                           "tokens_generated", "preemptions")}
        for s in summaries]
    lifecycle_complete = all(
        s["state"] == "finished" and s["ttft_s"] is not None
        and s["queue_wait_s"] is not None for s in summaries)

    # ---- compile-event ledger -----------------------------------
    compile_evs = tracing.events("compile")
    sigs = {(e["site"], json.dumps(e["signature"], sort_keys=True))
            for e in compile_evs}
    compile_ledger = [
        {"site": e["site"], "reason": e["reason"],
         "seconds": e["seconds"]} for e in compile_evs]
    one_per_signature = len(compile_evs) == len(sigs)
    all_attributed = all(e["reason"] for e in compile_evs)

    # ---- fault dump: eviction + divergence ----------------------
    _stage("fault-injected run (eviction + divergence) -> dump",
           tag="trace")
    dump_path = os.path.join(tempfile.mkdtemp(prefix="mxtpu_fr_"),
                             "flight.jsonl")
    prev_env = {k: os.environ.get(k) for k in
                ("MXTPU_TRACE_DUMP", "MXTPU_FAULT_SPEC",
                 "MXTPU_NONFINITE_POLICY", "MXTPU_MAX_BAD_STEPS")}
    try:
        os.environ["MXTPU_TRACE_DUMP"] = dump_path
        os.environ["MXTPU_FAULT_SPEC"] = \
            "serve:request:2:error,grad:nonfinite:*:nan"
        os.environ["MXTPU_NONFINITE_POLICY"] = "skip"
        os.environ["MXTPU_MAX_BAD_STEPS"] = "3"
        resilience.reset_faults()
        feng = ServingEngine(net, max_batch=2, block_size=16,
                             num_blocks=64)
        freqs = [feng.submit(p, 4) for p in prompts[:3]]
        feng.run()
        evicted = [r for r in freqs if r.state == "failed"]
        mlp = nn.HybridSequential()
        mlp.add(nn.Dense(16, activation="relu"))
        mlp.add(nn.Dense(3))
        mlp.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(mlp.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        x = nd.array(rs.randn(10, 8).astype("float32"))
        y = nd.array(rs.randint(0, 3, 10).astype("float32"))
        diverged = False
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            try:
                for _ in range(8):
                    with autograd.record():
                        loss = loss_fn(mlp(x), y)
                    loss.backward()
                    trainer.step(10)
            except resilience.DivergedError:
                diverged = True
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience.reset_faults()
    dump_lines = []
    if os.path.exists(dump_path):
        with open(dump_path) as f:
            dump_lines = [json.loads(line) for line in f]
    evicted_id = evicted[0].id if evicted else None
    dump_events = dump_lines[1:] if dump_lines else []
    evicted_lifecycle = sorted(
        e["event"] for e in dump_events
        if e.get("rid") == evicted_id
        and e.get("engine") == feng.engine_id)
    fault_dump = {
        "path": dump_path,
        "exists": bool(dump_lines),
        "reason": dump_lines[0]["reason"] if dump_lines else None,
        "events": len(dump_events),
        "diverged": diverged,
        "evicted_request": evicted_id,
        "evicted_lifecycle_events": evicted_lifecycle,
        "sentinel_events": sum(
            1 for e in dump_events
            if e["event"].startswith("sentinel_")),
    }

    artifact = {
        "metric": "tracing_flight_recorder",
        "platform": platform,
        "stream": {"requests": n_req, "max_new_tokens": max_new},
        "throughput": {
            "tokens_per_s_telemetry_off": round(ntok / off_s, 1),
            "tokens_per_s_tracing_on": round(ntok / on_s, 1),
            "overhead_pct": round(overhead * 100, 2),
            "overhead_under_2pct": overhead < 0.02},
        "ttft_decomposition_per_request": decomposition,
        "lifecycle_complete": lifecycle_complete,
        "compile_ledger": compile_ledger,
        "one_compile_per_signature": one_per_signature,
        "every_compile_attributed": all_attributed,
        "fault_dump": fault_dump,
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r09.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "tracing_flight_recorder",
        "value": artifact["throughput"]["overhead_pct"],
        "unit": "pct_overhead_vs_telemetry_off",
        "platform": platform,
        "tokens_per_s_on": artifact["throughput"][
            "tokens_per_s_tracing_on"],
        "one_compile_per_signature": one_per_signature,
        "lifecycle_complete": lifecycle_complete,
        "fault_dump_events": fault_dump["events"],
        "diverged_and_dumped": diverged and fault_dump["exists"],
        "artifact": "BENCH_r09.json",
    }))


def _bench_debugz(dev, platform):
    """Live introspection bench (ISSUE 20 acceptance, BENCH_r20.json):
    (a) serving throughput with the debugz endpoint disabled
    (MXTPU_DEBUGZ=0) vs enabled AND actively polled (a client thread
    cycling varz/statusz/healthz against the live endpoint during
    the measured pass) — the endpoint must cost < 2%; (b) the online
    AnomalyWatch fed a synthetic per-step timeline with a 3x
    ``data_wait`` regression injected — detected within 20 steps,
    attributed to the right component, exactly one episode.
    CPU-measurable; run with MXTPU_BENCH_MODEL=debugz."""
    import random
    import threading

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import debugz, rpc, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving import ServingEngine

    del dev
    mx.random.seed(0)
    rs = np.random.RandomState(7)
    vocab, d, layers, heads, max_len = 512, 256, 4, 8, 128
    n_req = int(os.environ.get("MXTPU_BENCH_SERVE_REQS", "16"))
    max_new = int(os.environ.get("MXTPU_BENCH_SERVE_NEW", "32"))
    _stage(f"building LM d={d} L={layers} ({n_req} requests x "
           f"{max_new} new tokens)", tag="debugz")
    net = TransformerLM(vocab, d_model=d, n_layers=layers,
                        n_heads=heads, max_len=max_len)
    net.initialize(mx.init.Xavier())
    prompts = []
    for _ in range(n_req):
        own = list(rs.randint(0, vocab, int(rs.randint(8, 40))))
        prompts.append(own[:max_len - max_new - 1])
    ntok = n_req * max_new

    def measured(poll_addr=None):
        """Compile-warm + cache-warm passes, then best-of-3 measured
        saturated passes; when ``poll_addr`` is set, a client thread
        hammers the live endpoint throughout the measured passes."""
        eng = ServingEngine(net, max_batch=8, block_size=16,
                            num_blocks=192)
        unreg = debugz.register_provider(
            "engine", lambda: {"stats_requests":
                               len(eng.stats()["requests"])}) \
            if poll_addr else None

        def one_pass():
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new)
            eng.run()
            return time.perf_counter() - t0

        one_pass()      # compiles prefill buckets + decode step
        one_pass()      # warm prefix cache's smaller buckets
        stop = threading.Event()
        polls = [0]

        def poller():
            ops = ({"op": "varz"}, {"op": "statusz"},
                   {"op": "healthz"})
            i = 0
            while not stop.wait(0.02):
                try:
                    rpc.call_once(poll_addr[0], poll_addr[1],
                                  ops[i % 3], timeout=2.0)
                    polls[0] += 1
                except rpc.RpcError:
                    pass
                i += 1

        t = None
        if poll_addr is not None:
            t = threading.Thread(target=poller, daemon=True)
            t.start()
        try:
            best = min(one_pass() for _ in range(3))
        finally:
            stop.set()
            if t is not None:
                t.join(timeout=5)
            if unreg is not None:
                unreg()
        return best, polls[0]

    prev_dz = os.environ.get("MXTPU_DEBUGZ")
    try:
        os.environ["MXTPU_DEBUGZ"] = "0"
        debugz.stop()
        _stage("serving pass, endpoint OFF (MXTPU_DEBUGZ=0)",
               tag="debugz")
        off_s, _ = measured()
        os.environ["MXTPU_DEBUGZ"] = "1"
        srv = debugz.maybe_start("bench")
        _stage(f"serving pass, endpoint ON + polled "
               f"(port {srv.port})", tag="debugz")
        on_s, n_polls = measured(poll_addr=(srv.host, srv.port))
    finally:
        if prev_dz is None:
            os.environ.pop("MXTPU_DEBUGZ", None)
        else:
            os.environ["MXTPU_DEBUGZ"] = prev_dz
        debugz.stop()
    overhead = (on_s - off_s) / off_s
    _stage(f"debugz overhead {overhead * 100:.2f}% "
           f"({ntok / off_s:.0f} -> {ntok / on_s:.0f} tok/s, "
           f"{n_polls} polls during measured passes)", tag="debugz")

    # ---- anomaly watchdog: injected 3x data_wait regression -----
    _stage("anomaly watchdog: inject 3x data_wait at step 33",
           tag="debugz")
    telemetry.reset_anomaly_for_tests()
    rnd = random.Random(3)
    baseline = {"data_wait": 0.010, "forward_backward": 0.030,
                "optimizer": 0.005, "host_sync": 0.002}

    def split(scale):
        return {k: v * (scale if k == "data_wait" else 1.0)
                * (1.0 + 0.02 * rnd.random())
                for k, v in baseline.items()}

    watch = telemetry.AnomalyWatch(group="bench", window=32,
                                   threshold=6.0, min_samples=8,
                                   cooldown=4)
    for _ in range(32):
        watch.observe(split(1.0))
    detect_steps, component = None, None
    for step in range(1, 21):
        ep = watch.observe(split(3.0))
        if ep is not None:
            detect_steps, component = step, ep["component"]
            break
    for _ in range(40):         # sustained: still one episode
        watch.observe(split(3.0))
    _stage(f"anomaly detected in {detect_steps} step(s), "
           f"component={component}, episodes={watch.episodes}",
           tag="debugz")

    artifact = {
        "metric": "debugz_introspection",
        "platform": platform,
        "stream": {"requests": n_req, "max_new_tokens": max_new},
        "throughput": {
            "tokens_per_s_debugz_off": round(ntok / off_s, 1),
            "tokens_per_s_debugz_on": round(ntok / on_s, 1),
            "overhead_pct": round(overhead * 100, 2),
            "overhead_under_2pct": overhead < 0.02,
            "polls_during_measured_passes": n_polls},
        "anomaly": {
            "injected": "data_wait x3 after 32 calm steps",
            "detect_steps": detect_steps,
            "detected_within_20_steps":
                detect_steps is not None and detect_steps <= 20,
            "component": component,
            "attributed_correctly": component == "data_wait",
            "episodes": watch.episodes,
            "exactly_one_episode": watch.episodes == 1},
        "endpoint_ops": list(debugz.OPS),
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r20.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "debugz_introspection",
        "value": artifact["throughput"]["overhead_pct"],
        "unit": "pct_overhead_vs_debugz_off",
        "platform": platform,
        "tokens_per_s_on": artifact["throughput"][
            "tokens_per_s_debugz_on"],
        "anomaly_detect_steps": detect_steps,
        "anomaly_component": component,
        "anomaly_exactly_one_episode": watch.episodes == 1,
        "artifact": "BENCH_r20.json",
    }))


def _make_synthetic_rec(path_prefix, n, edge=224):
    """Write n real JPEGs (structured noise) into an indexed .rec."""
    import io as _pyio

    from PIL import Image

    from incubator_mxnet_tpu import recordio as rio

    rec = rio.MXIndexedRecordIO(path_prefix + ".idx",
                                path_prefix + ".rec", "w")
    rs = np.random.RandomState(7)
    for i in range(n):
        # smooth gradient + noise: compresses like a natural image,
        # so decode cost is realistic (pure noise JPEGs decode slow)
        gx = np.linspace(0, 255, edge, dtype=np.float32)
        img = (gx[None, :, None] * 0.5 + gx[:, None, None] * 0.3
               + rs.rand(edge, edge, 3) * 64).astype(np.uint8)
        buf = _pyio.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=90)
        rec.write_idx(i, rio.pack(
            rio.IRHeader(0, float(i % 1000), i, 0), buf.getvalue()))
    rec.close()


def _bench_pipeline(dev, platform):
    """End-to-end input pipeline: JPEG .rec → threaded decode →
    DevicePrefetchIter (h2d overlap) → compiled ResNet-50 train step.
    The number that matters is e2e img/s vs the naked-step img/s —
    the reference's whole src/io/ exists to make those equal
    (iter_prefetcher.h:47).  Run with MXTPU_BENCH_MODEL=pipeline."""
    import tempfile

    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel

    n_img = int(os.environ.get("MXTPU_BENCH_PIPE_IMGS", "512"))
    net_kind = os.environ.get("MXTPU_BENCH_PIPE_NET", "resnet50")

    mx.random.seed(0)
    if net_kind == "tiny":
        # CPU-testable stand-in: same pipeline, cheap compute
        net = mx.gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(mx.gluon.nn.Conv2D(8, 3, strides=4,
                                       activation="relu"),
                    mx.gluon.nn.GlobalAvgPool2D(),
                    mx.gluon.nn.Dense(1000))
    else:
        net = mx.gluon.model_zoo.vision.resnet50_v1()
    net.initialize(mx.initializer.Xavier())
    pure = parallel.functionalize(
        net, jnp.zeros((1, 3, 224, 224), jnp.float32))

    compute_dtype = jnp.bfloat16 if platform != "cpu" else None
    step = parallel.ShardedTrainStep(
        pure, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9),
        mesh=parallel.make_mesh(devices=[dev]),
        compute_dtype=compute_dtype)
    rng = jax.random.PRNGKey(0)

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "synth")
        t0 = time.perf_counter()
        _make_synthetic_rec(prefix, n_img)
        gen_s = time.perf_counter() - t0

        def make_iter():
            return mx.io.ImageRecordIter(
                path_imgrec=prefix + ".rec", data_shape=(3, 224, 224),
                batch_size=BATCH, shuffle=False, preprocess_threads=8,
                round_batch=True)

        # (a) feed-only: decode+batch throughput, no device work
        it = make_iter()
        t0 = time.perf_counter()
        n = sum(b.data[0].shape[0] for b in it)
        feed_img_s = n / (time.perf_counter() - t0)

        # (b) naked compiled step on one device-resident batch
        it = make_iter()
        batch = it.next()
        x = jax.device_put(batch.data[0]._data, dev)
        y = jax.device_put(
            np.asarray(batch.label[0].asnumpy(), np.int32), dev)
        for _ in range(3):
            loss = step(x, y, rng=rng)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(10):
            loss = step(x, y, rng=rng)
        float(loss)
        step_img_s = BATCH * 10 / (time.perf_counter() - t0)

        # (c) end-to-end: rec decode → device prefetch → step
        ctx = mx.cpu(0) if platform == "cpu" else mx.tpu(0)
        pre = mx.io.DevicePrefetchIter(make_iter(), ctx=ctx, depth=2)
        t0 = time.perf_counter()
        n = 0
        loss = None
        for b in pre:
            y = b.label[0]._data.astype(jnp.int32)
            loss = step(b.data[0]._data, y, rng=rng)
            n += b.data[0].shape[0]
        float(loss)
        e2e_img_s = n / (time.perf_counter() - t0)

    ratio = e2e_img_s / step_img_s
    print(json.dumps({
        "metric": f"{net_kind}_e2e_pipeline_batch{BATCH}_1chip",
        "value": round(e2e_img_s, 2),
        "unit": "samples/sec",
        "vs_baseline": round(e2e_img_s / BASELINE_IMG_S, 3)
        if BATCH == 32 else None,
        **_device_stamp(dev),
        "feed_only_img_s": round(feed_img_s, 2),
        "naked_step_img_s": round(step_img_s, 2),
        "e2e_over_step": round(ratio, 3),
        "n_images": n_img,
        "rec_gen_s": round(gen_s, 1),
    }))


def _bench_data_service(dev, platform):
    """Sharded multi-process input service (docs/data_service.md):
    img/s at 1/2/4 decode worker processes vs the single-process
    native and PIL baselines, deterministic-mode bit-identity,
    mid-epoch resume exactness, and SIGKILL-worker recovery timing.
    Run with MXTPU_BENCH_MODEL=data_service; writes BENCH_r10.json.

    Methodology notes baked into the artifact: the ISSUE-10 baseline
    (766 img/s) was measured on the round-4 ONE-core host; absolute
    scaling here is bounded by this host's core count (`ncores`), so
    scaling efficiency is reported against the core-bounded ideal
    min(W, ncores), and each config is measured in interleaved
    rounds (median + best reported) because this host shows heavy
    run-to-run CPU-availability noise."""
    import signal
    import tempfile

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.data_service import DataServiceIter

    ncores = os.cpu_count() or 1
    n_img = int(os.environ.get("MXTPU_BENCH_DS_IMGS", "1024"))
    reps = int(os.environ.get("MXTPU_BENCH_DS_REPS", "3"))
    ISSUE_BASELINE = 766.0     # r4 single-process native (PERF.md)
    shape = (3, 224, 224)

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "synth")
        _stage(f"generating {n_img} JPEGs", "ds")
        _make_synthetic_rec(prefix, n_img)

        def single_iter(threads):
            return mx.io.ImageRecordIter(
                path_imgrec=prefix + ".rec", data_shape=shape,
                batch_size=BATCH, shuffle=False,
                preprocess_threads=threads, round_batch=True)

        def single_rate(threads, native=True):
            old = os.environ.get("MXTPU_NATIVE_DECODE")
            if not native:
                os.environ["MXTPU_NATIVE_DECODE"] = "0"
            try:
                it = single_iter(threads)
                t0 = time.perf_counter()
                n = sum(b.data[0].shape[0] - b.pad for b in it)
                return n / (time.perf_counter() - t0)
            finally:
                if not native:
                    if old is None:
                        os.environ.pop("MXTPU_NATIVE_DECODE", None)
                    else:
                        os.environ["MXTPU_NATIVE_DECODE"] = old

        def service_rate(W):
            svc = DataServiceIter(
                path_imgrec=prefix + ".rec", data_shape=shape,
                batch_size=BATCH, num_workers=W,
                preprocess_threads=1, round_batch=True)
            try:
                sum(1 for _ in svc)       # warm epoch (spawn, faults)
                svc.reset()
                t0 = time.perf_counter()
                n = sum(b.data[0].shape[0] - b.pad for b in svc)
                return n / (time.perf_counter() - t0)
            finally:
                svc.close()

        # interleaved rounds decorrelate host-availability noise
        # from the config under test
        workers = (1, 2, 4)
        # on a 1-core host ("single", 1) and ("single", ncores) are
        # the same dict key — measure each distinct config once
        single_cfgs = (1,) if ncores == 1 else (1, ncores)
        samples = {("svc", w): [] for w in workers}
        for c in single_cfgs:
            samples[("single", c)] = []
        samples[("pil", 4)] = []
        for r in range(reps):
            _stage(f"measurement round {r + 1}/{reps}", "ds")
            samples[("pil", 4)].append(single_rate(4, native=False))
            for c in single_cfgs:
                samples[("single", c)].append(single_rate(c))
            for w in workers:
                samples[("svc", w)].append(service_rate(w))

        def med(xs):
            return float(np.median(xs))

        svc_best = {w: max(samples[("svc", w)]) for w in workers}
        svc_med = {w: med(samples[("svc", w)]) for w in workers}

        # ---- correctness: bit-identity + resume + kill recovery
        _stage("bit-identity / resume / kill-recovery", "ds")
        it = single_iter(2)
        ref = [(b.data[0].asnumpy(), b.label[0].asnumpy(), b.pad)
               for b in it]

        def batches_equal(got):
            return len(got) == len(ref) and all(
                p == rp and np.array_equal(d, rd)
                and np.array_equal(l, rl)
                for (d, l, p), (rd, rl, rp) in zip(got, ref))

        with DataServiceIter(
                path_imgrec=prefix + ".rec", data_shape=shape,
                batch_size=BATCH, num_workers=2,
                preprocess_threads=1, round_batch=True) as svc:
            got = [(b.data[0].asnumpy(), b.label[0].asnumpy(), b.pad)
                   for b in svc]
            bit_identical = batches_equal(got)
            # resume: 5 delivered batches, snapshot, drain the rest
            svc.reset()
            for _ in range(5):
                svc.next()
            state = svc.state_dict()
            tail = [(b.data[0].asnumpy(), b.pad) for b in svc]
        with DataServiceIter(
                path_imgrec=prefix + ".rec", data_shape=shape,
                batch_size=BATCH, num_workers=2,
                preprocess_threads=1, round_batch=True) as svc:
            svc.load_state_dict(state)
            svc.reset()
            tail2 = [(b.data[0].asnumpy(), b.pad) for b in svc]
            resume_exact = len(tail) == len(tail2) and all(
                p == rp and np.array_equal(d, rd)
                for (d, p), (rd, rp) in zip(tail, tail2))

        import warnings as _warnings
        with DataServiceIter(
                path_imgrec=prefix + ".rec", data_shape=shape,
                batch_size=BATCH, num_workers=2,
                preprocess_threads=1, ring_depth=1,
                round_batch=True) as svc:
            got = [(svc.next().data[0].asnumpy(), None, 0)]
            os.kill(svc._procs[1].pid, signal.SIGKILL)
            # the killed worker usually has a batch already staged in
            # its ring, so the first post-kill next() can just drain
            # it — recovery is the next() whose consume notices the
            # dead producer, respawns, and waits for the restarted
            # worker's first batch: the one that moves _restarts
            kill_recovery_s = None
            with _warnings.catch_warnings():
                _warnings.simplefilter("ignore")
                try:
                    while True:
                        t0 = time.perf_counter()
                        b = svc.next()
                        dt = time.perf_counter() - t0
                        if kill_recovery_s is None and svc._restarts:
                            kill_recovery_s = dt
                        got.append((b.data[0].asnumpy(), None, 0))
                except StopIteration:
                    pass
            kill_identical = len(got) == len(ref) and all(
                np.array_equal(d, rd)
                for (d, _, _), (rd, _, _) in zip(got, ref))
            restarts = svc._restarts
        shm_clean = not [f for f in os.listdir("/dev/shm")
                         if f.startswith("mxtpu_ds")]

    ideal = {w: min(w, ncores) for w in workers}
    artifact = {
        "metric": "data_service_input_throughput",
        "platform": platform,
        "host": {"ncores": ncores, "n_images": n_img,
                 "batch": BATCH, "rounds": reps,
                 "note": ("heavy run-to-run CPU-availability noise "
                          "on this host (co-tenant steal): configs "
                          "measured in interleaved rounds, median "
                          "and best reported; acceptance uses best")},
        "issue_baseline_img_s": ISSUE_BASELINE,
        "issue_baseline_note": ("766 img/s was the r4 single-process "
                                "native ceiling measured on a ONE-"
                                "core host (PERF.md round 4)"),
        "baselines": {
            "pil_4threads_img_s": round(med(samples[("pil", 4)]), 1),
            "native_1thread_img_s": round(
                med(samples[("single", 1)]), 1),
            **({f"native_{ncores}threads_img_s": round(
                med(samples[("single", ncores)]), 1),
                "host_thread_scaling_1_to_2": round(
                    med(samples[("single", ncores)])
                    / med(samples[("single", 1)]), 2)}
               if ncores > 1 else {}),
            # the strongest single-process number this host produced
            # across all rounds: the service must beat THIS, not
            # just the one-core-host 766 figure
            "single_process_best_img_s": round(max(
                max(samples[("single", c)])
                for c in single_cfgs), 1),
        },
        "service": {
            str(w): {
                "img_s_median": round(svc_med[w], 1),
                "img_s_best": round(svc_best[w], 1),
                "vs_issue_baseline": round(
                    svc_best[w] / ISSUE_BASELINE, 2),
                "ideal_cores": ideal[w],
                "scaling_efficiency_vs_core_ideal": round(
                    (svc_best[w] / svc_best[1]) / ideal[w], 2),
            } for w in workers},
        "correctness": {
            "bit_identical_deterministic": bit_identical,
            "resume_exact": resume_exact,
            "kill_recovery_s": round(kill_recovery_s, 2),
            "kill_epoch_bit_identical": kill_identical,
            "worker_restarts": restarts,
            "no_orphan_shm": shm_clean,
        },
        "acceptance": {
            "ge_2x_over_766": max(svc_best.values())
            >= 2 * ISSUE_BASELINE,
            "beats_same_host_single_process": max(svc_best.values())
            >= max(max(samples[("single", c)]) for c in single_cfgs),
            "scaling_note": (f"absolute 1->4 scaling is bounded by "
                             f"ncores={ncores} on this host (in-"
                             "process native thread scaling 1->2 is "
                             "equally bounded — see host_thread_"
                             "scaling_1_to_2); efficiency is vs "
                             "min(W, ncores)"),
        },
    }
    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r10.json")
    with open(out, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "data_service_input_throughput",
        "value": round(max(svc_best.values()), 1),
        "unit": "img/sec",
        "vs_766_single_process": round(
            max(svc_best.values()) / ISSUE_BASELINE, 2),
        "bit_identical": bit_identical,
        "resume_exact": resume_exact,
        "kill_recovery_s": round(kill_recovery_s, 2),
        "platform": platform,
        "artifact": "BENCH_r10.json",
    }))


def _bench_data_service_net(dev, platform):
    """Remote data-service ranks (docs/data_service.md "Remote
    ranks"): loopback-remote vs local-shm shard throughput and
    per-batch overhead of the framed-RPC + base64 transport, mixed-
    placement bit-identity vs all-local, SIGKILL-host failover
    recovery timing with the epoch still bit-identical, and a
    no-leak audit (shm segments).  Run with
    MXTPU_BENCH_MODEL=data_service_net; writes BENCH_r17.json.

    Loopback is the honest worst case for transport overhead: real
    deployments hide the wire cost behind the credit window, but
    both placements here decode on the SAME host, so any rate gap
    IS the serialization + framing tax."""
    import signal
    import tempfile

    from incubator_mxnet_tpu.data_service import DataServiceIter
    from incubator_mxnet_tpu.data_service.net import RemoteShardServer

    ncores = os.cpu_count() or 1
    n_img = int(os.environ.get("MXTPU_BENCH_DSN_IMGS", "512"))
    reps = int(os.environ.get("MXTPU_BENCH_DSN_REPS", "3"))
    shape = (3, 224, 224)
    W = 2

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "synth")
        _stage(f"generating {n_img} JPEGs", "dsn")
        _make_synthetic_rec(prefix, n_img)

        srv = RemoteShardServer(host="127.0.0.1", port=0,
                                max_shards=W).start()
        addr = f"127.0.0.1:{srv.port}"

        def run_epoch(remote_addrs):
            svc = DataServiceIter(
                path_imgrec=prefix + ".rec", data_shape=shape,
                batch_size=BATCH, num_workers=W,
                preprocess_threads=1, round_batch=True,
                remote_addrs=remote_addrs)
            try:
                sum(1 for _ in svc)     # warm epoch (spawn/connect)
                svc.reset()
                t0 = time.perf_counter()
                n = sum(b.data[0].shape[0] - b.pad for b in svc)
                dt = time.perf_counter() - t0
                return n / dt, dt
            finally:
                svc.close()

        placements = {"local": [], "mixed": [addr],
                      "all_remote": [addr] * W}
        samples = {k: [] for k in placements}
        n_batches = (n_img + BATCH - 1) // BATCH
        for r in range(reps):
            _stage(f"measurement round {r + 1}/{reps}", "dsn")
            for k, addrs in placements.items():
                samples[k].append(run_epoch(addrs))

        def med_rate(k):
            return float(np.median([s[0] for s in samples[k]]))

        def best_rate(k):
            return max(s[0] for s in samples[k])

        def med_epoch_s(k):
            return float(np.median([s[1] for s in samples[k]]))

        # ---- bit-identity: mixed placement vs all-local ----------
        _stage("bit-identity mixed vs local", "dsn")

        def epoch_batches(remote_addrs):
            with DataServiceIter(
                    path_imgrec=prefix + ".rec", data_shape=shape,
                    batch_size=BATCH, num_workers=W,
                    preprocess_threads=1, round_batch=True,
                    remote_addrs=remote_addrs) as svc:
                return [(b.data[0].asnumpy(), b.label[0].asnumpy(),
                         b.pad) for b in svc]

        ref = epoch_batches([])
        got = epoch_batches([addr])
        bit_identical = len(got) == len(ref) and all(
            p == rp and np.array_equal(d, rd)
            and np.array_equal(l, rl)
            for (d, l, p), (rd, rl, rp) in zip(got, ref))
        srv.close()

        # ---- SIGKILL-host failover: recovery time + exactness ----
        _stage("host-kill failover", "dsn")
        import subprocess as _sp
        import warnings as _warnings
        pf = os.path.join(td, "port")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   MXTPU_FAULT_SPEC="data_service:host:3:kill")
        env.setdefault("PYTHONPATH", os.path.dirname(
            os.path.abspath(__file__)))
        proc = _sp.Popen(
            [sys.executable, "-m",
             "incubator_mxnet_tpu.data_service.net",
             "--port-file", pf, "--shards", "1"], env=env)
        deadline = time.monotonic() + 30
        while not os.path.exists(pf) and time.monotonic() < deadline:
            time.sleep(0.05)
        port = int(open(pf).read())
        os.environ["MXTPU_DATA_HOST_GRACE"] = "3"
        try:
            with DataServiceIter(
                    path_imgrec=prefix + ".rec", data_shape=shape,
                    batch_size=BATCH, num_workers=W,
                    preprocess_threads=1, round_batch=True,
                    remote_addrs=[f"127.0.0.1:{port}"]) as svc:
                got = []
                kill_recovery_s = None
                with _warnings.catch_warnings():
                    _warnings.simplefilter("ignore")
                    try:
                        while True:
                            t0 = time.perf_counter()
                            b = svc.next()
                            dt = time.perf_counter() - t0
                            if kill_recovery_s is None \
                                    and svc._restarts:
                                kill_recovery_s = dt
                            got.append((b.data[0].asnumpy(),
                                        b.label[0].asnumpy(), b.pad))
                    except StopIteration:
                        pass
                st = svc.stats()
                kill_identical = len(got) == len(ref) and all(
                    p == rp and np.array_equal(d, rd)
                    for (d, _, p), (rd, _, rp) in zip(got, ref))
                demoted_to_local = st["remote_shards"] == 0
                restarts = st["restarts"]
        finally:
            os.environ.pop("MXTPU_DATA_HOST_GRACE", None)
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
        # the resource tracker unlinks the killed host's ring
        # asynchronously — poll before auditing
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and any(
                f.startswith("mxtpu_ds")
                for f in os.listdir("/dev/shm")):
            time.sleep(0.1)
        shm_clean = not [f for f in os.listdir("/dev/shm")
                         if f.startswith("mxtpu_ds")]

    # per-batch transport tax: epoch wall-clock delta amortized over
    # the batches the REMOTE shard carried (~1/W of the epoch)
    remote_batches = max(n_batches // W, 1)
    tax_ms = (med_epoch_s("mixed") - med_epoch_s("local")) \
        / remote_batches * 1e3
    artifact = {
        "metric": "data_service_net_loopback_throughput",
        "platform": platform,
        "host": {"ncores": ncores, "n_images": n_img,
                 "batch": BATCH, "rounds": reps, "workers": W,
                 "note": ("loopback remote ranks decode on the SAME "
                          "host as the consumer: the rate gap vs "
                          "all-local IS the framed-RPC + base64 "
                          "serialization tax, with no extra cores "
                          "to pay for it — real multi-host fleets "
                          "add decode cores instead")},
        "throughput_img_s": {
            k: {"median": round(med_rate(k), 1),
                "best": round(best_rate(k), 1)}
            for k in placements},
        "transport": {
            "mixed_vs_local_ratio": round(
                med_rate("mixed") / med_rate("local"), 3),
            "all_remote_vs_local_ratio": round(
                med_rate("all_remote") / med_rate("local"), 3),
            "per_remote_batch_overhead_ms": round(tax_ms, 2),
        },
        "correctness": {
            "mixed_bit_identical": bit_identical,
            "host_kill_epoch_bit_identical": kill_identical,
            "host_kill_recovery_s": round(kill_recovery_s, 2)
            if kill_recovery_s is not None else None,
            "host_kill_demoted_to_local": demoted_to_local,
            "restarts": restarts,
            "no_orphan_shm": shm_clean,
        },
        "acceptance": {
            "bit_identical_all_placements": bool(
                bit_identical and kill_identical),
            "failover_no_lost_batches": kill_identical,
            "no_leaks": shm_clean,
        },
    }
    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r17.json")
    with open(out, "w") as f:
        f.write(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({
        "metric": "data_service_net_loopback_throughput",
        "mixed_img_s": round(med_rate("mixed"), 1),
        "local_img_s": round(med_rate("local"), 1),
        "per_remote_batch_overhead_ms": round(tax_ms, 2),
        "bit_identical": bit_identical,
        "kill_recovery_s": round(kill_recovery_s, 2)
        if kill_recovery_s is not None else None,
        "platform": platform,
        "artifact": "BENCH_r17.json",
    }))


def main():
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.utils.platform import \
        enable_compile_cache
    enable_compile_cache()

    dev = jax.devices()[0]
    platform = dev.platform
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("bench: jax found no accelerator; set "
                 "JAX_PLATFORMS=cpu to run on the CPU on purpose")

    if os.environ.get("MXTPU_BENCH_MODEL") == "transformer":
        _bench_transformer(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "pipeline":
        _bench_pipeline(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "graph":
        _bench_graph(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "serving":
        _bench_serving(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "serving_slo":
        _bench_serving_slo(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "serving_fleet":
        _bench_serving_fleet(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "tracing":
        _bench_tracing(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "data_service":
        _bench_data_service(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "data_service_net":
        _bench_data_service_net(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "perf_report":
        _bench_perf_report(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "memory":
        _bench_memory(dev, platform)
        return
    if os.environ.get("MXTPU_BENCH_MODEL") == "debugz":
        _bench_debugz(dev, platform)
        return

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel

    mx.random.seed(0)
    net = mx.gluon.model_zoo.vision.resnet50_v1()
    net.initialize(mx.initializer.Xavier())
    pure = parallel.functionalize(
        net, jnp.zeros((1, 3, 224, 224), jnp.float32))
    _stage("model built; cross-checking FLOPs vs the cost model")
    _crosscheck_resnet_flops(net)

    rs = np.random.RandomState(0)
    x_np = np.asarray(rs.rand(BATCH, 3, 224, 224), np.float32)
    y_np = np.asarray(rs.randint(0, 1000, (BATCH,)), np.int32)

    _stage("creating mesh step")
    compute_dtype = jnp.bfloat16 if platform != "cpu" else None
    step = parallel.ShardedTrainStep(
        pure, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9,
                              wd=1e-4),
        mesh=parallel.make_mesh(devices=[dev]),
        compute_dtype=compute_dtype)

    # Batches live on-device during the measure loop, modelling the
    # prefetch-to-device a real input pipeline does (the reference's
    # PrefetchingIter role).
    jax.block_until_ready(step.params)   # settle before the timer
    _stage("params resident; transferring batch")
    t0 = time.perf_counter()
    x, y = jax.block_until_ready(
        (jax.device_put(x_np, dev), jax.device_put(y_np, dev)))
    xfer_s = time.perf_counter() - t0

    _stage(f"batch resident ({xfer_s*1e3:.0f} ms); "
          "compiling + warming up")
    rng = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    for _ in range(WARMUP_STEPS):
        loss = step(x, y, rng=rng)
    float(loss)  # sync; includes compile
    print(f"bench: warmup ({WARMUP_STEPS} steps + compile) "
          f"{time.perf_counter() - t0:.1f}s on {platform}; "
          f"h2d batch transfer {xfer_s*1e3:.0f} ms",
          file=sys.stderr)

    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        loss = step(x, y, rng=rng)
    final_loss = float(loss)  # the host fetch is the barrier
    dt = time.perf_counter() - t0

    img_s = BATCH * MEASURE_STEPS / dt
    assert np.isfinite(final_loss), final_loss
    print(json.dumps({
        "metric": f"resnet50_train_throughput_batch{BATCH}_1chip",
        "value": round(img_s, 2),
        "unit": "samples/sec",
        # K80 baseline is a batch-32 number; only commensurate then
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3)
        if BATCH == 32 else None,
        **_device_stamp(dev),
        **_mfu_fields(dev, FLOPS_PER_IMG * img_s),
        "step_ms": round(1e3 * dt / MEASURE_STEPS, 2),
        "compute_dtype": "bfloat16" if compute_dtype else "float32",
        "final_loss": round(final_loss, 4),
        "model_tflops_per_step": round(
            FLOPS_PER_IMG * BATCH / 1e12, 3),
        "h2d_batch_ms": round(xfer_s * 1e3, 1),
    }))


if __name__ == "__main__":
    main()
