"""The three Symbol graphs the cost model and the memory planner are
held to (tests/test_perf.py, tests/test_memory_planner.py,
tests/test_tpu_compile.py): an MLP, a ResNet block and a decoder-LM
training step written at the primitive level.  Each builder takes the
``symbol`` module and returns ``(Group([outputs..., loss]), shapes)``.
"""


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
