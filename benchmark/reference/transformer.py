"""Decoder-only transformer LM: the plain reference.

Pre-LayerNorm blocks, causal multi-head attention, ReLU MLP, biases,
learned positions: facebook/opt's block (``do_layer_norm_before``,
``activation_function: relu``).  Departures from OPT, because the
program's ``TransformerLM`` is what it is (configs/*.json list them
under ``assumed``): the output head is a matrix of its own, token
embeddings are scaled by sqrt(d), positions carry no offset of 2.

Parameters are a flat dict under the names of ``param_shapes``, in
whatever dtype the checkpoint holds them: each leaf is widened to
float32 where it is used, which is exact.  One batch row is computed
at a time (``lax.map`` inside each layer, each layer under
``jax.checkpoint``), so that the float32 backward pass of the 1.3B
widths fits one chip beside the optimizer's state.
"""
import math

import jax
import jax.numpy as jnp

from .precision import carried, einsum

LN_EPS = 1e-5


def param_shapes(cfg):
    """name -> (shape, kind); kind is matrix, bias, gamma or beta."""
    d, ffn = cfg["hidden_size"], cfg["ffn_dim"]
    out = {"embedding0_weight": ((cfg["vocab_size"], d), "matrix"),
           "embedding1_weight": ((cfg["max_position_embeddings"], d),
                                 "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"transformerblock{i}_"
        a = p + "causalselfattention0_"
        out.update({
            p + "layernorm0_gamma": ((d,), "gamma"),
            p + "layernorm0_beta": ((d,), "beta"),
            a + "dense0_weight": ((3 * d, d), "matrix"),
            a + "dense0_bias": ((3 * d,), "bias"),
            a + "dense1_weight": ((d, d), "matrix"),
            a + "dense1_bias": ((d,), "bias"),
            p + "layernorm1_gamma": ((d,), "gamma"),
            p + "layernorm1_beta": ((d,), "beta"),
            p + "dense0_weight": ((ffn, d), "matrix"),
            p + "dense0_bias": ((ffn,), "bias"),
            p + "dense1_weight": ((d, ffn), "matrix"),
            p + "dense1_bias": ((d,), "bias")})
    out.update({"layernorm0_gamma": ((d,), "gamma"),
                "layernorm0_beta": ((d,), "beta"),
                "dense0_weight": ((cfg["vocab_size"], d), "matrix")})
    return out


def _layer_norm(x, gamma, beta):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * gamma + beta


def _dense(x, w, b, mode):
    y = einsum("ld,od->lo", x, w, mode)
    return y if b is None else carried(y + b, mode)


def _layer(x, lp, n_heads, mode):
    """One block on one row: x (L, d)."""
    length, d = x.shape
    dh = d // n_heads
    h = carried(_layer_norm(x, lp["ln1_g"], lp["ln1_b"]), mode)
    qkv = _dense(h, lp["qkv_w"], lp["qkv_b"], mode)
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(length, n_heads, dh)
               for i in range(3))
    scores = einsum("qhd,khd->hqk", q, k, mode) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((length, length), bool))
    probs = carried(jax.nn.softmax(
        jnp.where(causal, scores, -jnp.inf), axis=-1), mode)
    att = einsum("hqk,khd->qhd", probs, v, mode).reshape(length, d)
    x = carried(x + _dense(att, lp["proj_w"], lp["proj_b"], mode), mode)
    h = carried(_layer_norm(x, lp["ln2_g"], lp["ln2_b"]), mode)
    up = jax.nn.relu(_dense(h, lp["up_w"], lp["up_b"], mode))
    return carried(x + _dense(up, lp["down_w"], lp["down_b"], mode),
                   mode)


def _layer_params(leaf, i):
    p = f"transformerblock{i}_"
    a = p + "causalselfattention0_"
    return {"ln1_g": leaf(p + "layernorm0_gamma"),
            "ln1_b": leaf(p + "layernorm0_beta"),
            "qkv_w": leaf(a + "dense0_weight"),
            "qkv_b": leaf(a + "dense0_bias"),
            "proj_w": leaf(a + "dense1_weight"),
            "proj_b": leaf(a + "dense1_bias"),
            "ln2_g": leaf(p + "layernorm1_gamma"),
            "ln2_b": leaf(p + "layernorm1_beta"),
            "up_w": leaf(p + "dense0_weight"),
            "up_b": leaf(p + "dense0_bias"),
            "down_w": leaf(p + "dense1_weight"),
            "down_b": leaf(p + "dense1_bias")}


def _wide(leaf):
    return leaf.astype(jnp.float32)


def hidden(params, tokens, cfg, mode="f32"):
    """Final-norm hidden states (B, L, d) of tokens (B, L)."""
    d, n_heads = cfg["hidden_size"], cfg["num_attention_heads"]

    def leaf(name):
        return carried(_wide(params[name]), mode)

    length = tokens.shape[1]
    x = leaf("embedding0_weight")[tokens] * math.sqrt(d) \
        + leaf("embedding1_weight")[:length][None]
    x = carried(x, mode)
    for i in range(cfg["num_hidden_layers"]):
        lp = _layer_params(leaf, i)
        row = jax.checkpoint(
            lambda xr, lp=lp: _layer(xr, lp, n_heads, mode))
        x = jax.lax.map(row, x)
    return carried(_layer_norm(x, leaf("layernorm0_gamma"),
                               leaf("layernorm0_beta")), mode)


def logits(params, tokens, cfg, mode="f32"):
    """Logits (B, L, V), float32."""
    h = hidden(params, tokens, cfg, mode)
    head = _wide(params["dense0_weight"])
    return jax.lax.map(lambda hr: _dense(hr, head, None, mode), h)


def loss(params, tokens, labels, cfg, mode="f32"):
    """Mean next-token cross-entropy."""
    h = hidden(params, tokens, cfg, mode)
    head = _wide(params["dense0_weight"])

    @jax.checkpoint
    def row_loss(args):
        hr, yr = args
        lg = _dense(hr, head, None, mode)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, yr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    total = jnp.sum(jax.lax.map(row_loss, (h, labels)))
    return total / labels.size
