"""Decoder LM with latent attention and routed experts under a
manifold-constrained hyper-connection residual, with YaRN positions:
the plain reference (Xing4.0-29B-A4B's layer, DeepSeek-V3's with its
residual changed).

n = ``hc_mult`` and C = ``hidden_size``; each token carries
X in R^{n x C}.

- Entry: X = [e; ...; e], the token's embedding copied into every
  stream.
- Every sublayer (attention, then the FFN, dense or routed), each with
  its own Phi_pre, Phi_post in R^{nC x n}, Phi_res in R^{nC x n^2},
  b_pre, b_post in R^n, b_res in R^{n x n} and scalars a_pre, a_post,
  a_res:

  - u = vec(X) in R^{nC}; u^ = u / sqrt(mean(u^2) + rms_norm_eps), a
    norm without a gain;
  - h_pre = sigmoid(a_pre u^ Phi_pre + b_pre);
    h_post = 2 sigmoid(a_post u^ Phi_post + b_post);
  - M = exp(clip(a_res mat(u^ Phi_res) + b_res, clamp_min,
    clamp_max)), then ``hc_sinkhorn_iters`` times: each row divided by
    its sum + hc_eps, then each column by its sum + hc_eps; H_res = M;
  - h = sum_i h_pre[i] X[i]; y = F(h), F the sublayer with its own
    pre-norm, MLA(RMS(h)) or FFN(RMS(h)) as reference/latent_moe.py
    computes it;
  - X[i] <- sum_j H_res[i, j] X[j] + h_post[i] y.
- Exit: x = sum_i X[i]; logits = head(RMS(x)).
- YaRN, as DeepSeek-V3's ``yarn`` with d = qk_rope_head_dim, theta =
  rope_theta, s = factor and L0 = original_max_position_embeddings:
  f_i = theta^(-2i/d) and g_i = f_i / s; corr(b) = d ln(L0 / (2 pi b))
  / (2 ln theta); low = floor(corr(beta_fast)), high =
  ceil(corr(beta_slow)); ramp_i = clip((i - low) / (high - low), 0,
  1); inv_freq_i = g_i ramp_i + f_i (1 - ramp_i).  cos and sin are
  scaled by mscale(s, mscale) / mscale(s, mscale_all_dim), mscale(s,
  m) = 0.1 m ln s + 1 (1 at this configuration's keys); the softmax
  scale is (qk_nope + qk_rope)^-0.5 mscale(s, mscale_all_dim)^2.

The leaves of a sublayer's hyper-connection are one matrix whose rows
are Phi_pre, Phi_post and Phi_res (H_res row-major), the biases in the
same order, and the three gains: ``layer{i}_hc_{attn,ffn}_weight``,
``_bias``, ``_alpha``.  Everything is float32 and plain: no cache, no
kernel, no absorbed product; the routed layer, the gated FFN, the
precisions and the rounding of ``fp8`` are reference/latent_moe.py's,
imported.  Choices the published config does not state are listed
under ``assumed`` in the configuration's file: copies at the entry, a
sum at the exit, rows before columns in a round, the clip before
``exp`` and ``hc_eps`` in each denominator, a hyper-connection of each
sublayer's own, no gain in the norm of u, RoPE pairs interleaved, no
multi-token-prediction module.

``fault`` plants one for calibration (``FAULTS``).
"""
import math

import jax
import jax.numpy as jnp

from .latent_moe import (QUERY_BLOCK, _gated_ffn, _rms, _wide, carried,
                         einsum, layer_params, routed_layer)
from .latent_moe import param_shapes as _latent_shapes

FAULTS = {
    "res_identity": "H_res the identity: the streams are not mixed",
    "sinkhorn_one_round": "one round of Sinkhorn-Knopp, not all",
    "post_not_doubled": "h_post = sigmoid(...), not twice it",
    "static_only": "a_pre, a_post, a_res taken as 0: the mixing "
                   "weights are the biases' alone",
    "yarn_off": "theta's own frequencies and the scale "
                "(qk_nope + qk_rope)^-0.5",
    "mscale_off": "YaRN's frequencies, but the softmax scale without "
                  "mscale(s, mscale_all_dim)^2",
}


def param_shapes(cfg):
    """name -> (shape, kind): the latent layer's, and per layer and
    sublayer the hyper-connection's matrix, biases and gains."""
    out = _latent_shapes(cfg)
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    for i in range(cfg["num_hidden_layers"]):
        for s in ("attn", "ffn"):
            p = f"layer{i}_hc_{s}_"
            out.update({p + "weight": ((n * (n + 2), n * d), "matrix"),
                        p + "bias": ((n * (n + 2),), "bias"),
                        p + "alpha": ((3,), "gamma")})
    return out


def yarn(cfg, fault=None):
    """(inv_freq (d/2,), softmax scale) of the configuration."""
    d = cfg["qk_rope_head_dim"]
    theta = cfg["rope_theta"]
    plain = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs is None or fault == "yarn_off":
        return plain, scale
    s, l0 = rs["factor"], rs["original_max_position_embeddings"]

    def corr(b):
        return d * math.log(l0 / (2 * math.pi * b)) / (2 * math.log(theta))

    def mscale(m):
        return 0.1 * m * math.log(s) + 1.0

    low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rs.get("beta_slow", 1))), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv_freq = plain / s * ramp + plain * (1.0 - ramp)
    assert mscale(rs.get("mscale", 1)) == mscale(rs["mscale_all_dim"])
    if fault != "mscale_off":
        scale = scale * mscale(rs["mscale_all_dim"]) ** 2
    return inv_freq, scale


def _rotate(x, inv_freq):
    """x (L, ..., 2h) at positions 0..L-1, pairs (2i, 2i + 1)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def attention(h, lp, cfg, mode, fault=None):
    """Latent attention of one sequence ``h`` (L, d), expanded, with
    the configuration's YaRN frequencies and scale."""
    def wide(name):
        return _wide(lp[name], mode)

    length = h.shape[0]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope = cfg["qk_nope_head_dim"]
    eps = cfg["rms_norm_eps"]
    inv_freq, scale = yarn(cfg, fault)
    c_q = carried(_rms(einsum("ld,rd->lr", h, wide("q_a"), mode),
                       wide("q_norm"), eps), mode)
    q = einsum("lr,or->lo", c_q, wide("q_b"), mode) \
        .reshape(length, heads, -1)
    kv = einsum("ld,od->lo", h, wide("kv_a"), mode)
    c_kv = carried(_rms(kv[:, :rank], wide("kv_norm"), eps), mode)
    q_nope = q[..., :nope]
    q_rope = carried(_rotate(q[..., nope:], inv_freq), mode)
    k_rope = carried(_rotate(kv[:, rank:], inv_freq), mode)
    expanded = einsum("lc,oc->lo", c_kv, wide("kv_b"), mode) \
        .reshape(length, heads, -1)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]

    def block(args):
        qn, qr, at = args
        scores = (einsum("qhn,khn->hqk", qn, k_nope, mode)
                  + einsum("qhr,kr->hqk", qr, k_rope, mode)) * scale
        seen = jnp.arange(length)[None, :] <= at[:, None]
        probs = carried(jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), axis=-1), mode)
        return einsum("hqk,khv->qhv", probs, v, mode)

    at = jnp.arange(length)
    if length <= QUERY_BLOCK:
        out = block((q_nope, q_rope, at))
    else:
        pad = -length % QUERY_BLOCK
        out = jax.lax.map(block, tuple(
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            .reshape((-1, QUERY_BLOCK) + a.shape[1:])
            for a in (q_nope, q_rope, at)))
        out = out.reshape((-1,) + out.shape[2:])[:length]
    return einsum("lo,do->ld", out.reshape(length, -1), wide("o"),
                  mode)


def sinkhorn(m, rounds, eps):
    """``rounds`` rounds over m (..., n, n): rows, then columns, each
    divided by its sum + eps."""
    for _ in range(rounds):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def mixing(X, lp, s, cfg, mode, fault=None):
    """(h_pre (L, n), h_post (L, n), H_res (L, n, n)) of streams X
    (L, n, d) before sublayer ``s``."""
    n = cfg["hc_mult"]
    length = X.shape[0]
    u = X.reshape(length, -1)
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                     + cfg["rms_norm_eps"])
    z = einsum("lk,ok->lo", u, _wide(lp[f"hc_{s}"], mode), mode)
    a = _wide(lp[f"hc_{s}_alpha"], mode)
    if fault == "static_only":
        a = jnp.zeros_like(a)
    b = _wide(lp[f"hc_{s}_bias"], mode)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n]) \
        * (1.0 if fault == "post_not_doubled" else 2.0)
    logits = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(length, n, n)
    m = jnp.exp(jnp.clip(logits, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    res = sinkhorn(m, 1 if fault == "sinkhorn_one_round"
                   else cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    if fault == "res_identity":
        res = jnp.broadcast_to(jnp.eye(n), res.shape)
    return pre, post, res


def hyper(X, lp, s, cfg, mode, sublayer, fault=None):
    """Sublayer ``s`` under its hyper-connection: X (L, n, d) out.
    The mixes are weighted sums, float32 in every mode (``highest``:
    a TPU's default would round the weights to bfloat16)."""
    pre, post, res = mixing(X, lp, s, cfg, mode, fault)
    y = sublayer(carried(jnp.einsum("ln,lnd->ld", pre, X,
                                    precision="highest"), mode))
    return carried(jnp.einsum("lij,ljd->lid", res, X, precision="highest")
                   + post[..., None] * y[:, None], mode)


def _layer(X, lp, cfg, mode, fault):
    """One layer on one sequence's streams X (L, n, d)."""
    eps = cfg["rms_norm_eps"]

    def wide(name):
        return _wide(lp[name], mode)

    def attend(h):
        return attention(carried(_rms(h, wide("attn_norm"), eps), mode),
                         lp, cfg, mode, fault)

    def ffn(h):
        h = carried(_rms(h, wide("ffn_norm"), eps), mode)
        if "router" in lp:
            return routed_layer(h, lp, cfg, mode)
        return _gated_ffn(h, wide("gate"), wide("up"), wide("down"), mode)

    X = hyper(X, lp, "attn", cfg, mode, attend, fault)
    return hyper(X, lp, "ffn", cfg, mode, ffn, fault)


def hidden(params, tokens, cfg, mode="f32", fault=None):
    """Final-norm hidden states (B, L, d) of tokens (B, L)."""
    x = _wide(params["embed_weight"][tokens], mode)
    X = jnp.broadcast_to(x[:, :, None], x.shape[:2] + (cfg["hc_mult"],)
                         + x.shape[2:])
    for i in range(cfg["num_hidden_layers"]):
        lp = layer_params(params, i)
        X = jax.lax.map(
            lambda row, lp=lp: _layer(row, lp, cfg, mode, fault), X)
    return carried(_rms(jnp.sum(X, axis=2),
                        _wide(params["norm_gamma"], mode),
                        cfg["rms_norm_eps"]), mode)


def logits(params, tokens, cfg, mode="f32", fault=None):
    """Logits (B, L, V), float32."""
    h = hidden(params, tokens, cfg, mode, fault)
    head = _wide(params["head_weight"], mode)
    return jax.lax.map(
        lambda row: einsum("ld,vd->lv", row, head, mode), h)
