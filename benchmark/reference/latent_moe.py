"""Decoder LM with latent attention and routed experts: the plain
reference (DeepSeek-V3's layer, which JoyAI-LLM-Flash runs at its own
numbers; ISSUE 28 writes the equations down).

Each layer ``x = x + MLA(RMS(x)); x = x + FFN(RMS(x))``, after the
last ``RMS`` and the head (not tied).  No cache, no kernels and no
absorbed products: every position's keys and values are expanded from
its latent, per head, and attention runs over the whole sequence in
blocks of queries (the scores of a block, not of the sequence, are
held).  The routed layer is computed plainly: every expert on every
token, weighted by a gate that is zero off the token's top k.  The
first ``first_k_dense_replace`` layers carry a dense gated FFN.
The experts are a ``lax.scan`` over a layer's stacked leaves and the
query blocks a ``lax.map``, not Python loops, so that a sequence
length compiles in a time that does not grow with the number of
experts; the layers are a Python loop (a scan over layers would have
to pick a layer's stacked experts by ``lax.switch``, which copies
them every time it is passed: 5 s a pass on the chip, PERF.md).

Every product and every carried activation goes through ``einsum``
and ``carried`` below, which are reference/precision.py's for ``f32``,
``f32_default`` and ``bf16``.  ``fp8`` rounds by
``lax.reduce_precision`` (``_e4m3``): the chip's compiler does not
keep precision.py's round trip through ``float8_e4m3fn`` (PERF.md,
section 7).  A leaf is widened to float32 where it is used, which is
exact; an expert's matrices are widened inside the scan, one expert
at a time.

Departures from the published code, each listed under ``assumed`` in
the configuration's file: RoPE pairs are (2i, 2i + 1) and stay where
they are (the published code moves the rotated pairs to the halves
first, which permutes q and k alike and leaves every score as it
is); the multi-token-prediction module is left out.

``fault`` plants one for calibration (``FAULTS``).
"""
import jax
import jax.numpy as jnp

from . import precision

QUERY_BLOCK = 512
EXPERT_LEAVES = ("experts_gate", "experts_up", "experts_down")
_E4M3_MAX = 448.0

FAULTS = {
    "top7": "one expert a token too few, weights normalised over "
            "those that are left",
    "no_routed_experts": "the routed experts' sum left out",
    "no_shared_expert": "the shared expert left out",
    "no_select_bias": "the top k chosen by the scores alone",
    "not_normalised": "the chosen scores not divided by their sum",
    "rope_wrong_dims": "the first qk_rope_head_dim of each head's "
                       "other dims rotated, the rope dims not",
    "latent_one_down": "the cached row (c_kv, k_pe) carried one "
                       "precision below bfloat16 (e4m3)",
}


def _e4m3(x):
    """``x`` rounded to 3 bits of mantissa with one scale a tensor:
    what precision.py's ``fp8`` carries, by ``lax.reduce_precision``
    and not by a round trip through ``float8_e4m3fn``.  Five bits of
    exponent: nothing overflows below the scale's 448, and what e4m3
    would flush (under 2**-15 of the tensor's largest) keeps its 3
    bits, which no sum notices."""
    scale = _E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * scale, exponent_bits=5,
                                    mantissa_bits=3) / scale


def carried(x, mode):
    """An activation as ``mode`` carries it between operations:
    precision.py's, but for ``fp8`` (``_e4m3``)."""
    return _e4m3(x) if mode == "fp8" else precision.carried(x, mode)


def _wide(leaf, mode):
    """A leaf as ``mode`` holds it where it is used: widened to
    float32 (exact), then carried."""
    return carried(leaf.astype(jnp.float32), mode)


def einsum(spec, a, b, mode):
    """``jnp.einsum`` of two operands in ``mode``, float32 result:
    precision.py's, but for ``fp8``, whose operands and result are
    rounded by ``_e4m3``.  Operands of 4 significant bits are whole
    in bfloat16, so the chip's single bf16 pass multiplies them
    exactly and accumulates in float32: the default precision gives
    what ``highest`` gives there, in a sixth of the passes."""
    if mode != "fp8":
        return precision.einsum(spec, a, b, mode)
    return _e4m3(jnp.einsum(spec, a, b,
                            preferred_element_type=jnp.float32))


def param_shapes(cfg):
    """name -> (shape, kind); kind is matrix, bias or gamma."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    experts, width = cfg["n_routed_experts"], \
        cfg["moe_intermediate_size"]
    out = {"embed_weight": ((cfg["vocab_size"], d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}_"
        out.update({
            p + "attn_norm_gamma": ((d,), "gamma"),
            p + "q_a_weight": ((q_rank, d), "matrix"),
            p + "q_norm_gamma": ((q_rank,), "gamma"),
            p + "q_b_weight": ((heads * (nope + rope), q_rank),
                               "matrix"),
            p + "kv_a_weight": ((rank + rope, d), "matrix"),
            p + "kv_norm_gamma": ((rank,), "gamma"),
            p + "kv_b_weight": ((heads * (nope + cfg["v_head_dim"]),
                                 rank), "matrix"),
            p + "o_weight": ((d, heads * cfg["v_head_dim"]), "matrix"),
            p + "ffn_norm_gamma": ((d,), "gamma")})
        if i < cfg["first_k_dense_replace"]:
            ffn = cfg["intermediate_size"]
            out.update({p + "gate_weight": ((ffn, d), "matrix"),
                        p + "up_weight": ((ffn, d), "matrix"),
                        p + "down_weight": ((d, ffn), "matrix")})
            continue
        out.update({
            p + "router_weight": ((experts, d), "matrix"),
            p + "router_bias": ((experts,), "bias"),
            p + "experts_gate_weight": ((experts, width, d), "matrix"),
            p + "experts_up_weight": ((experts, width, d), "matrix"),
            p + "experts_down_weight": ((experts, d, width),
                                        "matrix")})
        shared = width * cfg["n_shared_experts"]
        if shared:
            out.update({
                p + "shared_gate_weight": ((shared, d), "matrix"),
                p + "shared_up_weight": ((shared, d), "matrix"),
                p + "shared_down_weight": ((d, shared), "matrix")})
    out.update({"norm_gamma": ((d,), "gamma"),
                "head_weight": ((cfg["vocab_size"], d), "matrix")})
    return out


def _rms(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma


def _rotate(x, cfg):
    """x (L, ..., 2h) at positions 0..L-1; pairs (2i, 2i + 1),
    frequencies ``rope_theta ** (-2i / 2h)``."""
    half = x.shape[-1] // 2
    freqs = cfg["rope_theta"] ** (
        -2.0 * jnp.arange(half, dtype=jnp.float32) / (2 * half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _gated_ffn(h, gate, up, down, mode):
    mid = carried(jax.nn.silu(einsum("ld,fd->lf", h, gate, mode))
                  * einsum("ld,fd->lf", h, up, mode), mode)
    return einsum("lf,df->ld", mid, down, mode)


def route(h, router, bias, cfg, mode, fault=None):
    """(L, E) gates, zero off each token's top k: ``s = sigmoid(h
    W_r)`` (or softmax), the top k of ``s + bias``, the chosen ``s``
    over their sum (``norm_topk_prob``) times
    ``routed_scaling_factor``."""
    top_k = cfg["num_experts_per_tok"] - (fault == "top7")
    logits = einsum("ld,ed->le", h, router, mode)
    s = jax.nn.sigmoid(logits) \
        if cfg.get("scoring_func", "sigmoid") == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, choice = jax.lax.top_k(
        s if fault == "no_select_bias" else s + bias, top_k)
    picked = jnp.take_along_axis(s, choice, axis=-1)
    if cfg.get("norm_topk_prob", True) and fault != "not_normalised":
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, choice].set(picked)


def routed_layer(h, lp, cfg, mode, fault=None, held=None):
    """What the routed experts and the shared expert give for rows
    ``h`` (L, d).  ``held=(first, count)`` gives those experts' part
    alone, the shared expert left out (one rank's part of an
    expert-parallel layer)."""
    def wide(w):
        return _wide(w, mode)

    gates = route(h, wide(lp["router"]), wide(lp["router_bias"]), cfg,
                  mode, fault)
    first, count = held or (0, gates.shape[1])

    def one(y, expert):
        gate, up, down, mine = expert
        return y + mine[:, None] * _gated_ffn(
            h, wide(gate), wide(up), wide(down), mode), None

    y = jnp.zeros_like(h)
    if fault != "no_routed_experts":
        y, _ = jax.lax.scan(one, y, tuple(
            lp[k][first:first + count] for k in EXPERT_LEAVES)
            + (gates.T[first:first + count],))
    if "shared_gate" in lp and fault != "no_shared_expert" \
            and held is None:
        y = y + _gated_ffn(h, wide(lp["shared_gate"]),
                           wide(lp["shared_up"]),
                           wide(lp["shared_down"]), mode)
    return y


def attention(h, lp, cfg, mode, fault=None):
    """Latent attention of one sequence ``h`` (L, d), expanded."""
    def wide(name):
        return _wide(lp[name], mode)

    length = h.shape[0]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps = cfg["rms_norm_eps"]
    c_q = carried(_rms(einsum("ld,rd->lr", h, wide("q_a"), mode),
                       wide("q_norm"), eps), mode)
    q = einsum("lr,or->lo", c_q, wide("q_b"), mode) \
        .reshape(length, heads, -1)
    kv = einsum("ld,od->lo", h, wide("kv_a"), mode)
    c_kv = carried(_rms(kv[:, :rank], wide("kv_norm"), eps), mode)
    q_nope, q_rope, k_rope = q[..., :nope], q[..., nope:], kv[:, rank:]
    if fault != "rope_wrong_dims":
        q_rope, k_rope = _rotate(q_rope, cfg), _rotate(k_rope, cfg)
    q_rope, k_rope = carried(q_rope, mode), carried(k_rope, mode)
    if fault == "latent_one_down":
        c_kv, k_rope = _e4m3(c_kv), _e4m3(k_rope)
    expanded = einsum("lc,oc->lo", c_kv, wide("kv_b"), mode) \
        .reshape(length, heads, -1)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    if fault == "rope_wrong_dims":
        q_nope, k_nope = (carried(jnp.concatenate(
            [_rotate(a[..., :rope], cfg), a[..., rope:]], -1), mode)
            for a in (q_nope, k_nope))
    scale = (nope + rope) ** -0.5

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


def layer_params(params, i):
    """Layer ``i``'s leaves under their names less the layer's prefix
    and a ``_weight`` or ``_gamma`` at the end."""
    p = f"layer{i}_"
    return {n[len(p):].removesuffix("_weight").removesuffix("_gamma"):
            v for n, v in params.items() if n.startswith(p)}


def _layer(x, lp, cfg, mode, fault):
    """One layer on one sequence: x (L, d)."""
    eps = cfg["rms_norm_eps"]

    def wide(name):
        return _wide(lp[name], mode)

    h = carried(_rms(x, wide("attn_norm"), eps), mode)
    x = carried(x + attention(h, lp, cfg, mode, fault), mode)
    h = carried(_rms(x, wide("ffn_norm"), eps), mode)
    if "router" in lp:
        y = routed_layer(h, lp, cfg, mode, fault)
    else:
        y = _gated_ffn(h, wide("gate"), wide("up"), wide("down"), mode)
    return carried(x + y, mode)


def hidden(params, tokens, cfg, mode="f32", fault=None):
    """Final-norm hidden states (B, L, d) of tokens (B, L)."""
    x = _wide(params["embed_weight"][tokens], mode)
    for i in range(cfg["num_hidden_layers"]):
        lp = layer_params(params, i)
        x = jax.lax.map(
            lambda row, lp=lp: _layer(row, lp, cfg, mode, fault), x)
    return carried(_rms(x, _wide(params["norm_gamma"], mode),
                        cfg["rms_norm_eps"]), mode)


def logits(params, tokens, cfg, mode="f32", fault=None):
    """Logits (B, L, V), float32."""
    h = hidden(params, tokens, cfg, mode, fault)
    head = _wide(params["head_weight"], mode)
    return jax.lax.map(
        lambda row: einsum("ld,vd->lv", row, head, mode), h)
