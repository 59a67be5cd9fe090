"""Hybrid decoder LM of Mamba-2 mixers, latent routed experts and
grouped-query attention: the plain reference (NVIDIA's ``nemotron_h``,
which Nemotron-3-Super runs at its own numbers; the equations are
written down below).

Each layer ``x = x + mixer(RMS(x))``, the mixer the layer's letter of
``hybrid_override_pattern``; after the last layer ``RMS`` and the head
(not tied).  No cache, no kernels, no chunks:

``M``  the recurrence itself, a token at a time (``lax.scan``):
       ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t .
       h_t + D x_t``, the conv over the zero-padded sequence before it;
       so the reference owes nothing to the program's chunked scan or
       its state-step kernel.
``E``  every held expert on every token, a block of
       ``EXPERT_BLOCK`` experts at a time (``lax.scan``), weighted by a
       gate that is zero off the token's top k (chosen over all
       ``published.n_routed_experts`` router outputs); the experts work
       on ``fc1_latent x`` and their sum goes through ``fc2_latent``;
       the shared expert on the row.
``*``  causal attention over the whole sequence in blocks of queries,
       no position encoding.

The configuration's file holds the experts that one chip of the
deployment holds (``n_routed_experts``: the first of them) and the
router's whole width (``published.n_routed_experts``): the reference
computes that chip's part, as the program does.

Every product and every carried activation goes through ``einsum``
and ``carried`` of reference/latent_moe.py (precision.py's, with
``fp8`` rounded by ``lax.reduce_precision``).  The state's sums are
float32 in every mode.

``fault`` plants one for calibration (``FAULTS``).
"""
import jax
import jax.numpy as jnp

from .latent_moe import _wide, carried, einsum

QUERY_BLOCK = 512
EXPERT_BLOCK = 8

FAULTS = {
    "state_not_carried": "no state carried from one position to the "
                         "next (h_{t-1} and the conv's earlier inputs "
                         "zero): what a decode step gives that does not "
                         "take the state its prefill, or the step "
                         "before, left",
    "conv_skipped": "xBC through silu without the conv",
    "no_d_skip": "D x left out of y",
    "norm_not_grouped": "the gated norm over all d_inner values at "
                        "once, not over n_groups groups",
    "relu_not_squared": "the experts and the shared expert relu(.) "
                        "where relu(.)^2",
    "rope_in_attention": "RoPE (rope_theta, the head's halves rotated) "
                         "on queries and keys of the attention layers",
}


def _dims(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * p
    return heads, p, groups, n, inner, inner + 2 * groups * n


def router_width(cfg):
    """The router's outputs: every expert of the published model."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def param_shapes(cfg):
    """name -> (shape, kind); kind is matrix, bias, gamma or beta.
    ``A_log``, ``dt_bias`` and the conv's bias are ``bias`` (about 0:
    ``A`` about -1, ``dt`` the softplus of in_proj's column alone);
    the conv's taps and ``D`` are ``beta`` (N(0, 0.1)): the conv's
    output, x, B and C, is then small enough that ``C . h`` and ``D x``
    are of one size; the attention layers' query weights are ``beta``
    too, so that a query's scores spread some 7.7 over the keys (1.5
    as ``matrix``) and each query reads a few positions, as a layer
    with no position encoding learns to: spread over hundreds of them,
    a position encoding planted in it moves the logits less than
    bfloat16 rounding does (PERF.md, section 4)."""
    d = cfg["hidden_size"]
    heads, _, _, _, inner, conv = _dims(cfg)
    latent, width = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"] \
        * cfg.get("n_shared_experts", 1)
    experts = cfg["n_routed_experts"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    out = {"embed_weight": ((cfg["vocab_size"], d), "matrix")}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"layer{i}_"
        out[p + "norm_gamma"] = ((d,), "gamma")
        if kind == "M":
            out.update({
                p + "in_proj_weight": ((inner + conv + heads, d),
                                       "matrix"),
                p + "conv_weight": ((conv, cfg["conv_kernel"]), "beta"),
                p + "conv_bias": ((conv,), "bias"),
                p + "dt_bias": ((heads,), "bias"),
                p + "A_log": ((heads,), "bias"),
                p + "D": ((heads,), "beta"),
                p + "gate_norm_gamma": ((inner,), "gamma"),
                p + "out_proj_weight": ((d, inner), "matrix")})
        elif kind == "E":
            out.update({
                p + "router_weight": ((router_width(cfg), d), "matrix"),
                p + "router_bias": ((router_width(cfg),), "bias"),
                p + "fc1_latent_weight": ((latent, d), "matrix"),
                p + "fc2_latent_weight": ((d, latent), "matrix"),
                p + "experts_up_weight": ((experts, width, latent),
                                          "matrix"),
                p + "experts_down_weight": ((experts, latent, width),
                                            "matrix"),
                p + "shared_up_weight": ((shared, d), "matrix"),
                p + "shared_down_weight": ((d, shared), "matrix")})
        else:
            out.update({p + "q_weight": ((q, d), "beta"),
                        p + "k_weight": ((kv, d), "matrix"),
                        p + "v_weight": ((kv, d), "matrix"),
                        p + "o_weight": ((d, q), "matrix")})
    out.update({"norm_gamma": ((d,), "gamma"),
                "head_weight": ((cfg["vocab_size"], d), "matrix")})
    return out


def _rms(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma


def _eps(cfg):
    return cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5))


def mamba(h, lp, cfg, mode, fault=None):
    """The Mamba-2 mixer of one sequence ``h`` (L, d), a token at a
    time."""
    def wide(name):
        return _wide(lp[name], mode)

    length = h.shape[0]
    heads, p, groups, n, inner, conv = _dims(cfg)
    taps = cfg["conv_kernel"]
    zx = einsum("ld,od->lo", h, wide("in_proj"), mode)
    z, xbc, dt = zx[:, :inner], zx[:, inner:inner + conv], \
        zx[:, inner + conv:]
    if fault != "conv_skipped":
        w = wide("conv")
        if fault == "state_not_carried":
            xbc = xbc * w[:, taps - 1]
        else:
            padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
            xbc = sum(padded[i:i + length] * w[:, i]
                      for i in range(taps))
        xbc = xbc + wide("conv_bias")
    xbc = carried(jax.nn.silu(xbc), mode)
    x = xbc[:, :inner].reshape(length, heads, p)
    b = jnp.repeat(xbc[:, inner:inner + groups * n].reshape(
        length, groups, n), heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + groups * n:].reshape(
        length, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + wide("dt_bias"))
    a = -jnp.exp(wide("A_log"))

    def one(state, row):
        x_t, dt_t, b_t, c_t = row
        if fault == "state_not_carried":
            state = jnp.zeros_like(state)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], -1)

    _, y = jax.lax.scan(one, jnp.zeros((heads, p, n), jnp.float32),
                        (x, dt, b, c))
    if fault != "no_d_skip":
        y = y + wide("D")[:, None] * x
    g = carried(y, mode).reshape(length, inner) * jax.nn.silu(z)
    g = g.reshape(length, 1 if fault == "norm_not_grouped" else groups,
                  -1)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + _eps(cfg))
    g = carried(g.reshape(length, inner) * wide("gate_norm"), mode)
    return einsum("li,di->ld", g, wide("out_proj"), mode)


def _relu2(v, fault):
    v = jax.nn.relu(v)
    return v if fault == "relu_not_squared" else v * v


def route(h, router, bias, cfg, mode):
    """(L, E) gates over the router's every output, zero off each
    token's top k: ``s = sigmoid(h W_r)``, the top k of ``s + bias``,
    the chosen ``s`` over their sum times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(einsum("ld,ed->le", h, router, mode))
    _, choice = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, choice, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, choice].set(picked)


def moe(h, lp, cfg, mode, fault=None):
    """The held experts' part and the shared expert, rows ``h`` (L,
    d)."""
    def wide(name):
        return _wide(lp[name], mode)

    held = cfg["n_routed_experts"]
    gates = route(h, wide("router"), wide("router_bias"), cfg,
                  mode)[:, :held]
    u = carried(einsum("ld,cd->lc", h, wide("fc1_latent"), mode), mode)

    def block(y, args):
        up, down, mine = args               # (B, W, C), (B, C, W), (B, L)
        mid = carried(_relu2(einsum("lc,bwc->blw", u, _wide(up, mode),
                                    mode), fault), mode)
        out = einsum("blw,bcw->blc", mid * mine[..., None],
                     _wide(down, mode), mode)
        return y + jnp.sum(out, 0), None

    k = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else 1

    def blocks(v):
        return v.reshape((held // k, k) + v.shape[1:])
    y, _ = jax.lax.scan(block, jnp.zeros_like(u), (
        blocks(lp["experts_up"]), blocks(lp["experts_down"]),
        blocks(gates.T)))
    y = einsum("lc,dc->ld", carried(y, mode), wide("fc2_latent"), mode)
    mid = carried(_relu2(einsum("ld,sd->ls", h, wide("shared_up"), mode),
                         fault), mode)
    return y + einsum("ls,ds->ld", mid, wide("shared_down"), mode)


def _rotate(x, theta):
    """x (L, heads, dim) at positions 0..L-1, the halves rotated."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(h, lp, cfg, mode, fault=None):
    """Causal grouped-query attention of one sequence ``h`` (L, d)."""
    def wide(name):
        return _wide(lp[name], mode)

    length = h.shape[0]
    heads, kv, dim = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    q = einsum("ld,od->lo", h, wide("q"), mode).reshape(length, heads,
                                                         dim)
    k = einsum("ld,od->lo", h, wide("k"), mode).reshape(length, kv, dim)
    v = einsum("ld,od->lo", h, wide("v"), mode).reshape(length, kv, dim)
    if fault == "rope_in_attention":
        q, k = (carried(_rotate(a, cfg["rope_theta"]), mode)
                for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))

    def block(args):
        qb, at = args
        scores = einsum("qhd,khd->hqk", qb, k, mode) * dim ** -0.5
        seen = jnp.arange(length)[None, :] <= at[:, None]
        probs = carried(jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), axis=-1), mode)
        return einsum("hqk,khd->qhd", probs, v, mode)

    at = jnp.arange(length)
    if length <= QUERY_BLOCK:
        out = block((q, at))
    else:
        pad = -length % QUERY_BLOCK
        out = jax.lax.map(block, tuple(
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            .reshape((-1, QUERY_BLOCK) + a.shape[1:])
            for a in (q, at)))
        out = out.reshape((-1,) + out.shape[2:])[:length]
    return einsum("lo,do->ld", carried(out.reshape(length, -1), mode),
                  wide("o"), mode)


MIXERS = {"M": mamba, "E": moe, "*": attention}


def layer_params(params, i):
    """Layer ``i``'s leaves under their names less the layer's prefix
    and a ``_weight`` or ``_gamma`` at the end."""
    p = f"layer{i}_"
    return {n[len(p):].removesuffix("_weight").removesuffix("_gamma"):
            v for n, v in params.items() if n.startswith(p)}


def hidden(params, tokens, cfg, mode="f32", fault=None):
    """Final-norm hidden states (B, L, d) of tokens (B, L)."""
    x = _wide(params["embed_weight"][tokens], mode)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        lp = layer_params(params, i)

        def one(row, lp=lp, mixer=MIXERS[kind]):
            h = carried(_rms(row, _wide(lp["norm"], mode), _eps(cfg)),
                        mode)
            return carried(row + mixer(h, lp, cfg, mode, fault), mode)
        x = jax.lax.map(one, x)
    return carried(_rms(x, _wide(params["norm_gamma"], mode), _eps(cfg)),
                   mode)


def logits(params, tokens, cfg, mode="f32", fault=None):
    """Logits (B, L, V), float32."""
    h = hidden(params, tokens, cfg, mode, fault)
    head = _wide(params["head_weight"], mode)
    return jax.lax.map(
        lambda row: einsum("ld,vd->lv", row, head, mode), h)
