"""Mixture-of-Experts FFN with top-2 routing (GShard/Switch style).

A capability the reference predates, designed TPU-first the way the
SURVEY (§5 long-context/parallelism) prescribes for new scale-out
features: routing is *dense dispatch* — fixed-capacity one-hot
dispatch/combine tensors contracted with einsums — so every shape is
static under jit, the expert matmuls are batched over the expert
dimension (one big MXU contraction, not E small ones), and sharding
the expert dimension over the mesh's 'ep' axis makes GSPMD insert the
token all-to-alls automatically (the expert-parallel pattern of
GShard; see parallel/sharding.py's ep rules).

Registered as the differentiable 2-output op ``_moe_ffn`` so the
eager tape, hybridized blocks, and ShardedTrainStep all route/
backprop through identical code: outputs are (tokens_out, aux_loss)
where aux_loss is the load-balance penalty (E * sum_e f_e * P_e;
f_e = top-1 dispatch fraction, P_e = mean router probability) the
training loss should add with a small weight (~1e-2).

Tokens over capacity (C = ceil(cf * 2 * T / E) per expert) are
DROPPED — their expert contribution is zero and the residual stream
carries them, the standard GShard overflow semantic that keeps shapes
static.

Beside it, for serving: :func:`routed_ffn_fn`, a DROPLESS routed layer
of gated experts (top k of many, sigmoid or softmax scores, a
selection bias, normalised and scaled weights, a shared expert).  No
token is dropped, so a token's result does not turn on its
batch-mates.  Dispatch is sorted: the (token, expert) pairs are
put in order of their expert, so the work is linear in tokens, an
expert nobody chose costs nothing, and the products run in the
tokens' own dtype.  Lowered for a TPU, with widths that are whole
lanes, the sorted rows go through a grouped matrix product (Pallas:
megablox ``gmm``), which reads an expert's matrix once for the rows
that chose it; anywhere else they go expert by expert in tiles of a
few rows, one tile a pass through one expert's three matrices.
``held=(first, count)`` computes the part of the result that those
experts give, which is what one rank of an expert-parallel layer
computes.
"""
import math

import jax
import jax.numpy as jnp

from .registry import defop

__all__ = ["moe_ffn_fn", "top2_gating", "routed_ffn_fn", "route_top_k",
           "gated_ffn", "relu2_ffn"]


def top2_gating(logits, capacity, renorm=True):
    """GShard top-2 gating with fixed expert capacity.

    logits : (T, E) router scores (any float dtype; gating runs fp32)
    returns (combine (T, E, C) f32, dispatch (T, E, C) f32 0/1,
             aux_loss scalar f32)
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    idx1 = jnp.argmax(probs, axis=-1)                   # (T,)
    mask1 = jax.nn.one_hot(idx1, e, dtype=jnp.float32)  # (T, E)
    p1 = jnp.sum(probs * mask1, axis=-1)
    probs_wo1 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs_wo1, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=jnp.float32)
    p2 = jnp.sum(probs * mask2, axis=-1)

    if renorm:
        denom = p1 + p2 + 1e-9
        g1, g2 = p1 / denom, p2 / denom
    else:
        g1, g2 = p1, p2

    # position of each token in its expert's buffer; second choices
    # queue behind ALL first choices (GShard's ordering)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1       # (T, E)
    count1 = jnp.sum(mask1, axis=0, keepdims=True)          # (1, E)
    pos2 = (jnp.cumsum(mask2, axis=0) - 1.0 + count1) * mask2

    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    oh1 = jax.nn.one_hot(pos1.astype(jnp.int32), capacity,
                         dtype=jnp.float32) * keep1[..., None]
    oh2 = jax.nn.one_hot(pos2.astype(jnp.int32), capacity,
                         dtype=jnp.float32) * keep2[..., None]
    dispatch = oh1 + oh2                                    # (T, E, C)
    combine = g1[:, None, None] * oh1 + g2[:, None, None] * oh2

    # load-balance aux: E * sum_e (top1 dispatch fraction * mean prob)
    f = jnp.mean(mask1, axis=0)
    p_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p_mean)
    return combine, dispatch, aux


def moe_ffn_fn(data, router_weight, up_weight, up_bias, down_weight,
               down_bias, capacity_factor=1.25, renorm=True):
    """Pure-jnp MoE FFN on flattened tokens.

    data          : (T, D)
    router_weight : (E, D)   — FullyConnected (out, in) convention
    up_weight     : (E, H, D);  up_bias (E, H)
    down_weight   : (E, D, H); down_bias (E, D)
    returns (out (T, D) in data.dtype, aux_loss scalar f32)
    """
    t, d = data.shape
    e = router_weight.shape[0]
    capacity = max(1, math.ceil(float(capacity_factor) * 2 * t / e))

    logits = jnp.dot(data.astype(jnp.float32),
                     router_weight.astype(jnp.float32).T)
    combine, dispatch, aux = top2_gating(logits, capacity,
                                         renorm=renorm)

    xf = data.astype(jnp.float32)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xf)
    hmid = jax.nn.relu(
        jnp.einsum("ecd,ehd->ech", expert_in,
                   up_weight.astype(jnp.float32))
        + up_bias.astype(jnp.float32)[:, None, :])
    expert_out = jnp.einsum("ech,edh->ecd", hmid,
                            down_weight.astype(jnp.float32)) \
        + down_bias.astype(jnp.float32)[:, None, :]
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.astype(data.dtype), aux


@defop("_moe_ffn", num_outputs=2,
       arg_names=["data", "router_weight", "up_weight", "up_bias",
                  "down_weight", "down_bias"])
def _moe_ffn(data, router_weight, up_weight, up_bias, down_weight,
             down_bias, capacity_factor=1.25, renorm=True):
    """Registry surface for :func:`moe_ffn_fn` (docstring above)."""
    return moe_ffn_fn(data, router_weight, up_weight, up_bias,
                      down_weight, down_bias,
                      capacity_factor=float(capacity_factor),
                      renorm=bool(renorm))


# --------------------------------------------------------------------------
# dropless top-k-of-many routed layer of gated experts (serving)
# --------------------------------------------------------------------------


def route_top_k(x, router_weight, top_k, scoring="sigmoid",
                select_bias=None, normalize=True, scale=1.0):
    """Which experts each token goes to, and with what weight.

    float32 throughout, whatever ``x`` holds: ``s = scoring(x W^T)``
    over every expert; the ``top_k`` experts are the largest of
    ``s + select_bias`` (the bias takes part in the choice alone);
    the weights are the chosen ``s``, over their sum if ``normalize``,
    times ``scale``.  Returns (choice (T, k) int32, weight (T, k)
    float32)."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring={scoring!r}: sigmoid or softmax")
    logits = jnp.dot(x.astype(jnp.float32),
                     router_weight.astype(jnp.float32).T)
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    chosen_by = s if select_bias is None \
        else s + select_bias.astype(jnp.float32)
    _, choice = jax.lax.top_k(chosen_by, top_k)
    weight = jnp.take_along_axis(s, choice, axis=-1)
    if normalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + 1e-20)
    return choice.astype(jnp.int32), weight * scale


def gated_ffn(x, gate, up, down):
    """``(silu(x gate^T) * (x up^T)) down^T``: products in x's dtype,
    accumulated in float32; float32 result."""
    hidden = jax.nn.silu(jnp.dot(
        x, gate.T, preferred_element_type=jnp.float32)) \
        * jnp.dot(x, up.T, preferred_element_type=jnp.float32)
    return jnp.dot(hidden.astype(x.dtype), down.T,
                   preferred_element_type=jnp.float32)


def relu2_ffn(x, up, down):
    """``relu(x up^T)^2 down^T``, an expert with no gate: products in
    x's dtype, accumulated in float32; float32 result."""
    hidden = jnp.square(jax.nn.relu(jnp.dot(
        x, up.T, preferred_element_type=jnp.float32)))
    return jnp.dot(hidden.astype(x.dtype), down.T,
                   preferred_element_type=jnp.float32)


# an expert's activation -> (the expert on rows, given its gate or
# None, up and down; the activation of its grouped product's hidden
# rows, given the gate's and the up's products)
ACTIVATIONS = {
    "silu": (gated_ffn, lambda gate, up: jax.nn.silu(gate) * up),
    "relu2": (lambda x, gate, up, down: relu2_ffn(x, up, down),
              lambda gate, up: jnp.square(jax.nn.relu(up))),
}


def _tile_rows(tokens, top_k, n_experts):
    """Rows of one tile of the sorted dispatch: the pairs an expert
    expects from ``tokens`` tokens, rounded up to a power of two,
    between 16 (a packed bfloat16 tile) and 256."""
    expected = -(-tokens * top_k // n_experts)
    return min(256, max(16, 1 << max(0, expected - 1).bit_length()))


GROUP_ROWS = 128      # rows of one tile of the grouped product
# bytes of one expert's weight tile in the grouped product: held twice
# (the next tile is fetched while one is used) beside the row tiles,
# inside the 16 MiB of scoped VMEM a kernel may take
WEIGHT_TILE_BYTES = 4 << 20


def _experts_tiled(x, order, pairs, flat_weight, gate_weight,
                   up_weight, down_weight, top_k, base, rows_tile,
                   activation="silu"):
    """The sorted pairs expert by expert, in tiles of ``rows_tile``
    rows: (sum (T, D) float32, rows multiplied)."""
    t = x.shape[0]
    d = down_weight.shape[1]
    ffn = ACTIVATIONS[activation][0]
    first_row = jnp.cumsum(pairs) - pairs
    tiles = -(-pairs // rows_tile)
    last_tile = jnp.cumsum(tiles)
    n_tiles = last_tile[-1]
    lane = jnp.arange(rows_tile, dtype=jnp.int32)

    def one_tile(i, out):
        e = jnp.sum(last_tile <= i)          # the tile's expert
        within = (i - (last_tile[e] - tiles[e])) * rows_tile + lane
        real = within < pairs[e]
        pair = order[jnp.minimum(first_row[e] + within,
                                 t * top_k - 1)]
        token = pair // top_k
        g, u, dn = (None if w is None else
                    jax.lax.dynamic_index_in_dim(w, base + e, 0, False)
                    for w in (gate_weight, up_weight, down_weight))
        y = ffn(x[token], g, u, dn)
        y = y * jnp.where(real, flat_weight[pair], 0.0)[:, None]
        return out.at[token].add(y)

    out = jax.lax.fori_loop(0, n_tiles, one_tile,
                            jnp.zeros((t, d), jnp.float32))
    return out, n_tiles * rows_tile


def _experts_grouped(x, order, pairs, flat_weight, gate_weight,
                     up_weight, down_weight, top_k, base,
                     interpret=False, activation="silu"):
    """The sorted pairs through a grouped matrix product: the rows of
    one expert lie together, and the kernel goes through them in tiles
    of ``GROUP_ROWS``, a tile that holds rows of several experts once
    for each of them: (sum (T, D) float32, rows multiplied)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    t = x.shape[0]
    d = down_weight.shape[1]
    count = pairs.shape[0]
    gate_weight, up_weight, down_weight = (
        w if w is None or w.shape[0] == count
        else jax.lax.slice_in_dim(w, base, base + count)
        for w in (gate_weight, up_weight, down_weight))

    def product(rows, weight):
        # a weight's whole rows, or halves of its output columns where
        # they would not fit (3584 x 1024 of bfloat16 is 7.3 MB)
        k, cols = rows.shape[1], weight.shape[1]
        while cols * k * weight.dtype.itemsize > WEIGHT_TILE_BYTES \
                and cols % 256 == 0:
            cols //= 2
        return gmm(rows, weight, pairs, jnp.float32,
                   (GROUP_ROWS, k, cols), transpose_rhs=True,
                   interpret=interpret)

    rows = x[order // top_k]
    hidden = ACTIVATIONS[activation][1](
        None if gate_weight is None else product(rows, gate_weight),
        product(rows, up_weight))
    y = product(hidden.astype(x.dtype), down_weight)
    # rows behind the last held pair were never written
    live = jnp.arange(t * top_k) < jnp.sum(pairs)
    y = jnp.where(live[:, None], y, 0.0) \
        * jnp.where(live, flat_weight[order], 0.0)[:, None]
    back = jnp.zeros(t * top_k, jnp.int32).at[order].set(
        jnp.arange(t * top_k, dtype=jnp.int32))
    out = jnp.sum(y[back].reshape(t, top_k, d), axis=1)
    end = jnp.cumsum(pairs)
    visits = jnp.sum(jnp.where(
        pairs > 0, -(-end // GROUP_ROWS) - (end - pairs) // GROUP_ROWS,
        0))
    return out, visits * GROUP_ROWS


def routed_ffn_fn(x, router_weight, gate_weight, up_weight,
                  down_weight, top_k, scoring="sigmoid",
                  select_bias=None, normalize=True, scale=1.0,
                  shared=None, held=None, valid=None,
                  activation="silu", latent=None):
    """A routed layer of experts, dropless.

    x             : (T, D) tokens
    router_weight : (E, D), select_bias (E,) or None: the whole
                    router (:func:`route_top_k` has the rest)
    gate_weight, up_weight : (E or count, H, D); down_weight
                    (E or count, D, H): stacked experts, (out, in)
                    as FullyConnected has them; ``gate_weight`` None
                    where the activation has no gate
    activation    : ``silu`` (gated: ``silu(x gate^T) * x up^T``) or
                    ``relu2`` (``relu(x up^T)^2``, no gate)
    latent        : None, or (down (L, D), up (D, L)): the experts
                    take ``x down^T`` (L wide, their stacked weights
                    L wide too) while the router reads ``x``; their sum
                    goes back through ``up`` before the shared expert
                    is added
    shared        : None, or (gate (S, D) or None, up (S, D), down (D,
                    S)): an expert of the same activation every token
                    goes through, on ``x``, added here
    held          : None (all), or (first, count): route over all
                    ``E``, compute what experts ``first .. first +
                    count - 1`` give.  The stacked weights then hold
                    all ``E`` experts or just those ``count``
    valid         : (T,) bool or None: rows that are tokens (padding
                    rows are routed nowhere)
    returns (y (T, D) in x.dtype: the sum over a token's chosen
             experts that are held of ``weight * E_e(x)``, plus the
             shared expert where given;
             stats: int32 scalars ``routed_rows`` (rows the expert
             products multiplied), ``padded_rows`` (how many of them
             were a tile's padding), ``experts_touched`` (held
             experts that got a token))

    The sum of ``y`` over shares that partition the experts, with
    ``shared`` given to one of them, is the whole layer."""
    t = x.shape[0]
    n_experts = router_weight.shape[0]
    first, count = (0, n_experts) if held is None else held
    if not 0 <= first <= first + count <= n_experts:
        raise ValueError(f"held={held!r} is not among {n_experts} "
                         "experts")
    if (gate_weight is None) != (activation == "relu2"):
        raise ValueError(f"activation={activation!r} with gate "
                         f"{'none' if gate_weight is None else 'given'}")
    stacked = up_weight.shape[0]
    if stacked == n_experts:
        base = first          # the stack holds every expert
    elif stacked == count:
        base = 0              # the stack holds the held ones
    else:
        raise ValueError(
            f"{stacked} stacked experts: neither all "
            f"{n_experts} nor the {count} held")
    rows_tile = _tile_rows(t, top_k, n_experts)
    d = up_weight.shape[2]    # the experts' rows: x's, or the latent's
    # the grouped product wants whole lanes and whole tiles of rows
    grouped = d % 128 == 0 and up_weight.shape[1] % 128 == 0 \
        and (t * top_k) % GROUP_ROWS == 0 and count > 0

    with jax.named_scope("moe_route"):
        choice, weight = route_top_k(x, router_weight, top_k, scoring,
                                     select_bias, normalize, scale)
        local = choice - first
        here = (local >= 0) & (local < count)
        if valid is not None:
            here &= valid[:, None]
        # pairs sorted by held expert; the others sort behind them
        key = jnp.where(here, local, count).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pairs = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                        dtype=jnp.int32)
        flat_weight = weight.reshape(-1)

    rows = x
    if latent is not None:
        with jax.named_scope("moe_latent"):
            rows = jnp.dot(x, latent[0].T,
                           preferred_element_type=jnp.float32) \
                .astype(x.dtype)
    with jax.named_scope("moe_experts"):
        # no operand for a gate there is not: None is no array of a
        # platform's branch
        gate = () if gate_weight is None else (gate_weight,)
        args = (rows, order, pairs, flat_weight) + gate \
            + (up_weight, down_weight)

        def taken(a):
            return a[:4] + (None,) + a[4:] if gate_weight is None else a
        if grouped:
            out, routed_rows = jax.lax.platform_dependent(
                *args,
                tpu=lambda *a: _experts_grouped(
                    *taken(a), top_k, base, activation=activation),
                default=lambda *a: _experts_tiled(
                    *taken(a), top_k, base, rows_tile, activation))
        else:
            out, routed_rows = _experts_tiled(*taken(args), top_k, base,
                                              rows_tile, activation)
    if latent is not None:
        with jax.named_scope("moe_latent"):
            out = jnp.dot(out.astype(x.dtype), latent[1].T,
                          preferred_element_type=jnp.float32)
    if shared is not None:
        with jax.named_scope("moe_shared"):
            out = out + ACTIVATIONS[activation][0](x, *shared)
    stats = {"routed_rows": routed_rows,
             "padded_rows": routed_rows - jnp.sum(pairs),
             "experts_touched": jnp.sum(pairs > 0, dtype=jnp.int32)}
    return out.astype(x.dtype), stats
