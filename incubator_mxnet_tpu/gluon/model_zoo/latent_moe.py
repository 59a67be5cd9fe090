"""Decoder LM with multi-head latent attention and routed experts: the
DeepSeek-V3 layer, which JoyAI-LLM-Flash, Kimi-K2 and others run with
their own numbers.  Built from the keys of such a model's own
``config.json``.

Each layer: ``x = x + MLA(RMS(x)); x = x + FFN(RMS(x))``; bias-free
projections, SiLU-gated FFNs, RoPE on ``qk_rope_head_dim`` of each
head's query/key dims (interleaved pairs), a head that is not tied.
The first ``first_k_dense_replace`` layers carry a dense gated FFN,
the rest a routed layer with a shared expert beside it
(``ops.moe.routed_ffn_fn``: sigmoid or softmax scores, the top k
chosen with a selection bias, dropless).  What a token leaves in the
cache is one row a layer: the normalised latent ``c_kv`` and the
rotated ``k_pe`` that all heads share, ``kv_lora_rank +
qk_rope_head_dim`` values where per-head keys and values would be
``heads * (qk_head_dim + v_head_dim)``.

Where the config sets ``hc_mult`` n > 1 the residual is a
manifold-constrained hyper-connection (Xing4.0's layer): a token
carries n streams, entered as n copies of its embedding and summed at
the exit, and each sublayer F reads and writes them through mixing
weights that the token's own state sets:
``h_pre = sigmoid(a_pre * u Phi_pre + b_pre)``, ``h_post = 2
sigmoid(a_post * u Phi_post + b_post)`` and ``H_res`` the matrix
``exp(clip(a_res * u Phi_res + b_res))`` after ``hc_sinkhorn_iters``
rounds of Sinkhorn-Knopp (rows, then columns, each over its sum +
``hc_eps``), u the streams flattened and RMS-normalised without a
gain; then ``X[i] <- sum_j H_res[i, j] X[j] + h_post[i] F(sum_k
h_pre[k] X[k])`` (``_hyper``).  ``rope_scaling`` of type ``yarn`` sets
the rotary frequencies and the softmax scale as DeepSeek-V3's code
does (``_yarn``).

The layer's mathematics is written once, :meth:`LatentMoELM.layer`,
as a function of (weights, rows, positions, cache).  The eager
``forward``, the paged prefill program and the paged decode program of
``serving.ServingEngine`` all call it; what differs is the cache they
hand it (none; the engine's latent pool through one table row; the
pool through a table row a slot) and with it the attention path:
expanded per head, blocked over queries and keys, or absorbed into
the latent for one query a slot.  Inference only: there is no
dense-cache ``generate()`` and ``forward`` is not on the autograd
tape.  The multi-token-prediction module some of these checkpoints
carry (``num_nextn_predict_layers``) is no part of the forward pass
that yields a token and is not built.
"""
import functools
import math
import operator

import numpy as np

from ... import ndarray as nd
from ..block import Block

__all__ = ["LatentMoELM"]

QUERY_BLOCK = 256     # rows of queries whose scores are held at once
KEY_BLOCK = 512       # context rows a pass of the expanded path takes
LANE = 128            # a pool's row is stored in whole lanes
SINKHORN_UNROLL = 5   # Sinkhorn rounds in one pass of their loop


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp
    wide = x.astype(jnp.float32)
    y = wide * jax.lax.rsqrt(jnp.mean(wide * wide, -1, keepdims=True)
                             + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    """``x @ w.T`` accumulated in float32, in x's dtype."""
    import jax.numpy as jnp
    return jnp.dot(x, w.T, preferred_element_type=jnp.float32) \
        .astype(x.dtype)


def _rope(x, positions, inv_freq):
    """Rotate the last axis of x (T, ..., 2h) at ``positions`` (T,);
    pairs are (2i, 2i + 1), interleaved."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _yarn(scaling, theta, d_rope, qk_dim):
    """DeepSeek-V3's ``yarn`` rotary embedding: (inv_freq, softmax
    scale).  Frequencies below the correction range keep theta's,
    those above it are divided by ``factor``, a linear ramp between;
    the softmax scale gains ``mscale(factor, mscale_all_dim) ** 2``.
    cos and sin would be scaled by ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``: a ratio other than 1 is not
    built."""
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def corr(rotations):
        return d_rope * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(corr(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(scaling.get("beta_slow", 1))), d_rope - 1)
    every = scaling.get("mscale_all_dim", 0)
    if mscale(scaling.get("mscale", 1)) != mscale(every):
        raise ValueError(f"rope_scaling={scaling!r}: cos and sin scaled "
                         "by other than 1 are not built")
    plain = theta ** (-2.0 * np.arange(d_rope // 2) / d_rope)
    ramp = np.clip((np.arange(d_rope // 2) - low) / ((high - low) or 1e-3),
                   0.0, 1.0)
    inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
    scale = qk_dim ** -0.5 * (mscale(every) ** 2 if every else 1.0)
    return inv_freq.astype(np.float32), scale


def _total(values):
    return functools.reduce(operator.add, values)


# ------------------------------------------------------------- caches
# What a cache hands the layer: ``context(li, rows)`` takes the rows
# this call adds to layer ``li`` and gives back what the queries may
# see; ``absorbed`` picks the attention path.  For the expanded path
# that is every such row, (C, r): row c of a context IS absolute
# position c.  For the absorbed path (one query a sequence) it is the
# pool as it came in, the tables that say each sequence's blocks, and
# the rows this call adds, which lie at the sequences' positions.  A
# pool's rows may be wider than the rows handed in (whole lanes): the
# rest is zeros.


class _NoCache:
    """One sequence that attends to itself (the eager forward)."""
    absorbed = False

    def context(self, li, rows):
        return rows


class _PagedCache:
    """The engine's latent pool, through block tables.  ``blk`` /
    ``off`` say where each row of this call is written; ``tables`` is
    one row of block ids (a prefill) or one a slot (a decode step,
    ``absorbed``).  ``pools`` ends as the updated pools.  A decode step
    reads the pool as it came in, so its write has no reader in the
    program and stays in place in the donated pool."""

    def __init__(self, pools, tables, blk, off, absorbed):
        self.pools = list(pools)
        self.tables, self.blk, self.off = tables, blk, off
        self.absorbed = absorbed

    def context(self, li, rows):
        import jax.numpy as jnp
        pool = self.pools[li]
        rows = jnp.pad(rows.astype(pool.dtype), (
            (0, 0), (0, pool.shape[-1] - rows.shape[-1])))
        self.pools[li] = pool.at[self.blk, self.off].set(rows)
        if self.absorbed:
            return pool, self.tables, rows
        got = self.pools[li][self.tables]  # (MB, bs, r)
        return got.reshape(-1, got.shape[-1])


class LatentMoELM(Block):
    """Token-in, logits-out decoder LM of the layer above.

    ``config`` holds the model's own keys (``_KEYS`` below, and
    ``n_shared_experts``, ``first_k_dense_replace``,
    ``routed_scaling_factor``, ``scoring_func``, ``norm_topk_prob``,
    ``rms_norm_eps`` where they differ from 0, 0, 1.0, ``sigmoid``,
    true, 1e-6; ``hc_mult`` with ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` and ``_max`` for hyper-connections;
    ``rope_scaling`` of type ``yarn``).  ``held=(first, count)``
    makes this one rank of an expert-parallel deployment: the router
    keeps all
    ``n_routed_experts`` outputs, the layer holds and computes experts
    ``first .. first + count - 1``, and ``shared_here`` says whether
    the shared expert is added on this rank.
    """

    _KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "intermediate_size",
             "moe_intermediate_size", "n_routed_experts",
             "num_experts_per_tok", "num_hidden_layers", "vocab_size",
             "max_position_embeddings", "rope_theta")

    def __init__(self, config, held=None, shared_here=True, **kwargs):
        super().__init__(**kwargs)
        lacking = [k for k in self._KEYS if k not in config]
        if lacking:
            raise KeyError(f"config lacks {lacking}")
        c = self._c = dict(config)
        for key, only in (("hidden_act", "silu"), ("n_group", 1),
                          ("topk_group", 1), ("moe_layer_freq", 1),
                          ("rope_interleave", True),
                          ("attention_bias", False)):
            if c.get(key, only) != only:
                raise ValueError(f"{key}={c[key]!r} is not built: "
                                 f"{only!r} only")
        scaling = c.get("rope_scaling")
        if scaling is not None and scaling.get(
                "type", scaling.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling={scaling!r} is not built: "
                             "None or type yarn only")
        self._d = c["hidden_size"]
        self._max_len = c["max_position_embeddings"]
        self.n_layers = c["num_hidden_layers"]
        self.n_heads = c["num_attention_heads"]
        self.vocab = c["vocab_size"]
        self.n_dense = min(c.get("first_k_dense_replace", 0),
                           self.n_layers)
        self.n_experts = c["n_routed_experts"]
        self.held = (0, self.n_experts) if held is None \
            else (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= sum(self.held) <= self.n_experts:
            raise ValueError(f"held={held!r} is not among "
                             f"{self.n_experts} experts")
        self.shared_here = bool(shared_here)
        self.top_k = c["num_experts_per_tok"]
        self.eps = c.get("rms_norm_eps", 1e-6)
        self.rank = c["kv_lora_rank"]
        self.d_nope, self.d_rope = (c["qk_nope_head_dim"],
                                    c["qk_rope_head_dim"])
        self.d_v = c["v_head_dim"]
        if scaling is None:
            self._inv_freq = (c["rope_theta"] ** (
                -2.0 * np.arange(self.d_rope // 2) / self.d_rope)) \
                .astype(np.float32)
            self.softmax_scale = (self.d_nope + self.d_rope) ** -0.5
        else:
            self._inv_freq, self.softmax_scale = _yarn(
                scaling, c["rope_theta"], self.d_rope,
                self.d_nope + self.d_rope)
        # the residual's streams a token carries (1: a plain residual)
        self.residual_streams = n = int(c.get("hc_mult", 1))
        if n > 1:
            self.hc_iters = int(c["hc_sinkhorn_iters"])
            self.hc_eps = float(c["hc_eps"])
            self.hc_clip = (float(c["mhc_h_res_clamp_min"]),
                            float(c["mhc_h_res_clamp_max"]))
        self._forward_fns = {}
        d, h = self._d, self.n_heads
        q_rank = c["q_lora_rank"]
        shared = c["moe_intermediate_size"] \
            * c.get("n_shared_experts", 0)
        with self.name_scope():
            get = self.params.get
            self.embed_weight = get("embed_weight",
                                    shape=(self.vocab, d))
            self.layers = []
            for i in range(self.n_layers):
                p = f"layer{i}_"
                lw = {
                    "attn_norm": get(p + "attn_norm_gamma",
                                     shape=(d,), init="ones"),
                    "q_a": get(p + "q_a_weight", shape=(q_rank, d)),
                    "q_norm": get(p + "q_norm_gamma",
                                  shape=(q_rank,), init="ones"),
                    "q_b": get(p + "q_b_weight", shape=(
                        h * (self.d_nope + self.d_rope), q_rank)),
                    "kv_a": get(p + "kv_a_weight", shape=(
                        self.rank + self.d_rope, d)),
                    "kv_norm": get(p + "kv_norm_gamma",
                                   shape=(self.rank,), init="ones"),
                    "kv_b": get(p + "kv_b_weight", shape=(
                        h * (self.d_nope + self.d_v), self.rank)),
                    "o": get(p + "o_weight",
                             shape=(d, h * self.d_v)),
                    "ffn_norm": get(p + "ffn_norm_gamma",
                                    shape=(d,), init="ones")}
                if i < self.n_dense:
                    width = c["intermediate_size"]
                    lw.update(
                        gate=get(p + "gate_weight", shape=(width, d)),
                        up=get(p + "up_weight", shape=(width, d)),
                        down=get(p + "down_weight",
                                 shape=(d, width)))
                else:
                    width, e = c["moe_intermediate_size"], self.held[1]
                    lw.update(
                        router=get(p + "router_weight",
                                   shape=(self.n_experts, d)),
                        router_bias=get(p + "router_bias",
                                        init="zeros",
                                        shape=(self.n_experts,)),
                        experts_gate=get(p + "experts_gate_weight",
                                         shape=(e, width, d)),
                        experts_up=get(p + "experts_up_weight",
                                       shape=(e, width, d)),
                        experts_down=get(p + "experts_down_weight",
                                         shape=(e, d, width)))
                    if shared:
                        lw.update(
                            shared_gate=get(p + "shared_gate_weight",
                                            shape=(shared, d)),
                            shared_up=get(p + "shared_up_weight",
                                          shape=(shared, d)),
                            shared_down=get(p + "shared_down_weight",
                                            shape=(d, shared)))
                for s in ("attn", "ffn") if n > 1 else ():
                    # Phi_pre, Phi_post and Phi_res as the rows of one
                    # matrix (n, n and n * n of them, H_res row-major),
                    # their biases alike, the gains a_pre, a_post, a_res
                    q = f"{p}hc_{s}_"
                    lw[f"hc_{s}"] = get(q + "weight",
                                        shape=(n * (n + 2), n * d))
                    lw[f"hc_{s}_bias"] = get(q + "bias", init="zeros",
                                             shape=(n * (n + 2),))
                    lw[f"hc_{s}_alpha"] = get(q + "alpha", init="ones",
                                              shape=(3,))
                self.layers.append(lw)
            self.norm = get("norm_gamma", shape=(d,), init="ones")
            self.head_weight = get("head_weight",
                                   shape=(self.vocab, d))

    # ------------------------------------------------ the mathematics
    def _expanded(self, q, positions, ctx, kv_b, last):
        """Per-head keys and values of every context row, then the
        queries in blocks of ``QUERY_BLOCK`` and for each of them the
        context in passes of ``KEY_BLOCK`` rows, as far as that
        block's last real query sees (``last``: the last real
        position of the call), with a running maximum and sum: no
        (heads, T, C) scores are ever held, and rows of the table
        past the sequence cost nothing."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        f32 = jnp.float32
        t, heads = q.shape[0], self.n_heads
        rank, d_nope, d_rope = self.rank, self.d_nope, self.d_rope
        pad_c = -ctx.shape[0] % KEY_BLOCK
        ctx = jnp.pad(ctx, ((0, pad_c), (0, 0)))
        n_ctx = ctx.shape[0]
        wide = jnp.einsum("kc,hnc->hkn", ctx[:, :rank], kv_b,
                          preferred_element_type=f32).astype(q.dtype)
        keys = jnp.concatenate(
            [wide[..., :d_nope], jnp.broadcast_to(
                ctx[None, :, rank:rank + d_rope],
                (heads, n_ctx, d_rope))], axis=-1)
        values = wide[..., d_nope:]
        scale = self.softmax_scale

        def block(args):
            qb, pos = args                  # (Q, heads, d), (Q,)
            n_q = qb.shape[0]
            passes = jnp.minimum(jnp.max(pos), last) // KEY_BLOCK + 1

            def one_pass(j, carry):
                top, total, acc = carry
                k = lax.dynamic_slice_in_dim(keys, j * KEY_BLOCK,
                                             KEY_BLOCK, axis=1)
                v = lax.dynamic_slice_in_dim(values, j * KEY_BLOCK,
                                             KEY_BLOCK, axis=1)
                scores = jnp.einsum("qhd,hkd->hqk", qb, k,
                                    preferred_element_type=f32)
                seen = (j * KEY_BLOCK + jnp.arange(KEY_BLOCK))[None] \
                    <= pos[:, None]
                scores = jnp.where(seen[None], scores * scale,
                                   -jnp.inf)
                new_top = jnp.maximum(top, jnp.max(scores, -1))
                w = jnp.exp(scores - new_top[..., None])
                keep = jnp.exp(top - new_top)
                acc = acc * keep[..., None] + jnp.einsum(
                    "hqk,hkv->hqv", w.astype(qb.dtype), v,
                    preferred_element_type=f32)
                return new_top, total * keep + jnp.sum(w, -1), acc

            # position 0 lies in the first pass and every query sees
            # it, so the running maximum is finite from there on
            _, total, acc = lax.fori_loop(0, passes, one_pass, (
                jnp.full((heads, n_q), -jnp.inf, f32),
                jnp.zeros((heads, n_q), f32),
                jnp.zeros((heads, n_q, self.d_v), f32)))
            return jnp.swapaxes(acc / total[..., None], 0, 1)

        if t <= QUERY_BLOCK:
            return block((q, positions))
        pad = -t % QUERY_BLOCK
        out = jax.lax.map(block, tuple(
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            .reshape((-1, QUERY_BLOCK) + a.shape[1:])
            for a in (q, positions)))
        return out.reshape((-1,) + out.shape[2:])[:t]

    def _absorbed(self, q_nope, q_rope, positions, ctx, kv_b):
        """One query a sequence at ``positions``, each over its cached
        rows ``0 .. positions - 1`` and its own new row: ``ctx`` is the
        pool as it came in, the tables, and the new rows
        (``ops.paged_attention.decode_attention``, which reads a
        sequence's blocks through its table as far as it is live, one
        pool for keys and values).  The key's expansion moves onto the
        query, the value's behind the weighted latents.  Scores and
        weighted rows run over the whole cached row (latent and rope
        lanes at once), so a row is read as the pool holds it."""
        import jax.numpy as jnp
        from ...ops.paged_attention import decode_attention
        pool, tables, rows = ctx
        t, heads = q_nope.shape[0], self.n_heads
        rank, d_nope, d_rope = self.rank, self.d_nope, self.d_rope
        lanes = pool.shape[-1]
        q_row = jnp.concatenate(
            [jnp.einsum("thn,hnc->thc", q_nope, kv_b[:, :d_nope]),
             q_rope, jnp.zeros((t, heads, lanes - rank - d_rope),
                               q_nope.dtype)], axis=-1)
        o = decode_attention(q_row, rows, rows, pool, pool, tables,
                             positions, scale=self.softmax_scale)
        o_lat = o.reshape(t, heads, lanes).astype(q_nope.dtype)
        return jnp.einsum("thc,hvc->thv", o_lat[..., :rank],
                          kv_b[:, d_nope:])

    def _attend(self, lw, h, positions, cache, li, last):
        """Latent attention of rows ``h`` (T, d) at ``positions``."""
        import jax
        import jax.numpy as jnp
        t = h.shape[0]
        heads, rank, d_nope = self.n_heads, self.rank, self.d_nope
        inv_freq = jnp.asarray(self._inv_freq)
        with jax.named_scope("mla_attend"):
            q = _mm(_rms(_mm(h, lw["q_a"]), lw["q_norm"], self.eps),
                    lw["q_b"]).reshape(t, heads, d_nope + self.d_rope)
            q_nope = q[..., :d_nope]
            q_rope = _rope(q[..., d_nope:], positions, inv_freq)
            kv = _mm(h, lw["kv_a"])
            rows = jnp.concatenate(
                [_rms(kv[:, :rank], lw["kv_norm"], self.eps),
                 _rope(kv[:, rank:], positions, inv_freq)], axis=-1)
            ctx = cache.context(li, rows)
            kv_b = lw["kv_b"].reshape(heads, d_nope + self.d_v, rank)
            if cache.absorbed:
                out = self._absorbed(q_nope, q_rope, positions, ctx,
                                     kv_b)
            else:
                out = self._expanded(
                    jnp.concatenate([q_nope, q_rope], axis=-1),
                    positions, ctx, kv_b, last)
            return _mm(out.astype(h.dtype).reshape(
                t, heads * self.d_v), lw["o"])

    def _ffn(self, lw, h, valid):
        """The FFN sublayer on normed rows ``h``: (its rows, the
        routed layer's statistics or None)."""
        from ...ops.moe import gated_ffn, routed_ffn_fn
        if "router" not in lw:
            return gated_ffn(h, lw["gate"], lw["up"], lw["down"]), None
        c = self._c
        shared = None
        if "shared_gate" in lw and self.shared_here:
            shared = (lw["shared_gate"], lw["shared_up"],
                      lw["shared_down"])
        return routed_ffn_fn(
            h, lw["router"], lw["experts_gate"], lw["experts_up"],
            lw["experts_down"], self.top_k,
            scoring=c.get("scoring_func", "sigmoid"),
            select_bias=lw["router_bias"],
            normalize=c.get("norm_topk_prob", True),
            scale=float(c.get("routed_scaling_factor", 1.0)),
            shared=shared, held=self.held, valid=valid)

    def _sinkhorn(self, m):
        """``hc_iters`` rounds of rows then columns, each divided by
        its sum + ``hc_eps``, over m (T, n * n) row-major and positive:
        (H[i][j], each (T,), and the largest |row or column sum - 1|
        after the last round, (T,)).  Written entry by entry, every
        operation elementwise on (T,), so that a round compiles to
        about two fusions and not to a reduction and a division for
        each row and each column; a loop of passes of
        ``SINKHORN_UNROLL`` rounds, since all the rounds unrolled
        (one fusion) cost the compiler seconds for each
        hyper-connection of every program."""
        import jax
        import jax.numpy as jnp
        n, eps = self.residual_streams, self.hc_eps

        def down(h, j):
            return [h[i][j] for i in range(n)]

        def one_round(_, h):
            h = [[v / (_total(row) + eps) for v in row] for row in h]
            sums = [_total(down(h, j)) + eps for j in range(n)]
            return [[v / s for v, s in zip(row, sums)] for row in h]
        cols = m.T
        h = jax.lax.fori_loop(
            0, self.hc_iters, one_round,
            [[cols[i * n + j] for j in range(n)] for i in range(n)],
            unroll=SINKHORN_UNROLL)
        err = functools.reduce(jnp.maximum, [
            jnp.abs(_total(v) - 1.0)
            for v in h + [down(h, j) for j in range(n)]])
        return h, err

    def _hyper(self, lw, s, x, sublayer):
        """Sublayer ``s`` (``attn`` or ``ffn``), ``sublayer(h)`` of
        rows h (T, d), under its hyper-connection: streams ``x`` (T, n,
        d) in and out, and each row's largest marginal error of its
        mixing matrix (T,).  The mapping is one product of the
        normalised streams (bfloat16 operands where the weights are,
        float32 sums); gates, the Sinkhorn rounds and both mixes are
        float32."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        t, n, d = x.shape
        with jax.named_scope("hc_map"):
            u = x.reshape(t, n * d).astype(f32)
            u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True)
                                  + self.eps)
            z = jnp.dot(u.astype(x.dtype), lw[f"hc_{s}"].T,
                        preferred_element_type=f32)
            a = lw[f"hc_{s}_alpha"].astype(f32)
            b = lw[f"hc_{s}_bias"].astype(f32)
            pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
            post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
            h_res, err = self._sinkhorn(jnp.exp(jnp.clip(
                a[2] * z[:, 2 * n:] + b[2 * n:], *self.hc_clip)))
        with jax.named_scope("hc_mix"):
            wide = x.astype(f32)
            h = _total([pre[:, i, None] * wide[:, i] for i in range(n)])
        y = sublayer(h.astype(x.dtype)).astype(f32)
        with jax.named_scope("hc_mix"):
            x = jnp.stack([_total([h_res[i][j][:, None] * wide[:, j]
                                   for j in range(n)])
                           + post[:, i, None] * y for i in range(n)],
                          axis=1).astype(x.dtype)
        return x, err

    def layer(self, lw, x, positions, cache, li, valid=None,
              last=None):
        """One layer on rows ``x`` (T, d), or on their streams (T, n,
        d) where the model has hyper-connections, at ``positions``
        (T,): (rows or streams out, the routed layer's statistics or
        None, the largest marginal error of the layer's mixing
        matrices or None)."""
        import jax.numpy as jnp
        if last is None:
            last = positions[-1]
        eps = self.eps
        if self.residual_streams == 1:
            x = x + self._attend(lw, _rms(x, lw["attn_norm"], eps),
                                 positions, cache, li, last)
            y, stats = self._ffn(lw, _rms(x, lw["ffn_norm"], eps),
                                 valid)
            x, err = x + y.astype(x.dtype), None
        else:
            out = {}

            def ffn(h):
                y, out["stats"] = self._ffn(
                    lw, _rms(h, lw["ffn_norm"], eps), valid)
                return y
            x, err_a = self._hyper(lw, "attn", x, lambda h: self._attend(
                lw, _rms(h, lw["attn_norm"], eps), positions, cache, li,
                last))
            x, err_f = self._hyper(lw, "ffn", x, ffn)
            err = jnp.maximum(err_a, err_f)
            if valid is not None:      # padding rows and idle slots
                err = jnp.where(valid, err, 0.0)
            stats, err = out["stats"], jnp.max(err)
        if stats is None:
            return x, None, err
        return x, jnp.stack([stats["routed_rows"], stats["padded_rows"],
                             stats["experts_touched"],
                             jnp.ones((), jnp.int32)]), err

    def _rows_to_logits(self, wts, x):
        import jax.numpy as jnp
        if self.residual_streams > 1:
            # the exit: the streams summed (a mean differs by nothing
            # after the norm but its epsilon)
            x = jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)
        return jnp.dot(_rms(x, wts["norm"], self.eps),
                       wts["head"].T,
                       preferred_element_type=jnp.float32)

    def _stack(self, wts, tokens, positions, cache, valid=None,
               last=None):
        """Embedding and every layer: (rows or streams, the routed
        layers' statistics summed: rows multiplied, rows of them
        padding, experts touched, routed layers; and where the model
        has hyper-connections, behind them as float32 bits, the largest
        marginal error of any mixing matrix)."""
        import jax
        import jax.numpy as jnp
        x = wts["embed"][tokens]
        n = self.residual_streams
        if n > 1:
            # the entry: the embedding copied into every stream
            x = jnp.broadcast_to(x[:, None], (x.shape[0], n, x.shape[1]))
        stats, worst = jnp.zeros(4, jnp.int32), None
        for li, lw in enumerate(wts["layers"]):
            x, s, err = self.layer(lw, x, positions, cache, li, valid,
                                   last)
            if s is not None:
                stats = stats + s
            if err is not None:
                worst = err if worst is None else jnp.maximum(worst, err)
        if worst is not None:
            stats = jnp.concatenate([stats, jax.lax.bitcast_convert_type(
                worst, jnp.int32)[None]])
        return x, stats

    # ---------------------------------------------------------- eager
    def forward(self, tokens):
        """Logits (B, L, V), float32; each row of ``tokens`` a
        sequence of its own.  One compiled program a shape."""
        import jax
        import jax.numpy as jnp
        toks = tokens._data if hasattr(tokens, "_data") \
            else jnp.asarray(tokens)
        if toks.shape[1] > self._max_len:
            raise ValueError(f"sequence {toks.shape[1]} exceeds "
                             f"max_len {self._max_len}")
        fn = self._forward_fns.get(toks.shape)
        if fn is None:
            def one(wts, row):
                x, _ = self._stack(wts, row,
                                   jnp.arange(row.shape[0]),
                                   _NoCache())
                return self._rows_to_logits(wts, x)
            fn = self._forward_fns[toks.shape] = jax.jit(
                lambda wts, toks: jax.lax.map(
                    lambda row: one(wts, row), toks))
        return nd.NDArray(fn(self._decode_weights(),
                             toks.astype(jnp.int32)))

    # --------------------------------------- the paged protocol
    # (docs/serving.md): what ServingEngine asks of a model
    def _check_paged(self):
        """Dropless routing: a token's experts do not turn on its
        batch-mates, so any batch serves it alike."""

    def _paged_cache(self):
        """One pool: the latent row a token leaves in a layer,
        ``kv_lora_rank + qk_rope_head_dim`` values in the dtype the
        weights are held in, stored in whole lanes of 128 (640 for
        576): the chip lays an array whose last axis is no whole lane
        out by another axis, and every program would turn the whole
        pool round before it scatters into it and again after
        (PERF.md, section 6)."""
        values = self.rank + self.d_rope
        return ({"name": "latent", "shape": (-(-values // LANE) * LANE,),
                 "dtype": str(self.embed_weight.dtype),
                 "values": values},)

    def _paged_read(self, block_size, platform):
        """Which read of the pool the decode step takes where it is
        traced now and lowered for ``platform``, with the shapes that
        decide it (``ops.paged_attention.read_kind``: every head's
        absorbed query against the one pool's whole row, one kv head),
        and the streams of its residual: what the engine's
        ``serve_paged_read`` event carries."""
        from ...ops.paged_attention import read_kind
        cache, = self._paged_cache()
        row, = cache["shape"]
        return dict(read=read_kind(self.n_heads, 1, row, int(block_size),
                                   cache["dtype"], platform),
                    heads=self.n_heads, kv_heads=1, head_dim=row,
                    dtype=cache["dtype"],
                    residual_streams=self.residual_streams)

    def _decode_weights(self):
        def w(param):
            return param.data()._data
        return {"embed": w(self.embed_weight),
                "norm": w(self.norm), "head": w(self.head_weight),
                "layers": [{k: w(p) for k, p in lw.items()}
                           for lw in self.layers]}

    def _build_paged_prefill(self, suffix_len, max_blocks,
                             block_size):
        """``prefill(wts, pool, table, n_past, tokens, true_len) ->
        (pool, next, logits)``: ``suffix_len`` rows at positions
        ``n_past + i`` through the expanded path; rows past
        ``true_len`` are padding, written to the scratch block and
        routed to no expert.  ``next`` is the greedy token after the
        last real row, then the statistics of ``_stack``."""
        import jax.numpy as jnp
        from jax import lax
        S, MB, bs = int(suffix_len), int(max_blocks), int(block_size)

        def prefill(wts, pool, table, n_past, tokens, true_len):
            pos = n_past + jnp.arange(S)
            valid = jnp.arange(S) < true_len
            wpos = jnp.where(valid, pos, 0)
            blk = jnp.where(
                valid, table[jnp.minimum(wpos // bs, MB - 1)], 0)
            cache = _PagedCache(pool, table, blk, wpos % bs, False)
            x, stats = self._stack(wts, tokens, pos, cache, valid,
                                   n_past + true_len - 1)
            logits = self._rows_to_logits(wts, lax.dynamic_index_in_dim(
                x, true_len - 1, 0, keepdims=False))
            nxt = jnp.argmax(logits).astype(jnp.int32)
            return cache.pools, jnp.concatenate(
                [nxt[None], stats]), logits

        return prefill

    def _build_paged_step(self, max_batch, max_blocks, block_size):
        """``step(wts, pool, tables, n_past, tokens) -> (pool, next,
        logits)``: every slot's newest token at its own position
        through the absorbed path, which reads each slot's blocks
        through its table row as far as the slot is live where it is
        lowered for a TPU and the shapes tile (``_paged_read`` says
        which), and gathers the row's ``max_blocks * block_size``
        positions anywhere else.  A slot with nothing cached is idle:
        it writes to the scratch block, reads no block and is routed
        to no expert.  ``next`` is a token a slot, then the
        statistics."""
        import jax.numpy as jnp
        bs = int(block_size)

        def step(wts, pool, tables, n_past, tokens):
            blk = jnp.take_along_axis(
                tables, (n_past // bs)[:, None], axis=1)[:, 0]
            cache = _PagedCache(pool, tables, blk, n_past % bs, True)
            x, stats = self._stack(wts, tokens, n_past, cache,
                                   n_past > 0)
            logits = self._rows_to_logits(wts, x)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return cache.pools, jnp.concatenate([nxt, stats]), logits

        return step

    def _decode_workspace_bytes(self, max_batch):
        """One decode step's logits and residual rows, float32: a
        token's residual is ``residual_streams`` rows wide."""
        return 4.0 * max_batch * (
            self.vocab + 8 * self._d * self.residual_streams)
