"""Hybrid decoder LM of Mamba-2 mixers, latent routed experts and a few
attention layers: NVIDIA's ``nemotron_h`` (Nemotron-3-Super and its
kin), built from the keys of such a model's own ``config.json``.

Each layer is one block ``x = x + mixer(RMS(x))`` whose mixer
``hybrid_override_pattern`` names, a letter a layer:

``M``  a Mamba-2 mixer (``ops/ssm.py``): ``[z | xBC | dt] = in_proj
       x``; ``xBC = silu(conv(xBC))``, the causal depthwise conv of
       ``conv_kernel`` taps with a bias, split into ``x`` (heads of
       ``mamba_head_dim``), ``B`` and ``C`` (``n_groups`` of
       ``ssm_state_size``); ``dt = softplus(dt + dt_bias)``, ``A =
       -exp(A_log)``; ``h_t = exp(dt A) h_{t-1} + dt x (x) B``, ``y =
       C . h + D x``; ``y = RMS_grouped(y * silu(z)) w`` over
       ``n_groups`` groups; ``out_proj y``.
``E``  latent routed experts (``ops.moe.routed_ffn_fn``): sigmoid
       scores of all ``n_routed_experts`` on the row, the top
       ``num_experts_per_tok`` by score plus ``e_score_correction_bias``,
       weights the chosen scores over their sum times
       ``routed_scaling_factor``; an expert ``relu(u W_up)^2 W_down``
       of ``u = fc1_latent x`` (``moe_latent_size`` wide), their sum
       through ``fc2_latent``, plus a shared expert of the same kind on
       the row.
``*``  grouped-query attention, causal, with no position encoding
       (``nemotron_h`` applies none; ``rope_theta`` goes unread).

The embedding is not scaled, the head is not tied, a final norm comes
before it.  ``held=(first, count)`` makes this one rank of an
expert-parallel deployment: the router keeps all ``n_routed_experts``
outputs, the layer holds and computes experts ``first .. first +
count - 1``, and ``shared_here`` says whether the shared expert is
added on this rank.  The multi-token-prediction module
(``num_nextn_predict_layers``) is no part of the forward pass that
yields a token and is not built.

Served through ``serving.ServingEngine`` on the paged protocol with two
kinds of request state (docs/serving.md, "Mamba-2 layers on the paged
path"): the attention layers' keys and values in blocks, a row a
token, and the Mamba layers' state and conv tail, of fixed size, a row
a slot.  A prefill starts its slot's state from zero (``n_past`` 0)
and leaves it where its last real row left it; a decode step carries
every live slot's state one token on.
"""
from ... import ndarray as nd
from ..block import Block
from .latent_moe import _mm, _rms

__all__ = ["NemotronHLM"]

QUERY_BLOCK = 256     # rows of queries whose scores are held at once
KEY_BLOCK = 512       # context rows a pass of the prefill's read takes
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


class _Context:
    """What a call hands its layers.  ``slots`` None: one sequence
    (eager, or a prefill into slot ``slot``); else a decode step over
    every slot.  ``kv`` / ``states`` / ``tails``: lists over the
    attention and Mamba layers (None in the eager forward); they end as
    the updated pools."""

    def __init__(self, kv=None, states=None, tails=None, **where):
        self.kv = None if kv is None else [list(p) for p in kv]
        self.states = None if states is None else list(states)
        self.tails = None if tails is None else list(tails)
        self.__dict__.update(where)


class NemotronHLM(Block):
    """Token-in, logits-out decoder LM of the layers above.

    ``config`` holds the model's own keys (``_KEYS`` below; ``norm_eps``
    or ``layer_norm_epsilon``, ``routed_scaling_factor``,
    ``norm_topk_prob``, ``n_shared_experts`` where they differ from
    1e-5, 1.0, true, 1)."""

    _KEYS = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern",
             "vocab_size", "max_position_embeddings", "mamba_num_heads",
             "mamba_head_dim", "n_groups", "ssm_state_size",
             "conv_kernel", "chunk_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "n_routed_experts",
             "num_experts_per_tok", "moe_intermediate_size",
             "moe_latent_size", "moe_shared_expert_intermediate_size")

    def __init__(self, config, held=None, shared_here=True, **kwargs):
        super().__init__(**kwargs)
        lacking = [k for k in self._KEYS if k not in config]
        if lacking:
            raise KeyError(f"config lacks {lacking}")
        c = self._c = dict(config)
        for key, only in (("mlp_hidden_act", "relu2"),
                          ("mamba_hidden_act", "silu"), ("n_group", 1),
                          ("topk_group", 1), ("use_conv_bias", True),
                          ("attention_bias", False),
                          ("mamba_proj_bias", False), ("mlp_bias", False)):
            if c.get(key, only) != only:
                raise ValueError(f"{key}={c[key]!r} is not built: "
                                 f"{only!r} only")
        pattern = c["hybrid_override_pattern"]
        self.n_layers = c["num_hidden_layers"]
        if len(pattern) != self.n_layers or set(pattern) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: one of "
                f"{''.join(KINDS)} for each of {self.n_layers} layers")
        self.kinds = [KINDS[ch] for ch in pattern]
        d = self._d = c["hidden_size"]
        self._max_len = c["max_position_embeddings"]
        self.vocab = c["vocab_size"]
        self.eps = c.get("norm_eps", c.get("layer_norm_epsilon", 1e-5))
        self.heads, self.head_dim = (c["mamba_num_heads"],
                                     c["mamba_head_dim"])
        self.groups, self.d_state = c["n_groups"], c["ssm_state_size"]
        self.d_inner = self.heads * self.head_dim
        self.conv_dim = self.d_inner + 2 * self.groups * self.d_state
        self.taps = c["conv_kernel"]
        self.chunk = c["chunk_size"]
        self.n_heads, self.n_kv = (c["num_attention_heads"],
                                   c["num_key_value_heads"])
        self.attn_dim = c["head_dim"]
        self.n_experts = c["n_routed_experts"]
        self.top_k = c["num_experts_per_tok"]
        self.held = (0, self.n_experts) if held is None \
            else (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= sum(self.held) <= self.n_experts:
            raise ValueError(f"held={held!r} is not among "
                             f"{self.n_experts} experts")
        self.shared_here = bool(shared_here)
        # the layers of each kind, in order: a pool lies over one kind
        self.attn_layers = [i for i, k in enumerate(self.kinds)
                            if k == "attention"]
        self.mamba_layers = [i for i, k in enumerate(self.kinds)
                             if k == "mamba"]
        # a layer's place among the layers of its kind: its pools' index
        self._within = [self.kinds[:i].count(k)
                        for i, k in enumerate(self.kinds)]
        self._forward_fns = {}
        latent, width = c["moe_latent_size"], c["moe_intermediate_size"]
        shared = c["moe_shared_expert_intermediate_size"] \
            * c.get("n_shared_experts", 1)
        with self.name_scope():
            get = self.params.get
            self.embed_weight = get("embed_weight", shape=(self.vocab, d))
            self.layers = []
            for i, kind in enumerate(self.kinds):
                p = f"layer{i}_"
                lw = {"norm": get(p + "norm_gamma", shape=(d,),
                                  init="ones")}
                if kind == "mamba":
                    lw.update(
                        in_proj=get(p + "in_proj_weight", shape=(
                            self.d_inner + self.conv_dim + self.heads, d)),
                        conv=get(p + "conv_weight",
                                 shape=(self.conv_dim, self.taps)),
                        conv_bias=get(p + "conv_bias", init="zeros",
                                      shape=(self.conv_dim,)),
                        dt_bias=get(p + "dt_bias", init="zeros",
                                    shape=(self.heads,)),
                        A_log=get(p + "A_log", init="zeros",
                                  shape=(self.heads,)),
                        D=get(p + "D", init="ones", shape=(self.heads,)),
                        gate_norm=get(p + "gate_norm_gamma", init="ones",
                                      shape=(self.d_inner,)),
                        out_proj=get(p + "out_proj_weight",
                                     shape=(d, self.d_inner)))
                elif kind == "moe":
                    e = self.held[1]
                    lw.update(
                        router=get(p + "router_weight",
                                   shape=(self.n_experts, d)),
                        router_bias=get(p + "router_bias", init="zeros",
                                        shape=(self.n_experts,)),
                        fc1_latent=get(p + "fc1_latent_weight",
                                       shape=(latent, d)),
                        fc2_latent=get(p + "fc2_latent_weight",
                                       shape=(d, latent)),
                        experts_up=get(p + "experts_up_weight",
                                       shape=(e, width, latent)),
                        experts_down=get(p + "experts_down_weight",
                                         shape=(e, latent, width)),
                        shared_up=get(p + "shared_up_weight",
                                      shape=(shared, d)),
                        shared_down=get(p + "shared_down_weight",
                                        shape=(d, shared)))
                else:
                    q, kv = (self.n_heads * self.attn_dim,
                             self.n_kv * self.attn_dim)
                    lw.update(q=get(p + "q_weight", shape=(q, d)),
                              k=get(p + "k_weight", shape=(kv, d)),
                              v=get(p + "v_weight", shape=(kv, d)),
                              o=get(p + "o_weight", shape=(d, q)))
                self.layers.append(lw)
            self.norm = get("norm_gamma", shape=(d,), init="ones")
            self.head_weight = get("head_weight", shape=(self.vocab, d))

    # ------------------------------------------------ the mathematics
    def _split(self, lw, h):
        """in_proj of rows h: (z, xBC, dt)."""
        zx = _mm(h, lw["in_proj"])
        a, b = self.d_inner, self.d_inner + self.conv_dim
        return zx[:, :a], zx[:, a:b], zx[:, b:]

    def _ssm_inputs(self, lw, xbc, dt, live):
        """(x (T, heads, head_dim), dt after softplus, B, C (T, groups,
        d_state), A, D), float32; rows not ``live`` get dt 0 and x 0,
        which leave the state as it was."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        t = xbc.shape[0]
        gn = self.groups * self.d_state
        x = jnp.where(live[:, None], xbc[:, :self.d_inner], 0.0)
        b = xbc[:, self.d_inner:self.d_inner + gn]
        c = xbc[:, self.d_inner + gn:]
        dt = jnp.where(live[:, None], jax.nn.softplus(
            dt.astype(f32) + lw["dt_bias"].astype(f32)), 0.0)
        return (x.reshape(t, self.heads, self.head_dim), dt,
                b.reshape(t, self.groups, self.d_state),
                c.reshape(t, self.groups, self.d_state),
                -jnp.exp(lw["A_log"].astype(f32)), lw["D"].astype(f32))

    def _gate_norm(self, lw, y, z, dtype):
        """``RMS_grouped(y * silu(z)) w`` in float32, over ``n_groups``
        groups of ``d_inner / n_groups``; in ``dtype``."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("ssm_gate_norm"):
            t = y.shape[0]
            g = (y.reshape(t, -1) * jax.nn.silu(z.astype(jnp.float32))) \
                .reshape(t, self.groups, -1)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                                  + self.eps)
            return (g.reshape(t, -1)
                    * lw["gate_norm"].astype(jnp.float32)).astype(dtype)

    def _mamba(self, lw, h, ctx, j, valid):
        """The Mamba mixer on normed rows ``h``: one sequence (a
        prefill or the eager forward) through the chunked scan, or one
        row a slot through the state step."""
        import jax
        import jax.numpy as jnp
        from ...ops import ssm
        f32 = jnp.float32
        z, xbc, dt = self._split(lw, h)
        if ctx.slots is not None:
            with jax.named_scope("ssm_conv"):
                xbc, ctx.tails[j] = ssm.conv_step(
                    xbc, ctx.tails[j], lw["conv"], lw["conv_bias"], valid)
                xbc = jax.nn.silu(xbc)
            x, dt, b, c, a, d = self._ssm_inputs(lw, xbc, dt, valid)
            with jax.named_scope("ssm_step"):
                y, ctx.states[j] = ssm.state_step(ctx.states[j], x, dt, a,
                                                  b, c, d)
        else:
            if ctx.states is None:       # the eager forward: from zero
                h0 = jnp.zeros((self.heads, self.head_dim, self.d_state),
                               f32)
                tail = jnp.zeros((self.taps - 1, self.conv_dim), h.dtype)
            else:
                fresh = ctx.n_past == 0
                h0 = jnp.where(fresh, 0.0, ssm.from_rows(
                    ctx.states[j][ctx.slot], self.head_dim))
                tail = jnp.where(fresh, 0, ctx.tails[j][ctx.slot])
            with jax.named_scope("ssm_conv"):
                xbc, tail = ssm.causal_conv(xbc, tail, lw["conv"],
                                            lw["conv_bias"], ctx.true_len)
                xbc = jax.nn.silu(xbc)
            x, dt, b, c, a, d = self._ssm_inputs(lw, xbc, dt, valid)
            with jax.named_scope("ssm_scan"):
                y, hn = ssm.chunked_scan(x, dt, a, b, c, h0, self.chunk)
                y = y + d[:, None] * x
            if ctx.states is not None:
                ctx.states[j] = ctx.states[j].at[ctx.slot].set(
                    ssm.to_rows(hn, self.groups))
                ctx.tails[j] = ctx.tails[j].at[ctx.slot].set(
                    tail.astype(ctx.tails[j].dtype))
        return _mm(self._gate_norm(lw, y, z, h.dtype), lw["out_proj"])

    def _blocked(self, q, keys, values, positions, last):
        """Causal attention of queries ``q`` (T, heads, dim) at
        ``positions`` over context rows ``keys``, ``values`` (C, kv,
        dim), row c at position c: the queries in blocks of
        ``QUERY_BLOCK``, each over the context in passes of
        ``KEY_BLOCK`` rows as far as the block's last real query sees
        (``last``), with a running maximum and sum."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        f32 = jnp.float32
        t, heads, dim = q.shape
        kv = keys.shape[1]
        rep = heads // kv
        pad_c = -keys.shape[0] % KEY_BLOCK
        keys, values = (jnp.pad(a, ((0, pad_c), (0, 0), (0, 0)))
                        for a in (keys, values))
        scale = dim ** -0.5

        def block(args):
            qb, pos = args
            n_q = qb.shape[0]
            qg = qb.reshape(n_q, kv, rep, dim)
            passes = jnp.minimum(jnp.max(pos), last) // KEY_BLOCK + 1

            def one_pass(i, carry):
                top, total, acc = carry
                k = lax.dynamic_slice_in_dim(keys, i * KEY_BLOCK,
                                             KEY_BLOCK, axis=0)
                v = lax.dynamic_slice_in_dim(values, i * KEY_BLOCK,
                                             KEY_BLOCK, axis=0)
                s = jnp.einsum("qgrd,cgd->grqc", qg, k,
                               preferred_element_type=f32) * scale
                seen = (i * KEY_BLOCK + jnp.arange(KEY_BLOCK))[None] \
                    <= pos[:, None]
                s = jnp.where(seen[None, None], s, -jnp.inf)
                new_top = jnp.maximum(top, jnp.max(s, -1))
                w = jnp.exp(s - new_top[..., None])
                keep = jnp.exp(top - new_top)
                acc = acc * keep[..., None] + jnp.einsum(
                    "grqc,cgd->grqd", w.astype(qb.dtype), v,
                    preferred_element_type=f32)
                return new_top, total * keep + jnp.sum(w, -1), acc

            # position 0 lies in the first pass and every query sees it
            _, total, acc = lax.fori_loop(0, passes, one_pass, (
                jnp.full((kv, rep, n_q), -jnp.inf, f32),
                jnp.zeros((kv, rep, n_q), f32),
                jnp.zeros((kv, rep, n_q, dim), f32)))
            out = acc / total[..., None]
            return jnp.moveaxis(out, 2, 0).reshape(n_q, heads, dim)

        if t <= QUERY_BLOCK:
            return block((q, positions))
        pad = -t % QUERY_BLOCK
        out = jax.lax.map(block, tuple(
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            .reshape((-1, QUERY_BLOCK) + a.shape[1:])
            for a in (q, positions)))
        return out.reshape((-1,) + out.shape[2:])[:t]

    def _attend(self, lw, h, positions, ctx, j, last):
        """Grouped-query attention of rows ``h`` at ``positions``, no
        position encoding."""
        import jax
        import jax.numpy as jnp
        from ...ops.paged_attention import decode_attention, \
            gathered_context
        t, dim = h.shape[0], self.attn_dim
        with jax.named_scope("gqa_attend"):
            q = _mm(h, lw["q"]).reshape(t, self.n_heads, dim)
            k, v = _mm(h, lw["k"]), _mm(h, lw["v"])
            if ctx.kv is None:
                out = self._blocked(q, k.reshape(t, self.n_kv, dim),
                                    v.reshape(t, self.n_kv, dim),
                                    positions, last)
            else:
                pools = ctx.kv[j]
                before = list(pools)
                for i, rows in enumerate((k, v)):
                    pools[i] = pools[i].at[ctx.blk, ctx.off].set(
                        rows.astype(pools[i].dtype))
                if ctx.slots is not None:
                    out = decode_attention(q, k, v, before[0], before[1],
                                           ctx.tables, positions)
                else:
                    got = [gathered_context(p, ctx.tables, ctx.n_past,
                                            r).reshape(-1, self.n_kv, dim)
                           for p, r in zip(before, (k, v))]
                    out = self._blocked(q, got[0].astype(h.dtype),
                                        got[1].astype(h.dtype),
                                        positions, last)
            return _mm(out.astype(h.dtype).reshape(t, -1), lw["o"])

    def _moe(self, lw, h, valid):
        """The latent routed layer: (rows, statistics)."""
        from ...ops.moe import routed_ffn_fn
        c = self._c
        shared = (None, lw["shared_up"], lw["shared_down"]) \
            if self.shared_here else None
        return routed_ffn_fn(
            h, lw["router"], None, lw["experts_up"], lw["experts_down"],
            self.top_k, scoring="sigmoid", select_bias=lw["router_bias"],
            normalize=c.get("norm_topk_prob", True),
            scale=float(c.get("routed_scaling_factor", 1.0)),
            shared=shared, held=self.held, valid=valid,
            activation="relu2",
            latent=(lw["fc1_latent"], lw["fc2_latent"]))

    def _stack(self, wts, tokens, positions, ctx, valid, last):
        """Embedding and every layer: (rows, the routed layers'
        statistics summed: rows multiplied, rows of them padding,
        experts touched, routed layers)."""
        import jax.numpy as jnp
        x = wts["embed"][tokens]
        stats = jnp.zeros(4, jnp.int32)
        for kind, j, lw in zip(self.kinds, self._within, wts["layers"]):
            h = _rms(x, lw["norm"], self.eps)
            if kind == "mamba":
                y = self._mamba(lw, h, ctx, j, valid)
            elif kind == "attention":
                y = self._attend(lw, h, positions, ctx, j, last)
            else:
                y, s = self._moe(lw, h, valid)
                stats = stats + jnp.stack([
                    s["routed_rows"], s["padded_rows"],
                    s["experts_touched"], jnp.ones((), jnp.int32)])
            x = x + y.astype(x.dtype)
        return x, stats

    def _rows_to_logits(self, wts, x):
        import jax.numpy as jnp
        return jnp.dot(_rms(x, wts["norm"], self.eps), wts["head"].T,
                       preferred_element_type=jnp.float32)

    # ---------------------------------------------------------- eager
    def forward(self, tokens):
        """Logits (B, L, V), float32; each row of ``tokens`` a
        sequence of its own, its state from zero.  One compiled
        program a shape."""
        import jax
        import jax.numpy as jnp
        toks = tokens._data if hasattr(tokens, "_data") \
            else jnp.asarray(tokens)
        if toks.shape[1] > self._max_len:
            raise ValueError(f"sequence {toks.shape[1]} exceeds "
                             f"max_len {self._max_len}")
        fn = self._forward_fns.get(toks.shape)
        if fn is None:
            n = toks.shape[1]

            def one(wts, row):
                ctx = _Context(slots=None, true_len=n)
                x, _ = self._stack(wts, row, jnp.arange(n), ctx,
                                   jnp.ones(n, jnp.bool_), n - 1)
                return self._rows_to_logits(wts, x)
            fn = self._forward_fns[toks.shape] = jax.jit(
                lambda wts, toks: jax.lax.map(
                    lambda row: one(wts, row), toks))
        return nd.NDArray(fn(self._decode_weights(),
                             toks.astype(jnp.int32)))

    # --------------------------------------- the paged protocol
    # (docs/serving.md): what ServingEngine asks of a model
    def _check_paged(self):
        """Dropless routing and a state a slot: any batch serves a
        token alike."""

    def _paged_cache(self):
        """Keys and values of the attention layers, a row a token in
        blocks, in the weights' dtype; the Mamba layers' state
        (``ops.ssm.state_shape``, float32: it adds one update a token)
        and conv tail (the last ``conv_kernel - 1`` inputs, in the
        weights' dtype), a row a slot."""
        from ...ops.ssm import state_shape
        dtype = str(self.embed_weight.dtype)
        row = (self.n_kv * self.attn_dim,)
        return tuple(
            [{"name": n, "shape": row, "dtype": dtype,
              "layers": tuple(self.attn_layers)} for n in ("k", "v")]
            + [{"name": "ssm_state", "per": "slot",
                "shape": state_shape(self.heads, self.head_dim,
                                     self.groups, self.d_state),
                "dtype": "float32", "layers": tuple(self.mamba_layers)},
               {"name": "conv_tail", "per": "slot",
                "shape": (self.taps - 1, self.conv_dim), "dtype": dtype,
                "layers": tuple(self.mamba_layers)}])

    def _paged_read(self, block_size, platform):
        """The decode step's read of the keys and values
        (``ops.paged_attention.read_kind``), with the shapes that decide
        it, the pools kept a slot and the layers that attend: what the
        engine's ``serve_paged_read`` event carries."""
        from ...ops.paged_attention import read_kind
        dtype = str(self.embed_weight.dtype)
        return dict(read=read_kind(self.n_heads, self.n_kv,
                                   self.attn_dim, int(block_size), dtype,
                                   platform),
                    heads=self.n_heads, kv_heads=self.n_kv,
                    head_dim=self.attn_dim, dtype=dtype,
                    state_pools=["ssm_state", "conv_tail"],
                    kv_layers=len(self.attn_layers))

    def _decode_weights(self):
        def w(param):
            return param.data()._data
        return {"embed": w(self.embed_weight),
                "norm": w(self.norm), "head": w(self.head_weight),
                "layers": [{k: w(p) for k, p in lw.items()}
                           for lw in self.layers]}

    def _build_paged_prefill(self, suffix_len, max_blocks,
                             block_size):
        """``prefill(wts, kpools, vpools, states, tails, table, n_past,
        tokens, true_len, slot) -> (kpools, vpools, states, tails,
        next, logits)``: ``suffix_len`` rows at positions ``n_past +
        i``; keys and values through the table, the Mamba layers from
        slot ``slot``'s state (zero where ``n_past`` is 0) to the state
        after row ``true_len - 1``, written back to the slot.  Rows
        past ``true_len`` are padding: written to the scratch block,
        routed to no expert, no step of any state.  ``next`` is the
        greedy token after the last real row, then the statistics of
        ``_stack``."""
        import jax.numpy as jnp
        from jax import lax
        S, MB, bs = int(suffix_len), int(max_blocks), int(block_size)

        def prefill(wts, kpools, vpools, states, tails, table, n_past,
                    tokens, true_len, slot):
            pos = n_past + jnp.arange(S)
            valid = jnp.arange(S) < true_len
            wpos = jnp.where(valid, pos, 0)
            blk = jnp.where(valid,
                            table[jnp.minimum(wpos // bs, MB - 1)], 0)
            ctx = _Context(list(zip(kpools, vpools)), states, tails,
                           slots=None, slot=slot, n_past=n_past,
                           true_len=true_len, tables=table, blk=blk,
                           off=wpos % bs)
            x, stats = self._stack(wts, tokens, pos, ctx, valid,
                                   n_past + true_len - 1)
            logits = self._rows_to_logits(wts, lax.dynamic_index_in_dim(
                x, true_len - 1, 0, keepdims=False))
            nxt = jnp.argmax(logits).astype(jnp.int32)
            kp, vp = (list(p) for p in zip(*ctx.kv)) if ctx.kv \
                else ([], [])
            return kp, vp, ctx.states, ctx.tails, jnp.concatenate(
                [nxt[None], stats]), logits

        return prefill

    def _build_paged_step(self, max_batch, max_blocks, block_size):
        """``step(wts, kpools, vpools, states, tails, tables, n_past,
        tokens) -> (kpools, vpools, states, tails, next, logits)``:
        every slot's newest token at its own position; keys and values
        read through the slot's table row (``ops.paged_attention``),
        each Mamba layer's states one token on through the state-step
        kernel.  A slot with nothing cached is idle: it writes to the
        scratch block, leaves its state and tail as they are and is
        routed to no expert.  ``next`` is a token a slot, then the
        statistics."""
        import jax.numpy as jnp
        bs = int(block_size)

        def step(wts, kpools, vpools, states, tails, tables, n_past,
                 tokens):
            blk = jnp.take_along_axis(
                tables, (n_past // bs)[:, None], axis=1)[:, 0]
            ctx = _Context(list(zip(kpools, vpools)), states, tails,
                           slots=tokens.shape[0], tables=tables, blk=blk,
                           off=n_past % bs)
            x, stats = self._stack(wts, tokens, n_past, ctx, n_past > 0,
                                   None)
            logits = self._rows_to_logits(wts, x)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            kp, vp = (list(p) for p in zip(*ctx.kv)) if ctx.kv \
                else ([], [])
            return kp, vp, ctx.states, ctx.tails, jnp.concatenate(
                [nxt, stats]), logits

        return step

    def _decode_workspace_bytes(self, max_batch):
        """One decode step's logits and residual rows, float32."""
        return 4.0 * max_batch * (self.vocab + 8 * self._d)

