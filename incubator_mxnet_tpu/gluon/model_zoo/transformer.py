"""Decoder-only transformer LM built from framework layers/ops.

A model family the reference era predates but today's users expect;
built TPU-first: every matmul (qkv/proj/mlp/head and the two
batch_dot attention products) lands on the MXU, shapes are static
under jit, and the causal mask is an additive constant folded by XLA.
Trains through the same paths as every other Block (Trainer,
ShardedTrainStep's kvstore='tpu' mesh step, bf16 master-weight mode);
for sequence-parallel scale-out the attention core swaps for
parallel.ring_attention (see parallel/ring_attention.py).
"""
import math
import time
from collections import OrderedDict

import numpy as np

from ... import ndarray as nd
from ... import tracing
from ..block import Block
from ..nn import Dense, Dropout, Embedding, LayerNorm

__all__ = ["TransformerLM", "TransformerBlock", "CausalSelfAttention",
           "transformer_lm"]


# --------------------------------------------------------------------------
# decode math shared by the paged-KV serving builders (serving/engine.py).
# Every formula mirrors _build_decode exactly so continuous batching
# emits the same greedy tokens as generate(); the only new ingredient
# is indirection through a block table.  Weights may be int8-quantized
# (serving/quantize.py): a {"q", "s"} dict leaf dequantizes at use.
# --------------------------------------------------------------------------


def _q_mat(w):
    """Dense matrix, dequantized if int8: ``q * s`` per out-channel.
    XLA fuses the dequant into the consuming matmul's weight read."""
    import jax.numpy as jnp
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"][:, None]
    return w


def _q_rows(w, idx):
    """Embedding-table gather; quantized tables dequantize only the
    gathered rows (never the dense table) inside the step."""
    import jax.numpy as jnp
    if isinstance(w, dict):
        return w["q"][idx].astype(jnp.float32) * w["s"][idx][..., None]
    return w[idx]


def _jln(x, gb):
    """LayerNorm over the last axis — same epsilon/formula as the
    ``ln`` closure in _build_decode."""
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * gb[0] + gb[1]


def _ffn_rows(lw, cf, x2d):
    """Dense or MoE FFN on flattened (T, D) tokens — the same
    routing code as training and _build_decode."""
    import jax
    if "moe" in lw:
        from ...ops.moe import moe_ffn_fn
        y, _ = moe_ffn_fn(x2d, *lw["moe"], capacity_factor=cf)
        return y
    return jax.nn.relu(x2d @ _q_mat(lw["up"][0]).T + lw["up"][1]) \
        @ _q_mat(lw["down"][0]).T + lw["down"][1]


def _rope_rows(x, pos, base=10000.0):
    """RoPE for one token per batch row: x (B, H, Dh), pos (B,)
    absolute positions.  The per-slot analog of
    ``ops.matrix.rope_fn(..., offset=i)`` — identical angle formula,
    so paged decode rotates exactly like generate()'s scan step."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _paged_write(pool, blk, off, rows):
    """A paged program's new rows into the donated pool, row ``i`` at
    ``[blk[i], off[i]]``.  The result is only ever returned: with no
    reader inside the program the update is made in place."""
    return pool.at[blk, off].set(rows.astype(pool.dtype))


# once-per-process notice when an explicit ulysses request falls back
_ULYSSES_WARNED = False


def _flash_on_mesh(q, k, v, mesh, n_heads, window):
    """The flash kernel under a multi-device mesh.  GSPMD cannot
    partition a Mosaic kernel, so ``shard_map`` hands each device its
    own (batch*head) rows: the batch over 'dp', the heads over 'tp'
    (attention never mixes either).  q/k/v: (B*H, L, Dh) jax arrays,
    batch-major."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ...ops.flash import flash_attention
    bh, l, dh = q.shape
    spec = P(*(a if a in mesh.axis_names else None
               for a in ("dp", "tp")))

    def local(q, k, v):                        # (b, h, L, Dh) shards
        lb, lh = q.shape[:2]
        out = flash_attention(
            *(t.reshape(lb * lh, l, dh) for t in (q, k, v)),
            causal=True, window=window)
        return out.reshape(lb, lh, l, dh)

    split = (bh // n_heads, n_heads, l, dh)
    # check_vma off: the kernel's out_shape says nothing of mesh axes
    out = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                        out_specs=spec, check_vma=False)(
        *(t.reshape(split) for t in (q, k, v)))
    return out.reshape(bh, l, dh)


class CausalSelfAttention(Block):
    """Multi-head causal self-attention over registry ops.

    With ``seq_parallel=True`` and an ambient mesh whose 'sp' axis is
    >1 (``parallel.use_mesh``), the attention core runs as ring
    attention over the sequence axis (parallel/ring_attention.py):
    K/V blocks rotate around the ring via ppermute while each shard
    holds only L/sp of the sequence — the long-context scale-out
    path.  Falls back to exact local attention off-mesh, and both
    paths compute identical values.
    """

    def __init__(self, d_model, n_heads, seq_parallel=False,
                 rope=False, n_kv_heads=None, attn_window=0,
                 **kwargs):
        super().__init__(**kwargs)
        assert d_model % n_heads == 0
        if seq_parallel not in (False, True, "ring", "ulysses"):
            raise ValueError(
                "seq_parallel must be False/True/'ring'/'ulysses', "
                f"got {seq_parallel!r}")
        if attn_window < 0:
            raise ValueError(
                f"attn_window must be >= 0, got {attn_window}")
        if attn_window and seq_parallel:
            raise ValueError(
                "attn_window with seq_parallel is not supported — "
                "windowed long-context runs single-shard on the "
                "banded flash kernels (O(L*window) already)")
        self._window = int(attn_window)
        kv = n_kv_heads if n_kv_heads is not None else n_heads
        if kv <= 0 or n_heads % kv:
            raise ValueError(
                f"n_heads ({n_heads}) must be a positive multiple of "
                f"n_kv_heads ({kv})")
        self._rope = bool(rope)
        self._d = d_model
        self._h = n_heads
        self._kv = kv
        self._dh = d_model // n_heads
        # True == 'ring' (the default scheme; no head-count constraint)
        self._seq_parallel = "ring" if seq_parallel is True \
            else seq_parallel
        with self.name_scope():
            # grouped-query attention: kv projections carry only
            # n_kv_heads head groups (the KV cache and the k/v
            # parameter cost shrink by n_heads/n_kv_heads)
            self.qkv = Dense(d_model + 2 * kv * self._dh,
                             flatten=False, use_bias=True)
            self.proj = Dense(d_model, flatten=False, use_bias=True)

    @staticmethod
    def _compiled_mesh():
        """The ambient multi-device mesh of a compiled step, or None.
        What runs under it here is raw jax (ring attention, the
        shard_map around the flash kernel), invisible to the
        imperative autograd tape: an eager record()/backward() pass
        must take the registry-op path (identical values, correct
        gradients), while ShardedTrainStep differentiates through
        the raw call via jax.grad."""
        from ... import autograd
        if autograd.is_recording():
            return None
        from ...parallel.mesh import current_mesh
        mesh = current_mesh()
        return mesh if mesh is not None and mesh.size > 1 else None

    def _ring_mesh(self, seq_len):
        """The mesh to ring over, or None to use exact local
        attention.  Ring requires: the flag, a compiled step's mesh
        with sp>1, and a divisible sequence."""
        if not self._seq_parallel:
            return None
        mesh = self._compiled_mesh()
        if (mesh is None or mesh.shape.get("sp", 1) <= 1
                or seq_len % mesh.shape["sp"] != 0):
            return None
        return mesh

    def forward(self, x):
        b, l, d = x.shape
        h, dh, kv = self._h, self._dh, self._kv
        kvd = kv * dh
        qkv = self.qkv(x)                   # (B, L, D + 2*KV*dh)
        q = nd.slice_axis(qkv, axis=2, begin=0, end=d)
        k = nd.slice_axis(qkv, axis=2, begin=d, end=d + kvd)
        v = nd.slice_axis(qkv, axis=2, begin=d + kvd,
                          end=d + 2 * kvd)
        if kv != h:
            # broadcast each kv group to its query heads for compute
            # (the cache/params stay at kv groups — the GQA win)
            rep = h // kv
            k = nd.repeat(k.reshape(b, l, kv, dh), repeats=rep,
                          axis=2).reshape(b, l, h * dh)
            v = nd.repeat(v.reshape(b, l, kv, dh), repeats=rep,
                          axis=2).reshape(b, l, h * dh)

        if self._rope:
            # rotate q/k per head BEFORE any sequence sharding:
            # positions are global along axis 1 (ops/matrix.rope_fn)
            q = nd._internal._rope(
                q.reshape(b, l, h, dh)).reshape(b, l, d)
            k = nd._internal._rope(
                k.reshape(b, l, h, dh)).reshape(b, l, d)

        mesh = self._ring_mesh(l)
        if mesh is not None:
            import jax
            from ...parallel import ring_attention, ulysses_attention
            # ulysses: all-to-all head sharding (needs h % sp == 0;
            # otherwise the ring scheme covers the shape)
            sp_fn = ring_attention
            if self._seq_parallel == "ulysses":
                if h % mesh.shape["sp"] == 0:
                    sp_fn = ulysses_attention
                else:
                    # once per process (a per-layer flag would log
                    # the identical line n_layers times)
                    global _ULYSSES_WARNED
                    if not _ULYSSES_WARNED:
                        from ...utils.log import get_logger
                        get_logger().warning(
                            "seq_parallel='ulysses' needs n_heads "
                            "%% sp == 0 (heads=%d, sp=%d); using "
                            "ring attention instead", h,
                            mesh.shape["sp"])
                        _ULYSSES_WARNED = True
            out = sp_fn(
                q.reshape(b, l, h, dh)._data,
                k.reshape(b, l, h, dh)._data,
                v.reshape(b, l, h, dh)._data, mesh, causal=True)
            if not isinstance(out, jax.core.Tracer):
                # eager: gather off the mesh so downstream ops can mix
                # with single-device parameters (under jit the step's
                # shardings govern instead)
                out = jax.device_put(
                    out, list(x._data.devices())[0])
            return self.proj(nd.NDArray(out).reshape(b, l, d))

        def heads(t):                              # (B, L, D)->(B*H, L, Dh)
            return t.reshape(b, l, h, dh).transpose(
                (0, 2, 1, 3)).reshape(b * h, l, dh)

        q, k, v = heads(q), heads(k), heads(v)
        if self._use_flash():
            # Pallas online-softmax kernel (ops/flash.py): no L x L
            # score tensor in HBM; registry op, so the tape and the
            # compiled paths both differentiate it
            import jax
            mesh = self._compiled_mesh()
            if mesh is not None and isinstance(q._data,
                                               jax.core.Tracer):
                out = nd.NDArray(_flash_on_mesh(
                    q._data, k._data, v._data, mesh, h, self._window))
            else:
                out = nd._internal._flash_attention(
                    q, k, v, causal=True, window=self._window)
        else:
            scores = nd.batch_dot(q, k, transpose_b=True) \
                / math.sqrt(dh)
            diff = np.subtract.outer(np.arange(l), np.arange(l))
            banned = diff < 0      # future
            if self._window:
                # sliding window: query i sees (i - window, i]
                banned |= diff >= self._window
            mask = nd.array(
                np.where(banned, -1e9, 0.0).astype(np.float32))
            scores = nd.broadcast_add(scores, mask.expand_dims(0))
            att = nd.softmax(scores, axis=-1)
            out = nd.batch_dot(att, v)             # (B*H, L, Dh)
        out = out.reshape(b, h, l, dh).transpose(
            (0, 2, 1, 3)).reshape(b, l, d)
        return self.proj(out)

    @staticmethod
    def _use_flash():
        import os

        import jax
        flag = os.environ.get("MXTPU_FLASH", "auto")
        if flag in ("1", "0"):
            return flag == "1"
        return jax.default_backend() == "tpu"


class MoEFFN(Block):
    """Mixture-of-Experts FFN (GShard top-2; see ops/moe.py).

    Expert weights are STACKED over a leading expert dimension —
    (E, H, D)/(E, D, H) — so the expert compute is one batched MXU
    contraction and the 'ep' mesh axis shards dimension 0 (the
    expert-parallel rules in parallel/sharding.py); GSPMD then
    derives the token all-to-alls.  ``forward`` returns the output
    AND exposes the load-balance aux loss as ``self.last_aux`` (read
    it in the same forward pass; add ~1e-2 of it to the loss).
    """

    def __init__(self, d_model, num_experts, hidden,
                 capacity_factor=1.25, **kwargs):
        super().__init__(**kwargs)
        self._cf = float(capacity_factor)
        self.num_experts = num_experts
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, d_model))
            self.expert_up_weight = self.params.get(
                "expert_up_weight", shape=(num_experts, hidden,
                                           d_model))
            self.expert_up_bias = self.params.get(
                "expert_up_bias", shape=(num_experts, hidden),
                init="zeros")
            self.expert_down_weight = self.params.get(
                "expert_down_weight", shape=(num_experts, d_model,
                                             hidden))
            self.expert_down_bias = self.params.get(
                "expert_down_bias", shape=(num_experts, d_model),
                init="zeros")

    def forward(self, x):                      # (B, L, D)
        b, l, d = x.shape
        y, aux = nd._internal._moe_ffn(
            x.reshape(b * l, d), self.router_weight.data(),
            self.expert_up_weight.data(),
            self.expert_up_bias.data(),
            self.expert_down_weight.data(),
            self.expert_down_bias.data(),
            capacity_factor=self._cf)
        self.last_aux = aux
        return y.reshape(b, l, d)


class TransformerBlock(Block):
    """Pre-norm attention + MLP with residuals (GPT-2 layout).

    ``moe_experts > 0`` swaps the dense MLP for a top-2-routed
    Mixture-of-Experts FFN (MoEFFN); the block then exposes the
    router's load-balance loss as ``self.last_aux``.
    """

    def __init__(self, d_model, n_heads, mlp_ratio=4, dropout=0.0,
                 seq_parallel=False, moe_experts=0,
                 moe_capacity_factor=1.25, rope=False,
                 n_kv_heads=None, attn_window=0, **kwargs):
        super().__init__(**kwargs)
        self.moe_experts = moe_experts
        with self.name_scope():
            self.ln1 = LayerNorm()
            self.attn = CausalSelfAttention(d_model, n_heads,
                                            seq_parallel=seq_parallel,
                                            rope=rope,
                                            n_kv_heads=n_kv_heads,
                                            attn_window=attn_window)
            self.ln2 = LayerNorm()
            if moe_experts:
                self.moe = MoEFFN(d_model, moe_experts,
                                  mlp_ratio * d_model,
                                  capacity_factor=moe_capacity_factor)
            else:
                self.up = Dense(mlp_ratio * d_model, flatten=False,
                                activation="relu")
                self.down = Dense(d_model, flatten=False)
            self.drop = Dropout(dropout)

    def forward(self, x):
        x = x + self.drop(self.attn(self.ln1(x)))
        if self.moe_experts:
            y = self.moe(self.ln2(x))
            self.last_aux = self.moe.last_aux
            return x + self.drop(y)
        return x + self.drop(self.down(self.up(self.ln2(x))))


class TransformerLM(Block):
    """Token-in, logits-out decoder LM.

    Parameters: vocab_size, d_model, n_layers, n_heads, max_len
    (learned positions), mlp_ratio, dropout.
    """

    def __init__(self, vocab_size, d_model=512, n_layers=6,
                 n_heads=8, max_len=1024, mlp_ratio=4, dropout=0.0,
                 seq_parallel=False, moe_experts=0,
                 moe_capacity_factor=1.25, pos="learned",
                 n_kv_heads=None, attn_window=0, **kwargs):
        super().__init__(**kwargs)
        if pos not in ("learned", "rope"):
            raise ValueError(
                f"pos must be 'learned' or 'rope', got {pos!r}")
        self._d = d_model
        self._max_len = max_len
        self._mlp_ratio = mlp_ratio
        self._pos_kind = pos
        self.moe_experts = moe_experts
        with self.name_scope():
            self.embed = Embedding(vocab_size, d_model)
            if pos == "learned":
                self.pos = Embedding(max_len, d_model)
            self.blocks = [
                TransformerBlock(d_model, n_heads, mlp_ratio, dropout,
                                 seq_parallel=seq_parallel,
                                 moe_experts=moe_experts,
                                 moe_capacity_factor=
                                 moe_capacity_factor,
                                 rope=(pos == "rope"),
                                 n_kv_heads=n_kv_heads,
                                 attn_window=attn_window)
                for _ in range(n_layers)]
            for i, blk in enumerate(self.blocks):
                setattr(self, f"block{i}", blk)   # register children
            self.ln_f = LayerNorm()
            self.head = Dense(vocab_size, flatten=False,
                              use_bias=False)
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads or n_heads
        self.attn_window = int(attn_window)

    def forward(self, tokens):
        """Logits (B, L, V); with ``moe_experts`` the return is
        ``[logits, aux]`` where aux is the summed router load-balance
        loss — add ``~1e-2 * aux`` to the training loss."""
        b, l = tokens.shape
        if l > self._max_len:
            raise ValueError(
                f"sequence {l} exceeds max_len {self._max_len}")
        x = self.embed(tokens) * math.sqrt(self._d)
        if self._pos_kind == "learned":
            pos = nd.arange(l).astype("int32")
            x = nd.broadcast_add(x, self.pos(pos).expand_dims(0))
        aux = None
        for blk in self.blocks:
            x = blk(x)
            if self.moe_experts:
                aux = blk.last_aux if aux is None \
                    else aux + blk.last_aux
        logits = self.head(self.ln_f(x))
        return [logits, aux] if self.moe_experts else logits

    # ------------------------------------------------------------ decode
    _GEN_CACHE_MAX = 16   # compiled decode executables kept (LRU)

    def generate(self, tokens, max_new_tokens, temperature=0.0,
                 top_k=0, top_p=1.0, rng=None):
        """Autoregressive decode with a KV cache, TPU-native: ONE
        batched prefill forward seeds the cache for the whole prompt,
        then ONE ``lax.scan`` emits the new tokens.  Static shapes
        throughout; compiled once per (batch, prompt_len,
        max_new_tokens, sampling-config) signature (bounded FIFO of
        executables — pad prompts to a few fixed lengths and keep the
        sampling config stable to maximise compile reuse).

        tokens : (B, P) int NDArray/numpy prompt
        temperature : 0 -> greedy argmax, >0 -> categorical sample
        top_k : keep only the k highest-probability tokens (0 = all)
        top_p : nucleus sampling — keep the smallest set of tokens
            whose cumulative probability exceeds top_p (1.0 = all)
        returns (B, P + max_new_tokens) int32 NDArray
        """
        import jax
        import jax.numpy as jnp

        if top_k < 0:
            raise ValueError(f"top_k must be >= 0 (got {top_k})")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1] (got {top_p})")
        toks_np = np.asarray(
            tokens.asnumpy() if hasattr(tokens, "asnumpy")
            else tokens).astype(np.int32)
        b, p = toks_np.shape
        total = p + int(max_new_tokens)
        if total > self._max_len:
            raise ValueError(
                f"prompt+new = {total} exceeds max_len "
                f"{self._max_len}")

        from ..parameter import DeferredInitializationError
        try:
            wts = self._decode_weights()
        except DeferredInitializationError:
            # deferred-init params (LayerNorm shapes): settle with a
            # tiny probe forward, as functionalize does
            from ... import autograd
            with autograd.pause():
                self.forward(nd.NDArray(jnp.zeros((1, 1), jnp.int32)))
            wts = self._decode_weights()

        sampling = temperature > 0
        # greedy ignores the sampling filters: normalize them out of
        # the compile key so greedy callers share one executable
        key = (b, p, int(max_new_tokens), sampling,
               int(top_k) if sampling else 0,
               float(top_p) if sampling else 1.0)
        cache = getattr(self, "_gen_cache", None)
        if not isinstance(cache, OrderedDict):
            # true LRU, not FIFO: an alternating pair of hot
            # signatures at capacity must not thrash recompiles
            cache = self._gen_cache = OrderedDict(cache or {})
        fn = cache.get(key)
        missed = fn is None
        t0 = time.monotonic()
        if missed:
            if len(cache) >= self._GEN_CACHE_MAX:
                cache.popitem(last=False)       # least recently used
            fn = cache[key] = jax.jit(self._build_decode(
                b, p, int(max_new_tokens), temperature > 0,
                top_k=int(top_k), top_p=float(top_p)))
        else:
            cache.move_to_end(key)              # refresh on hit
        if rng is None:
            rng = jax.random.PRNGKey(0)
        out = fn(wts, jnp.asarray(toks_np),
                 jnp.asarray(float(temperature or 1.0), jnp.float32),
                 rng)
        if missed:
            # jax.jit traces lazily: build + first call is the real
            # compile wall time this signature cost (compile ledger
            # attributes the miss — shape vs decode-config change)
            tracing.compile_ledger("transformer_generate").record(
                {"shape": (b, p),
                 "static_arg": (int(max_new_tokens), sampling,
                                key[4], key[5])},
                time.monotonic() - t0)
        return nd.NDArray(out)

    def _decode_weights(self):
        def w(param):
            return param.data()._data

        layers = []
        for blk in self.blocks:
            lw = dict(
                ln1=(w(blk.ln1.gamma), w(blk.ln1.beta)),
                qkv=(w(blk.attn.qkv.weight), w(blk.attn.qkv.bias)),
                proj=(w(blk.attn.proj.weight), w(blk.attn.proj.bias)),
                ln2=(w(blk.ln2.gamma), w(blk.ln2.beta)))
            if blk.moe_experts:
                lw["moe"] = (w(blk.moe.router_weight),
                             w(blk.moe.expert_up_weight),
                             w(blk.moe.expert_up_bias),
                             w(blk.moe.expert_down_weight),
                             w(blk.moe.expert_down_bias))
            else:
                lw["up"] = (w(blk.up.weight), w(blk.up.bias))
                lw["down"] = (w(blk.down.weight), w(blk.down.bias))
            layers.append(lw)
        wts = dict(embed=w(self.embed.weight),
                   ln_f=(w(self.ln_f.gamma), w(self.ln_f.beta)),
                   head=w(self.head.weight), layers=layers)
        if self._pos_kind == "learned":
            wts["pos"] = w(self.pos.weight)
        return wts

    def _build_decode(self, b, p, max_new, sample, top_k=0,
                      top_p=1.0):
        import jax
        import jax.numpy as jnp
        from jax import lax

        d, h = self._d, self.n_heads
        dh = d // h
        kv = self.n_kv_heads
        rep = h // kv
        kvd = kv * dh
        total = p + max_new
        scale = math.sqrt(d)
        use_rope = self._pos_kind == "rope"
        window = self.attn_window
        from ...ops.matrix import rope_fn

        # LayerNorm / FFN math is the module-level _jln/_ffn_rows —
        # one implementation shared with the paged serving builders,
        # so generate() and the serving engine can never diverge.
        # Capacity factors are STATIC per layer (compile-time), not
        # part of the traced weights pytree.
        cfs = [blk.moe._cf if blk.moe_experts else None
               for blk in self.blocks]

        def restrict(logits):
            """top-k / nucleus filtering on (B, V) logits."""
            if top_k and top_k < logits.shape[-1]:
                kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p < 1.0:
                sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(sorted_l, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # number of tokens needed to reach top_p (>= 1)
                k_eff = jnp.maximum(
                    jnp.sum(cum - probs < top_p, axis=-1,
                            keepdims=True), 1)
                cutoff = jnp.take_along_axis(sorted_l, k_eff - 1,
                                             axis=-1)
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
            return logits

        def pick(logits, temp, rng):
            if sample:
                rng, sub = jax.random.split(rng)
                nxt = jax.random.categorical(
                    sub, restrict(logits / temp))
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return nxt.astype(jnp.int32), rng

        def prefill(wts, prompt):
            """Batched forward over the whole prompt: seeds the KV
            caches in one pass and returns the last position's
            logits (same math as the per-token step)."""
            x = wts["embed"][prompt] * scale       # (B, P, D)
            if not use_rope:
                x = x + wts["pos"][jnp.arange(p)]
            diff = jnp.arange(p)[:, None] - jnp.arange(p)[None, :]
            mask = diff >= 0
            if window:
                # decode must mask exactly like training
                mask &= diff < window
            caches = []
            for lw, cf in zip(wts["layers"], cfs):
                xa = _jln(x, lw["ln1"])
                qkv = xa @ lw["qkv"][0].T + lw["qkv"][1]
                q = qkv[..., :d].reshape(b, p, h, dh)
                k = qkv[..., d:d + kvd].reshape(b, p, kv, dh)
                v = qkv[..., d + kvd:].reshape(b, p, kv, dh)
                if use_rope:
                    q, k = rope_fn(q), rope_fn(k)
                q = q.transpose(0, 2, 1, 3)
                k = k.transpose(0, 2, 1, 3)
                v = v.transpose(0, 2, 1, 3)
                # GQA: the cache holds only kv head groups
                kc = jnp.zeros((b, kv, total, dh),
                               jnp.float32).at[:, :, :p].set(k)
                vc = jnp.zeros((b, kv, total, dh),
                               jnp.float32).at[:, :, :p].set(v)
                # grouped einsum straight against the kv-group
                # tensors: the h-head repeat is never materialized
                qg = q.reshape(b, kv, rep, p, dh)
                s = jnp.einsum("bkrqd,bkcd->bkrqc", qg, k) \
                    / math.sqrt(dh)
                att = jax.nn.softmax(
                    jnp.where(mask[None, None, None], s, -1e9),
                    axis=-1)
                o = jnp.einsum("bkrqc,bkcd->bkrqd", att, v)
                o = o.reshape(b, h, p, dh) \
                    .transpose(0, 2, 1, 3).reshape(b, p, d)
                x = x + o @ lw["proj"][0].T + lw["proj"][1]
                xm = _jln(x, lw["ln2"])
                x = x + _ffn_rows(lw, cf, xm.reshape(b * p, d)) \
                    .reshape(b, p, d)
                caches.append((kc, vc))
            logits = _jln(x[:, -1], wts["ln_f"]) @ wts["head"].T
            return caches, logits

        def decode(wts, prompt, temp, rng):
            caches, logits = prefill(wts, prompt)
            first, rng = pick(logits, temp, rng)
            toks = jnp.zeros((b, total), jnp.int32)
            toks = toks.at[:, :p].set(prompt)
            toks = toks.at[:, p].set(first)

            def step(carry, i):
                toks, caches, rng = carry
                tok = lax.dynamic_index_in_dim(toks, i, axis=1,
                                               keepdims=False)
                x = wts["embed"][tok] * scale
                if not use_rope:
                    x = x + wts["pos"][i]
                new_caches = []
                for (lw, cf), (kc, vc) in zip(
                        zip(wts["layers"], cfs), caches):
                    xa = _jln(x, lw["ln1"])
                    qkv = xa @ lw["qkv"][0].T + lw["qkv"][1]
                    q = qkv[..., :d]
                    k = qkv[..., d:d + kvd]
                    v = qkv[..., d + kvd:]
                    if use_rope:
                        # this token sits at absolute position i
                        q = rope_fn(q.reshape(b, 1, h, dh),
                                    offset=i).reshape(b, h, dh)
                        k = rope_fn(k.reshape(b, 1, kv, dh),
                                    offset=i).reshape(b, kv, dh)
                    else:
                        q = q.reshape(b, h, dh)
                        k = k.reshape(b, kv, dh)
                    kc = lax.dynamic_update_index_in_dim(
                        kc, k, i, axis=2)
                    vc = lax.dynamic_update_index_in_dim(
                        vc, v.reshape(b, kv, dh), i, axis=2)
                    qg = q.reshape(b, kv, rep, dh)
                    s = jnp.einsum("bkrd,bkcd->bkrc", qg, kc) \
                        / math.sqrt(dh)
                    cpos = jnp.arange(total)[None, None, None]
                    keep = cpos <= i
                    if window:
                        keep &= cpos > i - window
                    s = jnp.where(keep, s, -1e9)
                    att = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum("bkrc,bkcd->bkrd", att, vc) \
                        .reshape(b, h, dh)
                    x = x + o.reshape(b, d) @ lw["proj"][0].T \
                        + lw["proj"][1]
                    xm = _jln(x, lw["ln2"])
                    x = x + _ffn_rows(lw, cf, xm)
                    new_caches.append((kc, vc))
                logits = _jln(x, wts["ln_f"]) @ wts["head"].T
                nxt, rng = pick(logits, temp, rng)
                toks = lax.dynamic_update_index_in_dim(
                    toks, nxt, i + 1, axis=1)
                return (toks, new_caches, rng), None

            # positions p .. total-2 each consume the token at i and
            # emit the one at i+1 (the prefill already emitted p)
            if max_new > 1:
                (toks, _, _), _ = lax.scan(
                    step, (toks, caches, rng),
                    jnp.arange(p, total - 1))
            return toks

        return decode

    # ---------------------------------------------------- paged decode
    # Block-table variants of prefill/step for the serving tier
    # (serving/engine.py, docs/serving.md).  KV lives in fixed pools
    # of shape (num_blocks, block_size, kv_heads * head_dim) per
    # layer, a cached row in whole lanes; a request's context is the
    # ordered block-id row it owns.  The programs go through the block
    # table INSIDE the jitted function, so admission/retirement never
    # changes the traced signature — one compiled step per
    # (max_batch, max_blocks) forever.  A program's new rows are
    # written into the donated pools and read by nothing else in it
    # (it attends over the pools as they came in plus the rows it
    # holds), so the write stays in place and no pool is ever copied.

    # what ServingEngine asks of a model beside the two builders
    # below (docs/serving.md, "The paged protocol")
    _paged_int8 = True      # the builders dequantize {"q", "s"} leaves

    def _paged_cache(self):
        """What one token leaves in one layer's cache: its keys and
        its values, every kv head's side by side in one row
        (``kv_heads * head_dim`` lanes: the layout the decode read
        walks, ops/paged_attention.py), float32, a pool each."""
        row = (self.n_kv_heads * (self._d // self.n_heads),)
        return tuple({"name": n, "shape": row, "dtype": "float32"}
                     for n in ("k", "v"))

    def _decode_workspace_bytes(self, max_batch):
        """One decode step's logits and residual stream, float32."""
        return 4.0 * max_batch * (self.head._units + 8 * self._d)

    def _paged_read(self, block_size, platform):
        """Which read of the cache the decode step takes where it is
        traced now and lowered for ``platform``, with the shapes that
        decide it (``ops.paged_attention.read_kind``): what the
        engine's ``serve_paged_read`` event carries."""
        from ...ops.paged_attention import read_kind
        h, kv = self.n_heads, self.n_kv_heads
        dh = self._d // h
        dtype = self._paged_cache()[0]["dtype"]
        return dict(read=read_kind(h, kv, dh, int(block_size), dtype,
                                   platform),
                    heads=h, kv_heads=kv, head_dim=dh, dtype=dtype)

    def _check_paged(self):
        if self.attn_window:
            raise NotImplementedError(
                "paged serving over sliding-window attention is not "
                "implemented — serve attn_window=0 models, or decode "
                "via generate()")
        if self.moe_experts:
            # top-2 routing sets expert capacity from the BATCH of
            # tokens in flight: concurrent slots contend for
            # capacity a sequential generate() call never sees, so
            # served logits would depend on batch occupancy and the
            # greedy-equivalence contract would silently break
            raise NotImplementedError(
                "paged serving of capacity-dropping routing "
                "(MoEFFN over ops.moe.moe_ffn_fn) is not implemented "
                "— shared expert capacity makes logits depend on "
                "batchmates; decode it via generate(), or serve "
                "dropless routing (model_zoo.latent_moe.LatentMoELM "
                "over ops.moe.routed_ffn_fn)")

    def _build_paged_prefill(self, suffix_len, max_blocks,
                             block_size):
        """Suffix prefill over the block-table cache.

        One traced signature per padded suffix length: embeds ``S``
        suffix tokens at absolute positions ``n_past + i``, writes
        their K/V into the request's blocks (``_paged_write``: in
        place, read by nothing in the program), and attends over the
        row's context as the pools held it on entry, gathered through
        the table, with the suffix's own rows laid over positions
        ``n_past ..`` of the gathered copy — ``n_past = 0`` is a full
        prefill; ``n_past > 0`` resumes after a prefix-cache hit
        without recomputing the shared blocks.  Rows past
        ``true_len`` are padding: they are written to the scratch
        block (id 0), no real row sees them, and their outputs are
        discarded.

        Returns ``prefill(wts, kpools, vpools, table, n_past,
        tokens, true_len) -> (kpools, vpools, next_token, logits)``
        where ``next_token`` is the greedy argmax after the last
        real suffix token.
        """
        import jax
        import jax.numpy as jnp
        from jax import lax

        self._check_paged()
        d, h = self._d, self.n_heads
        dh = d // h
        kv = self.n_kv_heads
        rep = h // kv
        kvd = kv * dh
        scale = math.sqrt(d)
        use_rope = self._pos_kind == "rope"
        max_len = self._max_len
        from ...ops.matrix import rope_fn
        from ...ops.paged_attention import gathered_context
        S, MB, bs = int(suffix_len), int(max_blocks), int(block_size)
        C = MB * bs
        cfs = [blk.moe._cf if blk.moe_experts else None
               for blk in self.blocks]

        def prefill(wts, kpools, vpools, table, n_past, tokens,
                    true_len):
            x = _q_rows(wts["embed"], tokens) * scale       # (S, D)
            pos = n_past + jnp.arange(S)
            if not use_rope:
                x = x + _q_rows(wts["pos"],
                                jnp.minimum(pos, max_len - 1))
            valid = jnp.arange(S) < true_len
            wpos = jnp.where(valid, pos, 0)
            blk = jnp.where(
                valid, table[jnp.minimum(wpos // bs, MB - 1)], 0)
            off = wpos % bs
            keep = jnp.arange(C)[None, :] <= pos[:, None]   # (S, C)

            def context(pool, rows):
                # the row's context as the pool came in, the suffix's
                # own rows over positions n_past .. of the copy
                return gathered_context(pool, table, n_past, rows) \
                    .reshape(C, kv, dh).transpose(1, 0, 2)

            new_k, new_v = [], []
            for li, (lw, cf) in enumerate(zip(wts["layers"], cfs)):
                xa = _jln(x, lw["ln1"])
                qkvm = xa @ _q_mat(lw["qkv"][0]).T + lw["qkv"][1]
                q = qkvm[:, :d].reshape(S, h, dh)
                k = qkvm[:, d:d + kvd].reshape(S, kv, dh)
                v = qkvm[:, d + kvd:].reshape(S, kv, dh)
                if use_rope:
                    q = rope_fn(q[None], offset=n_past)[0]
                    k = rope_fn(k[None], offset=n_past)[0]
                kp = _paged_write(kpools[li], blk, off,
                                  k.reshape(S, kvd))
                vp = _paged_write(vpools[li], blk, off,
                                  v.reshape(S, kvd))
                kc = context(kpools[li], k.reshape(S, kvd))
                vc = context(vpools[li], v.reshape(S, kvd))
                qg = q.transpose(1, 0, 2).reshape(kv, rep, S, dh)
                s = jnp.einsum("krsd,kcd->krsc", qg, kc) \
                    / math.sqrt(dh)
                att = jax.nn.softmax(
                    jnp.where(keep[None, None], s, -1e9), axis=-1)
                o = jnp.einsum("krsc,kcd->krsd", att, vc)
                o = o.reshape(h, S, dh).transpose(1, 0, 2) \
                    .reshape(S, d)
                x = x + o @ _q_mat(lw["proj"][0]).T + lw["proj"][1]
                xm = _jln(x, lw["ln2"])
                x = x + _ffn_rows(lw, cf, xm)
                new_k.append(kp)
                new_v.append(vp)
            xl = lax.dynamic_index_in_dim(x, true_len - 1, 0,
                                          keepdims=False)
            logits = _jln(xl, wts["ln_f"]) @ _q_mat(wts["head"]).T
            nxt = jnp.argmax(logits).astype(jnp.int32)
            return new_k, new_v, nxt, logits

        return prefill

    def _build_paged_step(self, max_batch, max_blocks, block_size):
        """One continuous-batching decode step over the block pool.

        Feeds every slot's newest token at its own position, writes
        the new K/V through each slot's block-table row
        (``_paged_write``: in place, read by nothing in the program)
        and attends over the slot's cached positions ``0 .. n_past -
        1`` as the pools held them on entry plus the step's own key
        and value (``ops.paged_attention.decode_attention``).  Where
        the shapes can be tiled and the step is lowered for a TPU
        that read is a kernel that walks each slot's blocks through
        the table as far as ``n_past`` and never materialises a
        context; anywhere else (another platform, a matmul precision
        above the platform's default in force at the trace) it is the
        plain gather of ``max_blocks * block_size`` positions a slot
        (``_paged_read`` says which).  Inactive slots ride along with
        ``n_past = 0`` and an all-scratch row — their writes land in
        block 0, they read no block, and their outputs are ignored
        by the host — so the step needs NO liveness branch and
        admission/retirement reuse the one compiled executable.

        Returns ``step(wts, kpools, vpools, tables, n_past, tokens)
        -> (kpools, vpools, next_tokens, logits)`` (greedy argmax
        per slot).
        """
        import jax.numpy as jnp

        self._check_paged()
        d, h = self._d, self.n_heads
        dh = d // h
        kv = self.n_kv_heads
        kvd = kv * dh
        scale = math.sqrt(d)
        use_rope = self._pos_kind == "rope"
        B, MB, bs = int(max_batch), int(max_blocks), int(block_size)
        cfs = [blk.moe._cf if blk.moe_experts else None
               for blk in self.blocks]
        from ...ops.paged_attention import decode_attention

        def step(wts, kpools, vpools, tables, n_past, tokens):
            x = _q_rows(wts["embed"], tokens) * scale       # (B, D)
            if not use_rope:
                x = x + _q_rows(wts["pos"], n_past)
            blk = jnp.take_along_axis(
                tables, (n_past // bs)[:, None], axis=1)[:, 0]
            off = n_past % bs
            new_k, new_v = [], []
            for li, (lw, cf) in enumerate(zip(wts["layers"], cfs)):
                xa = _jln(x, lw["ln1"])
                qkvm = xa @ _q_mat(lw["qkv"][0]).T + lw["qkv"][1]
                q = qkvm[:, :d].reshape(B, h, dh)
                k = qkvm[:, d:d + kvd]
                v = qkvm[:, d + kvd:]
                if use_rope:
                    q = _rope_rows(q, n_past)
                    k = _rope_rows(k.reshape(B, kv, dh),
                                   n_past).reshape(B, kvd)
                kp = _paged_write(kpools[li], blk, off, k)
                vp = _paged_write(vpools[li], blk, off, v)
                o = decode_attention(q, k, v, kpools[li], vpools[li],
                                     tables, n_past)
                x = x + o @ _q_mat(lw["proj"][0]).T + lw["proj"][1]
                xm = _jln(x, lw["ln2"])
                x = x + _ffn_rows(lw, cf, xm)
                new_k.append(kp)
                new_v.append(vp)
            logits = _jln(x, wts["ln_f"]) @ _q_mat(wts["head"]).T
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return new_k, new_v, nxt, logits

        return step


def transformer_lm(vocab_size=32000, size="small", **kwargs):
    """Factory: 'small' (125M-class), 'medium' (350M-class),
    'modern' (the rope + grouped-query configuration today's
    decoder LMs ship with), or pass explicit dims via kwargs."""
    presets = {
        "small": dict(d_model=768, n_layers=12, n_heads=12),
        "medium": dict(d_model=1024, n_layers=24, n_heads=16),
        "modern": dict(d_model=768, n_layers=12, n_heads=12,
                       n_kv_heads=4, pos="rope"),
    }
    if size not in presets:
        raise ValueError(
            f"unknown size {size!r}; presets: {sorted(presets)} "
            "(pass explicit dims via kwargs with any preset)")
    cfg = dict(presets[size])
    cfg.update(kwargs)
    return TransformerLM(vocab_size, **cfg)
