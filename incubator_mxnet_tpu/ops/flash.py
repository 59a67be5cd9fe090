"""Flash attention: a Pallas TPU kernel for the attention hot op.

The role the reference fills with hand-written CUDA for its hot ops
(ref: src/operator/*-inl.cuh), done the TPU way: a tiled
online-softmax kernel (Flash Attention) that keeps the O(L^2) score
matrix out of HBM — each (query-tile, key-tile) block is materialized
only in VMEM, with running max/denominator carried across key tiles.

STREAMING design (r5): the key/value (and in the backward, query)
sequence walks through VMEM one block per grid step — the inner grid
dimension is the tile loop, and the online-softmax carry (m, l, acc)
lives in VMEM scratch that persists across grid steps (TPU grids are
sequential).  VMEM use is O(block), independent of sequence length,
so the same kernel covers the long-context regime; the earlier
whole-sequence-staging version hit the ~16 MB VMEM wall near
L*D ~ 2^20 (r4 advisor).

Registered as the differentiable op ``_flash_attention`` so both the
eager tape and compiled paths use it; the backward is the tiled
FlashAttention recipe too — dq/dk/dv kernels rebuild each P tile from
the forward's log-sum-exp residual (delta = rowsum(g*o)), so no L x L
tensor exists in HBM on either direction.

Where a call is placed anywhere but on a TPU the kernel runs in
Pallas interpret mode (tests exercise it on CPU); numerics match the
reference implementation to float32 tolerance either way.
"""
import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax import lax

from .registry import defop

__all__ = ["flash_attention"]

_NEG = -1e30

# Mosaic's block-tiling rule wants the last two dims of every block
# (8k, 128k)-shaped or equal to the array's; per-row residuals (lse,
# delta) therefore carry a small trailing lane dim instead of being
# (BH, L) vectors — lane 0 holds the value, the rest are broadcast
# copies.  8 sublanes * 4 B is noise next to q/k/v.
_LANES = 8


def _reference_attention(q, k, v, causal, scale, window=0):
    """Plain XLA attention, the numeric oracle + backward path.
    q/k/v: (BH, L, D).  window > 0: sliding-window causal — query i
    attends to keys (i - window, i]."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    lq, lk = q.shape[1], k.shape[1]
    qp = jnp.arange(lq)[:, None]
    kp = jnp.arange(lk)[None, :]
    if causal:
        mask = qp >= kp
        if window > 0:
            mask &= (qp - kp) < window
        s = jnp.where(mask[None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _causal_mask(s, iq, jk, bq, bk, window=0):
    q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = jk * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = q_pos >= k_pos
    if window > 0:
        keep &= (q_pos - k_pos) < window
    return jnp.where(keep, s, _NEG)


def _block_live(iq, jk, bq, bk, causal, window):
    """Does the (q-tile iq, k-tile jk) block hold ANY unmasked pair?
    Dead blocks skip their FLOPs (the grid still steps through)."""
    if not causal:
        return True
    live = jk * bk <= (iq + 1) * bq - 1        # not above diagonal
    if window > 0:
        # below the band: newest key in tile >= oldest in-window key
        live &= (jk + 1) * bk - 1 >= iq * bq - window + 1
    return live


def _band_nj(window, b_res, b_str, n_str):
    """Inner-grid length for banded (sliding-window) iteration: the
    resident tile of size b_res sees at most window + b_res - 1
    streamed positions -> this many b_str-tiles (+1 for alignment),
    capped at the full count."""
    return min(n_str, (b_res + window - 2) // b_str + 2)


def _band_base_k(iq, bq, bk, window):
    """First k-tile of q-tile iq's band (k >= iq*bq - window + 1)."""
    return jnp.maximum((iq * bq - (window - 1)) // bk, 0)


def _band_k_index(iq, j, bq, bk, nk, window):
    """(k-tile, valid) for inner step j of q-tile iq.  Clamped so the
    DMA index stays in range; `valid` excludes clamp duplicates and
    tiles past the causal diagonal."""
    base = _band_base_k(iq, bq, bk, window)
    last = jnp.minimum(((iq + 1) * bq - 1) // bk, nk - 1)
    jk = jnp.minimum(base + j, nk - 1)
    return jk, base + j <= last


def _band_q_index(jk, j, bq, bk, nq, window):
    """(q-tile, valid) for inner step j of k-tile jk (dkv grid)."""
    base = (jk * bk) // bq
    last = jnp.minimum(((jk + 1) * bk - 1 + window - 1) // bq,
                       nq - 1)
    iq = jnp.minimum(base + j, nq - 1)
    return iq, base + j <= last


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                acc_sc, *, bq, bk, nk, nj, causal, scale, window):
    """grid = (BH, NQ, NK): one (q-tile, k-tile) block per step; the
    k dimension is innermost, so the online-softmax carry streams
    through the scratch accumulators."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    j = pl.program_id(2)
    if window > 0:
        # banded: the inner grid walks only the in-window k tiles
        jk, valid = _band_k_index(iq, j, bq, bk, nk, window)
        live = valid
    else:
        jk = j
        live = _block_live(iq, jk, bq, bk, causal, window)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # (BQ, D)
        kb = k_ref[0].astype(jnp.float32)             # (BK, D)
        vb = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, iq, jk, bq, bk, window)
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_sc[...] = m_new
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1,
                                                keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
            p, vb, preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        # log-sum-exp residual: what the backward needs to rebuild P
        # tile-by-tile without the L x L score matrix
        lse = m_sc[...][:, 0:1] + jnp.log(l[:, 0:1])   # (BQ, 1)
        lse_ref[0] = jnp.broadcast_to(lse, (bq, _LANES))


def _flash_fwd(q, k, v, causal, scale, interpret, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..rtc import pallas_call

    bh, lq, d = q.shape
    lk = k.shape[1]
    bq = min(128, lq)
    bk = min(128, lk)
    nk = lk // bk
    # banded (window > 0): the inner grid covers ONLY in-window k
    # tiles — dead tiles are neither stepped nor DMA'd, so compute
    # AND HBM traffic are O(L * window)
    nj = _band_nj(window, bq, bk, nk) if window > 0 else nk
    if window > 0:
        def kmap(b, i, j):
            return (b, _band_k_index(i, j, bq, bk, nk, window)[0], 0)
    else:
        def kmap(b, i, j):
            return (b, j, 0)
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk,
                               nj=nj, causal=causal, scale=scale,
                               window=window)
    o, lse = pallas_call(
        kernel,
        grid=(bh, lq // bq, nj),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, d), kmap),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, lq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
               dq_ref, dq_sc, *, bq, bk, nk, nj, causal, scale,
               window):
    """grid = (BH, NQ, NK): k/v stream past a resident q tile; dq
    accumulates in scratch."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    j = pl.program_id(2)
    if window > 0:
        jk, live = _band_k_index(iq, j, bq, bk, nk, window)
    else:
        jk = j
        live = _block_live(iq, jk, bq, bk, causal, window)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (BQ, D)
        g = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]                      # (BQ, 1)
        delta = delta_ref[0][:, 0:1]
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, kb.T,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, jk, bq, bk, window)
        p = jnp.exp(s - lse)
        dp = jnp.dot(g, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_sc[...] = dq_sc[...] + jnp.dot(
            ds, kb, preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, bq, bk, nq, nj,
                causal, scale, window):
    """grid = (BH, NK, NQ): q/g/lse/delta stream past a resident k/v
    tile; dk/dv accumulate in scratch."""
    from jax.experimental import pallas as pl

    jk = pl.program_id(1)
    j = pl.program_id(2)
    if window > 0:
        iq, live = _band_q_index(jk, j, bq, bk, nq, window)
    else:
        iq = j
        live = _block_live(iq, jk, bq, bk, causal, window)

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(live)
    def _step():
        kb = k_ref[0].astype(jnp.float32)             # (BK, D)
        vb = v_ref[0].astype(jnp.float32)
        qb = q_ref[0].astype(jnp.float32)             # (BQ, D)
        gb = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]                      # (BQ, 1)
        delta = delta_ref[0][:, 0:1]
        s = jnp.dot(qb, kb.T,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, jk, bq, bk, window)
        p = jnp.exp(s - lse)
        dv_sc[...] = dv_sc[...] + jnp.dot(
            p.T, gb, preferred_element_type=jnp.float32)
        dp = jnp.dot(gb, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_sc[...] = dk_sc[...] + jnp.dot(
            ds.T, qb, preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal, scale, interpret,
               window=0):
    """Tiled backward: rebuilds each P tile from (q, k, lse) — no
    L x L tensor in HBM on the gradient path either (the FlashAttention
    backward recipe: delta = rowsum(g * o), dS = P*(dP - delta))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..rtc import pallas_call

    bh, lq, d = q.shape
    lk = k.shape[1]
    bq = min(128, lq)
    bk = min(128, lk)
    # (BH, LQ, _LANES): lane-padded like lse (Mosaic block tiling)
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1, keepdims=True), (bh, lq, _LANES))
    nk = lk // bk
    nq = lq // bq
    nj_k = _band_nj(window, bq, bk, nk) if window > 0 else nk
    nj_q = _band_nj(window, bk, bq, nq) if window > 0 else nq
    if window > 0:
        def kmap(b, i, j):
            return (b, _band_k_index(i, j, bq, bk, nk, window)[0], 0)

        def qmap(b, jk, j):
            return (b, _band_q_index(jk, j, bq, bk, nq, window)[0],
                    0)
    else:
        def kmap(b, i, j):
            return (b, j, 0)

        def qmap(b, jk, j):
            return (b, j, 0)
    dq = pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk,
                          nj=nj_k, causal=causal, scale=scale,
                          window=window),
        grid=(bh, nq, nj_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    dk, dv = pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, nq=nq,
                          nj=nj_q, causal=causal, scale=scale,
                          window=window),
        grid=(bh, nk, nj_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bq, _LANES), qmap),
            pl.BlockSpec((1, bq, _LANES), qmap),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _supported(q, k):
    """The tiling needs 128-divisible (or single-tile) sequence
    lengths.  VMEM use is O(block) — sequence length is NOT a
    constraint (the r5 streaming kernels; the r4 whole-sequence
    staging hit the VMEM wall near L*D ~ 2^20)."""
    lq, lk = q.shape[1], k.shape[1]
    return (q.ndim == 3 and lq % min(128, lq) == 0
            and lk % min(128, lk) == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, interpret, window):
    return _flash_fwd(q, k, v, causal, scale, interpret, window)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, interpret, window):
    o, lse = _flash_fwd(q, k, v, causal, scale, interpret, window)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, interpret, window, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, scale, interpret,
                      window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@defop("_flash_attention")
def flash_attention(q, k, v, causal=True, scale=None,
                    interpret=None, window=0):
    """Tiled online-softmax attention.  q/k/v: (BH, L, D).

    ``interpret=None`` compiles the Mosaic kernel where the call is
    placed on a TPU and interprets it elsewhere (``rtc.pallas_call``).
    Shapes the tiling cannot cover get the XLA reference
    implementation, with a warning.

    ``window > 0`` (requires ``causal``): sliding-window attention —
    query i sees keys (i - window, i].  Blocks entirely outside the
    band skip their FLOPs, so compute is O(L * window) instead of
    O(L^2 / 2): the long-context local-attention regime (Mistral-
    style) on the same streaming kernels.
    """
    causal = bool(causal)
    window = int(window)
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window > 0 requires causal=True")
    if window and q.shape[1] != k.shape[1]:
        raise ValueError(
            "window > 0 requires self-attention shapes (lq == lk); "
            f"got lq={q.shape[1]}, lk={k.shape[1]} — a query past "
            "the key horizon would have an empty key set")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scale = float(scale)
    if not _supported(q, k):
        warnings.warn(
            f"flash_attention: q {q.shape} / k {k.shape} is not tiled "
            "by 128 — XLA attention instead of the Pallas kernel (the "
            "L x L scores go through HBM)", stacklevel=2)
        return _reference_attention(q, k, v, causal, scale,
                                    window=window)
    return _flash(q, k, v, causal, scale, interpret, window)
