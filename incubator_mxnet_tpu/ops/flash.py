"""Flash attention: a Pallas TPU kernel for the attention hot op.

The role the reference fills with hand-written CUDA for its hot ops
(ref: src/operator/*-inl.cuh), done the TPU way: a tiled
online-softmax kernel (Flash Attention) that keeps the O(L^2) score
matrix out of HBM — each (query-tile, key-tile) block exists only in
VMEM, with the running max/denominator carried across key tiles in
VMEM scratch (the inner grid dimension is the tile loop; TPU grids are
sequential).  VMEM use is O(tile), independent of sequence length.

What the kernels do with a call, all of it read from the call itself:

- Tile sizes come from ``_tiles(lq, lk, d, dtype, window)``: the most
  rows of 1024, 512, 256 or 128 that divide each length, fit the
  VMEM budget (1024 for bfloat16 heads up to 128 wide, 512 for
  float32) and are no longer than twice the window; a sequence of 128
  positions or fewer is one tile.  A grid step has a fixed cost of a
  third of a microsecond on a v5e; a 128 x 128 block at a head
  dimension of 64 is a twentieth of that in MXU work, a 1024 x 1024
  block four times it.
- Under the causal mask the inner grid walks only live blocks, for the
  plain triangle as for the sliding window's band (``_band_k_index``,
  ``_band_q_index``): step j of a resident tile names the j-th live
  streamed tile, and a step past the last live one names that last one
  again, so the pipeline fetches nothing for it and its body does
  nothing.  The mask itself is applied only to blocks that the
  diagonal or the band's lower edge cuts (``_on_edge``).
- Operands go to the MXU in the dtype they came in, with float32
  accumulation; P and dS are cast to that dtype for their products.
  The softmax statistics (m, l, lse, delta), ``exp``, the rescaling
  and every accumulator are float32.  A float32 call is float32
  throughout.
- ``scale`` is applied to a query tile once (forward, dq: into scratch
  when the tile becomes resident) and to dq once at the end; dk needs
  no factor of its own, since dS^T (q * scale) carries it.
- The dk/dv kernel works on transposed blocks (keys down, queries
  across: K Q^T, V dO^T), so P^T and dS^T come out of the MXU as they
  are needed and nothing is transposed; lse and delta reach it as rows.

Registered as the differentiable op ``_flash_attention`` so both the
eager tape and compiled paths use it; the backward is the tiled
FlashAttention recipe too — dq and dk/dv kernels rebuild each P block
from the forward's log-sum-exp residual (delta = rowsum(g*o)), so no
L x L tensor exists in HBM in either direction.

Where a call is placed anywhere but on a TPU the kernel runs in
Pallas interpret mode (tests exercise it on CPU).
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

# a @ b.T as the MXU takes it: both operands contract their last dim
_NT = (((1,), (1,)), ((), ()))

# what a kernel may take of Mosaic's default 16 MiB of scoped VMEM
_VMEM_BUDGET = 14 << 20
_TILE_ROWS = (1024, 512, 256, 128)


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


def _tile_rows(length, cap):
    """The most rows of _TILE_ROWS, at most ``cap``, that divide
    ``length``; 128 positions or fewer are one tile."""
    if length <= 128:
        return length
    return next(t for t in _TILE_ROWS if t <= cap and length % t == 0)


def _kernel_bytes(bq, bk, d, dtype):
    """Scoped VMEM of the hungriest kernel (dk/dv), from above: ten
    bytes a score of a block with 2-byte operands (P^T and dS^T in
    float32 and in the operands' dtype), fourteen with float32 ones
    (the compiler splits those for the MXU); the streamed q and g,
    the resident k and v and the dk and dv blocks, double-buffered;
    the two float32 accumulators.  What the compiler reports for a
    v5e (``used_scoped_memory_configs``) lies under it at every size
    ``_tiles`` takes."""
    item = jnp.dtype(dtype).itemsize
    return ((6 + 2 * item) * bq * bk
            + 2 * item * d * (2 * bq + 4 * bk) + 8 * bk * d)


def _tiles(lq, lk, d, dtype, window):
    """(bq, bk): rows of a query tile and of a key tile, for all three
    kernels.  The largest that divide the lengths and fit the budget:
    the fewer grid steps, the less of a kernel's time is the steps'
    own.  Under a window no longer than twice the window: a longer
    tile's blocks lie mostly outside the band."""
    most = max(128, 2 * window) if window else _TILE_ROWS[0]
    for cap in _TILE_ROWS:
        bq, bk = _tile_rows(lq, cap), _tile_rows(lk, cap)
        if cap <= most and _kernel_bytes(bq, bk, d, dtype) \
                <= _VMEM_BUDGET:
            break
    return bq, bk


def _causal_mask(s, iq, jk, bq, bk, window=0, q_axis=0):
    """Block (q-tile iq, k-tile jk) of scores with the pairs the mask
    forbids at _NEG.  ``q_axis`` 1: the block is transposed (keys
    down, queries across)."""
    q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = jk * bk + lax.broadcasted_iota(jnp.int32, s.shape,
                                           1 - q_axis)
    keep = q_pos >= k_pos
    if window > 0:
        keep &= (q_pos - k_pos) < window
    return jnp.where(keep, s, _NEG)


def _on_edge(iq, jk, bq, bk, window):
    """Does the mask cut block (q-tile iq, k-tile jk)?  A live block
    that it does not cut needs no masking at all."""
    edge = (jk + 1) * bk - 1 > iq * bq         # a key after a query
    if window > 0:
        # the oldest key is out of the newest query's window
        edge |= (iq + 1) * bq - 1 - jk * bk >= window
    return edge


def _band_nj(window, b_res, b_str, n_str):
    """Inner-grid length under the causal mask: with a window the
    resident tile of size b_res sees at most window + b_res - 1
    streamed positions -> this many b_str-tiles (+1 for alignment),
    capped at the full count; the plain triangle (window 0) has a
    resident tile that sees them all."""
    if window == 0:
        return n_str
    return min(n_str, (b_res + window - 2) // b_str + 2)


def _band_base_k(iq, bq, bk, window):
    """First live k-tile of q-tile iq: the band's
    (k >= iq*bq - window + 1), or tile 0 without a window."""
    if window == 0:
        return 0
    return jnp.maximum((iq * bq - (window - 1)) // bk, 0)


def _band_k_index(iq, j, bq, bk, nk, window):
    """(k-tile, live) for inner step j of q-tile iq under the causal
    mask, window or none.  Steps past the last tile at or under the
    diagonal name that tile again — no DMA — and are not live."""
    base = _band_base_k(iq, bq, bk, window)
    last = jnp.minimum(((iq + 1) * bq - 1) // bk, nk - 1)
    return jnp.minimum(base + j, last), base + j <= last


def _band_q_index(jk, j, bq, bk, nq, window):
    """(q-tile, live) for inner step j of k-tile jk (dkv grid): from
    the first q-tile that reaches the diagonal to the last one whose
    window still holds a key of the tile (without a window: the last
    one there is)."""
    base = (jk * bk) // bq
    last = nq - 1
    if window > 0:
        last = jnp.minimum(((jk + 1) * bk - 1 + window - 1) // bq,
                           last)
    return jnp.minimum(base + j, last), base + j <= last


def _on_live_blocks(live, edge, step):
    """``step(masked)`` once if the block is live, masked only where
    the mask cuts it.  ``live is True``: no mask, every block."""
    from jax.experimental import pallas as pl

    if live is True:
        step(False)
        return
    pl.when(live & edge)(lambda: step(True))
    pl.when(live & jnp.logical_not(edge))(lambda: step(False))


def _scaled(x_ref, scale):
    """The tile times ``scale``, in the tile's own dtype."""
    return (x_ref[0].astype(jnp.float32) * scale).astype(x_ref.dtype)


def _mxu(a, b, dims=None):
    """a @ b (``dims`` _NT: a @ b.T), accumulated in float32."""
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims,
                           preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs_sc, m_sc,
                l_sc, acc_sc, *, bq, bk, nk, nj, causal, scale,
                window):
    """grid = (BH, NQ, NJ): one (q-tile, k-tile) block per step; the
    k dimension is innermost, so the online-softmax carry streams
    through the scratch accumulators."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    j = pl.program_id(2)
    jk, live, edge = j, True, False
    if causal:
        jk, live = _band_k_index(iq, j, bq, bk, nk, window)
        edge = _on_edge(iq, jk, bq, bk, window)

    @pl.when(j == 0)
    def _init():
        qs_sc[...] = _scaled(q_ref, scale)
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        vb = v_ref[0]                                 # (BK, D)
        s = _mxu(qs_sc[...], k_ref[0], _NT)           # (BQ, BK)
        if masked:
            s = _causal_mask(s, iq, jk, bq, bk, window)
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_sc[...] = m_new
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1,
                                                keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + _mxu(p.astype(vb.dtype),
                                                 vb)

    _on_live_blocks(live, edge, step)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        # log-sum-exp residual: what the backward needs to rebuild P
        # block by block without the L x L score matrix
        lse = m_sc[...] + jnp.log(l)                  # (BQ, 1)
        lse_ref[0] = jnp.broadcast_to(lse, (bq, _LANES))


def _resident(b, i, j):
    """index_map of a tile that stays while the inner grid runs."""
    return (b, i, 0)


def _k_grid(causal, window, bq, bk, nk):
    """(inner-grid length, index_map) of the k and v tiles that stream
    past a resident q tile: forward and dq."""
    if not causal:
        return nk, lambda b, i, j: (b, j, 0)
    return (_band_nj(window, bq, bk, nk), lambda b, i, j: (
        b, _band_k_index(i, j, bq, bk, nk, window)[0], 0))


def _q_grid(causal, window, bq, bk, nq):
    """(inner-grid length, index_map) of the q and g tiles that stream
    past a resident k tile: dk/dv."""
    if not causal:
        return nq, lambda b, jk, j: (b, j, 0)
    return (_band_nj(window, bk, bq, nq), lambda b, jk, j: (
        b, _band_q_index(jk, j, bq, bk, nq, window)[0], 0))


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    # the streamed dimension carries the accumulators: in order
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# jitted so that a model's layers share one trace and one lowering of
# each kernel: the same shapes and statics hit jit's cache
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _flash_fwd(q, k, v, causal, scale, interpret, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..rtc import pallas_call

    bh, lq, d = q.shape
    lk = k.shape[1]
    bq, bk = _tiles(lq, lk, d, q.dtype, window)
    nk = lk // bk
    nj, kmap = _k_grid(causal, window, bq, bk, nk)

    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk,
                               nj=nj, causal=causal, scale=scale,
                               window=window)
    o, lse = pallas_call(
        kernel,
        grid=(bh, lq // bq, nj),
        in_specs=[
            pl.BlockSpec((1, bq, d), _resident),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, d), kmap),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), _resident),
            pl.BlockSpec((1, bq, _LANES), _resident),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, lq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), q.dtype),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
               dq_ref, qs_sc, dq_sc, *, bq, bk, nk, nj, causal, scale,
               window):
    """grid = (BH, NQ, NJ): k/v stream past a resident q tile; dq
    accumulates in scratch."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    j = pl.program_id(2)
    jk, live, edge = j, True, False
    if causal:
        jk, live = _band_k_index(iq, j, bq, bk, nk, window)
        edge = _on_edge(iq, jk, bq, bk, window)

    @pl.when(j == 0)
    def _init():
        qs_sc[...] = _scaled(q_ref, scale)
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked):
        kb = k_ref[0]                                 # (BK, D)
        s = _mxu(qs_sc[...], kb, _NT)                 # (BQ, BK)
        if masked:
            s = _causal_mask(s, iq, jk, bq, bk, window)
        p = jnp.exp(s - lse_ref[0][:, 0:1])
        dp = _mxu(g_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta_ref[0][:, 0:1])          # dS / scale
        dq_sc[...] = dq_sc[...] + _mxu(ds.astype(kb.dtype), kb)

    _on_live_blocks(live, edge, step)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, bq, bk, nq, nj,
                causal, scale, window):
    """grid = (BH, NK, NJ): q/g/lse/delta stream past a resident k/v
    tile; dk/dv accumulate in scratch.  Blocks are transposed — keys
    down, queries across — and lse/delta are (1, BQ) rows."""
    from jax.experimental import pallas as pl

    jk = pl.program_id(1)
    j = pl.program_id(2)
    iq, live, edge = j, True, False
    if causal:
        iq, live = _band_q_index(jk, j, bq, bk, nq, window)
        edge = _on_edge(iq, jk, bq, bk, window)

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        qs = _scaled(q_ref, scale)                    # (BQ, D)
        gb = g_ref[0]
        st = _mxu(k_ref[0], qs, _NT)                  # (BK, BQ)
        if masked:
            st = _causal_mask(st, iq, jk, bq, bk, window, q_axis=1)
        pt = jnp.exp(st - lse_ref[0])
        dv_sc[...] = dv_sc[...] + _mxu(pt.astype(gb.dtype), gb)
        dpt = _mxu(v_ref[0], gb, _NT)
        dst = pt * (dpt - delta_ref[0])               # dS^T / scale
        dk_sc[...] = dk_sc[...] + _mxu(dst.astype(qs.dtype), qs)

    _on_live_blocks(live, edge, step)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _flash_bwd(q, k, v, o, lse, g, causal, scale, interpret,
               window=0):
    """Tiled backward: rebuilds each P block from (q, k, lse) — no
    L x L tensor in HBM on the gradient path either (the FlashAttention
    backward recipe: delta = rowsum(g * o), dS = P*(dP - delta))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..rtc import pallas_call

    bh, lq, d = q.shape
    lk = k.shape[1]
    bq, bk = _tiles(lq, lk, d, q.dtype, window)
    nk = lk // bk
    nq = lq // bq
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                          # (BH, LQ)
    # (BH, LQ, _LANES) for dq: lane-padded like lse (Mosaic block
    # tiling); (BH, 1, LQ) rows for the transposed blocks of dk/dv
    delta_cols = jnp.broadcast_to(delta[..., None], lse.shape)
    delta_rows, lse_rows = delta[:, None, :], lse[:, None, :, 0]
    nj_k, kmap = _k_grid(causal, window, bq, bk, nk)
    nj_q, qmap = _q_grid(causal, window, bq, bk, nq)

    def qmap_rows(b, jk, j):
        return (b, 0, qmap(b, jk, j)[1])

    dq = pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk,
                          nj=nj_k, causal=causal, scale=scale,
                          window=window),
        grid=(bh, nq, nj_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), _resident),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bq, d), _resident),
            pl.BlockSpec((1, bq, _LANES), _resident),
            pl.BlockSpec((1, bq, _LANES), _resident),
        ],
        out_specs=pl.BlockSpec((1, bq, d), _resident),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), q.dtype),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, g, lse, delta_cols)
    dk, dv = pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, nq=nq,
                          nj=nj_q, causal=causal, scale=scale,
                          window=window),
        grid=(bh, nk, nj_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bk, d), _resident),
            pl.BlockSpec((1, bk, d), _resident),
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, 1, bq), qmap_rows),
            pl.BlockSpec((1, 1, bq), qmap_rows),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), _resident),
            pl.BlockSpec((1, bk, d), _resident),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, g, lse_rows, delta_rows)
    return dq, dk, dv


def _supported(q, k):
    """The tiling needs 128-divisible (or single-tile) sequence
    lengths.  VMEM use is O(tile): sequence length is no
    constraint."""
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
    band are neither stepped through nor fetched, so compute and HBM
    traffic are O(L * window) instead of O(L^2 / 2): the long-context
    local-attention regime (Mistral-style) on the same streaming
    kernels.
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
