"""Paged decode attention: one query a slot over a block-table cache.

The serving engine keeps keys and values in pools of fixed blocks,
``(num_blocks, block_size, kv_heads * head_dim)``, and a slot's context
is the ordered row of block ids it owns (docs/serving.md).  A decode
step attends, for every slot, one new query over the slot's cached
positions ``0 .. n_past - 1`` and over the step's own new key and value
(position ``n_past``), which the caller holds and which need not be in
the pool yet: the read has no use for the step's write, so the write
stays in place in the donated pool.  Keys and values may be one pool
(the caller passes the same array twice): a latent row is both, and
its step's own row is then ``k_new``.  Scores are scaled by the
caller's ``scale``, ``1 / sqrt(head_dim)`` unless given.

Two reads of the same result:

``plain``
    XLA: gather every table row's ``max_blocks * block_size`` positions,
    lay the step's own row over position ``n_past``, dense scores, mask,
    softmax.  Any shape, any platform; what is gathered scales with the
    allowed context.

``kernel``
    Pallas (TPU): block ids and ``n_past`` are scalar prefetch; a slot
    is one grid step that walks its blocks in chunks as far as
    ``n_past`` and no further, blocks copied HBM -> VMEM two chunks
    deep (a block of one pool copied once and read by both products);
    the softmax is streamed in float32 with a running maximum and
    sum (as ``ops/flash.py``).  All query heads go through the MXU at
    once against whole cached rows (``kv_heads * head_dim`` lanes):
    with several kv heads the query is laid out block-diagonally,
    ``(heads, kv_heads * head_dim)`` with head ``j``'s values in the
    lanes of its kv head, so one product gives every head's scores and
    one more every head's weighted sum, in whole lanes whatever the
    head dimension is; with one kv head the query is ``(heads, row)``
    as it comes.  The products take bfloat16 operands and add in
    float32: what the platform's default precision makes of a float32
    product, and so a read for callers who left
    ``jax.default_matmul_precision`` at its default (or at
    ``bfloat16``) only.

:func:`decode_attention` takes the kernel where the shapes can be
tiled, the matmul precision in force at the trace is the platform's
default and the call is lowered for a TPU; the plain read, which XLA
computes at whatever precision the caller asked for, anywhere else.
The choice is made from shapes, at the trace and at lowering, never
from an option.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["decode_attention", "read_kind", "plain_read", "kernel_read",
           "gathered_context"]

_NEG = -1e30
# a chunk holds at most _CHUNK_TOKENS positions and _CHUNK_BYTES of a
# pool (8 blocks of 16 x 2048 float32; 32 of 16 x 640 bfloat16), and
# the VMEM the kernel plans with (of the 16 MB a kernel may take, the
# rest left to what it computes with)
_CHUNK_TOKENS = 512
_CHUNK_BYTES = 1 << 20
_VMEM_BUDGET = 10 << 20
# settings of jax_default_matmul_precision under which a float32
# product on a TPU is the kernel's: one bfloat16 pass, float32 sums
_ONE_PASS = (None, "default", "bfloat16")


def _blocks_per_chunk(n_heads, row, block_size, itemsize, pools):
    """Blocks a chunk copies, or 0 where the shapes leave no room:
    the accumulator and the query's and the output's two buffers are
    ``(heads, row)`` float32 each, a block of ``itemsize`` bytes a
    value is held for each of ``pools``, two chunks deep."""
    block = itemsize * block_size * row
    room = _VMEM_BUDGET - 5 * 4 * n_heads * row
    return max(0, min(_CHUNK_TOKENS // block_size, _CHUNK_BYTES // block,
                      room // (2 * pools * block)))


def read_kind(n_heads, n_kv_heads, head_dim, block_size, dtype,
              platform="tpu"):
    """The read :func:`decode_attention` takes where it is traced now
    and lowered for ``platform``: ``"kernel"`` on a TPU where the
    kernel can tile these shapes (float32 or bfloat16 pools, a row of
    whole lanes, heads in whole sublanes, a block of whole sublane
    tiles of the pool's dtype: 8 rows of float32, 16 of bfloat16;
    buffers for two pools within VMEM) and the matmul precision in
    force is the one its products have; else ``"plain"``."""
    row = n_kv_heads * head_dim
    dtype = jnp.dtype(dtype)
    ok = (platform == "tpu"
          and jax.config.jax_default_matmul_precision in _ONE_PASS
          and dtype in (jnp.float32, jnp.bfloat16)
          and row % 128 == 0 and n_heads % 8 == 0
          and n_heads % n_kv_heads == 0
          and block_size % (32 // dtype.itemsize) == 0
          and _blocks_per_chunk(n_heads, row, block_size, dtype.itemsize,
                                2) > 0)
    return "kernel" if ok else "plain"


def gathered_context(pool, table, n_past, rows):
    """One table row's context ``(C, row)`` as ``pool`` ``(N, bs,
    row)`` holds it, gathered through ``table`` ``(MB,)``, with the
    program's own ``rows`` ``(S, row)`` laid over positions ``n_past
    ..`` of the copy.  Lane ``c`` of the ``C = MB * bs`` axis IS
    absolute position ``c``: a row is ordered by logical block index.
    The ``S`` rows of room behind ``C`` take what a clamped start
    would otherwise shift (only a prefill's padding lands there)."""
    s = rows.shape[0]
    c = table.shape[0] * pool.shape[1]
    got = jnp.pad(pool[table].reshape(c, -1), ((0, s), (0, 0)))
    return lax.dynamic_update_slice(
        got, rows.astype(pool.dtype), (n_past, 0))[:c]


def plain_read(q, k_new, v_new, kpool, vpool, tables, n_past,
               scale=None):
    """The XLA read.  q ``(B, H, Dh)``; k_new, v_new ``(B, KV * Dh)``;
    pools ``(N, bs, KV * Dh)``, ``vpool`` may be ``kpool`` (then the
    values are the keys' rows, ``k_new``'s too); tables ``(B, MB)``;
    n_past ``(B,)``.  Products add in float32, the weights meet the
    values in the values' dtype.  Returns ``(B, H * Dh)`` float32."""
    b, h, dh = q.shape
    kv = k_new.shape[-1] // dh
    c = tables.shape[1] * kpool.shape[1]

    def context(pool, new):
        got = jax.vmap(gathered_context, in_axes=(None, 0, 0, 0))(
            pool, tables, n_past, new[:, None, :])
        return got.reshape(b, c, kv, dh)

    kc = context(kpool, k_new)
    vc = kc if vpool is kpool else context(vpool, v_new)
    keep = jnp.arange(c)[None, :] <= n_past[:, None]
    qg = q.reshape(b, kv, h // kv, dh)
    s = jnp.einsum("bkrd,bckd->bkrc", qg, kc,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(dh) if scale is None else s * scale
    att = jax.nn.softmax(
        jnp.where(keep[:, None, None, :], s, -1e9), axis=-1)
    return jnp.einsum("bkrc,bckd->bkrd", att.astype(vc.dtype), vc,
                      preferred_element_type=jnp.float32) \
        .reshape(b, h * dh)


def _kernel(tables_ref, npast_ref, q_ref, kn_ref, vn_ref, *refs,
            block_size, chunk, max_blocks, scale, shared):
    """One slot: ``q_ref`` (1, H, row) the query (block-diagonal where
    there are several kv heads), ``kn_ref`` / ``vn_ref`` (1, 1, row)
    the step's own row, then the pools in HBM (one where ``shared``),
    ``o_ref`` (1, H, row) every head's weighted sum over every lane
    group (the caller keeps a head's own group), a VMEM buffer a pool,
    the accumulator and the copies' semaphores."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pools = 1 if shared else 2
    hbm, o_ref = refs[:n_pools], refs[n_pools]
    bufs, acc_ref, sems = refs[n_pools + 1:-2], refs[-2], refs[-1]
    kbuf, vbuf = bufs[0], bufs[-1]
    b = pl.program_id(0)
    n = npast_ref[b]
    tokens = chunk * block_size
    n_chunks = (n + tokens - 1) // tokens
    h = q_ref.shape[1]

    def each_copy(c, buf, fn):
        # the live blocks of chunk c, through the slot's table row
        for i in range(chunk):
            blk = c * chunk + i

            @pl.when(blk * block_size < n)
            def _():
                bid = tables_ref[b * max_blocks + blk]
                rows = pl.ds(i * block_size, block_size)
                for j, (pool, into) in enumerate(zip(hbm, bufs)):
                    fn(pltpu.make_async_copy(
                        pool.at[bid], into.at[buf, rows], sems.at[j, buf]))

    @pl.when(n_chunks > 0)
    def _():
        each_copy(0, 0, lambda copy: copy.start())

    q = q_ref[0].astype(jnp.bfloat16)                       # (H, row)
    # the step's own position opens the running softmax: its score is
    # the maximum so far, its weight 1
    kn = kn_ref[0].astype(jnp.bfloat16).astype(jnp.float32)  # (1, row)
    m0 = jnp.sum(q.astype(jnp.float32) * kn, axis=1,
                 keepdims=True) * scale                      # (H, 1)
    acc_ref[...] = jnp.broadcast_to(
        vn_ref[0].astype(jnp.float32), acc_ref.shape)

    def body(c, carry):
        m, l = carry
        buf = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            each_copy(c + 1, 1 - buf, lambda copy: copy.start())

        each_copy(c, buf, lambda copy: copy.wait())
        first = c * tokens
        k = kbuf[buf].astype(jnp.bfloat16)                  # (T, row)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        live = first + lax.broadcasted_iota(
            jnp.int32, (h, tokens), 1) < n
        s = jnp.where(live, s, _NEG)                        # (H, T)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # rows behind n_past hold whatever the buffer held: out, not
        # merely weighted by zero
        rows = first + lax.broadcasted_iota(
            jnp.int32, (tokens, 1), 0) < n
        v = jnp.where(rows, vbuf[buf], 0).astype(jnp.bfloat16)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(jnp.bfloat16), v,
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True)

    _, l = lax.fori_loop(0, n_chunks, body,
                         (m0, jnp.ones((h, 1), jnp.float32)))
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


# jitted so that a step's layers share one trace and one lowering of
# the kernel: the same shapes and statics hit jit's cache
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kernel_call(q, k_new, v_new, tables, n_past, *pools, scale,
                 interpret):
    """The kernel over every slot: ``q`` ``(B, H, row)`` as the kernel
    takes it, the step's own rows ``(B, row)``, one pool or two.
    Returns ``(B, H, row)`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..rtc import pallas_call

    b, h, row = q.shape
    bs = pools[0].shape[1]
    mb = tables.shape[1]
    chunk = min(mb, _blocks_per_chunk(h, row, bs, pools[0].dtype.itemsize,
                                      len(pools)))

    def per_slot(i, *_):
        return i, 0, 0

    return pallas_call(
        functools.partial(_kernel, block_size=bs, chunk=chunk,
                          max_blocks=mb, scale=scale,
                          shared=len(pools) == 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, row), per_slot),
                      pl.BlockSpec((1, 1, row), per_slot),
                      pl.BlockSpec((1, 1, row), per_slot)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, h, row), per_slot),
            scratch_shapes=[
                pltpu.VMEM((2, chunk * bs, row), pool.dtype)
                for pool in pools] + [
                pltpu.VMEM((h, row), jnp.float32),
                pltpu.SemaphoreType.DMA((len(pools), 2))]),
        out_shape=jax.ShapeDtypeStruct((b, h, row), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.reshape(-1).astype(jnp.int32), n_past.astype(jnp.int32),
      q, k_new.reshape(b, 1, row), v_new.reshape(b, 1, row), *pools)


def kernel_read(q, k_new, v_new, kpool, vpool, tables, n_past,
                scale=None, interpret=False):
    """The Pallas read; arguments and result as :func:`plain_read`.
    ``interpret=True`` runs it through the Pallas interpreter (tests on
    the CPU); the shapes must pass :func:`read_kind`."""
    b, h, dh = q.shape
    row = kpool.shape[-1]
    kv = row // dh
    rep = h // kv
    if kv > 1:
        # head j's query in the lanes of its kv head, zero elsewhere
        own = (jnp.arange(h)[:, None] // rep == jnp.arange(kv)[None, :])
        q = (q[:, :, None, :] * own[None, :, :, None].astype(q.dtype)) \
            .reshape(b, h, row)
    pools = (kpool,) if vpool is kpool else (kpool, vpool)
    out = _kernel_call(
        q, k_new, v_new, tables, n_past, *pools,
        scale=1.0 / math.sqrt(dh) if scale is None else float(scale),
        interpret=bool(interpret))
    if kv == 1:
        return out.reshape(b, h * row)
    # a head keeps the lanes of its own kv head
    out = out.reshape(b, kv, rep, kv, dh)
    return jnp.diagonal(out, axis1=1, axis2=3) \
        .transpose(0, 3, 1, 2).reshape(b, h * dh)


def decode_attention(q, k_new, v_new, kpool, vpool, tables, n_past,
                     scale=None):
    """One decode step's attention over the paged cache: the kernel
    where :func:`read_kind` says so for a TPU and the call is lowered
    for one, the plain read otherwise.  ``vpool`` may be ``kpool``
    itself: keys that are their own values (``v_new`` is then
    ``k_new``), read once."""
    _, h, dh = q.shape
    shared = vpool is kpool
    kind = read_kind(h, k_new.shape[-1] // dh, dh, kpool.shape[1],
                     kpool.dtype)
    if kind != "kernel":
        return plain_read(q, k_new, v_new, kpool, vpool, tables, n_past,
                          scale)
    if not shared:
        return lax.platform_dependent(
            q, k_new, v_new, kpool, vpool, tables, n_past,
            tpu=functools.partial(kernel_read, scale=scale),
            default=functools.partial(plain_read, scale=scale))

    # one operand for the one pool: the branches are traced anew, and
    # an array handed in twice would come out as two
    def one_pool(read):
        return lambda q, new, pool, tables, n_past: read(
            q, new, new, pool, pool, tables, n_past, scale)

    return lax.platform_dependent(
        q, k_new, kpool, tables, n_past, tpu=one_pool(kernel_read),
        default=one_pool(plain_read))
