"""Mamba-2's selective state-space mixer (the SSD form), for serving.

What a token leaves behind in such a layer is not a row a position but
a state of fixed size a sequence: ``h`` (heads, head_dim, d_state) and
the last ``conv_kernel - 1`` inputs of the causal depthwise conv.  With
``x`` (heads, head_dim), ``B``, ``C`` (groups, d_state; a group serves
``heads / groups`` heads), ``dt`` (heads,) after its softplus and
``A = -exp(A_log)`` (heads,):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    y_t = C_t . h_t + D x_t

Three pieces:

:func:`causal_conv` / :func:`conv_step`
    the depthwise conv over a sequence after a carried tail (a
    prefill), or one row a slot (a decode step); each gives the tail
    the next call starts from.
:func:`chunked_scan`
    the recurrence over a sequence in its chunked matrix form: within a
    chunk of ``chunk`` rows the outputs are one masked product of
    ``C B^T`` with the decays between rows, across chunks the state is
    carried by a ``lax.scan``.  Rows with ``dt = 0`` and ``x = 0`` (a
    prefill's padding) leave the state as it was.
:func:`state_step`
    one decode step of every slot: a Pallas kernel (``ssm_state_step``)
    that reads each slot's state once, writes it once in place and
    computes ``y`` from VMEM.  Lowered for a TPU it is compiled; on any
    other platform the same kernel runs interpreted.

A slot's state is held as the pool lays it out (:func:`state_shape`):
``(rows, d_state, lanes)`` float32, ``lanes`` of one row the
``lanes // head_dim`` heads side by side, whole lanes of 128 where the
heads of a group fill them, rows in order of heads, so that a group's
heads are whole rows and a kernel step takes one group of one slot.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["state_shape", "to_rows", "from_rows", "causal_conv",
           "conv_step", "chunked_scan", "state_step"]

LANES = 128


def _lanes(heads, head_dim, groups):
    per_group = heads // groups * head_dim
    lanes = min(LANES, per_group)
    if heads % groups or lanes % head_dim or per_group % lanes:
        raise ValueError(
            f"{heads} heads of {head_dim} in {groups} groups: a group's "
            "heads do not fill whole rows of lanes")
    return lanes


def state_shape(heads, head_dim, groups, d_state):
    """One slot's state as the pool holds it: (rows, d_state, lanes)."""
    lanes = _lanes(heads, head_dim, groups)
    return (heads * head_dim // lanes, d_state, lanes)


def to_rows(h, groups):
    """``h`` (..., heads, head_dim, d_state) -> the pool's layout
    (..., rows, d_state, lanes)."""
    *lead, heads, p, n = h.shape
    lanes = _lanes(heads, p, groups)
    k = lanes // p
    h = h.reshape(*lead, heads // k, k, p, n)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, heads // k, n, lanes)


def from_rows(s, head_dim):
    """The pool's layout (..., rows, d_state, lanes) -> (..., heads,
    head_dim, d_state)."""
    *lead, rows, n, lanes = s.shape
    k = lanes // head_dim
    s = s.reshape(*lead, rows, n, k, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, rows * k, head_dim, n)


def causal_conv(rows, tail, weight, bias, true_len):
    """The depthwise causal conv of one sequence: ``rows`` (T, C) after
    ``tail`` (K - 1, C), the inputs before them; ``weight`` (C, K) with
    tap ``K - 1`` on the newest input, ``bias`` (C,).  Returns (out (T,
    C) float32, the tail after row ``true_len - 1``): rows past
    ``true_len`` are padding and leave the tail as the real rows left
    it."""
    f32 = jnp.float32
    t, k = rows.shape[0], weight.shape[1]
    seq = jnp.concatenate([tail.astype(rows.dtype), rows], axis=0)
    w = weight.astype(f32)
    out = bias.astype(f32) + sum(
        seq[i:i + t].astype(f32) * w[:, i] for i in range(k))
    return out, lax.dynamic_slice_in_dim(seq, true_len, k - 1, axis=0)


def conv_step(rows, tails, weight, bias, live):
    """One row a slot: ``rows`` (S, C), ``tails`` (S, K - 1, C).
    Returns (out (S, C) float32, the tails after the row; an idle slot,
    ``live`` false, keeps its tail)."""
    f32 = jnp.float32
    seq = jnp.concatenate([tails, rows[:, None].astype(tails.dtype)],
                          axis=1)
    out = bias.astype(f32) + jnp.einsum(
        "skc,ck->sc", seq.astype(f32), weight.astype(f32),
        precision=lax.Precision.HIGHEST)
    return out, jnp.where(live[:, None, None], seq[:, 1:], tails)


def chunked_scan(x, dt, a, b, c, h0, chunk):
    """The recurrence over one sequence, a chunk of rows at a time.

    ``x`` (T, heads, head_dim), ``dt`` (T, heads) after its softplus,
    zero on padding rows; ``a`` (heads,) the negative ``A``; ``b``,
    ``c`` (T, groups, d_state); ``h0`` (heads, head_dim, d_state) the
    state before row 0.  All float32.  Returns (``C_t . h_t`` (T,
    heads, head_dim), without ``D x``; the state after the last row).
    Within a chunk: ``y_l = sum_{s <= l} exp(cs_l - cs_s) (C_l . B_s)
    dt_s x_s + exp(cs_l) C_l . h``, ``cs`` the running sum of ``dt
    A``; the state leaves the chunk as ``exp(cs_L) h + sum_s
    exp(cs_L - cs_s) dt_s x_s (x) B_s``.  Products at ``highest``
    precision."""
    f32 = jnp.float32
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    rep = heads // groups
    pad = -t % chunk

    def chunks(v):
        v = jnp.pad(v.astype(f32), ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((-1, chunk) + v.shape[1:])

    causal = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    a = a.astype(f32)
    # float32 products throughout (a few percent of a prefill's work):
    # the state that leaves the prefill is carried by every later step
    einsum = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)

    def one(h, blk):
        xc, dtc, bc, cc = blk
        cs = jnp.cumsum(dtc * a, axis=0)                  # (L, H)
        decay = jnp.exp(jnp.where(causal[..., None],
                                  cs[:, None] - cs[None], -jnp.inf))
        cb = jnp.repeat(einsum("lgn,sgn->gls", cc, bc), rep, axis=0)
        xdt = xc * dtc[..., None]                         # (L, H, P)
        y = einsum("hls,shp->lhp", cb * jnp.moveaxis(decay, -1, 0), xdt)
        hg = h.reshape(groups, rep, p, n)
        y = y + einsum("lgn,grpn->lgrp", cc, hg).reshape(
            chunk, heads, p) * jnp.exp(cs)[..., None]
        tail = (xdt * jnp.exp(cs[-1][None] - cs)[..., None]).reshape(
            chunk, groups, rep, p)
        h = jnp.exp(cs[-1])[:, None, None] * h + einsum(
            "lgn,lgrp->grpn", bc, tail).reshape(heads, p, n)
        return h, y

    h, y = lax.scan(one, h0.astype(f32),
                    tuple(chunks(v) for v in (x, dt, b, c)))
    return y.reshape(-1, heads, p)[:t], h


def _step_kernel(a_ref, d_ref, x_ref, dt_ref, b_ref, c_ref, h_ref,
                 y_ref, h_out):
    """One group of one slot.  ``h_ref`` (1, R, N, W) its rows of the
    state; ``x_ref``, ``dt_ref`` (1, 1, R, W) the step's inputs and
    step sizes in the state's lanes; ``b_ref``, ``c_ref`` (1, 1, 1, N);
    ``a_ref``, ``d_ref`` (1, R, W) ``A`` and ``D`` in those lanes.  A
    row's update is ``B`` down the sublanes times ``dt x`` across the
    lanes, ``y`` the sum down the sublanes of ``C`` times the new
    state."""
    rows, n, w = h_ref.shape[1:]
    # B and C down the sublanes, the same in every lane
    bt = jnp.broadcast_to(b_ref[0, 0], (w, n)).T
    ct = jnp.broadcast_to(c_ref[0, 0], (w, n)).T
    for r in range(rows):
        x = x_ref[0, 0, r:r + 1, :]
        dt = dt_ref[0, 0, r:r + 1, :]
        h = h_ref[0, r] * jnp.exp(dt * a_ref[0, r:r + 1, :]) \
            + bt * (dt * x)
        h_out[0, r] = h
        y_ref[0, 0, r:r + 1, :] = jnp.sum(ct * h, axis=0, keepdims=True) \
            + d_ref[0, r:r + 1, :] * x


# jitted so that a step's layers share one trace and one lowering of
# the kernel: the same shapes hit jit's cache
@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(a, d, x, dt, b, c, h, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..rtc import pallas_call

    slots, groups, rows, lanes = x.shape
    n = h.shape[2]

    def of_slot(s, g):
        return s, g, 0, 0

    def weights_of(s, g):
        return g, 0, 0

    return pallas_call(
        _step_kernel,
        grid=(slots, groups),
        in_specs=[pl.BlockSpec((1, rows, lanes), weights_of),
                  pl.BlockSpec((1, rows, lanes), weights_of),
                  pl.BlockSpec((1, 1, rows, lanes), of_slot),
                  pl.BlockSpec((1, 1, rows, lanes), of_slot),
                  pl.BlockSpec((1, 1, 1, n), of_slot),
                  pl.BlockSpec((1, 1, 1, n), of_slot),
                  pl.BlockSpec((1, rows, n, lanes), of_slot)],
        out_specs=[pl.BlockSpec((1, 1, rows, lanes), of_slot),
                   pl.BlockSpec((1, rows, n, lanes), of_slot)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_state_step",
    )(a, d, x, dt, b, c, h)


def _lay(v, heads, head_dim, groups, lanes):
    """A value a head (..., heads) spread over its head's lanes:
    (..., groups, rows of a group, lanes)."""
    v = jnp.repeat(v.astype(jnp.float32), head_dim, axis=-1)
    return v.reshape(v.shape[:-1] + (groups, -1, lanes))


def state_step(h, x, dt, a, b, c, d):
    """One decode step of every slot.  ``h`` (S, rows, N, W) float32
    the states as the pool lays them out; ``x`` (S, heads, head_dim);
    ``dt`` (S, heads) after its softplus (0 on an idle slot, which
    leaves its state as it is); ``a``, ``d`` (heads,) ``A`` (negative)
    and ``D``; ``b``, ``c`` (S, groups, N).  Returns (y (S, heads,
    head_dim) float32 with ``D x`` in it, the new states).  The kernel
    where the call is lowered for a TPU, interpreted elsewhere."""
    s, heads, p = x.shape
    groups = b.shape[1]
    lanes = h.shape[-1]
    args = (_lay(a[None], heads, p, groups, lanes)[0],
            _lay(d[None], heads, p, groups, lanes)[0],
            x.astype(jnp.float32).reshape(s, groups, -1, lanes),
            _lay(dt, heads, p, groups, lanes),
            b.astype(jnp.float32)[:, :, None],
            c.astype(jnp.float32)[:, :, None], h)
    y, h = lax.platform_dependent(
        *args, tpu=functools.partial(_step_call, interpret=False),
        default=functools.partial(_step_call, interpret=True))
    return y.reshape(s, heads, p), h

