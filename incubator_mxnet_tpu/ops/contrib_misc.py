"""Remaining contrib / legacy op families.

TPU-native implementations of the reference kernels:
  _contrib_fft / _contrib_ifft      (src/operator/contrib/fft.cc,
                                     ifft.cc — cuFFT wrappers)
  _contrib_count_sketch             (contrib/count_sketch.cc)
  _contrib_quantize / _dequantize   (contrib/quantize.cc,
                                     dequantize.cc)
  Correlation                       (src/operator/correlation.cc —
                                     the FlowNet layer)
  _contrib_DeformablePSROIPooling   (contrib/
                                     deformable_psroi_pooling.cc)
  IdentityAttachKLSparseReg         (identity_attach_KL_sparse_reg.cc)
  cast_storage / reshape_like / _sparse_retain / _square_sum and the
  sparse scatter aliases            (tensor/cast_storage.cc,
                                     elemwise_unary_op_basic.cc,
                                     sparse_retain.cc, square_sum.cc)

Everything is jnp/XLA (the FFTs hit XLA's native FFT HLO; Correlation
unrolls the static displacement grid into fused multiply-reduces).
"""
import functools

import jax
import jax.numpy as jnp

from .registry import defop, alias, OPS

# ---------------------------------------------------------------------------
# FFT family
# ---------------------------------------------------------------------------


@defop("_contrib_fft")
def contrib_fft(data, compute_size=128):
    """FFT along the last axis; complex output interleaved as
    [r0, i0, r1, i1, ...] -> (..., 2d) (ref: contrib/fft-inl.h).
    ``compute_size`` (the reference's batching knob) is accepted and
    ignored — XLA tiles the batch itself."""
    f = jnp.fft.fft(data.astype(jnp.complex64), axis=-1)
    out = jnp.stack([f.real, f.imag], axis=-1)
    return out.reshape(data.shape[:-1] + (2 * data.shape[-1],)) \
        .astype(data.dtype)


@defop("_contrib_ifft")
def contrib_ifft(data, compute_size=128):
    """Unnormalized inverse FFT of interleaved complex input:
    out = n * ifft(in) (cuFFT inverse applies no 1/n, and the
    reference passes it through — ref: contrib/ifft-inl.h)."""
    d = data.shape[-1] // 2
    c = data.reshape(data.shape[:-1] + (d, 2))
    z = c[..., 0] + 1j * c[..., 1]
    out = jnp.fft.ifft(z, axis=-1).real * d
    return out.astype(data.dtype)


# ---------------------------------------------------------------------------
# count sketch
# ---------------------------------------------------------------------------


@defop("_contrib_count_sketch")
def count_sketch(data, h, s, out_dim=0, processing_batch_size=32):
    """Count-sketch projection (ref: contrib/count_sketch-inl.h):
    out[n, h[j]] += s[j] * data[n, j]."""
    hh = h.reshape(-1).astype(jnp.int32)
    ss = s.reshape(-1).astype(data.dtype)
    signed = data * ss[None, :]
    out = jnp.zeros((data.shape[0], int(out_dim)), data.dtype)
    return out.at[:, hh].add(signed)


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


@defop("_contrib_quantize", num_outputs=3, differentiable=False)
def quantize(data, min_range, max_range, out_type="uint8"):
    """Linear quantization to uint8 over [min_range, max_range]
    (ref: contrib/quantize-inl.h QuantizeCompute — the reference
    kernel supports only uint8 too)."""
    if out_type != "uint8":
        raise ValueError(
            f"_contrib_quantize supports out_type='uint8' only "
            f"(like the reference kernel); got {out_type!r}")
    lo = min_range.reshape(())
    hi = max_range.reshape(())
    scale = 255.0 / (hi - lo)
    q = jnp.clip(jnp.round((data - lo) * scale), 0, 255)
    return (q.astype(jnp.uint8), min_range * 1.0, max_range * 1.0)


@defop("_contrib_dequantize", differentiable=False)
def dequantize(data, min_range, max_range, out_type="float32"):
    """(ref: contrib/dequantize-inl.h)"""
    lo = min_range.reshape(())
    hi = max_range.reshape(())
    scale = (hi - lo) / 255.0
    return data.astype(jnp.float32) * scale + lo


# ---------------------------------------------------------------------------
# Correlation (FlowNet)
# ---------------------------------------------------------------------------


@defop("Correlation")
def correlation(data1, data2, kernel_size=1, max_displacement=1,
                stride1=1, stride2=1, pad_size=0, is_multiply=True):
    """FlowNet correlation layer (ref: src/operator/
    correlation-inl.h).  For every output position and every
    displacement (dy,dx) on the stride2 grid within
    max_displacement, correlates a kernel_size^2 patch of data1 with
    the displaced patch of data2, averaged over channels*K^2.
    Output: (B, D*D, out_h, out_w), displacement-major like the
    reference (dy slow, dx fast).  The static D^2 loop unrolls into
    fused multiply-reduces under jit."""
    b, c, h, w = data1.shape
    K = int(kernel_size)
    pad = int(pad_size)
    md = int(max_displacement)
    s1, s2 = int(stride1), int(stride2)
    d2 = md // s2
    # pad both inputs
    p1 = jnp.pad(data1, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    p2 = jnp.pad(data2, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    kr = K // 2
    border = kr + md
    ph, pw = h + 2 * pad, w + 2 * pad
    out_h = (ph - 2 * border + s1 - 1) // s1
    out_w = (pw - 2 * border + s1 - 1) // s1
    ys = border + s1 * jnp.arange(out_h)
    xs = border + s1 * jnp.arange(out_w)

    combine = ((lambda a, b: a * b) if is_multiply
               else (lambda a, b: jnp.abs(a - b)))
    outs = []
    for dy in range(-d2 * s2, d2 * s2 + 1, s2):
        for dx in range(-d2 * s2, d2 * s2 + 1, s2):
            # correlate channel-wise then mean over c*K^2
            acc = 0
            for ky in range(-kr, K - kr):
                for kx in range(-kr, K - kr):
                    rows = ys + ky
                    cols = xs + kx
                    a = p1[:, :, rows][:, :, :, cols]
                    bb = p2[:, :, rows + dy][:, :, :, cols + dx]
                    acc = acc + combine(a, bb).sum(axis=1)
            outs.append(acc / (c * K * K))
    return jnp.stack(outs, axis=1).astype(data1.dtype)


# ---------------------------------------------------------------------------
# deformable PS-ROI pooling
# ---------------------------------------------------------------------------


@defop("_contrib_DeformablePSROIPooling", variadic=True,
       num_outputs=1)
def deformable_psroi_pooling(*inputs, spatial_scale=1.0, output_dim=1,
                             group_size=1, pooled_size=1, part_size=0,
                             sample_per_part=1, trans_std=0.0,
                             no_trans=False):
    """Deformable position-sensitive ROI pooling (ref: contrib/
    deformable_psroi_pooling-inl.h; R-FCN + Deformable ConvNets).

    inputs: data (B, output_dim*group_size^2, H, W), rois (R, 5)
    [batch_idx, x0, y0, x1, y1] in image coords, and unless
    ``no_trans`` a trans tensor (R, 2*cls, part, part) of normalized
    bin offsets.  Output (R, output_dim, pooled, pooled)."""
    data, rois = inputs[0], inputs[1]
    trans = None if (no_trans or len(inputs) < 3) else inputs[2]
    B, C, H, W = data.shape
    g = int(group_size)
    p = int(pooled_size)
    part = int(part_size) if part_size else p
    spp = int(sample_per_part)
    odim = int(output_dim)

    def one_roi(roi, tr):
        bidx = roi[0].astype(jnp.int32)
        x0 = roi[1] * spatial_scale - 0.5
        y0 = roi[2] * spatial_scale - 0.5
        x1 = roi[3] * spatial_scale + 0.5
        y1 = roi[4] * spatial_scale + 0.5
        rw = jnp.maximum(x1 - x0, 0.1)
        rh = jnp.maximum(y1 - y0, 0.1)
        bw, bh = rw / p, rh / p
        img = data[bidx]                      # (C, H, W)
        sub = bw / (spp + 1.0)
        sbh = bh / (spp + 1.0)
        ods = jnp.arange(odim)
        # per-class deformation offsets (ref: class_id = ctop /
        # channels_each_class, trans channels [2*cls, 2*cls+1])
        n_cls = 1 if tr is None else tr.shape[0] // 2
        cec = max(odim // max(n_cls, 1), 1)
        cls_ids = ods // cec
        outs = jnp.zeros((odim, p, p), data.dtype)
        for py in range(p):
            for px in range(p):
                pt_y = min(py * part // p, part - 1)
                pt_x = min(px * part // p, part - 1)
                if tr is None:
                    dx = dy = jnp.zeros((odim,), jnp.float32)
                else:
                    dx = tr[cls_ids * 2, pt_y, pt_x] \
                        * trans_std * rw
                    dy = tr[cls_ids * 2 + 1, pt_y, pt_x] \
                        * trans_std * rh
                gy = min(py * g // p, g - 1)
                gx = min(px * g // p, g - 1)
                # ctop-major channel map, same as PSROIPooling:
                # input channel = (ctop*g + gy)*g + gx
                chans = (ods * g + gy) * g + gx
                acc = jnp.zeros((odim,), data.dtype)
                for iy in range(1, spp + 1):
                    for ix in range(1, spp + 1):
                        sy = y0 + py * bh + iy * sbh + dy
                        sx = x0 + px * bw + ix * sub + dx
                        syc = jnp.clip(sy, 0.0, H - 1.0)
                        sxc = jnp.clip(sx, 0.0, W - 1.0)
                        yl = jnp.floor(syc).astype(jnp.int32)
                        xl = jnp.floor(sxc).astype(jnp.int32)
                        yh = jnp.minimum(yl + 1, H - 1)
                        xh = jnp.minimum(xl + 1, W - 1)
                        wy = syc - yl
                        wx = sxc - xl
                        v = ((1 - wy) * (1 - wx) * img[chans, yl, xl]
                             + (1 - wy) * wx * img[chans, yl, xh]
                             + wy * (1 - wx) * img[chans, yh, xl]
                             + wy * wx * img[chans, yh, xh])
                        inb = ((sy > -1) & (sy < H) & (sx > -1)
                               & (sx < W)).astype(data.dtype)
                        acc = acc + v * inb
                outs = outs.at[:, py, px].set(acc / (spp * spp))
        return outs

    if trans is None:
        return jax.vmap(lambda r: one_roi(r, None))(rois)
    return jax.vmap(one_roi)(rois, trans)


# ---------------------------------------------------------------------------
# loss attachments
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _kl_sparse_fn(sparseness_target, penalty):
    @jax.custom_vjp
    def f(data):
        return data * 1.0

    def fwd(data):
        return data * 1.0, data

    def bwd(data, g):
        # KL sparsity penalty on the mean activation per hidden unit
        # (ref: identity_attach_KL_sparse_reg-inl.h; divergence: the
        # batch mean stands in for the momentum moving average)
        rho = jnp.clip(jnp.mean(data, axis=0), 1e-6, 1 - 1e-6)
        t = sparseness_target
        kl = (-t / rho + (1 - t) / (1 - rho)) / data.shape[0]
        return (g + penalty * kl[None, :].astype(g.dtype),)

    f.defvjp(fwd, bwd)
    return f


@defop("IdentityAttachKLSparseReg")
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1,
                                  penalty=0.001, momentum=0.9):
    """Identity that adds a KL sparseness-penalty gradient
    (ref: src/operator/identity_attach_KL_sparse_reg.cc)."""
    return _kl_sparse_fn(float(sparseness_target), float(penalty))(data)


# ---------------------------------------------------------------------------
# storage / shape utilities
# ---------------------------------------------------------------------------


@defop("cast_storage", aliases=["_sparse_cast_storage"])
def cast_storage_op(data, stype="default"):
    """Graph-level storage cast (ref: tensor/cast_storage.cc).  In
    jnp graphs every tensor is dense, so 'default' is the identity;
    sparse targets exist only on the imperative NDArray surface
    (``arr.tostype`` / ``nd.sparse.cast_storage``)."""
    if stype != "default":
        raise ValueError(
            "cast_storage inside a compiled graph supports only "
            "stype='default' (XLA tensors are dense); use "
            "NDArray.tostype / nd.sparse.cast_storage imperatively")
    return data * 1.0


@defop("reshape_like")
def reshape_like(lhs, rhs):
    """(ref: tensor/elemwise_unary_op_basic.cc reshape_like)"""
    return lhs.reshape(rhs.shape)


@defop("_sparse_retain")
def sparse_retain_op(data, indices):
    """Dense-graph semantics of sparse_retain (ref: tensor/
    sparse_retain.cc): rows whose index is absent become zero."""
    idx = indices.reshape(-1).astype(jnp.int32)
    keep = (jnp.arange(data.shape[0])[:, None] == idx[None, :]) \
        .any(axis=1)
    return data * keep.reshape((-1,) + (1,) * (data.ndim - 1)) \
        .astype(data.dtype)


@defop("_square_sum")
def square_sum(data, axis=None, keepdims=False):
    """(ref: tensor/square_sum-inl.h — the sparse-optimized
    sum(x^2); dense here, XLA fuses the square into the reduce)"""
    ax = axis if axis is None else int(axis)
    return jnp.sum(jnp.square(data), axis=ax, keepdims=bool(keepdims))


@defop("_scatter_elemwise_div")
def scatter_elemwise_div(lhs, rhs):
    """(ref: tensor/elemwise_binary_op_basic.cc scatter alias —
    storage-aware division; dense math is identical)"""
    return lhs / rhs


@defop("_scatter_plus_scalar")
def scatter_plus_scalar(data, scalar=0.0):
    return data + scalar


@defop("_scatter_minus_scalar")
def scatter_minus_scalar(data, scalar=0.0):
    return data - scalar


# legacy plugin hooks: the Custom op is the supported extension point
@defop("_NDArray", differentiable=False)
def _ndarray_plugin(*args, **kwargs):
    """Legacy NDArray-function plugin hook (ref: plugin/). Python
    extension ops use operator.CustomOp here."""
    raise NotImplementedError(
        "_NDArray plugin ops are not supported; implement a Custom "
        "op (incubator_mxnet_tpu.operator.CustomOp) instead")


@defop("_Native", differentiable=False)
def _native_plugin(*args, **kwargs):
    """Legacy native-callback plugin hook (ref: plugin/)."""
    raise NotImplementedError(
        "_Native plugin ops are not supported; implement a Custom "
        "op (incubator_mxnet_tpu.operator.CustomOp) instead")


# MakeLoss: the op-property loss head (ref: src/operator/
# make_loss.cc) — forward identity, backward grad_scale (optionally
# normalized), independent of the incoming cotangent
@functools.lru_cache(maxsize=None)
def _make_loss_fn(grad_scale, valid_thresh, normalization):
    @jax.custom_vjp
    def f(data):
        return data * 1.0

    def fwd(data):
        return data * 1.0, data

    def bwd(data, g):
        scale = grad_scale
        if normalization == "batch":
            scale = scale / data.shape[0]
        grad = jnp.full(data.shape, scale, data.dtype)
        if normalization == "valid":
            valid = jnp.maximum(
                jnp.sum((data > valid_thresh).astype(data.dtype)), 1.0)
            grad = grad / valid
        return (grad,)

    f.defvjp(fwd, bwd)
    return f


def _make_loss_head(data, grad_scale=1.0, valid_thresh=0.0,
                    normalization="null"):
    """(ref: src/operator/make_loss.cc MakeLossOp)"""
    return _make_loss_fn(float(grad_scale), float(valid_thresh),
                         str(normalization))(data)


# upgrade the plain 'make_loss' registration in elemwise.py to the
# loss-head gradient semantics and add the legacy name
OPS["make_loss"].fn = _make_loss_head
alias("make_loss", "MakeLoss")
