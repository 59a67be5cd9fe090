"""Contrib op tail: fft/ifft, count_sketch,
quantize/dequantize, Correlation, DeformablePSROIPooling, MakeLoss,
IdentityAttachKLSparseReg, cast_storage/reshape_like/_sparse_retain/
_square_sum.  Oracles are independent numpy implementations of the
documented reference semantics."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd

RS = np.random.RandomState(0)


# ---------------------------------------------------------------- fft

def test_fft_matches_numpy():
    x = RS.rand(3, 8).astype(np.float32)
    out = nd.contrib.fft(nd.array(x)).asnumpy()
    ref = np.fft.fft(x, axis=-1)
    inter = np.empty((3, 16), np.float32)
    inter[:, 0::2] = ref.real
    inter[:, 1::2] = ref.imag
    np.testing.assert_allclose(out, inter, rtol=1e-4, atol=1e-4)


def test_ifft_unnormalized_roundtrip():
    """MXNet's ifft is the unnormalized cuFFT inverse: ifft(fft(x))
    == n * x (ref: contrib/ifft-inl.h)."""
    x = RS.rand(2, 16).astype(np.float32)
    rt = nd.contrib.ifft(nd.contrib.fft(nd.array(x))).asnumpy()
    np.testing.assert_allclose(rt, 16 * x, rtol=1e-4, atol=1e-4)


def test_fft_gradient():
    x = nd.array(RS.rand(2, 8).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y = (nd.contrib.fft(x) ** 2).sum()
    y.backward()
    g = x.grad.asnumpy()
    assert np.all(np.isfinite(g)) and np.abs(g).max() > 0


# ------------------------------------------------------- count sketch

def test_count_sketch_oracle():
    n, d, od = 4, 10, 6
    x = RS.rand(n, d).astype(np.float32)
    h = RS.randint(0, od, d).astype(np.float32)
    s = np.where(RS.rand(d) < 0.5, -1.0, 1.0).astype(np.float32)
    out = nd.contrib.count_sketch(
        nd.array(x), nd.array(h[None]), nd.array(s[None]),
        out_dim=od).asnumpy()
    want = np.zeros((n, od), np.float32)
    for j in range(d):
        want[:, int(h[j])] += s[j] * x[:, j]
    np.testing.assert_allclose(out, want, rtol=1e-5)


# --------------------------------------------------------- quantize

def test_quantize_dequantize_roundtrip():
    x = RS.uniform(-3, 7, (4, 5)).astype(np.float32)
    lo, hi = nd.array([-3.0]), nd.array([7.0])
    q, qlo, qhi = nd.contrib.quantize(nd.array(x), lo, hi)
    assert q.asnumpy().dtype == np.uint8
    back = nd.contrib.dequantize(q, qlo, qhi).asnumpy()
    np.testing.assert_allclose(back, x, atol=10.0 / 255 + 1e-6)


# ------------------------------------------------------- correlation

def _corr_oracle(a, b, md, pad):
    """kernel 1, strides 1: out[dy,dx] = mean_c a[y,x] b[y+dy,x+dx]."""
    B, C, H, W = a.shape
    ap = np.pad(a, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    bp = np.pad(b, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    D = 2 * md + 1
    out = np.zeros((B, D * D, H, W), np.float32)
    k = 0
    for dy in range(-md, md + 1):
        for dx in range(-md, md + 1):
            for y in range(H):
                for x in range(W):
                    yy, xx = y + md + dy, x + md + dx
                    out[:, k, y, x] = (
                        ap[:, :, y + md, x + md]
                        * bp[:, :, yy, xx]).mean(axis=1)
            k += 1
    return out


def test_correlation_oracle():
    a = RS.rand(2, 3, 5, 5).astype(np.float32)
    b = RS.rand(2, 3, 5, 5).astype(np.float32)
    out = nd.Correlation(nd.array(a), nd.array(b), kernel_size=1,
                         max_displacement=2, stride1=1, stride2=1,
                         pad_size=2).asnumpy()
    np.testing.assert_allclose(out, _corr_oracle(a, b, 2, 2),
                               rtol=1e-4, atol=1e-5)


def test_correlation_subtract_mode_and_grad():
    a = nd.array(RS.rand(1, 2, 4, 4).astype(np.float32))
    b = nd.array(RS.rand(1, 2, 4, 4).astype(np.float32))
    a.attach_grad()
    with autograd.record():
        y = nd.Correlation(a, b, kernel_size=1, max_displacement=1,
                           pad_size=1, is_multiply=False).sum()
    y.backward()
    assert np.all(np.isfinite(a.grad.asnumpy()))


# ---------------------------------------- deformable PS-ROI pooling

def _dpsroi_oracle(data, roi, odim, g, p, spp, scale, trans,
                   trans_std):
    """Loop oracle of deformable_psroi_pooling-inl.h for one roi."""
    _, C, H, W = data.shape
    b = int(roi[0])
    x0 = roi[1] * scale - 0.5
    y0 = roi[2] * scale - 0.5
    x1 = roi[3] * scale + 0.5
    y1 = roi[4] * scale + 0.5
    rw, rh = max(x1 - x0, 0.1), max(y1 - y0, 0.1)
    bw, bh = rw / p, rh / p
    sub, sbh = bw / (spp + 1.0), bh / (spp + 1.0)
    out = np.zeros((odim, p, p), np.float32)
    for py in range(p):
        for px in range(p):
            dx = dy = 0.0
            gy, gx = min(py * g // p, g - 1), min(px * g // p, g - 1)
            n_cls = 1 if trans is None else trans.shape[0] // 2
            cec = max(odim // max(n_cls, 1), 1)
            for od in range(odim):
                ch = (od * g + gy) * g + gx  # ctop-major, like PSROI
                if trans is not None:
                    cls = od // cec
                    pt_y = min(py * trans.shape[-2] // p,
                               trans.shape[-2] - 1)
                    pt_x = min(px * trans.shape[-1] // p,
                               trans.shape[-1] - 1)
                    dx = trans[cls * 2, pt_y, pt_x] * trans_std * rw
                    dy = trans[cls * 2 + 1, pt_y, pt_x] \
                        * trans_std * rh
                acc = 0.0
                for iy in range(1, spp + 1):
                    for ix in range(1, spp + 1):
                        sy = y0 + py * bh + iy * sbh + dy
                        sx = x0 + px * bw + ix * sub + dx
                        if not (-1 < sy < H and -1 < sx < W):
                            continue
                        syc = min(max(sy, 0.0), H - 1.0)
                        sxc = min(max(sx, 0.0), W - 1.0)
                        yl, xl = int(syc), int(sxc)
                        yh, xh = min(yl + 1, H - 1), min(xl + 1, W - 1)
                        wy, wx = syc - yl, sxc - xl
                        v = ((1 - wy) * (1 - wx) * data[b, ch, yl, xl]
                             + (1 - wy) * wx * data[b, ch, yl, xh]
                             + wy * (1 - wx) * data[b, ch, yh, xl]
                             + wy * wx * data[b, ch, yh, xh])
                        acc += v
                out[od, py, px] = acc / (spp * spp)
    return out


@pytest.mark.parametrize("with_trans", [False, True])
def test_deformable_psroi_oracle(with_trans):
    odim, g, p, spp = 2, 2, 2, 2
    data = RS.rand(1, odim * g * g, 9, 9).astype(np.float32)
    rois = np.array([[0, 1, 1, 7, 7]], np.float32)
    if with_trans:
        trans = (RS.rand(1, 2, p, p).astype(np.float32) - 0.5)
        out = nd.contrib.DeformablePSROIPooling(
            nd.array(data), nd.array(rois), nd.array(trans),
            spatial_scale=1.0, output_dim=odim, group_size=g,
            pooled_size=p, sample_per_part=spp, trans_std=0.1)
        want = _dpsroi_oracle(data, rois[0], odim, g, p, spp, 1.0,
                              trans[0], 0.1)
    else:
        out = nd.contrib.DeformablePSROIPooling(
            nd.array(data), nd.array(rois), spatial_scale=1.0,
            output_dim=odim, group_size=g, pooled_size=p,
            sample_per_part=spp, no_trans=True)
        want = _dpsroi_oracle(data, rois[0], odim, g, p, spp, 1.0,
                              None, 0.0)
    np.testing.assert_allclose(out.asnumpy()[0], want, rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------------------- loss heads

def test_make_loss_grad_scale_and_normalization():
    x = nd.array(RS.rand(4, 3).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.MakeLoss(x, grad_scale=2.0, normalization="batch")
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(),
                               np.full((4, 3), 0.5), rtol=1e-6)


def test_identity_attach_kl_sparse_reg_grad():
    x = nd.array(RS.uniform(0.2, 0.8, (6, 4)).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.IdentityAttachKLSparseReg(x, sparseness_target=0.2,
                                         penalty=0.1)
    y.backward()
    rho = x.asnumpy().mean(0)
    kl = (-0.2 / rho + 0.8 / (1 - rho)) / 6
    want = 1.0 + 0.1 * np.broadcast_to(kl, (6, 4))
    np.testing.assert_allclose(x.grad.asnumpy(), want, rtol=1e-4)


# ----------------------------------------------- storage / shapes

def test_cast_storage_graph_and_imperative():
    x = nd.array(RS.rand(2, 3).astype(np.float32))
    out = nd.cast_storage(x, stype="csr")  # imperative: storage-aware
    assert out.stype == "csr"
    sym_x = mx.sym.Variable("x")
    s = mx.sym.cast_storage(sym_x, stype="default")
    exe = s.simple_bind(mx.cpu(), grad_req="null", x=(2, 3))
    np.testing.assert_allclose(
        exe.forward(x=x)[0].asnumpy(), x.asnumpy())


def test_reshape_like_and_grad():
    a = nd.array(np.arange(6, dtype=np.float32))
    b = nd.array(np.zeros((2, 3), np.float32))
    a.attach_grad()
    with autograd.record():
        y = (nd.reshape_like(a, b) * 2).sum()
    y.backward()
    np.testing.assert_allclose(a.grad.asnumpy(), np.full(6, 2.0))


def test_square_sum_and_scatter_aliases():
    x = RS.rand(3, 4).astype(np.float32)
    out = nd._internal._square_sum(nd.array(x), axis=1).asnumpy()
    np.testing.assert_allclose(out, (x ** 2).sum(1), rtol=1e-5)
    d = nd._internal._scatter_elemwise_div(nd.array(x),
                                           nd.array(x + 1))
    np.testing.assert_allclose(d.asnumpy(), x / (x + 1), rtol=1e-6)


def test_plugin_hooks_raise_helpfully():
    with pytest.raises(NotImplementedError, match="Custom"):
        nd._internal._Native(nd.array(np.zeros(2, np.float32)))


# -------------------------------------------- appendix-A coverage

def test_appendix_a_coverage():
    """Every Appendix-A name the round-2 verdict listed as missing is
    now registered."""
    from incubator_mxnet_tpu.ops.registry import OPS
    for name in ["_contrib_fft", "_contrib_ifft",
                 "_contrib_count_sketch", "_contrib_quantize",
                 "_contrib_dequantize", "Correlation",
                 "_contrib_DeformablePSROIPooling", "MakeLoss",
                 "IdentityAttachKLSparseReg", "cast_storage",
                 "reshape_like", "_sparse_retain", "_square_sum",
                 "_scatter_elemwise_div", "_scatter_plus_scalar",
                 "_scatter_minus_scalar", "_NDArray", "_Native",
                 "_sparse_cast_storage"]:
        assert name in OPS, name


def test_appendix_a_full_parity():
    """Every op name in SURVEY.md Appendix A resolves in the registry
    (plugin ops excluded as out of scope: Caffe/Torch/WarpCTC)."""
    import os
    import re
    from incubator_mxnet_tpu.ops.registry import OPS
    survey = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SURVEY.md")
    txt = open(survey).read()
    sec = txt[txt.index("## Appendix A"):txt.index("## Appendix B")]

    def expand(tok):
        m = re.match(r"(.*)\{([^}]*)\}(.*)", tok)
        if not m:
            return [tok]
        out = []
        for mid in m.group(2).split(","):
            out.extend(expand(m.group(1) + mid.strip() + m.group(3)))
        return out

    names = set()
    for block in re.findall(r"`([^`]+)`", sec):
        for tok in block.replace("\n", " ").split():
            for n in expand(tok):
                if re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", n):
                    names.add(n)
    names -= {"CaffeLoss", "CaffeOp", "TorchCriterion", "TorchModule",
              "WarpCTC"}                       # plugins: out of scope
    names -= {"MXNET_REGISTER_OP_PROPERTY", "NNVM_REGISTER_OP",
              "add_alias"}                     # macro tokens, not ops
    missing = sorted(n for n in names if n not in OPS)
    assert not missing, missing
    assert len(names) >= 188  # the round-2 verdict's bar


def test_deformable_psroi_multiclass_trans():
    """Per-class offset channels are honored (round-3 review
    regression: class_id = ctop // channels_each_class)."""
    odim, g, p, spp = 2, 1, 2, 1
    data = RS.rand(1, odim * g * g, 8, 8).astype(np.float32)
    rois = np.array([[0, 1, 1, 6, 6]], np.float32)
    trans = (RS.rand(1, 4, p, p).astype(np.float32) - 0.5)  # 2 classes
    out = nd.contrib.DeformablePSROIPooling(
        nd.array(data), nd.array(rois), nd.array(trans),
        spatial_scale=1.0, output_dim=odim, group_size=g,
        pooled_size=p, sample_per_part=spp, trans_std=0.2)
    want = _dpsroi_oracle(data, rois[0], odim, g, p, spp, 1.0,
                          trans[0], 0.2)
    np.testing.assert_allclose(out.asnumpy()[0], want, rtol=1e-4,
                               atol=1e-5)


def test_cast_storage_dense_is_differentiable():
    """nd.cast_storage must stay on the autograd tape for dense
    arrays (round-3 review regression)."""
    x = nd.array(RS.rand(2, 3).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y = (nd.cast_storage(x, "default") * 2).sum()
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), np.full((2, 3), 2.0))
