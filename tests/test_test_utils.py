"""The shipped test_utils fixtures themselves."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import test_utils as tu


def _net():
    x = mx.sym.Variable("x")
    w = mx.sym.Variable("w")
    return mx.sym.FullyConnected(x, w, no_bias=True, num_hidden=4,
                                 name="fc")


def test_check_numeric_gradient_catches_good_and_bad():
    sym = mx.sym.tanh(mx.sym.Variable("x"))
    tu.check_numeric_gradient(sym, {"x": np.random.rand(3, 3)
                                    .astype(np.float32)})


def test_check_symbolic_forward_backward():
    rs = np.random.RandomState(0)
    x = rs.rand(2, 3).astype(np.float32)
    w = rs.rand(4, 3).astype(np.float32)
    sym = _net()
    tu.check_symbolic_forward(sym, {"x": x, "w": w}, [x @ w.T],
                              rtol=1e-4)
    og = rs.rand(2, 4).astype(np.float32)
    tu.check_symbolic_backward(sym, {"x": x, "w": w}, [og],
                               {"x": og @ w, "w": og.T @ x},
                               rtol=1e-4)


def test_check_consistency_dtypes():
    """fp32 vs bf16 vs fp16 runs of the same graph agree at relaxed
    tolerance — and the dtypes actually differ (round-3 review
    regression: specs used to be silently flattened to fp32)."""
    sym = _net()
    ctx_list = [
        dict(ctx=mx.cpu(), x=(2, 3), w=(4, 3)),
        dict(ctx=mx.cpu(), x=(2, 3), w=(4, 3),
             type_dict={"x": "bfloat16", "w": "bfloat16"}),
        dict(ctx=mx.cpu(), x=(2, 3), w=(4, 3),
             type_dict={"x": np.float16, "w": np.float16}),
    ]
    results = tu.check_consistency(sym, ctx_list)
    assert len(results) == 3


def test_check_consistency_lowprec_first_spec():
    """A low-precision entry listed first must still relax tolerance."""
    sym = _net()
    ctx_list = [
        dict(ctx=mx.cpu(), x=(2, 3), w=(4, 3),
             type_dict={"x": np.float16, "w": np.float16}),
        dict(ctx=mx.cpu(), x=(2, 3), w=(4, 3)),
    ]
    tu.check_consistency(sym, ctx_list)


def test_rand_ndarray_stypes():
    d = tu.rand_ndarray((4, 5))
    assert d.shape == (4, 5)
    c = tu.rand_ndarray((4, 5), stype="csr")
    assert c.stype == "csr" and not c.has_dense_mirror()
    r = tu.rand_ndarray((4, 5), stype="row_sparse")
    assert r.stype == "row_sparse" and not r.has_dense_mirror()


def test_assert_almost_equal_raises():
    with pytest.raises(AssertionError):
        tu.assert_almost_equal(np.ones(3), np.zeros(3))
