"""Native C training ABI (ref role: cpp-package/include/mxnet-cpp/
MxNetCpp.h — the reference's C++ training surface):
libmxtpu_train.so embeds the interpreter; a C client creates a
trainer from symbol JSON, feeds batches, steps the fused
fwd+bwd+update executable, and exports trained params that the
predict ABI then serves."""
import ctypes
import os
import subprocess

import numpy as np

import incubator_mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src", "c_train")
SO = os.path.join(SRC, "libmxtpu_train.so")


def _build_lib():
    if not os.path.exists(SO):
        subprocess.run(["make", "-C", SRC], check=True,
                       capture_output=True, timeout=300)
    return SO


def _bind(lib):
    u = ctypes.c_uint
    lib.MXTPUTrainGetLastError.restype = ctypes.c_char_p
    lib.MXTPUTrainCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, u, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(u), ctypes.POINTER(u), ctypes.c_char_p,
        ctypes.c_float, ctypes.POINTER(ctypes.c_void_p)]
    lib.MXTPUTrainSetInput.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_float), u]
    lib.MXTPUTrainStep.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_float)]
    lib.MXTPUTrainForward.argtypes = [ctypes.c_void_p]
    lib.MXTPUTrainGetOutputShape.argtypes = [
        ctypes.c_void_p, u, ctypes.POINTER(ctypes.POINTER(u)),
        ctypes.POINTER(u)]
    lib.MXTPUTrainGetOutput.argtypes = [
        ctypes.c_void_p, u, ctypes.POINTER(ctypes.c_float), u]
    lib.MXTPUTrainGetParams.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int)]
    lib.MXTPUTrainFree.argtypes = [ctypes.c_void_p]
    return lib


def _train_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=3)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _problem():
    rs = np.random.RandomState(0)
    x = rs.rand(32, 6).astype(np.float32)
    w = rs.rand(6, 3).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.float32)
    return x, y


def test_c_train_loss_decreases_and_params_deploy(tmp_path):
    lib = _bind(ctypes.CDLL(_build_lib()))
    sym_json = _train_symbol().tojson().encode()
    x, y = _problem()

    keys = (ctypes.c_char_p * 2)(b"data", b"softmax_label")
    indptr = (ctypes.c_uint * 3)(0, 2, 3)
    shape = (ctypes.c_uint * 3)(32, 6, 32)
    handle = ctypes.c_void_p()
    rc = lib.MXTPUTrainCreate(sym_json, None, 0, 1, 0, 2, keys,
                              indptr, shape, b"sgd",
                              ctypes.c_float(0.5),
                              ctypes.byref(handle))
    assert rc == 0, lib.MXTPUTrainGetLastError()

    xf = np.ascontiguousarray(x).ravel()
    yf = np.ascontiguousarray(y).ravel()
    xp = xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    yp = yf.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    assert lib.MXTPUTrainSetInput(handle, b"data", xp, xf.size) == 0
    assert lib.MXTPUTrainSetInput(handle, b"softmax_label", yp,
                                  yf.size) == 0

    loss = ctypes.c_float()
    losses = []
    for _ in range(40):
        assert lib.MXTPUTrainStep(handle, ctypes.byref(loss)) == 0, \
            lib.MXTPUTrainGetLastError()
        losses.append(float(loss.value))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

    # eval forward + output readback
    assert lib.MXTPUTrainForward(handle) == 0
    sdata = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    assert lib.MXTPUTrainGetOutputShape(
        handle, 0, ctypes.byref(sdata), ctypes.byref(ndim)) == 0
    oshape = tuple(sdata[i] for i in range(ndim.value))
    assert oshape == (32, 3), oshape
    probs = np.zeros(32 * 3, np.float32)
    pp = probs.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    assert lib.MXTPUTrainGetOutput(handle, 0, pp, probs.size) == 0
    acc = (probs.reshape(32, 3).argmax(1) == y).mean()
    assert acc > 0.8, acc

    # trained params round-trip into the predict ABI's loader
    blob = ctypes.c_void_p()
    size = ctypes.c_int()
    assert lib.MXTPUTrainGetParams(handle, ctypes.byref(blob),
                                   ctypes.byref(size)) == 0
    raw = ctypes.string_at(blob, size.value)
    pfile = tmp_path / "trained.params"
    pfile.write_bytes(raw)
    from incubator_mxnet_tpu.model import split_tagged_params
    arg_p, aux_p = split_tagged_params(mx.nd.load(str(pfile)))
    assert "fc1_weight" in arg_p and "fc2_bias" in arg_p
    # rebuilding a python Module from the blob reproduces the output
    mod = mx.mod.Module(_train_symbol(), context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", (32, 6))],
             label_shapes=[mx.io.DataDesc("softmax_label", (32,))],
             for_training=False)
    mod.set_params(arg_p, aux_p)
    mod.forward(mx.io.DataBatch([mx.nd.array(x)],
                                [mx.nd.array(y)]), is_train=False)
    np.testing.assert_allclose(
        mod.get_outputs()[0].asnumpy().ravel(), probs, rtol=1e-4,
        atol=1e-5)

    assert lib.MXTPUTrainFree(handle) == 0

    # error surface: unknown input key fails loudly at create
    bad_keys = (ctypes.c_char_p * 2)(b"data", b"nope_label")
    h2 = ctypes.c_void_p()
    rc = lib.MXTPUTrainCreate(sym_json, None, 0, 1, 0, 2, bad_keys,
                              indptr, shape, b"sgd",
                              ctypes.c_float(0.1), ctypes.byref(h2))
    assert rc == -1
    assert b"nope_label" in lib.MXTPUTrainGetLastError()


def test_c_train_resume_from_params(tmp_path):
    """param_bytes at create resumes training instead of Xavier."""
    lib = _bind(ctypes.CDLL(_build_lib()))
    sym_json = _train_symbol().tojson().encode()
    x, y = _problem()
    keys = (ctypes.c_char_p * 2)(b"data", b"softmax_label")
    indptr = (ctypes.c_uint * 3)(0, 2, 3)
    shape = (ctypes.c_uint * 3)(32, 6, 32)

    h1 = ctypes.c_void_p()
    assert lib.MXTPUTrainCreate(sym_json, None, 0, 1, 0, 2, keys,
                                indptr, shape, b"sgd",
                                ctypes.c_float(0.5),
                                ctypes.byref(h1)) == 0
    xf, yf = x.ravel().copy(), y.ravel().copy()
    xp = xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    yp = yf.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    lib.MXTPUTrainSetInput(h1, b"data", xp, xf.size)
    lib.MXTPUTrainSetInput(h1, b"softmax_label", yp, yf.size)
    loss = ctypes.c_float()
    for _ in range(20):
        lib.MXTPUTrainStep(h1, ctypes.byref(loss))
    mid_loss = float(loss.value)
    blob, size = ctypes.c_void_p(), ctypes.c_int()
    assert lib.MXTPUTrainGetParams(h1, ctypes.byref(blob),
                                   ctypes.byref(size)) == 0
    raw = ctypes.string_at(blob, size.value)
    lib.MXTPUTrainFree(h1)

    h2 = ctypes.c_void_p()
    assert lib.MXTPUTrainCreate(sym_json, raw, len(raw), 1, 0, 2,
                                keys, indptr, shape, b"sgd",
                                ctypes.c_float(0.5),
                                ctypes.byref(h2)) == 0, \
        lib.MXTPUTrainGetLastError()
    lib.MXTPUTrainSetInput(h2, b"data", xp, xf.size)
    lib.MXTPUTrainSetInput(h2, b"softmax_label", yp, yf.size)
    assert lib.MXTPUTrainStep(h2, ctypes.byref(loss)) == 0
    # resumed loss continues from the trained state, not from scratch
    assert float(loss.value) < mid_loss * 1.5
    lib.MXTPUTrainFree(h2)


def test_c_train_regression_head_reports_mse():
    """Loss semantics follow the head op:
    a LinearRegressionOutput head must report mean squared error —
    not the mean of the predictions — and it must decrease."""
    lib = _bind(ctypes.CDLL(_build_lib()))
    # the glue shares this process's interpreter: pin the init draw so
    # convergence doesn't depend on sibling tests' PRNG consumption
    mx.random.seed(7)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="rfc", num_hidden=1)
    sym = mx.sym.LinearRegressionOutput(net, name="lro")
    sym_json = sym.tojson().encode()

    rs = np.random.RandomState(1)
    x = rs.rand(32, 4).astype(np.float32)
    w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w).astype(np.float32)

    keys = (ctypes.c_char_p * 2)(b"data", b"lro_label")
    indptr = (ctypes.c_uint * 3)(0, 2, 4)
    shape = (ctypes.c_uint * 4)(32, 4, 32, 1)
    h = ctypes.c_void_p()
    assert lib.MXTPUTrainCreate(sym_json, None, 0, 1, 0, 2, keys,
                                indptr, shape, b"adam",
                                ctypes.c_float(0.1),
                                ctypes.byref(h)) == 0, \
        lib.MXTPUTrainGetLastError()
    xf, yf = x.ravel().copy(), y.ravel().copy()
    lib.MXTPUTrainSetInput(
        h, b"data", xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        xf.size)
    lib.MXTPUTrainSetInput(
        h, b"lro_label",
        yf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), yf.size)
    loss = ctypes.c_float()
    losses = []
    for _ in range(100):
        assert lib.MXTPUTrainStep(h, ctypes.byref(loss)) == 0
        losses.append(float(loss.value))

    # the first reported value must be an MSE (positive, plausibly
    # large), and training must shrink it hard on this linear problem
    assert losses[0] > 0.1, losses[0]
    assert losses[-1] < 0.05 * losses[0], (losses[0], losses[-1])

    # cross-check the final report against an MSE computed from the
    # outputs the ABI itself returns
    assert lib.MXTPUTrainForward(h) == 0
    out = np.empty(32, np.float32)
    assert lib.MXTPUTrainGetOutput(
        h, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size) == 0
    mse = float(((out.reshape(32, 1) - y) ** 2).mean())
    assert abs(mse - losses[-1]) < max(0.1 * losses[-1], 1e-3), \
        (mse, losses[-1])
    lib.MXTPUTrainFree(h)
