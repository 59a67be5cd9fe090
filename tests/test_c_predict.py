"""Native C predict ABI (ref role: include/mxnet/c_predict_api.h /
src/c_api/c_predict_api.cc): libmxtpu_predict.so embeds the
interpreter and serves exported models to C programs.

Two drive modes:
  * ctypes  — the .so loaded into this process (attaches to the
    running interpreter), full create/input/forward/output cycle
  * C client — a real C program compiled against the header, run in
    a subprocess with a fresh embedded interpreter
"""
import ctypes
import os
import subprocess
import textwrap

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src", "c_predict")
SO = os.path.join(SRC, "libmxtpu_predict.so")


def _build_lib():
    if not os.path.exists(SO):
        subprocess.run(["make", "-C", SRC], check=True,
                       capture_output=True, timeout=300)
    return SO


def _export_model(tmp_path):
    mx.random.seed(3)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"),
                gluon.nn.Dense(3))
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).rand(2, 5)
                    .astype("float32"))
    ref_out = net(x).asnumpy()
    prefix = str(tmp_path / "cnet")
    net.export(prefix)
    return prefix, x.asnumpy(), ref_out


def _bind(lib):
    u = ctypes.c_uint
    lib.MXTPUGetLastError.restype = ctypes.c_char_p
    lib.MXTPUPredCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, u, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(u), ctypes.POINTER(u),
        ctypes.POINTER(ctypes.c_void_p)]
    lib.MXTPUPredSetInput.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_float), u]
    lib.MXTPUPredForward.argtypes = [ctypes.c_void_p]
    lib.MXTPUPredGetOutputShape.argtypes = [
        ctypes.c_void_p, u, ctypes.POINTER(ctypes.POINTER(u)),
        ctypes.POINTER(u)]
    lib.MXTPUPredGetOutput.argtypes = [
        ctypes.c_void_p, u, ctypes.POINTER(ctypes.c_float), u]
    lib.MXTPUPredFree.argtypes = [ctypes.c_void_p]
    return lib


def test_c_predict_ctypes_roundtrip(tmp_path):
    lib = _bind(ctypes.CDLL(_build_lib()))
    prefix, x, ref_out = _export_model(tmp_path)
    sym_json = open(prefix + "-symbol.json", "rb").read()
    params = open(prefix + "-0000.params", "rb").read()

    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape = (ctypes.c_uint * 2)(2, 5)
    handle = ctypes.c_void_p()
    rc = lib.MXTPUPredCreate(sym_json, params, len(params), 1, 0,
                             1, keys, indptr, shape,
                             ctypes.byref(handle))
    assert rc == 0, lib.MXTPUGetLastError()

    flat = np.ascontiguousarray(x, dtype=np.float32).ravel()
    buf = flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    assert lib.MXTPUPredSetInput(handle, b"data", buf, flat.size) == 0,\
        lib.MXTPUGetLastError()
    assert lib.MXTPUPredForward(handle) == 0, lib.MXTPUGetLastError()

    sdata = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    assert lib.MXTPUPredGetOutputShape(
        handle, 0, ctypes.byref(sdata), ctypes.byref(ndim)) == 0
    out_shape = tuple(sdata[i] for i in range(ndim.value))
    assert out_shape == (2, 3), out_shape

    out = np.zeros(6, dtype=np.float32)
    optr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    assert lib.MXTPUPredGetOutput(handle, 0, optr, out.size) == 0, \
        lib.MXTPUGetLastError()
    np.testing.assert_allclose(out.reshape(2, 3), ref_out,
                               rtol=1e-5, atol=1e-5)
    assert lib.MXTPUPredFree(handle) == 0

    # error path: bad input key reports through MXTPUGetLastError
    handle2 = ctypes.c_void_p()
    assert lib.MXTPUPredCreate(sym_json, params, len(params), 1, 0,
                               1, keys, indptr, shape,
                               ctypes.byref(handle2)) == 0
    rc = lib.MXTPUPredSetInput(handle2, b"nope", buf, flat.size)
    assert rc == -1
    assert b"nope" in lib.MXTPUGetLastError()
    lib.MXTPUPredFree(handle2)


DEMO_C = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include "c_predict_api.h"

static char *slurp(const char *path, long *size) {
    FILE *f = fopen(path, "rb");
    if (!f) { perror(path); exit(2); }
    fseek(f, 0, SEEK_END); *size = ftell(f); fseek(f, 0, SEEK_SET);
    char *buf = (char *)malloc(*size + 1);
    if (fread(buf, 1, *size, f) != (size_t)*size) exit(2);
    buf[*size] = 0; fclose(f);
    return buf;
}

int main(int argc, char **argv) {
    long sym_size, param_size;
    char *sym = slurp(argv[1], &sym_size);
    char *params = slurp(argv[2], &param_size);
    const char *keys[1] = {"data"};
    mx_uint indptr[2] = {0, 2};
    mx_uint shape[2] = {2, 5};
    PredictorHandle h;
    if (MXTPUPredCreate(sym, params, (int)param_size, 1, 0, 1, keys,
                        indptr, shape, &h) != 0) {
        fprintf(stderr, "create: %s\n", MXTPUGetLastError());
        return 1;
    }
    float in[10];
    for (int i = 0; i < 10; ++i) in[i] = (float)i / 10.0f;
    if (MXTPUPredSetInput(h, "data", in, 10) != 0 ||
        MXTPUPredForward(h) != 0) {
        fprintf(stderr, "run: %s\n", MXTPUGetLastError());
        return 1;
    }
    mx_uint *oshape, ondim;
    MXTPUPredGetOutputShape(h, 0, &oshape, &ondim);
    mx_uint total = 1;
    for (mx_uint i = 0; i < ondim; ++i) total *= oshape[i];
    float *out = (float *)malloc(total * sizeof(float));
    if (MXTPUPredGetOutput(h, 0, out, total) != 0) {
        fprintf(stderr, "out: %s\n", MXTPUGetLastError());
        return 1;
    }
    for (mx_uint i = 0; i < total; ++i) printf("%.6f\n", out[i]);
    MXTPUPredFree(h);
    return 0;
}
"""


def test_c_predict_standalone_client(tmp_path):
    """Compile a real C program against the header and run it with a
    fresh embedded interpreter — the reference's deployment story."""
    _build_lib()
    prefix, _, _ = _export_model(tmp_path)

    demo_c = tmp_path / "demo.c"
    demo_c.write_text(DEMO_C)
    demo = str(tmp_path / "demo")
    subprocess.run(
        ["gcc", "-O2", "-I", SRC, str(demo_c), "-o", demo,
         "-L", SRC, f"-Wl,-rpath,{SRC}", "-lmxtpu_predict"],
        check=True, capture_output=True, timeout=120)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MXTPU_FORCE_CPU"] = "1"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [demo, prefix + "-symbol.json", prefix + "-0000.params"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    vals = np.array([float(v) for v in r.stdout.split()],
                    dtype=np.float32)
    assert vals.shape == (6,)

    # oracle: same input through the Python predictor
    from incubator_mxnet_tpu.predictor import Predictor
    x = (np.arange(10, dtype=np.float32) / 10.0).reshape(2, 5)
    pred = Predictor(prefix + "-symbol.json", prefix + "-0000.params",
                     {"data": (2, 5)})
    np.testing.assert_allclose(vals.reshape(2, 3), pred.predict(x),
                               rtol=1e-5, atol=1e-5)
