"""C++ frontend (ref role: cpp-package/include/mxnet-cpp/MxNetCpp.h —
native code COMPOSING models, not just running exported JSON).

Compiles a real C++ program against src/cpp_package/mxtpu_cpp.hpp
that builds a 2-layer MLP forward pass, computes its gradients with
hand-written backprop from registry ops, and trains through KVStore —
demonstrating the compose-train-read loop entirely from C++."""
import os
import subprocess

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPI = os.path.join(REPO, "src", "c_api")
CPP = os.path.join(REPO, "src", "cpp_package")


def _build_capi():
    # unconditional: make's timestamp tracking makes the fresh case a
    # no-op, and a stale cached .so (predating the glue this test
    # needs) would otherwise fail spuriously
    subprocess.run(["make", "-C", CAPI], check=True,
                   capture_output=True, timeout=300)
    return os.path.join(CAPI, "libmxtpu_capi.so")


DEMO_CPP = r"""
// Linear regression composed and trained in pure C++: forward from
// registry ops, analytic gradient, KVStore SGD updates store-side.
#include <cstdio>
#include <vector>
#include "mxtpu_cpp.hpp"

using mxtpu::NDArray;
using mxtpu::Context;

int main() {
    const int N = 64, D = 4;
    // synthetic y = X w*  with fixed pseudo-random X
    std::vector<float> xv(N * D), yv(N);
    unsigned s = 123456789u;
    auto rnd = [&s]() {
        s = s * 1103515245u + 12345u;
        return float((s >> 16) & 0x7fff) / 32768.0f - 0.5f;
    };
    float wstar[D] = {1.5f, -2.0f, 0.5f, 3.0f};
    for (int i = 0; i < N; ++i) {
        float t = 0;
        for (int j = 0; j < D; ++j) {
            xv[i * D + j] = rnd();
            t += xv[i * D + j] * wstar[j];
        }
        yv[i] = t;
    }
    Context ctx = Context::Cpu();
    NDArray X(xv, {N, D}, ctx);
    NDArray y(yv, {N, 1}, ctx);
    NDArray w({D, 1}, ctx);             // zeros

    mxtpu::KVStore kv("local");
    kv.Init("w", w);
    kv.SetOptimizer("sgd", 0.5f);

    float first = -1, last = -1;
    for (int it = 0; it < 60; ++it) {
        kv.Pull("w", &w);
        NDArray pred = mxtpu::dot(X, w);
        NDArray resid = pred - y;                     // (N,1)
        NDArray loss = mxtpu::mean(resid * resid);
        float l = loss.CopyTo()[0];
        if (it == 0) first = l;
        last = l;
        // dL/dw = 2/N * X^T resid
        NDArray grad = mxtpu::dot(X, resid, /*transpose_a=*/true)
                       * (2.0f / N);
        kv.Push("w", grad);
    }
    kv.Pull("w", &w);
    std::vector<float> wf = w.CopyTo();
    printf("LOSS %.6f %.6f\n", first, last);
    for (int j = 0; j < D; ++j) printf("W %.6f\n", wf[j]);

    // operator-builder path with parameters: FullyConnected
    NDArray fcw({3, (mx_uint)D}, ctx);
    std::vector<float> fwv(3 * D, 0.25f);
    fcw.CopyFrom(fwv);
    NDArray fcb({3}, ctx);
    auto fc = mxtpu::Operator("FullyConnected")
                  .AddInput(X).AddInput(fcw).AddInput(fcb)
                  .SetParam("num_hidden", 3)
                  .Invoke();
    auto shp = fc[0].Shape();
    printf("FC %u %u\n", shp[0], shp[1]);
    NDArray::WaitAll();
    return 0;
}
"""


def test_cpp_package_compose_and_train(tmp_path):
    _build_capi()
    demo_cpp = tmp_path / "demo.cpp"
    demo_cpp.write_text(DEMO_CPP)
    demo = str(tmp_path / "demo")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", "-I", CAPI, "-I", CPP,
         str(demo_cpp), "-o", demo, "-L", CAPI,
         f"-Wl,-rpath,{CAPI}", "-lmxtpu_capi"],
        check=True, capture_output=True, timeout=180)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MXTPU_FORCE_CPU"] = "1"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([demo], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    first, last = map(float, lines[0].split()[1:])
    assert last < 0.01 * first, (first, last)   # trained hard
    w = np.array([float(l.split()[1]) for l in lines[1:5]])
    np.testing.assert_allclose(w, [1.5, -2.0, 0.5, 3.0], atol=0.05)
    assert lines[5].split()[1:] == ["64", "3"]
