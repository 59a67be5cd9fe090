"""HybridBlock.export -> symbol JSON + params -> Predictor / Module
round-trip (ref: python/mxnet/gluon/block.py
HybridBlock.export, include/mxnet/c_predict_api.h)."""
import numpy as np

import incubator_mxnet_tpu as mx


def _build_net():
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"),
                mx.gluon.nn.BatchNorm(),
                mx.gluon.nn.Dense(4))
    net.initialize(mx.initializer.Xavier())
    return net


def test_export_predict_roundtrip(tmp_path):
    net = _build_net()
    rs = np.random.RandomState(0)
    x = rs.rand(8, 12).astype(np.float32)
    want = net(mx.nd.array(x)).asnumpy()  # also settles shapes
    prefix = str(tmp_path / "model")
    net.export(prefix)

    pred = mx.Predictor(prefix + "-symbol.json",
                        prefix + "-0000.params",
                        {"data": (8, 12)})
    got = pred.predict(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # C-api style set_input/forward/get_output
    pred.set_input("data", x)
    pred.forward()
    np.testing.assert_allclose(pred.get_output(0).asnumpy(), want,
                               rtol=1e-5, atol=1e-6)


def test_export_conv_net_and_reshape(tmp_path):
    mx.random.seed(1)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Conv2D(8, 3, padding=1),
                mx.gluon.nn.Activation("relu"),
                mx.gluon.nn.MaxPool2D(2),
                mx.gluon.nn.Flatten(),
                mx.gluon.nn.Dense(5))
    net.initialize(mx.initializer.Xavier())
    rs = np.random.RandomState(1)
    x = rs.rand(2, 3, 8, 8).astype(np.float32)
    want = net(mx.nd.array(x)).asnumpy()
    prefix = str(tmp_path / "conv")
    net.export(prefix)
    pred = mx.Predictor(prefix + "-symbol.json",
                        prefix + "-0000.params",
                        {"data": (2, 3, 8, 8)})
    np.testing.assert_allclose(pred.predict(x), want, rtol=1e-5,
                               atol=1e-6)
    # MXPredReshape analog: new batch size
    pred2 = pred.reshape({"data": (4, 3, 8, 8)})
    x4 = rs.rand(4, 3, 8, 8).astype(np.float32)
    want4 = net(mx.nd.array(x4)).asnumpy()
    np.testing.assert_allclose(pred2.predict(x4), want4, rtol=1e-5,
                               atol=1e-6)


def test_export_served_by_module(tmp_path):
    """The exported artifact is a valid Module checkpoint too."""
    net = _build_net()
    rs = np.random.RandomState(2)
    x = rs.rand(8, 12).astype(np.float32)
    want = net(mx.nd.array(x)).asnumpy()
    prefix = str(tmp_path / "m")
    net.export(prefix)
    mod = mx.mod.Module.load(prefix, 0, data_names=("data",),
                             label_names=None)
    mod.bind(data_shapes=[("data", (8, 12))], for_training=False)
    mod.forward(mx.io.DataBatch([mx.nd.array(x)], None))
    np.testing.assert_allclose(mod.get_outputs()[0].asnumpy(), want,
                               rtol=1e-5, atol=1e-6)


def test_model_zoo_export(tmp_path):
    """A model-zoo resnet exports and serves (the deployment story
    for config-2 models)."""
    mx.random.seed(3)
    net = mx.gluon.model_zoo.vision.resnet18_v1(classes=10)
    net.initialize(mx.initializer.Xavier())
    rs = np.random.RandomState(3)
    x = rs.rand(2, 3, 32, 32).astype(np.float32)
    want = net(mx.nd.array(x)).asnumpy()
    prefix = str(tmp_path / "resnet")
    net.export(prefix)
    pred = mx.Predictor(prefix + "-symbol.json",
                        prefix + "-0000.params",
                        {"data": (2, 3, 32, 32)})
    np.testing.assert_allclose(pred.predict(x), want, rtol=1e-4,
                               atol=1e-5)


def test_export_tags_aux_states(tmp_path):
    """BatchNorm moving stats must export as aux:, not arg:
    (round-3 review regression)."""
    net = _build_net()
    x = np.random.RandomState(4).rand(4, 12).astype(np.float32)
    net(mx.nd.array(x))
    prefix = str(tmp_path / "auxcheck")
    sym = net.export(prefix)
    aux = sym.list_auxiliary_states()
    assert any("running_mean" in n for n in aux), aux
    assert any("running_var" in n for n in aux), aux
    from incubator_mxnet_tpu.predictor import load_params
    arg_params, aux_params = load_params(prefix + "-0000.params")
    assert any("running_mean" in n for n in aux_params), aux_params
    assert not any("running" in n for n in arg_params)


def test_predictor_positional_order_and_arity(tmp_path):
    """predict() binds positionals in the declared input order and
    rejects wrong arity (round-3 review regression)."""

    class TwoIn(mx.gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.weight = self.params.get("weight", shape=(3, 4))

        def shape_from_input(self, *a):
            pass

        def hybrid_forward(self, F, a, b, weight):
            # first op consumes the SECOND input
            return F.FullyConnected(b, weight, no_bias=True,
                                    num_hidden=3) + a

    net = TwoIn()
    net.initialize(mx.initializer.Xavier())
    rs = np.random.RandomState(5)
    a = rs.rand(2, 3).astype(np.float32)
    b = rs.rand(2, 4).astype(np.float32)
    want = net(mx.nd.array(a), mx.nd.array(b)).asnumpy()
    prefix = str(tmp_path / "two")
    net.export(prefix)
    pred = mx.Predictor(prefix + "-symbol.json",
                        prefix + "-0000.params",
                        {"data0": (2, 3), "data1": (2, 4)})
    np.testing.assert_allclose(pred.predict(a, b), want, rtol=1e-5,
                               atol=1e-6)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="expected 2 inputs"):
        pred.predict(a)


def test_bundle_roundtrip(tmp_path):
    """tools/bundle.py (amalgamation-role deploy artifact): export a
    model, build the bundle, and serve it from the bundle's own
    loader in a fresh process with only the bundle dir."""
    import json
    import os
    import subprocess
    import sys as _sys

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon

    mx.random.seed(5)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(6, activation="relu"),
                gluon.nn.Dense(2))
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(1).rand(3, 4)
                    .astype("float32"))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "bnet")
    net.export(prefix)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _sys.path.insert(0, os.path.join(repo, "tools"))
    import bundle
    out = bundle.build_bundle(prefix, {"data": (3, 4)},
                              str(tmp_path / "bundle"))
    man = json.load(open(os.path.join(out, "MANIFEST.json")))
    assert man["inputs"] == ["data"]

    # fresh process, bundle dir only (forced-CPU embedded runtime)
    code = (
        "import sys, numpy as np\n"
        f"sys.path.insert(0, {out!r})\n"
        "import predict\n"
        "p = predict.load()\n"
        "x = np.load(sys.argv[1])\n"
        "np.save(sys.argv[2], p(data=x))\n")
    xin = tmp_path / "x.npy"
    xout = tmp_path / "y.npy"
    np.save(xin, x.asnumpy())
    env = dict(os.environ)
    env["MXTPU_FORCE_CPU"] = "1"
    env["PYTHONPATH"] = repo
    r = subprocess.run([_sys.executable, "-c", code, str(xin),
                       str(xout)], capture_output=True, text=True,
                      timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    got = np.load(xout)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
