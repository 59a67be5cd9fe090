"""Aux subsystems: profiler, monitor, custom ops, visualization
(model: reference tests/python/unittest/test_profiler.py,
test_operator.py CustomOp cases, test_viz.py)."""
import json
import os
import sys
import tempfile

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd


def test_profiler_collects_and_dumps():
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "prof.json")
        mx.profiler.set_config(filename=path, mode="sync")
        mx.profiler.set_state("run")
        a = nd.ones((8, 8))
        b = (a * 2 + 1).sum()
        b.wait_to_read()
        mx.profiler.set_state("stop")
        out = mx.profiler.dump_profile()
        assert out == path
        with open(path) as f:
            trace = json.load(f)
        # the dump also carries chrome-tracing metadata ('M') and
        # telemetry counter ('C') events, which have no duration —
        # only complete ('X') events do (docs/observability.md)
        ops = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        names = [e["name"] for e in ops]
        assert len(names) >= 2
        assert all(e["dur"] >= 0 for e in ops)
        assert any("sum" in n or "mul" in n or "plus" in n
                   for n in names), names
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in trace["traceEvents"])


def test_monitor_observes_ops():
    mon = mx.Monitor(interval=1, pattern=".*").install()
    try:
        mon.tic()
        x = nd.ones((4, 4))
        y = x * 3
        y.wait_to_read()
        rows = mon.toc()
        assert rows, "monitor saw no ops"
        assert any(abs(stat - 3.0) < 1e-6 for _, _, stat in rows)
    finally:
        mon.uninstall()


@mx.operator.register("scale2")
class Scale2Prop(mx.operator.CustomOpProp):
    def __init__(self, factor=2.0):
        super().__init__(need_top_grad=True)
        self.factor = float(factor)

    def create_operator(self, ctx, in_shapes, in_dtypes):
        factor = self.factor

        class Scale2(mx.operator.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                self.assign(out_data[0], req[0],
                            in_data[0] * factor)

            def backward(self, req, out_grad, in_data, out_data,
                         in_grad, aux):
                self.assign(in_grad[0], req[0],
                            out_grad[0] * factor)
        return Scale2()


def test_custom_op_forward_backward():
    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    y = nd.Custom(x, op_type="scale2", factor=3.0)
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy() * 3.0)
    x.attach_grad()
    with autograd.record():
        z = nd.Custom(x, op_type="scale2", factor=3.0).sum()
    z.backward()
    np.testing.assert_allclose(x.grad.asnumpy(),
                               np.full((2, 3), 3.0))


def test_custom_op_in_hybrid_jit():
    """Custom ops must survive jit (pure_callback path)."""
    import jax

    @jax.jit
    def f(v):
        from incubator_mxnet_tpu.operator import custom
        return custom(v, op_type="scale2", factor=4.0)

    import jax.numpy as jnp
    out = f(jnp.ones((3,)))
    np.testing.assert_allclose(np.asarray(out), np.full((3,), 4.0))


def test_custom_op_in_symbol_executor():
    data = mx.sym.Variable("data")
    out = mx.sym.Custom(data, op_type="scale2", factor=5.0,
                        name="my_custom")
    exe = out.simple_bind(None, data=(2, 2))
    res = exe.forward(is_train=False, data=nd.ones((2, 2)))
    np.testing.assert_allclose(res[0].asnumpy(),
                               np.full((2, 2), 5.0))


def test_print_summary():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    out = mx.sym.SoftmaxOutput(fc2, name="softmax")
    total = mx.visualization.print_summary(
        out, shape={"data": (8, 10)})
    # fc1: 10*16+16, fc2: 16*4+4
    assert total == 10 * 16 + 16 + 16 * 4 + 4


def test_autograd_function():
    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = 1 / (1 + nd.exp(-x))
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            y, = self.saved_tensors
            return dy * y * (1 - y)

    x = nd.array(np.array([0.0, 1.0, -2.0], np.float32))
    x.attach_grad()
    f = Sigmoid()
    with autograd.record():
        y = f(x)
    y.backward()
    sig = 1 / (1 + np.exp(-x.asnumpy()))
    np.testing.assert_allclose(x.grad.asnumpy(), sig * (1 - sig),
                               rtol=1e-5)


def test_monitor_compiled_path_per_op_rows():
    """Module.install_monitor streams EVERY graph op's outputs (ref:
    MXExecutorSetMonitorCallback), not just the heads, and
    uninstall restores the fused executable."""
    import incubator_mxnet_tpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=8)
    net = mx.sym.Activation(net, name="relu1", act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=4)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", (2, 6))],
             label_shapes=[mx.io.DataDesc("softmax_label", (2,))],
             for_training=True)
    mod.init_params(mx.initializer.Xavier())
    mon = mx.Monitor(interval=1, pattern=".*")
    mod.install_monitor(mon)
    mon.tic()
    mod.forward(mx.io.DataBatch(
        [mx.nd.array(np.random.RandomState(0)
                     .rand(2, 6).astype("float32"))],
        [mx.nd.array(np.zeros(2, "float32"))]), is_train=False)
    rows = mon.toc()
    names = {r[1] for r in rows}
    assert any("fc1" in n for n in names), names
    assert any("relu1" in n for n in names), names
    assert any("softmax" in n for n in names), names
    assert all(np.isfinite(r[2]) for r in rows)
    mon.uninstall()
    assert mod._exec._monitor_cb is None
    mod.forward(mx.io.DataBatch(
        [mx.nd.ones((2, 6))], [mx.nd.zeros((2,))]), is_train=False)


def test_monitor_streams_during_training_step():
    """The fit path calls forward_backward, which must also run
    tapped while a monitor is installed (review regression: only
    forward() was tapped, so fit(monitor=...) produced no rows)."""
    import incubator_mxnet_tpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=4)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", (2, 3))],
             label_shapes=[mx.io.DataDesc("softmax_label", (2,))],
             for_training=True)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd")
    mon = mx.Monitor(interval=1)
    mod.install_monitor(mon)
    mon.tic()
    mod.forward_backward(mx.io.DataBatch(
        [mx.nd.ones((2, 3))], [mx.nd.zeros((2,))]))
    mod.update()
    rows = mon.toc()
    names = [r[1] for r in rows]
    assert any("fc1" in n for n in names), names
    # exactly once per op per batch (review regression: forward +
    # backward's replay each tapped, doubling every row)
    fc1_rows = [n for n in names if "fc1" in n]
    assert len(fc1_rows) == 1, fc1_rows
    mon.uninstall()
    # untapped training still works after uninstall
    mod.forward_backward(mx.io.DataBatch(
        [mx.nd.ones((2, 3))], [mx.nd.zeros((2,))]))


def test_naive_engine_matches_async_results():
    """SURVEY §5 race-detection analog: the async-dispatch schedule
    must produce bit-identical results to serial naive mode (the
    reference validates its engine with a randomized scheduled-vs-
    serial stress test, threaded_engine_test.cc:122)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, engine, nd

    def workload():
        mx.random.seed(7)
        rs = np.random.RandomState(7)
        a = nd.array(rs.rand(8, 8).astype("float32"))
        b = nd.array(rs.rand(8, 8).astype("float32"))
        a.attach_grad()
        with autograd.record():
            c = nd.dot(a, b)
            d = nd.relu(c) + nd.sigmoid(c)
            # in-place style mutation + dependent reads, the classic
            # RAW/WAR shapes the reference's var protocol guards
            e = d.copy()
            e[:] = e * 2 - d
            loss = nd.sum(e * e)
        loss.backward()
        return (loss.asnumpy().copy(), e.asnumpy().copy(),
                a.grad.asnumpy().copy())

    engine.set_engine_type("async")
    async_res = workload()
    engine.set_engine_type("naive")
    try:
        naive_res = workload()
    finally:
        engine.set_engine_type("async")
    for x, y in zip(async_res, naive_res):
        np.testing.assert_array_equal(x, y)


def test_monitor_tapped_mode_warns(caplog):
    """Arming a monitor on an executor flips forward to un-jitted
    per-op evaluation (~100x slower); a user must be told."""
    import logging

    import incubator_mxnet_tpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc", num_hidden=4)
    mod = mx.mod.Module(net, context=mx.cpu(),
                        label_names=[])
    mod.bind(data_shapes=[mx.io.DataDesc("data", (2, 3))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier())
    logger = logging.getLogger("mxtpu")
    logger.propagate = True   # let caplog's root handler see it
    try:
        with caplog.at_level(logging.WARNING, logger="mxtpu"):
            mod._exec.set_monitor_callback(lambda name, arrs: None)
        assert any("un-jitted" in r.message and "slower" in r.message
                   for r in caplog.records), caplog.records
        # disarming is silent
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="mxtpu"):
            mod._exec.set_monitor_callback(None)
        assert not caplog.records
    finally:
        logger.propagate = False


def test_tensorboard_log_metrics_callback(tmp_path):
    """Contrib TensorBoard bridge (ref: contrib/tensorboard.py
    LogMetricsCallback): metrics stream to a writer; the JSONL
    fallback is asserted directly so the test needs no tensorboard."""
    import json as _json
    from collections import namedtuple

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.contrib.tensorboard import (
        LogMetricsCallback, _JsonlWriter)

    metric = mx.metric.Accuracy()
    metric.update([mx.nd.array([0.0, 1.0])],
                  [mx.nd.array([[0.9, 0.1], [0.2, 0.8]])])
    Param = namedtuple("BatchEndParam",
                       ["epoch", "nbatch", "eval_metric"])
    logdir = str(tmp_path / "tb")
    cb = LogMetricsCallback(
        logdir, prefix="train",
        summary_writer=_JsonlWriter(logdir))
    cb(Param(epoch=0, nbatch=1, eval_metric=metric))
    cb(Param(epoch=0, nbatch=2, eval_metric=metric))
    files = [f for f in os.listdir(logdir) if f.endswith(".jsonl")]
    assert files
    rows = [_json.loads(l) for l in
            open(os.path.join(logdir, files[0]))]
    assert [r["step"] for r in rows] == [1, 2]
    assert all(r["tag"] == "train-accuracy" for r in rows)
    assert all(r["value"] == 1.0 for r in rows)

    # a real SummaryWriter, when one is installed (make_writer tries
    # the one that imports fast first)
    cb2 = LogMetricsCallback(str(tmp_path / "tb2"), prefix="t")
    if isinstance(cb2.writer, _JsonlWriter):
        return
    cb2(Param(epoch=0, nbatch=1, eval_metric=metric))
    cb2.writer.flush()
    assert os.listdir(str(tmp_path / "tb2"))


def test_contrib_autograd_legacy_api():
    """contrib.autograd keeps the pre-1.0 experimental names alive
    over the core tape (ref: python/mxnet/contrib/autograd.py)."""
    import numpy as np

    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.contrib import autograd as cag

    @cag.grad_and_loss
    def f(a, b):
        return a * b + a

    x = nd.array(np.array([1., 2., 3.], np.float32))
    y = nd.array(np.array([4., 5., 6.], np.float32))
    grads, _ = f(x, y)
    np.testing.assert_allclose(grads[0].asnumpy(), [5., 6., 7.])
    np.testing.assert_allclose(grads[1].asnumpy(), [1., 2., 3.])

    x2 = nd.array(np.array([2., 3.], np.float32))
    x2.attach_grad()
    with cag.train_section():
        z = nd.sum(x2 * x2)
    cag.compute_gradient([z])
    np.testing.assert_allclose(x2.grad.asnumpy(), [4., 6.])


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_CHILD = (
    "import jax, jax.numpy as jnp\n"
    "from incubator_mxnet_tpu.utils.platform import "
    "enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _cache_child(tmp_path, src, **env_changes):
    """Run ``src`` in a fresh interpreter (jax reads
    JAX_COMPILATION_CACHE_DIR at import) from a foreign directory."""
    import subprocess
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_changes)
    r = subprocess.run([sys.executable, "-c", src], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_compile_cache_dir_given_from_outside_persists(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it and
    enable_compile_cache sets no directory of its own; a compile
    leaves an entry there, however quick it was."""
    cachedir = str(tmp_path / "xla-cache")
    out = _cache_child(
        tmp_path, _CACHE_CHILD
        + "x = jnp.ones((13, 29), jnp.float32)\n"
          "jax.block_until_ready(jax.jit(lambda a: (a @ a.T).sum())(x))\n",
        JAX_COMPILATION_CACHE_DIR=cachedir)
    assert out == [cachedir, cachedir]
    assert os.listdir(cachedir), "no persistent cache entry written"


def test_compile_cache_variable_set_sets_no_directory(monkeypatch,
                                                      tmp_path):
    """In-process view of the same rule: with the variable set the
    call leaves jax's directory setting exactly as it found it."""
    import jax

    from incubator_mxnet_tpu.utils import platform as plat

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: updates.append(k) or real(k, v))
    before = (jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        assert plat.enable_compile_cache() == str(tmp_path)
        assert updates and "jax_compilation_cache_dir" not in updates
    finally:
        real("jax_persistent_cache_min_compile_time_secs", before[0])
        real("jax_persistent_cache_min_entry_size_bytes", before[1])


def test_compile_cache_default_is_one_fixed_path_in_checkout(tmp_path):
    """Variable unset: the cache is at <checkout>/.jax_cache (listed
    in .gitignore) — the same path from any process and directory,
    because the directory is part of the cache key."""
    other = tmp_path / "elsewhere"
    other.mkdir()
    first = _cache_child(tmp_path, _CACHE_CHILD)
    second = _cache_child(other, _CACHE_CHILD)
    want = os.path.join(_REPO, ".jax_cache")
    assert first == second == [want, want]
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
