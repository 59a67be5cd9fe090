"""Mesh-aware frontends: Module.fit and Gluon Trainer on the 8-device
virtual CPU mesh.

Oracle = the single-device eager paths of the same frontends: the
compiled kvstore='tpu' step must reproduce them numerically (the
reference validates DataParallelExecutorGroup the same way — multi-
vs single-device consistency, ref: tests/python/unittest/
test_module.py test_module_states and test_multi_device_exec.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd
from incubator_mxnet_tpu.parallel import make_mesh, shard_batch


def _toy_data(n=512, d=20, k=10, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, d).astype(np.float32)
    w = rs.rand(d, k).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.float32)
    return x, y


def _mlp_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(kvstore, x, y, optimizer="sgd",
         optimizer_params=None, num_epoch=3):
    it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False,
                           label_name="softmax_label")
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, kvstore=kvstore,
            optimizer=optimizer,
            optimizer_params=optimizer_params
            or dict(learning_rate=0.5, momentum=0.9, wd=1e-4),
            initializer=mx.initializer.Xavier(
                rnd_type="uniform", factor_type="avg", magnitude=3))
    acc = dict(mod.score(it, "acc"))["accuracy"]
    arg, aux = mod.get_params()
    return acc, arg, mod


def test_module_fit_tpu_kvstore_matches_local():
    x, y = _toy_data()
    acc_l, p_l, _ = _fit("local", x, y)
    acc_t, p_t, mod = _fit("tpu", x, y)
    assert mod._mesh_step is not None  # actually took the mesh path
    assert acc_t > 0.8
    assert abs(acc_l - acc_t) < 1e-6
    for n in p_l:
        np.testing.assert_allclose(p_l[n].asnumpy(), p_t[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5)


def test_module_tpu_kvstore_adam_and_checkpoint(tmp_path):
    x, y = _toy_data()
    acc, _, mod = _fit("tpu", x, y, optimizer="adam",
                       optimizer_params=dict(learning_rate=0.01))
    assert acc > 0.8
    mod.save_checkpoint(str(tmp_path / "m"), 0,
                        save_optimizer_states=True)
    mod2 = mx.mod.Module.load(str(tmp_path / "m"), 0)
    it = mx.io.NDArrayIter(x, y, batch_size=64,
                           label_name="softmax_label")
    mod2.bind(data_shapes=it.provide_data,
              label_shapes=it.provide_label)
    acc2 = dict(mod2.score(it, "acc"))["accuracy"]
    assert abs(acc - acc2) < 1e-6


def test_module_tpu_kvstore_rejects_exotic_optimizer():
    x, y = _toy_data(n=64)
    with pytest.raises(ValueError, match="sgd/nag/adam"):
        _fit("tpu", x, y, optimizer="rmsprop",
             optimizer_params=dict(learning_rate=0.01))


def _train_gluon(force_eager=False, shard=False, steps=15):
    rs = np.random.RandomState(0)
    X = rs.rand(64, 12).astype(np.float32)
    Y = rs.randint(0, 5, (64,)).astype(np.float32)
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(32, activation="relu"),
                mx.gluon.nn.Dense(5))
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.array(X[:2]))  # settle shapes
    tr = mx.gluon.Trainer(
        net.collect_params(), "sgd",
        dict(learning_rate=0.2, momentum=0.9, wd=1e-3),
        kvstore="tpu" if shard else "device")
    if force_eager:
        tr._init_kvstore()
        tr._fused_update = False
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh()
    losses = []
    for _ in range(steps):
        xb, yb = mx.nd.array(X), mx.nd.array(Y)
        if shard:
            xb = mx.nd.NDArray(jax.device_put(
                xb._data, shard_batch(mesh, 2)))
            yb = mx.nd.NDArray(jax.device_put(
                yb._data, shard_batch(mesh, 1)))
        with autograd.record():
            loss = loss_fn(net(xb), yb)
        loss.backward()
        tr.step(X.shape[0])
        losses.append(float(loss.mean().asnumpy()))
    params = [p.data().asnumpy()
              for _, p in sorted(net.collect_params().items())]
    return losses, params, tr


def test_trainer_fused_matches_eager_updater():
    l_e, p_e, tr_e = _train_gluon(force_eager=True)
    l_f, p_f, tr_f = _train_gluon()
    assert tr_f._fused_update is not False and \
        tr_f._fused_update is not None
    assert l_f[-1] < l_f[0]
    np.testing.assert_allclose(l_e[-1], l_f[-1], rtol=1e-5)
    for a, b in zip(p_e, p_f):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_trainer_tpu_mesh_matches_single_device():
    l_e, p_e, _ = _train_gluon(force_eager=True)
    l_m, p_m, tr = _train_gluon(shard=True)
    # params really are replicated over the 8-device mesh
    some = tr._params[0].data()._data
    assert len(some.sharding.device_set) == 8
    np.testing.assert_allclose(l_e[-1], l_m[-1], rtol=1e-4)
    for a, b in zip(p_e, p_m):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_trainer_fused_lr_schedule_no_recompile():
    """lr is a traced scalar: changing it must not recompile."""
    _, _, tr = _train_gluon(steps=2)
    tr.set_learning_rate(0.01)
    # one more step at the new lr works and changes params
    before = [p.data().asnumpy().copy() for p in tr._params]
    rs = np.random.RandomState(1)
    X = rs.rand(64, 12).astype(np.float32)
    Y = rs.randint(0, 5, (64,)).astype(np.float32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    net_params = tr._params
    with autograd.record():
        h = mx.nd.dot(mx.nd.array(X), net_params[0].data().T) \
            + net_params[1].data()
        h = mx.nd.relu(h)
        out = mx.nd.dot(h, net_params[2].data().T) + net_params[3].data()
        loss = loss_fn(out, mx.nd.array(Y))
    loss.backward()
    tr.step(64)
    after = [p.data().asnumpy() for p in tr._params]
    assert any(not np.allclose(a, b) for a, b in zip(before, after))


def test_module_manual_loop_tpu_kvstore_updates_params():
    """forward/backward/update manual loop must not silently no-op
    under kvstore='tpu' (round-3 review regression)."""
    x, y = _toy_data(n=64)
    it = mx.io.NDArrayIter(x, y, batch_size=64,
                           label_name="softmax_label")
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params=dict(learning_rate=0.5))
    before, _ = mod.get_params()
    before = {n: v.asnumpy().copy() for n, v in before.items()}
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    after, _ = mod.get_params()
    assert any(not np.allclose(before[n], after[n].asnumpy())
               for n in before)
    # and the fused path still works afterwards (stale-refresh)
    mod.forward_backward(batch)
    mod.update()
    after2, _ = mod.get_params()
    assert any(not np.allclose(after[n].asnumpy(),
                               after2[n].asnumpy()) for n in after)


def test_trainer_stale_grad_keeps_momentum_consistent():
    """A stale-grad step must leave the skipped parameter's weight AND
    momentum untouched, staying equivalent to the eager updater."""
    def run(force_eager):
        rs = np.random.RandomState(0)
        X = rs.rand(16, 6).astype(np.float32)
        mx.random.seed(0)
        a = mx.gluon.nn.Dense(4, in_units=6)
        b = mx.gluon.nn.Dense(4, in_units=6)
        a.initialize(mx.initializer.Xavier())
        b.initialize(mx.initializer.Xavier())
        params = dict(list(a.collect_params().items())
                      + list(b.collect_params().items()))
        tr = mx.gluon.Trainer(params, "sgd",
                              dict(learning_rate=0.1, momentum=0.9))
        if force_eager:
            tr._init_kvstore()
            tr._fused_update = False
        for i in range(4):
            use_b = i != 1  # step 1: b's grads are stale
            with autograd.record():
                out = a(mx.nd.array(X))
                if use_b:
                    out = out + b(mx.nd.array(X))
                loss = (out * out).mean()
            loss.backward()
            if not use_b:
                for p in b.collect_params().values():
                    p._grad = None
            tr.step(1, ignore_stale_grad=True)
        # in the order built (a's, then b's): the names carry a counter
        # of the whole process, and sorted by name dense9 and dense10
        # change places
        return [p.data().asnumpy() for p in params.values()]

    for pe, pf in zip(run(True), run(False)):
        np.testing.assert_allclose(pe, pf, rtol=2e-5, atol=2e-6)
