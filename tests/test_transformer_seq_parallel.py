"""TransformerLM over a sequence-parallel mesh: ring and Ulysses
attention against local attention on the same weights, and training
through the mesh step.  A file of its own because a file is what the
driver's ``--dist loadfile`` hands to one worker."""
import numpy as np

import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel
from incubator_mxnet_tpu.gluon.model_zoo.transformer import TransformerLM

from test_transformer import _lm_loss, _tiny


def test_seq_parallel_ring_attention_matches_local(tmp_path):
    # seq_parallel=True under a mesh with sp>1 must compute the SAME
    # values as local attention (ring attention is exact)
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net_sp = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                           max_len=16, seq_parallel=True)
    net_sp.initialize(mx.initializer.Xavier())
    net_local = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                              max_len=16)
    net_local.initialize(mx.initializer.Xavier())

    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 8)).astype("int32"))
    ref = net_local(toks).asnumpy()
    # share the exact same weights across both attention impls
    f = str(tmp_path / "w.params")
    net_local.save_params(f)
    net_sp(toks)          # settle deferred shapes before loading
    net_sp.load_params(f)
    np.testing.assert_allclose(net_sp(toks).asnumpy(), ref,
                               rtol=1e-4, atol=1e-4)
    mesh = make_mesh(dp=2, sp=4)
    with use_mesh(mesh):
        got = net_sp(toks).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    # off-mesh it falls back to local attention and still agrees
    np.testing.assert_allclose(net_sp(toks).asnumpy(), ref,
                               rtol=1e-4, atol=1e-4)


def test_seq_parallel_trains_on_mesh():
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net = _tiny(seq_parallel=True)
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 37, (2, 8)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, 37, (2, 8)), jnp.int32)
    mesh = make_mesh(dp=2, sp=4)
    with use_mesh(mesh):
        step = parallel.ShardedTrainStep(
            net, optimizer="adam",
            optimizer_params=dict(learning_rate=1e-2),
            loss_fn=_lm_loss, mesh=mesh, seq_axis=1,
            example_args=[mx.nd.array(np.zeros((2, 8), "int32"))])
        losses = [float(step(toks, labels)) for _ in range(15)]
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_seq_parallel_eager_autograd_gets_gradients():
    # eager record()/backward() must take the registry-op attention
    # path (the raw-jax ring call is invisible to the tape), so qkv
    # weights receive real gradients
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net = _tiny(seq_parallel=True)
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 8)).astype("int32"))
    labels = mx.nd.array(np.random.RandomState(1)
                         .randint(0, 37, (2, 8)).astype("float32"))
    net(toks)            # settle deferred shapes
    for p in net.collect_params().values():
        p.data().attach_grad()
    lossf = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    with use_mesh(make_mesh(dp=2, sp=4)):
        with autograd.record():
            L = lossf(net(toks), labels).mean()
        L.backward()
    g = net.blocks[0].attn.qkv.weight.data().grad
    assert g is not None and float(np.abs(g.asnumpy()).max()) > 0


def test_seq_parallel_non_divisible_seq_falls_back():
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net = _tiny(seq_parallel=True)
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 6)).astype("int32"))
    ref = net(toks).asnumpy()          # off-mesh local path
    with use_mesh(make_mesh(dp=2, sp=4)):
        got = net(toks).asnumpy()      # L=6 % sp=4 != 0 -> local
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_sharded_step_traces_with_own_mesh_outside_scope():
    # first call outside use_mesh() must still trace the ring path
    # with the step's own mesh ambient (not bake in local attention)
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net = _tiny(seq_parallel=True)
    mesh = make_mesh(dp=2, sp=4)
    with use_mesh(mesh):
        step = parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            loss_fn=_lm_loss, mesh=mesh, seq_axis=1,
            example_args=[mx.nd.array(np.zeros((2, 8), "int32"))])
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 37, (2, 8)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, 37, (2, 8)), jnp.int32)
    # called OUTSIDE the with-block: ambient mesh is None here
    ring_calls = []
    import incubator_mxnet_tpu.gluon.model_zoo.transformer as tf_mod
    orig = tf_mod.CausalSelfAttention._ring_mesh
    def spy(self, seq_len):
        m = orig(self, seq_len)
        ring_calls.append(m is not None)
        return m
    tf_mod.CausalSelfAttention._ring_mesh = spy
    try:
        loss = float(step(toks, labels))
    finally:
        tf_mod.CausalSelfAttention._ring_mesh = orig
    assert np.isfinite(loss)
    assert any(ring_calls), "ring path never engaged during trace"


def test_seq_parallel_ulysses_matches_local(tmp_path):
    """seq_parallel='ulysses' under an sp>1 mesh computes the SAME
    values as local attention (all-to-all resharding is exact)."""
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net_sp = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                           max_len=16, seq_parallel="ulysses")
    net_sp.initialize(mx.initializer.Xavier())
    net_local = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                              max_len=16)
    net_local.initialize(mx.initializer.Xavier())
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 8)).astype("int32"))
    ref = net_local(toks).asnumpy()
    f = str(tmp_path / "w.params")
    net_local.save_params(f)
    net_sp(toks)
    net_sp.load_params(f)
    mesh = make_mesh(dp=2, sp=4)
    with use_mesh(mesh):
        got = net_sp(toks).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_seq_parallel_ulysses_trains_on_mesh():
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net = _tiny(seq_parallel="ulysses")
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 37, (2, 8)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, 37, (2, 8)), jnp.int32)
    mesh = make_mesh(dp=2, sp=4)
    with use_mesh(mesh):
        step = parallel.ShardedTrainStep(
            net, optimizer="adam",
            optimizer_params=dict(learning_rate=1e-2),
            loss_fn=_lm_loss, mesh=mesh, seq_axis=1,
            example_args=[mx.nd.array(np.zeros((2, 8), "int32"))])
        losses = [float(step(toks, labels)) for _ in range(15)]
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_rope_with_ring_attention_matches_local(tmp_path):
    """rope rotates q/k BEFORE sequence sharding, so ring attention
    over the mesh must equal the local forward exactly."""
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    net_sp = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                           max_len=16, pos="rope",
                           seq_parallel=True)
    net_sp.initialize(mx.initializer.Xavier())
    net_local = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                              max_len=16, pos="rope")
    net_local.initialize(mx.initializer.Xavier())
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 8)).astype("int32"))
    ref = net_local(toks).asnumpy()
    f = str(tmp_path / "w.params")
    net_local.save_params(f)
    net_sp(toks)
    net_sp.load_params(f)
    with use_mesh(make_mesh(dp=2, sp=4)):
        got = net_sp(toks).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
