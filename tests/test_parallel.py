"""Tests for the parallel package on the 8-device virtual CPU mesh
(conftest.py sets --xla_force_host_platform_device_count=8).

Testing model follows the reference's: model parallelism exercised on
CPU contexts without real accelerators (ref:
tests/python/unittest/test_multi_device_exec.py,
tests/nightly/dist_sync_kvstore.py run as local processes).
Oracle = unsharded single-device execution of the same computation.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel
from incubator_mxnet_tpu.parallel import optim as foptim


def test_make_mesh_axes():
    mesh = parallel.make_mesh()
    assert mesh.axis_names == parallel.AXES
    assert mesh.shape["dp"] == 8
    mesh2 = parallel.make_mesh(tp=2, sp=2)
    assert mesh2.shape["dp"] == 2
    assert mesh2.shape["tp"] == 2
    with pytest.raises(ValueError):
        parallel.make_mesh(dp=16)


def test_functionalize_matches_eager():
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"))
        net.add(mx.gluon.nn.Dense(4))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).rand(5, 8))
    eager = net(x).asnumpy()
    pure = parallel.functionalize(net, x)
    outs, _ = pure.apply(pure.params(), pure.states(), [x._data],
                         jax.random.PRNGKey(0), training=False)
    np.testing.assert_allclose(eager, np.asarray(outs[0]), rtol=1e-5)


def test_functional_sgd_matches_imperative():
    rs = np.random.RandomState(1)
    w = jnp.asarray(rs.rand(4, 3), jnp.float32)
    g = jnp.asarray(rs.rand(4, 3), jnp.float32)
    opt = foptim.sgd(learning_rate=0.1, momentum=0.9, wd=0.01)
    params = {"w": w}
    state = opt.init(params)
    p1, s1 = opt.update(params, {"w": g}, state)
    p2, _ = opt.update(p1, {"w": g}, s1)
    # reference semantics: grad += wd*w; mom = m*mom - lr*grad; w += mom
    wn, m = np.asarray(w), np.zeros_like(w)
    for _ in range(2):
        gg = np.asarray(g) + 0.01 * wn
        m = 0.9 * m - 0.1 * gg
        wn = wn + m
    np.testing.assert_allclose(np.asarray(p2["w"]), wn, rtol=1e-5)


def test_functional_nag_matches_imperative():
    rs = np.random.RandomState(11)
    w0 = rs.rand(4, 3).astype(np.float32)
    g = rs.rand(4, 3).astype(np.float32)
    opt = foptim.create("nag", learning_rate=0.1, momentum=0.9,
                        wd=0.01)
    p = {"w": jnp.asarray(w0)}
    s = opt.init(p)
    for _ in range(3):
        p, s = opt.update(p, {"w": jnp.asarray(g)}, s)
    iopt = mx.optimizer.create("nag", learning_rate=0.1, momentum=0.9,
                               wd=0.01)
    wi = mx.nd.array(w0)
    st = iopt.create_state(0, wi)
    for _ in range(3):
        iopt.update(0, wi, mx.nd.array(g), st)
    np.testing.assert_allclose(np.asarray(p["w"]), wi.asnumpy(),
                               rtol=1e-5)


def test_pipeline_stage_count_mismatch_raises():
    mesh = parallel.make_mesh(pp=2)
    stacked = parallel.stack_stage_params(
        [{"w": jnp.zeros((3, 3))} for _ in range(4)])
    with pytest.raises(ValueError, match="stages"):
        parallel.pipeline_apply(lambda p, x: x, stacked,
                                jnp.zeros((4, 3)), mesh,
                                n_microbatches=2)


def test_sharded_train_step_dp_loss_decreases():
    rs = np.random.RandomState(2)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(32, activation="relu"))
        net.add(mx.gluon.nn.Dense(10))
    net.initialize()
    x = jnp.asarray(rs.rand(16, 20), jnp.float32)
    y = jnp.asarray(rs.randint(0, 10, (16,)), jnp.int32)
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd", optimizer_params=dict(learning_rate=0.5),
        mesh=parallel.make_mesh(), example_args=[x])
    losses = [float(step(x, y)) for _ in range(60)]
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_sharded_train_step_matches_single_device():
    """DP-sharded step == unsharded step (the check_consistency analog)."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.rand(8, 6), jnp.float32)
    y = jnp.asarray(rs.randint(0, 3, (8,)), jnp.int32)

    def make_step(mesh):
        mx.random.seed(0)
        net = mx.gluon.nn.Dense(3, in_units=6, prefix="net_")
        net.initialize(mx.initializer.Xavier())
        return parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1), mesh=mesh)

    sharded = make_step(parallel.make_mesh())
    single = make_step(parallel.make_mesh(
        devices=jax.devices()[:1]))
    for _ in range(3):
        l_sh = float(sharded(x, y, rng=jax.random.PRNGKey(7)))
        l_si = float(single(x, y, rng=jax.random.PRNGKey(7)))
    np.testing.assert_allclose(l_sh, l_si, rtol=1e-4)
    for n in sharded.params:
        np.testing.assert_allclose(np.asarray(sharded.params[n]),
                                   np.asarray(single.params[n]),
                                   rtol=1e-4, atol=1e-5)


def test_tensor_parallel_rules():
    mesh = parallel.make_mesh(tp=4)
    rules = parallel.tp_rules_for_dense_stacks()
    params = {"mlp_up_weight": jnp.zeros((8, 4)),
              "mlp_down_weight": jnp.zeros((4, 8)),
              "norm_gamma": jnp.zeros((4,))}
    sh = rules.shardings(mesh, params)
    assert sh["mlp_up_weight"].spec == parallel.P("tp", None)
    assert sh["mlp_down_weight"].spec == parallel.P(None, "tp")
    assert sh["norm_gamma"].spec == parallel.P()
    # a tp-sharded matmul chain still computes the right thing
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.rand(2, 4), jnp.float32)
    wu = jnp.asarray(rs.rand(8, 4), jnp.float32)
    wd = jnp.asarray(rs.rand(4, 8), jnp.float32)
    from incubator_mxnet_tpu.parallel.sharding import apply_rules
    pv = apply_rules(mesh, {"mlp_up_weight": wu,
                            "mlp_down_weight": wd}, rules)

    @jax.jit
    def f(p, x):
        h = jax.nn.relu(x @ p["mlp_up_weight"].T)
        return h @ p["mlp_down_weight"].T
    got = f(pv, x)
    want = jax.nn.relu(x @ wu.T) @ wd.T
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)


def test_pipeline_apply_matches_sequential():
    mesh = parallel.make_mesh(pp=4)
    rs = np.random.RandomState(5)
    n_stages, d = 4, 6
    ws = [jnp.asarray(rs.rand(d, d) * 0.5, jnp.float32)
          for _ in range(n_stages)]
    stacked = parallel.stack_stage_params([{"w": w} for w in ws])

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    x = jnp.asarray(rs.rand(8, d), jnp.float32)
    y = parallel.pipeline_apply(stage, stacked, x, mesh,
                                n_microbatches=4)
    want = x
    for w in ws:
        want = jnp.tanh(want @ w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_grad():
    mesh = parallel.make_mesh(pp=2)
    rs = np.random.RandomState(6)
    d = 4
    ws = [jnp.asarray(rs.rand(d, d) * 0.5, jnp.float32)
          for _ in range(2)]
    stacked = parallel.stack_stage_params([{"w": w} for w in ws])
    x = jnp.asarray(rs.rand(4, d), jnp.float32)

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_pp(stk):
        return jnp.sum(parallel.pipeline_apply(
            stage, stk, x, mesh, n_microbatches=2) ** 2)

    def loss_seq(stk):
        h = x
        for i in range(2):
            h = jnp.tanh(
                h @ jax.tree_util.tree_map(lambda a: a[i], stk)["w"])
        return jnp.sum(h ** 2)

    g_pp = jax.grad(loss_pp)(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    np.testing.assert_allclose(np.asarray(g_pp["w"]),
                               np.asarray(g_seq["w"]),
                               rtol=1e-4, atol=1e-5)


def _ref_attention(q, k, v, causal):
    b, l, h, d = q.shape
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((l, l), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(causal):
    mesh = parallel.make_mesh(sp=4)
    rs = np.random.RandomState(7)
    b, l, h, d = 2, 16, 2, 8
    q = rs.rand(b, l, h, d).astype(np.float32)
    k = rs.rand(b, l, h, d).astype(np.float32)
    v = rs.rand(b, l, h, d).astype(np.float32)
    out = parallel.ring_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), mesh, causal=causal)
    want = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-5)


def test_ring_attention_grad():
    mesh = parallel.make_mesh(sp=2)
    rs = np.random.RandomState(8)
    b, l, h, d = 1, 8, 1, 4
    q = jnp.asarray(rs.rand(b, l, h, d), jnp.float32)
    k = jnp.asarray(rs.rand(b, l, h, d), jnp.float32)
    v = jnp.asarray(rs.rand(b, l, h, d), jnp.float32)

    def f(q):
        return jnp.sum(parallel.ring_attention(q, k, v, mesh) ** 2)

    def f_ref(q):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, v) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)),
                               np.asarray(jax.grad(f_ref)(q)),
                               rtol=1e-4, atol=1e-5)


def test_two_steps_same_block_donation_safe():
    """Donation must not delete the live Parameters or a sibling
    step's buffers: device_put aliases when a value already lives on
    a target device, so the step must own copies."""
    rs = np.random.RandomState(9)
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(8, in_units=6),
                mx.gluon.nn.BatchNorm(), mx.gluon.nn.Dense(3))
    net.initialize(mx.initializer.Xavier())
    pure = parallel.functionalize(net, jnp.zeros((4, 6), jnp.float32))
    x = jnp.asarray(rs.rand(8, 6), jnp.float32)
    y = jnp.asarray(rs.randint(0, 3, (8,)), jnp.int32)

    def make():
        return parallel.ShardedTrainStep(
            pure, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            mesh=parallel.make_mesh())

    def pointers(arrays):
        return {sh.data.unsafe_buffer_pointer()
                for a in arrays for sh in a.addressable_shards}

    s1 = make()
    assert not pointers(s1.params.values()) & pointers(
        p.data()._data for p in net.collect_params().values())
    float(s1(x, y, rng=jax.random.PRNGKey(0)))
    s2 = make()  # reads the live Parameters again
    float(s2(x, y, rng=jax.random.PRNGKey(0)))
    float(s1(x, y, rng=jax.random.PRNGKey(0)))  # s1 still usable
    for p in net.collect_params().values():
        assert not p.data()._data.is_deleted()


def test_write_back_then_step_keeps_parameters_alive():
    """write_back must hand Parameters owned copies, not the step's
    donated buffers (regression: round 3, reverse aliasing path)."""
    rs = np.random.RandomState(10)
    mx.random.seed(0)
    net = mx.gluon.nn.Dense(3, in_units=6)
    net.initialize(mx.initializer.Xavier())
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd", optimizer_params=dict(learning_rate=0.1),
        mesh=parallel.make_mesh(),
        example_args=[jnp.zeros((2, 6), jnp.float32)])
    x = jnp.asarray(rs.rand(8, 6), jnp.float32)
    y = jnp.asarray(rs.randint(0, 3, (8,)), jnp.int32)
    float(step(x, y))
    step.write_back()
    float(step(x, y))  # donates step buffers; Parameters must survive
    for p in net.collect_params().values():
        assert not p.data()._data.is_deleted()
        np.asarray(p.data()._data)  # still readable


def test_grad_accum_matches_full_batch():
    from incubator_mxnet_tpu import gluon

    def build(**kw):
        mx.random.seed(0)
        net = gluon.nn.Dense(4, in_units=8, prefix="ga_")
        net.initialize(mx.initializer.Xavier())
        return parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            example_args=[jnp.zeros((2, 8), jnp.float32)], **kw)

    rs = np.random.RandomState(0)
    xs = jnp.asarray(rs.rand(16, 8), jnp.float32)
    ys = jnp.asarray(rs.randint(0, 4, (16,)), jnp.int32)
    full = build()
    acc = build(grad_accum=2)
    l_full = [float(full(xs, ys)) for _ in range(3)]
    l_acc = [float(acc(xs, ys)) for _ in range(3)]
    # mean-of-micro-grads == full-batch grads for a linear net
    np.testing.assert_allclose(l_acc, l_full, rtol=1e-5)


def test_remat_matches_plain():
    from incubator_mxnet_tpu import gluon

    def build(**kw):
        mx.random.seed(1)
        net = gluon.nn.HybridSequential(prefix="rm_")
        with net.name_scope():
            net.add(gluon.nn.Dense(16, activation="relu"),
                    gluon.nn.Dense(4))
        net.initialize(mx.initializer.Xavier())
        return parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            example_args=[jnp.zeros((2, 8), jnp.float32)], **kw)

    rs = np.random.RandomState(1)
    xs = jnp.asarray(rs.rand(16, 8), jnp.float32)
    ys = jnp.asarray(rs.randint(0, 4, (16,)), jnp.int32)
    plain = build()
    remat = build(remat=True)
    l_plain = [float(plain(xs, ys)) for _ in range(3)]
    l_remat = [float(remat(xs, ys)) for _ in range(3)]
    np.testing.assert_allclose(l_remat, l_plain, rtol=1e-5)


def test_grad_accum_guards():
    import pytest
    from incubator_mxnet_tpu import gluon

    mx.random.seed(0)
    net = gluon.nn.Dense(4, in_units=8, prefix="gg_")
    net.initialize(mx.initializer.Xavier())
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd", optimizer_params=dict(learning_rate=.1),
        example_args=[jnp.zeros((2, 8), jnp.float32)], grad_accum=3)
    with pytest.raises(ValueError, match="divisible"):
        step(jnp.zeros((16, 8), jnp.float32),
             jnp.zeros((16,), jnp.int32))
    mx.random.seed(0)
    net2 = gluon.nn.Dense(4, in_units=8, prefix="gh_")
    net2.initialize(mx.initializer.Xavier())
    step2 = parallel.ShardedTrainStep(
        net2, optimizer="sgd",
        optimizer_params=dict(learning_rate=.1), batch_axis=1,
        example_args=[jnp.zeros((2, 8), jnp.float32)], grad_accum=2)
    with pytest.raises(ValueError, match="batch_axis"):
        step2(jnp.zeros((8, 16), jnp.float32),
              jnp.zeros((16,), jnp.int32))


def test_lr_schedule_in_step():
    # zero-wd, momentum-free SGD on a frozen gradient: per-step delta
    # is exactly lr(t), so the schedule is observable from weights
    from incubator_mxnet_tpu.parallel import optim as fo
    from incubator_mxnet_tpu import gluon

    mx.random.seed(0)
    net = gluon.nn.Dense(1, in_units=1, use_bias=False, prefix="ls_")
    net.initialize(mx.initializer.One())

    def loss_fn(outputs, labels):
        return outputs[0].sum()          # d/dw = sum(x)

    sched = fo.warmup_linear(1.0, warmup_steps=2, total_steps=6,
                             end_lr=0.0)
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd", optimizer_params=dict(learning_rate=9.9),
        loss_fn=loss_fn, lr_schedule=sched,
        example_args=[jnp.zeros((2, 1), jnp.float32)])
    x = jnp.ones((8, 1), jnp.float32)
    y = jnp.zeros((8,), jnp.int32)
    ws = [float(next(iter(step.params.values()))[0, 0])]
    for _ in range(5):
        step(x, y)
        ws.append(float(next(iter(step.params.values()))[0, 0]))
    deltas = [ws[i] - ws[i + 1] for i in range(5)]
    expected = [float(sched(t)) * 8.0 for t in range(5)]
    assert expected[0] > 0  # first update is not a no-op
    np.testing.assert_allclose(deltas, expected, rtol=1e-5)


def test_lr_schedule_survives_checkpoint(tmp_path):
    from incubator_mxnet_tpu.parallel import optim as fo
    from incubator_mxnet_tpu import gluon

    def build():
        mx.random.seed(0)
        net = gluon.nn.Dense(4, in_units=8, prefix="lc_")
        net.initialize(mx.initializer.Xavier())
        return parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            lr_schedule=fo.warmup_cosine(0.1, 2, 10),
            example_args=[jnp.zeros((2, 8), jnp.float32)])

    rs = np.random.RandomState(0)
    batches = [(jnp.asarray(rs.rand(8, 8), jnp.float32),
                jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32))
               for _ in range(6)]
    ref = build()
    ref_losses = [float(ref(x, y)) for x, y in batches]
    a = build()
    for x, y in batches[:3]:
        a(x, y)
    a.save_checkpoint(str(tmp_path / "ck"))
    b = build()
    b.load_checkpoint(str(tmp_path / "ck"))
    assert int(b.step_count) == 3       # schedule resumes mid-curve
    resumed = [float(b(x, y)) for x, y in batches[3:]]
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=1e-6)


def test_zero1_sharded_optimizer_state_matches_replicated():
    """ZeRO-1 (zero=True): fp32 masters + adam moments live
    dp-sharded; training is numerically identical to the replicated
    layout (GSPMD inserts the scatter/gather)."""
    import jax
    from jax.sharding import PartitionSpec as P

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel

    def build(zero):
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(64, activation="relu"),
                    gluon.nn.Dense(8))
        net.initialize(mx.initializer.Xavier())
        return parallel.ShardedTrainStep(
            net, optimizer="adam",
            optimizer_params=dict(learning_rate=1e-2),
            example_args=[mx.nd.zeros((2, 16))],
            mesh=parallel.make_mesh(), zero=zero,
            compute_dtype=None)

    rs = np.random.RandomState(0)
    x = rs.rand(16, 16).astype(np.float32)
    y = rs.randint(0, 8, (16,)).astype(np.int32)

    z, r = build(True), build(False)
    assert z.zero
    # masters are genuinely dp-sharded (not replicated)
    sharded = [n for n, a in z.params.items()
               if a.sharding.spec != P()]
    assert sharded, "no parameter got dp-sharded"
    # adam moments inherit the sharded layout
    m = z.opt_state["mean"][sharded[0]]
    assert m.sharding.spec != P()

    losses_z = [float(z(x, y, rng=jax.random.PRNGKey(1)))
                for _ in range(4)]
    losses_r = [float(r(x, y, rng=jax.random.PRNGKey(1)))
                for _ in range(4)]
    np.testing.assert_allclose(losses_z, losses_r, rtol=1e-5)
    # training actually converged a bit under ZeRO
    assert losses_z[-1] < losses_z[0]


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_exact(causal):
    """All-to-all head-sharded attention (parallel/ulysses.py) is
    EXACT: numerics match the dense oracle for both maskings."""
    mesh = parallel.make_mesh(sp=4)
    rs = np.random.RandomState(7)
    b, l, h, d = 2, 16, 8, 4
    q = rs.rand(b, l, h, d).astype(np.float32)
    k = rs.rand(b, l, h, d).astype(np.float32)
    v = rs.rand(b, l, h, d).astype(np.float32)
    out = parallel.ulysses_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
        causal=causal)
    want = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-5)


def test_ulysses_attention_grad_and_head_constraint():
    mesh = parallel.make_mesh(sp=2)
    rs = np.random.RandomState(8)
    b, l, h, d = 1, 8, 2, 4
    q = jnp.asarray(rs.rand(b, l, h, d), jnp.float32)
    k = jnp.asarray(rs.rand(b, l, h, d), jnp.float32)
    v = jnp.asarray(rs.rand(b, l, h, d), jnp.float32)

    def f(q):
        return jnp.sum(
            parallel.ulysses_attention(q, k, v, mesh) ** 2)

    def f_ref(q):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, v) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)),
                               np.asarray(jax.grad(f_ref)(q)),
                               rtol=1e-4, atol=1e-4)
    # heads not divisible by sp: loud error naming the fallback
    q3 = jnp.zeros((1, 8, 3, 4), jnp.float32)
    with pytest.raises(ValueError, match="ring"):
        parallel.ulysses_attention(q3, q3, q3, mesh)
