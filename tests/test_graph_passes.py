"""Graph-optimization pass pipeline + CachedOp (docs/graph_passes.md).

Covers: golden equivalence of randomized graphs across
MXTPU_GRAPH_OPT levels (bitwise), per-pass units (CSE, folding,
identity/transpose elimination, pruning reachability, fusion),
PassManager ordering, CachedOp hit/miss + train/eval separation +
stable scalar signatures (trace-count regression), and the _Node
mutation lint rule.
"""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, sym
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.graph import (CachedOp, Graph, PassManager,
                                       PASSES, optimize_symbol)
from incubator_mxnet_tpu.graph.fuse import FusedOp
from incubator_mxnet_tpu.symbol.symbol import _topo

import _graphs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _op_names(symbol):
    return [n.op.name for n in _topo(symbol._heads)
            if n.op is not None]


def _bind_forward(symbol, arg_vals, level, monkeypatch, is_train=False,
                  seed=None):
    monkeypatch.setenv("MXTPU_GRAPH_OPT", str(level))
    ex = symbol.bind(mx.cpu(), {k: nd.array(v)
                                for k, v in arg_vals.items()})
    if seed is not None:
        mx.random.seed(seed)
    return [o.asnumpy() for o in ex.forward(is_train=is_train)]


# ------------------------------------------------------------ goldens
def _random_symbol(seed, n_ops=24):
    """Randomized DAG over two variables: elementwise ops, scalar
    ops, transpose pairs, const subtrees, duplicated subexpressions,
    and identities — material for every pass."""
    rs = np.random.RandomState(seed)
    pool = [sym.Variable("a"), sym.Variable("b")]
    unary = ["tanh", "sin", "relu", "abs", "negative"]
    for _ in range(n_ops):
        r = rs.rand()
        pick = lambda: pool[rs.randint(len(pool))]
        if r < 0.30:
            t = getattr(sym, unary[rs.randint(len(unary))])(pick())
        elif r < 0.55:
            f = [sym.broadcast_add, sym.broadcast_mul,
                 sym.broadcast_sub, sym.broadcast_maximum][
                rs.randint(4)]
            t = f(pick(), pick())
        elif r < 0.65:
            t = pick() * float(round(rs.uniform(0.2, 2.2), 3))
        elif r < 0.73:
            t = sym.transpose(sym.transpose(pick(), axes=(1, 0)),
                              axes=(1, 0))
        elif r < 0.81:
            t = pick() + sym.ones((4, 8)) * \
                float(round(rs.uniform(0.5, 1.5), 3))
        elif r < 0.92:
            x = pick()
            t = sym.tanh(x) + sym.tanh(x)        # CSE material
        else:
            t = sym._internal._copy(pick())      # identity material
        pool.append(t)
    return sym.Group(pool[-2:])


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_golden_equivalence_bitwise_across_levels(seed, monkeypatch):
    s = _random_symbol(seed)
    rs = np.random.RandomState(100 + seed)
    vals = {"a": rs.randn(4, 8).astype("float32"),
            "b": rs.randn(4, 8).astype("float32")}
    outs = {lv: _bind_forward(s, vals, lv, monkeypatch)
            for lv in (0, 1, 2)}
    for lv in (1, 2):
        for o_ref, o_opt in zip(outs[0], outs[lv]):
            assert np.array_equal(o_ref, o_opt), \
                f"level {lv} diverged (seed {seed})"


# nodes before -> after level 2 on the graphs a frontend hands over
# (tests/_graphs.py): what the passes remove is counted, not timed
@pytest.mark.parametrize("graph,before,after", [
    ("mlp", 32, 30), ("resnet_block", 18, 17),
    ("transformer_step", 167, 121)])
def test_frontend_graphs_bitwise_and_smaller_at_level_2(
        graph, before, after, monkeypatch):
    s, shapes = getattr(_graphs, f"_graph_{graph}")(sym)
    _, report = s.optimize(level=2)
    assert (report["nodes_before"], report["nodes_after"]) == \
        (before, after)
    outs = {}
    for level in (0, 2):
        monkeypatch.setenv("MXTPU_GRAPH_OPT", str(level))
        exe = s.simple_bind(mx.cpu(), grad_req="null", **shapes)
        rs = np.random.RandomState(42)
        vals = {}
        for name in sorted(exe.arg_dict):
            shape = exe.arg_dict[name].shape
            if name in ("label", "labels", "tokens"):
                vals[name] = nd.array(
                    rs.randint(0, 10, shape).astype("float32"))
            else:
                vals[name] = nd.array(
                    (rs.rand(*shape) * 0.1 - 0.05).astype("float32"))
        exe.copy_params_from(vals)
        outs[level] = [o.asnumpy() for o in exe.forward()]
    assert outs[0] and len(outs[0]) == len(outs[2])
    for a, b in zip(outs[0], outs[2]):
        assert np.array_equal(a, b)


def test_golden_equivalence_gradients(monkeypatch):
    s = _random_symbol(7)
    rs = np.random.RandomState(7)
    vals = {"a": rs.randn(4, 8).astype("float32"),
            "b": rs.randn(4, 8).astype("float32")}
    grads = {}
    for lv in (0, 2):
        monkeypatch.setenv("MXTPU_GRAPH_OPT", str(lv))
        ex = s.simple_bind(mx.cpu(), grad_req="write",
                           a=(4, 8), b=(4, 8))
        ex.copy_params_from({k: nd.array(v) for k, v in vals.items()})
        ex.forward_backward()
        grads[lv] = {k: g.asnumpy()
                     for k, g in ex.grad_dict.items()}
    for k in grads[0]:
        np.testing.assert_allclose(grads[0][k], grads[2][k],
                                   rtol=1e-5, atol=1e-6)


def test_rng_stream_invariant_under_optimization(monkeypatch):
    """Dropout draws the identical mask at every opt level: rng fold
    indices are pinned pre-optimization (__rng_index__)."""
    x = sym.Variable("x")
    y = sym.Dropout(sym.tanh(x) + sym.tanh(x), p=0.5) * 1.0
    vals = {"x": np.random.RandomState(3).randn(8, 8)
            .astype("float32")}
    outs = {lv: _bind_forward(y, vals, lv, monkeypatch,
                              is_train=True, seed=123)
            for lv in (0, 1, 2)}
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][0], outs[2][0])


# ------------------------------------------------------------ passes
def test_cse_dedups_identical_subtrees():
    x = sym.Variable("x")
    y = sym.tanh(x) + sym.tanh(x)
    opt, report = y.optimize(level=1)
    assert _op_names(opt).count("tanh") == 1
    merged = [p for p in report["passes"]
              if p["pass"] == "eliminate_common_subexpressions"][0]
    assert merged["merged"] == 1


def test_cse_never_merges_rng_ops():
    x = sym.Variable("x")
    y = sym.Dropout(x, p=0.5) + sym.Dropout(x, p=0.5)
    opt, _ = y.optimize(level=1)
    assert _op_names(opt).count("Dropout") == 2


def test_constant_folding_removes_const_subtree(monkeypatch):
    x = sym.Variable("x")
    y = x + (sym.ones((4,)) * 2.0 + 1.0)
    opt, report = y.optimize(level=1)
    names = _op_names(opt)
    assert "_ones" not in names
    assert "_graph_const" in names
    folded = [p for p in report["passes"]
              if p["pass"] == "fold_constants"][0]
    assert folded["folded"] >= 1
    vals = {"x": np.zeros((2, 4), "float32")}
    out = _bind_forward(y, vals, 1, monkeypatch)[0]
    np.testing.assert_allclose(out, np.full((2, 4), 3.0))


def test_identity_elimination():
    x = sym.Variable("x")
    y = sym._internal._copy(sym.tanh(x) * 1.0) / 1.0
    opt, _ = y.optimize(level=1)
    assert _op_names(opt) == ["tanh"]


def test_scalar_identity_kept_after_relu_activation():
    """Activation(act_type='relu') preserves int dtype, so a
    downstream *1.0 still promotes and must survive; tanh-activation
    is a real float producer (review fix)."""
    x = sym.Variable("x")
    relu_mul = sym.Activation(x, act_type="relu") * 1.0
    opt, _ = relu_mul.optimize(level=1)
    assert "_mul_scalar" in _op_names(opt)
    tanh_mul = sym.Activation(x, act_type="tanh") * 1.0
    opt2, _ = tanh_mul.optimize(level=1)
    assert "_mul_scalar" not in _op_names(opt2)


def test_cachedop_entry_does_not_pin_input_arrays():
    """Replay closures capture only the argument structure — never
    the building call's tensors (review fix: an LRU of 64 entries
    must not pin 64 input batches)."""
    import gc
    import weakref
    net = _mlp()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).rand(3, 8)
                 .astype("float32"))
    net(x)
    ref = weakref.ref(x)
    del x
    gc.collect()
    assert ref() is None, "CachedOp entry retained the input NDArray"


def test_scalar_identity_kept_on_integer_inputs(monkeypatch):
    """`int32 * 1.0` promotes to float32 — the node must survive so
    optimized and unoptimized graphs agree on dtype (review fix)."""
    x = sym.Variable("x")
    y = x * 1.0                       # input dtype unknown: keep
    opt, _ = y.optimize(level=1)
    assert _op_names(opt) == ["_mul_scalar"]
    monkeypatch.setenv("MXTPU_GRAPH_OPT", "1")
    ex = y.simple_bind(mx.cpu(), grad_req="null",
                       type_dict={"x": "int32"}, x=(2, 2))
    ex.copy_params_from({"x": nd.array(np.ones((2, 2), "int32"))})
    out = ex.forward()[0]
    assert out.asnumpy().dtype == np.float32


def test_add_zero_is_not_eliminated():
    # x + 0.0 rewrites -0.0 to +0.0: must survive
    x = sym.Variable("x")
    y = (x + 0.0) - 0.0
    opt, _ = y.optimize(level=1)
    assert len(_op_names(opt)) == 2


def test_transpose_pair_elimination():
    x = sym.Variable("x")
    y = sym.tanh(sym.transpose(sym.transpose(x, axes=(1, 0)),
                               axes=(1, 0)))
    opt, _ = y.optimize(level=1)
    assert _op_names(opt) == ["tanh"]
    # non-cancelling pair merges into one transpose
    z = sym.transpose(sym.transpose(x, axes=(0, 1)), axes=(1, 0))
    opt2, _ = z.optimize(level=1)
    assert _op_names(opt2) == ["transpose"]


def test_pruning_never_drops_reachable_outputs(monkeypatch):
    for seed in range(3):
        s = _random_symbol(seed, n_ops=16)
        n_heads = len(s._heads)
        opt, report = s.optimize(level=2)
        assert len(opt._heads) == n_heads
        live = {id(n) for n in _topo(opt._heads)}
        g = Graph(opt._heads)
        assert {id(n) for n in g.topo()} == live
        assert report["nodes_after"] <= report["nodes_before"]


def test_fuse_elemwise_chains(monkeypatch):
    x = sym.Variable("x")
    y = sym.relu(sym.tanh(sym.sin(x) * 0.5) + 2.0)
    opt, report = y.optimize(level=2)
    ops = [n.op for n in _topo(opt._heads) if n.op is not None]
    assert len(ops) == 1 and isinstance(ops[0], FusedOp)
    fused = [p for p in report["passes"]
             if p["pass"] == "fuse_elemwise"][0]
    assert fused["chains"] == 1 and fused["ops_fused"] == 5
    vals = {"x": np.random.RandomState(0).randn(3, 3)
            .astype("float32")}
    assert np.array_equal(_bind_forward(y, vals, 0, monkeypatch)[0],
                          _bind_forward(y, vals, 2, monkeypatch)[0])


def test_fusion_respects_multi_consumer_boundaries():
    x = sym.Variable("x")
    t = sym.tanh(x)
    y = t * 2.0 + sym.sin(t)      # t has 2 consumers: chain breaker
    opt, _ = y.optimize(level=2)
    names = [n.op.name for n in _topo(opt._heads) if n.op is not None]
    assert "tanh" in names        # never swallowed into a chain


def test_pass_manager_ordering_and_unknown_pass():
    pm = PassManager(["prune_dead_nodes", "fold_constants",
                      "eliminate_identity"])
    order = pm.pass_names
    assert order.index("eliminate_identity") \
        < order.index("fold_constants") \
        < order.index("prune_dead_nodes")
    with pytest.raises(KeyError):
        PassManager(["no_such_pass"])


def test_custom_pass_registration():
    from incubator_mxnet_tpu.graph import GraphPass, register_pass

    @register_pass
    class CountTanh(GraphPass):
        name = "count_tanh_test"
        after = ("eliminate_identity",)

        def run(self, graph):
            n = sum(1 for nd_ in graph.topo()
                    if nd_.op is not None and nd_.op.name == "tanh")
            return {"tanh": n}

    try:
        x = sym.Variable("x")
        _, report = sym.tanh(x).optimize(
            level=1, pass_names=["eliminate_identity",
                                 "count_tanh_test"])
        row = [p for p in report["passes"]
               if p["pass"] == "count_tanh_test"][0]
        assert row["tanh"] == 1
    finally:
        del PASSES["count_tanh_test"]


def test_optimize_level_zero_returns_input():
    x = sym.Variable("x")
    y = sym.tanh(x)
    opt, report = y.optimize(level=0)
    assert opt is y and report["passes"] == []


def test_executor_and_module_expose_report(monkeypatch):
    monkeypatch.setenv("MXTPU_GRAPH_OPT", "1")
    x = sym.Variable("data")
    y = sym.FullyConnected(x, num_hidden=3, name="fcrep")
    mod = mx.mod.Module(y, data_names=["data"], label_names=[])
    mod.bind(data_shapes=[("data", (2, 4))], for_training=False)
    rep = mod.graph_opt_report
    assert rep is not None and rep["level"] == 1
    assert rep["nodes_after"] <= rep["nodes_before"]


def test_pipeline_telemetry_counters_and_span():
    from incubator_mxnet_tpu import telemetry
    before = telemetry.counter("graph_passes_total").value
    x = sym.Variable("x")
    (sym.tanh(x) + sym.tanh(x)).optimize(level=1)
    assert telemetry.counter("graph_passes_total").value > before
    snap = telemetry.snapshot()
    assert "span_graph_optimize_seconds" in snap["histograms"]


# ----------------------------------------------------------- CachedOp
def _mlp(width=16):
    net = nn.HybridSequential()
    net.add(nn.Dense(width, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return net


def test_cachedop_hit_miss_counts():
    net = _mlp()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).rand(3, 8)
                 .astype("float32"))
    net(x)
    net(x)
    st = net._cached_op.stats()
    assert st["misses"] == 1 and st["hits"] == 1 \
        and st["traces"] == 1
    net(nd.array(np.random.RandomState(1).rand(5, 8)
                 .astype("float32")))
    st = net._cached_op.stats()
    assert st["misses"] == 2 and st["traces"] == 2


def test_cachedop_train_eval_cache_separation():
    net = nn.HybridSequential()
    net.add(nn.BatchNorm(in_channels=3))
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).rand(4, 3)
                 .astype("float32") + 2)
    y_eval = net(x).asnumpy()
    bn = net[0]
    before = bn.running_mean.data().asnumpy().copy()
    np.testing.assert_allclose(bn.running_mean.data().asnumpy(),
                               before)            # eval: no update
    with autograd.record():
        net(x)
    after = bn.running_mean.data().asnumpy()
    assert not np.allclose(before, after)         # train: updated
    st = net._cached_op.stats()
    assert st["entries"] == 2                     # train + eval keys
    y_eval2 = net(x).asnumpy()
    assert not np.allclose(y_eval, y_eval2)       # stats moved


class _ScaledDense(mx.gluon.HybridBlock):
    def __init__(self, units, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.fc = nn.Dense(units, in_units=4)

    def hybrid_forward(self, F, x, scale):
        return self.fc(x) * scale


def test_cachedop_stable_scalar_signatures():
    """Regression (ISSUE 6 bugfix): equal constant args never force a
    retrace, whatever numeric wrapper they arrive in — and the scalar
    is actually applied in the replay."""
    net = _ScaledDense(3)
    net.initialize(mx.init.One())
    net.hybridize()
    x = nd.array(np.ones((2, 4), "float32"))
    base = net(x, 1.0).asnumpy()
    for v in (2.0, np.float32(2.0), np.float64(2.0),
              np.array(2.0)):
        out = net(x, v).asnumpy()
        np.testing.assert_allclose(out, base * 2.0, rtol=1e-6)
    st = net._cached_op.stats()
    assert st["traces"] == 2, \
        f"equal scalars retraced: {st}"           # 1.0 and 2.0 only
    net(x, 2)                                     # int is a new class
    assert net._cached_op.stats()["traces"] == 3


def test_cachedop_capacity_lru():
    net = _mlp()
    rs = np.random.RandomState(0)
    net(nd.array(rs.rand(2, 8).astype("float32")))   # settle shapes
    co = CachedOp(net, capacity=2)
    for b in (2, 3, 4, 5):
        co(nd.array(rs.rand(b, 8).astype("float32")))
    assert len(co._entries) == 2
    assert co.misses == 4


def test_cachedop_unhashable_arg_falls_back():
    class Weird(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, x, cfg):
            return x * (cfg["k"] if isinstance(cfg, dict) else cfg)

    net = Weird()
    net.initialize()
    net.hybridize()
    x = nd.array(np.ones((2, 2), "float32"))
    out = net(x, {"k": 3.0})
    np.testing.assert_allclose(out.asnumpy(), 3.0 * np.ones((2, 2)))
    assert net._cache_fallback        # warned once
    # the fallback is per-call: a keyable call still hits the cache
    out2 = net(x, 2.0)
    np.testing.assert_allclose(out2.asnumpy(), 2.0 * np.ones((2, 2)))
    assert net._cached_op.stats()["misses"] == 1
    out3 = net(x, {"k": 4.0})         # unsupported again: eager
    np.testing.assert_allclose(out3.asnumpy(), 4.0 * np.ones((2, 2)))
    assert net._cached_op.stats()["misses"] == 1


def test_cachedop_graph_mode_engages_and_matches_eager(monkeypatch):
    monkeypatch.setenv("MXTPU_GRAPH_OPT", "2")
    net = _mlp()
    x = nd.array(np.random.RandomState(2).rand(3, 8)
                 .astype("float32"))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    assert net._cached_op.stats()["modes"] == ["graph"]
    np.testing.assert_allclose(eager, hybrid, rtol=1e-5, atol=1e-6)


def test_cachedop_dropout_block_uses_jit_mode():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8))
    net.add(nn.Dropout(0.5))
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).rand(2, 8)
                 .astype("float32"))
    with autograd.record():
        net(x)
    assert net._cached_op.stats()["modes"] == ["jit"]


def test_cachedop_gradients_through_replay():
    net = _mlp()
    net.hybridize()
    x = nd.array(np.random.RandomState(5).rand(4, 8)
                 .astype("float32"))
    with autograd.record():
        y = net(x).sum()
    y.backward()
    g_hybrid = {k: p.grad().asnumpy().copy()
                for k, p in net.collect_params().items()}
    net.hybridize(active=False)         # same params, eager path
    with autograd.record():
        y2 = net(x).sum()
    y2.backward()
    for k, p in net.collect_params().items():
        np.testing.assert_allclose(g_hybrid[k], p.grad().asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_symbolblock_forward_caches_graph_fn():
    x = sym.Variable("sbx")
    y = sym.tanh(sym.FullyConnected(x, num_hidden=3, name="sbfc"))
    blk = mx.gluon.SymbolBlock(
        y, x, params={"sbfc_weight": nd.array(np.ones((3, 4), "f")),
                      "sbfc_bias": nd.array(np.zeros(3, "f"))})
    v = nd.array(np.ones((2, 4), "float32"))
    blk(v)
    first = blk._graph_fn
    blk(v)
    assert blk._graph_fn is first and first is not None


# ---------------------------------------------------------- lint rule
def test_lint_graph_mutation_rule(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lint", os.path.join(REPO, "ci", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    d = tmp_path / "incubator_mxnet_tpu" / "module"
    d.mkdir(parents=True)
    f = d / "x.py"
    f.write_text(
        "from incubator_mxnet_tpu.symbol.symbol import _Node\n"
        "def rewrite(node, other):\n"
        "    node.inputs = [(other, 0)]\n"
        "    node.attrs['k'] = 'v'\n"
        "    node.inputs.append((other, 1))\n")
    problems = lint.check_file(f)
    assert sum("pass pipeline" in p for p in problems) >= 4
    # escape hatch + self-attributes stay clean
    f.write_text(
        "def rewrite(node, other):\n"
        "    node.inputs = [(other, 0)]  # graph-ok: test fixture\n"
        "class T:\n"
        "    def __init__(self, inputs):\n"
        "        self.inputs = inputs\n"
        "        self.op = None\n")
    assert not any("pass pipeline" in p for p in lint.check_file(f))
    # inside graph/ the rule does not apply
    g = tmp_path / "incubator_mxnet_tpu" / "graph"
    g.mkdir()
    f2 = g / "y.py"
    f2.write_text("def rewrite(node, e):\n    node.inputs = [e]\n")
    assert not any("pass pipeline" in p for p in lint.check_file(f2))
