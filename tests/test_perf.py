"""The Symbol graph's static cost model (docs/observability.md):
its totals against XLA's own cost_analysis on the three graphs of
tests/_graphs.py and on ResNet-50, device-DB / roofline unit
semantics, Module.perf_report tables, and op-cost lint coverage."""
import os
import sys

import numpy as np
import pytest

import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import perf
from incubator_mxnet_tpu import symbol as symmod
from incubator_mxnet_tpu import telemetry as tel
from incubator_mxnet_tpu.executor import build_graph_fn
from incubator_mxnet_tpu.ops import registry as op_registry
from incubator_mxnet_tpu.perf import cost_model, device_db

import _graphs

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    tel.get_registry().reset()
    yield
    tel.get_registry().reset()


# ------------------------------------------------- analytic vs XLA
# The acceptance bar for the cost model: its totals must track XLA's
# own compiled cost_analysis within 10% on the three graphs.
def _analytic_vs_xla(s, shapes):
    """(CostReport, XLA's cost dict or None, relative FLOPs gap or
    None) for one graph's forward at fixed shapes."""
    rep = perf.symbol_cost(s, shapes)
    arg_names = s.list_arguments()
    aux_names = s.list_auxiliary_states()
    known = {k: v for k, v in shapes.items()
             if k in set(arg_names) | set(aux_names)}
    arg_shapes, _, aux_shapes = s.infer_shape_partial(**known)
    run = build_graph_fn(s)
    args = {n: jax.ShapeDtypeStruct(tuple(sh), np.float32)
            for n, sh in zip(arg_names, arg_shapes)}
    auxs = {n: jax.ShapeDtypeStruct(tuple(sh), np.float32)
            for n, sh in zip(aux_names, aux_shapes)}
    rng = jax.ShapeDtypeStruct((2,), np.uint32)
    xc = perf.jit_cost(lambda av, xv, r: run(av, xv, r, False),
                       args, auxs, rng)
    delta = (abs(rep.flops - xc["flops"]) / xc["flops"]
             if xc and xc.get("flops") else None)
    return rep, xc, delta


@pytest.mark.parametrize("graph", ["mlp", "resnet_block",
                                   "transformer_step"])
def test_analytic_flops_within_10pct_of_xla(graph):
    s, shapes = getattr(_graphs, f"_graph_{graph}")(symmod)
    rep, xc, delta = _analytic_vs_xla(s, shapes)
    assert rep.flops > 0 and rep.bytes > 0
    assert xc is not None and xc["flops"] > 0, \
        "backend reported no cost_analysis"
    assert delta is not None and delta <= 0.10, \
        f"{graph}: analytic {rep.flops:.3e} vs XLA " \
        f"{xc['flops']:.3e} (delta {delta:.1%})"
    # full coverage on these graphs: no unknown or default-cost
    # nodes sneak into the totals
    assert rep.coverage["unknown"] == 0, rep.unknown_ops
    assert rep.coverage["default"] == 0, rep.default_ops


def test_resnet50_forward_flops_are_the_published_count():
    """7.826 GFLOPs a 224 x 224 image with a multiply-add counted as
    two: an edit to the model or to the cost pass that moves the
    count by 2% shows here."""
    mx.random.seed(0)
    net = mx.gluon.model_zoo.vision.resnet50_v1()
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((1, 3, 32, 32)))     # settles the deferred shapes
    rep = perf.symbol_cost(net._to_symbol(symmod.Variable("data")),
                           {"data": (1, 3, 224, 224)})
    assert rep.flops == pytest.approx(7.826e9, rel=0.02)
    assert rep.coverage["unknown"] == 0, rep.unknown_ops
    assert rep.scaled(3.0).flops == pytest.approx(3 * rep.flops)


def test_cost_report_families_scaling_and_table():
    s, shapes = _graphs._graph_transformer_step(symmod)
    rep = perf.symbol_cost(s, shapes)
    # the symbol-level transformer step spells attention out as
    # matmuls + elementwise (no fused attention op in the graph)
    fams = set(rep.per_family)
    assert "matmul" in fams and "embedding" in fams
    # matmul dominates a transformer step's FLOPs
    assert rep.per_family["matmul"]["flops"] > 0.5 * rep.flops
    train = rep.scaled(3.0)
    assert train.flops == pytest.approx(3.0 * rep.flops)
    assert train.bytes == pytest.approx(3.0 * rep.bytes)
    assert train.arithmetic_intensity == pytest.approx(
        rep.arithmetic_intensity)
    caps = device_db.caps_for_kind("cpu")
    rows = train.table(caps, "float32")
    assert rows and all(
        {"family", "gflops", "flops_pct", "bound"} <= set(r)
        for r in rows)
    assert sum(r["flops_pct"] for r in rows) == pytest.approx(
        100.0, abs=0.5)
    summ = train.summary()
    assert summ["gflops"] == pytest.approx(train.flops / 1e9,
                                           abs=5e-4)


# --------------------------------------------- device DB + roofline
def test_device_db_peaks_and_dtype_conventions():
    v4 = device_db.caps_for_kind("TPU v4")
    assert v4.peak("bfloat16") == 275e12
    assert v4.peak("float32") == 275e12 / 8
    assert not v4.nominal
    v5e = device_db.caps_for_kind("TPU v5e chip")
    assert v5e.peak("int8") == 2 * v5e.peak("bfloat16")
    lite = device_db.caps_for_kind("TPU v5 lite")   # a v5e chip's kind
    assert (lite.peak("bfloat16"), lite.hbm_bytes) == \
        (197e12, 16 * (1 << 30))
    cpu = device_db.caps_for_kind("cpu")
    assert cpu.nominal
    assert cpu.peak("float32") == cpu.peak("bfloat16")
    import jax
    assert device_db.caps_for(jax.devices()[0]).nominal

    # an accelerator no row knows is an error, never CPU numbers: MFU
    # and the OOM gate's capacity would both be made up
    class FakeDev:
        device_kind = "some future accelerator"
    for ask in (lambda: device_db.caps_for_kind(FakeDev.device_kind),
                lambda: device_db.caps_for(FakeDev()),
                lambda: device_db.peak_flops(FakeDev()),
                lambda: device_db.hbm_capacity(FakeDev())):
        with pytest.raises(ValueError, match="DEVICE_DB"):
            ask()

    class V5p:
        device_kind = "TPU v5p"
    assert device_db.peak_flops(V5p()) == 459e12


def test_cpu_nominal_peaks_respect_env(monkeypatch):
    monkeypatch.setenv("MXTPU_PERF_CPU_PEAK_GFLOPS", "50")
    monkeypatch.setenv("MXTPU_PERF_CPU_GBPS", "10")
    caps = device_db.caps_for_kind("")
    assert caps.peak("float32") == 50e9
    assert caps.hbm_bytes_per_s == 10e9


def test_roofline_units_and_bound_classification():
    caps = device_db.DeviceCaps("test", 100e9, 100.0)  # ridge = 1.0
    r = device_db.roofline(200e9, 1e9, caps, "bfloat16")
    assert r["compute_s"] == pytest.approx(2.0)
    assert r["memory_s"] == pytest.approx(0.01)
    assert r["predicted_s"] == pytest.approx(2.0)
    assert r["bound"] == "compute"
    assert r["arithmetic_intensity"] == pytest.approx(200.0)
    assert r["ridge_intensity"] == pytest.approx(1.0)
    assert device_db.roofline(1e9, 200e9, caps)["bound"] == "memory"
    assert device_db.roofline(1e9, 1e9, caps)["bound"] == "balanced"
    assert device_db.roofline(0, 0, caps)["bound"] == "idle"


# ------------------------------------------------ perf_report views
def test_module_perf_report_tables():
    data = symmod.Variable("data")
    fc1 = symmod.FullyConnected(data, num_hidden=512, name="fc1")
    act = symmod.Activation(fc1, act_type="relu")
    fc2 = symmod.FullyConnected(act, num_hidden=64, name="fc2")
    out = symmod.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.bind(data_shapes=[("data", (64, 256))],
             label_shapes=[("softmax_label", (64,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    rep = mod.perf_report()
    assert rep["per_family"], "empty per-family table"
    assert "matmul" in {r["family"] for r in rep["per_family"]}
    assert rep["total"]["gflops"] > 0
    assert rep["roofline"]["bound"] in (
        "compute", "memory", "balanced")
    assert rep["coverage"]["unknown"] == 0
    xla = rep.get("xla_check")
    if xla is not None:          # backend-dependent
        assert xla["rel_delta"] <= 0.10


# ------------------------------------------------- op-cost coverage
def test_cost_model_covers_entire_op_registry():
    names = {op.name for op in op_registry.OPS.values()}
    assert names, "op registry unexpectedly empty"
    assert cost_model.coverage_gaps(names) == []
    # and the lint rule that enforces it stays armed
    sys.path.insert(0, os.path.join(REPO, "ci"))
    try:
        import lint
    finally:
        sys.path.pop(0)
    assert hasattr(lint, "check_op_cost_coverage")
