"""Perf observatory (docs/observability.md): analytic graph cost
model vs XLA's own cost_analysis on the three bench graphs, device-DB
/ roofline unit semantics, model-method FLOPs parity with the shared
formulas, the transfer-budget proof that the MFU gauges add zero
device->host reads, Module/ServingEngine perf_report tables,
launch.py fleet-MFU aggregation, op-cost lint coverage, and the
bench_gate regression gate over synthetic and real trajectories."""
import json
import os
import sys
import warnings

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, perf
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu import symbol as symmod
from incubator_mxnet_tpu import telemetry as tel
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.ops import registry as op_registry
from incubator_mxnet_tpu.perf import cost_model, device_db

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _load_tool(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def _load_bench():
    sys.path.insert(0, REPO)
    try:
        import importlib
        return importlib.import_module("bench")
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    tel.get_registry().reset()
    yield
    tel.get_registry().reset()


# ------------------------------------------------- analytic vs XLA
# The acceptance bar for the cost model: its totals must track XLA's
# own compiled cost_analysis within 10% on the three bench graphs.
@pytest.mark.parametrize("graph", ["mlp", "resnet_block",
                                   "transformer_step"])
def test_analytic_flops_within_10pct_of_xla(graph):
    bench = _load_bench()
    builder = getattr(bench, f"_graph_{graph}")
    s, shapes = builder(symmod)
    rep, xc, delta = bench._analytic_vs_xla(s, shapes)
    assert rep.flops > 0 and rep.bytes > 0
    assert xc is not None and xc["flops"] > 0, \
        "backend reported no cost_analysis"
    assert delta is not None and delta <= 0.10, \
        f"{graph}: analytic {rep.flops:.3e} vs XLA " \
        f"{xc['flops']:.3e} (delta {delta:.1%})"
    # full coverage on the bench graphs: no unknown or default-cost
    # nodes sneak into the headline numbers
    assert rep.coverage["unknown"] == 0, rep.unknown_ops
    assert rep.coverage["default"] == 0, rep.default_ops


def test_cost_report_families_scaling_and_table():
    bench = _load_bench()
    s, shapes = bench._graph_transformer_step(symmod)
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


# ------------------------------------- model-method formula parity
def test_transformer_flops_methods_match_shared_formulas():
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    net = TransformerLM(64, d_model=32, n_layers=2, n_heads=4,
                        max_len=16)
    assert net.train_flops_per_token(16) == \
        perf.transformer_train_flops_per_token(
            d_model=32, n_layers=2, vocab=64, seq_len=16, n_heads=4)
    assert net.decode_flops_per_token(12) == \
        perf.transformer_decode_flops_per_token(
            d_model=32, n_layers=2, vocab=64, context_len=12,
            n_heads=4)
    # windowed attention caps the context term
    win = TransformerLM(64, d_model=32, n_layers=2, n_heads=4,
                        max_len=64, attn_window=8)
    assert win.decode_flops_per_token(64) == \
        win.decode_flops_per_token(8)


# -------------------------------------------- transfer-budget proof
def test_mfu_gauges_add_zero_host_reads(monkeypatch):
    """The zero-added-syncs contract: with the sentinel at guard
    interval 4 and the MFU clock armed and PUBLISHING (interval 2),
    the sole device->host transfer point (read_window_bad) still
    fires exactly twice over 8 steps — the same count as the
    perf-off baseline in test_sentinel.py."""
    monkeypatch.setenv("MXTPU_NONFINITE_POLICY", "skip")
    monkeypatch.setenv("MXTPU_GUARD_INTERVAL", "4")
    monkeypatch.setenv("MXTPU_PERF_INTERVAL", "2")
    reads = []
    orig = opt_mod.read_window_bad
    monkeypatch.setattr(opt_mod, "read_window_bad",
                        lambda g: reads.append(1) or orig(g))
    mx.random.seed(0)
    rs = np.random.RandomState(0)
    data = rs.randn(80, 10).astype("float32")
    labels = rs.randint(0, 3, 80).astype("float32")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    clock = trainer.arm_perf(flops_per_step=1e9,
                             bytes_per_step=1e8,
                             tokens_per_step=10)
    assert clock is trainer._perf_clock
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for step in range(8):
            lo = (step * 10) % len(data)
            x = nd.array(data[lo:lo + 10])
            y = nd.array(labels[lo:lo + 10])
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(10)
    assert len(reads) == 2, \
        f"perf gauges changed the transfer budget: {len(reads)} reads"
    gauges = tel.snapshot()["gauges"]
    assert gauges["train_mfu"] > 0
    assert gauges["train_mbu"] > 0
    assert gauges["train_tokens_per_sec"] > 0


def test_sharded_step_cost_analysis_arms_clock(monkeypatch):
    monkeypatch.setenv("MXTPU_PERF_INTERVAL", "2")
    from incubator_mxnet_tpu import parallel
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd",
        optimizer_params={"learning_rate": 0.01},
        example_args=[mx.nd.zeros((2, 8))])
    rs = np.random.RandomState(0)
    x = np.asarray(rs.rand(8, 8), np.float32)
    y = np.asarray(rs.randint(0, 4, (8,)), np.int32)
    cost = step.cost_analysis(x, y)
    assert cost is not None and cost["flops"] > 0 \
        and cost["bytes"] > 0
    assert step._perf_clock is not None       # auto-armed
    for _ in range(4):
        loss = step(x, y)
    assert np.isfinite(float(loss))
    assert tel.snapshot()["gauges"]["train_mfu"] > 0


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


def test_serving_engine_perf_report_and_gauges(monkeypatch):
    monkeypatch.setenv("MXTPU_PERF_INTERVAL", "2")
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving.engine import ServingEngine
    mx.random.seed(0)
    net = TransformerLM(64, d_model=32, n_layers=2, n_heads=4,
                        max_len=32)
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.array(np.zeros((1, 4), "int32")))
    eng = ServingEngine(net, max_batch=2, block_size=8,
                        num_blocks=16)
    rs = np.random.RandomState(0)
    for _ in range(3):
        eng.submit([int(t) for t in rs.randint(1, 64, 5)],
                   max_new_tokens=6)
    events = list(eng.stream())
    assert len(events) == 3 * 6
    gauges = tel.snapshot()["gauges"]
    assert gauges["serving_mfu"] > 0
    assert gauges["serving_flops_per_token"] > 0
    rep = eng.perf_report()
    assert rep["flops_per_token"] > 0
    assert rep["per_family"], "empty decode per-family table"
    fams = {r["family"] for r in rep["per_family"]}
    assert "matmul" in fams and "attention" in fams
    assert rep["roofline"]["bound"] in (
        "compute", "memory", "balanced")


# ------------------------------------------- launch.py fleet view
def test_launch_fleet_mfu_aggregation():
    launch = _load_tool("launch")
    snaps = {
        0: {"counters": {"train_steps_total": 10},
            "gauges": {"train_mfu": 0.5}, "histograms": {}},
        1: {"counters": {"train_steps_total": 10},
            "gauges": {"train_mfu": 0.3}, "histograms": {}},
        2: {"counters": {}, "gauges": {"serving_mfu": 0.4},
            "histograms": {}},
    }
    agg = launch._aggregate_telemetry(snaps)
    assert agg["mfu"] == pytest.approx((0.5 + 0.3 + 0.4) / 3)
    assert agg["mfu_slowest"] == (1, 0.3)
    status = launch._format_status(agg)
    assert "mfu: 40.0%" in status
    assert "slowest rank 1 at 30.0%" in status
    report = launch._format_report(snaps)
    assert "mfu=50.0%" in report and "mfu=30.0%" in report
    # no rank publishing MFU -> the part is absent, not 0%
    agg0 = launch._aggregate_telemetry(
        {0: {"counters": {}, "gauges": {}, "histograms": {}}})
    assert agg0["mfu"] is None
    assert "mfu" not in launch._format_status(agg0)


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


# ------------------------------------------------------ bench_gate
def _rec(metric, value, rnd, hib=True):
    return {"schema": "bench-v1", "round": rnd, "metric": metric,
            "value": value, "unit": "u", "higher_is_better": hib}


def test_bench_gate_catches_injected_regression():
    bg = _load_tool("bench_gate")
    history = [_rec("tok_s", 100.0, 1), _rec("tok_s", 110.0, 2),
               _rec("p99_s", 1.0, 1, hib=False)]
    # 20% below best-so-far (110) with a 10% band -> regression
    failures, checked = bg.gate([_rec("tok_s", 88.0, 3)], history,
                                band=0.10)
    assert checked == 1 and len(failures) == 1
    assert failures[0]["metric"] == "tok_s"
    assert failures[0]["limit"] == pytest.approx(99.0)
    # within the band -> pass
    failures, _ = bg.gate([_rec("tok_s", 100.0, 3)], history, 0.10)
    assert failures == []
    # lower-is-better: +20% past the ceiling fails, first-seen skips
    failures, checked = bg.gate(
        [_rec("p99_s", 1.2, 3, hib=False), _rec("new_metric", 1, 3)],
        history, 0.10)
    assert checked == 1 and len(failures) == 1
    assert failures[0]["metric"] == "p99_s"


def test_bench_gate_normalizes_heterogeneous_rounds():
    bg = _load_tool("bench_gate")
    doc = {"metric": "perf_report", "train": {"mfu": 0.4},
           "serving": {"tokens_per_s": 50.0}}
    recs = bg.normalize(doc, round_no=18)
    assert {r["metric"] for r in recs} == \
        {"perf_train_mfu", "perf_serving_tokens_per_s"}
    assert all(r["schema"] == "bench-v1" and r["round"] == 18
               for r in recs)
    # r01-style driver envelopes unwrap; failed rounds -> no records
    wrapped = {"n": 3, "rc": 0, "parsed": doc}
    assert len(bg.normalize(wrapped)) == 2
    assert bg.normalize({"n": 4, "rc": 1, "parsed": None}) == []
    assert bg.normalize({"metric": "unknown_experiment"}) == []


def test_bench_gate_real_history_passes_and_appends(tmp_path,
                                                    capsys):
    bg = _load_tool("bench_gate")
    history = bg.load_history()
    assert history, "committed BENCH history normalized to nothing"
    traj = bg.trajectory_summary(history)
    assert len(traj) >= 10
    assert "serving_tokens_per_s" in traj
    # the ci gate over the committed history passes
    assert bg.main(["--check"]) == 0
    out = capsys.readouterr().out
    assert "bench_gate: OK" in out
    # trajectory records append once (dedup on round+metric)
    p = tmp_path / "PROGRESS.jsonl"
    p.write_text('{"driver": "unrelated line"}\n')
    n = bg.append_progress(history, str(p))
    assert n == len(history)
    assert bg.append_progress(history, str(p)) == 0
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert sum(1 for d in lines
               if d.get("schema") == "bench-v1") == len(history)
