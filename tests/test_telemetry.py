"""Unified telemetry (docs/observability.md): registry semantics,
disabled-mode zero side effects, emitter flush/rotation, heartbeat
ride-along, step-timeline spans for eager + fused Trainer and
Module.fit, counters wired from fault-injected resilience / data /
sentinel runs, launch.py multi-rank aggregation, profiler dump
hardening, and the transfer-budget proof that telemetry adds no
device->host reads beyond the sentinel's guard-interval baseline."""
import json
import os
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu import recordio as rio
from incubator_mxnet_tpu import resilience as rz
from incubator_mxnet_tpu import telemetry as tel
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.model import BatchEndParam

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _load_tool(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def _load_lint():
    sys.path.insert(0, os.path.join(REPO, "ci"))
    try:
        import lint
        return lint
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("MXTPU_TELEMETRY", raising=False)
    monkeypatch.delenv("MXTPU_TELEMETRY_FILE", raising=False)
    tel.stop_emitter()
    tel.get_registry().reset()
    rz.reset_faults()
    yield
    tel.stop_emitter()
    tel.get_registry().reset()
    rz.reset_faults()


# ------------------------------------------------------------ registry
def test_counter_thread_safety_under_concurrent_increments():
    c = tel.get_registry().counter("train_steps_total")
    threads = [threading.Thread(
        target=lambda: [c.inc() for _ in range(500)])
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8 * 500


def test_registry_type_conflict_raises():
    tel.get_registry().gauge("loss_scale").set(2.0)
    with pytest.raises(TypeError, match="already registered"):
        tel.get_registry().counter("loss_scale")


def test_histogram_reservoir_is_bounded():
    h = tel.get_registry().histogram("prefetch_queue_wait_seconds",
                                     max_samples=64)
    for i in range(5000):
        h.observe(float(i))
    st = h.stats()
    assert st["count"] == 5000          # exact over the whole run
    assert st["min"] == 0.0 and st["max"] == 4999.0
    assert len(h._samples) == 64        # reservoir stays bounded
    # percentiles come from the most recent window
    assert st["p50"] >= 4936


def test_snapshot_shape_and_rank(monkeypatch):
    monkeypatch.setenv("MXTPU_WORKER_RANK", "3")
    tel.counter("train_steps_total").inc(7)
    tel.gauge("loss_scale").set(4.0)
    with tel.span("data_wait"):
        pass
    snap = tel.snapshot()
    assert snap["rank"] == 3
    assert snap["counters"]["train_steps_total"] == 7
    assert snap["gauges"]["loss_scale"] == 4.0
    assert snap["histograms"]["span_data_wait_seconds"]["count"] == 1
    text = tel.prometheus_text()
    assert "mxtpu_train_steps_total 7" in text
    assert "mxtpu_span_data_wait_seconds_count 1" in text


def test_disabled_mode_has_zero_side_effects(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    assert tel.counter("train_steps_total") is tel.NULL_METRIC
    assert tel.gauge("loss_scale") is tel.NULL_METRIC
    assert tel.histogram("prefetch_queue_wait_seconds") \
        is tel.NULL_METRIC
    assert tel.span("data_wait") is tel.NULL_SPAN
    tel.counter("train_steps_total").inc()
    with tel.span("data_wait"):
        pass
    snap = tel.snapshot()
    assert not snap["counters"] and not snap["histograms"]
    assert tel.heartbeat_payload() == ""
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE", str(tmp_path / "t"))
    assert tel.start_emitter() is None          # no emitter thread
    assert tel.maybe_start_emitter() is None
    # instrumented hot paths run clean with everything off
    up = opt_mod.GuardedUpdater(
        opt_mod.create("sgd"),
        guard=rz.NumericGuard(policy="skip", max_bad_steps=0))
    g = mx.nd.array(np.ones((2,), np.float32))
    w = mx.nd.array(np.ones((2,), np.float32))
    assert up.begin_step([g])
    up(0, g, w)
    assert not tel.snapshot()["counters"]


# ------------------------------------------- spans in a profiler session
@pytest.fixture
def fresh_ring():
    from incubator_mxnet_tpu import tracing
    tracing.reset_for_tests()
    yield tracing
    tracing.reset_for_tests()


def test_span_without_a_session_leaves_no_event(fresh_ring):
    with tel.span("data_wait", rid=3) as sp:
        with tel.span("host_sync"):
            pass
    assert fresh_ring.events("span") == []
    assert sp.elapsed > 0
    hists = tel.snapshot()["histograms"]
    assert hists["span_data_wait_seconds"]["count"] == 1
    assert hists["span_host_sync_seconds"]["count"] == 1


def test_span_in_a_session_records_tree_fields_and_shared_clock(
        fresh_ring, profiler_session, newest_spans):
    with profiler_session() as rec:
        with tel.span("data_wait", rid=7) as outer:
            with tel.span("host_sync", tokens=5):
                time.sleep(0.002)
            with tel.span("optimizer") as last:
                time.sleep(0.001)
                last.set(emitted=2)
    with tel.span("data_wait"):         # the session is over
        pass
    evs = {e["name"]: e for e in newest_spans()}
    assert set(evs) == {"data_wait", "host_sync", "optimizer"}
    top = evs["data_wait"]
    assert top["parent"] is None and top["rid"] == 7
    assert evs["host_sync"]["parent"] == top["id"]
    assert evs["host_sync"]["tokens"] == 5
    assert evs["optimizer"]["parent"] == top["id"]
    assert evs["optimizer"]["emitted"] == 2     # set inside the span
    for child in (evs["host_sync"], evs["optimizer"]):
        assert top["t0"] <= child["t0"] <= child["t1"] <= top["t1"]
    assert evs["host_sync"]["t1"] <= evs["optimizer"]["t0"]
    assert abs((top["t1"] - top["t0"]) - outer.elapsed) < 1e-9
    # the histograms count as they do without a session
    assert tel.snapshot()["histograms"][
        "span_data_wait_seconds"]["count"] == 2
    # the same spans lie in the trace as mx.<name>, on one clock:
    # durations and distances agree with perf_counter's to 0.2 ms
    traced = {n: (s, d) for n, s, d in rec.host_events()}
    assert set(traced) == {"mx." + n for n in evs}
    s0 = traced["mx.data_wait"][0]
    for name, e in evs.items():
        start, dur = traced["mx." + name]
        assert abs(dur / 1e9 - (e["t1"] - e["t0"])) < 2e-4, name
        assert abs((start - s0) / 1e9 - (e["t0"] - top["t0"])) < 2e-4


def test_sessions_are_counted_so_the_newest_can_be_taken_alone(
        fresh_ring, profiler_session):
    for _ in range(2):
        with profiler_session():
            with tel.span("data_wait"):
                pass
        with tel.span("data_wait"):     # a span sees the session end
            pass
    first, second = fresh_ring.events("span")
    assert second["session"] == first["session"] + 1
    assert second["id"] > first["id"]


def test_a_retrace_inside_a_span_gives_a_compile_span_under_it(
        fresh_ring, profiler_session, newest_spans):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def retraced_here(x):
        return x * 3 + 1

    with tel.span("data_wait"):
        pass                    # the compile listeners are in place
    x4, x5 = jnp.ones(4), jnp.ones(5)       # made before the count
    before = tel.counter("xla_compiles_total").value
    with profiler_session():
        with tel.span("forward_backward") as sp:
            retraced_here(x4)
            retraced_here(x4)               # no second compile
            with tel.span("host_sync"):
                retraced_here(x5)           # a new shape: a retrace
    evs = newest_spans()
    mine = [e for e in evs if e["name"] == "compile"
            and "retraced_here" in e["fun_name"]]
    by_name = {e["name"]: e for e in evs if e["name"] != "compile"}
    assert [e["parent"] for e in mine] == [
        by_name["forward_backward"]["id"], by_name["host_sync"]["id"]]
    for e in mine:
        assert e["cached"] is False and e["t1"] > e["t0"]
        assert e["t0"] >= by_name["forward_backward"]["t0"]
    compiles = [e for e in evs if e["name"] == "compile"]
    assert tel.counter("xla_compiles_total").value - before \
        == len(compiles) >= 2
    assert sp.elapsed > 0
    # without a session a compile is counted and leaves no event
    retraced_here(jnp.ones(6))
    assert tel.counter("xla_compiles_total").value - before \
        > len(compiles)
    assert len(newest_spans()) == len(evs)


def test_disabled_mode_spans_stay_silent_in_a_session(
        monkeypatch, fresh_ring, profiler_session):
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    with profiler_session() as rec:
        with tel.span("data_wait", rid=1) as sp:
            sp.set(emitted=1)
            jax.jit(lambda x: x - 2)(jnp.ones(3))
    assert sp is tel.NULL_SPAN and sp.elapsed == 0.0
    assert rec.host_events() == []
    monkeypatch.delenv("MXTPU_TELEMETRY")
    assert fresh_ring.events("span") == []
    assert "xla_compiles_total" not in tel.snapshot()["counters"]


# ------------------------------------------------------------- emitter
def test_emitter_flush_writes_jsonl_and_atomic_prom(tmp_path):
    tel.counter("train_steps_total").inc(5)
    path = str(tmp_path / "telemetry.jsonl")
    em = tel.TelemetryEmitter(path=path, interval=999)
    em.flush()
    tel.counter("train_steps_total").inc(5)
    em.flush()
    lines = [json.loads(s) for s in
             open(path).read().splitlines()]
    assert [s["counters"]["train_steps_total"]
            for s in lines] == [5, 10]
    prom = open(path + ".prom").read()
    assert "mxtpu_train_steps_total 10" in prom
    assert prom.startswith("# TYPE")
    assert not os.path.exists(path + ".prom.tmp")  # atomic replace


def test_emitter_rotation(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    em = tel.TelemetryEmitter(path=path, interval=999, max_bytes=300)
    tel.counter("train_steps_total").inc()
    for _ in range(8):
        em.flush()
    assert os.path.exists(path + ".1")
    # both generations hold parseable JSONL
    for p in (path, path + ".1"):
        for line in open(p).read().splitlines():
            json.loads(line)


def test_emitter_background_thread_and_retarget(tmp_path,
                                                monkeypatch):
    path = str(tmp_path / "t1.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE", path)
    monkeypatch.setenv("MXTPU_TELEMETRY_INTERVAL", "0.05")
    em = tel.maybe_start_emitter()
    assert em is not None and em.running
    assert tel.maybe_start_emitter() is em      # idempotent
    deadline = time.time() + 5
    while em.flushes == 0 and time.time() < deadline:
        time.sleep(0.02)
    assert em.flushes > 0
    # re-target to a new path stops the old emitter
    path2 = str(tmp_path / "t2.jsonl")
    em2 = tel.start_emitter(path=path2, interval=999)
    assert em2 is not em and not em.running
    tel.stop_emitter()                          # final flush
    assert not em2.running
    assert os.path.exists(path2)


def test_emitter_final_flush_at_process_exit(tmp_path):
    """A run shorter than MXTPU_TELEMETRY_INTERVAL must still leave
    a complete record: start_emitter registers an atexit final
    flush."""
    import subprocess
    path = str(tmp_path / "exit.jsonl")
    env = dict(os.environ, MXTPU_TELEMETRY="1",
               MXTPU_TELEMETRY_FILE=path,
               MXTPU_TELEMETRY_INTERVAL="600")
    env.pop("MXTPU_WORKER_RANK", None)
    code = ("import sys; sys.path.insert(0, %r); "
            "from incubator_mxnet_tpu import telemetry as tel; "
            "tel.counter('train_steps_total').inc(4); "
            "tel.maybe_start_emitter()" % REPO)
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    lines = [json.loads(s) for s in open(path).read().splitlines()]
    assert lines[-1]["counters"]["train_steps_total"] == 4
    assert os.path.exists(path + ".prom")


def test_emitter_rank_suffix_avoids_shared_path_collision(
        tmp_path, monkeypatch):
    """The launcher exports ONE MXTPU_TELEMETRY_FILE to every
    worker; nonzero ranks must suffix it or two emitters would race
    the rotation and tear each other's textfile."""
    base = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE", base)
    monkeypatch.setenv("MXTPU_WORKER_RANK", "1")
    em = tel.start_emitter(interval=999)
    assert em.path == base + ".rank1"
    assert tel.maybe_start_emitter() is em      # path stays stable
    em.flush()
    assert os.path.exists(base + ".rank1")
    assert not os.path.exists(base)
    tel.stop_emitter()
    monkeypatch.setenv("MXTPU_WORKER_RANK", "0")
    em = tel.start_emitter(interval=999)
    assert em.path == base                      # rank 0: bare path


def test_snapshots_ride_heartbeat_file(tmp_path):
    tel.counter("train_steps_total").inc(9)
    hb = str(tmp_path / "hb")
    try:
        rz.start_heartbeat(hb, interval=0.05)
        deadline = time.time() + 5
        snap = None
        while snap is None and time.time() < deadline:
            if os.path.exists(hb):
                lines = open(hb).read().splitlines()
                if len(lines) > 1:
                    snap = json.loads(lines[-1])
                    float(lines[0])     # line 1: bare timestamp
            time.sleep(0.02)
    finally:
        rz.stop_heartbeat()
    assert snap is not None
    assert snap["counters"]["train_steps_total"] == 9


# --------------------------------------------------------- speedometer
class _FakeTime:
    def __init__(self):
        self.now = 1000.0

    def time(self):
        return self.now


def test_speedometer_publishes_and_first_window_not_inflated(
        monkeypatch):
    import incubator_mxnet_tpu.callback as cb
    clk = _FakeTime()
    monkeypatch.setattr(cb, "time", clk)
    speedo = cb.Speedometer(batch_size=10, frequent=4,
                            auto_reset=False)
    # first callback lands mid-epoch at nbatch=2 (resumed stream):
    # the first measured window holds only 2 batches, not `frequent`
    speedo(BatchEndParam(0, 2, None, {}))
    clk.now += 2.0
    speedo(BatchEndParam(0, 4, None, {}))
    speed = tel.snapshot()["gauges"]["throughput_samples_per_sec"]
    assert speed == pytest.approx(2 * 10 / 2.0)   # not 4 * 10 / 2.0
    assert tel.snapshot()["gauges"]["nbatch"] == 4
    # steady state: full window over the full elapsed time
    clk.now += 4.0
    speedo(BatchEndParam(0, 8, None, {}))
    speed = tel.snapshot()["gauges"]["throughput_samples_per_sec"]
    assert speed == pytest.approx(4 * 10 / 4.0)


# ------------------------------------------------------------- monitor
def test_monitor_armed_interval_emits_span_and_row_counts():
    mon = mx.monitor.Monitor(interval=2)
    mon.install()
    try:
        for _ in range(4):
            mon.tic()
            if mon.activated:
                mx.monitor.observe_op(
                    "fc", [nd.array(np.ones((2, 2), np.float32))])
            mon.toc()
    finally:
        mon.uninstall()
    snap = tel.snapshot()
    assert snap["counters"]["monitor_armed_batches_total"] == 2
    assert snap["counters"]["monitor_stat_rows_total"] == 2
    assert snap["histograms"]["span_monitor_armed_seconds"][
        "count"] == 2


def test_monitor_span_closed_when_batch_aborts_before_toc():
    """An exception between tic() and toc() (sentinel raise mid
    forward/update) must not leak the armed span open: the next
    re-arm — or uninstall — closes it, so the armed section still
    lands in the timeline."""
    mon = mx.monitor.Monitor(interval=1)
    mon.install()
    try:
        mon.tic()            # armed; batch "aborts": no toc()
        mon.tic()            # re-arm closes the stale span
        mon.toc()
    finally:
        mon.uninstall()
    h = tel.snapshot()["histograms"]["span_monitor_armed_seconds"]
    assert h["count"] == 2
    mon2 = mx.monitor.Monitor(interval=1)
    mon2.install()
    mon2.tic()               # armed, aborted, never re-armed
    mon2.uninstall()         # closes the open span
    h = tel.snapshot()["histograms"]["span_monitor_armed_seconds"]
    assert h["count"] == 3
    assert mon2._span is None


# --------------------------------------------------------- tensorboard
def test_tensorboard_log_telemetry_writes_scalars():
    from incubator_mxnet_tpu.contrib import tensorboard as tb

    class W:
        def __init__(self):
            self.rows = []

        def add_scalar(self, tag, value, step):
            self.rows.append((tag, value, step))

    tel.counter("train_steps_total").inc(12)
    tel.gauge("throughput_samples_per_sec").set(640.0)
    w = W()
    n = tb.log_telemetry(w)
    assert n == len(w.rows) == 2
    rows = dict((t, (v, s)) for t, v, s in w.rows)
    assert rows["telemetry/throughput_samples_per_sec"] == \
        (640.0, 12)
    assert rows["telemetry/train_steps_total"] == (12, 12)


# ------------------------------------------------------------ profiler
def test_profiler_dump_metadata_and_counter_events(tmp_path):
    prof = mx.profiler._profiler
    prof.set_config(filename=str(tmp_path / "trace.json"))
    prof.set_state("run")
    try:
        t = time.perf_counter()
        prof.add_event("op_a", t, t + 0.001)
        tel.counter("train_steps_total").inc(3)
        with tel.span("data_wait"):
            pass                # spans land in the profiler stream
        out = mx.profiler.dump_profile()
    finally:
        prof.set_state("stop")
        prof.set_config(filename="profile.json")
    events = json.load(open(out))["traceEvents"]
    phases = {}
    for e in events:
        phases.setdefault(e["ph"], []).append(e)
    assert any(e["name"] == "process_name" for e in phases["M"])
    assert any(e["name"] == "thread_name" for e in phases["M"])
    names = {e["name"] for e in phases["X"]}
    assert {"op_a", "data_wait"} <= names
    counter_events = {e["name"]: e["args"] for e in phases["C"]}
    assert counter_events["train_steps_total"] == \
        {"train_steps_total": 3}


def test_profiler_concurrent_dump_loses_no_events(tmp_path):
    prof = mx.profiler._profiler
    prof.set_config(filename=str(tmp_path / "trace.json"))
    prof.set_state("stop")
    with prof._lock:
        prof._events = []
    total = 800
    stop_adding = threading.Event()

    def add():
        for i in range(total // 4):
            t = time.perf_counter()
            prof.add_event("op", t, t)
        stop_adding.set()

    adders = [threading.Thread(target=add) for _ in range(4)]
    seen = 0
    for t in adders:
        t.start()
    try:
        while not all(stop_adding.is_set()
                      for _ in adders) or any(t.is_alive()
                                              for t in adders):
            out = prof.dump(finished=True)
            seen += sum(e["ph"] == "X"
                        for e in json.load(open(out))["traceEvents"])
            if all(not t.is_alive() for t in adders):
                break
    finally:
        for t in adders:
            t.join()
    out = prof.dump(finished=True)
    seen += sum(e["ph"] == "X"
                for e in json.load(open(out))["traceEvents"])
    prof.set_config(filename="profile.json")
    assert seen == 4 * (total // 4)


# ------------------------------------------------- fit-loop timelines
def _toy_module_problem(n=64, dim=10, classes=5, batch=16, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, dim).astype(np.float32)
    w = rs.rand(dim, classes).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=batch,
                           label_name="softmax_label")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc", num_hidden=classes)
    return it, mx.sym.SoftmaxOutput(net, name="softmax")


def _make_image_rec(tmp_path, n=12, bad=()):
    rec = str(tmp_path / "d.rec")
    idx = str(tmp_path / "d.idx")
    w = rio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        if i in bad:
            w.write_idx(i, rio.pack(rio.IRHeader(0, i, i, 0),
                                    b"not-an-image"))
        else:
            img = np.full((16, 16, 3), (i * 9) % 255, np.uint8)
            w.write_idx(i, rio.pack_img(rio.IRHeader(0, i, i, 0),
                                        img))
    w.close()
    return rec


def test_module_fit_full_telemetry_stream(tmp_path, monkeypatch):
    """Acceptance path: a CPU Module.fit run under fault injection
    produces a JSONL stream holding the per-step timeline breakdown
    plus non-zero counters from all three prior subsystems, and
    launch.py renders an aggregated final run report from it."""
    jsonl = str(tmp_path / "telemetry.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE", jsonl)
    monkeypatch.setenv("MXTPU_TELEMETRY_INTERVAL", "600")
    monkeypatch.setenv("MXTPU_NONFINITE_POLICY", "skip")
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "grad:nonfinite:2:nan")
    rz.reset_faults()
    # sentinel subsystem: one injected bad step gets skipped
    it, sym = _toy_module_problem()
    mod = mx.mod.Module(sym, context=mx.cpu())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mod.fit(it, num_epoch=2, optimizer="sgd",
                initializer=mx.initializer.Xavier())
    # data-pipeline subsystem: corrupt records quarantined in-budget
    monkeypatch.setenv("MXTPU_MAX_BAD_RECORDS", "5")
    rec_it = mx.image.ImageRecordIter(
        path_imgrec=_make_image_rec(tmp_path, bad={3}),
        data_shape=(3, 16, 16), batch_size=4, preprocess_threads=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in rec_it:
            pass
    # resilience subsystem: one transient failure retried
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise rz.TransientError("transient")
        return "ok"

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert rz.retry_call(
            flaky, policy=rz.RetryPolicy(
                max_retries=2, base_delay=0.001, jitter=0)) == "ok"

    tel.stop_emitter()      # final flush
    lines = [json.loads(s) for s in open(jsonl).read().splitlines()]
    assert lines
    snap = lines[-1]
    hists = snap["histograms"]
    for phase in ("data_wait", "forward_backward", "optimizer",
                  "host_sync"):
        assert hists[f"span_{phase}_seconds"]["count"] >= 8, phase
    counters = snap["counters"]
    assert counters["sentinel_skipped_steps_total"] >= 1
    assert counters["sentinel_bad_steps_total"] >= 1
    assert counters["data_quarantined_records_total"] == 1
    assert counters["retry_attempts_total"] == 1
    assert counters["train_steps_total"] == 8
    assert counters["prefetch_batches_total"] >= 1
    # launch.py renders a final run report from this snapshot
    launch = _load_tool("launch")
    report = launch._format_report({0: snap})
    assert "rank 0: steps=8" in report
    assert "sentinel_skipped_steps_total" in report


@pytest.mark.parametrize("optimizer", ["sgd", "test"])
def test_trainer_step_timeline_fused_and_eager(optimizer,
                                               monkeypatch):
    """Both Trainer update paths — fused in-jit ('sgd') and the
    eager per-param fallback ('test', no functional counterpart) —
    emit the optimizer span, the guard-interval host_sync span, and
    the step counter."""
    monkeypatch.setenv("MXTPU_NONFINITE_POLICY", "skip")
    mx.random.seed(42)
    rs = np.random.RandomState(0)
    data = rs.randn(60, 10).astype("float32")
    labels = rs.randint(0, 3, 60).astype("float32")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    steps, batch = 6, 10
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for step in range(steps):
            lo = (step * batch) % len(data)
            x = nd.array(data[lo:lo + batch])
            y = nd.array(labels[lo:lo + batch])
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(batch)
    fused = trainer._fused_active()
    assert fused == (optimizer == "sgd")
    snap = tel.snapshot()
    assert snap["counters"]["train_steps_total"] == steps
    assert snap["histograms"]["span_optimizer_seconds"][
        "count"] == steps
    # guard interval 1: every step pays exactly one host read
    assert snap["histograms"]["span_host_sync_seconds"][
        "count"] == steps
    assert trainer.guard.checks == steps


def test_transfer_budget_unchanged_with_telemetry_on(monkeypatch,
                                                     tmp_path):
    """Telemetry adds NO device->host reads: with the sentinel at
    interval 4 and telemetry fully armed (registry + emitter), the
    sole transfer point (read_window_bad) still fires exactly once
    per interval — the same count as the telemetry-off baseline in
    test_sentinel.py."""
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE",
                       str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("MXTPU_NONFINITE_POLICY", "skip")
    monkeypatch.setenv("MXTPU_GUARD_INTERVAL", "4")
    reads = []
    orig = opt_mod.read_window_bad
    monkeypatch.setattr(opt_mod, "read_window_bad",
                        lambda g: reads.append(1) or orig(g))
    it, sym = _toy_module_problem()
    mod = mx.mod.Module(sym, context=mx.cpu())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mod.fit(it, num_epoch=2, optimizer="sgd",
                initializer=mx.initializer.Xavier())
    # 8 update steps, interval 4 -> exactly 2 host reads, telemetry on
    assert mod._guard.steps == 8
    assert len(reads) == 2
    assert mod._guard.checks == 2
    snap = tel.snapshot()
    assert snap["histograms"]["span_data_wait_seconds"]["count"] >= 8


# ----------------------------------------------- launch.py aggregation
def _fake_worker_files(tmp_path, snaps):
    files = {}
    for rank, snap in snaps.items():
        p = str(tmp_path / f"hb-0-{rank}")
        with open(p, "w") as f:
            f.write(f"{time.time():.3f}\n")
            f.write(json.dumps(snap) + "\n")
        files[rank] = p
    return files


def test_launch_aggregates_two_worker_heartbeats(tmp_path):
    launch = _load_tool("launch")
    snaps = {
        0: {"ts": 1.0, "rank": 0,
            "counters": {"train_steps_total": 100,
                         "retry_attempts_total": 2},
            "gauges": {"throughput_samples_per_sec": 500.0},
            "histograms": {}},
        1: {"ts": 1.0, "rank": 1,
            "counters": {"train_steps_total": 90,
                         "sentinel_skipped_steps_total": 3},
            "gauges": {"throughput_samples_per_sec": 450.0},
            "histograms": {}},
    }
    files = _fake_worker_files(tmp_path, snaps)
    ts, snap0 = launch._read_heartbeat(files[0])
    assert ts is not None and snap0["counters"][
        "train_steps_total"] == 100
    collected = launch._collect_snapshots(files)
    assert set(collected) == {0, 1}
    agg = launch._aggregate_telemetry(collected)
    assert agg["counters"]["train_steps_total"] == 190
    assert agg["counters"]["retry_attempts_total"] == 2
    assert agg["throughput"] == pytest.approx(950.0)
    assert agg["straggler"] == (1, 90, 100)
    status = launch._format_status(agg)
    assert "steps=190" in status
    assert "950.0 samples/s" in status
    assert "sentinel_skipped_steps_total=3" in status
    assert "straggler: rank 1 at step 90/100" in status
    report = launch._format_report(collected)
    assert "rank 0: steps=100" in report
    assert "rank 1: steps=90" in report
    assert "retry_attempts_total = 2" in report
    # malformed / telemetry-less heartbeat files degrade gracefully
    bare = str(tmp_path / "hb-0-9")
    open(bare, "w").write("123.0\n")
    assert launch._read_heartbeat(bare) == (123.0, None)
    torn = str(tmp_path / "hb-0-8")
    open(torn, "w").write("123.0\n{\"cut")
    assert launch._read_heartbeat(torn)[1] is None
    assert "no worker telemetry" in launch._format_report({})


# ---------------------------------------------------------------- lint
def test_lint_metric_catalog_and_perf_counter_rules(tmp_path):
    lint = _load_lint()
    d = tmp_path / "incubator_mxnet_tpu"
    d.mkdir(parents=True)
    f = d / "somemod.py"
    f.write_text("from . import telemetry\n"
                 "telemetry.counter('train_steps_total').inc()\n"
                 "telemetry.span('data_wait')\n")
    assert lint.check_metric_catalog([f]) == []
    f.write_text("from . import telemetry\n"
                 "telemetry.counter('undocumented_metric_xyz')\n")
    problems = lint.check_metric_catalog([f])
    assert any("undocumented_metric_xyz" in p for p in problems)
    # raw perf_counter section timing is forbidden in instrumented
    # hot-path modules (telemetry.span is the sanctioned tool)
    hot = tmp_path / "incubator_mxnet_tpu" / "module"
    hot.mkdir(parents=True)
    g = hot / "base_module.py"
    g.write_text("import time\n"
                 "def fit(self):\n"
                 "    t0 = time.perf_counter()\n"
                 "    return t0\n")
    problems = lint.check_file(g)
    assert any("perf_counter" in p for p in problems), problems
    g.write_text("import time\n"
                 "def fit(self):\n"
                 "    t0 = time.perf_counter()  # timing-ok: bench\n"
                 "    return t0\n")
    assert not any("perf_counter" in p for p in lint.check_file(g))


# ------------------------------------- the program computes no MFU
def test_the_program_publishes_no_utilisation_of_its_own():
    """Utilisation is the benchmark's to measure, from a device trace
    (docs/observability.md "Perf observatory").  An engine that has
    stepped, a sharded step that has run twice and a Module that has
    updated twice leave rates and counts in the registry and no
    share of a peak."""
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    from incubator_mxnet_tpu.serving import ServingEngine
    mx.random.seed(0)
    rs = np.random.RandomState(0)
    lm = TransformerLM(64, d_model=32, n_layers=2, n_heads=4,
                       max_len=32)
    lm.initialize(mx.init.Xavier())
    lm(mx.nd.array(np.zeros((1, 4), "int32")))
    eng = ServingEngine(lm, max_batch=2, block_size=8, num_blocks=16)
    eng.submit([int(t) for t in rs.randint(1, 64, 5)],
               max_new_tokens=4)
    assert len(list(eng.stream())) == 4

    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd",
        optimizer_params={"learning_rate": 0.01},
        example_args=[mx.nd.zeros((2, 8))])
    x = np.asarray(rs.rand(8, 8), np.float32)
    y = np.asarray(rs.randint(0, 4, (8,)), np.int32)
    for _ in range(2):
        loss = step(x, y)
    assert np.isfinite(float(loss))

    data = mx.sym.Variable("data")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd")
    batch = mx.io.DataBatch(
        data=[nd.array(x)],
        label=[nd.array(y.astype("float32"))])
    for _ in range(2):
        mod.forward_backward(batch)
        mod.update()

    snap = tel.snapshot()
    names = set(snap["counters"]) | set(snap["gauges"]) \
        | set(snap["histograms"])
    assert snap["counters"]["train_steps_total"] >= 2
    assert "serving_batch_occupancy" in names
    assert "span_train_step_seconds" in names
    gone = {f"{side}_{what}" for side in ("serving", "train")
            for what in ("mfu", "mbu", "flops_per_token",
                         "tokens_per_sec")}
    assert not names & gone, sorted(names & gone)
    assert not [n for n in names if n.endswith(("_mfu", "_mbu"))]
