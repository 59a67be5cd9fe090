"""Live introspection plane (ISSUE 20; docs/observability.md
"Introspection plane"): the per-process debugz endpoint (all six ops
over the CRC-framed rpc transport), the online AnomalyWatch (3x
data_wait inflation detected within 20 steps, attributed to the
right component, exactly one episode), the fleet CLI fan-out with a
deliberately SIGSTOPped replica (bounded, never hung), launch.py's
live-over-mtime freshness/snapshot preference, the emitter's atexit
final flush, the Prometheus HELP/TYPE/quantile exposition, live
tracez payloads through stitch_dumps, and the ci/lint.py
debugz-catalog satellites."""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import incubator_mxnet_tpu as mx  # noqa: F401  (package init wiring)
from incubator_mxnet_tpu import debugz, rpc, telemetry, tracing
from incubator_mxnet_tpu import resilience as rz

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    for var in ("MXTPU_DEBUGZ", "MXTPU_DEBUGZ_PORT",
                "MXTPU_DEBUGZ_PORTFILE", "MXTPU_ANOMALY_WINDOW",
                "MXTPU_ANOMALY_THRESHOLD", "MXTPU_ANOMALY_MIN_STEPS",
                "MXTPU_ANOMALY_COOLDOWN", "MXTPU_TELEMETRY_FILE"):
        monkeypatch.delenv(var, raising=False)
    # NOTE: never get_registry().reset() here — rpc.py/router.py
    # cache their Counter objects at import time (the registry's
    # documented process-lifetime contract), so a reset would orphan
    # them for every later test in the session
    debugz.stop()
    telemetry.reset_anomaly_for_tests()
    tracing.get_recorder().clear()
    yield
    debugz.stop()
    telemetry.reset_anomaly_for_tests()
    tracing.get_recorder().clear()


def _counter(name):
    return telemetry.get_registry().counter(name).value


# ------------------------------------------------- anomaly watchdog
BASELINE = {"data_wait": 0.010, "forward_backward": 0.030,
            "optimizer": 0.005, "host_sync": 0.002}


def _jittered(rs, scale=1.0):
    return {k: v * scale * (1.0 + 0.02 * rs.random())
            if k == "data_wait" else v * (1.0 + 0.02 * rs.random())
            for k, v in BASELINE.items()}


def test_anomaly_watch_detects_3x_data_wait_within_20_steps():
    import random
    rs = random.Random(7)
    watch = telemetry.AnomalyWatch(group="t", window=32,
                                   threshold=6.0, min_samples=8,
                                   cooldown=4)
    c0 = _counter("anomaly_detections_total")
    for _ in range(16):                         # calm baseline
        assert watch.observe(_jittered(rs)) is None
    assert _counter("anomaly_detections_total") == c0

    detect_step, episode = None, None
    for step in range(1, 21):                   # inject 3x data_wait
        ep = watch.observe(_jittered(rs, scale=3.0))
        if ep is not None:
            detect_step, episode = step, ep
            break
    assert detect_step is not None and detect_step <= 20
    assert episode["component"] == "data_wait"  # right attribution
    assert episode["episode"] == 1
    # one emission per episode: counter bumped once, one trace event
    assert _counter("anomaly_detections_total") == c0 + 1
    evs = tracing.events("anomaly")
    assert len(evs) == 1 and evs[0]["component"] == "data_wait"
    assert evs[0]["group"] == "t"

    # healthz-facing verdicts while the episode is open
    v = watch.verdicts()
    assert v["anomalous"] and v["episodes"] == 1
    assert v["open"]["component"] == "data_wait"

    # sustained inflation: still exactly one episode, and the shift
    # becomes the new baseline so the episode closes on its own
    for _ in range(64):
        assert watch.observe(_jittered(rs, scale=3.0)) is None
    assert watch.episodes == 1
    assert _counter("anomaly_detections_total") == c0 + 1
    assert not watch.verdicts()["anomalous"]    # hysteresis closed it


def test_anomaly_watch_warmup_and_disabled_paths(monkeypatch):
    watch = telemetry.AnomalyWatch(group="w", window=8,
                                   threshold=6.0, min_samples=50,
                                   cooldown=2)
    for _ in range(20):                 # under min_samples: no score
        assert watch.observe({"data_wait": 0.01}) is None
    assert watch.observe({"data_wait": 10.0}) is None   # still warmup
    assert watch.episodes == 0
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    w2 = telemetry.AnomalyWatch(group="off", window=4, threshold=1.0,
                                min_samples=1, cooldown=1)
    for _ in range(10):
        assert w2.observe({"x": 1.0}) is None
    assert w2.observe({"x": 99.0}) is None      # disabled: inert
    assert w2.episodes == 0


def test_anomaly_watch_registry_and_env_defaults(monkeypatch):
    monkeypatch.setenv("MXTPU_ANOMALY_WINDOW", "17")
    monkeypatch.setenv("MXTPU_ANOMALY_THRESHOLD", "4.5")
    w = telemetry.anomaly_watch("train")
    assert w is telemetry.anomaly_watch("train")    # get-or-create
    assert w.window == 17 and w.threshold == 4.5
    assert telemetry.anomaly_watch("serving") is not w
    verdicts = telemetry.anomaly_verdicts()
    assert set(verdicts) == {"train", "serving"}
    assert not verdicts["train"]["anomalous"]


# ------------------------------------------------- endpoint ops
def _ops_server():
    return debugz.DebugzServer("test").start()


def _call(srv, msg, timeout=5.0):
    reply, _ = rpc.call_once(srv.host, srv.port, msg, timeout=timeout)
    return reply


def test_debugz_varz_statusz_publish_and_providers():
    srv = _ops_server()
    try:
        s0 = _counter("steps_total")
        telemetry.counter("steps_total").inc(3)
        debugz.publish("train", step=7, epoch=1)
        debugz.publish("train", step=8)             # merge, not replace
        unreg = debugz.register_provider("engine", lambda: {"q": 2})
        debugz.register_provider("broken", lambda: 1 / 0)

        varz = _call(srv, {"op": "varz"})
        assert varz["op"] == "varz" and varz["role"] == "test"
        assert varz["telemetry"]["counters"]["steps_total"] == s0 + 3
        assert varz["uptime_s"] >= 0

        status = _call(srv, {"op": "statusz"})["status"]
        assert status["train"] == {"step": 8, "epoch": 1}
        assert status["engine"] == {"q": 2}
        assert "error" in status["broken"]      # one broken source
        unreg()                                 # must not take statusz
        assert "engine" not in _call(srv, {"op": "statusz"})["status"]
    finally:
        srv.close()


def test_debugz_tracez_memz_healthz_and_unknown_op():
    srv = _ops_server()
    try:
        tracing.trace_event("submit", rid="r1")
        tracing.trace_event("finish", rid="r1")
        tracing.trace_event("submit", rid="r2")

        t = _call(srv, {"op": "tracez", "event": "submit"})
        assert [e["rid"] for e in t["events"]] == ["r1", "r2"]
        t = _call(srv, {"op": "tracez", "rid": "r1"})
        assert [e["event"] for e in t["events"]] == ["submit",
                                                     "finish"]
        t = _call(srv, {"op": "tracez", "limit": 1})
        assert len(t["events"]) == 1 and "dropped" in t

        tracing.set_memory_plan(12345, {"params": 12000.0})
        m = _call(srv, {"op": "memz"})
        assert m["plan"]["predicted_bytes"] == 12345
        assert "memory" in m

        h = _call(srv, {"op": "healthz"})
        assert h["ok"] and not h["anomalous"]
        assert "heartbeat_age_s" in h and "anomaly" in h

        bad = _call(srv, {"op": "nope"})
        assert bad["op"] == "error" and "unknown" in bad["error"]
        assert bad["ops"] == list(debugz.OPS)
    finally:
        srv.close()


def test_debugz_profilez_bounded_dump():
    srv = _ops_server()
    try:
        p = _call(srv, {"op": "profilez", "seconds": 0.05},
                  timeout=10.0)
        assert p["op"] == "profilez" and p["seconds"] <= 0.05
        dump = json.loads(p["profile"])
        assert "traceEvents" in dump
    finally:
        srv.close()


def test_maybe_start_gating_portfile_and_idempotence(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("MXTPU_DEBUGZ", "0")
    assert debugz.maybe_start("test") is None       # gated off
    assert debugz.server() is None

    pf = tmp_path / "dz.port"
    monkeypatch.setenv("MXTPU_DEBUGZ", "1")
    monkeypatch.setenv("MXTPU_DEBUGZ_PORTFILE", str(pf))
    srv = debugz.maybe_start("test")
    assert srv is not None
    assert debugz.maybe_start("test") is srv        # idempotent
    assert debugz.port() == srv.port
    host, port = pf.read_text().strip().rsplit(":", 1)
    assert int(port) == srv.port                    # atomic handshake
    reply, _ = rpc.call_once(host, int(port), {"op": "healthz"})
    assert reply["ok"]
    debugz.stop()
    assert debugz.server() is None


def test_rpc_call_once_and_heartbeat_age(tmp_path, monkeypatch):
    # as in a process that has not beaten yet: another file's
    # heartbeat in the same worker leaves its last beat behind
    monkeypatch.setitem(rz._HB_STATE, "last_beat", None)
    assert rz.heartbeat_age() is None               # no beat yet
    rz.start_heartbeat(path=str(tmp_path / "hb"), interval=0.05)
    try:
        deadline = time.monotonic() + 10
        while rz.heartbeat_age() is None:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert rz.heartbeat_age() < 5.0
    finally:
        rz.stop_heartbeat()
    # call_once against a dead port: bounded failure, not a hang
    t0 = time.monotonic()
    with pytest.raises(rpc.RpcError):
        rpc.call_once("127.0.0.1", 1, {"op": "healthz"}, timeout=1.0)
    assert time.monotonic() - t0 < 5.0


# ------------------------------------------------- stitcher (live)
def test_stitch_dumps_accepts_live_tracez_payloads(tmp_path):
    dump = tmp_path / "rank0.jsonl"
    dump.write_text(
        json.dumps({"reason": "test"}) + "\n"
        + json.dumps({"event": "a", "ts": 1.0, "seq": 0}) + "\n")
    live = {"op": "tracez", "rank": 7,
            "events": [{"event": "b", "ts": 2.0, "seq": 0},
                       {"event": "c", "ts": 0.5, "seq": 1}]}
    merged = tracing.stitch_dumps([str(dump), live,
                                   [{"event": "d", "ts": 3.0}]])
    assert [e["event"] for e in merged] == ["c", "a", "b", "d"]
    srcs = {e["event"]: e["src"] for e in merged}
    assert srcs["b"] == "live:rank7" and srcs["c"] == "live:rank7"
    assert srcs["d"] == "live" and srcs["a"].endswith("rank0.jsonl")
    role_live = {"role": "router", "events": [{"event": "e",
                                               "ts": 9.0}]}
    assert tracing.stitch_dumps([role_live])[0]["src"] == \
        "live:router"


# ------------------------------------------------- emitter / prom
def test_emitter_atexit_final_flush(tmp_path):
    out = tmp_path / "tele.jsonl"
    code = (
        "from incubator_mxnet_tpu import telemetry\n"
        # huge interval: only the atexit final flush can write
        f"e = telemetry.TelemetryEmitter(path={str(out)!r}, "
        "interval=3600)\n"
        "e.start()\n"
        "telemetry.counter('steps_total').inc(5)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_TELEMETRY="1")
    env.pop("MXTPU_TELEMETRY_FILE", None)
    subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                   check=True, timeout=180)
    lines = out.read_text().splitlines()
    assert lines                    # short-lived run still flushed
    last = json.loads(lines[-1])    # ...and the record is complete
    assert last["counters"]["steps_total"] == 5
    prom = (str(out) + ".prom")
    assert os.path.exists(prom)     # textfile replaced atomically too


def test_prometheus_text_help_type_and_quantiles():
    telemetry.counter("anomaly_detections_total").inc(0)
    telemetry.gauge("engine_queue_depth").set(3)
    # a name unique to this test so the quantiles are exactly ours
    # even in a full-suite run (histograms persist for the process)
    h = telemetry.histogram("debugz_probe_seconds")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    text = telemetry.prometheus_text()
    assert "# TYPE mxtpu_anomaly_detections_total counter" in text
    assert "# TYPE mxtpu_engine_queue_depth gauge" in text
    assert "# TYPE mxtpu_debugz_probe_seconds summary" in text
    # catalogued metrics carry HELP from docs/observability.md
    assert any(line.startswith(
        "# HELP mxtpu_anomaly_detections_total ")
        for line in text.splitlines())
    # quantile gauges derived from the histogram window
    assert "# TYPE mxtpu_debugz_probe_seconds_p50 gauge" in text
    assert "mxtpu_debugz_probe_seconds_p50 0.2" in text
    assert "# TYPE mxtpu_debugz_probe_seconds_p99 gauge" in text
    assert "mxtpu_debugz_probe_seconds_count 4" in text


# ------------------------------------------------- launch.py helpers
def _load_launch():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "launch", os.path.join(REPO, "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)
    return launch


def test_launch_prefers_live_snapshots_with_file_fallback(
        tmp_path, monkeypatch):
    launch = _load_launch()
    hb0 = str(tmp_path / "hb-0")
    hb1 = str(tmp_path / "hb-1")
    assert launch._dz_portfile(hb0) == hb0 + ".debugz"
    file_snap = {"counters": {"file_only": 1.0}}
    for hb in (hb0, hb1):
        with open(hb, "w") as f:
            f.write("123.0\n" + json.dumps(file_snap) + "\n")

    telemetry.counter("live_marker").inc(7)
    srv = debugz.DebugzServer("train").start()
    try:
        with open(launch._dz_portfile(hb0), "w") as f:
            f.write(f"{srv.host}:{srv.port}\n")
        snaps = launch._collect_snapshots({0: hb0, 1: hb1})
        # rank 0 live (current counters), rank 1 heartbeat ride-along
        assert snaps[0]["counters"]["live_marker"] == 7
        assert "file_only" not in snaps[0]["counters"]
        assert snaps[1]["counters"]["file_only"] == 1.0
        assert launch._live_fresh(hb0)
    finally:
        srv.close()
    # dead endpoint: bounded False, callers fall back to mtimes
    with open(launch._dz_portfile(hb1), "w") as f:
        f.write("127.0.0.1:1\n")
    t0 = time.monotonic()
    assert not launch._live_fresh(hb1)
    assert time.monotonic() - t0 < 5.0


# ------------------------------------------------- fleet e2e (procs)
def _spawn_replica_proc(tmp_path, idx):
    port_file = tmp_path / f"port{idx}"
    dz_file = tmp_path / f"hb{idx}.debugz"
    log = open(tmp_path / f"replica{idx}.log", "wb")
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_DEBUGZ="1",
               MXTPU_DEBUGZ_PORTFILE=str(dz_file))
    env.pop("MXTPU_FAULT_SPEC", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.serving.replica",
         "--port-file", str(port_file), "--name", f"dz{idx}",
         "--max-batch", "2", "--block-size", "4",
         "--num-blocks", "64", "--prefix-cache", "0"],
        cwd=REPO, env=env, stdout=log, stderr=log)
    return proc, dz_file, log


def _wait_files(files, timeout=180):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(f.exists() for f in files):
            return
        time.sleep(0.1)
    raise AssertionError("debugz port files never appeared")


def test_fleet_fanout_with_sigstopped_replica(tmp_path):
    """Acceptance: a SIGSTOPped rank cannot hang the fan-out CLI or
    launch.py's liveness probe; the healthy rank's payload arrives
    complete and the hung one is reported within the deadline."""
    procs = [_spawn_replica_proc(tmp_path, i) for i in range(2)]
    try:
        _wait_files([dz for _, dz, _ in procs])
        # sanity: both endpoints answer before the wedge
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "debugz.py"),
             str(procs[0][1]), str(procs[1][1]),
             "--op", "healthz", "--deadline", "5"],
            cwd=REPO, capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stdout + out.stderr
        assert all(r.get("ok") for r in
                   json.loads(out.stdout).values())

        os.kill(procs[0][0].pid, signal.SIGSTOP)    # wedge rank 0
        time.sleep(0.2)
        t0 = time.monotonic()
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "debugz.py"),
             str(procs[0][1]), str(procs[1][1]),
             "--op", "statusz", "--deadline", "2"],
            cwd=REPO, capture_output=True, text=True, timeout=60)
        elapsed = time.monotonic() - t0
        assert elapsed < 15.0           # bounded, never hung
        assert out.returncode == 1      # ...and the wedge is reported
        replies = json.loads(out.stdout)
        assert len(replies) == 2
        errs = [r for r in replies.values()
                if "error" in r and "op" not in r]
        good = [r for r in replies.values() if r.get("op") ==
                "statusz"]
        assert len(errs) == 1 and len(good) == 1
        assert good[0]["role"] == "replica"     # healthy payload whole
        assert "engine" in good[0]["status"]

        # launch.py's probe: live rank fresh, wedged rank bounded-dead
        launch = _load_launch()
        hb_live = str(procs[1][1])[:-len(".debugz")]
        hb_hung = str(procs[0][1])[:-len(".debugz")]
        assert launch._live_fresh(hb_live)
        t0 = time.monotonic()
        assert not launch._live_fresh(hb_hung, deadline=1.0)
        assert time.monotonic() - t0 < 5.0
    finally:
        for proc, _, log in procs:
            try:
                os.kill(proc.pid, signal.SIGCONT)
            except OSError:
                pass
            proc.terminate()
        for proc, _, log in procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
            log.close()


# ------------------------------------------------- lint satellites
def _load_lint():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lint", os.path.join(REPO, "ci", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    return lint


def test_lint_debugz_catalog_and_socket_rules(tmp_path, monkeypatch):
    monkeypatch.chdir(REPO)     # catalog checks read docs/ relatively
    lint = _load_lint()
    d = tmp_path / "incubator_mxnet_tpu"
    d.mkdir(parents=True)
    f = d / "debugz.py"
    # documented ops pass; an undocumented op is flagged
    f.write_text('OPS = ("varz", "healthz")\n')
    assert not lint.check_debugz_catalog([f])
    f.write_text('OPS = ("varz", "coffeez")\n')
    probs = lint.check_debugz_catalog([f])
    assert any("coffeez" in p for p in probs)
    # anomaly metric/event names must stay catalogued too
    assert not any("anomaly" in p for p in probs)
    # unbounded socket waits inside a debugz module are flagged
    f.write_text('OPS = ("varz",)\nimport socket\n'
                 "s = socket.socket()\nc = s.recv(4)\n")
    assert any("recv" in p for p in lint.check_file(f))
    # ...and a deadline-ok annotation clears it
    f.write_text('OPS = ("varz",)\nimport socket\n'
                 "s = socket.socket()\n"
                 "c = s.recv(4)  # deadline-ok: settimeout armed\n")
    assert not any("recv" in p for p in lint.check_file(f))
    # the real repo files pass the full catalog check
    from pathlib import Path
    real = [Path("incubator_mxnet_tpu/debugz.py"),
            Path("tools/debugz.py")]
    assert not lint.check_debugz_catalog(real)
