"""Fault-tolerant serving fleet (ISSUE 16; docs/serving.md "Fleet"):
CRC-framed RPC with poisoned-connection recovery, full-jitter
reconnect backoff, the circuit breaker's exactly-one-half-open-probe
contract, fleet admission, prefix-affinity routing, failover
re-dispatch with token-identical continuations, exactly-one-terminal
fleet-wide under router:replica kill + router:net garble chaos,
SIGTERM fleet drain with restorable per-replica snapshots, the
cross-process flight-recorder stitcher, and the launch.py
--serve-fleet / ci/lint.py socket-wait satellites."""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import resilience as rz
from incubator_mxnet_tpu import rpc as transport
from incubator_mxnet_tpu import tracing
from incubator_mxnet_tpu.gluon.model_zoo.transformer import (
    TransformerLM)
from incubator_mxnet_tpu.serving import (
    EXPIRED, FINISHED, ServeRejectedError, ServingEngine)
from incubator_mxnet_tpu.serving import replica as replica_mod
from incubator_mxnet_tpu.serving import router as router_mod
from incubator_mxnet_tpu.serving import rpc
from incubator_mxnet_tpu.serving.router import ServingRouter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 37


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("MXTPU_FAULT_SPEC", raising=False)
    rz.reset_faults()
    yield
    rz.reset_faults()


def _tiny(vocab=VOCAB, **kw):
    cfg = dict(d_model=32, n_layers=2, n_heads=4, max_len=64)
    cfg.update(kw)
    mx.random.seed(0)
    net = TransformerLM(vocab, **cfg)
    net.initialize(mx.initializer.Xavier())
    return net


_NET = None


def _shared_net():
    """One deterministic tiny LM for the module (seeded init; engines
    never mutate the model) — the same weights every replica process
    builds, which is what makes re-dispatch token-identical."""
    global _NET
    if _NET is None:
        _NET = _tiny()
    return _NET


def _gen_ref(net, prompt, max_new):
    out = net.generate(
        mx.nd.array(np.asarray([prompt], np.int32)), max_new)
    return [int(t) for t in out.asnumpy()[0]]


# The counters the code increments.  rpc.py and router.py hold theirs
# from import on, so after another file's ``get_registry().reset()`` in
# the same worker the registry would hand out a fresh one of the same
# name that nothing counts into.
_HELD = {"rpc_frame_errors_total": transport._m_frame_errors,
         "router_rejected_total": router_mod._m_rejected,
         "router_redispatches_total": router_mod._m_redispatch}


def _counter(name):
    return _HELD[name].value


def _start_replica(name, **engine_kw):
    """One in-process replica on an ephemeral port, engine loop on a
    daemon thread (kill-kind faults need the subprocess variant)."""
    srv = replica_mod.ReplicaServer(_shared_net(), name=name,
                                    port=0, **engine_kw)
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name=f"replica-loop-{name}")
    t.start()
    return srv, t


# ---------------------------------------------------- backoff policy
def test_full_jitter_backoff_distribution():
    base, cap = 1.0, 8.0
    caps = [min(base * 2 ** i, cap) for i in range(6)]

    def mk(seed):
        return rz.RetryPolicy(max_retries=6, base_delay=base,
                              max_delay=cap, jitter=True, seed=seed)

    assert mk(7).delays() == mk(7).delays()     # seeded: reproducible
    for seed in range(40):
        for d, c in zip(mk(seed).delays(), caps):
            assert 0.0 <= d <= c        # full window, never beyond it
    # genuinely *full* jitter: across seeds the capped tail delays
    # land in both halves of [0, cap] (fractional jitter never gets
    # below the base — that tight wave is the thundering herd)
    tails = [mk(seed).delays()[-1] for seed in range(40)]
    assert min(tails) < cap / 4
    assert max(tails) > cap / 2
    # legacy fractional jitter is unchanged: widens, never shrinks
    p = rz.RetryPolicy(max_retries=4, base_delay=1.0, max_delay=8.0,
                       jitter=0.5, seed=7)
    for b, d in zip([1.0, 2.0, 4.0, 8.0], p.delays()):
        assert b <= d <= b * 1.5


# ------------------------------------------------------- rpc framing
def test_garbled_crc_frame_drops_connection_not_later_requests():
    got = []

    def handler(msg, conn, budget):
        got.append((msg, budget))
        return {"op": "echo", "x": msg.get("x")}

    srv = rpc.RpcServer(handler, name="t-echo").start()
    try:
        err0 = _counter("rpc_frame_errors_total")
        cli = rpc.RpcClient("127.0.0.1", srv.port).connect()
        reply, _ = cli.call({"op": "echo", "x": 1}, budget=12.5)
        assert reply == {"op": "echo", "x": 1}
        assert got[0][1] == 12.5    # deadline budget crossed the wire
        # garble a frame below the client API: CRC over the clean
        # payload, then one byte flipped on the wire — exactly what
        # the router:net corrupt injection produces
        header, payload = rpc.encode_frame({"op": "echo", "x": 2})
        cli._sock.sendall(header + bytes([payload[0] ^ 0xFF])
                          + payload[1:])
        # the server rejects the frame, counts it, and drops THIS
        # connection (poisoned framing); the client sees EOF, not an
        # idle timeout
        with pytest.raises(rpc.RpcError):
            cli.recv(timeout=10.0)
        assert not cli.connected
        assert _counter("rpc_frame_errors_total") - err0 == 1
        # a reconnect talks to the same server unpoisoned
        cli.connect_retry()
        reply, _ = cli.call({"op": "echo", "x": 3})
        assert reply == {"op": "echo", "x": 3}
        # the garbled frame was never delivered upward
        assert [m.get("x") for m, _ in got] == [1, 3]
    finally:
        srv.close()


def test_injected_net_corrupt_poisons_exactly_nth_frame(monkeypatch):
    seen = []
    srv = rpc.RpcServer(lambda m, c, b: seen.append(m),
                        name="t-sink").start()
    try:
        cli = rpc.RpcClient("127.0.0.1", srv.port).connect()
        monkeypatch.setenv("MXTPU_FAULT_SPEC", "router:net:2:corrupt")
        rz.reset_faults()
        cli.send({"op": "a"})
        cli.send({"op": "b"})       # the garbled one
        # the receiver dropped the connection; our next send fails
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                cli.send({"op": "probe"})
                time.sleep(0.02)
            except rpc.RpcError:
                break
        assert not cli.connected
        cli.connect_retry()
        cli.send({"op": "c"})
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                [m["op"] for m in seen if m["op"] == "c"] == []:
            time.sleep(0.02)
        ops = [m["op"] for m in seen]
        assert "a" in ops and "c" in ops and "b" not in ops
    finally:
        srv.close()


# --------------------------------------------------- circuit breaker
def test_breaker_half_open_admits_exactly_one_probe():
    b = router_mod._Breaker(threshold=2, cooldown=5.0)
    now = 100.0
    assert b.allow(now)
    assert b.fail(now) is False and b.state == "closed"
    assert b.fail(now) is True and b.state == "open"  # newly opened
    assert not b.allow(now + 4.9)
    assert b.allow(now + 5.0)       # cooldown over -> half_open
    assert b.state == "half_open" and b.probe_rid is None
    b.probe_rid = 42                # the dispatch path stamps it
    assert not b.allow(now + 5.1)   # EXACTLY one probe in flight
    assert not b.allow(now + 5.2)
    assert b.fail(now + 5.3) is True        # probe failed: re-open
    assert b.state == "open" and b.probe_rid is None
    assert not b.allow(now + 5.4)
    assert b.allow(now + 10.4)      # next cooldown, next single probe
    b.probe_rid = 43
    b.ok()                          # probe succeeded
    assert b.state == "closed" and b.probe_rid is None
    assert b.allow(now + 10.5) and b.allow(now + 10.6)  # no gate


def test_breaker_trips_probes_reopen_then_recover(monkeypatch):
    rep, t = _start_replica("b0", max_batch=1, block_size=4,
                            num_blocks=64, prefix_cache=False)
    tracing.get_recorder().clear()
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "router:replica:*:error")
    rz.reset_faults()
    router = ServingRouter(replicas=[("127.0.0.1", rep.port)],
                           breaker_threshold=1, breaker_cooldown=0.2,
                           poll_interval=0.02).connect()
    try:
        link = router._links["replica0"]
        req = router.submit([1, 2, 3], 4, deadline=300.0)
        deadline = time.monotonic() + 60
        # every dispatch nacks: one failure trips the breaker open
        while time.monotonic() < deadline \
                and link.breaker.state != "open":
            router.poll()
            time.sleep(0.02)
        assert link.breaker.state == "open"
        # each cooldown admits one half-open probe; the probe nacks
        # and the breaker re-opens (trace: half_open then reopened)
        while time.monotonic() < deadline and not \
                tracing.events("router_breaker", state="reopened"):
            router.poll()
            time.sleep(0.02)
        assert tracing.events("router_breaker", state="half_open")
        assert tracing.events("router_breaker", state="reopened")
        # heal the replica: the next probe's tokens close the breaker
        # and the parked request finishes — never silently lost
        monkeypatch.setenv("MXTPU_FAULT_SPEC", "")
        rz.reset_faults()
        assert router.wait([req], timeout=120.0)
        assert req.state == FINISHED
        assert req.tokens == _gen_ref(_shared_net(), [1, 2, 3], 4)
        assert link.breaker.state == "closed"
        assert tracing.events("router_breaker", state="closed")
    finally:
        router.close()
        rep.close()
        t.join(timeout=10)


# ------------------------------------------------ admission + net
def test_fleet_admission_sheds_typed_and_drain_rejects():
    router = ServingRouter(replicas=[], queue_limit=2,
                           queue_tokens=50, poll_interval=0.01)
    rej0 = _counter("router_rejected_total")
    router.submit([1, 2, 3], 2)
    with pytest.raises(ServeRejectedError, match="queue_tokens"):
        router.submit(list(range(48)), 2)
    router.submit([4, 5], 2)
    with pytest.raises(ServeRejectedError, match="queue_limit"):
        router.submit([6], 2)
    assert _counter("router_rejected_total") - rej0 == 2
    router.drain(wait=False)
    with pytest.raises(ServeRejectedError, match="draining"):
        router.submit([7], 2)
    router.close()


def test_deadline_net_expires_unserviceable_request():
    """A request whose owner can never deliver a terminal (here: no
    replica at all) must still end in exactly one terminal state —
    the router's deadline net expires it locally."""
    router = ServingRouter(replicas=[], poll_interval=0.01,
                           expiry_grace=0.05)
    req = router.submit([1, 2, 3], 4, deadline=0.1)
    assert router.wait([req], timeout=30.0)
    assert req.state == EXPIRED
    assert "router net" in req.error
    assert req.id in router._terminal_ids
    assert not router._pending            # not parked after terminal
    router.close()


def test_prefix_affinity_prefers_prior_replica():
    router = ServingRouter(
        replicas=["127.0.0.1:9", "127.0.0.1:10"], block_size=4)
    now = time.monotonic()
    for link in router._links.values():
        link.alive = True
        link.last_heard = now
    req = router_mod.FleetRequest(0, list(range(12)), 4)
    # no affinity yet: least-queued wins
    assert router._pick(req).name == "replica0"
    router._remember_affinity(req, router._links["replica1"])
    assert router._pick(req).name == "replica1"     # cache affinity
    router._links["replica1"].inflight = {1, 2, 3}
    assert router._pick(req).name == "replica1"     # beats load
    router._links["replica1"].alive = False         # unusable: fall
    assert router._pick(req).name == "replica0"     # back to load
    router.close()


# ------------------------------------------------- in-process fleet
def test_fleet_finishes_token_identical_no_leaks():
    net = _shared_net()
    rs = np.random.RandomState(60)
    prompts = [list(rs.randint(0, VOCAB, int(rs.randint(3, 12))))
               for _ in range(6)]
    refs = [_gen_ref(net, p, 6) for p in prompts]
    reps = [_start_replica(f"f{i}", max_batch=2, block_size=4,
                           num_blocks=64, prefix_cache=False)
            for i in range(2)]
    tracing.get_recorder().clear()
    router = ServingRouter(
        replicas=[("127.0.0.1", r.port) for r, _ in reps],
        poll_interval=0.01).connect()
    try:
        reqs = [router.submit(p, 6, deadline=300.0) for p in prompts]
        assert router.wait(reqs, timeout=300.0)
        for req, ref in zip(reqs, refs):
            assert req.state == FINISHED
            assert req.tokens == ref        # fleet == single engine
            assert len(tracing.events("router_terminal",
                                      rid=req.id)) == 1
        assert {r.link for r in reqs} == {"replica0", "replica1"}
        # per-replica block-pool audit over the stats RPC
        for name in ("replica0", "replica1"):
            st = router.replica_stats(name)
            assert st["num_allocated"] == 0
            assert st["pool_live"] == {}
        drained = router.drain(wait=True, timeout=60.0)
        assert drained == {"replica0", "replica1"}
    finally:
        router.close()
        for r, t in reps:
            r.close()
            t.join(timeout=10)


def test_replica_death_redispatches_token_identical():
    net = _shared_net()
    rs = np.random.RandomState(61)
    prompts = [list(rs.randint(0, VOCAB, int(rs.randint(3, 10))))
               for _ in range(4)]
    refs = [_gen_ref(net, p, 10) for p in prompts]
    reps = [_start_replica(f"d{i}", max_batch=2, block_size=4,
                           num_blocks=64, prefix_cache=False)
            for i in range(2)]
    tracing.get_recorder().clear()
    router = ServingRouter(
        replicas=[("127.0.0.1", r.port) for r, _ in reps],
        poll_interval=0.01).connect()
    try:
        reqs = [router.submit(p, 10, deadline=300.0)
                for p in prompts]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not \
                any(r.generated for r in reqs):
            router.poll()
            time.sleep(0.01)
        assert any(r.generated for r in reqs)
        victim = reps[0][0]
        owned = [r for r in reqs if r.link == "replica0"
                 and not r.done]
        assert owned            # least-queued routing spread the load
        victim.close()          # ungraceful: sockets die mid-stream
        assert router.wait(reqs, timeout=300.0)
        for req, ref in zip(reqs, refs):
            assert req.state == FINISHED
            assert req.tokens == ref    # greedy recompute: identical
            assert len(tracing.events("router_terminal",
                                      rid=req.id)) == 1
        moved = [r for r in owned if r.redispatches > 0]
        assert moved            # the victim's in-flight work re-homed
        assert tracing.events("router_redispatch")
        assert all(r.link == "replica1" for r in moved)
    finally:
        router.close()
        for r, t in reps:
            r.close()
            t.join(timeout=10)


def test_router_sigterm_drains_fleet_snapshots_restorable(tmp_path):
    net = _shared_net()
    rs = np.random.RandomState(62)
    prompts = [list(rs.randint(0, VOCAB, int(rs.randint(3, 10))))
               for _ in range(4)]
    refs = [_gen_ref(net, p, 10) for p in prompts]
    # max_batch=1: each replica gets one running + one queued request,
    # so the drain snapshots carry genuinely unfinished work
    reps = [_start_replica(f"s{i}", max_batch=1, block_size=4,
                           num_blocks=64, prefix_cache=False)
            for i in range(2)]
    router = ServingRouter(
        replicas=[("127.0.0.1", r.port) for r, _ in reps],
        poll_interval=0.01).connect()
    prev = signal.getsignal(signal.SIGTERM)
    try:
        reqs = [router.submit(p, 10, deadline=300.0)
                for p in prompts]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not \
                any(r.generated for r in reqs):
            router.poll()
            time.sleep(0.01)
        # SIGTERM only latches (no socket work in the handler); the
        # next poll performs the drain
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        assert router.install_sigterm(snapshot_dir=str(tmp_path))
        signal.raise_signal(signal.SIGTERM)
        assert router._drain_requested
        router.poll()
        assert router._draining
        with pytest.raises(ServeRejectedError, match="draining"):
            router.submit([1, 2, 3], 2)
        drained = router.drain(wait=True, timeout=120.0)
        assert drained == {"replica0", "replica1"}
        # every replica snapshotted; restoring each into a fresh
        # engine completes its requests token-identically — the
        # shrink/grow fleet restart story
        restored = {}
        for name in sorted(drained):
            snap = tmp_path / f"{name}.snap"
            assert snap.exists()
            eng = ServingEngine.restore(net, str(snap), max_batch=1,
                                        block_size=4, num_blocks=64,
                                        prefix_cache=False)
            restored.update(eng.run())
            assert eng.pool.num_allocated == 0
        for req, ref in zip(reqs, refs):
            # running requests finished live (drain completes the
            # running batch) AND restore from their snapshot copy is
            # token-identical; queued ones live on only in snapshots
            if req.done:
                assert req.state == FINISHED and req.tokens == ref
            else:
                assert req.id in restored
            if req.id in restored:
                assert restored[req.id] == ref
        assert any(not r.done for r in reqs)    # drain left work
    finally:
        signal.signal(signal.SIGTERM, prev)
        router.close()
        for r, t in reps:
            r.close()
            t.join(timeout=10)


# ------------------------------------------ chaos acceptance (procs)
def _spawn_replica_proc(tmp_path, idx, fault_spec=""):
    port_file = tmp_path / f"port{idx}"
    log = open(tmp_path / f"replica{idx}.log", "wb")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_FAULT_SPEC", None)
    if fault_spec:
        env["MXTPU_FAULT_SPEC"] = fault_spec
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.serving.replica",
         "--port-file", str(port_file), "--name", f"chaos{idx}",
         "--max-batch", "2", "--block-size", "4",
         "--num-blocks", "64", "--prefix-cache", "0"],
        cwd=REPO, env=env, stdout=log, stderr=log)
    return proc, port_file, log


def _wait_ports(port_files, timeout=180):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(f.exists() for f in port_files):
            return [int(f.read_text()) for f in port_files]
        time.sleep(0.1)
    raise AssertionError("replica subprocesses never came up")


def test_chaos_kill_and_net_garble_exactly_one_terminal(
        tmp_path, monkeypatch):
    """Acceptance: a 3-replica fleet under a seeded router:replica
    kill (one replica hard-dies mid-stream) plus router:net frame
    garbling + delay on the router's own send path.  Every admitted
    request must end in exactly one terminal state fleet-wide,
    re-dispatched outputs must be token-identical to an unkilled
    single-engine run, surviving replicas must leak zero blocks, and
    the drain snapshots must restore into fresh engines."""
    net = _shared_net()
    rs = np.random.RandomState(77)
    prompts = [list(rs.randint(0, VOCAB, int(rs.randint(3, 12))))
               for _ in range(6)]
    refs = [_gen_ref(net, p, 8) for p in prompts]
    # replica 0 hard-dies (os._exit) serving its 2nd dispatch
    specs = ["router:replica:2:kill", "", ""]
    procs = [_spawn_replica_proc(tmp_path, i, spec)
             for i, spec in enumerate(specs)]
    try:
        ports = _wait_ports([pf for _, pf, _ in procs])
        tracing.get_recorder().clear()
        # the router's own frame path: garble the 3rd frame it sends
        # (CRC rejection drops that link) and delay the 9th
        monkeypatch.setenv("MXTPU_FAULT_SPEC",
                           "router:net:3:corrupt,router:net:9:hang")
        monkeypatch.setenv("MXTPU_FAULT_HANG_S", "0.2")
        rz.reset_faults()
        red0 = _counter("router_redispatches_total")
        router = ServingRouter(
            replicas=[("127.0.0.1", p) for p in ports],
            poll_interval=0.02, stale_after=5.0).connect()
        try:
            reqs = [router.submit(p, 8, deadline=300.0)
                    for p in prompts]
            assert router.wait(reqs, timeout=300.0)
            for req, ref in zip(reqs, refs):
                assert req.state == FINISHED, (req.id, req.error)
                assert req.tokens == ref    # token-identical failover
                assert len(tracing.events("router_terminal",
                                          rid=req.id)) == 1
            # the killed replica really died, and its work re-homed
            assert procs[0][0].wait(timeout=60) == 1
            assert sum(r.redispatches for r in reqs) >= 1
            assert _counter("router_redispatches_total") > red0
            assert tracing.events("router_redispatch")
            # survivors leak zero blocks (per-replica RPC audit)
            for name in ("replica1", "replica2"):
                st = router.replica_stats(name)
                assert st["num_allocated"] == 0
                assert st["pool_live"] == {}
            # phase 2: drain mid-stream; survivors snapshot, exit 0,
            # and the snapshots restore token-identically
            prompts2 = [list(rs.randint(0, VOCAB,
                                        int(rs.randint(3, 10))))
                        for _ in range(4)]
            refs2 = [_gen_ref(net, p, 10) for p in prompts2]
            reqs2 = [router.submit(p, 10, deadline=300.0)
                     for p in prompts2]
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and not \
                    any(r.generated for r in reqs2):
                router.poll()
                time.sleep(0.02)
            snapdir = tmp_path / "snaps"
            snapdir.mkdir()
            drained = router.drain(wait=True, timeout=120.0,
                                   snapshot_dir=str(snapdir))
            assert drained == {"replica1", "replica2"}
            for proc, _, _ in procs[1:]:
                assert proc.wait(timeout=120) == 0  # drained cleanly
            restored = {}
            for name in sorted(drained):
                snap = snapdir / f"{name}.snap"
                assert snap.exists()
                eng = ServingEngine.restore(
                    net, str(snap), max_batch=2, block_size=4,
                    num_blocks=64, prefix_cache=False)
                restored.update(eng.run())
                assert eng.pool.num_allocated == 0
            for req, ref in zip(reqs2, refs2):
                if req.done:
                    assert req.state == FINISHED
                    assert req.tokens == ref
                else:
                    assert req.id in restored   # never silently lost
                if req.id in restored:
                    assert restored[req.id] == ref
        finally:
            router.close()
    finally:
        for proc, _, log in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
            log.close()


# --------------------------------------------- cross-process stitch
def test_stitch_dumps_merges_fleet_timeline(tmp_path):
    a = tmp_path / "flight.rank0.jsonl"
    b = tmp_path / "flight.rank1.jsonl"
    a.write_text("\n".join([
        json.dumps({"flight_recorder": 1, "rank": 0}),
        json.dumps({"event": "router_dispatch", "rid": 1,
                    "replica": "r0", "ts": 1.0, "seq": 0}),
        json.dumps({"event": "router_terminal", "rid": 1,
                    "replica": "r0", "ts": 4.0, "seq": 1}),
    ]) + "\n")
    b.write_text("\n".join([
        json.dumps({"flight_recorder": 1, "rank": 1}),
        "torn non-json line",
        json.dumps({"event": "fleet_dispatch", "rid": 1,
                    "replica": "r0", "ts": 2.0, "seq": 0}),
        json.dumps({"event": "fleet_terminal", "rid": 1,
                    "replica": "r0", "ts": 3.0, "seq": 1}),
        json.dumps({"event": "fleet_dispatch", "rid": 2,
                    "replica": "r0", "ts": 2.5, "seq": 2}),
    ]) + "\n")
    # rid filter reads one request's hops across both processes in
    # wall-clock order; missing files (a killed replica never dumps)
    # and torn lines are skipped
    evs = tracing.stitch_dumps(
        [str(a), str(b), str(tmp_path / "missing.jsonl")], rid=1)
    assert [e["event"] for e in evs] == [
        "router_dispatch", "fleet_dispatch", "fleet_terminal",
        "router_terminal"]
    assert evs[0]["src"] == "flight.rank0.jsonl"
    assert evs[1]["src"] == "flight.rank1.jsonl"
    assert len(tracing.stitch_dumps([str(a), str(b)])) == 5


# ------------------------------------------------ launch.py helpers
def _load_launch():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "launch", os.path.join(REPO, "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)
    return launch


def test_fleet_env_and_status_helpers():
    launch = _load_launch()

    class Args:
        env = ["FOO=bar"]

    env = launch._fleet_env(Args(), "replica", 1, 7000, [7001, 7002])
    assert env["MXTPU_FLEET_ROLE"] == "replica"
    assert env["MXTPU_FLEET_REPLICAS"] == "2"
    assert env["MXTPU_REPLICA_PORT"] == "7002"
    assert env["MXTPU_REPLICA_ADDRS"] == \
        "127.0.0.1:7001,127.0.0.1:7002"
    assert env["MXTPU_WORKER_RANK"] == "1"
    assert env["FOO"] == "bar"
    renv = launch._fleet_env(Args(), "router", 0, 7000, [7001, 7002])
    assert renv["MXTPU_FLEET_ROLE"] == "router"
    assert renv["MXTPU_ROUTER_PORT"] == "7000"
    assert "MXTPU_REPLICA_PORT" not in renv
    with pytest.raises(ValueError, match="KEY=VALUE"):
        bad = Args()
        bad.env = ["NOVALUE"]
        launch._fleet_env(bad, "router", 0, 7000, [7001])
    # status line: health ratio + request rate from counter deltas
    rate_state = {"ts": None, "total": 0}
    snaps = {0: {"counters": {"serving_requests_total": 10}}}
    line = launch._fleet_status(snaps, 2, 3, rate_state)
    assert "fleet: 2/3 healthy" in line
    assert "0.0 req/s" in line          # no prior tick: no rate yet
    time.sleep(0.05)
    snaps2 = {0: {"counters": {"serving_requests_total": 30}}}
    line2 = launch._fleet_status(snaps2, 3, 3, rate_state)
    assert "fleet: 3/3 healthy" in line2
    # 20 reqs since last tick (", 370.0 req/s" also ends in "0.0 req/s")
    assert ", 0.0 req/s" not in line2


# ------------------------------------------------------- lint rule
def _load_lint():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lint", os.path.join(REPO, "ci", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    return lint


def test_lint_flags_unbounded_socket_waits(tmp_path):
    lint = _load_lint()
    d = tmp_path / "incubator_mxnet_tpu" / "serving"
    d.mkdir(parents=True)
    f = d / "rpc.py"
    f.write_text("import socket\ns = socket.socket()\n"
                 "c = s.accept()\n")
    assert any("accept" in p for p in lint.check_file(f))
    # a timeout= kwarg bounds the wait
    f.write_text("import socket\n"
                 "c = socket.create_connection(('h', 1), timeout=5)\n")
    assert not any("create_connection" in p
                   for p in lint.check_file(f))
    # same-line annotation
    f.write_text("import socket\ns = socket.socket()\n"
                 "c = s.recv(4)  # deadline-ok: settimeout armed\n")
    assert not any("recv" in p for p in lint.check_file(f))
    # contiguous comment block above annotates too
    f.write_text("import socket\ns = socket.socket()\n"
                 "# bounded by the caller's poll loop\n"
                 "# deadline-ok: settimeout(poll) armed above\n"
                 "c = s.accept()\n")
    assert not any("accept" in p for p in lint.check_file(f))
    # ...but only a CONTIGUOUS block: code between breaks the chain
    f.write_text("import socket\n# deadline-ok: stale note\n"
                 "s = socket.socket()\n"
                 "c = s.accept()\n")
    assert any("accept" in p for p in lint.check_file(f))
    # outside the fleet RPC modules the rule does not fire
    o = tmp_path / "incubator_mxnet_tpu" / "other.py"
    o.write_text("import socket\ns = socket.socket()\n"
                 "c = s.accept()\n")
    assert not any("accept" in p for p in lint.check_file(o))
