"""Flight recorder (incubator_mxnet_tpu/tracing.py,
docs/observability.md): ring bounds + thread safety, disabled-mode
zero side effects, retrace attribution (shape/dtype/static/train),
the compile-budget watchdog, fault dumps (DivergedError /
DataPipelineError / serving eviction), serving lifecycle
completeness with preemption visible, device-memory accounting,
profiler async events, launch.py memory aggregation, and the new
lint rules."""
import json
import logging
import os
import statistics
import sys
import threading
import warnings

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import (autograd, gluon, nd, profiler,
                                 resilience as rz, telemetry as tel,
                                 tracing)
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.utils.log import get_logger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 37


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
def _fresh_tracing(monkeypatch):
    for var in ("MXTPU_TELEMETRY", "MXTPU_TRACE_DUMP",
                "MXTPU_TRACE_BUFFER", "MXTPU_COMPILE_BUDGET",
                "MXTPU_FAULT_SPEC"):
        monkeypatch.delenv(var, raising=False)
    tracing.reset_for_tests()
    tel.get_registry().reset()
    rz.reset_faults()
    yield
    tracing.reset_for_tests()
    tel.get_registry().reset()
    rz.reset_faults()


def _tiny_lm(**kw):
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    cfg = dict(d_model=32, n_layers=2, n_heads=4, max_len=64)
    cfg.update(kw)
    mx.random.seed(0)
    net = TransformerLM(VOCAB, **cfg)
    net.initialize(mx.initializer.Xavier())
    return net


# --------------------------------------------------------- ring buffer
def test_ring_bound_and_drop_count():
    rec = tracing.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("compile", i=i)
    evs = rec.events()
    assert len(evs) == 8
    assert rec.recorded == 20
    assert rec.dropped == 12
    # oldest evicted first; order and seq stamps survive
    assert [e["i"] for e in evs] == list(range(12, 20))
    assert [e["seq"] for e in evs] == list(range(12, 20))


def test_ring_capacity_env(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_BUFFER", "3")
    tracing.reset_for_tests()
    for i in range(5):
        tracing.trace_event("compile", i=i)
    assert len(tracing.events()) == 3
    assert tracing.get_recorder().capacity == 3


def test_ring_thread_safety():
    rec = tracing.FlightRecorder(capacity=128)
    n_threads, per = 8, 200

    def worker(t):
        for i in range(per):
            rec.record("compile", t=t, i=i)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.recorded == n_threads * per
    evs = rec.events()
    assert len(evs) == 128
    # seq stamps are unique and strictly increasing in buffer order
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_clear_does_not_count_as_dropped():
    rec = tracing.FlightRecorder(capacity=8)
    for i in range(10):
        rec.record("compile", i=i)
    assert rec.dropped == 2
    rec.clear()                 # deliberate drop != lost history
    rec.record("compile", i=99)
    assert rec.dropped == 2
    assert len(rec.events()) == 1


def test_snapshot_lock_timeout_never_blocks():
    rec = tracing.FlightRecorder(capacity=8)
    rec.record("compile", i=0)
    rec._lock.acquire()         # simulate the interrupted holder
    try:
        evs = rec._snapshot(lock_timeout=0.05)
        assert [e["i"] for e in evs] == [0]
    finally:
        rec._lock.release()


def test_sigusr1_dumps_without_killing(tmp_path):
    """An operator's `kill -USR1` must leave a dump AND a live
    process; run in a subprocess so the handler install stays out of
    the test runner."""
    import subprocess
    dump = str(tmp_path / "flight.jsonl")
    code = (
        "import os, signal\n"
        "os.environ['MXTPU_TRACE_DUMP'] = %r\n"
        "from incubator_mxnet_tpu import tracing\n"
        "tracing.trace_event('serve_enqueue', rid=5)\n"
        "assert tracing.install_signal_dump()\n"
        "os.kill(os.getpid(), signal.SIGUSR1)\n"
        "print('alive')\n" % dump)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "alive" in r.stdout, r.stderr
    lines = [json.loads(line)
             for line in open(dump).read().splitlines()]
    assert lines[0]["reason"].startswith("signal_")
    assert lines[1]["rid"] == 5


def test_sigterm_sig_ign_preserved(tmp_path):
    """A SIGTERM disposition of SIG_IGN (set by a parent that meant
    'only SIGKILL stops this worker') must survive the dump handler:
    dump, then stay alive — never escalate to SIG_DFL."""
    import subprocess
    dump = str(tmp_path / "flight.jsonl")
    code = (
        "import os, signal\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "os.environ['MXTPU_TRACE_DUMP'] = %r\n"
        "from incubator_mxnet_tpu import tracing\n"
        "tracing.trace_event('serve_enqueue', rid=7)\n"
        "assert tracing.install_signal_dump()\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "print('alive')\n" % dump)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "alive" in r.stdout, r.stderr
    lines = [json.loads(line)
             for line in open(dump).read().splitlines()]
    assert lines[0]["reason"].startswith("signal_")
    assert lines[1]["rid"] == 7


def test_events_filtering():
    tracing.trace_event("serve_enqueue", rid=1)
    tracing.trace_event("serve_enqueue", rid=2)
    tracing.trace_event("serve_retire", rid=1)
    assert len(tracing.events("serve_enqueue")) == 2
    assert len(tracing.events(rid=1)) == 2
    assert [e["event"] for e in tracing.events(rid=1)] == \
        ["serve_enqueue", "serve_retire"]


def test_disabled_mode_zero_side_effects(monkeypatch):
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    tracing.trace_event("serve_enqueue", rid=1)
    assert tracing.recorder() is tracing.NULL_RECORDER
    # no recorder object was even allocated
    assert tracing._RECORDER["obj"] is None
    assert tracing.events() == []
    assert tracing.update_memory_gauges() == {}
    assert tel.get_registry().snapshot()["gauges"] == {}
    # the compile ledger honors the same contract: no history, no
    # totals, no budget accounting
    monkeypatch.setenv("MXTPU_COMPILE_BUDGET", "0.001")
    led = tracing.compile_ledger("disabled_site")
    assert led.record({"shape": (1,)}, 99.0) == "disabled"
    assert tracing.compile_totals() == (0, 0.0)
    assert len(led._sigs) == 0


# ------------------------------------------------- retrace attribution
def test_signature_diff_unit():
    base = {"shape": ((2, 3),), "dtype": ("float32",),
            "static_arg": (("i", 2),), "train_flag": False}
    assert tracing.signature_diff(base, []) == ("first_compile", [])

    def vary(**kw):
        sig = dict(base)
        sig.update(kw)
        return sig

    assert tracing.signature_diff(
        vary(shape=((4, 3),)), [base]) == ("shape", ["shape"])
    assert tracing.signature_diff(
        vary(dtype=("int32",)), [base]) == ("dtype", ["dtype"])
    assert tracing.signature_diff(
        vary(static_arg=(("i", 3),)), [base]) == \
        ("static_arg", ["static_arg"])
    assert tracing.signature_diff(
        vary(train_flag=True), [base]) == \
        ("train_flag", ["train_flag"])
    reason, changed = tracing.signature_diff(
        vary(shape=((4, 3),), train_flag=True), [base])
    assert reason == "shape+train_flag"
    assert tracing.signature_diff(dict(base), [base]) == \
        ("duplicate", [])
    # nearest-entry selection: diff against the closest prior
    # signature, not the first one
    other = vary(shape=((9, 9),), dtype=("int32",),
                 static_arg=(("i", 7),))
    assert tracing.signature_diff(
        vary(dtype=("int32",)), [other, base]) == \
        ("dtype", ["dtype"])


def test_compile_ledger_records_and_budget(caplog):
    led = tracing.compile_ledger("unit_site")
    assert tracing.compile_ledger("unit_site") is led
    assert led.record({"shape": (2,)}, 0.25) == "first_compile"
    assert led.record({"shape": (4,)}, 0.25) == "shape"
    evs = tracing.events("compile", site="unit_site")
    assert [e["reason"] for e in evs] == ["first_compile", "shape"]
    assert all(e["seconds"] == 0.25 for e in evs)
    reg = tel.get_registry()
    assert reg.counter("compile_events_total").value == 2
    assert reg.histogram("compile_seconds").count == 2
    assert tracing.compile_totals() == (2, 0.5)


def test_compile_budget_watchdog_warns_on_storm(caplog,
                                                monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_BUDGET", "1.0")
    logger = get_logger()
    logger.propagate = True   # let caplog's root handler see it
    try:
        led = tracing.compile_ledger("storm_site")
        with caplog.at_level(logging.WARNING, logger=logger.name):
            led.record({"shape": (1,)}, 0.6)     # 0.6 < 1.0
            assert not caplog.records
            led.record({"shape": (2,)}, 0.6)     # 1.2 >= 1.0: warn
            assert len(caplog.records) == 1
            led.record({"shape": (3,)}, 0.3)     # 1.5 < 2.0: quiet
            assert len(caplog.records) == 1
            led.record({"shape": (4,)}, 0.6)     # 2.1 >= 2.0: again
            assert len(caplog.records) == 2
        assert "compile budget exceeded" in caplog.records[0].message
        assert "storm_site" in caplog.records[-1].getMessage()
    finally:
        logger.propagate = False


def test_cachedop_miss_attribution_shape_static_train():
    net = nn.Dense(4)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    site = f"cachedop:{net.name}"
    net(nd.array(np.zeros((2, 3), "float32")))
    net(nd.array(np.zeros((5, 3), "float32")))       # shape miss
    with autograd.record():                          # train-flag miss
        net(nd.array(np.zeros((5, 3), "float32")))
    reasons = [e["reason"]
               for e in tracing.events("compile", site=site)]
    assert reasons[0] == "first_compile"
    assert "shape" in reasons[1]
    assert "train_flag" in reasons[2]
    # replay: no further compile events
    n = len(tracing.events("compile", site=site))
    net(nd.array(np.zeros((2, 3), "float32")))
    assert len(tracing.events("compile", site=site)) == n


def test_cachedop_dtype_and_static_arg_components():
    from incubator_mxnet_tpu.graph import cached_op as co
    f32 = co._ArgsTemplate([nd.array(np.zeros((2, 3), "float32"))])
    i32 = co._ArgsTemplate([nd.array(np.zeros((2, 3), "int32"))])
    c_f = co._signature_components(f32, False)
    c_i = co._signature_components(i32, False)
    assert tracing.signature_diff(c_i, [c_f]) == ("dtype", ["dtype"])
    s2 = co._ArgsTemplate([nd.array(np.zeros((2, 3), "float32")), 2])
    s3 = co._ArgsTemplate([nd.array(np.zeros((2, 3), "float32")), 3])
    assert tracing.signature_diff(
        co._signature_components(s3, False),
        [co._signature_components(s2, False)]) == \
        ("static_arg", ["static_arg"])


def test_generate_compile_ledger():
    net = _tiny_lm()
    x = nd.array(np.asarray([[1, 2, 3]], np.int32))
    net.generate(x, 4)
    net.generate(x, 4)                               # replay
    net.generate(x, 6)                               # static miss
    net.generate(nd.array(np.asarray([[1, 2, 3, 4]], np.int32)), 6)
    evs = tracing.events("compile", site="transformer_generate")
    assert len(evs) == 3
    assert evs[0]["reason"] == "first_compile"
    assert evs[1]["reason"] == "static_arg"
    assert evs[2]["reason"] == "shape"
    assert all(e["seconds"] > 0 for e in evs)


# ----------------------------------------------------------- fault dumps
def test_manual_dump_atomic_jsonl(tmp_path):
    for i in range(5):
        tracing.trace_event("serve_enqueue", rid=i)
    path = str(tmp_path / "flight.jsonl")
    assert tracing.dump(path, reason="unit") == path
    lines = [json.loads(line)
             for line in open(path).read().splitlines()]
    assert lines[0]["flight_recorder"] == 1
    assert lines[0]["reason"] == "unit"
    assert lines[0]["events"] == 5 and lines[0]["dropped"] == 0
    assert [e["rid"] for e in lines[1:]] == list(range(5))
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_auto_dump_path_suffixed_per_rank(tmp_path, monkeypatch):
    """Multi-rank runs (MXTPU_WORKER_RANK set) suffix the automatic
    dump path per rank, so a healthy rank's SIGTERM dump can never
    clobber the faulting rank's post-mortem; explicit paths are
    written verbatim."""
    path = str(tmp_path / "flight.jsonl")
    monkeypatch.setenv("MXTPU_TRACE_DUMP", path)
    monkeypatch.setenv("MXTPU_WORKER_RANK", "3")
    tracing.trace_event("serve_enqueue", rid=1)
    got = tracing.dump(reason="unit")
    assert got == str(tmp_path / "flight.rank3.jsonl")
    lines = [json.loads(line)
             for line in open(got).read().splitlines()]
    assert lines[0]["rank"] == 3
    # explicit path: no suffix, caller said exactly where
    assert tracing.dump(path, reason="unit") == path


def test_no_dump_path_means_no_dump(tmp_path):
    tracing.trace_event("serve_enqueue", rid=0)
    assert tracing.dump() is None
    rz.DataPipelineError("boom")        # constructing must be inert
    assert os.listdir(tmp_path) == []


def test_dump_on_data_pipeline_error(tmp_path, monkeypatch):
    path = str(tmp_path / "flight.jsonl")
    monkeypatch.setenv("MXTPU_TRACE_DUMP", path)
    tracing.trace_event("serve_enqueue", rid=7)
    rz.DataPipelineError("prefetch wedged")
    lines = [json.loads(line)
             for line in open(path).read().splitlines()]
    assert lines[0]["reason"] == "data_pipeline_error"
    assert any(e.get("rid") == 7 for e in lines[1:])


def test_dump_on_diverged_error_e2e(tmp_path, monkeypatch):
    """Fault-injected divergence (MXTPU_FAULT_SPEC grad:nonfinite)
    leaves a flight-recorder dump holding the last events before the
    divergence — the sentinel's bad-step trail included."""
    path = str(tmp_path / "flight.jsonl")
    monkeypatch.setenv("MXTPU_TRACE_DUMP", path)
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "grad:nonfinite:*:nan")
    monkeypatch.setenv("MXTPU_NONFINITE_POLICY", "skip")
    monkeypatch.setenv("MXTPU_MAX_BAD_STEPS", "2")
    rz.reset_faults()
    mx.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(10, 4).astype("float32"))
    y = nd.array(rs.randint(0, 3, 10).astype("float32"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(rz.DivergedError):
            for _ in range(8):
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                trainer.step(10)
    lines = [json.loads(line)
             for line in open(path).read().splitlines()]
    assert lines[0]["reason"] == "diverged_error"
    events = [e["event"] for e in lines[1:]]
    assert events.count("sentinel_bad_step") >= 2
    assert events[-1] == "sentinel_diverged"


# ------------------------------------------------ serving lifecycle
def test_serving_lifecycle_complete_with_preemption_and_eviction(
        monkeypatch):
    """Every submitted request's lifecycle is closed in the ring:
    enqueue -> admit -> ... -> exactly one terminal retire|evict;
    preemption is visible as preempt+requeue+re-admit; evicted and
    preempted requests record queue-wait like retired ones."""
    from incubator_mxnet_tpu.serving import ServingEngine
    net = _tiny_lm()
    rs = np.random.RandomState(19)
    prompts = [list(rs.randint(0, VOCAB, n)) for n in (9, 10, 6)]
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "serve:request:3:error")
    rz.reset_faults()
    # pool too small for two full sequences -> preemption
    eng = ServingEngine(net, max_batch=2, block_size=4,
                        num_blocks=12, prefix_cache=False)
    reqs = [eng.submit(p, 14) for p in prompts]
    eng.run()
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.state for r in reqs].count("failed") == 1
    for req in reqs:
        evs = tracing.events(rid=req.id, engine=eng.engine_id)
        names = [e["event"] for e in evs]
        assert names[0] == "serve_enqueue"
        terminal = [n for n in names
                    if n in ("serve_retire", "serve_evict")]
        assert len(terminal) == 1          # no silent exits
        assert terminal[0] == ("serve_evict"
                               if req.state == "failed"
                               else "serve_retire")
        assert evs[-1]["event"] == terminal[0]
        assert evs[-1]["queue_wait_s"] >= 0
        if req.preemptions:
            assert "serve_preempt" in names
            assert "serve_requeue" in names
            # one admit per admission; a request evicted on a
            # re-admission attempt dies queued, one admit short
            expect = req.preemptions + 1
            if req.state == "failed":
                assert names.count("serve_admit") in (expect - 1,
                                                      expect)
            else:
                assert names.count("serve_admit") == expect
        # cumulative queue wait covers every queued segment: the
        # terminal event's value is the sum over (re-)admissions
        # plus any open segment closed at eviction
        segs = [e["queue_wait_s"] for e in evs
                if e["event"] == "serve_admit"]
        if req.state == "failed":
            assert evs[-1]["queue_wait_s"] >= round(sum(segs), 6)
    # stats() parity: every request summarized, evicted included
    summaries = {s["id"]: s for s in eng.stats()["requests"]}
    assert set(summaries) == {r.id for r in reqs}
    failed = [s for s in summaries.values()
              if s["state"] == "failed"]
    assert len(failed) == 1 and failed[0]["queue_wait_s"] is not None
    assert failed[0]["error"]


def test_serving_eviction_triggers_fault_dump(tmp_path, monkeypatch):
    from incubator_mxnet_tpu.serving import ServingEngine
    path = str(tmp_path / "flight.jsonl")
    monkeypatch.setenv("MXTPU_TRACE_DUMP", path)
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "serve:request:1:error")
    rz.reset_faults()
    net = _tiny_lm()
    eng = ServingEngine(net, max_batch=1, block_size=4,
                        num_blocks=32, prefix_cache=False)
    req = eng.submit([1, 2, 3], 2)
    eng.run()
    assert req.state == "failed"
    lines = [json.loads(line)
             for line in open(path).read().splitlines()]
    assert lines[0]["reason"] == "serving_eviction"
    evicted = [e for e in lines[1:] if e.get("rid") == req.id
               and e.get("engine") == eng.engine_id]
    assert [e["event"] for e in evicted] == \
        ["serve_enqueue", "serve_evict"]


def test_serving_stats_ttft_decomposition():
    from incubator_mxnet_tpu.serving import ServingEngine
    net = _tiny_lm()
    eng = ServingEngine(net, max_batch=2, block_size=4,
                        num_blocks=64)
    reqs = [eng.submit([1, 2, 3, 4, 5], 6), eng.submit([7, 8], 4)]
    eng.run()
    stats = eng.stats()
    assert stats["live"] == []
    by_id = {s["id"]: s for s in stats["requests"]}
    for req in reqs:
        s = by_id[req.id]
        assert s["state"] == "finished"
        assert s["tokens_generated"] == req.max_new_tokens
        assert s["ttft_s"] >= s["prefill_s"] >= 0
        assert s["queue_wait_s"] >= 0
        assert s["decode_s"] >= 0
    assert stats["trace_counts"].get("decode") == 1


def test_serving_compiles_once_a_signature_each_with_its_reason():
    """A stream of mixed lengths compiles the decode step and one
    prefill a bucket, each once; every compile event of the engine's
    site says why, and the same stream again compiles nothing."""
    from incubator_mxnet_tpu.serving import ServingEngine
    net = _tiny_lm()
    eng = ServingEngine(net, max_batch=2, block_size=4,
                        num_blocks=64)
    site = f"serving_engine:{eng.engine_id}"
    prompts = [[1, 2, 3], list(range(1, 7)), list(range(2, 14)),
               [5, 6, 7, 8, 9]]

    def stream():
        for p in prompts:
            eng.submit(p, 3)
        eng.run()
        return tracing.events("compile", site=site)

    first = stream()
    signatures = [json.dumps(e["signature"], sort_keys=True)
                  for e in first]
    assert len(first) == sum(eng.trace_counts.values()) >= 3
    assert len(set(signatures)) == len(signatures)
    assert first[0]["reason"] == "first_compile"
    assert all(e["reason"] and e["seconds"] > 0 for e in first)
    assert len(stream()) == len(first)


def test_serving_disabled_telemetry_records_nothing(monkeypatch):
    from incubator_mxnet_tpu.serving import ServingEngine
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    net = _tiny_lm()
    eng = ServingEngine(net, max_batch=1, block_size=4,
                        num_blocks=32)
    eng.submit([1, 2, 3], 3)
    eng.run()
    assert tracing._RECORDER["obj"] is None
    assert tracing.events() == []
    # stats() still works: it is an API, not telemetry
    assert len(eng.stats()["requests"]) == 1


# ------------------------------------- program spans (profiler session)
def _tree(evs):
    """(by id, id -> children in order of start)."""
    by_id = {e["id"]: e for e in evs}
    kids = {}
    for e in sorted(evs, key=lambda e: e["t0"]):
        kids.setdefault(e["parent"], []).append(e)
    return by_id, kids


def _assert_children_lie_inside_in_order(evs):
    by_id, kids = _tree(evs)
    for parent, children in kids.items():
        if parent is None:
            continue
        lo = by_id[parent]["t0"]
        for c in children:
            assert lo <= c["t0"] <= c["t1"] <= by_id[parent]["t1"], c
            lo = c["t1"]            # siblings do not overlap


def _tiny_latent_moe():
    from incubator_mxnet_tpu.gluon.model_zoo.latent_moe import \
        LatentMoELM
    mx.random.seed(0)
    net = LatentMoELM(dict(
        hidden_size=32, num_attention_heads=2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        num_hidden_layers=3, first_k_dense_replace=1, vocab_size=VOCAB,
        max_position_embeddings=64, rope_theta=10000.0,
        routed_scaling_factor=2.5))
    net.initialize(mx.initializer.Normal(0.1))
    return net


@pytest.mark.parametrize("model", ["transformer_lm", "latent_moe_lm"])
def test_engine_step_spans_form_the_tree_with_request_ids(
        profiler_session, newest_spans, model):
    from incubator_mxnet_tpu.serving import ServingEngine
    routed = model == "latent_moe_lm"
    eng = ServingEngine(_tiny_latent_moe() if routed else _tiny_lm(),
                        max_batch=2, block_size=4,
                        num_blocks=64, prefix_cache=False)
    eng.submit([1, 2, 3], 2)            # warm prefill_4 and decode
    eng.run()
    tokens0 = tel.counter("serving_prefill_tokens_total").value
    padded0 = tel.counter("serving_prefill_padded_tokens_total").value
    assert tracing.events("span") == []     # no session: no event
    req = eng.submit([5, 6, 7, 8, 9], 3)    # suffix 5 -> bucket 8
    with profiler_session() as rec:
        eng.step()                          # admission + one decode
        eng.step()                          # decode only
    eng.run()
    evs = newest_spans()
    by_id, kids = _tree(evs)
    first, second = kids[None]
    assert first["name"] == second["name"] == "serve_step"
    assert first["waiting"] == 1 and first["running"] == 0
    assert first["emitted"] == 2 and second["emitted"] == 1
    assert [c["name"] for c in kids[first["id"]]] == [
        "serve_reap", "serve_admit", "serve_grow",
        "serve_decode_prep", "serve_decode", "serve_token_fetch",
        "serve_emit"]
    assert [c["name"] for c in kids[second["id"]]] == [
        "serve_reap", "serve_grow", "serve_decode_prep",
        "serve_decode", "serve_token_fetch", "serve_emit"]
    # a routed model's decode says how many experts the step read:
    # one slot, 2 routed layers, 2 experts a token and layer
    for step in (first, second):
        decode = kids[step["id"]][-3]
        assert decode.get("experts_touched") == (4 if routed else None)
    admit = kids[first["id"]][1]
    assert admit["rid"] == req.id and admit["cached_tokens"] == 0
    assert admit["slot"] == req.last_slot
    prefill, fetch = (c for c in kids[admit["id"]]
                      if c["name"] != "compile")
    assert (prefill["name"], fetch["name"]) == (
        "serve_prefill", "serve_token_fetch")
    assert (prefill["rid"], prefill["tokens"], prefill["bucket"]) \
        == (req.id, 5, 8)
    # the new bucket compiled inside the traced step: the compile is
    # found under the prefill, by the name the program is counted under
    named = [e for e in evs if e["name"] == "compile"
             and "serve_prefill_8" in e["fun_name"]]
    assert [e["parent"] for e in named] == [prefill["id"]]
    _assert_children_lie_inside_in_order(
        [e for e in evs if e["name"] != "compile"])
    # a step's children cover it but for its own self time
    for step in (first, second):
        own = (step["t1"] - step["t0"]) - sum(
            c["t1"] - c["t0"] for c in kids[step["id"]])
        assert 0 <= own < 0.2
    # every span lies in the trace under mx.<name>, equally long.
    # The annotation is entered just before the span reads its clock
    # and left just after, so it is never the shorter, and longer by
    # microseconds.  They are still two pairs of clock reads: what
    # the process loses between two of them (a scheduler's slice on a
    # machine that six workers share, the interpreter's switch
    # interval to a thread an earlier file of the worker left behind)
    # shows in one and not in the other, 5 ms in the driver's run of
    # PR 26's tree.  So the rule is held at the median span, and a
    # single span only to the order and to a stall's length.
    traced = sorted((s, n, d) for n, s, d in rec.host_events())
    spans = sorted((e["t0"], "mx." + e["name"], e["t1"] - e["t0"])
                   for e in evs if e["name"] != "compile")
    assert [n for _, n, _ in traced] == [n for _, n, _ in spans]
    longer = [dur_ns / 1e9 - dur_s for (_, _, dur_ns), (_, _, dur_s)
              in zip(traced, spans)]
    assert min(longer) > -2e-5 and max(longer) < 0.1, longer
    assert statistics.median(longer) < 2e-4, longer
    # the one stopwatch: the lifecycle event and the request carry
    # the prefill span's own duration
    event, = tracing.events("serve_prefill", rid=req.id)
    assert event["seconds"] == round(prefill["t1"] - prefill["t0"], 6)
    assert req.prefill_s == pytest.approx(
        prefill["t1"] - prefill["t0"], abs=1e-9)
    # padding is counted where the bucket is chosen
    assert tel.counter("serving_prefill_tokens_total").value \
        - tokens0 == 5
    assert tel.counter("serving_prefill_padded_tokens_total").value \
        - padded0 == 8


def test_sharded_train_step_spans_first_call_and_later(
        profiler_session, newest_spans):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import parallel
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.initializer.Xavier())
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(8, 12), jnp.float32)
    y = jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32)
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd", optimizer_params=dict(learning_rate=0.1),
        mesh=parallel.make_mesh(devices=jax.devices()[:1]),
        example_args=[x])
    with profiler_session() as rec:
        step(x, y)
        step(x, y)
    evs = newest_spans()
    by_id, kids = _tree(evs)
    first, second = kids[None]
    assert (first["name"], first["step"]) == ("train_step", 0)
    assert (second["name"], second["step"]) == ("train_step", 1)

    def names(parent):
        return [c["name"] for c in kids[parent["id"]]]
    assert names(first) == ["train_preflight", "train_build",
                            "train_put", "train_dispatch"]
    assert names(second) == ["train_put", "train_dispatch"]
    dispatch = kids[first["id"]][-1]
    assert [c["fun_name"] for c in kids[dispatch["id"]]
            if c["name"] == "compile"] == ["jit(step)"]
    _assert_children_lie_inside_in_order(
        [e for e in evs if e["name"] != "compile"])
    traced = [n for n, _, _ in rec.host_events()]
    assert sorted(traced) == sorted(
        "mx." + e["name"] for e in evs if e["name"] != "compile")


def test_engine_programs_are_named_as_they_are_counted(
        profiler_session, newest_spans):
    from incubator_mxnet_tpu.serving import ServingEngine
    eng = ServingEngine(_tiny_lm(), max_batch=1, block_size=4,
                        num_blocks=32, prefix_cache=False)
    with profiler_session():
        eng.submit([1, 2, 3], 2)
        eng.run()
    by_id, _ = _tree(newest_spans())
    under = {e["fun_name"]: by_id[e["parent"]]["name"]
             for e in by_id.values() if e["name"] == "compile"
             and "serve_" in e["fun_name"]}
    assert under == {"jit(serve_prefill_4)": "serve_prefill",
                     "jit(serve_decode)": "serve_decode"}
    assert set(eng.trace_counts) == {"decode", "prefill_4"}


# ----------------------------------------------- profiler async events
def test_profiler_async_events_and_lanes(tmp_path):
    from incubator_mxnet_tpu.serving import ServingEngine
    net = _tiny_lm()
    profiler.set_config(filename=str(tmp_path / "prof.json"))
    profiler.set_state("run")
    try:
        eng = ServingEngine(net, max_batch=1, block_size=4,
                            num_blocks=32)
        eng.submit([1, 2, 3, 4], 3)
        eng.run()
        out = profiler.dump_profile()
    finally:
        profiler.set_state("stop")
    data = json.load(open(out))
    evs = data["traceEvents"]
    asyncs = [e for e in evs if e.get("ph") in ("b", "e")
              and e.get("cat") == "serving"]
    assert asyncs, "no async serving events in the dump"
    aid = f"req{eng.engine_id}.0"
    names = {e["name"] for e in asyncs if e["id"] == aid}
    assert {"request", "queue_wait", "prefill",
            "decode"} <= names
    # every b has a matching e per (name, id), ON THE SAME LANE —
    # terminal events fire after Scheduler.clear nulls req.slot, so
    # lane choice must not depend on the live slot
    for name in names:
        pair = [e for e in asyncs
                if e["id"] == aid and e["name"] == name]
        phases = [e["ph"] for e in pair]
        assert phases.count("b") == phases.count("e")
        assert len({e["tid"] for e in pair}) == 1, \
            f"phase {name} split across lanes"
    lanes = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "serve queue" in lanes
    assert "serve slot 0" in lanes


def test_profiler_async_rejects_bad_phase():
    with pytest.raises(ValueError, match="'b'/'e'"):
        profiler._profiler.add_async_event("x", "id1", "X")


# ------------------------------------------------- memory accounting
def test_memory_accounting_attribution():
    import jax.numpy as jnp
    bufs = [jnp.zeros((16, 16), jnp.float32),
            jnp.zeros((8,), jnp.float32)]
    nbytes = sum(int(b.nbytes) for b in bufs)
    unreg = tracing.register_memory("kv_pools", lambda: bufs)
    stats = tracing.device_memory_stats()
    assert stats["host_rss_bytes"] > 0
    assert stats["device_bytes_kv_pools"] == nbytes
    assert stats["device_live_bytes"] >= nbytes
    assert stats["device_bytes_workspace"] >= 0
    unreg()
    assert tracing.device_memory_stats()[
        "device_bytes_kv_pools"] == 0
    with pytest.raises(ValueError, match="memory kind"):
        tracing.register_memory("frobnicator", lambda: [])
    # a raising provider is skipped, never fatal
    unreg2 = tracing.register_memory(
        "params", lambda: (_ for _ in ()).throw(RuntimeError()))
    assert tracing.device_memory_stats()["device_bytes_params"] == 0
    unreg2()


def test_memory_gauges_ride_heartbeat_payload():
    tracing.compile_ledger("hb_site").record({"shape": (1,)}, 0.01)
    payload = tel.heartbeat_payload()
    snap = json.loads(payload)
    assert snap["gauges"]["host_rss_bytes"] > 0
    assert "device_live_bytes" in snap["gauges"]
    assert snap["counters"]["compile_events_total"] == 1


def test_serving_engine_registers_kv_pool_bytes():
    from incubator_mxnet_tpu.serving import ServingEngine
    net = _tiny_lm()
    eng = ServingEngine(net, max_batch=1, block_size=4,
                        num_blocks=16)
    expect = sum(int(a.nbytes)
                 for pool in eng._pools for a in pool)
    stats = tracing.device_memory_stats()
    assert stats["device_bytes_kv_pools"] == expect
    # owner teardown unregisters the provider (weakref.finalize):
    # repeated engine construction must not grow the table forever
    import gc
    del eng
    gc.collect()
    assert tracing.device_memory_stats()[
        "device_bytes_kv_pools"] == 0
    assert not tracing._MEM_PROVIDERS.get("kv_pools")


# --------------------------------------------- launch.py aggregation
def test_launch_aggregates_memory_and_compiles():
    launch = _load_tool("launch")
    snaps = {
        0: {"counters": {"train_steps_total": 10},
            "gauges": {"device_live_bytes": float(100 << 20),
                       "host_rss_bytes": float(500 << 20)}},
        1: {"counters": {"train_steps_total": 10,
                         "compile_events_total": 3},
            "gauges": {"device_live_bytes": float(200 << 20)}},
    }
    agg = launch._aggregate_telemetry(snaps)
    assert agg["max_memory"] == (1, float(200 << 20))
    assert agg["memory"][0] == float(100 << 20)
    assert agg["compiles"] == {1: 3}
    status = launch._format_status(agg)
    assert "mem: max rank 1 at 200MB" in status
    assert "compiles=3" in status
    report = launch._format_report(snaps)
    assert "max memory: rank 1 at 200MB" in report
    assert "rank 1: steps=10 mem=200MB compiles=3" in report
    # rss fallback when no device gauge is present
    assert launch._rank_memory(
        {"gauges": {"host_rss_bytes": 7.0}}) == 7.0
    assert launch._fmt_bytes(3 << 30) == "3.0GB"


# ------------------------------------------------------------ lint rules
def test_lint_trace_event_catalog_rule(tmp_path, monkeypatch):
    lint = _load_lint()
    monkeypatch.chdir(REPO)
    d = tmp_path / "incubator_mxnet_tpu"
    d.mkdir()
    f = d / "x.py"
    f.write_text("from . import tracing\n"
                 "tracing.trace_event('totally_undocumented_ev')\n")
    probs = lint.check_metric_catalog([f])
    assert any("trace-event name" in p for p in probs)
    f.write_text("from . import tracing\n"
                 "tracing.trace_event('serve_enqueue', rid=1)\n")
    assert not lint.check_metric_catalog([f])


def test_lint_host_sync_rule_covers_tracing(tmp_path):
    lint = _load_lint()
    d = tmp_path / "incubator_mxnet_tpu"
    d.mkdir()
    f = d / "tracing.py"
    f.write_text(
        "import numpy as np\n\n\n"
        "def update_memory_gauges(arr):\n"
        "    return np.asarray(arr)\n")
    assert any("host sync" in p for p in lint.check_file(f))
    f.write_text(
        "import numpy as np\n\n\n"
        "def update_memory_gauges(arr):\n"
        "    return np.asarray(arr)  # sync-ok: unit test\n")
    assert not any("host sync" in p for p in lint.check_file(f))
    # the shipped module passes its own rule
    real = os.path.join(REPO, "incubator_mxnet_tpu", "tracing.py")
    assert not any("host sync" in p
                   for p in lint.check_file(
                       __import__("pathlib").Path(real)))
