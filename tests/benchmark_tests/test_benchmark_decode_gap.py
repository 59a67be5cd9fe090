"""The per-layer metrics that read the engine's decode launches
(benchmark/metrics/decode_gap.py and program_span.py): the readers'
arithmetic on hand-made span records, what a traced rehearsal records,
and the three metrics found by a harness that lists them."""
import json
import os
import shutil

import pytest

from incubator_mxnet_tpu import tracing

from benchmark.harness import HERE, Harness
from benchmark.metrics import decode_gap, program_span

REPO = os.path.dirname(HERE)
NAMES = ("decode_dispatch_ms.serve", "exposed_host_ms.serve",
         "admit_stall_ms.serve")
TRACED = {"trace": {"busy_s": 1.0, "window_s": 1.0, "op_seconds": {}}}


def spec(name):
    return json.load(open(os.path.join(HERE, "metrics", name + ".json")))


def read(name, ctx):
    s = spec(name)
    reader = {"program_span.py": program_span,
              "decode_gap.py": decode_gap}[s["reader"]]
    return reader.read(ctx, s)


@pytest.fixture
def ring():
    """record(name, t0, t1, parent=None, session=1, **fields) -> id;
    the session's ``span_clock`` first, as the program records it."""
    tracing.reset_for_tests()
    ids = iter(range(1, 100000))
    clocked = set()

    def record(name, t0, t1, parent=None, session=1, **fields):
        if session not in clocked:
            clocked.add(session)
            tracing.trace_event("span_clock", session=session,
                                perf_counter=t0, time_ns=0)
        sid = next(ids)
        tracing.trace_event("span", id=sid, parent=parent, name=name,
                            t0=t0, t1=t1, session=session, **fields)
        return sid
    yield record
    tracing.reset_for_tests()


def decode_step(record, t0, launch_s, session=2, **gap):
    """One ``serve_decode`` of 10 ms whose launch takes ``launch_s``
    and carries ``gap`` (``gap_s``, ``busy_s``, ``admitted``)."""
    dec = record("serve_decode", t0, t0 + 0.010, session=session)
    record("serve_decode_dispatch", t0, t0 + launch_s, parent=dec,
           session=session, **gap)


@pytest.fixture
def serve_window(ring):
    """An older session, then the newest: a first launch after an idle
    engine (no gap), two plain steps, and two that took prompts in."""
    decode_step(ring, 0.0, 0.009, session=1, gap_s=0.5, busy_s=0.0,
                admitted=0)
    decode_step(ring, 10.0, 0.0005)
    decode_step(ring, 10.02, 0.0006, gap_s=0.0010, busy_s=0.0,
                admitted=0)
    decode_step(ring, 10.04, 0.0004, gap_s=0.0014, busy_s=0.0,
                admitted=0)
    decode_step(ring, 10.06, 0.0005, gap_s=0.0312, busy_s=0.0290,
                admitted=1)
    decode_step(ring, 10.10, 0.0007, gap_s=0.0705, busy_s=0.0660,
                admitted=2)


@pytest.mark.parametrize("name,expected", [
    # the median launch of the newest session's five
    ("decode_dispatch_ms.serve", 0.5),
    # gap - busy + launch: 1.6, 1.8, 2.7, 4.5 -> 2.25
    ("exposed_host_ms.serve", 2.25),
    # gap + launch of the steps that took prompts in: 31.7, 71.2
    ("admit_stall_ms.serve", (31.7 + 71.2) / 2),
])
def test_reader_arithmetic_on_the_newest_session(serve_window, name,
                                                 expected):
    ctx = dict(TRACED)
    assert read(name, ctx) == pytest.approx(expected)


def test_the_notes_sum_the_window(serve_window):
    ctx = dict(TRACED)
    read("exposed_host_ms.serve", ctx)
    said = ctx["notes"]["decode_gap"]
    assert said == {
        "launches": 5, "with_gap": 4, "without_gap": 1, "admitted": 3,
        "gap_s": pytest.approx(0.1041), "busy_s": pytest.approx(0.095),
        "launch_s": pytest.approx(0.0027), "session_complete": True}


def test_a_window_without_gaps_reads_nothing(ring):
    ctx = dict(TRACED)
    for name in NAMES[1:]:
        assert read(name, ctx) is None
    assert "notes" not in ctx               # no launch: nothing counted
    decode_step(ring, 1.0, 0.0005)          # a launch after idling
    decode_step(ring, 1.02, 0.0005, gap_s=0.001, busy_s=0.0,
                admitted=0)
    assert read("exposed_host_ms.serve", dict(TRACED)) \
        == pytest.approx(1.5)
    assert read("admit_stall_ms.serve", dict(TRACED)) is None
    # a rehearsal (no traced window) never reads a number
    for name in NAMES:
        assert read(name, {"trace": None}) is None


@pytest.mark.parametrize("older,steps,complete", [
    (0, 40, False),         # the drop took the session's first events
    (50, 20, True),         # it took an older session's events only
])
def test_session_complete_says_whether_the_ring_kept_the_window(
        monkeypatch, ring, older, steps, complete):
    """A ring of 64 events: the session's clock and two events a step
    after ``older`` events of an older session."""
    monkeypatch.setenv("MXTPU_TRACE_BUFFER", "64")
    tracing.reset_for_tests()
    for i in range(older):
        ring("serve_step", i, i + 0.5, session=1)
    for i in range(steps):
        decode_step(ring, 10 + i, 0.0005, gap_s=0.001, busy_s=0.0,
                    admitted=0)
    assert tracing.recorder().dropped > 0
    ctx = dict(TRACED)
    assert read("exposed_host_ms.serve", ctx) == pytest.approx(1.5)
    assert ctx["notes"]["decode_gap"]["session_complete"] is complete


def test_the_three_metrics_are_found_by_a_harness(tmp_path,
                                                  serve_window):
    """As the benchmark would list them: the files alone in a directory
    of their own, with a BENCHMARK.json whose ``per_layer`` names them
    for every cell that reports ``serve_tok_per_s``."""
    d = tmp_path / "bench"
    (d / "metrics").mkdir(parents=True)
    for name in NAMES:
        shutil.copy(os.path.join(HERE, "metrics", name + ".json"),
                    d / "metrics")
    for reader in ("program_span.py", "decode_gap.py"):
        shutil.copy(os.path.join(HERE, "metrics", reader), d / "metrics")
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "serving engine",
         "moves": "serve_tok_per_s"} for name in NAMES]
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    h = Harness(str(d), str(d / "BENCHMARK.json"))
    serving = [w["name"] for w in bench["workloads"]
               if ".serve" in w["name"]]
    assert serving
    for cell in serving:
        got = h.read_per_layer(cell, dict(TRACED, peaks={}, chips=1))
        assert set(NAMES) <= set(got)
        assert got["decode_dispatch_ms.serve"]["value"] \
            <= got["exposed_host_ms.serve"]["value"]
    got = h.read_per_layer("opt-1.3b.train", dict(TRACED, peaks={},
                                                  chips=1))
    assert not set(got) & set(NAMES)


def test_a_traced_rehearsal_records_the_launches_and_their_gaps(
        measure):
    """On the CPU the engine records its launches and the clock pair
    while the rehearsal traces; with no device plane the metrics read
    nothing."""
    tracing.reset_for_tests()
    result = measure("tiny-lm.serve", seconds=0.3, trace=1)
    assert result["correct"], result["compared"]
    events, dropped = program_span.span_events()
    by_id = {e["id"]: e for e in events}
    launches = [e for e in events
                if e["name"] == "serve_decode_dispatch"]
    assert launches and all(by_id[e["parent"]]["name"] == "serve_decode"
                            for e in launches)
    said = decode_gap.notes(launches, dropped)
    assert said["with_gap"] >= 1 and said["session_complete"]
    assert all(0 <= e["busy_s"] <= e["gap_s"]
               for e in launches if "gap_s" in e)
    session = launches[0]["session"]
    assert [c["session"] for c in tracing.events("span_clock")
            if c["session"] == session] == [session]
    assert not set(result["metrics"]) & set(NAMES)
    tracing.reset_for_tests()
