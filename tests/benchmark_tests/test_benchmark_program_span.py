"""The per-layer metrics that read the program's spans
(benchmark/metrics/program_span.py): the reader's arithmetic on
hand-made span records, and the four metrics found by name."""
import json
import os
import shutil

import pytest

from incubator_mxnet_tpu import tracing

from benchmark.harness import HERE, Harness
from benchmark.metrics import program_span

REPO = os.path.dirname(HERE)
NAMES = ("engine_host_ms.serve", "decode_ms.serve",
         "prefill_ms_per_ktok.serve", "step_dispatch_ms.train")
TRACED = {"trace": {"busy_s": 1.0, "window_s": 1.0, "op_seconds": {}}}


def spec(name):
    return json.load(open(os.path.join(HERE, "metrics", name + ".json")))


@pytest.fixture
def ring():
    """record(name, t0, t1, parent=None, session=1, **fields) -> id."""
    tracing.reset_for_tests()
    ids = iter(range(1, 1000))

    def record(name, t0, t1, parent=None, session=1, **fields):
        sid = next(ids)
        tracing.trace_event("span", id=sid, parent=parent, name=name,
                            t0=t0, t1=t1, session=session, **fields)
        return sid
    yield record
    tracing.reset_for_tests()


def engine_step(record, t0, decode_s, host_s, prefill=None, **kw):
    """One ``serve_step``: an admission with its prefill (seconds,
    tokens, bucket) if given, then prep, decode, fetch, emit."""
    t = t0
    step = record("serve_step", t0, t0 + decode_s + host_s
                  + (prefill[0] if prefill else 0.0), **kw)
    if prefill:
        admit = record("serve_admit", t, t + prefill[0] + host_s / 4,
                       parent=step, rid=9, **kw)
        record("serve_prefill", t + host_s / 8, t + host_s / 8
               + prefill[0], parent=admit, rid=9, tokens=prefill[1],
               bucket=prefill[2], **kw)
        t += prefill[0] + host_s / 4
    record("serve_decode_prep", t, t + host_s / 4, parent=step, **kw)
    t += host_s / 4
    record("serve_decode", t, t + decode_s, parent=step, **kw)
    return step


@pytest.fixture
def serve_window(ring):
    """An older session, then the newest: three decode steps of
    70/72/74 ms with 4/5/6 ms of host work, one of them with a prefill
    of 60 ms for 300 tokens in a bucket of 512 and one of 100 ms for
    1000 in 1024; and a step that only reaps (no decode)."""
    engine_step(ring, 0.0, 0.500, 0.100, session=1)
    engine_step(ring, 10.0, 0.070, 0.004, session=2)
    engine_step(ring, 10.1, 0.072, 0.005, (0.060, 300, 512), session=2)
    engine_step(ring, 10.3, 0.074, 0.006, (0.100, 1000, 1024),
                session=2)
    idle = ring("serve_step", 10.6, 10.601, session=2)
    ring("serve_reap", 10.6, 10.6005, parent=idle, session=2)
    ring("compile", 10.31, 10.35, parent=None, session=2,
         fun_name="jit(serve_prefill_1024)", cached=True)


@pytest.mark.parametrize("name,expected", [
    # median of the steps that decoded, less prefill and decode
    ("engine_host_ms.serve", 5.0),
    ("decode_ms.serve", 72.0),
    # 1e6 x (0.060 + 0.100) s / (512 + 1024) padded tokens
    ("prefill_ms_per_ktok.serve", 1e6 * 0.160 / 1536),
])
def test_reader_arithmetic_on_the_newest_session(serve_window, name,
                                                 expected):
    ctx = dict(TRACED)
    assert program_span.read(ctx, spec(name)) == pytest.approx(expected)
    said = ctx["notes"]["program_span"]
    assert said["ring_dropped"] == 0
    assert said["spans_read"] == 16         # the older session is out
    assert said["count"]["serve_step"] == 4
    assert said["compiles"] == [
        {"fun_name": "jit(serve_prefill_1024)", "cached": True,
         "under": None, "seconds": pytest.approx(0.04)}]
    assert said["prefill_padded_share"] == pytest.approx(
        1 - 1300 / 1536)
    # self time: a step keeps what its children leave
    assert said["self_seconds"]["serve_decode"] == pytest.approx(0.216)
    assert said["self_seconds"]["serve_admit"] == pytest.approx(
        (0.005 + 0.006) / 4)


def test_train_step_median_and_nothing_to_read(ring):
    ctx = dict(TRACED)
    assert program_span.read(ctx, spec("step_dispatch_ms.train")) is None
    assert "notes" not in ctx               # no span: nothing counted
    for i, ms in enumerate((9.0, 2.0, 3.0)):
        step = ring("train_step", i, i + ms / 1e3, step=i)
        ring("train_put", i, i + 1e-4, parent=step)
    assert program_span.read(dict(TRACED),
                             spec("step_dispatch_ms.train")) \
        == pytest.approx(3.0)
    # a rehearsal (no traced window) never reads a number
    assert program_span.read({"trace": None},
                             spec("step_dispatch_ms.train")) is None
    # the engine's metrics find no span of theirs here
    for name in NAMES[:3]:
        assert program_span.read(dict(TRACED), spec(name)) is None


def test_nested_spans_are_subtracted_once(ring):
    step = ring("serve_step", 0.0, 1.0)
    outer = ring("serve_decode", 0.1, 0.7, parent=step)
    ring("serve_decode", 0.2, 0.3, parent=outer)
    assert program_span.read(dict(TRACED),
                             spec("engine_host_ms.serve")) \
        == pytest.approx(400.0)


def test_the_four_metrics_are_found_by_a_harness(tmp_path, serve_window):
    """As a later PR's would be: the files of these metrics alone in
    a directory of their own, with BENCHMARK.json beside them."""
    d = tmp_path / "bench"
    (d / "metrics").mkdir(parents=True)
    for name in NAMES:
        shutil.copy(os.path.join(HERE, "metrics", name + ".json"),
                    d / "metrics")
    shutil.copy(os.path.join(HERE, "metrics", "program_span.py"),
                d / "metrics")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), d)
    h = Harness(str(d), str(d / "BENCHMARK.json"))
    listed = {m["name"]: m for m in h.spec["per_layer"]}
    for name in NAMES:
        assert listed[name]["source"] == "program_span"
        assert listed[name]["better"] == "lower"
    mine = {"opt-1.3b.serve-closed16": set(NAMES[:3]),
            "opt-1.3b.train": set()}      # no train_step span recorded
    for cell, expected in mine.items():
        got = h.read_per_layer(cell, dict(TRACED, peaks={}, chips=1))
        assert set(got) & set(NAMES) == expected
        for name in expected:
            assert got[name]["unit"] == listed[name]["unit"]
            assert got[name]["value"] > 0
    # and with no traced window none of them prints a number
    got = h.read_per_layer("opt-1.3b.serve-closed16", {"trace": None})
    assert not set(got) & set(NAMES)


@pytest.mark.parametrize("trace", [0, 1])
def test_spans_are_recorded_only_while_a_rehearsal_traces(measure,
                                                          trace):
    """``--trace 0`` is tracing off: a whole run leaves no span event.
    ``--trace 1`` records the engine's spans, and on the CPU (no
    traced window to stand beside) still no number under these names."""
    tracing.reset_for_tests()
    result = measure("tiny-lm.serve", seconds=0.3, trace=trace)
    assert result["correct"], result["compared"]
    names = {e["name"] for e in tracing.events("span")}
    if trace:
        assert {"serve_step", "serve_decode", "serve_emit"} <= names
    else:
        assert names == set()
    assert not set(result["metrics"]) & set(NAMES)
    for name in NAMES:
        assert program_span.read({"trace": None}, spec(name)) is None
    tracing.reset_for_tests()
