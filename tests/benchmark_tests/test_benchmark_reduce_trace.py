"""The reduction from a trace to numbers, on the recorded trace of two
steps of the rehearsal LM on a TPU v5 lite (benchmark/testdata/)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import reduce_trace  # noqa: E402
from benchmark.harness import HERE  # noqa: E402
from benchmark.metrics import device_idle, kernel_roofline  # noqa: E402

TRACE = os.path.join(HERE, "testdata", "tiny_lm_step.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace.reduce(TRACE)


def test_busy_and_window(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.017948589, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.000104444, rel=1e-6)
    # self times partition the busy time: nothing counted twice
    assert sum(reduced["op_seconds"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)
    idle = device_idle.read({"trace": reduced}, {})
    assert idle == pytest.approx(99.418, abs=1e-3)


def test_operations_and_their_short_names(reduced):
    assert len(reduced["device_ops"]) == 10
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0] == "fusion.2 kCustom"
    assert "branch_0_fun.6 tpu_custom_call" in names
    seconds = [s for _, s in reduced["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert all(len(n) <= 100 for n in names)


def test_gaps_are_named_by_the_host_span_they_fall_in(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"bench.dispatch", "bench.fetch", "no_span"}
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert gaps["bench.fetch"] > gaps["bench.dispatch"] > 0
    assert reduced["spans"]["bench.fetch"] == pytest.approx(
        [0.012712409], rel=1e-6)


def test_kernel_roofline_reads_the_flash_kernel_or_nothing(reduced):
    spec = {"name": "flash_roofline", "peak": "bfloat16",
            "cost": "flash_train_flops_bytes",
            "patterns": ['custom_call_target="tpu_custom_call"']}
    ctx = {"trace": dict(reduced, steps=2),
           "config": {"num_hidden_layers": 2, "hidden_size": 64},
           "traffic": {"batch": 2, "seq_len": 128},
           "peaks": {"flops_per_s": {"bfloat16": 197e12},
                     "hbm_bytes_per_s": 819e9}}
    share = kernel_roofline.read(ctx, spec)
    assert 0 < share < 100
    assert ctx["notes"]["flash_roofline"]["bound_by"] == "bytes"
    spec["patterns"] = ["no_such_kernel"]
    assert kernel_roofline.read(ctx, spec) is None
    assert kernel_roofline.read({"trace": None}, spec) is None


def test_self_time_charges_a_parent_only_what_its_children_leave():
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"),
              (60, 70, "c"), (200, 210, "a")]
    assert reduce_trace._self_times(events) == {
        "while": 30, "a": 40, "b": 30, "c": 10}
    assert reduce_trace._union([(0, 5), (3, 8), (10, 12)]) == [
        [0, 8], [10, 12]]


def test_short_names():
    text = ('%branch_0_fun.6 = (bf16[4,128,32]{2,1,0}) custom-call('
            'bf16[4,128,32] %x), custom_call_target="tpu_custom_call"')
    assert reduce_trace.short_name(text) == \
        "branch_0_fun.6 tpu_custom_call"
    assert reduce_trace.short_name(
        "%fusion.3 = bf16[2] fusion(bf16[2] %p), kind=kLoop, calls=%f"
    ) == "fusion.3 kLoop"
    assert reduce_trace.short_name("jit_step(123)") == "jit_step(123)"
