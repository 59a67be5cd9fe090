"""Tool scripts (ref: tools/rec2idx.py, tools/parse_log.py)."""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from incubator_mxnet_tpu import recordio as rio

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)


def test_rec2idx_roundtrip():
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "d")
        rec = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                    "w")
        payloads = {}
        for i in range(7):
            buf = os.urandom(10 + i)
            payloads[i] = buf
            rec.write_idx(i, rio.pack(
                rio.IRHeader(0, float(i), i, 0), buf))
        rec.close()
        orig = open(prefix + ".idx").read()
        os.unlink(prefix + ".idx")

        import rec2idx
        idx_path, n = rec2idx.build_index(prefix + ".rec")
        assert n == 7
        assert open(idx_path).read() == orig
        # random access works through the rebuilt index
        r = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                  "r")
        for i in (3, 0, 6):
            header, buf = rio.unpack(r.read_idx(i))
            assert buf == payloads[i]
            assert int(header.id) == i


def test_parse_log():
    import parse_log
    log = """\
INFO Epoch[0] Batch [20]  Speed: 100.0 samples/sec  accuracy=0.5
INFO Epoch[0] Batch [40]  Speed: 300.0 samples/sec  accuracy=0.6
INFO Epoch[0] Train-accuracy=0.61
INFO Epoch[0] Time cost=10.5
INFO Epoch[0] Validation-accuracy=0.58
INFO Epoch[1] Batch [20]  Speed: 200.0 samples/sec  accuracy=0.7
INFO Epoch[1] Train-accuracy=0.72
INFO Epoch[1] Time cost=9.0
INFO Epoch[1] Validation-accuracy=0.69
"""
    epochs = parse_log.parse(log.splitlines())
    assert epochs[0]["speed"] == [100.0, 300.0]
    assert epochs[0]["train"]["accuracy"] == 0.61
    assert epochs[1]["val"]["accuracy"] == 0.69
    md = parse_log.render(epochs)
    assert "train-accuracy" in md and "| 0" in md
    csv = parse_log.render(epochs, "csv")
    assert csv.splitlines()[0].startswith("epoch,speed")
    assert "200" in csv


def test_bandwidth_tool_collectives_and_kvstore():
    """tools/bandwidth.py (ref: tools/bandwidth/measure.py) runs all
    benches on the 8-virtual-device mesh and reports sane records."""
    import bandwidth
    recs = bandwidth.main(["--benches", "collectives,kvstore,h2d",
                           "--sizes-mb", "0.5", "--iters", "2"])
    by_bench = {}
    for r in recs:
        by_bench.setdefault(r["bench"], []).append(r)
    coll = by_bench["collectives"]
    assert {c["op"] for c in coll} == {
        "allreduce", "reduce_scatter", "all_gather", "ppermute"}
    assert all(c["ms"] > 0 and c["bus_gbps"] > 0 for c in coll)
    assert all(c["devices"] == 8 for c in coll)
    kv = by_bench["kvstore"][0]
    assert kv["payload_mb"] > 10 and kv["gbps"] > 0
    h2d = by_bench["h2d"][0]
    assert h2d["h2d_gbps"] > 0 and h2d["d2h_gbps"] > 0


def _chip_smoke(*args, **env_changes):
    repo = os.path.dirname(TOOLS)
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_changes)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)


def test_chip_smoke_needs_a_tpu():
    """Without a TPU the smoke exits non-zero at once with one line
    and no result: nothing here may read as a chip run."""
    r = _chip_smoke()
    assert r.returncode != 0
    assert r.stdout == "" and "needs a TPU" in r.stderr


# slow: 58 s alone and 74-82 s among six workers in a checkout whose
# compile cache is empty, 31 s of it the ResNet-50 phase (some 170 eager
# operators compiled to settle its shapes, then the step compiled twice,
# PERF.md section 7.6); no argument makes ResNet-50 smaller.  Run it by
# hand before a chip call: pytest tests/test_tools.py -k rehearsal
@pytest.mark.slow
def test_chip_smoke_rehearsal_on_cpu_is_never_a_result():
    """The first rehearsal of the on-chip-measurement guide, kept as a
    test: every phase end to end at a tiny size on the CPU, kernels
    interpreted.  It runs to its end, and still exits non-zero and
    prints neither ``"ok": true`` nor a TPU platform."""
    import json
    r = _chip_smoke(
        "--rehearse", "--mlp-samples", "1024", "--resnet-batch", "2",
        "--resnet-hw", "32", "--resnet-steps", "3", "--lm-vocab", "256",
        "--lm-d-model", "64", "--lm-layers", "2", "--lm-heads", "2",
        "--lm-batch", "2", "--lm-seq", "128", "--lm-steps", "3",
        "--kernel-shape", "4,256,32", "--kernel-window", "128",
        "--serve-prompts", "5,17,40", "--serve-new", "8",
        MXTPU_FLASH="1")
    assert r.returncode == 2, r.stderr[-3000:]
    assert "rehearsal on cpu passed; not a result" in r.stderr
    lines = [json.loads(ln) for ln in r.stdout.strip().splitlines()]
    assert [ln["phase"] for ln in lines] == [
        "device", "eager+module", "resnet50", "transformer_lm",
        "flash_kernel", "serve", "done"]
    assert not any("ok" in ln for ln in lines)
    assert '"tpu"' not in r.stdout


def test_nothing_names_the_root_benchmark_that_went():
    """One yardstick (BENCHMARK.json + benchmark/): no script, page
    or program file points a reader at the 13-mode root benchmark, its
    gate, its records, or the wall-clock utilisation clock it read.
    Left out: the benchmark's own tree, this file, and the records of
    what past PRs did."""
    repo = os.path.dirname(TOOLS)
    # the options' prefix in two pieces: ROADMAP D5 counts the
    # distinct MXTPU_* names of tracked Python, and this is none
    names = ["bench.py", "bench_gate", "profile_step", "BENCH_r",
             "MULTICHIP_r", "MXTPU_" "BENCH_", "TrainPerfClock",
             "arm_perf"]
    records = {"CHANGES.md", "PERF.md", "ROADMAP.md", "SURVEY.md",
               "ISSUE.md", "REVIEW.md"}
    with open(os.path.join(repo, ".gitignore")) as f:
        ignored = {ln.strip().rstrip("/") for ln in f
                   if ln.strip().endswith("/")}
    ignored |= {".git", "benchmark", "benchmark_tests"}
    found = []
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs if d not in ignored]
        for name in files:
            path = os.path.relpath(os.path.join(root, name), repo)
            if not name.endswith((".py", ".sh", ".md")) \
                    or path in records \
                    or path == os.path.join("tests", "test_tools.py"):
                continue
            with open(os.path.join(repo, path), errors="replace") as f:
                text = f.read()
            found += [f"{path}: {n}" for n in names if n in text]
    assert found == []
