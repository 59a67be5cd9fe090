"""The seam between the harness and a family of models: a served
family counts its own work, every key that a configuration states is
read or named a statement, and a family that is not ``transformer_lm``
is added as files (``other_family/`` beside this file) and goes
through ``run.py --rehearse`` with no file of ``benchmark/`` edited.
(Set-up and ``weights_dtype``: test_benchmark_setup.py.)"""
import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops, serve  # noqa: E402
from benchmark.harness import HERE, Harness  # noqa: E402

OTHER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "other_family")
REHEARSE = os.path.join(HERE, "testdata", "rehearse")


def _harness(folder):
    return Harness(folder, os.path.join(folder, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def other():
    return _harness(OTHER)


# ------------------------------------------ the family counts its work
def _drive_with_records():
    """Three requests' records: prompt lengths 300, 17 and 1200, each
    token's time a whole second; the window [2, 5] holds the first
    request's tokens 2..5 (its prefill is outside), all of the second
    (prefill at 2) and none of the third."""
    drive = serve.Drive(None, None)
    for rid, (plen, times) in enumerate((
            (300, [1, 2, 3, 4, 5, 6]), (17, [2, 3, 4]),
            (1200, [6, 7]))):
        drive.records[rid] = {"prompt": [0] * plen, "times": times}
    return drive


@pytest.mark.parametrize("folder,cell", [
    (HERE, "opt-1.3b.serve-closed16"), (OTHER, "tiny-other.serve")],
    ids=["transformer_lm", "other_lm"])
def test_flops_between_is_the_sum_of_the_familys_counts(folder, cell):
    """For fixed records, exactly: through ``transformer_lm`` the sum
    of ``flops.lm_prefill_flops`` / ``lm_decode_flops`` as before; a
    family with other key names gives the same count from its own
    arithmetic."""
    h = Harness() if folder == HERE else _harness(folder)
    cfg = h.cell(cell).config
    fam = h.family(cfg)
    as_opt = {**cfg, "ffn_dim": cfg.get("ffn_dim",
                                        cfg.get("intermediate_size"))}
    expected = sum(flops.lm_decode_flops(as_opt, 300 + i)
                   for i in (1, 2, 3, 4)) \
        + flops.lm_prefill_flops(as_opt, 17) \
        + sum(flops.lm_decode_flops(as_opt, 17 + i) for i in (1, 2))
    got = _drive_with_records().flops_between(fam, cfg, 2, 5)
    assert isinstance(got, int) and got == expected
    assert _drive_with_records().flops_between(fam, cfg, 8, 9) == 0


def test_no_direct_call_of_the_lm_counts_is_left_in_serve():
    source = open(os.path.join(HERE, "serve.py")).read()
    assert "flops.lm_" not in source and "import flops" not in source
    assert not re.search(r"weights\.make\([^)]*seed\)", source), \
        "a serve path makes weights without the stated dtype"


def _family_without(tmp_path, lacking):
    """``other_family`` copied, its family's file without the
    functions named."""
    d = tmp_path / "bench"
    shutil.copytree(OTHER, d, ignore=shutil.ignore_patterns(
        "__pycache__"))
    path = d / "models" / "other_lm.py"
    text = path.read_text()
    for name in lacking:
        text = text.replace(f"def {name}(", f"def _no_{name}(")
    path.write_text(text)
    return _harness(str(d))


@pytest.mark.parametrize("lacking", [
    ("decode_flops",), ("prefill_flops", "decode_flops")])
def test_a_served_family_without_its_count_fails_before_set_up(
        tmp_path, monkeypatch, lacking):
    import incubator_mxnet_tpu as mx
    h = _family_without(tmp_path, lacking)

    def set_up(*a, **k):
        raise AssertionError("set-up was paid")

    monkeypatch.setattr(serve, "settled_block", set_up)
    with pytest.raises(AttributeError) as err:
        serve.build(h, h.cell("tiny-other.serve"), 1, mx)
    assert "'other_lm'" in str(err.value)
    for name in lacking:
        assert name in str(err.value)


# ------------------------------- a family that is not transformer_lm
def test_the_other_family_shares_no_key_of_opts_widths(other):
    cfg = other.cell("tiny-other.serve").config
    assert "ffn_dim" not in cfg and cfg["intermediate_size"] == 192
    assert cfg["serve"]["weights_dtype"] == "bfloat16"
    assert cfg["serve"]["control_precision"] == "fp8"
    with pytest.raises(KeyError):
        flops.lm_decode_flops(cfg, 5)       # hole 1 of ISSUE 26
    # found in the directory of its own, not among the benchmark's
    assert not os.path.exists(os.path.join(HERE, "models",
                                           "other_lm.py"))
    assert other.family(cfg).__file__.startswith(OTHER)


def _rehearse(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_FLASH="1")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--rehearse", OTHER, "--workload", "tiny-other.serve", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_other_family_goes_through_run_py_rehearse(trace):
    out = _rehearse("--seed", str(2 ** 31 + 26), "--seconds", "0.4",
                    "--trace", str(trace))
    assert out.returncode == 2, out.stderr[-3000:]
    last = out.stderr.strip().splitlines()[-1]
    assert last.startswith("benchmark: rehearsal, not a result: ")
    assert not any(line.startswith('{"correct"')
                   for line in out.stdout.splitlines())
    result = json.loads(last.split("not a result: ", 1)[1])
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert result["compared"]["far_gap_share"]["value"] < 0.01
    expected = {"engine_step_ms.serve"} if trace else {
        "setup_s", "serve_tok_per_s", "itl_p95_ms"}
    # the CPU has no device plane: the readers of the trace and of
    # the program's spans find nothing to read and are left out
    assert set(result["metrics"]) == expected
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_traced_rehearsal_asks_the_family_for_its_count(
        monkeypatch, other):
    """Under ``--trace 1`` the count of the window's work is the
    family's, on the CPU too (where no reader can use it)."""
    from benchmark import run
    asked, sound = {"prefill": [], "decode": []}, Harness.family

    def family(self, config):
        fam = sound(self, config)       # loaded anew for each call
        for kind in asked:
            count = getattr(fam, kind + "_flops")
            setattr(fam, kind + "_flops",
                    lambda cfg, n, kind=kind, count=count:
                    asked[kind].append(n) or count(cfg, n))
        return fam

    monkeypatch.setattr(Harness, "family", family)
    monkeypatch.setenv("MXTPU_FLASH", "1")
    result = run.measure(argparse.Namespace(
        workload="tiny-other.serve", seed=8, seconds=0.4, trace=1,
        rehearse=OTHER), look_for_chip=False)
    assert result["correct"], result["compared"]
    assert asked["prefill"] and len(asked["decode"]) > 10
    assert all(8 <= n <= 100 for n in asked["prefill"])


def test_the_rehearsal_lists_every_per_layer_metric_of_the_benchmark():
    """ROADMAP B4: a CPU rehearsal runs each reader through run.py."""
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    toy = json.load(open(os.path.join(REHEARSE, "BENCHMARK.json")))

    def entries(spec):
        return sorted(({k: v for k, v in m.items() if k != "workloads"}
                       for m in spec["per_layer"]),
                      key=lambda m: m["name"])
    assert entries(toy) == entries(real)


# ------------------------------ every stated key is read, or said to be
def _stated_keys():
    files = glob.glob(os.path.join(HERE, "configs", "*.json")) \
        + glob.glob(os.path.join(REHEARSE, "configs", "*.json")) \
        + glob.glob(os.path.join(OTHER, "configs", "*.json"))
    for path in sorted(files):
        cfg = json.load(open(path))
        for group in ("train", "serve"):
            for key in cfg.get(group, {}):
                yield pytest.param(
                    group, key,
                    id=f"{os.path.basename(path)[:-5]}-{group}.{key}")


@pytest.mark.parametrize("group,key", list(_stated_keys()))
def test_every_stated_key_is_read_or_named_a_statement(group, key):
    """No key of a configuration's ``train`` or ``serve`` is dead: the
    harness reads it, or the README names it as a statement that
    ``correct`` holds the program to by other keys."""
    code = "".join(open(p).read() for p in glob.glob(
        os.path.join(HERE, "*.py")))
    read = re.search(
        r'\["%s"\]\s*\[\s*"%s"\]' % (group, key), code) is not None
    readme = open(os.path.join(HERE, "README.md")).read()
    rows = [line for line in readme.splitlines()
            if line.startswith(f"| `{group}.{key}`")]
    assert len(rows) == 1, f"README.md has no row for {group}.{key}"
    said = rows[0].split("|")[2].strip()
    assert said in ("read", "statement")
    assert read == (said == "read"), (group, key, said)


@pytest.mark.parametrize("cell,readers", [
    ("tiny-lm.train", {"step_mfu.py": 1, "device_idle.py": 1,
                       "kernel_roofline.py": 1, "program_span.py": 1}),
    ("tiny-lm.serve", {"step_mfu.py": 1, "device_idle.py": 1,
                       "span_median_ms.py": 1, "program_span.py": 3})])
def test_a_traced_rehearsal_runs_every_reader_through_run_py(
        monkeypatch, measure, cell, readers):
    """ROADMAP B4: with ``--trace 1`` on the CPU each per-layer metric
    of the cell has its reader called by name, PR 24's four among
    them; a reader that needs the device's trace finds nothing and
    its metric is left out of the line."""
    called, sound = {}, Harness._module

    def module(self, sub, filename):
        mod = sound(self, sub, filename)
        if sub == "metrics":
            called[filename] = called.get(filename, 0) + 1
        return mod

    monkeypatch.setattr(Harness, "_module", module)
    result = measure(cell, seconds=0.3, trace=1)
    assert result["correct"], result["compared"]
    assert called == readers
    assert set(result["metrics"]) == (
        {"engine_step_ms.serve"} if cell.endswith("serve") else set())
    assert "busy_s" not in result["device"]
