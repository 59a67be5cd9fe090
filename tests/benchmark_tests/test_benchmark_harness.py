"""The benchmark's harness: BENCHMARK.json keeps to the contract's
names, every named thing has its files, and a configuration, a cell
and a per-layer metric can be added as new files with no edit."""
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

from benchmark.harness import HERE, Harness, NAME  # noqa: E402

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            yield group, entry["name"]
    for w in SPEC["workloads"]:
        yield "config of", w["config"]
        yield "traffic of", w["traffic"]
    for c in SPEC["configs"]:
        for key in c["reduced"]:
            yield "reduced", key


@pytest.mark.parametrize("group,name", list(_names()))
def test_names_hold_only_permitted_characters(group, name):
    assert NAME.match(name), (group, name)


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"] + SPEC["per_layer"],
    ids=lambda m: m["name"])
def test_metric_entries_keep_to_the_contract(metric):
    assert UNIT.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    if metric["name"] in e2e:
        assert set(metric) <= {"name", "unit", "better", "bound",
                               "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source",
                               "layer", "moves", "workloads"}
        assert metric["moves"] in e2e
        assert LINE.match(metric["layer"])
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in SPEC[g]]
    assert len(names) == len(set(names))
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(SPEC["workloads"]) // 4)
    for entry in SPEC["configs"] + SPEC["workloads"]:
        assert LINE.match(entry["why"]), entry
    under = tuple(p + "/" for p in SPEC["paths"])
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
    for c in SPEC["configs"]:
        assert c["file"].startswith(under)
        assert os.path.exists(os.path.join(REPO, c["file"]))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_has_its_files_and_its_metrics(cell):
    h = Harness()
    c = h.cell(cell)
    assert c.limits, f"no limits/{cell}.json"
    assert c.traffic["kind"] in ("train", "serve")
    assert h.family(c.config).param_shapes(c.config)
    e2e = [m["name"] for m in h.metrics(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = h.metrics(cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], e2e)
        spec = json.load(open(os.path.join(
            HERE, "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           spec["reader"]))


@pytest.mark.parametrize("config", SPEC["configs"],
                         ids=lambda c: c["name"])
def test_reduced_never_names_a_width(config):
    for key in config["reduced"]:
        assert not re.search(
            r"(hidden|intermediate|latent|state|proj)_size|ffn|_dim$"
            r"|_rank$|head_size|expansion|per_tok", key), key


def test_opt_widths_are_the_published_ones():
    cfg = json.load(open(os.path.join(HERE, "configs", "opt-1.3b.json")))
    published = {"hidden_size": 2048, "ffn_dim": 8192,
                 "num_attention_heads": 32, "vocab_size": 50272,
                 "max_position_embeddings": 2048,
                 "word_embed_proj_dim": 2048,
                 "activation_function": "relu",
                 "do_layer_norm_before": True}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"]["num_hidden_layers"] == 24
    assert set(cfg["assumed"]) >= {"untied_head", "embed_scale",
                                   "position_offset"}


def test_a_config_a_cell_and_a_metric_are_added_as_files(tmp_path):
    """opt-125m, a cell on it and a new per-layer metric with a reader
    of its own, in a directory of their own: nothing that is there is
    edited, and the harness finds all three by name."""
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "limits"):
        (d / sub).mkdir(parents=True)
    cfg = json.load(open(os.path.join(HERE, "configs", "opt-1.3b.json")))
    cfg.update(hidden_size=768, ffn_dim=3072, num_attention_heads=12,
               num_hidden_layers=12, word_embed_proj_dim=768,
               source="https://huggingface.co/facebook/opt-125m")
    (d / "configs" / "opt-125m.json").write_text(json.dumps(cfg))
    (d / "traffic" / "train-16x2048.json").write_text(json.dumps(
        {"kind": "train", "batch": 16, "seq_len": 2048,
         "resident_batches": 8, "fetch_every": 10, "checked_steps": 3,
         "trace_steps": 10}))
    (d / "limits" / "opt-125m.train.json").write_text('{"loss1": 1e-3}')
    (d / "metrics" / "head_share.json").write_text(json.dumps(
        {"name": "head_share", "reader": "head_share.py"}))
    (d / "metrics" / "head_share.py").write_text(
        "from .. import flops\n"
        "def read(ctx, spec):\n"
        "    c = ctx['config']\n"
        "    return 100.0 * c['hidden_size'] * c['vocab_size'] \\\n"
        "        / flops.lm_matmul_params(c)\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(
        {"name": "opt-125m", "source": cfg["source"],
         "file": "benchmark/configs/opt-125m.json", "reduced": [],
         "why": "small widths"})
    spec["workloads"].append(
        {"name": "opt-125m.train", "config": "opt-125m",
         "traffic": "train-16x2048", "chips": 1, "why": "whole model"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_step_ms":
            m["workloads"].append("opt-125m.train")
    spec["per_layer"].append(
        {"name": "head_share", "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "train step",
         "moves": "train_step_ms", "workloads": ["opt-125m.train"]})
    (d / "BENCHMARK.json").write_text(json.dumps(spec))

    h = Harness(str(d), str(d / "BENCHMARK.json"))
    cell = h.cell("opt-125m.train")
    assert cell.config["hidden_size"] == 768
    assert cell.traffic["batch"] == 16 and cell.limits
    fam = h.family(cell.config)          # the family's code is shared
    assert fam.train_flops(cell.config, cell.traffic) > 0
    got = h.read_per_layer("opt-125m.train",
                           {"config": cell.config, "trace": None})
    assert set(got) == {"head_share"}    # the others found no trace
    assert 20 < got["head_share"]["value"] < 40
    # and the cells that were there are found as before
    assert h.cell("opt-1.3b.train").config["hidden_size"] == 2048


def test_unknown_device_kind_is_an_error():
    h = Harness()
    assert h.peaks("TPU v5 lite")["flops_per_s"]["bfloat16"] == 197e12
    with pytest.raises(KeyError, match="never a default"):
        h.peaks("TPU v9 imaginary")


def _run(cwd, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *argv],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_no_result_is_printed():
    cell = SPEC["workloads"][0]["name"]
    out = _run(REPO, "--workload", cell, "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert out.returncode not in (0, 2), out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_without_the_program_no_result_is_printed(tmp_path):
    """A directory with only BENCHMARK.json and the files under
    ``paths``: the run fails and prints no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(
                        ".jax_cache", "__pycache__"))
    # even with the look for a chip passed (a rehearsal), there is no
    # program to import
    out = _run(str(tmp_path), "--rehearse",
               os.path.join("benchmark", "testdata", "rehearse"),
               "--workload", "tiny-lm.train", "--seconds", "0.1")
    assert out.returncode not in (0, 2), out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "incubator_mxnet_tpu" in out.stderr
