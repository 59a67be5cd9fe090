"""The ``mhc_latent_moe_lm`` family in the benchmark: a tiny
configuration of it goes through ``run.py --rehearse`` and
``faults.py`` with no file of ``benchmark/`` edited (``mhc_family/``
beside this file holds a configuration, a traffic mix, limits and a
BENCHMARK.json; family and reference are the benchmark's own), its
counts are a hand count, the committed readings of the chip hold to
the committed limits, and the configuration and the cell say what
they were asked to say."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import HERE, Harness  # noqa: E402

MHC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "mhc_family")
CELL = "xing4.0-29b-a4b.serve-closed64-decode"


def _run(script, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", script), "--rehearse",
         MHC, "--workload", "tiny-mhc.serve", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_mhc_configuration_goes_through_rehearse(trace):
    out = _run("run.py", "--seed", str(2 ** 31 + 35), "--seconds", "0.4",
               "--trace", str(trace))
    assert out.returncode == 2, out.stderr[-3000:]
    result = json.loads(out.stderr.strip().splitlines()[-1].split(
        "not a result: ", 1)[1])
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    # bfloat16 program against the float32 reference: no gap beyond
    # the fp8 control's noise
    assert result["compared"]["far_gap_share"]["value"] < 0.05
    expected = {"engine_step_ms.serve"} if trace else {
        "setup_s", "serve_tok_per_s", "itl_p95_ms"}
    assert set(result["metrics"]) == expected
    assert sorted(os.listdir(MHC)) == [
        "BENCHMARK.json", "configs", "limits", "traffic"]


def test_faults_py_plants_the_familys_faults(tmp_path):
    """``faults.py`` finds the new reference's faults by name and
    reads a row for each beside the program's and the control's; the
    program's rows are correct and ``calibrate.py --judge`` reads every
    row again as it was judged."""
    rows_file = tmp_path / "rows.jsonl"
    out = _run("faults.py", "--seeds", f"{2 ** 31 + 53},7",
               "--controls", "1", "--fault-seeds", "1", "--seconds",
               "0.3", "--faults", "res_identity,static_only",
               "--out", str(rows_file))
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in open(rows_file)]
    judged = {(r["who"], r["seed"]): r["correct"] for r in rows
              if "correct" in r}
    assert set(judged) == {
        ("program", 2 ** 31 + 53), ("program", 7),
        ("control_fp8", 2 ** 31 + 53), ("fault_res_identity", 2 ** 31 + 53),
        ("fault_static_only", 2 ** 31 + 53)}
    assert judged[("program", 2 ** 31 + 53)] and judged[("program", 7)]
    again = _run("calibrate.py", "--judge", str(rows_file))
    assert again.returncode == 0, again.stderr[-2000:]
    said = [json.loads(line) for line in again.stdout.splitlines()]
    assert {(r["who"], r["seed"]): r["correct"] for r in said} == judged


def test_the_familys_counts_are_a_hand_count_at_the_tiny_size():
    h = Harness(MHC, os.path.join(MHC, "BENCHMARK.json"))
    cfg = h.cell("tiny-mhc.serve").config
    fam = h.family(cfg)
    attention = 64 * 24 + 24 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 64 * 64
    layers = 2 * attention + 3 * 64 * 96 + 4 * 64 + 3 * 3 * 64 * 32
    # 2 layers x 2 sublayers: the mapping 256 x 24, H_res over 4
    # streams of 64, h_pre's and h_post's rows
    hyper = 4 * (2 * 256 * 24 + 2 * 16 * 64 + 4 * 256)
    head, pair = 2 * 64 * 97, 2 * 2 * 4 * (24 + 16)
    assert fam.decode_flops(cfg, 30) == 2 * layers + hyper + head \
        + pair * 30
    assert fam.prefill_flops(cfg, 30) == (2 * layers + hyper) * 30 \
        + head + pair * 30 * 31 // 2
    n = sum(int(np.prod(s)) for s, _ in fam.param_shapes(cfg).values())
    assert n == 2 * 97 * 64 + 64 + 2 * (
        64 + 64 * 24 + 24 + 96 * 24 + 24 * 64 + 16 + 128 * 16
        + 64 * 64 + 64) + 3 * 64 * 96 + 4 * 64 + 4 + 3 * 5 * 64 * 32 \
        + 4 * (24 * 256 + 24 + 3)


def test_the_committed_readings_hold_to_the_limits():
    """readings/<cell>.jsonl keeps the chip's rows (benchmark/faults.py
    and run.py): under the committed limits every program row is
    correct and every control and planted fault is not."""
    path = os.path.join(HERE, "readings", CELL + ".jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "calibrate.py"),
         "--workload", CELL, "--judge", path],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    said = [json.loads(line) for line in out.stdout.splitlines()]
    program = [r["correct"] for r in said if r["who"] == "program"]
    others = [r["correct"] for r in said if r["who"] != "program"]
    assert len(program) >= 6 and all(program)
    faults = {r["who"] for r in said if r["who"].startswith("fault_")}
    from benchmark.reference.mhc_latent_moe import FAULTS
    assert faults == {f"fault_{f}" for f in FAULTS}
    assert others and not any(others)


PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_the_published_value(key):
    assert Harness().cell(CELL).config[key] == PUBLISHED[key]


def test_what_the_configuration_cut_is_what_it_says():
    """Depth, and the vocabulary's slice that the harness's comparison
    forces: nothing else differs from the published file, and both are
    listed with what was published; 3,471,234,628 parameters."""
    h = Harness()
    cfg = h.cell(CELL).config
    entry, = (c for c in h.spec["configs"]
              if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 32768)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 131072}
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 1
    assert dep["chips_sharing_embedding_and_head"] == dep["vocab_split"] \
        == 4
    assert dep["ep_size"] == cfg["ep_size"] == 1
    assert set(cfg["assumed"]) >= {
        "hc_entry", "hc_exit", "hc_sinkhorn_order", "hc_clip_and_eps",
        "hc_per_sublayer", "hc_norm", "rope_pairs",
        "multi_token_prediction", "weights"}
    joyai = h.cell("joyai-llm-flash.serve-closed64-decode").config
    assert cfg["serve"] == joyai["serve"] and "train" not in cfg
    n = sum(int(np.prod(s)) for s, _ in
            h.family(cfg).param_shapes(cfg).values())
    assert n == 3471234628


def test_the_cell_is_the_one_the_issue_named():
    h = Harness()
    cell = h.cell(CELL)
    entry, = (w for w in h.spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "xing4.0-29b-a4b", "serve-closed64-decode", 1)
    assert len(entry["why"]) <= 200
    assert cell.traffic == h.cell(
        "joyai-llm-flash.serve-closed64-decode").traffic
    # throughput and set-up, not the tail (PERF.md, section 7)
    for metric in h.spec["end_to_end"]:
        listed = CELL in metric.get("workloads", [CELL])
        assert listed == (metric["name"] in ("setup_s", "serve_tok_per_s"))
    assert sorted(m["name"] for m in h.metrics(CELL, "per_layer")) \
        == sorted(["step_mfu.serve", "device_idle.serve",
                   "engine_host_ms.serve", "decode_ms.serve"])
